"""Device time of the collective operations per training step: the
union of their in-flight intervals in the traced window, averaged over
the chips, over the steps traced (profiler trace), in ms."""


def read(rec):
    if rec.trace is None or not getattr(rec, "trace_steps", 0):
        return None
    if not rec.trace["collective_ns"]:
        return None
    return rec.trace["collective_ns"] / 1e6 / rec.trace_steps
