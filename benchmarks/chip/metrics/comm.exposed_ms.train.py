"""The part of the collectives' in-flight time in which no other
operation ran on the same chip, per training step (profiler trace), in
ms."""


def read(rec):
    if rec.trace is None or not getattr(rec, "trace_steps", 0):
        return None
    if not rec.trace["collective_ns"]:
        return None
    return rec.trace["exposed_ns"] / 1e6 / rec.trace_steps
