"""Share of the traced window in which no operation ran on the device,
on the chip that idled most (profiler trace), in %."""


def read(rec):
    if rec.trace is None or not hasattr(rec, "book"):
        return None
    return 100.0 * rec.trace["idle_share_worst"]
