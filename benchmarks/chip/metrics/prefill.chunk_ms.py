"""Mean device time of one prefill-chunk program in the traced window
(profiler trace), in ms."""


def read(rec):
    if not hasattr(rec, "book"):
        return None
    import drive_serve
    progs = drive_serve.traced_programs(rec)
    if not progs or not progs.get("chunk"):
        return None
    d = progs["chunk"]
    return sum(d) / len(d) / 1e6
