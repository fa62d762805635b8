"""Bytes the paged decode steps in the traced window had to read (every
weight but the embedding table, and the live KV cache of the decoding
slots; counts.decode_bytes) over the device time of the decode program
and the chip's HBM bandwidth, in %."""

import counts


def read(rec):
    if not hasattr(rec, "book"):
        return None
    import drive_serve
    progs = drive_serve.traced_programs(rec)
    if not progs or "decode" not in progs:
        return None
    calls = [k for k in rec.book.calls if k.traced and k.decode]
    need = sum(counts.decode_bytes(rec.config, k.kv) for k in calls)
    n = min(len(calls), len(progs["decode"]))
    if not n:
        return None
    t = sum(progs["decode"][:n]) / 1e9
    need *= n / len(calls)
    return 100.0 * need / (t * rec.peaks["hbm_bytes_per_s"])
