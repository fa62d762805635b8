"""95th percentile (nearest rank) over every gap between two consecutive
tokens of a request, where the later token came in the window."""

import math


def read(rec):
    if not hasattr(rec, "book"):
        return None
    w0, w1 = rec.window
    gaps = sorted(b - a for ts in rec.book.times.values()
                  for a, b in zip(ts, ts[1:]) if w0 <= b < w1)
    if not gaps:
        return None
    return 1e3 * gaps[math.ceil(0.95 * len(gaps)) - 1]
