"""90th percentile (nearest rank) over every request due in the window
of the time from when it was due to its first token.  A request that got
no first token counts at the time the run stopped waiting for it."""

import math


def read(rec):
    if not hasattr(rec, "in_window") or not rec.in_window:
        return None
    b = rec.book
    end = max(k.t1 for k in b.calls)
    ttft = sorted((b.times[r][0] if b.times[r] else end) - b.due[r]
                  for r in rec.in_window)
    return 1e3 * ttft[math.ceil(0.9 * len(ttft)) - 1]
