"""Model FLOPs of the prompt and generated tokens the server processed in
the window (counts.forward_flops_per_token at each token's context) over
the summed host time of the step() and submit() calls in the window and
the chip's bf16 peak, in %."""

import counts


def read(rec):
    if not hasattr(rec, "book"):
        return None
    c = rec.config
    w0, w1 = rec.window
    calls = [k for k in rec.book.calls if w0 <= k.t0 < w1]
    base = counts.forward_flops_per_token(c, 0.0)
    per_ctx = counts.forward_flops_per_token(c, 1.0) - base
    flops = wall = 0.0
    for k in calls:
        wall += k.t1 - k.t0
        flops += k.decode * base + per_ctx * k.kv
        flops += sum(p * base + per_ctx * p * (p - 1) / 2 for p in k.prompts)
    if wall <= 0:
        return None
    return 100.0 * flops / (wall * rec.peaks["bf16_flops"])
