"""Model FLOPs of the steps in the window (forward and backward, no
recompute; counts.train_flops_per_token) over the window, the chips and
the chip's bf16 peak, in %."""

import counts


def read(rec):
    if not hasattr(rec, "steps"):
        return None
    f = counts.train_flops_per_token(rec.config, rec.mix["seq_len"])
    rate = rec.steps * rec.tokens_per_step / rec.window_s
    return 100.0 * f * rate / (rec.chips * rec.peaks["bf16_flops"])
