"""Tokens of every step completed in the window over the window (host
clock; each step ends in block_until_ready)."""


def read(rec):
    if not hasattr(rec, "steps"):
        return None
    return rec.steps * rec.tokens_per_step / rec.window_s
