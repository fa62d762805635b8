"""The benchmark's yardstick: chip peaks and the work a model step needs.

Peaks are keyed by ``device_kind`` as JAX reports it.  A kind that is not
in the table is an error, never a default.  Work is counted from a
configuration file's published sizes alone (no program object), so a
change to the program cannot change what a metric divides by.  What
differs between architectures (a layer's weights, the mixer's own FLOPs,
the cache a token holds) is in ``families/<family>.py``; the arithmetic
here is shared by all.
"""

from __future__ import annotations

from typing import Dict

from harness import family

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, HBM at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def vocab(c: dict) -> int:
    return c.get("padded_vocab_size", c["vocab_size"])


def params(c: dict) -> Dict[str, int]:
    """{"layers", "embed", "head", "final_norm", "total"} parameter counts
    as the configuration file states the model."""
    f = family(c)
    d = f.width(c)
    out = {"layers": f.depth(c) * f.layer_params(c), "embed": vocab(c) * d,
           "head": 0 if f.tied(c) else vocab(c) * d, "final_norm": d}
    out["total"] = sum(out.values())
    return out


def cache_bytes_per_token(c: dict) -> int:
    """Cache bytes a decode step reads per live token, over every layer."""
    return family(c).cache_bytes_per_token(c, DTYPE_BYTES[c["dtype"]])


# ---------------------------------------------------------------------------
# Model FLOPs (no recompute, no padding)
# ---------------------------------------------------------------------------

def forward_flops_per_token(c: dict, context: float = 0.0) -> float:
    """Forward FLOPs per token: 2 x every weight a token's activations are
    multiplied by (embedding lookup excluded, the output head included;
    norm scales, biases and convolution taps are counted as weights too, a
    few parts in 10^4), plus the mixer's own (the family's count) over
    ``context`` earlier positions."""
    p = params(c)
    head = p["head"] or p["embed"]             # tied: the embedding is the head
    return 2.0 * (p["layers"] + head) + family(c).mixer_flops_per_token(
        c, context)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward (3 x forward), the mixer's context averaged
    over the sequence (causal); recomputation is not counted."""
    return 3.0 * forward_flops_per_token(c, context=(seq_len - 1) / 2.0)


def decode_bytes(c: dict, live_tokens: int) -> float:
    """Bytes one decode step must read: every weight but the embedding
    table (a decode step gathers only its rows), plus the live cache."""
    p = params(c)
    w = (p["layers"] + p["head"] + p["final_norm"]) * DTYPE_BYTES[c["dtype"]]
    if p["head"] == 0:
        w += p["embed"] * DTYPE_BYTES[c["dtype"]]
    return float(w + cache_bytes_per_token(c) * live_tokens)
