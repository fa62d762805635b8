"""The Mamba-2 stack of SSD layers (arXiv:2405.21060): what the yardstick
counts and checks for a configuration of this family, from the sizes its
file states alone.  The functions are those of ``gqa.py``."""

from __future__ import annotations

import math

# Test-only sizes: the program's smoke-test size of the architecture.
TINY = {"program": {"arch": "mamba2-1.3b", "reduced": True},
        "d_model": 64, "n_layer": 3, "vocab_size": 256,
        "padded_vocab_size": 256, "d_state": 16, "headdim": 16,
        "chunk_size": 8, "dtype": "float32"}


def _a_log(key, shape, dtype):
    """A = -exp(A_log) in [-16, -1]."""
    import jax
    import jax.numpy as jnp
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                   ).astype(dtype)


def _dt_bias(key, shape, dtype):
    """softplus(dt_bias) in [1e-3, 1e-1]."""
    import jax
    import jax.numpy as jnp
    dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                     math.log(1e-3), math.log(1e-1)))
    return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)


# How each weight is drawn, by the last name in its path (weights.py).
WEIGHTS = {"in_proj": "fan_in", "out_proj": "fan_in", "conv_w": "fan_in",
           "lm_head": "fan_in", "embed": 0.02, "conv_b": 0.02,
           "scale": "ones", "D": "ones", "A_log": _a_log,
           "dt_bias": _dt_bias}


def width(c: dict) -> int:
    return c["d_model"]


def depth(c: dict) -> int:
    return c["n_layer"]


def tied(c: dict) -> bool:
    return c["tie_embeddings"]


def layer_params(c: dict) -> int:
    d, n, hdim = c["d_model"], c["d_state"], c["headdim"]
    di = c["expand"] * d
    g = c.get("ngroups", 1)
    heads = di // hdim
    proj = d * (2 * di + 2 * g * n + heads)
    conv_ch = di + 2 * g * n
    conv = c["d_conv"] * conv_ch + conv_ch
    return proj + conv + 3 * heads + di + di * d + d   # A, D, dt; norms


def mixer_flops_per_token(c: dict, context: float) -> float:
    """Forward FLOPs of the SSD scan's own contractions per token over
    every layer, in its chunked matmul form (sec. 6): the intra-chunk C.B
    scores and their product with x, the chunk states, and the carried
    state's output.  They do not grow with the context."""
    q, n, p = c["chunk_size"], c["d_state"], c["headdim"]
    heads = c["expand"] * c["d_model"] // p
    macs = heads * (q * n + q * p + 2 * p * n)
    return c["n_layer"] * 2.0 * macs


def cache_bytes_per_token(c: dict, dtype_bytes: int) -> int:
    """0: the recurrent state is one per slot, whatever the length."""
    return 0


def program_pairs(c: dict, cfg) -> list:
    """(key, file's value, program's value) for every published size the
    program's ``TransformerCfg`` must run as the file states it."""
    mb = cfg.mamba
    return [("d_model", c["d_model"], cfg.d_model),
            ("n_layer", c["n_layer"], cfg.num_layers),
            ("d_state", c["d_state"], mb.d_state),
            ("d_conv", c["d_conv"], mb.d_conv),
            ("expand", c["expand"], mb.expand),
            ("headdim", c["headdim"], mb.headdim),
            ("ngroups", c["ngroups"], mb.ngroups),
            ("chunk_size", c["chunk_size"], mb.chunk),
            ("padded_vocab_size", c["padded_vocab_size"], cfg.vocab_size),
            ("tie_embeddings", c["tie_embeddings"], cfg.tie_embeddings)]
