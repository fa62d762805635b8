"""The dense decoder with grouped-query attention (Qwen2, Llama): what the
yardstick counts and checks for a configuration of this family, from the
sizes its file states alone.

A configuration file names its family (``"family": "gqa"``) and the
harness finds this file by that name; a new architecture adds a file
beside it with the same functions.
"""

from __future__ import annotations

# Test-only sizes: the program's smoke-test size of the architecture.
TINY = {"program": {"arch": "qwen2-72b", "reduced": True, "layers": 2},
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "head_dim": 16, "num_hidden_layers": 2, "vocab_size": 256,
        "dtype": "float32"}

# How each weight is drawn, by the last name in its path (weights.py).
WEIGHTS = {"wq": "fan_in", "wk": "fan_in", "wv": "fan_in", "wo": "fan_in",
           "w_gate": "fan_in", "w_up": "fan_in", "w_down": "fan_in",
           "lm_head": "fan_in", "embed": 0.02, "bq": 0.02, "bk": 0.02,
           "bv": 0.02, "scale": "ones"}


def width(c: dict) -> int:
    return c["hidden_size"]


def depth(c: dict) -> int:
    return c["num_hidden_layers"]


def tied(c: dict) -> bool:
    return c["tie_word_embeddings"]


def layer_params(c: dict) -> int:
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if c.get("qkv_bias"):
        attn += h * hd + 2 * kv * hd
    mlp = 3 * d * c["intermediate_size"]
    return attn + mlp + 2 * d                  # two RMSNorm scales


def mixer_flops_per_token(c: dict, context: float) -> float:
    """Attention's own forward FLOPs per token over ``context`` earlier
    positions: the scores and their product with the values."""
    return (4.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * context)


def cache_bytes_per_token(c: dict, dtype_bytes: int) -> int:
    """Key and value cache bytes per token over every layer."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes)


def program_pairs(c: dict, cfg) -> list:
    """(key, file's value, program's value) for every published size the
    program's ``TransformerCfg`` must run as the file states it."""
    a, m = cfg.attn, cfg.mlp
    return [("hidden_size", c["hidden_size"], cfg.d_model),
            ("num_attention_heads", c["num_attention_heads"], a.num_heads),
            ("num_key_value_heads", c["num_key_value_heads"],
             a.num_kv_heads),
            ("head_dim", c["head_dim"], a.head_dim),
            ("intermediate_size", c["intermediate_size"], m.d_ff),
            ("hidden_act", c["hidden_act"],
             "silu" if m.activation == "swiglu" else m.activation),
            ("vocab_size", c["vocab_size"], cfg.vocab_size),
            ("rope_theta", c["rope_theta"], a.rope_theta),
            ("qkv_bias", c["qkv_bias"], a.qkv_bias),
            ("tie_word_embeddings", c["tie_word_embeddings"],
             cfg.tie_embeddings),
            ("num_hidden_layers", c["num_hidden_layers"], cfg.num_layers)]
