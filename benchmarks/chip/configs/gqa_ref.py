"""Plain float32 reference of a Qwen2-style decoder (arXiv:2407.10671):
pre-norm RMSNorm, grouped-query attention with q/k/v biases and rotary
positions (rotate-half, theta from the config), a SwiGLU MLP, a final
RMSNorm and an untied output head.

Straight ``jax.numpy``: no cache, no batching of requests, no kernel, one
sequence at a time.  Weights are read by the names of the program's
parameter tree (``embed``, ``stage0/layer0/attn/wq``, ...; layers stacked
on the leading axis) and made by ``weights.py`` from the seed.

``mode`` "f32" computes every product in float32 at the highest matmul
precision.  "fp8" is the control: every matrix product's inputs rounded
to float8 (e4m3) first, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
HEAD_BLOCKS = 8


def _round(x, mode):
    x = x.astype(jnp.float32)
    if mode == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(x, w, mode):
    return jnp.matmul(_round(x, mode), _round(w, mode), precision=HI)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, pos, theta):
    """x: (S, H, Dh); rotate-half over the head dim's two halves."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2
                          / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, c, mode):
    s = x.shape[0]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    a = p["attn"]
    y = rmsnorm(x, p["norm_mixer"]["scale"], eps)
    q = (mm(y, a["wq"], mode) + a["bq"].astype(jnp.float32)).reshape(s, h, hd)
    k = (mm(y, a["wk"], mode) + a["bk"].astype(jnp.float32)).reshape(s, kv, hd)
    v = (mm(y, a["wv"], mode) + a["bv"].astype(jnp.float32)).reshape(s, kv, hd)
    pos = jnp.arange(s)
    q, k = rope(q, pos, c["rope_theta"]), rope(k, pos, c["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=1)           # query head i reads kv i // g
    v = jnp.repeat(v, h // kv, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", _round(q, mode), _round(k, mode),
                    precision=HI) / jnp.sqrt(jnp.float32(hd))
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", _round(jax.nn.softmax(sc, -1), mode),
                   _round(v, mode), precision=HI).reshape(s, h * hd)
    x = x + mm(o, a["wo"], mode)
    m = p["mlp"]
    y = rmsnorm(x, p["norm_ffn"]["scale"], eps)
    g = jax.nn.silu(mm(y, m["w_gate"], mode)) * mm(y, m["w_up"], mode)
    return x + mm(g, m["w_down"], mode)


def logits(params, c, tokens, mode="f32"):
    """(S,) token ids -> (S, vocab) float32 logits of one sequence."""
    x = params["embed"][tokens].astype(jnp.float32)
    stage = params["stage0"]
    for i in range(c["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(lambda w: w[i], stage["layer0"])
        x = layer(lp, x, c, mode)
    x = rmsnorm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    head = params["lm_head"]
    cols = head.shape[1] // HEAD_BLOCKS          # the f32 head in blocks
    return jnp.concatenate(
        [mm(x, head[:, j * cols:(j + 1) * cols], mode)
         for j in range(HEAD_BLOCKS)], axis=-1)
