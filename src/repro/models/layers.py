"""Shared neural layers: norms, RoPE / M-RoPE, attention, MLPs.

Conventions
-----------
- Params are nested dicts of jnp arrays; every ``init_*`` returns
  ``(params, specs)`` where ``specs`` mirrors the tree with
  ``jax.sharding.PartitionSpec`` leaves (mesh axes: "data", "model";
  cross-pod replication/batch over "pod" is added by the launcher).
- Attention defaults to the blockwise (flash) jnp algorithm — the same
  schedule as the Pallas kernel in ``repro.kernels.flash_attention`` —
  so no S×S score matrix is ever materialized in the HLO; the roofline
  memory term read off the compiled dry-run is therefore kernel-faithful.
- TP layout is Megatron-style: QKV/up projections shard the output dim
  over "model"; O/down projections shard the input dim; FSDP additionally
  shards the complementary dim over "data" (ZeRO-3).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.kernels.paged_attention import ops as paged_ops

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, fan_in: Optional[int] = None):
    fan_in = fan_in if fan_in is not None else shape[0]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32):
    return {"scale": jnp.ones((dim,), dtype)}, {"scale": P(None)}


def rmsnorm(params: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def init_layernorm(dim: int, dtype=jnp.float32):
    return ({"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)},
            {"scale": P(None), "bias": P(None)})


def layernorm(params: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE and M-RoPE
# ---------------------------------------------------------------------------


def rope_freqs(dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))


def rope_cos_sin(positions: jax.Array, dim: int, theta: float = 1e4
                 ) -> Tuple[jax.Array, jax.Array]:
    """positions: (..., S) int -> cos/sin (..., S, dim/2) f32."""
    ang = positions[..., None].astype(jnp.float32) * rope_freqs(dim, theta)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, H, D); cos/sin: (B, S, D/2) — rotate-half convention."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


def mrope_cos_sin(positions_3d: jax.Array, dim: int, sections: Tuple[int, ...],
                  theta: float = 1e6) -> Tuple[jax.Array, jax.Array]:
    """Qwen2-VL multimodal RoPE.  positions_3d: (3, B, S) for (t, h, w);
    ``sections`` partitions dim/2 into per-component frequency bands
    (e.g. (16, 24, 24) for D=128).  Returns cos/sin (B, S, dim/2)."""
    assert sum(sections) == dim // 2, (sections, dim)
    freqs = rope_freqs(dim, theta)                       # (dim/2,)
    ang_all = positions_3d[..., None].astype(jnp.float32) * freqs  # (3,B,S,dim/2)
    parts = []
    lo = 0
    for comp, sec in enumerate(sections):
        parts.append(ang_all[comp, :, :, lo:lo + sec])
        lo += sec
    ang = jnp.concatenate(parts, axis=-1)                # (B, S, dim/2)
    return jnp.cos(ang), jnp.sin(ang)


def text_positions(batch: int, seq: int, offset: int = 0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(seq) + offset, (batch, seq))


# ---------------------------------------------------------------------------
# Attention cores
# ---------------------------------------------------------------------------


def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, q_offset=0,
                        block_k: int = 512, sm_scale: float | None = None,
                        kv_len: Optional[jax.Array] = None) -> jax.Array:
    """Blockwise-softmax attention in pure jnp (the Pallas kernel's schedule).

    q: (B, Sq, H, D); k/v: (B, Skv, Hkv, Dv); GQA folded via head grouping.
    ``q_offset``: absolute position of q[.., 0] (static int) for causal
    masking.  ``kv_len``: (B,) valid kv lengths (ragged cache).

    Forward never materializes the (Sq, Skv) score matrix, and the
    backward is a custom VJP that RECOMPUTES scores blockwise from the
    saved (q, k, v, out, lse) — the FlashAttention-2 backward.  Without
    it, differentiating the kv scan stores every block's softmax, i.e.
    the full attention matrix (a ~30 GB/device bomb at 4k train shapes).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    nblk = -(-skv // block_k)
    pad = nblk * block_k - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = jnp.full((b,), skv, jnp.int32)
    if kv_len is None:
        kv_len = jnp.full((b,), skv, jnp.int32)
    if not isinstance(q_offset, (int, np.integer)):
        q_offset = int(q_offset)
    fn = _flash_vjp(causal, int(q_offset), block_k, float(scale))
    return fn(q, k, v, kv_len)


def _seq_flash_hint(x):
    """Sequence-parallel flash attention (REPRO_SEQ_FLASH=1): pin the
    query/score tiles to sequence-sharding over the TP axis.  With
    kv_heads < TP degree GSPMD cannot head-shard the score tensor and
    falls back to all-gathering it (a ~2 GB/layer tile); Sq-sharding
    keeps every tile local — each shard attends its query slice against
    the (small, replicated) KV."""
    import os
    if os.environ.get("REPRO_SEQ_FLASH", "0") != "1" or x.ndim < 3:
        return x
    from repro.parallel.sharding import shard_hint
    return shard_hint(
        x, P(("pod", "data"), "model", *([None] * (x.ndim - 2))))


def _flash_blocks(q, k, v, kv_len, causal, q_offset, block_k, scale):
    """Shared forward: returns (out f32, lse f32) with shapes
    (B,Sq,Hkv,G,Dv) / (B,Sq,Hkv,G,1).  Inputs stay in their dtype; the
    contractions accumulate in f32 via preferred_element_type."""
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    dv = v.shape[-1]
    group = h // hkv
    nblk = skv // block_k
    qg = _seq_flash_hint(q.reshape(b, sq, hkv, group, d))
    kb = jnp.moveaxis(k.reshape(b, nblk, block_k, hkv, d), 1, 0)
    vb = jnp.moveaxis(v.reshape(b, nblk, block_k, hkv, dv), 1, 0)
    q_pos = jnp.arange(sq) + q_offset

    def step(carry, blk):
        m, l, acc = carry
        kblk, vblk, j = blk
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kblk,
                       preferred_element_type=jnp.float32) * scale
        kpos = j * block_k + jnp.arange(block_k)
        mask = (kpos[None, None, :] < kv_len[:, None, None])
        if causal:
            mask &= (q_pos[None, :, None] >= kpos[None, None, :])
        mask_e = mask[:, :, None, None, :]
        s = jnp.where(mask_e, s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask_e, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = alpha[..., 0][..., None] * acc + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, sq, hkv, group, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, group, 1), jnp.float32)
    a0 = jnp.zeros((b, sq, hkv, group, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nblk)))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    lse = m_safe + jnp.log(l_safe)
    return out, lse


@functools.lru_cache(maxsize=None)
def _flash_vjp(causal: bool, q_offset: int, block_k: int, scale: float):
    @jax.custom_vjp
    def attn(q, k, v, kv_len):
        out, _ = _flash_blocks(q, k, v, kv_len, causal, q_offset, block_k,
                               scale)
        b, sq, hkv, group, dv = out.shape
        return out.reshape(b, sq, hkv * group, dv).astype(q.dtype)

    def fwd(q, k, v, kv_len):
        out, lse = _flash_blocks(q, k, v, kv_len, causal, q_offset, block_k,
                                 scale)
        b, sq, hkv, group, dv = out.shape
        o = out.reshape(b, sq, hkv * group, dv).astype(q.dtype)
        return o, (q, k, v, kv_len, o, lse)

    def bwd(res, do):
        q, k, v, kv_len, o, lse = res
        b, sq, h, d = q.shape
        _, skv, hkv, _ = k.shape
        dv = v.shape[-1]
        group = h // hkv
        nblk = skv // block_k
        qg = _seq_flash_hint(q.reshape(b, sq, hkv, group, d))
        og = _seq_flash_hint(
            o.reshape(b, sq, hkv, group, dv).astype(jnp.float32))
        dog = _seq_flash_hint(
            do.reshape(b, sq, hkv, group, dv).astype(jnp.float32))
        # delta_i = rowsum(dO ∘ O)  (FlashAttention-2, eq. 19)
        delta = jnp.sum(og * dog, axis=-1, keepdims=True)
        kb = jnp.moveaxis(k.reshape(b, nblk, block_k, hkv, d), 1, 0)
        vb = jnp.moveaxis(v.reshape(b, nblk, block_k, hkv, dv), 1, 0)
        q_pos = jnp.arange(sq) + q_offset

        def step(dq_acc, blk):
            kblk, vblk, j = blk
            s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kblk,
                           preferred_element_type=jnp.float32) * scale
            kpos = j * block_k + jnp.arange(block_k)
            mask = (kpos[None, None, :] < kv_len[:, None, None])
            if causal:
                mask &= (q_pos[None, :, None] >= kpos[None, None, :])
            mask_e = mask[:, :, None, None, :]
            p = jnp.where(mask_e, jnp.exp(s - lse), 0.0)   # recompute
            dp = jnp.einsum("bqhgd,bkhd->bqhgk", dog, vblk,
                            preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dv_blk = jnp.einsum("bqhgk,bqhgd->bkhd",
                                p.astype(dog.dtype), dog,
                                preferred_element_type=jnp.float32)
            dk_blk = jnp.einsum("bqhgk,bqhgd->bkhd",
                                ds.astype(qg.dtype), qg,
                                preferred_element_type=jnp.float32)
            dq_acc = dq_acc + jnp.einsum(
                "bqhgk,bkhd->bqhgd", ds.astype(kblk.dtype), kblk,
                preferred_element_type=jnp.float32)
            return dq_acc, (dk_blk, dv_blk)

        dq0 = jnp.zeros((b, sq, hkv, group, d), jnp.float32)
        dq, (dk_blks, dv_blks) = jax.lax.scan(
            step, dq0, (kb, vb, jnp.arange(nblk)))
        dk = jnp.moveaxis(dk_blks, 0, 1).reshape(b, skv, hkv, d)
        dv_ = jnp.moveaxis(dv_blks, 0, 1).reshape(b, skv, hkv, dv)
        return (dq.reshape(b, sq, h, d).astype(q.dtype),
                dk.astype(k.dtype), dv_.astype(v.dtype), None)

    attn.defvjp(fwd, bwd)
    return attn


def chunk_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                    q_pos: jax.Array, sm_scale: float | None = None
                    ) -> jax.Array:
    """Chunked-prefill attention: a chunk of queries against the FULL
    cache (prior chunks + this one already written), causal-masked by
    absolute position.

    q: (B, Sq, H, D); caches: (B, Smax, Hkv, D); q_pos: (B, Sq) absolute
    positions of the queries.  Unlike ``flash_attention_jnp`` the offset
    is a *traced* value — one trace serves every chunk index, which is
    what bounds the serving tier's prefill trace count.  Cache positions
    above a query (pad tail, unwritten pages) are causal-masked, so
    page-pool garbage never leaks into the softmax.  Serving chunks are
    page-sized, so the (Sq, Smax) score tensor stays small.
    """
    b, sq, h, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hkv, group, d).astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, kf)       # (B,Sq,Hkv,G,Smax)
    mask = jnp.arange(smax)[None, None, :] <= q_pos[:, :, None]
    s = jnp.where(mask[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array, sm_scale: float | None = None
                     ) -> jax.Array:
    """Single-token attention against a (possibly ragged) cache.

    q: (B, 1, H, D); caches: (B, Smax, Hkv, D); kv_len: (B,) valid lengths.
    Reads all ``Smax`` positions and masks afterwards: the path of the
    contiguous caches (``generate``) and of the page pool's arena program
    (models whose cache is not all GQA K/V).  The pool's GQA decode reads
    only the live pages instead (``attention_decode_paged``).
    """
    b, _, h, d = q.shape
    _, smax, hkv, _ = k_cache.shape
    group = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, hkv, group, d).astype(jnp.float32) * scale
    kf = k_cache.astype(jnp.float32)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, kf)             # (B,Hkv,G,Smax)
    mask = jnp.arange(smax)[None, :] < kv_len[:, None]    # (B,Smax)
    s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA/MQA/MHA attention layer (dense QKV path; MLA lives in models/mla.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttentionCfg:
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False          # qwen3-style per-head RMS q/k norm
    rope_theta: float = 1e4
    mrope_sections: Optional[Tuple[int, ...]] = None  # qwen2-vl M-RoPE
    causal: bool = True
    sliding_window: Optional[int] = None


def init_attention(key, cfg: AttentionCfg, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    D, H, Hkv, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p: Params = {
        "wq": dense_init(kq, (D, H * Dh), dtype),
        "wk": dense_init(kk, (D, Hkv * Dh), dtype),
        "wv": dense_init(kv, (D, Hkv * Dh), dtype),
        "wo": dense_init(ko, (H * Dh, D), dtype, fan_in=H * Dh),
    }
    s: Params = {
        "wq": P("data", "model"), "wk": P("data", "model"),
        "wv": P("data", "model"), "wo": P("model", "data"),
    }
    if cfg.qkv_bias:
        p.update({"bq": jnp.zeros((H * Dh,), dtype),
                  "bk": jnp.zeros((Hkv * Dh,), dtype),
                  "bv": jnp.zeros((Hkv * Dh,), dtype)})
        s.update({"bq": P("model"), "bk": P("model"), "bv": P("model")})
    if cfg.qk_norm:
        p["q_norm"], s["q_norm"] = init_rmsnorm(Dh, dtype)
        p["k_norm"], s["k_norm"] = init_rmsnorm(Dh, dtype)
    return p, s


def _project_qkv(params: Params, cfg: AttentionCfg, x: jax.Array):
    b, sq, _ = x.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(b, sq, H, Dh)
    k = k.reshape(b, sq, Hkv, Dh)
    v = v.reshape(b, sq, Hkv, Dh)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    return q, k, v


def _rope_for(cfg: AttentionCfg, positions, batch, seq):
    if positions is None:
        positions = text_positions(batch, seq)
    if cfg.mrope_sections is not None:
        if positions.ndim == 2:      # text-only fallback: t == h == w
            positions = jnp.broadcast_to(positions, (3,) + positions.shape)
        return mrope_cos_sin(positions, cfg.head_dim, cfg.mrope_sections,
                             cfg.rope_theta)
    return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)


def attention_forward(params: Params, cfg: AttentionCfg, x: jax.Array, *,
                      positions: Optional[jax.Array] = None,
                      q_offset=0,
                      kv_cache: Optional[Dict[str, jax.Array]] = None,
                      block_k: int = 512, chunked: bool = False,
                      valid_len: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Optional[Dict]]:
    """Full-sequence (train/prefill) path.  Returns (out, new_cache).

    ``chunked=True`` is the paged-prefill variant: ``q_offset`` may be a
    TRACED chunk offset, queries attend the whole cache through
    ``chunk_attention`` (earlier chunks included), and ``valid_len``
    clamps the length counter so a chunk right-padded to the page
    boundary doesn't count its pad positions.
    """
    b, sq, _ = x.shape
    q, k, v = _project_qkv(params, cfg, x)
    if positions is None:
        positions = text_positions(b, sq) + q_offset
    cos, sin = _rope_for(cfg, positions, b, sq)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    new_cache = None
    if kv_cache is not None:
        new_len = kv_cache["len"] + sq
        if valid_len is not None:
            new_len = jnp.minimum(new_len, valid_len)
        new_cache = {
            "k": jax.lax.dynamic_update_slice_in_dim(
                kv_cache["k"], k.astype(kv_cache["k"].dtype), q_offset, 1),
            "v": jax.lax.dynamic_update_slice_in_dim(
                kv_cache["v"], v.astype(kv_cache["v"].dtype), q_offset, 1),
            "len": new_len,
        }
    if chunked:
        assert new_cache is not None, "chunked prefill needs a cache"
        q_pos = jnp.arange(sq)[None, :] + jnp.asarray(q_offset).reshape(
            (1, 1))
        q_pos = jnp.broadcast_to(q_pos, (b, sq))
        out = chunk_attention(q, new_cache["k"], new_cache["v"], q_pos)
    else:
        out = flash_attention_jnp(q, k, v, causal=cfg.causal,
                                  q_offset=q_offset, block_k=block_k)
    out = out.reshape(b, sq, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"], new_cache


def attention_decode(params: Params, cfg: AttentionCfg, x: jax.Array,
                     kv_cache: Dict[str, jax.Array], *,
                     positions: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode with cache update.  x: (B, 1, D)."""
    b = x.shape[0]
    q, k, v = _project_qkv(params, cfg, x)
    pos = positions
    if pos is None:
        pos = kv_cache["len"][:, None]                    # (B, 1)
    if cfg.mrope_sections is not None and pos.ndim == 2:
        pos = jnp.broadcast_to(pos, (3,) + pos.shape)
    cos, sin = _rope_for(cfg, pos, b, 1)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    # Scatter the new kv at each sequence's own length (ragged batch).
    idx = kv_cache["len"]                                 # (B,)
    kc = _scatter_token(kv_cache["k"], k, idx)
    vc = _scatter_token(kv_cache["v"], v, idx)
    new_len = idx + 1
    out = decode_attention(q, kc, vc, new_len)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    return out @ params["wo"], {"k": kc, "v": vc, "len": new_len}


def attention_decode_paged(params: Params, cfg: AttentionCfg, x: jax.Array,
                           cache: Dict[str, jax.Array], layer, table, active
                           ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against the page pool, with no arena.

    x: (B, 1, D); cache: this layer's "k"/"v" pool leaves, ``(num_pages +
    1, page_tokens, L, Hkv, Dh)`` read at ``layer``, and "len" (B,), the
    positions each slot holds; table: (B, pages_per_slot) page ids;
    active: (B,) bool, the slots that decode.  Attention reads each active
    slot's live pages (``repro.kernels.paged_attention``) with the new
    token's own k/v folded in.  Nothing is written here: the returned
    cache holds the new token's "k"/"v" (B, Hkv, Dh) and the advanced
    "len", and the page pool writes every layer's token into its page.
    """
    b = x.shape[0]
    q, k, v = _project_qkv(params, cfg, x)
    pos = cache["len"][:, None]                           # (B, 1)
    if cfg.mrope_sections is not None:
        pos = jnp.broadcast_to(pos, (3,) + pos.shape)
    cos, sin = _rope_for(cfg, pos, b, 1)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    k_tok = k[:, 0].astype(cache["k"].dtype)
    v_tok = v[:, 0].astype(cache["v"].dtype)
    lengths = jnp.where(active, cache["len"] + 1, 0)
    out = paged_ops.paged_attention(q[:, 0], cache["k"], cache["v"], layer,
                                    lengths, table, k_tok, v_tok)
    out = out.reshape(b, 1, cfg.num_heads * cfg.head_dim)
    new_len = cache["len"] + active.astype(cache["len"].dtype)
    return out @ params["wo"], {"k": k_tok, "v": v_tok, "len": new_len}


def _scatter_token(cache: jax.Array, token: jax.Array, idx: jax.Array
                   ) -> jax.Array:
    """cache: (B, Smax, H, D); token: (B, 1, H, D); idx: (B,)."""
    b, smax = cache.shape[:2]
    onehot = (jnp.arange(smax)[None, :] == idx[:, None])  # (B, Smax)
    return jnp.where(onehot[:, :, None, None],
                     token.astype(cache.dtype), cache)


def init_kv_cache(batch: int, max_len: int, cfg: AttentionCfg,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.zeros((batch,), jnp.int32)}


def kv_cache_specs(cfg: AttentionCfg) -> Dict[str, P]:
    return {"k": P(("pod", "data"), None, "model", None),
            "v": P(("pod", "data"), None, "model", None),
            "len": P(("pod", "data"))}


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MLPCfg:
    d_model: int
    d_ff: int
    activation: str = "swiglu"     # swiglu | squared_relu | gelu


def init_mlp(key, cfg: MLPCfg, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    D, F = cfg.d_model, cfg.d_ff
    if cfg.activation == "swiglu":
        p = {"w_gate": dense_init(k1, (D, F), dtype),
             "w_up": dense_init(k2, (D, F), dtype),
             "w_down": dense_init(k3, (F, D), dtype, fan_in=F)}
        s = {"w_gate": P("data", "model"), "w_up": P("data", "model"),
             "w_down": P("model", "data")}
    else:
        p = {"w_up": dense_init(k1, (D, F), dtype),
             "w_down": dense_init(k2, (F, D), dtype, fan_in=F)}
        s = {"w_up": P("data", "model"), "w_down": P("model", "data")}
    return p, s


def mlp_forward(params: Params, cfg: MLPCfg, x: jax.Array) -> jax.Array:
    if cfg.activation == "swiglu":
        g = x @ params["w_gate"]
        u = x @ params["w_up"]
        h = jax.nn.silu(g) * u
    elif cfg.activation == "squared_relu":
        h = jax.nn.relu(x @ params["w_up"])
        h = h * h
    elif cfg.activation == "gelu":
        h = jax.nn.gelu(x @ params["w_up"])
    else:
        raise ValueError(cfg.activation)
    return h @ params["w_down"]


# ---------------------------------------------------------------------------
# Cross-attention (enc-dec decoder)
# ---------------------------------------------------------------------------


def init_cross_attention(key, cfg: AttentionCfg, dtype=jnp.float32):
    return init_attention(key, cfg, dtype)


def cross_attention_forward(params: Params, cfg: AttentionCfg,
                            x: jax.Array, memory: jax.Array,
                            block_k: int = 512) -> jax.Array:
    """x: (B, Sq, D) queries; memory: (B, Skv, D) encoder states."""
    b, sq, _ = x.shape
    skv = memory.shape[1]
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (x @ params["wq"]).reshape(b, sq, H, Dh)
    k = (memory @ params["wk"]).reshape(b, skv, Hkv, Dh)
    v = (memory @ params["wv"]).reshape(b, skv, Hkv, Dh)
    if cfg.qkv_bias:
        q = q + params["bq"].reshape(H, Dh)
        k = k + params["bk"].reshape(Hkv, Dh)
        v = v + params["bv"].reshape(Hkv, Dh)
    out = flash_attention_jnp(q, k, v, causal=False, block_k=block_k)
    out = out.reshape(b, sq, H * Dh)
    return out @ params["wo"]
