"""Pallas TPU kernel: blockwise (flash) attention forward.

TPU-native adaptation: the GPU flash algorithm tiles for shared memory per
SM; here the tiling targets VMEM and the MXU.  Q/K/V blocks are
(BLOCK_Q, D) / (BLOCK_K, D) with D the full head dim (MXU-aligned, 128|256),
the running max/denominator live in VMEM scratch that persists across the
innermost (kv) grid dimension, and the S = Q·Kᵀ / O += P·V contractions are
MXU matmuls with f32 accumulation (``preferred_element_type``).

Grid: (BH, num_q_blocks, num_kv_blocks); kv innermost ("arbitrary"), so the
(m, l, acc) scratch carries across kv steps.  Causal blocks strictly above
the diagonal are skipped with ``pl.when`` — ~2x fewer MXU flops at train
shapes.  GQA is expressed in the K/V index maps (query head h reads kv head
h // group), so no repeated KV is ever materialized in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale: float, causal: bool, block_q: int, block_k: int,
                  num_kv_blocks: int, q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Causal: the diagonal block of queries starts at q_offset + qi*block_q;
    # kv blocks strictly past the last query position contribute nothing.
    run = True
    if causal:
        last_q = q_offset + (qi + 1) * block_q - 1
        run = kj * block_k <= last_q

    @pl.when(run)
    def _body():
        q = q_ref[0]                       # (block_q, D)
        k = k_ref[0]                       # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (bq, bk)
        if causal:
            qpos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = qpos >= kpos
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, :1]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == num_kv_blocks - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sm_scale", "block_q", "block_k", "q_offset",
                     "interpret"))
def flash_attention_bhsd(
    q: jax.Array,      # (BH, Sq, D)
    k: jax.Array,      # (BHkv, Skv, D)
    v: jax.Array,      # (BHkv, Skv, D)
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    q_offset: int = 0,
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    bhkv, skv, _ = k.shape
    assert bh % bhkv == 0, (bh, bhkv)
    group = bh // bhkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    num_q = sq // block_q
    num_kv = skv // block_k

    kernel = functools.partial(
        _flash_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv_blocks=num_kv, q_offset=q_offset)

    return pl.pallas_call(
        kernel,
        grid=(bh, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, g=group: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),   # running max
            pltpu.VMEM((block_q, 128), jnp.float32),   # running denom
            pltpu.VMEM((block_q, d), jnp.float32),     # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
