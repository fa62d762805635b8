"""Pallas TPU kernel: one decode token's attention read straight from the
page pool.

The pool keeps every layer of a stage in one page, ``(num_pages + 1,
page_tokens, L, Hkv, D)``; the kernel reads layer ``layer`` of the pages
in each slot's table, and only the live ones.  Grid: one step per slot.
Inside it a loop walks the slot's live blocks (a page, or a slice of
``BLOCK_TOKENS`` positions of a larger page), double-buffered: while
block ``i`` is scored, the DMA of block ``i + 1`` (one ``(block, Hkv,
D)`` copy for K and one for V; at 16-token pages, 8 KV heads of 128 in
bf16, 32 KiB each) is in flight.  Blocks past the slot's length are never
copied, and a slot of length 0 (inactive) copies nothing and returns
zeros.  One grid step per slot, not per page, because a grid step costs a
fixed fraction of a microsecond and most of a ``(slots, pages_per_slot)``
grid would be dead pages.

All heads of a block are scored at once on the MXU: the block is viewed
as ``(block * Hkv, D)`` rows (token-major), the queries as ``(H, D)``,
and each score whose query head does not read that row's KV head is
masked away.  That is ``Hkv`` times the scores GQA needs, on an MXU that
is idle in a decode anyway, and no transpose.  The softmax is online and
in float32, as ``models.layers.decode_attention`` computes it.

The new token's own K/V (not yet in the pool: the caller writes it after
every layer has run) come in as operands and are folded in last, so the
kernel reads nothing the same program writes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_TOKENS = 64          # largest copy of one page's positions


def fits(k_pages) -> bool:
    """Whether a page of this pool can be copied into VMEM at all: the
    head dim must fill whole 128-lane tiles and the KV heads whole 32-bit
    sublane words (the pool's own HBM tiling; the compiler refuses a
    slice of a partial tile)."""
    *_, hkv, d = k_pages.shape
    return d % 128 == 0 and hkv * jnp.dtype(k_pages.dtype).itemsize % 4 == 0


def _nt(a, b):
    """a @ b.T with float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online(carry, s, mask, v):
    """Fold one block of scores ``s`` (H, R) and values ``v`` (R, D) into
    the running (max, denominator, accumulator)."""
    m, l, acc = carry
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    acc = alpha * acc + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l, acc


def _paged_kernel(layer_ref, lengths_ref, table_ref,       # scalar prefetch
                  q_ref, kn_ref, vn_ref, k_hbm, v_hbm,     # inputs
                  o_ref,                                   # output
                  kbuf, vbuf, sems,                        # scratch
                  *, sm_scale: float, pages_per_slot: int):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = lengths_ref[b]
    _, pt, _, hkv, d = k_hbm.shape
    h = q_ref.shape[0]
    group = h // hkv
    blk = kbuf.shape[1]
    per_page = pt // blk
    rows = blk * hkv
    cached = length - 1                    # positions held by the pool
    n_blocks = (cached + blk - 1) // blk

    def copies(i, slot):
        pid = table_ref[b * pages_per_slot + i // per_page]
        at = pl.ds(pl.multiple_of((i % per_page) * blk, blk), blk)
        return (pltpu.make_async_copy(k_hbm.at[pid, at, layer],
                                      kbuf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid, at, layer],
                                      vbuf.at[slot], sems.at[1, slot]))

    @pl.when(length == 0)
    def _inactive():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(length > 0)
    def _attend():
        q = q_ref[...].astype(jnp.float32) * sm_scale          # (H, D)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (h, rows), 1)
        same_head = (row // group) == (col % hkv)
        tok = col // hkv

        @pl.when(n_blocks > 0)
        def _prefetch_first():
            for c in copies(0, 0):
                c.start()

        def block(i, carry):
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 < n_blocks)
            def _prefetch_next():
                for c in copies(i + 1, 1 - slot):
                    c.start()

            for c in copies(i, slot):
                c.wait()
            k = kbuf[slot].astype(jnp.float32).reshape(rows, d)
            v = vbuf[slot].astype(jnp.float32).reshape(rows, d)
            mask = same_head & (i * blk + tok < cached)
            return _online(carry, _nt(q, k), mask, v)

        carry = (jnp.full((h, 1), NEG_INF, jnp.float32),
                 jnp.zeros((h, 1), jnp.float32),
                 jnp.zeros((h, d), jnp.float32))
        carry = jax.lax.fori_loop(0, n_blocks, block, carry)
        own = (jax.lax.broadcasted_iota(jnp.int32, (h, hkv), 0) // group
               == jax.lax.broadcasted_iota(jnp.int32, (h, hkv), 1))
        _, l, acc = _online(carry, _nt(q, kn_ref[...].astype(jnp.float32)),
                            own, vn_ref[...].astype(jnp.float32))
        o_ref[...] = (acc / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_attention(q, k_pages, v_pages, layer, lengths, table, k_new, v_new,
                    *, sm_scale: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """Shapes as ``ref.paged_attention``; returns (B, H, D) in q's dtype."""
    b, h, d = q.shape
    _, pt, _, hkv, _ = k_pages.shape
    pps = table.shape[1]
    blk = math.gcd(pt, BLOCK_TOKENS)
    sm_scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    kernel = functools.partial(_paged_kernel, sm_scale=sm_scale,
                               pages_per_slot=pps)
    per_slot = lambda n: pl.BlockSpec((None, n, d), lambda i, *_: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[per_slot(h), per_slot(hkv), per_slot(hkv),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=per_slot(h),
            scratch_shapes=[
                pltpu.VMEM((2, blk, hkv, d), k_pages.dtype),
                pltpu.VMEM((2, blk, hkv, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      table.reshape(-1).astype(jnp.int32), q, k_new, v_new, k_pages, v_pages)
