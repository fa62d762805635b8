"""Pure-jnp oracle for one decode token's attention over the page pool.

Shapes follow the kernel's convention:
  q: (B, H, D); k_pages, v_pages: (num_pages + 1, page_tokens, L, Hkv, D)
  (the pool's token leaves, every layer of a stage in one page); layer: the
  layer the call reads; lengths: (B,) positions attended, the new token's
  included (it sits at ``lengths - 1``; 0 marks an inactive slot); table:
  (B, pages_per_slot) page ids; k_new, v_new: (B, Hkv, D), the new token's
  own key and value, which the pool does not hold yet.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_attention(q, k_pages, v_pages, layer, lengths, table, k_new, v_new,
                    *, sm_scale: float | None = None) -> jax.Array:
    """Exact attention: gathers each slot's pages into a contiguous row,
    writes the new token at ``lengths - 1``, then a masked float32 softmax
    (the arithmetic of ``models.layers.decode_attention``).  Inactive
    slots return zeros."""
    b, h, d = q.shape
    pt, hkv = k_pages.shape[1], k_pages.shape[3]
    smax = table.shape[1] * pt
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    pos = jnp.arange(smax)[None, :]
    new = (pos == lengths[:, None] - 1)[:, :, None, None]

    def row(pages, tok):                     # -> (B, smax, Hkv, D) float32
        g = pages[table, :, layer].reshape(b, smax, hkv, d)
        return jnp.where(new, tok[:, None], g).astype(jnp.float32)

    k, v = row(k_pages, k_new), row(v_pages, v_new)
    qg = q.reshape(b, hkv, h // hkv, d).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k)
    s = jnp.where((pos < lengths[:, None])[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v).reshape(b, h, d)
    out = jnp.where((lengths > 0)[:, None, None], out, 0.0)
    return out.astype(q.dtype)
