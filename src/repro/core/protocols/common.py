"""Shared helpers for protocol implementations.

Every protocol in this package is pure JAX and must be called INSIDE a
``substrate.shard_map`` region where ``axis_name`` is a *manual* mesh axis.  The
schedules are built from ``lax.ppermute`` so that the exact communication
pattern we cost-modeled is the one that compiles — this is the TPU analogue
of the paper's "MPI-protocol offloaded to the MPI-network".
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax


def axis_size(axis_name: str) -> int:
    """Static size of a manual mesh axis."""
    return lax.psum(1, axis_name)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def fwd_perm(p: int, shift: int = 1):
    return [(j, (j + shift) % p) for j in range(p)]


def bwd_perm(p: int, shift: int = 1):
    return [(j, (j - shift) % p) for j in range(p)]


def xor_perm(p: int, k: int):
    return [(j, j ^ k) for j in range(p)]


def complete_perm(pairs, p: int):
    """Extend a partial (src, dst) permutation to a full one over p ranks.

    ``lax.ppermute`` under real shard_map accepts partial permutations
    (silent zero-fill), but the vmap batching rule — which our single-device
    tests rely on — requires a full permutation.  Protocols that use partial
    perms always mask non-participating receivers, so the filler edges are
    semantically inert (they cost idle-link bandwidth only on cold paths).
    """
    pairs = list(pairs)
    srcs = {s for s, _ in pairs}
    dsts = {d for _, d in pairs}
    free_src = [j for j in range(p) if j not in srcs]
    free_dst = [j for j in range(p) if j not in dsts]
    return pairs + list(zip(free_src, free_dst))


def pad_flat(x: jax.Array, multiple: int):
    """Flatten ``x`` and zero-pad to a multiple.  Returns (flat, orig_size)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    rem = (-n) % multiple
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), flat.dtype)])
    return flat, n


def unpad(flat: jax.Array, n: int, shape) -> jax.Array:
    return flat[:n].reshape(shape)


LANES = 128


def _row_shape(n: int) -> tuple:
    return (n // LANES, LANES) if n % (2 * LANES) == 0 else (n,)


def rows(flat: jax.Array) -> jax.Array:
    """A flat chunk as ``(c // 128, 128)`` rows where ``c`` is a multiple
    of 2 * 128, else unchanged.  The TPU compiler takes minutes to lay
    out an array whose minor dimension holds ~1e8 elements (a ``(p, c)``
    view of a large gradient), while 128-lane rows are a plain relayout.
    The even row count keeps the halves the bidirectional ring splits a
    chunk into the same elements as on the flat chunk."""
    return flat.reshape(_row_shape(flat.shape[0]))


def chunk_view(flat: jax.Array, p: int) -> jax.Array:
    """``(p, ...)`` view of a padded flat vector: row ``j`` is chunk ``j``,
    laid out as ``rows`` lays out one chunk."""
    return flat.reshape((p,) + _row_shape(flat.shape[0] // p))


def chunk_size(x2d: jax.Array) -> int:
    """Elements per chunk of a ``chunk_view``."""
    return math.prod(x2d.shape[1:])


def dyn_chunk(x2d: jax.Array, idx) -> jax.Array:
    """x2d: (p, c); idx: traced int (any sign) -> row idx mod p."""
    p = x2d.shape[0]
    return lax.dynamic_index_in_dim(x2d, jnp.mod(idx, p), axis=0, keepdims=False)


def dyn_put(x2d: jax.Array, row: jax.Array, idx) -> jax.Array:
    p = x2d.shape[0]
    return lax.dynamic_update_index_in_dim(x2d, row, jnp.mod(idx, p), axis=0)


def is_pow2(p: int) -> bool:
    return p > 0 and (p & (p - 1)) == 0
