"""Public jit'd wrapper for the tiled chunk reduction (flat API)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.local_reduce import kernel as K
from repro.kernels.local_reduce import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sum_chunks(x: jax.Array, dtype=None,
               force_kernel: bool | None = None,
               interpret: bool = False) -> jax.Array:
    """x: (k, n) -> (n,) sum accumulated in f32.  The kernel runs on TPU
    (or when ``force_kernel``), compiled unless ``interpret=True``."""
    dtype = dtype or x.dtype
    use_kernel = force_kernel if force_kernel is not None else _on_tpu()
    if not use_kernel:
        return ref.sum_chunks(x, dtype)
    k, n = x.shape
    tile = K.TILE_ROWS * K.LANES
    pad = (-n) % tile
    xp = jnp.pad(x, ((0, 0), (0, pad))) if pad else x
    x3 = xp.reshape(k, -1, K.LANES)
    out = K.sum_chunks_3d(x3, interpret=interpret)
    return out.reshape(-1)[:n].astype(dtype)
