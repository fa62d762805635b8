"""Public jit'd wrappers for the quantize kernel (flat-array API).

On a TPU backend the Pallas kernel runs; elsewhere the jnp oracle, which
XLA-CPU fuses well anyway.  ``force_kernel`` overrides the choice, and the
kernel compiles unless the caller passes ``interpret=True`` (the CPU
tests).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.quantize import kernel as K
from repro.kernels.quantize import ref

QBLOCK = K.QBLOCK


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(force_kernel: bool | None) -> str:
    if force_kernel is None:
        return "kernel" if _on_tpu() else "ref"
    return "kernel" if force_kernel else "ref"


def quantize(x: jax.Array, block: int = QBLOCK,
             force_kernel: bool | None = None, interpret: bool = False
             ) -> Tuple[jax.Array, jax.Array]:
    """Flat x (n,), n % block == 0 -> (q int8 (n,), scales f32 (n/block,))."""
    assert block == QBLOCK, f"kernel is specialized for block={QBLOCK}"
    assert x.size % block == 0, (x.size, block)
    mode = _mode(force_kernel)
    if mode == "ref":
        return ref.quantize(x, block)
    rows = x.size // block
    pad_rows = (-rows) % K.ROWS_PER_TILE
    x2d = x.reshape(rows, block).astype(jnp.float32)
    if pad_rows:
        x2d = jnp.concatenate(
            [x2d, jnp.zeros((pad_rows, block), jnp.float32)])
    q2d, s2d = K.quantize_2d(x2d, interpret=interpret)
    return q2d[:rows].reshape(-1), s2d[:rows, 0]


def dequantize(q: jax.Array, scale: jax.Array, block: int = QBLOCK,
               dtype=jnp.float32, force_kernel: bool | None = None,
               interpret: bool = False
               ) -> jax.Array:
    assert block == QBLOCK
    mode = _mode(force_kernel)
    if mode == "ref":
        return ref.dequantize(q, scale, block, dtype)
    rows = q.size // block
    pad_rows = (-rows) % K.ROWS_PER_TILE
    q2d = q.reshape(rows, block)
    s2d = scale.reshape(rows, 1)
    if pad_rows:
        q2d = jnp.concatenate([q2d, jnp.zeros((pad_rows, block), jnp.int8)])
        s2d = jnp.concatenate([s2d, jnp.ones((pad_rows, 1), jnp.float32)])
    x2d = K.dequantize_2d(q2d, s2d, dtype=dtype, interpret=interpret)
    return x2d[:rows].reshape(-1)


def dequant_add(acc: jax.Array, q: jax.Array, scale: jax.Array,
                block: int = QBLOCK, force_kernel: bool | None = None,
                interpret: bool = False
                ) -> jax.Array:
    assert block == QBLOCK
    mode = _mode(force_kernel)
    if mode == "ref":
        return ref.dequant_add(acc, q, scale, block)
    rows = q.size // block
    pad_rows = (-rows) % K.ROWS_PER_TILE
    a2d = acc.reshape(rows, block)
    q2d = q.reshape(rows, block)
    s2d = scale.reshape(rows, 1)
    if pad_rows:
        a2d = jnp.concatenate([a2d, jnp.zeros((pad_rows, block), acc.dtype)])
        q2d = jnp.concatenate([q2d, jnp.zeros((pad_rows, block), jnp.int8)])
        s2d = jnp.concatenate([s2d, jnp.ones((pad_rows, 1), jnp.float32)])
    out = K.dequant_add_2d(a2d, q2d, s2d, interpret=interpret)
    return out[:rows].reshape(acc.shape)
