"""Public attention op: (B, S, H, D) layout, GQA-aware, kernel/oracle switch.

``attention`` routes to the Pallas kernel on TPU and to the exact jnp
oracle elsewhere; ``force_kernel`` overrides the choice.  The kernel
compiles unless the caller passes ``interpret=True`` (the CPU tests).
The custom-VJP backward recomputes attention with the oracle (flash
backward is a follow-up kernel; recompute-backward is the standard remat
policy at these sizes anyway).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flatten(x):  # (B, S, H, D) -> (B*H, S, D)
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unflatten(x, b):  # (B*H, S, D) -> (B, S, H, D)
    bh, s, d = x.shape
    return x.reshape(b, bh // b, s, d).transpose(0, 2, 1, 3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, sm_scale: float | None = None,
              q_offset: int = 0, force_kernel: bool | None = None,
              interpret: bool = False,
              block_q: int = K.DEFAULT_BLOCK_Q,
              block_k: int = K.DEFAULT_BLOCK_K) -> jax.Array:
    """q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D); returns (B, Sq, Hq, D)."""
    b = q.shape[0]
    qf, kf, vf = _flatten(q), _flatten(k), _flatten(v)
    use_kernel = force_kernel if force_kernel is not None else _on_tpu()
    if use_kernel:
        out = K.flash_attention_bhsd(
            qf, kf, vf, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, q_offset=q_offset,
            interpret=interpret)
    else:
        out = ref.attention(qf, kf, vf, causal=causal, sm_scale=sm_scale,
                            q_offset=q_offset)
    return _unflatten(out, b)
