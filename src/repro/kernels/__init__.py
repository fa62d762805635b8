"""Pallas TPU kernels for the comm-stack and model compute hot spots.

Each kernel subpackage follows the pattern:
  kernel.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — public wrapper (kernel on TPU; interpret only on request)
  ref.py    — pure-jnp oracle used by tests and as the CPU fallback
"""

__all__ = ["flash_attention", "local_reduce", "paged_attention", "quantize"]
