from repro.kernels.paged_attention import kernel, ops, ref

__all__ = ["kernel", "ops", "ref"]
