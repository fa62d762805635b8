"""Public paged decode attention: kernel/oracle switch.

``paged_attention`` routes to the Pallas kernel on TPU where the pool's
layout lets it copy a page (``kernel.fits``: a head dim of whole
128-lane tiles, KV heads of whole 32-bit words) and to the exact jnp
oracle elsewhere; ``force_kernel`` overrides the choice.  The kernel
compiles unless the caller passes ``interpret=True`` (the CPU tests).
The page pool takes its paged decode on TPU only where the kernel runs
(``repro.serve.paging.decodes_paged``: the layout fits and the decode
spans one device), so on the chip the oracle is never the decode.
Decode only: there is no backward.
"""

from __future__ import annotations

import jax

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ref


def paged_attention(q, k_pages, v_pages, layer, lengths, table, k_new, v_new,
                    *, sm_scale: float | None = None,
                    force_kernel: bool | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v pages: (num_pages + 1, page_tokens, L, Hkv, D);
    layer: scalar; lengths: (B,) positions attended, the new token's
    included (0: inactive, returns zeros); table: (B, pages_per_slot);
    k_new, v_new: (B, Hkv, D).  Returns (B, H, D)."""
    use_kernel = force_kernel if force_kernel is not None \
        else jax.default_backend() == "tpu" and K.fits(k_pages)
    if use_kernel:
        return K.paged_attention(q, k_pages, v_pages, layer, lengths, table,
                                 k_new, v_new, sm_scale=sm_scale,
                                 interpret=interpret)
    return ref.paged_attention(q, k_pages, v_pages, layer, lengths, table,
                               k_new, v_new, sm_scale=sm_scale)
