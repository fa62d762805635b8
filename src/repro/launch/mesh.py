"""Production mesh definitions (TPU v5e-class pods).

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16) — the "pod"
axis rides DCN; collectives over it are costed/scheduled accordingly by
the engine's topology model.

Functions, not module constants: importing this module never touches JAX
device state (the dry-run sets XLA_FLAGS before any JAX import).
Construction goes through the device substrate.
"""

from __future__ import annotations

import jax

from repro.runtime import substrate


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return substrate.make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 2, pods: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    mp = min(model_parallel, n)
    if pods > 1 and n % (pods * mp) == 0:
        return substrate.make_mesh((pods, n // (pods * mp), mp),
                                   ("pod", "data", "model"))
    return substrate.make_mesh((n // mp, mp), ("data", "model"))
