"""Sharding helpers shared by models, train, serve, and launch.

Models annotate activations with ``shard_hint(x, spec)`` — a no-op outside
a mesh context (single-device smoke tests), a
``with_sharding_constraint`` under ``substrate.set_mesh``.  Spec axis
names not present in the active mesh are dropped, so the same model code
runs on (data, model), (pod, data, model), or single-device meshes
unchanged.  All mesh-context and mode queries go through the single
device-substrate entity (``repro.runtime.substrate``).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.runtime import substrate


def filter_spec(spec: P, axis_names: Sequence[str]) -> P:
    """Drop mesh-axis names not present in ``axis_names`` from a spec."""
    names = set(axis_names)
    out = []
    for entry in spec:
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            out.append(kept if kept else None)
        else:
            out.append(entry if entry in names else None)
    return P(*out)


def active_mesh():
    """The active mesh, or None outside any mesh context (never raises)."""
    return substrate.active_mesh()


def auto_axis_names(mesh) -> tuple:
    """Mesh axes currently in Auto mode (constrainable).  Inside a
    shard_map body the manual axes must not appear in constraints."""
    return substrate.auto_axis_names(mesh)


def shard_hint(x: jax.Array, spec: P) -> jax.Array:
    """Best-effort sharding constraint: identity without a mesh context.

    Under tracing the spec is applied as-is against the active mesh
    (hints are load-bearing for the partitioner).  On *concrete* values —
    eager execution, e.g. the serving tier's un-jitted batch-1 prefill —
    the constraint is a ``NamedSharding`` on the device mesh that
    ``set_mesh`` entered (identity under an abstract-only context), and
    spec entries whose mesh-axis product does not divide the dim are
    dropped, because placing a concrete array needs exact divisibility."""
    mesh = active_mesh()
    if mesh is None:
        return x
    fs = filter_spec(spec, auto_axis_names(mesh))
    if isinstance(x, jax.core.Tracer):
        return jax.lax.with_sharding_constraint(x, fs)
    devices = substrate.concrete_mesh()
    if devices is None:
        return x
    sizes = dict(mesh.shape)
    out = []
    for i, entry in enumerate(fs):
        if entry is None or i >= x.ndim:
            out.append(entry if i < x.ndim else None)
            continue
        n = 1
        for a in (entry if isinstance(entry, tuple) else (entry,)):
            n *= sizes.get(a, 1)
        out.append(entry if x.shape[i] % n == 0 else None)
    return jax.lax.with_sharding_constraint(x,
                                            NamedSharding(devices, P(*out)))


def activation_hint(x: jax.Array) -> jax.Array:
    """Layer-boundary activation constraint: batch over (pod, data) and —
    sequence-parallel style — the sequence dim over "model" when it
    divides.  The saved remat/scan boundary stacks inherit this sharding,
    cutting their per-device footprint by the TP degree (the difference
    between fitting and OOM for the 123B–671B train cells)."""
    mesh = active_mesh()
    if mesh is None or x.ndim < 3:
        return x
    auto = set(auto_axis_names(mesh))
    sizes = {k: v for k, v in dict(mesh.shape).items() if k in auto}
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    bsz = 1
    for a in batch_axes:
        bsz *= sizes[a]
    b_entry = batch_axes if (batch_axes and x.shape[0] % bsz == 0) else None
    s_entry = "model" if ("model" in sizes
                          and x.shape[1] % sizes["model"] == 0
                          and x.shape[1] >= 2 * sizes["model"]) else None
    return shard_hint(x, P(b_entry, s_entry, *([None] * (x.ndim - 2))))


def tree_filter_specs(spec_tree: Any, axis_names: Sequence[str]) -> Any:
    return jax.tree_util.tree_map(
        lambda s: filter_spec(s, axis_names), spec_tree,
        is_leaf=lambda s: isinstance(s, P))


def named_shardings(mesh, spec_tree: Any) -> Any:
    """Spec tree -> NamedSharding tree on a concrete mesh (specs filtered
    to the mesh's axes)."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, filter_spec(s, mesh.axis_names)),
        spec_tree, is_leaf=lambda s: isinstance(s, P))


def fitted_shardings(mesh, spec_tree: Any, shaped_tree: Any) -> Any:
    """Like named_shardings but drops spec entries whose mesh-axis product
    does not divide the corresponding dim (elastic re-mesh onto odd device
    counts needs this — a (256, 64) leaf cannot shard dim1 over 3)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def fit(spec: P, leaf) -> NamedSharding:
        fs = filter_spec(spec, mesh.axis_names)
        out = []
        for i, entry in enumerate(fs):
            if entry is None or i >= len(leaf.shape):
                out.append(None if i >= len(leaf.shape) else entry)
                continue
            n = 1
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                n *= sizes.get(a, 1)
            out.append(entry if leaf.shape[i] % n == 0 else None)
        return NamedSharding(mesh, P(*out))

    return jax.tree_util.tree_map(fit, spec_tree, shaped_tree,
                                  is_leaf=lambda s: isinstance(s, P))


def stack_specs(spec_tree: Any, extra_leading: int = 1) -> Any:
    """Prepend ``extra_leading`` None dims to every spec (stacked layers)."""
    def one(s: P) -> P:
        return P(*((None,) * extra_leading + tuple(s)))
    return jax.tree_util.tree_map(one, spec_tree,
                                  is_leaf=lambda s: isinstance(s, P))


def batch_spec(extra_dims: int = 1) -> P:
    """Default activation spec: batch over (pod, data)."""
    return P(("pod", "data"), *([None] * extra_dims))
