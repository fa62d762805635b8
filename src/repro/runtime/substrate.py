"""Single-entity device substrate: the mesh layer over JAX's explicit-axis
sharding API.

The paper's §2 prescription — MPI-network / MPI-protocol / MPI as *one
entity* instead of a stack of independently-versioned layers — applied to
the JAX device layer: every mesh construction, active-mesh context, mode
query, and ``shard_map`` entry in this repo goes through this one module,
so call sites never spell a JAX mesh API themselves.

Each function is a thin call into ``jax.make_mesh``, ``jax.set_mesh``,
``jax.sharding.use_abstract_mesh`` / ``get_abstract_mesh`` /
``get_mesh`` and ``jax.shard_map``.  Meshes default to all-``Auto`` axes
(GSPMD partitions whatever the program does not constrain).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh

__all__ = ["AxisType", "abstract_mesh", "active_mesh", "auto_axis_names",
           "concrete_mesh", "is_abstract", "make_mesh", "set_mesh",
           "shard_map", "use_abstract_mesh"]


def _types(n: int, axis_types: Optional[Sequence[Any]]) -> tuple:
    if axis_types is None:
        return (AxisType.Auto,) * n
    return tuple(axis_types)


# ---------------------------------------------------------------------------
# Mesh construction
# ---------------------------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Optional[Sequence[Any]] = None,
              devices: Optional[Sequence[Any]] = None) -> Mesh:
    """Concrete mesh over local devices; ``axis_types`` defaults to
    all-Auto."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=_types(len(names), axis_types),
                         devices=devices)


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
                  axis_types: Optional[Sequence[Any]] = None) -> AbstractMesh:
    """Device-less mesh for pre-execution tracing (the §2.2 application
    scan runs over one of these — nothing is allocated)."""
    names = tuple(axis_names)
    return AbstractMesh(tuple(axis_shapes), names,
                        axis_types=_types(len(names), axis_types))


# ---------------------------------------------------------------------------
# Active-mesh context
# ---------------------------------------------------------------------------

def set_mesh(mesh):
    """The one mesh-entry point (``jax.set_mesh``), usable as a context
    manager."""
    return jax.set_mesh(mesh)


def use_abstract_mesh(mesh):
    """Abstract-mesh tracing context (scan/compose probes)."""
    return jax.sharding.use_abstract_mesh(mesh)


def active_mesh():
    """The (abstract) mesh of the innermost context, or ``None`` outside
    any.  Inside a ``shard_map`` body its manual axes carry
    ``AxisType.Manual``."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def concrete_mesh() -> Optional[Mesh]:
    """The device mesh set by ``set_mesh``, or ``None`` (outside any
    context, under an abstract-only context, or while tracing — where
    only ``active_mesh()`` is meaningful)."""
    try:
        m = jax.sharding.get_mesh()
    except ValueError:                      # called under a trace
        return None
    return None if m.empty else m


# ---------------------------------------------------------------------------
# Mode queries
# ---------------------------------------------------------------------------

def is_abstract(mesh) -> bool:
    return isinstance(mesh, AbstractMesh)


def auto_axis_names(mesh) -> Tuple[str, ...]:
    """Mesh axes currently in Auto mode (constrainable)."""
    if mesh is None:
        return ()
    return tuple(n for n, t in zip(mesh.axis_names, mesh.axis_types)
                 if t == AxisType.Auto)


# ---------------------------------------------------------------------------
# shard_map
# ---------------------------------------------------------------------------

def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool = False):
    """``jax.shard_map``; ``axis_names`` is the set of *manual* axes (the
    rest stay auto).  Usable via ``functools.partial(...)`` as a
    decorator."""
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)
