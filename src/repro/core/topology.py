"""MPI-network analogue: a model of the physical network under a JAX mesh.

The paper (§4) argues the network should be designed *for* the protocol and
the protocol *for* each function — a "single entity".  On TPU the network is
fixed (ICI torus within a pod, DCN between pods), so the co-design runs in
the other direction: the protocol layer reads an explicit topology model and
specializes per function.  This module is that topology model.

Link constants are looked up by the devices' ``device_kind`` in one table
(``DEVICES``); a kind that is not in it is an error, never a default.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class Link:
    """A class of links along one mesh axis."""

    bandwidth: float  # bytes/s, per direction
    alpha: float      # seconds per message
    wraparound: bool  # torus wraparound (ring protocols get full bisection)
    duplex: bool = True


@dataclasses.dataclass(frozen=True)
class Topology:
    """Physical interpretation of a named JAX mesh.

    ``axis_sizes`` maps mesh axis name -> number of devices along it.
    ``axis_links`` maps axis name -> the Link class connecting neighbours
    along that axis.  Axes within a pod ride the ICI torus; the ``pod``
    axis (if present) rides DCN.
    """

    axis_sizes: Mapping[str, int]
    axis_links: Mapping[str, Link]

    @property
    def num_devices(self) -> int:
        return math.prod(self.axis_sizes.values())

    def size(self, axes: str | Sequence[str]) -> int:
        if isinstance(axes, str):
            axes = (axes,)
        return math.prod(self.axis_sizes[a] for a in axes)

    def link(self, axis: str) -> Link:
        return self.axis_links[axis]

    def is_cross_pod(self, axis: str) -> bool:
        return axis == "pod"

    def with_axis_sizes(self, sizes: Mapping[str, int]) -> "Topology":
        """The same physical network with some axes resized — the elastic
        shrink/grow variant (device loss changes axis extents, not link
        classes).  Unknown axis names are rejected: a new axis would need
        a link model."""
        unknown = set(sizes) - set(self.axis_sizes)
        if unknown:
            raise KeyError(f"unknown axes {sorted(unknown)}; "
                           f"have {sorted(self.axis_sizes)}")
        merged = dict(self.axis_sizes)
        merged.update(sizes)
        return Topology(axis_sizes=merged, axis_links=dict(self.axis_links))

    def fingerprint(self) -> tuple:
        """Hashable identity of the modeled network: the protocol-plan
        cache key component — equal fingerprints must cost identically."""
        return tuple(sorted(
            (name, size, self.axis_links[name])
            for name, size in self.axis_sizes.items()))

    def describe(self) -> str:
        parts = []
        for name, n in self.axis_sizes.items():
            link = self.axis_links[name]
            kind = "DCN" if self.is_cross_pod(name) else "ICI"
            parts.append(
                f"{name}={n} [{kind} {link.bandwidth / 1e9:.1f} GB/s, "
                f"alpha={link.alpha * 1e6:.1f}us, "
                f"{'torus' if link.wraparound else 'line'}]"
            )
        return " x ".join(parts)


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Per-chip peaks and the link classes a mesh of these chips rides."""

    peak_flops_bf16: float   # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    ici: Link                # neighbour link along an in-pod mesh axis
    dcn: Link                # per-host link across pods
    source: str


#: ``device_kind`` as JAX reports it for a TPU v5e chip.
V5E = "TPU v5 lite"

_V5E_SPEC = DeviceSpec(
    peak_flops_bf16=197e12, hbm_bw=819e9,
    # 1,600 Gbit/s of ICI per chip over 4 links = 50 GB/s per link
    ici=Link(bandwidth=50e9, alpha=1e-6, wraparound=True),
    dcn=Link(bandwidth=6.25e9, alpha=10e-6, wraparound=False),
    source="Google Cloud TPU v5e documentation (peaks, HBM, ICI "
           "bandwidth); ICI/DCN latencies and DCN bandwidth are assumed, "
           "not measured")

DEVICES: Mapping[str, DeviceSpec] = {
    V5E: _V5E_SPEC,
    # The CPU test host's virtual devices have no links.  They plan with
    # the v5e's constants so tests exercise the protocol choices the chip
    # makes; nothing timed on the CPU is a link measurement.
    "cpu": dataclasses.replace(_V5E_SPEC,
                               source="test host: the TPU v5e entry"),
}


def device_spec(device_kind: str) -> DeviceSpec:
    try:
        return DEVICES[device_kind]
    except KeyError:
        raise KeyError(f"no link constants for device kind "
                       f"{device_kind!r}; known: {sorted(DEVICES)}") from None


def topology_from_mesh_shape(
    axis_names: Sequence[str], axis_sizes: Sequence[int],
    device_kind: str = V5E,
) -> Topology:
    """Build the physical model for a mesh of ``device_kind`` chips
    (default: the v5e, the planning target of device-less meshes).

    Any axis named ``pod`` is DCN; everything else is ICI torus.
    """
    spec = device_spec(device_kind)
    sizes = dict(zip(axis_names, axis_sizes))
    links = {name: spec.dcn if name == "pod" else spec.ici
             for name in axis_names}
    return Topology(axis_sizes=sizes, axis_links=links)


def topology_from_mesh(mesh) -> Topology:
    """The model for a concrete mesh takes its devices' kind; an abstract
    mesh with no device kind plans for the v5e."""
    sizes = dict(mesh.shape)
    if isinstance(mesh, Mesh):
        kind = mesh.devices.flat[0].device_kind
    else:
        device = mesh.abstract_device
        kind = device.device_kind if device is not None else V5E
    return topology_from_mesh_shape(tuple(sizes), tuple(sizes.values()),
                                    device_kind=kind)
