"""The CollectiveEngine: a dynamically composed, tiered, per-function-
protocol communication library (paper §2+§3+§4 as one object).

Construction mirrors the paper's pipeline exactly:

  1. scan the application          -> ``trace.scan_step``       (§2.2)
  2. compose the thin library      -> ``compose.compose``        (§2)
  3. assign per-function tiers     -> ``layers.assign_tiers``    (§3)
  4. plan per-function protocols   -> ``plan.CommPlan``          (§4)

Step 4 is *planned once*: the engine precomputes a (function, axis,
size-bucket) protocol table from the cost model and pre-binds each
function's tier wrapper at construction, so a collective call is a dict
lookup plus the schedule itself — no per-call cost-model sort, no
per-call closure building (``EngineConfig(plan=False)`` restores the
per-call baseline for benchmarking).

``mode="monolithic"`` is the conventional baseline: every function present
(no composition), every function at the conventional tier, every call
lowered through the one generic XLA path — the "TCP/IP stack" of Fig 2.

All collective methods must be called inside a ``substrate.shard_map``
region whose manual axes include the named axis.  Protocol schedules compile to
explicit ``ppermute`` chains — the TPU analogue of a NIC-offloaded
MPI-protocol (no host on the critical path).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compose as compose_mod
from repro.core import compression, costmodel, layers, registry, trace
from repro.core import plan as plan_mod
from repro.core.compose import ComposedLibrary, NotComposedError
from repro.core.protocols import bruck, recursive, ring, tree, twophase, xla
from repro.core.protocols import common as c
from repro.core.topology import Topology, topology_from_mesh

#: stats key the gradient-sync paths record wire-payload bytes under.
SYNC_STATS_KEY = "sync_gradients"


def _nbytes_of(x) -> int:
    return int(x.size) * jnp.dtype(x.dtype).itemsize


def _as_axes(axis_name) -> Tuple[str, ...]:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


@dataclasses.dataclass
class EngineConfig:
    mode: str = "composed"               # "composed" | "monolithic"
    tier_policy: layers.TierPolicy = dataclasses.field(
        default_factory=layers.TierPolicy)
    sanitize_checked: bool = False       # L2+: runtime finite-guard op
    use_quantize_kernel: bool = False    # Pallas path for compression
    use_local_reduce_kernel: bool = False  # Pallas path for RS combine
    force_protocol: Mapping[str, str] = dataclasses.field(default_factory=dict)
    plan: bool = True                    # False: per-call selection baseline

    def __post_init__(self):
        if self.mode not in ("composed", "monolithic"):
            raise ValueError(f"unknown engine mode: {self.mode!r}")


@dataclasses.dataclass
class InFlight:
    """A started-but-unfinished collective (MPIX_Start's return value).

    ``finish`` is the remaining pipeline stage(s) as a closure over the
    in-flight arrays; ``scale`` is the mean factor the wait arm applies
    after the last stage (finalization belongs to wait, never start).
    This is a plain Python object holding tracers, NOT a pytree: it must
    be consumed exactly once, inside the same trace that produced it.
    """

    fn: str
    axes: Tuple[str, ...]
    finish: Callable[[], jax.Array]
    protocol: str = costmodel.XLA_DEFAULT
    start_bytes: int = 0        # wire bytes the start phase moved
    wait_bytes: int = 0         # wire bytes the wait phase will move
    scale: Optional[float] = None
    waited: bool = False
    #: steppable wait-phase stage machine (a protocol *Run object) when
    #: the protocol supports per-stage progress; None = wait-only seam.
    stepper: Any = None


@dataclasses.dataclass
class SyncInFlight:
    """An in-flight gradient-sync collective: one bucket (or leaf) whose
    start phase has been issued.  ``repro.comm``'s ``sync_gradient_wait``
    consumes it — running the remaining stages, the cross-axis reductions
    of the compressed path, the mean scale, and (compressed only) the
    error-feedback residual update."""

    inner: Any                  # InFlight | compression.CompressedInFlight
    compress: bool
    axes: Tuple[str, ...]
    scale: Optional[float]
    waited: bool = False


class CollectiveEngine:
    """One application ↔ one engine (paper §2.1)."""

    def __init__(
        self,
        topology: Topology,
        library: Optional[ComposedLibrary] = None,
        frequencies: Optional[Mapping[str, float]] = None,
        config: Optional[EngineConfig] = None,
    ) -> None:
        self.topology = topology
        self.config = config or EngineConfig()
        self.stats = layers.CommStats()
        self._initialized = False
        self._finalized = False
        self.last_init_rebuilt = False
        self._invoked = set()

        if self.config.mode == "monolithic":
            # Conventional library: everything present, uniform depth.
            self.library = compose_mod.compose(registry.ALL_FUNCTIONS)
            self.frequencies = dict(registry.DEFAULT_FREQUENCIES)
            self.tiers = layers.conventional_tiers(registry.ALL_FUNCTIONS)
        else:
            if library is None:
                raise ValueError("composed engine needs a ComposedLibrary "
                                 "(use CollectiveEngine.from_application)")
            self.library = library
            self.frequencies = dict(frequencies or registry.DEFAULT_FREQUENCIES)
            self.tiers = layers.assign_tiers(
                {fn: self.frequencies.get(
                    fn, registry.DEFAULT_FREQUENCIES.get(fn, 1.0))
                 for fn in library.provided},
                self.config.tier_policy,
            )
        self._build_plan()

    # ------------------------------------------------------------------
    # Construction from an application (the paper's §2.2 flow)
    #
    # The classmethod constructors are deprecated caller-facing surface:
    # the Sessions-style facade (``repro.comm``) owns engine construction
    # now — ``Session(...)``, ``Session.from_application(...)``, and
    # ``Session(mode="monolithic")`` replace them.  They keep working
    # (same behaviour) so out-of-tree callers migrate at leisure.
    # ------------------------------------------------------------------

    @staticmethod
    def _deprecated(old: str, new: str) -> None:
        warnings.warn(
            f"CollectiveEngine.{old} is deprecated; construct communicators "
            f"through the repro.comm facade instead ({new})",
            DeprecationWarning, stacklevel=3)

    @classmethod
    def from_application(
        cls,
        step_fn: Callable,
        *abstract_args,
        topology: Topology,
        config: Optional[EngineConfig] = None,
        extra_functions: Sequence[str] = (),
        steps_hint: float = 1e4,
        **abstract_kwargs,
    ) -> "CollectiveEngine":
        """Deprecated: use ``repro.comm.Session.from_application``.

        Scan ``step_fn`` (traced with abstract inputs), compose the thin
        library covering exactly what it invokes, and build the engine.

        ``steps_hint``: traced counts are per *step*; the paper's layer
        placement (§3) weighs per-application frequency, so counts are
        scaled by the expected number of step executions."""
        cls._deprecated("from_application", "repro.comm.Session."
                        "from_application(step_fn, ..., mesh=...)")
        report = trace.scan_step(step_fn, *abstract_args, **abstract_kwargs)
        library = compose_mod.compose_from_trace(report, extra=extra_functions)
        freqs = dict(registry.DEFAULT_FREQUENCIES)
        freqs.update({fn: c * steps_hint
                      for fn, c in report.frequencies().items()})
        return cls(topology, library=library, frequencies=freqs, config=config)

    @classmethod
    def monolithic(cls, topology: Topology,
                   config: Optional[EngineConfig] = None) -> "CollectiveEngine":
        """Deprecated: use ``repro.comm.Session(..., mode="monolithic")``."""
        cls._deprecated("monolithic",
                        'repro.comm.Session(..., mode="monolithic")')
        cfg = config or EngineConfig()
        cfg = dataclasses.replace(cfg, mode="monolithic")
        return cls(topology, config=cfg)

    @classmethod
    def for_mesh(cls, mesh, **kwargs) -> "CollectiveEngine":
        """Deprecated: use ``repro.comm.Session(mesh=...)``."""
        cls._deprecated("for_mesh", "repro.comm.Session(mesh=...)")
        return cls(topology_from_mesh(mesh), **kwargs)

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def composed(self) -> bool:
        return self.config.mode == "composed"

    def tier(self, fn: str) -> int:
        return self.tiers.get(fn, layers.CONVENTIONAL_TIER)

    def average_layer_number(self) -> float:
        freqs = {fn: self.frequencies.get(
            fn, registry.DEFAULT_FREQUENCIES.get(fn, 1.0))
            for fn in self.tiers}
        return layers.average_layer_number(self.tiers, freqs)

    def protocol_for(self, fn: str, nbytes: float, axis: str) -> str:
        return self.plan.protocol_for(fn, nbytes, axis)

    def describe(self) -> str:
        rows = [f"CollectiveEngine(mode={self.config.mode}, "
                f"avg_layer={self.average_layer_number():.3f})",
                f"  library: {self.library.describe()}",
                f"  plan: {self.plan.describe()}"]
        for fn in sorted(self.library.provided):
            rows.append(f"  {fn:<22s} tier={layers.TIER_NAMES[self.tier(fn)]}")
        return "\n".join(rows)

    # ------------------------------------------------------------------
    # Planning: protocol table + pre-bound tier wrappers ("plan once")
    # ------------------------------------------------------------------

    def _build_plan(self) -> None:
        """(Re)build the protocol plan and the flattened dispatch table.

        Called at construction and from ``init`` (topology change =>
        rebuild).  Pre-binding here means the hot path never re-enters
        ``layers.wrap_tier``; the wrappers also capture the *current*
        stats object, so a stats reset requires a rebuild too."""
        self.plan = plan_mod.CommPlan(
            self.topology, composed=self.composed,
            force=self.config.force_protocol, enabled=self.config.plan,
            warm_functions=tuple(self.library.provided))
        self._rebind_dispatch()

    def _rebind_dispatch(self) -> None:
        self._dispatch: Dict[str, Callable] = {}
        if self.config.plan:
            for fn in self.library.provided:
                impl = self._impl_for(fn)
                if impl is not None:
                    self._dispatch[fn] = self._bind(fn, impl)

    def _bind(self, fn: str, impl: Callable) -> Callable:
        return layers.wrap_tier(fn, self.tier(fn), impl, self.stats,
                                sanitize=self.config.sanitize_checked)

    def dispatcher(self, fn: str) -> Callable:
        """The pre-bound tier-wrapped schedule for ``fn`` — a single dict
        lookup on planned engines, a per-call rebuild on plan=False."""
        d = self._dispatch.get(fn)
        if d is None:
            d = self._bind(fn, self._impl_for(fn))
            if self.config.plan:
                self._dispatch[fn] = d
        return d

    def _impl_for(self, fn: str) -> Optional[Callable]:
        """The protocol-level implementation (pre-tier-wrap) for ``fn``.
        None for functions with no array schedule (init/finalize/...)."""
        mono = not self.composed
        table = {
            registry.ALL_REDUCE:
                self._allreduce_mono if mono else self._allreduce_composed,
            registry.REDUCE_SCATTER:
                self._reduce_scatter_mono if mono
                else self._reduce_scatter_composed,
            registry.ALL_GATHER:
                self._all_gather_mono if mono else self._all_gather_composed,
            registry.ALL_TO_ALL:
                self._all_to_all_mono if mono else self._all_to_all_composed,
            registry.BROADCAST:
                self._broadcast_mono if mono else self._broadcast_composed,
            registry.PERMUTE: self._permute_impl,
            registry.SEND_RECV: self._send_recv_impl,
            registry.BARRIER: self._barrier_impl,
            registry.COMPRESSED_ALL_REDUCE: self._compressed_impl,
        }
        return table.get(fn)

    # ------------------------------------------------------------------
    # Internal plumbing
    # ------------------------------------------------------------------

    def _check(self, fn: str) -> None:
        self._invoked.add(fn)
        self.library.require(fn)

    @property
    def invoked_functions(self) -> frozenset:
        """Engine-level functions the application has invoked through this
        engine — the §2.2 scan at the API layer.  Protocol lowering turns
        e.g. all_reduce into ppermute chains, so the jaxpr scanner alone
        cannot attribute them; a probe engine traced through the step
        records them here."""
        return frozenset(self._invoked)

    def _axis_size(self, axis: str) -> int:
        if axis in self.topology.axis_sizes:
            return self.topology.axis_sizes[axis]
        return c.axis_size(axis)

    def mean_scale(self, axis_name) -> float:
        """1 / prod(axis sizes): the one authority every mean-reduction
        path divides through (topology first, live axis as fallback —
        the same resolution order protocol dispatch uses)."""
        scale = 1.0
        for ax in _as_axes(axis_name):
            scale /= self._axis_size(ax)
        return scale

    @staticmethod
    def _chunked(x: jax.Array, p: int) -> Tuple[jax.Array, int, tuple]:
        flat, n = c.pad_flat(x, p)
        return c.chunk_view(flat, p), n, x.shape

    # ------------------------------------------------------------------
    # The function set (paper's "MPI functions")
    # ------------------------------------------------------------------

    # ---- all_reduce ---------------------------------------------------

    def all_reduce(self, x: jax.Array, axis_name) -> jax.Array:
        fn = registry.ALL_REDUCE
        self._check(fn)
        axes = _as_axes(axis_name)
        # single axis stays a bare string (stable 'fn@axis' stats labels)
        return self.dispatcher(fn)(x, axes if len(axes) > 1 else axes[0])

    def _allreduce_mono(self, x: jax.Array, axes) -> jax.Array:
        out = x
        for ax in _as_axes(axes):
            out = xla.all_reduce(out, ax)
        return out

    def _allreduce_composed(self, x: jax.Array, axes) -> jax.Array:
        axes = _as_axes(axes)
        if len(axes) > 1:
            return self._allreduce_multiaxis(x, axes)
        return self._allreduce_1d(x, axes[0])

    def _allreduce_1d(self, x: jax.Array, axis: str,
                      proto: Optional[str] = None) -> jax.Array:
        # blocking = start + finish of the SAME stage split, so the
        # overlapped path is bit-identical by construction
        return self._allreduce_1d_start(x, axis, proto=proto).finish()

    def _allreduce_1d_start(self, x: jax.Array, axis: str,
                            proto: Optional[str] = None) -> InFlight:
        """Launch the first pipeline stage of a 1-axis all-reduce; the
        returned token's ``finish`` runs the remaining stage(s)."""
        fn = registry.ALL_REDUCE
        p = self._axis_size(axis)
        if p == 1:
            return InFlight(fn, (axis,), lambda: x, protocol="local")
        if proto is None:
            proto = self.protocol_for(fn, _nbytes_of(x), axis)
        sb, wb = plan_mod.phase_wire_bytes(proto, p, _nbytes_of(x))
        if proto == costmodel.XLA_DEFAULT:
            y = xla.all_reduce(x, axis)
            return InFlight(fn, (axis,), lambda: y, proto, sb, wb)
        if proto == costmodel.RECURSIVE_DOUBLING:
            y = recursive.recursive_doubling_all_reduce(x, axis)
            return InFlight(fn, (axis,), lambda: y, proto, sb, wb)
        x2d, n, shape = self._chunked(x, p)
        uk = self.config.use_local_reduce_kernel
        # the wait phase is held as a steppable Run object so progress()
        # can retire individual AG stages; result() drains the rest, and
        # a never-progressed token runs the exact blocking stage order
        if proto == costmodel.RING:
            shard = ring.ring_all_reduce_start(x2d, axis, uk)
            run = ring.RingAllGatherRun(shard, axis)
        elif proto == costmodel.BIDIR_RING:
            shard = ring.bidir_ring_all_reduce_start(x2d, axis, uk)
            run = ring.BidirRingAllGatherRun(shard, axis)
        elif proto == costmodel.RECURSIVE_HALVING:
            shard = recursive.halving_reduce_scatter_flat(x2d, axis)
            run = recursive.DoublingAllGatherRun(shard, axis)
        else:
            raise ValueError(f"no all_reduce impl for protocol {proto!r}")
        fin = lambda: c.unpad(run.result().reshape(-1), n, shape)
        return InFlight(fn, (axis,), fin, proto, sb, wb, stepper=run)

    def _allreduce_multiaxis(self, x: jax.Array, axes: Tuple[str, ...]
                             ) -> jax.Array:
        return self._allreduce_multiaxis_start(x, axes).finish()

    def _allreduce_multiaxis_start(self, x: jax.Array,
                                   axes: Tuple[str, ...]) -> InFlight:
        fn = registry.ALL_REDUCE
        nb = _nbytes_of(x)
        if "pod" in axes:
            intra = tuple(a for a in axes if a != "pod")
            if intra:
                flat, sizes = twophase.hierarchical_start(x, intra)
                fin = lambda: twophase.hierarchical_finish(
                    flat, sizes, intra, "pod", x.shape)
                # phase shares follow the full intra-pod extent (the RS
                # spans every intra axis before the pod hop)
                p_intra = 1
                for ax in intra:
                    p_intra *= self._axis_size(ax)
                sb, wb = plan_mod.phase_wire_bytes(
                    costmodel.HIERARCHICAL, p_intra, nb)
                return InFlight(fn, axes, fin, costmodel.HIERARCHICAL,
                                sb, wb)
            return self._allreduce_1d_start(x, "pod")
        if len(axes) == 2:
            p0 = self._axis_size(axes[0])
            x2d, n, shape = self._chunked(x, p0)
            shard = twophase.two_phase_start(x2d, axes[0])
            fin = lambda: c.unpad(
                twophase.two_phase_finish(shard, axes[0], axes[1],
                                          x2d.shape[0], c.chunk_size(x2d)),
                n, shape)
            sb, wb = plan_mod.phase_wire_bytes(costmodel.TWO_PHASE_2D, p0, nb)
            return InFlight(fn, axes, fin, costmodel.TWO_PHASE_2D, sb, wb)
        return self._allreduce_seq_start(
            x, tuple((ax, None) for ax in axes))

    def _allreduce_seq_start(self, x: jax.Array,
                             protos: Tuple[Tuple[str, Optional[str]], ...]
                             ) -> InFlight:
        """Sequential per-axis chain: start the first axis's protocol; the
        wait arm finishes it and runs the remaining axes blocking (they
        depend on the first axis's result, so only the first stage can
        overlap)."""
        (ax0, p0), rest = protos[0], protos[1:]
        tok0 = self._allreduce_1d_start(x, ax0, proto=p0)

        def fin():
            y = tok0.finish()
            for ax, pr in rest:
                y = self._allreduce_1d(y, ax, proto=pr)
            return y

        # unplanned later axes resolve to what the cost model will pick
        # per call, so the phase accounting matches the real schedule
        wait_extra = sum(
            sum(plan_mod.phase_wire_bytes(
                pr or self.protocol_for(registry.ALL_REDUCE,
                                        _nbytes_of(x), ax),
                self._axis_size(ax), _nbytes_of(x)))
            for ax, pr in rest)
        return InFlight(registry.ALL_REDUCE, tuple(a for a, _ in protos),
                        fin, tok0.protocol, tok0.start_bytes,
                        tok0.wait_bytes + wait_extra)

    # ---- reduce_scatter / all_gather ---------------------------------

    def reduce_scatter(self, x: jax.Array, axis_name: str, dim: int = 0
                       ) -> jax.Array:
        """Tiled semantics: output = input with ``dim`` shrunk by p."""
        fn = registry.REDUCE_SCATTER
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, dim=dim)

    def _reduce_scatter_mono(self, x, axis: str, dim: int = 0):
        return xla.reduce_scatter(x, axis, dim)

    def _reduce_scatter_composed(self, x, axis: str, dim: int = 0,
                                 proto: Optional[str] = None):
        p = self._axis_size(axis)
        if p == 1:
            return x
        if x.shape[dim] % p:
            return xla.reduce_scatter(x, axis, dim)  # generic fallback
        if proto is None:
            proto = self.protocol_for(registry.REDUCE_SCATTER,
                                      _nbytes_of(x), axis)
        xm = jnp.moveaxis(x, dim, 0)
        x2d = xm.reshape(p, -1)
        uk = self.config.use_local_reduce_kernel
        if proto == costmodel.RECURSIVE_HALVING:
            shard = recursive.halving_reduce_scatter_flat(x2d, axis)
        elif proto == costmodel.BIDIR_RING:
            shard = ring.bidir_ring_reduce_scatter_flat(x2d, axis, uk)
        else:
            shard = ring.ring_reduce_scatter_flat(x2d, axis, uk)
        out = shard.reshape((xm.shape[0] // p,) + xm.shape[1:])
        return jnp.moveaxis(out, 0, dim)

    def all_gather(self, x: jax.Array, axis_name: str, dim: int = 0
                   ) -> jax.Array:
        """Tiled semantics: output = input with ``dim`` grown by p."""
        fn = registry.ALL_GATHER
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, dim=dim)

    def _all_gather_mono(self, x, axis: str, dim: int = 0):
        return xla.all_gather(x, axis, dim)

    def _all_gather_composed(self, x, axis: str, dim: int = 0,
                             proto: Optional[str] = None):
        p = self._axis_size(axis)
        if p == 1:
            return x
        if proto is None:
            proto = self.protocol_for(registry.ALL_GATHER,
                                      _nbytes_of(x) * p, axis)
        xm = jnp.moveaxis(x, dim, 0)
        shard = xm.reshape(-1)
        if proto == costmodel.BRUCK:
            flat = recursive.doubling_all_gather_flat(shard, axis)
            buf = flat.reshape((p,) + shard.shape)
        elif proto == costmodel.BIDIR_RING:
            buf = ring.bidir_ring_all_gather_flat(shard, axis)
        else:
            buf = ring.ring_all_gather_flat(shard, axis)
        out = buf.reshape((p * xm.shape[0],) + xm.shape[1:])
        return jnp.moveaxis(out, 0, dim)

    # ---- all_to_all ----------------------------------------------------

    def all_to_all(self, x: jax.Array, axis_name: str,
                   split_dim: int = 0, concat_dim: int = 0) -> jax.Array:
        """Tiled semantics of ``lax.all_to_all``."""
        fn = registry.ALL_TO_ALL
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, split_dim=split_dim,
                                   concat_dim=concat_dim)

    def _all_to_all_mono(self, x, axis: str, split_dim: int = 0,
                         concat_dim: int = 0):
        return xla.all_to_all(x, axis, split_dim, concat_dim)

    def _all_to_all_composed(self, x, axis: str, split_dim: int = 0,
                             concat_dim: int = 0,
                             proto: Optional[str] = None):
        p = self._axis_size(axis)
        if p == 1:
            return x
        if x.shape[split_dim] % p:
            return xla.all_to_all(x, axis, split_dim, concat_dim)
        if proto is None:
            proto = self.protocol_for(registry.ALL_TO_ALL, _nbytes_of(x), axis)
        xm = jnp.moveaxis(x, split_dim, 0)
        blocks = xm.reshape((p, xm.shape[0] // p) + xm.shape[1:])
        if proto == costmodel.BRUCK:
            out_blocks = bruck.bruck_all_to_all(blocks, axis)
        else:
            out_blocks = bruck.pairwise_all_to_all(blocks, axis)
        # out_blocks[j] = block received from device j; lax.all_to_all tiled
        # semantics concatenates received blocks (block-major) at concat_dim.
        ob = jnp.moveaxis(out_blocks, 1, split_dim + 1)  # restore split pos
        ob = jnp.moveaxis(ob, 0, concat_dim)             # p next to concat
        shape = list(ob.shape)
        shape[concat_dim:concat_dim + 2] = [shape[concat_dim]
                                            * shape[concat_dim + 1]]
        return ob.reshape(shape)

    # ---- broadcast / permute / send_recv -------------------------------

    def broadcast(self, x: jax.Array, axis_name: str, root: int = 0
                  ) -> jax.Array:
        fn = registry.BROADCAST
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, root=root)

    def _broadcast_mono(self, x, axis: str, root: int = 0):
        return xla.broadcast(x, axis, root)

    def _broadcast_composed(self, x, axis: str, root: int = 0,
                            proto: Optional[str] = None):
        return self._broadcast_start(x, axis, root=root, proto=proto).finish()

    def _broadcast_start(self, x, axis: str, root: int = 0,
                         proto: Optional[str] = None) -> InFlight:
        """Stage-split broadcast: the van de Geijn protocol starts with
        its binomial scatter and finishes with the ring all-gather; the
        binomial tree has no seam and runs entirely in start."""
        fn = registry.BROADCAST
        if proto is None:
            proto = self.protocol_for(fn, _nbytes_of(x), axis)
        p = self._axis_size(axis)
        if proto == costmodel.RING and c.is_pow2(p) and p > 1:
            sb, wb = plan_mod.phase_wire_bytes(proto, p, _nbytes_of(x))
            x2d, n, shape = self._chunked(x, p)
            chunk = tree.scatter_allgather_start(x2d, axis, root)
            fin = lambda: c.unpad(
                tree.scatter_allgather_finish(chunk, axis, root).reshape(-1),
                n, shape)
            return InFlight(fn, (axis,), fin, proto, sb, wb)
        y = tree.binomial_broadcast(x, axis, root)
        sb, _ = plan_mod.phase_wire_bytes(costmodel.BINOMIAL_TREE, p,
                                          _nbytes_of(x))
        return InFlight(fn, (axis,), lambda: y, costmodel.BINOMIAL_TREE, sb, 0)

    def permute(self, x: jax.Array, axis_name: str, shift: int = 1
                ) -> jax.Array:
        fn = registry.PERMUTE
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, shift=shift)

    def _permute_impl(self, x, axis: str, shift: int = 1):
        return xla.permute(x, axis, shift)

    def send_recv(self, x: jax.Array, axis_name: str,
                  pairs: Sequence[Tuple[int, int]]) -> jax.Array:
        """Explicit (src, dst) exchange — MPI_Send/MPI_Recv analogue."""
        fn = registry.SEND_RECV
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, pairs=tuple(pairs))

    def _send_recv_impl(self, x, axis: str, pairs=()):
        return lax.ppermute(x, axis, list(pairs))

    # ---- feature / sync / setup ----------------------------------------

    def compressed_all_reduce(self, x: jax.Array, axis_name: str,
                              state: Optional[compression.EFState] = None):
        fn = registry.COMPRESSED_ALL_REDUCE
        self._check(fn)
        return self.dispatcher(fn)(x, axis_name, state=state)

    def _compressed_impl(self, x, axis: str, state=None):
        return compression.compressed_all_reduce(
            x, axis, state, use_kernel=self.config.use_quantize_kernel)

    # ------------------------------------------------------------------
    # Nonblocking two-phase arms (MPIX_Start / MPIX_Wait analogue)
    #
    # ``*_start`` launches a collective's first pipeline stage(s) and
    # returns an in-flight token; ``*_wait`` runs the remaining stages and
    # finalizes (unpad, mean scale, EF-residual update).  The blocking
    # methods above are literally start∘wait of the same stage split, so
    # the two paths are bit-identical by construction.  Tokens are plain
    # Python objects over tracers: consume each exactly once, within the
    # trace that created it.
    # ------------------------------------------------------------------

    def all_reduce_start(self, x: jax.Array, axis_name, *,
                         mean: bool = False) -> InFlight:
        fn = registry.ALL_REDUCE
        self._check(fn)
        axes = _as_axes(axis_name)
        # the checked/full tier layers run input-side here and output-side
        # in the wait arm, so blocking (tier-wrapped dispatch) and
        # overlapped runs stay bit-identical AND count the same stats
        x = layers.tier_input(fn, self.tier(fn), x,
                              axes if len(axes) > 1 else axes[0],
                              self.stats,
                              sanitize=self.config.sanitize_checked)
        if not self.composed:
            # monolithic baseline has no stage seam: the generic XLA path
            # runs whole in start, so blocking and overlapped stay
            # bit-identical in that mode too
            y = self._allreduce_mono(x, axes)
            sb = sum(plan_mod.phase_wire_bytes(
                costmodel.XLA_DEFAULT, self._axis_size(ax),
                _nbytes_of(x))[0] for ax in axes)
            tok = InFlight(fn, axes, lambda: y,
                           costmodel.XLA_DEFAULT, sb, 0)
        elif len(axes) == 1:
            tok = self._allreduce_1d_start(x, axes[0])
        else:
            tok = self._allreduce_multiaxis_start(x, axes)
        if mean:
            tok.scale = self.mean_scale(axes)
        self.stats.record_phase(fn, "start", tok.start_bytes)
        return tok

    def all_reduce_wait(self, token: InFlight) -> jax.Array:
        return self._wait_inflight(token)

    def all_reduce_progress(self, token: InFlight, stages: int = 1) -> int:
        return self._progress_inflight(token, stages)

    def _progress_inflight(self, token: InFlight, stages: int = 1) -> int:
        """The per-stage progression hop (*MPI Progress For All*): retire
        up to ``stages`` wait-phase protocol stages of an in-flight
        collective without completing it.  Returns stages actually taken
        (0 for seamless protocols or a drained wait phase).

        Byte conservation: each hop moves ``wait_bytes * k / remaining``
        and decrements the token's wait budget, so start + progress +
        wait phase bytes always sum to the blocking path's wire bytes.
        """
        if token.waited:
            raise RuntimeError(
                f"cannot progress an already-waited {token.fn} token")
        run = token.stepper
        if run is None or run.remaining <= 0:
            return 0
        remaining_before = run.remaining
        k = run.step(stages)
        if k:
            moved = token.wait_bytes * k // remaining_before
            token.wait_bytes -= moved
            self.stats.record_phase(token.fn, "progress", moved)
        return k

    def _wait_inflight(self, token: InFlight) -> jax.Array:
        if token.waited:
            raise RuntimeError(
                f"in-flight {token.fn} token was already waited — each "
                f"start() produces exactly one wait()able reduction")
        token.waited = True
        self.stats.record_phase(token.fn, "wait", token.wait_bytes)
        y = token.finish()
        if token.scale is not None:
            y = y * jnp.asarray(token.scale, y.dtype)
        # L3 output fence (identity for values; ordering semantics only)
        return layers.tier_output(self.tier(token.fn), y)

    def compressed_all_reduce_start(self, x: jax.Array, axis_name: str,
                                    state: Optional[compression.EFState]
                                    = None):
        fn = registry.COMPRESSED_ALL_REDUCE
        self._check(fn)
        x = layers.tier_input(fn, self.tier(fn), x, axis_name, self.stats,
                              sanitize=self.config.sanitize_checked)
        tok = compression.compressed_all_reduce_start(
            x, axis_name, state,
            use_kernel=self.config.use_quantize_kernel)
        sb, _ = plan_mod.phase_wire_bytes(
            costmodel.RING, tok.p, _compressed_wire_bytes(x.size))
        self.stats.record_phase(fn, "start", sb)
        return tok

    def compressed_all_reduce_progress(self, token, stages: int = 1) -> int:
        """Per-stage progression of an in-flight compressed all-reduce
        (same byte-conservation contract as ``_progress_inflight``)."""
        fn = registry.COMPRESSED_ALL_REDUCE
        if token.p == 1:
            return 0
        if token.wait_bytes_left is None:
            _, wb = plan_mod.phase_wire_bytes(
                costmodel.RING, token.p,
                _compressed_wire_bytes(int(token.n)))
            token.wait_bytes_left = wb
        remaining_before = (token.ag_run.remaining
                            if token.ag_run is not None else token.p - 1)
        if remaining_before <= 0:
            return 0
        k = compression.compressed_all_reduce_progress(token, stages)
        if k:
            moved = token.wait_bytes_left * k // remaining_before
            token.wait_bytes_left -= moved
            self.stats.record_phase(fn, "progress", moved)
        return k

    def compressed_all_reduce_wait(self, token):
        fn = registry.COMPRESSED_ALL_REDUCE
        if token.wait_bytes_left is not None:
            wb = token.wait_bytes_left   # progress() already billed the rest
        else:
            _, wb = plan_mod.phase_wire_bytes(
                costmodel.RING, token.p,
                _compressed_wire_bytes(int(token.n)))
        self.stats.record_phase(fn, "wait", wb)
        return layers.tier_output(self.tier(fn),
                                  compression.compressed_all_reduce_wait(
                                      token))

    # -- two-phase gradient sync (what the overlapped trainer drives) ---

    def sync_gradient_start(self, g: jax.Array, axis_name, *,
                            mean: bool = True, compress: bool = False,
                            ef_residual: Optional[jax.Array] = None
                            ) -> SyncInFlight:
        """Issue the start phase of ONE gradient tensor's sync (a fused
        bucket or a leaf).  Records wire bytes under ``SYNC_STATS_KEY``
        identically to the blocking ``sync_gradients[_bucketed]`` paths,
        so overlapped and blocking runs report the same traffic."""
        axes = _as_axes(axis_name)
        scale = self.mean_scale(axes) if mean else None
        if compress:
            self.stats.record(SYNC_STATS_KEY,
                              _compressed_wire_bytes(g.size))
            state = (compression.EFState(residual=ef_residual)
                     if ef_residual is not None else None)
            inner = self.compressed_all_reduce_start(g, axes[0], state)
        else:
            self.stats.record(SYNC_STATS_KEY, _nbytes_of(g))
            inner = self.all_reduce_start(
                g, axes if len(axes) > 1 else axes[0])
        return SyncInFlight(inner=inner, compress=compress, axes=axes,
                            scale=scale)

    def sync_gradient_progress(self, token: SyncInFlight,
                               stages: int = 1) -> int:
        """Advance one in-flight gradient sync by up to ``stages``
        wait-phase protocol stages (ring hops / doubling rounds) without
        finalizing it — the schedule IR's ``progress`` op.  EF residuals
        and mean scaling remain untouched (they belong to wait)."""
        if token.waited:
            raise RuntimeError(
                "cannot progress an already-waited gradient sync")
        if token.compress:
            return self.compressed_all_reduce_progress(token.inner, stages)
        return self._progress_inflight(token.inner, stages)

    def sync_gradient_wait(self, token: SyncInFlight):
        """Finalize one in-flight gradient sync: remaining stages, the
        compressed path's cross-axis reductions, the mean scale, and the
        EF-residual update (residuals mutate here and ONLY here).
        Returns (synced, new_ef_residual | None)."""
        if token.waited:
            raise RuntimeError("in-flight gradient sync was already waited")
        token.waited = True
        new_residual = None
        if token.compress:
            y, st = self.compressed_all_reduce_wait(token.inner)
            for ax in token.axes[1:]:
                y = self.all_reduce(y, ax)
            if st is not None:
                new_residual = st.residual
        else:
            y = self._wait_inflight(token.inner)
        if token.scale is not None:
            y = y * jnp.asarray(token.scale, y.dtype)
        return y, new_residual

    # -- the ZeRO-1 seam: RS-only grad sync + updated-param all-gather --
    #
    # Every planned all-reduce protocol already decomposes into a
    # reduce-scatter arm and an all-gather arm; ZeRO-1 stops gradient
    # sync at that seam (each rank keeps its reduced chunk, runs the
    # elementwise optimizer update on it) and all-gathers the *updated
    # params* back instead.  Bit-identity with the unsharded path is by
    # construction: the RS half below IS the planned all-reduce's own
    # start phase — same protocol, same padding, same stage order.

    def zero_protocols(self, nbytes: int, axis: str) -> Tuple[str, str]:
        """(rs_protocol, ag_protocol) the ZeRO seam uses for an ``nbytes``
        payload on ``axis``: the PLANNED all-reduce protocol's own halves.
        Seamless protocols (xla, recursive doubling) have no RS/AG split —
        the RS arm then runs the whole planned all-reduce and slices, and
        the gather side defaults to the ring all-gather."""
        ar = self.protocol_for(registry.ALL_REDUCE, nbytes, axis)
        ag = {costmodel.RING: costmodel.RING,
              costmodel.BIDIR_RING: costmodel.BIDIR_RING,
              costmodel.RECURSIVE_HALVING: costmodel.RECURSIVE_DOUBLING,
              }.get(ar, costmodel.RING)
        return ar, ag

    def _zero_rs_start(self, x: jax.Array, axis: str) -> InFlight:
        """The RS half of the planned all-reduce for ``x`` on one axis;
        the token's finish yields this rank's reduced padded-flat chunk
        (rows ``axis_index`` of the blocking all-reduce's chunk view,
        bit-for-bit).  No stats here — public/persistent arms record."""
        fn = registry.REDUCE_SCATTER
        p = self._axis_size(axis)
        if p == 1:
            flat = x.reshape(-1)
            return InFlight(fn, (axis,), lambda: flat, protocol="local")
        proto = self.zero_protocols(_nbytes_of(x), axis)[0]
        sb, _ = plan_mod.phase_wire_bytes(proto, p, _nbytes_of(x), fn)
        x2d, _, _ = self._chunked(x, p)
        uk = self.config.use_local_reduce_kernel
        if proto == costmodel.RING:
            chunk = ring.ring_reduce_scatter_flat(x2d, axis, uk)
        elif proto == costmodel.BIDIR_RING:
            chunk = ring.bidir_ring_reduce_scatter_flat(x2d, axis, uk)
        elif proto == costmodel.RECURSIVE_HALVING:
            chunk = recursive.halving_reduce_scatter_flat(x2d, axis)
        else:
            # no seam: run the planned all-reduce whole and keep this
            # rank's rows — identical bits, billed at the full AR share.
            y = self._allreduce_1d(x, axis, proto=proto)
            y2d, _, _ = self._chunked(y, p)
            chunk = c.dyn_chunk(y2d, c.axis_index(axis))
        chunk = chunk.reshape(-1)
        return InFlight(fn, (axis,), lambda: chunk, proto, sb, 0)

    def _zero_ag_start(self, shard: jax.Array, axis: str) -> InFlight:
        """The AG half: replicate per-rank updated chunks back into the
        full padded-flat vector (pure data movement — any gather order is
        bit-identical).  ``finish`` yields the flat (p*chunk,) vector."""
        fn = registry.ALL_GATHER
        p = self._axis_size(axis)
        flat = shard.reshape(-1)
        if p == 1:
            return InFlight(fn, (axis,), lambda: flat, protocol="local")
        full = _nbytes_of(shard) * p
        proto = self.zero_protocols(full, axis)[1]
        sb, _ = plan_mod.phase_wire_bytes(proto, p, full, fn)
        if proto == costmodel.RECURSIVE_DOUBLING:
            buf = recursive.doubling_all_gather_flat(flat, axis)
        elif proto == costmodel.BIDIR_RING:
            buf = ring.bidir_ring_all_gather_flat(c.rows(flat), axis)
        else:
            buf = ring.ring_all_gather_flat(c.rows(flat), axis)
        return InFlight(fn, (axis,), lambda: buf.reshape(-1), proto, sb, 0)

    def zero_reduce_scatter_start(self, g: jax.Array, axis_name, *,
                                  mean: bool = True) -> InFlight:
        """ZeRO-1 gradient sync stopped at the RS/AG seam: only the
        reduce-scatter half of the PLANNED all-reduce runs; the wait arm
        yields this rank's reduced padded-flat chunk with the mean scale
        applied.  ``SYNC_STATS_KEY`` records the RS phase share alone —
        the wire-byte drop vs. a full all-reduce is the measured claim."""
        fn = registry.REDUCE_SCATTER
        self._check(fn)
        axes = _as_axes(axis_name)
        if len(axes) != 1:
            raise ValueError(f"zero_reduce_scatter runs over exactly one "
                             f"data axis, got {axes}")
        g = layers.tier_input(fn, self.tier(fn), g, axes[0], self.stats,
                              sanitize=self.config.sanitize_checked)
        tok = self._zero_rs_start(g, axes[0])
        if mean:
            tok.scale = self.mean_scale(axes)
        self.stats.record(SYNC_STATS_KEY, tok.start_bytes)
        self.stats.record_phase(fn, "start", tok.start_bytes)
        return tok

    def zero_reduce_scatter_wait(self, token: InFlight) -> jax.Array:
        return self._wait_inflight(token)

    def zero_all_gather_start(self, shard: jax.Array, axis_name) -> InFlight:
        """Start the updated-param all-gather of a ZeRO step.  The wait
        arm yields the full padded-flat vector; callers unpad/reshape."""
        fn = registry.ALL_GATHER
        self._check(fn)
        axes = _as_axes(axis_name)
        if len(axes) != 1:
            raise ValueError(f"zero_all_gather runs over exactly one "
                             f"data axis, got {axes}")
        shard = layers.tier_input(fn, self.tier(fn), shard, axes[0],
                                  self.stats,
                                  sanitize=self.config.sanitize_checked)
        tok = self._zero_ag_start(shard, axes[0])
        self.stats.record_phase(fn, "start", tok.start_bytes)
        return tok

    def zero_all_gather_wait(self, token: InFlight) -> jax.Array:
        return self._wait_inflight(token)

    def barrier(self, axis_name, token: jax.Array | None = None) -> jax.Array:
        fn = registry.BARRIER
        self._check(fn)
        t = token if token is not None else jnp.zeros((), jnp.float32)
        axes = _as_axes(axis_name)
        return self.dispatcher(fn)(t, axes if len(axes) > 1 else axes[0])

    def _barrier_impl(self, t, axes):
        for ax in _as_axes(axes):
            t = lax.psum(t, ax) * 0.0
        return lax.optimization_barrier(t)

    def checkpoint_fence(self, tree_: Any) -> Any:
        fn = registry.CHECKPOINT_FENCE
        self._check(fn)
        self.stats.event("checkpoint_fence")
        return jax.tree_util.tree_map(lax.optimization_barrier, tree_)

    def axis_index(self, axis_name: str):
        self._check(registry.AXIS_INDEX)
        return lax.axis_index(axis_name)

    def axis_size(self, axis_name: str) -> int:
        self._check(registry.AXIS_SIZE)
        return self._axis_size(axis_name)

    def init(self, mesh=None) -> "CollectiveEngine":
        """MPI_Init analogue: bind the runtime, reset stats, and re-plan
        (topology change => plan rebuild; same topology keeps the cached
        protocol table but re-binds wrappers to the fresh stats).  With no
        explicit mesh, binds to the substrate's active mesh (if any)."""
        self._check(registry.INIT)
        if mesh is None:
            from repro.runtime import substrate
            mesh = substrate.active_mesh()
        if mesh is not None:
            self.topology = topology_from_mesh(mesh)
        self.stats = layers.CommStats()
        # topology change => CommPlan clears + re-warms its table in place
        # (plan.stats.rebuilds records it); wrappers capture the stats
        # object, so they re-bind to the fresh one either way.
        self.last_init_rebuilt = self.plan.maybe_rebuild(self.topology)
        self._rebind_dispatch()
        self._initialized = True
        return self

    @property
    def plan_rebuilds(self) -> int:
        """Lifetime count of fingerprint-triggered CommPlan rebuilds —
        the elastic controller's invalidation contract is asserted
        against this."""
        return self.plan.stats.rebuilds

    def finalize(self) -> str:
        """MPI_Finalize analogue: flush stats, mark the engine dead."""
        self._check(registry.FINALIZE)
        self._finalized = True
        return self.stats.summary()

    # ------------------------------------------------------------------
    # Persistent bindings (MPI Advance's MPIX_*_init analogue)
    # ------------------------------------------------------------------

    def bind_persistent(self, fn: str, shape: Sequence[int], dtype,
                        axis_name, *, mean: bool = False,
                        sync_stats: bool = False,
                        **kw) -> "PersistentBinding":
        """Resolve everything one collective call site needs — protocol,
        tier wrapper, mean scale — ONCE, for a fixed (shape, dtype, axis)
        signature.  The returned binding's ``call`` is zero-lookup on the
        hot path: no cost-model run, no plan-table get, no wrapper
        construction per call (persistent collectives; the step past the
        plan-once dict lookup).

        Every binding also carries the two-phase ``start``/``wait`` arms
        (MPIX_Start/MPIX_Wait): ``start(x)`` launches the first pipeline
        stage(s) and returns an in-flight token, ``wait(token)`` runs the
        remaining stages and finalizes (unpad + mean scale live in wait).
        Blocking ``call`` composes the same stages, so both paths are
        bit-identical.

        ``sync_stats=True`` marks the binding as a gradient-sync call
        site: every call/start records its wire bytes under
        ``SYNC_STATS_KEY`` exactly like the planned ``sync_gradients*``
        paths do (without it, handle-covered syncs under-report).

        This is the private layer under ``repro.comm``'s persistent
        handles, which add lifecycle on top (revocation + rebind when the
        elastic controller re-meshes).  Binding requires every axis to be
        in the engine topology — the plan has nothing to resolve against
        otherwise.
        """
        axes = _as_axes(axis_name)
        self._check(fn)
        zero = bool(kw.pop("zero", False))
        if zero and fn not in (registry.REDUCE_SCATTER, registry.ALL_GATHER):
            raise ValueError(f"zero=True binds the ZeRO-1 seam arms; only "
                             f"reduce_scatter/all_gather support it, "
                             f"not {fn!r}")
        if sync_stats and fn != registry.ALL_REDUCE and \
                not (zero and fn == registry.REDUCE_SCATTER):
            raise ValueError(f"sync_stats=True marks a gradient-sync "
                             f"all_reduce handle, not {fn!r}")
        for ax in axes:
            if ax not in self.topology.axis_sizes:
                raise ValueError(
                    f"cannot bind persistent {fn!r}: axis {ax!r} is not in "
                    f"the engine topology "
                    f"({sorted(self.topology.axis_sizes)})")
        shape = tuple(int(s) for s in shape)
        dtype = jnp.dtype(dtype)
        nbytes = math.prod(shape) * dtype.itemsize if shape else dtype.itemsize
        sync_nbytes = nbytes            # what sync_stats records per call
        if mean and fn != registry.ALL_REDUCE and \
                not (zero and fn == registry.REDUCE_SCATTER):
            raise ValueError(f"mean=True is only supported for all_reduce, "
                             f"not {fn!r}")
        single_axis_only = (registry.REDUCE_SCATTER, registry.ALL_GATHER,
                            registry.ALL_TO_ALL, registry.BROADCAST,
                            registry.PERMUTE, registry.SEND_RECV)
        if fn in single_axis_only and len(axes) != 1:
            raise ValueError(f"{fn!r} binds over exactly one axis, "
                             f"got {axes}")
        mono = not self.composed
        xla_tag = costmodel.XLA_DEFAULT
        start_impl: Optional[Callable] = None   # non-trivial stage split

        if fn == registry.ALL_REDUCE:
            if mono:
                target = lambda x: self._allreduce_mono(x, axes)
                protocols = tuple((ax, xla_tag) for ax in axes)
            elif len(axes) == 1:
                ax0, proto = axes[0], self.protocol_for(fn, nbytes, axes[0])
                target = lambda x: self._allreduce_1d(x, ax0, proto=proto)
                start_impl = lambda x: self._allreduce_1d_start(
                    x, ax0, proto=proto)
                protocols = ((ax0, proto),)
            elif "pod" in axes or len(axes) == 2:
                # these multi-axis schedules are fixed by the axis set —
                # no per-call protocol lookup exists to eliminate
                name = costmodel.HIERARCHICAL if "pod" in axes \
                    else costmodel.TWO_PHASE_2D
                target = lambda x: self._allreduce_multiaxis(x, axes)
                start_impl = lambda x: self._allreduce_multiaxis_start(
                    x, axes)
                protocols = (("+".join(axes), name),)
            else:
                protocols = tuple((ax, self.protocol_for(fn, nbytes, ax))
                                  for ax in axes)

                def target(x, _protos=protocols):
                    for ax, pr in _protos:
                        x = self._allreduce_1d(x, ax, proto=pr)
                    return x

                start_impl = lambda x, _protos=protocols: \
                    self._allreduce_seq_start(x, _protos)
        elif fn == registry.REDUCE_SCATTER:
            ax0, dim = axes[0], int(kw.pop("dim", 0))
            if zero:
                # ZeRO seam: the RS half of the PLANNED all-reduce's own
                # stage split (bit-identity contract) — output is this
                # rank's padded-flat chunk, not the tiled RS, and
                # sync_stats bills the RS phase share alone.
                proto = self.zero_protocols(nbytes, ax0)[0]
                target = lambda x: self._zero_rs_start(x, ax0).finish()
                start_impl = lambda x: self._zero_rs_start(x, ax0)
                sync_nbytes = plan_mod.phase_wire_bytes(
                    proto, self._axis_size(ax0), nbytes, fn)[0]
            elif mono:
                proto = xla_tag
                target = lambda x: self._reduce_scatter_mono(x, ax0, dim=dim)
            else:
                proto = self.protocol_for(fn, nbytes, ax0)
                target = lambda x: self._reduce_scatter_composed(
                    x, ax0, dim=dim, proto=proto)
            protocols = ((ax0, proto),)
        elif fn == registry.ALL_GATHER:
            ax0, dim = axes[0], int(kw.pop("dim", 0))
            if zero:
                # ZeRO seam: gather per-rank chunks back to the padded
                # flat vector; the binding shape is the CHUNK, planning
                # happens at the gathered size like the tiled path.
                proto = self.zero_protocols(
                    nbytes * self._axis_size(ax0), ax0)[1]
                target = lambda x: self._zero_ag_start(x, ax0).finish()
                start_impl = lambda x: self._zero_ag_start(x, ax0)
            elif mono:
                proto = xla_tag
                target = lambda x: self._all_gather_mono(x, ax0, dim=dim)
            else:
                # all_gather plans at the gathered size (matches the
                # per-call convention in _all_gather_composed)
                proto = self.protocol_for(
                    fn, nbytes * self._axis_size(ax0), ax0)
                target = lambda x: self._all_gather_composed(
                    x, ax0, dim=dim, proto=proto)
            protocols = ((ax0, proto),)
        elif fn == registry.ALL_TO_ALL:
            ax0 = axes[0]
            sd = int(kw.pop("split_dim", 0))
            cd = int(kw.pop("concat_dim", 0))
            if mono:
                proto = xla_tag
                target = lambda x: self._all_to_all_mono(
                    x, ax0, split_dim=sd, concat_dim=cd)
            else:
                proto = self.protocol_for(fn, nbytes, ax0)
                target = lambda x: self._all_to_all_composed(
                    x, ax0, split_dim=sd, concat_dim=cd, proto=proto)
            protocols = ((ax0, proto),)
        elif fn == registry.BROADCAST:
            ax0, root = axes[0], int(kw.pop("root", 0))
            if mono:
                proto = xla_tag
                target = lambda x: self._broadcast_mono(x, ax0, root=root)
            else:
                proto = self.protocol_for(fn, nbytes, ax0)
                target = lambda x: self._broadcast_composed(
                    x, ax0, root=root, proto=proto)
                start_impl = lambda x: self._broadcast_start(
                    x, ax0, root=root, proto=proto)
            protocols = ((ax0, proto),)
        elif fn == registry.PERMUTE:
            ax0, shift = axes[0], int(kw.pop("shift", 1))
            target = lambda x: self._permute_impl(x, ax0, shift=shift)
            protocols = ((ax0, xla_tag),)
        elif fn == registry.SEND_RECV:
            ax0, pairs = axes[0], tuple(kw.pop("pairs"))
            target = lambda x: self._send_recv_impl(x, ax0, pairs=pairs)
            protocols = ((ax0, xla_tag),)
        elif fn == registry.BARRIER:
            target = lambda t: self._barrier_impl(t, axes)
            protocols = tuple((ax, xla_tag) for ax in axes)
        else:
            raise ValueError(f"{fn!r} does not support persistent binding")
        if kw:
            raise TypeError(f"unknown bind options for {fn!r}: {sorted(kw)}")

        base_target = target            # unscaled schedule (wait finalizes)
        scale = None
        if mean:
            scale = self.mean_scale(axes)   # static: axes are in topology

            def target(x, _inner=target, _s=scale):
                y = _inner(x)
                return y * jnp.asarray(_s, y.dtype)

        tier = self.tier(fn)
        if tier >= 2:
            # tier semantics preserved: checked/full layers still wrap the
            # schedule, but they are STACKED at bind time, not per call.
            axis_label = axes if len(axes) > 1 else axes[0]
            wrapped = layers.wrap_tier(
                fn, tier, lambda x, _axis, **_: target(x), self.stats,
                sanitize=self.config.sanitize_checked)
            call = lambda x, _w=wrapped, _a=axis_label: _w(x, _a)
        else:
            call = target
        if sync_stats:
            def call(x, _inner=call, _nb=sync_nbytes):
                self.stats.record(SYNC_STATS_KEY, _nb)
                return _inner(x)

        # -- two-phase arms: protocols with no seam run fully in start --
        if start_impl is None:
            def start_impl(x, _t=base_target):
                y = _t(x)
                return InFlight(fn, axes, lambda: y,
                                protocols[0][1], nbytes, 0)

        axis_label = axes if len(axes) > 1 else axes[0]

        def start(x, _impl=start_impl, _tier=tier, _nb=sync_nbytes, _s=scale,
                  _a=axis_label):
            if sync_stats:
                self.stats.record(SYNC_STATS_KEY, _nb)
            # same checked/full input stack the blocking call wraps with
            # (output fence runs in _wait_inflight) — values and stats
            # match the tier-wrapped dispatch exactly
            x = layers.tier_input(fn, _tier, x, _a, self.stats,
                                  sanitize=self.config.sanitize_checked)
            tok = _impl(x)
            if _s is not None:
                tok.scale = _s
            self.stats.record_phase(fn, "start", tok.start_bytes)
            return tok

        wait = self._wait_inflight

        return PersistentBinding(
            fn=fn, axes=axes, protocols=protocols, tier=tier,
            nbytes=nbytes, mean_scale=scale,
            fingerprint=self.topology.fingerprint(), call=call,
            start=start, wait=wait, progress=self._progress_inflight,
            sync_stats=sync_stats)

    # ------------------------------------------------------------------
    # Gradient synchronisation (the application-facing convenience API)
    # ------------------------------------------------------------------

    def sync_gradients(self, grads: Any, axis_name, *, mean: bool = True,
                       compress: bool = False, ef_state: Any = None):
        """Sum (or mean) a gradient pytree over the data-parallel axes,
        one collective per leaf.

        Call inside the shard_map training region.  With ``compress=True``
        uses the int8 error-feedback protocol and threads ``ef_state``
        (a pytree of EFState matching ``grads``; pass None to init).
        Returns (synced_grads, new_ef_state).
        """
        axes = _as_axes(axis_name)
        scale = self.mean_scale(axes) if mean else 1.0

        if not compress:
            def one(g):
                self.stats.record(SYNC_STATS_KEY, _nbytes_of(g))
                y = self.all_reduce(g, axes if len(axes) > 1 else axes[0])
                return y * jnp.asarray(scale, g.dtype) if mean else y
            return jax.tree_util.tree_map(one, grads), ef_state

        if ef_state is None:
            ef_state = jax.tree_util.tree_map(
                compression.EFState.zeros_like, grads)
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        states = treedef.flatten_up_to(ef_state)
        out_leaves, out_states = [], []
        for g, s in zip(leaves, states):
            # compressed protocol runs on the first axis; remaining axes
            # (e.g. cross-pod) use the hierarchical uncompressed path.
            self.stats.record(SYNC_STATS_KEY, _compressed_wire_bytes(g.size))
            y, s2 = self.compressed_all_reduce(g, axes[0], s)
            for ax in axes[1:]:
                y = self.all_reduce(y, ax)
            out_leaves.append(y * jnp.asarray(scale, g.dtype) if mean else y)
            out_states.append(s2)
        return (jax.tree_util.tree_unflatten(treedef, out_leaves),
                jax.tree_util.tree_unflatten(treedef, out_states))

    def sync_gradients_bucketed(
        self, grads: Any, axis_name, *, mean: bool = True,
        bucket_bytes: Optional[int] = plan_mod.DEFAULT_BUCKET_BYTES,
        compress: bool = False, ef_state: Any = None,
        dtype_aware: bool = True,
    ):
        """Fused, dtype-grouped, size-capped gradient sync.

        Leaves are grouped by dtype (bf16 stays bf16 on the wire), each
        group is split into buckets of at most ``bucket_bytes``, and each
        bucket is one independent collective with its own planned protocol
        — the alpha term amortizes across a bucket's leaves while XLA
        remains free to overlap the buckets.  ``dtype_aware=False``
        restores the legacy upcast-everything-to-f32 wire format (2x the
        bytes for bf16 grads; kept for comparison).

        ``ef_state`` (compress only) is a tuple of per-bucket flat f32
        residuals matching ``plan.plan_buckets`` on these leaves (pass
        None to init; persistent state layouts come from
        ``compression.bucket_ef_zeros``).  Returns
        (synced_grads, new_ef_state).
        """
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:
            return grads, ef_state
        axes = _as_axes(axis_name)
        buckets = plan_mod.plan_buckets(leaves, bucket_bytes,
                                        dtype_aware=dtype_aware)
        scale = self.mean_scale(axes) if mean else 1.0
        out: List[Optional[jax.Array]] = [None] * len(leaves)
        new_ef: List[Any] = []
        if compress:
            if ef_state is None:   # same auto-init contract as sync_gradients
                ef_state = compression.bucket_ef_zeros(buckets)
            elif (len(ef_state) != len(buckets)
                  or any(e.shape[-1] != b.size
                         for e, b in zip(ef_state, buckets))):
                raise ValueError(
                    f"ef_state layout {[e.shape[-1] for e in ef_state]} "
                    f"does not match the bucket plan "
                    f"{[b.size for b in buckets]} — was it built with the "
                    f"same bucket_bytes?")
        for bi, bucket in enumerate(buckets):
            flat = plan_mod.gather_bucket(leaves, bucket)
            if compress:
                self.stats.record(SYNC_STATS_KEY,
                                  _compressed_wire_bytes(bucket.size))
                st = compression.EFState(residual=ef_state[bi])
                y, st2 = self.compressed_all_reduce(flat, axes[0], st)
                for ax in axes[1:]:
                    y = self.all_reduce(y, ax)
                new_ef.append(st2.residual)
            else:
                self.stats.record(SYNC_STATS_KEY, bucket.nbytes)
                y = self.all_reduce(flat, axes if len(axes) > 1 else axes[0])
            if mean:
                y = y * jnp.asarray(scale, y.dtype)
            plan_mod.scatter_bucket(y, bucket, out)
        return (jax.tree_util.tree_unflatten(treedef, out),
                tuple(new_ef) if compress else ef_state)


@dataclasses.dataclass(frozen=True)
class PersistentBinding:
    """A fully-resolved collective call site: the output of
    ``CollectiveEngine.bind_persistent``.  ``call`` takes the array and
    nothing else — protocol, tier stack, and mean scale were baked in at
    bind time.  ``start``/``wait`` are the two-phase arms of the same
    schedule (``call`` ≡ ``wait(start(x))`` bit-identically); ``wait`` is
    where unpad + mean scale happen, so compute issued between the two
    overlaps the transfer.  ``fingerprint`` records the topology it was
    resolved against (the repro.comm handle lifecycle compares it to
    decide staleness)."""

    fn: str
    axes: Tuple[str, ...]
    protocols: Tuple[Tuple[str, str], ...]   # (axis-label, protocol)
    tier: int
    nbytes: int
    mean_scale: Optional[float]
    fingerprint: Any
    call: Callable
    start: Optional[Callable] = None      # x -> InFlight
    wait: Optional[Callable] = None       # InFlight -> array
    progress: Optional[Callable] = None   # (InFlight, stages) -> int
    sync_stats: bool = False              # records SYNC_STATS_KEY per call

    def describe(self) -> str:
        protos = ", ".join(f"{a}:{p}" for a, p in self.protocols)
        return (f"{self.fn}@{'+'.join(self.axes)} "
                f"[{protos}] tier=L{self.tier} {self.nbytes}B"
                + (f" mean={self.mean_scale:.4g}"
                   if self.mean_scale is not None else ""))


def _compressed_wire_bytes(size: int) -> int:
    """Payload bytes per hop of the int8 protocol: 1 byte/value + one f32
    scale per quantization block."""
    return int(size) + 4 * math.ceil(int(size) / compression.QBLOCK)
