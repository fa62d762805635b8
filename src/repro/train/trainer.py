"""Training step: microbatched grad accumulation + communicator-mediated
sync.

Three gradient-synchronisation modes (the paper's A/B/C):

  auto       — GSPMD end-to-end: batch sharded over ("pod","data"), XLA
               inserts every collective (the conventional generic stack).
  composed   — the loss/grad computation runs inside ``substrate.shard_map``
               manual over the data axes (model axes stay auto); gradients
               are synced through a ``repro.comm`` communicator whose
               per-function protocols are cost-model-selected
               (ring / two-phase / hierarchical).
  compressed — composed + int8 error-feedback compressed all-reduce
               (feature injected in the protocol, paper §4); the EF
               residual lives in the train state and persists across steps.

Distributed work routes through the Sessions-style facade: pass
``comm=`` (a ``repro.comm.Communicator``, usually ``session.world``) to
``make_train_step``; the step splits it into the data-axis
sub-communicator internally.  ``mesh=``+``engine=`` is the pre-PR-4
spelling, adopted into a session-less communicator for back-compat.

Gradient bucketing (``TrainCfg.bucket_grads``) is a beyond-paper
optimization: leaves are grouped by dtype (bf16 stays bf16 on the wire)
and fused into buckets of at most ``TrainCfg.bucket_bytes``, each an
independent cost-model-planned collective (``comm.
sync_gradients_bucketed``) so the alpha term amortizes and XLA overlaps
the buckets.

``TrainCfg.overlap`` (``--overlap`` on the launch CLI) switches the sync
to the nonblocking start/wait protocol (MPI Advance's MPIX_Start/Wait
analogue): the last microbatch is peeled out of the accumulation scan,
buckets (or leaves) are synced in reverse layout order through persistent
handles / two-phase communicator arms, and each unit's start phase is in
flight while its neighbour reduces and the peeled backward runs.  The
overlapped path performs the exact same arithmetic as the blocking one —
losses are bit-identical (tests/test_overlap.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import comm as comm_mod
from repro.core import plan as plan_mod
from repro.core import schedule as schedule_mod
from repro.core.compression import EFState, bucket_ef_zeros
from repro.parallel.sharding import shard_hint
from repro.runtime import substrate

Params = Any


@dataclasses.dataclass(frozen=True)
class TrainCfg:
    microbatches: int = 1
    sync_mode: str = "auto"              # auto | composed | compressed
    data_axes: Tuple[str, ...] = ("pod", "data")
    bucket_grads: bool = False           # beyond-paper: fused dtype buckets
    bucket_bytes: int = plan_mod.DEFAULT_BUCKET_BYTES  # size cap per bucket
    grad_dtype: Any = jnp.float32        # accumulation dtype
    overlap: bool = False                # nonblocking start/wait grad sync
    # peel the last microbatch out of the accumulation scan so bucket
    # starts overlap its backward.  None = auto: peel on accelerator
    # backends, skip on CPU hosts (no async dispatch to overlap with —
    # inlining a second copy of the model body only slows the step).
    overlap_peel: Any = None             # True | False | None (auto)
    # in-flight collectives the schedule IR's interleave pass keeps live.
    # 2 = the classic depth-2 software pipeline (no progress hops, the
    # bit-identity reference); >=3 adds per-stage progress() hops that
    # drain wait-phase stages of younger in-flight units early.
    overlap_depth: int = 2
    # ZeRO-1: gradients sync with only the reduce-scatter half of the
    # planned all-reduce, each data-parallel rank updates its shard of a
    # data-axis-sharded optimizer state (1/N memory), and updated params
    # all-gather back through the schedule IR.  Elementwise updates make
    # losses bit-identical to the unsharded composed path at clip_norm=0
    # on pow2 data-parallel sizes; elsewhere odd per-rank chunks drop the
    # bidir-ring RS to plain ring, whose summation order differs from the
    # all-reduce's in the last ulp.
    zero: bool = False

    def __post_init__(self):
        if not self.zero:
            return
        if self.sync_mode != "composed":
            raise ValueError(
                f"zero=True shards the optimizer update on the planned "
                f"all-reduce's RS/AG seam, which only the composed sync "
                f"path exposes (compression's EF residual would defeat "
                f"the sharding); got sync_mode={self.sync_mode!r}")
        if self.bucket_grads:
            raise ValueError(
                "zero=True runs one RS/AG pair per parameter leaf — "
                "fused buckets cross leaf boundaries and have no "
                "per-param shard to update; disable bucket_grads")


def _tree_size(tree) -> int:
    return sum(l.size for l in jax.tree_util.tree_leaves(tree))


def _grad_structs(params, cfg: TrainCfg):
    """Abstract leaves with the dtype gradients actually have in the step:
    microbatched accumulation casts to ``grad_dtype``; a single microbatch
    keeps each param's own dtype."""
    return [jax.ShapeDtypeStruct(
                l.shape, cfg.grad_dtype if cfg.microbatches > 1 else l.dtype)
            for l in jax.tree_util.tree_leaves(params)]


def grad_bucket_plan(params, cfg: TrainCfg) -> tuple:
    """The dtype-grouped bucket layout the step's fused sync will use —
    deterministic in (shapes, dtypes, order, bucket_bytes), so state
    creation and the traced step always agree."""
    return plan_mod.plan_buckets(_grad_structs(params, cfg), cfg.bucket_bytes)


# ---------------------------------------------------------------------------
# ZeRO-1 state layout (data-parallel-degree dependent, hence mesh=)
# ---------------------------------------------------------------------------

def zero_layout(cfg: TrainCfg, mesh) -> Tuple[str, int]:
    """(axis, size) of the single data axis ZeRO-1 shards over."""
    if mesh is None:
        raise ValueError("zero=True makes the optimizer-state layout "
                         "data-parallel-degree dependent; pass mesh=")
    sizes = dict(mesh.shape)
    axes = tuple(a for a in cfg.data_axes if a in sizes)
    if len(axes) != 1:
        raise ValueError(
            f"zero=True shards optimizer state over exactly ONE data "
            f"axis; cfg.data_axes={cfg.data_axes} resolves to {axes} on "
            f"mesh axes {tuple(sizes)}")
    return axes[0], int(sizes[axes[0]])


def _zero_pad_len(n: int, p: int) -> int:
    return ((int(n) + p - 1) // p) * p


def _zero_flat_params(params, p: int, abstract: bool):
    """The global ZeRO optimizer-state layout: each param leaf flattened
    and zero-padded to a multiple of the data-parallel size — i.e. the
    concatenation of the per-rank padded-flat chunks the RS protocols
    produce, with all padding as TRAILING zeros (which is what makes
    restore-time re-sharding onto a different survivor mesh a pure
    truncate/re-pad)."""
    def leaf(l):
        n = _zero_pad_len(l.size, p)
        if abstract:
            return jax.ShapeDtypeStruct((n,), l.dtype)
        return jnp.zeros((n,), l.dtype)
    return jax.tree_util.tree_map(leaf, params)


def _zero_chunk(x, p: int, idx):
    """This rank's padded-flat chunk of ``x`` — the exact pad-and-split
    layout the RS protocols use, so param chunks line up element-for-
    element with the reduced grad chunks."""
    flat = x.reshape(-1)
    rem = (-flat.shape[0]) % p
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), flat.dtype)])
    c = flat.shape[0] // p
    return jax.lax.dynamic_slice_in_dim(flat, idx * c, c)


def _zero_opt_specs(model, optimizer, cfg: TrainCfg, mesh):
    """Optimizer-state specs for the ZeRO layout: every flat leaf sharded
    over the data axis on dim 0 (the optimizer's own state_specs machinery
    runs over the flat layout, so AdamW and Adafactor both land here —
    1-D leaves take Adafactor's unfactored branch)."""
    ax, zp = zero_layout(cfg, mesh)
    params = model.abstract_params()
    pspecs = jax.tree_util.tree_map(lambda _: P(ax), params)
    return optimizer.state_specs(pspecs, _zero_flat_params(params, zp, True))


def make_train_state(model, optimizer, rng=None, abstract: bool = False,
                     cfg: TrainCfg = TrainCfg(), mesh=None):
    """{"params", "opt", "step"[, "ef"]} pytree.  With ``cfg.zero`` the
    optimizer state is laid out over FLAT padded leaves (see
    ``_zero_flat_params``) sharded on the data axis — ``mesh=`` is then
    required because the padding depends on the data-parallel size."""
    if abstract:
        params = model.abstract_params()
        opt_params = (_zero_flat_params(params, zero_layout(cfg, mesh)[1],
                                        True) if cfg.zero else params)
        opt = jax.eval_shape(optimizer.init, opt_params)
        step = jax.ShapeDtypeStruct((), jnp.int32)
    else:
        params = model.init(rng if rng is not None else jax.random.PRNGKey(0))
        opt_params = (_zero_flat_params(params, zero_layout(cfg, mesh)[1],
                                        False) if cfg.zero else params)
        opt = optimizer.init(opt_params)
        step = jnp.zeros((), jnp.int32)
    state = {"params": params, "opt": opt, "step": step}
    if cfg.sync_mode == "compressed":
        if cfg.bucket_grads:
            state["ef"] = bucket_ef_zeros(grad_bucket_plan(params, cfg),
                                          abstract=abstract)
        else:
            mk = (lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32)) \
                if abstract else (lambda p: jnp.zeros(p.shape, jnp.float32))
            state["ef"] = jax.tree_util.tree_map(mk, params)
    return state


def state_specs(model, optimizer, cfg: TrainCfg = TrainCfg(), mesh=None
                ) -> Dict[str, Any]:
    ps = model.param_specs()
    opt_specs = (_zero_opt_specs(model, optimizer, cfg, mesh) if cfg.zero
                 else optimizer.state_specs(ps, model.abstract_params()))
    specs = {"params": ps,
             "opt": opt_specs,
             "step": P()}
    if cfg.sync_mode == "compressed":
        if cfg.bucket_grads:
            specs["ef"] = tuple(
                P() for _ in grad_bucket_plan(model.abstract_params(), cfg))
        else:
            specs["ef"] = ps
    return specs


def batch_specs(batch: Dict[str, Any], data_axes=("pod", "data")
                ) -> Dict[str, P]:
    """Batch sharding: batch dim over the data axes.  M-RoPE ``positions``
    are (3, B, S) — batch at dim 1."""
    def one(path, _):
        name = path[-1].key if path else ""
        if name == "positions":
            return P(None, data_axes)
        return P(data_axes)
    return jax.tree_util.tree_map_with_path(one, batch)


# ---------------------------------------------------------------------------
# Grad accumulation over microbatches
# ---------------------------------------------------------------------------

def _split_micro(batch: Dict[str, jax.Array], n: int) -> Dict[str, jax.Array]:
    def one(path, x):
        name = path[-1].key if path else ""
        if name == "positions":              # (3, B, S) -> (n, 3, B/n, S)
            y = x.reshape((x.shape[0], n, x.shape[1] // n) + x.shape[2:])
            return jnp.moveaxis(y, 1, 0)
        return x.reshape((n, x.shape[0] // n) + x.shape[1:])
    return jax.tree_util.tree_map_with_path(one, batch)


def _accumulate_grads(loss_fn: Callable, params, batch, n_micro: int,
                      grad_dtype, peel_last: bool = False
                      ) -> Tuple[jax.Array, Params]:
    """Microbatched gradient accumulation.

    ``peel_last=True`` peels the final microbatch out of the scan body
    into straight-line code: a collective started right after the scan
    then overlaps the peeled backward pass (XLA cannot interleave ops
    into a scan, so without the peel every gradient sync waits for the
    whole accumulation loop).  The peeled iteration performs the exact
    same op sequence as the in-scan one, so losses stay bit-identical.
    """
    if n_micro == 1:
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, grads

    micro = _split_micro(batch, n_micro)

    # jit: the in-scan and the peeled iteration are then the same compiled
    # program — run eagerly, the peeled call would otherwise execute op by
    # op and round differently from the compiled scan body.
    @jax.jit
    def body(carry, mb):
        loss_acc, grads_acc = carry
        (loss, _), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        grads_acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(grad_dtype), grads_acc, grads)
        return (loss_acc + loss, grads_acc), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, grad_dtype), params)
    init = (jnp.zeros((), jnp.float32), zeros)
    if peel_last:
        head = jax.tree_util.tree_map(lambda x: x[:-1], micro)
        tail = jax.tree_util.tree_map(lambda x: x[-1], micro)
        carry, _ = jax.lax.scan(body, init, head)
        (loss_sum, grads_sum), _ = body(carry, tail)
    else:
        (loss_sum, grads_sum), _ = jax.lax.scan(body, init, micro)
    inv = 1.0 / n_micro
    grads = jax.tree_util.tree_map(lambda g: g * inv, grads_sum)
    return loss_sum * inv, grads


# ---------------------------------------------------------------------------
# Gradient sync flavours (both route mean-scaling through comm.mean_scale)
# ---------------------------------------------------------------------------

def _bucket_sync(dcomm: "comm_mod.Communicator", grads, compress, ef,
                 bucket_bytes):
    """Fused dtype-grouped buckets: amortizes the alpha term across each
    bucket's leaves while keeping bf16 gradients bf16 on the wire."""
    return dcomm.sync_gradients_bucketed(
        grads, mean=True, bucket_bytes=bucket_bytes,
        compress=compress, ef_state=ef)


def _leaf_sync(dcomm: "comm_mod.Communicator", axis_comms, grads, compress,
               ef_tree):
    if not compress:
        synced, _ = dcomm.sync_gradients(grads, mean=True)
        return synced, ef_tree
    ef_states = jax.tree_util.tree_map(lambda r: EFState(residual=r), ef_tree)
    synced, new_states = axis_comms[0].sync_gradients(
        grads, mean=True, compress=True, ef_state=ef_states)
    for acomm in axis_comms[1:]:
        synced = jax.tree_util.tree_map(
            lambda g, _c=acomm: _c.all_reduce(g, mean=True), synced)
    new_ef = jax.tree_util.tree_map(
        lambda s: s.residual, new_states,
        is_leaf=lambda x: isinstance(x, EFState))
    return synced, new_ef


# ---------------------------------------------------------------------------
# Overlapped (nonblocking start/wait) gradient sync — schedule IR
#
# Since PR 6 the overlapped sync is not hand-sequenced: the communicator
# builds the canonical *blocking* program (``comm.sync_schedule``), the
# planner's pass pipeline rewrites it (reverse layout order, depth-N
# interleaving, start hoisting across the peeled microbatch), and
# ``schedule.execute`` turns op order into start/progress/wait calls.
# ``overlap_depth=2`` reproduces the old hand-scheduled pipeline op for
# op — start unit i, then wait its already-started neighbour, no progress
# hops — so per-unit arithmetic (stage split, scale, EF update) is
# identical to the blocking paths and losses stay bit-identical.
# ``overlap_depth>=3`` keeps more transfers live and drains wait-phase
# protocol stages early via per-stage ``progress`` hops (*MPI Progress
# For All*); each unit's hop chain is unchanged, only its placement.
# ---------------------------------------------------------------------------


def _overlap_sync_schedule(ucomm, specs, compress, depth, compute=()):
    """Blocking sync program → canonical overlap pass pipeline."""
    base = ucomm.sync_schedule(specs, compress=compress, compute=compute)
    sched, timings = plan_mod.run_passes(
        base, plan_mod.canonical_overlap_passes(depth))
    sched.meta["depth"] = depth
    sched.meta["pass_us"] = timings
    return sched


def _bucket_sync_overlapped(dcomm, axis_comms, handles, buckets, grads,
                            compress, ef, sched=None, depth=2):
    """Overlapped twin of ``_bucket_sync``: uncompressed buckets go
    through pre-bound persistent handles (one revocation check per start),
    compressed buckets through the communicator's planned two-phase sync
    (the EF residual mutates in its wait arm, nowhere else)."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out = [None] * len(leaves)
    new_ef = [None] * len(buckets)
    if compress:
        # same layout contract (and the same actionable error) as the
        # blocking engine.sync_gradients_bucketed path
        if ef is None:
            ef = bucket_ef_zeros(buckets)
        elif (len(ef) != len(buckets)
              or any(e.shape[-1] != b.size for e, b in zip(ef, buckets))):
            raise ValueError(
                f"ef_state layout {[e.shape[-1] for e in ef]} does not "
                f"match the bucket plan {[b.size for b in buckets]} — was "
                f"it built with the same bucket_bytes?")
    if sched is None:
        sched = _overlap_sync_schedule(
            dcomm, [(f"bucket{i}", b.size, b.wire_dtype)
                    for i, b in enumerate(buckets)], compress, depth)

    def start(u):
        flat = plan_mod.gather_bucket(leaves, buckets[u.index])
        if compress:
            # mean=False: the blocking bucketed path applies ONE full-axes
            # scale after the cross-axis reductions — replicated below so
            # the float op order (and hence the loss bits) match exactly.
            return axis_comms[0].sync_gradient_start(
                flat, mean=False, compress=True, ef_residual=ef[u.index])
        return handles[u.index].start(flat)

    def progress(u, tok, stages):
        if compress:
            axis_comms[0].sync_gradient_progress(tok, stages)
        else:
            handles[u.index].progress(tok, stages)
        return tok

    def wait(u, tok):
        bi = u.index
        if compress:
            y, res = axis_comms[0].sync_gradient_wait(tok)
            for acomm in axis_comms[1:]:
                y = acomm.all_reduce(y)
            y = y * jnp.asarray(dcomm.mean_scale(), y.dtype)
            new_ef[bi] = res
        else:
            y = handles[bi].wait(tok)
        plan_mod.scatter_bucket(y, buckets[bi], out)
        return y

    schedule_mod.execute(sched, start=start, wait=wait, progress=progress)
    return (jax.tree_util.tree_unflatten(treedef, out),
            tuple(new_ef) if compress else ef)


def _leaf_sync_overlapped(dcomm, axis_comms, grads, compress, ef_tree,
                          sched=None, depth=2):
    """Overlapped twin of ``_leaf_sync``: one two-phase sync per leaf,
    schedule-IR sequenced."""
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    out = [None] * len(leaves)
    if compress:
        ef_leaves = treedef.flatten_up_to(ef_tree)
        new_ef = [None] * len(leaves)
    if sched is None:
        sched = _overlap_sync_schedule(
            dcomm, [(f"leaf{i}", l.size, l.dtype)
                    for i, l in enumerate(leaves)], compress, depth)

    def start(u):
        i = u.index
        if compress:
            return axis_comms[0].sync_gradient_start(
                leaves[i], compress=True, ef_residual=ef_leaves[i])
        return dcomm.sync_gradient_start(leaves[i])

    def progress(u, tok, stages):
        comm = axis_comms[0] if compress else dcomm
        comm.sync_gradient_progress(tok, stages)
        return tok

    def wait(u, tok):
        i = u.index
        if compress:
            y, res = axis_comms[0].sync_gradient_wait(tok)
            for acomm in axis_comms[1:]:
                y = acomm.all_reduce(y, mean=True)
            new_ef[i] = res
        else:
            y, _ = dcomm.sync_gradient_wait(tok)
        out[i] = y
        return y

    schedule_mod.execute(sched, start=start, wait=wait, progress=progress)
    synced = jax.tree_util.tree_unflatten(treedef, out)
    if not compress:
        return synced, ef_tree
    return synced, jax.tree_util.tree_unflatten(treedef, new_ef)


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def make_train_step(model, optimizer, cfg: TrainCfg = TrainCfg(),
                    mesh=None, engine=None,
                    comm: Optional["comm_mod.Communicator"] = None
                    ) -> Callable:
    """Returns train_step(state, batch) -> (state, metrics).

    Composed/compressed modes need a communicator: pass ``comm=``
    (normally ``session.world`` from a ``repro.comm.Session``).  The
    legacy ``mesh=``+``engine=`` pair still works and is adopted into a
    communicator internally."""

    def loss_fn(p, b):
        return model.loss(p, b)

    if cfg.sync_mode == "auto":
        def train_step(state, batch):
            loss, grads = _accumulate_grads(
                loss_fn, state["params"], batch, cfg.microbatches,
                cfg.grad_dtype)
            new_params, new_opt, om = optimizer.update(
                grads, state["opt"], state["params"])
            return ({"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}, {"loss": loss, **om})
        train_step.schedule = None
        train_step.ag_schedule = None
        train_step.schedule_pass_us = {}
        return train_step

    if cfg.sync_mode not in ("composed", "compressed"):
        raise ValueError(cfg.sync_mode)
    if comm is None:
        if mesh is None or engine is None:
            raise ValueError("composed mode needs comm= (repro.comm "
                             "Communicator) or the legacy mesh= + engine=")
        comm = comm_mod.Session.adopt(engine, mesh).world
    if mesh is None:
        mesh = comm.mesh
    if mesh is None:
        raise ValueError("the communicator's session has no mesh; "
                         "pass mesh= explicitly")

    compress = cfg.sync_mode == "compressed"
    data_axes = tuple(a for a in cfg.data_axes if a in mesh.axis_names)
    if not data_axes:
        raise ValueError(
            f"sync_mode={cfg.sync_mode!r} has nothing to sync over: none "
            f"of cfg.data_axes={cfg.data_axes} exist in the mesh axes "
            f"{tuple(mesh.axis_names)}")
    manual = set(data_axes)
    dcomm = comm.split(*data_axes)
    # per-axis sub-communicators: the loss reduction and the compressed
    # path's cross-axis stage are sequential single-axis collectives.
    axis_comms = tuple(comm.split(a) for a in data_axes)

    # Overlapped mode: the bucket layout is static in (param shapes,
    # dtypes, bucket_bytes), so uncompressed buckets get persistent
    # handles bound ONCE here — protocol + tier + mean scale resolved at
    # build time, a start is one revocation check.  sync_stats=True makes
    # each start record its wire bytes under the engine's sync key
    # exactly like the blocking planned path (the CommStats parity fix).
    overlap = bool(cfg.overlap)
    depth = int(cfg.overlap_depth)
    peel = cfg.overlap_peel
    if peel is None:
        peel = jax.default_backend() != "cpu"
    peel = overlap and bool(peel)
    buckets = ()
    bucket_handles = ()
    sched = None
    if overlap and cfg.bucket_grads:
        buckets = grad_bucket_plan(model.abstract_params(), cfg)
        if not compress:
            bucket_handles = tuple(
                dcomm.persistent("all_reduce", (b.size,), b.wire_dtype,
                                 mean=True, sync_stats=True)
                for b in buckets)
    if overlap and not cfg.zero:
        # the work-unit layout is static in (param shapes, dtypes,
        # bucket_bytes), so the sync program is built + rewritten ONCE
        # here; every traced step executes the same schedule.
        if cfg.bucket_grads:
            specs = [(f"bucket{i}", b.size, b.wire_dtype)
                     for i, b in enumerate(buckets)]
        else:
            specs = [(f"leaf{i}", math.prod(s.shape), s.dtype)
                     for i, s in enumerate(_grad_structs(
                         model.abstract_params(), cfg))]
        tags = (("peeled_microbatch", True),) if peel else ()
        sched = _overlap_sync_schedule(dcomm, specs, compress, depth,
                                       compute=tags)

    # ZeRO-1: two persistent arms per leaf (RS of the grad, AG of the
    # updated param chunk) plus the two schedule-IR programs sequencing
    # them.  All of it is static in (param shapes, dtypes, DP size), so
    # it is built ONCE here; the optimizer update sits between the two
    # programs, which is why they cannot be one schedule.
    zero = bool(cfg.zero)
    rs_handles = ag_handles = ()
    rs_sched = ag_sched = None
    zstate_specs = None
    if zero:
        zax, zp = zero_layout(cfg, mesh)
        zcomm = axis_comms[0]            # == dcomm: single data axis
        params_abs = model.abstract_params()
        pleaves_abs = jax.tree_util.tree_leaves(params_abs)
        gstructs = _grad_structs(params_abs, cfg)
        chunk_sizes = [_zero_pad_len(g.size, zp) // zp for g in gstructs]
        rs_handles = tuple(
            zcomm.persistent("reduce_scatter", g.shape, g.dtype,
                             mean=True, sync_stats=True, zero=True)
            for g in gstructs)
        ag_handles = tuple(
            zcomm.persistent("all_gather", (csz,), l.dtype, zero=True)
            for csz, l in zip(chunk_sizes, pleaves_abs))
        rs_specs = [(f"leaf{i}", math.prod(g.shape), g.dtype)
                    for i, g in enumerate(gstructs)]
        ag_specs = [(f"param{i}", csz * zp, l.dtype)
                    for i, (csz, l) in enumerate(zip(chunk_sizes,
                                                     pleaves_abs))]
        tags = (("peeled_microbatch", True),) if peel else ()
        rs_sched = zcomm.zero_sync_schedule(rs_specs, kind="rs",
                                            compute=tags)
        # the AG's compute op models the NEXT step's forward: the
        # interleave/hoist passes place AG starts before it so the
        # gather drains under compute the model says is there.
        ag_sched = zcomm.zero_sync_schedule(
            ag_specs, kind="ag", compute=(("next_forward", True),))
        if overlap:
            rs_sched, rs_us = plan_mod.run_passes(
                rs_sched, plan_mod.canonical_overlap_passes(depth))
            ag_sched, ag_us = plan_mod.run_passes(
                ag_sched, plan_mod.canonical_overlap_passes(depth))
            rs_sched.meta["depth"] = ag_sched.meta["depth"] = depth
            rs_sched.meta["pass_us"] = rs_us
            ag_sched.meta["pass_us"] = ag_us
        # optimizer state is data-axis sharded: its specs (not P()) go
        # into the step's shard_map so each rank holds 1/N of it.
        zstate_specs = {"params": P(),
                        "opt": _zero_opt_specs(model, optimizer, cfg, mesh),
                        "step": P()}
        zstate_rest = P(tuple(a for a in mesh.axis_names if a != zax))

    def _zero_inner(st, loss, grads):
        """The ZeRO-1 step body (runs inside the manual shard_map):
        RS-schedule the grads down to this rank's chunks, update the
        local state shard, AG-schedule the new param chunks back up."""
        gleaves, gdef = jax.tree_util.tree_flatten(grads)
        chunks = [None] * len(gleaves)

        def rs_start(u):
            return rs_handles[u.index].start(gleaves[u.index])

        def rs_progress(u, tok, stages):
            rs_handles[u.index].progress(tok, stages)
            return tok

        def rs_wait(u, tok):
            chunks[u.index] = rs_handles[u.index].wait(tok)
            return chunks[u.index]

        schedule_mod.execute(rs_sched, start=rs_start, wait=rs_wait,
                             progress=rs_progress)
        for acomm in axis_comms:
            loss = acomm.all_reduce(loss)
        loss = loss * dcomm.mean_scale()
        # global grad norm from shard-local partial sums + ONE scalar
        # all-reduce (the unsharded path reduces over full leaves; same
        # value up to float summation order, so bit-identity of the
        # LOSSES needs clip_norm=0, where the norm is metric-only).
        sq = sum(jnp.sum(jnp.square(ch.astype(jnp.float32)))
                 for ch in chunks)
        gsq = zcomm.all_reduce(sq)

        def gnorm_fn(_tree, _n=gsq):
            return jnp.sqrt(_n)

        idx = zcomm.axis_index()
        pleaves = jax.tree_util.tree_leaves(st["params"])
        pchunks = [_zero_chunk(l, zp, idx) for l in pleaves]
        new_pc, new_opt, om = optimizer.update(
            jax.tree_util.tree_unflatten(gdef, chunks), st["opt"],
            jax.tree_util.tree_unflatten(gdef, pchunks),
            global_norm_fn=gnorm_fn)
        # This rank's state chunk spreads over the remaining (auto) mesh
        # axes too, so ZeRO divides the optimizer state by the
        # data-parallel degree on top of the model-axis sharding the
        # unsharded state has.
        new_opt = jax.tree_util.tree_map(
            lambda l: shard_hint(l, zstate_rest) if l.ndim == 1 else l,
            new_opt)
        npc = jax.tree_util.tree_leaves(new_pc)
        fulls = [None] * len(pleaves)

        def ag_start(u):
            return ag_handles[u.index].start(npc[u.index])

        def ag_progress(u, tok, stages):
            ag_handles[u.index].progress(tok, stages)
            return tok

        def ag_wait(u, tok):
            y = ag_handles[u.index].wait(tok)
            ref = pleaves[u.index]
            fulls[u.index] = shard_hint(y[:ref.size].reshape(ref.shape),
                                        P())
            return fulls[u.index]

        schedule_mod.execute(ag_sched, start=ag_start, wait=ag_wait,
                             progress=ag_progress)
        new_params = jax.tree_util.tree_unflatten(gdef, fulls)
        return ({"params": new_params, "opt": new_opt,
                 "step": st["step"] + 1}, {"loss": loss, **om})

    def train_step(state, batch):
        bspecs = batch_specs(batch, data_axes)

        st_specs = zstate_specs if zero else P()

        @functools.partial(
            substrate.shard_map, mesh=mesh,
            in_specs=(st_specs, bspecs),
            out_specs=(st_specs, P()),
            axis_names=manual, check_vma=False)
        def inner(st, local_batch):
            # overlap: peel the last microbatch out of the scan so the
            # reverse-order bucket starts interleave with its backward.
            loss, grads = _accumulate_grads(
                loss_fn, st["params"], local_batch, cfg.microbatches,
                cfg.grad_dtype, peel_last=peel)
            if zero:
                return _zero_inner(st, loss, grads)
            ef = st.get("ef")
            if cfg.bucket_grads:
                if overlap:
                    grads, new_ef = _bucket_sync_overlapped(
                        dcomm, axis_comms, bucket_handles, buckets, grads,
                        compress, ef, sched=sched, depth=depth)
                else:
                    grads, new_ef = _bucket_sync(dcomm, grads, compress,
                                                 ef, cfg.bucket_bytes)
            elif overlap:
                grads, new_ef = _leaf_sync_overlapped(
                    dcomm, axis_comms, grads, compress, ef,
                    sched=sched, depth=depth)
            else:
                grads, new_ef = _leaf_sync(dcomm, axis_comms, grads,
                                           compress, ef)
            for acomm in axis_comms:
                loss = acomm.all_reduce(loss)
            loss = loss * dcomm.mean_scale()
            new_params, new_opt, om = optimizer.update(
                grads, st["opt"], st["params"])
            new_state = {"params": new_params, "opt": new_opt,
                         "step": st["step"] + 1}
            if compress:
                new_state["ef"] = new_ef
            return new_state, {"loss": loss, **om}

        return inner(state, batch)

    # introspection: the executed sync program + per-pass rewrite timings
    # (zero mode runs TWO programs; .schedule is the RS half, the AG half
    # hangs off .ag_schedule)
    active = rs_sched if zero else sched
    train_step.schedule = active
    train_step.ag_schedule = ag_sched
    train_step.schedule_pass_us = (dict(active.meta.get("pass_us", {}))
                                   if active is not None else {})
    return train_step


# ---------------------------------------------------------------------------
# TrainSession: one (model, optimizer, cfg) bundle, many meshes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainSession:
    """Everything about a training run that survives a re-mesh.

    The elastic controller rebuilds the mesh-bound pieces (step function,
    shardings, engine plan) after every topology change; the pieces that
    must NOT change across a recovery — model, optimizer, TrainCfg, and
    through them the state structure and bucket layout — live here so the
    launch driver and the controller construct them exactly once and the
    same way.
    """

    model: Any
    optimizer: Any
    cfg: TrainCfg = TrainCfg()

    def state_specs(self, mesh=None) -> Dict[str, Any]:
        """``mesh=`` is required with ``cfg.zero`` (state layout depends
        on the data-parallel size) and ignored otherwise."""
        return state_specs(self.model, self.optimizer, self.cfg, mesh=mesh)

    def abstract_state(self, mesh=None):
        return make_train_state(self.model, self.optimizer, abstract=True,
                                cfg=self.cfg, mesh=mesh)

    def init_state(self, rng=None, mesh=None):
        return make_train_state(self.model, self.optimizer, rng,
                                cfg=self.cfg, mesh=mesh)

    def step_fn(self, mesh=None, engine=None,
                comm: Optional["comm_mod.Communicator"] = None) -> Callable:
        """Build the topology-bound train step (pass ``comm=`` — the
        session's world communicator — or the legacy mesh+engine pair);
        called again after every re-mesh."""
        return make_train_step(self.model, self.optimizer, self.cfg,
                               mesh=mesh, engine=engine, comm=comm)

    def batch_axes(self) -> Tuple[str, ...]:
        """Axes the data pipeline shards batches over (filtered to the
        mesh's axes by the pipeline/spec machinery downstream)."""
        return tuple(self.cfg.data_axes)
