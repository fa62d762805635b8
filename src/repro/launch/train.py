"""End-to-end training driver with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch granite-34b \
        --reduced --steps 200 --sync composed --ckpt-dir /tmp/ckpt

Runs on whatever devices exist (reduced configs on CPU for the example;
the full configs on a real pod).  Demonstrates the whole substrate:
synthetic sharded data -> engine-composed collectives -> microbatched
train step -> async checkpointing -> watchdog -> crash recovery with
elastic re-mesh.

``--elastic`` hands the loop to ``repro.runtime.controller.
ElasticController`` — the supervised fail/shrink/grow path; combine with
``--fault-plan 'lose@5:2,gain@9:2'`` to drive deterministic fault
injection on fake host devices (``XLA_FLAGS=
--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import logging
import time
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import comm as comm_mod
from repro.checkpoint import CheckpointManager
from repro.configs import ARCH_IDS, get_config
from repro.core.plan import DEFAULT_BUCKET_BYTES
from repro.data import SyntheticLMDataset
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build_model
from repro.optim import cosine_schedule, make_optimizer
from repro.parallel.sharding import named_shardings
from repro.runtime import (ElasticController, FaultPlan, StepWatchdog,
                           substrate)
from repro.runtime import compile_cache, ctrlplane, health
from repro.train import trainer

logger = logging.getLogger("repro.train")


def build_session(mesh, model, opt, ds, args) -> "comm_mod.Session":
    """Paper §2.2 through the facade: trace a composed-mode probe step
    over ``Session.probe``'s abstract (4, 2) mesh to discover the
    collective set 𝓕 — the probe must use the *actual* sync mode (a
    compressed launch invokes compressed_all_reduce, which the composed
    library must cover) — then ``Session.from_application`` composes the
    thin library and initializes the session for the real mesh."""
    probe = comm_mod.Session.probe((4, 2), ("data", "model"))
    probe_cfg = trainer.TrainCfg(microbatches=args.microbatches,
                                 sync_mode=args.sync,
                                 data_axes=("data",),
                                 bucket_grads=args.bucket_grads,
                                 bucket_bytes=args.bucket_bytes,
                                 overlap=args.overlap,
                                 overlap_depth=args.overlap_depth,
                                 zero=args.zero)
    # the probe's abstract state must be laid out for the PROBE mesh:
    # with --zero the optimizer-state padding tracks the data-parallel
    # size, and the probe traces over the abstract (4, 2) mesh.
    probe_step = trainer.make_train_step(model, opt, probe_cfg,
                                         mesh=probe.mesh, comm=probe.world)
    abstate = trainer.make_train_state(model, opt, abstract=True,
                                       cfg=probe_cfg, mesh=probe.mesh)
    abatch = jax.eval_shape(
        lambda: {k: jnp.zeros(v.shape, v.dtype)
                 for k, v in ds.host_batch(0).items()})
    return comm_mod.Session.from_application(
        probe_step, abstate, abatch, mesh=mesh, probe=probe)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="granite-34b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--sync", choices=["auto", "composed", "compressed"],
                    default="auto")
    ap.add_argument("--bucket-grads", action="store_true")
    ap.add_argument("--bucket-bytes", type=int,
                    default=DEFAULT_BUCKET_BYTES,
                    help="size cap per fused dtype-grouped "
                         "gradient bucket")
    ap.add_argument("--overlap", action="store_true", default=False,
                    help="nonblocking start/wait gradient sync: bucket "
                         "transfers overlap the peeled last microbatch's "
                         "backward and each other (composed/compressed "
                         "modes; bit-identical losses to blocking)")
    ap.add_argument("--no-overlap", dest="overlap", action="store_false",
                    help="force the blocking gradient-sync path")
    ap.add_argument("--overlap-depth", type=int, default=2,
                    help="in-flight collectives the schedule IR's "
                         "interleave pass keeps live (2 = classic "
                         "software pipeline; >=3 adds per-stage "
                         "progress hops)")
    ap.add_argument("--zero", action="store_true", default=False,
                    help="ZeRO-1 optimizer-state sharding on the RS/AG "
                         "seam: gradients sync with only the reduce-"
                         "scatter half of the planned all-reduce, each "
                         "data-parallel rank updates its 1/N shard of "
                         "the optimizer state, and updated params all-"
                         "gather back through the schedule IR (losses "
                         "bit-identical to the unsharded composed path "
                         "at clip_norm=0).  Needs --sync composed; "
                         "incompatible with --bucket-grads.  Example: "
                         "--sync composed --zero --overlap "
                         "--ckpt-sharded")
    ap.add_argument("--ckpt-sharded", action="store_true", default=False,
                    help="write distributed state leaves per shard "
                         "(leaf_XXXXX.shard_RRR.bin + manifest shard "
                         "map) so no host gathers a full leaf; restore "
                         "reassembles by global index onto any survivor "
                         "mesh (pair with --zero)")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--elastic", action="store_true",
                    help="supervised fail/shrink/grow loop "
                         "(ElasticController); needs --ckpt-dir")
    ap.add_argument("--max-recoveries", type=int, default=8,
                    help="abort after this many elastic recoveries")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault injection, e.g. "
                         "'lose@5:2,gain@9:2,stall@7'")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for fault-victim selection")
    ap.add_argument("--watchdog-timeout", type=float, default=300.0)
    ap.add_argument("--ctrl-peers", default="",
                    help="control-plane peers as 'host:port,host:port' "
                         "(the OTHER members); enables the multi-host "
                         "membership vote — re-meshes then happen only "
                         "on committed, fenced epochs")
    ap.add_argument("--ctrl-port", type=int, default=0,
                    help="TCP port this member's control plane listens "
                         "on (0 = ephemeral; peers must name the real "
                         "port)")
    ap.add_argument("--ctrl-host", default="127.0.0.1",
                    help="address this member is ADVERTISED as — what "
                         "the peers' --ctrl-peers lists call it (the "
                         "member id defaults to '<ctrl-host>:<port>'); "
                         "the listener binds all interfaces regardless")
    ap.add_argument("--ctrl-member", default="",
                    help="explicit member id, when the peers' lists use "
                         "'name=host:port' entries instead of raw "
                         "endpoints")
    ap.add_argument("--heartbeat-interval", type=float, default=0.5,
                    help="control-plane heartbeat cadence in seconds "
                         "(peer declared dead after interval-derived "
                         "suspicion strikes)")
    ap.add_argument("--ctrl-fault-plan", default="",
                    help="injected control-plane message faults, e.g. "
                         "'drop@3:2,delay@5:4,partition@0:40'")
    return ap


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = build_parser()
    args = ap.parse_args(argv)
    if args.zero and args.sync != "composed":
        ap.error("--zero needs --sync composed (the RS/AG seam only "
                 "exists on the composed planned-collective path)")
    if args.zero and args.bucket_grads:
        ap.error("--zero runs one RS/AG pair per parameter leaf and is "
                 "incompatible with --bucket-grads")
    if args.elastic and not args.ckpt_dir:
        ap.error("--elastic needs --ckpt-dir (recovery restores from "
                 "the atomic checkpoint store)")
    return args


@dataclasses.dataclass
class Setup:
    """What both loops build from the arguments."""
    model: Any
    optimizer: Any
    tcfg: "trainer.TrainCfg"
    mesh: Any
    dataset: SyntheticLMDataset
    comm_session: Optional["comm_mod.Session"]


def setup(args: argparse.Namespace) -> Setup:
    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_host_mesh(model_parallel=args.model_parallel))
    logger.info("mesh: %s  model: %s (%.2fM params)", mesh, model.name,
                model.param_count() / 1e6)

    opt = make_optimizer(
        args.optimizer,
        lr=cosine_schedule(args.lr, warmup=max(args.steps // 20, 1),
                           total=args.steps))
    tcfg = trainer.TrainCfg(microbatches=args.microbatches,
                            sync_mode=args.sync,
                            bucket_grads=args.bucket_grads,
                            bucket_bytes=args.bucket_bytes,
                            overlap=args.overlap,
                            overlap_depth=args.overlap_depth,
                            zero=args.zero)

    ds = SyntheticLMDataset(vocab_size=cfg.vocab_size,
                            seq_len=args.seq_len,
                            global_batch=args.global_batch)

    comm_session = None
    if args.sync != "auto":
        comm_session = build_session(mesh, model, opt, ds, args)
        logger.info("composed session:\n%s", comm_session.describe())
    return Setup(model, opt, tcfg, mesh, ds, comm_session)


def train_elastic(args: argparse.Namespace, s: Setup):
    """The supervised fail/shrink/grow loop (``--elastic``); returns the
    controller's report."""
    session = trainer.TrainSession(s.model, s.optimizer, s.tcfg)
    fplan = (FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
             if args.fault_plan else None)
    # SIGTERM (what cloud schedulers send ahead of eviction) becomes
    # a step-boundary drain + re-mesh instead of a corpse.
    notice = health.PreemptionNotice()
    try:
        health.install_preemption_handler(notice)
    except ValueError:                  # not the main thread
        logger.warning("not on the main thread: SIGTERM preemption "
                       "handler not installed")
    membership = None
    if args.ctrl_peers:
        cplan = (ctrlplane.CtrlFaultPlan.parse(args.ctrl_fault_plan,
                                               seed=args.fault_seed)
                 if args.ctrl_fault_plan else None)
        membership = ctrlplane.connect(
            args.ctrl_member or None,
            port=args.ctrl_port, host=args.ctrl_host,
            peers=args.ctrl_peers,
            config=ctrlplane.CtrlConfig(
                heartbeat_interval=args.heartbeat_interval,
                heartbeat_timeout=5 * args.heartbeat_interval),
            fault_plan=cplan)
        logger.info("control plane: %s with peers %s",
                    membership.member, membership.peers)
    try:
        ctl = ElasticController(
            session, s.dataset, s.mesh, total_steps=args.steps,
            ckpt_dir=args.ckpt_dir, comm=s.comm_session,
            ckpt_every=args.ckpt_every,
            ckpt_sharded=args.ckpt_sharded,
            fault_plan=fplan,
            max_recoveries=args.max_recoveries,
            watchdog_timeout=args.watchdog_timeout,
            preemption=notice, membership=membership,
            on_step=lambda st, l: (st % args.log_every == 0
                                   and logger.info("step %4d  "
                                                   "loss %.4f", st, l)))
        report = ctl.run()
    finally:
        if membership is not None:
            membership.close()
    logger.info("elastic run done:\n%s", report.describe())
    if s.comm_session is not None:
        logger.info("session stats:\n%s", s.comm_session.finalize())
    return report


@dataclasses.dataclass
class TrainRun:
    """Result of ``train``: per-step losses, the final state, and host
    wall times (compile, first step, mean of the later steps)."""
    losses: List[float]
    state: Any
    compile_s: float
    first_step_s: float
    warm_step_s: float


def train(args: argparse.Namespace, s: Setup) -> TrainRun:
    """The plain training loop (no controller): AOT-compile the step,
    run ``args.steps`` steps, checkpoint as asked."""
    mesh, tcfg = s.mesh, s.tcfg
    step_fn = trainer.make_train_step(
        s.model, s.optimizer, tcfg, mesh=mesh,
        comm=s.comm_session.world if s.comm_session is not None else None)
    shardings = named_shardings(
        mesh, trainer.state_specs(s.model, s.optimizer, tcfg, mesh=mesh))

    with substrate.set_mesh(mesh):
        # built in place on its shardings: an eager init would first hold
        # the whole state on one device
        state = jax.jit(
            functools.partial(trainer.make_train_state, s.model,
                              s.optimizer, cfg=tcfg, mesh=mesh),
            out_shardings=shardings)(jax.random.PRNGKey(0))

        ckpt = (CheckpointManager(args.ckpt_dir, every=args.ckpt_every,
                                  sharded=args.ckpt_sharded)
                if args.ckpt_dir else None)
        start = 0
        if ckpt is not None:
            restored, rstep = ckpt.restore_latest(
                jax.eval_shape(lambda: state), shardings,
                allow_resize_1d=tcfg.zero)
            if restored is not None:
                state, start = restored, rstep
                logger.info("restored checkpoint at step %d", start)

        # the state keeps its layout across steps, so the one compiled
        # executable serves every step
        t0 = time.perf_counter()
        jstep = jax.jit(step_fn, out_shardings=(shardings, None),
                        donate_argnums=0).lower(
            state, s.dataset.sharded_batch(start, mesh)).compile()
        compile_s = time.perf_counter() - t0
        logger.info("train step compiled in %.1fs", compile_s)

        wd = StepWatchdog(timeout=args.watchdog_timeout).start()
        losses = []
        t0 = time.perf_counter()
        t_first = None
        for step in range(start, args.steps):
            batch = s.dataset.sharded_batch(step, mesh)
            state, metrics = jstep(state, batch)
            losses.append(metrics["loss"])
            wd.beat()
            if ckpt is not None:
                ckpt.maybe_save(step + 1, state)
            if t_first is None:
                jax.block_until_ready(metrics)
                t_first = time.perf_counter()
            if step % args.log_every == 0 or step == args.steps - 1:
                logger.info("step %4d  loss %.4f  |g| %.3f  lr %.2e  "
                            "(%.2fs/step)",
                            step, float(metrics["loss"]),
                            float(metrics.get("grad_norm", 0.0)),
                            float(metrics.get("lr", 0.0)),
                            (time.perf_counter() - t0)
                            / max(step - start + 1, 1))
        jax.block_until_ready(state)
        t_end = time.perf_counter()
        wd.stop()
        if ckpt is not None:
            ckpt.maybe_save(args.steps, state, force=True)
            ckpt.wait()
        if s.comm_session is not None:
            logger.info("session stats:\n%s", s.comm_session.finalize())
    n = len(losses)
    return TrainRun(
        losses=[float(l) for l in losses], state=state, compile_s=compile_s,
        first_step_s=(t_first - t0) if t_first is not None else 0.0,
        warm_step_s=(t_end - t_first) / (n - 1) if n > 1 else 0.0)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    compile_cache.enable()
    s = setup(args)
    if args.elastic:
        train_elastic(args, s)
    else:
        train(args, s)


if __name__ == "__main__":
    main()
