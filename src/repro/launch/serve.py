"""Serving driver: continuous-batching generation (``--reduced`` toy
config by default, ``--full`` for the published one), optionally
supervised by the elastic ``ServeController``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b \
        --requests 16 --batch 4 --max-new 12

    # elastic: 8 fake host devices, lose 2 at decode step 3
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --elastic \
        --fault-plan lose@3:2 --requests 16 --batch 8
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time
from typing import Any, List, Optional, Sequence

import jax
import numpy as np

from repro import comm as comm_mod
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime import compile_cache, ctrlplane, health
from repro.runtime.controller import FaultPlan
from repro.serve import BatchScheduler, Request, ServeCfg, ServeController

logger = logging.getLogger("repro.serve")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-72b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated prompt lengths, cycled over the "
                         "requests (default: random lengths 4-15)")
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed (ServeCfg.seed)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission-control backlog bound (shed beyond)")
    ap.add_argument("--page-tokens", type=int, default=None,
                    help="KV page size (pow2 dividing max-len; equal to "
                         "max-len = contiguous layout; default auto)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="page-pool capacity (default batch*max_len/"
                         "page_tokens; smaller values overcommit and "
                         "exercise preemption)")
    ap.add_argument("--no-chunked-prefill", action="store_true",
                    help="run prompts one-shot at admission instead of "
                         "page-sized chunks interleaved with decode")
    ap.add_argument("--elastic", action="store_true",
                    help="supervise with ServeController (drain/re-mesh/"
                         "re-admit on device loss)")
    ap.add_argument("--fault-plan", default="",
                    help='injected faults, e.g. "lose@3:2,stall@5"')
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--max-recoveries", type=int, default=8)
    ap.add_argument("--watchdog-timeout", type=float, default=300.0)
    ap.add_argument("--snapshot-dir", default=None,
                    help="persist drained scheduler snapshots here")
    ap.add_argument("--ctrl-peers", default="",
                    help="control-plane peers as 'host:port,host:port' "
                         "(the OTHER members); enables the multi-host "
                         "membership vote")
    ap.add_argument("--ctrl-port", type=int, default=0,
                    help="TCP port this member's control plane listens "
                         "on (0 = ephemeral)")
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
                    help="control-plane heartbeat cadence in seconds")
    ap.add_argument("--ctrl-fault-plan", default="",
                    help="injected control-plane message faults, e.g. "
                         "'drop@3:2,partition@0:40'")
    return ap


def make_requests(args: argparse.Namespace, vocab_size: int
                  ) -> List[Request]:
    """Seeded random prompts: lengths from ``--prompt-lens`` (cycled) or
    drawn in [4, 16)."""
    rng = np.random.RandomState(0)
    lens = [int(n) for n in args.prompt_lens.split(",") if n]
    requests = []
    for rid in range(args.requests):
        n = lens[rid % len(lens)] if lens else rng.randint(4, 16)
        requests.append(Request(rid=rid,
                                prompt=rng.randint(0, vocab_size,
                                                   size=n).tolist(),
                                max_new=args.max_new))
    return requests


@dataclasses.dataclass
class ServeRun:
    """Result of ``serve``: the model it built, the requests that
    completed and were shed, and host wall seconds."""
    model: Any
    params: Any
    scfg: ServeCfg
    done: List[Request]
    shed: List[Request]
    seconds: float


def serve(args: argparse.Namespace) -> ServeRun:
    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    if model.kind == "encdec":
        raise SystemExit("serve driver targets decoder LMs; "
                         "see examples/serving.py for enc-dec")
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    logger.info("model %s: %.2fM params", model.name,
                model.param_count() / 1e6)

    # The session owns the serving mesh (one entity); the scheduler's
    # prefill/decode steps run inside it.
    session = comm_mod.Session(mesh=make_host_mesh(model_parallel=1))
    logger.info("serving session: %s", session.world.describe())

    scfg = ServeCfg(max_len=args.max_len, batch=args.batch,
                    cache_dtype=cfg.param_dtype, seed=args.seed,
                    max_queue=args.max_queue,
                    page_tokens=args.page_tokens,
                    pool_pages=args.pool_pages,
                    chunked_prefill=not args.no_chunked_prefill)
    requests = make_requests(args, cfg.vocab_size)

    t0 = time.time()
    if args.elastic:
        plan = (FaultPlan.parse(args.fault_plan, seed=args.fault_seed)
                if args.fault_plan else None)
        notice = health.PreemptionNotice()
        try:                  # SIGTERM -> graceful drain, not a corpse
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
            ctl = ServeController(
                model, params, scfg, comm=session.world, fault_plan=plan,
                max_recoveries=args.max_recoveries,
                watchdog_timeout=args.watchdog_timeout,
                snapshot_dir=args.snapshot_dir,
                preemption=notice, membership=membership)
            for req in requests:
                ctl.submit(req)
            report = ctl.run()
        finally:
            if membership is not None:
                membership.close()
        done, shed = report.completed, report.shed
        pool = ctl.sched.pool
        logger.info("%s", report.describe())
    else:
        sched = BatchScheduler(model, params, scfg, comm=session.world)
        for req in requests:
            sched.submit(req)
        done, shed = sched.run(), sched.shed
        pool = sched.pool
    dt = time.time() - t0
    logger.info("page pool: %d-token pages, %d/%d allocated at exit, "
                "%d bytes resident (contiguous layout: %d)",
                pool.page_tokens, pool.pages_allocated, pool.pages_total,
                pool.resident_bytes(), pool.contiguous_bytes())
    total_tokens = sum(len(r.generated) for r in done)
    logger.info("served %d requests (%d shed), %d tokens in %.2fs "
                "(%.1f tok/s)", len(done), len(shed), total_tokens, dt,
                total_tokens / dt)
    for r in done[:4]:
        logger.info("req %d: %s", r.rid, r.generated)
    return ServeRun(model=model, params=params, scfg=scfg, done=done,
                    shed=shed, seconds=dt)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    compile_cache.enable()
    serve(args)


if __name__ == "__main__":
    main()
