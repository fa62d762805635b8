"""Training driver: the program's compiled train step, timed.

Built as the training launcher builds it (``repro.launch.train``): the
mix's ``launcher_args`` go through the launcher's own parser, so every
setting the mix leaves out is the launcher's default; the composed
``Session`` comes from the launcher's ``build_session``, and the step
from ``trainer.make_train_step``.  The weights come from ``weights.py``
and the batches from ``loadgen.py``, both from the seed.

Set-up compiles the step ahead of time and drives it through its first
``CHECK_STEPS`` steps, through the very call and feed that the window
uses, keeping what the correctness check compares: each step's loss, the
per-leaf norm of the first gradient as the optimizer got it (read back
from its state after one step), and the per-leaf norm of the weights'
change after the last of those steps.  The window then times whole
steps, each ending in ``block_until_ready``, for ``--seconds``.  After
it, the plain reference (the configuration's ``loss`` and
``optim_ref``) runs the same steps from the same weights in float32.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

import harness
import loadgen
import modelcfg
import optim_ref
import weights

CHECK_STEPS = 3


def _mesh(devs, model_parallel: int):
    from repro.runtime import substrate
    n = len(devs)
    mp = min(model_parallel, n)
    return substrate.make_mesh((n // mp, mp), ("data", "model"),
                               devices=devs)


def _check_optimizer(args, o: dict) -> None:
    want = {"name": args.optimizer, "lr": args.lr,
            "warmup": max(args.steps // 20, 1), "total": args.steps}
    bad = {k: (o.get(k), v) for k, v in want.items() if o.get(k) != v}
    if bad:
        raise ValueError(f"the mix's optimizer block disagrees with the "
                         f"launcher's settings: {bad}")


def leaf_norms(tree):
    import jax.numpy as jnp
    import jax
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def grad_norms_from_state(o: dict, opt):
    """Per-leaf norm of the first gradient the optimizer was given, from
    AdamW's state after one step: m = (1 - b1) g."""
    return leaf_norms(opt["m"]) / (1.0 - o["b1"])


def measure(c, rec, devs, seed, seconds, tracer, compiles, t_start, log):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import train as train_mod
    from repro.models import build_model
    from repro.optim import cosine_schedule, make_optimizer
    from repro.parallel.sharding import named_shardings
    from repro.runtime import substrate
    from repro.train import trainer

    mix, o = c["mix"], c["mix"]["optimizer"]
    args = train_mod.parse_args(["--full", *mix["launcher_args"]])
    _check_optimizer(args, o)
    if (args.seq_len, args.global_batch) != (mix["seq_len"],
                                             mix["global_batch"]):
        raise ValueError("the mix's seq_len/global_batch disagree with "
                         "its launcher_args")
    model = build_model(modelcfg.transformer_cfg(c["config"]))
    vocab = model.cfg.vocab_size
    mesh = _mesh(devs, args.model_parallel)
    opt = make_optimizer(args.optimizer, lr=cosine_schedule(
        args.lr, warmup=max(args.steps // 20, 1), total=args.steps))
    tcfg = trainer.TrainCfg(microbatches=args.microbatches,
                            sync_mode=args.sync,
                            bucket_grads=args.bucket_grads,
                            bucket_bytes=args.bucket_bytes,
                            overlap=args.overlap,
                            overlap_depth=args.overlap_depth,
                            zero=args.zero)

    class Feed:
        def host_batch(self, step):
            return loadgen.train_batch(seed, step, mix, vocab)

    feed = Feed()
    session = (train_mod.build_session(mesh, model, opt, feed, args)
               if args.sync != "auto" else None)
    step_fn = trainer.make_train_step(
        model, opt, tcfg, mesh=mesh,
        comm=session.world if session is not None else None)
    shardings = named_shardings(
        mesh, trainer.state_specs(model, opt, tcfg, mesh=mesh))
    abstract = model.abstract_params()
    bsh = NamedSharding(mesh, P("data"))

    def batch(step):
        host = feed.host_batch(step)
        return {k: jax.make_array_from_callback(v.shape, bsh,
                                                lambda i, v=v: v[i])
                for k, v in host.items()}

    def init(key):
        st = trainer.make_train_state(model, opt, key, cfg=tcfg, mesh=mesh)
        st["params"] = weights.make(key, abstract, c["config"])
        return st

    key = weights.key_for(seed)
    with substrate.set_mesh(mesh):
        state = jax.jit(init, out_shardings=shardings)(key)
        jstep = jax.jit(step_fn, out_shardings=(shardings, None),
                        donate_argnums=0).lower(state, batch(0)).compile()
        gnorm_fn = jax.jit(functools.partial(grad_norms_from_state, o))
        dnorm_fn = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype("float32") - b.astype("float32"), p,
            weights.make(k, abstract, c["config"]))))
        losses = []
        for step in range(CHECK_STEPS):
            state, m = jstep(state, batch(step))
            losses.append(float(m["loss"]))
            if step == 0:
                gnorms = np.asarray(gnorm_fn(state["opt"]))
        dnorms = np.asarray(dnorm_fn(state["params"], key))
        log(f"set-up steps' losses {losses}")

        tokens_per_step = mix["global_batch"] * mix["seq_len"]
        step, n, n_before_trace = CHECK_STEPS, 0, None
        c0 = compiles.n
        t0 = time.perf_counter()
        while True:
            tracer.poll(time.perf_counter(), t0 + seconds)
            if tracer.active and n_before_trace is None:
                n_before_trace = n
            with tracer.span("bench.feed"):
                b = batch(step)
            with tracer.span("bench.step"):
                state, m = jstep(state, b)
                jax.block_until_ready(m["loss"])
            n += 1
            step += 1
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
        tracer.stop()
        last_loss = float(m["loss"])
    rec.setup_s = t0 - t_start
    rec.window_s = now - t0
    rec.steps = n
    rec.trace_steps = n - (n_before_trace or 0)
    rec.tokens_per_step = tokens_per_step
    rec.window_compiles = compiles.n - c0
    rec.attempted, rec.failed = n, 0 if math.isfinite(last_loss) else n
    log(f"window: {n} steps in {rec.window_s:.4f} s, last loss {last_loss}")
    return {"state": state, "jstep": jstep, "losses": losses,
            "gnorms": gnorms, "dnorms": dnorms, "abstract": abstract,
            "mesh": mesh}


def release(out) -> None:
    for k in ("state", "jstep", "mesh"):
        out.pop(k, None)


def _ref_sharding(devs, abstract):
    """Each leaf split over every chip along its last axis that divides
    evenly (the stacked-layer axis only as a last resort), else whole."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devs), ("d",))
    n = len(devs)

    def one(s):
        axes = list(range(len(s.shape)))
        for ax in reversed(axes[1:] if len(axes) > 2 else axes):
            if s.shape[ax] % n == 0 and s.shape[ax] >= n:
                spec = [None] * len(axes)
                spec[ax] = "d"
                return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())
    return mesh, jax.tree_util.tree_map(one, abstract)


def reference_run(c, out, seed, devs, mode="f32", rows=None):
    """The plain reference over the set-up's steps: (losses, per-leaf
    norms of the first gradient as the optimizer used it, per-leaf norms
    of the weights' change after the last step).  ``mode`` "fp8" is the
    control; ``rows`` keeps only the first rows of every batch (a fault
    that leaves the rest of the batch out)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    ref = harness.reference(c["config"])
    mix, o, cfg = c["mix"], c["mix"]["optimizer"], c["config"]
    abstract = out["abstract"]
    rmesh, psh = _ref_sharding(devs, abstract)
    rows = rows or mix["global_batch"]
    bsh = NamedSharding(rmesh, P("d") if rows % len(devs) == 0 else P())
    key = weights.key_for(seed)
    make = jax.jit(lambda k: weights.make(k, abstract, cfg),
                   out_shardings=psh)
    opt_abs = jax.eval_shape(functools.partial(optim_ref.init, o), abstract)
    osh = _ref_sharding(devs, opt_abs)[1]

    def step(p, s, b, t):
        p32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
        loss, g = jax.value_and_grad(
            lambda q: ref.loss(q, cfg, b, mode))(p32)
        p2, s2, used = optim_ref.update(o, g, s, p, t)
        return loss, p2, s2, leaf_norms(used)

    jstep = jax.jit(step, in_shardings=(psh, osh, bsh, None),
                    out_shardings=(None, psh, osh, None),
                    donate_argnums=(0, 1))
    p = make(key)
    s = jax.jit(functools.partial(optim_ref.init, o), out_shardings=osh)(p)
    vocab = abstract["embed"].shape[0]
    losses = []
    for t in range(CHECK_STEPS):
        host = loadgen.train_batch(seed, t, mix, vocab)
        b = {k: jax.device_put(v[:rows], bsh) for k, v in host.items()}
        loss, p, s, gn = jstep(p, s, b, jnp.float32(t + 1))
        losses.append(float(loss))
        if t == 0:
            gnorms = np.asarray(gn)
    del s
    dnorm = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p,
        weights.make(k, abstract, cfg))), in_shardings=(psh, None))
    dnorms = np.asarray(dnorm(p, key))
    return losses, gnorms, dnorms


def compare(prog, ref, floor_share: float):
    """The numbers the check compares, from the program's and the
    reference's (losses, grad norms, change norms)."""
    pl, pg, pd = prog
    rl, rg, rd = ref
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
    med_g = float(np.median(rg))
    grad_gap = float(np.max(np.abs(pg - rg) / np.maximum(rg, med_g)))
    moved = rg >= floor_share * med_g       # leaves whose gradient is not
    med_d = float(np.median(rd[moved]))     # nought to rounding
    change_gap = float(np.max(np.abs(pd[moved] - rd[moved])
                              / np.maximum(rd[moved], med_d)))
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "change_norm_gap": change_gap}


def check(c, rec, out, seed, devs, log):
    ref = reference_run(c, out, seed, devs)
    log(f"reference losses {ref[0]}")
    nums = compare((out["losses"], out["gnorms"], out["dnorms"]), ref,
                   c["limits"]["moved_floor_share"])
    lim = c["limits"]["limits"]
    return [(k, nums[k], lim[k]) for k in lim]
