#!/usr/bin/env python3
"""Bring-up check: the trainer and the paged server run on a TPU.

    python chip_smoke.py              # one chip: serve, then train
    python chip_smoke.py --chips 4    # four chips: gradient sync and ZeRO

Everything runs in this one process, through the launchers' own functions
(``repro.launch.serve.serve``, ``repro.launch.train.setup``/``train``), on
``mamba2-1.3b`` at its published width with random weights from seed 0.
It drives the scheduler and the train loop directly, never the elastic
controllers, whose recovery would hide a failing step.

The lines before the last are one-off bring-up readings, not benchmark
metrics.  The last line is one JSON object naming the device; it is
printed only when every check passed.  Without a TPU, or without the
repository's ``src/`` next to this file, the script exits non-zero and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
ARCH = "mamba2-1.3b"

# serve: 8 requests over 4 decode slots, prompts of 128-1024 tokens
SERVE_ARGS = ["--arch", ARCH, "--full", "--requests", "8", "--batch", "4",
              "--max-new", "32", "--prompt-lens", "128,1024,384,640",
              "--max-len", "1152"]
# decode-path logits against the full forward, in bf16 over 48 layers: a
# decode that lost its state is off by the whole logit range, while
# rounding with a bf16 cache stays within a few percent of it (6.6% on a
# v5e)
LOGIT_RTOL = 0.1
# train: 2048-token sequences (the model's training context), 4 per step.
# At the launcher's default lr of 1e-3 the loss jumps from 11.2 to 19 after
# one step, and that divergence amplifies rounding differences between
# the syncs; 1e-4 keeps the comparison about the sync.
TRAIN_ARGS = ["--arch", ARCH, "--full", "--steps", "3", "--seq-len", "2048",
              "--global-batch", "4", "--lr", "1e-4", "--log-every", "1"]
# step-0 loss of a random-init LM sits near ln(vocab)
LOSS0_ATOL = 1.0
# composed vs native sync: same math, other summation order
SYNC_LOSS_ATOL = 1e-2


def note(msg: str) -> None:
    print(f"bring-up reading (one-off, not a benchmark metric): {msg}",
          flush=True)


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str) -> None:
        print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def serve_phase(jax, checks: Checks) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve as serve_mod
    from repro.serve import paging

    args = serve_mod.build_parser().parse_args(SERVE_ARGS)
    run = serve_mod.serve(args)
    vocab = run.model.cfg.vocab_size
    checks.expect(len(run.done) == args.requests and not run.shed,
                  f"serve: {len(run.done)}/{args.requests} requests "
                  f"completed, {len(run.shed)} shed")
    toks = [t for r in run.done for t in r.generated]
    checks.expect(len(toks) == args.requests * args.max_new
                  and all(0 <= t < vocab for t in toks),
                  f"serve: {len(toks)} tokens, all in [0, {vocab})")
    note(f"serve wall s (compiles included) = {run.seconds}")
    note(f"serve tokens generated = {len(toks)}")

    # Teacher-force the longest request's tokens through prefill + the
    # decode step, and compare each position's logits with one forward
    # pass over the same tokens.
    req = max(run.done, key=lambda r: len(r.prompt))
    model, params, scfg = run.model, run.params, run.scfg
    prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
    caches = paging.contiguous_caches(model, 1, scfg.max_len,
                                      dtype=scfg.cache_dtype)
    logits, caches = jax.jit(model.prefill)(params, {"tokens": prompt},
                                            caches)
    rows = [logits[0]]
    decode = jax.jit(model.decode_step)
    for t in req.generated[:-1]:
        logits, caches = decode(params, {"tokens": jnp.asarray([[t]])},
                                caches)
        rows.append(logits[0])
    dec = np.asarray(jnp.stack(rows), np.float32)
    seq = jnp.asarray(req.prompt + req.generated[:-1], jnp.int32)[None, :]
    full = np.asarray(jax.jit(model.logits)(params, {"tokens": seq}),
                      np.float32)[0, len(req.prompt) - 1:]
    err = float(np.abs(dec - full).max())
    scale = float(np.abs(full).max())
    checks.expect(np.isfinite(dec).all() and err <= LOGIT_RTOL * scale,
                  f"serve: rid {req.rid} decode logits vs forward over "
                  f"{full.shape[0]} positions: max |diff| {err} <= "
                  f"{LOGIT_RTOL} * max |logit| {scale}")
    note(f"serve decode/forward argmax agreement = "
         f"{float((dec.argmax(-1) == full.argmax(-1)).mean())}")
    note(f"peak_bytes_in_use after serve = {peak_bytes(jax)}")


def train_run(extra):
    from repro.launch import train as train_mod
    args = train_mod.parse_args(TRAIN_ARGS + list(extra))
    setup = train_mod.setup(args)
    return train_mod.train(args, setup), setup


def report_train(name, run, checks: Checks) -> None:
    note(f"{name} compile s = {run.compile_s}")
    note(f"{name} first step s = {run.first_step_s}")
    note(f"{name} warm step s = {run.warm_step_s}")
    note(f"{name} losses = {run.losses}")
    checks.expect(all(math.isfinite(l) for l in run.losses),
                  f"{name}: losses finite")


def train_phase(jax, checks: Checks) -> None:
    run, setup = train_run(["--sync", "composed",
                            "--optimizer", "adafactor"])
    report_train("train", run, checks)
    ln_v = math.log(setup.model.cfg.vocab_size)
    checks.expect(abs(run.losses[0] - ln_v) <= LOSS0_ATOL,
                  f"train: step-0 loss {run.losses[0]} within "
                  f"{LOSS0_ATOL} of ln(vocab) {ln_v}")
    checks.expect(jax.devices()[0].platform == "tpu", "train ran on tpu")
    note(f"peak_bytes_in_use after train = {peak_bytes(jax)}")


def opt_bytes_per_device(jax, state) -> dict:
    out = {}
    for leaf in jax.tree_util.tree_leaves(state["opt"]):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return out


def four_chip_phase(jax, checks: Checks) -> None:
    common = ["--model-parallel", "1"]
    losses = {}
    for sync in ("composed", "auto"):
        run, _ = train_run(common + ["--sync", sync,
                                     "--optimizer", "adafactor"])
        report_train(f"4-chip {sync}", run, checks)
        losses[sync] = run.losses
        del run
        gc.collect()
    diff = max(abs(a - b) for a, b in zip(losses["composed"],
                                          losses["auto"]))
    checks.expect(diff <= SYNC_LOSS_ATOL,
                  f"4-chip: composed vs auto losses differ by at most "
                  f"{diff} <= {SYNC_LOSS_ATOL}")

    # ZeRO-1 with AdamW: the unsharded composed layout would hold the
    # whole optimizer state on every device (it does not fit a 16 GB
    # chip next to the params), so its bytes come from the abstract state
    run, setup = train_run(common + ["--sync", "composed", "--zero",
                                     "--optimizer", "adamw"])
    report_train("4-chip composed zero", run, checks)
    per_dev = opt_bytes_per_device(jax, run.state)
    unsharded = sum(
        l.size * l.dtype.itemsize for l in jax.tree_util.tree_leaves(
            jax.eval_shape(setup.optimizer.init,
                           setup.model.abstract_params())))
    for dev, nbytes in sorted(per_dev.items()):
        note(f"zero optimizer-state bytes on device {dev} = {nbytes}")
    note(f"unsharded optimizer-state bytes per device = {unsharded}")
    checks.expect(len(per_dev) == 4
                  and max(per_dev.values()) * 3 <= unsharded,
                  f"4-chip zero: optimizer state per device at most "
                  f"{max(per_dev.values())} bytes, >= 3x below the "
                  f"unsharded {unsharded}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip sync and ZeRO phase")
    opts = ap.parse_args(argv)

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}; run this script "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    from repro.runtime import compile_cache

    compile_cache.enable()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
              f"this check runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} needs {opts.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    import logging
    logging.basicConfig(level=logging.INFO)
    note(f"jax {jax.__version__}, device_kind {devices[0].device_kind!r}, "
         f"{len(devices)} devices")

    checks = Checks()
    phases = ([four_chip_phase] if opts.chips == 4
              else [serve_phase, train_phase])
    for phase in phases:
        t0 = time.perf_counter()
        phase(jax, checks)
        note(f"{phase.__name__} wall s = {time.perf_counter() - t0}")
        gc.collect()
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
