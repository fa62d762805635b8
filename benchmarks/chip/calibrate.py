#!/usr/bin/env python3
"""Readings for the limits of a cell's correctness check (not run by the
benchmark itself).

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,...,12 --control-seeds 3 [--seconds 10]

In one process, on the chip, for each seed: the cell's set-up (and, for a
serving cell, a short window at the cell's own load), then the numbers
its check compares, for the program (the sound readings).  For the first
``--control-seeds`` seeds also the control, the plain reference computed
with every matrix product's inputs in float8, put in the program's place;
for a training cell also the faults planted in the reference (half of
every batch left out) and, on more than one chip, in the program (no
collective moves any data).  One JSON line per reading on standard output.
"""

import argparse
import gc
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def emit(**kw):
    print(json.dumps(kw), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=0.5)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    from repro.runtime import compile_cache
    compile_cache.enable()
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    c = harness.resolve(bench, args.workload)
    devs = harness.devices(c["cell"]["chips"])
    drv = harness.driver(c["mix"])
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    seeds = [int(s) for s in args.seeds.split(",")]

    def program(seed):
        import counts
        rec = types.SimpleNamespace(
            cell=c["cell"], config=c["config"], mix=c["mix"],
            chips=len(devs), device_kind=devs[0].device_kind,
            peaks=counts.peaks(devs[0].device_kind), trace=None)
        out = drv.measure(c, rec, devs, seed, args.seconds,
                          harness.Tracer(False, 0.0),
                          harness.CompileCounter.start(),
                          time.perf_counter(), log)
        drv.release(out)
        gc.collect()
        return out

    for i, seed in enumerate(seeds):
        out = program(seed)
        if c["mix"]["kind"] == "serve":
            modes = ("f32", "fp8") if i < args.control_seeds else ("f32",)
            g = drv.gaps(c, out, seed, modes)
            emit(seed=seed, reading="sound", served_logit_gap=g["f32"])
            if "fp8" in g:
                emit(seed=seed, reading="control", served_logit_gap=g["fp8"])
            continue
        floor = c["limits"]["moved_floor_share"]
        ref = drv.reference_run(c, out, seed, devs)
        prog = (out["losses"], out["gnorms"], out["dnorms"])
        emit(seed=seed, reading="sound", **drv.compare(prog, ref, floor))
        if i >= args.control_seeds:
            continue
        ctl = drv.reference_run(c, out, seed, devs, mode="fp8")
        emit(seed=seed, reading="control", **drv.compare(ctl, ref, floor))
        half = drv.reference_run(c, out, seed, devs,
                                 rows=c["mix"]["global_batch"] // 2)
        emit(seed=seed, reading="half_batch",
             **drv.compare(half, ref, floor))
        if len(devs) > 1:
            import jax
            keep = jax.lax.ppermute
            jax.lax.ppermute = lambda x, axis_name, perm: x
            try:
                bad = program(seed)
            finally:
                jax.lax.ppermute = keep
            emit(seed=seed, reading="no_exchange",
                 **drv.compare((bad["losses"], bad["gnorms"],
                                bad["dnorms"]), ref, floor))
    return 0


if __name__ == "__main__":
    sys.exit(main())
