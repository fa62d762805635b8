#!/usr/bin/env python3
"""Find a serving cell's knee once, by a sweep of offered rates on the
chip (not run by the benchmark itself).

    python3 benchmarks/chip/sweep.py --workload <name> \\
        --rates 0.35,0.40,0.45 --seconds 51 [--seed 1]

Each rate runs the cell's set-up, its warm arrivals (the mix's
``warm_s``) and its window in this one process, with the mix's rate
replaced and the schedule drawn by the same generator as the cell's.  One
JSON line per rate: requests due in the window, requests and output
tokens finished in it and offered to it per second, the backlog
(requests due and not finished, in flight included) when the window
opened and when it closed, how long a request that finished stayed in
the system (due to last token), and the median and 90th percentile of
the time to first token.  The knee is the highest rate whose backlog does
not grow over the window.
"""

import argparse
import copy
import json
import math
import os
import statistics
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import harness
    from repro.runtime import compile_cache
    compile_cache.enable()
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    base = harness.resolve(bench, args.workload)
    devs = harness.devices(base["cell"]["chips"])
    drv = harness.driver(base["mix"])
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(base)
        c["mix"]["rate_per_s"] = rate
        rec = types.SimpleNamespace(trace=None)
        out = drv.measure(c, rec, devs, args.seed, args.seconds,
                          harness.Tracer(False, 0.0),
                          harness.CompileCounter.start(),
                          time.perf_counter(), log)
        drv.release(out)
        b, (w0, w1) = out["book"], rec.window
        span = w1 - w0

        def backlog(t):
            return sum(1 for r, d in b.due.items()
                       if d <= t and not (b.reqs[r].done
                                          and b.times[r][-1] <= t))
        done = [r for r in b.reqs if b.reqs[r].done
                and w0 <= b.times[r][-1] < w1]
        stay = [b.times[r][-1] - b.due[r] for r in b.reqs
                if b.reqs[r].done and b.times[r]]
        toks = sum(1 for ts in b.times.values() for t in ts if w0 <= t < w1)
        offered = sum(b.reqs[r].max_new for r in rec.in_window)
        ttft = sorted((b.times[r][0] if b.times[r] else math.inf) - b.due[r]
                      for r in rec.in_window)
        print(json.dumps({
            "rate": rate, "warm_s": c["mix"]["warm_s"], "window_s": span,
            "due": len(rec.in_window),
            "finished_per_s": len(done) / span,
            "tokens_per_s": toks / span,
            "offered_tokens_per_s": offered / span,
            "backlog_open": backlog(w0), "backlog_close": backlog(w1),
            "stay_mean_s": statistics.fmean(stay) if stay else None,
            "stay_max_s": max(stay) if stay else None,
            "ttft_p50_s": ttft[len(ttft) // 2] if ttft else None,
            "ttft_p90_s": ttft[math.ceil(0.9 * len(ttft)) - 1]
            if ttft else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
