#!/usr/bin/env python3
"""The device's idle time in a serving cell, attributed to the phases of
the program's scheduler (not run by the benchmark itself).

    python3 benchmarks/chip/serve_spans.py --workload <name> --seed <n> \\
        --seconds 45 [--trace 1] [--python-tracer 1]

The program marks its serving phases with profiler spans
(``repro.serve.engine``): ``serve.step`` (metadata ``step_num``) holds
``serve.admit``, ``serve.prefill_chunk`` (``rid``, ``chunk``),
``serve.decode`` (``slots``) and ``serve.sample`` (``rid`` after a
prompt's last chunk); the page pool's ``serve.pages`` sits inside a chunk
or a decode.  They share the profiler's clock with the device's events.
``host_spans`` reads them, and the benchmark's own ``bench.*`` spans,
from a trace; ``split`` gives each span's self time (its interval less
its children's) and the device idle that falls in it; ``readings`` turns
that into per-step numbers.

The command runs a cell's set-up and window as ``run.py`` does, with the
profiler over the window's last ``trace_seconds`` where ``--trace 1``,
and prints one JSON line: ``itl_p95_ms`` over the window, the token gaps
(p50, p95) and decode tokens per second over its last ``trace_seconds``
(the same span whether traced or not, so that two runs on one seed give
the cost of tracing), the readers of the cell's traced per-layer
metrics, and, traced, the split.  It runs no correctness check.
``--python-tracer 0`` turns off the profiler's tracing of every Python
call, which the benchmark's own traced run (``harness.Tracer``, the
profiler's defaults) keeps on; the annotated spans are traced either
way.
"""

import argparse
import bisect
import glob
import json
import math
import os
import shutil
import statistics
import sys
import time
import types

import harness
import trace_reduce

T_START = time.perf_counter()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
PREFIXES = ("bench.", "serve.")


def host_spans(path: str) -> list:
    """(start_ns, end_ns, name, metadata) of the ``bench.*`` and
    ``serve.*`` host spans in the newest trace under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append((s, s + int(ev.duration_ns), ev.name,
                                dict(ev.stats)))
    return out


def split(spans, gaps, window) -> dict:
    """The device idle time ``gaps`` (merged, sorted [start, end) ns, all
    within ``window``) over the host ``spans`` that started in the
    window.  A span's self idle is the idle in its interval less the idle
    in its children's.

    step_idle_ns: the idle in each ``serve.step``; step_split_ns: self
    idle per span name, over the spans in a ``serve.step`` (itself
    included) and the ``bench.step`` spans, whose self time is the
    harness's own work around ``step()``; self_idle_ns: self idle per
    span name over all spans; decode_slots: each ``serve.decode``'s
    ``slots``; idle_gaps: the ten longest gaps, each named by the
    innermost span that covers its middle; idle_ns: all idle."""
    spans = sorted((sp for sp in spans if window[0] <= sp[0] < window[1]
                    and sp[2] != "bench.window"),
                   key=lambda sp: (sp[0], -sp[1]))
    ends = [e for _, e in gaps]

    def idle(s, e):
        n, i = 0, bisect.bisect_right(ends, s)
        while i < len(gaps) and gaps[i][0] < e:
            n += min(e, gaps[i][1]) - max(s, gaps[i][0])
            i += 1
        return n

    own = [idle(s, e) for s, e, _, _ in spans]
    self_idle = list(own)
    in_step, stack = [], []
    for i, (s, e, name, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] < e:
            stack.pop()
        parent = stack[-1] if stack else None
        if parent is not None:
            self_idle[parent] -= own[i]
        in_step.append(name == "serve.step"
                       or (parent is not None and in_step[parent]))
        stack.append(i)
    step_split, by_name = {}, {}
    for i, (_, _, name, _) in enumerate(spans):
        by_name[name] = by_name.get(name, 0) + self_idle[i]
        if in_step[i] or name == "bench.step":
            step_split[name] = step_split.get(name, 0) + self_idle[i]

    def innermost(t):
        best = "bench.none"
        for s, e, name, _ in spans:
            if s > t:
                break
            if t < e:
                best = name
        return best
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return {"step_idle_ns": [own[i] for i, sp in enumerate(spans)
                             if sp[2] == "serve.step"],
            "step_split_ns": step_split, "self_idle_ns": by_name,
            "decode_slots": [sp[3]["slots"] for sp in spans
                             if sp[2] == "serve.decode"],
            "idle_gaps": [[innermost((s + e) // 2), (e - s) / 1e9]
                          for s, e in longest],
            "idle_ns": sum(e - s for s, e in gaps)}


def readings(sp: dict) -> dict:
    """Per ``serve.step`` of the split: the device idle inside it
    (``serve.step_idle_ms``) and in the self time of its ``serve.sample``
    spans (``serve.sample_idle_ms``); the mean ``slots`` of the decode
    programs (``serve.decode_batch``).  Empty without ``serve.*`` spans."""
    steps = sp["step_idle_ns"]
    if not steps:
        return {}
    out = {"serve.step_idle_ms": statistics.fmean(steps) / 1e6,
           "serve.sample_idle_ms":
           sp["step_split_ns"].get("serve.sample", 0) / len(steps) / 1e6}
    if sp["decode_slots"]:
        out["serve.decode_batch"] = statistics.fmean(sp["decode_slots"])
    return out


class LightTracer(harness.Tracer):
    """``harness.Tracer`` with the profiler's Python tracer off."""

    def poll(self, now: float, window_end: float) -> None:
        if not self.on or self.t0 is not None \
                or now < window_end - self.seconds:
            return
        import tempfile
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.t0 = time.perf_counter()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--python-tracer", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import counts
    from repro.runtime import compile_cache
    compile_cache.enable()
    bench = harness.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    c = harness.resolve(bench, args.workload)
    devs = harness.devices(c["cell"]["chips"])
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    kind = devs[0].device_kind
    rec = types.SimpleNamespace(
        cell=c["cell"], config=c["config"], mix=c["mix"], chips=len(devs),
        device_kind=kind, peaks=counts.peaks(kind), trace=None)
    drv = harness.driver(c["mix"])
    traced_s = float(c["mix"].get("trace_seconds", args.seconds))
    tracer = (harness.Tracer if args.python_tracer
              else LightTracer)(bool(args.trace), traced_s)
    out = drv.measure(c, rec, devs, args.seed, args.seconds, tracer,
                      harness.CompileCounter.start(), T_START, log)
    drv.release(out)
    line = {"workload": args.workload, "seed": args.seed,
            "trace": args.trace, "python_tracer": args.python_tracer,
            "device": kind}
    if tracer.dir is not None:
        try:
            tr = trace_reduce.load(tracer.dir)
            spans = host_spans(tracer.dir)
        finally:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        win = next((s, e) for s, e, n in tr["host"] if n == "bench.window")
        rec.trace = trace_reduce.reduce(tr, win)
        ops = tr["devices"][min(tr["devices"])]["ops"]
        busy = trace_reduce.union(trace_reduce.clip(
            [(s, e) for s, e, _ in ops], win))
        sp = split(spans, trace_reduce.subtract([win], busy), win)
        line.update(readings(sp))
        line["split_ms"] = {k: v / 1e6
                            for k, v in sorted(sp["step_split_ns"].items())}
        line["steps"] = len(sp["step_idle_ns"])
        line["self_idle_ms"] = {k: v / 1e6 for k, v in
                                sorted(sp["self_idle_ns"].items())}
        line["idle_gaps"] = sp["idle_gaps"]
        line["window_idle_ms"] = sp["idle_ns"] / 1e6
        line["programs"] = {n: [len(d), statistics.fmean(d) / 1e6]
                            for n, d in sorted(rec.trace["programs"].items())}
        log(f"step idle split (ms, {line['steps']} steps): "
            f"{line['split_ms']}")
    w1 = rec.window[1]
    tail = sorted(b - a for ts in rec.book.times.values()
                  for a, b in zip(ts, ts[1:]) if w1 - traced_s <= b < w1)
    metric = lambda n: harness.load_module(  # noqa: E731
        os.path.join(HERE, "metrics", n + ".py"))
    line["itl_p95_ms"] = metric("itl_p95_ms").read(rec)
    line["last_traced_s"] = {
        "itl_p50_ms": 1e3 * tail[math.ceil(0.5 * len(tail)) - 1],
        "itl_p95_ms": 1e3 * tail[math.ceil(0.95 * len(tail)) - 1],
        "decode_tokens_per_s": len(tail) / traced_s}
    if rec.trace is not None:
        for m in harness.metrics_for(bench, args.workload, True):
            line[m["name"]] = metric(m["name"]).read(rec)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
