"""One run of one cell: the data-driven core behind ``run.py``.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, the configuration file that entry names (and the
family module ``families/<family>.py`` that the file names), the traffic
mix ``traffic/<mix>.json`` (whose ``kind`` picks the driver,
``drive_<kind>.py``), the limits of its correctness check
``limits/<workload>.json``, and each metric's reader
``metrics/<metric>.py``.  Adding a cell, a mix or a metric adds files.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import sys
import time
import types
from typing import List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_module(path: str):
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell ``workload``: its entry, configuration, mix and limits,
    read from the checkout at ``root`` (tests point it at small ones)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(os.path.join(root, conf["file"]))
    config["_dir"] = os.path.dirname(os.path.join(root, conf["file"]))
    data = os.path.join(root, os.path.relpath(HERE, ROOT))
    mix = read_json(os.path.join(data, "traffic", cell["traffic"] + ".json"))
    limits = read_json(os.path.join(data, "limits", workload + ".json"))
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "bench": bench}


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or workload in m["workloads"]]


def reference(config: dict):
    return load_module(os.path.join(config["_dir"], config["reference"]))


def family(config: dict):
    """``families/<family>.py``: what the yardstick counts and checks for
    the configuration's architecture."""
    return load_module(os.path.join(HERE, "families",
                                    config["family"] + ".py"))


def driver(mix: dict):
    return load_module(os.path.join(HERE, f"drive_{mix['kind']}.py"))


def devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r}); "
                     f"this benchmark runs only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts XLA backend compilations in this process (a compile in the
    measured window is a fault of the set-up)."""

    n = 0
    _on = False

    @classmethod
    def start(cls) -> "CompileCounter":
        if not cls._on:
            import jax
            jax.monitoring.register_event_duration_secs_listener(cls._event)
            cls._on = True
        return cls()

    @classmethod
    def _event(cls, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            CompileCounter.n += 1


class Tracer:
    """The profiler over the last ``seconds`` of the measured window
    (``--trace 1``), with the benchmark's own host spans (``bench.*``) in
    it.  It stops when the window closes, so that writing the trace out
    falls outside the window."""

    def __init__(self, on: bool, seconds: float):
        self.on = on
        self.seconds = seconds
        self.dir = None
        self.t0 = self.t1 = None
        self._ann = None

    def poll(self, now: float, window_end: float) -> None:
        """Start once ``now`` is within ``seconds`` of the window's end."""
        if not self.on or self.t0 is not None \
                or now < window_end - self.seconds:
            return
        import tempfile
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()
        self.t0 = time.perf_counter()

    def span(self, name: str):
        if self.active:
            import jax
            return jax.profiler.TraceAnnotation(name)
        import contextlib
        return contextlib.nullcontext()

    @property
    def active(self) -> bool:
        return self.t0 is not None and self.t1 is None

    def stop(self):
        if not self.active:
            return
        import jax
        self._ann.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self, log) -> Optional[dict]:
        if self.dir is None:
            return None
        import shutil
        import trace_reduce
        try:
            tr = trace_reduce.load(self.dir)
            win = [(s, e) for s, e, n in tr["host"] if n == "bench.window"]
            if not win:
                raise RuntimeError("the trace holds no bench.window span")
            red = trace_reduce.reduce(tr, win[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        log(f"trace: {red['devices']} devices, window "
            f"{red['window_ns'] / 1e9:.4f} s, busy {red['busy_ns'] / 1e9:.4f}"
            f" s, collectives {red['collective_ns'] / 1e9:.4f} s, exposed "
            f"{red['exposed_ns'] / 1e9:.4f} s")
        for name, d in sorted(red["programs"].items()):
            log(f"trace program {name}: {len(d)} runs, mean "
                f"{sum(d) / len(d) / 1e6:.4f} ms")
        log(f"trace top ops: {red['top_ops']}")
        log(f"trace idle gaps: {red['idle_gaps']}")
        return red


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        bench: Optional[dict] = None, root: str = ROOT,
        require_tpu: bool = True, t_start: Optional[float] = None,
        log=None, persistent_cache: bool = True) -> dict:
    """Set up, measure, check; returns the result object (the last line)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    if bench is None:
        bench = read_json(os.path.join(root, "BENCHMARK.json"))
    c = resolve(bench, workload, root)
    if persistent_cache:
        from repro.runtime import compile_cache
        compile_cache.enable()
    devs = devices(c["cell"]["chips"], require_tpu)
    import counts
    kind = devs[0].device_kind
    rec = types.SimpleNamespace(
        cell=c["cell"], config=c["config"], mix=c["mix"],
        chips=len(devs), device_kind=kind,
        peaks=counts.peaks(kind) if require_tpu else counts.PEAKS[
            "TPU v5 lite"], trace=None)
    drv = driver(c["mix"])
    tracer = Tracer(trace, float(c["mix"].get("trace_seconds", seconds)))
    compiles = CompileCounter.start()
    out = drv.measure(c, rec, devs, seed, seconds, tracer, compiles,
                      t_start, log)
    rec.memory_peak_bytes = memory_peak(devs)
    rec.trace = tracer.reduce(log)
    drv.release(out)
    gc.collect()
    log(f"compiles in the measured window: {rec.window_compiles}")
    checks = drv.check(c, rec, out, seed, devs, log)
    correct = all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        log(f"compared {name} = {v!r} (limit {lim!r})"
            f"{'' if v <= lim else '  FAILED'}")
    metrics = {}
    for m in metrics_for(bench, workload, trace):
        val = load_module(os.path.join(HERE, "metrics",
                                       m["name"] + ".py")).read(rec)
        if val is not None:
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs),
              "memory_peak_bytes": rec.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": rec.attempted,
              "failed": rec.failed, "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace["busy_ns"] / 1e9
        device["window_s"] = rec.trace["window_ns"] / 1e9
        result["breakdown"] = {"device_ops": rec.trace["top_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in checks}
    return result
