"""Shared benchmark helpers (single CPU host; timings are trace/dispatch
and HLO-structure measurements, roofline terms come from the dry-run)."""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, List

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cpu_child_env(devices: int = 8) -> dict:
    """Environment for a bench child process that emulates ``devices``
    devices on the host CPU.  Such a child counts bytes and collectives;
    it measures nothing on a chip.  Where this process sees a TPU it
    holds it, so the bench refuses to start instead of spawning children
    that would compete for the chip."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        raise RuntimeError(
            "this bench runs child processes on emulated CPU devices and "
            "refuses to start where a TPU is visible; on the chip run "
            "`python chip_smoke.py`")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = (os.path.join(REPO, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    return env


def time_python(fn: Callable, repeat: int = 200, warmup: int = 5) -> float:
    """Median wall µs of a Python-level call (dispatch/trace cost)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter_ns()
        fn()
        ts.append((time.perf_counter_ns() - t0) / 1e3)
    return float(np.median(ts))


def time_jitted(fn: Callable, *args, repeat: int = 20) -> float:
    """Median wall µs of an already-compiled jitted call."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter_ns()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter_ns() - t0) / 1e3)
    return float(np.median(ts))


def hlo_op_counts(fn: Callable, *args) -> Dict[str, int]:
    """Count op kinds in the optimized HLO of ``fn`` (+ 'total')."""
    import re
    from collections import Counter
    txt = jax.jit(fn).lower(*args).compile().as_text()
    ops = Counter(re.findall(r"= \S+ ([\w\-]+)\(", txt))
    out = dict(ops)
    out["total"] = sum(ops.values())
    return out


class Table:
    def __init__(self, title: str, columns: List[str]):
        self.title = title
        self.columns = columns
        self.rows: List[List] = []

    def add(self, *row):
        self.rows.append(list(row))

    def render(self) -> str:
        widths = [max(len(str(c)), *(len(str(r[i])) for r in self.rows))
                  if self.rows else len(str(c))
                  for i, c in enumerate(self.columns)]
        def fmt(row):
            return "  ".join(str(v).ljust(w) for v, w in zip(row, widths))
        lines = [f"== {self.title} ==", fmt(self.columns),
                 fmt(["-" * w for w in widths])]
        lines += [fmt(r) for r in self.rows]
        return "\n".join(lines)

    def print(self):
        print(self.render(), flush=True)
        return self
