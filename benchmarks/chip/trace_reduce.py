"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
intervals: per device, its operations and its programs (XLA modules); on
the host, the spans the benchmark annotated (names starting ``bench.``).
``reduce`` then works on those intervals alone, so a test can feed it a
synthetic trace.

Collectives are recognised by their HLO names.  An asynchronous one is a
``-start`` op and a ``-done`` op; it is in flight from the start of the
first to the end of the second (paired first in, first out per kind).
The part of a collective's time in which no other operation runs on the
same device is its exposed time.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

Interval = Tuple[int, int]          # [start_ns, end_ns)

COLLECTIVE = re.compile(
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ragged-all-to-all|collective-broadcast)")
ASYNC = re.compile(r"-(start|done)(\.\d+)?$")
# ops that hold others (a loop and its body): busy, but neither compute of
# their own nor a line of the breakdown
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)*$")


def op_name(name: str) -> str:
    """An event's op name: TPU traces name an op event by its whole HLO
    instruction (``%fusion.3 = bf16[...] fusion(...)``); keep ``fusion.3``
    so that nothing in its operands is taken for the op."""
    if " = " in name:
        name = name.split(" = ", 1)[0]
    return name.lstrip("%")


def _events(line) -> Iterable[Tuple[int, int, str]]:
    for ev in line.events:
        s = int(ev.start_ns)
        yield s, s + int(ev.duration_ns), op_name(ev.name)


def load(path: str) -> dict:
    """{"devices": {plane name: {"ops": [...], "modules": [...]}},
    "host": [(start, end, name)]} from the newest xplane under ``path``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    data = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"].extend(_events(line))
                elif line.name == "XLA Modules":
                    dev["modules"].extend(_events(line))
            devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e[2].startswith("bench."))
    return {"devices": devices, "host": sorted(host)}


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals ``a`` that no interval of the
    (merged) ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def collective_intervals(ops) -> List[Interval]:
    """In-flight intervals of the collectives among ``ops`` (start, end,
    name): a synchronous one for its duration, an asynchronous one from
    its ``-start`` to its ``-done``."""
    pending: Dict[str, List[int]] = {}
    out = []
    for s, e, name in sorted(ops):
        m = COLLECTIVE.search(name)
        if not m:
            continue
        a = ASYNC.search(name)
        if a is None:
            out.append((s, e))
        elif a.group(1) == "start":
            pending.setdefault(m.group(1), []).append(s)
        else:
            starts = pending.get(m.group(1))
            out.append((starts.pop(0) if starts else s, e))
    for starts in pending.values():          # started, never finished here
        out.extend((s, s) for s in starts)
    return out


def op_base(name: str) -> str:
    """An op's name without its HLO numbering (``fusion.12`` -> ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", name)


# ---------------------------------------------------------------------------
# The reduction
# ---------------------------------------------------------------------------

def reduce(trace: dict, window: Interval) -> dict:
    """Per-device numbers over ``window`` (ns), and their mean or worst:

    busy_ns: union of operation intervals; idle_share: 1 - busy / window;
    collective_ns / exposed_ns: union of collective in-flight intervals,
    and the part of it with no other operation on the device;
    programs: {module name: [device durations]} of programs that started
    in the window, and runs: their (start, end, name); op_ns: {op base
    name: summed duration}; gaps: idle intervals, longest first, each
    named by the host span (``bench.*``) that covers its middle; spans:
    the host spans that started in the window."""
    span = window[1] - window[0]
    per = {}
    for name, dev in sorted(trace["devices"].items()):
        ops = [(s, e, n) for s, e, n in dev["ops"]]
        busy = union(clip([(s, e) for s, e, _ in ops], window))
        coll = union(clip(collective_intervals(ops), window))
        compute = union(clip([(s, e) for s, e, n in ops
                              if not COLLECTIVE.search(n)
                              and not CONTAINER.match(n)], window))
        runs = sorted((s, e, n) for s, e, n in dev["modules"]
                      if window[0] <= s < window[1])
        programs: Dict[str, List[int]] = {}
        for s, e, n in runs:
            programs.setdefault(n, []).append(e - s)
        op_ns: Dict[str, int] = {}
        for s, e, n in ops:
            cs = clip([(s, e)], window)
            if cs and not CONTAINER.match(n):
                op_ns[op_base(n)] = op_ns.get(op_base(n), 0) + total(cs)
        per[name] = {"busy_ns": total(busy),
                     "idle_share": 1.0 - total(busy) / span if span else 0.0,
                     "collective_ns": total(coll),
                     "exposed_ns": total(subtract(coll, compute)),
                     "programs": programs, "runs": runs, "op_ns": op_ns,
                     "gaps": subtract([window], busy)}
    host = trace.get("host", [])

    def host_at(t: int) -> str:
        best = "bench.none"
        for s, e, n in host:                 # innermost span wins
            if s <= t < e and n != "bench.window":
                best = n
        return best

    n = max(len(per), 1)
    first = per[min(per)] if per else {"gaps": [], "op_ns": {}}
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    op_ns = sorted(first["op_ns"].items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_ns": span,
        "devices": len(per),
        "busy_ns": sum(p["busy_ns"] for p in per.values()) / n,
        "idle_share_worst": max((p["idle_share"] for p in per.values()),
                                default=1.0),
        "collective_ns": sum(p["collective_ns"] for p in per.values()) / n,
        "exposed_ns": sum(p["exposed_ns"] for p in per.values()) / n,
        "programs": first.get("programs", {}),
        "runs": first.get("runs", []),
        "spans": [h for h in host if window[0] <= h[0] < window[1]
                  and h[2] != "bench.window"],
        "top_ops": [[k, v / 1e9] for k, v in op_ns],
        "idle_gaps": [[host_at((s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps],
    }
