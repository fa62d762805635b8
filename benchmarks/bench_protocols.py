"""P3 — a protocol for every function (paper §4).

Claims measured:
  (a) no single protocol wins everywhere: the alpha-beta cost model's
      per-(function, size, topology) winner table with crossover points.
  (b) the predicted effects are real in compiled code: HLO collective-op
      counts / schedule shapes differ per protocol, and single-host
      wall-clock of the compiled schedules (8 emulated devices) tracks
      the latency-vs-bandwidth prediction directionally.
  (c) topology-awareness: the hierarchical cross-pod protocol moves
      (p_intra)x fewer bytes over DCN than a flat ring.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Table, cpu_child_env
from repro.core import costmodel, topology_from_mesh_shape


def run() -> list:
    tables = []
    topo = topology_from_mesh_shape(("data", "model"), (16, 16))

    # (a) winner tables per collective and message size
    for coll in ("all_reduce", "all_gather", "all_to_all", "broadcast"):
        t = Table(f"bench_protocols: {coll} over ICI axis p=16",
                  ["bytes", "winner", "est us", "runner-up", "gap"])
        for nbytes in (1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 26, 1 << 30):
            c = costmodel.choose_protocol(coll, nbytes, topo, "data")
            alts = [a for a in c.alternatives if np.isfinite(a[1])]
            ru = alts[1] if len(alts) > 1 else ("-", float("inf"))
            gap = (f"{ru[1] / c.est_seconds:.2f}x"
                   if np.isfinite(ru[1]) else "-")
            t.add(f"{nbytes:>11,d}", c.protocol, f"{c.est_seconds * 1e6:.1f}",
                  ru[0], gap)
        tables.append(t)

    # (c) hierarchical vs flat across pods
    topo2 = topology_from_mesh_shape(("pod", "data", "model"), (2, 16, 16))
    t = Table("bench_protocols: cross-pod all_reduce (256 MB grads)",
              ["protocol", "DCN bytes/device", "est ms"])
    n = 256 * 2**20
    flat = costmodel.cost_allreduce_ring(n, topo2, "pod")
    t.add("flat ring over DCN", f"{2 * n * (2 - 1) // 2:,d}",
          f"{flat * 1e3:.1f}")
    hier = costmodel.cost_allreduce_hierarchical(
        n, topo2, ("data", "model"), "pod")
    t.add("hierarchical (intra-RS -> DCN AR -> intra-AG)",
          f"{2 * (n // 256):,d}", f"{hier * 1e3:.1f}")
    t.add("DCN traffic ratio", f"{256}x less", "")
    tables.append(t)

    # (b) compiled-schedule reality check on 8 emulated devices
    tables.append(_compiled_check())
    return tables


def _compiled_check() -> Table:
    import subprocess
    import sys
    t = Table("bench_protocols: compiled schedules (8 host devices)",
              ["protocol", "HLO collective ops", "wall us (1MB AR)"])
    code = r"""
import jax, jax.numpy as jnp, numpy as np, time, re
from functools import partial
from jax.sharding import PartitionSpec as P
from repro.core import CollectiveEngine, EngineConfig, compose_library, registry, topology_from_mesh
from repro.runtime import substrate
mesh = substrate.make_mesh((8,), ("data",))
x = jnp.asarray(np.random.RandomState(0).randn(8, 131072).astype(np.float32))
for proto in ("xla_default", "ring", "bidir_ring", "recursive_doubling", "recursive_halving"):
    eng = CollectiveEngine(topology_from_mesh(mesh),
                           library=compose_library(registry.ALL_FUNCTIONS),
                           config=EngineConfig(force_protocol={"all_reduce": proto}))
    @partial(substrate.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"), check_vma=False)
    def f(v):
        return eng.all_reduce(v[0], "data")[None]
    jf = jax.jit(f)
    compiled = jf.lower(x).compile()
    ops = len(re.findall(r"= \S+ (?:all-reduce|collective-permute|all-gather|reduce-scatter)\(", compiled.as_text()))
    out = jf(x); jax.block_until_ready(out)
    ts = []
    for _ in range(10):
        t0 = time.perf_counter_ns(); jax.block_until_ready(jf(x)); ts.append((time.perf_counter_ns()-t0)/1e3)
    print(f"{proto},{ops},{np.median(ts):.0f}")
"""
    proc = subprocess.run([sys.executable, "-c", code], env=cpu_child_env(),
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        t.add("(subprocess failed)", proc.stderr[-200:], "")
        return t
    for line in proc.stdout.strip().splitlines():
        proto, ops, us = line.split(",")
        t.add(proto, ops, us)
    return t


def main():
    for t in run():
        t.print()
        print()


if __name__ == "__main__":
    main()
