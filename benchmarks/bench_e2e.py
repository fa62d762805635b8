"""P1+P2+P3 combined (paper §5: "they can be combined").

End-to-end reduced-model training on 8 emulated devices: the conventional
stack (auto/GSPMD, monolithic engine semantics) vs the composed system
(thin library + tiers + per-function protocols), plus the compressed
variant (feature injected in the protocol).  Reports loss parity, step
wall time (CPU emulation — directional only), and HLO collective counts.
"""

from __future__ import annotations

import subprocess
import sys

from benchmarks.common import Table, cpu_child_env

CODE = r"""
import jax, jax.numpy as jnp, numpy as np, time, re
from repro.configs import get_config
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train import TrainCfg, make_train_state, make_train_step, trainer
from repro.core import CollectiveEngine, EngineConfig, compose_library, registry, topology_from_mesh
from repro.data import SyntheticLMDataset
from repro.parallel.sharding import named_shardings
from repro.runtime import substrate

mesh = substrate.make_mesh((4, 2), ("data", "model"))
cfg = get_config("granite-34b", reduced=True)
model = build_model(cfg)
opt = make_optimizer("adamw", lr=1e-3)
ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=64, global_batch=8)
engine = CollectiveEngine(topology_from_mesh(mesh),
                          library=compose_library(registry.ALL_FUNCTIONS),
                          config=EngineConfig(mode="composed"))
for mode, bucket in (("auto", False), ("composed", False),
                     ("composed", True), ("compressed", True)):
    tcfg = TrainCfg(sync_mode=mode, data_axes=("data",), bucket_grads=bucket)
    step = make_train_step(model, opt, tcfg, mesh=mesh, engine=engine)
    with substrate.set_mesh(mesh):
        state = make_train_state(model, opt, jax.random.PRNGKey(0), cfg=tcfg)
        state = jax.device_put(state, named_shardings(mesh, trainer.state_specs(model, opt, tcfg)))
        jstep = jax.jit(step, donate_argnums=0)
        batches = [ds.sharded_batch(i, mesh, batch_axes=("data",)) for i in range(8)]
        compiled = jstep.lower(state, batches[0]).compile()
        colls = len(re.findall(r"= \S+ (?:all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all)", compiled.as_text()))
        state, m = jstep(state, batches[0])
        jax.block_until_ready(m["loss"])
        ts = []
        for i in range(1, 8):
            t0 = time.perf_counter_ns()
            state, m = jstep(state, batches[i])
            jax.block_until_ready(m["loss"])
            ts.append((time.perf_counter_ns() - t0) / 1e6)
        print(f"{mode}{'+bucket' if bucket else ''},{float(m['loss']):.4f},"
              f"{np.median(ts):.1f},{colls}")
"""


def run() -> Table:
    t = Table("bench_e2e: conventional vs composed system (paper §5)",
              ["system", "loss@8", "ms/step (CPU emu)", "HLO collectives"])
    proc = subprocess.run([sys.executable, "-c", CODE], env=cpu_child_env(),
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        t.add("(subprocess failed)", proc.stderr[-300:], "", "")
        return t
    for line in proc.stdout.strip().splitlines():
        mode, loss, ms, colls = line.split(",")
        t.add(mode, loss, ms, colls)
    return t


def main():
    run().print()


if __name__ == "__main__":
    main()
