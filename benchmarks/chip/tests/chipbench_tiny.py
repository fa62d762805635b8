"""Small copies of the benchmark's cells for tests on the CPU.

``make_root(tmp)`` writes a checkout-shaped directory: ``BENCHMARK.json``
with the real cells (and the training cell that waits for its bound),
whose configurations are the program's smoke-test sizes of the same
architectures (each family's ``TINY``, in float32) and whose mixes are
the real ones shrunk; the limits are the real files.  ``run`` drives the
harness over it with the look for a chip skipped.
"""

from __future__ import annotations

import json
import os
import shutil
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

# The four-chip training cell, not yet in BENCHMARK.json (its bound is
# unmeasured), with its metrics: the tests drive it all the same.
TRAIN_CELL = {
    "configs": [{"name": "mamba2-1.3b",
                 "source": "https://huggingface.co/state-spaces/mamba2-1.3b",
                 "file": "benchmarks/chip/configs/mamba2-1.3b.json",
                 "reduced": [], "why": "pure SSD stack"}],
    "workloads": [{"name": "mamba2_train_dp4_zero", "config": "mamba2-1.3b",
                   "traffic": "zero_adamw_2k", "chips": 4,
                   "why": "composed ZeRO-1 sync over data=4"}],
    "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s",
                    "better": "higher", "bound": 0.05,
                    "source": "host_clock",
                    "workloads": ["mamba2_train_dp4_zero"]}],
    "per_layer": [{"name": name, "unit": unit, "better": better,
                   "source": source, "layer": layer,
                   "moves": "train_tokens_per_s",
                   "workloads": ["mamba2_train_dp4_zero"]}
                  for name, unit, better, source, layer in (
                      ("train.mfu", "%", "higher", "host_clock",
                       "train step"),
                      ("device.idle_share.train", "%", "lower",
                       "device_trace", "device"),
                      ("comm.collective_ms.train", "ms", "lower",
                       "device_trace", "collectives"),
                      ("comm.exposed_ms.train", "ms", "lower",
                       "device_trace", "collectives"))],
}


def read(path):
    with open(path) as f:
        return json.load(f)


def bench():
    """BENCHMARK.json with ``TRAIN_CELL`` added where it is not there."""
    b = read(os.path.join(ROOT, "BENCHMARK.json"))
    for key, entries in TRAIN_CELL.items():
        names = {e["name"] for e in b[key]}
        b[key] += [e for e in entries if e["name"] not in names]
    return b


def tiny_config(name: str) -> dict:
    """The configuration file of ``name`` at the program's smoke size."""
    b = {c["name"]: c for c in bench()["configs"]}[name]
    conf = read(os.path.join(ROOT, b["file"]))
    conf.update(harness.family(conf).TINY)
    conf["_dir"] = os.path.join(CHIP, "configs")
    return conf


def _shrink(mix: dict) -> dict:
    mix = dict(mix)
    if mix["kind"] == "train":
        sizes = {str(mix["seq_len"]): "32", str(mix["global_batch"]): "4"}
        mix["launcher_args"] = [sizes.get(a, a) for a in mix["launcher_args"]]
        mix.update(seq_len=32, global_batch=4)
    else:
        mix.update(rate_per_s=6.0, warm_s=0.5, slots=4, max_len=64,
                   grace_s=20,
                   prompt={"median": 16, "sigma": 1.0, "min": 4, "max": 40},
                   output={"median": 8, "sigma": 0.8, "min": 2, "max": 16})
    mix["trace_seconds"] = 0.5
    return mix


def make_root(tmp: str) -> tuple:
    """(root, bench) of a tiny checkout under ``tmp``."""
    b = bench()
    data = os.path.join(tmp, "benchmarks", "chip")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d), exist_ok=True)
    for c in b["configs"]:
        conf = tiny_config(c["name"])
        with open(os.path.join(tmp, c["file"]), "w") as f:
            json.dump(conf, f)
        shutil.copy(os.path.join(CHIP, "configs", conf["reference"]),
                    os.path.join(data, "configs"))
    for w in b["workloads"]:
        mix = _shrink(read(os.path.join(CHIP, "traffic",
                                        w["traffic"] + ".json")))
        with open(os.path.join(data, "traffic", w["traffic"] + ".json"),
                  "w") as f:
            json.dump(mix, f)
        shutil.copy(os.path.join(CHIP, "limits", w["name"] + ".json"),
                    os.path.join(data, "limits"))
    return tmp, b


def run(tmp: str, workload: str, seed: int = 5, seconds: float = 0.5,
        trace: bool = False) -> dict:
    import harness
    root, b = make_root(tmp)
    return harness.run(workload, seed, seconds, trace, bench=b, root=root,
                       require_tpu=False, persistent_cache=False,
                       log=lambda m: None)
