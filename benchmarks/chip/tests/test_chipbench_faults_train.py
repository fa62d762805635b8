"""The training cell's check, driven end to end on the CPU at a small size
(four host devices) with the look for a chip skipped: a sound run is
correct, and each fault planted under the timed path makes it false."""

import json
import os
import subprocess
import sys

import chipbench_tiny as tiny

RUNS = """
import json, sys
sys.path[:0] = {paths!r}
import jax
import chipbench_tiny as tiny
from repro.models import model as model_mod
from repro.train import trainer
CELL, tmp, out = "mamba2_train_dp4_zero", {tmp!r}, {{}}

def run(name):
    out[name] = tiny.run(tmp + "/" + name, CELL)

run("sound")

make = trainer.make_train_step
def unchanged(*a, **k):                   # the step returns its state
    step = make(*a, **k)
    return lambda state, batch: (state, step(state, batch)[1])
trainer.make_train_step = unchanged
run("state_unchanged")
trainer.make_train_step = make

loss = model_mod.Model.loss
def half(self, params, batch):            # half of each chip's rows
    return loss(self, params, {{k: v[: v.shape[0] // 2]
                               for k, v in batch.items()}})
model_mod.Model.loss = half
run("half_batch")
model_mod.Model.loss = loss

jax.lax.ppermute = lambda x, axis_name, perm: x   # nothing crosses chips
run("no_exchange")
print(json.dumps(out))
"""


def runs(tmp):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RUNS.format(paths=[tiny.CHIP, os.path.dirname(__file__),
                              os.path.join(tiny.ROOT, "src")], tmp=tmp)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_each_fault_fails(tmp_path):
    out = runs(str(tmp_path))
    sound = out["sound"]
    assert sound["correct"], sound["compared"]
    assert sound["metrics"]["train_tokens_per_s"]["value"] > 0
    assert list(sound)[-1] == "compared"
    assert out["state_unchanged"]["compared"]["change_norm_gap"][
        "value"] >= 0.99
    for fault in ("state_unchanged", "half_batch", "no_exchange"):
        assert not out[fault]["correct"], (fault, out[fault]["compared"])
