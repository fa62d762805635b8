"""ZeRO-1 on the reduce-scatter/all-gather seam (PR 8).

The contract under test: gradients sync with ONLY the reduce-scatter
phase of the planned all-reduce, each data-parallel rank updates its
shard of a data-axis-sharded optimizer state, and updated params
all-gather back — with losses bit-identical to the unsharded composed
path at clip_norm=0, optimizer-state bytes per device shrinking ~DP×,
and sharded checkpoints restoring onto a different survivor mesh
(padded-flat leaves resize exactly: padding is trailing zeros).

Also covers this PR's satellite fixes: ``AdafactorCfg.min_dim_factored``
actually threaded through init/update/state_specs, checkpoint GC
surviving stray ``step_*`` names and reclaiming orphaned ``.tmp`` dirs,
and bf16 optimizer state surviving a save/restore round-trip bit-for-bit.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from conftest import run_subprocess_script

from repro.checkpoint.manager import (CheckpointManager, restore_checkpoint,
                                      save_checkpoint)
from repro.optim.optimizer import (AdafactorCfg, AdamWCfg, make_adafactor,
                                   make_adamw)
from repro.train import trainer


# ---------------------------------------------------------------------------
# TrainCfg surface
# ---------------------------------------------------------------------------

def test_zero_cfg_validation():
    with pytest.raises(ValueError, match="composed"):
        trainer.TrainCfg(sync_mode="auto", zero=True)
    with pytest.raises(ValueError, match="composed"):
        trainer.TrainCfg(sync_mode="compressed", zero=True)
    with pytest.raises(ValueError, match="bucket_grads"):
        trainer.TrainCfg(sync_mode="composed", zero=True, bucket_grads=True)
    # the valid combination constructs
    trainer.TrainCfg(sync_mode="composed", zero=True)


def test_zero_layout_needs_mesh_and_single_axis():
    cfg = trainer.TrainCfg(sync_mode="composed", zero=True,
                           data_axes=("data",))
    with pytest.raises(ValueError, match="mesh"):
        trainer.zero_layout(cfg, None)


def test_zero_pad_len_and_chunk_layout():
    assert trainer._zero_pad_len(10, 4) == 12
    assert trainer._zero_pad_len(12, 4) == 12
    x = jnp.arange(10, dtype=jnp.float32)
    # rank chunks concatenate back to [values, trailing zeros]
    chunks = [np.asarray(trainer._zero_chunk(x, 4, r)) for r in range(4)]
    flat = np.concatenate(chunks)
    np.testing.assert_array_equal(flat[:10], np.arange(10))
    np.testing.assert_array_equal(flat[10:], np.zeros(2))


# ---------------------------------------------------------------------------
# Satellite: AdafactorCfg.min_dim_factored is real, not a dead knob
# ---------------------------------------------------------------------------

def test_min_dim_factored_threaded_through():
    params = {"w": jnp.ones((8, 8), jnp.float32)}
    grads = {"w": jnp.full((8, 8), 0.1, jnp.float32)}

    small = make_adafactor(AdafactorCfg(min_dim_factored=16))
    st = small.init(params)
    assert set(st["f"]["w"]) == {"v"}, "8x8 < 16 must stay unfactored"
    _, st2, _ = small.update(grads, st, params)
    assert set(st2["f"]["w"]) == {"v"}

    big = make_adafactor(AdafactorCfg(min_dim_factored=4))
    st = big.init(params)
    assert set(st["f"]["w"]) == {"vr", "vc"}, "8x8 >= 4 must factor"
    _, st2, _ = big.update(grads, st, params)
    assert set(st2["f"]["w"]) == {"vr", "vc"}

    # state_specs must agree with init's factoring decision
    pspecs = {"w": P(None, "model")}
    abstract = jax.eval_shape(lambda: params)
    sp_small = small.state_specs(pspecs, abstract)
    assert set(sp_small["f"]["w"]) == {"v"}
    sp_big = big.state_specs(pspecs, abstract)
    assert set(sp_big["f"]["w"]) == {"vr", "vc"}
    assert sp_big["f"]["w"]["vr"] == P(None)
    assert sp_big["f"]["w"]["vc"] == P("model")


# ---------------------------------------------------------------------------
# Satellite: checkpoint round-trips and GC
# ---------------------------------------------------------------------------

def test_bf16_opt_state_roundtrip(tmp_path):
    opt = make_adamw(AdamWCfg(state_dtype=jnp.bfloat16))
    params = {"w": jnp.linspace(-1, 1, 12, dtype=jnp.float32).reshape(4, 3)}
    grads = {"w": jnp.full((4, 3), 0.25, jnp.float32)}
    state = opt.init(params)
    _, state, _ = opt.update(grads, state, params)
    assert state["m"]["w"].dtype == jnp.bfloat16

    d = str(tmp_path / "ck")
    save_checkpoint(d, 0, state)
    restored = restore_checkpoint(d, jax.eval_shape(lambda: state))
    assert restored["m"]["w"].dtype == jnp.bfloat16
    for k in ("m", "v"):
        a = np.asarray(state[k]["w"]).view(np.uint16)
        b = np.asarray(restored[k]["w"]).view(np.uint16)
        np.testing.assert_array_equal(a, b)


def test_restore_resize_1d(tmp_path):
    d = str(tmp_path / "ck")
    # a ZeRO-layout leaf: 13 logical values padded to 16 (DP=8 on n=13)
    padded = jnp.concatenate([jnp.arange(13, dtype=jnp.float32),
                              jnp.zeros(3, jnp.float32)])
    save_checkpoint(d, 0, {"v": padded, "w": jnp.ones((2, 2))})

    shrunk = {"v": jax.ShapeDtypeStruct((15,), jnp.float32),
              "w": jax.ShapeDtypeStruct((2, 2), jnp.float32)}
    with pytest.raises(ValueError, match="shape"):
        restore_checkpoint(d, shrunk)
    out = restore_checkpoint(d, shrunk, allow_resize_1d=True)
    np.testing.assert_array_equal(np.asarray(out["v"])[:13], np.arange(13))
    np.testing.assert_array_equal(np.asarray(out["v"])[13:], np.zeros(2))

    grown = {"v": jax.ShapeDtypeStruct((18,), jnp.float32),
             "w": jax.ShapeDtypeStruct((2, 2), jnp.float32)}
    out = restore_checkpoint(d, grown, allow_resize_1d=True)
    np.testing.assert_array_equal(np.asarray(out["v"])[:13], np.arange(13))
    np.testing.assert_array_equal(np.asarray(out["v"])[13:], np.zeros(5))

    # the flag is 1-D only: a 2-D mismatch still refuses
    bad = {"v": jax.ShapeDtypeStruct((16,), jnp.float32),
           "w": jax.ShapeDtypeStruct((3, 2), jnp.float32)}
    with pytest.raises(ValueError, match="shape"):
        restore_checkpoint(d, bad, allow_resize_1d=True)


def test_gc_skips_stray_names_and_reclaims_orphan_tmp(tmp_path):
    d = str(tmp_path / "ck")
    mgr = CheckpointManager(d, every=1, keep=2, async_=False)
    os.makedirs(os.path.join(d, "step_foo"))          # unparseable: skip
    os.makedirs(os.path.join(d, "step_00000009.tmp"))  # killed writer
    for s in (1, 2, 3):
        mgr.maybe_save(s, {"x": jnp.zeros(2)})
    names = set(os.listdir(d))
    assert "step_foo" in names, "GC must not delete non-checkpoint dirs"
    assert not any(n.endswith(".tmp") for n in names), \
        "orphaned .tmp dirs must be reclaimed"
    assert names >= {"step_00000002", "step_00000003"}
    assert "step_00000001" not in names     # keep=2 retention


# ---------------------------------------------------------------------------
# Wire bytes: zero RS/AG arms vs the schedule's plan-table prediction
# ---------------------------------------------------------------------------

def _deviceless_engine(p=8):
    from repro.core import (CollectiveEngine, EngineConfig, compose_library,
                            registry, topology_from_mesh_shape)
    return CollectiveEngine(
        topology_from_mesh_shape(("data",), (p,)),
        library=compose_library(registry.ALL_FUNCTIONS),
        config=EngineConfig())


def test_zero_rs_phase_bytes_predicted_equals_measured():
    from repro import comm as comm_mod
    from repro.core import topology_from_mesh_shape
    from repro.core.engine import SYNC_STATS_KEY

    p = 8
    leaves = [jax.ShapeDtypeStruct((p, 1000), jnp.float32),
              jax.ShapeDtypeStruct((p, 37), jnp.float32)]
    eng = _deviceless_engine(p)

    def sync(tree):
        def leaf(x):
            tok = eng.zero_reduce_scatter_start(x, "data", mean=True)
            return eng.zero_reduce_scatter_wait(tok)
        return [leaf(x) for x in tree]

    out = jax.eval_shape(
        lambda t: jax.vmap(sync, axis_name="data")(t), leaves)
    # each rank's chunk of the padded flat grad
    assert out[0].shape == (p, 1000 // p)
    assert out[1].shape == (p, -(-37 // p))

    sess = comm_mod.Session(
        topology=topology_from_mesh_shape(("data",), (p,)))
    sched = sess.world.zero_sync_schedule(
        [("leaf0", 1000, jnp.float32), ("leaf1", 37, jnp.float32)],
        kind="rs")
    predicted = sum(sched.predicted_phase_bytes().values())
    measured = sum(v for k, v in eng.stats.phase_bytes.items()
                   if k.startswith("reduce_scatter."))
    assert predicted == measured, (predicted, measured,
                                   dict(eng.stats.phase_bytes))
    # the sync ledger records the RS wire share, not the AR payload
    assert eng.stats.bytes[SYNC_STATS_KEY] == measured


def test_zero_ag_phase_bytes_predicted_equals_measured():
    from repro import comm as comm_mod
    from repro.core import topology_from_mesh_shape

    p = 8
    chunk = 125
    eng = _deviceless_engine(p)

    def gather(x):
        tok = eng.zero_all_gather_start(x, "data")
        return eng.zero_all_gather_wait(tok)

    out = jax.eval_shape(
        lambda x: jax.vmap(gather, axis_name="data")(x),
        jax.ShapeDtypeStruct((p, chunk), jnp.float32))
    assert out.shape == (p, p * chunk)

    sess = comm_mod.Session(
        topology=topology_from_mesh_shape(("data",), (p,)))
    sched = sess.world.zero_sync_schedule(
        [("param0", p * chunk, jnp.float32)], kind="ag")
    predicted = sum(sched.predicted_phase_bytes().values())
    measured = sum(v for k, v in eng.stats.phase_bytes.items()
                   if k.startswith("all_gather."))
    assert predicted == measured, (predicted, measured,
                                   dict(eng.stats.phase_bytes))


def test_zero_schedule_hoists_ag_under_next_forward():
    from repro import comm as comm_mod
    from repro.core import plan as plan_mod
    from repro.core import schedule as schedule_mod
    from repro.core import topology_from_mesh_shape

    sess = comm_mod.Session(
        topology=topology_from_mesh_shape(("data",), (8,)))
    specs = [(f"param{i}", 4096, jnp.float32) for i in range(4)]
    base = sess.world.zero_sync_schedule(
        specs, kind="ag", compute=(("next_forward", True),))
    rewritten, _ = plan_mod.run_passes(
        base, plan_mod.canonical_overlap_passes(2))
    w = float(sum(base.predicted_phase_bytes().values()))
    exposed_base = schedule_mod.modeled_exposed_comm_frac(
        base, compute_weight=w)
    exposed = schedule_mod.modeled_exposed_comm_frac(
        rewritten, compute_weight=w)
    assert exposed_base == 1.0
    assert exposed < exposed_base, (exposed, exposed_base)


# ---------------------------------------------------------------------------
# 8-device subprocess: bit-identity and the elastic/sharded-ckpt seam
# ---------------------------------------------------------------------------

def test_zero_bit_identical_losses_and_sharded_state():
    run_subprocess_script("""
import numpy as np
import jax
from repro import comm as comm_mod
from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.models import build_model
from repro.optim import make_optimizer
from repro.parallel.sharding import named_shardings
from repro.runtime import substrate
from repro.train import trainer

cfg = get_config("granite-34b", reduced=True)
model = build_model(cfg)
mesh = substrate.make_mesh((4, 2), ("data", "model"))
opt = make_optimizer("adamw", lr=1e-3, clip_norm=0.0)
ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=32,
                        global_batch=8)
sess = comm_mod.Session(mesh=mesh)

losses, shard_bytes = {}, {}
for zero in (False, True):
    tcfg = trainer.TrainCfg(microbatches=2, sync_mode="composed",
                            data_axes=("data",), zero=zero, overlap=True)
    step_fn = trainer.make_train_step(model, opt, tcfg, mesh=mesh,
                                      comm=sess.world)
    sspecs = trainer.state_specs(model, opt, tcfg, mesh=mesh)
    with substrate.set_mesh(mesh):
        state = trainer.make_train_state(model, opt, jax.random.PRNGKey(0),
                                         cfg=tcfg, mesh=mesh)
        state = jax.device_put(state, named_shardings(mesh, sspecs))
        jstep = jax.jit(step_fn, donate_argnums=0)
        ls = []
        for step in range(3):
            batch = ds.sharded_batch(step, mesh, batch_axes=("data",))
            state, metrics = jstep(state, batch)
            ls.append(np.float32(jax.device_get(metrics["loss"])))
        losses[zero] = ls
        shard_bytes[zero] = sum(
            int(np.asarray(l.addressable_shards[0].data).nbytes)
            for l in jax.tree_util.tree_leaves(state["opt"]))
    sess.remesh(mesh)     # revoke this build's persistent handles

a = np.asarray(losses[False]); b = np.asarray(losses[True])
assert (a.view(np.uint32) == b.view(np.uint32)).all(), (a, b)
# optimizer state per device shrinks ~DP x (DP=4; scalar step stays)
ratio = shard_bytes[False] / shard_bytes[True]
assert ratio > 3.0, (shard_bytes, ratio)
print("OK zero bit-identical", losses[True], "shrink", ratio)
""", timeout=420)


def test_zero_elastic_recovery_from_sharded_checkpoint():
    run_subprocess_script("""
import glob
import json
import os
import tempfile
import jax
from repro.configs import get_config
from repro.models import build_model
from repro.optim import make_optimizer
from repro.train import TrainCfg, TrainSession
from repro.core import (CollectiveEngine, EngineConfig, compose_library,
                        registry, topology_from_mesh)
from repro.checkpoint.manager import restore_checkpoint
from repro.data import SyntheticLMDataset
from repro.runtime import ElasticController, FaultEvent, FaultPlan, substrate
from repro.runtime.elastic import make_mesh_from_shape, remesh

tmp = tempfile.mkdtemp()
cfg = get_config("granite-34b", reduced=True)
tcfg = TrainCfg(sync_mode="composed", data_axes=("data",), zero=True)
session = TrainSession(build_model(cfg),
                       make_optimizer("adamw", lr=1e-3, clip_norm=0.0),
                       tcfg)
ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=16,
                        global_batch=12)
mesh0 = substrate.make_mesh((4, 2), ("data", "model"))
engine = CollectiveEngine(topology_from_mesh(mesh0),
                          library=compose_library(registry.ALL_FUNCTIONS),
                          config=EngineConfig(mode="composed"))
ctl = ElasticController(
    session, ds, mesh0, total_steps=8, ckpt_dir=tmp, engine=engine,
    ckpt_every=2, ckpt_keep=0, ckpt_sharded=True,
    fault_plan=FaultPlan([FaultEvent(5, "lose", 2)], seed=1),
    watchdog_timeout=600.0)
report = ctl.run()

assert len(report.recoveries) == 1, report.describe()
rec = report.recoveries[0]
assert rec.before_shape == (4, 2) and rec.after_shape == (3, 2)
assert rec.restored_step == 4, rec
assert sorted(report.losses) == list(range(8))

# the sharded layout actually engaged: per-shard files + manifest map
step4 = os.path.join(tmp, "step_00000004")
with open(os.path.join(step4, "manifest.json")) as f:
    man = json.load(f)
assert any("shards" in e for e in man["leaves"]), "no sharded leaves"
assert glob.glob(os.path.join(step4, "*.shard_*.bin"))

# baseline: restore the p=4-padded sharded checkpoint onto the 6
# survivors (p'=3 layout — restore resizes the flat leaves) and step;
# every loss must match the controller's post-recovery losses bit-
# for-bit.
surv = [d for d in jax.devices() if d.id in rec.healthy_after]
mesh6 = make_mesh_from_shape((3, 2), devices=surv)
eng6 = CollectiveEngine(topology_from_mesh(mesh6),
                        library=compose_library(registry.ALL_FUNCTIONS),
                        config=EngineConfig(mode="composed"))
state = restore_checkpoint(tmp, session.abstract_state(mesh=mesh6),
                           step=4, allow_resize_1d=True)
state = remesh(state, session.state_specs(mesh=mesh6), mesh6)
with substrate.set_mesh(mesh6):
    jstep = jax.jit(session.step_fn(mesh=mesh6, engine=eng6),
                    donate_argnums=0)
    for s in range(4, 8):
        batch = ds.sharded_batch(s, mesh6, batch_axes=("data",))
        state, metrics = jstep(state, batch)
        assert float(metrics["loss"]) == report.losses[s], (
            s, float(metrics["loss"]), report.losses[s])
print("OK zero elastic recovery", report.losses)
""", timeout=600)

def test_zero_matches_unsharded_on_non_pow2_dp():
    # Regression: on a (3, 2) mesh the param->chunk->all-gather chain
    # for leaves the forward shards over "model" (embed/lm_head/mlp/
    # final-norm) once miscompiled and losses exploded after one step.
    # Odd per-rank chunks use plain-ring RS, so equality is up to
    # summation order here, not bitwise.
    run_subprocess_script("""
import numpy as np
import jax
from repro import comm as comm_mod
from repro.configs import get_config
from repro.data import SyntheticLMDataset
from repro.models import build_model
from repro.optim import make_optimizer
from repro.runtime import substrate
from repro.runtime.elastic import remesh
from repro.train import trainer

cfg = get_config("granite-34b", reduced=True)
model = build_model(cfg)
mesh = substrate.make_mesh((3, 2), ("data", "model"),
                           devices=jax.devices()[:6])
opt = make_optimizer("adamw", lr=1e-3, clip_norm=0.0)
ds = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=32,
                        global_batch=12)
sess = comm_mod.Session(mesh=mesh)

losses, params = {}, {}
for zero in (False, True):
    tcfg = trainer.TrainCfg(microbatches=1, sync_mode="composed",
                            data_axes=("data",), zero=zero)
    step_fn = trainer.make_train_step(model, opt, tcfg, mesh=mesh,
                                      comm=sess.world)
    sspecs = trainer.state_specs(model, opt, tcfg, mesh=mesh)
    with substrate.set_mesh(mesh):
        state = trainer.make_train_state(model, opt, jax.random.PRNGKey(0),
                                         cfg=tcfg, mesh=mesh)
        state = remesh(state, sspecs, mesh)   # (3,2): drop indivisible specs
        jstep = jax.jit(step_fn, donate_argnums=0)
        ls = []
        for step in range(4):
            batch = ds.sharded_batch(step, mesh, batch_axes=("data",))
            state, metrics = jstep(state, batch)
            ls.append(float(jax.device_get(metrics["loss"])))
        losses[zero] = ls
        params[zero] = jax.device_get(state["params"])
    sess.remesh(mesh)

np.testing.assert_allclose(losses[True], losses[False], rtol=1e-6, atol=0)
for a, b in zip(jax.tree_util.tree_leaves(params[False]),
                jax.tree_util.tree_leaves(params[True])):
    np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                               rtol=0, atol=1e-6)
print("OK zero non-pow2 DP", losses[True])
""", devices=6, timeout=420)
