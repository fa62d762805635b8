"""Core engine: trace -> compose -> tiers -> protocol selection (paper
§2+§3+§4 mechanics) plus engine collectives vs lax semantics under vmap."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CollectiveEngine, EngineConfig, compose_library,
                        costmodel, layers, registry, scan_step,
                        topology_from_mesh_shape)
from repro.core.compose import NotComposedError, compose_from_trace

AX = "data"


@pytest.fixture
def topo():
    return topology_from_mesh_shape((AX,), (8,))


def full_engine(topo, **cfg):
    return CollectiveEngine(topo, library=compose_library(
        registry.ALL_FUNCTIONS), config=EngineConfig(**cfg))


# ---------------------------------------------------------------------------
# Trace (application scan, §2.2)
# ---------------------------------------------------------------------------

def test_trace_finds_collectives_and_counts():
    def step(v):
        def body(c, _):
            return jax.lax.psum(c, AX), None
        c, _ = jax.lax.scan(body, v, None, length=7)
        return c, jax.lax.all_gather(v, AX)

    rep = scan_step(lambda v: jax.vmap(step, axis_name=AX)(v),
                    np.zeros((8, 4), np.float32))
    assert rep.count(registry.ALL_REDUCE) == 7      # scan multiplies
    assert registry.ALL_REDUCE in rep.function_set


def test_trace_through_shard_map():
    from repro.runtime import substrate
    mesh = substrate.make_mesh((1,), (AX,))
    from functools import partial
    from jax.sharding import PartitionSpec as P

    @partial(substrate.shard_map, mesh=mesh, in_specs=P(AX),
             out_specs=(P(), P(AX)), check_vma=False)
    def step(v):
        return jax.lax.psum(v, AX), jax.lax.all_to_all(
            v.reshape(1, -1), AX, 0, 0, tiled=True)

    rep = scan_step(step, np.zeros((8, 4), np.float32))
    assert {registry.ALL_REDUCE, registry.ALL_TO_ALL} <= rep.function_set
    assert rep.bytes_by_function()[registry.ALL_REDUCE] > 0


# ---------------------------------------------------------------------------
# Compose (§2): minimal set cover, one application ↔ one library
# ---------------------------------------------------------------------------

def test_compose_minimal_cover():
    lib = compose_library({registry.ALL_REDUCE})
    assert lib.m == 1 and lib.blocks == ("F_reduce",)
    lib = compose_library({registry.ALL_REDUCE, registry.ALL_GATHER,
                           registry.PERMUTE})
    assert lib.m == 3
    assert set(lib.blocks) == {"F_reduce", "F_gather", "F_pt2pt"}


def test_compose_exact_beats_greedy_structure():
    # exact solver must return a true minimum: covering needs both blocks
    blocks = {"A": frozenset({"all_reduce", "all_gather"}),
              "B": frozenset({"all_reduce"}),
              "C": frozenset({"all_gather"})}
    lib = compose_library({"all_reduce", "all_gather"}, blocks=blocks)
    assert lib.m == 1 and lib.blocks == ("A",)


def test_not_composed_raises(topo):
    small = CollectiveEngine(topo, library=compose_library({"all_reduce"}),
                             config=EngineConfig())
    x = np.zeros((8, 8), np.float32)
    with pytest.raises(NotComposedError):
        jax.vmap(lambda v: small.all_to_all(v, AX), axis_name=AX)(x)
    # but the composed function works
    jax.vmap(lambda v: small.all_reduce(v, AX), axis_name=AX)(x)


def test_compose_from_trace_adds_setup():
    def step(v):
        return jax.lax.psum(v, AX)
    rep = scan_step(lambda v: jax.vmap(step, axis_name=AX)(v),
                    np.zeros((8, 2), np.float32))
    lib = compose_from_trace(rep)
    assert lib.supports(registry.INIT) and lib.supports(registry.FINALIZE)


# ---------------------------------------------------------------------------
# Layers (§3): tiers + average layer number
# ---------------------------------------------------------------------------

def test_tier_assignment_and_average():
    freqs = {"all_reduce": 1e7, "broadcast": 1e3, "init": 1.0}
    tiers = layers.assign_tiers(freqs)
    assert tiers["all_reduce"] == 0
    assert tiers["broadcast"] == 2
    assert tiers["init"] == 3
    avg = layers.average_layer_number(tiers, freqs)
    conv = layers.average_layer_number(
        layers.conventional_tiers(freqs), freqs)
    assert avg < conv                       # the paper's claim, mechanically
    assert conv == layers.CONVENTIONAL_TIER


def test_engine_average_layer_lower_than_monolithic(topo):
    eng = full_engine(topo)
    mono = CollectiveEngine.monolithic(topo)
    assert eng.average_layer_number() < mono.average_layer_number()


def test_checked_tier_validates(topo):
    eng = full_engine(topo)
    with pytest.raises((TypeError, ValueError)):
        # broadcast sits at a checked tier; passing a non-array must raise
        jax.vmap(lambda v: eng.broadcast("not an array", AX),
                 axis_name=AX)(np.zeros((8, 2), np.float32))


# ---------------------------------------------------------------------------
# Cost model (§4): per-function, per-size protocol selection
# ---------------------------------------------------------------------------

def test_latency_vs_bandwidth_crossover(topo):
    small = costmodel.choose_protocol("all_reduce", 1024, topo, AX)
    large = costmodel.choose_protocol("all_reduce", 1 << 30, topo, AX)
    assert small.protocol == costmodel.RECURSIVE_DOUBLING
    assert large.protocol in (costmodel.BIDIR_RING,
                              costmodel.RECURSIVE_HALVING)
    assert small.est_seconds < large.est_seconds


def test_crossover_intervals_cover_range(topo):
    iv = costmodel.crossover_bytes("all_reduce", topo, AX)
    assert len(iv) >= 2                     # at least two regimes exist


def test_dcn_axis_prefers_low_latency():
    topo2 = topology_from_mesh_shape(("pod", AX), (2, 8))
    c_ici = costmodel.cost_allreduce_ring(1 << 20, topo2, AX)
    c_dcn = costmodel.cost_allreduce_ring(1 << 20, topo2, "pod")
    assert c_dcn > c_ici                    # DCN is the slow network


@settings(max_examples=20, deadline=None)
@given(nbytes=st.integers(64, 1 << 28))
def test_prop_chosen_protocol_is_argmin(nbytes):
    topo = topology_from_mesh_shape((AX,), (16,))
    choice = costmodel.choose_protocol("all_reduce", nbytes, topo, AX)
    best = min(c for _, c in choice.alternatives)
    assert choice.est_seconds == best


# ---------------------------------------------------------------------------
# Engine collectives == lax semantics (forced through every protocol)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("proto", ["ring", "bidir_ring",
                                   "recursive_doubling",
                                   "recursive_halving", "xla_default"])
def test_engine_allreduce_protocols(topo, rng, proto):
    eng = full_engine(topo, force_protocol={"all_reduce": proto})
    x = rng.randn(8, 33).astype(np.float32)
    out = jax.vmap(lambda v: eng.all_reduce(v, AX), axis_name=AX)(x)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(x.sum(0), x.shape),
                               rtol=1e-4, atol=1e-5)


def test_engine_monolithic_matches_composed(topo, rng):
    x = rng.randn(8, 16, 8).astype(np.float32)
    eng = full_engine(topo)
    mono = CollectiveEngine.monolithic(topo)
    for fn in ("all_reduce", "reduce_scatter", "all_gather", "all_to_all"):
        a = jax.vmap(lambda v: getattr(eng, fn)(v, AX), axis_name=AX)(x)
        b = jax.vmap(lambda v: getattr(mono, fn)(v, AX), axis_name=AX)(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6, err_msg=fn)


def test_engine_multiaxis_hierarchical(rng):
    topo = topology_from_mesh_shape(("pod", AX), (2, 4))
    eng = CollectiveEngine(topo, library=compose_library(
        registry.ALL_FUNCTIONS), config=EngineConfig())
    x = rng.randn(2, 4, 37).astype(np.float32)
    f = lambda v: eng.all_reduce(v, ("pod", AX))
    out = jax.vmap(jax.vmap(f, axis_name=AX), axis_name="pod")(x)
    want = np.broadcast_to(x.sum((0, 1)), x.shape)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


def test_engine_stats_and_lifecycle(topo, rng):
    eng = full_engine(topo)
    eng.init()
    x = rng.randn(8, 2048).astype(np.float32)   # large -> checked-tier path?
    jax.vmap(lambda v: eng.broadcast(v, AX), axis_name=AX)(x)
    summary = eng.finalize()
    assert "broadcast" in summary


def test_sync_gradients_mean(topo, rng):
    eng = full_engine(topo)
    grads = {"a": rng.randn(8, 6).astype(np.float32),
             "b": rng.randn(8, 3, 4).astype(np.float32)}
    synced, _ = jax.vmap(
        lambda g: eng.sync_gradients(g, AX), axis_name=AX,
        out_axes=(0, None))(grads)
    for k in grads:
        want = np.broadcast_to(grads[k].mean(0), grads[k].shape)
        np.testing.assert_allclose(np.asarray(synced[k]), want, rtol=1e-5)
