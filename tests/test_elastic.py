"""Property tests for elastic mesh planning (`plan_mesh_shape`) and the
fault-injection plan (hypothesis)."""

import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime.controller import FaultEvent, FaultPlan
from repro.runtime.elastic import (make_mesh_from_shape, plan_mesh_shape,
                                   plan_from_mesh)

MP = st.sampled_from([1, 2, 4, 8])
N = st.integers(min_value=1, max_value=64)
PODS = st.integers(min_value=1, max_value=4)


@settings(max_examples=80, deadline=None)
@given(n=N, mp=MP, pods=PODS)
def test_prop_never_exceeds_device_count(n, mp, pods):
    shape = plan_mesh_shape(n, mp, pods)
    assert math.prod(shape) <= n, (n, mp, pods, shape)
    assert all(s >= 1 for s in shape)


@settings(max_examples=80, deadline=None)
@given(n=N, mp=MP, pods=PODS)
def test_prop_model_axis_held_until_forced(n, mp, pods):
    """TP degree is sacred (param layout) unless a single model-parallel
    group no longer fits; only then it shrinks (by halving)."""
    shape = plan_mesh_shape(n, mp, pods)
    if n >= mp:
        assert shape[-1] == mp, (n, mp, pods, shape)
    else:
        assert shape[-1] < mp and mp % shape[-1] == 0, (n, mp, pods, shape)


@settings(max_examples=60, deadline=None)
@given(n=st.integers(min_value=1, max_value=63), mp=MP, pods=PODS)
def test_prop_monotone_device_utilization(n, mp, pods):
    """One more healthy device never *reduces* the devices in use."""
    used = math.prod(plan_mesh_shape(n, mp, pods))
    used_next = math.prod(plan_mesh_shape(n + 1, mp, pods))
    assert used_next >= used, (n, mp, pods, used, used_next)


@settings(max_examples=60, deadline=None)
@given(n=N, mp=MP, pods=PODS)
def test_prop_ndim_normalization_consistent(n, mp, pods):
    """ndim=3 always yields a 3-tuple covering the same device count as
    the un-normalized plan."""
    base = plan_mesh_shape(n, mp, pods)
    three = plan_mesh_shape(n, mp, pods, ndim=3)
    assert len(three) == 3
    assert math.prod(three) == math.prod(base)
    assert three[-1] == base[-1]


# ---------------------------------------------------------------------------
# Regression: pods == 1 callers holding 3-axis meshes (the silent 2-tuple)
# ---------------------------------------------------------------------------

def test_regression_single_pod_three_axis_mesh():
    # Historical bug: pods == 1 silently returned a 2-tuple, so a caller
    # re-meshing a (pod, data, model) mesh got mismatched shape/names.
    assert plan_mesh_shape(8, 2) == (4, 2)
    assert plan_mesh_shape(8, 2, ndim=3) == (1, 4, 2)
    assert plan_mesh_shape(6, 2, pods=1, ndim=3) == (1, 3, 2)
    # and the normalized shape maps onto the 3-axis name set by default
    assert len(plan_mesh_shape(8, 2, ndim=3)) == 3


def test_ndim_2_rejects_multi_pod_plan():
    with pytest.raises(ValueError):
        plan_mesh_shape(16, 2, pods=2, ndim=2)   # (2, 4, 2) can't drop pod
    # but a multi-pod *budget* that plans down to one pod normalizes fine
    assert plan_mesh_shape(2, 2, pods=4, ndim=2) == (1, 2)


def test_plan_from_mesh_preserves_rank(monkeypatch):
    class FakeMesh:
        shape = {"pod": 2, "data": 2, "model": 2}
    assert plan_from_mesh(FakeMesh(), 6) == (1, 3, 2)
    class FakeMesh2:
        shape = {"data": 4, "model": 2}
    assert plan_from_mesh(FakeMesh2(), 6) == (3, 2)


def test_degraded_fallback_keeps_rank():
    # fewer devices than one model-parallel group: TP shrinks, rank holds
    assert plan_mesh_shape(1, 8) == (1, 1)
    assert plan_mesh_shape(3, 8, pods=2) == (1, 1, 2)
    assert plan_mesh_shape(1, 8, ndim=3) == (1, 1, 1)


def test_make_mesh_from_shape_default_names():
    # names are inferred from rank (devices=None covers the 1-device CPU)
    m2 = make_mesh_from_shape((1, 1))
    assert tuple(m2.axis_names) == ("data", "model")
    m3 = make_mesh_from_shape((1, 1, 1))
    assert tuple(m3.axis_names) == ("pod", "data", "model")


# ---------------------------------------------------------------------------
# FaultPlan: seeded, deterministic, parseable
# ---------------------------------------------------------------------------

def test_fault_plan_parse():
    fp = FaultPlan.parse("lose@5:2, gain@9:2, stall@7")
    assert [(e.kind, e.step, e.count) for e in fp.events] == \
        [("lose", 5, 2), ("stall", 7, 0), ("gain", 9, 2)]
    assert fp.at(5) == (FaultEvent(5, "lose", 2),)
    assert fp.at(6) == ()


def test_fault_plan_victims_deterministic():
    fp = FaultPlan([FaultEvent(5, "lose", 2)], seed=3)
    ids = list(range(8))
    v1 = fp.pick_victims(ids, 2, 5)
    v2 = fp.pick_victims(ids, 2, 5)
    assert v1 == v2 and len(v1) == 2 and set(v1) <= set(ids)
    # a different step draws independently (same-seed reproducibility is
    # the contract; cross-step equality is not)
    assert fp.pick_victims(ids, 2, 6) == fp.pick_victims(ids, 2, 6)


def test_fault_event_validation():
    with pytest.raises(ValueError):
        FaultEvent(1, "explode", 1)
    with pytest.raises(ValueError):
        FaultEvent(1, "lose", 0)
    FaultEvent(1, "stall")   # stall needs no count
