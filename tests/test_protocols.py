"""Protocol correctness: every schedule == the mathematical collective.

Multi-device semantics are emulated with ``jax.vmap(axis_name=...)`` —
ppermute/psum over a vmapped named axis behave exactly like a manual mesh
axis, so these tests sweep axis sizes on one CPU.  Property tests
(hypothesis) sweep shapes/dtypes/sizes.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import compression
from repro.core.protocols import bruck, pipeline, recursive, ring, tree

AX = "x"


def run_spmd(fn, *per_device_args):
    return jax.vmap(fn, axis_name=AX)(*per_device_args)


def rand(rng, *shape):
    return rng.randn(*shape).astype(np.float32)


# ---------------------------------------------------------------------------
# Ring family
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 3, 4, 8])
def test_ring_reduce_scatter(rng, p):
    x = rand(rng, p, p, 5)           # per device: (p, chunk)
    out = run_spmd(lambda v: ring.ring_reduce_scatter_flat(v, AX), x)
    want = x.sum(0)                  # (p, 5): chunk i on device i
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


@pytest.mark.parametrize("p", [2, 3, 5, 8])
def test_ring_all_gather(rng, p):
    shard = rand(rng, p, 7)
    out = run_spmd(lambda v: ring.ring_all_gather_flat(v, AX), shard)
    for i in range(p):
        np.testing.assert_allclose(np.asarray(out[i]), shard)


@pytest.mark.parametrize("p", [2, 4, 6, 8])
def test_bidir_ring_all_reduce(rng, p):
    x = rand(rng, p, p, 6)
    out = run_spmd(lambda v: ring.bidir_ring_all_reduce_flat(v, AX), x)
    want = np.broadcast_to(x.sum(0).reshape(-1), (p, p * 6))
    np.testing.assert_allclose(np.asarray(out).reshape(p, -1), want,
                               rtol=1e-4, atol=1e-5)


def test_bidir_odd_chunk_falls_back(rng):
    p = 4
    x = rand(rng, p, p, 5)           # chunk=5 odd -> unidirectional path
    out = run_spmd(lambda v: ring.bidir_ring_reduce_scatter_flat(v, AX), x)
    np.testing.assert_allclose(np.asarray(out), x.sum(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Recursive halving/doubling
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4, 8, 16])
def test_recursive_doubling_all_reduce(rng, p):
    x = rand(rng, p, 9)
    out = run_spmd(lambda v: recursive.recursive_doubling_all_reduce(v, AX),
                   x)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(x.sum(0), (p, 9)), rtol=1e-5)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_rabenseifner_all_reduce(rng, p):
    x = rand(rng, p, p, 4)
    out = run_spmd(lambda v: recursive.rabenseifner_all_reduce_flat(v, AX), x)
    want = np.broadcast_to(x.sum(0).reshape(-1), (p, p * 4))
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_halving_rs_matches_device_chunk(rng, p):
    x = rand(rng, p, p, 4)
    out = run_spmd(lambda v: recursive.halving_reduce_scatter_flat(v, AX), x)
    np.testing.assert_allclose(np.asarray(out), x.sum(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Bruck / pairwise all-to-all
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("impl", [bruck.bruck_all_to_all,
                                  bruck.pairwise_all_to_all])
def test_all_to_all(rng, p, impl):
    x = rand(rng, p, p, 3)
    out = run_spmd(lambda v: impl(v, AX), x)
    want = np.swapaxes(x, 0, 1)      # out[d][j] = x[j][d]
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)


def test_bruck_non_pow2(rng):
    p = 6
    x = rand(rng, p, p, 2)
    out = run_spmd(lambda v: bruck.pairwise_all_to_all(v, AX), x)
    np.testing.assert_allclose(np.asarray(out), np.swapaxes(x, 0, 1),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# Tree broadcast / reduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 3, 4, 8])
@pytest.mark.parametrize("root", [0, 1])
def test_binomial_broadcast(rng, p, root):
    if root >= p:
        pytest.skip("root >= p")
    x = rand(rng, p, 5)
    out = run_spmd(lambda v: tree.binomial_broadcast(v, AX, root), x)
    np.testing.assert_allclose(np.asarray(out),
                               np.broadcast_to(x[root], (p, 5)))


@pytest.mark.parametrize("p", [2, 4, 8])
@pytest.mark.parametrize("root", [0, 1, 3])
def test_scatter_allgather_broadcast(rng, p, root):
    if root >= p:
        pytest.skip("root >= p")
    x = rand(rng, p, p, 6)           # per device: (p, chunk)
    out = run_spmd(lambda v: tree.scatter_allgather_broadcast(v, AX, root), x)
    want = np.broadcast_to(x[root], (p, p, 6))
    np.testing.assert_allclose(np.asarray(out), want)


@pytest.mark.parametrize("p", [2, 4, 8])
def test_binomial_reduce_root(rng, p):
    x = rand(rng, p, 5)
    out = run_spmd(lambda v: tree.binomial_reduce_to_root(v, AX, 0), x)
    np.testing.assert_allclose(np.asarray(out)[0], x.sum(0), rtol=1e-5)


# ---------------------------------------------------------------------------
# Pipeline (GPipe)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,n_micro", [(2, 3), (4, 4), (4, 8)])
def test_gpipe_forward(rng, p, n_micro):
    stage_w = np.arange(1, p + 1, dtype=np.float32)
    mbs = rand(rng, n_micro, 6)
    out = run_spmd(
        lambda w: pipeline.gpipe_forward(
            lambda wi, a: a * wi, w, jnp.asarray(mbs), AX),
        stage_w)
    want = mbs * np.prod(stage_w)
    np.testing.assert_allclose(np.asarray(out)[-1], want, rtol=1e-5)


# ---------------------------------------------------------------------------
# Compression protocol
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p", [2, 4, 8])
def test_compressed_all_reduce_close(rng, p):
    x = rand(rng, p, 700) * 3
    y, _ = jax.vmap(lambda v: compression.compressed_all_reduce(v, AX),
                    axis_name=AX, out_axes=(0, None))(x)
    want = x.sum(0)
    err = np.abs(np.asarray(y) - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 0.05, err


def test_error_feedback_reduces_bias(rng):
    """With EF, the *accumulated* quantization error stays bounded while
    repeated stateless quantization of the same gradient drifts."""
    p = 4
    g = rand(rng, p, 512) * 0.1
    state = jax.vmap(
        lambda v: compression.EFState.zeros_like(v), axis_name=AX)(g)

    def step(st, v):
        y, st2 = compression.compressed_all_reduce(
            v, AX, compression.EFState(st.residual))
        return y, st2

    acc_ef = np.zeros(512, np.float32)
    acc_plain = np.zeros(512, np.float32)
    for _ in range(20):
        y, state = jax.vmap(step, axis_name=AX,
                            out_axes=(0, 0))(state, jnp.asarray(g))
        acc_ef += np.asarray(y)[0]
        y2, _ = jax.vmap(lambda v: compression.compressed_all_reduce(v, AX),
                         axis_name=AX, out_axes=(0, None))(jnp.asarray(g))
        acc_plain += np.asarray(y2)[0]
    want = g.sum(0) * 20
    err_ef = np.abs(acc_ef - want).mean()
    err_plain = np.abs(acc_plain - want).mean()
    assert err_ef <= err_plain * 1.05


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(p=st.sampled_from([2, 4, 8]),
       n=st.integers(1, 40),
       dtype=st.sampled_from([np.float32, np.float16]))
def test_prop_ring_all_reduce_any_size(p, n, dtype):
    rng = np.random.RandomState(n * p)
    x = rng.randn(p, p, n).astype(dtype)
    out = jax.vmap(lambda v: ring.ring_all_reduce_flat(v, AX),
                   axis_name=AX)(x)
    want = np.broadcast_to(x.astype(np.float32).sum(0).reshape(-1),
                           (p, p * n))
    np.testing.assert_allclose(np.asarray(out, np.float32).reshape(p, -1),
                               want,
                               rtol=2e-2 if dtype == np.float16 else 1e-4,
                               atol=1e-2 if dtype == np.float16 else 1e-5)


@settings(max_examples=25, deadline=None)
@given(p=st.sampled_from([2, 3, 4, 6, 8]), n=st.integers(1, 30))
def test_prop_pairwise_a2a_involution(p, n):
    """all_to_all is an involution: applying it twice restores the input."""
    rng = np.random.RandomState(n + p)
    x = rng.randn(p, p, n).astype(np.float32)
    f = lambda v: bruck.pairwise_all_to_all(
        bruck.pairwise_all_to_all(v, AX), AX)
    out = jax.vmap(f, axis_name=AX)(x)
    np.testing.assert_allclose(np.asarray(out), x, rtol=1e-6)
