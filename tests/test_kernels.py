"""Pallas kernels vs their pure-jnp oracles (interpret mode on CPU).

Each kernel sweeps shapes/dtypes; hypothesis drives the property sweeps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import ops as fops
from repro.kernels.flash_attention import ref as fref
from repro.kernels.local_reduce import ops as lops
from repro.kernels.local_reduce import ref as lref
from repro.kernels.quantize import ops as qops
from repro.kernels.quantize import ref as qref

# ---------------------------------------------------------------------------
# quantize
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [256, 1024, 4096])
@pytest.mark.parametrize("scale", [0.1, 10.0])
def test_quantize_matches_ref(rng, n, scale):
    x = jnp.asarray(rng.randn(n).astype(np.float32) * scale)
    qk, sk = qops.quantize(x, force_kernel=True, interpret=True)
    qr, sr = qref.quantize(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


def test_quantize_roundtrip_error_bound(rng):
    x = jnp.asarray(rng.randn(2048).astype(np.float32))
    q, s = qops.quantize(x, force_kernel=True, interpret=True)
    y = qops.dequantize(q, s, force_kernel=True, interpret=True)
    blockmax = np.abs(np.asarray(x).reshape(-1, 256)).max(1, keepdims=True)
    bound = np.repeat(blockmax / 127.0, 256, 1).reshape(-1) * 0.5 + 1e-7
    assert (np.abs(np.asarray(y) - np.asarray(x)) <= bound + 1e-6).all()


def test_dequant_add_fused(rng):
    acc = jnp.asarray(rng.randn(1024).astype(np.float32))
    x = jnp.asarray(rng.randn(1024).astype(np.float32))
    q, s = qops.quantize(x, force_kernel=True, interpret=True)
    out = qops.dequant_add(acc, q, s, force_kernel=True, interpret=True)
    want = qref.dequant_add(acc, q, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_quantize_zero_block():
    x = jnp.zeros((512,), jnp.float32)
    q, s = qops.quantize(x, force_kernel=True, interpret=True)
    assert (np.asarray(q) == 0).all()
    np.testing.assert_allclose(np.asarray(s), 1.0)  # no div-by-zero


@settings(max_examples=15, deadline=None)
@given(blocks=st.integers(1, 16),
       scale=st.floats(1e-3, 1e3),
       dtype=st.sampled_from([np.float32, np.float16]))
def test_prop_quantize_roundtrip(blocks, scale, dtype):
    rng = np.random.RandomState(blocks)
    x = jnp.asarray((rng.randn(blocks * 256) * scale).astype(dtype))
    q, s = qops.quantize(x.astype(jnp.float32), force_kernel=True,
                         interpret=True)
    y = qops.dequantize(q, s, force_kernel=True, interpret=True)
    err = np.abs(np.asarray(y) - np.asarray(x, np.float32))
    assert err.max() <= np.abs(np.asarray(x, np.float32)).max() / 100

# ---------------------------------------------------------------------------
# local_reduce
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,n", [(2, 128), (5, 1000), (8, 4096), (3, 77)])
def test_sum_chunks(rng, k, n):
    x = jnp.asarray(rng.randn(k, n).astype(np.float32))
    out = lops.sum_chunks(x, force_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(lref.sum_chunks(x)),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(k=st.integers(1, 12), n=st.integers(1, 3000))
def test_prop_sum_chunks(k, n):
    rng = np.random.RandomState(k * 1000 + n)
    x = jnp.asarray(rng.randn(k, n).astype(np.float32))
    out = lops.sum_chunks(x, force_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x).sum(0), rtol=1e-4, atol=1e-4)

# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
def test_flash_vs_exact(rng, causal, hq, hkv):
    B, S, D = 2, 256, 128
    q = jnp.asarray(rng.randn(B, S, hq, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, hkv, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, hkv, D).astype(np.float32))
    outk = fops.attention(q, k, v, causal=causal, force_kernel=True,
                          interpret=True, block_q=128, block_k=128)
    outr = fops.attention(q, k, v, causal=causal, force_kernel=False)
    np.testing.assert_allclose(np.asarray(outk), np.asarray(outr), atol=3e-5)


def test_flash_q_offset_decode_block(rng):
    B, S, H, D = 1, 256, 2, 128
    q = jnp.asarray(rng.randn(B, 128, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, H, D).astype(np.float32))
    outk = fops.attention(q, k, v, causal=True, q_offset=128,
                          force_kernel=True, interpret=True,
                          block_q=128, block_k=128)
    outr = fops.attention(q, k, v, causal=True, q_offset=128,
                          force_kernel=False)
    np.testing.assert_allclose(np.asarray(outk), np.asarray(outr), atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(rng, dtype):
    B, S, H, D = 1, 128, 2, 128
    q = jnp.asarray(rng.randn(B, S, H, D)).astype(dtype)
    k = jnp.asarray(rng.randn(B, S, H, D)).astype(dtype)
    v = jnp.asarray(rng.randn(B, S, H, D)).astype(dtype)
    outk = fops.attention(q, k, v, causal=True, force_kernel=True,
                          interpret=True, block_q=128, block_k=128)
    outr = fops.attention(q, k, v, causal=True, force_kernel=False)
    tol = 2e-2 if dtype == jnp.bfloat16 else 3e-5
    np.testing.assert_allclose(np.asarray(outk, np.float32),
                               np.asarray(outr, np.float32), atol=tol)


@settings(max_examples=8, deadline=None)
@given(sq_blocks=st.integers(1, 3), skv_blocks=st.integers(1, 3),
       h=st.sampled_from([1, 2]))
def test_prop_flash_shapes(sq_blocks, skv_blocks, h):
    rng = np.random.RandomState(sq_blocks * 10 + skv_blocks)
    B, D, blk = 1, 128, 128
    sq, skv = sq_blocks * blk, skv_blocks * blk
    q = jnp.asarray(rng.randn(B, sq, h, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, skv, h, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, skv, h, D).astype(np.float32))
    # causal only valid when sq <= skv (query block ends inside kv)
    causal = sq <= skv
    outk = fops.attention(q, k, v, causal=causal, force_kernel=True,
                          interpret=True, block_q=blk, block_k=blk)
    outr = fops.attention(q, k, v, causal=causal, force_kernel=False)
    np.testing.assert_allclose(np.asarray(outk), np.asarray(outr), atol=3e-5)


def test_blockwise_jnp_matches_oracle(rng):
    """The model-side jnp flash (models.layers.flash_attention_jnp) is the
    same schedule as the Pallas kernel — verify against the exact ref."""
    from repro.models.layers import flash_attention_jnp
    B, S, Hq, Hkv, D = 2, 100, 4, 2, 32
    q = jnp.asarray(rng.randn(B, S, Hq, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, S, Hkv, D).astype(np.float32))
    out = flash_attention_jnp(q, k, v, causal=True, block_k=32)
    ref = fref.attention(
        q.transpose(0, 2, 1, 3).reshape(B * Hq, S, D),
        k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D),
        v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D), causal=True)
    ref = ref.reshape(B, Hq, S, D).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
