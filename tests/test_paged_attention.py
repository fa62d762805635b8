"""Paged decode attention (``repro.kernels.paged_attention``): the Pallas
kernel (interpret mode) and its jnp oracle against ``decode_attention``
on a contiguous arena gathered from the same pages, with the new token
written at its position."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ref
from repro.models.layers import decode_attention

HKV, GROUP, D, LAYERS = 2, 8, 32, 2
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def impl(name):
    if name == "kernel":
        return lambda *a: K.paged_attention(*a, interpret=True)
    return ref.paged_attention


def setup(lengths, page_tokens, max_len, dtype, seed=0):
    """A pool holding ``len(lengths)`` slots' pages in shuffled order
    (page 0 stays the zero page), their tables, queries and new tokens."""
    rng = np.random.RandomState(seed)
    b, pps = len(lengths), max_len // page_tokens
    shape = (b * pps + 1, page_tokens, LAYERS, HKV, D)
    k_pages = rng.randn(*shape).astype(np.float32)
    v_pages = rng.randn(*shape).astype(np.float32)
    k_pages[0] = v_pages[0] = 0.0
    table = (rng.permutation(b * pps) + 1).reshape(b, pps)
    f = lambda x: jnp.asarray(x, dtype)
    return dict(q=f(rng.randn(b, HKV * GROUP, D)), k_pages=f(k_pages),
                v_pages=f(v_pages), lengths=jnp.asarray(lengths, jnp.int32),
                table=jnp.asarray(table, jnp.int32),
                k_new=f(rng.randn(b, HKV, D)), v_new=f(rng.randn(b, HKV, D)))


def call(fn, s, layer):
    return fn(s["q"], s["k_pages"], s["v_pages"], jnp.int32(layer),
              s["lengths"], s["table"], s["k_new"], s["v_new"])


def arena_attention(s, layer):
    """``decode_attention`` over (B, max_len) rows gathered page by page,
    each new token written at ``lengths - 1``."""
    b, pps = s["table"].shape
    rows = []
    for pages, new in (("k_pages", "k_new"), ("v_pages", "v_new")):
        g = np.asarray(s[pages], np.float32)[np.asarray(s["table"]), :,
                                              layer]
        g = g.reshape(b, -1, HKV, D)
        for i, n in enumerate(np.asarray(s["lengths"])):
            if n:
                g[i, n - 1] = np.asarray(s[new][i], np.float32)
        rows.append(jnp.asarray(g, s[pages].dtype))
    out = decode_attention(s["q"][:, None], rows[0], rows[1], s["lengths"])
    return out[:, 0]


def check(got, want, lengths, dtype):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    live = np.asarray(lengths) > 0
    assert not np.isnan(got).any()
    np.testing.assert_allclose(got[live], want[live], atol=TOL[dtype],
                               rtol=TOL[dtype])
    assert (got[~live] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["kernel", "oracle"])
def test_ragged_lengths_match_decode_attention(name, dtype):
    # GQA group 8, 16-token pages in shuffled order, an inactive slot
    lengths = [1, 15, 16, 17, 2047, 2048, 0]
    s = setup(lengths, 16, 2048, dtype)
    for layer in range(LAYERS):
        check(call(impl(name), s, layer), arena_attention(s, layer),
              lengths, dtype)


@pytest.mark.parametrize("name", ["kernel", "oracle"])
def test_degenerate_contiguous_layout(name):
    # page_tokens == max_len: one page per slot, read in blocks
    lengths = [1, 63, 65, 128, 0]
    s = setup(lengths, 128, 128, jnp.float32, seed=1)
    check(call(impl(name), s, 1), arena_attention(s, 1), lengths,
          jnp.float32)


@pytest.mark.parametrize("name", ["kernel", "oracle"])
def test_the_new_token_comes_from_its_operand_not_the_pool(name):
    lengths = [5, 16, 33]
    s = setup(lengths, 16, 64, jnp.float32, seed=2)
    base = call(impl(name), s, 0)
    # what the pool holds at the new token's position is never read
    at = (s["table"][jnp.arange(3), (s["lengths"] - 1) // 16],
          (s["lengths"] - 1) % 16, 0)
    poked = dict(s, k_pages=s["k_pages"].at[at].set(50.0),
                 v_pages=s["v_pages"].at[at].set(50.0))
    np.testing.assert_array_equal(np.asarray(call(impl(name), poked, 0)),
                                  np.asarray(base))
    # while the operand is
    moved = dict(s, v_new=s["v_new"] + 1.0)
    assert not np.allclose(np.asarray(call(impl(name), moved, 0)),
                           np.asarray(base))


def test_the_kernel_reads_only_live_pages():
    lengths = [3, 20, 0]
    s = setup(lengths, 16, 64, jnp.float32, seed=3)
    table = np.asarray(s["table"])
    dead = np.concatenate([table[0, 1:], table[1, 2:], table[2]])
    nan = lambda x: x.at[jnp.asarray(dead)].set(jnp.nan)
    poisoned = dict(s, k_pages=nan(s["k_pages"]), v_pages=nan(s["v_pages"]))
    check(call(impl("kernel"), poisoned, 1), arena_attention(s, 1), lengths,
          jnp.float32)
