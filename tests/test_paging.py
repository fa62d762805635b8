"""Property tests for the paged KV-cache allocator (PR 9).

Driven random op sequences (admit / grow / finish / park / resume /
defragment) against ``PagePool.check_integrity`` prove the allocator
never leaks or double-frees pages; separate tests pin the page-granular
splice/extract inversion (data survives a round trip to host, including
across a defragment) and the snapshot -> restore free-list accounting.
"""

import random

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.engine import ServeCfg
from repro.serve.paging import (OutOfPages, PagePool, RequestCache,
                                resolve_page_tokens)
from test_serve import CacheLM


def make_pool(batch=4, max_len=32, page_tokens=4, pool_pages=None):
    cfg = ServeCfg(max_len=max_len, batch=batch, cache_dtype=jnp.float32,
                   page_tokens=page_tokens, pool_pages=pool_pages)
    return PagePool(CacheLM(), cfg)


def _filled_request_cache(pool, rid, tokens):
    """A RequestCache with per-page data unique to (rid, page index) so a
    misplaced or mixed-up page shows up as a value mismatch."""
    n = pool.pages_for(tokens)
    pages, state = [], []
    for i in pool.layout.token_leaf_ids:
        l = pool.layout.leaves[i]
        rest = [s for ax, s in enumerate(l.shape)
                if ax not in (l.batch_axis, l.token_axis)]
        shape = (n, pool.page_tokens, *rest)
        size = int(np.prod(shape, initial=1))
        pages.append((np.arange(size, dtype=np.float32)
                      .reshape(shape) + 1000.0 * rid))
    for i in pool.layout.state_leaf_ids:
        l = pool.layout.leaves[i]
        shape = tuple(1 if ax == l.batch_axis else s
                      for ax, s in enumerate(l.shape))
        state.append(np.full(shape, rid, np.int32))
    return RequestCache(pages=pages, state=state, tokens=tokens)


# ---------------------------------------------------------------------------
# resolve_page_tokens
# ---------------------------------------------------------------------------


def test_resolve_page_tokens():
    assert resolve_page_tokens(64, None) == 16
    assert resolve_page_tokens(24, None) == 8
    assert resolve_page_tokens(6, None) == 2
    assert resolve_page_tokens(64, 8) == 8
    # degenerate contiguous layout: page == row, pow2 not required
    assert resolve_page_tokens(48, 48) == 48
    with pytest.raises(ValueError):
        resolve_page_tokens(64, 6)         # not pow2
    with pytest.raises(ValueError):
        resolve_page_tokens(24, 16)        # doesn't divide


@settings(max_examples=40, deadline=None)
@given(exp=st.integers(0, 5), mult=st.integers(1, 8))
def test_resolve_auto_is_pow2_and_divides(exp, mult):
    max_len = (2 ** exp) * mult
    pt = resolve_page_tokens(max_len, None)
    assert pt & (pt - 1) == 0 and max_len % pt == 0 and pt <= 16


# ---------------------------------------------------------------------------
# allocator invariants under random op sequences
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10 ** 6))
def test_allocator_never_leaks_or_double_frees(seed):
    """Random admit/grow/finish/park/resume/defragment churn: after every
    op the pool's free and allocated sets partition the pages, no page
    has two owners, the zero page never circulates — and a failed
    allocation (OutOfPages) changes nothing."""
    rnd = random.Random(seed)
    pool = make_pool(batch=4, max_len=32, page_tokens=4, pool_pages=16)
    live = {}            # rid -> tokens (in-pool)
    parked = {}          # rid -> RequestCache (host)
    next_rid = 0
    for _ in range(60):
        op = rnd.choice(["admit", "grow", "finish", "park", "resume",
                         "defrag"])
        free_before = pool.pages_free
        if op == "admit":
            rid, next_rid = next_rid, next_rid + 1
            want = rnd.randint(1, 12)
            try:
                pool.ensure(rid, want)
                pool.tables[rid].tokens = want
                live[rid] = want
            except OutOfPages:
                assert pool.pages_free == free_before
                assert rid not in pool.tables or not pool.tables[rid].pages
                pool.tables.pop(rid, None)
        elif op == "grow" and live:
            rid = rnd.choice(list(live))
            want = live[rid] + rnd.randint(1, 6)
            try:
                pool.ensure(rid, want)
                pool.tables[rid].tokens = want
                live[rid] = want
            except OutOfPages:
                assert pool.pages_free == free_before
        elif op == "finish" and live:
            rid = rnd.choice(list(live))
            freed = pool.release(rid)
            assert freed == pool.pages_for(live.pop(rid))
            assert pool.pages_free == free_before + freed
        elif op == "park" and live:
            rid = rnd.choice(list(live))
            parked[rid] = pool.park(rid, rnd.randrange(4))
            assert parked[rid].tokens == live.pop(rid)
        elif op == "resume" and parked:
            rid = rnd.choice(list(parked))
            try:
                pool.splice(rid, rnd.randrange(4), parked[rid])
                live[rid] = parked.pop(rid).tokens
            except OutOfPages:
                assert pool.pages_free == free_before
                pool.tables.pop(rid, None)
        elif op == "defrag":
            pool.defragment()
            # compacted: allocated ids form the dense prefix 1..n
            n = pool.pages_allocated
            owned = sorted(p for t in pool.tables.values()
                           for p in t.pages)
            assert owned == list(range(1, n + 1))
        pool.check_integrity()
    assert pool.pages_allocated == sum(pool.pages_for(t)
                                       for t in live.values())


# ---------------------------------------------------------------------------
# splice/extract inversion + defragment data safety
# ---------------------------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(tokens=st.integers(1, 16), slot=st.integers(0, 3))
def test_splice_extract_inversion(tokens, slot):
    pool = make_pool()
    rc = _filled_request_cache(pool, rid=7, tokens=tokens)
    pool.splice(7, slot, rc)
    assert pool.pages_allocated == pool.pages_for(tokens)
    back = pool.extract(7, slot)
    assert back.tokens == tokens
    for a, b in zip(rc.pages, back.pages):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(rc.state, back.state):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # double-splice of a live rid is a caller bug, not silent corruption
    with pytest.raises(ValueError):
        pool.splice(7, slot, rc)
    pool.check_integrity()


def test_defragment_preserves_extracted_data():
    """Churn a fragmented pool, defragment, and re-extract: tables are
    rewritten to the compacted ids but every request's bytes survive."""
    pool = make_pool(batch=4, max_len=32, page_tokens=4, pool_pages=16)
    rcs = {rid: _filled_request_cache(pool, rid, tokens=9)
           for rid in range(4)}
    for rid, rc in rcs.items():
        pool.splice(rid, rid, rc)
    pool.release(0)
    pool.release(2)                       # holes at the front
    moved = pool.defragment()
    assert moved > 0
    pool.check_integrity()
    owned = sorted(p for t in pool.tables.values() for p in t.pages)
    assert owned == list(range(1, pool.pages_allocated + 1))
    for rid in (1, 3):
        back = pool.extract(rid, rid)
        for a, b in zip(rcs[rid].pages, back.pages):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_snapshot_restore_free_list_integrity():
    """Extract-all (snapshot) is read-only; park-all then splice-all
    (restore) returns the pool to the exact same accounting."""
    pool = make_pool(batch=3, max_len=32, page_tokens=8)
    for rid, tokens in enumerate([5, 16, 1]):
        pool.splice(rid, rid, _filled_request_cache(pool, rid, tokens))
    alloc_before = pool.pages_allocated
    snaps = {rid: pool.extract(rid, rid) for rid in range(3)}
    assert pool.pages_allocated == alloc_before      # extract = read-only
    pool.check_integrity()
    for rid in range(3):
        pool.release(rid)
    assert pool.pages_free == pool.pages_total
    pool.check_integrity()
    for rid, rc in snaps.items():
        pool.splice(rid, rid, rc)
    assert pool.pages_allocated == alloc_before
    pool.check_integrity()
    for rid, rc in snaps.items():
        back = pool.extract(rid, rid)
        assert back.tokens == rc.tokens
        for a, b in zip(rc.pages, back.pages):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
