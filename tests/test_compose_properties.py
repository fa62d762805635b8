"""Property tests for the §2 composition machinery (hypothesis)."""

import itertools

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import registry
from repro.core.compose import NotComposedError, compose
from repro.core.layers import TierPolicy, assign_tiers, average_layer_number

FUNCS = list(registry.ALL_FUNCTIONS)


@settings(max_examples=60, deadline=None)
@given(fns=st.sets(st.sampled_from(FUNCS), min_size=1, max_size=10))
def test_cover_is_valid_and_minimal(fns):
    lib = compose(fns)
    # validity: every invoked function is provided
    assert fns <= lib.provided
    # minimality: no smaller union of blocks covers 𝓕 (brute force)
    blocks = registry.BLOCKS
    for m in range(lib.m):
        for combo in itertools.combinations(blocks, m):
            union = frozenset().union(*(blocks[b] for b in combo)) \
                if combo else frozenset()
            assert not (fns <= union), (combo, fns)


@settings(max_examples=30, deadline=None)
@given(fns=st.sets(st.sampled_from(FUNCS), min_size=1, max_size=6))
def test_compose_idempotent_and_monotone(fns):
    lib1 = compose(fns)
    lib2 = compose(lib1.provided)
    # composing the provided set never needs more blocks
    assert lib2.m <= len(registry.BLOCKS)
    assert lib1.provided <= lib2.provided
    # growing 𝓕 never shrinks the cover
    bigger = compose(set(fns) | {registry.BARRIER})
    assert bigger.m >= lib1.m - 1


@settings(max_examples=30, deadline=None)
@given(fns=st.sets(st.sampled_from(FUNCS), min_size=1, max_size=8))
def test_absent_functions_raise(fns):
    lib = compose(fns)
    absent = set(FUNCS) - lib.provided
    for fn in absent:
        with pytest.raises(NotComposedError):
            lib.require(fn)


@settings(max_examples=40, deadline=None)
@given(freqs=st.dictionaries(
    st.sampled_from(FUNCS),
    st.floats(min_value=1.0, max_value=1e9),
    min_size=2, max_size=10))
def test_tiered_average_never_worse_than_conventional(freqs):
    """The paper's §3 objective: frequency-aware placement can only lower
    the frequency-weighted average layer number vs the flat stack — as
    long as hot thresholds map the most frequent calls at or above L2."""
    tiers = assign_tiers(freqs, TierPolicy())
    avg = average_layer_number(tiers, freqs)
    conv = average_layer_number({f: 2 for f in freqs}, freqs)
    # tiered average is bounded by the deepest tier and, for any profile
    # where the max-frequency function lands at L0/L1, beats conventional.
    assert 0.0 <= avg <= 3.0
    hot = max(freqs, key=freqs.get)
    if tiers[hot] < 2 and freqs[hot] >= 2 * sum(
            v for k, v in freqs.items() if k != hot):
        assert avg < conv


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_overlapping_blocks_still_exact(data):
    """The solver must stay exact for overlapping (non-partition) blocks."""
    fns = data.draw(st.sets(st.sampled_from(FUNCS[:8]), min_size=1,
                            max_size=5))
    blocks = {
        "A": frozenset(FUNCS[:4]), "B": frozenset(FUNCS[2:8]),
        "C": frozenset(FUNCS[:1]), "D": frozenset(FUNCS),
    }
    lib = compose(fns, blocks=blocks)
    assert fns <= lib.provided
    # "D" covers everything, so the exact cover always has m == 1
    assert lib.m == 1
