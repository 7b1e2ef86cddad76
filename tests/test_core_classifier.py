"""Unit + property tests for warp-type taxonomy and the online classifier."""
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings

from repro.core import classifier as CLF
from repro.core import warp_types as WT


def test_classify_boundaries():
    acc = jnp.full((6,), 100, jnp.int32)
    # hit ratios 0.0, 0.1, 0.2, 0.5, 0.85, 1.0 over 100 samples
    hits = jnp.asarray([0, 10, 20, 50, 85, 100], jnp.int32)
    t = WT.classify(hits, acc)
    assert list(np.asarray(t)) == [WT.ALL_MISS, WT.MOSTLY_MISS,
                                   WT.MOSTLY_MISS, WT.BALANCED,
                                   WT.MOSTLY_HIT, WT.ALL_HIT]


def test_classify_insufficient_samples_defaults_balanced():
    t = WT.classify(jnp.asarray([2]), jnp.asarray([2]), min_samples=8)
    assert int(t[0]) == WT.BALANCED


def test_policy_predicates():
    assert bool(WT.is_bypass_type(jnp.int32(WT.ALL_MISS)))
    assert bool(WT.is_bypass_type(jnp.int32(WT.MOSTLY_MISS)))
    assert not bool(WT.is_bypass_type(jnp.int32(WT.BALANCED)))
    assert bool(WT.is_priority_type(jnp.int32(WT.MOSTLY_HIT)))
    assert bool(WT.is_priority_type(jnp.int32(WT.ALL_HIT)))
    assert not bool(WT.is_priority_type(jnp.int32(WT.BALANCED)))


def test_insertion_rank_ordering():
    ranks = [int(WT.insertion_rank(jnp.int32(t))) for t in range(5)]
    # higher utility -> lower rank (evicted later)
    assert ranks[WT.ALL_HIT] <= ranks[WT.MOSTLY_HIT] < ranks[WT.BALANCED] \
        <= ranks[WT.MOSTLY_MISS] == ranks[WT.ALL_MISS]


def test_classifier_converges_to_behavior():
    st8 = CLF.init(2)
    # warp 0 always hits, warp 1 always misses
    for _ in range(40):
        st8 = CLF.observe(st8, jnp.asarray([0, 1]),
                          jnp.asarray([True, False]),
                          sampling_interval=16)
    assert int(st8.warp_type[0]) == WT.ALL_HIT
    assert int(st8.warp_type[1]) == WT.ALL_MISS


def test_classifier_adapts_to_phase_change():
    st8 = CLF.init(1)
    for _ in range(32):
        st8 = CLF.observe(st8, jnp.asarray([0]), jnp.asarray([True]),
                          sampling_interval=16)
    assert int(st8.warp_type[0]) == WT.ALL_HIT
    for _ in range(32):
        st8 = CLF.observe(st8, jnp.asarray([0]), jnp.asarray([False]),
                          sampling_interval=16)
    assert int(st8.warp_type[0]) == WT.ALL_MISS


@settings(max_examples=30, deadline=None)
@given(st.lists(st.booleans(), min_size=1, max_size=200),
       st.integers(min_value=4, max_value=64))
def test_classifier_counters_invariant(outcomes, interval):
    """hits <= accesses < interval always; ratio in [0,1]."""
    s = CLF.init(1)
    for o in outcomes:
        s = CLF.observe(s, jnp.asarray([0]), jnp.asarray([o]),
                        sampling_interval=interval)
        assert 0 <= int(s.hits[0]) <= int(s.accesses[0]) < interval
        assert 0.0 <= float(s.ratio[0]) <= 1.0
        assert 0 <= int(s.warp_type[0]) < WT.NUM_TYPES


@settings(max_examples=20, deadline=None)
@given(st.floats(min_value=0, max_value=1),
       st.integers(min_value=8, max_value=1000))
def test_classify_total_and_monotone(ratio, acc):
    """Every ratio maps to exactly one type; type is monotone in ratio."""
    h1 = round(ratio * acc)
    h2 = min(h1 + round(0.3 * acc), acc)
    t1 = int(WT.classify(jnp.int32(h1), jnp.int32(acc)))
    t2 = int(WT.classify(jnp.int32(h2), jnp.int32(acc)))
    assert 0 <= t1 < WT.NUM_TYPES
    assert t2 >= t1
