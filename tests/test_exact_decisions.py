"""Every device-side policy decision is made in integers and equals the
float32 comparison of the plain reference on every pair of counters it
can see.

The reference (``bench/lib/ref_sim.py``) decides with numpy's float32
division, which is correctly rounded. A TPU's float32 division is not,
so a quotient compared with a threshold can flip there; the program
therefore compares integers. These tables pin the two forms equal over
the whole reachable range, and the planted case shows that the tables
would catch the old quotient form under an inexact division.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core import classifier as CLF
from repro.core import warp_types as WT
from repro.core.engine import SimParams
from repro.policy import Policy, ops as POL, to_arrays

F32 = np.float32
N = WT.MAX_SAMPLES


def _pairs(s_lo, s_hi):
    """Every ``(h, s)`` with ``0 <= h <= s`` and ``s_lo <= s < s_hi``."""
    s = np.arange(s_lo, s_hi, dtype=np.int32)
    sizes = s + 1
    start = np.repeat(np.cumsum(sizes) - sizes, sizes)
    h = (np.arange(int(sizes.sum()), dtype=np.int32) - start).astype(
        np.int32)
    return h, np.repeat(s, sizes)


def _row_chunks(n_max, per_chunk=1 << 22):
    """Ranges ``[lo, hi)`` of ``s`` whose pairs number about
    ``per_chunk``."""
    lo = 0
    while lo <= n_max:
        hi = lo + 1
        while hi <= n_max and (hi - lo) * (hi + 1) < per_chunk:
            hi += 1
        yield lo, hi
        lo = hi


def _float_ladder(h, s, hit_thr, miss_thr, min_samples, divide=None):
    """The reference's ladder: ``ref_sim.classify`` on
    ``float32(h) / float32(max(s, 1))``."""
    den = np.maximum(s, 1).astype(F32)
    r = (h.astype(F32) / den if divide is None
         else divide(h.astype(F32), den))
    t = np.full(r.shape, WT.BALANCED, np.int32)
    t = np.where(r <= F32(miss_thr), WT.MOSTLY_MISS, t)
    t = np.where(r <= F32(1e-6), WT.ALL_MISS, t)
    t = np.where(r >= F32(hit_thr), WT.MOSTLY_HIT, t)
    t = np.where(r >= F32(1.0 - 1e-6), WT.ALL_HIT, t)
    return np.where(s >= min_samples, t, WT.BALANCED)


def _reciprocal_multiply(a, b):
    """Division as a chip without a correctly rounded divide may do it:
    the rounded reciprocal, then a rounded multiply."""
    return (a * (F32(1.0) / b)).astype(F32)


# the SimParams defaults, which are also medic_fig7's values (Table 1
# leaves the thresholds to the model), and one pair off that grid
@pytest.mark.parametrize("hit_thr,miss_thr,min_samples", [
    (SimParams.mostly_hit_threshold, SimParams.mostly_miss_threshold, 8),
    (0.9, 0.1, 1),
])
def test_classify_ladder_exhaustive(hit_thr, miss_thr, min_samples):
    """``WT.classify`` equals the reference's float32 ladder on every
    ``0 <= hits <= samples <= MAX_SAMPLES``, including the ``_EPS``
    ends and the ``min_samples`` floor."""
    cls = jax.jit(lambda h, s: WT.classify(
        h, s, mostly_hit_threshold=hit_thr, mostly_miss_threshold=miss_thr,
        min_samples=min_samples))
    n_pairs = 0
    for lo, hi in _row_chunks(N):
        h, s = _pairs(lo, hi)
        got = np.asarray(cls(h, s))
        want = _float_ladder(h, s, hit_thr, miss_thr, min_samples)
        bad = np.flatnonzero(got != want)
        assert bad.size == 0, list(zip(h[bad[:5]], s[bad[:5]]))
        n_pairs += h.size
    assert n_pairs == (N + 1) * (N + 2) // 2


def test_min_probe_samples_exhaustive():
    """The classify floor ``clip(interval // probe, 1, 8)`` in integers
    equals the reference's float32 ``floor(interval / probe)`` for every
    window up to ``MAX_SAMPLES`` and every probe cadence up to 64."""
    iv, pi = np.meshgrid(np.arange(1, N + 1), np.arange(0, 65),
                         indexing="ij")
    got = np.asarray(jax.jit(CLF.min_probe_samples)(
        iv.astype(F32), pi.astype(F32)))
    want = np.clip(np.floor(iv.astype(F32) / np.maximum(pi, 1).astype(F32)),
                   1.0, 8.0)
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)


def _bypass(pol, *, pc_hits, pc_acc, rand_h):
    """``POL.bypass_decision`` of ``pol`` with the other signals neutral
    (no probe of either kind, token held, a mid-cadence PC request)."""
    n = np.shape(pc_hits)[0]
    return np.asarray(jax.jit(POL.bypass_decision)(
        to_arrays(pol), wtype=jnp.full((n,), WT.BALANCED, jnp.int32),
        probe=jnp.zeros((n,), bool), token_bit=jnp.ones((n,), bool),
        pc_hits=jnp.asarray(pc_hits, jnp.int32),
        pc_acc=jnp.asarray(pc_acc, jnp.int32),
        pc_req=jnp.zeros((n,), jnp.int32),
        rand_h=jnp.asarray(rand_h, jnp.int32)))


def _float_pcbyp(h, a, divide=None):
    den = np.maximum(a, 1).astype(F32)
    r = h.astype(F32) / den if divide is None else divide(h.astype(F32),
                                                          den)
    return (a > 32) & (r < F32(0.25))


def test_pcbyp_decision_exhaustive():
    """PC-Byp's ``4 * pc_hits < pc_acc`` equals the reference's
    ``pc_hits / pc_acc < 0.25`` on every pair up to 2,048 accesses
    (through ``bypass_decision``), and at the decision boundary of every
    ``32 < pc_acc < 2**24``: the float form is monotone in ``pc_hits``,
    so a row is equal when its boundary is."""
    h, a = _pairs(0, 2049)
    got = _bypass(BL.PC_BYP, pc_hits=h, pc_acc=a,
                  rand_h=np.full(h.shape, POL.RAND_RANGE))
    np.testing.assert_array_equal(got, _float_pcbyp(h, a))

    for lo in range(33, 1 << 24, 1 << 21):
        a = np.arange(lo, min(lo + (1 << 21), 1 << 24), dtype=np.int32)
        last = (a - 1) // 4                 # largest h with 4h < a
        assert _float_pcbyp(last, a).all()
        assert not _float_pcbyp(last + 1, a).any()


@pytest.mark.parametrize("p", [0.25, 0.5, 0.75, 0.0, 0.1, 1 / 3, 1.0])
def test_rand_coin_exhaustive(p):
    """Rand(p)'s ``draw < ceil(p * 2**16)`` equals the reference's
    ``draw / 65536 < p`` in float32 for every draw; the fig7 batch
    probes p = 0.25, 0.5 and 0.75."""
    d = np.arange(POL.RAND_RANGE + 1)
    got = _bypass(BL.rand(p), pc_hits=np.zeros_like(d),
                  pc_acc=np.zeros_like(d), rand_h=d)
    want = d.astype(F32) / F32(65536) < F32(p)
    np.testing.assert_array_equal(got, want)
    assert not got[-1]                      # the neutral draw never fires


def test_rand_draw_range():
    addr = jnp.arange(-1, 200_000, 37, dtype=jnp.int32)
    d = np.asarray(POL.rand_draw(addr))
    assert d.min() >= 0 and d.max() < POL.RAND_RANGE


def test_reciprocal_division_flips_the_quotient_forms():
    """Planted fault: with division done as reciprocal-then-multiply,
    the old quotient forms flip entries of the tables above on small
    counts, while the integer forms, which never divide, cannot."""
    h, s = _pairs(1, 257)
    hit_thr, miss_thr = 0.8, 0.2
    exact = _float_ladder(h, s, hit_thr, miss_thr, 1)
    planted = _float_ladder(h, s, hit_thr, miss_thr, 1,
                            divide=_reciprocal_multiply)
    flips = exact != planted
    assert flips.sum() >= 1
    # among them the mostly-hit and mostly-miss thresholds themselves
    assert ((exact == WT.MOSTLY_HIT) & (planted == WT.BALANCED)).any()
    assert ((exact == WT.MOSTLY_MISS) & (planted == WT.BALANCED)).any()
    got = np.asarray(WT.classify(jnp.asarray(h), jnp.asarray(s),
                                 min_samples=1))
    np.testing.assert_array_equal(got, exact)

    h, a = _pairs(33, 257)
    pc_flips = (_float_pcbyp(h, a)
                != _float_pcbyp(h, a, divide=_reciprocal_multiply))
    assert pc_flips.sum() >= 1


def test_windows_beyond_the_exact_ladder_are_refused():
    with pytest.raises(ValueError):
        SimParams(sampling_interval=N + 1)
    with pytest.raises(ValueError):
        Policy("big", reclass_interval=N + 1)
    SimParams(sampling_interval=N)
    Policy("edge", reclass_interval=N)


@pytest.mark.parametrize("threshold", [0.8, 0.2, 1e-6, 1 - 1e-6, 0.5,
                                       0.0, 1.0, 1.5, -0.5])
def test_ratio_cut_is_the_least_passing_ratio(threshold):
    """``ratio_cut`` returns the smallest ratio of the Farey range that
    passes ``>= float32(threshold)``, in lowest terms (``(1, 0)`` when
    none does)."""
    p, q = WT.ratio_cut(threshold)
    t = F32(threshold)
    if q == 0:
        assert t > 1
        return
    assert np.gcd(p, q) == 1 and 0 <= p <= q <= N
    assert F32(p) / F32(q) >= t
