"""Warp-type taxonomy (paper Fig 3).

Five types keyed by shared-cache hit ratio, sampled over an interval:

    all-miss     ratio == 0
    mostly-miss  0 < ratio <= mostly_miss_threshold   (paper: ~20%)
    balanced     mmiss < ratio < mostly_hit_threshold
    mostly-hit   mhit <= ratio < 1
    all-hit      ratio == 1

Codes are ordered so that *larger code == higher cache utility*, which lets
the policies compare with a single threshold (e.g. bypass iff
type <= MOSTLY_MISS, prioritize iff type >= MOSTLY_HIT).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax.numpy as jnp
import numpy as np

ALL_MISS = 0
MOSTLY_MISS = 1
BALANCED = 2
MOSTLY_HIT = 3
ALL_HIT = 4

NUM_TYPES = 5
TYPE_NAMES = ("all-miss", "mostly-miss", "balanced", "mostly-hit", "all-hit")

# epsilon so that e.g. 127/128 still counts as mostly-hit, not all-hit
_EPS = 1e-6

#: the largest sample count the integer ladder decides exactly. A
#: window's sample count never exceeds its sampling window, so
#: ``SimParams.sampling_interval`` and ``Policy.reclass_interval`` are
#: held to it; ``q * hits`` then stays far inside int32.
MAX_SAMPLES = 1 << 14


@functools.lru_cache(maxsize=None)
def ratio_cut(threshold: float) -> Tuple[int, int]:
    """``(p, q)`` with ``q * h >= p * s`` exactly when
    ``float32(h) / float32(s) >= float32(threshold)``, correctly rounded,
    for every ``0 <= h <= s``, ``1 <= s <= MAX_SAMPLES``.

    The float comparison is monotone in the exact ratio ``h / s``, so
    the ratios that pass are those at or above the smallest passing one;
    ``p / q`` is that ratio. It is found on the host with numpy's float32
    division (correctly rounded, as the reference's is), and device
    decisions then never divide: the TPU's float32 division is not
    correctly rounded, and a quotient one ulp off flips a threshold.
    """
    t = np.float32(threshold)
    s = np.arange(1, MAX_SAMPLES + 1, dtype=np.int64)
    # the first passing numerator of each s lies within 1 of t * s
    cand = np.clip(np.floor(s * np.float64(t)).astype(np.int64)[:, None]
                   + np.arange(-2, 3), 0, s[:, None] + 1)
    ok = (cand > s[:, None]) | (
        cand.astype(np.float32) / s[:, None].astype(np.float32) >= t)
    assert ((cand[:, 0] == 0) | (cand[:, 0] > s) | ~ok[:, 0]).all()
    assert ok[:, -1].all()
    first = cand[np.arange(len(s)), np.argmax(ok, axis=1)]
    reach = first <= s
    if not reach.any():
        return 1, 0                   # no ratio in [0, 1] passes
    k = np.argmin(np.where(reach, first / s, np.inf))
    h, d = int(first[k]), int(s[k])
    g = math.gcd(h, d)
    return h // g, d // g


def ratio_at_least(hits, samples, threshold: float):
    """``float32(hits / samples) >= float32(threshold)`` as integers
    (``samples`` >= 1; ``threshold`` a static Python float)."""
    p, q = ratio_cut(float(threshold))
    return q * hits >= p * samples


def ratio_at_most(hits, samples, threshold: float):
    """``float32(hits / samples) <= float32(threshold)`` as integers."""
    above = np.nextafter(np.float32(threshold), np.float32(np.inf))
    return ~ratio_at_least(hits, samples, float(above))


def classify(hits, samples, *, mostly_hit_threshold: float = 0.8,
             mostly_miss_threshold: float = 0.2, min_samples=8):
    """Vectorized window counts -> warp-type. Unsampled warps default
    BALANCED.

    hits, samples: i32[...] cache-path hits and samples of a window
    (``hits <= samples <= MAX_SAMPLES``). The type is the float32 ladder
    of ``_ladder_np`` on ``hits / max(samples, 1)``, decided in integers
    (``ratio_cut``), so it is the same on every chip.
    """
    s = jnp.maximum(samples, 1)
    t = jnp.full(jnp.shape(hits), BALANCED, jnp.int32)
    t = jnp.where(ratio_at_most(hits, s, mostly_miss_threshold),
                  MOSTLY_MISS, t)
    t = jnp.where(ratio_at_most(hits, s, _EPS), ALL_MISS, t)
    t = jnp.where(ratio_at_least(hits, s, mostly_hit_threshold),
                  MOSTLY_HIT, t)
    t = jnp.where(ratio_at_least(hits, s, 1.0 - _EPS), ALL_HIT, t)
    return jnp.where(samples >= min_samples, t,
                     jnp.full_like(t, BALANCED))


def _ladder_np(hit_ratio, mostly_hit_threshold: float,
               mostly_miss_threshold: float) -> np.ndarray:
    """The ratio->type threshold ladder, numpy-vectorized in float32 —
    the host-side form of the comparisons ``classify`` makes in integers
    (numpy's division is correctly rounded, so the two agree on every
    ``hits / samples``; tests/test_exact_decisions.py). ``classify_np``
    and ``oracle_type_np`` both call this, so the ladder cannot
    desynchronize between them."""
    r = np.asarray(hit_ratio, np.float32)
    t = np.full(r.shape, BALANCED, np.int32)
    t = np.where(r <= np.float32(mostly_miss_threshold), MOSTLY_MISS, t)
    t = np.where(r <= np.float32(_EPS), ALL_MISS, t)
    t = np.where(r >= np.float32(mostly_hit_threshold), MOSTLY_HIT, t)
    t = np.where(r >= np.float32(1.0 - _EPS), ALL_HIT, t)
    return t


def classify_np(hit_ratio: float, accesses: int, *,
                mostly_hit_threshold: float = 0.8,
                mostly_miss_threshold: float = 0.2,
                min_samples: int = 8) -> int:
    """Scalar numpy mirror of `classify` for host-side control planes."""
    if accesses < min_samples:
        return BALANCED
    return int(_ladder_np(hit_ratio, mostly_hit_threshold,
                          mostly_miss_threshold))


def oracle_type_np(reuse_p, ws_lines, *, mostly_hit_threshold: float = 0.8,
                   mostly_miss_threshold: float = 0.2) -> np.ndarray:
    """Vectorized numpy ground-truth labeling from lowered trace params.

    The warp type a converged classifier would settle on, given the
    phase's reuse probability (≈ the warp's steady-state hit ratio) and
    working-set size (0 lines = pure streaming = all-miss regardless of
    the nominal reuse column). Same float32 threshold semantics as
    ``classify``/``classify_np`` (shared ``_ladder_np``); used by
    tracegen to emit the per-phase oracle labels the engines' oracle
    labeling mode consumes.
    """
    t = _ladder_np(reuse_p, mostly_hit_threshold, mostly_miss_threshold)
    return np.where(np.asarray(ws_lines) == 0,
                    np.int32(ALL_MISS), t).astype(np.int32)


def is_bypass_type(warp_type):
    """Mostly-miss and all-miss warps bypass the shared cache (paper §3.2)."""
    return warp_type <= MOSTLY_MISS


def is_priority_type(warp_type):
    """Mostly-hit (and mischaracterized all-hit) requests take the
    high-priority memory queue (paper §3.4)."""
    return warp_type >= MOSTLY_HIT


def insertion_rank(warp_type, max_rank: int = 3):
    """Warp-type -> RRIP-style insertion rank (paper §3.3).

    0 = insert at MRU (evict last) ... max_rank = insert at LRU (evict
    first). all/mostly-hit -> 0, balanced -> max_rank-1, mostly/all-miss ->
    max_rank.
    """
    r = jnp.full(jnp.shape(warp_type), max_rank, jnp.int32)
    r = jnp.where(warp_type == BALANCED, max_rank - 1, r)
    r = jnp.where(warp_type >= MOSTLY_HIT, 0, r)
    return r
