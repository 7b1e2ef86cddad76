"""Online warp-type identification (paper §3.1, mechanism ①).

Hardware model: two counters per warp (hits, accesses) incremented at the
shared cache, sampled every ``sampling_interval`` accesses; at each sampling
boundary the warp's type is re-evaluated from the observed hit ratio and the
counters reset. Between boundaries the warp keeps its last classification
(paper observation O2: divergence behaviour is stable over long periods).

The sampling/reclassification window is a first-class knob (ISSUE 5):
``sampling_interval`` may be a *traced* value (the policy layer supplies a
per-policy window via ``PolicyArrays.reclass_interval``), and
``max_windows`` caps how many sampling windows are allowed to update the
label — ``max_windows=1`` is the "stale phase-0 labeling" baseline that
classifies each warp once and then freezes, the foil the phased scenario
family measures online reclassification against. The window bookkeeping
follows the EAF generation-bump idiom: ``windows`` counts completed
windows per warp and label updates are gated on it, instead of keeping a
separate frozen-label array.

Under a bypass policy most of a bypassing warp's requests never touch the
cache, so they carry no hit/miss evidence. The classifier therefore keeps
TWO per-window counters: ``accesses`` counts every valid request (the
window/probe *cadence* clock — it must keep ticking while a warp
bypasses, or the probe phase would never come around again), while
``sampled`` counts only the requests that actually took the cache path
(non-bypassed requests plus the periodic probes — every
``probe_interval``-th access of a bypassing warp is forced down the
cache path by the engines). The classified hit ratio is
``hits / sampled``: the undiluted cache-path sample. Before PR 7 the
ratio was ``hits / accesses``, which capped a bypassing warp's
observable ratio at ``1/probe_interval`` = 0.125 < the 0.2 mostly-miss
threshold — labels ratcheted down and could never recover (the bug
DESIGN.md §11 kept on record since PR 5). With the probe-sample window a
reformed warp's probe stream can exceed the 0.8 mostly-hit threshold
and the label ratchets back up.

``min_samples`` adapts to the probe cadence: a window of
``sampling_interval`` accesses guarantees only ``interval /
probe_interval`` cache-path samples for a fully-bypassing warp, so the
classify floor is ``clip(interval / probe_interval, 1, 8)`` — small
windows (e.g. the win-32 fast ladder rung) would otherwise never reach
8 probes and oscillate through BALANCED every window. A window that
closes with NO cache-path sample at all (e.g. a token-less PCAL warp)
relabels to BALANCED: no evidence reverts to the prior.

The label is decided from the integer counts (``warp_types.classify``),
never from the float ``ratio``: that quotient is telemetry only (Fig 4),
since the TPU's float32 division is not correctly rounded and a
threshold such as 0.8 would flip on it.

Everything is functional and vectorized over warps so both the altitude-A
simulator and the altitude-B serving pool manager use the same code.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import warp_types as WT


class ClassifierState(NamedTuple):
    hits: jnp.ndarray        # i32[W] cache-path hits in current window
    accesses: jnp.ndarray    # i32[W] ALL valid requests in current window
    #                          (window + probe cadence clock)
    warp_type: jnp.ndarray   # i32[W] current classification
    ratio: jnp.ndarray       # f32[W] last sampled cache-path hit ratio
    windows: jnp.ndarray     # i32[W] completed sampling windows
    sampled: jnp.ndarray     # i32[W] cache-path requests in current window
    #                          (non-bypassed + probes; the classify sample)


def init(n_warps: int) -> ClassifierState:
    return ClassifierState(
        hits=jnp.zeros((n_warps,), jnp.int32),
        accesses=jnp.zeros((n_warps,), jnp.int32),
        warp_type=jnp.full((n_warps,), WT.BALANCED, jnp.int32),
        ratio=jnp.full((n_warps,), 0.5, jnp.float32),
        windows=jnp.zeros((n_warps,), jnp.int32),
        sampled=jnp.zeros((n_warps,), jnp.int32),
    )


def min_probe_samples(sampling_interval, probe_interval):
    """Classify floor adapted to the probe cadence: a window of
    ``sampling_interval`` accesses guarantees only ``interval /
    probe_interval`` cache-path samples for a fully-bypassing warp.
    Shared by ``observe`` and the wavefront engine's fused observe
    variants so the three observe paths cannot desynchronize."""
    guaranteed = jnp.asarray(sampling_interval).astype(jnp.int32) // \
        jnp.maximum(jnp.asarray(probe_interval).astype(jnp.int32), 1)
    return jnp.clip(guaranteed, 1, 8)


def observe(state: ClassifierState, warp_id, is_hit, *,
            sampling_interval=256,
            mostly_hit_threshold: float = 0.8,
            mostly_miss_threshold: float = 0.2,
            weight=None, max_windows=None, probed=None,
            probe_interval=None) -> ClassifierState:
    """Record one (or a batch of) access outcome(s) and re-classify any warp
    whose sampling window filled up.

    warp_id: i32[] or i32[N]; is_hit: bool same shape.
    sampling_interval may be a traced scalar (policy-visible window).
    max_windows (optional, traced ok): label updates stop after this many
    completed windows — the window still resets (counters keep cycling,
    ``ratio`` telemetry stays live), only ``warp_type`` freezes.
    probed (optional): i32 mask/weight of requests that took the cache
    path (non-bypassed + periodic probes). Defaults to ``weight`` (every
    counted request is a cache-path sample — the non-bypass case).
    Requests with ``probed == 0`` still advance the ``accesses`` cadence
    clock but carry no hit/miss evidence: the classified ratio is
    ``hits / sampled`` over cache-path samples only, so a bypassing
    warp's ratio is NOT diluted toward ``1/probe_interval``.
    probe_interval (optional, traced ok): the probe cadence, used only
    to adapt the classify floor (``min_probe_samples``); None keeps the
    default floor of 8 samples.
    """
    warp_id = jnp.atleast_1d(warp_id)
    is_hit = jnp.atleast_1d(is_hit).astype(jnp.int32)
    if weight is None:
        weight = jnp.ones_like(is_hit)
    if probed is None:
        probed = weight
    hits = state.hits.at[warp_id].add(is_hit * probed)
    accesses = state.accesses.at[warp_id].add(weight)
    sampled = state.sampled.at[warp_id].add(probed)

    due = accesses >= sampling_interval
    ratio_now = hits.astype(jnp.float32) / jnp.maximum(sampled, 1)
    min_samples = 8 if probe_interval is None \
        else min_probe_samples(sampling_interval, probe_interval)
    new_type = WT.classify(hits, sampled,
                           mostly_hit_threshold=mostly_hit_threshold,
                           mostly_miss_threshold=mostly_miss_threshold,
                           min_samples=min_samples)
    relabel = due if max_windows is None \
        else due & (state.windows < max_windows)
    warp_type = jnp.where(relabel, new_type, state.warp_type)
    ratio = jnp.where(due, ratio_now, state.ratio)
    windows = state.windows + due.astype(jnp.int32)
    hits = jnp.where(due, 0, hits)
    accesses = jnp.where(due, 0, accesses)
    sampled = jnp.where(due, 0, sampled)
    return ClassifierState(hits=hits, accesses=accesses,
                           warp_type=warp_type, ratio=ratio,
                           windows=windows, sampled=sampled)


def force_classify(state: ClassifierState, *, mostly_hit_threshold=0.8,
                   mostly_miss_threshold=0.2, min_samples: int = 1
                   ) -> ClassifierState:
    """Classify immediately from whatever counts exist (end-of-window)."""
    ratio_now = state.hits.astype(jnp.float32) / jnp.maximum(state.sampled, 1)
    new_type = WT.classify(state.hits, state.sampled,
                           mostly_hit_threshold=mostly_hit_threshold,
                           mostly_miss_threshold=mostly_miss_threshold,
                           min_samples=min_samples)
    keep = state.sampled < min_samples
    return ClassifierState(
        hits=state.hits, accesses=state.accesses,
        warp_type=jnp.where(keep, state.warp_type, new_type),
        ratio=jnp.where(keep, state.ratio, ratio_now),
        windows=state.windows, sampled=state.sampled)
