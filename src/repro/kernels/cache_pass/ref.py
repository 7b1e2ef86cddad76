"""Reference wavefront cache pass: the per-lane sequential scan.

Extracted verbatim from ``engine/wavefront.py`` (where it lived through
PR 7) so it can serve as the unfused side of the in-run perf A/B and as
the parity oracle for the fused/Pallas backends in this package. One
wave of B warps runs L lane sub-steps under ``jax.lax.scan``; each lane
services at most ONE request per warp, [B]-vectorized, slots in
chronological order:

  * ②  bypass decision from the carried classifier rows + PC table,
  * L2 tag lookup against the sub-step-start tags,
  * ③  RRIP fill/aging/eviction with masked scatters (an out-of-bounds
    set index drops the update; duplicate-set conflicts between wave
    members resolve last-write-wins in slot order — the semantics the
    fused backend must reproduce explicitly),
  * EAF and PC-table bookkeeping,
  * ①  the classifier observe on wave-resident [B] counter slices
    (``observe_vec``; gathered once per wave, scattered back once by the
    engine — sound because wave warp ids are distinct).

None of these outcomes depend on request *timing*, so the pass needs no
queue state; the per-lane record tuple feeds the timing pass and the
per-wave hoisted metrics (lifetime counters and scalar sums are integer
adds that nothing reads mid-wave, so the engine applies them once per
wave for every backend).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import classifier as CLF
from repro.core import warp_types as WT
from repro.core.engine import request as REQ
from repro.core.engine.state import SimParams, SimState
from repro.policy import PolicyArrays, ops as POL

F32 = jnp.float32
I32 = jnp.int32


def observe_consts(prm: SimParams, pa: PolicyArrays) -> tuple:
    """The policy-only observe scalars ``(interval, max_windows,
    min_samples)`` — pure in ``(prm, pa)``, so the fused sweep computes
    them once per wave and passes them to every lane's ``observe_vec``
    instead of re-deriving them L times."""
    interval = POL.reclass_interval(pa, prm.sampling_interval)
    max_windows = POL.reclass_max_windows(pa)
    min_samples = CLF.min_probe_samples(
        interval, POL.probe_interval(pa, prm.probe_interval))
    return interval, max_windows, min_samples


def observe_gathered(clf: CLF.ClassifierState, w, is_hit, weight, probed,
                     prm: SimParams, pa: PolicyArrays
                     ) -> CLF.ClassifierState:
    """``classifier.observe`` restricted to the B touched warps.

    Equivalent to the full-width observe — an untouched warp's counters
    don't change, so its window can never reset on this call — but costs
    O(B) gather/scatter instead of O(W) elementwise work per sub-step.
    Wave warp ids are distinct, so the scatters don't collide. Parity
    with `CLF.observe` is pinned by tests/test_engine_differential.py.
    Kept as the documented bridge between ``CLF.observe`` and the
    wave-resident ``observe_vec`` below (which is this function minus
    the gather/scatter, on rows the engine keeps wave-resident).

    The sampling window, probe cadence and label-freeze cap come from
    the policy (①, same knobs the event engine passes to
    ``CLF.observe``); ``probed`` marks the cache-path requests whose
    undiluted sample the window ratio is measured over.
    """
    interval, max_windows, min_samples = observe_consts(prm, pa)
    hits = clf.hits[w] + is_hit.astype(I32) * probed
    accesses = clf.accesses[w] + weight
    sampled = clf.sampled[w] + probed
    due = accesses >= interval
    ratio_now = hits.astype(jnp.float32) / jnp.maximum(sampled, 1)
    new_type = WT.classify(hits, sampled,
                           mostly_hit_threshold=prm.mostly_hit_threshold,
                           mostly_miss_threshold=prm.mostly_miss_threshold,
                           min_samples=min_samples)
    relabel = due & (clf.windows[w] < max_windows)
    return CLF.ClassifierState(
        hits=clf.hits.at[w].set(jnp.where(due, 0, hits)),
        accesses=clf.accesses.at[w].set(jnp.where(due, 0, accesses)),
        warp_type=clf.warp_type.at[w].set(
            jnp.where(relabel, new_type, clf.warp_type[w])),
        ratio=clf.ratio.at[w].set(jnp.where(due, ratio_now, clf.ratio[w])),
        windows=clf.windows.at[w].add(due.astype(I32)),
        sampled=clf.sampled.at[w].set(jnp.where(due, 0, sampled)),
    )


def observe_vec(clf_b: CLF.ClassifierState, is_hit, weight, probed,
                prm: SimParams, pa: PolicyArrays,
                consts: Optional[tuple] = None) -> CLF.ClassifierState:
    """``observe_gathered`` on wave-resident [B] counter slices.

    The engine gathers the wave's classifier rows ONCE before the cache
    pass, every backend updates them as plain [B] vectors here (no
    per-lane gather/scatter against the [W] arrays — XLA:CPU serializes
    those), and the engine scatters them back once per wave. Sound
    because wave warp ids are distinct: nothing else reads or writes
    those rows mid-wave, so the carried slice is exactly what a fresh
    gather would return, and the write-back stores exactly what the
    per-lane scatters would have."""
    interval, max_windows, min_samples = (
        observe_consts(prm, pa) if consts is None else consts)
    hits = clf_b.hits + is_hit.astype(I32) * probed
    accesses = clf_b.accesses + weight
    sampled = clf_b.sampled + probed
    due = accesses >= interval
    ratio_now = hits.astype(jnp.float32) / jnp.maximum(sampled, 1)
    new_type = WT.classify(hits, sampled,
                           mostly_hit_threshold=prm.mostly_hit_threshold,
                           mostly_miss_threshold=prm.mostly_miss_threshold,
                           min_samples=min_samples)
    relabel = due & (clf_b.windows < max_windows)
    return CLF.ClassifierState(
        hits=jnp.where(due, 0, hits),
        accesses=jnp.where(due, 0, accesses),
        warp_type=jnp.where(relabel, new_type, clf_b.warp_type),
        ratio=jnp.where(due, ratio_now, clf_b.ratio),
        windows=clf_b.windows + due.astype(I32),
        sampled=jnp.where(due, 0, sampled))


def lane_cache_step(st: SimState, t_arr, addr, pc, valid, owt,
                    prm: SimParams, pa: PolicyArrays,
                    clf_b: CLF.ClassifierState, tokens_b) -> tuple:
    """One lane sub-step of a wave: the timing-independent half of
    ``event._request_step`` for [B] requests (at most one per warp),
    slots in chronological order. Returns ``(st, clf_b, record)``.

    ``clf_b`` carries the wave's classifier rows as [B] vectors through
    the lane scan instead of gathering/scattering the [W] arrays every
    lane — see ``observe_vec`` for why that is bitwise-equivalent.
    Lifetime counters and scalar metric sums — write-only until
    finalize — are hoisted to one per-wave update in the engine; the
    per-lane outputs it needs ride along in the record tuple.
    """
    # ---- ①② label select + bypass decision (shared branchless math) --------
    byp, wtype, pidx = REQ.bypass_decision_vals(
        clf_b.warp_type, clf_b.accesses, tokens_b, st, addr, pc,
        valid, prm, pa, owt)
    use_l2 = valid & ~byp

    # ---- L2 lookup (sub-step-start tags) -----------------------------------
    sidx = REQ.set_index(addr, prm)
    tset = st.tags[sidx]                              # [B, ways]
    is_line = tset == addr[:, None]
    hit = jnp.any(is_line, axis=1) & use_l2
    hit_way = jnp.argmax(is_line, axis=1)
    way_oh = jnp.arange(prm.ways, dtype=I32)[None, :] == hit_way[:, None]
    rset = st.rrip[sidx]
    rset = jnp.where(hit[:, None] & way_oh, 0, rset)

    # ---- ③ fill + insertion -------------------------------------------------
    allocate = use_l2 & ~hit
    shift = prm.rrip_max - jnp.max(rset, axis=1)
    rset_aged = rset + jnp.where(allocate, shift, 0)[:, None]
    victim = jnp.argmax(rset_aged, axis=1)
    evicted = jnp.take_along_axis(tset, victim[:, None], axis=1)[:, 0]
    victim_type = st.meta_type[sidx, victim]          # read BEFORE overwrite
    rank = REQ.insertion_rank(st, wtype, addr, prm, pa)

    # masked scatters: an out-of-bounds set index drops the update, and
    # duplicate-set conflicts resolve last-write-wins in arrival order
    s_alloc = jnp.where(allocate, sidx, prm.sets)
    tags = st.tags.at[s_alloc, victim].set(addr, mode="drop")
    vict_oh = jnp.arange(prm.ways, dtype=I32)[None, :] == victim[:, None]
    new_row = jnp.where(allocate[:, None],
                        jnp.where(vict_oh, rank[:, None], rset_aged), rset)
    s_l2 = jnp.where(use_l2, sidx, prm.sets)
    rrip = st.rrip.at[s_l2].set(new_row, mode="drop")
    meta_type = st.meta_type.at[s_alloc, victim].set(wtype, mode="drop")

    # EAF bookkeeping: remember evicted addresses; the periodic reset is
    # a generation bump (state.py), not an array clear
    ev_valid = allocate & (evicted >= 0)
    eidx = REQ.eaf_index(evicted, prm)
    eaf = st.eaf.at[jnp.where(ev_valid, eidx, prm.eaf_bits)].set(
        st.eaf_gen, mode="drop")
    eaf_ctr = st.eaf_ctr + jnp.sum(ev_valid.astype(I32))
    reset = eaf_ctr >= prm.eaf_capacity
    eaf_gen = jnp.where(reset, st.eaf_gen + 1, st.eaf_gen)
    eaf_ctr = jnp.where(reset, 0, eaf_ctr)

    # ---- ① classifier + PC table (read by later lanes — never hoisted) -----
    clf_b = observe_vec(clf_b, hit, valid.astype(I32),
                        use_l2.astype(I32), prm, pa)
    pc_hits = st.pc_hits.at[pidx].add((hit & use_l2).astype(I32))
    pc_acc = st.pc_acc.at[pidx].add(use_l2.astype(I32))
    pc_req = st.pc_req.at[pidx].add(valid.astype(I32))

    new_st = st._replace(
        tags=tags, rrip=rrip, meta_type=meta_type, eaf=eaf,
        eaf_gen=eaf_gen, eaf_ctr=eaf_ctr, pc_hits=pc_hits, pc_acc=pc_acc,
        pc_req=pc_req)

    hp = POL.is_high_priority(pa, wtype)
    return new_st, clf_b, (t_arr, addr, valid, byp, use_l2, hit, hp,
                           victim_type, ev_valid)


def wave_cache_pass_ref(st: SimState, clf_b0: CLF.ClassifierState,
                        tokens_b, t0, addr_lb, pc_b, owt_b, slot_ok,
                        prm: SimParams, pa: PolicyArrays) -> tuple:
    """One wave's full cache pass: the L-lane ``lax.scan`` driver.

    ``addr_lb`` is i32[L, B] (lane-major: the engine's swapaxes of the
    wave's [B, L] line block); ``t0``/``pc_b``/``owt_b``/``slot_ok``/
    ``tokens_b`` are per-slot [B]. Returns ``(st, clf_b, records)`` with
    each record stacked [L, B] in lane-major chronological order —
    exactly the layout the timing pass flattens warp-major.
    """
    lanes = addr_lb.shape[0]
    xs = (jnp.arange(lanes, dtype=I32), addr_lb)

    def lane_step(c, x):
        s, cb = c
        lane, addr = x                               # i32[], i32[B]
        valid = (addr >= 0) & slot_ok
        t_arr = t0 + lane.astype(F32) * prm.lane_skew
        s, cb, rec = lane_cache_step(s, t_arr, addr, pc_b, valid, owt_b,
                                     prm, pa, cb, tokens_b)
        return (s, cb), rec

    (st, clf_b), recs = jax.lax.scan(lane_step, (st, clf_b0), xs)
    return st, clf_b, recs
