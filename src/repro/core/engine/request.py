"""Per-request math shared by the event and wavefront engines.

Every function here is shape-polymorphic: the exact event loop calls them
with scalars (one request at a time), the wavefront loop with ``[N]``
vectors (one arrival-ordered wave at a time). Keeping the two engines on
the same decision/index/timing math is what makes the differential suite
(tests/test_engine_differential.py) meaningful: the engines may only
differ in *ordering* approximations, never in per-request semantics.
"""
from __future__ import annotations

from typing import Any, Dict

import jax.numpy as jnp

from repro.core.engine.state import _QBINS, SimParams, SimState
from repro.policy import PolicyArrays, ops as POL

F32 = jnp.float32
I32 = jnp.int32

hash_index = POL.hash_index


# ---------------------------------------------------------------------------
# structure indexing (set / bank / channel / PC-table / EAF)
# ---------------------------------------------------------------------------

def bank_index(addr, prm: SimParams):
    return hash_index(addr, 1, prm.banks)


def set_index(addr, prm: SimParams):
    return hash_index(addr, 2, prm.sets)


def pc_index(pc, prm: SimParams):
    return hash_index(pc, 3, prm.pc_entries)


def dram_channel(addr, prm: SimParams):
    return hash_index(addr // prm.row_lines, 4, prm.dram_channels)


def dram_row(addr, prm: SimParams):
    return (addr // prm.row_lines).astype(I32)


def eaf_index(addr, prm: SimParams):
    return hash_index(addr, 5, prm.eaf_bits)


# ---------------------------------------------------------------------------
# ② bypass decision from current classifier / PC-table state
# ---------------------------------------------------------------------------

def bypass_decision_core(warp_type_w, accesses_w, token_w, pc_hits_v,
                         pc_acc_v, pc_req_v, addr, valid, prm: SimParams,
                         pa: PolicyArrays, oracle_wt):
    """The bypass decision on fully-gathered inputs: per-warp classifier
    values AND the request's PC-table counter values. The innermost
    shared form — the cache-pass backends (``repro.kernels.cache_pass``)
    call it with their own PC-table representation (the fused sweep
    threads the tables across unrolled lanes; the Pallas kernel reads
    one-hot selections from VMEM scratch), which keeps all engines and
    backends on one copy of the mechanism-② math.
    """
    wtype = POL.select_label(pa, warp_type_w, oracle_wt)
    # periodic re-learning probe: the Nth access of each probe window
    # (cadence ``accesses``, which counts ALL valid requests, so it
    # keeps ticking while the warp bypasses) is forced down the cache
    # path. ``% pi == pi - 1`` — not ``== 0``, which would fire on a
    # warp's zeroth access instead of its Nth. The cadence is the traced
    # ``PolicyArrays.probe_interval`` (0 defers to SimParams).
    pi = POL.probe_interval(pa, prm.probe_interval).astype(I32)
    probe = (accesses_w % pi) == pi - 1
    byp = POL.bypass_decision(pa, wtype=wtype, probe=probe,
                              token_bit=token_w,
                              pc_hits=pc_hits_v,
                              pc_acc=pc_acc_v,
                              pc_req=pc_req_v,
                              rand_h=POL.rand_draw(addr))
    return byp & valid, wtype


def bypass_decision_vals(warp_type_w, accesses_w, token_w, st: SimState,
                         addr, pc, valid, prm: SimParams,
                         pa: PolicyArrays, oracle_wt):
    """``bypass_decision`` with the per-warp classifier inputs
    (``clf.warp_type[w]``, ``clf.accesses[w]``, ``tokens[w]``) passed
    as values instead of gathered here. The wavefront engine's fused
    path carries those as wave-resident vectors across the lane scan
    (each warp appears at most once per wave, so the carried slice is
    exactly what a fresh gather would read); the event path and the
    unfused wavefront path gather per call via ``bypass_decision``.
    """
    pidx = pc_index(pc, prm)
    byp, wtype = bypass_decision_core(
        warp_type_w, accesses_w, token_w, st.pc_hits[pidx],
        st.pc_acc[pidx], st.pc_req[pidx], addr, valid, prm, pa, oracle_wt)
    return byp, wtype, pidx


def bypass_decision(st: SimState, w, addr, pc, valid, prm: SimParams,
                    pa: PolicyArrays, tokens, oracle_wt):
    """Returns (byp, wtype, pidx) for one request or a wave of requests.

    ``oracle_wt`` is the trace generator's ground-truth per-phase label
    for the request's (instruction, warp); the policy's labeling mode
    (①) selects between it and the online classifier's label, so one
    vmapped sweep can compare oracle / online / stale labelings.

    Periodic probe so a reformed warp can be re-learned: every
    ``probe_interval``-th access of a bypassing warp still takes the
    cache path, and the classifier's window ratio is measured over that
    cache-path sample only (``classifier.observe``'s ``probed`` mask) —
    an undiluted probe stream is what lets a label ratchet back UP.
    """
    return bypass_decision_vals(st.clf.warp_type[w], st.clf.accesses[w],
                                tokens[w], st, addr, pc, valid, prm, pa,
                                oracle_wt)


# ---------------------------------------------------------------------------
# ③ insertion rank (policy + evicted-address-filter signal)
# ---------------------------------------------------------------------------

def insertion_rank(st: SimState, wtype, addr, prm: SimParams,
                   pa: PolicyArrays):
    # a filter bit is set iff it carries the current generation stamp
    # (the periodic EAF reset bumps the generation instead of clearing
    # the array — same semantics, no O(eaf_bits) work per request)
    ebit = st.eaf[eaf_index(addr, prm)] == st.eaf_gen
    return POL.insertion_rank(pa, wtype=wtype, eaf_bit=ebit,
                              rrip_max=prm.rrip_max)


# ---------------------------------------------------------------------------
# ④ DRAM row-buffer timing split
# ---------------------------------------------------------------------------

def dram_occ_lat(row_hit, prm: SimParams):
    """Row-hit/row-miss split into occupancy (pipelined throughput) and
    latency (critical path) components."""
    occ = jnp.where(row_hit, prm.occ_rowhit, prm.occ_rowmiss)
    lat = jnp.where(row_hit, prm.t_rowhit, prm.t_rowmiss)
    return occ, lat


# ---------------------------------------------------------------------------
# queuing-delay histogram binning (Fig 5)
# ---------------------------------------------------------------------------

def qdelay_bin(qdelay):
    """Map queue delays to their _QBINS histogram bin, elementwise."""
    edges = _QBINS[1:-1]
    return jnp.sum(qdelay[..., None] >= edges, axis=-1).astype(I32)


# ---------------------------------------------------------------------------
# end-of-simulation outputs shared by both engines
# ---------------------------------------------------------------------------

def finalize_outputs(st: SimState, ready, ratio_t, compute_gap, *,
                     n_instr: int, n_warps: int,
                     prm: SimParams) -> Dict[str, Any]:
    """Aggregate the final state into the public metrics dict."""
    makespan = jnp.max(ready)
    m = dict(st.metrics)
    total_instr = jnp.asarray(n_instr * n_warps, F32)
    # System throughput in a steady state where finished warps' slots are
    # backfilled by fresh thread blocks (as on a real GPU): the sum of
    # per-warp progress rates. makespan-based IPC is also reported.
    # compute_gap may be per-instruction (f32[I], phased intensity): each
    # warp's ready time includes one trailing gap — the last
    # instruction's.
    last_gap = compute_gap if jnp.ndim(compute_gap) == 0 else compute_gap[-1]
    per_warp_time = jnp.maximum(ready - last_gap, 1.0)
    ipc = jnp.sum(n_instr / per_warp_time)
    ipc_makespan = total_instr / jnp.maximum(makespan, 1.0)
    energy = (m["l2_accesses"] * prm.e_l2 + m["dram_accesses"] * prm.e_dram
              + makespan * prm.e_static)
    out = dict(m)
    out.update({
        "makespan": makespan,
        "ipc": ipc,
        "ipc_makespan": ipc_makespan,
        "warp_time": per_warp_time,
        "energy": energy,
        "perf_per_energy": ipc / energy * 1e3,
        "warp_hit_ratio": st.tot_hits / jnp.maximum(st.tot_acc, 1),
        "warp_type": st.clf.warp_type,
        "ratio_over_time": ratio_t,            # [I, W]
        "miss_rate": 1.0 - m["l2_hits"] / jnp.maximum(m["l2_accesses"], 1),
        "mean_qdelay": m["qdelay_sum"] / jnp.maximum(m["l2_accesses"], 1),
    })
    return out
