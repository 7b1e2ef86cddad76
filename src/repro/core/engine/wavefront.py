"""Wavefront engine (``engine="wavefront"``): batched round-lockstep
event loop.

Instead of popping ONE earliest-ready warp per step (the exact event
engine), each step pops a *wave* of the ``wave_size`` earliest-ready
warps and services all their W×L requests vectorized. Because the wave is
selected by readiness, its requests are close together in simulated time,
which is what makes batched processing faithful. Each wave runs two
passes:

  1. **Cache pass**: bypass decisions, tag lookup, RRIP fill/eviction,
     EAF and PC-table bookkeeping, and the classifier update on
     wave-resident [B] counter rows. A lane sub-step carries at most ONE
     request per warp, so the batched observe is equivalent to the event
     loop's sequential per-request observes (warp ids are distinct —
     pinned by the differential suite). None of these outcomes depend on
     request *timing*, so the pass needs no queue state. Cross-slot
     structural conflicts inside one sub-step (two wave warps filling
     the same cache set) resolve last-write-wins in chronological slot
     order. The implementation lives in ``repro.kernels.cache_pass``
     behind a backend gate (``cache_backend``, mirroring the timing
     pass's ``scan_backend``): ``"ref"`` is the original per-lane
     ``lax.scan``, ``"fused"`` a bitwise-identical one-sweep
     reformulation that resolves same-set write conflicts with explicit
     per-set chronology pointers (the default), ``"pallas"`` a
     lane-chunked TPU kernel. The lifetime counters and scalar metrics
     — never read during the wave — are hoisted out of the pass and
     applied once per wave for every backend (integer adds, so the
     totals are exact either way).

  2. **Timing pass**: all B×L requests of the wave, in warp-major
     chronological order (the event loop's pop-and-service order), go
     through segmented prefix queue recovery — ``start_j = c_j +
     max_{i<=j}(max(t_i, free) - c_i)`` with ``c`` the exclusive prefix
     occupancy of the request's queue (exactly the sequential FR-FCFS
     arrival-order service times). The implementation now lives in
     ``repro.kernels.wavefront_scan`` behind a backend gate
     (``scan_backend``): ``"ref"`` is the original unfused multi-pass
     form, ``"fused"`` a bitwise-identical slot-major reformulation with
     fast associative scans (the default), ``"pallas"`` a one-pass
     TPU kernel. The DRAM row-buffer chain links each request to its
     true chronological predecessor in its channel, and the low-priority
     queue's floor folds in the running busy horizon of the wave's
     high-priority chain (strict priority, as in the event engine).
     Cross-wave carry uses the work-conserving backlog floor
     (``wavefront_scan.ref.carry_floor``).

The approximation ladder (DESIGN.md §9): event (wave of 1, exact) →
wavefront (wave of W/6, W/4 at stress populations — near-chronological;
the differential suite pins the envelope) → full round-lockstep
(``wave_size=n_warps`` — one scan step services an entire instruction
round). A wave of one warp reduces every prefix op to the event
engine's scalar update, so single-warp traces match the event path
exactly.

Cost: the wave loop is a ``lax.while_loop`` capped at ``ceil(I·W/B) +
I`` steps but exiting at the first wave with no active warp left: with
>= B warps active every wave services B instructions (<= ceil(I·W/B)
such waves), and once fewer than B remain every wave advances ALL of
them (<= I further waves) — the cap is only reached when warp
completion is maximally staggered, so typical runs take close to
ceil(I·W/B) steps instead of the cap (the seed-era scan always ran all
of them; a wave of inactive warps is a proven no-op, so early exit is
byte-identical). Each step does O(B)-vectorized work, vs the event
loop's O(I·W·L) sequential steps — this is what runs the 1k–4k-warp
stress matrix (tracegen/stress.py) end-to-end.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.engine import request as REQ
from repro.core.engine.state import SimParams, SimState, init_state
from repro.kernels.cache_pass import ops as CPASS
from repro.kernels.cache_pass.ref import observe_gathered, observe_vec
from repro.kernels.wavefront_scan import ops as WSCAN
from repro.kernels.wavefront_scan.ref import QueueCarry
from repro.policy import PolicyArrays, ops as POL

# the O(B) classifier-observe forms moved to repro.kernels.cache_pass.ref
# with the rest of the pass (PR 8); re-exported for their established
# import site (tests/test_engine_differential.py pins them against the
# full-width ``classifier.observe``)
_observe_gathered = observe_gathered
_observe_vec = observe_vec

F32 = jnp.float32
I32 = jnp.int32

_NEG = -jnp.inf


def _warp_constraint(mesh, axes, dim: int):
    """Sharding constraint placing mesh ``axes`` on dimension ``dim``
    (the warp axis) of an array; identity without a mesh. Composes with
    vmap — the batch rule inserts the vmapped dim as replicated, so the
    same constraint serves the policy/seed-vmapped sweep."""
    if mesh is None or axes is None:
        return lambda x: x

    def constrain(x):
        spec = [None] * x.ndim
        spec[dim] = axes
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec(*spec)))
    return constrain


def _replicate_constraint(mesh):
    """Constraint gathering an array to full replication — applied to
    the per-warp state right before ``finalize_outputs`` so the final
    float reductions (e.g. the IPC sum over warps) run over a replicated
    array in the exact single-device order (bitwise parity)."""
    if mesh is None:
        return lambda x: x
    return lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*([None] * x.ndim))))


def _fixed_order_sum(x):
    """Sum of a 1-D f32 array in one fixed pairwise order, written out
    as elementwise adds. ``jnp.sum`` leaves the order to the compiler,
    which picks it per backend and per program (XLA:TPU differs from
    XLA:CPU, and the warp-sharded program from the one-chip one); once
    partial sums pass 2**24 the order shows in the last bits. Zero
    padding to a power of two adds nothing."""
    n = x.shape[0]
    size = 1 << max(n - 1, 0).bit_length()
    x = jnp.pad(x, (0, size - n))
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def default_wave_size(n_warps: int) -> int:
    """Readiness-window size. W/6 keeps a wave chronologically tight
    (warp populations that drifted apart never share a wave); calibrated
    on the 15-workload × 4-policy differential matrix at the paper's 48
    warps (worst |IPC| deviation 1.9%, worst makespan deviation 2.1% —
    DESIGN.md §9). Above the differential-verified zone the stress
    populations are W/4-waved: thousands of statistically similar warps
    keep waves relatively tight, and the wider wave amortizes per-step
    cost further."""
    if n_warps > 256:
        return n_warps // 4
    return max(min(n_warps, 8), n_warps // 6)


class QueueAnchors(NamedTuple):
    """Per-queue service frontier, in two time axes.

    ``*_ts`` is the largest L2-arrival (wave sort) time the queue has
    serviced; ``*_sa`` the largest service-arrival time (equal to
    ``*_ts`` for banks, but DRAM requests arrive at ``t_head + l2_lat``
    after the L2 queue, so the axes differ there). Together with the
    queue's busy-until (``bank_free``/``hp_free``/``lp_free`` in
    SimState) they summarize the queue's backlog for the next wave:
    ``backlog = free - sa``.
    """
    bank_ts: jnp.ndarray     # f32[banks]
    hp_ts: jnp.ndarray       # f32[channels]
    hp_sa: jnp.ndarray       # f32[channels]
    lp_ts: jnp.ndarray       # f32[channels]
    lp_sa: jnp.ndarray       # f32[channels]


def init_anchors(prm: SimParams) -> QueueAnchors:
    c = jnp.full((prm.dram_channels,), _NEG, F32)
    return QueueAnchors(bank_ts=jnp.full((prm.banks,), _NEG, F32),
                        hp_ts=c, hp_sa=c, lp_ts=c, lp_sa=c)


def _timing_pass(st: SimState, an: QueueAnchors, recs, prm: SimParams,
                 backend: str) -> tuple:
    """Arrival-ordered queue recovery for one wave's B×L requests.

    Chronological bank/channel semantics come from segmented prefix
    queue recovery per L2 bank, DRAM channel and priority class over the
    wave's requests in WARP-MAJOR order — warp slots ascend in ready
    time (the wave selection) and a warp's lanes stay consecutive, which
    is exactly the event loop's processing order (pop the earliest warp,
    service all its lanes back-to-back). Interleaving by raw per-lane
    arrival instead would shred the DRAM row-buffer streaks a streaming
    warp's consecutive lines produce. The recovery itself is
    ``repro.kernels.wavefront_scan`` under the selected backend.
    """
    t_s, addr_s, valid_s, byp_s, use_l2_s, hit_s, hp_s = \
        [jnp.swapaxes(x, 0, 1).reshape(-1) for x in recs[:7]]  # [N = B*L]
    # a wave of ONE warp is the event loop — no batching to compensate
    # for, so the carry floor is the plain busy-until (bitwise parity
    # with engine="event", asserted by the differential suite)
    exact = recs[0].shape[1] == 1

    bank = REQ.bank_index(addr_s, prm)
    ch = REQ.dram_channel(addr_s, prm)
    row = REQ.dram_row(addr_s, prm)
    go_dram = valid_s & (byp_s | ~hit_s)

    carry = QueueCarry(
        bank_free=st.bank_free, bank_ts=an.bank_ts,
        hp_free=st.hp_free, hp_ts=an.hp_ts, hp_sa=an.hp_sa,
        lp_free=st.lp_free, lp_ts=an.lp_ts, lp_sa=an.lp_sa,
        cur_row=st.cur_row)
    t_head, t0, row_hit, nc = WSCAN.wave_queue_recovery(
        t_s, bank, use_l2_s, ch, row, go_dram, byp_s, hp_s, carry,
        banks=prm.banks, channels=prm.dram_channels, l2_svc=prm.l2_svc,
        l2_lat=prm.l2_lat, occ_rowhit=prm.occ_rowhit,
        occ_rowmiss=prm.occ_rowmiss, exact=exact, backend=backend)

    qdelay = jnp.where(use_l2_s, t_head - t_s, 0.0)
    _, lat = REQ.dram_occ_lat(row_hit, prm)
    t_done = jnp.where(hit_s, t_head + prm.l2_lat, t0 + lat)
    t_done = jnp.where(valid_s, t_done, t_s)

    # ---- metrics ------------------------------------------------------------
    m = st.metrics
    qbin = REQ.qdelay_bin(qdelay)
    metrics = dict(m)
    if WSCAN.resolve_backend(backend) != "ref":
        # one-hot histogram: integer adds in any order are exact, and
        # the dense [N, bins] reduce beats XLA:CPU's serialized
        # scatter-add by ~4x at stress-scale N (the ref backend keeps
        # the original scatter so the A/B baseline graph is unchanged)
        nb = m["qdelay_hist"].shape[0]
        oh = qbin[:, None] == jnp.arange(nb, dtype=I32)[None, :]
        metrics["qdelay_hist"] = m["qdelay_hist"] + jnp.sum(
            jnp.where(oh, use_l2_s[:, None].astype(I32), 0), axis=0)
    else:
        metrics["qdelay_hist"] = m["qdelay_hist"].at[qbin].add(
            use_l2_s.astype(I32))
    metrics["qdelay_sum"] = m["qdelay_sum"] + _fixed_order_sum(qdelay)
    metrics["dram_accesses"] = m["dram_accesses"] + jnp.sum(
        go_dram.astype(I32))
    metrics["row_hits"] = m["row_hits"] + jnp.sum(row_hit.astype(I32))

    new_st = st._replace(bank_free=nc.bank_free, cur_row=nc.cur_row,
                         hp_free=nc.hp_free, lp_free=nc.lp_free,
                         metrics=metrics)
    new_an = QueueAnchors(bank_ts=nc.bank_ts, hp_ts=nc.hp_ts,
                          hp_sa=nc.hp_sa, lp_ts=nc.lp_ts, lp_sa=nc.lp_sa)
    # back to the cache pass's [L, B] layout
    lanes, b = recs[0].shape
    t_done_lb = jnp.swapaxes(t_done.reshape(b, lanes), 0, 1)
    return new_st, new_an, t_done_lb


def resolve_wave_size(wave_size: Optional[int], n_warps: int) -> int:
    """The warps one wave holds: ``wave_size``, else the default, and
    never more than the population."""
    return max(1, min(wave_size or default_wave_size(n_warps), n_warps))


def simulate_core(trace_lines, trace_pcs, compute_gap, oracle_types,
                  pa: PolicyArrays, *, n_warps: int, lanes: int,
                  prm: SimParams, wave_size: Optional[int] = None,
                  scan_backend: str = "auto",
                  cache_backend: str = "auto",
                  warp_mesh=None, warp_axes=None
                  ) -> Tuple[Dict[str, Any], Any]:
    """One workload × one policy on the wavefront engine. Vmappable.

    Returns ``(metrics, waves)``: the metrics dict of
    ``event.simulate_core`` and the i32 count of waves the loop ran.
    ``compute_gap`` is a scalar or f32[I]; ``oracle_types`` i32[I, W]
    (same contract as ``event.simulate_core``). ``scan_backend`` selects
    the timing-pass implementation (``wavefront_scan.BACKENDS``) and
    ``cache_backend`` the cache-pass one (``cache_pass.BACKENDS``):
    ``"ref"`` is the respective pre-fusion path kept as the unfused side
    of the in-run perf A/B; every other backend is output-identical to
    it (bitwise for ``"fused"``, the default under ``"auto"``), so
    the two knobs compose freely.

    ``warp_mesh`` + ``warp_axes`` (both static, pre-resolved by the
    ``simulate``/``simulate_sweep`` front door) enable the sharded-warp
    path: the trace storage arrays ([W, I, L] — the memory that grows
    with the population) and the per-warp machine state (ready/ptr
    clocks, classifier rows, lifetime counters, the [I, W] ratio trace)
    are constrained to shard their warp axis over those mesh axes, so a
    16k–64k-warp stress spec spreads across the mesh instead of sitting
    on one device. The wave gathers/scatters cross shards (XLA inserts
    the collectives); the per-wave [B]-sized compute is replicated, and
    the state is gathered back to full replication before
    ``finalize_outputs`` so the closing float reductions keep the exact
    single-device operand order — the whole path is bitwise-identical
    to the unsharded engine (pinned by tests/test_sharded_sweep.py)."""
    n_instr = trace_lines.shape[0]
    shard_w0 = _warp_constraint(warp_mesh, warp_axes, 0)
    shard_w1 = _warp_constraint(warp_mesh, warp_axes, 1)
    B = resolve_wave_size(wave_size, n_warps)
    # wave-count CAP (the while_loop usually exits earlier, see module
    # docstring): phase 1 (>= B warps active) services B instructions
    # per wave; once fewer than B warps remain every wave advances all
    # of them, so at most n_instr further waves finish the tail
    n_waves = -(-n_instr * n_warps // B) + n_instr
    # the stable-argsort wave selection only survives in the all-ref
    # baseline graph; any fused backend takes the top_k form (bitwise
    # tie-parity between the two is pinned by the differential suite)
    fused = (WSCAN.resolve_backend(scan_backend) != "ref"
             or CPASS.resolve_backend(cache_backend) != "ref")
    tokens = POL.pcal_tokens(pa, n_warps)

    lines_wi = shard_w0(jnp.swapaxes(trace_lines, 0, 1))  # [W, I, L]
    pcs_wi = shard_w0(jnp.swapaxes(trace_pcs, 0, 1))      # [W, I]
    oracle_wi = shard_w0(jnp.swapaxes(oracle_types, 0, 1))  # [W, I]

    st0 = init_state(n_warps, prm)
    st0 = st0._replace(clf=jax.tree.map(shard_w0, st0.clf),
                       tot_hits=shard_w0(st0.tot_hits),
                       tot_acc=shard_w0(st0.tot_acc))
    an0 = init_anchors(prm)
    ready0 = shard_w0(jnp.zeros((n_warps,), F32))
    ptr0 = shard_w0(jnp.zeros((n_warps,), I32))
    ratio0 = shard_w1(jnp.zeros((n_instr, n_warps), F32))

    def wave_step(carry):
        st, an, ready, ptr, ratio_t, k = carry
        # each pass runs in a named scope of its own. The names reach
        # the device trace as op metadata only (no op changes), and the
        # benchmark finds each pass's device time by them
        # (bench/lib/scopes.py): keep them through refactors
        with jax.named_scope("wave_select"):
            active = ptr < n_instr
            # wave = the B earliest-ready active warps, slots in
            # chronological order, ties by warp id (the event loop's
            # argmin). top_k on the negated keys returns exactly the
            # first B entries of the stable ascending argsort (equal
            # keys by lower index) at O(W log B) instead of the full
            # O(W log W) sort — tie-parity is pinned by the
            # differential suite.
            if fused:
                w_sel = jax.lax.top_k(
                    jnp.where(active, -ready, _NEG), B)[1].astype(I32)
            else:
                order = jnp.argsort(jnp.where(active, ready, jnp.inf))
                w_sel = order[:B].astype(I32)
            slot_ok = active[w_sel]
            i_sel = ptr[w_sel]
            t0 = ready[w_sel]
            lines_b = lines_wi[w_sel, i_sel]             # [B, L]
            pc_b = pcs_wi[w_sel, i_sel]                  # [B]
            owt_b = oracle_wi[w_sel, i_sel]              # [B]

            # wave-resident classifier rows: gather once, carry [B]
            # slices through the pass, scatter back once (wave warp ids
            # are distinct, so nothing else touches the rows mid-wave —
            # see cache_pass.ref.observe_vec)
            clf_b0 = jax.tree.map(lambda a: a[w_sel], st.clf)
            tokens_b = tokens[w_sel]

        with jax.named_scope("cache_pass"):
            st, clf_b, recs = CPASS.wave_cache_pass(
                st, clf_b0, tokens_b, t0, jnp.swapaxes(lines_b, 0, 1),
                pc_b, owt_b, slot_ok, prm, pa, backend=cache_backend)
            st = st._replace(clf=jax.tree.map(
                lambda full, b: full.at[w_sel].set(b), st.clf, clf_b))

        with jax.named_scope("timing_pass"):
            st, an, t_done = _timing_pass(st, an, recs, prm, scan_backend)

        with jax.named_scope("wave_commit"):
            (_, _, valid_lb, byp_lb, use_lb, hit_lb, _, vt_lb, ev_lb) = recs
            # hoisted write-only bookkeeping: one update per wave instead
            # of one per lane (integer adds — exact either way)
            m = st.metrics
            metrics = dict(m)
            metrics["l2_accesses"] = m["l2_accesses"] + jnp.sum(
                use_lb.astype(I32))
            metrics["l2_hits"] = m["l2_hits"] + jnp.sum(
                hit_lb.astype(I32))
            metrics["bypasses"] = m["bypasses"] + jnp.sum(
                byp_lb.astype(I32))
            # one-hot over the type bins (victim_type is always a written
            # label, in range) instead of an [N] scatter-add, which
            # XLA:CPU serializes per element
            n_types = m["evictions_by_type"].shape[0]
            vt_oh = vt_lb.reshape(-1)[:, None] \
                == jnp.arange(n_types, dtype=I32)[None, :]
            metrics["evictions_by_type"] = m["evictions_by_type"] \
                + jnp.sum(jnp.where(
                    vt_oh, ev_lb.reshape(-1)[:, None].astype(I32), 0),
                    axis=0)
            st = st._replace(
                tot_hits=st.tot_hits.at[w_sel].add(
                    jnp.sum(hit_lb.astype(I32), axis=0)),
                tot_acc=st.tot_acc.at[w_sel].add(
                    jnp.sum(valid_lb.astype(I32), axis=0)),
                metrics=metrics)

            dmax = jnp.max(jnp.where(valid_lb, t_done, -jnp.inf), axis=0)
            dmin = jnp.min(jnp.where(valid_lb, t_done, jnp.inf), axis=0)
            has_req = jnp.isfinite(dmax)
            stall = jnp.where(has_req & slot_ok, dmax - dmin, 0.0)
            metrics = dict(st.metrics)
            metrics["stall_cycles"] = (metrics["stall_cycles"]
                                       + _fixed_order_sum(stall))
            st = st._replace(metrics=metrics)

            w_ok = jnp.where(slot_ok, w_sel, n_warps)    # OOB -> dropped
            gap = compute_gap if jnp.ndim(compute_gap) == 0 \
                else compute_gap[i_sel]
            ready = ready.at[w_ok].set(
                jnp.where(has_req, dmax + gap, t0 + gap),
                mode="drop")
            ptr = ptr.at[w_ok].add(1, mode="drop")
            # Fig 4 snapshot: sampled ratio after each serviced
            # instruction
            ratio_t = ratio_t.at[i_sel, w_ok].set(st.clf.ratio[w_sel],
                                                  mode="drop")
        # pin the loop-carried warp-axis sharding (no-ops unsharded):
        # without the constraint GSPMD may resolve the scattered-into
        # carries to a different layout each iteration
        st = st._replace(clf=jax.tree.map(shard_w0, st.clf),
                         tot_hits=shard_w0(st.tot_hits),
                         tot_acc=shard_w0(st.tot_acc))
        return (st, an, shard_w0(ready), shard_w0(ptr),
                shard_w1(ratio_t), k + 1)

    def wave_pending(carry):
        _, _, _, ptr, _, k = carry
        return (k < n_waves) & jnp.any(ptr < n_instr)

    (st, _, ready, _, ratio_t, waves) = jax.lax.while_loop(
        wave_pending, wave_step,
        (st0, an0, ready0, ptr0, ratio0, jnp.zeros((), I32)))

    # gather the per-warp state back to replication before the closing
    # reductions — jnp.sum over a sharded axis would reduce shard-local
    # partials first, changing the float accumulation order vs the
    # single-device engine
    rep = _replicate_constraint(warp_mesh)
    st = st._replace(clf=jax.tree.map(rep, st.clf),
                     tot_hits=rep(st.tot_hits), tot_acc=rep(st.tot_acc))
    return REQ.finalize_outputs(st, rep(ready), rep(ratio_t), compute_gap,
                                n_instr=n_instr, n_warps=n_warps,
                                prm=prm), waves
