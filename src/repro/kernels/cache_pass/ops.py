"""Backend-gated entry point for the wavefront cache pass.

``wave_cache_pass`` services one wave's B×L requests — bypass decision,
L2 tag lookup, RRIP fill/eviction, EAF + PC-table bookkeeping, and the
classifier observe — and returns the advanced state plus the per-lane
record tuple the timing pass consumes. Backends:

  * ``"ref"``    — the original per-lane ``lax.scan`` (ref.py), carried
    over verbatim from the engine. The unfused side of the in-run perf
    A/B and the parity oracle.
  * ``"fused"``  — bitwise-identical one-sweep reformulation. Duplicate
    set indices between a lane's wave members (lanes CAN alias sets
    even though warp ids are distinct) resolve last-write-wins in slot
    order — the ordering the sequential ref scan gets for free. The
    sweep picks one of two constructions per wave width (a static,
    shape-level choice — B is fixed per compiled wave step):

      - wide waves (B ≥ 128, where same-set aliasing is dense and the
        scatter volume dominates): the CHRONOLOGY-POINTER construction.
        Every slot's post-write row lands in a private row buffer (one
        dynamic-update-slice per lane), and conflict resolution is an
        explicit segmented argmax over chronological slot index per
        touched set: each writing slot scatter-MAXES its chronology
        index into a per-set pointer table, so after the sweep each
        set's pointer names exactly the last slot in program order that
        wrote it; the winning rows are dereferenced once at the end.
        Three pointer chains ride one fused [2·sets + eaf_bits] table:
        tags+meta advance only on ``allocate``, RRIP on every
        ``use_l2`` request (hits rewrite their row), and the EAF write
        degenerates to the same scatter-max because the generation
        stamp is monotone nondecreasing. The construction is bitwise
        because (a) every slot's row is computed from lane-start state
        — exactly what the ref scatters write — and (b) same-lane
        same-set allocators share identical lane-start RRIP rows, hence
        the same victim way, so the winning row subsumes the losers'
        single-element writes.
      - narrow waves (B < 128, paper scale, where the pass is dispatch-
        bound — every extra XLA fusion boundary costs more than the
        work it saves): the ref-shaped masked scatters are kept (XLA
        applies scatter updates in operand order, which IS slot order,
        so the same last-write-wins semantics fall out and the aliasing
        suites pin them), and the win comes from retiring redundant
        dispatches: the three PC counters travel as ONE stacked
        [pc_entries, 3] working table (one gather + one row scatter-add
        per lane instead of three of each), the hit-way ``argmax`` is
        dropped (the tag-match mask already IS the hit-way one-hot: a
        line lives in at most one way of its set), and the per-request
        index/draw precomputation is folded into the lane body where
        XLA fuses it for free, keeping the lane scan's sliced inputs
        down to the address matrix alone.

    Wide waves also sort the wave by PC entry once (slots sharing a PC
    entry form segments) so each lane's counter reads are exact segment
    sums off one cumsum and the [pc_entries] tables take a single
    conflict-free scatter-add at wave end. ``"auto"`` picks this
    backend on every platform.
  * ``"pallas"`` — lane-chunked TPU kernel (kernel.py): grid over the
    L lanes with the cache state carried in VMEM scratch and all
    gather/scatter replaced by dense one-hot selects/reductions.
    Explicit opt-in only: on a TPU it is lowered through Mosaic, which
    refuses it today (see kernel.py), and it raises rather than falling
    back; off a TPU it runs in interpret mode, which is how the CPU
    tests validate it.
  * ``"auto"``   — ``"fused"`` on every platform.

The differential suites pin fused == ref == pallas bitwise on every
metric across the workload × policy matrix and on adversarial same-set
aliasing grids (tests/test_kernels.py, tests/test_engine_differential.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import classifier as CLF
from repro.core.engine import request as REQ
from repro.core.engine.state import SimParams, SimState
from repro.kernels.cache_pass import ref as _ref
from repro.kernels.cache_pass.kernel import wave_cache_kernel
from repro.policy import PolicyArrays, ops as POL

F32 = jnp.float32
I32 = jnp.int32

BACKENDS = ("auto", "fused", "ref", "pallas")

# Static wave-width threshold between the two fused constructions. Below
# it the pass is dispatch-bound and ref-shaped scatters are effectively
# free; above it scatter volume dominates and the chronology-pointer
# merge + sorted-PC segments pay for their fixed overhead.
WIDE_WAVE_MIN_B = 128


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown cache backend {backend!r}; choose from {BACKENDS}")
    return "fused" if backend == "auto" else backend


def _fused_narrow(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                  t0, addr_lb, pc_b, owt_b, slot_ok,
                  prm: SimParams, pa: PolicyArrays) -> tuple:
    """Narrow-wave (B < 128) fused sweep — see the module docstring.

    The lane body mirrors ``ref.lane_cache_step`` line for line; the
    deltas are all dispatch-count reductions: one stacked PC table, no
    hit-way argmax, per-request indices/draws computed in-body (fused),
    and a lane scan whose sliced inputs are just (lane, addr row).
    """
    lanes, B = addr_lb.shape
    W = prm.ways
    obs_consts = _ref.observe_consts(prm, pa)
    pidx = REQ.pc_index(pc_b, prm)                    # constant across lanes
    pc_tab0 = jnp.stack([st.pc_hits, st.pc_acc, st.pc_req], axis=1)

    def lane_step(carry, x):
        tags, meta, rrip, eaf, eaf_gen, eaf_ctr, clf_b, pc_tab = carry
        lane, addr = x
        # pure-in-addr precomputation: elementwise, fused into the body
        valid = (addr >= 0) & slot_ok
        sidx = REQ.set_index(addr, prm)
        erd = REQ.eaf_index(addr, prm)
        t_arr = t0 + lane.astype(F32) * prm.lane_skew

        # ---- ①② label select + bypass decision ----------------------------
        pc_vals = pc_tab[pidx]                        # [B, 3] one gather
        byp, wtype = REQ.bypass_decision_core(
            clf_b.warp_type, clf_b.accesses, tokens_b, pc_vals[:, 0],
            pc_vals[:, 1], pc_vals[:, 2], addr, valid, prm, pa, owt_b)
        use_l2 = valid & ~byp

        # ---- L2 lookup (lane-start rows) -----------------------------------
        tset = tags[sidx]
        # the match mask doubles as the hit-way one-hot: a line lives in
        # at most one way of its set (allocation happens only on miss;
        # same-lane duplicate allocators pick the same victim)
        is_line = tset == addr[:, None]
        hit = jnp.any(is_line, axis=1) & use_l2
        rset = rrip[sidx]
        rset = jnp.where(hit[:, None] & is_line, 0, rset)

        # ---- ③ fill + insertion --------------------------------------------
        allocate = use_l2 & ~hit
        shift = prm.rrip_max - jnp.max(rset, axis=1)
        rset_aged = rset + jnp.where(allocate, shift, 0)[:, None]
        victim = jnp.argmax(rset_aged, axis=1)
        evicted = jnp.take_along_axis(tset, victim[:, None], axis=1)[:, 0]
        victim_type = meta[sidx, victim]
        ebit = eaf[erd] == eaf_gen
        rank = POL.insertion_rank(pa, wtype=wtype, eaf_bit=ebit,
                                  rrip_max=prm.rrip_max)

        # ---- slot-ordered masked scatters (LWW falls out of the
        # ---- operand-order application; pinned by the aliasing suites) -----
        s_alloc = jnp.where(allocate, sidx, prm.sets)
        tags = tags.at[s_alloc, victim].set(addr, mode="drop")
        vict_oh = jnp.arange(W, dtype=I32)[None, :] == victim[:, None]
        new_row = jnp.where(allocate[:, None],
                            jnp.where(vict_oh, rank[:, None], rset_aged),
                            rset)
        s_l2 = jnp.where(use_l2, sidx, prm.sets)
        rrip = rrip.at[s_l2].set(new_row, mode="drop")
        meta = meta.at[s_alloc, victim].set(wtype, mode="drop")
        ev_valid = allocate & (evicted >= 0)
        eidx = REQ.eaf_index(evicted, prm)
        eaf = eaf.at[jnp.where(ev_valid, eidx, prm.eaf_bits)].set(
            eaf_gen, mode="drop")

        # ---- ① classifier + PC table + EAF counter -------------------------
        clf_b = _ref.observe_vec(clf_b, hit, valid.astype(I32),
                                 use_l2.astype(I32), prm, pa,
                                 consts=obs_consts)
        delta = jnp.stack([(hit & use_l2), use_l2, valid, ev_valid],
                          axis=1).astype(I32)
        pc_tab = pc_tab.at[pidx].add(delta[:, :3])    # one row scatter-add
        n_ev = jnp.sum(ev_valid.astype(I32))
        eaf_ctr = eaf_ctr + n_ev
        reset = eaf_ctr >= prm.eaf_capacity
        eaf_gen = jnp.where(reset, eaf_gen + 1, eaf_gen)
        eaf_ctr = jnp.where(reset, 0, eaf_ctr)

        hp = POL.is_high_priority(pa, wtype)
        rec = (t_arr, addr, valid, byp, use_l2, hit, hp,
               victim_type, ev_valid)
        return (tags, meta, rrip, eaf, eaf_gen, eaf_ctr, clf_b, pc_tab), rec

    carry0 = (st.tags, st.meta_type, st.rrip, st.eaf, st.eaf_gen,
              st.eaf_ctr, clf_b0, pc_tab0)
    xs = (jnp.arange(lanes, dtype=I32), addr_lb)
    carry, records = jax.lax.scan(lane_step, carry0, xs)
    tags, meta, rrip, eaf, eaf_gen, eaf_ctr, clf_b, pc_tab = carry
    new_st = st._replace(
        tags=tags, rrip=rrip, meta_type=meta, eaf=eaf, eaf_gen=eaf_gen,
        eaf_ctr=eaf_ctr, pc_hits=pc_tab[:, 0], pc_acc=pc_tab[:, 1],
        pc_req=pc_tab[:, 2])
    return new_st, clf_b, records


def _fused_wide(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                t0, addr_lb, pc_b, owt_b, slot_ok,
                prm: SimParams, pa: PolicyArrays) -> tuple:
    """Wide-wave (B ≥ 128) fused sweep — the chronology-pointer
    construction (see the module docstring). Per lane: one fused
    3B-index gather resolves tag/meta, RRIP, and EAF reads through the
    pointer table; every slot's post-write row lands in a private row
    buffer via one dynamic-update-slice; and the explicit last-write-
    wins reduction is a single 3B-element scatter-MAX of chronology
    indices (segmented argmax over slot order per touched set), with
    non-writing slots parked one-past-the-end and dropped.
    """
    lanes, B = addr_lb.shape
    W = prm.ways
    S = prm.sets
    E = prm.pc_entries
    DROP = 2 * S + prm.eaf_bits                       # park index, dropped
    slot = jnp.arange(B, dtype=I32)
    obs_consts = _ref.observe_consts(prm, pa)
    pidx = REQ.pc_index(pc_b, prm)                    # constant across lanes

    # ---- PC segments: sort the wave by PC entry once -----------------------
    # slots sharing an entry form runs; per lane, one cumsum over the
    # sorted deltas + two gathers yield each slot's exact running entry
    # total (integer adds commute), so counter reads are scatter-free
    # and the [E] tables take ONE conflict-free scatter-add at wave end.
    pperm = jnp.argsort(pidx)                         # stable
    spidx = pidx[pperm]
    inv = jnp.argsort(pperm)
    brk = spidx[1:] != spidx[:-1]
    is_end = jnp.concatenate([brk, jnp.ones((1,), bool)])
    seg_start = jax.lax.cummax(
        jnp.where(jnp.concatenate([jnp.ones((1,), bool), brk]), slot, -1))
    seg_end = jax.lax.cummin(jnp.where(is_end, slot, B), reverse=True)
    first_seg = seg_start == 0
    seg_idx = jnp.concatenate([seg_end, jnp.maximum(seg_start - 1, 0)])
    base_pc = jnp.stack([st.pc_hits[pidx], st.pc_acc[pidx],
                         st.pc_req[pidx]], axis=1)    # [B, 3]

    # ---- row buffer + chronology-pointer table -----------------------------
    # buf rows 0..S-1 hold the wave-start [tags | meta | rrip] rows; each
    # lane's B slots own rows S + lane·B + slot. A set's current row is
    # buf[pointer]; pointers only ever move FORWARD in chronology, which
    # is what makes the scatter-max below an exact LWW reduction.
    buf0 = jnp.concatenate(
        [jnp.concatenate([st.tags, st.meta_type, st.rrip], axis=1),
         jnp.zeros((lanes * B, 3 * W), I32)], axis=0)
    # one fused table: [tag/meta ptrs | rrip ptrs | EAF stamps]. The EAF
    # chain shares the max-reduction because the generation stamp is
    # monotone nondecreasing (stored stamps ≤ current gen).
    mtab0 = jnp.concatenate(
        [jnp.tile(jnp.arange(S, dtype=I32), 2), st.eaf])

    def lane_step(carry, x):
        buf, mtab, eaf_gen, eaf_ctr, clf_b, acc_b = carry
        lane, addr = x
        valid = (addr >= 0) & slot_ok
        sidx = REQ.set_index(addr, prm)
        erd = REQ.eaf_index(addr, prm)
        t_arr = t0 + lane.astype(F32) * prm.lane_skew

        # ---- ①② label select + bypass decision ----------------------------
        pc_vals = base_pc + acc_b
        byp, wtype = REQ.bypass_decision_core(
            clf_b.warp_type, clf_b.accesses, tokens_b, pc_vals[:, 0],
            pc_vals[:, 1], pc_vals[:, 2], addr, valid, prm, pa, owt_b)
        use_l2 = valid & ~byp

        # ---- L2 lookup: one pointer gather, one row gather -----------------
        rd = mtab[jnp.concatenate([sidx, S + sidx, 2 * S + erd])]
        rows2 = buf[rd[:2 * B]]                       # [2B, 3W]
        tset, mrow = rows2[:B, :W], rows2[:B, W:2 * W]
        is_line = tset == addr[:, None]
        hit = jnp.any(is_line, axis=1) & use_l2
        rset = rows2[B:, 2 * W:]
        rset = jnp.where(hit[:, None] & is_line, 0, rset)

        # ---- ③ fill + insertion --------------------------------------------
        allocate = use_l2 & ~hit
        shift = prm.rrip_max - jnp.max(rset, axis=1)
        rset_aged = rset + jnp.where(allocate, shift, 0)[:, None]
        victim = jnp.argmax(rset_aged, axis=1)
        vict_oh = jnp.arange(W, dtype=I32)[None, :] == victim[:, None]
        pair = jnp.take_along_axis(                   # evicted tag + its type
            rows2[:B, :2 * W],
            jnp.stack([victim, W + victim], axis=1), axis=1)
        evicted, victim_type = pair[:, 0], pair[:, 1]
        ebit = rd[2 * B:] == eaf_gen
        rank = POL.insertion_rank(pa, wtype=wtype, eaf_bit=ebit,
                                  rrip_max=prm.rrip_max)

        # ---- private row buffer + explicit LWW pointer reduction -----------
        new_row = jnp.concatenate(
            [jnp.where(vict_oh, addr[:, None], tset),
             jnp.where(vict_oh, wtype[:, None], mrow),
             jnp.where(allocate[:, None],
                       jnp.where(vict_oh, rank[:, None], rset_aged),
                       rset)], axis=1)
        base = S + lane * B
        buf = jax.lax.dynamic_update_slice(buf, new_row, (base, 0))
        ev_valid = allocate & (evicted >= 0)
        chrono = base + slot                          # strictly slot-ordered
        wr_at = jnp.concatenate(
            [jnp.where(allocate, sidx, DROP),
             jnp.where(use_l2, S + sidx, DROP),
             jnp.where(ev_valid, 2 * S + REQ.eaf_index(evicted, prm),
                       DROP)])
        wr_val = jnp.concatenate(
            [chrono, chrono, jnp.broadcast_to(eaf_gen, (B,))])
        mtab = mtab.at[wr_at].max(wr_val, mode="drop")

        # ---- ① classifier + PC segments + EAF counter ----------------------
        clf_b = _ref.observe_vec(clf_b, hit, valid.astype(I32),
                                 use_l2.astype(I32), prm, pa,
                                 consts=obs_consts)
        delta = jnp.stack([(hit & use_l2), use_l2, valid, ev_valid],
                          axis=1).astype(I32)
        csum = jnp.cumsum(delta[pperm], axis=0)
        g = csum[seg_idx]                             # [2B, 4] seg ends/starts
        tot = g[:B] - jnp.where(first_seg[:, None], 0, g[B:])
        acc_b = acc_b + tot[inv, :3]
        n_ev = csum[B - 1, 3]
        eaf_ctr = eaf_ctr + n_ev
        reset = eaf_ctr >= prm.eaf_capacity
        eaf_gen = jnp.where(reset, eaf_gen + 1, eaf_gen)
        eaf_ctr = jnp.where(reset, 0, eaf_ctr)

        hp = POL.is_high_priority(pa, wtype)
        rec = (t_arr, addr, valid, byp, use_l2, hit, hp,
               victim_type, ev_valid)
        return (buf, mtab, eaf_gen, eaf_ctr, clf_b, acc_b), rec

    carry0 = (buf0, mtab0, st.eaf_gen, st.eaf_ctr, clf_b0,
              jnp.zeros((B, 3), I32))
    xs = (jnp.arange(lanes, dtype=I32), addr_lb)
    carry, records = jax.lax.scan(lane_step, carry0, xs)
    buf, mtab, eaf_gen, eaf_ctr, clf_b, acc_b = carry

    # dereference the winning rows once; write each PC entry's total at
    # its segment end (conflict-free by construction)
    fin = buf[mtab[:2 * S]]
    pc_fin = jnp.stack([st.pc_hits, st.pc_acc, st.pc_req], axis=1).at[
        jnp.where(is_end, spidx, E)].add(acc_b[pperm], mode="drop")
    new_st = st._replace(
        tags=fin[:S, :W], rrip=fin[S:, 2 * W:], meta_type=fin[:S, W:2 * W],
        eaf=mtab[2 * S:], eaf_gen=eaf_gen, eaf_ctr=eaf_ctr,
        pc_hits=pc_fin[:, 0], pc_acc=pc_fin[:, 1], pc_req=pc_fin[:, 2])
    return new_st, clf_b, records


def _fused_sweep(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                 t0, addr_lb, pc_b, owt_b, slot_ok,
                 prm: SimParams, pa: PolicyArrays) -> tuple:
    """One-sweep cache pass; picks the construction by wave width (a
    static shape property — see the module docstring)."""
    _, B = addr_lb.shape
    impl = _fused_wide if B >= WIDE_WAVE_MIN_B else _fused_narrow
    return impl(st, clf_b0, tokens_b, t0, addr_lb, pc_b, owt_b, slot_ok,
                prm, pa)


def wave_cache_pass(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                    t0, addr_lb, pc_b, owt_b, slot_ok, prm: SimParams,
                    pa: PolicyArrays, *, backend: str = "auto") -> tuple:
    """One wave's cache pass under the selected backend.

    Deliberately NOT jitted here: the engine inlines it into its own
    jitted wave step (jitting at this level would force the [sets, ways]
    state through a call boundary every wave). The pallas backend is
    Mosaic-lowered on a TPU and interpreted everywhere else.
    """
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.wave_cache_pass_ref(st, clf_b0, tokens_b, t0, addr_lb,
                                        pc_b, owt_b, slot_ok, prm, pa)
    if b == "pallas":
        return wave_cache_kernel(st, clf_b0, tokens_b, t0, addr_lb, pc_b,
                                 owt_b, slot_ok, prm, pa,
                                 interpret=jax.default_backend() != "tpu")
    return _fused_sweep(st, clf_b0, tokens_b, t0, addr_lb, pc_b, owt_b,
                        slot_ok, prm, pa)
