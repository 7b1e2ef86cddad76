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

      - wide waves (B ≥ 128, where same-set aliasing is dense and
        gathers/scatters over the wave's slots dominate — on a TPU they
        run element by element): the DENSE LANE-START-TABLE
        construction. The lane scan carries the state tables themselves
        and touches them only through one-hot contractions and masked
        reductions. A read is ``onehot[B, N] @ table`` on the MXU, made
        bitwise by holding each i32 as its four bytes in bf16 (one
        nonzero term per output). A write is last-write-wins in slot
        order, made explicit: per table row, the last writing slot is
        a masked max over slot index, and its row is selected by a
        second exact contraction. Two chains share the [sets, 3·ways]
        tag|meta|RRIP table: tags+meta advance only on ``allocate``,
        RRIP on every ``use_l2`` request (hits rewrite their row). The
        EAF write stores one stamp, so "any writer" suffices (a count
        over a [hi, lo] fold of the filter, ~√eaf_bits one-hots); the
        PC counters are read as ``pc_onehot @ table`` and advanced by
        ``pc_onehotᵀ @ deltas``. The construction is bitwise because
        (a) every slot's row is computed from lane-start state —
        exactly what the ref scatters write — and (b) same-lane
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

    ``"auto"`` picks this backend on every platform.
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
import numpy as np

from repro.core import classifier as CLF
from repro.core.engine import request as REQ
from repro.core.engine.state import SimParams, SimState
from repro.kernels.cache_pass import ref as _ref
from repro.kernels.cache_pass.kernel import wave_cache_kernel
from repro.policy import PolicyArrays, ops as POL

F32 = jnp.float32
I32 = jnp.int32
U32 = jnp.uint32
BF16 = jnp.bfloat16

BACKENDS = ("auto", "fused", "ref", "pallas")

# Static wave-width threshold between the two fused constructions. Below
# it the pass is dispatch-bound and ref-shaped scatters are effectively
# free; above it scatter volume dominates and the dense tables pay for
# their fixed one-hot work.
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


def _bytes(x):
    """i32[..., C] -> bf16[..., 4C]: the four bytes of each entry's
    uint32 bit pattern, byte-major (column ``k·C + c`` holds byte ``k``
    of entry ``c``). Every byte is exact in bf16, so a contraction with
    a one-hot operand (one nonzero term per output) is exact on the
    MXU at its default precision."""
    u = jax.lax.bitcast_convert_type(x, U32)
    return jnp.concatenate([(u >> (8 * k)) & 0xFF for k in range(4)],
                           axis=-1).astype(BF16)


def _unbytes(y):
    """Inverse of ``_bytes``: byte planes (any float dtype holding
    0..255) -> the i32 entries."""
    u = y.astype(U32)
    c = y.shape[-1] // 4
    v = u[..., :c]
    for k in range(1, 4):
        v = v | (u[..., k * c:(k + 1) * c] << (8 * k))
    return jax.lax.bitcast_convert_type(v, I32)


def _pick(onehot, table_bytes):
    """``table[i]`` for each row's one-hot ``i``, as one contraction:
    bool[B, N] × bf16[N, 4C] byte planes -> i32[B, C], bitwise."""
    return _unbytes(jnp.dot(onehot.astype(BF16), table_bytes,
                            preferred_element_type=F32))


def _lww(table_bytes, onehot, writes, new_bytes):
    """Last-write-wins row writes in slot order, dense: each row ``s``
    of ``table_bytes`` [N, 4C] takes the ``new_bytes`` [B, 4C] row of
    the LAST slot ``b`` with ``onehot[b, s] & writes[b]``, and keeps its
    value where no slot writes it. The winner is a masked max over
    slot index; its row is selected by one exact contraction."""
    slot = jnp.arange(onehot.shape[0], dtype=I32)[:, None]
    last = jnp.max(jnp.where(onehot & writes[:, None], slot, -1), axis=0)
    won = jnp.einsum("bn,bc->nc", (slot == last[None, :]).astype(BF16),
                     new_bytes, preferred_element_type=F32)
    return jnp.where((last >= 0)[:, None], won.astype(BF16), table_bytes)


def _fused_wide(st: SimState, clf_b0: CLF.ClassifierState, tokens_b,
                t0, addr_lb, pc_b, owt_b, slot_ok,
                prm: SimParams, pa: PolicyArrays) -> tuple:
    """Wide-wave (B ≥ 128) fused sweep — the dense lane-start-table
    construction (see the module docstring). The lane scan carries the
    state itself: the [sets, 3·ways] tag|meta|RRIP table as byte
    planes, the [pc_entries, 3] PC counters, and the EAF stamps folded
    to [hi, lo]. Every read is a one-hot contraction and every write a
    masked max-reduction over slots plus a contraction, so no lane
    step gathers or scatters.
    """
    lanes = addr_lb.shape[0]
    W = prm.ways
    S = prm.sets
    E = prm.pc_entries
    obs_consts = _ref.observe_consts(prm, pa)
    # EAF bit e lives at (e // lo, e % lo) of a [hi, lo] fold, so its
    # one-hots are [B, hi] and [B, lo] (~√eaf_bits wide each) rather
    # than [B, eaf_bits]; the pad past eaf_bits is never indexed
    lo = 1 << (((prm.eaf_bits - 1).bit_length() + 1) // 2)
    hi = -(-prm.eaf_bits // lo)

    def fold(e):
        return ((e // lo)[:, None] == jnp.arange(hi, dtype=I32),
                (e % lo)[:, None] == jnp.arange(lo, dtype=I32))

    # the wave's PC entries are fixed across its lanes
    pc_oh = REQ.pc_index(pc_b, prm)[:, None] == jnp.arange(E, dtype=I32)
    # table columns: tags | meta advance only on allocate, RRIP on every
    # use_l2 request (hits rewrite their row)
    tm_col = (np.arange(12 * W) % (3 * W)) < 2 * W

    def lane_step(carry, addr):
        tab, pc_tab, eaf, eaf_gen, eaf_ctr, clf_b = carry
        valid = (addr >= 0) & slot_ok
        set_oh = REQ.set_index(addr, prm)[:, None] == jnp.arange(
            S, dtype=I32)

        # ---- ①② label select + bypass decision ----------------------------
        pc_vals = _pick(pc_oh, _bytes(pc_tab))
        byp, wtype = REQ.bypass_decision_core(
            clf_b.warp_type, clf_b.accesses, tokens_b, pc_vals[:, 0],
            pc_vals[:, 1], pc_vals[:, 2], addr, valid, prm, pa, owt_b)
        use_l2 = valid & ~byp

        # ---- L2 lookup: the lane-start rows, one contraction ---------------
        rows = _pick(set_oh, tab)                     # [B, 3W]
        tset, mrow = rows[:, :W], rows[:, W:2 * W]
        is_line = tset == addr[:, None]
        hit = jnp.any(is_line, axis=1) & use_l2
        rset = jnp.where(hit[:, None] & is_line, 0, rows[:, 2 * W:])

        # ---- ③ fill + insertion --------------------------------------------
        allocate = use_l2 & ~hit
        shift = prm.rrip_max - jnp.max(rset, axis=1)
        rset_aged = rset + jnp.where(allocate, shift, 0)[:, None]
        victim = jnp.argmax(rset_aged, axis=1)
        vict_oh = jnp.arange(W, dtype=I32)[None, :] == victim[:, None]
        evicted = jnp.sum(jnp.where(vict_oh, tset, 0), axis=1)
        victim_type = jnp.sum(jnp.where(vict_oh, mrow, 0), axis=1)
        hi_oh, lo_oh = fold(REQ.eaf_index(addr, prm))
        ebit = jnp.any(lo_oh & (jnp.dot(
            hi_oh.astype(BF16), (eaf == eaf_gen).astype(BF16),
            preferred_element_type=F32) > 0), axis=1)
        rank = POL.insertion_rank(pa, wtype=wtype, eaf_bit=ebit,
                                  rrip_max=prm.rrip_max)

        # ---- explicit LWW writes in slot order -----------------------------
        new_row = _bytes(jnp.concatenate(
            [jnp.where(vict_oh, addr[:, None], tset),
             jnp.where(vict_oh, wtype[:, None], mrow),
             jnp.where(allocate[:, None],
                       jnp.where(vict_oh, rank[:, None], rset_aged),
                       rset)], axis=1))
        tab = jnp.where(tm_col, _lww(tab, set_oh, allocate, new_row),
                        _lww(tab, set_oh, use_l2, new_row))
        # every EAF write stores the same stamp, so any writer sets it
        ev_valid = allocate & (evicted >= 0)
        hi_oh, lo_oh = fold(REQ.eaf_index(evicted, prm))
        n_set = jnp.einsum("bh,bl->hl",
                           (hi_oh & ev_valid[:, None]).astype(BF16),
                           lo_oh.astype(BF16), preferred_element_type=F32)
        eaf = jnp.where(n_set > 0, eaf_gen, eaf)

        # ---- ① classifier + PC table + EAF counter -------------------------
        clf_b = _ref.observe_vec(clf_b, hit, valid.astype(I32),
                                 use_l2.astype(I32), prm, pa,
                                 consts=obs_consts)
        delta = jnp.stack([(hit & use_l2), use_l2, valid], axis=1)
        pc_tab = pc_tab + jnp.einsum(
            "be,bc->ec", pc_oh.astype(BF16), delta.astype(BF16),
            preferred_element_type=F32).astype(I32)
        eaf_ctr = eaf_ctr + jnp.sum(ev_valid.astype(I32))
        reset = eaf_ctr >= prm.eaf_capacity
        eaf_gen = jnp.where(reset, eaf_gen + 1, eaf_gen)
        eaf_ctr = jnp.where(reset, 0, eaf_ctr)

        hp = POL.is_high_priority(pa, wtype)
        rec = (byp, use_l2, hit, hp, victim_type, ev_valid)
        return (tab, pc_tab, eaf, eaf_gen, eaf_ctr, clf_b), rec

    tab0 = _bytes(jnp.concatenate([st.tags, st.meta_type, st.rrip], axis=1))
    pc_tab0 = jnp.stack([st.pc_hits, st.pc_acc, st.pc_req], axis=1)
    eaf0 = jnp.pad(st.eaf, (0, hi * lo - prm.eaf_bits)).reshape(hi, lo)
    carry0 = (tab0, pc_tab0, eaf0, st.eaf_gen, st.eaf_ctr, clf_b0)
    carry, recs = jax.lax.scan(lane_step, carry0, addr_lb)
    tab, pc_tab, eaf, eaf_gen, eaf_ctr, clf_b = carry
    # the records that depend on the lane alone are built outside the scan
    t_arr = t0 + jnp.arange(lanes, dtype=I32)[:, None].astype(F32) \
        * prm.lane_skew
    records = (t_arr, addr_lb, (addr_lb >= 0) & slot_ok) + recs
    fin = _unbytes(tab)
    new_st = st._replace(
        tags=fin[:, :W], meta_type=fin[:, W:2 * W], rrip=fin[:, 2 * W:],
        eaf=eaf.reshape(-1)[:prm.eaf_bits], eaf_gen=eaf_gen,
        eaf_ctr=eaf_ctr, pc_hits=pc_tab[:, 0], pc_acc=pc_tab[:, 1],
        pc_req=pc_tab[:, 2])
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
