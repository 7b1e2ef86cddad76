"""Backend-gated entry point for wavefront segmented queue recovery.

``wave_queue_recovery`` computes one wave's bank / high-priority /
low-priority service times plus the advanced cross-wave queue carry.
Backends:

  * ``"ref"``    — the engine's original unfused multi-pass formulation
    (ref.py): cumsum + ``lax.cummax`` per queue family over [Q, N]
    masks. The unfused side of the in-run perf A/B.
  * ``"fused"``  — bitwise-identical reformulation on slot-major [N, Q]
    layout: the same exclusive-prefix-occupancy / running-max recovery,
    but the pathologically slow XLA:CPU ``cummax`` is replaced by a
    custom ``lax.associative_scan(jnp.maximum)`` (exactly associative,
    so bitwise-equal), the prefix-occupancy cumsums by
    ``associative_scan(jnp.add)`` (exact because service occupancies
    are integer-valued — see ``_scan_add``), per-slot floors are
    gathered instead of materializing [Q, N] floor matrices, and the
    carry update runs as dense masked max reductions sharing one mask
    per queue family (XLA:CPU serializes scatter-max into a
    per-element loop). Every intermediate that reaches an output is
    either the same float operation on the same values as ref.py or an
    exact re-association, so outputs are bit-for-bit equal — which is
    what lets the engine default to it under the 1e-6 golden suites.
  * ``"pallas"`` — one-pass TPU kernel (kernel.py): a single chunked
    sweep with a combined (prefix-occ, running-max, predecessor) carry
    recovers bank, HP and LP service times together. Exact on dyadic
    inputs (integer occupancies; the chunked prefix sums re-associate,
    which is exact below 2**24). Explicit opt-in only: on a TPU it is
    lowered through Mosaic, which refuses it today (see kernel.py), and
    it raises rather than falling back; off a TPU it runs in interpret
    mode, which is how the CPU tests validate it.
  * ``"auto"``   — ``"fused"`` on every platform: the one non-reference
    backend that compiles for the chip and is pinned bitwise to ref.

The differential suites pin fused == ref bitwise and pallas == ref on
fuzzed queue loads (tests/test_kernels.py, test_engine_differential.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.wavefront_scan import ref as _ref
from repro.kernels.wavefront_scan.kernel import wave_queue_kernel
from repro.kernels.wavefront_scan.ref import QueueCarry

F32 = jnp.float32
I32 = jnp.int32
_NEG = -jnp.inf

BACKENDS = ("auto", "fused", "ref", "pallas")


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown scan backend {backend!r}; choose from {BACKENDS}")
    return "fused" if backend == "auto" else backend


def _scan_max(x):
    """Inclusive running max along axis 0. Bitwise-equal to
    ``lax.cummax`` (max is exactly associative and the inputs carry no
    NaNs) but 9–16x faster on XLA:CPU, where the cummax primitive
    lowers to a degenerate reduce-window."""
    return jax.lax.associative_scan(jnp.maximum, x, axis=0)


def _scan_add(x):
    """Inclusive prefix sum along axis 0 via ``associative_scan`` —
    ~4x faster than ``jnp.cumsum`` on XLA:CPU. The tree re-associates
    the additions, which is exact whenever the summands accumulate
    without rounding: queue occupancies are integer-valued service
    times (``l2_svc`` / ``occ_rowhit`` / ``occ_rowmiss``) well below
    2**24, so every partial sum is an exactly-representable integer
    and the fused backend stays bitwise-equal to ref.py's sequential
    ``jnp.cumsum`` on them."""
    return jax.lax.associative_scan(jnp.add, x, axis=0)


def _floor_slot(free, last_ts, last_sa, q, t_s, t_svc, exact):
    """``ref.carry_floor`` evaluated only at each slot's own queue —
    an O(N) gather instead of a [Q, N] matrix. Identical elementwise
    math on identical values, so bitwise-equal where it is consumed."""
    f = free[q]
    if exact:
        return f
    backlog = f - last_sa[q]
    interp = jnp.minimum(f, t_svc + backlog)
    return jnp.where(t_s >= last_ts[q], f, interp)


def _take_q(x_nq, q):
    """x[j, q_j] for per-slot queue gather on [N, Q] arrays."""
    return jnp.take_along_axis(x_nq, q[:, None], axis=1)[:, 0]


def _fused_core(t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
                *, banks, channels, l2_svc, l2_lat, occ_rowhit,
                occ_rowmiss, exact):
    """Slot-major [N, Q] recovery; returns (t_head, t0, row_hit)."""
    n = t_s.shape[0]
    slot = jnp.arange(n, dtype=I32)

    # ---- L2 bank queues ----------------------------------------------------
    # the DRAM predecessor-chain scan is independent of the bank scan,
    # so both ride ONE associative scan on a [N, banks+channels] concat
    # (slot indices stay exact in f32 — they are < 2**24)
    bmask = (bank[:, None] == jnp.arange(banks, dtype=I32)[None, :]) \
        & use_l2[:, None]
    cmask = (ch[:, None] == jnp.arange(channels, dtype=I32)[None, :]) \
        & go_dram[:, None]
    occ_b = jnp.where(bmask, jnp.full((n,), l2_svc, F32)[:, None], 0.0)
    c_b = _scan_add(occ_b) - occ_b
    u_b = jnp.maximum(t_s, _floor_slot(carry.bank_free, carry.bank_ts,
                                       carry.bank_ts, bank, t_s, t_s,
                                       exact))
    v_b = jnp.where(bmask, u_b[:, None] - c_b, _NEG)
    chain = jnp.where(cmask, slot[:, None], -1).astype(F32)
    joint = _scan_max(jnp.concatenate([v_b, chain], axis=1))
    b_start = c_b + joint[:, :banks]
    inc = joint[:, banks:].astype(I32)
    t_head = jnp.where(use_l2, _take_q(b_start, bank), 0.0)

    # ---- DRAM two-queue FR-FCFS --------------------------------------------
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    prev_idx = jnp.concatenate(
        [jnp.full((1, channels), -1, I32), inc[:-1]], axis=0)
    prev_slot = _take_q(prev_idx, ch)
    prev_row = jnp.where(prev_slot >= 0,
                         jnp.take(row, jnp.maximum(prev_slot, 0)),
                         carry.cur_row[ch])
    row_hit = (prev_row == row) & go_dram
    occ = jnp.where(row_hit, occ_rowhit, occ_rowmiss)

    f_hp = _floor_slot(carry.hp_free, carry.hp_ts, carry.hp_sa, ch,
                       t_s, t_da, exact)
    mask_hp = cmask & hp[:, None]
    occ_hp = jnp.where(mask_hp, occ[:, None], 0.0)
    c_hp = _scan_add(occ_hp) - occ_hp
    u_hp = jnp.maximum(t_da, f_hp)
    v_hp = jnp.where(mask_hp, u_hp[:, None] - c_hp, _NEG)
    hp_start = c_hp + _scan_max(v_hp)
    hp_end = jnp.where(mask_hp, hp_start + occ_hp, _NEG)
    hp_busy = jnp.concatenate(
        [jnp.full((1, channels), _NEG), _scan_max(hp_end)[:-1]], axis=0)

    f_lp = _floor_slot(carry.lp_free, carry.lp_ts, carry.lp_sa, ch,
                       t_s, t_da, exact)
    mask_lp = cmask & ~hp[:, None]
    occ_lp = jnp.where(mask_lp, occ[:, None], 0.0)
    c_lp = _scan_add(occ_lp) - occ_lp
    u_lp = jnp.maximum(t_da, jnp.maximum(
        f_lp, jnp.maximum(f_hp, _take_q(hp_busy, ch))))
    v_lp = jnp.where(mask_lp, u_lp[:, None] - c_lp, _NEG)
    lp_start = c_lp + _scan_max(v_lp)

    t0 = jnp.where(hp, _take_q(hp_start, ch), _take_q(lp_start, ch))
    return t_head, t0, row_hit


def _carry_epilogue(t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
                    t_head, t0, row_hit, *, banks, channels, l2_svc,
                    l2_lat, occ_rowhit, occ_rowmiss) -> QueueCarry:
    """Advance the cross-wave carry from per-slot outputs.

    Dense masked [N, Q] max reductions, sharing one mask per queue
    family. A scatter-max (`.at[q].max`) would be O(N) on paper but
    lowers to a serialized per-element loop on XLA:CPU — measured ~3x
    slower than the dense reduce at N=4096 — while max is
    order-independent and exact, so both forms are bitwise-equal to
    ref.py's per-queue reductions. The open-row update recovers each
    channel's LAST serviced slot as a masked max over slot indices."""
    n = t_s.shape[0]
    slot = jnp.arange(n, dtype=I32)
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    occ = jnp.where(row_hit, occ_rowhit, occ_rowmiss)

    bm = (bank[:, None] == jnp.arange(banks, dtype=I32)[None, :]) \
        & use_l2[:, None]
    cm = (ch[:, None] == jnp.arange(channels, dtype=I32)[None, :]) \
        & go_dram[:, None]
    cm_hp = cm & hp[:, None]
    cm_lp = cm & ~hp[:, None]

    def qmax(mask, val, base):
        return jnp.maximum(
            base, jnp.max(jnp.where(mask, val[:, None], _NEG), axis=0))

    last_idx = jnp.max(jnp.where(cm, slot[:, None], -1), axis=0)
    cur_row = jnp.where(last_idx >= 0,
                        jnp.take(row, jnp.maximum(last_idx, 0)),
                        carry.cur_row)
    return QueueCarry(
        bank_free=qmax(bm, t_head + l2_svc, carry.bank_free),
        bank_ts=qmax(bm, t_s, carry.bank_ts),
        hp_free=qmax(cm_hp, t0 + occ, carry.hp_free),
        hp_ts=qmax(cm_hp, t_s, carry.hp_ts),
        hp_sa=qmax(cm_hp, t_da, carry.hp_sa),
        lp_free=qmax(cm_lp, t0 + occ, carry.lp_free),
        lp_ts=qmax(cm_lp, t_s, carry.lp_ts),
        lp_sa=qmax(cm_lp, t_da, carry.lp_sa),
        cur_row=cur_row)


def wave_queue_recovery(t_s, bank, use_l2, ch, row, go_dram, byp, hp,
                        carry: QueueCarry, *, banks: int, channels: int,
                        l2_svc: float, l2_lat: float, occ_rowhit: float,
                        occ_rowmiss: float, exact: bool,
                        backend: str = "auto"):
    """One wave's queue recovery under the selected backend.

    Slot arrays are [N] in warp-major chronological order. Returns
    ``(t_head, t0, row_hit, new_carry)`` — see ref.py for the contract.
    The pallas backend is Mosaic-lowered on a TPU and interpreted
    everywhere else.

    Deliberately NOT jitted here: the wavefront engine inlines it into
    its own jitted wave step (a nested pjit boundary would block XLA
    fusion with the surrounding pass); standalone callers (tests,
    benchmarks/roofline.py) wrap it in ``jax.jit`` at the call site.
    """
    kw = dict(banks=banks, channels=channels, l2_svc=l2_svc,
              l2_lat=l2_lat, occ_rowhit=occ_rowhit,
              occ_rowmiss=occ_rowmiss, exact=exact)
    b = resolve_backend(backend)
    if b == "ref":
        return _ref.wave_queue_recovery_ref(
            t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry, **kw)
    if b == "pallas":
        t_head, t0, row_hit = wave_queue_kernel(
            t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
            interpret=jax.default_backend() != "tpu", **kw)
    else:
        t_head, t0, row_hit = _fused_core(
            t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry, **kw)
    new_carry = _carry_epilogue(
        t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
        t_head, t0, row_hit, banks=banks, channels=channels,
        l2_svc=l2_svc, l2_lat=l2_lat, occ_rowhit=occ_rowhit,
        occ_rowmiss=occ_rowmiss)
    return t_head, t0, row_hit, new_carry
