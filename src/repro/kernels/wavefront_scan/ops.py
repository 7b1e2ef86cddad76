"""Backend-gated entry point for wavefront segmented queue recovery.

``wave_queue_recovery`` computes one wave's bank / high-priority /
low-priority service times plus the advanced cross-wave queue carry.
Backends:

  * ``"ref"``    — the engine's original unfused multi-pass formulation
    (ref.py): cumsum + ``lax.cummax`` per queue family over [Q, N]
    masks. The unfused side of the in-run perf A/B.
  * ``"fused"``  — bitwise-identical reformulation laid out for the
    TPU's vector unit. Every table is queue-major [Q, N], so the wave's
    N slots run along the 128-wide lane axis and the Q <= 18 queues
    along the sublanes (slot-major [N, Q] would fill 6–18 of every 128
    lanes). The prefix scans are ``lax.cumsum`` / ``lax.cummax`` along
    the slots, which XLA lowers to a two-level reduce-window scan (128
    lanes, then the blocks); the three occupancy families share one
    cumsum. Nothing is gathered per slot: the carry floors are [Q, N]
    tables built elementwise, each slot's own-queue value is a masked
    max over the queue axis with the one-hot queue masks, and the DRAM
    row chain is a "last valid value" scan that carries the
    predecessor's row itself (not an index to look up). The carry
    update is masked max reductions over the slot axis. Exact by
    construction: max is exactly associative, the occupancy prefix
    sums add integer-valued service times far below 2**24, and selects
    and masked maxes copy values unchanged — so the outputs equal
    ref.py bit for bit, which is what lets the engine default to it
    under the 1e-6 golden suites. (A per-slot gather compiles on the TPU
    to a custom fusion the compiler's cost model cannot estimate;
    tests/test_tpu_compile.py pins that the compiled pass holds none.)
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


def _onehot(q, n_q, on):
    """bool[n_q, N]: slot j is in queue ``q[j]`` and ``on[j]`` holds."""
    return (q[None, :] == jnp.arange(n_q, dtype=I32)[:, None]) \
        & on[None, :]


def _pick(mask, x_qn):
    """Each slot's entry of a [Q, N] array at its own queue: a masked max
    over the queue axis. A slot lies in at most one queue, so this copies
    that entry unchanged (-inf where ``mask`` holds for no queue)."""
    return jnp.max(jnp.where(mask, x_qn, _NEG), axis=0)


def _floor(free, last_ts, last_sa, t_s, t_svc, exact):
    """The carry floor of every (queue, slot) pair, [Q, N] or [Q, 1]."""
    if exact:
        return free[:, None]
    return _ref.carry_floor(free, last_ts, last_sa, t_s, t_svc)


def _scan_add(x):
    """Inclusive prefix sum along the slot axis. Exact in any order of
    association: the summands are integer-valued service occupancies
    (``l2_svc``, ``occ_rowhit``, ``occ_rowmiss``) and every partial sum
    stays far below 2**24."""
    return jax.lax.cumsum(x, axis=1)


def _scan_max(x):
    """Inclusive running max along the slot axis (max is exactly
    associative, and no NaN enters)."""
    return jax.lax.cummax(x, axis=1)


def _shift(x, first):
    """``x[:, j - 1]`` along the slot axis; ``first`` (a scalar or one
    value per queue) at j = 0."""
    head = jnp.broadcast_to(jnp.asarray(first, x.dtype).reshape(-1, 1),
                            (x.shape[0], 1))
    return jnp.concatenate([head, x[:, :-1]], axis=1)


def _hold_last(valid, x):
    """Inclusive "last valid value" scan along the slot axis: ``x`` at
    the last j' <= j where ``valid`` holds. log2(N) rounds of shift and
    select; the combine only copies values, so it is exact."""
    n = x.shape[1]
    d = 1
    while d < n:
        x = jnp.where(valid, x, jnp.pad(x[:, :-d], ((0, 0), (d, 0))))
        valid = valid | jnp.pad(valid[:, :-d], ((0, 0), (d, 0)))
        d *= 2
    return x


def _fused_core(t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
                *, banks, channels, l2_svc, l2_lat, occ_rowhit,
                occ_rowmiss, exact):
    """Queue-major [Q, N] recovery; returns (t_head, t0, row_hit)."""
    bmask = _onehot(bank, banks, use_l2)
    cmask = _onehot(ch, channels, go_dram)
    mask_hp = cmask & hp[None, :]
    mask_lp = cmask & ~hp[None, :]

    # ---- DRAM row chain: no service time enters it -------------------------
    # a request's predecessor is the previous request of its channel in
    # this wave, else the carried open row: hold each channel's last row,
    # one slot late, so that slot j sees only the slots before it
    rows = jnp.broadcast_to(row[None, :], cmask.shape)
    prev_row = _hold_last(_shift(cmask, True),
                          _shift(rows, carry.cur_row))
    row_hit = jnp.any(cmask & (prev_row == rows), axis=0)
    occ = jnp.where(row_hit, occ_rowhit, occ_rowmiss)

    # ---- exclusive prefix occupancy of all three families, one scan --------
    occ_q = jnp.concatenate([jnp.where(bmask, jnp.float32(l2_svc), 0.0),
                             jnp.where(mask_hp, occ[None, :], 0.0),
                             jnp.where(mask_lp, occ[None, :], 0.0)])
    c_q = _scan_add(occ_q) - occ_q
    occ_hp = occ_q[banks:banks + channels]
    c_b, c_hp, c_lp = (c_q[:banks], c_q[banks:banks + channels],
                       c_q[banks + channels:])

    # ---- L2 bank queues ----------------------------------------------------
    f_b = _floor(carry.bank_free, carry.bank_ts, carry.bank_ts, t_s, t_s,
                 exact)
    v_b = jnp.where(bmask, jnp.maximum(t_s[None, :], f_b) - c_b, _NEG)
    b_start = c_b + _scan_max(v_b)
    t_head = jnp.where(use_l2, _pick(bmask, b_start), 0.0)

    # ---- DRAM two-queue FR-FCFS --------------------------------------------
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    f_hp = _floor(carry.hp_free, carry.hp_ts, carry.hp_sa, t_s, t_da,
                  exact)
    v_hp = jnp.where(mask_hp, jnp.maximum(t_da[None, :], f_hp) - c_hp,
                     _NEG)
    hp_start = c_hp + _scan_max(v_hp)
    # strict priority: a low-priority request waits for the high queue's
    # busy horizon at its chronological position
    hp_end = jnp.where(mask_hp, hp_start + occ_hp, _NEG)
    hp_busy = _shift(_scan_max(hp_end), _NEG)

    f_lp = _floor(carry.lp_free, carry.lp_ts, carry.lp_sa, t_s, t_da,
                  exact)
    u_lp = jnp.maximum(t_da[None, :], jnp.maximum(
        f_lp, jnp.maximum(f_hp, hp_busy)))
    v_lp = jnp.where(mask_lp, u_lp - c_lp, _NEG)
    lp_start = c_lp + _scan_max(v_lp)

    t0 = jnp.where(hp, _pick(mask_hp, hp_start), _pick(mask_lp, lp_start))
    return t_head, t0, row_hit


def _carry_epilogue(t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
                    t_head, t0, row_hit, *, banks, channels, l2_svc,
                    l2_lat, occ_rowhit, occ_rowmiss) -> QueueCarry:
    """Advance the cross-wave carry from per-slot outputs: masked max
    reductions over the slot axis of [Q, N] tables, one mask per queue
    family. Max is order-independent and exact, so they equal ref.py's
    per-queue reductions bit for bit. The open-row update recovers each
    channel's last serviced slot as a masked max over slot indices."""
    n = t_s.shape[0]
    slot = jnp.arange(n, dtype=I32)
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    occ = jnp.where(row_hit, occ_rowhit, occ_rowmiss)

    bm = _onehot(bank, banks, use_l2)
    cm = _onehot(ch, channels, go_dram)
    cm_hp = cm & hp[None, :]
    cm_lp = cm & ~hp[None, :]

    def qmax(mask, val, base):
        return jnp.maximum(
            base, jnp.max(jnp.where(mask, val[None, :], _NEG), axis=1))

    last_idx = jnp.max(jnp.where(cm, slot[None, :], -1), axis=1)
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
    fusion with the surrounding pass); standalone callers (tests) wrap
    it in ``jax.jit`` at the call site.
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
