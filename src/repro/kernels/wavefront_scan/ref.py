"""Unfused multi-pass oracle for wavefront queue recovery.

This is the wavefront engine's original timing-pass formulation,
extracted verbatim (ISSUE 6): one cumsum + ``lax.cummax`` segmented
prefix per queue family over dense ``[Q, N]`` masks, a ``cummax``
predecessor chain for the DRAM row buffer, and a second prefix pass for
the low-priority queue whose floor folds in the high-priority busy
horizon. It recovers, for one wave of N arrival-ordered requests, the
exact FIFO service times the event engine would produce request by
request: ``start_j = c_j + max_{i<=j}(max(t_i, floor_i) - c_i)`` with
``c`` the exclusive prefix occupancy of the request's queue.

It is the differential oracle for ``ops.py``'s fused queue-major
formulation (bitwise-identical, no per-slot gather) and the Pallas
one-pass kernel (``kernel.py``), and it IS the engine's
``scan_backend="ref"`` path — the unfused side of the in-run A/B that
benchmarks/engine_bench.py gates on.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
I32 = jnp.int32
_NEG = -jnp.inf


class QueueCarry(NamedTuple):
    """Cross-wave queue state threaded through every backend.

    ``*_free`` are busy-until horizons (SimState fields), ``*_ts``/
    ``*_sa`` the service-frontier anchors in wave-sort / service-arrival
    time (wavefront.QueueAnchors), ``cur_row`` the open DRAM row per
    channel."""
    bank_free: jnp.ndarray   # f32[banks]
    bank_ts: jnp.ndarray     # f32[banks]
    hp_free: jnp.ndarray     # f32[channels]
    hp_ts: jnp.ndarray       # f32[channels]
    hp_sa: jnp.ndarray       # f32[channels]
    lp_free: jnp.ndarray     # f32[channels]
    lp_ts: jnp.ndarray       # f32[channels]
    lp_sa: jnp.ndarray       # f32[channels]
    cur_row: jnp.ndarray     # i32[channels]


def carry_floor(free, last_ts, last_sa, t_s, t_svc):
    """Work-conserving carry floor [Q, N] for the next wave's requests.

    A request at/after the queue's serviced frontier (``t_s >= last_ts``)
    waits for the full busy-until, exactly like the event engine. A
    *retrograde* request — its warp raced ahead of the warps that last
    used the queue, so in true event order it would have been serviced
    amid that backlog, not after it — sees the queue's STANDING BACKLOG
    (``free - last_sa``) anchored at its own service-arrival time instead
    of the absolute end-of-service. Single-warp traces are always at the
    frontier, so they stay exact.
    """
    backlog = (free - last_sa)[:, None]              # +inf if queue unused
    interp = jnp.minimum(free[:, None], t_svc[None, :] + backlog)
    return jnp.where(t_s[None, :] >= last_ts[:, None], free[:, None],
                     interp)


def anchor_update(last, mask, t):
    return jnp.maximum(last,
                       jnp.max(jnp.where(mask, t[None, :], _NEG), axis=1))


def queue_prefix(mask, t_arr, occ, free):
    """FIFO service start times for one queue family, vectorized.

    mask: bool[Q, N] — request j belongs to queue q; slots in
    chronological order. t_arr: f32[N] arrivals; occ: f32[N] per-request
    occupancy; free: f32[Q, 1|N] per-slot busy-until floor.

    Returns (start[Q, N], end[Q, N]); ``end`` is -inf outside ``mask`` so
    row-wise maxima skip those entries.
    """
    occ_m = jnp.where(mask, occ[None, :], 0.0)
    c = jnp.cumsum(occ_m, axis=1) - occ_m            # exclusive prefix occ
    v = jnp.where(mask, jnp.maximum(t_arr[None, :], free) - c, _NEG)
    start = c + jax.lax.cummax(v, axis=1)
    end = jnp.where(mask, start + occ_m, _NEG)
    return start, end


def wave_queue_recovery_ref(t_s, bank, use_l2, ch, row, go_dram, byp, hp,
                            carry: QueueCarry, *, banks: int, channels: int,
                            l2_svc: float, l2_lat: float, occ_rowhit: float,
                            occ_rowmiss: float, exact: bool):
    """Recover one wave's bank/HP/LP service times, multi-pass.

    Slot arrays are [N] in warp-major chronological order; ``carry`` is
    the cross-wave queue state. ``exact=True`` (a wave of one warp — the
    event loop) uses the plain busy-until floor instead of the backlog
    interpolation. Returns ``(t_head, t0, row_hit, new_carry)``:
    per-slot L2-bank service start (0 outside ``use_l2``), DRAM service
    start (garbage outside ``go_dram``), row-buffer hit flags, and the
    advanced carry.
    """
    n = t_s.shape[0]
    slot = jnp.arange(n, dtype=I32)

    def floor(free, last_ts, last_sa, t_svc):
        if exact:
            return free[:, None]
        return carry_floor(free, last_ts, last_sa, t_s, t_svc)

    # ---- L2 bank queues ----------------------------------------------------
    bmask = (bank[None, :] == jnp.arange(banks, dtype=I32)[:, None]) \
        & use_l2[None, :]
    svc = jnp.full((n,), l2_svc, F32)
    b_start, b_end = queue_prefix(
        bmask, t_s, svc,
        floor(carry.bank_free, carry.bank_ts, carry.bank_ts, t_s))
    t_head = jnp.sum(jnp.where(bmask, b_start, 0.0), axis=0)
    bank_free = jnp.maximum(carry.bank_free, jnp.max(b_end, axis=1))

    # ---- DRAM two-queue FR-FCFS --------------------------------------------
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    cmask = (ch[None, :] == jnp.arange(channels, dtype=I32)[:, None]) \
        & go_dram[None, :]

    # row-buffer chain: each request's predecessor is the previous
    # request in its channel within this wave, else the carried open row
    inc = jax.lax.cummax(jnp.where(cmask, slot[None, :], -1), axis=1)
    prev_idx = jnp.concatenate(
        [jnp.full((channels, 1), -1, I32), inc[:, :-1]], axis=1)
    prev_row = jnp.where(prev_idx >= 0,
                         jnp.take(row, jnp.maximum(prev_idx, 0)),
                         carry.cur_row[:, None])
    row_hit = (prev_row == row[None, :])[ch, slot] & go_dram
    occ = jnp.where(row_hit, occ_rowhit, occ_rowmiss)

    mask_hp = cmask & hp[None, :]
    hp_carry = floor(carry.hp_free, carry.hp_ts, carry.hp_sa, t_da)
    hp_start, hp_end = queue_prefix(mask_hp, t_da, occ, hp_carry)
    # strict priority: a low-priority request waits for the high queue's
    # busy horizon at its chronological position
    hp_busy = jnp.concatenate(
        [jnp.full((channels, 1), _NEG),
         jax.lax.cummax(hp_end, axis=1)[:, :-1]], axis=1)
    lp_floor = jnp.maximum(
        floor(carry.lp_free, carry.lp_ts, carry.lp_sa, t_da),
        jnp.maximum(hp_carry, hp_busy))
    mask_lp = cmask & ~hp[None, :]
    lp_start, lp_end = queue_prefix(mask_lp, t_da, occ, lp_floor)

    t0 = jnp.where(hp, hp_start[ch, slot], lp_start[ch, slot])
    hp_free = jnp.maximum(carry.hp_free, jnp.max(hp_end, axis=1))
    lp_free = jnp.maximum(carry.lp_free, jnp.max(lp_end, axis=1))
    last_idx = inc[:, -1]
    cur_row = jnp.where(last_idx >= 0,
                        jnp.take(row, jnp.maximum(last_idx, 0)),
                        carry.cur_row)

    new_carry = QueueCarry(
        bank_free=bank_free,
        bank_ts=anchor_update(carry.bank_ts, bmask, t_s),
        hp_free=hp_free,
        hp_ts=anchor_update(carry.hp_ts, mask_hp, t_s),
        hp_sa=anchor_update(carry.hp_sa, mask_hp, t_da),
        lp_free=lp_free,
        lp_ts=anchor_update(carry.lp_ts, mask_lp, t_s),
        lp_sa=anchor_update(carry.lp_sa, mask_lp, t_da),
        cur_row=cur_row)
    return t_head, t0, row_hit, new_carry
