"""Pallas one-pass segmented queue recovery for the wavefront engine.

One sequential grid sweep over the wave's N slots (chunks of C) recovers
the L2-bank, DRAM-high-priority and DRAM-low-priority service times
TOGETHER — the three passes the unfused path runs back-to-back collapse
into a single kernel whose cross-chunk state is the combined carry the
segmented-prefix identity needs, per queue:

  * **prefix-occ**  ``S_q``  — total service occupancy of q's requests
    seen so far (the exclusive prefix ``c`` continues across chunks);
  * **running-max** ``M_q``  — ``max_i (max(t_i, floor_i) - c_i)`` so
    far, so ``start_j = c_j + max(M_q, within-chunk running max)``;
  * **predecessor** ``row_q`` / ``HB_q`` — the DRAM row chain's last
    open row per channel and the high-priority queue's busy horizon
    (what the strict-priority low queue floors on).

Within a chunk the same quantities come from ``jnp.cumsum`` /
``lax.associative_scan`` on [C, Q] tiles held in VMEM; chunk reductions
then advance the carry scratch. Occupancies are small integers, so the
re-associated prefix sums are exact (< 2**24) and the kernel matches
ref.py bit-for-bit on dyadic inputs; tests/test_kernels.py pins that
under ``interpret=True`` on fuzzed queue loads.

Status: interpret mode only. The TPU compiler (Mosaic, jax 0.9.0)
refuses this kernel for a v5e. With one chunk (N <= 256 slots) it stops
at ``jnp.cumsum``: "Unimplemented primitive in Pallas TPU lowering ...:
cumsum". With more slots the ``(1, C)`` chunk block over the ``(k, C)``
slot arrays breaks the rule that a block's last two dims be divisible
by (8, 128) or equal the array's. So the kernel is an explicit
``backend="pallas"`` opt-in, and ``"auto"`` resolves to ``"fused"``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
I32 = jnp.int32
_NEG = -jnp.inf

_CHUNK = 256


def _scan_max(x):
    return jax.lax.associative_scan(jnp.maximum, x, axis=0)


def _take_q(x_cq, q):
    return jnp.take_along_axis(x_cq, q[:, None], axis=1)[:, 0]


def _queue_kernel(t_s_ref, bank_ref, use_ref, ch_ref, row_ref, go_ref,
                  byp_ref, hp_ref,
                  bank_free_ref, bank_ts_ref, hp_free_ref, hp_ts_ref,
                  hp_sa_ref, lp_free_ref, lp_ts_ref, lp_sa_ref,
                  cur_row_ref,
                  t_head_ref, t0_ref, row_hit_ref,
                  sb_ref, mb_ref, shp_ref, mhp_ref, slp_ref, mlp_ref,
                  hb_ref, lr_ref,
                  *, banks, channels, l2_svc, l2_lat, occ_rowhit,
                  occ_rowmiss, exact):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        sb_ref[0, :] = jnp.zeros((banks,), F32)
        mb_ref[0, :] = jnp.full((banks,), _NEG, F32)
        shp_ref[0, :] = jnp.zeros((channels,), F32)
        mhp_ref[0, :] = jnp.full((channels,), _NEG, F32)
        slp_ref[0, :] = jnp.zeros((channels,), F32)
        mlp_ref[0, :] = jnp.full((channels,), _NEG, F32)
        hb_ref[0, :] = jnp.full((channels,), _NEG, F32)
        lr_ref[0, :] = cur_row_ref[0, :]

    t_s = t_s_ref[0, :]
    bank = bank_ref[0, :]
    ch = ch_ref[0, :]
    row = row_ref[0, :]
    use_l2 = use_ref[0, :] != 0
    go_dram = go_ref[0, :] != 0
    byp = byp_ref[0, :] != 0
    hp = hp_ref[0, :] != 0
    c_len = t_s.shape[0]

    def floor_of(free, last_ts, last_sa, q, t_svc):
        f = free[q]
        if exact:
            return f
        interp = jnp.minimum(f, t_svc + (f - last_sa[q]))
        return jnp.where(t_s >= last_ts[q], f, interp)

    # ---- L2 bank queues (prefix-occ S_b + running-max M_b carry) -----------
    iota_b = jax.lax.broadcasted_iota(I32, (c_len, banks), 1)
    bm = (bank[:, None] == iota_b) & use_l2[:, None]
    occ_b = jnp.where(bm, jnp.float32(l2_svc), 0.0)
    c_loc = jnp.cumsum(occ_b, axis=0) - occ_b
    c_b = sb_ref[0, :][None, :] + c_loc
    u_b = jnp.maximum(t_s, floor_of(bank_free_ref[0, :], bank_ts_ref[0, :],
                                    bank_ts_ref[0, :], bank, t_s))
    v_b = jnp.where(bm, u_b[:, None] - c_b, _NEG)
    m_loc = _scan_max(v_b)
    b_start = c_b + jnp.maximum(mb_ref[0, :][None, :], m_loc)
    t_head = jnp.where(use_l2, _take_q(b_start, bank), 0.0)
    t_head_ref[0, :] = t_head

    # ---- DRAM row-buffer predecessor chain ---------------------------------
    t_da = jnp.where(byp, t_s, t_head + l2_lat)
    iota_c = jax.lax.broadcasted_iota(I32, (c_len, channels), 1)
    slot_c = jax.lax.broadcasted_iota(I32, (c_len, channels), 0)
    cm = (ch[:, None] == iota_c) & go_dram[:, None]
    inc = _scan_max(jnp.where(cm, slot_c, -1))
    prev = jnp.concatenate(
        [jnp.full((1, channels), -1, I32), inc[:-1]], axis=0)
    prev_slot = _take_q(prev, ch)
    prev_row = jnp.where(prev_slot >= 0,
                         jnp.take(row, jnp.maximum(prev_slot, 0)),
                         lr_ref[0, :][ch])
    row_hit = (prev_row == row) & go_dram
    row_hit_ref[0, :] = row_hit.astype(I32)
    occ = jnp.where(row_hit, jnp.float32(occ_rowhit),
                    jnp.float32(occ_rowmiss))

    # ---- high-priority queue ------------------------------------------------
    f_hp = floor_of(hp_free_ref[0, :], hp_ts_ref[0, :], hp_sa_ref[0, :],
                    ch, t_da)
    m_hp = cm & hp[:, None]
    occ_hp = jnp.where(m_hp, occ[:, None], 0.0)
    c_hp = shp_ref[0, :][None, :] + (jnp.cumsum(occ_hp, axis=0) - occ_hp)
    v_hp = jnp.where(m_hp, jnp.maximum(t_da, f_hp)[:, None] - c_hp, _NEG)
    mh_loc = _scan_max(v_hp)
    hp_start = c_hp + jnp.maximum(mhp_ref[0, :][None, :], mh_loc)
    hp_end = jnp.where(m_hp, hp_start + occ_hp, _NEG)
    hp_end_run = _scan_max(hp_end)
    hp_busy = jnp.maximum(
        hb_ref[0, :][None, :],
        jnp.concatenate([jnp.full((1, channels), _NEG),
                         hp_end_run[:-1]], axis=0))

    # ---- low-priority queue (floored on the HP busy horizon) ---------------
    f_lp = floor_of(lp_free_ref[0, :], lp_ts_ref[0, :], lp_sa_ref[0, :],
                    ch, t_da)
    m_lp = cm & ~hp[:, None]
    occ_lp = jnp.where(m_lp, occ[:, None], 0.0)
    c_lp = slp_ref[0, :][None, :] + (jnp.cumsum(occ_lp, axis=0) - occ_lp)
    u_lp = jnp.maximum(t_da, jnp.maximum(
        f_lp, jnp.maximum(f_hp, _take_q(hp_busy, ch))))
    v_lp = jnp.where(m_lp, u_lp[:, None] - c_lp, _NEG)
    ml_loc = _scan_max(v_lp)
    lp_start = c_lp + jnp.maximum(mlp_ref[0, :][None, :], ml_loc)

    t0_ref[0, :] = jnp.where(hp, _take_q(hp_start, ch),
                             _take_q(lp_start, ch))

    # ---- advance the combined carry ----------------------------------------
    last = inc[-1]
    lr_ref[0, :] = jnp.where(last >= 0,
                             jnp.take(row, jnp.maximum(last, 0)),
                             lr_ref[0, :])
    hb_ref[0, :] = jnp.maximum(hb_ref[0, :], hp_end_run[-1])
    sb_ref[0, :] = sb_ref[0, :] + jnp.sum(occ_b, axis=0)
    mb_ref[0, :] = jnp.maximum(mb_ref[0, :], m_loc[-1])
    shp_ref[0, :] = shp_ref[0, :] + jnp.sum(occ_hp, axis=0)
    mhp_ref[0, :] = jnp.maximum(mhp_ref[0, :], mh_loc[-1])
    slp_ref[0, :] = slp_ref[0, :] + jnp.sum(occ_lp, axis=0)
    mlp_ref[0, :] = jnp.maximum(mlp_ref[0, :], ml_loc[-1])


def wave_queue_kernel(t_s, bank, use_l2, ch, row, go_dram, byp, hp, carry,
                      *, banks: int, channels: int, l2_svc: float,
                      l2_lat: float, occ_rowhit: float, occ_rowmiss: float,
                      exact: bool, interpret: bool = False):
    """Chunked one-pass recovery; returns ``(t_head, t0, row_hit)``.

    Same slot-array contract as ``ops.wave_queue_recovery``. The tail
    chunk is padded with all-invalid slots (every mask false), which are
    identity elements for every carried quantity.
    """
    n = t_s.shape[0]
    c_len = min(n, _CHUNK)
    k = -(-n // c_len)
    pad = k * c_len - n

    def shape2(x, fill):
        x = jnp.pad(x, (0, pad), constant_values=fill)
        return x.reshape(k, c_len)

    slot_in = [shape2(t_s, 0.0), shape2(bank, 0),
               shape2(use_l2.astype(I32), 0), shape2(ch, 0),
               shape2(row, 0), shape2(go_dram.astype(I32), 0),
               shape2(byp.astype(I32), 0), shape2(hp.astype(I32), 0)]
    carry_in = [x[None, :] for x in (carry.bank_free, carry.bank_ts,
                                     carry.hp_free, carry.hp_ts,
                                     carry.hp_sa, carry.lp_free,
                                     carry.lp_ts, carry.lp_sa,
                                     carry.cur_row)]

    chunk_spec = pl.BlockSpec((1, c_len), lambda i: (i, 0))
    qf_spec = pl.BlockSpec((1, banks), lambda i: (0, 0))
    qc_spec = pl.BlockSpec((1, channels), lambda i: (0, 0))

    kern = partial(_queue_kernel, banks=banks, channels=channels,
                   l2_svc=l2_svc, l2_lat=l2_lat, occ_rowhit=occ_rowhit,
                   occ_rowmiss=occ_rowmiss, exact=exact)
    t_head, t0, row_hit = pl.pallas_call(
        kern,
        grid=(k,),
        in_specs=[chunk_spec, chunk_spec, chunk_spec, chunk_spec,
                  chunk_spec, chunk_spec, chunk_spec, chunk_spec,
                  qf_spec, qf_spec, qc_spec, qc_spec, qc_spec,
                  qc_spec, qc_spec, qc_spec, qc_spec],
        out_specs=[chunk_spec, chunk_spec, chunk_spec],
        out_shape=[jax.ShapeDtypeStruct((k, c_len), F32),
                   jax.ShapeDtypeStruct((k, c_len), F32),
                   jax.ShapeDtypeStruct((k, c_len), I32)],
        scratch_shapes=[pltpu.VMEM((1, banks), F32),
                        pltpu.VMEM((1, banks), F32),
                        pltpu.VMEM((1, channels), F32),
                        pltpu.VMEM((1, channels), F32),
                        pltpu.VMEM((1, channels), F32),
                        pltpu.VMEM((1, channels), F32),
                        pltpu.VMEM((1, channels), F32),
                        pltpu.VMEM((1, channels), I32)],
        interpret=interpret,
    )(*slot_in, *carry_in)
    return (t_head.reshape(-1)[:n], t0.reshape(-1)[:n],
            row_hit.reshape(-1)[:n] != 0)
