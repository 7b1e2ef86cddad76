"""Plain reference of the simulated L2 + DRAM under MeDiC-style policies.

Written in numpy from the model's semantics, independent of the
program's code. ``K`` independent simulations (one policy on one trace
each) advance side by side; every array carries a leading ``[K]`` axis.

The model, per memory instruction of a warp (16 lanes, one line each):

* ① label: the warp's type from its online hit-ratio classifier
  (sampling window, probe cadence, label freeze for ``stale``) or the
  trace's ground-truth label (``oracle``);
* ② bypass decision of the policy's mechanism (warp type, PCAL tokens,
  PC-table hit ratio, or a per-address coin);
* L2: set-associative tags with SRRIP aging; ③ insertion rank of the
  policy (LRU, warp type, evicted-address filter);
* queues: FIFO L2 banks, then DRAM channels with an open row and two
  priority classes (④ MeDiC sends mostly-hit warps to the high class).

Order of service. The event engine pops the earliest-ready warp (ties
by lowest id) and serves its lanes in order: ``wave = 1`` here. The
wavefront engine serves a wave of the ``B`` earliest-ready warps at a
time: for each lane, the wave's slots read the cache as it stood before
that lane (writes to one cell resolve to the latest slot), then the
wave's ``B x L`` requests go through the queues in warp-major order, a
request behind the queue's served frontier waiting only for the queue's
standing backlog. Queue start times use the closed form ``start_j =
c_j + max_{i<=j}(max(t_i, floor_i) - c_i)`` (``c``: the queue's
occupancy before ``j``), which equals the sequential recurrence
``start_j = max(t_j, floor_j, end_{j-1})`` wherever the clocks are
exact in float32.

``clock`` is the type of every simulated time. The configuration states
float32; a lower precision is the benchmark's control.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

I32 = np.int32
F32 = np.float32

ALL_MISS, MOSTLY_MISS, BALANCED, MOSTLY_HIT, ALL_HIT = range(5)
N_TYPES = 5
QBIN_EDGES = np.asarray([1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024], F32)
N_QBINS = len(QBIN_EDGES) + 1
PC_PROBE_INTERVAL = 16
NO_WINDOW_CAP = 1 << 30

BYPASS = ("none", "medic", "pcal", "pcbyp", "rand")
INSERTION = ("lru", "medic", "eaf")
LABELING = ("online", "stale", "oracle")

INT_METRICS = ("qdelay_hist", "l2_accesses", "l2_hits", "dram_accesses",
               "row_hits", "bypasses", "evictions_by_type", "warp_type")
FLOAT_METRICS = ("qdelay_sum", "stall_cycles", "makespan", "ipc",
                 "ipc_makespan", "warp_time", "energy", "perf_per_energy",
                 "warp_hit_ratio", "ratio_over_time", "miss_rate",
                 "mean_qdelay")


def hash_index(x, salt: int, mod: int):
    """Multiplicative hash in uint32 arithmetic -> [0, mod)."""
    with np.errstate(over="ignore"):
        h = (np.asarray(x).astype(I32).astype(np.uint32)
             * np.uint32(2654435761)
             + np.uint32((salt * 0x9E3779B9) & 0xFFFFFFFF))
        h ^= h >> np.uint32(15)
        return (h % np.uint32(mod)).astype(I32)


def default_wave(n_warps: int) -> int:
    """The wavefront engine's default wave: W/4 above 256 warps, else
    max(min(W, 8), W/6)."""
    if n_warps > 256:
        return n_warps // 4
    return max(min(n_warps, 8), n_warps // 6)


def classify(ratio, samples, min_samples, hit_thr, miss_thr):
    r = ratio
    t = np.full(r.shape, BALANCED, I32)
    t = np.where(r <= F32(miss_thr), MOSTLY_MISS, t)
    t = np.where(r <= F32(1e-6), ALL_MISS, t)
    t = np.where(r >= F32(hit_thr), MOSTLY_HIT, t)
    t = np.where(r >= F32(1.0 - 1e-6), ALL_HIT, t)
    return np.where(samples >= min_samples, t, BALANCED).astype(I32)


def last_writes(idx):
    """Positions of the last occurrence of each value of ``idx``."""
    _, first_rev = np.unique(idx[::-1], return_index=True)
    return len(idx) - 1 - first_rev


def pairwise_sum(x):
    """Sum over the last axis in the fixed pairwise order the wavefront
    engine states: pad to a power of two, add the halves, repeat."""
    n = x.shape[-1]
    size = 1 << max(n - 1, 0).bit_length()
    x = np.concatenate([x, np.zeros(x.shape[:-1] + (size - n,), x.dtype)],
                       axis=-1)
    while x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return x[..., 0]


def _shift_right(x, fill):
    pad = np.full(x.shape[:-1] + (1,), fill, x.dtype)
    return np.concatenate([pad, x[..., :-1]], axis=-1)


class _Queue:
    """Per-queue FIFO service over one wave, vectorized over K."""

    def __init__(self, clock):
        self.ct = np.dtype(clock)
        self.ninf = np.asarray(-np.inf, self.ct)

    def starts(self, mask, t_arr, occ, floor):
        occ_m = np.where(mask, occ, np.asarray(0, self.ct)).astype(self.ct)
        c = (np.cumsum(occ_m, axis=-1, dtype=self.ct) - occ_m).astype(self.ct)
        v = np.where(mask, np.maximum(t_arr, floor) - c, self.ninf)
        start = (c + np.maximum.accumulate(v.astype(self.ct), axis=-1)
                 ).astype(self.ct)
        end = np.where(mask, start + occ_m, self.ninf).astype(self.ct)
        return start, end

    def floor(self, free, last_ts, last_sa, t_s, t_svc, exact):
        """Per-request floor [K, Q, N] of each queue (free etc. [K, Q]):
        its busy-until; for a request behind the queue's served frontier
        (``t_s < last_ts``, the wave ran ahead of the warps that last
        used it), at most its arrival plus the standing backlog."""
        f = free[..., None]
        if exact:
            return np.broadcast_to(f, np.broadcast_shapes(f.shape, t_s.shape))
        backlog = (free - last_sa)[..., None]
        interp = np.minimum(f, (t_svc + backlog).astype(self.ct))
        return np.where(t_s >= last_ts[..., None], f, interp).astype(self.ct)


def simulate(traces: Sequence[Dict[str, np.ndarray]],
             policies: Sequence[dict], prm: dict, *, engine: str,
             clock=F32) -> List[Dict[str, np.ndarray]]:
    """Simulate ``traces[k]`` under ``policies[k]`` for every k.

    A trace holds lines i32[I, W, L], pcs i32[I, W], oracle_wtype
    i32[I, W] and compute_gap (a scalar). A policy is a dict with the
    keys name, bypass, insertion, scheduler, rand_p, pcal_frac,
    labeling, reclass_interval, probe_interval. ``prm`` holds the
    simulated GPU's parameters. Returns one metrics dict per k."""
    ct = np.dtype(clock)
    C = lambda x: np.asarray(x, ct)  # noqa: E731
    q = _Queue(ct)
    K = len(traces)
    lines = np.stack([t["lines"] for t in traces]).astype(I32)  # [K,I,W,L]
    pcs = np.stack([t["pcs"] for t in traces]).astype(I32)
    oracle = np.stack([t["oracle_wtype"] for t in traces]).astype(I32)
    gap = np.asarray([t["compute_gap"] for t in traces], F32).astype(ct)
    _, n_i, n_w, n_l = lines.shape
    sets, ways = prm["sets"], prm["ways"]
    banks, chans = prm["banks"], prm["dram_channels"]
    rmax, ebits, pce = prm["rrip_max"], prm["eaf_bits"], prm["pc_entries"]
    if engine == "event":
        B, exact, seq_sums = 1, True, True
    elif engine == "wavefront":
        B = max(1, min(default_wave(n_w), n_w))
        exact, seq_sums = B == 1, False
    else:
        raise ValueError(f"unknown engine {engine!r}")

    # ---- per-simulation policy constants --------------------------------
    byp_m = np.asarray([BYPASS.index(p["bypass"]) for p in policies])
    ins_m = np.asarray([INSERTION.index(p["insertion"]) for p in policies])
    lab_m = np.asarray([LABELING.index(p["labeling"]) for p in policies])
    sched = np.asarray([p["scheduler"] == "medic" for p in policies])
    rand_p = np.asarray([p["rand_p"] for p in policies], F32)
    interval = np.asarray([p["reclass_interval"] or prm["sampling_interval"]
                           for p in policies], F32)
    probe_iv = np.asarray([p["probe_interval"] or prm["probe_interval"]
                           for p in policies], F32)
    pi = probe_iv.astype(I32)
    min_samples = np.clip(np.floor(interval / np.maximum(probe_iv, F32(1))),
                          1.0, 8.0).astype(F32)
    max_windows = np.where(lab_m == 1, 1, NO_WINDOW_CAP).astype(I32)
    n_tok = np.maximum(1, np.round(
        np.asarray([p["pcal_frac"] for p in policies], F32) * F32(n_w))
    ).astype(I32)
    tokens = (hash_index(np.arange(n_w), 11, 997)[None, :]
              < (997 * n_tok // n_w)[:, None])                 # [K, W]

    # ---- machine state ---------------------------------------------------
    tags = np.full((K * sets, ways), -1, I32)      # row = k * sets + set
    rrip = np.full((K * sets, ways), rmax, I32)
    meta = np.full((K * sets, ways), BALANCED, I32)
    eaf = np.zeros((K, ebits), I32)
    eaf_gen = np.ones(K, I32)
    eaf_ctr = np.zeros(K, I32)
    pc_hits = np.zeros((K, pce), I32)
    pc_acc = np.zeros((K, pce), I32)
    pc_req = np.zeros((K, pce), I32)
    c_hits = np.zeros((K, n_w), I32)
    c_acc = np.zeros((K, n_w), I32)
    c_type = np.full((K, n_w), BALANCED, I32)
    c_ratio = np.full((K, n_w), 0.5, F32)
    c_win = np.zeros((K, n_w), I32)
    c_samp = np.zeros((K, n_w), I32)
    tot_hits = np.zeros((K, n_w), I32)
    tot_acc = np.zeros((K, n_w), I32)
    bank_free = np.zeros((K, banks), ct)
    hp_free = np.zeros((K, chans), ct)
    lp_free = np.zeros((K, chans), ct)
    cur_row = np.full((K, chans), -1, I32)
    bank_ts = np.full((K, banks), -np.inf, ct)
    hp_ts = np.full((K, chans), -np.inf, ct)
    hp_sa = np.full((K, chans), -np.inf, ct)
    lp_ts = np.full((K, chans), -np.inf, ct)
    lp_sa = np.full((K, chans), -np.inf, ct)
    ready = np.zeros((K, n_w), ct)
    ptr = np.zeros((K, n_w), I32)
    ratio_t = np.zeros((K, n_i, n_w), F32)
    m = {"qdelay_hist": np.zeros((K, N_QBINS), I32),
         "qdelay_sum": np.zeros(K, ct), "stall_cycles": np.zeros(K, ct),
         "l2_accesses": np.zeros(K, I32), "l2_hits": np.zeros(K, I32),
         "dram_accesses": np.zeros(K, I32), "row_hits": np.zeros(K, I32),
         "bypasses": np.zeros(K, I32),
         "evictions_by_type": np.zeros((K, N_TYPES), I32)}

    kk = np.arange(K)[:, None]                                     # [K, 1]
    way = np.arange(ways)
    col = lambda x: np.asarray(x)[:, None]  # noqa: E731
    by_medic, by_pcal, by_pcbyp, by_rand = (col(byp_m == i)
                                            for i in range(1, 5))
    ins_medic, ins_eaf = col(ins_m == 1), col(ins_m == 2)
    by_oracle, sched = col(lab_m == 2), col(sched)
    any_pcbyp, any_eaf = bool(by_pcbyp.any()), bool(ins_eaf.any())

    def scatter_add(table, idx, val):
        """table[k, idx[k, b]] += val[k, b], repeated indices adding up."""
        if B == 1:
            table[kk, idx] += val.astype(I32)
        else:
            flat_i = (kk * table.shape[1] + idx).ravel()
            table += np.bincount(flat_i, val.ravel().astype(I32),
                                 table.size).reshape(table.shape).astype(I32)

    lane_t = np.arange(n_l).astype(F32) * F32(prm["lane_skew"])

    while (ptr < n_i).any():
        # ---- the wave: B earliest-ready active warps, ties by id ---------
        active = ptr < n_i
        key = np.where(active, ready, C(np.inf))
        if B == 1:
            w_sel = np.argmin(key, axis=1)[:, None]
        else:
            w_sel = np.argsort(key, axis=1, kind="stable")[:, :B]
        slot_ok = active[kk, w_sel]                                # [K, B]
        i_sel = np.minimum(ptr[kk, w_sel], n_i - 1)
        t0 = ready[kk, w_sel]
        addr_b = lines[kk, i_sel, w_sel]                           # [K,B,L]
        pc_b = pcs[kk, i_sel, w_sel]
        owt_b = oracle[kk, i_sel, w_sel]
        tok_b = tokens[kk, w_sel]
        h_b, a_b, ty_b = c_hits[kk, w_sel], c_acc[kk, w_sel], c_type[kk, w_sel]
        r_b, wi_b, s_b = c_ratio[kk, w_sel], c_win[kk, w_sel], c_samp[kk, w_sel]
        pidx = hash_index(pc_b, 3, pce)
        # per-lane inputs that do not depend on the cache's state
        valid_l = (addr_b >= 0) & slot_ok[..., None]               # [K,B,L]
        row_l = kk[..., None] * sets + hash_index(addr_b, 2, sets)
        coin_l = (hash_index(addr_b, 7, 65536).astype(F32) / F32(65536)
                  < rand_p[:, None, None])
        ebit_l = hash_index(addr_b, 5, ebits)
        no_token = by_pcal & ~tok_b
        rec = {k: np.zeros((K, B, n_l), bool)
               for k in ("byp", "use", "hit", "hp")}

        # ---- cache side, lane by lane ------------------------------------
        for lane in range(n_l):
            addr, valid = addr_b[..., lane], valid_l[..., lane]
            row = row_l[..., lane]
            wtype = np.where(by_oracle, owt_b, ty_b)
            probe = (a_b % pi[:, None]) == pi[:, None] - 1
            byp = (by_medic & (wtype <= MOSTLY_MISS) & ~probe) | no_token \
                | (by_rand & coin_l[..., lane])
            if any_pcbyp:
                ph, pa_, pr = (t[kk, pidx] for t in (pc_hits, pc_acc, pc_req))
                ratio_pc = ph.astype(F32) / np.maximum(pa_, 1).astype(F32)
                byp |= by_pcbyp & (pa_ > 32) & (ratio_pc < F32(0.25)) \
                    & ((pr % PC_PROBE_INTERVAL) != PC_PROBE_INTERVAL - 1)
            byp &= valid
            use = valid & ~byp

            tset = tags[row]                                       # [K,B,ways]
            is_line = tset == addr[..., None]
            hit = is_line.any(-1) & use
            rset = rrip[row]
            rset = np.where(hit[..., None] & (way == np.argmax(is_line, -1)
                                              [..., None]), 0, rset)
            alloc = use & ~hit
            aged = rset + np.where(alloc, rmax - rset.max(-1), 0)[..., None]
            victim = np.argmax(aged, axis=-1)
            vict_oh = way == victim[..., None]
            evicted = tset[vict_oh].reshape(K, B)
            vtype = meta[row, victim]
            rank = np.where(wtype >= MOSTLY_HIT, 0,
                            np.where(wtype == BALANCED, rmax - 2, rmax - 1))
            rank = np.where(ins_medic, rank, 0)
            if any_eaf:
                ebit = eaf[kk, ebit_l[..., lane]] == eaf_gen[:, None]
                rank = np.where(ins_eaf & ~ebit, rmax - 1, rank)
            new_row = np.where(alloc[..., None],
                               np.where(vict_oh, rank[..., None], aged), rset)

            # writes, slots in order: the latest slot wins a cell
            a_idx = np.flatnonzero(alloc)
            cell = row.ravel()[a_idx] * ways + victim.ravel()[a_idx]
            if B > 1:
                keep = last_writes(cell)
                a_idx, cell = a_idx[keep], cell[keep]
            tags.ravel()[cell] = addr.ravel()[a_idx]
            meta.ravel()[cell] = wtype.ravel()[a_idx]
            u_idx = np.flatnonzero(use)
            rows_w = row.ravel()[u_idx]
            if B > 1:
                keep = last_writes(rows_w)
                u_idx, rows_w = u_idx[keep], rows_w[keep]
            rrip[rows_w] = new_row.reshape(-1, ways)[u_idx]

            ev = alloc & (evicted >= 0)
            e_k, e_b = np.nonzero(ev)
            eaf[e_k, hash_index(evicted[e_k, e_b], 5, ebits)] = eaf_gen[e_k]
            eaf_ctr = eaf_ctr + ev.sum(1).astype(I32)
            reset = eaf_ctr >= prm["eaf_capacity"]
            eaf_gen = eaf_gen + reset
            eaf_ctr = np.where(reset, 0, eaf_ctr).astype(I32)

            # ① classifier rows of the wave's warps
            h_b = h_b + hit
            a_b = a_b + valid
            s_b = s_b + use
            due = a_b.astype(F32) >= interval[:, None]
            r_now = h_b.astype(F32) / np.maximum(s_b, 1).astype(F32)
            t_new = classify(r_now, s_b.astype(F32), min_samples[:, None],
                             prm["mostly_hit_threshold"],
                             prm["mostly_miss_threshold"])
            ty_b = np.where(due & (wi_b < max_windows[:, None]), t_new, ty_b)
            r_b = np.where(due, r_now, r_b)
            wi_b = wi_b + due
            h_b, a_b, s_b = (np.where(due, 0, x) for x in (h_b, a_b, s_b))
            scatter_add(pc_hits, pidx, hit)
            scatter_add(pc_acc, pidx, use)
            scatter_add(pc_req, pidx, valid)
            scatter_add(m["evictions_by_type"], vtype, ev)
            for name, x in (("byp", byp), ("use", use), ("hit", hit),
                            ("hp", sched & (wtype >= MOSTLY_HIT))):
                rec[name][..., lane] = x

        for name, x in ((c_hits, h_b), (c_acc, a_b), (c_type, ty_b),
                        (c_ratio, r_b), (c_win, wi_b), (c_samp, s_b)):
            name[kk, w_sel] = x
        rec["valid"] = valid_l
        for name, key in (("l2_accesses", "use"), ("l2_hits", "hit"),
                          ("bypasses", "byp")):
            m[name] += rec[key].sum((1, 2)).astype(I32)
        tot_hits[kk, w_sel] += rec["hit"].sum(-1).astype(I32)
        tot_acc[kk, w_sel] += rec["valid"].sum(-1).astype(I32)

        # ---- queue side: the wave's requests in warp-major order ---------
        N = B * n_l
        flat = lambda x: x.reshape(K, N)  # noqa: E731
        valid, byp, use, hit, hp = (flat(rec[k]) for k in
                                    ("valid", "byp", "use", "hit", "hp"))
        addr = flat(addr_b)
        t_s = (t0[..., None] + lane_t.astype(ct)).reshape(K, N).astype(ct)
        bank = hash_index(addr, 1, banks)
        drow = (addr // prm["row_lines"]).astype(I32)
        ch = hash_index(drow, 4, chans)
        go = valid & (byp | ~hit)

        # one row per queue: [K, Q, N] masks, requests in service order
        t_s3, slot = t_s[:, None, :], np.arange(N)
        bmask = (bank[:, None, :] == np.arange(banks)[:, None]) \
            & use[:, None, :]
        fl = q.floor(bank_free, bank_ts, bank_ts, t_s3, t_s3, exact)
        st, en = q.starts(bmask, t_s3, C(prm["l2_svc"]), fl)
        t_head = np.where(bmask, st, C(0)).sum(1, dtype=ct)
        bank_free = np.maximum(bank_free, en.max(-1))
        bank_ts = np.maximum(bank_ts, np.where(bmask, t_s3, C(-np.inf))
                             .max(-1))

        t_da = np.where(byp, t_s, (t_head + C(prm["l2_lat"])).astype(ct))
        t_da3 = t_da[:, None, :]
        cm = (ch[:, None, :] == np.arange(chans)[:, None]) & go[:, None, :]
        # open row: the previous request of the channel in this wave, else
        # the row the channel left open
        last = np.maximum.accumulate(np.where(cm, slot, -1), axis=-1)
        prev = _shift_right(last, -1)
        drow3 = np.broadcast_to(drow[:, None, :], cm.shape)
        prev_row = np.where(prev >= 0, np.take_along_axis(
            drow3, np.maximum(prev, 0), -1), cur_row[..., None])
        rh = (prev_row == drow3) & cm
        row_hit = rh.any(1)
        occ = np.where(rh, C(prm["occ_rowhit"]), C(prm["occ_rowmiss"]))
        m_hp, m_lp = cm & hp[:, None, :], cm & ~hp[:, None, :]
        hp_fl = q.floor(hp_free, hp_ts, hp_sa, t_s3, t_da3, exact)
        hs, he = q.starts(m_hp, t_da3, occ, hp_fl)
        # strict priority: a low-priority request also waits for the high
        # queue's busy horizon at its place in the order
        hp_busy = _shift_right(np.maximum.accumulate(he, axis=-1), -np.inf)
        lp_fl = np.maximum(q.floor(lp_free, lp_ts, lp_sa, t_s3, t_da3, exact),
                           np.maximum(hp_fl, hp_busy)).astype(ct)
        ls, le = q.starts(m_lp, t_da3, occ, lp_fl)
        t_dram = np.take_along_axis(np.where(hp[:, None, :], hs, ls),
                                    ch[:, None, :], 1)[:, 0]
        hp_free = np.maximum(hp_free, he.max(-1))
        lp_free = np.maximum(lp_free, le.max(-1))
        cur_row = np.where(last[..., -1] >= 0, np.take_along_axis(
            drow, np.maximum(last[..., -1], 0), -1), cur_row)
        hp_ts, hp_sa, lp_ts, lp_sa = (
            np.maximum(anchor, np.where(mask, t, C(-np.inf)).max(-1))
            for anchor, mask, t in ((hp_ts, m_hp, t_s3), (hp_sa, m_hp, t_da3),
                                    (lp_ts, m_lp, t_s3), (lp_sa, m_lp, t_da3)))

        qdelay = np.where(use, t_head - t_s, C(0)).astype(ct)
        lat = np.where(row_hit, C(prm["t_rowhit"]), C(prm["t_rowmiss"]))
        t_done = np.where(hit, t_head + C(prm["l2_lat"]), t_dram + lat)
        t_done = np.where(valid, t_done, t_s).astype(ct)

        qbin = (qdelay[..., None] >= QBIN_EDGES.astype(ct)).sum(-1)
        m["qdelay_hist"] += np.bincount(
            (kk * N_QBINS + qbin).ravel(), use.ravel().astype(I32),
            K * N_QBINS).reshape(K, N_QBINS).astype(I32)
        if seq_sums:
            for j in range(N):
                m["qdelay_sum"] = (m["qdelay_sum"] + qdelay[:, j]).astype(ct)
        else:
            m["qdelay_sum"] = (m["qdelay_sum"]
                               + pairwise_sum(qdelay)).astype(ct)
        m["dram_accesses"] += go.sum(1).astype(I32)
        m["row_hits"] += row_hit.sum(1).astype(I32)

        # ---- the instruction completes when its last lane returns --------
        v3 = rec["valid"]
        done = t_done.reshape(K, B, n_l)
        dmax = np.where(v3, done, C(-np.inf)).max(-1)
        dmin = np.where(v3, done, C(np.inf)).min(-1)
        has = v3.any(-1)
        stall = np.where(has & slot_ok, dmax - dmin, C(0)).astype(ct)
        m["stall_cycles"] = (m["stall_cycles"] + pairwise_sum(stall)
                             ).astype(ct)
        g = gap[:, None]
        new_ready = np.where(has, dmax + g, t0 + g).astype(ct)
        ok_k, ok_b = np.nonzero(slot_ok)
        w_ok = w_sel[ok_k, ok_b]
        ready[ok_k, w_ok] = new_ready[ok_k, ok_b]
        ptr[ok_k, w_ok] += 1
        ratio_t[ok_k, i_sel[ok_k, ok_b], w_ok] = c_ratio[ok_k, w_ok]

    # ---- closing statistics ---------------------------------------------
    out = []
    for k in range(K):
        mk = {name: v[k] for name, v in m.items()}
        makespan = ready[k].max()
        wt = np.maximum(ready[k] - gap[k], C(1.0)).astype(ct)
        ipc = (F32(n_i) / wt.astype(F32)).astype(F32).sum(dtype=F32)
        l2a = F32(mk["l2_accesses"])
        energy = (l2a * F32(prm["e_l2"]) + F32(mk["dram_accesses"])
                  * F32(prm["e_dram"]) + F32(makespan) * F32(prm["e_static"]))
        mk.update({
            "makespan": makespan,
            "ipc": ipc,
            "ipc_makespan": F32(n_i * n_w) / max(F32(makespan), F32(1.0)),
            "warp_time": wt,
            "energy": energy,
            "perf_per_energy": ipc / energy * F32(1e3),
            "warp_hit_ratio": tot_hits[k].astype(F32)
            / np.maximum(tot_acc[k], 1).astype(F32),
            "warp_type": c_type[k],
            "ratio_over_time": ratio_t[k],
            "miss_rate": F32(1.0) - F32(mk["l2_hits"]) / max(l2a, F32(1)),
            "mean_qdelay": F32(mk["qdelay_sum"]) / max(l2a, F32(1)),
        })
        out.append(mk)
    return out
