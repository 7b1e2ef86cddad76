"""Branchless policy decision ops (paper mechanisms ②③④).

Every function computes the candidate decision of *each* mechanism on the
menu and selects the active one with a one-hot dot product against the
``PolicyArrays`` select weights — no Python dispatch, so a single jit
trace covers every policy and a stacked ``PolicyArrays`` vmaps cleanly.

Callers supply the raw signals a hardware decision point would see
(current warp type, PC-table counters, PCAL token bit, a per-address
hash draw); the ops own the mechanism semantics.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import warp_types as WT
from repro.policy.spec import PolicyArrays

F32 = jnp.float32
I32 = jnp.int32

#: default classifier probe cadence (accesses): every Nth access of a
#: bypassing warp still takes the cache path, keeping an undiluted
#: cache-path sample stream alive for reclassification. Deferred to when
#: ``PolicyArrays.probe_interval`` is 0 (via ``SimParams.probe_interval``).
DEFAULT_PROBE_INTERVAL = 8

#: PC-table probe cadence (requests): every Nth *request* hitting a PC
#: entry takes the cache path even if the entry's ratio says bypass, so
#: the entry's hit/access counters — which only advance on the cache
#: path — keep sampling and a reformed PC can recover. The cadence
#: counter is ``SimState.pc_req`` (all valid requests), NOT ``pc_acc``:
#: gating the probe on a counter that freezes while bypassing would fire
#: at most once more after bypassing starts, then never again.
PC_PROBE_INTERVAL = 16

#: the Rand(p) coin draws a 16-bit hash of the request's address
RAND_RANGE = 1 << 16


def hash_index(x, salt, mod):
    """Knuth-style multiplicative hash -> [0, mod). Shared by the
    simulator's set/bank/channel indexing and the policy ops."""
    h = (jnp.asarray(x).astype(jnp.uint32) * jnp.uint32(2654435761)
         + jnp.uint32(salt) * jnp.uint32(0x9E3779B9))
    h ^= h >> 15
    return (h % jnp.uint32(mod)).astype(I32)


def rand_draw(addr):
    """The Rand(p) coin's draw for a request: i32 in [0, RAND_RANGE)."""
    return hash_index(addr, 7, RAND_RANGE)


def rand_cut(pa: PolicyArrays):
    """Rand(p) bypasses iff ``draw < rand_cut``. ``draw / 2**16 < p`` in
    float32 is exact (both sides are), and so is ``p * 2**16``, so the
    integer form ``draw < ceil(p * 2**16)`` decides the same on every
    chip without a division."""
    return jnp.ceil(pa.rand_p * RAND_RANGE).astype(I32)


def bypass_decision(pa: PolicyArrays, *, wtype, probe, token_bit,
                    pc_hits, pc_acc, pc_req, rand_h):
    """② Should this request skip the shared cache?

    wtype:     i32[] current warp/sequence type (mechanism "medic")
    probe:     bool[] periodic re-learning probe (forces the cache path)
    token_bit: bool[] PCAL token ownership (mechanism "pcal")
    pc_hits/pc_acc: i32[] PC-table cache-path counters (mechanism "pcbyp")
    pc_req:    i32[] PC-table all-request cadence counter (probe clock)
    rand_h:    i32[] the request's coin draw, ``rand_draw`` (mechanism
               "rand")

    Every decision is made in integers: no float quotient is compared
    with a threshold, so the answer is the same on every chip.
    """
    c_none = jnp.zeros(jnp.shape(wtype), bool)
    c_medic = WT.is_bypass_type(wtype) & ~probe
    c_pcal = ~token_bit
    # probe on the Nth request of each cadence window (not the zeroth —
    # `% N == 0` would fire on a fresh entry's very first request)
    pc_probe = (pc_req % PC_PROBE_INTERVAL) == PC_PROBE_INTERVAL - 1
    # the entry's hit ratio below 0.25, in integers: 0.25 is exact in
    # float32, so this equals the float32 comparison for every
    # pc_acc < 2**24 (where the float form's own operands are exact)
    c_pcbyp = (pc_acc > 32) & (4 * pc_hits < pc_acc) & ~pc_probe
    c_rand = rand_h < rand_cut(pa)
    cand = jnp.stack([c_none, c_medic, c_pcal, c_pcbyp, c_rand]).astype(F32)
    return jnp.tensordot(pa.bypass_sel, cand, axes=1) > 0.5


def insertion_rank(pa: PolicyArrays, *, wtype, eaf_bit, rrip_max: int):
    """③ RRIP insertion rank for a filled line/block.

    eaf_bit: bool[] — the address was seen in the evicted-address filter.
    """
    r_lru = jnp.zeros(jnp.shape(wtype), I32)
    r_medic = WT.insertion_rank(wtype, rrip_max - 1)
    r_eaf = jnp.where(eaf_bit, 0, rrip_max - 1).astype(I32)
    cand = jnp.stack([r_lru, r_medic, r_eaf]).astype(F32)
    return jnp.round(jnp.tensordot(pa.ins_sel, cand, axes=1)).astype(I32)


def is_high_priority(pa: PolicyArrays, wtype):
    """④ Does this request take the strict-priority high queue?"""
    return (pa.sched_medic > 0.5) & WT.is_priority_type(wtype)


def select_label(pa: PolicyArrays, clf_wtype, oracle_wtype):
    """① Which warp-type label drives decisions ②③④ for this request.

    ``label_sel`` is one-hot over LABEL_MECHS = (online, stale, oracle);
    online and stale both READ the classifier's label (stale differs in
    how the label is *updated* — see ``reclass_max_windows``), oracle
    substitutes the trace generator's ground-truth per-phase label.
    """
    return jnp.where(pa.label_sel[2] > 0.5, oracle_wtype, clf_wtype)


def reclass_interval(pa: PolicyArrays, default):
    """① Effective classifier sampling window (accesses) — the
    policy-visible reclassification knob; 0 defers to the SimParams
    default."""
    return jnp.where(pa.reclass_interval > 0.5, pa.reclass_interval,
                     jnp.asarray(default, F32))


def probe_interval(pa: PolicyArrays, default):
    """①② Effective probe cadence (accesses between forced cache-path
    probes of a bypassing warp) — policy-visible and sweepable like the
    sampling window; 0 defers to the SimParams default
    (``DEFAULT_PROBE_INTERVAL``)."""
    return jnp.where(pa.probe_interval > 0.5, pa.probe_interval,
                     jnp.asarray(default, F32))


#: effectively-unbounded window count for the online labeling mode
_NO_WINDOW_CAP = 1 << 30


def reclass_max_windows(pa: PolicyArrays):
    """① How many sampling windows may update a warp's label: 1 for the
    stale (classify-once, phase-0) mode, unbounded otherwise. The window
    machinery keeps cycling either way (EAF-style generation counting in
    ``classifier.observe``) — only the label write is gated."""
    return jnp.where(pa.label_sel[1] > 0.5, 1, _NO_WINDOW_CAP).astype(I32)


def pcal_tokens(pa: PolicyArrays, n_warps: int):
    """PCAL token assignment: a pseudo-random but fixed subset of warps,
    blind to warp type (first-come/scheduler-order in the paper)."""
    n_tokens = jnp.maximum(
        1, jnp.round(pa.pcal_frac * n_warps)).astype(I32)
    return hash_index(jnp.arange(n_warps, dtype=I32), 11, 997) < (
        997 * n_tokens // n_warps)
