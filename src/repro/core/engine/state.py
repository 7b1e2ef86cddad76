"""Simulation state and static configuration shared by both engines.

``SimParams`` is the hardware description (frozen dataclass — a static
jit argument), ``SimState`` the mutable machine state threaded through
either event loop, and ``init_state`` the common initial condition.
Everything here is engine-agnostic: the exact discrete-event loop
(``engine/event.py``) and the round-lockstep wavefront loop
(``engine/wavefront.py``) both start from the same state and mutate the
same fields.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple

import jax.numpy as jnp

from repro.core import classifier as CLF
from repro.core import warp_types as WT

F32 = jnp.float32
I32 = jnp.int32


@dataclasses.dataclass(frozen=True)
class SimParams:
    sets: int = 512
    ways: int = 8
    banks: int = 6
    l2_svc: float = 4.0        # bank occupancy per request (cycles)
    l2_lat: float = 20.0       # tag+data latency after reaching bank head
    dram_channels: int = 8
    row_lines: int = 32        # lines per DRAM row
    # occupancy (pipelined throughput) vs latency (critical path) split
    occ_rowhit: float = 5.0
    occ_rowmiss: float = 10.0
    t_rowhit: float = 100.0
    t_rowmiss: float = 200.0
    lane_skew: float = 0.5     # per-lane issue skew within an instruction
    rrip_max: int = 7
    eaf_bits: int = 4096
    eaf_capacity: int = 1024   # filter reset period (insertions)
    pc_entries: int = 256
    sampling_interval: int = 64
    # classifier probe cadence: every Nth access of a bypassing warp is
    # forced down the cache path (the default when the policy's traced
    # ``PolicyArrays.probe_interval`` is 0 — see ``POL.probe_interval``)
    probe_interval: int = 8
    mostly_hit_threshold: float = 0.8
    mostly_miss_threshold: float = 0.2
    # energy model (relative units, GPUWattch-flavoured)
    e_l2: float = 1.0
    e_dram: float = 12.0
    e_static: float = 0.08     # per cycle of makespan

    def __post_init__(self):
        # a window's sample count is at most its window, and the label
        # ladder decides exactly up to MAX_SAMPLES (warp_types.ratio_cut)
        if not 0 < self.sampling_interval <= WT.MAX_SAMPLES:
            raise ValueError(
                f"sampling_interval must be in [1, {WT.MAX_SAMPLES}], got "
                f"{self.sampling_interval!r}")


class SimState(NamedTuple):
    tags: jnp.ndarray          # i32[sets, ways] line addr or -1
    rrip: jnp.ndarray          # i32[sets, ways]
    meta_type: jnp.ndarray     # i32[sets, ways] inserting warp's type
    bank_free: jnp.ndarray     # f32[banks]
    cur_row: jnp.ndarray       # i32[channels]
    hp_free: jnp.ndarray       # f32[channels]
    lp_free: jnp.ndarray       # f32[channels]
    clf: CLF.ClassifierState
    eaf: jnp.ndarray           # i32[eaf_bits] generation-stamped bloom bits
    eaf_gen: jnp.ndarray       # i32[] current generation: a bit is set iff
    #                            eaf[i] == eaf_gen, so the periodic filter
    #                            reset is a generation bump, not a (costly
    #                            per-step) array clear
    eaf_ctr: jnp.ndarray       # i32[] insertions since reset
    pc_hits: jnp.ndarray       # i32[pc_entries] cache-path hits
    pc_acc: jnp.ndarray        # i32[pc_entries] cache-path accesses
    pc_req: jnp.ndarray        # i32[pc_entries] ALL valid requests — the
    #                            PC-probe cadence clock. pc_acc freezes
    #                            while a PC bypasses, so gating the probe
    #                            on it would never fire again (the PR 7
    #                            ratchet audit); pc_req keeps ticking.
    tot_hits: jnp.ndarray      # i32[W] lifetime counters (never reset)
    tot_acc: jnp.ndarray       # i32[W]
    metrics: Dict[str, jnp.ndarray]


_QBINS = jnp.asarray([0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 1 << 30],
                     jnp.float32)
N_QBINS = len(_QBINS) - 1      # one bin per [edge_i, edge_{i+1}) interval


def init_state(n_warps: int, prm: SimParams) -> SimState:
    metrics = {
        "qdelay_hist": jnp.zeros((N_QBINS,), I32),
        "qdelay_sum": jnp.zeros((), F32),
        "l2_accesses": jnp.zeros((), I32),
        "l2_hits": jnp.zeros((), I32),
        "dram_accesses": jnp.zeros((), I32),
        "row_hits": jnp.zeros((), I32),
        "bypasses": jnp.zeros((), I32),
        "stall_cycles": jnp.zeros((), F32),
        "evictions_by_type": jnp.zeros((WT.NUM_TYPES,), I32),
    }
    return SimState(
        tags=jnp.full((prm.sets, prm.ways), -1, I32),
        rrip=jnp.full((prm.sets, prm.ways), prm.rrip_max, I32),
        meta_type=jnp.full((prm.sets, prm.ways), WT.BALANCED, I32),
        bank_free=jnp.zeros((prm.banks,), F32),
        cur_row=jnp.full((prm.dram_channels,), -1, I32),
        hp_free=jnp.zeros((prm.dram_channels,), F32),
        lp_free=jnp.zeros((prm.dram_channels,), F32),
        clf=CLF.init(n_warps),
        eaf=jnp.zeros((prm.eaf_bits,), I32),
        eaf_gen=jnp.ones((), I32),
        eaf_ctr=jnp.zeros((), I32),
        pc_hits=jnp.zeros((prm.pc_entries,), I32),
        pc_acc=jnp.zeros((prm.pc_entries,), I32),
        pc_req=jnp.zeros((prm.pc_entries,), I32),
        tot_hits=jnp.zeros((n_warps,), I32),
        tot_acc=jnp.zeros((n_warps,), I32),
        metrics=metrics,
    )
