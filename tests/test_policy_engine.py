"""Unified policy engine tests: branchless ops, vmapped-sweep parity with
per-policy `simulate`, and array-pool parity with the dict reference."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core import warp_types as WT
from repro.core import workloads as WL
from repro.core.simulator import SimParams, simulate, simulate_sweep
from repro.policy import (BYPASS_MECHS, DecisionTables, Policy,
                          stack_policies, to_arrays)
from repro.serving.pool import MedicPoolManager, PoolConfig
from repro.serving.pool_ref import DictPoolManager

PRM = SimParams()

# one policy per mechanism family — exercises every branchless select lane
PARITY_POLICIES = (BL.BASELINE, BL.MEDIC, BL.PCAL, BL.EAF, BL.rand(0.4))


# ---------------------------------------------------------------------------
# PolicyArrays / spec
# ---------------------------------------------------------------------------

def test_to_arrays_one_hot():
    pa = to_arrays(BL.MEDIC)
    assert np.asarray(pa.bypass_sel).sum() == 1.0
    assert np.asarray(pa.bypass_sel)[BYPASS_MECHS.index("medic")] == 1.0
    assert float(pa.sched_medic) == 1.0
    base = to_arrays(BL.BASELINE)
    assert float(base.sched_medic) == 0.0
    assert np.asarray(base.ins_sel)[0] == 1.0   # lru


def test_stack_policies_shapes():
    pa = stack_policies(PARITY_POLICIES)
    assert pa.bypass_sel.shape == (len(PARITY_POLICIES), len(BYPASS_MECHS))
    assert pa.rand_p.shape == (len(PARITY_POLICIES),)


def test_policy_validates_mechanism_names():
    with pytest.raises(ValueError):
        Policy("bad", bypass="nope")
    with pytest.raises(ValueError):
        Policy("bad", insertion="nope")


# ---------------------------------------------------------------------------
# decision tables (host-side mirror of the ops)
# ---------------------------------------------------------------------------

def test_decision_tables_medic_match_warp_type_predicates():
    tb = DecisionTables.from_arrays(
        to_arrays(Policy("m", bypass="medic", insertion="medic",
                         scheduler="medic")), rrip_max=7)
    for t in range(WT.NUM_TYPES):
        assert tb.bypass_by_type[t] == bool(WT.is_bypass_type(jnp.int32(t)))
        assert tb.hp_by_type[t] == bool(WT.is_priority_type(jnp.int32(t)))
        assert tb.rank_by_type[t] == int(WT.insertion_rank(jnp.int32(t), 6))


def test_decision_tables_lru_neutral():
    tb = DecisionTables.from_arrays(to_arrays(Policy("lru")), rrip_max=7)
    assert not tb.bypass_by_type.any()
    assert not tb.hp_by_type.any()
    assert (tb.rank_by_type == 0).all()


# ---------------------------------------------------------------------------
# vmapped sweep == per-policy simulate, bit for bit
# ---------------------------------------------------------------------------

def test_simulate_sweep_matches_per_policy_bitwise():
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM)
    sweep = {k: np.asarray(v)
             for k, v in simulate_sweep(*args, PARITY_POLICIES, **kw).items()}
    for i, pol in enumerate(PARITY_POLICIES):
        one = simulate(*args, pol=pol, **kw)
        for key, v in one.items():
            assert np.array_equal(np.asarray(v), sweep[key][i]), \
                (pol.name, key)


def test_simulate_sweep_seed_stacked_axes():
    spec = WL.WORKLOADS["BP"]
    trs = [WL.generate(spec, seed=s) for s in (0, 1)]
    lines = jnp.stack([jnp.asarray(t["lines"]) for t in trs])
    pcs = jnp.stack([jnp.asarray(t["pcs"]) for t in trs])
    gap = jnp.stack([jnp.asarray(t["compute_gap"]) for t in trs])
    pols = (BL.BASELINE, BL.MEDIC)
    out = simulate_sweep(lines, pcs, gap, pols, n_warps=spec.n_warps,
                         lanes=spec.lines_per_instr, prm=PRM)
    assert out["ipc"].shape == (len(pols), 2)          # [P, S]
    # seed 0 column must equal the unstacked sweep on seed 0
    flat = simulate_sweep(jnp.asarray(trs[0]["lines"]),
                          jnp.asarray(trs[0]["pcs"]),
                          jnp.asarray(trs[0]["compute_gap"]), pols,
                          n_warps=spec.n_warps, lanes=spec.lines_per_instr,
                          prm=PRM)
    assert np.array_equal(np.asarray(out["ipc"][:, 0]),
                          np.asarray(flat["ipc"]))


def test_single_trace_shared_across_policies():
    """The policy is a traced argument: running N policies must not add
    N jit traces (that was the seed's retracing bug)."""
    from repro.core.simulator import _simulate_one
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM)
    before = _simulate_one._cache_size()
    for pol in PARITY_POLICIES:
        simulate(*args, pol=pol, **kw)
    after = _simulate_one._cache_size()
    assert after - before <= 1


# ---------------------------------------------------------------------------
# array pool == dict pool on a recorded access trace
# ---------------------------------------------------------------------------

def _replay(policy: str, seed: int = 0, steps: int = 300):
    cfg = PoolConfig(budget_blocks=24, sampling_interval=8, policy=policy,
                     fetch_occupancy=2.0)
    ev_a, ev_b = [], []
    arr = MedicPoolManager(cfg, max_seqs=8, on_evict=ev_a.append)
    ref = DictPoolManager(cfg, max_seqs=8, on_evict=ev_b.append)
    rng = np.random.default_rng(seed)
    for step in range(steps):
        op = rng.random()
        slot = int(rng.integers(0, 6))
        if op < 0.05:
            arr.reset_slot(slot)
            ref.reset_slot(slot)
        elif op < 0.15:
            key = (slot, int(rng.integers(0, 50)))
            stype = int(ref.seq_type[slot])
            arr.insert_prefill(key, stype)
            ref.insert_prefill(key, stype)
        else:
            hot = rng.random() < 0.5
            blocks = [int(rng.integers(0, 4 if hot else 1000))
                      for _ in range(int(rng.integers(1, 5)))]
            ra, fa = arr.access(slot, blocks, float(step))
            rb, fb = ref.access(slot, blocks, float(step))
            assert ra == rb and fa == fb, step
    return arr, ref, ev_a, ev_b


@pytest.mark.parametrize("policy", ["medic", "lru"])
def test_array_pool_matches_dict_pool(policy):
    arr, ref, ev_a, ev_b = _replay(policy)
    sa, sb = arr.snapshot(), ref.snapshot()
    assert set(sa) == set(sb)
    for k in sa:
        assert np.array_equal(np.asarray(sa[k]), np.asarray(sb[k]),
                              equal_nan=True), k
    # full residency contents + eviction callbacks, in order
    assert arr.resident == ref.resident
    assert ev_a == ev_b
    assert len(ev_a) > 0                      # the trace exercised eviction


def test_array_pool_insert_at_budget_is_vectorized_aging():
    """Filling past budget ages residents via one clamp, same as the dict's
    per-key loop: after pressure, earlier cold inserts carry higher rank."""
    cfg = PoolConfig(budget_blocks=4, sampling_interval=4, policy="lru")
    pool = MedicPoolManager(cfg, max_seqs=2)
    for blk in range(4):
        pool.access(0, [blk], 0.0)
    ranks = pool.resident
    assert len(ranks) == 4
    assert max(ranks.values()) > 0            # aging actually happened
    pool.access(0, [99], 1.0)                 # forces an eviction
    assert len(pool.resident) == 4
    assert pool.snapshot()["evictions_by_type"].sum() == 1


def test_paper_figures_run_covers_off_sweep_policies():
    """_run serves sweep members from the batched cache and anything else
    (e.g. BL.RAND_SWEEP points) through an equivalent one-off path."""
    from benchmarks import paper_figures as PF
    on = PF._run("BP", BL.BASELINE)
    off = PF._run("BP", BL.rand(0.1))           # not in SWEEP_POLICIES
    assert "sweep_wall_s" in on and "sweep_wall_s" in off
    assert float(off["ipc"]) > 0
    # a same-named but differently-configured policy must not be served
    # from the sweep cache
    tweaked = dataclasses.replace(BL.BASELINE, insertion="eaf")
    out = PF._run("BP", tweaked)
    assert float(out["l2_hits"]) != float(on["l2_hits"]) or \
        float(out["ipc"]) != float(on["ipc"])


def test_classify_np_matches_jnp():
    rng = np.random.default_rng(0)
    for _ in range(200):
        acc = int(rng.integers(1, 64))
        hits = int(rng.integers(0, acc + 1))
        r = hits / acc
        a = WT.classify_np(r, acc, min_samples=1)
        b = int(WT.classify(jnp.int32(hits), jnp.int32(acc), min_samples=1))
        assert a == b, (hits, acc)
