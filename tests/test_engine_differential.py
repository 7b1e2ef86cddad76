"""Differential suite: wavefront engine vs the exact event engine.

Three rungs, mirroring the repo's other ref-vs-vectorized pairs
(`pool_ref`, `tracegen/ref.py`):

  1. single-warp traces — EXACT parity (a wave of one warp reduces every
     prefix op to the event engine's scalar update);
  2. ``wave_size=1`` at paper scale — exact parity (the wave machinery
     with chronological selection IS the event loop);
  3. default wave size at paper scale — documented tolerance: ≤2% on
     IPC/makespan and identical Fig 7 policy ordering, across all 15
     workloads (DESIGN.md §9 accuracy envelope).

Plus the batched-classifier property tests the wavefront engine relies
on: an [N]-shaped ``classifier.observe`` with distinct warp ids must
equal N sequential scalar observes, window resets included.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core import classifier as CLF
from repro.core import tracegen as TG
from repro.core import workloads as WL
from repro.core.simulator import SimParams, simulate, simulate_sweep
from repro.policy import to_arrays

PRM = SimParams()
# one policy per mechanism family, matching the stress-matrix sweep
DIFF_POLICIES = (BL.BASELINE, BL.PCAL, BL.WBYP, BL.MEDIC)
#: default labeling/window knobs — what the pre-phased engines ran with
PA_DEFAULT = to_arrays(BL.BASELINE)

INT_KEYS = ("l2_accesses", "l2_hits", "dram_accesses", "row_hits",
            "bypasses", "qdelay_hist", "evictions_by_type")


def _run_pair(trace, n_warps, lanes, policies, **wf_kw):
    args = (jnp.asarray(trace["lines"]), jnp.asarray(trace["pcs"]),
            jnp.asarray(trace["compute_gap"]))
    kw = dict(n_warps=n_warps, lanes=lanes, prm=PRM)
    if "oracle_wtype" in trace:
        kw["oracle_types"] = jnp.asarray(trace["oracle_wtype"])
    ev = simulate_sweep(*args, policies, engine="event", **kw)
    wf = simulate_sweep(*args, policies, engine="wavefront", **kw, **wf_kw)
    tonp = lambda d: {k: np.asarray(v) for k, v in d.items()}
    return tonp(ev), tonp(wf)


# ---------------------------------------------------------------------------
# rung 1: single-warp traces are exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", ["BFS", "BP"])
def test_single_warp_exact(workload):
    spec = dataclasses.replace(
        TG.TraceSpec.from_workload(WL.WORKLOADS[workload]), n_warps=1)
    tr = TG.generate(spec, seed=0)
    ev, wf = _run_pair(tr, 1, spec.lines_per_instr, DIFF_POLICIES)
    for k in INT_KEYS:
        assert np.array_equal(ev[k], wf[k]), k
    for k in ("makespan", "ipc", "stall_cycles", "qdelay_sum",
              "warp_hit_ratio", "ratio_over_time"):
        np.testing.assert_allclose(wf[k], ev[k], rtol=1e-5, atol=1e-5,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# rung 2: wave_size=1 IS the event loop
# ---------------------------------------------------------------------------

def test_wave_of_one_matches_event_at_paper_scale():
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    ev, wf = _run_pair(tr, spec.n_warps, spec.lines_per_instr,
                       (BL.BASELINE, BL.MEDIC), wave_size=1)
    for k in ev:
        np.testing.assert_allclose(wf[k], ev[k], rtol=1e-5, atol=1e-5,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# rung 3: default wave size, tolerance + ordering across all 15 workloads
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _pair_48(workload: str):
    spec = WL.WORKLOADS[workload]
    tr = WL.generate(spec, seed=0)
    return _run_pair(tr, spec.n_warps, spec.lines_per_instr, DIFF_POLICIES)


@pytest.mark.parametrize("workload", WL.WORKLOAD_NAMES)
def test_tolerance_and_ordering_at_48_warps(workload):
    """Measured accuracy envelope at the default wave size (W//6):
    worst |IPC| 1.9% and worst makespan 4.2% over the 15-workload ×
    4-policy matrix (DESIGN.md §9) — asserted at 2% / 4.5%. The
    makespan envelope was re-measured for PR 7: the probe-ratchet fix
    makes labels responsive to the probe sample, so a single warp whose
    window closes on different wave boundaries can relabel a wave apart
    between engines and finish visibly later — makespan (a max, not a
    mean) sees it undamped. One cell (NW × MeDiC, 4.2%) sits past the
    old 2.5% bound; the next-worst cell is 2.0%."""
    ev, wf = _pair_48(workload)
    ipc_rel = np.abs(wf["ipc"] - ev["ipc"]) / ev["ipc"]
    mk_rel = np.abs(wf["makespan"] - ev["makespan"]) / ev["makespan"]
    assert ipc_rel.max() <= 0.02, (workload, ipc_rel)
    assert mk_rel.max() <= 0.045, (workload, mk_rel)
    # identical Fig 7 policy ordering
    assert np.array_equal(np.argsort(wf["ipc"]), np.argsort(ev["ipc"])), \
        (workload, wf["ipc"], ev["ipc"])


def test_aggregate_counters_close_at_48_warps():
    """Decision-dependent counters may drift slightly with ordering, but
    totals must stay conserved and close."""
    ev, wf = _pair_48("BFS")
    total = ev["l2_accesses"] + ev["bypasses"]
    assert np.array_equal(total, wf["l2_accesses"] + wf["bypasses"])
    for k in ("l2_hits", "dram_accesses"):
        np.testing.assert_allclose(wf[k], ev[k], rtol=0.02, err_msg=k)


def test_wavefront_sweep_matches_per_policy_bitwise():
    """The vmapped wavefront sweep must equal per-policy wavefront
    `simulate` calls bit-for-bit, mirroring the event-engine guarantee
    in tests/test_policy_engine.py."""
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM,
              engine="wavefront")
    sweep = {k: np.asarray(v) for k, v in
             simulate_sweep(*args, DIFF_POLICIES, **kw).items()}
    for i, pol in enumerate(DIFF_POLICIES):
        one = simulate(*args, pol=pol, **kw)
        for key, v in one.items():
            assert np.array_equal(np.asarray(v), sweep[key][i]), \
                (pol.name, key)


# ---------------------------------------------------------------------------
# phased envelope: the accuracy claim covers drifting traces too
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4)
def _phased_pair_48(policy_set: str):
    spec = TG.PHASED_SPECS["PHASED48"]
    tr = TG.generate(spec, seed=0)
    pols = DIFF_POLICIES if policy_set == "mechanisms" \
        else BL.LABELING_LADDER
    return _run_pair(tr, spec.n_warps, spec.lines_per_instr, pols)


def test_phased_tolerance_and_ordering_at_48_warps():
    """Same envelope as the steady-state rung 3 (|IPC| ≤ 2%, makespan ≤
    2.5%, identical policy ordering), on the drifting PHASED48 trace —
    measured worst |IPC| 0.9% / makespan 1.1% across the 4-policy
    mechanism set."""
    ev, wf = _phased_pair_48("mechanisms")
    ipc_rel = np.abs(wf["ipc"] - ev["ipc"]) / ev["ipc"]
    mk_rel = np.abs(wf["makespan"] - ev["makespan"]) / ev["makespan"]
    assert ipc_rel.max() <= 0.02, ipc_rel
    assert mk_rel.max() <= 0.025, mk_rel
    assert np.array_equal(np.argsort(wf["ipc"]), np.argsort(ev["ipc"])), \
        (wf["ipc"], ev["ipc"])


def test_phased_labeling_ladder_cross_engine_envelope():
    """The labeling modes (stale freeze, online windows, oracle
    substitution) must deviate identically in both engines: same ≤2% /
    ≤2.5% envelope across the 5-policy ladder. Ordering is NOT asserted
    here — stale and default-window online are a designed near-tie at 48
    warps (the gap opens at 256+; see benchmarks/phased_bench.py)."""
    ev, wf = _phased_pair_48("ladder")
    ipc_rel = np.abs(wf["ipc"] - ev["ipc"]) / ev["ipc"]
    mk_rel = np.abs(wf["makespan"] - ev["makespan"]) / ev["makespan"]
    assert ipc_rel.max() <= 0.02, ipc_rel
    assert mk_rel.max() <= 0.025, mk_rel
    # oracle labels bypass the classifier identically in both engines:
    # bypass totals must agree to the envelope too
    oi = [p.name for p in BL.LABELING_LADDER].index("MeDiC-oracle")
    np.testing.assert_allclose(wf["bypasses"][oi], ev["bypasses"][oi],
                               rtol=0.02)


def test_oracle_policy_without_oracle_types_rejected():
    """labeling='oracle' READS the ground-truth labels; omitting them
    must raise (a silent zeros fallback would label every warp all-miss)."""
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM)
    with pytest.raises(ValueError, match="oracle"):
        simulate(*args, pol=BL.MEDIC_ORACLE, **kw)
    with pytest.raises(ValueError, match="oracle"):
        simulate_sweep(*args, (BL.BASELINE, BL.MEDIC_ORACLE), **kw)
    # ...and passing the trace's labels makes the same calls legal
    simulate(*args, pol=BL.MEDIC_ORACLE,
             oracle_types=jnp.asarray(tr["oracle_wtype"]), **kw)


def test_unknown_engine_rejected():
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    with pytest.raises(ValueError, match="unknown engine"):
        simulate(jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
                 jnp.asarray(tr["compute_gap"]), n_warps=spec.n_warps,
                 lanes=spec.lines_per_instr, prm=PRM, pol=BL.MEDIC,
                 engine="warp-drive")


# ---------------------------------------------------------------------------
# batched classifier.observe == N sequential scalar observes
# ---------------------------------------------------------------------------

def _observe_kw(interval=16):
    return dict(sampling_interval=interval, mostly_hit_threshold=0.8,
                mostly_miss_threshold=0.2)


def _states_equal(a: CLF.ClassifierState, b: CLF.ClassifierState):
    for name in a._fields:
        assert np.array_equal(np.asarray(getattr(a, name)),
                              np.asarray(getattr(b, name))), name


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_observe_equals_sequential_scalar(seed):
    """One batched observe over N DISTINCT warps == N scalar observes,
    in any order, including the weight-0 (invalid lane) path."""
    rng = np.random.default_rng(seed)
    n = 24
    batched = seq = CLF.init(n)
    for _ in range(40):                       # ~2.5 windows per warp
        warps = rng.permutation(n)[:rng.integers(1, n + 1)]
        hits = rng.random(warps.size) < 0.6
        weights = (rng.random(warps.size) < 0.8).astype(np.int32)
        batched = CLF.observe(batched, jnp.asarray(warps),
                              jnp.asarray(hits), weight=jnp.asarray(weights),
                              **_observe_kw())
        for w, h, wt in zip(warps, hits, weights):
            seq = CLF.observe(seq, jnp.asarray(w), jnp.asarray(h),
                              weight=jnp.asarray([int(wt)]), **_observe_kw())
        _states_equal(batched, seq)


@pytest.mark.parametrize("seed", [0, 1])
def test_gathered_observe_matches_full_observe(seed):
    """The wavefront's O(B) gather/scatter observe must equal the full
    classifier.observe for distinct warp ids (an untouched warp's window
    can never reset, so restricting the update to touched rows is
    lossless)."""
    from repro.core.engine.wavefront import _observe_gathered
    prm = SimParams(sampling_interval=8)
    rng = np.random.default_rng(seed)
    n = 32
    full = gath = CLF.init(n)
    for _ in range(60):
        warps = rng.permutation(n)[:rng.integers(1, 12)]
        hits = rng.random(warps.size) < 0.5
        weights = (rng.random(warps.size) < 0.9).astype(np.int32)
        full = CLF.observe(full, jnp.asarray(warps), jnp.asarray(hits),
                           sampling_interval=prm.sampling_interval,
                           mostly_hit_threshold=prm.mostly_hit_threshold,
                           mostly_miss_threshold=prm.mostly_miss_threshold,
                           weight=jnp.asarray(weights))
        gath = _observe_gathered(gath, jnp.asarray(warps),
                                 jnp.asarray(hits), jnp.asarray(weights),
                                 jnp.asarray(weights), prm, PA_DEFAULT)
        _states_equal(full, gath)


@pytest.mark.parametrize("policy", [BL.MEDIC_STALE,
                                    BL.with_labeling(BL.MEDIC, "online",
                                                     "MeDiC-w8",
                                                     reclass_interval=8)])
def test_gathered_observe_matches_full_observe_labeling_knobs(policy):
    """The policy-visible window/freeze knobs must behave identically in
    the wavefront's O(B) gathered observe and the full classifier.observe
    the event engine uses — stale's one-window label freeze included."""
    from repro.core.engine.wavefront import _observe_gathered
    from repro.policy import ops as POL
    pa = to_arrays(policy)
    prm = SimParams(sampling_interval=16)
    interval = POL.reclass_interval(pa, prm.sampling_interval)
    max_windows = POL.reclass_max_windows(pa)
    rng = np.random.default_rng(3)
    n = 16
    full = gath = CLF.init(n)
    for step in range(200):
        warps = rng.permutation(n)[:rng.integers(1, 10)]
        # drift the ground truth mid-run so stale vs online labels differ
        p_hit = 0.9 if step < 100 else 0.1
        hits = rng.random(warps.size) < p_hit
        weights = (rng.random(warps.size) < 0.9).astype(np.int32)
        full = CLF.observe(full, jnp.asarray(warps), jnp.asarray(hits),
                           sampling_interval=interval,
                           mostly_hit_threshold=prm.mostly_hit_threshold,
                           mostly_miss_threshold=prm.mostly_miss_threshold,
                           weight=jnp.asarray(weights),
                           max_windows=max_windows)
        gath = _observe_gathered(gath, jnp.asarray(warps),
                                 jnp.asarray(hits), jnp.asarray(weights),
                                 jnp.asarray(weights), prm, pa)
        _states_equal(full, gath)
    if policy.labeling == "stale":
        # the run drove warps through multiple windows, so the freeze
        # path (windows >= max_windows) was actually exercised
        assert np.asarray(gath.windows).max() >= 2


def test_batched_observe_window_resets_fire_identically():
    """Warps straddling the sampling boundary must reset (and re-classify)
    on exactly the same observe call in batched and scalar form."""
    interval = 8
    n = 4
    batched = seq = CLF.init(n)
    # drive warp w with hit-pattern w%2; after `interval` observes each
    # warp's window must have reset exactly once
    for step in range(interval):
        warps = jnp.arange(n)
        hits = jnp.asarray([w % 2 == 0 for w in range(n)])
        batched = CLF.observe(batched, warps, hits,
                              **_observe_kw(interval))
        for w in range(n):
            seq = CLF.observe(seq, jnp.asarray(w), hits[w],
                              **_observe_kw(interval))
        _states_equal(batched, seq)
    assert np.all(np.asarray(batched.accesses) == 0)      # window reset
    assert np.all(np.asarray(batched.ratio)
                  == np.asarray([1.0, 0.0, 1.0, 0.0]))    # re-sampled


# ---------------------------------------------------------------------------
# fused scan backend: bitwise-equal to the unfused engine (ISSUE 6)
# ---------------------------------------------------------------------------

def _run_backends(trace, n_warps, lanes, policies, backends,
                  bkw="scan_backend", **kw0):
    args = (jnp.asarray(trace["lines"]), jnp.asarray(trace["pcs"]),
            jnp.asarray(trace["compute_gap"]))
    kw = dict(n_warps=n_warps, lanes=lanes, prm=PRM, engine="wavefront",
              **kw0)
    if "oracle_wtype" in trace:
        kw["oracle_types"] = jnp.asarray(trace["oracle_wtype"])
    outs = {b: simulate_sweep(*args, policies, **{bkw: b}, **kw)
            for b in backends}
    return {b: {k: np.asarray(v) for k, v in o.items()}
            for b, o in outs.items()}


@pytest.mark.parametrize("workload", WL.WORKLOAD_NAMES)
def test_fused_backend_bitwise_on_workload_matrix(workload):
    """scan_backend="fused" (the auto default on CPU) must equal the
    pre-fusion "ref" path BIT-FOR-BIT on every metric across the full
    15-workload × 4-policy matrix: the fused timing pass only swaps in
    exactly-associative primitives, top_k selection is tie-identical to
    the stable argsort, and the hoisted cache-pass bookkeeping is
    integer accumulation."""
    spec = WL.WORKLOADS[workload]
    tr = WL.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         DIFF_POLICIES, ("ref", "fused"))
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


def test_fused_backend_bitwise_on_phased():
    """Same bitwise claim on a drifting-intensity PHASED trace — the
    non-dyadic compute_gap schedule is what would expose any rounding
    difference between the formulations."""
    spec = TG.PHASED_SPECS["PHASED48"]
    tr = TG.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.BASELINE, BL.MEDIC), ("ref", "fused"))
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


def test_fused_backend_bitwise_wave_of_one():
    """exact=True corner: a wave of one warp uses the plain busy-until
    floor; the fused gathered floor must stay bitwise there too."""
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.MEDIC,), ("ref", "fused"), wave_size=1)
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


def test_pallas_backend_close_at_engine_level():
    """scan_backend="pallas" (interpret-forced on CPU) through the whole
    engine: chunk re-association may round non-dyadic floats, so the
    claim is allclose, not bitwise. Kept tiny — interpret mode runs the
    kernel chunk loop in Python."""
    spec = dataclasses.replace(
        TG.TraceSpec.from_workload(WL.WORKLOADS["BFS"]),
        n_warps=12, n_instr=8)
    tr = TG.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.MEDIC,), ("ref", "pallas"))
    for k in outs["ref"]:
        np.testing.assert_allclose(outs["pallas"][k], outs["ref"][k],
                                   rtol=1e-5, atol=1e-5, err_msg=k)


def test_topk_selection_ties_match_stable_argsort():
    """The fused wave selection: `top_k(-ready)` must break equal-ready
    ties exactly like the stable ascending argsort (lower warp id wins)
    — fuzzed over heavily-tied readiness vectors."""
    rng = np.random.default_rng(0)
    for _ in range(50):
        w = int(rng.integers(2, 200))
        b = int(rng.integers(1, w + 1))
        # few distinct values => many ties
        ready = rng.choice(rng.uniform(0, 10, 3), size=w)
        active = rng.random(w) < 0.8
        r = jnp.asarray(ready, jnp.float32)
        a = jnp.asarray(active)
        ref = np.argsort(np.where(active, ready, np.inf),
                         kind="stable")[:b]
        got = np.asarray(
            jax.lax.top_k(jnp.where(a, -r, -jnp.inf), b)[1])
        assert np.array_equal(ref, got), (w, b, ready, active)


def test_scan_backend_validation():
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM,
              pol=BL.MEDIC)
    with pytest.raises(ValueError, match="scan_backend"):
        simulate(*args, engine="wavefront", scan_backend="vector9", **kw)
    with pytest.raises(ValueError, match="only meaningful"):
        simulate(*args, engine="event", scan_backend="fused", **kw)


# ---------------------------------------------------------------------------
# fused cache backend: bitwise-equal to the per-lane ref pass (ISSUE 8)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", WL.WORKLOAD_NAMES)
def test_cache_fused_bitwise_on_workload_matrix(workload):
    """cache_backend="fused" (the auto default on CPU) must equal the
    per-lane "ref" cache pass BIT-FOR-BIT on every metric across the
    full 15-workload × 4-policy matrix: the one-sweep reformulation
    computes every slot's row from lane-start state (exactly what the
    ref scatters write) and resolves same-set conflicts last-write-wins
    in slot order."""
    spec = WL.WORKLOADS[workload]
    tr = WL.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         DIFF_POLICIES, ("ref", "fused"),
                         bkw="cache_backend")
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


@pytest.mark.parametrize("spec_name", ["PHASED48", "PHASED_RECOVER48"])
def test_cache_fused_bitwise_on_phased(spec_name):
    """Same bitwise claim on the drifting-intensity and recovery-shaped
    phased traces — window resets, relabeling, and EAF generation bumps
    all land mid-run there."""
    specs = {**TG.PHASED_SPECS, **TG.PHASED_RECOVER_SPECS}
    spec = specs[spec_name]
    tr = TG.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.BASELINE, BL.MEDIC), ("ref", "fused"),
                         bkw="cache_backend")
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


def test_cache_fused_bitwise_wave_of_one():
    """A wave of one warp still aliases sets ACROSS LANES of the same
    warp; the fused pass must stay bitwise in that degenerate shape."""
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.MEDIC,), ("ref", "fused"),
                         bkw="cache_backend", wave_size=1)
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["fused"][k],
                              equal_nan=True), k


def test_cache_fused_bitwise_both_backends_fused():
    """Both passes fused at once (the shipping default) must still
    equal the double-ref engine bitwise — the two fusions compose."""
    spec = WL.WORKLOADS["BFS"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM,
              engine="wavefront")
    ref = simulate_sweep(*args, DIFF_POLICIES, scan_backend="ref",
                         cache_backend="ref", **kw)
    fus = simulate_sweep(*args, DIFF_POLICIES, scan_backend="fused",
                         cache_backend="fused", **kw)
    for k in ref:
        assert np.array_equal(np.asarray(ref[k]), np.asarray(fus[k]),
                              equal_nan=True), k


def test_cache_pallas_backend_bitwise_at_engine_level():
    """cache_backend="pallas" (interpret-forced on CPU) through the
    whole engine. The cache pass is integer/select arithmetic — no
    re-associated float reductions — so unlike the timing-pass kernel
    this one is asserted BITWISE. Kept tiny: interpret mode runs the
    lane grid in Python."""
    spec = dataclasses.replace(
        TG.TraceSpec.from_workload(WL.WORKLOADS["BFS"]),
        n_warps=12, n_instr=8)
    tr = TG.generate(spec, seed=0)
    outs = _run_backends(tr, spec.n_warps, spec.lines_per_instr,
                         (BL.MEDIC,), ("ref", "pallas"),
                         bkw="cache_backend")
    for k in outs["ref"]:
        assert np.array_equal(outs["ref"][k], outs["pallas"][k],
                              equal_nan=True), k


def test_cache_backend_validation():
    spec = WL.WORKLOADS["BP"]
    tr = WL.generate(spec, seed=0)
    args = (jnp.asarray(tr["lines"]), jnp.asarray(tr["pcs"]),
            jnp.asarray(tr["compute_gap"]))
    kw = dict(n_warps=spec.n_warps, lanes=spec.lines_per_instr, prm=PRM,
              pol=BL.MEDIC)
    with pytest.raises(ValueError, match="cache_backend"):
        simulate(*args, engine="wavefront", cache_backend="sweep9", **kw)
    with pytest.raises(ValueError, match="only meaningful"):
        simulate(*args, engine="event", cache_backend="fused", **kw)
