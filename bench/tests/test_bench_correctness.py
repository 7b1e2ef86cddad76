"""The comparison that decides ``correct``, on the CPU at small sizes:
the reference agrees with the program, the control (the reference with
simulated time in bfloat16) is refused, and a run whose timed path is
broken underneath comes out not correct."""
from __future__ import annotations

import dataclasses
import json

import jax
import ml_dtypes
import numpy as np
import pytest

from bench import run
from bench.lib import check, ref_sim, ref_traces, sweep
from bench.tests.cell_fixture import POLICIES, make_cell


def _spec(name, n_warps, n_instr=8, **kw):
    from repro.core import tracegen as TG
    from repro.core import workloads as WL
    s = TG.TraceSpec.from_workload(WL.WORKLOADS[name]) \
        if name in WL.WORKLOADS else TG.STRESS_SPECS[name]
    return dataclasses.replace(s, n_warps=n_warps, n_instr=n_instr, **kw)


def _as_dict(spec):
    from repro.core import tracegen as TG
    d = {f.name: getattr(spec, f.name) for f in dataclasses.fields(spec)}
    d["archetypes"] = [list(a) for a in TG.ARCHETYPES.values()]
    return d


@pytest.mark.parametrize("name,n_warps,shift", [
    ("BFS", 48, False), ("SRAD", 48, True), ("HAMMER2K", 300, False)])
def test_reference_traces_equal_the_generators(name, n_warps, shift):
    from repro.core import tracegen as TG
    spec = _spec(name, n_warps, n_instr=16, phase_shift=shift)
    for seed in (0, 2 ** 31 + 3):
        got = TG.generate(spec, seed)
        want = ref_traces.generate(_as_dict(spec), seed)
        for k in ("lines", "pcs", "oracle_wtype"):
            np.testing.assert_array_equal(got[k], want[k])
        assert np.float32(got["compute_gap"]) == want["compute_gap"]


def _program_vs_reference(engine, spec, prm, pols, seed):
    """Integer counters bit for bit; floats to the last bits."""
    from repro.core import tracegen as TG
    from repro.core.engine import SimParams, simulate_sweep

    tr = TG.generate(spec, seed)
    out = simulate_sweep(tr["lines"], tr["pcs"], tr["compute_gap"], pols,
                         n_warps=spec.n_warps, lanes=16,
                         prm=SimParams(**prm), engine=engine,
                         oracle_types=tr["oracle_wtype"])
    out = {k: np.asarray(v) for k, v in out.items()}
    rt = ref_traces.generate(_as_dict(spec), seed)
    want = ref_sim.simulate([rt] * len(pols),
                            [dataclasses.asdict(p) for p in pols],
                            prm, engine=engine)
    got = [{k: v[p] for k, v in out.items()} for p in range(len(pols))]
    numbers = check.readings(got, want)
    assert numbers["int_mismatch"] == 0
    assert numbers["float_rel_dev"] < 1e-6


@pytest.mark.parametrize("engine,name,n_warps", [
    ("event", "BFS", 16), ("event", "SRAD", 12),
    ("wavefront", "HAMMER2K", 64), ("wavefront", "CONS", 24)])
def test_reference_equals_the_program(engine, name, n_warps):
    from repro.api import registry
    from repro.core.engine import SimParams

    _program_vs_reference(
        engine, _spec(name, n_warps, phase_shift=name == "SRAD"),
        dataclasses.asdict(SimParams()), registry.FIG7_SWEEP_POLICIES, 11)


@pytest.mark.parametrize("engine,name,n_warps", [
    ("event", "BFS", 16), ("wavefront", "BFS", 96),
    ("wavefront", "HAMMER2K", 300)])
def test_reference_equals_the_program_on_the_configured_gpu(
        engine, name, n_warps):
    """On the simulated GPU the cell states (MeDiC's Table 1: 384 sets x
    16 ways, 12 banks, 6 channels), under the cell's four policies."""
    from repro.api import registry

    prm = json.loads(open(run.ROOT / "bench" / "configs"
                          / "medic_bfs_64k.json").read())["sim"]
    _program_vs_reference(engine, _spec(name, n_warps), prm,
                          registry.STRESS_POLICIES, 23)


@pytest.mark.parametrize("engine,n_warps", [("event", 16),
                                            ("wavefront", 64)])
def test_control_in_bfloat16_is_refused(engine, n_warps):
    """The control: the reference with every simulated time in bfloat16,
    the precision below the float32 the configuration states."""
    spec = _as_dict(_spec("HAMMER2K", n_warps))
    rt = ref_traces.generate(spec, 5)
    prm = json.loads(open(run.ROOT / "bench" / "configs"
                          / "medic_bfs_64k.json").read())["sim"]
    f32 = ref_sim.simulate([rt, rt], POLICIES, prm, engine=engine)
    low = ref_sim.simulate([rt, rt], POLICIES, prm, engine=engine,
                           clock=ml_dtypes.bfloat16)
    numbers = check.readings(low, f32)
    assert not check.verdict(numbers)
    assert numbers["float_rel_dev"] > 10 * check.LIMITS["float_rel_dev"]


def _drive(tmp_path, capsys, engine="event"):
    bench = make_cell(tmp_path, engine=engine)
    jax.clear_caches()
    try:
        run.main(["--workload", "tiny", "--seed", "123", "--seconds", "0.2",
                  "--trace", "0"], root=tmp_path, bench_dir=bench,
                 devices=jax.devices())
    finally:
        jax.clear_caches()
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_sound_run_is_correct(tmp_path, capsys):
    assert _drive(tmp_path, capsys)["correct"] is True


def test_step_that_leaves_its_state_unchanged(tmp_path, capsys,
                                              monkeypatch):
    from repro.core.engine import event

    real = event._request_step

    def frozen(st, req, prm, pa, tokens):
        _, t_done = real(st, req, prm, pa, tokens)
        return st, t_done
    monkeypatch.setattr(event, "_request_step", frozen)
    out = _drive(tmp_path, capsys)
    assert out["correct"] is False and out["failed"] == 1


def _patch_sweep(monkeypatch, alter):
    from repro.api import experiment
    real = experiment.simulate_sweep

    def patched(*args, **kw):
        return alter({k: np.array(v) for k, v in real(*args, **kw).items()})
    monkeypatch.setattr(experiment, "simulate_sweep", patched)


def test_half_of_the_batch_left_out(tmp_path, capsys, monkeypatch):
    """The second half of the stacked traces is not simulated: its
    results are the mean of the first half's."""
    def half(out):
        f = out["l2_hits"].shape[1]
        for k, v in out.items():
            mean = v[:, :f // 2].mean(axis=1, keepdims=True)
            v[:, f // 2:] = mean.astype(v.dtype)
        return out
    _patch_sweep(monkeypatch, half)
    assert _drive(tmp_path, capsys)["correct"] is False


def test_an_answer_altered_where_it_is_produced(tmp_path, capsys,
                                                monkeypatch):
    def bump(out):
        out["l2_hits"][-1, -1] += 1
        return out
    _patch_sweep(monkeypatch, bump)
    assert _drive(tmp_path, capsys)["correct"] is False


def test_wavefront_fault_is_caught(tmp_path, capsys, monkeypatch):
    """A wavefront sweep whose last simulation's clocks run 0.1% fast."""
    def fast(out):
        out["makespan"][-1, -1] *= np.float32(0.999)
        return out
    _patch_sweep(monkeypatch, fast)
    assert _drive(tmp_path, capsys, engine="wavefront")["correct"] is False
