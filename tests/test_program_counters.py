"""The program's own instrumentation: the wavefront engine's wave
counter, the host spans of ``Plan.execute``, and the named scopes of the
wave loop's passes (which must change no compiled op)."""
import re
import time

import jax
import numpy as np
import pytest

from repro import api
from repro.core import baselines as BL
from repro.core import engine as ENG
from repro.core import tracegen as TG
from repro.core.engine import SimParams, simulate_sweep
from repro.serving.sim import SERVING_SPECS

N_WARPS, N_INSTR, LANES = 8, 4, 4
SPEC = TG.TraceSpec(name="TINY", mix=(0.1, 0.2, 0.3, 0.2, 0.2),
                    intensity=0.9, n_warps=N_WARPS, n_instr=N_INSTR,
                    lines_per_instr=LANES)

#: the keys of one simulation's statistics, as ``ResultSet.get`` and
#: ``simulate_sweep`` return them on both engines
STAT_KEYS = {
    "bypasses", "dram_accesses", "energy", "evictions_by_type", "ipc",
    "ipc_makespan", "l2_accesses", "l2_hits", "makespan", "mean_qdelay",
    "miss_rate", "perf_per_energy", "qdelay_hist", "qdelay_sum",
    "ratio_over_time", "row_hits", "stall_cycles", "warp_hit_ratio",
    "warp_time", "warp_type"}


def _exp(engine="wavefront", **kw):
    return api.Experiment(f"t:{engine}",
                          (api.Scenario.from_spec(SPEC, seeds=(0, 1)),),
                          (BL.BASELINE, BL.MEDIC), engine=engine, **kw)


@pytest.fixture(scope="module")
def whole_waves():
    """A run whose waves hold every warp; its wall on the host clock."""
    t0 = time.perf_counter()
    rs = _exp(wave_size=N_WARPS).run()
    return rs, time.perf_counter() - t0


def test_a_wave_of_every_warp_runs_one_wave_per_instruction(whole_waves):
    rs, _ = whole_waves
    (counts,) = rs.wave_counts()
    assert counts.waves.shape == (2, 2)
    assert counts.waves.dtype == np.int32
    assert (counts.waves == N_INSTR).all()
    assert counts.wave_size == N_WARPS
    assert counts.warp_instructions == N_INSTR * N_WARPS


def test_a_wave_of_one_warp_runs_one_wave_per_warp_instruction():
    (counts,) = _exp(wave_size=1).run().wave_counts()
    assert (counts.waves == N_INSTR * N_WARPS).all()
    assert counts.wave_size == 1


_METADATA = re.compile(r",? metadata=\{[^}]*\}")
#: the debug tables of a compiled module (source files, functions,
#: locations and stack frames), which metadata refers to by id
_DEBUG_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|"
                          r"StackFrames)$|^\d+ ")


def _ops(text: str) -> list:
    """A compiled module's computations and ops, metadata stripped."""
    return [_METADATA.sub("", line) for line in text.splitlines()
            if not _DEBUG_TABLE.match(line)]
_WAVE3 = dict(n_warps=N_WARPS, lanes=LANES, prm=SimParams(),
              engine="wavefront", wave_size=3)


def _stacked_args():
    """Three traces stacked on the seed axis; in the second and third,
    many warp-instructions have no request, so those warps run ahead of
    the others and the three simulations end at different waves."""
    from repro.policy import stack_policies
    tr = TG.generate(SPEC, seed=0)
    rng = np.random.default_rng(0)
    lines = np.stack([tr["lines"]] * 3)
    for s, share in ((1, 0.6), (2, 0.9)):
        lines[s][rng.random((N_INSTR, N_WARPS)) < share] = -1
    return (lines, np.stack([tr["pcs"]] * 3),
            np.full((3,), tr["compute_gap"], np.float32),
            np.zeros((3, N_INSTR, N_WARPS), np.int32),
            stack_policies((BL.MEDIC,)))


def _compile(args):
    """The vmapped sweep, traced anew (a fresh function each time) and
    compiled."""
    batch = ENG._simulate_batch.__wrapped__
    return jax.jit(lambda *a: batch(*a, **_WAVE3)).lower(*args).compile()


@pytest.fixture(scope="module")
def scoped():
    args = _stacked_args()
    return args, _compile(args)


def test_each_entry_of_a_vmapped_sweep_keeps_its_own_count(scoped):
    """The entry whose warps all have requests ends at ceil(I*W/B)
    waves; the others, whose warps drift apart, at more."""
    args, compiled = scoped
    _, waves = compiled(*args)
    waves = np.asarray(waves)
    assert waves.shape == (1, 3)
    assert waves[0, 0] == -(-N_INSTR * N_WARPS // 3)
    assert len(set(waves[0].tolist())) > 1
    assert (waves <= -(-N_INSTR * N_WARPS // 3) + N_INSTR).all()


def test_the_pass_scopes_change_no_compiled_op(scoped, monkeypatch):
    """The wavefront sweep compiles to the same program, op for op, with
    ``jax.named_scope`` a no-op: the scopes reach only the metadata, and
    both programs give the same statistics and counts, bit for bit."""
    import contextlib
    args, compiled = scoped
    text = compiled.as_text()
    for name in ("wave_select", "cache_pass", "timing_pass", "wave_commit"):
        assert re.search(rf'op_name="[^"]*/{name}/', text)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _compile(args)
    assert not re.search(r'op_name="[^"]*/cache_pass/', plain.as_text())
    ops, plain_ops = _ops(text), _ops(plain.as_text())
    assert len(ops) > 100
    assert ops == plain_ops
    got, want = compiled(*args), plain(*args)
    jax.tree.map(np.testing.assert_array_equal, got, want)


def test_event_and_serving_calls_count_no_waves():
    rs = _exp(engine="event").run()
    assert rs.wave_counts() == () and rs._blocks[0].waves is None
    assert set(rs._blocks[0].spans) == {"repro.tracegen", "repro.call"}
    assert set(rs.get(seed=0)) == STAT_KEYS
    spec = SERVING_SPECS["SERVE_POISSON64"]
    spec = type(spec)(**{**spec.__dict__, "n_requests": 32,
                         "max_steps": 400})
    serve = api.Experiment("t:serve", (api.Scenario.serving(spec),),
                           (BL.MEDIC,), engine="serving").run()
    assert serve.wave_counts() == ()
    assert serve._blocks[0].wall_s == serve._blocks[0].spans["repro.call"]


def test_the_statistics_keep_their_keys(whole_waves):
    rs, _ = whole_waves
    assert set(rs.get(seed=0)) == STAT_KEYS
    assert set(rs.get(seed=1, policy="MeDiC")) == STAT_KEYS


def test_every_call_records_its_spans(whole_waves):
    """The call records trace generation and the call itself; together
    they fit in the run's own wall, and the call's span is the interval
    ``wall_s`` times."""
    rs, wall = whole_waves
    for blk in rs._blocks:
        assert set(blk.spans) == {"repro.tracegen", "repro.call"}
        assert all(v > 0 for v in blk.spans.values())
        assert blk.wall_s == blk.spans["repro.call"]
    assert rs.span_s("repro.call") == rs.wall_s
    assert rs.span_s("repro.tracegen") + rs.span_s("repro.call") <= wall
    assert rs.span_s("absent") == 0.0
