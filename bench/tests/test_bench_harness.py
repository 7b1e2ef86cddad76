"""The harness on the CPU: cells, configurations and readers found by
name, the work counted from shapes, the trace reduction, and the run's
last line."""
from __future__ import annotations

import dataclasses
import gzip
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import run
from bench.lib import cells, peaks, sweep, trace
from bench.tests.cell_fixture import make_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
DATA = Path(__file__).resolve().parent / "data"


def test_every_cell_and_metric_is_found_by_name():
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        cell = cells.find_cell(w["name"])
        assert sweep.specs(cell.config)
        readers = cells.metric_readers(cell)
        assert set(readers) == {m["name"] for m in bench["per_layer"]}
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_a_cell_added_in_a_directory_of_its_own(tmp_path):
    bench = make_cell(tmp_path)
    cell = cells.find_cell("tiny", tmp_path, bench)
    assert cell.config["name"] == "tiny" and cell.chips == 1
    readers = cells.metric_readers(cell, bench)
    runs = {"sweeps": [{"wall_s": 2.0, "call_s": 1.5},
                       {"wall_s": 3.0, "call_s": 2.5}], "trace": None}
    assert readers["host_s"](runs) == pytest.approx(0.5)
    assert readers["call_s"](runs) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        cells.find_cell("absent", tmp_path, bench)


def _files(config, traffic):
    """A configuration and a traffic mix read from their files, whether
    or not a cell of BENCHMARK.json names them."""
    return (json.loads((BENCH / "configs" / f"{config}.json").read_text()),
            json.loads((BENCH / "traffic" / f"{traffic}.json").read_text()))


PAIRS = [("medic_bfs_64k", "stress4_wave")]


@pytest.mark.parametrize("pair,requests", zip(PAIRS, [4 * 64 * 2048 * 16]))
def test_requests_per_sweep_from_shapes(pair, requests):
    assert sweep.requests_per_sweep(*_files(*pair)) == requests


def test_configurations_are_the_programs_own():
    """The configurations state every parameter the program takes, the
    program's archetype table and BFS row, and its stress policies."""
    from repro.api import registry
    from repro.core import tracegen as TG
    from repro.core import workloads as WL
    from repro.core.engine import SimParams

    bfs = WL.WORKLOADS["BFS"]
    for pair in PAIRS:
        config, traffic = _files(*pair)
        assert set(config["sim"]) == set(dataclasses.asdict(SimParams()))
        SimParams(**config["sim"])
        for s in sweep.specs(config):
            assert set(s) <= {f.name for f in dataclasses.fields(
                TG.TraceSpec)}
            assert s["archetypes"] == [list(a)
                                       for a in TG.ARCHETYPES.values()]
            assert (tuple(s["mix"]), s["intensity"]) == (bfs.mix,
                                                        bfs.intensity)
    assert _files(*PAIRS[0])[1]["policies"] == [
        dataclasses.asdict(p) for p in registry.STRESS_POLICIES]


def test_sweep_seeds_are_fresh_and_fixed_by_the_seed():
    big = 2 ** 31 + 12345
    a = [sweep.sweep_seeds(big, k, 2) for k in range(6)]
    assert a == [sweep.sweep_seeds(big, k, 2) for k in range(6)]
    flat = [s for pair in a for s in pair]
    assert len(set(flat)) == len(flat)
    assert all(0 <= s < sweep.SEED_SPACE for s in flat)


def _naive_busy(planes, lo, hi):
    """Busy nanoseconds of the first device, one interval at a time."""
    dev = next(p for p in planes if p["name"].startswith("/device:"))
    ivs = sorted((max(s, lo), min(s + d, hi)) for l in dev["lines"]
                 if l["name"] in trace.OP_LINES for _, s, d in l["events"])
    busy, reach = 0, lo
    for s, e in ivs:
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    return busy


def test_trace_reduction_on_a_recorded_trace():
    """A trace recorded on a TPU v5e (``data/record_trace.py``): two
    sweeps of a tiny wavefront experiment."""
    with gzip.open(DATA / "small_trace.json.gz", "rt") as f:
        planes = json.load(f)
    out = trace.reduce_planes(planes, ["sweep 1", "sweep 2"])
    want = json.loads((DATA / "small_trace_reduced.json").read_text())
    host = [e for p in planes if p["name"].startswith("/host:")
            for l in p["lines"] for e in l["events"]
            if e[0] in ("sweep 1", "sweep 2")]
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    assert out["devices"] == 1 and not out["dropped"]
    assert out["sweeps"] == pytest.approx(1.0)
    assert out["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert out["busy_s"] == pytest.approx(_naive_busy(planes, lo, hi) * 1e-9)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["breakdown"] == want["breakdown"]
    assert 0 < len(out["breakdown"]["device_ops"]) <= trace.TOP
    assert 0 < len(out["breakdown"]["idle_gaps"]) <= trace.TOP
    idle = out["window_s"] - out["busy_s"]
    assert sum(t for _, t in out["breakdown"]["idle_gaps"]) <= idle * 1.0001


def test_busy_union_and_gaps():
    merged = trace.union(np.asarray([5, 0, 1, 8]), np.asarray([8, 2, 3, 9]))
    assert merged.tolist() == [[0, 3], [5, 9]]
    assert trace.gaps([(0, 3), (5, 9)], -1, 12) == [(-1, 0), (3, 5),
                                                    (9, 12)]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ("sweep 1", 0, 100), ("generate", 10, 30)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [("fusion", 50, 20),
                                           ("while", 60, 30)]},
            {"name": "XLA Modules", "events": [("jit_x", 0, 100)]}]},
    ]
    out = trace.reduce_planes(planes, ["sweep 1"])
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    gaps = dict((n, t) for n, t in out["breakdown"]["idle_gaps"])
    assert gaps["sweep 1 / generate"] == pytest.approx(50e-9)
    assert gaps["sweep 1 / no host event"] == pytest.approx(10e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops == pytest.approx({"while": 30e-9, "fusion": 20e-9})
    no_device = trace.reduce_planes(planes[:1], ["sweep 1"])
    assert no_device["busy_s"] == 0.0
    # the device's buffers ran over at 80: the window ends there
    planes[1]["lines"].append({"name": "XLA TraceMe", "events": [
        (trace.DROPPED, 80, 500)]})
    cut = trace.reduce_planes(planes, ["sweep 1"])
    assert cut["dropped"] and cut["window_s"] == pytest.approx(80e-9)
    assert cut["busy_s"] == pytest.approx(30e-9)
    assert cut["sweeps"] == pytest.approx(0.8)


def test_last_line_of_a_run_on_the_cpu(tmp_path, capsys):
    """The whole run, its look for chips skipped: the last line carries
    the keys the contract names, the checks last."""
    bench = make_cell(tmp_path)
    run.main(["--workload", "tiny", "--seed", str(2 ** 31 + 7),
              "--seconds", "0.5", "--trace", "0"],
             root=tmp_path, bench_dir=bench, devices=jax.devices())
    out = capsys.readouterr()
    last = json.loads(out.out.strip().splitlines()[-1])
    assert list(last) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"sim_req_per_s", "setup_s"}
    assert last["metrics"]["sim_req_per_s"]["value"] > 0
    assert last["device"]["platform"] == "cpu"
    assert set(last["checks"]) == {"int_mismatch", "float_rel_dev"}
    assert out.err.strip().splitlines()[-1].startswith("check ")


def test_no_result_without_a_tpu():
    """Off a TPU the run exits non-zero and prints no result, so no
    device metric is ever printed from the CPU."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "bfs_2k", "--seed", "1", "--seconds", "1", "--trace", "1"],
        capture_output=True, text=True, env=env, timeout=120, cwd=ROOT)
    assert p.returncode == 2
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_peaks_are_known_only_for_the_chips_in_the_table():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
