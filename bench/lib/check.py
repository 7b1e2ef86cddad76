"""Decide ``correct``: the timed sweeps' results against the plain
reference (``ref_traces`` + ``ref_sim``), which imports nothing of the
program and takes nothing it made.

One sweep of the window and up to ``MAX_TRACES`` of its traces, drawn
from the run's seed, are checked under every policy, every metric. The
reference regenerates each trace from the configuration's spec and the
sweep's seed and simulates it on the host, in less time than the
window.

Two numbers are compared, each against its limit:

* ``int_mismatch``: integer entries (counters, histograms, warp types)
  that differ. The model's counters are exact, so the limit is 0.
* ``float_rel_dev``: the widest relative gap ``|a - b| / max(|a|, |b|)``
  of any float entry (clocks, rates, ratios). The chip's float32
  division and the order of its float sums differ from the host's in
  the last bits (sound runs read under 3e-7); the control, the
  reference with simulated time in bfloat16, reads 1.0. The limit sits
  between, far above the sound readings (PERF.md).
"""
from __future__ import annotations

import random
from typing import Dict, List, Sequence

import numpy as np

from bench.lib import ref_sim, ref_traces, sweep

LIMITS = {"int_mismatch": 0, "float_rel_dev": 1e-5}
MAX_TRACES = 5


def compare(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray]):
    """(integer entries that differ, widest relative float gap) of one
    simulation's metrics against the reference's."""
    bad, dev = 0, 0.0
    for k in ref_sim.INT_METRICS:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        bad += int(a.size) if a.shape != b.shape else int((a != b).sum())
    for k in ref_sim.FLOAT_METRICS:
        a = np.asarray(got[k], np.float64)
        b = np.asarray(want[k], np.float64)
        if a.shape != b.shape or not np.isfinite(a).all():
            return bad, float("inf")
        scale = np.maximum(np.abs(a), np.abs(b))
        gap = np.abs(a - b) / np.where(scale > 0, scale, 1.0)
        dev = max(dev, float(gap.max()) if gap.size else 0.0)
    return bad, dev


def sample(config: dict, traffic: dict, seeds: Sequence[int],
           seed: int) -> List[dict]:
    """The entries of a sweep to check: every policy on up to
    ``MAX_TRACES`` of its (scenario, seed) traces, drawn from ``seed``."""
    traces = [(s["name"], sd) for s in sweep.specs(config) for sd in seeds]
    picked = set(random.Random(int(seed) + 1).sample(
        traces, min(MAX_TRACES, len(traces))))
    return [e for e in sweep.entries(config, traffic, list(seeds))
            if (e["scenario"], e["seed"]) in picked]


def reference(config: dict, traffic: dict, ents: Sequence[dict],
              clock=np.float32) -> List[Dict[str, np.ndarray]]:
    """Reference metrics of ``ents`` (entries of ``sweep.entries``),
    with simulated time kept in ``clock``."""
    specs = {s["name"]: s for s in sweep.specs(config)}
    traces = {(e["scenario"], e["seed"]): None for e in ents}
    for s, sd in traces:
        traces[s, sd] = ref_traces.generate(specs[s], sd)
    return ref_sim.simulate(
        [traces[e["scenario"], e["seed"]] for e in ents],
        [e["policy"] for e in ents], config["sim"],
        engine=traffic["engine"], clock=clock)


def program_metrics(rs, ents: Sequence[dict]):
    """The program's metrics of ``ents``, same order, copied to host."""
    return [{k: np.array(v) for k, v in
             rs.get(scenario=e["scenario"], seed=e["seed"],
                    policy=e["policy"]["name"]).items()} for e in ents]


def readings(got: Sequence[dict], want: Sequence[dict]) -> Dict[str, float]:
    bad, dev = 0, 0.0
    for g, w in zip(got, want, strict=True):
        b, d = compare(g, w)
        bad, dev = bad + b, max(dev, d)
    return {"int_mismatch": bad, "float_rel_dev": dev}


def pick_sweep(seed: int, n_sweeps: int) -> int:
    """The window sweep to check, drawn from the run's seed."""
    return random.Random(int(seed)).randrange(n_sweeps)


def verdict(numbers: Dict[str, float], limits=LIMITS) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)
