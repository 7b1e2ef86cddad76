"""One sweep of a cell: its seeds, its ``Experiment`` and its work.

A sweep is one call of the program's public entry,
``repro.api.Experiment(...).run()``: every trace spec of the cell's
configuration, under the traffic's policies and engine, with default
backends. The configuration's file is what both the program and the
reference run. Every sweep of a run gets fresh trace seeds, derived from
the run's ``--seed`` and the sweep's index.
"""
from __future__ import annotations

from typing import Dict, List

from bench.lib.ref_traces import GAMMA, mix64_int

SEED_SPACE = 1 << 31


def sweep_seeds(seed: int, index: int, n: int) -> List[int]:
    """``n`` distinct trace seeds of sweep ``index`` of a run with
    ``--seed seed`` (any non-negative integer)."""
    out: List[int] = []
    j = 0
    while len(out) < n:
        s = mix64_int(mix64_int(int(seed) * GAMMA + index) + j) % SEED_SPACE
        if s not in out:
            out.append(s)
        j += 1
    return out


def specs(config: dict) -> List[dict]:
    """Every trace spec of a configuration, in file order, as plain
    data."""
    return [{**config["trace_defaults"], **s, "name": name}
            for name, s in config["specs"].items()]


def requests_per_sweep(config: dict, traffic: dict) -> int:
    """Simulated memory requests of one sweep: one valid lane of one
    (policy, trace) is one request, and every lane the generator emits
    is valid (the harness checks that in set-up)."""
    per_trace = sum(s["n_instr"] * s["n_warps"] * s["lines_per_instr"]
                    for s in specs(config))
    return (len(traffic["policies"]) * traffic["seeds_per_sweep"]
            * per_trace)


def build(config: dict, traffic: dict, seeds: List[int], label: str):
    """The program's ``Experiment`` for one sweep."""
    from repro.api.experiment import Experiment
    from repro.api.scenario import Scenario
    from repro.core.engine import SimParams
    from repro.core.tracegen import TraceSpec
    from repro.policy import Policy

    scenarios = []
    for s in specs(config):
        s["mix"] = tuple(s["mix"])
        s["archetypes"] = tuple(tuple(a) for a in s["archetypes"])
        scenarios.append(Scenario.from_spec(TraceSpec(**s),
                                            seeds=tuple(seeds)))
    policies = tuple(Policy(**p) for p in traffic["policies"])
    return Experiment(label, tuple(scenarios), policies,
                      engine=traffic["engine"],
                      prm=SimParams(**config["sim"]))


def entries(config: dict, traffic: dict, seeds: List[int]) -> List[Dict]:
    """Every (scenario, seed, policy) of a sweep, in a fixed order."""
    return [{"scenario": s["name"], "seed": sd, "policy": p}
            for s in specs(config) for sd in seeds
            for p in traffic["policies"]]
