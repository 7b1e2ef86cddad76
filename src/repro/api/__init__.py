"""Declarative experiment API (ISSUE 4 tentpole) — DESIGN.md §10.

One front door for every sweep:

    Scenario   what to simulate   (named, hashable; lowers via tracegen)
    Experiment scenarios × policies × engine; ``compile()`` -> Plan
    Plan       the minimal set of jitted ``simulate_sweep`` calls
               (one per (trace-shape, engine) bucket, policies vmapped,
               scenarios/seeds stacked on the flat axis)
    ResultSet  labeled results: ``.sel()``, ``.speedup_over()``,
               ``.to_rows()`` / ``.to_json()`` instead of positional
               ``v[0]``/``v[1]`` indexing
    registry   the paper suites as data: ``registry.PAPER_FIG7``,
               ``registry.STRESS``

``simulate`` / ``simulate_sweep`` stay available as the thin imperative
facades underneath; the golden fig7 suite pins that this layer is a
byte-identical re-expression of them.
"""
from repro.api import registry
from repro.api.experiment import Experiment, Plan, PlanCall, run
from repro.api.results import ResultBlock, ResultSet, WaveCounts
from repro.api.scenario import Scenario

__all__ = [
    "Experiment", "Plan", "PlanCall", "ResultBlock", "ResultSet",
    "Scenario", "WaveCounts", "registry", "run",
]
