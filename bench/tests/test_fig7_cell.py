"""The ``fig7_wave`` cell: the paper's own evaluation, found by name, and
on the CPU equal to the plain reference through the harness's own path
on the cell's GPU, under all 11 Fig 7 policies."""
from __future__ import annotations

import copy
import dataclasses

import numpy as np
import pytest

from bench.lib import cells, check, sweep


def test_fig7_wave_loads_by_name():
    from repro.api import registry
    from repro.core import tracegen as TG
    from repro.core import workloads as WL
    from repro.core.engine import SimParams

    cell = cells.find_cell("fig7_wave")
    assert cell.chips == 1
    assert sweep.requests_per_sweep(cell.config, cell.traffic) == 8_110_080
    tr = cell.traffic
    assert tr["engine"] == "wavefront" and tr["seeds_per_sweep"] == 1
    assert tr["policies"] == [dataclasses.asdict(p)
                              for p in registry.FIG7_SWEEP_POLICIES]
    # the 15 applications, each as the registry's paper suite runs it
    specs = sweep.specs(cell.config)
    assert [s["name"] for s in specs] == list(WL.WORKLOAD_NAMES)
    for s in specs:
        want = TG.TraceSpec.from_workload(WL.WORKLOADS[s["name"]])
        for f in dataclasses.fields(want):
            if f.name in ("archetypes", "phases"):
                continue
            got = s[f.name]
            assert (tuple(got) if f.name == "mix" else got) == \
                getattr(want, f.name), (s["name"], f.name)
        np.testing.assert_array_equal(
            np.asarray(s["archetypes"], np.float64), want.archetype_table())
    # the simulated GPU is Table 1, key for key as bfs_2k's
    assert cell.config["sim"] == cells.find_cell("bfs_2k").config["sim"]
    SimParams(**cell.config["sim"])


@pytest.mark.parametrize("app", ["SRAD", "CONS"])
def test_fig7_wave_equals_the_reference(app):
    """One application at 48 warps (so the wave stays 8 and the narrow
    fused cache pass runs), every Fig 7 policy; only ``n_instr`` is cut
    for test time. SRAD carries the mid-kernel phase flip."""
    cell = cells.find_cell("fig7_wave")
    config = copy.deepcopy(cell.config)
    config["specs"] = {app: config["specs"][app]}
    config["trace_defaults"]["n_instr"] = 16
    seeds = sweep.sweep_seeds(3_100_001_501, 1, 1)
    rs = sweep.build(config, cell.traffic, seeds, "fig7_test").run()
    (counts,) = rs.wave_counts()
    assert counts.wave_size == 8
    ents = sweep.entries(config, cell.traffic, seeds)
    assert len(ents) == 11
    numbers = check.readings(check.program_metrics(rs, ents),
                             check.reference(config, cell.traffic, ents))
    assert numbers["int_mismatch"] == 0
    assert numbers["float_rel_dev"] < 1e-6
