"""Quickstart: the MeDiC policy core in 60 seconds.

Runs one memory-intensive workload through the altitude-A simulator under
the baseline and full-MeDiC policies via the declarative experiment API —
a `Scenario` names what to simulate, an `Experiment` crosses it with
policies, and the plan compiler lowers the whole thing to a single
vmapped, jitted `simulate_sweep` call — then prints the headline effects
the paper predicts straight off the labeled `ResultSet`: bypass volume,
queue-delay relief, warp-type conversion, and speedup.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro import api
from repro.core import baselines as BL
from repro.core import warp_types as WT


def main():
    exp = api.Experiment("quickstart",
                         scenarios=(api.Scenario.workload("BFS"),),
                         policies=(BL.BASELINE, BL.MEDIC))
    print(exp.compile().describe())
    rs = exp.run()

    spec = exp.scenarios[0].trace_spec
    print(f"\nworkload: {spec.name} ({spec.n_warps} warps, "
          f"{spec.n_instr} memory instructions each)")

    # the per-policy table, by label — no positional v[0]/v[1] slicing
    for row in rs.to_rows(metrics=("ipc", "miss_rate", "mean_qdelay",
                                   "bypasses")):
        types = np.bincount(
            np.asarray(rs.get(policy=row["policy"])["warp_type"]),
            minlength=WT.NUM_TYPES)
        print(f"\n[{row['policy']}]")
        print(f"  IPC proxy          : {row['ipc']:.4f}")
        print(f"  L2 miss rate       : {row['miss_rate']:.3f}")
        print(f"  mean L2 queue delay: {row['mean_qdelay']:.1f} cyc")
        print(f"  bypassed requests  : {int(row['bypasses'])}")
        print("  warp types         : " + ", ".join(
            f"{n}={c}" for n, c in zip(WT.TYPE_NAMES, types)))

    speedup = rs.speedup_over("Baseline")["BFS"]["MeDiC"]
    print(f"\nMeDiC speedup: {speedup:.3f}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
