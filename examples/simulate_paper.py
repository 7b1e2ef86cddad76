"""Reproduce the paper's headline table (Fig 7) over all 15 workloads.

    PYTHONPATH=src python examples/simulate_paper.py [--quick] [--seeds N]
                                                     [--engine ENGINE]
                                                     [--stress]

``--seeds N`` averages each speedup over N trace seeds; the seeds ride
the policy sweep in one jitted call per workload (the vectorized
tracegen path stacks them via ``generate_batch``).

``--engine wavefront`` runs the Fig 7 sweep on the batched wavefront
engine (same orderings within the documented tolerance, DESIGN.md §9).

``--stress`` runs the STRESS_SPECS scheduler-stress matrix (1k–4k warps)
on the wavefront engine — the only path that can — and prints the
per-scenario policy rankings.

Everything routes through the declarative ``repro.api`` layer
(DESIGN.md §10): the fig7 table via ``benchmarks.paper_figures`` (one
single-scenario ``Experiment`` per workload, golden-pinned), the stress
matrix via ``benchmarks.engine_bench.run_stress_matrix`` (one
``Experiment`` whose plan compiles to one jitted call per trace shape).
"""
import argparse
import os
import sys

# make `benchmarks` importable when run as a script from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_stress():
    import numpy as np

    from benchmarks.engine_bench import STRESS_POLICIES, run_stress_matrix
    from repro.core import tracegen as TG

    print("stress matrix (wavefront engine, "
          f"policies: {', '.join(p.name for p in STRESS_POLICIES)})")
    results, walls, group_walls = run_stress_matrix()
    names = [p.name for p in STRESS_POLICIES]
    for name, spec in TG.STRESS_SPECS.items():
        ipc = np.asarray(results[name]["ipc"], dtype=float)
        order = np.argsort(-ipc)
        ranking = " > ".join(f"{names[i]}({ipc[i]:.3f})" for i in order)
        print(f"  {name:10s} [{spec.n_warps:4d} warps, "
              f"group wall {walls[name]:6.1f}s]  {ranking}")
    print(f"total wall: {sum(group_walls):.1f}s "
          f"({len(group_walls)} jitted sweep calls, one per trace-shape "
          "bucket of the compiled plan)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")

    def positive_int(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError("need at least 1 seed")
        return n

    ap.add_argument("--seeds", type=positive_int, default=1, metavar="N",
                    help="trace seeds per workload (default 1)")
    ap.add_argument("--engine", choices=("event", "wavefront"),
                    default="event",
                    help="simulation engine (default: exact event loop)")
    ap.add_argument("--stress", action="store_true",
                    help="run the 1k-4k-warp stress matrix instead of "
                         "the paper table (implies the wavefront engine)")
    args = ap.parse_args()

    if args.stress:
        run_stress()
        return

    from benchmarks.paper_figures import fig7_performance
    from repro.core.workloads import WORKLOAD_NAMES

    wls = ("BFS", "SSSP", "BP", "CONS") if args.quick else WORKLOAD_NAMES
    rows, derived = fig7_performance(wls, seeds=tuple(range(args.seeds)),
                                     engine=args.engine)

    policies = []
    for r in rows:
        if r["policy"] not in policies:
            policies.append(r["policy"])
    print(f"engine: {args.engine}")
    print(f"{'workload':10s}" + "".join(f"{p:>12s}" for p in policies))
    for wl in wls:
        vals = {r["policy"]: r["speedup"] for r in rows
                if r["workload"] == wl}
        print(f"{wl:10s}" + "".join(f"{vals[p]:>12.3f}" for p in policies))
    print("\nharmonic-mean speedups (paper: WByp 1.336, MeDiC 1.415, "
          "MeDiC vs best prior 1.218):")
    for k, v in derived.items():
        print(f"  {k}: {v}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
