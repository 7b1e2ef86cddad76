"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows per benchmark plus the derived
headline numbers (harmonic-mean speedups etc.). Run:

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only SUBSTR]
                                            [--json PATH]

``--json PATH`` additionally dumps a machine-readable record (one entry
per benchmark: wall time, rows, derived headline numbers) in the
``BENCH_*.json`` trajectory format, so perf can be tracked across PRs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _emit(name, rows, derived):
    print(f"\n## {name}")
    if rows:
        # union of keys, first-seen order — sections may mix row shapes
        # (e.g. engine_fused's scan_backend vs cache_backend A/B rows)
        keys = list(dict.fromkeys(k for r in rows for k in r))
        print(",".join(keys))
        for r in rows:
            print(",".join(str(r.get(k, "-")) for k in keys))
    for k, v in derived.items():
        print(f"derived,{name}.{k},{v}")


def _jsonable(v):
    try:
        json.dumps(v)
        return v
    except TypeError:
        return str(v)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subset of workloads for a fast pass")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump per-benchmark us_per_call + derived numbers "
                         "to a BENCH_*.json-compatible file")
    args = ap.parse_args()
    if args.json:
        # fail fast on an unwritable path instead of after the full run,
        # without truncating an existing record or leaving a zero-byte
        # file behind if the run crashes before the final dump
        probe_created = not os.path.exists(args.json)
        with open(args.json, "a"):
            pass
        if probe_created:
            os.remove(args.json)

    from benchmarks import (api_bench, engine_bench, kernel_micro,
                            paper_figures, phased_bench, serving_ab,
                            sharded_bench, tracegen_bench)
    from repro.core import workloads as WL

    wls = ("BFS", "SSSP", "BP", "CONS") if args.quick else WL.WORKLOAD_NAMES

    benches = {
        "fig2_heterogeneity": lambda: paper_figures.fig2_heterogeneity(),
        "fig4_stability": lambda: paper_figures.fig4_stability(),
        "fig5_queueing": lambda: paper_figures.fig5_queueing(),
        "fig7_performance": lambda: paper_figures.fig7_performance(wls),
        "fig8_energy": lambda: paper_figures.fig8_energy(wls),
        "tracegen_scale": lambda: tracegen_bench.tracegen_scale(
            loop_sample=1 if args.quick else 3),
        "engine_scale": lambda: engine_bench.engine_scale(quick=args.quick),
        # in-run unfused-vs-fused wavefront A/B (ISSUE 6 acceptance:
        # fused_speedup_wide1k >= 1.5 at 1024 warps, same process)
        "engine_fused": lambda: engine_bench.fused_ab(quick=args.quick),
        # api-layer overhead is always measured on the quick suite (the
        # gated configuration); the full fig7 suite is the same single
        # shape bucket with more scenarios
        "api_overhead": lambda: api_bench.api_overhead(quick=True),
        # reclassification-lag vs oblivious-static-label IPC gap on the
        # drifting-regime specs, both directions: degrading PHASED_* +
        # recovery-shaped PHASED_RECOVER_* (quick: 48+256 warps; full
        # adds the 1k/2k sizes)
        "phased_gap": lambda: phased_bench.phased_gap(quick=args.quick),
        # multi-device sweep correctness + scale (--only sharded runs
        # both): in-run unsharded-vs-sharded bitwise parity on both
        # engines, then the 16k-warp warp-sharded stress demonstration;
        # each reports skipped=True without >=2 devices (tier2-sharded
        # provides 8 virtual devices via XLA_FLAGS)
        "sharded_parity": lambda: sharded_bench.sharded_parity(
            quick=args.quick),
        "sharded_stress": lambda: sharded_bench.sharded_stress(
            quick=args.quick),
        "serving_ab": serving_ab.serving_ab,
        # open-loop serving simulator A/B via the declarative registry
        # (--only serving runs both serving benches); carries the in-run
        # medic-vs-lru bursty p99 gate for the tier2-serving CI job
        "serving_sim": lambda: serving_ab.serving_sim(quick=args.quick),
        "kernel_micro": kernel_micro.kernel_micro,
    }
    t00 = time.time()
    record = {"schema": "bench-v1", "quick": args.quick, "benchmarks": []}
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        t0 = time.time()
        rows, derived = fn()
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},rows={len(rows)}")
        _emit(name, rows, derived)
        record["benchmarks"].append({
            "name": name,
            "us_per_call": round(us),
            "n_rows": len(rows),
            "rows": [{k: _jsonable(v) for k, v in r.items()} for r in rows],
            "derived": {k: _jsonable(v) for k, v in derived.items()},
        })
        sys.stdout.flush()
    total = time.time() - t00
    record["total_wall_s"] = round(total, 1)
    print(f"\ntotal_wall_s,{total:.1f},")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(record, f, indent=1, sort_keys=True)
        print(f"json,{args.json},")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
