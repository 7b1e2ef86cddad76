#!/usr/bin/env python3
"""Readings that the limits of ``bench/lib/check.py`` are set from.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

For each seed: one sweep of the cell through the program's public entry
(on the chip this process finds), then the checked sample of it against
the plain reference twice: with simulated time in float32, as the
configuration states (a sound run: the lower reading), and in bfloat16,
the precision below it (the control: the upper reading). One JSON line
per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)

    import ml_dtypes
    from bench import run
    from bench.lib import cells, check, sweep

    cell = cells.find_cell(args.workload)
    run.use_cache()
    devices = run.chips_or_exit(cell.chips)
    tr = cell.traffic
    for seed in args.seeds:
        seeds = sweep.sweep_seeds(seed, 1, tr["seeds_per_sweep"])
        rs = sweep.build(cell.config, tr, seeds, cell.name).run()
        ents = check.sample(cell.config, tr, seeds, seed)
        got = check.program_metrics(rs, ents)
        del rs
        t0 = time.perf_counter()
        sound = check.readings(got, check.reference(cell.config, tr, ents))
        t1 = time.perf_counter()
        low = check.readings(got, check.reference(
            cell.config, tr, ents, clock=ml_dtypes.bfloat16))
        print(json.dumps({"cell": cell.name, "seed": seed,
                          "device": devices[0].device_kind,
                          "simulations": len(ents), "sound": sound,
                          "control_bf16": low,
                          "ref_s": t1 - t0,
                          "control_s": time.perf_counter() - t1}),
              flush=True)


if __name__ == "__main__":
    main()
