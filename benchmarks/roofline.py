"""Emit the EXPERIMENTS.md §Dry-run / §Roofline tables from the per-cell
JSONs produced by ``repro.launch.dryrun``.

    PYTHONPATH=src python -m benchmarks.roofline [--dir results/dryrun]
"""
from __future__ import annotations

import argparse
import glob
import json
import os


def load(dirpath):
    cells = []
    for fp in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(fp) as f:
            cells.append(json.load(f))
    return cells


def fmt_bytes(b):
    if b >= 1e12:
        return f"{b/1e12:.2f}TB"
    if b >= 1e9:
        return f"{b/1e9:.2f}GB"
    return f"{b/1e6:.1f}MB"


def emit(cells, mesh="16x16"):
    print(f"\n### Roofline table — mesh {mesh} (per device, per step)\n")
    print("| arch | shape | compute s | memory s | collective s | dominant "
          "| MODEL_FLOPS | useful ratio | roofline frac | mem/dev | fits "
          "16GB |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    for c in cells:
        if c.get("mesh") != mesh:
            continue
        if c["status"] == "skipped":
            print(f"| {c['arch']} | {c['shape']} | — | — | — | skipped | — "
                  f"| — | — | — | — |")
            continue
        if c["status"] != "ok":
            print(f"| {c['arch']} | {c['shape']} | ERROR: "
                  f"{c.get('error','')[:60]} | | | | | | | | |")
            continue
        r = c["roofline"]
        m = c["memory"]
        print(f"| {c['arch']} | {c['shape']} | {r['compute_s']:.3f} | "
              f"{r['memory_s']:.3f} | {r['collective_s']:.3f} | "
              f"{r['dominant']} | {c['model_flops_global']:.2e} | "
              f"{c['useful_ratio']:.3f} | {r['roofline_fraction']:.4f} | "
              f"{fmt_bytes(m['per_device_live_bytes'])} | "
              f"{'yes' if m['fits_16gb'] else 'NO'} |")


def summarize(cells):
    ok = [c for c in cells if c["status"] == "ok"]
    sp = [c for c in ok if c["mesh"] == "16x16"]
    if not sp:
        return
    worst = min(sp, key=lambda c: c["roofline"]["roofline_fraction"])
    coll = max(sp, key=lambda c: c["roofline"]["collective_s"] /
               max(sum((c["roofline"]["compute_s"],
                        c["roofline"]["memory_s"],
                        c["roofline"]["collective_s"])), 1e-12))
    print("\n### Hillclimb candidates (single-pod)")
    print(f"- worst roofline fraction: {worst['arch']} x {worst['shape']} "
          f"({worst['roofline']['roofline_fraction']:.4f})")
    print(f"- most collective-bound: {coll['arch']} x {coll['shape']} "
          f"(coll {coll['roofline']['collective_s']:.2f}s of "
          f"{coll['roofline']['compute_s']:.2f}s compute)")
    n_err = sum(1 for c in cells if c["status"] == "error")
    n_skip = sum(1 for c in cells if c["status"] == "skipped")
    print(f"\ncells: {len(cells)} total, {len(ok)} ok, {n_skip} skipped "
          f"(documented), {n_err} errors")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    args = ap.parse_args()
    cells = load(args.dir)
    emit(cells, "16x16")
    emit(cells, "2x16x16")
    summarize(cells)


if __name__ == "__main__":
    main()
