#!/usr/bin/env python3
"""Chip smoke test: the simulator's main path, end to end, on a TPU.

    python chip_smoke.py                  # one chip: phases A and B
    python chip_smoke.py --four-chips     # four chips: phase C only

Everything runs in this one process, through ``api.Experiment.run()``
at the sizes users run:

  A. ``registry.PAPER_FIG7`` on the event engine (15 workloads of 48
     warps x the fig7 policy batch); its fig7-quick derived numbers
     must equal the table pinned in tests/test_golden_fig7.py.
  B. ``HAMMER2K`` (2048 warps) x ``registry.STRESS_POLICIES`` on the
     wavefront engine with the default backends, run on the chip and
     again on the host CPU in this process: integer counters must
     match bitwise and MeDiC must rank first; the largest relative
     deviation of each float metric is printed.
  C. (``--four-chips``) ``HAMMER16K`` x MeDiC warp-sharded over a 1x4
     mesh against the same experiment on one chip: bitwise equal, and
     every chip must have held its share of the work.

Earlier lines give the device and each phase's first-call (compile
included) and warm wall times. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed
on a TPU. Without a TPU the script exits non-zero and prints no result;
``--cpu-rehearsal`` runs the phases on the CPU backend anyway (tests of
this script's own control flow) and then still never reports ok.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import use_compile_cache  # noqa: E402


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _run_twice(label, exp):
    """First call (trace generation + compile + execute) and a warm call
    of the same experiment; results are host numpy arrays, so the device
    has finished when each timer stops."""
    rs, first = _timed(exp.run)
    rs_warm, warm = _timed(exp.run)
    print(f"[{label}] first call {first:.3f} s (device call "
          f"{rs.wall_s:.3f} s), warm {warm:.3f} s (device call "
          f"{rs_warm.wall_s:.3f} s)")
    return rs_warm


def _compare(label, got, want, bitwise_floats=False):
    """Integer metrics must match bitwise; returns the names that do not
    (floats too when ``bitwise_floats``). Prints each float metric's
    largest relative deviation."""
    bad = []
    for k in sorted(want):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if a.shape != b.shape or a.dtype != b.dtype:
            bad.append(k)
            continue
        if np.issubdtype(b.dtype, np.floating):
            dev = np.abs(a.astype(np.float64) - b) / np.maximum(
                np.abs(b.astype(np.float64)), np.finfo(b.dtype).tiny)
            worst = float(dev.max()) if dev.size else 0.0
            print(f"[{label}] float {k}: max rel deviation {worst!r}")
            if not np.isfinite(a).all() or (
                    bitwise_floats and not np.array_equal(a, b)):
                bad.append(k)
        elif not np.array_equal(a, b):
            print(f"[{label}] int {k}: {int((a != b).sum())} of {a.size} "
                  "entries differ")
            bad.append(k)
    return bad


def phase_fig7():
    """A: the paper suite on the event engine vs the pinned fig7 table."""
    from benchmarks.paper_figures import fig7_table
    from repro.api import registry
    from tests.test_golden_fig7 import (GOLDEN_BFS_SPEEDUPS,
                                        GOLDEN_DERIVED, QUICK_WORKLOADS)

    rs = _run_twice("A fig7", registry.PAPER_FIG7)
    rows, derived = fig7_table(
        lambda wl, pol, sd: float(
            rs.get(scenario=wl, policy=pol.name, seed=sd)["ipc"]),
        QUICK_WORKLOADS)
    bfs = {r["policy"]: r["speedup"] for r in rows
           if r["workload"] == "BFS"}
    bad = []
    for got, golden in ((derived, GOLDEN_DERIVED),
                        (bfs, GOLDEN_BFS_SPEEDUPS)):
        for k, want in golden.items():
            if not abs(got[k] - want) <= 1e-6:
                bad.append(f"{k}={got[k]} (pinned {want})")
    print(f"[A fig7] hmean_speedup[MeDiC]="
          f"{derived['hmean_speedup[MeDiC]']} medic_vs_best_prior="
          f"{derived['medic_vs_best_prior']}")
    if bad:
        raise AssertionError(f"fig7-quick differs from the pinned table: "
                             f"{bad}")
    print(f"[A fig7] {len(GOLDEN_DERIVED) + len(GOLDEN_BFS_SPEEDUPS)} "
          "pinned numbers match")


def phase_stress():
    """B: HAMMER2K on the wavefront engine, chip vs host CPU."""
    from repro.api import registry

    exp = registry.stress(scenarios=("HAMMER2K",))
    got = _run_twice("B HAMMER2K", exp).get(scenario="HAMMER2K")
    with jax.default_device(jax.devices("cpu")[0]):
        rs_cpu, cpu = _timed(exp.run)
    print(f"[B HAMMER2K] host CPU run {cpu:.3f} s")
    bad = _compare("B HAMMER2K", got, rs_cpu.get(scenario="HAMMER2K"))
    names = [p.name for p in exp.policies]
    order = [names[i] for i in np.argsort(-np.asarray(got["ipc"]))]
    print(f"[B HAMMER2K] ranking by ipc: {' > '.join(order)}")
    if bad:
        raise AssertionError(f"chip and CPU differ on {bad}")
    if order[0] != "MeDiC":
        raise AssertionError(f"MeDiC does not rank first: {order}")


def _peak_bytes(devices):
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return [int(s["peak_bytes_in_use"]) for s in stats]


def _collectives(exp):
    """Collective ops in the compiled program of ``exp``'s one call,
    lowered with the placement ``Plan.execute`` gives its arguments
    (replicated over the mesh, warp axis sharded inside)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.engine import SimParams, _simulate_batch
    from repro.policy import stack_policies

    (call,) = exp.compile().calls
    (scen,) = call.scenarios
    tr = scen.materialize()
    rep = NamedSharding(call.mesh, PartitionSpec())
    shape = lambda x: jax.ShapeDtypeStruct(
        np.shape(x), np.asarray(x).dtype, sharding=rep)
    args = [shape(tr[k]) for k in ("lines", "pcs", "compute_gap",
                                   "oracle_wtype")]
    pa = jax.tree.map(shape, stack_policies(exp.policies))
    _, n_warps, lanes = call.shape
    text = _simulate_batch.lower(
        *args, pa, n_warps=n_warps, lanes=lanes, prm=SimParams(),
        engine=exp.engine, warp_mesh=call.mesh,
        warp_axes=call.warp_axes).compile().as_text()
    return {op: len(re.findall(rf" {op}(?:-start)?\(", text))
            for op in ("all-gather", "all-reduce")}


def phase_sharded(rehearsal):
    """C: HAMMER16K warp-sharded over four chips vs one chip."""
    from repro.api import registry
    from repro.core import baselines as BL
    from repro.launch.mesh import make_local_mesh

    devices = jax.devices()
    if len(devices) < 4:
        raise AssertionError(f"--four-chips needs 4 devices, found "
                             f"{len(devices)}")
    exp = registry.stress_shard(scenarios=("HAMMER16K",),
                                policies=(BL.MEDIC,))
    sharded = exp.with_(mesh=make_local_mesh(1, 4),
                        mesh_axes=(None, None, "model"))
    (call,) = sharded.compile().calls
    if call.warp_axes != "model":
        raise AssertionError(f"warp axis resolved to {call.warp_axes!r}, "
                             "not the 4-way mesh axis")
    # the sharded run goes first, so each chip's peak memory is that of
    # its own part of the sharded program
    got = _run_twice("C HAMMER16K 4-chip", sharded).get(
        scenario="HAMMER16K")
    peaks = _peak_bytes(devices[:4])
    colls = _collectives(sharded)
    print(f"[C HAMMER16K] collectives in the 4-chip program: {colls}")
    want = _run_twice("C HAMMER16K 1-chip", exp).get(scenario="HAMMER16K")
    bad = _compare("C HAMMER16K", got, want, bitwise_floats=True)
    if bad:
        raise AssertionError(f"4-chip and 1-chip differ on {bad}")
    print("[C HAMMER16K] 4-chip == 1-chip bitwise on every metric")
    if not all(colls.values()):
        raise AssertionError("the 4-chip program has no all-gather or "
                             "no all-reduce: the warp axis is not split")
    if peaks is None:
        if not rehearsal:
            raise AssertionError("device memory stats unavailable")
        print("[C HAMMER16K] device memory stats unavailable here")
        return
    print(f"[C HAMMER16K] peak bytes per chip after the 4-chip run: "
          f"{peaks}")
    if min(peaks) < 0.5 * max(peaks):
        raise AssertionError("the 4-chip run did not spread its work "
                             "evenly over the chips")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the warp-sharded 4-chip phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run on a non-TPU backend; never reports ok")
    args = ap.parse_args()

    cache_dir = use_compile_cache()
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    if d0.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (JAX found {d0.platform}); nothing "
              "was run", file=sys.stderr)
        return 2
    print(f"device: {json.dumps(device)}")
    print(f"compile cache: {cache_dir}")

    phases = ([("C", lambda: phase_sharded(args.cpu_rehearsal))]
              if args.four_chips else
              [("A", phase_fig7), ("B", phase_stress)])
    failed = []
    for name, fn in phases:
        _, wall = _timed(lambda: _guard(name, fn, failed))
        print(f"[{name}] phase wall {wall:.3f} s")
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    if args.cpu_rehearsal:
        print(f"chip_smoke: rehearsal on {d0.platform} passed; no result "
              "is reported off a TPU")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


def _guard(name, fn, failed):
    """Run one phase; a failure is reported and recorded, and the next
    phase still runs, but the script then exits non-zero."""
    try:
        fn()
    except Exception:  # noqa: BLE001 — reported, then exit code 1
        traceback.print_exc()
        failed.append(name)


if __name__ == "__main__":
    sys.exit(main())
