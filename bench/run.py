#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up: find the cell's files by name, keep JAX's compilation cache in
``.bench_cache/jax`` inside the checkout, and run one warm-up sweep of
the cell's own shape (it compiles, or loads from the cache). ``setup_s``
is the time from process start to the end of that sweep.

Window: sweeps of ``repro.api.Experiment(...).run()``, each with fresh
trace seeds, back to back. A sweep starts while less than ``--seconds``
have passed since the window opened; the window ends when that sweep
returns, so it holds whole sweeps only. ``sim_req_per_s`` is the
simulated requests of all its sweeps over their total host wall time.
With ``--trace 1`` one more sweep follows the window under the
profiler, and the result carries the per-layer metrics instead: the
host-clock ones over the window's sweeps, the device ones over the
traced sweep.

Then one window sweep, drawn from the seed, is compared with the plain
reference (``bench/lib/check.py``). The last line of standard output is
one JSON object; the numbers compared and their limits come last, on
standard error too. Without a TPU, or with fewer chips than the cell
asks for, the run exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

#: fixed, inside the checkout, so a later run of the same checkout finds
#: every program compiled by an earlier one
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def use_cache():
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts compilations and cache loads while ``on``."""

    def __init__(self):
        from jax import monitoring
        self.on, self.count = False, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1

    def _event(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_hits":
            self.count += 1


def _cache_entries() -> int:
    return len(os.listdir(CACHE_DIR)) if CACHE_DIR.is_dir() else 0


def chips_or_exit(need: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < need:
        print(f"needs {need} TPU chip(s); JAX finds {len(devices)} "
              f"{devices[0].platform} device(s)", file=sys.stderr)
        sys.exit(2)
    return devices


def run_sweep(cell, seed, k):
    """Sweep ``k`` of the run: fresh seeds, one ``Experiment.run()``."""
    import jax
    from bench.lib import sweep

    tr = cell.traffic
    seeds = sweep.sweep_seeds(seed, k, tr["seeds_per_sweep"])
    exp = sweep.build(cell.config, tr, seeds, f"{cell.name}.{k}")
    with jax.profiler.TraceAnnotation(f"sweep {k}"):
        t0 = time.perf_counter()
        rs = exp.run()
        wall = time.perf_counter() - t0
    call = sum(rs.call_walls())
    print(f"[{cell.name}] sweep {k}: {wall:.3f} s, of which the program's "
          f"calls {call:.3f} s", file=sys.stderr)
    return {"seeds": seeds, "rs": rs, "wall_s": wall, "call_s": call}


def run_window(cell, seed, seconds, counter):
    """Whole sweeps until ``seconds`` have passed since the first began."""
    sweeps = []
    counter.on = True
    t_open = time.perf_counter()
    while not sweeps or time.perf_counter() - t_open < seconds:
        sweeps.append(run_sweep(cell, seed, len(sweeps) + 1))
    counter.on = False
    return sweeps


def traced_sweep(cell, seed, k, profile_dir):
    """One more sweep, after the window, under the profiler. Its
    collection takes minutes (the device buffers hold about 6 M
    operations), and a profiler session may leave the process changed,
    so no window sweep follows a trace."""
    import jax
    from bench.lib import trace

    jax.profiler.start_trace(profile_dir)
    try:
        run_sweep(cell, seed, k)
    finally:
        jax.profiler.stop_trace()
    return trace.reduce(profile_dir, [f"sweep {k}"])


def main(argv=None, root=ROOT, bench_dir=None, devices=None):
    """One run. ``root``/``bench_dir`` locate ``BENCHMARK.json`` and the
    cell's files; ``devices`` given skips the look for chips (and the
    chip-only set-up), which the harness's own tests use on the CPU."""
    args = parse_args(argv)
    from bench.lib import cells, check, peaks, sweep

    cell = cells.find_cell(args.workload, root, bench_dir)
    readers = cells.metric_readers(cell, bench_dir) if args.trace else {}
    cached = None
    if devices is None:
        use_cache()
        devices = chips_or_exit(cell.chips)
        peaks.peaks_for(devices[0].device_kind)
        cached = _cache_entries()
    import jax
    kind = devices[0].device_kind

    # ---- set-up: one warm-up sweep of the cell's own shape --------------
    tr = cell.traffic
    counter = CompileCounter()
    warm_seeds = sweep.sweep_seeds(args.seed, 0, tr["seeds_per_sweep"])
    warm = sweep.build(cell.config, tr, warm_seeds, f"{cell.name}.0")
    rs = warm.run(keep_traces=True)
    for sc in warm.scenarios:
        for sd in sc.seeds:
            if (rs.trace(sc.name, sd)["lines"] < 0).any():
                raise AssertionError(f"{sc.name} seed {sd}: the generator "
                                     "emitted an invalid lane")
    del rs
    setup_s = time.perf_counter() - T_START
    if cached is not None:
        print(f"[{cell.name}] set-up {setup_s:.3f} s; compile cache "
              f"{CACHE_DIR}: {cached} entries before, {_cache_entries()} "
              "after", file=sys.stderr)

    # ---- the measured window, then the traced sweep ---------------------
    sweeps = run_window(cell, args.seed, args.seconds, counter)
    trace = None
    if args.trace:
        with tempfile.TemporaryDirectory(prefix="bench_trace_") as tmp:
            trace = traced_sweep(cell, args.seed, len(sweeps) + 1, tmp)
    stats = [d.memory_stats() or {} for d in devices[:cell.chips]]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    print(f"[{cell.name}] {len(sweeps)} sweeps in the window; compilations"
          f" and cache loads inside it: {counter.count}", file=sys.stderr)

    wall = sum(s["wall_s"] for s in sweeps)
    n_req = sweep.requests_per_sweep(cell.config, tr) * len(sweeps)
    if args.trace:
        run = {"sweeps": sweeps, "trace": trace}
        metrics = {}
        for m in cell.per_layer:
            v = readers[m["name"]](run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {"sim_req_per_s": n_req / wall, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end}

    # ---- correctness: one window sweep against the reference ------------
    pick = check.pick_sweep(args.seed, len(sweeps))
    ents = check.sample(cell.config, tr, sweeps[pick]["seeds"], args.seed)
    got = check.program_metrics(sweeps[pick]["rs"], ents)
    for s in sweeps:
        s.pop("rs")
    jax.clear_caches()
    t0 = time.perf_counter()
    want = check.reference(cell.config, tr, ents)
    numbers = check.readings(got, want)
    correct = check.verdict(numbers)
    print(f"[{cell.name}] reference for sweep {pick + 1} "
          f"({len(got)} simulations): {time.perf_counter() - t0:.1f} s",
          file=sys.stderr)

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": len(sweeps),
           "failed": 0 if correct else 1, "metrics": metrics,
           "device": device}
    if args.trace:
        device.update({"busy_s": trace["busy_s"],
                       "window_s": trace["window_s"]})
        out["breakdown"] = trace["breakdown"]
    out["checks"] = {k: {"value": numbers[k], "limit": check.LIMITS[k]}
                     for k in check.LIMITS}
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
