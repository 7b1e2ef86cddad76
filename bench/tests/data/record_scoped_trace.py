#!/usr/bin/env python3
"""Record the small profiler trace, with the wave loop's pass scopes,
that the harness tests reduce.

    python bench/tests/data/record_scoped_trace.py   # on a machine with a TPU

Two sweeps of a tiny wavefront experiment (8 warps x 2 instructions,
two policies) under the profiler, each in a ``sweep <k>`` annotation as
``bench/run.py`` makes them. Writes ``small_trace_scoped.json.gz`` (the
host and device lines the reduction reads, and the op_name paths of the
device's scoped operations, as plain data) and
``small_trace_scoped_reduced.json`` (what ``bench.lib.trace`` made of it
then, with the scopes' times from ``bench.lib.scopes`` under
``"scopes"``) beside this file.
"""
from __future__ import annotations

import gzip
import json
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SWEEPS = ("sweep 1", "sweep 2")


def keep_sweeps(planes, cut: int = 80):
    """The host lines and the device's operation lines inside the two
    sweeps, names cut to ``cut`` characters, and the op_name paths of
    the operations kept."""
    from bench.lib import trace

    sweeps = [e for p in planes if p["name"].startswith("/host:")
              for l in p["lines"] for e in l["events"] if e[0] in SWEEPS]
    lo = min(s for _, s, _ in sweeps)
    hi = max(s + d for _, s, d in sweeps)
    keep = []
    for p in planes:
        lines, kept = [], set()
        for l in p["lines"]:
            if p["name"].startswith("/host:") or l["name"] in trace.OP_LINES:
                ev = [(n[:cut], s, d) for n, s, d in l["events"]
                      if s < hi and s + d > lo]
                kept |= {n for n, _, _ in ev}
                if ev:
                    lines.append({"name": l["name"], "events": ev})
        if lines:
            plane = {"name": p["name"], "lines": lines}
            paths = {n[:cut]: path
                     for n, path in p.get("op_names", {}).items()
                     if n[:cut] in kept}
            if paths:
                plane["op_names"] = paths
            keep.append(plane)
    return keep


def main():
    import jax
    from bench.lib import cells, scopes, sweep, trace
    from bench.tests.cell_fixture import make_cell

    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    with tempfile.TemporaryDirectory() as root:
        bench = make_cell(Path(root), engine="wavefront", n_warps=8,
                          n_instr=2)
        cell = cells.find_cell("tiny", Path(root), bench)
        exp = lambda k: sweep.build(cell.config, cell.traffic,  # noqa: E731
                                    [k], f"tiny.{k}")
        exp(0).run()
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            for k, name in enumerate(SWEEPS, 1):
                with jax.profiler.TraceAnnotation(name):
                    exp(k).run()
            jax.profiler.stop_trace()
            planes = scopes.load_planes(d)
    keep = keep_sweeps(planes)
    with gzip.open(HERE / "small_trace_scoped.json.gz", "wt") as f:
        json.dump(keep, f)
    out = trace.reduce_planes(keep, list(SWEEPS))
    out["scopes"] = scopes.reduce_planes(keep, list(SWEEPS))
    (HERE / "small_trace_scoped_reduced.json").write_text(
        json.dumps(out, indent=1))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
