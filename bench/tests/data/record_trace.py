#!/usr/bin/env python3
"""Record the small profiler trace the harness tests reduce.

    python bench/tests/data/record_trace.py   # on a machine with a TPU

Two sweeps of a tiny wavefront experiment (8 warps x 2 instructions,
two policies) under the profiler, each in a ``sweep <k>`` annotation as
``bench/run.py`` makes them. Writes ``small_trace.json.gz`` (the host and
device lines the reduction reads, as plain data) and
``small_trace_reduced.json`` (what ``bench.lib.trace`` made of it then)
beside this file.
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


def main():
    import jax
    from bench.lib import cells, sweep, trace
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
            for k in (1, 2):
                with jax.profiler.TraceAnnotation(f"sweep {k}"):
                    exp(k).run()
            jax.profiler.stop_trace()
            planes = trace.load_planes(d)
    # keep what the reduction reads, inside the two sweeps, with names
    # cut short: the host lines and the device's operation lines
    sweeps = [e for p in planes if p["name"].startswith("/host:")
              for l in p["lines"] for e in l["events"]
              if e[0] in ("sweep 1", "sweep 2")]
    lo = min(s for _, s, _ in sweeps)
    hi = max(s + d for _, s, d in sweeps)
    keep = []
    for p in planes:
        lines = []
        for l in p["lines"]:
            if p["name"].startswith("/host:") or l["name"] in trace.OP_LINES:
                ev = [(n[:80], s, d) for n, s, d in l["events"]
                      if s < hi and s + d > lo]
                if ev:
                    lines.append({"name": l["name"], "events": ev})
        if lines:
            keep.append({"name": p["name"], "lines": lines})
    with gzip.open(HERE / "small_trace.json.gz", "wt") as f:
        json.dump(keep, f)
    out = trace.reduce_planes(keep, ["sweep 1", "sweep 2"])
    (HERE / "small_trace_reduced.json").write_text(json.dumps(out, indent=1))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
