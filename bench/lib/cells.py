"""Find a cell, its configuration, its traffic and its per-layer metric
readers by name.

``BENCHMARK.json`` lists the cells. Everything that belongs to one of
them lives in a file of its own, found by name:

* ``bench/configs/<config>.json``  the simulated GPU and trace specs;
* ``bench/traffic/<traffic>.json`` the sweep: policies, seeds, engine;
* ``bench/metrics/<metric>.py``    a reader with ``read(run) -> float | None``.

A later cell, configuration or metric is added by adding files and an
entry in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path} is missing")
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files, read
    from ``bench_dir`` (default: the directory this harness lives in)."""
    bench = load_benchmark(root)
    bench_dir = bench_dir or BENCH_DIR
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown cell {name!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    return Cell(name, int(w["chips"]), config, traffic,
                bench["end_to_end"], bench["per_layer"])


def metric_readers(cell: Cell, bench_dir: Optional[Path] = None
                   ) -> Dict[str, Callable]:
    """``{metric name: read}`` for the cell's per-layer metrics, each
    loaded from ``bench/metrics/<name>.py``."""
    bench_dir = bench_dir or BENCH_DIR
    readers = {}
    for m in cell.per_layer:
        path = bench_dir / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        if spec is None or not path.is_file():
            raise FileNotFoundError(f"no reader {path} for metric "
                                    f"{m['name']!r}")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        readers[m["name"]] = mod.read
    return readers
