"""A small cell defined in a directory of its own, as a later change
would add one: a BENCHMARK.json entry and files, nothing edited."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

POLICIES = [
    {"name": "Baseline", "bypass": "none", "insertion": "lru",
     "scheduler": "frfcfs", "rand_p": 0.5, "pcal_frac": 0.375,
     "labeling": "online", "reclass_interval": 0, "probe_interval": 0},
    {"name": "MeDiC", "bypass": "medic", "insertion": "medic",
     "scheduler": "medic", "rand_p": 0.5, "pcal_frac": 0.375,
     "labeling": "online", "reclass_interval": 0, "probe_interval": 0},
]


def make_cell(root: Path, engine: str = "event", n_warps: int = 8,
              n_instr: int = 8) -> Path:
    """Write a cell ``tiny`` under ``root``; returns its bench dir."""
    bench = root / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for m in ("host_s", "call_s"):
        shutil.copy(BENCH / "metrics" / f"{m}.py", bench / "metrics")
    gpu = json.loads((BENCH / "configs" / "medic_bfs_64k.json").read_text())
    config = {"name": "tiny", "sim": gpu["sim"],
              "trace_defaults": {**gpu["trace_defaults"],
                                 "n_instr": n_instr},
              "specs": {"TINY_A": {"mix": [0.1, 0.2, 0.3, 0.2, 0.2],
                                   "intensity": 0.9, "n_warps": n_warps},
                        "TINY_B": {"mix": [0.0, 0.1, 0.2, 0.4, 0.3],
                                   "intensity": 1.0, "n_warps": n_warps,
                                   "phase_shift": True}}}
    traffic = {"seeds_per_sweep": 1, "policies": POLICIES,
               "engine": engine}
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "tiny_sweep.json").write_text(json.dumps(traffic))
    bm = {"command": ["python3", "bench/run.py"], "paths": ["bench"],
          "run_seconds": 1,
          "configs": [{"name": "tiny", "source": "test",
                       "file": "bench/configs/tiny.json", "reduced": [],
                       "why": "test"}],
          "workloads": [{"name": "tiny", "config": "tiny",
                         "traffic": "tiny_sweep", "chips": 1,
                         "why": "test"}],
          "end_to_end": [
              {"name": "sim_req_per_s", "unit": "req/s", "better": "higher",
               "bound": 0.03, "source": "host_clock"},
              {"name": "setup_s", "unit": "s", "better": "lower",
               "bound": 0.25, "source": "host_clock"}],
          "per_layer": [
              {"name": "host_s", "unit": "s/sweep", "better": "lower",
               "source": "program_span", "layer": "front door",
               "moves": "sim_req_per_s"},
              {"name": "call_s", "unit": "s/sweep", "better": "lower",
               "source": "program_span", "layer": "engine call",
               "moves": "sim_req_per_s"}]}
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return bench
