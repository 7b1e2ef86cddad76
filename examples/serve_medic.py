"""Serve a small model with batched requests under the MeDiC pool manager
and print the policy A/B against LRU (altitude-B deployment of the paper).

    PYTHONPATH=src python examples/serve_medic.py
"""
from repro.configs.base import get_config
from repro.serving.engine import EngineConfig, run_ab
from repro.serving.pool import PoolConfig
from repro.serving.request import ServeWorkload


def main():
    cfg = get_config("qwen3_1_7b").reduced(num_layers=2)
    wl = ServeWorkload(n_requests=24, chat_frac=0.6)
    pool = PoolConfig(budget_blocks=48, block_tokens=16)
    out = run_ab(cfg, wl, pool, EngineConfig(max_slots=4, max_len=448))

    print(f"{'':22s}{'LRU':>12s}{'MeDiC':>12s}")
    for key in ("throughput", "completed", "mean_ttft", "mean_qdelay",
                "bypassed_blocks", "stall_steps"):
        a, b = out["lru"][key], out["medic"][key]
        print(f"{key:22s}{a:>12.3f}{b:>12.3f}" if isinstance(a, float)
              else f"{key:22s}{a:>12d}{b:>12d}")
    gain = out["medic"]["throughput"] / max(out["lru"]["throughput"], 1e-9)
    print(f"\nMeDiC throughput gain under pool oversubscription: {gain:.2f}x")

    # per-sequence-type view (the paper's Fig 2 analogue at the pool)
    import numpy as np
    print("\nper-sequence pool hit ratios (MeDiC run):")
    # re-run one engine to snapshot
    from repro.serving.engine import ServeEngine
    from repro.serving.request import generate_requests
    eng = ServeEngine(cfg, EngineConfig(max_slots=4, max_len=448), pool)
    eng.run(generate_requests(wl, seed=0), max_steps=800)
    snap = eng.pool.snapshot()
    ratios = snap["seq_hit_ratio"]
    print("  " + " ".join(f"{r:.2f}" for r in ratios if np.isfinite(r)))


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
