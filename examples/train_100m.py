"""End-to-end training driver: a ~100M-parameter qwen3-family model for a
few hundred steps with the full production substrate — synthetic data
pipeline, AdamW + cosine schedule, gradient accumulation, checkpointing,
fault injection + restart, straggler detection.

    PYTHONPATH=src python examples/train_100m.py [--steps 300] [--tiny]
"""
import argparse
import dataclasses

import jax

from repro.checkpoint.checkpointing import CheckpointManager
from repro.configs.base import OptimizerConfig, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.model import build_model
from repro.optim.optimizer import init_opt_state, make_train_step
from repro.runtime.fault_tolerance import FailureInjector, run_fault_tolerant


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--tiny", action="store_true",
                    help="tiny config for CI-speed runs")
    ap.add_argument("--ckpt", default="/tmp/repro_train100m")
    args = ap.parse_args()

    if args.tiny:
        cfg = get_config("qwen3_1_7b").reduced(num_layers=2)
        seq, batch = 64, 8
    else:
        # ~100M params: 12 x 512 qwen3-family (qk-norm, GQA, tied embed)
        cfg = dataclasses.replace(
            get_config("qwen3_1_7b"), num_layers=12, d_model=512,
            num_heads=8, num_kv_heads=4, head_dim=64, d_ff=2048,
            vocab_size=32000, remat=False)
        seq, batch = 256, 8
    model = build_model(cfg)
    print(f"model: {cfg.name}-derived, {cfg.num_params/1e6:.1f}M params")

    params = model.init_params(jax.random.PRNGKey(0))
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    opt = init_opt_state(params, ocfg)
    step = jax.jit(make_train_step(model, ocfg, microbatches=2))
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                global_batch=batch, n_chains=2))

    ck = CheckpointManager(args.ckpt, keep=2)
    res = run_fault_tolerant(
        step, params, opt, ds.iterator(), ckpt=ck,
        total_steps=args.steps, checkpoint_every=50,
        injector=FailureInjector(fail_at=(args.steps // 3,)),
        on_metrics=lambda s, m: print(
            f"step {s:4d} loss {m['loss']:.4f} lr {m['lr']:.2e} "
            f"gnorm {m['grad_norm']:.2f}") if s % 20 == 0 else None)

    losses = [m["loss"] for m in res.metrics_history]
    print(f"\nrestarts={res.restarts} straggler_events="
          f"{len(res.straggler_events)}")
    print(f"loss: {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
