import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (SPMD
partitioning succeeds, no sharding mismatch, no unsupported collective) and
extracts the roofline inputs:

  * compiled.memory_analysis()   -> per-device bytes (fits-in-HBM check)
  * compiled.cost_analysis()     -> raw per-device FLOPs/bytes (loop bodies
                                    counted once — see hlo_analysis)
  * hlo_analysis.analyze()       -> loop-aware per-device dot FLOPs, memory
                                    estimate, collective bytes by kind and
                                    replica-group size

Results are written one JSON per cell (restartable); `--emit-table` prints
the EXPERIMENTS.md rows.

Usage:
  python -m repro.launch.dryrun --arch qwen3_1_7b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh both] [--out results/dryrun]
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (ARCH_IDS, SHAPES, OptimizerConfig,
                                get_config, shape_applicable)
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models.model import build_model
from repro.optim.optimizer import init_opt_state, make_train_step
from repro.sharding import (Logical, build_rules, sharding_ctx,
                            tree_shardings)

HW = {  # TPU v5e-class single chip
    "peak_flops_bf16": 197e12,
    "hbm_bw": 819e9,
    "ici_bw": 50e9,
    "hbm_bytes": 16e9,
}


def _opt_cfg(cfg) -> OptimizerConfig:
    # bf16 moments for >20B-param models: the optimizer-state lever that
    # fits grok-1-314b / qwen1.5-110b training on a 256-chip pod
    big = cfg.num_params > 20e9
    return OptimizerConfig(moment_dtype="bfloat16" if big else "float32")


def build_cell(arch: str, shape_name: str, multi_pod: bool):
    """Returns (fn, example_args, in_shardings, donate) for the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = build_rules(mesh)

    param_shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    plog = model.logical_params()
    pshard = tree_shardings(plog, param_shapes, mesh, rules)

    batch_specs = model.input_specs(shape)
    blog = model.batch_logical(shape)
    bshard = tree_shardings(blog, batch_specs, mesh, rules)

    if shape.kind == "train":
        ocfg = _opt_cfg(cfg)
        opt_shapes = jax.eval_shape(
            lambda p: init_opt_state(p, ocfg), param_shapes)
        olog = {"m": plog, "v": plog, "count": Logical()}
        if "err" in opt_shapes:
            olog["err"] = plog
        oshard = tree_shardings(olog, opt_shapes, mesh, rules)
        step = make_train_step(model, ocfg)
        fn = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     donate_argnums=(0, 1))
        args = (param_shapes, opt_shapes, batch_specs)
    elif shape.kind == "prefill":
        cache_shapes = model.cache_specs(shape)
        clog = model.cache_logical(shape.global_batch, shape)
        cshard = tree_shardings(clog, cache_shapes, mesh, rules)

        def prefill_step(params, batch, cache):
            return model.prefill(params, batch, cache)

        fn = jax.jit(prefill_step, in_shardings=(pshard, bshard, cshard),
                     donate_argnums=(2,))
        args = (param_shapes, batch_specs, cache_shapes)
    else:  # decode
        cache_shapes = model.cache_specs(shape)
        clog = model.cache_logical(shape.global_batch, shape)
        cshard = tree_shardings(clog, cache_shapes, mesh, rules)
        tok_shard = tree_shardings(
            {"tokens": Logical("batch", None)},
            {"tokens": batch_specs["tokens"]}, mesh, rules)["tokens"]

        def decode_step(params, tokens, cache):
            return model.decode(params, tokens, cache)

        fn = jax.jit(decode_step, in_shardings=(pshard, tok_shard, cshard),
                     donate_argnums=(2,))
        args = (param_shapes, batch_specs["tokens"], cache_shapes)
    return cfg, shape, mesh, rules, fn, args


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs per step (global), per the brief."""
    n = cfg.num_active_params
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    t0 = time.time()
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}
    cfg, shape, mesh, rules, fn, args = build_cell(arch, shape_name,
                                                   multi_pod)
    with mesh, sharding_ctx(mesh, rules):
        lowered = fn.lower(*args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    summ = hlo_analysis.analyze(compiled.as_text())
    n_dev = mesh.devices.size

    mf = model_flops(cfg, shape)
    hlo_flops_global = summ.dot_flops * n_dev
    per_dev_bytes = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    # loop-corrected HBM traffic: scale XLA's fusion-aware byte count by the
    # flops loop-correction ratio (cost_analysis counts loop bodies once);
    # the op-output sum from hlo_analysis is kept as an upper bound.
    raw_flops = ca.get("flops", 0.0) or 0.0
    loop_ratio = (summ.dot_flops / raw_flops) if raw_flops else 1.0
    mem_scaled = (ca.get("bytes accessed", 0.0) or 0.0) * max(loop_ratio, 1.0)
    out = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "n_devices": n_dev,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "memory": {
            "argument_bytes": ma.argument_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "per_device_live_bytes": per_dev_bytes,
            "fits_16gb": bool(per_dev_bytes < HW["hbm_bytes"]),
        },
        "cost_analysis_raw": {
            "flops": ca.get("flops", 0.0),
            "bytes_accessed": ca.get("bytes accessed", 0.0),
        },
        "hlo": {
            "dot_flops_per_dev": summ.dot_flops,
            "mem_bytes_per_dev": mem_scaled,
            "mem_bytes_upper_per_dev": summ.mem_bytes,
            "loop_ratio": loop_ratio,
            "coll_bytes_per_dev": summ.coll_total,
            "coll_by_kind": summ.coll_bytes,
            "coll_by_group": {f"{k}@{g}": v for (k, g), v in
                              summ.coll_by_group.items()},
            "cross_pod_bytes": summ.cross_pod_bytes(),
            "n_while": summ.n_while,
            "trip_counts": summ.trip_counts,
        },
        "model_flops_global": mf,
        "useful_ratio": mf / hlo_flops_global if hlo_flops_global else None,
        "roofline": roofline_terms(summ, mem_scaled, mf, n_dev),
    }
    return out


def roofline_terms(summ, mem_scaled, mf_global, n_dev) -> dict:
    compute_s = summ.dot_flops / HW["peak_flops_bf16"]
    memory_s = mem_scaled / HW["hbm_bw"]
    coll_s = summ.coll_total / HW["ici_bw"]
    dom = max((compute_s, "compute"), (memory_s, "memory"),
              (coll_s, "collective"))[1]
    bound = max(compute_s, memory_s, coll_s)
    mfu_bound = (mf_global / n_dev / HW["peak_flops_bf16"]) / bound \
        if bound else 0.0
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dom,
        "roofline_fraction": mfu_bound,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'mp' if mp else 'sp'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached ] {tag}")
                    continue
                try:
                    res = run_cell(arch, shape_name, mp)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": arch, "shape": shape_name,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures += 1
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    r = res["roofline"]
                    extra = (f"compile={res['compile_s']}s "
                             f"mem/dev={res['memory']['per_device_live_bytes']/1e9:.2f}GB "
                             f"dom={r['dominant']} "
                             f"frac={r['roofline_fraction']:.3f}")
                elif status == "error":
                    extra = res["error"][:120]
                else:
                    extra = res["reason"][:60]
                print(f"[{status:7s}] {tag} {extra}", flush=True)
    print(f"done; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
