"""Checkpointing (atomic/async/restore/reshard) + fault-tolerant loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointing import CheckpointManager
from repro.configs.base import OptimizerConfig, get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models.model import build_model
from repro.optim.optimizer import init_opt_state, make_train_step
from repro.runtime.fault_tolerance import (FailureInjector, StragglerDetector,
                                           run_fault_tolerant)


@pytest.fixture(scope="module")
def small_setup():
    cfg = get_config("qwen3_1_7b").reduced(num_layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ocfg = OptimizerConfig(lr=1e-2, warmup_steps=5, total_steps=100)
    opt = init_opt_state(params, ocfg)
    step = jax.jit(make_train_step(model, ocfg))
    ds = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                global_batch=4, n_chains=1))
    return cfg, model, params, ocfg, opt, step, ds


def test_checkpoint_roundtrip_bf16(tmp_path, small_setup):
    _, _, params, _, opt, _, _ = small_setup
    ck = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    ck.save(3, {"params": params, "opt": opt}, {"data": {"step": 3}})
    out = ck.restore_latest({"params": params, "opt": opt})
    assert out is not None
    step, tree, extra = out
    assert step == 3 and extra["data"]["step"] == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(tree["params"])):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_retention_and_atomicity(tmp_path, small_setup):
    _, _, params, _, opt, _, _ = small_setup
    ck = CheckpointManager(str(tmp_path), keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        ck.save(s, {"params": params, "opt": opt})
    assert ck.all_steps() == [3, 4]
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))


def test_async_save(tmp_path, small_setup):
    _, _, params, _, opt, _, _ = small_setup
    ck = CheckpointManager(str(tmp_path), keep=2, async_save=True)
    ck.save(7, {"params": params, "opt": opt})
    ck.wait()
    assert ck.latest_step() == 7


def test_restart_resume_bitwise_identical(tmp_path, small_setup):
    """A run with injected failures must produce the same final loss as an
    uninterrupted run (checkpoint/restart correctness)."""
    _, _, params, ocfg, opt, step, ds = small_setup

    ck1 = CheckpointManager(str(tmp_path / "a"), keep=3, async_save=False)
    r1 = run_fault_tolerant(step, params, opt, ds.iterator(), ckpt=ck1,
                            total_steps=12, checkpoint_every=4,
                            injector=FailureInjector(fail_at=(6,)))
    ck2 = CheckpointManager(str(tmp_path / "b"), keep=3, async_save=False)
    r2 = run_fault_tolerant(step, params, opt, ds.iterator(), ckpt=ck2,
                            total_steps=12, checkpoint_every=4)
    assert r1.restarts == 1 and r2.restarts == 0
    l1 = r1.metrics_history[-1]["loss"]
    l2 = r2.metrics_history[-1]["loss"]
    assert l1 == pytest.approx(l2, rel=1e-6)


def test_elastic_reshard_between_meshes(tmp_path, small_setup):
    """Save on one 'mesh', restore onto a different sharding layout
    (elastic re-scale path; single device here, shardings still differ)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.sharding import single_device_mesh
    _, _, params, _, opt, _, _ = small_setup
    ck = CheckpointManager(str(tmp_path), keep=1, async_save=False)
    ck.save(1, {"params": params})
    mesh = single_device_mesh()
    shardings = jax.tree.map(
        lambda _: NamedSharding(mesh, P()), {"params": params})
    step, tree, _ = ck.restore_latest({"params": params}, shardings)
    leaf = jax.tree.leaves(tree["params"])[0]
    assert leaf.sharding == NamedSharding(mesh, P())


def test_straggler_detector_flags_outliers():
    det = StragglerDetector(window=10, threshold=3.0)
    hits = []
    for i in range(30):
        dt = 1.0 if i != 25 else 8.0
        det.observe(i, dt, mitigate=lambda s: hits.append(s))
    assert any(e["step"] == 25 for e in det.events)
    assert hits == [25]


def test_data_pipeline_determinism_and_resume():
    ds = SyntheticLM(DataConfig(vocab_size=128, seq_len=16, global_batch=4))
    it = ds.iterator()
    batches = [next(it) for _ in range(5)]
    state = it.state_dict()
    it2 = ds.iterator()
    it2.load_state_dict(state)
    np.testing.assert_array_equal(next(it2)["tokens"],
                                  ds.get_batch(5)["tokens"])
    np.testing.assert_array_equal(batches[2]["tokens"],
                                  ds.get_batch(2)["tokens"])


def test_grad_compression_int8_close_to_exact(small_setup):
    """int8-with-error-feedback training should track exact training."""
    cfg, model, params, _, _, _, ds = small_setup
    o1 = OptimizerConfig(lr=5e-3, warmup_steps=2, total_steps=30)
    o2 = OptimizerConfig(lr=5e-3, warmup_steps=2, total_steps=30,
                         grad_compression="int8")
    s1 = jax.jit(make_train_step(model, o1))
    s2 = jax.jit(make_train_step(model, o2))
    p1 = p2 = params
    st1 = init_opt_state(params, o1)
    st2 = init_opt_state(params, o2)
    it = ds.iterator()
    for _ in range(10):
        b = next(it)
        p1, st1, m1 = s1(p1, st1, b)
        p2, st2, m2 = s2(p2, st2, b)
    assert float(m2["loss"]) == pytest.approx(float(m1["loss"]), rel=0.05)


def test_compressed_psum_matches_psum():
    from repro.optim.optimizer import compressed_psum
    from repro.sharding import single_device_mesh
    import jax
    mesh = single_device_mesh()
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64,)),
                    jnp.float32)

    def f(v):
        return compressed_psum(v, "data")

    y = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec()))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.02)
