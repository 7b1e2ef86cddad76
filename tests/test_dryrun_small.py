"""Small-mesh dry-run machinery tests (the 512-device sweep itself runs via
``python -m repro.launch.dryrun``; these tests exercise the same builders on
the single real CPU device) + HLO analyzer unit tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_analysis import analyze, _shape_bytes_and_elems


def test_shape_bytes():
    b, e = _shape_bytes_and_elems("f32[128,64]{1,0}")
    assert e == 128 * 64 and b == 4 * e
    b, e = _shape_bytes_and_elems("(bf16[2,3]{1,0}, s32[])")
    assert e == 7 and b == 16


def test_analyzer_counts_scan_trips_and_dots():
    """A scanned matmul chain: rolled dot flops == unrolled hand count."""
    L, B, D = 8, 4, 32

    def layer(x, w):
        return jnp.tanh(x @ w), None

    def f(ws, x):
        x, _ = jax.lax.scan(layer, x, ws)
        return x.sum()

    ws = jnp.ones((L, D, D))
    x = jnp.ones((B, D))
    compiled = jax.jit(f).lower(ws, x).compile()
    s = analyze(compiled.as_text())
    expected = 2 * B * D * D * L
    assert s.n_while >= 1
    assert max(s.trip_counts) == L
    np.testing.assert_allclose(s.dot_flops, expected, rtol=0.01)


def test_analyzer_vs_cost_analysis_consistency():
    """Without loops, rolled dot flops ~= XLA's own flops count."""
    a = jnp.ones((64, 128))
    b = jnp.ones((128, 96))
    compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
    s = analyze(compiled.as_text())
    ca = compiled.cost_analysis()
    np.testing.assert_allclose(s.dot_flops, ca["flops"], rtol=0.05)


def test_build_cell_lowers_on_tiny_config(monkeypatch):
    """End-to-end cell builder path on 1 device with a reduced config (the
    512-device meshes are exercised by the real dry-run)."""
    import repro.launch.dryrun as DR
    from repro.configs.base import get_config

    tiny = get_config("qwen3_1_7b").reduced(num_layers=2)
    monkeypatch.setattr(DR, "get_config", lambda a: tiny)
    monkeypatch.setattr(
        DR, "make_production_mesh",
        lambda multi_pod=False: __import__(
            "repro.launch.mesh", fromlist=["x"]).make_local_mesh(1, 1))
    # shrink the shape so CPU compile stays fast
    import dataclasses
    from repro.configs.base import ShapeConfig
    monkeypatch.setitem(DR.SHAPES, "train_4k",
                        ShapeConfig("train_4k", 64, 4, "train"))
    res = DR.run_cell("qwen3_1_7b", "train_4k", multi_pod=False)
    assert res["status"] == "ok"
    assert res["hlo"]["dot_flops_per_dev"] > 0
    assert res["roofline"]["dominant"] in ("compute", "memory", "collective")


def test_dryrun_results_if_present():
    """When the real sweep has produced results, validate the contract:
    every non-skipped cell compiled, and long_500k skips match DESIGN."""
    import glob
    import json
    import os
    files = glob.glob("results/dryrun/*.json")
    if not files:
        pytest.skip("512-device sweep not run in this environment")
    bad = []
    for fp in files:
        with open(fp) as f:
            d = json.load(f)
        if d["status"] == "error":
            bad.append((os.path.basename(fp), d.get("error", "")[:80]))
    assert not bad, bad
