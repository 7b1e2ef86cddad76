"""Sharding rule resolution + small-mesh end-to-end partitioning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

st = pytest.importorskip("hypothesis.strategies")
from hypothesis import given, settings
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding import (Logical, build_rules, spec_for, shard_act,
                            sharding_ctx, single_device_mesh)


def _mesh_16x16_abstract():
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def test_spec_basic():
    mesh = _mesh_16x16_abstract()
    rules = build_rules(mesh)
    s = spec_for(("embed", "heads", "head_dim"), (4096, 32, 128), mesh, rules)
    assert s == P("data", "model", None)


def test_spec_divisibility_fallback():
    mesh = _mesh_16x16_abstract()
    rules = build_rules(mesh)
    # 10 heads don't divide 16 -> unsharded
    s = spec_for(("embed", "heads", "head_dim"), (2560, 10, 256), mesh, rules)
    assert s == P("data", None, None)
    # 8 experts don't divide 16 -> expert falls back, mlp takes model
    s = spec_for(("expert", "embed", "mlp"), (8, 6144, 32768), mesh, rules)
    assert s == P(None, "data", "model")
    # 64 experts divide -> expert takes model, mlp falls back (axis used)
    s = spec_for(("expert", "embed", "mlp"), (64, 2048, 1024), mesh, rules)
    assert s == P("model", "data", None)


def test_spec_missing_mesh_axis_removed():
    mesh = _mesh_16x16_abstract()   # no "pod" axis
    rules = build_rules(mesh)
    s = spec_for(("batch", None), (256, 4096), mesh, rules)
    assert s == P("data", None)


def test_multipod_batch_axes():
    mesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    rules = build_rules(mesh)
    s = spec_for(("batch", None), (256, 4096), mesh, rules)
    assert s == P(("pod", "data"), None)
    # batch=1 (long_500k): not divisible -> unsharded
    s = spec_for(("batch", "kv_seq"), (1, 524288), mesh, rules)
    assert s == P(None, "model")


@settings(max_examples=50, deadline=None)
@given(st.lists(st.sampled_from(["batch", "embed", "heads", "mlp", "vocab",
                                 "expert", None]), min_size=1, max_size=4),
       st.lists(st.sampled_from([1, 2, 7, 16, 48, 64, 256, 4096]),
                min_size=4, max_size=4))
def test_spec_never_overassigns(axes, dims):
    """Property: every produced spec uses each mesh axis at most once and
    always divides the dimension."""
    mesh = _mesh_16x16_abstract()
    rules = build_rules(mesh)
    shape = tuple(dims[: len(axes)])
    s = spec_for(tuple(axes), shape, mesh, rules)
    used = []
    for dim, assignment in zip(shape, tuple(s)):
        if assignment is None:
            continue
        axs = (assignment,) if isinstance(assignment, str) else assignment
        size = 1
        for a in axs:
            assert a not in used
            used.append(a)
            size *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
        assert dim % size == 0


def test_shard_act_noop_without_ctx():
    x = jnp.ones((4, 4))
    y = shard_act(x, "batch", None)
    assert y is x


def test_model_logical_trees_cover_params():
    """Every param leaf has a Logical leaf of matching rank."""
    from repro.configs.base import get_config
    from repro.models.model import build_model
    for arch in ("grok_1_314b", "whisper_tiny", "xlstm_125m"):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
        lg = model.logical_params()
        def chk(l, s):
            assert isinstance(l, Logical)
            assert len(l.axes) == len(s.shape), (l.axes, s.shape)
        jax.tree.map(chk, lg, shapes,
                     is_leaf=lambda x: isinstance(x, Logical))
