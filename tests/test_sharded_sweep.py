"""Multi-device sharded sweeps: parity + the sharding-layer bug tail
(ISSUE 10).

Three tiers:

  * resolution tests on ``AbstractMesh`` grids — always run, no devices
    needed: the size-1-axis contract of ``spec_for`` / ``resolve_axes``
    ((1,N) / (N,1) / (2,2) meshes), ``make_local_mesh`` error quality,
    and the ``Experiment`` / ``validate_mesh_args`` front-door checks;
  * in-process parity + the ``shard_act`` (1,N)-mesh regression — need
    >= 2 jax devices (the tier2-sharded CI job provides 8 via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), skipped on
    a single-device box;
  * one subprocess smoke that sets ``XLA_FLAGS`` itself before the
    first jax import, so plain tier-1 on a 1-device box still
    exercises the multi-device paths end to end every run.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro import sharding as SH
from repro.api import registry
from repro.api.experiment import Experiment
from repro.core.engine import validate_mesh_args
from repro.launch.mesh import make_local_mesh
from repro.sharding import build_rules, shard_act, sharding_ctx, spec_for

needs_multi = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 jax devices (XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _amesh(shape, names):
    return jax.sharding.AbstractMesh(tuple(shape), tuple(names))


# ---------------------------------------------------------------------------
# spec_for: size-1 mesh axes carry no parallelism — they must resolve
# to None WITHOUT being consumed (the (1,N)/(N,1) degenerate-mesh bug)
# ---------------------------------------------------------------------------

_CASES = [
    (("batch", "embed"), (16, 64)),
    (("embed", "heads"), (64, 8)),
    (("batch", "heads", "mlp"), (16, 8, 64)),
    (("expert", "embed", "mlp"), (8, 64, 32)),
    (("batch", "kv_seq"), (16, 256)),
]


@pytest.mark.parametrize("shape,names", [
    ((1, 4), ("data", "model")),
    ((4, 1), ("data", "model")),
    ((1, 8), ("data", "model")),
    ((8, 1), ("data", "model")),
    ((2, 2), ("data", "model")),
    ((1, 2, 4), ("pod", "data", "model")),
    ((2, 1, 4), ("pod", "data", "model")),
])
def test_spec_size1_axes_never_appear_never_consumed(shape, names):
    """Grid property over (1,N)/(N,1)/(2,2) meshes: no size-1 mesh axis
    ever appears in a produced spec, every appearing axis is unique,
    and every assignment divides its dimension."""
    mesh = _amesh(shape, names)
    rules = build_rules(mesh)
    sizes = dict(zip(names, shape))
    size1 = {a for a, n in sizes.items() if n == 1}
    for logical, dims in _CASES:
        s = spec_for(logical, dims, mesh, rules)
        flat = []
        for dim, assignment in zip(dims, tuple(s)):
            if assignment is None:
                continue
            axs = (assignment,) if isinstance(assignment, str) \
                else assignment
            flat.extend(axs)
            assert dim % int(np.prod([sizes[a] for a in axs])) == 0
        assert not (set(flat) & size1), (logical, s)
        assert len(flat) == len(set(flat)), (logical, s)


@pytest.mark.parametrize("deg_shape,deg_names,eff_shape,eff_names", [
    ((1, 8), ("data", "model"), (8,), ("model",)),
    ((8, 1), ("data", "model"), (8,), ("data",)),
    ((1, 1, 8), ("pod", "data", "model"), (8,), ("model",)),
])
def test_spec_degenerate_mesh_matches_reduced_mesh(
        deg_shape, deg_names, eff_shape, eff_names):
    """A mesh with size-1 axes must produce exactly the specs of the
    mesh with those axes removed — the regression that used to fail:
    the size-1 axis was assigned (``dim % 1 == 0``) and consumed."""
    deg = _amesh(deg_shape, deg_names)
    eff = _amesh(eff_shape, eff_names)
    dr, er = build_rules(deg), build_rules(eff)
    for logical, dims in _CASES:
        assert spec_for(logical, dims, deg, dr) == \
            spec_for(logical, dims, eff, er), (logical, dims)


def test_resolve_axes_contract():
    mesh = _amesh((1, 8), ("data", "model"))
    # size-1 mesh axes never shard
    assert SH.resolve_axes(mesh, "data", 8) is None
    # ...and are dropped from tuples, leaving the working suffix
    assert SH.resolve_axes(mesh, ("data", "model"), 16) == "model"
    # non-dividing -> replication fallback, never an error
    assert SH.resolve_axes(mesh, "model", 12) is None
    assert SH.resolve_axes(mesh, "model", 16) == "model"
    # no mesh / no request -> no placement
    assert SH.resolve_axes(None, "model", 16) is None
    assert SH.resolve_axes(mesh, None, 16) is None
    m22 = _amesh((2, 2), ("data", "model"))
    assert SH.resolve_axes(m22, ("data", "model"), 8) == ("data", "model")
    assert SH.resolve_axes(m22, ("data", "model"), 6) is None


# ---------------------------------------------------------------------------
# front-door validation
# ---------------------------------------------------------------------------

def test_make_local_mesh_too_few_devices_message():
    avail = len(jax.devices())
    with pytest.raises(ValueError) as ei:
        make_local_mesh(avail + 1, 2)
    msg = str(ei.value)
    assert f"needs {2 * (avail + 1)} device(s)" in msg
    assert f"only {avail} are available" in msg
    assert "xla_force_host_platform_device_count" in msg
    # the degenerate mesh is always constructible
    assert make_local_mesh(1, 1).size == 1


def test_validate_mesh_args_errors():
    mesh = _amesh((2, 4), ("data", "model"))
    with pytest.raises(ValueError, match="without a mesh"):
        validate_mesh_args(None, policy_axes="data")
    with pytest.raises(ValueError, match="only has"):
        validate_mesh_args(mesh, policy_axes="pod")
    with pytest.raises(ValueError, match="claimed by both"):
        validate_mesh_args(mesh, policy_axes="data", seed_axes="data")
    with pytest.raises(ValueError, match="wavefront"):
        validate_mesh_args(mesh, warp_axes="model", engine="event")
    validate_mesh_args(mesh, policy_axes="data", seed_axes="model")


def test_experiment_mesh_axes_without_mesh():
    with pytest.raises(ValueError, match="without a mesh"):
        registry.paper_fig7(("BFS",), name="x").with_(
            mesh_axes=("data", None, None))


# ---------------------------------------------------------------------------
# shard_act (1, N)-mesh regression: len(mesh.devices) measures only the
# first dimension of the device ndarray, so the pre-fix guard treated
# every (1, N) mesh as single-device and constraints silently no-opped
# ---------------------------------------------------------------------------

@needs_multi
def test_shard_act_constrains_on_1xN_mesh():
    n = len(jax.devices())
    mesh = make_local_mesh(1, n)                 # the (1, N) shape
    assert len(mesh.devices) == 1                # the measurement the
    assert mesh.size == n                        # old guard got wrong
    with sharding_ctx(mesh):
        f = jax.jit(lambda x: shard_act(x, "batch", "heads"))
        y = f(jnp.zeros((4, 8 * n)))
    # "heads" -> model must actually shard: pre-fix the constraint
    # no-opped and the output stayed on one device
    assert y.sharding.is_equivalent_to(
        jax.sharding.NamedSharding(mesh, P(None, "model")), 2)
    assert len(y.sharding.device_set) == n


# ---------------------------------------------------------------------------
# end-to-end parity: the sharded Experiment is bitwise-identical to the
# single-device one (golden suites pin the single-device numbers)
# ---------------------------------------------------------------------------

def _bitwise(rs_a, rs_b):
    assert rs_a.scenarios == rs_b.scenarios
    assert rs_a.policies == rs_b.policies
    for name in rs_a.scenarios:
        for seed in rs_a.seeds(name):
            ma = rs_a.get(name, seed=seed)
            mb = rs_b.get(name, seed=seed)
            assert set(ma) == set(mb)
            for k in ma:
                np.testing.assert_array_equal(
                    np.asarray(ma[k]), np.asarray(mb[k]),
                    err_msg=f"{name} seed={seed} metric={k}")


def _mesh2d():
    n = len(jax.devices())
    pow2 = 1 << (n.bit_length() - 1)
    return make_local_mesh(2, pow2 // 2) if pow2 >= 4 \
        else make_local_mesh(1, pow2)


@needs_multi
def test_event_sharded_parity_fig7_quick():
    exp = registry.paper_fig7(registry.QUICK_WORKLOADS, seeds=(0, 1),
                              name="parity_ev")
    sh = exp.with_(mesh=_mesh2d(), mesh_axes=("data", "model", None))
    _bitwise(exp.run(), sh.run())


@needs_multi
def test_wavefront_sharded_parity_phased48():
    exp = registry.phased(("PHASED48",), name="parity_wf")
    sh = exp.with_(mesh=_mesh2d(), mesh_axes=("data", None, "model"))
    call = sh.compile().calls[0]
    assert call.mesh is not None and call.warp_axes == "model"
    _bitwise(exp.run(), sh.run())


@needs_multi
def test_event_sharded_parity_phased48():
    exp = registry.phased(("PHASED48",), engine="event",
                          name="parity_ev48")
    sh = exp.with_(mesh=_mesh2d(), mesh_axes=("data", None, None))
    _bitwise(exp.run(), sh.run())


@needs_multi
def test_nondividing_axes_fall_back_to_replication():
    """3 policies on a 2-wide mesh axis, 1-entry seed stack: every
    placement resolves to None, the plan still runs, and results match
    the mesh-less run bitwise."""
    from repro.core import baselines as BL
    exp = Experiment("parity_fb",
                     registry.paper_fig7(("BFS",)).scenarios,
                     (BL.BASELINE, BL.PCAL, BL.MEDIC))
    sh = exp.with_(mesh=_mesh2d(), mesh_axes=("data", "model", None))
    call = sh.compile().calls[0]
    assert call.policy_axes is None and call.seed_axes is None
    _bitwise(exp.run(), sh.run())


# ---------------------------------------------------------------------------
# subprocess smoke: gives plain tier-1 (single-device) real multi-device
# coverage — XLA_FLAGS must be set before the first jax import, so this
# cannot be an in-process fixture
# ---------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os, re
    # the inherited env may already force a device count (e.g. the
    # 512-device dry-run suite exports XLA_FLAGS into the pytest
    # process) — strip it and put ours LAST so it wins
    flags = re.sub(r"--xla_force_host_platform_device_count=\\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")
    import jax, numpy as np
    assert len(jax.devices()) == 8, jax.devices()
    from repro.api import registry
    from repro.core import baselines as BL
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh(2, 4)
    ev = registry.paper_fig7(("BFS",), seeds=(0, 1), name="sm_ev").with_(
        policies=(BL.BASELINE, BL.PCAL, BL.WBYP, BL.MEDIC))
    wf = registry.phased(("PHASED48",), name="sm_wf")
    for exp, axes in ((ev, ("data", "model", None)),
                      (wf, ("data", None, "model"))):
        rs0 = exp.run()
        rs1 = exp.with_(mesh=mesh, mesh_axes=axes).run()
        for name in rs0.scenarios:
            for seed in rs0.seeds(name):
                a, b = rs0.get(name, seed=seed), rs1.get(name, seed=seed)
                for k in a:
                    assert np.array_equal(
                        np.asarray(a[k]), np.asarray(b[k]),
                        equal_nan=True), (exp.name, name, seed, k)
    print("SHARDED_PARITY_OK")
""")


def test_multi_device_parity_subprocess(tmp_path):
    script = tmp_path / "sharded_smoke.py"
    script.write_text(_SUBPROC)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), os.pardir, "src"),
         env.get("PYTHONPATH", "")])
    # the child never touches a chip: this process may hold it
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_PARITY_OK" in out.stdout
