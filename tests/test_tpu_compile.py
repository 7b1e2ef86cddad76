"""Ahead-of-time compiles of the simulator's main path for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests catch what the chip's compiler
would refuse (an op Mosaic cannot lower, a program that does not fit)
without a chip. They compile the two engines' jitted sweep at the
widths users run; nothing executes, so they say nothing about results
or times.

The topology is described only inside a fixture: one process at a time
may load the TPU library, and a test file that loads it while being
imported would break multi-worker collection.
"""
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api import Scenario, registry
from repro.core.engine import SimParams, _simulate_batch
from repro.core.engine import wavefront as WAVE
from repro.kernels.cache_pass import ops as CPASS
from repro.kernels.wavefront_scan import ops as WSCAN
from repro.policy import stack_policies

_TRACE_KEYS = ("lines", "pcs", "compute_gap", "oracle_wtype")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(exp, sharding, **engine_kw):
    """Lower and compile the one jitted sweep call ``exp`` plans, with
    its real trace shapes placed on the described chip."""
    (call,) = exp.compile().calls
    parts = [s.materialize() for s in call.scenarios]
    shape = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding)
    tr = [shape(np.concatenate([p[k] for p in parts]))
          for k in _TRACE_KEYS]
    pa = jax.tree.map(lambda a: shape(np.asarray(a)),
                      stack_policies(exp.policies))
    _, n_warps, lanes = call.shape
    return _simulate_batch.lower(
        *tr, pa, n_warps=n_warps, lanes=lanes, prm=exp.prm,
        engine=exp.engine, **engine_kw).compile()


def test_event_engine_compiles_for_v5e(one_chip, no_persistent_cache):
    """The paper suite: 15 workloads of 48 warps stacked on the seed
    axis, the whole fig7 policy batch vmapped — one executable."""
    exp = registry.PAPER_FIG7
    assert {s.shape[1] for s in exp.scenarios} == {48}
    compiled = _compile(exp, one_chip)
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_paper_suite_wavefront_compiles_for_v5e(one_chip,
                                                no_persistent_cache):
    """The paper suite on the wavefront engine at its default wave (8 of
    48 warps), on MeDiC's Table 1 GPU: the narrow fused cache pass with
    every Fig 7 policy's integer decisions, no kernel."""
    exp = registry.PAPER_FIG7.with_(
        engine="wavefront",
        prm=SimParams(sets=384, ways=16, banks=12, l2_lat=10.0,
                      dram_channels=6))
    assert WAVE.default_wave_size(48) < CPASS.WIDE_WAVE_MIN_B
    compiled = _compile(exp, one_chip)
    assert "tpu_custom_call" not in compiled.as_text()


def test_wavefront_fused_compiles_for_v5e(one_chip, no_persistent_cache):
    """The stress path at 2048 warps on the fused backends."""
    exp = registry.stress(scenarios=("HAMMER2K",))
    assert exp.scenarios[0].shape[1] == 2048
    compiled = _compile(exp, one_chip, scan_backend="fused",
                        cache_backend="fused")
    assert "tpu_custom_call" not in compiled.as_text()


def test_auto_backends_hold_no_kernel_the_chip_refuses(
        one_chip, no_persistent_cache):
    """``auto`` resolves to ``fused`` on every platform, so the default
    chip path carries no Pallas custom call (both kernels are refused by
    Mosaic today, DESIGN.md §12/§13)."""
    assert WSCAN.resolve_backend("auto") == "fused"
    assert CPASS.resolve_backend("auto") == "fused"
    exp = registry.PAPER_PHASED.with_(
        scenarios=(Scenario.phased("PHASED48"),))
    compiled = _compile(exp, one_chip, scan_backend="auto",
                        cache_backend="auto")
    assert "tpu_custom_call" not in compiled.as_text()


def _gathers_in_scope(hlo: str, scope: str):
    """Element counts of the gathers the compiled module ``hlo`` runs
    under the named scope ``scope``: each gather fusion (a fusion whose
    computation holds a gather) and each bare gather whose ``op_name``
    path has ``scope`` as a component."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and line.strip():
            cur.append(line.strip())
    holds = {c for c, ls in comps.items()
             if any(re.search(r"\bgather\(", l) for l in ls)}
    under = re.compile(r'op_name="[^"]*(?:^|/)' + scope + r'(?:/|")')
    out = []
    for ls in comps.values():
        for l in ls:
            op = re.match(r"%\S+ = (\w+)\[([\d,]*)\]\S* (\w[\w-]*)\(", l)
            if not op or not under.search(l):
                continue
            called = re.search(r"calls=%([\w.\-]+)", l)
            if op.group(3) == "gather" or (
                    op.group(3) == "fusion" and called
                    and called.group(1) in holds):
                out.append(int(np.prod([int(d) for d in op.group(2).split(",")
                                        if d])))
    return out


def test_timing_pass_gathers_nothing_per_slot(one_chip, no_persistent_cache):
    """The benchmark cell's sweep (2,048 warps x 4 policies, 12 L2
    banks, 6 DRAM channels, waves of 512 warps x 16 lanes): the timing
    pass takes every per-slot value with one-hot queue masks and scans,
    so its compiled form holds no gather over the wave's slots."""
    exp = registry.stress(scenarios=("HAMMER2K",)).with_(
        prm=SimParams(sets=384, ways=16, banks=12, l2_lat=10.0,
                      dram_channels=6))
    (call,) = exp.compile().calls
    _, n_warps, lanes = call.shape
    slots = WAVE.resolve_wave_size(None, n_warps) * lanes
    assert (n_warps, lanes, slots) == (2048, 16, 8192)
    hlo = _compile(exp, one_chip).as_text()
    assert "timing_pass" in hlo
    per_slot = [n for n in _gathers_in_scope(hlo, "timing_pass")
                if n % slots == 0]
    assert per_slot == [], per_slot
