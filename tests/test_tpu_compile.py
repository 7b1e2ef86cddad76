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


def _ops_in_scope(hlo: str, scope: str, kind: str = "gather"):
    """Element counts of the ``kind`` ops (``gather`` or ``scatter``)
    the compiled module ``hlo`` runs under the named scope ``scope``:
    each such op, and each fusion whose computation holds one, that
    has ``scope`` as a component of its ``op_name`` path or sits in a
    computation a scoped op calls (a loop body, say). The TPU compiler
    drops the metadata of scatter fusions, so inside a scoped loop they
    are found the second way."""
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
             if any(re.search(rf"\b{kind}\(", l) for l in ls)}
    under = re.compile(r'op_name="[^"]*(?:^|/)' + scope + r'(?:/|")')
    callee = re.compile(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)")
    inside, todo = set(), [c for ls in comps.values() for l in ls
                           if under.search(l) for c in callee.findall(l)]
    while todo:
        c = todo.pop()
        if c in comps and c not in inside:
            inside.add(c)
            todo += [x for l in comps[c] for x in callee.findall(l)]
    out = []
    for name, ls in comps.items():
        for l in ls:
            op = re.match(r"%\S+ = (.+?) (\w[\w-]*)\(", l)
            if not op or not (name in inside or under.search(l)):
                continue
            called = re.search(r"calls=%([\w.\-]+)", l)
            if op.group(2) == kind or (
                    op.group(2) == "fusion" and called
                    and called.group(1) in holds):
                dims = re.search(r"\w+\[([\d,]*)\]", op.group(1))
                out.append(int(np.prod([int(d) for d in
                                        dims.group(1).split(",") if d])))
    return out


def test_timing_pass_gathers_nothing_per_slot(bfs_2k_hlo):
    """The benchmark cell's sweep (2,048 warps x 4 policies, 12 L2
    banks, 6 DRAM channels, waves of 512 warps x 16 lanes): the timing
    pass takes every per-slot value with one-hot queue masks and scans,
    so its compiled form holds no gather over the wave's slots."""
    hlo, slots = bfs_2k_hlo
    assert "timing_pass" in hlo
    per_slot = [n for n in _ops_in_scope(hlo, "timing_pass")
                if n % slots == 0]
    assert per_slot == [], per_slot


@pytest.fixture(scope="module")
def bfs_2k_hlo(one_chip, no_persistent_cache):
    """The benchmark cell's sweep compiled for the described chip:
    HAMMER2K's 2,048 warps x 4 policies on MeDiC's Table 1 GPU (384 sets
    x 16 ways, 12 L2 banks, 6 DRAM channels), waves of 512 warps x 16
    lanes."""
    exp = registry.stress(scenarios=("HAMMER2K",)).with_(
        prm=SimParams(sets=384, ways=16, banks=12, l2_lat=10.0,
                      dram_channels=6))
    (call,) = exp.compile().calls
    _, n_warps, lanes = call.shape
    B = WAVE.resolve_wave_size(None, n_warps)
    assert (n_warps, lanes, B) == (2048, 16, 512)
    return _compile(exp, one_chip).as_text(), B * lanes


def test_wide_cache_pass_gathers_no_rows(bfs_2k_hlo):
    """The same sweep's wide cache pass (waves of 512 >= the wide
    threshold) reads the set table, the PC counters and the EAF only by
    one-hot contractions and writes them by masked reductions, so its
    compiled form, lane loop included, holds no gather and no scatter:
    no set-indexed row gather, no chronology-pointer scatter."""
    hlo, _ = bfs_2k_hlo
    assert 512 >= CPASS.WIDE_WAVE_MIN_B
    assert "cache_pass/while" in hlo
    for kind in ("gather", "scatter"):
        found = _ops_in_scope(hlo, "cache_pass", kind)
        assert found == [], (kind, found)


def test_wide_cache_pass_compiles_at_64k_warp_width(one_chip,
                                                    no_persistent_cache):
    """The widest wave the repo runs, WIDE64K's (65,536 warps, waves of
    16,384 x 16 lanes, default SimParams), through the wide cache pass
    alone: its [B, sets] one-hot tables still compile for one v5e, and
    without a gather or a scatter. Shapes only: no 64k-warp trace is
    generated."""
    from repro.core import baselines as BL
    from repro.core.engine.state import init_state
    from repro.core.tracegen.stress import SHARD_STRESS_SPECS
    from repro.policy import ops as POL, to_arrays

    spec = SHARD_STRESS_SPECS["WIDE64K"]
    n_warps, lanes = spec.n_warps, spec.lines_per_instr
    B = WAVE.resolve_wave_size(None, n_warps)
    assert (n_warps, lanes, B) == (65536, 16, 16384)
    prm = SimParams()
    pa = to_arrays(BL.MEDIC)
    shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                           sharding=one_chip)
    per_slot = lambda dtype: shape(  # noqa: E731
        jax.ShapeDtypeStruct((B,), dtype))
    st = jax.eval_shape(lambda: init_state(n_warps, prm))
    tokens = jax.eval_shape(lambda: POL.pcal_tokens(pa, n_warps))
    args = (jax.tree.map(shape, st),
            jax.tree.map(lambda a: per_slot(a.dtype), st.clf),
            per_slot(tokens.dtype), per_slot(np.float32),
            shape(jax.ShapeDtypeStruct((lanes, B), np.int32)),
            per_slot(np.int32), per_slot(np.int32), per_slot(np.bool_),
            jax.tree.map(lambda a: shape(np.asarray(a)), pa))

    def cache_pass(st, clf_b0, tokens_b, t0, addr_lb, pc_b, owt_b,
                   slot_ok, pa):
        return CPASS.wave_cache_pass(st, clf_b0, tokens_b, t0, addr_lb,
                                     pc_b, owt_b, slot_ok, prm, pa,
                                     backend="fused")

    # the chip's compiler refuses a program that does not fit
    hlo = jax.jit(cache_pass).lower(*args).compile().as_text()
    assert " gather(" not in hlo and " scatter(" not in hlo
