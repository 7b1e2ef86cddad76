"""Wavefront simulation engine subsystem (ISSUE 3 tentpole).

Two interchangeable engines behind one API (DESIGN.md §9):

  * ``engine/event.py``     — the exact discrete-event reference loop
    (one earliest-ready warp per scan step; O(I·W·L) sequential);
  * ``engine/wavefront.py`` — the batched round-lockstep event loop
    (a wave of the ``wave_size`` earliest-ready warps per scan step,
    queue semantics recovered with sort-by-arrival + segmented prefix
    ops; runs the 1k–4k-warp stress matrix end-to-end);
  * ``engine/state.py``     — SimParams / SimState / init shared by both;
  * ``engine/request.py``   — per-request math shared by both.

``simulate`` / ``simulate_sweep`` keep their historical signatures and
grow an ``engine=`` argument; the default (``"event"``) is byte-identical
to the pre-split simulator, which the golden fig7 suite pins.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core.engine import event as _event
from repro.core.engine import wavefront as _wavefront
from repro.core.engine.state import (N_QBINS, SimParams, SimState,
                                     init_state)
from repro.kernels.cache_pass.ops import BACKENDS as CACHE_BACKENDS
from repro.kernels.wavefront_scan.ops import BACKENDS as SCAN_BACKENDS
from repro.policy import Policy, stack_policies, to_arrays

ENGINES = ("event", "wavefront")


def validate_engine_args(engine: str, wave_size: Optional[int] = None,
                         scan_backend: str = "auto",
                         cache_backend: str = "auto") -> None:
    """Front-door validation shared by ``simulate``/``simulate_sweep`` and
    the declarative ``repro.api`` layer.

    Raises ``ValueError`` for an unknown engine, and — instead of silently
    ignoring it — for a ``wave_size``, non-default ``scan_backend`` or
    non-default ``cache_backend`` passed to any engine that does not
    consume one (only ``"wavefront"`` does). Catching a bad backend
    string here, before any tracing starts, is what keeps the failure a
    one-line ``ValueError`` with the allowed set instead of a shape
    error deep inside jit.
    """
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; choose from {ENGINES}")
    if wave_size is not None:
        if engine != "wavefront":
            raise ValueError(
                f"wave_size={wave_size!r} is only meaningful with "
                f"engine='wavefront'; engine={engine!r} would silently "
                f"ignore it")
        if wave_size != int(wave_size):
            raise ValueError(
                f"wave_size must be an integer, got {wave_size!r}")
        if wave_size < 1:
            raise ValueError(f"wave_size must be >= 1, got {wave_size!r}")
    if scan_backend not in SCAN_BACKENDS:
        raise ValueError(
            f"unknown scan_backend {scan_backend!r}; choose from "
            f"{SCAN_BACKENDS}")
    if scan_backend != "auto" and engine != "wavefront":
        raise ValueError(
            f"scan_backend={scan_backend!r} is only meaningful with "
            f"engine='wavefront'; engine={engine!r} would silently "
            f"ignore it")
    if cache_backend not in CACHE_BACKENDS:
        raise ValueError(
            f"unknown cache_backend {cache_backend!r}; choose from "
            f"{CACHE_BACKENDS}")
    if cache_backend != "auto" and engine != "wavefront":
        raise ValueError(
            f"cache_backend={cache_backend!r} is only meaningful with "
            f"engine='wavefront'; engine={engine!r} would silently "
            f"ignore it")


def validate_mesh_args(mesh, policy_axes=None, seed_axes=None,
                       warp_axes=None, engine: str = "event") -> None:
    """Front-door validation for the multi-device sweep knobs.

    Mesh-axis assignments without a mesh, axis names the mesh does not
    carry, one mesh axis claimed by two sweep axes, and warp-axis
    sharding on an engine without a sharded-warp path all fail here with
    a one-line ``ValueError`` — before any device placement or tracing.
    (Divisibility is NOT validated: an axis product that does not divide
    its dimension falls back to replication, ``sharding.resolve_axes``.)
    """
    from repro import sharding as SH
    named = {"policy_axes": SH.norm_axes(policy_axes),
             "seed_axes": SH.norm_axes(seed_axes),
             "warp_axes": SH.norm_axes(warp_axes)}
    if mesh is None:
        given = [k for k, v in named.items() if v is not None]
        if given:
            raise ValueError(f"{', '.join(given)} given without a mesh; "
                             "pass mesh= as well")
        return
    present = set(mesh.axis_names)
    for k, axes in named.items():
        for a in axes or ():
            if a not in present:
                raise ValueError(
                    f"{k} names mesh axis {a!r} but the mesh only has "
                    f"axes {tuple(mesh.axis_names)}")
    claimed: dict = {}
    for k, axes in named.items():
        for a in axes or ():
            if a in claimed:
                raise ValueError(
                    f"mesh axis {a!r} is claimed by both {claimed[a]} "
                    f"and {k}; each sweep axis needs its own mesh axes")
            claimed[a] = k
    if named["warp_axes"] is not None and engine != "wavefront":
        raise ValueError(
            f"warp_axes={warp_axes!r} is only meaningful with "
            f"engine='wavefront' (the sharded-warp path); "
            f"engine={engine!r} would silently ignore it")


def _core(engine: str, wave_size: Optional[int], scan_backend: str,
          cache_backend: str, warp_mesh=None, warp_axes=None):
    validate_engine_args(engine, wave_size, scan_backend, cache_backend)
    if engine == "event":
        return _event.simulate_core
    return partial(_wavefront.simulate_core, wave_size=wave_size,
                   scan_backend=scan_backend, cache_backend=cache_backend,
                   warp_mesh=warp_mesh, warp_axes=warp_axes)


def _oracle_or_zeros(oracle_types, trace_lines, policies):
    """Resolve the ground-truth label input. A policy with
    labeling="oracle" READS these labels, so omitting them there is a
    caller error (zeros would silently label every warp all-miss);
    otherwise the labels are never read and a zero placeholder keeps the
    jit signature uniform. Shape follows the trace minus lanes."""
    if oracle_types is not None:
        return oracle_types
    needs = [p.name for p in policies if p.labeling == "oracle"]
    if needs:
        raise ValueError(
            f"policies {needs} use labeling='oracle' but no oracle_types "
            "were passed; supply the trace's 'oracle_wtype' array "
            "(repro.core.tracegen emits it for every spec)")
    return jnp.zeros(trace_lines.shape[:-1], jnp.int32)


@partial(jax.jit,
         static_argnames=("prm", "n_warps", "lanes", "engine", "wave_size",
                          "scan_backend", "cache_backend", "warp_mesh",
                          "warp_axes"))
def _simulate_one(trace_lines, trace_pcs, compute_gap, oracle_types, pa, *,
                  n_warps: int, lanes: int, prm: SimParams,
                  engine: str = "event",
                  wave_size: Optional[int] = None,
                  scan_backend: str = "auto",
                  cache_backend: str = "auto",
                  warp_mesh=None, warp_axes=None) -> Dict[str, Any]:
    core = _core(engine, wave_size, scan_backend, cache_backend,
                 warp_mesh, warp_axes)
    return core(trace_lines, trace_pcs, compute_gap, oracle_types, pa,
                n_warps=n_warps, lanes=lanes, prm=prm)


@partial(jax.jit,
         static_argnames=("prm", "n_warps", "lanes", "engine", "wave_size",
                          "scan_backend", "cache_backend", "warp_mesh",
                          "warp_axes"))
def _simulate_batch(trace_lines, trace_pcs, compute_gap, oracle_types,
                    pa_batch, *, n_warps: int, lanes: int, prm: SimParams,
                    engine: str = "event",
                    wave_size: Optional[int] = None,
                    scan_backend: str = "auto",
                    cache_backend: str = "auto",
                    warp_mesh=None, warp_axes=None):
    one = partial(_core(engine, wave_size, scan_backend, cache_backend,
                        warp_mesh, warp_axes),
                  n_warps=n_warps, lanes=lanes, prm=prm)
    if trace_lines.ndim == 4:      # seed-stacked traces [S, I, W, L]
        over_seeds = jax.vmap(one, in_axes=(0, 0, 0, 0, None))
        return jax.vmap(over_seeds, in_axes=(None, None, None, None, 0))(
            trace_lines, trace_pcs, compute_gap, oracle_types, pa_batch)
    return jax.vmap(one, in_axes=(None, None, None, None, 0))(
        trace_lines, trace_pcs, compute_gap, oracle_types, pa_batch)


def simulate(trace_lines, trace_pcs, compute_gap, *, n_warps: int,
             lanes: int, prm: SimParams, pol: Policy,
             engine: str = "event", wave_size: Optional[int] = None,
             scan_backend: str = "auto", cache_backend: str = "auto",
             oracle_types=None, mesh=None, warp_axes=None
             ) -> Dict[str, Any]:
    """Run one workload under one policy.

    ``engine="event"`` (default) is the exact discrete-event reference:
    each outer step pops the globally earliest ready warp, so queue
    counters are updated chronologically (up to intra-instruction lane
    skew). ``engine="wavefront"`` batches ``wave_size`` earliest-ready
    warps per step (default ``max(min(W, 8), W//6)``, widening to
    ``W//4`` above 256 warps — see ``wavefront.default_wave_size``) —
    within the documented tolerance of the event path (DESIGN.md §9)
    and the only path that completes the tracegen stress matrix.

    The policy enters as a traced `PolicyArrays`, so every `Policy` preset
    reuses the same compiled executable for a given workload shape.

    ``scan_backend`` selects the wavefront timing-pass implementation
    (``repro.kernels.wavefront_scan``) and ``cache_backend`` the
    cache-pass one (``repro.kernels.cache_pass``): ``"auto"`` (default)
    picks the fused one-sweep path on every platform, output-identical
    to ``"ref"``, the unfused pre-fusion form kept for in-run perf A/Bs;
    ``"pallas"`` is an opt-in the TPU compiler refuses today. The two
    knobs compose freely.

    trace_lines: i32[I, W, L]; trace_pcs: i32[I, W]; compute_gap: f32
    scalar or f32[I] (phased per-instruction intensity); oracle_types:
    optional i32[I, W] ground-truth labels — required (pass the trace's
    ``oracle_wtype``) when the policy's labeling mode is "oracle".
    Returns metrics dict (all jnp arrays).

    ``mesh`` + ``warp_axes`` enable the wavefront engine's sharded-warp
    path: the warp axis of the trace arrays and the per-warp machine
    state is constrained to those mesh axes (replication fallback when
    the axis product does not divide ``n_warps``). Output-identical to
    the unsharded run — sharding is placement, never semantics.
    """
    validate_engine_args(engine, wave_size, scan_backend, cache_backend)
    validate_mesh_args(mesh, warp_axes=warp_axes, engine=engine)
    from repro import sharding as SH
    w_res = SH.resolve_axes(mesh, warp_axes, n_warps)
    return _simulate_one(trace_lines, trace_pcs, compute_gap,
                         _oracle_or_zeros(oracle_types, trace_lines,
                                          (pol,)),
                         to_arrays(pol), n_warps=n_warps, lanes=lanes,
                         prm=prm, engine=engine, wave_size=wave_size,
                         scan_backend=scan_backend,
                         cache_backend=cache_backend,
                         warp_mesh=mesh if w_res is not None else None,
                         warp_axes=w_res)


def simulate_sweep(trace_lines, trace_pcs, compute_gap,
                   policies: Sequence[Policy], *, n_warps: int, lanes: int,
                   prm: SimParams, engine: str = "event",
                   wave_size: Optional[int] = None,
                   scan_backend: str = "auto",
                   cache_backend: str = "auto",
                   oracle_types=None, mesh=None, policy_axes=None,
                   seed_axes=None, warp_axes=None) -> Dict[str, Any]:
    """Run a whole policy sweep in ONE jitted, vmapped call.

    trace_lines may be [I, W, L] (one workload instance — outputs get a
    leading policy axis P) or seed-stacked [S, I, W, L] (outputs get
    leading axes [P, S]); trace_pcs/compute_gap/oracle_types follow suit
    (compute_gap gains a trailing [I] axis for phased specs whose
    schedule varies intensity).

    ``oracle_types`` (i32[(S,) I, W], the trace's ``oracle_wtype``) is
    only read by policies with labeling="oracle" — passing it lets one
    vmapped sweep compare oracle / online / stale labelings.

    Multi-device placement (``mesh`` + any of the three axis knobs):
    ``policy_axes`` shards the stacked policy axis of the traced
    ``PolicyArrays``, ``seed_axes`` the seed-stack axis of the trace
    arrays, and ``warp_axes`` the warp axis INSIDE the wavefront engine
    (trace storage + per-warp machine state). Every (policy, seed) cell
    of the vmapped sweep is an independent simulation, so batch-axis
    sharding is pure data parallelism and the outputs are bitwise
    identical to the unsharded call (pinned by
    tests/test_sharded_sweep.py). Any axis whose mesh product does not
    divide its dimension falls back to replication.

    Metrics match per-policy `simulate` calls bit-for-bit on either
    engine (the parity is enforced by tests/test_policy_engine.py).
    """
    validate_engine_args(engine, wave_size, scan_backend, cache_backend)
    validate_mesh_args(mesh, policy_axes, seed_axes, warp_axes, engine)
    pa = stack_policies(policies)
    oracle = _oracle_or_zeros(oracle_types, trace_lines, policies)
    w_res = None
    if mesh is not None:
        from repro import sharding as SH
        p_res = SH.resolve_axes(mesh, policy_axes, len(policies))
        pa = jax.tree.map(lambda a: SH.put_leading(a, mesh, p_res), pa)
        s_res = None
        if jnp.ndim(trace_lines) == 4:     # seed-stacked [S, I, W, L]
            s_res = SH.resolve_axes(mesh, seed_axes,
                                    trace_lines.shape[0])
        trace_lines = SH.put_leading(trace_lines, mesh, s_res)
        trace_pcs = SH.put_leading(trace_pcs, mesh, s_res)
        oracle = SH.put_leading(oracle, mesh, s_res)
        gap_res = s_res if jnp.ndim(compute_gap) >= 1 else None
        compute_gap = SH.put_leading(compute_gap, mesh, gap_res)
        w_res = SH.resolve_axes(mesh, warp_axes, n_warps)
    return _simulate_batch(trace_lines, trace_pcs, compute_gap, oracle,
                           pa, n_warps=n_warps, lanes=lanes, prm=prm,
                           engine=engine, wave_size=wave_size,
                           scan_backend=scan_backend,
                           cache_backend=cache_backend,
                           warp_mesh=mesh if w_res is not None else None,
                           warp_axes=w_res)


__all__ = [
    "CACHE_BACKENDS", "ENGINES", "N_QBINS", "SCAN_BACKENDS", "SimParams",
    "SimState", "init_state", "simulate", "simulate_sweep",
    "validate_engine_args", "validate_mesh_args",
]
