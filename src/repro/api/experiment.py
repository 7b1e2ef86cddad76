"""Experiment: scenarios × policies × engine, compiled to a minimal Plan.

The declarative front door (DESIGN.md §10):

    exp  = Experiment("fig7", scenarios, policies, engine="event")
    plan = exp.compile()     # inspectable, no traces materialized yet
    rs   = plan.execute()    # == exp.run()

The **plan compiler** buckets scenarios by trace shape (I, W, L): every
scenario in a bucket rides the seed-stack axis of ONE jitted
``simulate_sweep`` call (policies vmapped on the leading axis), so the
whole experiment runs in exactly one call per (shape, engine) bucket —
the invariant the seed-era callers each re-implemented by hand.
Executables are further shared ACROSS buckets (and across experiments)
whenever the jit compile key — (shape, flat batch size, policy count,
engine, wave_size, scan_backend, cache_backend, SimParams) — agrees,
because ``simulate_sweep``'s
underlying jit cache is keyed on exactly those; the plan reports that
via ``n_executables``.

A single-scenario experiment lowers to the identical call the seed-era
positional idiom made (same trace arrays, same stacking), which is what
keeps the golden fig7 suite byte-identical through the migration.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro import sharding as SH
from repro.api.results import ResultBlock, ResultSet, WaveCounts
from repro.api.scenario import Scenario, Shape
from repro.core.engine import (SimParams, simulate_sweep,
                               validate_engine_args, validate_mesh_args)
from repro.core.engine.wavefront import resolve_wave_size
from repro.policy import Policy

_TRACE_KEYS = ("lines", "pcs", "compute_gap", "archetype", "oracle_wtype")


@contextlib.contextmanager
def span(spans: Dict[str, float], name: str):
    """A program span: its host-clock seconds go into ``spans[name]``,
    and a profiler annotation of the same name puts it on the clock of
    a device trace, where one is being taken."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        yield
        spans[name] = time.perf_counter() - t0


@dataclasses.dataclass(frozen=True)
class PlanCall:
    """One emitted ``simulate_sweep`` call: a (shape, engine) bucket.
    Serving buckets run the (host-side, unjitted) serving simulator
    instead; their shape is ``(-1, max_slots, n_requests)``.

    ``mesh`` + the three axis fields are the bucket's RESOLVED
    multi-device placement (``None`` everywhere on single-device plans):
    the plan compiler applies the replication fallback per bucket —
    a seed-stack or warp count the mesh axis does not divide resolves
    to ``None`` here, so ``describe()`` and ``compile_key`` reflect
    what will actually shard, not what was asked for."""
    shape: Shape                       # (n_instr, n_warps, lines_per_instr)
    engine: str
    wave_size: Optional[int]
    scan_backend: str
    cache_backend: str
    scenarios: Tuple[Scenario, ...]    # seed blocks stack in this order
    mesh: Optional[object] = None      # jax.sharding.Mesh
    policy_axes: Optional[object] = None
    seed_axes: Optional[object] = None
    warp_axes: Optional[object] = None

    @property
    def flat(self) -> int:
        """Stacked trace count of the call (sum of scenario seed counts)."""
        return sum(s.n_seeds for s in self.scenarios)

    def compile_key(self, n_policies: int, prm: SimParams) -> tuple:
        """Everything ``simulate_sweep``'s jit cache keys on: two calls
        with equal keys share one compiled executable."""
        return (self.shape, self.flat, n_policies, self.engine,
                self.wave_size, self.scan_backend, self.cache_backend, prm,
                self.mesh, self.policy_axes, self.seed_axes,
                self.warp_axes)

    def execute_serving(self, exp: "Experiment") -> ResultBlock:
        """Run the serving simulator over this bucket: every (scenario,
        seed) request stream under every policy, metrics stacked to the
        standard ``[P, F]`` layout. One stream is generated per entry
        and shared across policies, so an A/B always compares on the
        IDENTICAL arrival sequence."""
        from repro.serving.sim import generate_serving, simulate_serving
        spans: Dict[str, float] = {}
        with span(spans, "repro.call"):
            entries: List[Tuple[str, int]] = []
            cols: List[List[Dict[str, float]]] = []   # [F][P] metric dicts
            for s in self.scenarios:
                for seed in s.seeds:
                    reqs = generate_serving(s.spec, seed)
                    entries.append((s.name, seed))
                    cols.append([simulate_serving(
                        reqs, s.spec, policy=pol,
                        pool_backend=exp.pool_backend)["metrics"]
                        for pol in exp.policies])
            metrics = {k: np.asarray(
                [[cols[f][p][k] for f in range(len(entries))]
                 for p in range(len(exp.policies))], np.float64)
                for k in cols[0][0]}
        return ResultBlock(tuple(entries), metrics, spans["repro.call"],
                           spans=spans)


@dataclasses.dataclass(frozen=True)
class Plan:
    """Compiled experiment: the minimal list of jitted calls to make."""
    experiment: "Experiment"
    calls: Tuple[PlanCall, ...]

    @property
    def n_calls(self) -> int:
        """Jitted calls to make — one per (trace-shape, engine) bucket,
        so this IS the bucket count."""
        return len(self.calls)

    @property
    def n_executables(self) -> int:
        """Distinct jit compile keys — calls beyond this reuse an
        executable compiled for an earlier bucket."""
        exp = self.experiment
        return len({c.compile_key(len(exp.policies), exp.prm)
                    for c in self.calls})

    def describe(self) -> str:
        exp = self.experiment
        lines = [f"plan[{exp.name}]: {len(exp.scenarios)} scenarios x "
                 f"{len(exp.policies)} policies -> {self.n_calls} call(s), "
                 f"{self.n_executables} executable(s)"]
        for c in self.calls:
            i, w, l = c.shape
            names = ", ".join(f"{s.name}x{s.n_seeds}" for s in c.scenarios)
            if c.engine == "serving":
                lines.append(f"  [serving] slots={w} requests={l} "
                             f"flat={c.flat}: {names}")
            else:
                shard = ""
                if c.mesh is not None:
                    shard = (f" sharded(policy={c.policy_axes} "
                             f"seed={c.seed_axes} warp={c.warp_axes})")
                lines.append(f"  [{c.engine}] shape I={i} W={w} L={l} "
                             f"flat={c.flat}{shard}: {names}")
        return "\n".join(lines)

    def execute(self, keep_traces: bool = False) -> ResultSet:
        """Materialize traces and run every planned call.

        Each call records two program spans in its block:
        ``repro.tracegen`` (materializing its traces) and ``repro.call``
        (the engine call and the copy of its results back, the interval
        ``wall_s`` times); a serving call records ``repro.call`` only."""
        exp = self.experiment
        blocks: List[ResultBlock] = []
        for call in self.calls:
            if call.engine == "serving":
                blocks.append(call.execute_serving(exp))
                continue
            n_instr, n_warps, lanes = call.shape
            spans: Dict[str, float] = {}
            with span(spans, "repro.tracegen"):
                parts = [s.materialize() for s in call.scenarios]
            # a bucket may mix constant-intensity scenarios (scalar gap
            # per seed, [S]) with phased ones ([S, I]): broadcast the
            # scalars so the stacked axis is uniform
            if any(p["compute_gap"].ndim == 2 for p in parts):
                for p in parts:
                    g = p["compute_gap"]
                    if g.ndim == 1:
                        p["compute_gap"] = np.broadcast_to(
                            g[:, None], (g.shape[0], n_instr))
            tr = {k: np.concatenate([p[k] for p in parts])
                  for k in _TRACE_KEYS}
            counters: Dict[str, object] = {}
            with span(spans, "repro.call"):
                out = simulate_sweep(
                    np.asarray(tr["lines"]), np.asarray(tr["pcs"]),
                    np.asarray(tr["compute_gap"]), exp.policies,
                    n_warps=n_warps, lanes=lanes, prm=exp.prm,
                    engine=call.engine, wave_size=call.wave_size,
                    scan_backend=call.scan_backend,
                    cache_backend=call.cache_backend,
                    oracle_types=np.asarray(tr["oracle_wtype"]),
                    mesh=call.mesh, policy_axes=call.policy_axes,
                    seed_axes=call.seed_axes, warp_axes=call.warp_axes,
                    counters=counters)
                out = {k: np.asarray(v)                   # [P, F, ...]
                       for k, v in out.items()}
            waves = None
            if "waves" in counters:
                waves = WaveCounts(
                    np.asarray(counters["waves"]),
                    resolve_wave_size(call.wave_size, n_warps),
                    n_instr * n_warps)
            entries = tuple((s.name, seed) for s in call.scenarios
                            for seed in s.seeds)
            traces = None
            if keep_traces:
                traces = tuple(
                    {k: tr[k][f] for k in _TRACE_KEYS}
                    for f in range(call.flat))
            blocks.append(ResultBlock(entries, out, spans["repro.call"],
                                      traces, waves, spans))
        meta = {"experiment": exp.name, "engine": exp.engine,
                "n_calls": self.n_calls,
                "n_executables": self.n_executables}
        return ResultSet([p.name for p in exp.policies], blocks, meta)


@dataclasses.dataclass(frozen=True)
class Experiment:
    """Scenarios × policies × engine options — the one front door.

    ``run()`` compiles the plan and executes it; ``compile()`` exposes
    the plan for inspection (bucketing, call count, executable reuse)
    without materializing any traces.
    """
    name: str
    scenarios: Tuple[Scenario, ...]
    policies: Tuple[Policy, ...]
    engine: str = "event"
    wave_size: Optional[int] = None
    #: wavefront timing-pass backend (repro.kernels.wavefront_scan);
    #: "auto" = fused lax scans on every platform; "pallas" is an
    #: opt-in that the TPU compiler refuses today
    scan_backend: str = "auto"
    #: wavefront cache-pass backend (repro.kernels.cache_pass);
    #: "auto" = fused one-sweep on every platform; "pallas" is an
    #: opt-in that the TPU compiler refuses today
    cache_backend: str = "auto"
    #: serving-engine pool-transaction backend (engine="serving" only);
    #: "auto"/"fast" = vectorized access_batch, "ref" = sequential per-key
    pool_backend: str = "auto"
    #: device mesh for multi-device sweeps (``jax.sharding.Mesh``, e.g.
    #: ``launch.mesh.make_local_mesh``); None = single-device execution.
    #: Every (policy, seed) cell is an independent simulation, so the
    #: sharded run is bitwise-identical to the single-device one.
    mesh: Optional[object] = None
    #: (policy, seed, warp) mesh-axis assignment — which mesh axes the
    #: stacked policy axis, the seed-stack axis and (wavefront only) the
    #: engine-internal warp axis shard over. Entries are None, an axis
    #: name, or a tuple of names; an axis that does not divide its
    #: dimension falls back to replication per bucket. Defaults (when a
    #: mesh is given) to the mesh's first two axis names for (policy,
    #: seed) and no warp sharding.
    mesh_axes: Optional[Tuple] = None
    prm: SimParams = SimParams()

    def __post_init__(self):
        object.__setattr__(self, "scenarios", tuple(self.scenarios))
        object.__setattr__(self, "policies", tuple(self.policies))
        if not self.scenarios:
            raise ValueError(f"experiment {self.name!r}: needs >= 1 "
                             "scenario")
        if not self.policies:
            raise ValueError(f"experiment {self.name!r}: needs >= 1 policy")
        names = [s.name for s in self.scenarios]
        dupes = {n for n in names if names.count(n) > 1}
        if dupes:
            raise ValueError(f"experiment {self.name!r}: duplicate scenario "
                             f"names {sorted(dupes)} — results would "
                             "collide; pass name= to disambiguate")
        pnames = [p.name for p in self.policies]
        pdupes = {n for n in pnames if pnames.count(n) > 1}
        if pdupes:
            raise ValueError(f"experiment {self.name!r}: duplicate policy "
                             f"names {sorted(pdupes)}")
        if self.mesh_axes is not None and self.mesh is None:
            raise ValueError(f"experiment {self.name!r}: mesh_axes given "
                             "without a mesh; pass mesh= as well")
        if self.mesh is not None:
            if self.engine == "serving":
                raise ValueError(
                    f"experiment {self.name!r}: engine='serving' runs "
                    "host-side and does not take a mesh")
            axes = self.mesh_axes
            if axes is None:
                names = tuple(self.mesh.axis_names)
                axes = (names[0], names[1] if len(names) > 1 else None,
                        None)
            axes = tuple(axes) + (None,) * (3 - len(axes))
            if len(axes) != 3:
                raise ValueError(
                    f"experiment {self.name!r}: mesh_axes must be up to "
                    "3 entries (policy, seed, warp); got "
                    f"{self.mesh_axes!r}")
            object.__setattr__(self, "mesh_axes", axes)
            validate_mesh_args(self.mesh, *axes, engine=self.engine)
        serving = [s.name for s in self.scenarios if s.is_serving]
        if self.engine == "serving":
            if len(serving) != len(self.scenarios):
                raise ValueError(
                    f"experiment {self.name!r}: engine='serving' takes "
                    "only serving scenarios (Scenario.serving)")
            from repro.serving.sim.step import POOL_BACKENDS
            if self.pool_backend not in POOL_BACKENDS:
                raise ValueError(
                    f"experiment {self.name!r}: unknown pool_backend "
                    f"{self.pool_backend!r}; choose from {POOL_BACKENDS}")
        else:
            if serving:
                raise ValueError(
                    f"experiment {self.name!r}: serving scenarios "
                    f"{serving} need engine='serving'")
            validate_engine_args(self.engine, self.wave_size,
                                 self.scan_backend, self.cache_backend)

    def compile(self) -> Plan:
        """Bucket scenarios by trace shape; one PlanCall per bucket.

        With a mesh, each bucket's placement is resolved here (the
        replication fallback applied against the bucket's concrete
        policy count / seed-stack size / warp count), so the emitted
        plan is inspectable: ``describe()`` shows exactly which axes of
        which bucket will shard."""
        buckets: Dict[Shape, List[Scenario]] = {}
        for s in self.scenarios:
            buckets.setdefault(s.shape, []).append(s)
        calls = []
        for shape, scens in buckets.items():
            mesh = pol_ax = seed_ax = warp_ax = None
            if self.mesh is not None and self.engine != "serving":
                mesh = self.mesh
                p_want, s_want, w_want = self.mesh_axes
                flat = sum(s.n_seeds for s in scens)
                pol_ax = SH.resolve_axes(mesh, p_want, len(self.policies))
                seed_ax = SH.resolve_axes(mesh, s_want, flat)
                warp_ax = SH.resolve_axes(mesh, w_want, shape[1])
            calls.append(
                PlanCall(shape, self.engine, self.wave_size,
                         self.scan_backend, self.cache_backend,
                         tuple(scens), mesh, pol_ax, seed_ax, warp_ax))
        return Plan(self, tuple(calls))

    def run(self, keep_traces: bool = False) -> ResultSet:
        return self.compile().execute(keep_traces=keep_traces)

    # convenience for quick derivative experiments
    def with_(self, **changes) -> "Experiment":
        return dataclasses.replace(self, **changes)


def run(scenarios: Sequence[Scenario], policies: Sequence[Policy],
        engine: str = "event", wave_size: Optional[int] = None,
        scan_backend: str = "auto", cache_backend: str = "auto",
        prm: SimParams = SimParams(), mesh=None, mesh_axes=None,
        name: str = "adhoc", keep_traces: bool = False) -> ResultSet:
    """One-shot helper: ``api.run(scenarios, policies)`` -> ResultSet."""
    return Experiment(name, tuple(scenarios), tuple(policies), engine,
                      wave_size, scan_backend, cache_backend,
                      mesh=mesh, mesh_axes=mesh_axes,
                      prm=prm).run(keep_traces=keep_traces)
