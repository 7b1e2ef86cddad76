"""The per-pass device times and the program's own spans and counters
on the CPU: the scope reduction on synthetic planes, the trace metadata
read from the wire format, a trace recorded on a TPU v5e with its scope
paths, and the readers of the program's spans and wave counter."""
from __future__ import annotations

import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from bench import run  # noqa: F401 (puts the program's src on the path)
from bench.lib import cells, scopes, trace

DATA = Path(__file__).resolve().parent / "data"

LOOP = "jit(_simulate_batch)/vmap(vmap(while))"


def _path(scope, op):
    return f"{LOOP}/body/{scope}/{op}"


def _device(name, events, op_names, extra_lines=()):
    return {"name": name,
            "lines": [{"name": "XLA Ops", "events": events}, *extra_lines],
            "op_names": op_names}


def _planes():
    """One sweep [0, 100) on the host; on the device the wave loop
    spans it all, a cache pass holds a nested while and its body, a
    timing pass two overlapping ops, and one op starts before the
    window."""
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("sweep 1", 0, 100)]}]}
    events = [("while.1", -5, 105),          # the wave loop: no scope
              ("select", -4, 8),             # clipped to [0, 4)
              ("while.2", 10, 30),           # the cache pass's lane loop
              ("fusion.3", 12, 8),           # ... and its body
              ("fusion.4", 30, 5),
              ("fusion.5", 50, 10),          # timing pass
              ("fusion.6", 55, 15),
              ("scatter", 80, 5),            # wave commit
              ("copy", 90, 5)]               # no scope
    names = {"select": _path("wave_select", "top_k"),
             "while.2": _path("cache_pass", "while"),
             "fusion.3": _path("cache_pass", "while/body/add"),
             "fusion.4": _path("cache_pass", "while/body/select_n"),
             "fusion.5": _path("timing_pass", "cumsum"),
             "fusion.6": _path("timing_pass", "cummax"),
             "scatter": _path("wave_commit", "scatter-add")}
    return host, events, names


def test_scope_time_is_the_union_of_its_ops_in_the_window():
    host, events, names = _planes()
    planes = [host, _device("/device:TPU:0", events, names)]
    out = scopes.reduce_planes(planes, ["sweep 1"])
    assert out == pytest.approx({
        "wave_select": 4e-9, "cache_pass": 30e-9, "timing_pass": 20e-9,
        "wave_commit": 5e-9})
    busy_s = trace.reduce_planes(planes, ["sweep 1"])["busy_s"]
    assert busy_s == pytest.approx(100e-9)
    assert sum(out.values()) <= busy_s


def test_scope_time_is_averaged_over_devices_and_cut_where_buffers_drop():
    host, events, names = _planes()
    late = [(n, s + 40, d) for n, s, d in events]
    dropped = {"name": "XLA TraceMe", "events": [(trace.DROPPED, 60, 5)]}
    planes = [host, _device("/device:TPU:0", events, names),
              _device("/device:TPU:1", late, names, [dropped])]
    # the window ends at 60; device 1's ops are 40 later than device 0's
    window = trace.reduce_planes(planes, ["sweep 1"])
    assert window["dropped"] and window["window_s"] == pytest.approx(60e-9)
    assert scopes.reduce_planes(planes, ["sweep 1"]) == pytest.approx({
        "wave_select": (4 + 8) / 2 * 1e-9, "cache_pass": (30 + 10) / 2 * 1e-9,
        "timing_pass": (10 + 0) / 2 * 1e-9, "wave_commit": 0.0})


def test_a_trace_without_scope_paths_reads_no_pass_time():
    host, events, _ = _planes()
    bare = {"name": "/device:TPU:0",
            "lines": [{"name": "XLA Ops", "events": events}]}
    for device in (_device("/device:TPU:0", events, {}), bare):
        assert scopes.reduce_planes([host, device], ["sweep 1"]) == \
            dict.fromkeys(scopes.SCOPES, 0.0)
    with pytest.raises(ValueError):
        scopes.reduce_planes([bare], ["sweep 1"])


def _naive_scope_time(planes, scope, lo, hi):
    """Nanoseconds of the first device in ``scope``, one interval at a
    time."""
    dev = next(p for p in planes if p["name"].startswith("/device:"))
    ivs = sorted((max(s, lo), min(s + d, hi)) for l in dev["lines"]
                 if l["name"] in trace.OP_LINES for n, s, d in l["events"]
                 if scopes.scope_of(dev["op_names"].get(n, "")) == scope)
    busy, reach = 0, lo
    for s, e in ivs:
        if e > reach:
            busy += e - max(s, reach)
            reach = e
    return busy


def test_scope_reduction_on_a_recorded_trace():
    """A trace recorded on a TPU v5e (``data/record_scoped_trace.py``):
    two sweeps of a tiny wavefront experiment, with the op_name paths of
    the device's scoped operations."""
    with gzip.open(DATA / "small_trace_scoped.json.gz", "rt") as f:
        planes = json.load(f)
    out = trace.reduce_planes(planes, ["sweep 1", "sweep 2"])
    passes = scopes.reduce_planes(planes, ["sweep 1", "sweep 2"])
    want = json.loads((DATA / "small_trace_scoped_reduced.json").read_text())
    assert out["devices"] == 1 and not out["dropped"]
    assert out["busy_s"] == pytest.approx(want["busy_s"])
    assert passes == pytest.approx(want["scopes"])
    host = [e for p in planes if p["name"].startswith("/host:")
            for l in p["lines"] for e in l["events"]
            if e[0] in ("sweep 1", "sweep 2")]
    lo = min(s for _, s, _ in host)
    hi = max(s + d for _, s, d in host)
    for scope in scopes.SCOPES:
        assert passes[scope] > 0
        assert passes[scope] == pytest.approx(
            _naive_scope_time(planes, scope, lo, hi) * 1e-9)
    assert sum(passes.values()) <= out["busy_s"]
    # the program's host spans fall on the trace's clock too
    gaps = " ".join(n for n, _ in out["breakdown"]["idle_gaps"])
    assert "repro.call" in gaps


@pytest.mark.parametrize("text,scope", [
    (_path("cache_pass", "while/body/add"), "cache_pass"),
    (f"{LOOP}/body/cache_pass/timing_pass/add", "timing_pass"),
    (f"{LOOP}/body/add", None),
    (f"{LOOP}", None),
    ("src/repro/kernels/cache_pass.py", None),
    ("jit(f)/wave_select_x/add", None),
    ("jit(_simulate_batch)/vmap(vmap())/while/body/wave_select:",
     "wave_select"),
    ("jit(_simulate_batch)/vmap(vmap())/while:", None),
    ('%fusion.3 = f32[8] fusion(...), metadata={op_name="jit(f)/while/'
     'body/wave_commit" source_file="x.py"}', "wave_commit"),
])
def test_scope_of_reads_a_path_component(text, scope):
    assert scopes.scope_of(text) == scope


# -- the wire format of an XSpace, written by hand -----------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _xspace():
    """A device plane with a line of events, two scoped operations (one
    path a string stat, one a reference to a stat's name) and two
    unscoped, one of them from a source file named like a scope; and a
    host plane."""
    stat_md = [(5, _msg((1, k), (2, _msg((1, k), (2, name)))))
               for k, name in ((1, "tf_op"), (2, "flops"),
                               (3, _path("timing_pass", "copy")),
                               (4, "source_stack"))]
    event_md = [
        (4, _msg((1, 7), (2, _msg((1, 7), (2, "%fusion.7 = f32[] fusion()"),
                                   (5, _msg((1, 1), (5, _path("cache_pass",
                                                              "add")))),
                                   (5, _msg((1, 2), (3, 99))))))),
        (4, _msg((1, 8), (2, _msg((1, 8), (2, "copy.8"),
                                   (5, _msg((1, 1), (7, 3))))))),
        (4, _msg((1, 9), (2, _msg((1, 9), (2, "while.9"),
                                   (5, _msg((1, 1), (5, LOOP))))))),
        (4, _msg((1, 10), (2, _msg(
            (1, 10), (2, "copy.10"),
            (5, _msg((1, 4), (5, "src/repro/kernels/cache_pass/ops.py:1"))),
            (5, _msg((1, 1), (5, LOOP + ":")))))))]
    line = (3, _msg((1, 1), (2, "XLA Ops"),
                    (4, _msg((1, 7), (2, 5000), (3, 3000)))))
    device = _msg((1, 1), (2, "/device:TPU:0"), line, *event_md, *stat_md)
    host = _msg((1, 2), (2, "/host:CPU"))
    return _msg((1, device), (1, host))


def test_op_names_are_read_from_the_metadata_of_a_serialized_trace():
    assert scopes.op_names(_xspace()) == {"/device:TPU:0": {
        "%fusion.7 = f32[] fusion()": _path("cache_pass", "add"),
        "copy.8": _path("timing_pass", "copy")}}
    planes = scopes.xspace_metadata(_xspace())
    assert [p["name"] for p in planes] == ["/device:TPU:0", "/host:CPU"]
    assert planes[0]["events"][8] == (
        "copy.8", "", {"tf_op": _path("timing_pass", "copy")})


def test_a_profile_directory_gives_its_planes_with_their_op_names(tmp_path):
    run_dir = tmp_path / "plugins" / "profile" / "run"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(_xspace())
    want = {"%fusion.7 = f32[] fusion()": _path("cache_pass", "add"),
            "copy.8": _path("timing_pass", "copy")}
    assert scopes.load_op_names(str(tmp_path)) == {"/device:TPU:0": want}
    planes = {p["name"]: p for p in scopes.load_planes(str(tmp_path))}
    assert planes["/device:TPU:0"]["op_names"] == want
    # the event's offset and duration are in picoseconds
    assert planes["/device:TPU:0"]["lines"][0]["events"] == [
        ("%fusion.7 = f32[] fusion()", 5, 3)]
    assert "op_names" not in planes["/host:CPU"]


# -- readers -------------------------------------------------------------

class _RS:
    """A window sweep's ``ResultSet``, as far as the readers look."""

    def __init__(self, tracegen, counts):
        self._tracegen, self._counts = tracegen, counts

    def span_s(self, name):
        return self._tracegen if name == "repro.tracegen" else 0.0

    def wave_counts(self):
        return self._counts


class _Counts:
    def __init__(self, waves, wave_size, warp_instructions):
        self.waves = np.asarray(waves, np.int32)
        self.wave_size = wave_size
        self.warp_instructions = warp_instructions


def _readers():
    bench = cells.load_benchmark()
    cell = cells.find_cell(bench["workloads"][0]["name"])
    return cells.metric_readers(cell)


def test_readers_of_the_programs_spans_and_counters():
    read = _readers()
    counts = [_Counts([[256, 250], [260, 256]], 512, 131072)]
    run = {"sweeps": [{"rs": _RS(0.25, counts)},
                      {"rs": _RS(0.35, counts + counts)}],
           "trace": None}
    assert read["tracegen_s"](run) == pytest.approx(0.30)
    assert read["waves_per_sweep"](run) == pytest.approx((260 + 520) / 2)
    assert read["wave_fill_share"](run) == pytest.approx(
        100.0 * 131072 / (512 * 260))


def test_readers_find_nothing_in_a_program_without_spans_or_counters():
    """A program that records no span and counts no waves, as the one
    before them: the readers return None and do not raise."""
    read = _readers()

    class Bare:
        def call_walls(self):
            return (1.0,)
    run = {"sweeps": [{"rs": Bare(), "wall_s": 1.2, "call_s": 1.0}],
           "trace": {"sweeps": 1.0, "busy_s": 0.9, "window_s": 1.2}}
    for name in ("tracegen_s", "waves_per_sweep", "wave_fill_share"):
        assert read[name](run) is None
    run["sweeps"] = [{"rs": _RS(0.0, ())}]
    for name in ("tracegen_s", "waves_per_sweep", "wave_fill_share"):
        assert read[name](run) is None


def test_readers_on_a_run_of_the_program(tmp_path):
    """The readers on real sweeps of a tiny wavefront cell."""
    from bench.lib import sweep
    from bench.tests.cell_fixture import make_cell

    bench = make_cell(tmp_path, engine="wavefront", n_warps=8, n_instr=2)
    cell = cells.find_cell("tiny", tmp_path, bench)
    sweeps = [{"rs": sweep.build(cell.config, cell.traffic, [k],
                                 f"tiny.{k}").run()} for k in (1, 2)]
    read = _readers()
    run = {"sweeps": sweeps, "trace": None}
    assert 0 < read["tracegen_s"](run)
    # two specs of one shape, one call; a default wave holds all 8
    # warps, so the call runs one wave per instruction
    assert read["waves_per_sweep"](run) == 2
    assert read["wave_fill_share"](run) == pytest.approx(100.0)
