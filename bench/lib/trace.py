"""Reduce a profiler trace of the window to the device's busy time, its
idle gaps and the operations that took the most time.

The traced window is the span of the benchmark's own ``sweep <k>``
annotations on the host, cut short where the device's trace buffers ran
over (the profiler marks that with a ``Trace Buffers Dropped`` event;
what follows is unknown, not idle). Busy time is the union of the
intervals in which an operation ran on a device (the device planes'
``XLA Ops`` lines; a while loop is one operation there, its body's
operations nested inside it), clipped to the window and averaged over
the devices that ran any. An idle gap is a stretch of the window with no
operation on the device; it is labelled with what the host trace shows
there: the sweep and the most specific host event that covers most of
the gap.
"""
from __future__ import annotations

import collections
import glob
import os
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

Interval = Tuple[int, int]

OP_LINES = ("XLA Ops",)
DROPPED = "Trace Buffers Dropped"
TOP = 10


def union(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """The [start, end) intervals merged where they overlap, as [n, 2]."""
    if starts.size == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], reach[last]], axis=1)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The stretches of [lo, hi) that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def _label(gap: Interval, host: Sequence[tuple], sweeps: Sequence[tuple]):
    s, e = gap
    mid = (s + e) // 2
    where = next((n for n, a, b in sweeps if a <= mid < b), "between sweeps")
    best = None
    for name, a, b in host:
        cover = min(b, e) - max(a, s)
        if cover * 2 >= e - s and (best is None or b - a < best[0]):
            best = (b - a, name)
    return f"{where} / {best[1] if best else 'no host event'}"


def reduce_planes(planes: Iterable[dict], sweep_names: Sequence[str]) -> Dict:
    """The reduction on planes given as
    ``[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]``
    (``events`` may be any iterable; each is read once)."""
    wanted = set(sweep_names)
    sweeps, host, drops = [], [], []
    dev_iv: Dict[str, list] = collections.defaultdict(list)
    op_time: Dict[str, int] = collections.Counter()
    for plane in planes:
        pname = plane["name"]
        is_host = pname.startswith("/host:")
        is_dev = pname.startswith("/device:") and not is_host
        for line in plane["lines"]:
            if is_host:
                for name, start, dur in line["events"]:
                    ev = (name, int(start), int(start + dur))
                    (sweeps if name in wanted else host).append(ev)
            elif is_dev and line["name"] in OP_LINES:
                starts, ends = [], []
                for name, start, dur in line["events"]:
                    starts.append(start)
                    ends.append(start + dur)
                    op_time[name] += dur
                dev_iv[pname].append((np.asarray(starts, np.int64),
                                      np.asarray(ends, np.int64)))
            elif is_dev:
                drops += [int(s) for n, s, _ in line["events"]
                          if n == DROPPED]
    if not sweeps:
        raise ValueError("the trace holds none of the sweep annotations")
    sweeps.sort(key=lambda x: x[1])
    lo = min(a for _, a, _ in sweeps)
    span = max(b for _, _, b in sweeps)
    hi = min([span] + drops)

    merged = {}
    for dev, parts in dev_iv.items():
        s = np.concatenate([p[0] for p in parts])
        e = np.concatenate([p[1] for p in parts])
        s, e = np.maximum(s, lo), np.minimum(e, hi)
        keep = e > s
        if keep.any():
            merged[dev] = union(s[keep], e[keep])
    busy = {d: int((iv[:, 1] - iv[:, 0]).sum()) for d, iv in merged.items()}
    busy_s = (sum(busy.values()) / len(busy) * 1e-9) if busy else 0.0

    idle = []
    if merged:
        first = merged[sorted(merged)[0]]
        idle = sorted(gaps([tuple(x) for x in first.tolist()], lo, hi),
                      key=lambda g: g[0] - g[1])[:TOP]
    host = [h for h in host if h[2] > lo and h[1] < hi]
    breakdown = {
        "device_ops": [[n, t * 1e-9] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[_label(g, host, sweeps), (g[1] - g[0]) * 1e-9]
                      for g in idle],
    }
    return {"busy_s": busy_s, "window_s": (hi - lo) * 1e-9,
            "sweeps": (hi - lo) / (span - lo), "dropped": hi < span,
            "devices": len(busy), "breakdown": breakdown}


def _profile_planes(pd):
    for p in pd.planes:
        yield {"name": p.name,
               "lines": ({"name": l.name,
                          "events": ((e.name, int(e.start_ns),
                                      int(e.duration_ns)) for e in l.events)}
                         for l in p.lines)}


def load(profile_dir: str):
    """The profiler's ``.xplane.pb`` under ``profile_dir``."""
    import jax
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {profile_dir}, "
                                f"found {files}")
    return jax.profiler.ProfileData.from_file(files[0])


def load_planes(profile_dir: str) -> List[dict]:
    """The trace as plain data (for recording a small one)."""
    return [{"name": p["name"],
             "lines": [{"name": l["name"], "events": list(l["events"])}
                       for l in p["lines"]]}
            for p in _profile_planes(load(profile_dir))]


def reduce(profile_dir: str, sweep_names: Sequence[str]) -> Dict:
    return reduce_planes(_profile_planes(load(profile_dir)), sweep_names)
