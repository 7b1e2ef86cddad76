"""Device time of the wave loop's passes, from the named scopes the
program puts on them.

The program runs each pass of a wave inside a ``jax.named_scope``
(``core/engine/wavefront.py``): ``wave_select``, ``cache_pass``,
``timing_pass`` and ``wave_commit``. The scope reaches the compiled
program as a component of each operation's ``op_name`` path
(``jit(...)/while/body/cache_pass/...``), and the profiler keeps that
path with the operation's metadata in the device plane of its trace.
The trace's ``.xplane.pb`` is an ``XSpace`` protobuf; ``op_names`` reads
the metadata of its planes straight from the wire format (the profiler's
Python reader gives an event's name and times, not its metadata), and
skips the lines of events, which hold nearly all of its bytes.

A scope's time is the union of the intervals of the operations in it,
clipped to the window that ``bench/lib/trace.py`` reduces (the sweeps'
annotations, cut where the device's buffers dropped) and averaged over
the devices that ran any operation there: a while loop inside a pass and
the operations of its body count once. The wave loop itself carries no
pass scope and counts in none.

No per-layer metric reads this yet: ``trace.reduce`` would have to hand
its planes, with ``load_op_names``, to ``reduce_planes`` as well, and
that file is the benchmark's own to change.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from bench.lib import trace

SCOPES = ("wave_select", "cache_pass", "timing_pass", "wave_commit")

#: the stats of an operation's metadata that hold its op_name path (the
#: TPU's trace gives ``tf_op``, e.g. ``jit(f)/while/body/cache_pass/add:``);
#: other stats, such as ``source_stack``, name source files, not scopes
PATH_STATS = ("tf_op", "long_name")

_SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=[/:\"]|$)")

# field numbers of tsl/profiler/protobuf/xplane.proto
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_MD, _PLANE_STAT_MD = 2, 4, 5
_ENTRY_KEY, _ENTRY_VALUE = 1, 2
_EMD_NAME, _EMD_DISPLAY, _EMD_STATS = 2, 4, 5
_SMD_NAME = 2
_STAT_MD_ID, _STAT_STR, _STAT_REF = 1, 5, 7


def scope_of(path: str) -> Optional[str]:
    """The pass scope named as a component of an ``op_name`` path (or
    of text that holds one), or None."""
    found = _SCOPE.findall(path)
    return found[-1] if found else None


def _varint(buf, i: int) -> Tuple[int, int]:
    out, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf, lo: int = 0, hi: Optional[int] = None
           ) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``: an int for a varint, ``(start, end)`` offsets for a
    length-delimited field, raw bytes for a fixed-width one."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        num, kind = key >> 3, key & 7
        if kind == 0:
            val, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            val, i = (i, i + n), i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            val, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"wire type {kind} of field {num} is not "
                             "one an XSpace uses")
        yield num, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, value = 0, None
    for num, val in fields(buf, *span):
        if num == _ENTRY_KEY:
            key = val
        elif num == _ENTRY_VALUE:
            value = val
    return key, value


def plane_metadata(buf, span) -> Dict:
    """One plane's name, its stat names by id, and its event metadata by
    id as ``(name, display name, {stat id: str or ref id})``."""
    name, events, stat_names = "", {}, {}
    for num, val in fields(buf, *span):
        if num == _PLANE_NAME:
            name = _text(buf, val)
        elif num == _PLANE_STAT_MD:
            key, value = _map_entry(buf, val)
            for n, v in fields(buf, *value):
                if n == _SMD_NAME:
                    stat_names[key] = _text(buf, v)
        elif num == _PLANE_EVENT_MD:
            key, value = _map_entry(buf, val)
            ev_name, display, stats = "", "", {}
            for n, v in fields(buf, *value):
                if n == _EMD_NAME:
                    ev_name = _text(buf, v)
                elif n == _EMD_DISPLAY:
                    display = _text(buf, v)
                elif n == _EMD_STATS:
                    sid, sval = None, None
                    for sn, sv in fields(buf, *v):
                        if sn == _STAT_MD_ID:
                            sid = sv
                        elif sn == _STAT_STR:
                            sval = _text(buf, sv)
                        elif sn == _STAT_REF:
                            sval = sv
                    if sid is not None and sval is not None:
                        stats[sid] = sval
            events[key] = (ev_name, display, stats)
    return {"name": name, "stat_names": stat_names, "events": events}


def xspace_metadata(data: bytes) -> List[Dict]:
    """The metadata of every plane of a serialized ``XSpace``, with the
    string stats resolved: ``events`` maps id to ``(name, display name,
    {stat name: value})``."""
    buf = memoryview(data)
    planes = []
    for num, val in fields(buf):
        if num != _SPACE_PLANES:
            continue
        p = plane_metadata(buf, val)
        names = p["stat_names"]
        p["events"] = {
            k: (n, d, {names.get(s, str(s)):
                       (names.get(v, str(v)) if isinstance(v, int) else v)
                       for s, v in st.items()})
            for k, (n, d, st) in p["events"].items()}
        planes.append(p)
    return planes


def op_names(data: bytes) -> Dict[str, Dict[str, str]]:
    """``{device plane: {operation name: op_name path}}`` for every
    operation of a serialized ``XSpace`` whose op_name path (the first
    of ``PATH_STATS`` its metadata has) names a pass scope."""
    out: Dict[str, Dict[str, str]] = {}
    for p in xspace_metadata(data):
        if not p["name"].startswith("/device:"):
            continue
        found = {}
        for name, _, stats in p["events"].values():
            path = next((stats[k] for k in PATH_STATS if k in stats), "")
            if scope_of(path):
                found[name] = path
        if found:
            out[p["name"]] = found
    return out


def _arrays(starts: list, ends: list) -> Tuple[np.ndarray, np.ndarray]:
    return np.asarray(starts, np.int64), np.asarray(ends, np.int64)


def _covered(parts, lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) that the intervals ``[(starts, ends)]``
    cover."""
    if not parts:
        return 0
    s = np.maximum(np.concatenate([p[0] for p in parts]), lo)
    e = np.minimum(np.concatenate([p[1] for p in parts]), hi)
    keep = e > s
    iv = trace.union(s[keep], e[keep])
    return int((iv[:, 1] - iv[:, 0]).sum())


def reduce_planes(planes: Iterable[dict], sweep_names: Sequence[str]
                  ) -> Dict[str, float]:
    """``{scope: device seconds in the window}`` for planes given as
    ``trace.reduce_planes`` takes them, each device plane with its
    ``"op_names"`` (``{operation name: op_name path}``, as ``op_names``
    gives them); a plane without them has no scoped operation."""
    wanted = set(sweep_names)
    sweeps, drops = [], []
    dev_iv: Dict[str, list] = collections.defaultdict(list)
    scope_iv: Dict[str, Dict[str, list]] = {
        s: collections.defaultdict(list) for s in SCOPES}
    for plane in planes:
        pname = plane["name"]
        if pname.startswith("/host:"):
            sweeps += [(int(s), int(s + d)) for line in plane["lines"]
                       for n, s, d in line["events"] if n in wanted]
            continue
        if not pname.startswith("/device:"):
            continue
        in_scope = {n: scope_of(p)
                    for n, p in plane.get("op_names", {}).items()}
        for line in plane["lines"]:
            if line["name"] not in trace.OP_LINES:
                drops += [int(s) for n, s, _ in line["events"]
                          if n == trace.DROPPED]
                continue
            starts, ends = [], []
            scoped = collections.defaultdict(lambda: ([], []))
            for name, start, dur in line["events"]:
                starts.append(start)
                ends.append(start + dur)
                scope = in_scope.get(name)
                if scope:
                    scoped[scope][0].append(start)
                    scoped[scope][1].append(start + dur)
            dev_iv[pname].append(_arrays(starts, ends))
            for scope, (ss, es) in scoped.items():
                scope_iv[scope][pname].append(_arrays(ss, es))
    if not sweeps:
        raise ValueError("the trace holds none of the sweep annotations")
    lo = min(a for a, _ in sweeps)
    hi = min([max(b for _, b in sweeps)] + drops)
    devices = [d for d, parts in dev_iv.items() if _covered(parts, lo, hi)]
    return {scope: (sum(_covered(per_dev.get(d, []), lo, hi)
                        for d in devices) / len(devices) * 1e-9
                    if devices else 0.0)
            for scope, per_dev in scope_iv.items()}


def load_op_names(profile_dir: str) -> Dict[str, Dict[str, str]]:
    """``op_names`` of the profiler's ``.xplane.pb`` under
    ``profile_dir``."""
    files = glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one trace under {profile_dir}, "
                                f"found {files}")
    with open(files[0], "rb") as f:
        return op_names(f.read())


def load_planes(profile_dir: str) -> List[dict]:
    """``trace.load_planes`` with each device plane's ``"op_names"``."""
    names = load_op_names(profile_dir)
    planes = trace.load_planes(profile_dir)
    for p in planes:
        if p["name"] in names:
            p["op_names"] = names[p["name"]]
    return planes

