"""tracegen_s: host seconds per sweep in which the program generates
its traces: the program's own ``repro.tracegen`` span (the
``materialize`` loop of each call, ``api/experiment.py``), summed over a
sweep's calls and averaged over the window's sweeps. None where the
program records no such span."""


def read(run):
    per = []
    for s in run["sweeps"]:
        span_s = getattr(s["rs"], "span_s", None)
        if span_s is None:
            return None
        per.append(span_s("repro.tracegen"))
    if not any(per):
        return None
    return sum(per) / len(per)
