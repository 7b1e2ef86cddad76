"""host_s: host time per sweep outside the program's device calls:
plan, trace generation, stacking and result assembly. Each sweep's
host-clock wall minus the program's own timer around its calls
(``ResultBlock.wall_s``: the jitted call and the copy back), averaged
over the window's sweeps."""


def read(run):
    sweeps = run["sweeps"]
    return sum(s["wall_s"] - s["call_s"] for s in sweeps) / len(sweeps)
