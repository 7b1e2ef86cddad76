"""call_s: the program's device calls per sweep, by its own timer
(``ResultBlock.wall_s``, summed over a sweep's calls): the jitted engine
call, the copy of the traces in and the copy of the results back,
averaged over the window's sweeps."""


def read(run):
    sweeps = run["sweeps"]
    return sum(s["call_s"] for s in sweeps) / len(sweeps)
