"""waves_per_sweep: the wave loop's iterations the device runs per
sweep, by the program's own counter: for each wavefront call the most
waves any of its simulations ran (under vmap the device runs that many
for all of them), summed over a sweep's calls and averaged over the
window's sweeps. None where the program counts no waves."""


def read(run):
    per = []
    for s in run["sweeps"]:
        counts = getattr(s["rs"], "wave_counts", None)
        if counts is None or not counts():
            return None
        per.append(sum(int(c.waves.max()) for c in counts()))
    return sum(per) / len(per)
