"""wave_fill_share: the share, in percent, of the wave slots the device
runs that service a warp-instruction, by the program's own counter:
100 * sum(P * F * I * W) / sum(P * F * B * waves), over every wavefront
call of the window's sweeps, where a call of P x F simulations of I x W
warp-instructions runs ``waves`` (its most) waves of B slots each. None
where the program counts no waves."""


def read(run):
    used = ran = 0
    for s in run["sweeps"]:
        counts = getattr(s["rs"], "wave_counts", None)
        if counts is None or not counts():
            return None
        for c in counts():
            used += c.waves.size * c.warp_instructions
            ran += c.waves.size * c.wave_size * int(c.waves.max())
    return 100.0 * used / ran
