"""device_idle_share: the share of the traced window, in percent, in
which no operation ran on the device: 100 * (1 - busy / window). None
without a device timeline."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
