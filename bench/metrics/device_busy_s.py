"""device_busy_s: seconds per sweep in which an operation ran on the
device: the union of the device's operation intervals in the profiler
trace, over the sweeps the trace covers (one whole sweep, or the part of
it before the device's trace buffers ran over). None without a device
timeline."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    return trace["busy_s"] / trace["sweeps"]
