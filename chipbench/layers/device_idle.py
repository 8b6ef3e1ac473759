"""``device_idle.*`` — layer: device.

1 - (union of the intervals in which an operation ran on the device) /
(the traced window), averaged over the chips, in percent. Source: the
profiler's trace of the last seconds of the window."""


def read(result, trace, ctx):
    if trace is None:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
