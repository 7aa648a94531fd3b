"""Percent of the traced window in which no operation ran on the device
(1 minus the union of the device-op intervals, from the profiler
trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
