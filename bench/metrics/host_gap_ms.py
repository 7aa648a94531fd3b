"""Mean host milliseconds from ``executor.readback`` returning to the
next ``executor.launch`` having enqueued its dispatch: the lifecycle,
``collect``, sampling and planning that the device waits on between
two rounds of the window."""


def read(ctx):
    ends = {r: e for name, r, _, e in ctx.spans if name == "readback"}
    launched = {r: e for name, r, _, e in ctx.spans if name == "launch"}
    gaps = [launched[r + 1] - ends[r] for r in ends if r + 1 in launched]
    if not gaps:
        return None
    return 1e3 * sum(gaps) / len(gaps)
