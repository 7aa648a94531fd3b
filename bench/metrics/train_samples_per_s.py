"""Training examples consumed per second by the real (model, device)
pairs of the window's rounds (padded pairs excluded), over the
window's wall time."""


def read(ctx):
    return sum(r["samples"] for r in ctx.rounds) / ctx.window_s
