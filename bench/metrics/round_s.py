"""Seconds per federated round: the window's wall time, from the first
round's start to the last round's end (the bank ready on the device),
over the rounds it ran."""


def read(ctx):
    return ctx.window_s / len(ctx.rounds)
