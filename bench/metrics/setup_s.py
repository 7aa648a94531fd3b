"""Process start to the first timed round: data and weights, server
construction, the compared and warm-up rounds, compiles and
compile-cache loads included."""


def read(ctx):
    return ctx.setup_s
