"""Mean host milliseconds per window round in ``RoundPlanner.build``,
from the benchmark's span around the server's planner call."""


def read(ctx):
    plans = [e - s for name, _, s, e in ctx.spans if name == "plan"]
    if not plans:
        return None
    return 1e3 * sum(plans) / len(ctx.rounds)
