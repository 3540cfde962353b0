"""Share of the traced window, on the worst device, in which a
collective op runs and no other op does."""

from bench import trace


def read(ctx):
    if ctx.trace is None or len(ctx.trace["devices"]) < 2:
        return None
    lo, hi = ctx.window
    exposed = trace.exposed_collective(ctx.trace, ctx.window)
    return 100.0 * max(exposed.values()) / (hi - lo)
