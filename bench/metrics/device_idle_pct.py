"""Share of the traced window in which no op ran on the device: one
minus the union of the device's op intervals over the window, on the
worst device."""

from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = ctx.window
    busy = trace.busy(ctx.trace, ctx.window)
    return 100.0 * (1.0 - min(busy.values()) / (hi - lo))
