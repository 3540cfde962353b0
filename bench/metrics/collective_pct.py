"""Share of the slowest device's busy time in the traced window in
which a collective op runs, hidden behind other ops or not: how much
of the work is communication.  The slowest device is the one with the
most busy time (the union of its ops)."""

from bench import trace


def read(ctx):
    if ctx.trace is None or len(ctx.trace["devices"]) < 2:
        return None
    lo, hi = ctx.window
    busy = trace.busy(ctx.trace, ctx.window)
    dev = max(busy, key=busy.get)
    if busy[dev] <= 0:
        return None
    ops = ctx.trace["devices"][dev]["ops"]
    coll = trace.union(trace.clip(
        [e for e in ops if trace.is_collective(e[0])], lo, hi))
    return 100.0 * trace.length(coll) / busy[dev]
