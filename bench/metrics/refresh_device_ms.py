"""Median device time of one execution of the bank's refresh (updater)
program in the traced window, on the slowest device."""

import statistics

from bench import trace


def read(ctx):
    module = ctx.programs.get("update")
    if ctx.trace is None or module is None:
        return None
    times = trace.module_times(ctx.trace, module, ctx.window)
    meds = [statistics.median(v) for v in times.values() if v]
    return max(meds) / 1e6 if meds else None
