"""Share of the roofline of the solve program: the least time the
problem's work could take on the chips used (bench/work.py), over the
device time of the solve program's executions in the traced window, on
the slowest device.  The program's XLA module name comes from the
program the harness warmed up."""

from bench import trace, work


def read(ctx):
    if ctx.trace is None or not ctx.solve_work.get("W"):
        return None
    times = trace.module_times(ctx.trace, ctx.programs["solve"], ctx.window)
    device_s = max((sum(v) for v in times.values()), default=0) / 1e9
    if device_s <= 0:
        return None
    pct, bound = work.roofline(ctx.solve_work["W"], ctx.solve_work["Q"],
                               device_s, ctx.peak, ctx.chips)
    ctx.notes.append(f"solve_roofline: {bound}-bound, W={ctx.solve_work['W']:.6e} "
                     f"flops, Q={ctx.solve_work['Q']:.6e} bytes, solve device "
                     f"time {device_s:.6f} s over "
                     f"{max(len(v) for v in times.values())} executions")
    return pct
