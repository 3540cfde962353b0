"""The program's own host spans over the traced window.

While a profiler trace is taken the program keeps every ``trsm.*`` span
it annotates (``repro.core.spans``), with the thread's CPU time inside
it, so a reader here gets the intervals without the trace file.  The
intervals are on the program's clock, not the trace's:
``on_trace_clock`` moves them by the offset of a harness span and the
program span its call opens.  A program without the module keeps
nothing, and every reader here then reads nothing.
"""

from __future__ import annotations

import statistics

from bench import trace

# kept intervals that are no host activity: a request's wait in its queue
WAITS = ("trsm.queue",)


def recorded() -> list:
    """[[name, start_ns, end_ns, cpu_ns]] the program kept, oldest
    first."""
    try:
        from repro.core import spans
    except ImportError:
        return []
    return [list(r) for r in spans.recorded()]


def durations(spans: list, name: str) -> list:
    """Durations (ns) of the spans named ``name``."""
    return [r[2] - r[1] for r in spans if r[0] == name]


def median_ms(spans: list, name: str):
    d = durations(spans, name)
    return statistics.median(d) / 1e6 if d else None


def mean_ms(spans: list, name: str):
    d = durations(spans, name)
    return statistics.fmean(d) / 1e6 if d else None


def on_trace_clock(ctx, spans: list, anchor: tuple):
    """The spans moved onto the trace's clock, or None where the trace
    cannot place them.  ``anchor`` is a (harness span, program span)
    pair: the first harness span of that name in the trace opens with
    the first program span of the other, a few microseconds apart."""
    if ctx.trace is None:
        return None
    outer = [s for n, s, _ in ctx.trace["host"] if n == anchor[0]]
    inner = [r[1] for r in spans if r[0] == anchor[1]]
    if not outer or not inner:
        return None
    shift = min(outer) - min(inner)
    return [[r[0], r[1] + shift, r[2] + shift, *r[3:]] for r in spans]


def cpu_pct(spans: list, names, window):
    """The threads' CPU time inside the spans named in ``names``, over
    the length of ``window`` (lo, hi).  A span that crosses an edge of
    the window counts in proportion to its part inside."""
    lo, hi = window
    cpu = 0.0
    for _, s, e, c in (r for r in spans if r[0] in names):
        inside = min(e, hi) - max(s, lo)
        if inside > 0:
            cpu += c * inside / (e - s)
    return 100.0 * cpu / (hi - lo)


def note_idle_gaps(ctx, spans: list) -> None:
    """Add the note line that names the traced window's longest idle
    gaps (``trace.idle_gaps``) by the innermost program span, on the
    trace's clock, covering each gap's middle."""
    if not ctx.trace["devices"]:
        return
    moved = {"host": [r[:3] for r in spans if r[0] not in WAITS],
             "devices": ctx.trace["devices"]}
    gaps = trace.idle_gaps(moved, ctx.window)
    ctx.notes.append("idle gaps by program span: " + ", ".join(
        f"{name} {1e3 * s:.6f} ms" for name, s in gaps))
