"""Median host time of one ``FactorBank.replace`` (the updater lookup,
the placement and the updater's dispatch, not its device time): the
program's ``trsm.replace`` spans over the traced window.  Also notes
the idle gaps by program span."""

from bench import program


def read(ctx):
    spans = program.recorded()
    moved = program.on_trace_clock(ctx, spans,
                                   ("bench.refresh", "trsm.replace"))
    if moved is not None:
        program.note_idle_gaps(ctx, moved)
    return program.median_ms(spans, "trsm.replace")
