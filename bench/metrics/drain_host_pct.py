"""Share of the traced window in which the drain thread does host work:
its CPU time inside the program's ``trsm.pack``, ``trsm.dispatch`` and
``trsm.resolve`` spans, over the window.  CPU time, not the spans'
length: on the chip a call that enqueues a program can hold the drain
thread inside ``trsm.dispatch`` for up to a wave, asleep, and a
sleeping thread does no host work.  Neither
``trsm.device_wait`` nor the idle poll between steps counts.  The
spans are placed on the trace's clock by the first ``bench.submit`` and
the ``trsm.submit`` it opens.  Also notes the idle gaps by program
span."""

from bench import program

DRAIN = ("trsm.pack", "trsm.dispatch", "trsm.resolve")


def read(ctx):
    spans = program.on_trace_clock(ctx, program.recorded(),
                                   ("bench.submit", "trsm.submit"))
    if spans is None:
        return None
    program.note_idle_gaps(ctx, spans)
    return program.cpu_pct(spans, DRAIN, ctx.window)
