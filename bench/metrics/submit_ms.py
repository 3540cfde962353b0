"""Mean host time of one call of the front door's ``submit``: the
program's ``trsm.submit`` spans over the traced window.  The mean, not
the median: a few uploads in each wave wait about a wave, and those
waits set the client's rate (one caller thread submits in turn)."""

from bench import program


def read(ctx):
    return program.mean_ms(program.recorded(), "trsm.submit")
