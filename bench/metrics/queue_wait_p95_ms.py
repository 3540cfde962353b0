"""95th percentile of a request's wait in its queue, arrival to the
dispatch of its wave: the ``trsm.queue`` intervals the front door keeps
for every request it dispatches in the traced window."""

import numpy as np

from bench import program


def read(ctx):
    waits = program.durations(program.recorded(), "trsm.queue")
    if not waits:
        return None
    return float(np.percentile(np.asarray(waits, np.float64), 95)) / 1e6
