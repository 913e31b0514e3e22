"""Decode worker: median length of the ``step.rebuild`` spans on the
decode workers' tracks.  A rebuild follows a join, a leave or an
exhausted page margin: the device state is written back to the host
(``step.writeback``), rebuilt there and put back (``step.build``)."""
import numpy as np


def read(ctx):
    vals = [s.t1 - s.t0 for s in ctx.spans("step.rebuild", "worker")]
    return 1e3 * float(np.median(vals)) if vals else None
