"""Decode worker: median length of the serving loop's ``tick.step`` spans
on the decode workers' tracks (one continuous-batching step, with any
batch rebuild it triggers)."""
import numpy as np


def read(ctx):
    vals = [s.t1 - s.t0 for s in ctx.spans("tick.step", "worker")]
    return 1e3 * float(np.median(vals)) if vals else None
