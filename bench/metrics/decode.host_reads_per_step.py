"""Decode worker: mean, over the ``step.commit`` spans in the traced
window and drain, of their ``reads`` attribute, the device-to-host reads
one step's commit makes.  None where no commit carries the attribute."""
import numpy as np


def read(ctx):
    reads = [s.attrs["reads"] for s in ctx.spans("step.commit", "worker")
             if "reads" in s.attrs]
    return float(np.mean(reads)) if reads else None
