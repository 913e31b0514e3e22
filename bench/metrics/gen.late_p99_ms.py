"""Load generator: 99th percentile of how late each window request was
submitted after it was due (harness clock).  A late generator would
otherwise read as a fast server."""
import numpy as np


def read(ctx):
    late = [r["submitted"] - r["due"] for r in ctx.requests]
    return 1e3 * float(np.percentile(late, 99)) if late else None
