"""Serving loop: 90th percentile of the time a window request spent in
its first ``queue`` phase (submitted, waiting for prefill), from the
program's request-lifecycle spans."""
import numpy as np


def read(ctx):
    rids = {r["rid"] for r in ctx.requests if r["rid"]}
    first = {}
    for s in sorted(ctx.spans("queue", "request"), key=lambda s: s.t0):
        first.setdefault(s.track[1], s.t1 - s.t0)
    vals = [v for rid, v in first.items() if rid in rids]
    return 1e3 * float(np.percentile(vals, 90)) if vals else None
