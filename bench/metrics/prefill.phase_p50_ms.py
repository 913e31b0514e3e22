"""Prefill worker: median length of a window request's ``prefill`` phase
span (model prefill, device-to-host park of the KV, block hashes)."""
import numpy as np


def read(ctx):
    rids = {r["rid"] for r in ctx.requests if r["rid"]}
    vals = [s.t1 - s.t0 for s in ctx.spans("prefill", "request") if s.track[1] in rids]
    return 1e3 * float(np.median(vals)) if vals else None
