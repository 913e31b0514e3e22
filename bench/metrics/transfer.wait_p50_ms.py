"""Transfer: median, over window requests, of the time from the first
token to the start of decode: the ``queue.kv`` and ``transfer`` phases
(and any ``queue.decode``) of the program's request-lifecycle spans."""
import collections

import numpy as np

PHASES = ("queue.kv", "transfer", "queue.decode")


def read(ctx):
    rids = {r["rid"] for r in ctx.requests if r["rid"]}
    acc = collections.Counter()
    for s in ctx.spans(track_kind="request"):
        if s.name in PHASES and s.track[1] in rids:
            acc[s.track[1]] += s.t1 - s.t0
    return 1e3 * float(np.median(list(acc.values()))) if acc else None
