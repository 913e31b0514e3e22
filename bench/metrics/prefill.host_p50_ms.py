"""Prefill worker: median, over prefills, of the host work after the
pages reach the host: ``prefill.park`` (the slab writes), ``prefill.hash``
(the block digests) and ``prefill.quant`` (int8 scales, when transfer
quantisation is on).  Each ``prefill.compute`` on a worker's track opens
the next prefill."""
import collections

import numpy as np

HOST = ("prefill.park", "prefill.hash", "prefill.quant")


def read(ctx):
    tracks = collections.defaultdict(list)
    for s in ctx.spans(track_kind="worker"):
        if s.name == "prefill.compute" or s.name in HOST:
            tracks[s.track].append(s)
    vals = []
    for spans in tracks.values():
        per = []
        for s in sorted(spans, key=lambda s: s.t0):
            if s.name == "prefill.compute":
                per.append(0.0)
            elif per:
                per[-1] += s.t1 - s.t0
        vals += per
    return 1e3 * float(np.median(vals)) if vals else None
