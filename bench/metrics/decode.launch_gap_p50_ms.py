"""Decode worker: median, over consecutive decode steps on one worker,
of the next ``step.launch`` start minus the previous ``step.commit`` end.
The commit waits for the chip, so the chip has nothing queued from then
until the next launch: a lower bound on the time the host keeps it idle
between steps."""
import collections

import numpy as np


def read(ctx):
    tracks = collections.defaultdict(list)
    for s in ctx.spans(track_kind="worker"):
        if s.name in ("step.launch", "step.commit"):
            tracks[s.track].append(s)
    gaps = []
    for spans in tracks.values():
        committed = None  # end of the last commit since the last launch
        for s in sorted(spans, key=lambda s: s.t0):
            if s.name == "step.commit":
                committed = s.t1
            elif committed is not None:
                gaps.append(s.t0 - committed)
                committed = None
    return 1e3 * float(np.median(gaps)) if gaps else None
