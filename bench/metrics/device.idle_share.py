"""Device: share of the traced window in which no operation ran on the
chip: 1 - (union of the device's op intervals) / window."""
from devtrace import share_pct


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace
    return share_pct(t["window_s"] - t["busy_s"], t["window_s"], "device.idle_share")
