"""Kernels: the roofline bound of the traced window's decode steps over
the device time of ``jit_decode_step``.  Each step's bound is the larger
of its FLOPs over peak FLOP/s and its bytes (weights once, the valid KV
once) over HBM bandwidth; at these batches the bytes bound it."""
from devtrace import share_pct


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    f = ctx.flops
    bound = sum(f.roofline_s(f.decode_flops(ctx.sizes, c), f.decode_bytes(ctx.sizes, c),
                             ctx.peak) for c in ctx.calls["decode"])
    return share_pct(bound, ctx.trace["decode_s"], "decode_step_roofline")
