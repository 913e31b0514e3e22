"""Model step: model FLOPs of the traced window's decode steps (from the
batch's valid contexts) over their device time times the chip's peak
bf16 FLOP/s."""
from devtrace import share_pct


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    flops = sum(ctx.flops.decode_flops(ctx.sizes, c) for c in ctx.calls["decode"])
    return share_pct(flops, ctx.trace["decode_s"] * ctx.peak["bf16_flop_per_s"],
                     "decode.mfu")
