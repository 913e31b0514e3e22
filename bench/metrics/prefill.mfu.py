"""Model step: model FLOPs of the traced window's prefill calls (from
their shapes) over their device time times the chip's peak bf16 FLOP/s."""
from devtrace import share_pct


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    flops = sum(ctx.flops.prefill_flops(ctx.sizes, n) for n in ctx.calls["prefill"])
    return share_pct(flops, ctx.trace["prefill_s"] * ctx.peak["bf16_flop_per_s"],
                     "prefill.mfu")
