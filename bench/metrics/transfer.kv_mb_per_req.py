"""Transfer: mean KV megabytes pulled per window request, from the
program's ``HandleMetrics.kv_bytes_pulled`` counter."""


def read(ctx):
    vals = [r["kv_bytes_pulled"] for r in ctx.requests if r["ok"]]
    return sum(vals) / len(vals) / 1e6 if vals and sum(vals) else None
