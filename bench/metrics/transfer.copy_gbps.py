"""Transfer: logical KV bytes the engine copied over the time spent in
its copy loops, from the ``transfer.copy`` spans (one per executed
window of reads) in GB/s.  Against ``transfer.wait_p50_ms`` it says
whether a pull is bound by the copying or by its pacing."""


def read(ctx):
    spans = ctx.spans("transfer.copy", "engine")
    secs = sum(s.t1 - s.t0 for s in spans)
    nbytes = sum(s.attrs.get("bytes", 0) for s in spans)
    return nbytes / secs / 1e9 if secs > 0 and nbytes else None
