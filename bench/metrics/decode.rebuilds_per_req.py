"""Decode worker: ``step.rebuild`` spans in the traced window and drain
over the window's requests.  None where the program records no decode
steps (no ``step.launch`` span) to count rebuilds against."""


def read(ctx):
    if not ctx.spans("step.launch", "worker") or not ctx.requests:
        return None
    return len(ctx.spans("step.rebuild", "worker")) / len(ctx.requests)
