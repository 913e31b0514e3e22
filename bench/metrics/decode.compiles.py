"""Decode worker: ``jit_decode_step`` programs added to the jit cache
inside the window and drain (compiled, or loaded from the persistent
cache).  Set-up warms every shape the schedule can reach, so this should
read 0."""


def read(ctx):
    return float(ctx.compiles["jit_decode_step"])
