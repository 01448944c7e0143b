"""Seconds of the first call of the timed entry in set-up: the warm-up
dispatch (sweep), or the step's compile plus its first step (train).  With
a warm compilation cache this is mostly loading and running; cold, mostly
compiling.  Host clock."""


def read(ctx):
    return ctx["layer"]["first_call_s"]
