"""Seconds of XLA backend compile per sweep (engine buckets, eager ops and
dataset programs alike), from JAX's compile-duration events."""


def read(ctx):
    return ctx["compile_s"] / ctx["sweeps"]
