"""XLA backend compiles per sweep, counted from JAX's events (the program
counts its own engine compiles as repro_engine_jit_compiles_total; the
run prints that beside this on standard error)."""


def read(ctx):
    return ctx["compiles"] / ctx["sweeps"]
