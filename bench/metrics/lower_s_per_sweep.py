"""Seconds per sweep that JAX spends tracing programs to jaxprs and
lowering them to StableHLO, before XLA compiles them: the union of the
program's ``jaxpr_trace`` and ``lower`` spans (JAX's own compile-phase
events; a trace nested in another counts once)."""

PHASES = ("jaxpr_trace", "lower")


def read(ctx):
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in ctx["spans"]
                   if e["name"] in PHASES)
    if not spans:
        return None
    total, (lo, hi) = 0.0, spans[0]
    for a, b in spans[1:]:
        if a > hi:
            total += hi - lo
            lo, hi = a, b
        else:
            hi = max(hi, b)
    total += hi - lo
    return total / 1e6 / ctx["sweeps"]
