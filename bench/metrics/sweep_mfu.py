"""The whole sweep's share of the chips' peak: operations the spec
requires (live workers only, padding excluded; bench/flops) over the host
seconds of one untraced sweep (timed just before the profiled one), over
chips times the bf16 peak."""

from harness import work


def read(ctx):
    return 100.0 * work.sweep_flops(ctx["timed_spec"]) / ctx["timed_s"] / (
        ctx["chips"] * ctx["peaks"]["flops"])
