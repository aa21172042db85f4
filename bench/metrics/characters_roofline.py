"""Share of their roofline that the characters programs reach: C_sim
(``jit_csim_kernel``) and the batch similarity (``jit__pairwise_l0_means``),
both scans over the Pallas ``l0_rows`` kernel.  The least time their calls
could take on this chip (operations over peak, or the bytes they must
read from HBM over its bandwidth, whichever is larger; bench/flops/kernels)
over their device time on the trace's "XLA Modules" line."""

from harness import cells, work


def read(ctx):
    seconds = sum(ctx["trace"]["program_s"].get(k, 0.0)
                  for k in ("csim", "pairwise_l0"))
    if seconds <= 0:
        return None                  # they did not run on the device
    least = 0.0
    for spec in ctx["specs"]:
        for name, calls in work.character_calls(spec).items():
            k = cells.kernel_counts(name)
            for c in calls:
                least += max(k.ops(*c) / ctx["peaks"]["flops"],
                             k.bytes_moved(*c)
                             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
