"""``jit_csim_kernel`` (kernels/csim.py): C_sim of Eq. 3 as one scan over
the shift range, each shift one Pallas ``l0_rows`` call comparing the
(n, d) rows with their shifted copy.

One call is ``(n, d, rng)``.  Its input starts in HBM, so the least time
reads it once; the scan then works in on-chip memory."""

#: the program's name on the trace's "XLA Modules" line
NAME = r"^jit_csim_kernel\("


def ops(n: int, d: int, rng: int) -> int:
    """subtract, magnitude, compare, accumulate per element and shift."""
    return 4 * n * d * rng


def bytes_moved(n: int, d: int, rng: int) -> int:
    """The rows read once; one float32 result written."""
    return 4 * n * d + 4
