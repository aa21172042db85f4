"""``jit__pairwise_l0_means`` (core/metrics.py): the mean pairwise L0
distance inside each of nb batches of b rows, as one scan over the b - 1
in-batch shifts, each one Pallas ``l0_rows`` call over all nb * b rows.

One call is ``(nb, b, d)``.  Its input starts in HBM, so the least time
reads it once; the scan then works in on-chip memory."""

#: the program's name on the trace's "XLA Modules" line
NAME = r"^jit__pairwise_l0_means\("


def ops(nb: int, b: int, d: int) -> int:
    """subtract, magnitude, compare, accumulate per element and shift."""
    return 4 * nb * b * d * (b - 1)


def bytes_moved(nb: int, b: int, d: int) -> int:
    """The batches read once; one float32 mean per batch written."""
    return 4 * nb * b * d + 4 * nb
