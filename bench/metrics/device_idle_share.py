"""Share of the traced window in which no operation runs on the device
(the union of device op intervals), the mean over the cell's chips."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
