"""Share of the traced window in which no operation ran on a chip, mean
over the cell's chips: 1 - (union of busy intervals) / window. Layer:
device."""
UNIT = "%"


def read(ctx):
    return 100.0 * (1.0 - ctx.busy_s / ctx.traced_window_s)
