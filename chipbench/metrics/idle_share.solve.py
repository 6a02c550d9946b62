"""Share of the traced window in which no operation ran on the device, in
the solve cells: 100 * (1 - busy / window), busy the union of the
device's operation intervals (averaged over the chips used)."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
