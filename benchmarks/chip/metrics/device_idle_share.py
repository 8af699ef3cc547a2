"""Share of the traced window in which no operation ran on the chip
(mean over the cell's chips): 100 * (1 - busy / window)."""


def read(ctx):
    t = ctx.trace
    if not t.device_ops or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
