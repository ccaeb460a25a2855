"""Share of the traced window in which no op ran on a device, in percent,
averaged over the cell's devices."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
