"""1 - (union of the device's operation intervals) / traced window."""


def read(ctx):
    r = ctx.reduced
    return None if r is None else 100.0 * (1.0 - r.busy_s / r.window_s)
