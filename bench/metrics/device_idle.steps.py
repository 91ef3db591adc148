"""The share of the traced window in which no operation ran on the
device, averaged over the chips, in a fixed-step cell."""


def read(ctx):
    t = ctx.trace
    if not t.window_s or t.busy_s is None:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
