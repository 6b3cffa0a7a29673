"""One less the device's busy time over the traced window, in %."""


def read(ctx):
    r = ctx.reduction
    if r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
