"""Device idle share of the traced window: 1 - (union of the intervals in
which an operation ran on the chip) / (window length), in %."""


def read(ctx):
    r = ctx.reduction
    if r is None or r.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
