"""Share of the traced window in which no operation ran on the chip."""


def read(ctx):
    lo, hi = ctx.traced
    return 100.0 * (1.0 - ctx.busy_s / (hi - lo))
