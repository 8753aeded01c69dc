"""Device: the share of the traced window in which no operation ran on
the fullest device, in percent.  Moves ``teps``."""


def read(ctx):
    window = ctx.hi - ctx.lo
    if window <= 0 or not ctx.busy[ctx.fullest]:
        return None
    return 100.0 * (1.0 - ctx.busy[ctx.fullest] / window)
