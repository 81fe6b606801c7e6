"""Device: the share of the traced stretch in which no operation ran
(1 - union of op intervals / stretch), in percent."""


def read(ctx):
    if ctx.trace.window_s <= 0 or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
