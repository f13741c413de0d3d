"""Device: share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window), in %."""


def read(ctx):
    w = ctx.reduced.window[1] - ctx.reduced.window[0]
    if w <= 0:
        return None
    return 100.0 * (1.0 - ctx.reduced.busy_ns / w)
