"""Device: the share of the traced window in which no operation ran on
the chip (mean over the chips), in %."""


def read(ctx):
    s = ctx.summary
    if s is None or s.window_ns <= 0 or not s.busy_ns:
        return None
    return 100.0 * s.idle_share
