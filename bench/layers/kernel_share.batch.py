"""Kernels: the Pallas kernels' device time over the device's busy time,
in %.  The rest is argsort, gathers, the loss and Adam."""


def read(ctx):
    s = ctx.summary
    if s is None or sum(s.busy_ns) <= 0 or s.kernel_ns <= 0:
        return None
    return 100.0 * s.kernel_ns / sum(s.busy_ns)
