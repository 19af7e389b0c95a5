"""Kernels: device time of the forward Pallas kernels (the sweep and
its colsum pass, ``softsort_*_fwd`` and ``softsort_*_colsum``), in ms
per engine call the trace holds whole, mean over the chips."""
import phases


def read(ctx):
    p = phases.of(ctx)
    return None if p is None else p.kernel_ms(phases.FWD_KERNEL)
