"""Kernels: device time of the backward Pallas kernels (its two passes,
``softsort_*_bwd_*``), in ms per engine call the trace holds whole,
mean over the chips."""
import phases


def read(ctx):
    p = phases.of(ctx)
    return None if p is None else p.kernel_ms(phases.BWD_KERNEL)
