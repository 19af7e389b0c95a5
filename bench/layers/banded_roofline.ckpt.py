"""Kernels: the least time the chip needs for the applies' counted work
(bench/work.py, the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s) over the Pallas kernels' device time, in the engine calls the
trace holds whole, in %.  Every apply in this mix is banded (K 256 at N
4096) on a 4096-wide payload; the counted bytes still set the bound
(per call 1.05 s against 0.65 s of operations at the bf16 peak)."""


def read(ctx):
    return ctx.roofline_share()
