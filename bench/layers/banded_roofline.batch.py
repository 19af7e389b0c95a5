"""Kernels: the least time the chip needs for the applies' counted work
(bench/work.py, the larger of FLOPs over peak FLOP/s and bytes over peak
bytes/s) over the Pallas kernels' device time, in the engine calls the
trace holds whole, in %.  Every apply in this mix is banded."""


def read(ctx):
    return ctx.roofline_share()
