"""Cells cut to a size the CPU runs in seconds, for the harness's tests.

The deployment's shapes shrink to grids of 16x16 (``band="auto"`` gives
K 64: the banded kernels, in interpret mode) and to 32 rounds of 8
steps, enough for a layout to settle so that an answer left unsorted
reads apart from a sound one.  The drivers, the reference and the
limits are the cell's own."""
from __future__ import annotations

import copy
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import cell  # noqa: E402


def small_spec(workload: str):
    spec = copy.deepcopy(cell.load_spec(workload))
    spec.config["sort"]["rounds"] = 32
    spec.traffic.update(side=16, collections=2, pool=2, sampled_calls=1)
    return spec
