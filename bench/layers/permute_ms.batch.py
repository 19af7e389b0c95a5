"""Kernels: device time of the ops that move data by a permutation,
in ms per engine call the trace holds whole, mean over the chips:
XLA's sorts and its gathers and scatters, Pallas kernels left out.  On
a TPU v5e each gather or scatter runs as a fusion of kind ``kCustom``
(and no other op here does), and each sort as an op ``sort.<n>``.  In
the batch cell they order the keys, move the applies' keys, column sums
and payload between column and rank order, and move the loss's rows
back out of the round's shuffle; a permutation moved by a sort rather
than by a gather is counted either way.  Copies are not counted: a
``copy`` does not say whose layout it changes.  Like bench/phases.py it
reads the run's trace file again, counting only inside ``bench.window``
and the ``bench.engine_call`` spans the trace holds whole."""
import bisect
import functools
import glob
import os

import phases
import trace_reduce

CUSTOM_FUSION = "kind=kCustom"


def is_permute(op):
    """Whether the device op named ``op`` is a sort, a gather or a
    scatter."""
    if trace_reduce.KERNEL_TAG in op:
        return False
    return CUSTOM_FUSION in op or trace_reduce.op_kind(op) == "sort"


def reduce(profile):
    """(window start or None, whole calls, chips, [(op, ns)] of the
    sorts, gathers and scatters inside those calls, over every chip)."""
    devices, spans = trace_reduce.read_planes(profile)
    win = [sp for sp in spans if sp.name == "bench.window"]
    lo, hi = ((win[0].start, win[0].end) if win
              else (float("-inf"), float("inf")))
    calls = trace_reduce._union(
        [(sp.start, sp.end) for sp in spans
         if sp.name == phases.CALL_SPAN and "call" in sp.args])
    starts = [a for a, _ in calls]
    found = []
    for dev in devices:
        for s, e, op in dev.intervals:
            if not is_permute(op) or s < lo or e > hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and e <= calls[i][1]:
                found.append((op, e - s))
    return (win[0].start if win else None, len(calls),
            max(len(devices), 1), found)


@functools.lru_cache(maxsize=2)
def _reduce_file(path, mtime_ns):
    return reduce(trace_reduce.load(path))


def read(ctx):
    if ctx.summary is None:
        return None
    want = [sp.start for sp in ctx.summary.spans
            if sp.name == "bench.window"]
    paths = glob.glob(os.path.join(phases.WORK, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        start, calls, chips, found = _reduce_file(
            path, os.stat(path).st_mtime_ns)
        if [start] == want or (start is None and not want):
            if not calls or not found:
                return None
            return sum(ns for _, ns in found) / chips / calls / 1e6
    return None
