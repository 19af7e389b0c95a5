"""Codec: host time in the program's ``compress.encode`` span (quantize,
delta-code and deflate a group's sorted tensors;
``repro.runtime.sog_compress.compress_group``), in ms per engine call
the trace holds whole.  Like bench/phases.py it reads the run's trace
file again, counting only inside ``bench.window`` and the
``bench.engine_call`` spans the trace holds whole.  None where the
program has no such span."""
import functools
import glob
import os

import phases
import trace_reduce

SPAN = "compress.encode"


def reduce(profile):
    """(window start or None, whole calls, ns of ``compress.encode``
    inside them)."""
    _, spans = trace_reduce.read_planes(profile)
    win = [sp for sp in spans if sp.name == "bench.window"]
    lo, hi = ((win[0].start, win[0].end) if win
              else (float("-inf"), float("inf")))
    calls = trace_reduce._union(
        [(sp.start, sp.end) for sp in spans
         if sp.name == phases.CALL_SPAN and "call" in sp.args])
    total = 0.0
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if trace_reduce.parse_span_name(ev.name)[0] != SPAN:
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if s >= lo and e <= hi and any(a <= s and e <= b
                                               for a, b in calls):
                    total += e - s
    return (win[0].start if win else None, len(calls), total)


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
        start, calls, ns = _reduce_file(path, os.stat(path).st_mtime_ns)
        if [start] == want or (start is None and not want):
            if not calls or not ns:
                return None
            return ns / calls / 1e6
    return None
