"""Split a traced run's engine calls by phase, from the program's own
spans and kernel names.

The batched engine's fixed-schedule path (``shuffle_soft_sort_batched``)
marks each call with host spans on the profiler's clock:

    sort.call        the whole call
      sort.prep      inputs, schedule and band on the host
      sort.launch    enqueueing the scanned device program
      sort.wait      the host blocked until the program's outputs are ready
      sort.collect   finite check, fetch, winner selection, sorted gather

and names each Pallas kernel ``softsort_<tier>_<pass>``: forward passes
``fwd`` and ``colsum``, backward passes ``bwd_*``.  In the trace the
name sits inside JAX's transform prefixes, e.g.
``jvp_softsort_band_fwd_`` and ``transpose_jvp_softsort_band_bwd_delta__``.

``trace_reduce.read_planes`` keeps only the harness's ``bench.*`` spans,
so this module reads the run's trace file again.  Like ``bench/run.py``
it counts only the engine calls the trace holds whole (the
``bench.engine_call`` spans in it) and only inside ``bench.window``.
A program without these spans or names gives empty tables, and the
readers then report nothing.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import glob
import os
import re

import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(BENCH, ".work")      # where bench/run.py's tracer writes
CALL_SPAN = "bench.engine_call"
HOST_PHASES = ("sort.prep", "sort.launch", "sort.collect")
WAIT_PHASE = "sort.wait"
# By whole name: on some backends the host plane also holds XLA ops
# named like ``sort.96``.
SPANS = ("sort.call",) + HOST_PHASES + (WAIT_PHASE,)
FWD_KERNEL = re.compile(r"softsort_[a-z]+_(fwd|colsum)(?![a-z])")
BWD_KERNEL = re.compile(r"softsort_[a-z]+_bwd_")


@dataclasses.dataclass
class Phases:
    window_start: float | None   # the trace's bench.window span, ns
    calls: int          # engine calls the trace holds whole
    n_devices: int
    idle_ns: dict       # span name -> device-idle ns inside those spans
    kernel_ns: dict     # kernel op kind -> device ns; both summed over chips

    def per_call_ms(self, ns: float) -> float:
        """``ns`` summed over the chips, as ms per chip and whole call."""
        return ns / self.n_devices / self.calls / 1e6

    def idle_ms(self, names) -> float | None:
        """Device-idle ms per chip and whole call inside the spans named
        ``names``; None when the trace holds none of them."""
        found = [self.idle_ns[n] for n in names if n in self.idle_ns]
        return self.per_call_ms(sum(found)) if found and self.calls else None

    def kernel_ms(self, pattern: re.Pattern) -> float | None:
        """Device ms per chip and whole call of the kernels whose op
        kind matches ``pattern``; None when none does."""
        found = [ns for kind, ns in self.kernel_ns.items()
                 if pattern.search(kind)]
        return self.per_call_ms(sum(found)) if found and self.calls else None


def host_spans(profile) -> list:
    """The program's ``sort.*`` spans on the host plane, as
    [(name, start_ns, end_ns)]."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                name, _ = trace_reduce.parse_span_name(ev.name)
                if name in SPANS:
                    out.append((name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _clip(intervals, allowed):
    """The parts of ``intervals`` [(start, end)] inside the merged,
    sorted ``allowed`` ones."""
    starts = [a for a, _ in allowed]
    out = []
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        for a, b in allowed[i:]:
            if a >= e:
                break
            if b > s:
                out.append((max(s, a), min(e, b)))
    return out


def _idle_before(t, gap_starts, gap_ends, gap_cum):
    """Idle ns before ``t`` along sorted, disjoint gaps."""
    i = bisect.bisect_right(gap_starts, t) - 1
    if i < 0:
        return 0.0
    return gap_cum[i] + min(t, gap_ends[i]) - gap_starts[i]


def reduce(profile) -> Phases:
    devices, bench_spans = trace_reduce.read_planes(profile)
    win = [sp for sp in bench_spans if sp.name == "bench.window"]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        ops = [iv for d in devices for iv in d.intervals]
        lo = min((s for s, _, _ in ops), default=0.0)
        hi = max((e for _, e, _ in ops), default=0.0)
    calls = trace_reduce._union(
        [(sp.start, sp.end) for sp in bench_spans
         if sp.name == CALL_SPAN and "call" in sp.args])
    named = {}
    for name, start, end in host_spans(profile) + [
            (sp.name, sp.start, sp.end) for sp in bench_spans
            if sp.name == CALL_SPAN]:
        named.setdefault(name, []).append((start, end))
    named = {k: _clip(trace_reduce._union(v), calls)
             for k, v in named.items()}
    call_starts = [a for a, _ in calls]
    idle, kernels = {}, {}
    for dev in devices:
        busy = trace_reduce._union(
            [(max(s, lo), min(e, hi)) for s, e, _ in dev.intervals
             if e > lo and s < hi])
        edges = [lo] + [x for se in busy for x in se] + [hi]
        gaps = [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2])
                if g1 > g0]
        starts = [g0 for g0, _ in gaps]
        ends = [g1 for _, g1 in gaps]
        cum = [0.0]
        for g0, g1 in gaps:
            cum.append(cum[-1] + g1 - g0)
        for name, ivs in named.items():
            idle[name] = idle.get(name, 0.0) + sum(
                _idle_before(e, starts, ends, cum)
                - _idle_before(s, starts, ends, cum) for s, e in ivs)
        for s, e, op in dev.intervals:
            if trace_reduce.KERNEL_TAG not in op or s < lo or e > hi:
                continue
            i = bisect.bisect_right(call_starts, s) - 1
            if i < 0 or e > calls[i][1]:
                continue
            kind = trace_reduce.op_kind(op)
            kernels[kind] = kernels.get(kind, 0.0) + e - s
    return Phases(window_start=win[0].start if win else None,
                  calls=len(calls), n_devices=max(len(devices), 1),
                  idle_ns=idle, kernel_ns=kernels)


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime_ns: int):
    return reduce(trace_reduce.load(path))


def of(ctx) -> Phases | None:
    """The phases of the trace behind ``ctx.summary``: the newest trace
    under ``WORK`` whose ``bench.window`` starts where the summary's
    does.  None when the run traced nothing."""
    if ctx.summary is None:
        return None
    want = [sp.start for sp in ctx.summary.spans
            if sp.name == "bench.window"]
    paths = glob.glob(os.path.join(WORK, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        phases = _reduce_file(path, os.stat(path).st_mtime_ns)
        if [phases.window_start] == want or (
                phases.window_start is None and not want):
            return phases
    return None
