"""Reduce a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

What a TPU trace holds, as read by ``jax.profiler.ProfileData``: one
plane per chip named ``/device:TPU:<i>`` whose line ``XLA Ops`` has one
event per executed HLO op.  Ops nest: a ``while`` (a scan or a
fori_loop) is an event that contains the events of its body, so a sum
of durations counts nested time twice.  Pallas kernels are custom calls
whose HLO text names ``custom_call_target="tpu_custom_call"``.  The
host plane ``/host:CPU`` holds the harness's ``TraceAnnotation`` spans
(names starting ``bench.``), on the same clock as the device events.

The reduction, per chip:

* busy: the union of the op intervals inside the window; idle is the
  rest of the window;
* kernel time: the summed durations of the Pallas custom calls (they
  are leaves: nothing nests inside a kernel), optionally only those
  inside given host spans;
* top ops: device time by op kind, counting self time only (an op's
  duration less the ops nested inside it);
* idle gaps: each stretch of the window with no op on the chip,
  labelled by the harness span the host was in at the gap's midpoint.
"""
from __future__ import annotations

import bisect
import dataclasses
import re

KERNEL_TAG = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."

# Host spans a gap is attributed to, most specific first.
SPAN_ORDER = ("bench.engine_call", "bench.fetch", "bench.submit",
              "bench.window")


@dataclasses.dataclass
class Span:
    name: str            # "bench.engine_call"
    args: dict           # TraceAnnotation keyword arguments, as strings
    start: float         # ns, trace clock
    end: float


@dataclasses.dataclass
class DeviceOps:
    device: int
    intervals: list      # [(start_ns, end_ns, name)], sorted by start


@dataclasses.dataclass
class TraceSummary:
    window_ns: float
    busy_ns: list                 # per device, inside the window
    kernel_ns: float              # summed over devices
    top_ops: list                 # [(kind, self seconds)] summed over devices
    idle_gaps: list               # [(label, idle seconds)] summed over devices
    spans: list                   # harness spans (Span)

    @property
    def n_devices(self) -> int:
        return len(self.busy_ns)

    @property
    def busy_s(self) -> float:
        """Mean over the chips of the seconds in which an op ran."""
        return sum(self.busy_ns) / max(len(self.busy_ns), 1) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def parse_span_name(raw: str) -> tuple[str, dict]:
    """Split ``bench.x#call=3#`` into the name and its arguments (a
    ``TraceAnnotation``'s keyword arguments come as the event's stats
    too, which ``read_planes`` adds)."""
    name, _, rest = raw.partition("#")
    args = {}
    for part in rest.split("#"):
        key, eq, val = part.partition("=")
        if eq:
            args[key] = val
    return name, args


def op_kind(name: str) -> str:
    """``%fusion.162 = f32[...] fusion(...)`` -> ``fusion``; Pallas
    kernels -> ``pallas:<op>``, e.g. ``pallas:jvp__`` (a forward kernel
    under a custom VJP) and ``pallas:transpose_jvp___`` (a backward)."""
    lhs = name.split(" = ", 1)[0].lstrip("%")
    base = re.sub(r"(\.\d+)+$", "", lhs)
    if KERNEL_TAG in name:
        return "pallas:" + base
    return base


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def read_planes(profile):
    """(device ops per chip, harness spans) from a ProfileData."""
    devices, spans = [], []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    iv = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in line.events]
                    iv.sort(key=lambda t: (t[0], -t[1]))
                    devices.append(DeviceOps(int(m.group(1)), iv))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        name, args = parse_span_name(ev.name)
                        args.update((str(k), str(v)) for k, v in ev.stats)
                        spans.append(Span(name, args, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    devices.sort(key=lambda d: d.device)
    return devices, spans


def _self_times(intervals, lo, hi, acc):
    """Add each op's self time (clipped to [lo, hi]) to ``acc`` by kind.
    ``intervals`` are sorted by (start, -end), so a parent precedes the
    ops nested inside it."""
    stack = []          # [end, kind, child_ns, clipped_ns]

    def close(entry):
        acc[entry[1]] = acc.get(entry[1], 0.0) + entry[3] - entry[2]

    for s, e, name in intervals:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        clipped = max(0.0, min(e, hi) - max(s, lo))
        if stack:
            stack[-1][2] += clipped
        stack.append([e, op_kind(name), 0.0, clipped])
    while stack:
        close(stack.pop())


def _label_at(t, spans):
    for name in SPAN_ORDER:
        for sp in spans:
            if sp.name == name and sp.start <= t <= sp.end:
                return name
    return "host outside harness spans"


def summarize(profile, kernel_spans: list | None = None,
              top: int = 10) -> TraceSummary:
    """Reduce ``profile`` over the ``bench.window`` span (else the span of
    all device ops), in trace-clock ns.

    ``kernel_spans``: count kernel time only inside these [(start,
    end)] host intervals (the engine calls whose work the caller
    counted); None counts every kernel in the window.
    """
    devices, spans = read_planes(profile)
    win = [sp for sp in spans if sp.name == "bench.window"]
    if win:
        lo, hi = win[0].start, win[0].end
    else:
        starts = [d.intervals[0][0] for d in devices if d.intervals]
        ends = [max(e for _, e, _ in d.intervals) for d in devices
                if d.intervals]
        lo, hi = (min(starts), max(ends)) if starts else (0.0, 0.0)
    allowed = None if kernel_spans is None else _union(kernel_spans)
    allowed_starts = [a for a, _ in allowed or []]
    busy, kernel_ns, self_acc, gap_acc = [], 0.0, {}, {}
    for dev in devices:
        inside = [(max(s, lo), min(e, hi)) for s, e, _ in dev.intervals
                  if e > lo and s < hi]
        merged = _union(inside)
        busy.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for se in merged for x in se] + [hi]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                label = _label_at((g0 + g1) / 2, spans)
                gap_acc[label] = gap_acc.get(label, 0.0) + (g1 - g0)
        for s, e, name in dev.intervals:
            if KERNEL_TAG not in name or s < lo or e > hi:
                continue
            if allowed is not None:
                i = bisect.bisect_right(allowed_starts, s) - 1
                if i < 0 or e > allowed[i][1]:
                    continue
            kernel_ns += e - s
        _self_times(dev.intervals, lo, hi, self_acc)
    top_ops = sorted(((k, v / 1e9) for k, v in self_acc.items() if v > 0),
                     key=lambda kv: -kv[1])[:top]
    gaps = sorted(((k, v / 1e9) for k, v in gap_acc.items()),
                  key=lambda kv: -kv[1])[:top]
    return TraceSummary(window_ns=hi - lo, busy_ns=busy,
                        kernel_ns=kernel_ns, top_ops=top_ops,
                        idle_gaps=gaps, spans=spans)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)
