"""Shared pieces of the harness: the benchmark's files, the device
check, the log of engine calls, the tracer, and the comparison of the
readings with their limits.

Every file a cell needs is found by name:

    BENCHMARK.json                     workloads, configs, metrics
    bench/configs/<config>.json        the deployment (``file`` in configs)
    bench/traffic/<traffic>.json       the mix; names its driver
    bench/drivers/<driver>.py          runs the mix against the system
    bench/limits/<workload>.json       the limit of each compared number
    bench/layers/<metric>.py           reads one per-layer metric
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SRC = os.path.join(ROOT, "src")


class NoDevice(RuntimeError):
    """The run found no chip it may measure on."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """Everything one workload is made of, read from its files."""
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])


def load_spec(workload: str, root: str = ROOT) -> Spec:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise SystemExit(f"unknown workload {workload!r}")
    wl = found[0]
    conf = [c for c in bench["configs"] if c["name"] == wl["config"]][0]
    spec = Spec(
        workload=wl,
        config=load_json(os.path.join(root, conf["file"])),
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       wl["traffic"] + ".json")),
        limits=load_json(os.path.join(BENCH, "limits", workload + ".json")),
        end_to_end=bench["end_to_end"],
        per_layer=bench["per_layer"],
    )
    return spec


def driver_module(spec: Spec):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    return importlib.import_module("drivers." + spec.traffic["driver"])


def layer_reader(metric: str):
    path = os.path.join(BENCH, "layers", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "layer_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def sort_config(spec: Spec):
    """The program's ShuffleSoftSortConfig for this deployment."""
    from repro.core.shufflesoftsort import ShuffleSoftSortConfig
    return ShuffleSoftSortConfig(**spec.config["sort"])


def check_device(chips: int) -> dict:
    """The chips this run measures on, or NoDevice: a TPU whose kind is
    in the peaks table, with at least ``chips`` of them."""
    import jax
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX found no device: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise NoDevice(f"backend is {platform!r}, not 'tpu'")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise NoDevice(f"device kind {kind!r} is not in bench/peaks.json")
    if len(devices) < chips:
        raise NoDevice(f"{len(devices)} chips found, the cell needs {chips}")
    return {"platform": platform, "kind": kind, "count": chips,
            "peak": peaks[kind], "devices": devices[:chips]}


def host_device(chips: int) -> dict:
    """The device record of a run that measures nothing (tests on the
    CPU): same shape as ``check_device``'s, never printed as a result."""
    import jax
    devices = jax.devices()
    peaks = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": chips,
            "peak": next(iter(peaks.values())), "devices": devices[:chips]}


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# ---------------------------------------------------------------------------
# Engine calls: timing, counted work, and a seeded sample kept for the check.
# ---------------------------------------------------------------------------

class Reservoir:
    """A uniform sample of at most ``size`` items from a stream, drawn
    with the run's seeded generator."""

    def __init__(self, rng: np.random.Generator, size: int):
        self.rng = rng
        self.size = int(size)
        self.items: list = []
        self.seen = 0

    def offer(self, make_item):
        """``make_item()`` builds the item only when it is kept."""
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(make_item())
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.size:
                self.items[j] = make_item()


@dataclasses.dataclass
class Call:
    """One call into the system's engine, as the harness timed it."""
    index: int
    t0: float              # perf_counter at entry
    t1: float              # perf_counter once its results were on the host
    flops: float           # counted work (bench/work.py)
    nbytes: float
    slots: int             # instances the call computed


class CallLog:
    def __init__(self):
        self.calls: list[Call] = []
        self._index = 0

    def next_index(self) -> int:
        self._index += 1
        return self._index - 1


def apply_work(n: int, k: int | None, d: int, slots: int, rounds: int,
               inner: int, dtype: str) -> tuple[float, float]:
    """Counted work of ``rounds`` outer rounds of ``inner`` train steps
    on ``slots`` instances: one apply forward and backward per step."""
    import work
    steps = slots * rounds * inner
    return (float(work.apply_flops(1, n, k, d)) * steps,
            float(work.apply_bytes(1, n, d, dtype)) * steps)


@dataclasses.dataclass
class LayerInput:
    """What a per-layer reader may read: the trace's summary (None when
    the run traced nothing), the driver's counters over the window, its
    engine calls and the chip's peaks."""
    summary: object
    counters: dict
    calls: list
    peak: dict

    def traced_calls(self) -> list:
        if self.summary is None:
            return []
        whole = set(self.summary.traced_calls)
        return [c for c in self.calls if c.index in whole]

    def roofline_share(self) -> float | None:
        """Least time for the counted work of the engine calls the trace
        holds whole, over the kernels' device time inside them, in %."""
        import work
        calls = self.traced_calls()
        if not calls or self.summary.kernel_ns <= 0:
            return None
        t, _ = work.roofline_seconds(sum(c.flops for c in calls),
                                     sum(c.nbytes for c in calls), self.peak)
        return 100.0 * t / (self.summary.kernel_ns / 1e9)


# ---------------------------------------------------------------------------
# Tracing a stretch of the window.
# ---------------------------------------------------------------------------

class Tracer:
    """Profiles [start, stop) seconds into the window when ``on``; the
    driver calls ``poll(elapsed)`` at points where it may start or stop.
    The ``bench.window`` span marks what was traced."""

    def __init__(self, on: bool, outdir: str, start: float, stop: float):
        self.on = on
        self.outdir = outdir
        self.start_at = start
        self.stop_at = stop
        self.state = "idle" if on else "off"
        self._span = None

    def poll(self, elapsed: float) -> None:
        """Start or stop the profiler when ``elapsed`` (seconds into the
        window) has reached the stretch's start or stop."""
        import jax
        if self.state == "idle" and elapsed >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.outdir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self.state = "tracing"
        elif self.state == "tracing" and elapsed >= self.stop_at:
            self.finish()

    def finish(self) -> None:
        import jax
        if self.state == "tracing":
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"

    def xplane(self) -> str | None:
        found = []
        for d, _, files in os.walk(self.outdir):
            found += [os.path.join(d, f) for f in files
                      if f.endswith(".xplane.pb")]
        return max(found, key=os.path.getmtime) if found else None


def span(name: str, **kw):
    """A host span in the profiler's trace (free when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


# ---------------------------------------------------------------------------
# Comparison with the limits.
# ---------------------------------------------------------------------------

def compare(readings: dict, limits: dict) -> tuple[bool, dict]:
    """Each reading against its limit: a number is within its limit when
    it is at most the limit.  A reading that is missing or not finite
    fails."""
    out, ok = {}, True
    for name, lim in limits["numbers"].items():
        v = readings.get(name)
        good = v is not None and np.isfinite(v) and v <= lim["limit"]
        ok &= bool(good)
        out[name] = {"value": None if v is None else float(v),
                     "limit": float(lim["limit"])}
    return ok, out
