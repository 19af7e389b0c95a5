"""Run one benchmark cell once and print its result as one JSON line.

    python3 bench/run.py --workload photo_grid.batch --seed 7 \
        --seconds 45 --trace 0

In order: keep JAX's compilation cache at a fixed path in the checkout
(or where JAX_COMPILATION_CACHE_DIR says), refuse any backend but a TPU
whose kind is in ``bench/peaks.json``, build the cell's data on the
host from ``--seed``, warm every program the cell's traffic runs (all
of that is ``setup_s``), measure for ``--seconds``, then check what the
window produced against the plain reference (``bench/reference.py``)
and print

    {"correct", "attempted", "failed", "metrics", "device",
     ["breakdown"], "compared"}

With ``--trace 1`` the metrics are the cell's per-layer metrics, read
from a profiler trace of a stretch of the window; with ``--trace 0``
its end-to-end metrics.  The numbers compared and their limits are the
last lines on standard error and the last key of the result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_SECONDS = 10.0


def _paths() -> None:
    for p in (BENCH, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout unless JAX_COMPILATION_CACHE_DIR names one; every program
    is kept, however fast it compiled, so warm set-up loads them all."""
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(ROOT, ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             chip: bool = True, spec=None, fault: str | None = None,
             control: bool = False) -> dict:
    """One run of ``workload``.  ``chip=False`` runs on whatever backend
    JAX has (tests on the CPU); ``spec`` replaces the cell's files (tests
    at a small size); ``fault`` plants one of ``bench/faults.py``'s
    faults under the timed path; ``control`` also reads the window
    against the reference at the next precision below
    (``control_readings``)."""
    _paths()
    import cell
    import check
    import faults
    import reference
    import trace_reduce

    spec = spec or cell.load_spec(workload)
    dev = cell.check_device(spec.chips) if chip else cell.host_device(
        spec.chips)
    driver = cell.driver_module(spec).Driver(spec, seed, dev)
    outdir = os.path.join(BENCH, ".work", f"{workload}.{seed}")
    shutil.rmtree(outdir, ignore_errors=True)
    lo, hi = driver.trace_stretch(seconds, min(seconds, TRACE_SECONDS))
    tracer = cell.Tracer(trace, outdir, lo, hi)
    with faults.planted(fault):
        driver.setup(seconds)
        setup_s = time.perf_counter() - T_PROCESS
        driver.run(seconds, tracer)
    e2e = driver.end_to_end()
    counts = driver.counts()
    mem = cell.memory_peak_bytes(dev["devices"])
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": mem}
    metrics, breakdown = {}, None
    units = {m["name"]: m["unit"] for m in spec.end_to_end + spec.per_layer}
    if trace:
        summary = _reduce(tracer, driver, trace_reduce)
        ctx = cell.LayerInput(summary=summary, counters=driver.counters(),
                              calls=driver.calls(), peak=dev["peak"])
        for m in spec.per_layer:
            if spec.reports(m):
                v = cell.layer_reader(m["name"])(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": [list(t) for t in summary.top_ops],
                         "idle_gaps": [list(t) for t in summary.idle_gaps]}
        shutil.rmtree(outdir, ignore_errors=True)
    else:
        for m in spec.end_to_end:
            if not spec.reports(m):
                continue
            v = setup_s if m["name"] == "setup_s" else e2e[m["name"]]
            metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    driver.release()
    ref = reference.Reference(spec.config["sort"], "highest",
                              seg_len=driver.seg_len)
    t_check = time.perf_counter()
    check.DETAIL.clear()
    readings = driver.readings(ref)
    check_s = time.perf_counter() - t_check
    detail = dict(check.DETAIL)
    control_readings = control_detail = None
    if control:
        check.DETAIL.clear()
        control_readings = driver.readings(reference.Reference(
            spec.config["sort"], "high", seg_len=driver.seg_len))
        control_detail = dict(check.DETAIL)
    ok, compared = cell.compare(readings, spec.limits)
    result = {"correct": bool(ok and counts["failed"] == 0),
              "attempted": counts["attempted"], "failed": counts["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    result["readings"] = readings
    result["control_readings"] = control_readings
    result["detail"] = detail
    result["control_detail"] = control_detail
    result["check_s"] = check_s
    result["counters"] = driver.counters()
    return result


def _reduce(tracer, driver, trace_reduce):
    """The trace's summary, with kernel time counted only inside the
    engine calls the trace holds whole."""
    path = tracer.xplane()
    if path is None:
        return None
    profile = trace_reduce.load(path)
    _, spans = trace_reduce.read_planes(profile)
    whole = {int(sp.args["call"]): (sp.start, sp.end) for sp in spans
             if sp.name == "bench.engine_call" and "call" in sp.args}
    summary = trace_reduce.summarize(profile,
                                     kernel_spans=list(whole.values()))
    summary.traced_calls = sorted(whole)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_cache()
    _paths()
    import cell
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except cell.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for key in ("readings", "control_readings", "check_s", "counters",
                "detail", "control_detail"):
        result.pop(key)
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
