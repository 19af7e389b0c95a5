"""Closed-loop load generator for the continuous-batching SortServer.

    PYTHONPATH=src python -m benchmarks.serving_bench [--smoke]
        [--inject-faults] [--out BENCH_serving.json]

Drives synthetic heavy traffic at a live ``SortServer`` and records the
tail-latency/robustness numbers the serving tier claims (EXPERIMENTS.md
§Serving): Poisson arrivals (seeded, reproducible), a mixed-(N, d)
problem population exercising the shape-bucket compile cache, a
closed-loop outstanding-request window so the generator applies
backpressure-aware load rather than unbounded open-loop pile-up, and —
with ``--inject-faults`` — deterministic worker failures and straggler
delays injected at exact dispatch indices via
``runtime.fault_tolerance.FaultInjector``.

Four scenarios per run:

  * ``steady``    — in-budget load, no perturbations: the baseline
    p50/p99 and goodput row.
  * ``faults``    — same load with injected dispatch failures and one
    injected straggler delay; the row proves recovery (every fault is
    retried from the last committed round boundary; ``recoveries``
    counts requests that completed after >= 1 failed dispatch).
  * ``overload``  — arrival rate above service rate into a shallow
    queue with tight deadlines: the row shows load shedding doing its
    job (``queue_rejected`` + ``deadline_missed`` > 0) while admitted,
    in-deadline requests still complete.
  * ``preempt``   — warm restart under load: the server is killed
    (``close(drain=False)``) once a quarter of the traffic has
    completed, and a successor adopts the in-flight requests from
    their last committed round boundaries.  The row proves
    exactly-once accounting ACROSS server generations
    (``completed_gen1 + completed_gen2 == completed``) and that every
    preempted request was resumed (``resumed_requests ==
    preempted_inflight``) — EXPERIMENTS.md §Robustness.
  * ``capacity``  — elastic capacity loss (needs >= 8 devices, e.g.
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``): Poisson
    load against an 8-device mesh while a ``FaultInjector`` takes two
    devices down mid-run and brings one back; the DeviceHealthMonitor
    evicts, the server re-shards at rung boundaries, and the armed
    brownout ladder degrades deadline-bound admissions instead of
    shedding.  The row commits goodput / miss-rate / the degradation
    mix, and ``tools/check_bench.py`` gates zero lost futures,
    ``reshards == evictions``, and (non-smoke) brownout p50 <= 2x the
    steady row's p50.

Every request is accounted for exactly once:

    completed + failed + deadline_missed + queue_rejected == offered

which ``tools/check_bench.py`` gates on the committed
``BENCH_serving.json`` — a row that leaks a request fails CI.  On a
non-TPU backend the per-cell ``wall_clock`` label is "emulated"
(forced-host CPU timings are scheduling-overhead signals, not TPU
serving numbers); counters, accounting, and rates are exact anywhere.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax

from repro.core.shufflesoftsort import (
    ShuffleSoftSortConfig,
    run_round_segment,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import (
    DeadlineExceeded,
    QueueFull,
    RequestFailed,
    ServerClosed,
    SortServer,
)
from repro.runtime.fault_tolerance import FaultInjector, RetryPolicy
from repro.runtime.straggler import StragglerMonitor


# (hw, d) mix: two shape buckets so every scenario exercises the
# pad-to-bucket compile cache across mixed traffic.
SHAPES = (((4, 4), 2), ((8, 8), 2))


def _gen_problems(rng, count):
    probs = []
    for i in range(count):
        hw, d = SHAPES[i % len(SHAPES)]
        probs.append((hw, d,
                      rng.rand(hw[0] * hw[1], d).astype(np.float32)))
    return probs


def _percentile(vals, q):
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if vals else 0.0


_WARMED: set = set()


def _warm_compile_cache(cfg, seg_len, max_batch, meshes=(None,),
                        adaptive=False):
    """Pre-trace every (shape, pow2-bucket) program the scenario can
    dispatch, directly against the engine, so the recorded latencies
    measure scheduling and annealing rather than XLA compiles (compile
    amortization is a given in a long-lived server; a fresh-process
    benchmark has to buy it explicitly).  ``meshes`` lists every device
    layout the scenario will dispatch on (the capacity scenario knows
    its eviction schedule, so it warms the survivor meshes too);
    ``adaptive=True`` additionally warms the controller-driven dispatch
    the brownout ladder degrades requests onto."""
    import dataclasses
    acfg = dataclasses.replace(cfg, schedule="adaptive")
    for mesh in meshes:
        mesh_key = (None if mesh is None
                    else tuple(dv.id for dv in mesh.devices.flat))
        for hw, d in SHAPES:
            n = hw[0] * hw[1]
            b = 1
            while b <= max_batch:
                xs = np.zeros((b, n, d), np.float32)
                orders = np.tile(np.arange(n, dtype=np.int32), (b, 1))
                keys = np.ones((b, 2), np.uint32)
                norms = np.ones(b, np.float32)
                progress = np.zeros(b, np.int64)
                sig = (hw, d, b, seg_len, cfg, mesh_key)
                if sig not in _WARMED:
                    _WARMED.add(sig)
                    run_round_segment(xs, orders, keys, norms, progress,
                                      seg_len, hw=hw, cfg=cfg, mesh=mesh)
                sig_a = sig + ("adaptive",)
                if adaptive and sig_a not in _WARMED:
                    _WARMED.add(sig_a)
                    run_round_segment(xs, orders, keys, norms, progress,
                                      seg_len, hw=hw, cfg=acfg, mesh=mesh,
                                      regime="dense", with_w=True)
                b *= 2


def run_scenario(name, cfg, *, requests, rate_hz, window,
                 queue_depth, max_batch, deadline_s=None,
                 fail_every=0, delay_call=None, seed=0):
    """Offer ``requests`` Poisson arrivals at ``rate_hz`` to a fresh
    server; returns the metrics cell."""
    inject = fail_every > 0 or delay_call is not None
    fail_calls = set(range(fail_every, 10_000, fail_every)) \
        if fail_every else set()
    delay_calls = {delay_call: 0.25} if delay_call is not None else {}
    engine = FaultInjector(run_round_segment, fail_calls=fail_calls,
                           delay_calls=delay_calls)
    hw0, d0 = SHAPES[0]
    server = SortServer(
        hw0, d=d0, cfg=cfg, max_batch=max_batch, max_wait_ms=2.0,
        queue_depth=queue_depth, seed=seed,
        retry=RetryPolicy(max_retries=4, backoff_base_s=0.01,
                          backoff_max_s=0.1),
        straggler=StragglerMonitor(z=4.0, min_ratio=2.0, warmup=8),
        engine_fn=engine if inject else run_round_segment)
    _warm_compile_cache(cfg, server.seg_len, max_batch)

    rng = np.random.RandomState(seed)
    problems = _gen_problems(rng, requests)
    gaps = rng.exponential(1.0 / rate_hz, size=requests)

    futs, rejected = [], 0
    t_start = time.perf_counter()
    next_at = t_start
    for i, (hw, d, x) in enumerate(problems):
        next_at += gaps[i]
        pause = next_at - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        # closed loop: never more than ``window`` requests outstanding
        while sum(not f.done() for f in futs) >= window:
            time.sleep(0.005)
        try:
            futs.append(server.submit(x, hw=hw, priority=i % 3,
                                      deadline_s=deadline_s))
        except QueueFull:
            rejected += 1
    outcomes = {"completed": 0, "failed": 0, "deadline_missed": 0}
    for f in futs:
        try:
            f.result(timeout=600)
            outcomes["completed"] += 1
        except DeadlineExceeded:
            outcomes["deadline_missed"] += 1
        except (RequestFailed, ServerClosed):
            outcomes["failed"] += 1
    wall = time.perf_counter() - t_start
    server.close()

    st = server.stats
    assert st["queue_rejected"] == rejected, (st["queue_rejected"], rejected)
    lat = st["latencies_ms"]
    cell = {
        "scenario": name,
        "requests": requests,
        "arrival_rate_hz": rate_hz,
        "deadline_s": deadline_s,
        "shapes": [[list(hw), d] for hw, d in SHAPES],
        "rounds": cfg.rounds,
        "wall_clock": ("measured" if jax.default_backend() == "tpu"
                       else "emulated"),
        "wall_s": wall,
        "completed": outcomes["completed"],
        "failed": outcomes["failed"],
        "deadline_missed": outcomes["deadline_missed"],
        "queue_rejected": rejected,
        "goodput_rps": outcomes["completed"] / max(wall, 1e-9),
        "p50_ms": _percentile(lat, 50),
        "p99_ms": _percentile(lat, 99),
        "deadline_miss_rate": outcomes["deadline_missed"] / requests,
        "retries": st["retries"],
        "recoveries": st["recoveries"],
        "stragglers": st["stragglers"],
        "batches": st["batches"],
        "mean_batch": (float(np.mean(st["batch_sizes"]))
                       if st["batch_sizes"] else 0.0),
        "compile_programs": len(st["compile_keys"]),
        "injected_faults": engine.faults if inject else 0,
        "injected_delays": engine.delays if inject else 0,
    }
    # cross-check the server ledger against the client-observed outcomes
    assert st["completed"] == outcomes["completed"], (st, outcomes)
    assert (cell["completed"] + cell["failed"] + cell["deadline_missed"]
            + cell["queue_rejected"]) == requests, cell
    return cell


def run_preempt_scenario(cfg, *, requests, rate_hz, window, queue_depth,
                         max_batch, seed=0):
    """Kill-and-resume under load: offer Poisson traffic to generation-1,
    preempt it (``close(drain=False)``) once a quarter of the requests
    completed, hand the in-flight requests to generation-2, and account
    for every future exactly once across both servers."""
    def make(resume=None):
        hw0, d0 = SHAPES[0]
        return SortServer(
            hw0, d=d0, cfg=cfg, max_batch=max_batch, max_wait_ms=2.0,
            queue_depth=queue_depth, seed=seed,
            retry=RetryPolicy(max_retries=4, backoff_base_s=0.01,
                              backoff_max_s=0.1),
            straggler=StragglerMonitor(z=4.0, min_ratio=2.0, warmup=8),
            resume=resume)

    server = make()
    _warm_compile_cache(cfg, server.seg_len, max_batch)
    rng = np.random.RandomState(seed)
    problems = _gen_problems(rng, requests)
    gaps = rng.exponential(1.0 / rate_hz, size=requests)

    futs, rejected = [], 0
    t_start = time.perf_counter()
    next_at = t_start
    for i, (hw, d, x) in enumerate(problems):
        next_at += gaps[i]
        pause = next_at - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        while sum(not f.done() for f in futs) >= window:
            time.sleep(0.005)
        try:
            futs.append(server.submit(x, hw=hw, priority=i % 3))
        except QueueFull:
            rejected += 1
    # preempt once a quarter of the offered load has completed but
    # in-flight traffic remains (deadline: everything finished first)
    quarter = max(1, requests // 4)
    while (server.stats["completed"] < quarter
           and any(not f.done() for f in futs)):
        time.sleep(0.002)
    handoff = server.close(drain=False)
    gen1 = dict(server.stats)

    server2 = make(resume=handoff)
    outcomes = {"completed": 0, "failed": 0, "deadline_missed": 0}
    for f in futs:
        try:
            f.result(timeout=600)
            outcomes["completed"] += 1
        except DeadlineExceeded:
            outcomes["deadline_missed"] += 1
        except (RequestFailed, ServerClosed):
            outcomes["failed"] += 1
    wall = time.perf_counter() - t_start
    server2.close()
    gen2 = server2.stats

    lat = gen1["latencies_ms"] + gen2["latencies_ms"]
    cell = {
        "scenario": "preempt",
        "requests": requests,
        "arrival_rate_hz": rate_hz,
        "deadline_s": None,
        "shapes": [[list(hw), d] for hw, d in SHAPES],
        "rounds": cfg.rounds,
        "wall_clock": ("measured" if jax.default_backend() == "tpu"
                       else "emulated"),
        "wall_s": wall,
        "completed": outcomes["completed"],
        "failed": outcomes["failed"],
        "deadline_missed": outcomes["deadline_missed"],
        "queue_rejected": rejected,
        "goodput_rps": outcomes["completed"] / max(wall, 1e-9),
        "p50_ms": _percentile(lat, 50),
        "p99_ms": _percentile(lat, 99),
        "deadline_miss_rate": outcomes["deadline_missed"] / requests,
        "retries": gen1["retries"] + gen2["retries"],
        "recoveries": gen1["recoveries"] + gen2["recoveries"],
        "stragglers": gen1["stragglers"] + gen2["stragglers"],
        "batches": gen1["batches"] + gen2["batches"],
        "mean_batch": (float(np.mean(gen1["batch_sizes"]
                                     + gen2["batch_sizes"]))
                       if gen1["batch_sizes"] + gen2["batch_sizes"]
                       else 0.0),
        "compile_programs": len(gen1["compile_keys"]
                                | gen2["compile_keys"]),
        "injected_faults": 0,
        "injected_delays": 0,
        # warm-restart accounting (gated by tools/check_bench.py)
        "preempted_inflight": len(handoff.requests),
        "resumed_requests": gen2["resumed"],
        "completed_gen1": gen1["completed"],
        "completed_gen2": gen2["completed"],
    }
    assert (cell["completed"] + cell["failed"] + cell["deadline_missed"]
            + cell["queue_rejected"]) == requests, cell
    assert cell["completed_gen1"] + cell["completed_gen2"] \
        == cell["completed"], cell
    assert cell["resumed_requests"] == cell["preempted_inflight"], cell
    return cell


def run_capacity_scenario(cfg, *, requests, rate_hz, window, queue_depth,
                          max_batch, deadline_s, seed=0):
    """Elastic capacity loss under Poisson load: serve from an 8-device
    mesh, take two devices down mid-run (the health layer evicts and
    re-shards over the survivors at rung boundaries), bring one back,
    and let the armed brownout ladder degrade deadline-bound admissions
    instead of shedding them.  The server runs a 2-restart tournament
    so the ladder's first rung ("culled" — keep only the best restart
    at cull edges) deterministically fires while any device is out;
    requests carry a deadline inside the policy's full-level slack
    band so they take the full ladder level.  The cell commits the
    goodput/miss-rate and the full degradation mix; every offered
    future must still resolve exactly once (``lost_futures == 0``)."""
    from repro.launch.mesh import make_sort_mesh
    from repro.launch.serve import BrownoutPolicy
    from repro.runtime.straggler import DeviceHealthMonitor

    mesh = make_sort_mesh(8)
    devs = list(mesh.devices.flat)
    lose_a, lose_b = devs[3].id, devs[5].id
    engine = FaultInjector(run_round_segment,
                           device_loss={1: lose_a, 3: lose_b},
                           device_return={8: lose_a})
    hw0, d0 = SHAPES[0]
    server = SortServer(
        hw0, d=d0, cfg=cfg, max_batch=max_batch, max_wait_ms=2.0,
        queue_depth=queue_depth, seed=seed, mesh=mesh,
        n_restarts=2, tournament_rungs=2, cull_fraction=0.25,
        retry=RetryPolicy(max_retries=4, backoff_base_s=0.01,
                          backoff_max_s=0.1),
        straggler=StragglerMonitor(z=4.0, min_ratio=2.0, warmup=8),
        engine_fn=engine,
        brownout=BrownoutPolicy(slack_full_s=10.0),
        device_health=DeviceHealthMonitor(lost_after=1,
                                          probe=engine.healthy))
    # Warm every device layout the eviction schedule will produce
    # (8 -> minus A -> minus A,B -> A returns: minus B), and the
    # adaptive dispatch the ladder degrades onto.
    surv_a = [dv for dv in devs if dv.id != lose_a]
    surv_ab = [dv for dv in devs if dv.id not in (lose_a, lose_b)]
    surv_b = [dv for dv in devs if dv.id != lose_b]
    _warm_compile_cache(
        cfg, server.seg_len, max_batch, adaptive=True,
        meshes=(mesh,
                make_sort_mesh(7, devices=surv_a),
                make_sort_mesh(6, devices=surv_ab),
                make_sort_mesh(7, devices=surv_b)))

    rng = np.random.RandomState(seed)
    problems = _gen_problems(rng, requests)
    gaps = rng.exponential(1.0 / rate_hz, size=requests)

    futs, rejected = [], 0
    t_start = time.perf_counter()
    next_at = t_start
    for i, (hw, d, x) in enumerate(problems):
        next_at += gaps[i]
        pause = next_at - time.perf_counter()
        if pause > 0:
            time.sleep(pause)
        while sum(not f.done() for f in futs) >= window:
            time.sleep(0.005)
        try:
            futs.append(server.submit(x, hw=hw, priority=i % 3,
                                      deadline_s=deadline_s))
        except QueueFull:
            rejected += 1
    outcomes = {"completed": 0, "failed": 0, "deadline_missed": 0}
    for f in futs:
        try:
            f.result(timeout=600)
            outcomes["completed"] += 1
        except DeadlineExceeded:
            outcomes["deadline_missed"] += 1
        except (RequestFailed, ServerClosed):
            outcomes["failed"] += 1
    wall = time.perf_counter() - t_start
    server.close()

    st = server.stats
    lat = st["latencies_ms"]
    resolved = (outcomes["completed"] + outcomes["failed"]
                + outcomes["deadline_missed"] + rejected)
    cell = {
        "scenario": "capacity",
        "requests": requests,
        "arrival_rate_hz": rate_hz,
        "deadline_s": deadline_s,
        "shapes": [[list(hw), d] for hw, d in SHAPES],
        "rounds": cfg.rounds,
        "wall_clock": ("measured" if jax.default_backend() == "tpu"
                       else "emulated"),
        "wall_s": wall,
        "completed": outcomes["completed"],
        "failed": outcomes["failed"],
        "deadline_missed": outcomes["deadline_missed"],
        "queue_rejected": rejected,
        "goodput_rps": outcomes["completed"] / max(wall, 1e-9),
        "p50_ms": _percentile(lat, 50),
        "p99_ms": _percentile(lat, 99),
        "deadline_miss_rate": outcomes["deadline_missed"] / requests,
        "retries": st["retries"],
        "recoveries": st["recoveries"],
        "stragglers": st["stragglers"],
        "batches": st["batches"],
        "mean_batch": (float(np.mean(st["batch_sizes"]))
                       if st["batch_sizes"] else 0.0),
        "compile_programs": len(st["compile_keys"]),
        "injected_faults": engine.faults,
        "injected_delays": engine.delays,
        # elastic-capacity accounting (gated by tools/check_bench.py)
        "devices_start": len(devs),
        "device_faults": engine.device_faults,
        "evictions": st["evictions"],
        "reshards": st["reshards"],
        "device_returns": st["device_returns"],
        "degraded_requests": st["brownouts"],
        "degradations": {k: int(v)
                         for k, v in st["degradations"].items()},
        "lost_futures": requests - resolved,
    }
    assert cell["lost_futures"] == 0, cell
    assert cell["reshards"] == cell["evictions"] == 2, cell
    assert cell["device_returns"] == 1, cell
    assert st["completed"] == outcomes["completed"], (st, outcomes)
    return cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized load (fewer requests, short anneal)")
    ap.add_argument("--inject-faults", action="store_true",
                    help="add the fault-injection scenario")
    ap.add_argument("--out", default="BENCH_serving.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    requests = 16 if args.smoke else 48
    rounds = 4 if args.smoke else 8
    cfg = ShuffleSoftSortConfig(rounds=rounds, inner_steps=2, chunk=64)

    cells = [run_scenario(
        "steady", cfg, requests=requests, rate_hz=40.0, window=16,
        queue_depth=64, max_batch=8, seed=args.seed)]
    if args.inject_faults:
        cells.append(run_scenario(
            "faults", cfg, requests=requests, rate_hz=40.0, window=16,
            queue_depth=64, max_batch=8, fail_every=7, delay_call=11,
            seed=args.seed))
    cells.append(run_scenario(
        "overload", cfg, requests=requests, rate_hz=500.0, window=requests,
        queue_depth=12, max_batch=4, deadline_s=0.5, seed=args.seed))
    cells.append(run_preempt_scenario(
        cfg, requests=requests, rate_hz=80.0, window=requests,
        queue_depth=64, max_batch=4, seed=args.seed))
    if len(jax.devices()) >= 8:
        cells.append(run_capacity_scenario(
            cfg, requests=requests, rate_hz=60.0, window=16,
            queue_depth=16, max_batch=8, deadline_s=5.0,
            seed=args.seed))
    else:
        print("capacity scenario skipped: needs >= 8 devices (set "
              "XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    record = {
        "bench": "serving_bench",
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "note": ("closed-loop Poisson load over mixed shape buckets; "
                 "counters/accounting exact on any backend, wall-clock "
                 "labeled emulated off-TPU"),
        "cells": cells,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    for c in cells:
        line = (f"{c['scenario']:>9}: {c['completed']}/{c['requests']} ok, "
                f"p50 {c['p50_ms']:.0f}ms p99 {c['p99_ms']:.0f}ms, "
                f"goodput {c['goodput_rps']:.1f}/s, "
                f"missed {c['deadline_missed']}, shed {c['queue_rejected']}, "
                f"retries {c['retries']}, recoveries {c['recoveries']}")
        if c["scenario"] == "capacity":
            deg = c["degradations"]
            line += (f", evicted {c['evictions']} resharded "
                     f"{c['reshards']} returned {c['device_returns']}, "
                     f"degraded {c['degraded_requests']} "
                     f"(culled={deg['culled']} adaptive={deg['adaptive']} "
                     f"banded={deg['banded']} bf16={deg['bf16']})")
        print(line)
    print(f"wrote {args.out}")
    return record


if __name__ == "__main__":
    main()
