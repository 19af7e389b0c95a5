"""Throughput sweep: batched multi-problem engine vs the sequential loop.

    PYTHONPATH=src python -m benchmarks.batched_bench [--full]

For each (B, N) cell, times B independent grid-sorting problems solved

  * sequentially — B ``shuffle_soft_sort`` calls (the pre-batching API:
    one Python round-loop per problem, one host sync per round), and
  * batched      — ONE ``shuffle_soft_sort_batched`` call (one vmapped
    device program per round for all B problems).

and reports sorts/sec for both plus the speedup.  Default sweep is
B in {1, 8, 64} at N = 1024 with a short round budget so it finishes on
the CI CPU backend; ``--full`` extends to N = 4096 (the paper-scale
grid) and a longer budget.  Compile time is excluded (one warmup per
shape); per-seed results of the two paths are bit-identical, so this is
a pure scheduling/throughput comparison.  Results fill the table in
EXPERIMENTS.md §Perf.

Scaling mode (EXPERIMENTS.md §Scaling):

    PYTHONPATH=src python -m benchmarks.batched_bench --devices 1 2 8

spawns one worker subprocess per requested device count on the CPU
backend (each with ``XLA_FLAGS=--xla_force_host_platform_device_count=<D>``
so the sweep runs anywhere; on an accelerator it runs in process over
the first D devices), and in each sweeps the devices x B x S grid over three
engines — vmap, mesh-sharded, and the successive-halving restart
tournament — recording wall time, best-restart loss, and the
tournament's executed-rounds fraction.  The aggregate is written to
``BENCH_scaling.json``.  On a forced-host CPU the "devices" are slices
of one physical socket, so treat the timings as shape/overhead signals;
the quality columns (tournament loss vs full loss) are exact.

Adaptive mode (EXPERIMENTS.md §Adaptive):

    PYTHONPATH=src python -m benchmarks.batched_bench --adaptive

compares the fixed schedule against ``schedule="adaptive"`` on a
deliberately over-provisioned anneal (cold ``tau_end``, long budget —
the serving norm) and merges ``"mode": "adaptive"`` rows into
``BENCH_scaling.json`` recording rounds-saved fraction vs final-loss
gap; ``tools/check_bench.py`` gates those rows (>= 20% saved at <= 1%
gap).  The loss columns are backend-exact, like the tournament's.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.shufflesoftsort import (
    ShuffleSoftSortConfig,
    restart_tournament,
    shuffle_soft_sort,
    shuffle_soft_sort_batched,
)
from repro.launch.compile_cache import enable_compile_cache


def _square_hw(n: int) -> tuple[int, int]:
    h = int(np.sqrt(n))
    assert h * h == n, f"N={n} is not square"
    return (h, h)


def bench_cell(b: int, n: int, d: int, cfg: ShuffleSoftSortConfig,
               warm: bool = True):
    hw = _square_hw(n)
    xs = jax.random.uniform(jax.random.PRNGKey(0), (b, n, d))
    keys = jax.random.split(jax.random.PRNGKey(1), b)

    def run_sequential():
        outs = []
        for i in range(b):
            outs.append(shuffle_soft_sort(xs[i], hw, cfg, key=keys[i]))
        return outs

    def run_batched():
        return shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=1,
                                         keys=keys)

    if warm:  # compile both programs outside the timed region
        shuffle_soft_sort(xs[0], hw, cfg, key=keys[0])
        run_batched()

    t0 = time.perf_counter()
    seq = run_sequential()
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    bat = run_batched()
    t_bat = time.perf_counter() - t0

    # Sanity: the two paths must agree per seed (bit-identical orders).
    for i in range(b):
        assert np.array_equal(seq[i][0], bat.all_orders[i, 0]), i

    return {
        "B": b, "N": n,
        "seq_s": t_seq, "bat_s": t_bat,
        "seq_sorts_per_s": b / t_seq,
        "bat_sorts_per_s": b / t_bat,
        "speedup": t_seq / t_bat,
    }


# --------------------------------------------------------------------------
# Scaling sweep: devices x B x S over vmap / sharded / tournament engines.
# --------------------------------------------------------------------------

def bench_scaling_cell(b: int, s: int, n: int, d: int,
                       cfg: ShuffleSoftSortConfig, n_devices: int,
                       rungs: int, cull: float) -> dict:
    """One devices x B x S cell: time the three engines on identical
    problems/keys and audit the sharded path's bit-identity."""
    from repro.launch.mesh import make_sort_mesh

    hw = _square_hw(n)
    xs = jax.random.uniform(jax.random.PRNGKey(0), (b, n, d))
    keys = jax.random.split(jax.random.PRNGKey(1), b * s)
    mesh = make_sort_mesh(n_devices)

    def run_vmap():
        return shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                         keys=keys)

    def run_shard():
        return shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                         keys=keys, mesh=mesh)

    def run_tour():
        return restart_tournament(xs, hw, cfg, n_restarts=s, keys=keys,
                                  cull_fraction=cull, n_rungs=rungs,
                                  mesh=mesh)

    ref, shd, _ = run_vmap(), run_shard(), run_tour()    # compile warmup
    assert np.array_equal(ref.all_orders, shd.all_orders), (b, s, n_devices)

    t0 = time.perf_counter()
    ref = run_vmap()
    t_vmap = time.perf_counter() - t0
    t0 = time.perf_counter()
    run_shard()
    t_shard = time.perf_counter() - t0
    t0 = time.perf_counter()
    tour = run_tour()
    t_tour = time.perf_counter() - t0

    full_loss = float(ref.losses[:, -1].mean())
    tour_loss = float(tour.final_loss.mean())
    return {
        "devices": n_devices, "B": b, "S": s, "N": n,
        "rounds": cfg.rounds, "rungs": rungs, "cull_fraction": cull,
        "vmap_s": t_vmap, "shard_s": t_shard, "tournament_s": t_tour,
        "shard_speedup": t_vmap / t_shard,
        "tournament_speedup": t_vmap / t_tour,
        "full_best_loss": full_loss,
        "tournament_best_loss": tour_loss,
        # > 0 when culling dropped the seed that would have won.
        "tournament_loss_gap": tour_loss - full_loss,
        "tournament_rounds_frac": tour.rounds_run / tour.rounds_full,
    }


def run_scaling_worker(args, n_dev: int | None = None) -> list[dict]:
    """In-process sweep over the first ``n_dev`` devices (default: every
    visible device)."""
    n_dev = len(jax.devices()) if n_dev is None else n_dev
    cfg = ShuffleSoftSortConfig(rounds=args.rounds or 8, inner_steps=4,
                                chunk=256)
    rows = []
    for b in (args.bs or (4, 16)):
        for s in (args.restarts or (2, 8)):
            rows.append(bench_scaling_cell(
                b, s, args.n, args.d, cfg, n_dev,
                rungs=args.tournament_rungs, cull=args.cull_fraction))
    return rows


def run_scaling_sweep(args) -> dict:
    """Sweep every requested device count, aggregate, and write the
    BENCH_scaling.json artifact.

    On the CPU backend each count runs in a worker subprocess, because
    forced host devices must be set before jax initializes.  On an
    accelerator the flag cannot create chips, and a chip belongs to one
    process, so the sweep runs in this process over
    ``jax.devices()[:D]``."""
    backend = jax.default_backend()
    if backend == "cpu":
        cells = _scaling_subprocesses(args)
    else:
        avail = len(jax.devices())
        if max(args.devices) > avail:
            raise SystemExit(
                f"--devices {max(args.devices)} exceeds the {avail} "
                f"{backend} devices visible to this process")
        cells = [row for n_dev in args.devices
                 for row in run_scaling_worker(args, n_dev)]
    record = {
        "bench": "batched_bench --devices",
        "backend": backend,
        "note": ("forced-host devices share one socket: timings are "
                 "overhead/shape signals, loss columns are exact"
                 if backend == "cpu" else
                 "in-process sweep over the visible devices"),
        "cells": cells,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {len(cells)} cells -> {args.out}")
    for r in cells:
        print(f"  dev={r['devices']} B={r['B']} S={r['S']}: "
              f"shard {r['shard_speedup']:.2f}x, tournament "
              f"{r['tournament_speedup']:.2f}x at "
              f"{r['tournament_rounds_frac']:.2f} of the rounds "
              f"(loss gap {r['tournament_loss_gap']:+.4f})")
    return record


def _scaling_subprocesses(args) -> list[dict]:
    """One worker subprocess per device count, each with that many
    forced host devices."""
    cells = []
    for n_dev in args.devices:
        env = dict(os.environ)
        flags = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_dev}".strip())
        cmd = [sys.executable, "-m", "benchmarks.batched_bench",
               "--scaling-worker", "--n", str(args.n), "--d", str(args.d),
               "--rounds", str(args.rounds or 8),
               "--tournament-rungs", str(args.tournament_rungs),
               "--cull-fraction", str(args.cull_fraction)]
        if args.bs:
            cmd += ["--bs"] + [str(x) for x in args.bs]
        if args.restarts:
            cmd += ["--restarts"] + [str(x) for x in args.restarts]
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             check=True)
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("SCALING_JSON ")][-1]
        rows = json.loads(line[len("SCALING_JSON "):])
        for r in rows:
            assert r["devices"] == n_dev, (r["devices"], n_dev)
        cells.extend(rows)
    return cells


def run_cull_sweep(args) -> list[dict]:
    """Tournament quality/compute tradeoff: sweep the cull fraction at
    fixed B x S and compare winner loss against the run-everything
    engine.  Fills the cull-fraction table in EXPERIMENTS.md §Scaling."""
    b = (args.bs or [4])[0]
    s = (args.restarts or [8])[0]
    n = args.n
    cfg = ShuffleSoftSortConfig(rounds=args.rounds or 12, inner_steps=4,
                                chunk=256)
    hw = _square_hw(n)
    xs = jax.random.uniform(jax.random.PRNGKey(0), (b, n, args.d))
    keys = jax.random.split(jax.random.PRNGKey(1), b * s)
    full = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s, keys=keys)
    full_loss = float(full.losses[:, -1].mean())
    rows = []
    print(f"cull sweep: B={b} S={s} N={n} rounds={cfg.rounds} "
          f"rungs={args.tournament_rungs}; full-engine loss {full_loss:.4f}")
    for cull in (0.0, 0.25, 0.5, 0.75):
        res = restart_tournament(xs, hw, cfg, n_restarts=s, keys=keys,
                                 cull_fraction=cull,
                                 n_rungs=args.tournament_rungs)
        row = {
            "cull_fraction": cull,
            "rounds_frac": res.rounds_run / res.rounds_full,
            "final_loss": float(res.final_loss.mean()),
            "loss_gap_vs_full": float(res.final_loss.mean()) - full_loss,
        }
        rows.append(row)
        print(f"  cull={cull:.2f}: rounds_frac={row['rounds_frac']:.3f} "
              f"loss={row['final_loss']:.4f} "
              f"gap={row['loss_gap_vs_full']:+.4f}")
    return rows


def bench_adaptive_cell(b: int, n: int, d: int, rounds: int,
                        args) -> dict:
    """Fixed vs adaptive schedule on one over-provisioned cell.

    The schedule is deliberately conservative (cold ``tau_end``, long
    round budget — the serving norm, where one config covers many
    problem instances), so its tail is flat; the adaptive controller
    converts the measured plateau into skipped rounds.  The row records
    the two gated quantities: the fraction of schedule rounds the
    controller saved and the final-loss gap it cost (both vs the fixed
    engine on identical problems/keys — tools/check_bench.py enforces
    saved >= 20% at a gap <= 1%).
    """
    hw = _square_hw(n)
    xs = jax.random.uniform(jax.random.PRNGKey(0), (b, n, d))
    keys = jax.random.split(jax.random.PRNGKey(1), b)
    base = dict(rounds=rounds, inner_steps=4, chunk=min(n, 256),
                tau_end=args.adaptive_tau_end)
    fixed = ShuffleSoftSortConfig(**base)
    adapt = ShuffleSoftSortConfig(
        **base, schedule="adaptive", adapt_every=args.adapt_every,
        patience=args.patience, plateau_rtol=args.plateau_rtol,
        decay_rungs=args.decay_rungs)

    rf = shuffle_soft_sort_batched(xs, hw, fixed, keys=keys)  # warmup
    ra = shuffle_soft_sort_batched(xs, hw, adapt, keys=keys)

    t0 = time.perf_counter()
    rf = shuffle_soft_sort_batched(xs, hw, fixed, keys=keys)
    t_fixed = time.perf_counter() - t0
    t0 = time.perf_counter()
    ra = shuffle_soft_sort_batched(xs, hw, adapt, keys=keys)
    t_adapt = time.perf_counter() - t0

    executed = ra.rounds_executed[:, 0]
    fixed_loss = float(rf.losses[:, -1].mean())
    adapt_loss = float(ra.losses[np.arange(b), executed - 1].mean())
    return {
        "mode": "adaptive",
        "devices": 1, "B": b, "N": n, "rounds": rounds,
        "tau_end": args.adaptive_tau_end,
        "adapt_every": args.adapt_every, "patience": args.patience,
        "plateau_rtol": args.plateau_rtol,
        "decay_rungs": args.decay_rungs,
        "fixed_s": t_fixed, "adaptive_s": t_adapt,
        "fixed_final_loss": fixed_loss,
        "adaptive_final_loss": adapt_loss,
        "mean_rounds_executed": float(executed.mean()),
        "rounds_saved_frac": float(1.0 - executed.sum() / (b * rounds)),
        "final_loss_gap_pct": (adapt_loss - fixed_loss) / fixed_loss * 100,
    }


def run_adaptive_sweep(args) -> dict:
    """Fixed-vs-adaptive rows, merged into the BENCH_scaling.json
    artifact alongside the devices x B x S cells (adaptive rows carry
    ``"mode": "adaptive"`` and replace any previous adaptive rows;
    EXPERIMENTS.md §Adaptive is built from exactly these columns)."""
    rounds = args.rounds or 80
    rows = [bench_adaptive_cell(b, n, args.d, rounds, args)
            for n in (args.adaptive_ns or (64, 256))
            for b in (args.bs or (4,))]

    cells, envelope = [], {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            prior = json.load(f)
        envelope = {k: v for k, v in prior.items() if k != "cells"}
        cells = [c for c in prior.get("cells", [])
                 if c.get("mode") != "adaptive"]
    envelope.setdefault("bench", "batched_bench --devices")
    envelope["backend"] = jax.default_backend()
    cells.extend(rows)
    record = dict(envelope, cells=cells)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {len(rows)} adaptive cells -> {args.out} "
          f"({len(cells)} total)")
    for r in rows:
        print(f"  B={r['B']} N={r['N']} R={r['rounds']}: saved "
              f"{r['rounds_saved_frac']:.1%} of rounds at "
              f"{r['final_loss_gap_pct']:+.2f}% final-loss gap "
              f"({r['mean_rounds_executed']:.1f}/{r['rounds']} rounds, "
              f"{r['fixed_s']:.2f}s -> {r['adaptive_s']:.2f}s)")
    return record


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="add N=4096 and a longer round budget")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--d", type=int, default=3)
    ap.add_argument("--bs", type=int, nargs="+", default=None)
    ap.add_argument("--devices", type=int, nargs="+", default=None,
                    help="run the scaling sweep at these device counts "
                         "(one forced-host-device subprocess each) and "
                         "write BENCH_scaling.json")
    ap.add_argument("--restarts", type=int, nargs="+", default=None,
                    help="S values for the scaling sweep")
    ap.add_argument("--n", type=int, default=256,
                    help="N for the scaling sweep")
    ap.add_argument("--tournament-rungs", type=int, default=3)
    ap.add_argument("--cull-fraction", type=float, default=0.5)
    ap.add_argument("--out", default="BENCH_scaling.json")
    ap.add_argument("--cull-sweep", action="store_true",
                    help="sweep tournament cull fractions at fixed B x S "
                         "and report the quality/compute tradeoff")
    ap.add_argument("--adaptive", action="store_true",
                    help="fixed-vs-adaptive schedule rows (rounds saved "
                         "vs final-loss gap), merged into --out")
    ap.add_argument("--adaptive-ns", type=int, nargs="+", default=None,
                    help="N values for the adaptive sweep")
    ap.add_argument("--adaptive-tau-end", type=float, default=0.02,
                    help="conservative (cold) schedule end for the "
                         "adaptive sweep — the over-provisioned regime")
    ap.add_argument("--adapt-every", type=int, default=5)
    ap.add_argument("--patience", type=int, default=1)
    ap.add_argument("--plateau-rtol", type=float, default=0.02)
    ap.add_argument("--decay-rungs", type=int, default=2)
    ap.add_argument("--scaling-worker", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.scaling_worker:
        rows = run_scaling_worker(args)
        print("SCALING_JSON " + json.dumps(rows))
        return rows
    if args.cull_sweep:
        return run_cull_sweep(args)
    if args.adaptive:
        return run_adaptive_sweep(args)
    if args.devices:
        return run_scaling_sweep(args)

    ns = (1024, 4096) if args.full else (1024,)
    bs = tuple(args.bs) if args.bs else (1, 8, 64)
    rounds = args.rounds or (50 if args.full else 10)
    cfg = ShuffleSoftSortConfig(rounds=rounds, inner_steps=4, chunk=256)

    print("name,us_per_call,derived")
    rows = []
    for n in ns:
        for b in bs:
            r = bench_cell(b, n, args.d, cfg)
            rows.append(r)
            print(f"batched_bench.B{b}_N{n},{r['bat_s'] * 1e6 / b:.0f},"
                  f"seq={r['seq_sorts_per_s']:.2f}sorts/s;"
                  f"bat={r['bat_sorts_per_s']:.2f}sorts/s;"
                  f"speedup={r['speedup']:.2f}x")
    return rows


if __name__ == "__main__":
    main()
