"""Guardrail detection / probe-overhead benchmark (ISSUE 9 tentpole).

    PYTHONPATH=src python -m benchmarks.guardrail_bench [--smoke]
        [--out BENCH_guardrails.json]

Two cell families, both deterministic per seed:

* ``"kind": "detection"`` — the chaos grid from
  tests/test_guardrails.py rerun as a measured artifact: every
  ``FaultInjector`` value-corruption mode (bit-flip / sign-flip /
  stale-buffer / NaN-splat) injected at an exact dispatch index into
  every serving path (pure-jnp oracle / fused kernel / banded kernel /
  bf16 kernel) under a full-rate shadow guardrail.  Each cell records
  whether the corruption was *detected* (an ``IntegrityViolation``
  incident with the firing probe's name), *repaired* (the request
  still completed), and *bit_identical* (the repaired result equals an
  uninjected run of the same config and seed).  ``tools/check_bench.py``
  gates the committed file on all three being true in every cell —
  detection_rate must be exactly 1.0.

* ``"kind": "overhead"`` — the cost of the probes on a clean batched
  anneal, one cell per (mode, shadow_rate) point including the default
  serving rate (1/32).  Guarded and unguarded runs execute the SAME
  rung-segmented schedule (the unguarded baseline gets a no-op
  ``rung_hook`` so both pay identical host-sync seams) and the
  reported ``overhead_pct`` is min-of-reps over interleaved
  repetitions — min, not mean, because on a shared CPU box background
  load only ever inflates a wall-clock sample.  The committed file is
  gated on the default-rate cell staying <= 5%; smoke runs
  (``"smoke": true``) skip the timing gate (schema and detection are
  machine-independent, wall-clock thresholds are not) and CI re-checks
  the committed full-run artifact instead.

Off-TPU the ``wall_clock`` label is "emulated", same convention as
every other committed bench: detection booleans and probe bookkeeping
are exact anywhere, absolute times are not TPU numbers.
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
    shuffle_soft_sort_batched,
)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import SortServer
from repro.runtime.fault_tolerance import (
    CorruptionSpec,
    FaultInjector,
    RetryPolicy,
)
from repro.runtime.guardrails import GuardrailPolicy, shadow_sampled

# ----------------------------------------------------- detection grid
# Mirrors tests/test_guardrails.py: small problems, exact dispatch
# index 1 (the second rung), one corruption per run.

N_DET, HW_DET, D_DET = 16, (4, 4), 3
FULL_SHADOW = GuardrailPolicy(mode="shadow", shadow_rate=1.0)
FAST_RETRY = RetryPolicy(max_retries=4, backoff_base_s=0.0)

PATHS = {
    "oracle": {},
    "kernel": {"use_kernel": True},
    "banded": {"use_kernel": True, "band": 8},
    "bf16": {"use_kernel": True, "compute_dtype": "bfloat16"},
}
CORRUPTIONS = {
    "bitflip": ("orders", 5),
    "signflip": ("losses", 1),
    "stale": ("losses", 0),
    "nan": ("losses", 2),
}


def _det_cfg(path):
    return ShuffleSoftSortConfig(rounds=4, inner_steps=2, chunk=N_DET,
                                 **PATHS[path])


def _serve_once(cfg, x, key, *, engine=None, guardrail=None):
    server = SortServer(HW_DET, d=D_DET, cfg=cfg, max_wait_ms=0.0,
                        sched_rungs=2, engine_fn=engine,
                        guardrail=guardrail, retry=FAST_RETRY)
    try:
        out = server.submit(x, key=key).result(timeout=300)
    finally:
        stats = server.stats
        server.close()
    return out, stats


def run_detection_grid(paths, corruptions):
    x = np.random.RandomState(0).rand(N_DET, D_DET).astype(np.float32)
    key = jax.random.PRNGKey(11)
    cells = []
    for path in paths:
        cfg = _det_cfg(path)
        clean, _ = _serve_once(cfg, x, key)
        for name in corruptions:
            target, index = CORRUPTIONS[name]
            inj = FaultInjector(
                run_round_segment,
                corrupt_calls={1: CorruptionSpec(name, target, index)})
            t0 = time.perf_counter()
            try:
                out, stats = _serve_once(cfg, x, key, engine=inj,
                                         guardrail=FULL_SHADOW)
                repaired = True
            except Exception:
                out, stats, repaired = None, {}, False
            wall = time.perf_counter() - t0
            detected = stats.get("integrity_violations", 0) >= 1
            incidents = stats.get("integrity_incidents", [])
            probe = incidents[0]["probe"] if incidents else None
            identical = repaired and all(
                np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(out, clean))
            cells.append({
                "kind": "detection",
                "path": path,
                "corruption": name,
                "target": target,
                "dispatch_index": 1,
                "injected": int(inj.corruptions),
                "detected": bool(detected),
                "probe": probe,
                "repaired": bool(repaired),
                "bit_identical": bool(identical),
                "violations": int(stats.get("integrity_violations", 0)),
                "self_heals": int(stats.get("self_heals", 0)),
                "wall_s": wall,
            })
            flag = "ok" if detected and repaired and identical else "FAIL"
            print(f"  detection {path:7s} x {name:9s} -> "
                  f"probe={probe!s:13s} {flag}")
    return cells


# ---------------------------------------------------- probe overhead
# One clean batched anneal per (mode, rate) point, all points running
# the identical rung-segmented schedule.  Sized so per-rung compute
# dominates the fixed per-rung probe cost — overhead on a toy problem
# measures host dispatch, not the probes' marginal price.

def overhead_points(default_rate):
    return [
        ("off", None, False),
        ("invariants", GuardrailPolicy(mode="invariants", seed=3), False),
        ("shadow", GuardrailPolicy(mode="shadow",
                                   shadow_rate=default_rate, seed=3), True),
        ("shadow", GuardrailPolicy(mode="shadow",
                                   shadow_rate=0.25, seed=3), False),
        ("shadow", GuardrailPolicy(mode="shadow",
                                   shadow_rate=1.0, seed=3), False),
    ]


def run_overhead(*, hw, b, rounds, inner_steps, every, reps,
                 default_rate):
    n = hw[0] * hw[1]
    cfg = ShuffleSoftSortConfig(rounds=rounds, inner_steps=inner_steps,
                                chunk=n)
    xs = np.random.RandomState(0).rand(b, n, D_DET).astype(np.float32)
    key = jax.random.PRNGKey(0)

    def once(guardrail):
        return shuffle_soft_sort_batched(
            xs, hw, cfg, key=key, rung_hook=lambda s: None,
            checkpoint_every=every, guardrail=guardrail)

    points = overhead_points(default_rate)
    once(None)                    # warm the segment programs
    once(points[-1][1])           # ...and the shadow/oracle program
    best = [float("inf")] * len(points)
    monitors = [None] * len(points)
    for _ in range(reps):         # interleaved: drift hits all points
        for i, (_, pol, _) in enumerate(points):
            t0 = time.perf_counter()
            once(pol)
            best[i] = min(best[i], time.perf_counter() - t0)
    base = best[0]
    rungs = len(range(0, rounds, every))
    cells = []
    for i, (mode, pol, is_default) in enumerate(points):
        rate = 0.0 if pol is None or pol.mode != "shadow" \
            else pol.shadow_rate
        sampled = sum(shadow_sampled(pol.seed, s, rate)
                      for s in range(0, rounds, every)) if pol else 0
        cell = {
            "kind": "overhead",
            "mode": mode,
            "shadow_rate": rate,
            "default": bool(is_default),
            "B": b, "N": hw[0] * hw[1], "rounds": rounds,
            "inner_steps": inner_steps, "rungs": rungs,
            "rungs_shadowed": int(sampled),
            "reps": reps,
            "unguarded_s": base,
            "guarded_s": best[i],
            "overhead_pct": 100.0 * (best[i] - base) / base,
        }
        cells.append(cell)
        print(f"  overhead {mode:10s} rate={rate:<7.5g} "
              f"{best[i]:.3f}s  {cell['overhead_pct']:+.1f}%"
              + ("  [default]" if is_default else ""))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid + small overhead problem; output "
                    "is schema-checked but exempt from the timing gate")
    ap.add_argument("--out", default="BENCH_guardrails.json")
    args = ap.parse_args()
    enable_compile_cache()

    default_rate = GuardrailPolicy().shadow_rate
    print("detection grid:")
    if args.smoke:
        det = run_detection_grid(("oracle", "kernel"),
                                 ("signflip", "nan"))
    else:
        det = run_detection_grid(sorted(PATHS), sorted(CORRUPTIONS))
    print("probe overhead:")
    if args.smoke:
        over = run_overhead(hw=(8, 8), b=4, rounds=8, inner_steps=2,
                            every=1, reps=2, default_rate=default_rate)
    else:
        over = run_overhead(hw=(16, 16), b=16, rounds=96, inner_steps=4,
                            every=2, reps=4, default_rate=default_rate)

    ok = [c for c in det if c["detected"] and c["repaired"]
          and c["bit_identical"]]
    doc = {
        "bench": "guardrail_bench",
        "backend": jax.default_backend(),
        "smoke": bool(args.smoke),
        "note": ("chaos detection grid + probe overhead; detection "
                 "booleans exact on any backend, wall-clock labeled "
                 "emulated off-TPU"),
        "wall_clock": ("measured" if jax.default_backend() == "tpu"
                       else "emulated"),
        "default_shadow_rate": default_rate,
        "detection_rate": len(ok) / max(1, len(det)),
        "cells": det + over,
    }
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}: {len(det)} detection cells "
          f"(rate {doc['detection_rate']:.2f}), {len(over)} overhead "
          "cells")


if __name__ == "__main__":
    main()
