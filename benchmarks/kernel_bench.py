"""Kernel-tier microbenchmark: the SoftSort apply, fwd and fwd+grad,
one row per implementation layer, swept over an (N, d, B, K, dtype)
grid:

  * ``dense``     — O(N^2)-memory jnp oracle (``kernels/ref.py``)
  * ``chunked``   — streamed pure-jnp row blocks (``core/softsort.py``)
  * ``kernel_v1`` — v1 Pallas path: 3-pass forward + chunked jnp-scan
                    backward (``ops.softsort_apply_v1``, PR 1/2 design)
  * ``fused``     — fused online-softmax forward (2 passes) + 2-pass
                    Pallas backward with (perm, m, l, y) residuals
  * ``banded``    — O(N*K) band-grid Pallas path
                    (``ops.softsort_apply_banded``): both axes in
                    sorted-rank order, width-(2K+1) band scored,
                    payload carried d-on-sublanes; each cell's K is the
                    fourth sweep axis

The dtype axis (``float32`` / ``bfloat16``) exercises the kernels'
``compute_dtype`` tier: bf16 cells run ONLY the kernel impls (fused,
banded — the jnp tiers are the f32 reference and have no bf16 mode) and
their parity columns are measured against the same f32 oracles, gated
by the looser documented bf16 tolerance (``--tol-bf16``).  Block sizes
come from the committed autotune table exactly as production dispatch
does (``repro.kernels.autotune.lookup_blocks``, hardcoded-256 fallback).

Emits ``BENCH_kernels.json`` (committed at the repo root; validated by
``tools/check_bench.py``).  Three kinds of columns:

  * measured wall-clock (``fwd_s`` / ``fwdgrad_s``) — every cell also
    carries ``wall_clock``: "measured" on a real TPU, "emulated" on any
    other backend, where Pallas runs in INTERPRET mode and the numbers
    are shape/ordering signals only — emulation is known to INVERT real
    orderings (the jnp-scan baseline gets native XLA fusion while every
    Pallas grid step pays emulation overhead; EXPERIMENTS.md §Perf).
  * parity (``parity`` / ``band``) — max abs error against the dense
    oracle (and, for the banded kernel, against the windowed jnp oracle
    it must match).  Backend-independent; CI gates on these
    (``--check``): f32 columns against ``--tol``, bf16 columns against
    the documented ``--tol-bf16``.  Banded-vs-dense parity is
    additionally slacked by the recorded ``band.tail_bound``: the keys
    here are a shuffled arange — the trainer's per-round linear init —
    so the K-rank gap is K exactly and the bound is astronomically
    small.
  * modeled HBM traffic (``model_hbm_mb``) — per-pass bytes moved
    between HBM and VMEM for one fwd+grad step, counted mechanically
    from the block specs (block bytes x revisit count, at each
    operand's HBM dtype; see ``_model_hbm_bytes``) — EMITTED FOR EVERY
    DTYPE CELL so check_bench gates on it uniformly.  At the paper's
    d <= 50 the apply is memory-bound (EXPERIMENTS.md §Roofline), so
    TPU step time is proportional to these bytes;
    ``model_fused_over_v1`` / ``model_banded_over_fused`` are the
    expected on-TPU fwd+grad speedups of each transition, and bf16
    cells add ``model_f32_over_this`` — the bf16-vs-f32 traffic
    reduction of each kernel tier at that shape.

Usage:

    PYTHONPATH=src python -m benchmarks.kernel_bench            # full sweep
    PYTHONPATH=src python -m benchmarks.kernel_bench --smoke --check
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.softsort import (
    band_tail_bound,
    softsort_apply_banded as banded_oracle,
    softsort_apply_chunked,
)
from repro.kernels.autotune import lookup_blocks
from repro.kernels.ops import (
    _band_geometry,
    _block_geometry,
    softsort_apply,
    softsort_apply_banded,
    softsort_apply_v1,
)
from repro.kernels.ref import softsort_apply_ref
from repro.launch.compile_cache import enable_compile_cache

FULL_CELLS = [  # (N, d, B, K)
    (1024, 8, 1, 128),
    (1024, 8, 8, 128),
    (1024, 50, 1, 128),
    (2048, 8, 1, 128),
    (4096, 8, 1, 256),
]
SMOKE_CELLS = [(384, 8, 2, 64)]    # multi-block grids, tiny runtime

DTYPES = ("float32", "bfloat16")
F32 = 4                        # bytes
DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def _time(fn, *args, reps: int = 3):
    """(mean seconds over reps, last output) — the output is returned so
    parity columns reuse it instead of re-running the (interpret-mode
    slow) computation a third time."""
    out = fn(*args)            # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def _batched_ref(w, x, tau):
    return jax.vmap(lambda wi, xi: softsort_apply_ref(wi, xi, tau))(w, x)


def _impls(tau, band, dtype):
    """name -> apply(w (B,N), x (B,N,d)) returning (y, c).  bf16 cells
    carry only the kernel impls — the jnp tiers are the f32 oracles."""
    kernel = {
        "fused": lambda w, x: softsort_apply(w, x, tau, compute_dtype=dtype),
        "banded": lambda w, x: softsort_apply_banded(w, x, tau, band,
                                                     compute_dtype=dtype),
    }
    if dtype != "float32":
        return kernel
    return {
        "dense": lambda w, x: _batched_ref(w, x, tau),
        "chunked": lambda w, x: softsort_apply_chunked(w, x, tau, 256),
        "kernel_v1": lambda w, x: softsort_apply_v1(w, x, tau),
        **kernel,
    }


def _model_hbm_bytes(n: int, d: int, bsz: int, band: int,
                     dtype: str = "float32") -> dict:
    """Per-step (fwd+grad) HBM<->VMEM bytes for the kernel paths,
    counted from the block specs: each pass moves ``block bytes x
    revisit count`` per operand (an operand whose index map ignores the
    innermost grid axis is fetched once per outer step and reused), at
    each operand's HBM dtype under the mixed-precision contract — keys,
    m/l/D, and the key/tau gradients are always f32; the payload, the
    dy/dc cotangents, the saved y residual, the y forward output and
    the dx gradient ride in the compute dtype (f32 scratch accumulators
    never touch HBM).  Block sizes resolve through the same autotune
    lookup production dispatch uses.

    N^2-scale terms exist ONLY in the v1 jnp-scan backward (f32-only):
    its einsum boundaries materialize p / dP / ds as (B, chunk, N) HBM
    arrays — one write + one read each, 6 x N^2 x 4 bytes per instance
    (delta, s, sgn fold into fused elementwise ops and are not counted
    — the model is conservative in v1's favor).  The fused backward
    consumes every score block inside its VMEM tile but still STREAMS
    the full (N/block)^2 tile space in TWO passes (the PR-5 merge of
    the delta pass into the dws sweep removed the third); the banded
    path visits only the (N/blk) * (2*ceil(K/blk)+1) band cells AND
    carries the payload d-on-sublanes (dsub = round_up(d, 8) instead of
    the 128-lane pad), which is where its order-of-magnitude byte
    reduction comes from at the paper's small d.
    """
    cdb = DTYPE_BYTES[dtype]
    brc, bcc = lookup_blocks("fused", n=n, d=d, dtype=dtype)
    br, bc, np_, dp = _block_geometry(n, d, brc, bcc)
    ni, nj = np_ // br, np_ // bc
    keys = np_ * F32                      # one (Np,)-sized f32 vector
    keys_c = np_ * cdb                    # one (Np,)-sized cd vector (dc)
    xmat = np_ * dp * cdb                 # one lane-padded (Np, dp) cd matrix
    # v1 is never autotuned: it always runs its hardcoded 256-square
    # blocks, so its model must use THAT geometry, not the fused
    # winner's.
    brv, bcv, npv, dpv = _block_geometry(n, d, 256, 256)
    niv, njv = npv // brv, npv // bcv
    keys_v = npv * F32
    xmat32 = npv * dpv * F32

    # Streamed passes (per instance).  "re-read k x" = the operand's
    # index map varies with the inner grid axis.
    fwd_fused = (
        # fused sweep: ws once, w/x re-read per row block, y (cd, via
        # the f32 scratch accumulator) / m / l written
        (keys + keys * ni + xmat * ni + xmat + 2 * keys)
        # colsum: w once, ws/m/l re-read per col block, c written
        + (keys + 3 * keys * nj + keys)
    )
    bwd_fused = (
        # merged delta+dws sweep: ws/m/l once, w/dc re-read per row
        # block, x re-read per row block, dy/y (cd) row-aligned once,
        # D/dws written (A/S partial sums live in VMEM scratch)
        (3 * keys + keys * ni + keys_c * ni + xmat * ni + 2 * xmat
         + 2 * keys)
        # dx pass: dy re-read per col block, x once, ws/m/l/D re-read,
        # w/dc once, dx (cd, via scratch) / dw_cols / dtau written
        + (xmat * nj + xmat + 4 * keys * nj + keys + keys_c + xmat
           + 2 * keys)
    )
    fwd_v1 = (
        (keys_v + keys_v * niv + 2 * keys_v)               # stats pass
        + (keys_v + keys_v * niv + xmat32 * niv + 2 * keys_v
           + xmat32)                                       # apply pass
        + (keys_v + 3 * keys_v * njv + keys_v)             # colsum pass
        # + m/l round-trip between stats and apply (written then re-read
        # per row block) — the mid-forward HBM traffic the fusion removes
        + 2 * keys_v * 2
    )
    n2 = 6 * n * n * F32                                   # p/dP/ds, w+r
    bwd_v1 = n2 + 2 * n * d * F32 * (n // min(256, n))     # + x/dy per chunk

    # Banded path: square blk-blocks, band cells only, transposed
    # payload (dsub sublanes x Np lanes).
    blkc, _ = lookup_blocks("banded", n=n, d=d, k=band, dtype=dtype)
    blk, npb, dsub = _band_geometry(n, d, blkc)
    nib = npb // blk
    off = -(-band // blk)
    cells = nib * (2 * off + 1)           # vs nib^2 dense grid cells
    bkeys = npb * F32
    bkeys_c = npb * cdb
    keyblk = blk * F32
    keyblk_c = blk * cdb
    xtb = blk * dsub * cdb                # one payload band block, cd
    xt = npb * dsub * cdb                 # whole transposed payload, cd
    fwd_banded = (
        # band sweep: wr once, wc/xt re-read per band cell, y (cd, via
        # scratch) / m / l written
        (bkeys + cells * keyblk + cells * xtb + xt + 2 * bkeys)
        # band colsum: wc once, wr/m/l re-read per band cell, c written
        + (bkeys + 3 * cells * keyblk + bkeys)
    )
    bwd_banded = (
        # merged delta+dws_row band sweep: wr/m/l once, wc per cell,
        # xs_t per cell, dy_t/y_t (cd) row-aligned once, dc (cd) per
        # cell, D/dws_row written (A/S in VMEM scratch)
        (3 * bkeys + cells * keyblk + cells * xtb + 2 * xt
         + cells * keyblk_c + 2 * bkeys)
        # dcol: dy_t per cell, xs_t once, wr/m/l/D per cell, wc once,
        # dc (cd) once, dxs_t (cd, via scratch) / dw_col / dtau written
        + (cells * xtb + xt + 4 * cells * keyblk + bkeys + bkeys_c
           + xt + 2 * bkeys)
    )

    model = {
        "fused": bsz * (fwd_fused + bwd_fused) / 1e6,
        "banded": bsz * (fwd_banded + bwd_banded) / 1e6,
    }
    if dtype == "float32":
        model["kernel_v1"] = bsz * (fwd_v1 + bwd_v1) / 1e6
    # Record the tilings the model was evaluated at — THIS backend's
    # dispatch resolution (autotuned winners where a table row matches,
    # the hardcoded fallback elsewhere).  A different backend may
    # dispatch different blocks (e.g. a TPU host misses every cpu-keyed
    # table row until re-tuned), so the committed model is explicitly a
    # projection at the recorded tiling, not at some other host's.
    blocks = {"fused": [br, bc], "banded": [blk], "kernel_v1": [brv, bcv]}
    return model, blocks


def _cell_operands(n: int, d: int, bsz: int):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(n + d + bsz), 4)
    # Keys are a shuffled arange — exactly the per-round linear init the
    # trainer uses (w = arange(N) re-shuffled each round), so the bench
    # measures the operating regime: unit rank gaps, no bitwise ties (at
    # a bitwise-equal tie |.| has no derivative and blocked vs dense
    # autodiff legitimately pick different subgradients), and a K-rank
    # key spread of exactly K, which is what makes the banded tier's
    # tail bound (and hence its vs-dense parity gate) meaningful.  The
    # same keys ALSO make the bf16 score rounding exact here (scores
    # are small integer multiples of 1/tau), so bf16 cells isolate the
    # payload-side quantization.
    w = jax.vmap(lambda k: jax.random.permutation(
        k, jnp.arange(n, dtype=jnp.float32)))(jax.random.split(k1, bsz))
    x = jax.random.normal(k2, (bsz, n, d))
    a = jax.random.normal(k3, (bsz, n, d))
    b = jax.random.normal(k4, (bsz, n))
    return w, x, a, b


def _loss_fn(apply_fn, a, b):
    def f(w, x):
        y, c = apply_fn(w, x)
        return jnp.sum(y * a) + jnp.sum(c * b)
    return f


def _cell_refs(w, x, a, b, tau: float, band: int) -> dict:
    """The f32 oracle references (dense + windowed banded, fwd and dw),
    computed ONCE per (N, d, B, K) shape — every dtype cell of that
    shape shares the identical keys and payload, so recomputing the
    O(N^2) dense oracle per dtype would only burn bench time."""
    dense = jax.jit(lambda w, x: _batched_ref(w, x, tau))
    y_ref, c_ref = dense(w, x)
    dw_ref = jax.jit(jax.grad(_loss_fn(
        lambda w, x: _batched_ref(w, x, tau), a, b)))(w, x)
    ob = jax.jit(lambda w, x: banded_oracle(w, x, tau, band))
    y_ob, c_ob = ob(w, x)
    dw_ob = jax.jit(jax.grad(_loss_fn(
        lambda w, x: banded_oracle(w, x, tau, band), a, b)))(w, x)
    return {"y": y_ref, "c": c_ref, "dw": dw_ref,
            "y_band": y_ob, "c_band": c_ob, "dw_band": dw_ob}


def run_cell(n: int, d: int, bsz: int, band: int, dtype: str,
             tau: float = 0.5, reps: int = 3, operands=None,
             refs=None) -> dict:
    w, x, a, b = operands if operands is not None else _cell_operands(
        n, d, bsz)
    if refs is None:
        refs = _cell_refs(w, x, a, b, tau, band)

    impls = _impls(tau, band, dtype)

    fwd_s, fwdgrad_s, grads, outs = {}, {}, {}, {}
    for name, fn in impls.items():
        fwd_s[name], outs[name] = _time(jax.jit(fn), w, x, reps=reps)
        jg = jax.jit(jax.value_and_grad(_loss_fn(fn, a, b)))
        fwdgrad_s[name], (_, grads[name]) = _time(jg, w, x, reps=reps)

    y_ref, c_ref, dw_ref = refs["y"], refs["c"], refs["dw"]

    def relerr(got, want):
        # max abs error relative to the oracle's max magnitude — scale-
        # free, so one tolerance gates every N/d/B cell.
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        return float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want))) / scale

    parity = {}
    for name in impls:
        if name in ("dense", "banded"):
            continue
        parity[f"{name}_y_relerr"] = relerr(outs[name][0], y_ref)
        parity[f"{name}_c_relerr"] = relerr(outs[name][1], c_ref)
        parity[f"{name}_dw_relerr"] = relerr(grads[name], dw_ref)

    # Banded: against its windowed f32 jnp oracle (same truncation, so
    # this isolates the kernel/precision error), within the analytic
    # tail bound (plus tolerance) against the dense oracle.
    band_cols = {
        "K": band,
        "tail_bound": float(jnp.max(band_tail_bound(w, tau, band))),
        "vs_oracle_y_relerr": relerr(outs["banded"][0], refs["y_band"]),
        "vs_oracle_c_relerr": relerr(outs["banded"][1], refs["c_band"]),
        "vs_oracle_dw_relerr": relerr(grads["banded"], refs["dw_band"]),
        "vs_dense_y_relerr": relerr(outs["banded"][0], y_ref),
        "vs_dense_c_relerr": relerr(outs["banded"][1], c_ref),
        "vs_dense_dw_relerr": relerr(grads["banded"], dw_ref),
    }

    model, model_blocks = _model_hbm_bytes(n, d, bsz, band, dtype)
    cell = {
        "N": n, "d": d, "B": bsz, "K": band, "tau": tau,
        "dtype": dtype,
        "wall_clock": ("measured" if jax.default_backend() == "tpu"
                       else "emulated"),
        "fwd_s": fwd_s,
        "fwdgrad_s": fwdgrad_s,
        "parity": parity,
        "band": band_cols,
        "model_hbm_mb": model,
        "model_blocks": model_blocks,
        "model_banded_over_fused": model["fused"] / model["banded"],
        "passes": {"kernel_v1_fwd": 3, "fused_fwd": 2, "fused_bwd": 2,
                   "banded_fwd": 2, "banded_bwd": 2, "kernel_v1_bwd": 0},
    }
    if dtype == "float32":
        cell["model_fused_over_v1"] = model["kernel_v1"] / model["fused"]
    else:
        f32_model, _ = _model_hbm_bytes(n, d, bsz, band, "float32")
        cell["model_f32_over_this"] = {
            name: f32_model[name] / model[name] for name in model}
    return cell


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single tiny multi-block cell (CI), both dtypes")
    ap.add_argument("--check", action="store_true",
                    help="assert every parity column <= its dtype's tol "
                         "(banded-vs-dense <= tol + tail bound) and exit "
                         "non-zero otherwise")
    ap.add_argument("--tol", type=float, default=2e-3,
                    help="f32 parity gate: max abs error vs the dense "
                         "oracle, scaled by the oracle magnitude")
    ap.add_argument("--tol-bf16", type=float, default=2e-2,
                    help="bf16 parity gate — the documented bf16 "
                         "envelope (EXPERIMENTS.md §Perf): payload "
                         "quantization is ~0.4%% relative and the "
                         "observed worst case across the sweep is "
                         "under 1%%")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_kernels.json "
                         "for the full sweep, stdout-only for --smoke)")
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)
    enable_compile_cache()

    shapes = SMOKE_CELLS if args.smoke else FULL_CELLS
    rows = []
    for n, d, bsz, band in shapes:
        operands = _cell_operands(n, d, bsz)
        refs = _cell_refs(*operands, 0.5, band)   # shared across dtypes
        for dtype in DTYPES:
            cell = run_cell(n, d, bsz, band, dtype, reps=args.reps,
                            operands=operands, refs=refs)
            rows.append(cell)
            extra = (f"fused/v1 HBM {cell['model_fused_over_v1']:.2f}x"
                     if dtype == "float32" else
                     f"f32/bf16 banded HBM "
                     f"{cell['model_f32_over_this']['banded']:.2f}x")
            print(f"N={n} d={d} B={bsz} K={band} {dtype}: "
                  f"fwd fused {cell['fwd_s']['fused']*1e3:.1f}ms "
                  f"banded {cell['fwd_s']['banded']*1e3:.1f}ms "
                  f"({cell['wall_clock']}), {extra}, "
                  f"banded/fused win "
                  f"{cell['model_banded_over_fused']:.2f}x, "
                  f"banded dw err vs oracle "
                  f"{cell['band']['vs_oracle_dw_relerr']:.2e} "
                  f"(vs dense {cell['band']['vs_dense_dw_relerr']:.2e}, "
                  f"bound {cell['band']['tail_bound']:.2e})")

    doc = {
        "bench": "kernel_bench",
        "backend": jax.default_backend(),
        "tol": args.tol,
        "tol_bf16": args.tol_bf16,
        "note": ("off-TPU the Pallas kernels run in interpret mode: "
                 "wall-clock columns are labeled 'emulated' and are "
                 "shape signals only (emulation overhead penalizes the "
                 "Pallas backward; the jnp-scan baseline gets native "
                 "XLA fusion — orderings INVERT vs real TPU, see "
                 "EXPERIMENTS.md §Perf); parity columns are exact "
                 "everywhere (f32 gated by tol, bf16 by tol_bf16); "
                 "model_hbm_mb counts per-step HBM<->VMEM bytes from "
                 "the block specs at each operand's HBM dtype and is "
                 "the memory-bound TPU projection (EXPERIMENTS.md "
                 "§Roofline) AT the tilings recorded in model_blocks — "
                 "this backend's dispatch resolution (autotuned winners "
                 "where present, 256 fallback elsewhere; v1 always its "
                 "hardcoded 256); another backend may dispatch "
                 "different blocks; banded vs-dense parity is gated "
                 "against band.tail_bound"),
        "cells": rows,
    }
    out = args.out or (None if args.smoke else "BENCH_kernels.json")
    if out:
        with open(out, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {out}")

    if args.check:
        bad = []
        for cell in rows:
            tol = args.tol if cell["dtype"] == "float32" else args.tol_bf16
            for key, val in cell["parity"].items():
                if not np.isfinite(val) or val > tol:
                    bad.append((cell["N"], cell["d"], cell["B"],
                                cell["dtype"], key, val))
            bound = cell["band"]["tail_bound"]
            for key, val in cell["band"].items():
                if key in ("K", "tail_bound"):
                    continue
                lim = tol + (bound if key.startswith("vs_dense") else 0)
                if not np.isfinite(val) or val > lim:
                    bad.append((cell["N"], cell["d"], cell["B"],
                                cell["dtype"], f"band.{key}", val))
        if bad:
            raise SystemExit(f"parity gate failed: {bad}")
        ncols = sum(len(c["parity"]) + len(c["band"]) - 2 for c in rows)
        print(f"parity gate OK (tol={args.tol}, tol_bf16={args.tol_bf16}, "
              f"{ncols} columns)")
    return doc


if __name__ == "__main__":
    main()
