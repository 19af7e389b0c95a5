"""Per-shape block-size autotuning for the SoftSort-apply kernel tiers.

The fused dense kernels are tiled by (Br, Bc) and the banded kernels by
one square block edge; the right tiling depends on (N, d, K, dtype,
backend) — lane-padded payload blocks want narrow Bc at large d, band
grids want blk commensurate with K, and bf16 halves every block's bytes
which moves the VMEM sweet spot.  Rather than freeze 256 everywhere,
this module:

  * ``search`` / ``search_cells`` — times every candidate tiling on the
    kernel-bench harness (fwd+grad of the real custom_vjp path, shuffled
    -arange keys — the trainer's operating regime) and records the
    winner per (tier, N, d, K, dtype, backend);
  * persists winners to a committed JSON table
    (``src/repro/kernels/autotune_table.json``, envelope ``bench:
    "autotune"`` — schema-checked by ``tools/check_bench.py``, which
    also rejects winners that are not in the recorded candidate grid);
  * ``lookup_blocks`` — consulted by ``repro.kernels.ops`` at dispatch
    time whenever the caller leaves the block sizes unset (for the
    banded tier an upper bound: ``ops._band_geometry`` halves the edge
    while a wide payload's working set exceeds the default VMEM).  A lookup
    miss (unknown shape, un-tuned backend, missing/corrupt table) falls
    back to the safe hardcoded 256-square tiling — the pre-autotune
    default, valid for every shape — so dispatch NEVER searches and
    NEVER fails; the table only ever upgrades it.

Block choice is pure performance: every candidate computes the identical
math (asserted by the parity suites for the 256 default and by
``--check`` here for each searched winner), so consulting the table
cannot perturb the engines' bit-identity contracts — within one fixed
(dtype, block) choice results are bitwise reproducible, and the table
pins exactly that choice per shape.

Wall-clock caveat: on a CPU CI backend the kernels run in interpret
mode, so the committed winners for ``backend: "cpu"`` rank *emulation*
cost, not MXU cost (EXPERIMENTS.md §Autotune).  The table keys include
the backend precisely so a TPU run re-tunes into its own rows:

    PYTHONPATH=src python -m repro.kernels.autotune            # full
    PYTHONPATH=src python -m repro.kernels.autotune --smoke --check
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import time

import jax
import jax.numpy as jnp

TABLE_PATH = os.path.join(os.path.dirname(__file__), "autotune_table.json")

# The pre-autotune defaults: valid for every shape (the geometry helpers
# clamp oversized blocks), so they are the safe lookup-miss fallback.
FALLBACK = {"fused": (256, 256), "banded": (256, 256)}

# Candidate tilings.  Dense: (Br, Bc) pairs — Br is sublane-quantized,
# Bc lane-quantized.  Banded: square block edges (the band offset
# arithmetic wants one edge length).  Kept deliberately small: each
# candidate is a full recompile of fwd+grad.
CANDIDATES = {
    "fused": [(128, 128), (128, 256), (256, 128), (256, 256), (512, 256)],
    "banded": [128, 256, 512],
}
SMOKE_CANDIDATES = {
    "fused": [(128, 128), (256, 256)],
    "banded": [128, 256],
}

# (tier, N, d, K) cells of the full search — the bench sweep's shapes.
# K = 0 means the dense tier (no band).
FULL_CELLS = [
    ("fused", 1024, 8, 0),
    ("fused", 1024, 50, 0),
    ("banded", 1024, 8, 128),
    ("banded", 1024, 50, 128),
    ("banded", 2048, 8, 128),
    ("banded", 4096, 8, 256),
]
SMOKE_CELLS = [
    ("fused", 256, 8, 0),
    ("banded", 384, 8, 64),
]

DTYPES = ("float32", "bfloat16")


def _cell_key(tier: str, n: int, d: int, k: int, dtype: str,
              backend: str) -> tuple:
    return (tier, int(n), int(d), int(k or 0), str(dtype), str(backend))


def _cand_label(cand) -> str:
    return "x".join(str(v) for v in cand) if isinstance(cand, (list, tuple)) \
        else str(cand)


def _effective(tier: str, cand, n: int):
    """Collapse candidates that the geometry helpers would clamp to the
    same tiling at this N, so the search never times duplicates."""
    from repro.kernels.ops import _band_geometry, _block_geometry
    if tier == "fused":
        br, bc, _, _ = _block_geometry(n, 1, cand[0], cand[1])
        return (br, bc)
    blk, _, _ = _band_geometry(n, 1, cand)
    return (blk,)


@functools.lru_cache(maxsize=8)
def _load_table(path: str):
    """Parse the table once per path; None when absent or unreadable
    (the fallback then applies — dispatch must never fail)."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if not isinstance(doc, dict) or doc.get("bench") != "autotune":
        return None
    rows = {}
    for cell in doc.get("cells", ()):
        try:
            key = _cell_key(cell["tier"], cell["N"], cell["d"], cell["K"],
                            cell["dtype"], cell["backend"])
            rows[key] = tuple(int(v) for v in (
                cell["winner"] if isinstance(cell["winner"], list)
                else [cell["winner"]]))
        except (KeyError, TypeError, ValueError):
            continue
    return rows


def lookup_blocks(tier: str, n: int, d: int, k: int | None = None,
                  dtype: str = "float32",
                  path: str = TABLE_PATH) -> tuple[int, int]:
    """Autotuned (block_rows, block_cols) for the fused tier or
    (blk, blk) for the banded tier; hardcoded fallback on any miss.

    Pure host-side reading of a static table — called at trace time on
    static shapes, never searches, never raises.
    """
    assert tier in FALLBACK, tier
    rows = _load_table(path)
    if rows:
        key = _cell_key(tier, n, d, k or 0, dtype, jax.default_backend())
        win = rows.get(key)
        if win:
            return (win[0], win[1]) if len(win) > 1 else (win[0], win[0])
    return FALLBACK[tier]


# --------------------------------------------------------------------------
# Search: the kernel-bench timing harness over the candidate grid.
# --------------------------------------------------------------------------

def _make_operands(n: int, d: int, bsz: int = 1):
    """Shuffled-arange keys + normal payload — the trainer's per-round
    linear-init regime, same as benchmarks/kernel_bench.py."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(n + d))
    w = jax.vmap(lambda k: jax.random.permutation(
        k, jnp.arange(n, dtype=jnp.float32)))(jax.random.split(k1, bsz))
    x = jax.random.normal(k2, (bsz, n, d))
    return w, x


def _time_apply(apply_fn, w, x, reps: int) -> float:
    """Mean fwd+grad seconds — the step the trainer actually pays."""
    def loss(w, x):
        y, c = apply_fn(w, x)
        return jnp.sum(y) + jnp.sum(c)

    f = jax.jit(jax.grad(loss, argnums=(0, 1)))
    jax.block_until_ready(f(w, x))                     # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(w, x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / max(reps, 1)


def search_cell(tier: str, n: int, d: int, k: int, dtype: str,
                candidates, reps: int = 2, tau: float = 0.5) -> dict:
    """Time every (deduplicated) candidate tiling for one cell; returns
    the table row with the winner and the full per-candidate timings."""
    from repro.kernels.ops import softsort_apply, softsort_apply_banded
    w, x = _make_operands(n, d)
    timings: dict[str, float] = {}
    seen_geom: dict[tuple, str] = {}
    best, best_s = None, float("inf")
    for cand in candidates:
        geom = _effective(tier, cand, n)
        if geom in seen_geom:       # clamps to an already-timed tiling
            timings[_cand_label(cand)] = timings[seen_geom[geom]]
            continue
        if tier == "fused":
            def apply_fn(w, x, cand=cand):
                return softsort_apply(w, x, tau, block_rows=cand[0],
                                      block_cols=cand[1],
                                      compute_dtype=dtype)
        else:
            def apply_fn(w, x, cand=cand):
                return softsort_apply_banded(w, x, tau, band=k,
                                             block=cand,
                                             compute_dtype=dtype)
        label = _cand_label(cand)
        seen_geom[geom] = label
        secs = _time_apply(apply_fn, w, x, reps)
        timings[label] = secs
        if secs < best_s:
            best, best_s = cand, secs
    winner = list(best) if isinstance(best, (list, tuple)) else [best]
    return {
        "tier": tier, "N": n, "d": d, "K": int(k or 0), "dtype": dtype,
        "backend": jax.default_backend(),
        "winner": winner,
        "winner_s": best_s,
        "candidate_s": timings,
    }


def search_cells(cells, candidates_by_tier, reps: int = 2,
                 verbose: bool = True) -> list[dict]:
    rows = []
    for tier, n, d, k in cells:
        for dtype in DTYPES:
            row = search_cell(tier, n, d, k, dtype,
                              candidates_by_tier[tier], reps=reps)
            rows.append(row)
            if verbose:
                print(f"autotune {tier} N={n} d={d} K={k} {dtype}: "
                      f"winner {_cand_label(row['winner'])} "
                      f"({row['winner_s'] * 1e3:.1f} ms)")
    return rows


def write_table(rows, candidates_by_tier, path: str) -> dict:
    """Merge ``rows`` into the table at ``path`` and rewrite it.

    MERGE, not replace: rows keep their (tier, N, d, K, dtype, backend)
    identity, so re-tuning on one backend updates that backend's rows
    and leaves every other backend's committed rows intact (the whole
    point of keying rows by backend — a TPU re-tune must not delete the
    cpu CI rows, nor vice versa).  A cell searched again simply
    replaces its previous row.  Candidate grids merge per tier the same
    way (new grid wins for its tier)."""
    existing = _load_table(path)
    if existing:
        with open(path) as f:
            old_doc = json.load(f)
        merged = {  # key -> row, old rows first so new ones replace them
            _cell_key(c["tier"], c["N"], c["d"], c["K"], c["dtype"],
                      c["backend"]): c
            for c in old_doc.get("cells", ()) if isinstance(c, dict)}
        for row in rows:
            merged[_cell_key(row["tier"], row["N"], row["d"], row["K"],
                             row["dtype"], row["backend"])] = row
        rows = [merged[k] for k in sorted(merged)]
        # Candidate grids UNION per tier: a narrow (e.g. smoke) re-tune
        # must not shrink the grid out from under previously committed
        # winners (check_bench requires every winner to be in the grid).
        union: dict[str, list] = {}
        old_cands = old_doc.get("candidates", {})
        for source in (old_cands, candidates_by_tier):
            for t, cands in source.items():
                if not isinstance(cands, (list, tuple)):
                    continue
                seen = union.setdefault(t, [])
                for c in cands:
                    tup = tuple(c) if isinstance(c, (list, tuple)) else (c,)
                    if tup not in [tuple(v) if isinstance(v, (list, tuple))
                                   else (v,) for v in seen]:
                        seen.append(c)
        candidates_by_tier = union
    doc = {
        "bench": "autotune",
        "version": 1,
        "backend": jax.default_backend(),
        "note": ("block-size winners per (tier, N, d, K, dtype, backend) "
                 "from the fwd+grad timing harness; consulted by "
                 "repro.kernels.ops when block sizes are unset, with a "
                 "hardcoded 256 fallback on any miss.  CPU rows rank "
                 "interpret-mode emulation cost, not MXU cost — re-run "
                 "on a TPU backend to add real rows (EXPERIMENTS.md "
                 "§Autotune)."),
        "candidates": {t: [list(c) if isinstance(c, (list, tuple)) else [c]
                           for c in cands]
                       for t, cands in candidates_by_tier.items()},
        "cells": rows,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    _load_table.cache_clear()
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny cells + candidate grid (CI; interpret "
                         "mode on CPU as always)")
    ap.add_argument("--out", default=None,
                    help="output table path (default: the committed "
                         "table for the full search, a throwaway "
                         "/tmp file for --smoke)")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--check", action="store_true",
                    help="after writing, re-read the table via "
                         "lookup_blocks and assert every searched cell "
                         "round-trips to its winner")
    args = ap.parse_args(argv)

    cells = SMOKE_CELLS if args.smoke else FULL_CELLS
    cands = SMOKE_CANDIDATES if args.smoke else CANDIDATES
    out = args.out or (os.path.join("/tmp", "autotune_smoke.json")
                       if args.smoke else TABLE_PATH)
    rows = search_cells(cells, cands, reps=args.reps)
    write_table(rows, cands, out)
    print(f"wrote {out} ({len(rows)} cells)")

    if args.check:
        bad = []
        for row in rows:
            got = lookup_blocks(row["tier"], row["N"], row["d"], row["K"],
                                row["dtype"], path=out)
            want = tuple(row["winner"])
            want = want if len(want) > 1 else (want[0], want[0])
            if got != want:
                bad.append((row, got))
        if bad:
            raise SystemExit(f"autotune round-trip failed: {bad}")
        print(f"round-trip OK ({len(rows)} cells, cold write -> warm "
              "lookup, no re-search)")
    return rows


if __name__ == "__main__":
    main()
