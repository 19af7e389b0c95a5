"""Paper Table III reproduction: Gumbel-Sinkhorn vs Kissing vs SoftSort vs
ShuffleSoftSort on random RGB colors.

Reports: learnable-parameter memory, wall-clock runtime, DPQ_16 quality,
mean neighbour distance, and permutation validity — the paper's exact
comparison axes (runtime is CPU-relative, as the paper's M1 numbers are).
"""
from __future__ import annotations

import time

import numpy as np

import jax

from repro.core import ShuffleSoftSortConfig, shuffle_soft_sort, soft_sort_baseline
from repro.core.baselines.gumbel_sinkhorn import (
    GumbelSinkhornConfig,
    gumbel_sinkhorn_sort,
)
from repro.core.baselines.kissing import KissingConfig, kissing_sort
from repro.core.metrics import dpq, mean_neighbor_distance
from repro.core.softsort import is_valid_permutation
from repro.launch.compile_cache import enable_compile_cache


def run(n: int = 1024, budget: str = "full", seed: int = 42):
    hw = (int(np.sqrt(n)), int(np.sqrt(n)))
    assert hw[0] * hw[1] == n
    x = jax.random.uniform(jax.random.PRNGKey(seed), (n, 3))
    xs_np = np.asarray(x)

    fast = budget == "fast"
    rows = []

    def add(name, mem, t, order, xsorted, valid=None):
        rows.append({
            "method": name,
            "params": mem,
            "runtime_s": round(t, 1),
            "dpq16": round(dpq(xsorted, hw), 3) if valid in (None, True)
                     else float("nan"),
            "nbr_dist": round(mean_neighbor_distance(xsorted, hw), 3),
            "valid": bool(is_valid_permutation(order)
                          if valid is None else valid),
        })

    # Gumbel-Sinkhorn (N^2 params)
    t0 = time.time()
    gs_cfg = GumbelSinkhornConfig(steps=200 if fast else 1200)
    o, xsr, _ = gumbel_sinkhorn_sort(x, hw, gs_cfg)
    add("gumbel-sinkhorn", n * n, time.time() - t0, o, xsr)

    # Kissing (2NM params)
    t0 = time.time()
    m = max(int(np.ceil(np.sqrt(n) / 2.46)), 13 if n >= 1024 else 8)
    ki_cfg = KissingConfig(rank=m, steps=200 if fast else 1200)
    o, xsr, _, valid = kissing_sort(x, hw, ki_cfg)
    add("kissing", 2 * n * m, time.time() - t0, o, xsr, valid=valid)

    # SoftSort (N params)
    t0 = time.time()
    ss_cfg = ShuffleSoftSortConfig(rounds=250 if fast else 1000,
                                   inner_steps=8, chunk=min(256, n))
    o, xsr, _ = soft_sort_baseline(x, hw, ss_cfg)
    add("softsort", n, time.time() - t0, o, xsr)

    # ShuffleSoftSort (ours reproduced; N params)
    t0 = time.time()
    o, xsr, _ = shuffle_soft_sort(x, hw, ss_cfg, key=jax.random.PRNGKey(1))
    add("shufflesoftsort", n, time.time() - t0, o, xsr)

    return rows


def print_table(rows):
    hdr = f"{'method':18s} {'params':>9s} {'runtime[s]':>10s} " \
          f"{'DPQ16':>6s} {'nbr':>6s} {'valid':>5s}"
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(f"{r['method']:18s} {r['params']:>9,d} "
              f"{r['runtime_s']:>10.1f} {r['dpq16']:>6.3f} "
              f"{r['nbr_dist']:>6.3f} {str(r['valid']):>5s}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1024)
    ap.add_argument("--budget", choices=("fast", "full"), default="full")
    a = ap.parse_args()
    enable_compile_cache()
    print_table(run(a.n, a.budget))
