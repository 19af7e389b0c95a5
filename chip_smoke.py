"""On-chip smoke test: run the permutation-learning path on a TPU.

    python chip_smoke.py             # one chip: device, kernel, engine,
                                     # serving phases
    python chip_smoke.py --chips 4   # four chips: the mesh phase only

Runs in one process and starts no children.  Each phase prints one
line: what it ran, compile seconds, run seconds and the device's
``peak_bytes_in_use``.  Any failed check exits non-zero before the last
line, which is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

A backend other than ``tpu`` is refused at the device phase: nothing
runs on the CPU.  Data is drawn from ``--seed``; the compilation cache
lives where ``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

BF16_TOL = 2e-2          # documented bf16 envelope (README "Mixed precision")
F32_FWD_ATOL = 2e-5      # tests/test_kernels.py forward tolerance
F32_GRAD_RTOL = 2e-3     # tests/test_kernels.py gradient tolerance, x max|g|
TIER_LOSS_RTOL = 5e-2    # kernel vs jnp tier final loss, per instance
MESH_LOSS_RTOL = 1e-6    # mesh vs one-device losses (same program per shard)


class SmokeFailure(RuntimeError):
    pass


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Phase:
    """Times one phase: wall clock, and the XLA compile time JAX reports
    for it (its backend-compile events, persistent-cache loads
    included), read from the program's compile counter.  Run seconds
    are the rest, tracing included."""

    def __init__(self, name: str):
        self.name = name
        self.compile_s = 0.0

    def __enter__(self):
        from repro.launch.compile_cache import compile_counter
        self._counter = compile_counter()
        self._compile_s0 = self._counter.compile_s
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall_s = time.perf_counter() - self.t0
        self.compile_s = self._counter.compile_s - self._compile_s0
        return False

    def report(self, what: str) -> None:
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use", "not reported")
        print(f"phase={self.name} {what} compile_s={self.compile_s:.2f} "
              f"run_s={self.wall_s - self.compile_s:.2f} "
              f"peak_bytes_in_use={peak}", flush=True)


# --------------------------------------------------------------------------
# Phases.
# --------------------------------------------------------------------------

def device_phase(chips: int):
    import jax
    with Phase("device") as ph:
        devs = jax.devices()
        platform = devs[0].platform
        if platform != "tpu":
            raise SmokeFailure(
                f"no TPU: JAX's default backend is {platform!r} "
                f"({len(devs)} device(s)); this smoke runs only on a TPU")
        require(len(devs) >= chips,
                f"--chips {chips} needs {chips} devices, found {len(devs)}")
        try:
            libtpu = importlib.metadata.version("libtpu")
        except importlib.metadata.PackageNotFoundError:
            libtpu = "not installed"
    ph.report(f"platform={platform} kind={devs[0].device_kind!r} "
              f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _relerr(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def kernel_phase(seed: int) -> None:
    """fwd+grad of the dense and banded applies, f32 and bf16, against
    the f32 O(N^2) oracle (matmuls at highest precision)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels.autotune import (
        TABLE_PATH, _cell_key, _load_table, lookup_blocks)
    from repro.kernels.ref import softsort_apply_ref

    table = _load_table(TABLE_PATH) or {}
    tau = jnp.float32(1.0)
    cases = [("dense", 4, 4096, 3, None), ("banded", 1, 16384, 50, 1024)]
    with Phase("kernel") as ph:
        for tier, b, n, d, k in cases:
            ks = jax.random.split(jax.random.PRNGKey(seed + n + d), 5)
            # Trainer regime: shuffled-arange keys, plus jitter so no two
            # keys are an exact integer apart.
            w = (jax.vmap(lambda kk: jax.random.permutation(
                kk, jnp.arange(n, dtype=jnp.float32)))(
                    jax.random.split(ks[0], b))
                 + 0.1 * jax.random.normal(ks[1], (b, n)))
            x = jax.random.normal(ks[2], (b, n, d))
            a = jax.random.normal(ks[3], (b, n, d))
            c = jax.random.normal(ks[4], (b, n))

            def loss_of(apply_fn):
                def f(w, x, a, c):
                    y, cs = apply_fn(w, x)
                    return jnp.sum(y * a) + jnp.sum(cs * c), (y, cs)
                return jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                                  has_aux=True))

            ref = loss_of(jax.vmap(lambda w, x: softsort_apply_ref(w, x, tau)))
            with jax.default_matmul_precision("highest"):
                (_, (y_r, c_r)), (gw_r, gx_r) = ref(w, x, a, c)
            for dtype in ("float32", "bfloat16"):
                if tier == "dense":
                    blocks = lookup_blocks("fused", n=n, d=d, dtype=dtype)
                    key = _cell_key("fused", n, d, 0, dtype, "tpu")
                    fn = lambda w, x, dt=dtype: ops.softsort_apply(
                        w, x, tau, compute_dtype=dt)
                else:
                    blocks = lookup_blocks("banded", n=n, d=d, k=k,
                                           dtype=dtype)
                    key = _cell_key("banded", n, d, k, dtype, "tpu")
                    fn = lambda w, x, dt=dtype: ops.softsort_apply_banded(
                        w, x, tau, k, compute_dtype=dt)
                step = loss_of(fn)
                t0 = time.perf_counter()
                compiled = step.lower(w, x, a, c).compile()
                t_compile = time.perf_counter() - t0
                require("tpu_custom_call" in compiled.as_text(),
                        f"{tier} {dtype}: no tpu_custom_call in the "
                        "compiled program (the kernel is not Mosaic)")
                t0 = time.perf_counter()
                (_, (y, cs)), (gw, gx) = jax.block_until_ready(
                    compiled(w, x, a, c))
                t_run = time.perf_counter() - t0
                errs = {"y": _relerr(y, y_r), "colsum": _relerr(cs, c_r),
                        "dw": _relerr(gw, gw_r), "dx": _relerr(gx, gx_r)}
                if dtype == "float32":
                    fwd_abs = max(
                        float(np.max(np.abs(np.asarray(y) - y_r))),
                        float(np.max(np.abs(np.asarray(cs) - c_r))))
                    ok = (fwd_abs <= F32_FWD_ATOL
                          and errs["dw"] <= F32_GRAD_RTOL
                          and errs["dx"] <= F32_GRAD_RTOL)
                    tol = (f"fwd max abs {fwd_abs:.3g} <= {F32_FWD_ATOL}, "
                           f"grads <= {F32_GRAD_RTOL} x max|g|")
                else:
                    ok = max(errs.values()) <= BF16_TOL
                    tol = f"all <= {BF16_TOL} relative"
                print(f"  kernel {tier} B={b} N={n} d={d} K={k} {dtype} "
                      f"blocks={blocks} table_hit={key in table} "
                      f"tpu_custom_call=yes compile_s={t_compile:.2f} "
                      f"run_s={t_run:.3f} rel_err="
                      + ",".join(f"{nm}:{v:.2e}" for nm, v in errs.items())
                      + f" ({tol})", flush=True)
                require(ok, f"kernel {tier} {dtype} misses the oracle: "
                            f"{errs} ({tol})")
    ph.report("dense (B4 N4096 d3) + banded (B1 N16384 d50 K1024) "
              "fwd+grad, f32+bf16, vs softsort_apply_ref")


def _valid_orders(orders) -> bool:
    import numpy as np
    o = np.sort(np.asarray(orders).reshape(-1, np.shape(orders)[-1]), axis=1)
    return bool((o == np.arange(o.shape[1])).all())


def engine_phase(seed: int) -> None:
    import dataclasses
    import jax
    import numpy as np
    from repro.core import ShuffleSoftSortConfig, shuffle_soft_sort_batched

    b, s, hw, d = 4, 2, (64, 64), 3
    xs = jax.random.uniform(jax.random.PRNGKey(seed), (b, hw[0] * hw[1], d))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), b * s)
    cfg_k = ShuffleSoftSortConfig(rounds=200, use_kernel=True, band="auto")
    cfg_j = dataclasses.replace(cfg_k, use_kernel=False)
    with Phase("engine") as ph:
        res = {}
        for name, cfg in (("kernel", cfg_k), ("jnp", cfg_j)):
            r = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                          keys=keys)
            require(_valid_orders(r.all_orders),
                    f"{name} tier: an order is not a permutation")
            first, last = r.all_losses[..., 0], r.all_losses[..., -1]
            require(np.isfinite(r.all_losses).all(),
                    f"{name} tier: non-finite loss")
            require((last < first).all(),
                    f"{name} tier: final loss not below first-round loss "
                    f"({last} vs {first})")
            res[name] = last
        gap = np.abs(res["kernel"] - res["jnp"]) / res["jnp"]
        print(f"  engine final loss kernel mean {res['kernel'].mean():.6f} "
              f"jnp mean {res['jnp'].mean():.6f} max rel gap "
              f"{gap.max():.3e} (<= {TIER_LOSS_RTOL})", flush=True)
        require(gap.max() <= TIER_LOSS_RTOL,
                f"kernel and jnp tiers disagree: rel gaps {gap}")
    ph.report("shuffle_soft_sort_batched 64x64 d3 B4 S2 rounds200 "
              "band=auto, kernel tier and jnp tier")


def _check_server_stats(stats: dict, what: str) -> None:
    require(stats["retries"] == 0, f"{what}: {stats['retries']} retries")
    require(stats["failed"] == 0, f"{what}: {stats['failed']} failures")
    require(stats["self_heals"] == 0,
            f"{what}: {stats['self_heals']} self-heals")
    require(not any(stats["degradations"].values()),
            f"{what}: degradations {stats['degradations']}")


def _neighbor_distance(grid_vectors, hw) -> float:
    """Mean distance of 4-neighbour grid cells (lower is better): the
    numerator of ``repro.core.metrics.mean_neighbor_distance``, whose
    O(N^2 d) random-pair normaliser would take ~100 GB of host memory
    at N 16384, d 50 and cancels in a before/after comparison."""
    import numpy as np
    g = np.asarray(grid_vectors, np.float64).reshape(hw[0], hw[1], -1)
    dh = np.linalg.norm(g[:, 1:] - g[:, :-1], axis=-1)
    dv = np.linalg.norm(g[1:, :] - g[:-1, :], axis=-1)
    return float((dh.sum() + dv.sum()) / (dh.size + dv.size))


def _serve_cli(requests: int, rounds: int, extra: list[str]) -> None:
    from repro.launch import serve
    out = serve.main([
        "--workload", "sort", "--sort-n", "4096", "--sort-hw", "64",
        "--sort-d", "3", "--requests", str(requests),
        "--rounds", str(rounds), "--max-batch", "8", "--use-kernel", "--band", "auto", *extra])
    require(out["completed"] == requests,
            f"serve: {out['completed']}/{requests} completed")
    require(out["improved"] == requests,
            f"serve: {out['improved']}/{requests} layouts improved")
    _check_server_stats(out, "serve")


def serving_phase(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import ShuffleSoftSortConfig
    from repro.launch.serve import SortServer

    with Phase("serving") as ph:
        _serve_cli(8, 200, [])
        hw, d = (128, 128), 50
        x = np.asarray(jax.random.uniform(jax.random.PRNGKey(seed + 2),
                                          (hw[0] * hw[1], d)))
        cfg = ShuffleSoftSortConfig(rounds=50, use_kernel=True, band="auto")
        server = SortServer(hw, d=d, cfg=cfg, max_batch=1)
        try:
            fut = server.submit(x, key=jax.random.PRNGKey(seed + 3))
            order, x_sorted, losses = fut.result(timeout=900)
        finally:
            server.close()
        require(_valid_orders(order[None]), "128x128: invalid order")
        before = _neighbor_distance(x, hw)
        after = _neighbor_distance(x_sorted, hw)
        print(f"  SortServer 128x128 d50 rounds50: neighbour distance "
              f"{before:.4f} -> {after:.4f}, loss {losses[0]:.4f} -> "
              f"{losses[-1]:.4f}", flush=True)
        require(after < before, "128x128: layout did not improve")
        require(server.stats["completed"] == 1, "128x128: not completed")
        _check_server_stats(server.stats, "128x128 SortServer")
    ph.report("serve.main sort 8x(64x64 d3) rounds200 kernel band=auto, "
              "then SortServer 1x(128x128 d50) rounds50")


def mesh_phase(seed: int, chips: int) -> None:
    """The sharded engine on ``chips`` devices against the one-device
    engine on the same seeds, then SortServer over a mesh.  Fifty rounds:
    the phase checks equality and placement, which the round count does
    not change, and four chips cost four times as much."""
    import jax
    import numpy as np
    from repro.core import (
        ShuffleSoftSortConfig, run_round_segment, shuffle_soft_sort_batched)
    from repro.launch.mesh import make_sort_mesh

    b, s, hw, d, rounds = 8, 2, (64, 64), 3, 50
    xs = jax.random.uniform(jax.random.PRNGKey(seed), (b, hw[0] * hw[1], d))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), b * s)
    cfg = ShuffleSoftSortConfig(rounds=rounds, use_kernel=True, band="auto")
    mesh = make_sort_mesh(chips)
    with Phase("mesh") as ph:
        one = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                        keys=keys)
        shd = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s,
                                        keys=keys, mesh=mesh)
        require(_valid_orders(shd.all_orders), "mesh: invalid order")
        require(np.array_equal(one.all_orders, shd.all_orders),
                "mesh: orders differ from the one-device engine")
        gap = float(np.max(np.abs(one.all_losses - shd.all_losses)
                           / np.abs(one.all_losses)))
        require(gap <= MESH_LOSS_RTOL, f"mesh: loss rel gap {gap:.3e}")
        # Where the shards land: one rung of 8 instances on the mesh (the
        # program the server below dispatches first).
        orders = np.tile(np.arange(hw[0] * hw[1], dtype=np.int32), (b, 1))
        o, _, _ = run_round_segment(
            np.asarray(xs), orders, np.asarray(keys[:b]),
            np.ones(b, np.float32), np.zeros(b, int), cfg.rounds // 4,
            hw=hw, cfg=cfg, mesh=mesh)
        devs = {sh.device.id for sh in o.addressable_shards}
        require(len(o.sharding.device_set) == chips and len(devs) == chips,
                f"mesh: segment output on devices {sorted(devs)}, "
                f"wanted {chips}")
        print(f"  mesh orders equal to one-device run, loss rel gap "
              f"{gap:.3e}; shards on devices {sorted(devs)}", flush=True)
        _serve_cli(8, rounds, ["--mesh-devices", str(chips)])
    ph.report(f"shuffle_soft_sort_batched 64x64 d3 B8 S2 rounds{rounds} "
              f"on a {chips}-device mesh vs mesh=None, then serve.main "
              f"--mesh-devices {chips}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh phase, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import (compile_counter,
                                            enable_compile_cache)

    cache_dir = enable_compile_cache()
    counter = compile_counter()
    try:
        device = device_phase(args.chips)
        if args.chips == 1:
            kernel_phase(args.seed)
            engine_phase(args.seed)
            serving_phase(args.seed)
        else:
            mesh_phase(args.seed, args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(f"compile cache {cache_dir}: {counter.cache_hits} hits",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
