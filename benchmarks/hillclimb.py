"""§Perf hillclimb driver: lower+compile one cell under named variants and
report the probe-corrected roofline deltas.

    PYTHONPATH=src python -m benchmarks.hillclimb --cell granite_train
    PYTHONPATH=src python -m benchmarks.hillclimb --cell llama4_prefill
    PYTHONPATH=src python -m benchmarks.hillclimb --cell 405b_decode

Each cell definition lists (variant-name, opts) pairs in hypothesis
order; results land in hillclimb_<cell>.json for EXPERIMENTS.md §Perf.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models.config import cell_by_name

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

CELLS = {
    "granite_train": {
        "arch": "granite-moe-3b-a800m", "shape": "train_4k",
        "variants": [
            ("baseline", {}),
            ("moe_shard", {"moe_shard": True}),
            ("group256", {"moe_group_size": 256}),
            ("group256+moe_shard", {"moe_group_size": 256,
                                    "moe_shard": True}),
            ("group128+moe_shard", {"moe_group_size": 128,
                                    "moe_shard": True}),
            ("no_remat+group256+moe_shard", {"moe_group_size": 256,
                                             "moe_shard": True,
                                             "remat": False}),
            ("gather_moe+group256", {"moe_impl": "gather",
                                     "moe_group_size": 256,
                                     "moe_shard": True}),
            ("gather_moe+group256+no_remat", {"moe_impl": "gather",
                                              "moe_group_size": 256,
                                              "moe_shard": True,
                                              "remat": False}),
        ],
    },
    "llama4_prefill": {
        "arch": "llama4-scout-17b-a16e", "shape": "prefill_32k",
        "variants": [
            ("baseline", {}),
            ("moe_shard", {"moe_shard": True}),
            ("moe_shard+group256", {"moe_shard": True,
                                    "moe_group_size": 256}),
            ("moe_shard+group1024", {"moe_shard": True,
                                     "moe_group_size": 1024}),
            ("gather_moe", {"moe_impl": "gather", "moe_shard": True}),
            ("gather_moe+group1024", {"moe_impl": "gather",
                                      "moe_shard": True,
                                      "moe_group_size": 1024}),
            ("router_bf16", {}),     # code change: router matmul in bf16
            ("router_bf16+seq_parallel", {"force_sp": True}),
            ("router_bf16+gather_moe", {"moe_impl": "gather",
                                        "moe_shard": True}),
            ("router_bf16+sp+gather", {"force_sp": True,
                                       "moe_impl": "gather",
                                       "moe_shard": True}),
        ],
    },
    "405b_decode": {
        "arch": "llama3-405b", "shape": "decode_32k",
        "variants": [
            ("baseline", {}),
            ("weight_stationary_2dtp", {"decode_dshard": True}),
        ],
    },
}


def run_cell_variants(name, mesh):
    from repro.launch.dryrun import probe_corrected_costs
    spec = CELLS[name]
    cfg = get_config(spec["arch"])
    cell = cell_by_name(spec["shape"])
    out = []
    for vname, opts in spec["variants"]:
        t0 = time.time()
        try:
            costs = probe_corrected_costs(cfg, cell, mesh, opts)
            rec = {
                "variant": vname, "opts": opts,
                "flops": costs["flops"],
                "bytes": costs["bytes_accessed"],
                "coll": costs["collective_bytes"],
                "compute_s": costs["flops"] / PEAK_FLOPS,
                "memory_s": costs["bytes_accessed"] / HBM_BW,
                "collective_s": costs["collective_bytes"] / LINK_BW,
                "wall_s": round(time.time() - t0, 1),
            }
            rec["bound_s"] = max(rec["compute_s"], rec["memory_s"],
                                 rec["collective_s"])
        except Exception as e:                              # noqa: BLE001
            rec = {"variant": vname, "opts": opts, "error": repr(e)}
        out.append(rec)
        if "bound_s" in rec:
            print(f"  {vname:32s} compute={rec['compute_s']:8.3f}s "
                  f"memory={rec['memory_s']:8.3f}s "
                  f"coll={rec['collective_s']:8.3f}s "
                  f"bound={rec['bound_s']:8.3f}s", flush=True)
        else:
            print(f"  {vname:32s} ERROR {rec['error'][:80]}", flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", choices=list(CELLS) + ["paper_sort"],
                    required=True)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    args = ap.parse_args()
    enable_compile_cache()
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    print(f"hillclimbing {args.cell} on {args.mesh} mesh", flush=True)
    if args.cell == "paper_sort":
        out = run_paper_variants(mesh)
    else:
        out = run_cell_variants(args.cell, mesh)
    path = f"hillclimb_{args.cell}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {path}")





# ---------------------------------------------------------------- paper cell

def run_paper_variants(mesh, n=1 << 20, d=59):
    """Hillclimb the paper's own workload: one SoftSort grad step over
    N=2^20 splat attributes.  Variants: row-shard topology, payload
    dtype, chunk size.  (The Pallas kernel's terms are analytic — it
    lowers only for TPU; see EXPERIMENTS.md §Perf.)"""
    import functools
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.losses import grid_sorting_loss
    from repro.core.softsort import softsort_apply_chunked

    hw = (1 << 10, 1 << 10)
    axis0 = mesh.axis_names[0]
    all_axes = tuple(mesh.axis_names)

    def make_step(chunk, bf16_payload):
        def loss(w, x, tau, norm):
            xx = x.astype(jnp.bfloat16) if bf16_payload else x
            y, cs = softsort_apply_chunked(w, xx, tau, chunk=chunk)
            return grid_sorting_loss(y.astype(jnp.float32), cs, x, hw, norm)

        def step(w, x, tau, norm):
            l, g = jax.value_and_grad(loss)(w, x, tau, norm)
            return l, g
        return step

    def measure(name, chunk, bf16_payload, shard_axes):
        w = jax.ShapeDtypeStruct((n,), jnp.float32)
        x = jax.ShapeDtypeStruct((n, d), jnp.float32)
        tau = jax.ShapeDtypeStruct((), jnp.float32)
        norm = jax.ShapeDtypeStruct((), jnp.float32)
        sh_x = NamedSharding(mesh, P(shard_axes, None))
        sh_w = NamedSharding(mesh, P())       # N params replicated
        jfn = jax.jit(make_step(chunk, bf16_payload),
                      in_shardings=(sh_w, sh_x, None, None),
                      out_shardings=(None, NamedSharding(mesh, P())))
        with jax.set_mesh(mesh):
            compiled = jfn.lower(w, x, tau, norm).compile()
        from repro.launch.dryrun import collective_stats
        cost = compiled.cost_analysis()
        coll = collective_stats(compiled.as_text())
        rec = {
            "variant": name,
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll["total_bytes"],
        }
        rec["compute_s"] = rec["flops"] / PEAK_FLOPS
        rec["memory_s"] = rec["bytes"] / HBM_BW
        rec["collective_s"] = rec["coll"] / LINK_BW
        rec["bound_s"] = max(rec["compute_s"], rec["memory_s"],
                             rec["collective_s"])
        print(f"  {name:32s} compute={rec['compute_s']:8.4f}s "
              f"memory={rec['memory_s']:8.4f}s "
              f"coll={rec['collective_s']:8.4f}s "
              f"bound={rec['bound_s']:8.4f}s", flush=True)
        return rec

    out = []
    out.append(measure("baseline_rows_axis0_c512", 512, False, axis0))
    out.append(measure("rows_all_axes_c512", 512, False, all_axes))
    out.append(measure("rows_all_axes_c2048", 2048, False, all_axes))
    out.append(measure("rows_all_axes_c512_bf16x", 512, True, all_axes))
    out.append(measure("rows_all_axes_c2048_bf16x", 2048, True, all_axes))
    return out


if __name__ == "__main__":
    main()
