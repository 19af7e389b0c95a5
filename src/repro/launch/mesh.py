"""Mesh factories + per-mesh axis rules.

Every factory here is a FUNCTION (not a module constant) so importing
this module never touches jax device state — entrypoints set XLA_FLAGS
(e.g. ``--xla_force_host_platform_device_count``) before any jax
initialization.

Two workload families share this module:

* **LM training/serving** (``make_production_mesh``) — 2-D / 3-D meshes:

      single-pod: (16, 16)      axes ("data", "model")        — 256 chips
      multi-pod : (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

  Rationale: the batch shards over ("pod", "data"); params/optimizer
  FSDP over "data" (ZeRO-3 inside a pod, pure DP across pods — the
  gradient all-reduce over "pod" is the only cross-DCN collective in
  the baseline); tensor/expert parallelism over "model".  The "model"
  axis is kept innermost so TP collectives stay on the fastest (ICI)
  links.  The factory generalizes to any (P, D, T) for elastic
  restarts.

* **Permutation workloads** (``make_sort_mesh``) — a 1-D mesh with a
  single "data" axis.  ShuffleSoftSort instances are embarrassingly
  parallel (N parameters each, zero cross-instance communication until
  the final best-restart argmin), so the right topology is the
  degenerate one: flatten the B problems x S restarts grid and shard it
  over every device.  See EXPERIMENTS.md §Scaling for measured
  devices x B x S sweeps.
"""
from __future__ import annotations

import jax

from repro.models.layers import AxisRules


def _auto(ndim: int) -> tuple:
    """Auto axis types for every mesh here.  ``jax.make_mesh`` defaults
    to Explicit axes, under which sharded results carry their mesh axes
    in their types and plain indexing of them (the engines' winner
    gather and survivor scatter) raises ``ShardingTypeError``; with Auto
    axes the compiler places such ops, as ``jit`` does."""
    return (jax.sharding.AxisType.Auto,) * ndim


def make_production_mesh(*, multi_pod: bool = False,
                         shape: tuple[int, ...] | None = None):
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = (("pod", "data", "model") if len(shape) == 3
            else ("data", "model"))
    ndev = 1
    for s in shape:
        ndev *= s
    avail = len(jax.devices())
    if avail < ndev:
        raise RuntimeError(
            f"mesh {shape} needs {ndev} devices, have {avail}; the dry-run "
            "entrypoint must set XLA_FLAGS=--xla_force_host_platform_"
            "device_count before importing jax")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(shape)),
                         devices=jax.devices()[:ndev])


def make_sort_mesh(n_devices: int | None = None, *, devices=None):
    """1-D ("data",) mesh for sharded permutation workloads.

    ``n_devices=None`` uses every visible device.  The sharded engine
    (``shuffle_soft_sort_batched(..., mesh=...)``) splits the flattened
    B x S instance axis over "data", padding the tail shard; per-seed
    results are bit-identical to the single-device vmap engine, so the
    mesh size is purely a throughput knob (EXPERIMENTS.md §Scaling).

    ``devices=`` restricts the mesh to an explicit device list — the
    elastic re-shard path rebuilds the mesh over the SURVIVORS of a
    device eviction at a rung boundary (EXPERIMENTS.md §Robustness,
    "Elastic capacity"); because the rung carry is stored in logical
    layout, the rebuilt mesh is purely a throughput change and per-seed
    results stay bit-identical.
    """
    avail = jax.devices() if devices is None else list(devices)
    n = len(avail) if n_devices is None else int(n_devices)
    if n <= 0:
        raise RuntimeError(
            f"sort mesh wants {n} devices; n_devices must be >= 1 "
            "(None = every visible device)")
    if n > len(avail):
        raise RuntimeError(
            f"sort mesh wants {n} devices, have {len(avail)}; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax to fake more on CPU")
    return jax.make_mesh((n,), ("data",), axis_types=_auto(1),
                         devices=avail[:n])


def axis_rules_for(mesh) -> AxisRules:
    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    return AxisRules(fsdp="data", tp="model", dp=dp)


def dp_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
