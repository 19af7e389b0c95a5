"""Launcher-layer tests that don't need 512 devices: HLO collective
parser, spec sanitizer, roofline math, and the CPU-scale train/serve
drivers (end-to-end system behaviour)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ------------------------------------------------------- collective parse

HLO_SAMPLE = """
  %ag = bf16[16,1024]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%y), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = u32[8]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %done = bf16[16,1024]{1,0} all-gather-done(%ag)
"""


def test_collective_stats_parser():
    from repro.launch.dryrun import collective_stats
    st = collective_stats(HLO_SAMPLE)
    assert st["all-gather"]["count"] == 1
    # all-gather: result 16*1024*2 B * (g-1)/g with g=4
    np.testing.assert_allclose(st["all-gather"]["bytes"],
                               16 * 1024 * 2 * 3 / 4)
    # all-reduce: 2 * size * (g-1)/g, g=2
    np.testing.assert_allclose(st["all-reduce"]["bytes"],
                               2 * 256 * 4 * 1 / 2)
    # reduce-scatter: result * (g-1), g=4
    np.testing.assert_allclose(st["reduce-scatter"]["bytes"],
                               64 * 4 * 3)
    assert st["collective-permute"]["count"] == 1
    assert st["total_bytes"] > 0


def test_shape_bytes_tuple_types():
    from repro.launch.dryrun import _shape_bytes
    assert _shape_bytes("bf16[8,4]") == 64
    assert _shape_bytes("(f32[2,2], s8[16])") == 32


# -------------------------------------------------------- spec sanitizer

def test_sanitize_spec_drops_indivisible_axes():
    from repro.launch.steps import _sanitize_spec
    mesh = jax.make_mesh((1,), ("data",))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    m = FakeMesh()
    s = _sanitize_spec(m, P("model", "data"), (40, 1536))
    assert s == P(None, "data")          # 40 % 16 != 0 -> dropped
    s = _sanitize_spec(m, P("model", None), (512, 7))
    assert s == P("model", None)
    s = _sanitize_spec(m, P(("data", "model"), None), (512, 7))
    assert s == P(("data", "model"), None)
    s = _sanitize_spec(m, P(("data", "model"), None), (128, 7))
    assert s == P(None, None)            # 128 % 256 != 0


# ---------------------------------------------------------- roofline math

def test_roofline_analyze_toy_record():
    from benchmarks.roofline import analyze, PEAK_FLOPS
    rec = {
        "arch": "qwen1.5-0.5b", "shape": "train_4k", "mesh": "single",
        "kind": "train", "status": "ok",
        "roofline_inputs": {"flops": 1e13, "bytes_accessed": 1e12,
                            "collective_bytes": 1e11},
    }
    rows = analyze([rec])
    assert len(rows) == 1
    r = rows[0]
    np.testing.assert_allclose(r["compute_s"], 1e13 / PEAK_FLOPS)
    assert r["dominant"] in ("compute", "memory", "collective")
    assert 0 < r["useful_ratio"] < 2.0
    assert r["roofline_frac"] <= 1.0 + 1e-6


def test_active_param_counts_moe_scaling():
    from benchmarks.roofline import active_param_counts
    a_moe, e_moe = active_param_counts("granite-moe-3b-a800m")
    a_dense, _ = active_param_counts("qwen1.5-0.5b")
    assert a_moe > 0 and e_moe > 0
    # granite: top-8 of 40 experts -> active far below total
    from repro.configs import get_config
    from repro.models import model as model_lib
    total = sum(np.prod(l.shape) for l in jax.tree.leaves(
        jax.eval_shape(lambda k: model_lib.init_model(
            k, get_config("granite-moe-3b-a800m"))[0],
            jax.random.PRNGKey(0))))
    assert a_moe < 0.45 * total


# ----------------------------------------------------- end-to-end drivers

def test_train_driver_loss_decreases(tmp_path):
    from repro.launch.train import main
    stats = main(["--preset", "tiny", "--steps", "40", "--batch", "4",
                  "--seq", "32", "--ckpt-dir", str(tmp_path),
                  "--lr", "1e-3"])
    assert stats["steps"] == 40
    assert stats["last_loss"] < stats["first_loss"]


def test_train_driver_failure_recovery(tmp_path):
    from repro.launch.train import main
    stats = main(["--preset", "tiny", "--steps", "30", "--batch", "2",
                  "--seq", "16", "--ckpt-dir", str(tmp_path),
                  "--fail-at", "15", "--ckpt-every", "10"])
    assert stats["restarts"] == 1
    assert stats["steps"] == 30


def test_train_driver_with_compression(tmp_path):
    from repro.launch.train import main
    stats = main(["--preset", "tiny", "--steps", "30", "--batch", "2",
                  "--seq", "16", "--ckpt-dir", str(tmp_path),
                  "--compress-grads", "--lr", "1e-3"])
    assert stats["last_loss"] < stats["first_loss"]


def test_serve_driver_batched_requests():
    from repro.launch.serve import main
    stats = main(["--preset", "tiny", "--requests", "4", "--max-new", "8"])
    assert stats["tok_per_s"] > 0


# ------------------------------------------------- mesh axes + compile cache

def test_sort_mesh_axes_are_auto():
    """Explicit axes would type sharded engine outputs by their mesh
    axes, and the winner gather on them would raise."""
    from repro.launch.mesh import make_sort_mesh
    mesh = make_sort_mesh(1)
    assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
    sharded = jax.device_put(jnp.arange(8).reshape(4, 2),
                             jax.sharding.NamedSharding(mesh, P("data")))
    np.testing.assert_array_equal(np.asarray(sharded[jnp.array([3, 1])]),
                                  [[6, 7], [2, 3]])


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_enable_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    """The env var wins and nothing is set; otherwise the fixed path in
    the checkout."""
    import os
    from repro.launch import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_dir is None:
            monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
            got = compile_cache.enable_compile_cache()
            root = os.path.dirname(os.path.dirname(os.path.abspath(
                __file__)))
            assert got == os.path.join(root, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            want = str(tmp_path / env_dir)
            monkeypatch.setenv(compile_cache.CACHE_ENV, want)
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_counter_counts_new_shapes_only():
    """One compile for a new shape, none for the same call again; the
    same counter from every call."""
    import time
    from repro.launch.compile_cache import compile_counter
    counter = compile_counter()
    assert compile_counter() is counter

    @jax.jit
    def f(x):
        return x * 3 + 1

    t0 = time.perf_counter()
    n0, s0 = len(counter.ends), counter.compile_s
    f(np.ones(7, np.float32)).block_until_ready()
    assert len(counter.ends) == n0 + 1
    assert counter.compile_s > s0
    t1 = time.perf_counter()
    assert counter.compiles_between(t0, t1) == 1
    f(np.zeros(7, np.float32)).block_until_ready()
    assert len(counter.ends) == n0 + 1
    assert counter.compiles_between(t1, time.perf_counter()) == 0
    f(np.ones(9, np.float32)).block_until_ready()
    assert len(counter.ends) == n0 + 2
