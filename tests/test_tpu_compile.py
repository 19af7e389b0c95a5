"""Compile rehearsals of the SoftSort-apply kernels for a described TPU v5e.

Nothing runs: each case lowers fwd+grad of a kernel tier at a real width
and compiles it with the TPU compiler for a v5e chip that is described,
not attached, so what Mosaic or XLA would refuse on the chip fails here.
The topology is described inside a fixture (never at import), so every
pytest-xdist worker collects the same tests and only the worker given
this file loads the TPU compiler.  The kernel wrappers pick interpret
mode from the default backend, which is the CPU here; each test steers
``repro.kernels.ops._on_tpu`` so the Mosaic path is lowered.
"""
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# (tier, B, N, d, K): the batched engine's paper-scale grid (64x64, d=3),
# a 128x128 grid of 50-wide embeddings on the banded tier, and what the
# checkpoint codec sorts of command-a-plus-05-2026, on blocks the banded
# tier sizes from d: the 4096 x 4096 experts (64x64 grids of 4096-wide
# columns), the 4096 x 16384 attention projections (128x128 grids), and
# the 262144 x 4096 embedding, its columns cut to the widest payload the
# kernels hold (``band_max_width``: 10728 rows in float32).
SHAPES = [("dense", 4, 4096, 3, None), ("banded", 1, 16384, 50, 1024),
          ("banded", 1, 4096, 4096, 256), ("banded", 1, 16384, 4096, 1024),
          ("banded", 1, 4096, 10728, 256)]

# Each tier's four kernels by the names the device trace shows them under
# (bench/phases.py reads forward and backward kernel time by these).
KERNEL_NAMES = {
    "dense": ("softsort_dense_fwd", "softsort_dense_colsum",
              "softsort_dense_bwd_delta", "softsort_dense_bwd_dx"),
    "banded": ("softsort_band_fwd", "softsort_band_colsum",
               "softsort_band_bwd_delta", "softsort_band_bwd_dcol"),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        # Without it the TPU compiler writes its logs outside the tree.
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tier,b,n,d,k", SHAPES)
def test_kernel_fwd_grad_compiles_for_v5e(one_chip, monkeypatch,
                                          tier, b, n, d, k, dtype):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    if tier == "dense":
        def apply(w, x):
            return ops.softsort_apply(w, x, 0.5, compute_dtype=dtype)
    else:
        def apply(w, x):
            return ops.softsort_apply_banded(w, x, 0.5, k,
                                             compute_dtype=dtype)

    def loss(w, x):
        y, c = apply(w, x)
        return jnp.sum(y) + jnp.sum(c)

    w = jax.ShapeDtypeStruct((b, n), jnp.float32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((b, n, d), jnp.float32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        w, x).compile()
    # Four Mosaic kernels: the forward sweep and its colsum pass, and the
    # two backward passes — the whole train step on the kernel tier.
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    # Each by its own name, inside JAX's transform prefixes
    # (jvp_softsort_band_fwd_, transpose_jvp_softsort_band_bwd_delta__).
    kernels = [line.split(" = ", 1)[0] for line in text.splitlines()
               if "tpu_custom_call" in line]
    found = sorted(name for name in KERNEL_NAMES[tier] for op in kernels
                   if re.search(name + r"(?![a-z])", op))
    assert found == sorted(KERNEL_NAMES[tier]), kernels


def test_banded_permutes_are_sorts_on_v5e(one_chip, monkeypatch):
    """fwd+grad of the banded apply at photo_grid's shapes (B 8 under the
    engines' vmap, N 1024, d 50, K 64), and at B 2, N 256, differentiated
    in the keys as the engines do: every N-length array moves between
    column and rank order inside a sort, so the optimized program's one
    gather is the (N, d) payload's and it holds no scatter."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    d, k = 50, 64

    def loss(w, x):
        y, c = jax.vmap(
            lambda w, x: ops.softsort_apply_banded(w, x, 0.5, k))(w, x)
        return jnp.sum(y) + jnp.sum(c * c)

    for b, n in ((8, 1024), (2, 256)):
        w = jax.ShapeDtypeStruct((b, n), jnp.float32, sharding=one_chip)
        x = jax.ShapeDtypeStruct((b, n, d), jnp.float32, sharding=one_chip)
        text = jax.jit(jax.grad(loss)).lower(w, x).compile().as_text()
        moves = [(shape.split("{")[0], op) for shape, op in
                 re.findall(r"= (\S+) (gather|scatter)\(", text)]
        assert moves == [(f"f32[{b},{n},{d}]", "gather")], (b, n, moves)
        assert re.search(r" sort\(", text), (b, n)
