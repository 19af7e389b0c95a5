"""Banded SoftSort-apply tier tests — hypothesis-free on purpose.

Covers the three layers the banded tier ships at:

  * the windowed pure-jnp oracle ``core.softsort.softsort_apply_banded``
    vs the dense matrix (within the analytic ``band_tail_bound``) and
    the bound itself as a true upper bound on dropped mass;
  * the band-grid Pallas kernels ``kernels.ops.softsort_apply_banded``
    vs the oracle — EXACT parity (same truncated math), forward and
    gradients including the dtau cotangent, uneven N/d, B > 1, and the
    band >= N-1 fallback onto the fused dense path;
  * the tau-adaptive dispatcher: switch-round model boundary, engine
    bit-identity (sequential vs batched) across a mid-schedule
    dense->banded switch, on both the jnp and kernel tiers.

Also hosts the ``descending`` parity tests for every apply
implementation (the flag the chunked path was missing).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.softsort import (
    band_tail_bound,
    is_valid_permutation,
    softsort_apply_banded,
    softsort_apply_chunked,
    softsort_matrix,
)
from repro.core.shufflesoftsort import (
    ShuffleSoftSortConfig,
    _band_switch_round,
    resolve_band,
    shuffle_soft_sort,
    shuffle_soft_sort_batched,
)
from repro.kernels.ops import softsort_apply
from repro.kernels.ops import softsort_apply_banded as kernel_banded
from repro.kernels.ref import softsort_apply_ref


def _arange_keys(key, n, bsz=None):
    """Shuffled arange — the trainer's per-round linear init, the
    operating regime the band targets (unit rank gaps, tiny tail)."""
    if bsz is None:
        return jax.random.permutation(key, jnp.arange(n, dtype=jnp.float32))
    return jax.vmap(lambda k: jax.random.permutation(
        k, jnp.arange(n, dtype=jnp.float32)))(jax.random.split(key, bsz))


def _loss_of(apply_fn, a, b):
    def f(w, x, tau):
        y, c = apply_fn(w, x, tau)
        return jnp.sum(y * a) + jnp.sum(c * b)
    return f


def _assert_close(got, want, rtol=1e-4):
    scale = float(jnp.max(jnp.abs(want))) + 1e-9
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=rtol * scale)


# ------------------------------------------------ oracle vs dense + bound

@pytest.mark.parametrize("n,d,k,tau", [(100, 3, 16, 0.5), (300, 7, 40, 0.5),
                                       (129, 5, 8, 0.3), (64, 2, 63, 1.0)])
def test_banded_oracle_within_tail_bound_of_dense(n, d, k, tau):
    w = _arange_keys(jax.random.PRNGKey(n + k), n)
    x = jax.random.normal(jax.random.PRNGKey(n + 1), (n, d))
    y_ref, c_ref = softsort_apply_ref(w, x, tau)
    y, c = softsort_apply_banded(w, x, tau, k)
    bound = float(band_tail_bound(w, tau, k))
    # Each row drops <= bound probability mass; y rows are convex-ish
    # combinations of payload rows, so the output error is bounded by
    # (dropped + renormalization) * payload scale ~ 2 * bound * max|x|.
    slack = 2.0 * bound * float(jnp.max(jnp.abs(x))) + 5e-6
    assert float(jnp.max(jnp.abs(y - y_ref))) <= slack
    assert float(jnp.max(jnp.abs(c - c_ref))) <= 2.0 * bound + 5e-6


def test_band_tail_bound_upper_bounds_dropped_mass():
    """The analytic bound must dominate the actually dropped mass on
    arbitrary (non-arange) keys, including hot taus where it is loose."""
    for seed, tau, k in [(0, 1.3, 6), (1, 0.4, 6), (2, 2.5, 12),
                         (3, 0.1, 3), (4, 0.7, 20)]:
        n = 80
        w = jax.random.normal(jax.random.PRNGKey(seed), (n,)) * 2
        perm = jnp.argsort(w)
        ps = softsort_matrix(w, tau)[:, perm]      # columns in rank order
        ii = jnp.arange(n)
        out_of_band = jnp.abs(ii[:, None] - ii[None, :]) > k
        dropped = float(jnp.max(
            jnp.sum(jnp.where(out_of_band, ps, 0.0), axis=1)))
        bound = float(band_tail_bound(w, tau, k))
        assert dropped <= bound + 1e-6, (seed, tau, k, dropped, bound)


def test_band_tail_bound_batched_and_degenerate():
    w = _arange_keys(jax.random.PRNGKey(0), 50, bsz=3)
    b = band_tail_bound(w, 0.5, 8)
    assert b.shape == (3,) and bool(jnp.all(b >= 0))
    assert float(jnp.max(band_tail_bound(w, 0.5, 49))) == 0.0


# ------------------------------------------- kernel vs oracle (exact)

# d 1800 is wide enough that the block rule halves 256 to 128.
@pytest.mark.parametrize("bsz,n,d,k", [(1, 300, 7, 40), (1, 129, 17, 16),
                                       (3, 100, 2, 8), (2, 260, 5, 96),
                                       (1, 256, 1800, 64)])
def test_banded_kernel_forward_matches_oracle(bsz, n, d, k):
    keys = jax.random.split(jax.random.PRNGKey(n * 13 + d + k), 2)
    w = _arange_keys(keys[0], n, bsz=bsz)
    x = jax.random.normal(keys[1], (bsz, n, d))
    if bsz == 1:
        w, x = w[0], x[0]
    yk, ck = kernel_banded(w, x, 0.5, k)
    yo, co = softsort_apply_banded(w, x, 0.5, k)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yo), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(co), atol=2e-5)


@pytest.mark.parametrize("n,d,k", [(200, 5, 24), (129, 17, 16), (300, 2, 100),
                                   (256, 1800, 64)])
def test_banded_kernel_gradients_match_oracle(n, d, k):
    """dw, dx AND dtau — the full cotangent surface, uneven N and d."""
    keys = jax.random.split(jax.random.PRNGKey(n + d + k), 4)
    w = _arange_keys(keys[0], n)
    x = jax.random.normal(keys[1], (n, d))
    a = jax.random.normal(keys[2], (n, d))
    b = jax.random.normal(keys[3], (n,))
    gk = jax.grad(_loss_of(lambda w, x, t: kernel_banded(w, x, t, k), a, b),
                  argnums=(0, 1, 2))(w, x, jnp.float32(0.6))
    go = jax.grad(_loss_of(
        lambda w, x, t: softsort_apply_banded(w, x, t, k), a, b),
        argnums=(0, 1, 2))(w, x, jnp.float32(0.6))
    for g1, g2 in zip(gk, go):
        _assert_close(g1, g2)


def test_band_block_rule_sized_from_d():
    """The banded kernels' block edge comes from the payload width: the
    photo-grid cell's d 50 keeps the 256 block and Mosaic's default
    VMEM limit, a d-4096 payload takes 128 and a limit above its working
    set, and an edge never drops below the lane width."""
    from repro.kernels import ops
    from repro.kernels.softsort_apply import (
        SCOPED_VMEM_DEFAULT,
        _band_compiler_params,
        band_block,
        band_vmem_bytes,
    )
    assert ops._band_geometry(1024, 50, 256) == (256, 1024, 56)
    assert _band_compiler_params(56, 256, jnp.float32) is None
    assert ops._band_geometry(4096, 4096, 256) == (128, 4096, 4096)
    assert ops._band_geometry(4096, 4096, 256, 2)[0] == 128
    assert ops._band_geometry(256, 1800, 256)[0] == 128
    assert ops._band_geometry(256, 1600, 256)[0] == 256
    assert band_block(512, 65536, 4) == 128
    need = band_vmem_bytes(4096, 128, 4)
    assert need > SCOPED_VMEM_DEFAULT
    params = _band_compiler_params(4096, 128, jnp.float32)
    assert params.vmem_limit_bytes > need


def test_banded_kernel_batched_gradients_match_per_instance():
    bsz, n, d, k = 3, 100, 4, 12
    keys = jax.random.split(jax.random.PRNGKey(9), 4)
    w = _arange_keys(keys[0], n, bsz=bsz)
    x = jax.random.normal(keys[1], (bsz, n, d))
    a = jax.random.normal(keys[2], (bsz, n, d))
    b = jax.random.normal(keys[3], (bsz, n))
    tau = jnp.float32(0.7)
    dw, dx, dtau = jax.grad(
        _loss_of(lambda w, x, t: kernel_banded(w, x, t, k), a, b),
        argnums=(0, 1, 2))(w, x, tau)
    dtau_sum = 0.0
    for bi in range(bsz):
        dwi, dxi, dti = jax.grad(
            _loss_of(lambda w, x, t: softsort_apply_banded(w, x, t, k),
                     a[bi], b[bi]),
            argnums=(0, 1, 2))(w[bi], x[bi], tau)
        _assert_close(dw[bi], dwi)
        _assert_close(dx[bi], dxi)
        dtau_sum += float(dti)
    np.testing.assert_allclose(float(dtau), dtau_sum,
                               atol=1e-4 * (abs(dtau_sum) + 1e-9))


def test_banded_kernel_colsum_cotangent_only():
    """dc alone (dy = 0) exercises the P~ @ dc~ term of the delta pass."""
    n, d, k = 200, 3, 16
    w = _arange_keys(jax.random.PRNGKey(21), n)
    x = jax.random.normal(jax.random.PRNGKey(22), (n, d))
    b = jax.random.normal(jax.random.PRNGKey(23), (n,))

    def loss(fn):
        def f(w, tau):
            _, c = fn(w, x, tau, k)
            return jnp.sum(jnp.square(c) * b)
        return f

    gk = jax.grad(loss(kernel_banded), argnums=(0, 1))(w, jnp.float32(0.4))
    go = jax.grad(loss(softsort_apply_banded), argnums=(0, 1))(
        w, jnp.float32(0.4))
    for g1, g2 in zip(gk, go):
        _assert_close(g1, g2)


def test_banded_fallback_band_covers_everything():
    """band >= N - 1 must be the exact fused dense result."""
    n, d = 96, 4
    w = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 2
    x = jax.random.normal(jax.random.PRNGKey(2), (n, d))
    yk, ck = kernel_banded(w, x, 0.5, n - 1)
    yd, cd = softsort_apply(w, x, 0.5)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yd))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cd))


# ---------------------------------------------------- descending parity

@pytest.mark.parametrize("impl", ["chunked", "fused", "banded_jnp",
                                  "banded_kernel"])
def test_descending_matches_dense_matrix(impl):
    n, d, tau = 100, 3, 0.6
    w = _arange_keys(jax.random.PRNGKey(11), n)
    x = jax.random.normal(jax.random.PRNGKey(12), (n, d))
    p = softsort_matrix(w, tau, descending=True)
    y_ref, c_ref = p @ x, p.sum(0)
    fn = {
        "chunked": lambda: softsort_apply_chunked(w, x, tau, 32,
                                                  descending=True),
        "fused": lambda: softsort_apply(w, x, tau, descending=True),
        "banded_jnp": lambda: softsort_apply_banded(w, x, tau, 24,
                                                    descending=True),
        "banded_kernel": lambda: kernel_banded(w, x, tau, 24,
                                               descending=True),
    }[impl]
    y, c = fn()
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), atol=2e-5)


def test_descending_batched_chunked():
    w = jax.random.normal(jax.random.PRNGKey(7), (2, 33))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 33, 4))
    y, c = softsort_apply_chunked(w, x, 0.5, 16, descending=True)
    pm = jax.vmap(lambda wi: softsort_matrix(wi, 0.5, descending=True))(w)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(jnp.einsum("bij,bjd->bid", pm, x)),
        atol=2e-5)
    np.testing.assert_allclose(np.asarray(c), np.asarray(pm.sum(1)),
                               atol=2e-5)


# ------------------------------------------------- dispatcher + engines

def test_band_switch_round_boundary():
    """The switch model: hot start -> dense prefix; geometric anneal is
    monotone so every round past the switch also qualifies."""
    n = 64
    cfg = ShuffleSoftSortConfig(rounds=12, inner_steps=2, tau_start=60.0,
                                tau_end=0.2, band=8)
    sw = _band_switch_round(cfg, n)
    assert 0 < sw < cfg.rounds
    k = resolve_band(cfg, n)
    taus = cfg.tau_start * (cfg.tau_end / cfg.tau_start) ** (
        np.arange(1, cfg.rounds + 1) / cfg.rounds)
    model = (n - k) * np.exp(-(k / 2.0) / taus)
    assert np.all(model[sw:] <= cfg.band_eps)
    assert model[sw - 1] > cfg.band_eps
    # Default (cold) schedule: banded from round 0; band=None: never.
    assert _band_switch_round(
        ShuffleSoftSortConfig(rounds=10, band=16), n) < 10
    cfg_none = ShuffleSoftSortConfig(rounds=10)
    assert resolve_band(cfg_none, n) is None
    assert _band_switch_round(cfg_none, n) == cfg_none.rounds


def test_resolve_band_auto_scales_with_n():
    cfg = ShuffleSoftSortConfig(band="auto")
    assert resolve_band(cfg, 4096) == 256          # N/16 floor
    assert resolve_band(cfg, 1024) == 64
    # Degenerate bands (K would cover every pair) resolve to the exact
    # dense path — same math, none of the windowed-gather overhead.
    assert resolve_band(cfg, 64) is None
    assert resolve_band(ShuffleSoftSortConfig(band=32), 1000) == 32
    assert resolve_band(ShuffleSoftSortConfig(band=2000), 100) is None
    # "auto" sizes from tau_end, so a hot tau_start inflates the DENSE
    # PREFIX (dispatcher), not K itself.
    hot = ShuffleSoftSortConfig(band="auto", tau_start=60.0, rounds=50)
    assert resolve_band(hot, 1024) == 64
    assert 0 < _band_switch_round(hot, 1024) < hot.rounds


@pytest.mark.parametrize("cfg", [
    # mid-schedule dense->banded switch, jnp tier
    ShuffleSoftSortConfig(rounds=8, inner_steps=2, chunk=32, tau_start=60.0,
                          tau_end=0.2, band=8),
    # banded from round 0, jnp tier ("auto" at this tiny N resolves to
    # dense, so the whole-schedule-banded case pins an explicit K)
    ShuffleSoftSortConfig(rounds=6, inner_steps=2, chunk=32, band=30),
    # kernel tier with a mid-schedule switch
    ShuffleSoftSortConfig(rounds=6, inner_steps=2, tau_start=60.0,
                          tau_end=0.2, band=12, use_kernel=True),
], ids=["switch-jnp", "full-band-jnp", "switch-kernel"])
def test_batched_band_bit_identical_to_sequential(cfg):
    """The banded dispatcher must keep the engine contract: batched ==
    sequential per seed, with both agreeing round-by-round on which
    apply ran (the segmented scan vs the per-round Python loop)."""
    b, s, n, hw = 2, 2, 64, (8, 8)
    xs = jax.random.uniform(jax.random.PRNGKey(42), (b, n, 2))
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(b * s)])
    res = shuffle_soft_sort_batched(xs, hw, cfg, n_restarts=s, keys=keys)
    for bi in range(b):
        for si in range(s):
            o, _, losses = shuffle_soft_sort(xs[bi], hw, cfg,
                                             key=keys[bi * s + si])
            np.testing.assert_array_equal(res.all_orders[bi, si], o)
            np.testing.assert_array_equal(res.all_losses[bi, si],
                                          np.asarray(losses))
        assert is_valid_permutation(res.order[bi])


def test_band_auto_loss_close_to_dense():
    """band="auto" must not cost quality: final loss within 1% of the
    dense path on the same seeds (acceptance bar; the full-size run is
    recorded in EXPERIMENTS.md §Perf)."""
    n, hw = 256, (16, 16)
    xs = jax.random.uniform(jax.random.PRNGKey(3), (2, n, 3))
    base = dict(rounds=30, inner_steps=4, chunk=64)
    dense = shuffle_soft_sort_batched(
        xs, hw, ShuffleSoftSortConfig(**base), key=jax.random.PRNGKey(1))
    banded = shuffle_soft_sort_batched(
        xs, hw, ShuffleSoftSortConfig(band="auto", **base),
        key=jax.random.PRNGKey(1))
    l_dense = float(np.mean(dense.losses[:, -1]))
    l_band = float(np.mean(banded.losses[:, -1]))
    assert abs(l_band - l_dense) <= 0.01 * abs(l_dense) + 1e-6, (
        l_dense, l_band)
