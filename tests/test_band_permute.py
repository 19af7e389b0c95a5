"""The banded kernel apply moves its N-length arrays between column and
rank order with ``lax.sort``.  It must give exactly what a plain
``take_along_axis`` / ``.at[]`` wrapper around the same Pallas kernels
gives: y, colsum and every gradient, bit for bit (only the sign of a
zero may differ, which ``==`` ignores).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.softsort_apply import (
    softsort_apply_bwd_banded_pallas,
    softsort_apply_fwd_banded_pallas,
)

BAND, BLOCK, D, TAU = 16, 128, 5, 0.7


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def gather_apply(w, x, tau, band, block):
    """One instance, (N,) keys and (N, d) payload, permuted by gathers
    and scatters through ``jnp.argsort``'s permutation."""
    return _gather_fwd(w, x, tau, band, block)[0]


def _gather_fwd(w, x, tau, band, block):
    n, d = x.shape
    blk, np_, dsub = ops._band_geometry(n, d, block)
    perm = jnp.argsort(w)
    ws_p = jnp.pad(w[perm], (0, np_ - n))
    wr, wc = ws_p.reshape(1, 1, np_), ws_p.reshape(1, np_, 1)
    xt = jnp.pad(x[perm], ((0, np_ - n), (0, dsub - d))).T[None]
    y_t, c_s, m, l = softsort_apply_fwd_banded_pallas(
        wr, wc, xt, jnp.reshape(tau, (1, 1)), n=n, k=band, blk=blk,
        interpret=True)
    y = y_t[0, :d, :n].T
    c = jnp.zeros(n, jnp.float32).at[perm].set(c_s[0, :n, 0])
    return (y, c), (tau, perm, wr, wc, xt, m, l, y)


def _gather_bwd(band, block, res, cot):
    tau, perm, wr, wc, xt, m, l, y = res
    dy, dc = cot
    n, d = dy.shape
    blk, np_, dsub = ops._band_geometry(n, d, block)

    def to_t(a):
        return jnp.pad(a, ((0, np_ - n), (0, dsub - d))).T[None]

    dc_p = jnp.pad(dc[perm], (0, np_ - n)).reshape(1, np_, 1)
    dws_row, dws_col, dxt, dtau_cols = softsort_apply_bwd_banded_pallas(
        wr, wc, xt, jnp.reshape(tau, (1, 1)), m, l, to_t(y), to_t(dy), dc_p,
        n=n, k=band, blk=blk, interpret=True)
    dw = jnp.zeros(n, jnp.float32).at[perm].add(
        dws_row[0, 0, :n] + dws_col[0, :n, 0])
    dx = jnp.zeros((n, d), jnp.float32).at[perm].add(dxt[0, :d, :n].T)
    return dw, dx, jnp.sum(dtau_cols)


gather_apply.defvjp(lambda w, x, tau, band, block: _gather_fwd(
    w, x, tau, band, block), _gather_bwd)


def _apply_and_pullback(apply, w, x, tau, dy, dc):
    """y, c and (dw, dx, dtau) of ``apply`` vmapped over instances, as
    the engines call it (tau shared)."""
    fn = jax.vmap(apply, in_axes=(0, 0, None))
    (y, c), pull = jax.vjp(fn, w, x, tau)
    return (y, c) + pull((dy, dc))


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("n", [515, 1024])
def test_banded_permutes_equal_gather_scatter(n, b):
    kw, kx, ky, kc = jax.random.split(jax.random.PRNGKey(n + b), 4)
    # Keys on a coarse lattice: about four per value, so most keys tie
    # exactly and the stable order decides the permutation.
    w = jnp.floor(jax.random.uniform(kw, (b, n)) * n / 4) - n / 8
    x = jax.random.normal(kx, (b, n, D))
    dy = jax.random.normal(ky, (b, n, D))
    dc = jax.random.normal(kc, (b, n))
    tau = jnp.float32(TAU)
    got = jax.jit(functools.partial(
        _apply_and_pullback,
        lambda w, x, t: ops.softsort_apply_banded(w, x, t, BAND,
                                                  block=BLOCK)))(
        w, x, tau, dy, dc)
    want = jax.jit(functools.partial(
        _apply_and_pullback,
        lambda w, x, t: gather_apply(w, x, t, BAND, BLOCK)))(
        w, x, tau, dy, dc)
    assert len(np.unique(np.asarray(w[0]))) < n // 2       # ties present
    for name, g, r in zip(("y", "c", "dw", "dx", "dtau"), got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r),
                                      err_msg=name)
