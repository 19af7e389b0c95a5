"""Plain reference of one ShuffleSoftSort outer round, in float32 jnp.

It follows the paper's Algorithm 1 (arXiv:2503.13051) and imports
nothing of the program under test.  Given a problem ``x`` (N, d), the
order ``order`` the round starts from and the round's PRNG key, it
draws the round's shuffle, runs the inner Adam steps on the N keys
``w`` (re-initialised to 0..N-1) against the grid loss of eq. 2, and
commits the hard permutation through the shuffle.  Its SoftSort apply
scores only the pairs whose sorted ranks lie within K of each other
(K = N - 1 scores every pair), evaluated in blocks of rows so that no
N x N array exists, in plain jnp: no kernel, no custom VJP.

``precision`` selects how the apply's matrix products are computed:

* ``"highest"``: float32 products (``Precision.HIGHEST``), the
  configuration's own precision;
* ``"high"``: three bfloat16 passes, hi*hi + hi*lo + lo*hi, the next
  precision below, written out so that it reads the same on every
  backend.  It is the control that the comparison must tell apart.

Everything else (scores, softmax, loss, Adam) is float32 elementwise.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Schedule, band and normalisation, as the configuration defines them.
# ---------------------------------------------------------------------------

def tau_schedule(sort: dict) -> np.ndarray:
    """Outer-round temperatures: geometric from tau_start to tau_end."""
    r = int(sort["rounds"])
    return np.float32(sort["tau_start"]
                      * (sort["tau_end"] / sort["tau_start"])
                      ** (np.arange(1, r + 1) / r))


def band_width(sort: dict, n: int) -> int:
    """Half-width K of the scored band in rank space; N - 1 when every
    pair is scored.  ``band="auto"`` keeps the tail mass outside the
    band under ``band_eps`` at tau_end, with a floor of N/16 rounded up
    to 64."""
    band = sort.get("band")
    if band is None:
        return n - 1
    if band == "auto":
        eps = max(sort["band_eps"], 1e-30)
        safety = int(np.ceil(2.0 * sort["tau_end"] * np.log(max(n, 2) / eps)))
        floor = -(-max(n // 16, 1) // 64) * 64
        k = max(64, safety, floor)
    else:
        k = int(band)
    return n - 1 if k >= n - 1 else max(1, k)


def banded_from_round(sort: dict, n: int, seg_len: int = 1) -> int:
    """First round that scores only the band: the first whose modelled
    tail mass (N - K) exp(-(K/2)/tau_r) is under band_eps, rounded up
    to a multiple of ``seg_len`` (a scheduler that preempts at segment
    boundaries cannot switch inside one).  Earlier rounds score every
    pair."""
    k = band_width(sort, n)
    r = int(sort["rounds"])
    if k >= n - 1:
        return r
    ok = (n - k) * np.exp(-(k / 2.0) / tau_schedule(sort)) <= sort["band_eps"]
    idx = np.flatnonzero(ok)
    first = int(idx[0]) if idx.size else r
    return min(-(-first // seg_len) * seg_len, r)


def mean_pair_distance(x) -> jnp.ndarray:
    """Loss normalisation: mean distance over pairs, exact up to N = 2048
    (every ordered pair, the diagonal's sqrt(1e-12) included, over
    N (N - 1)), else over 2048 pairs drawn with key 0."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[0]
    if n * n <= 4_194_304:
        def row(xi):
            return jnp.sum(jnp.sqrt(jnp.sum(jnp.square(xi - x), -1) + 1e-12))
        return jnp.sum(jax.lax.map(row, x)) / (n * (n - 1))
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    i = jax.random.randint(k1, (2048,), 0, n)
    j = jax.random.randint(k2, (2048,), 0, n)
    return jnp.mean(jnp.sqrt(jnp.sum(jnp.square(x[i] - x[j]), -1) + 1e-12))


def round_keys(key, rounds: int) -> np.ndarray:
    """The per-round subkeys of one instance: each round splits the
    chained key into (next key, this round's key)."""
    def step(k, _):
        k2 = jax.random.split(k)
        return k2[0], k2[1]
    _, subs = jax.lax.scan(step, jnp.asarray(key, jnp.uint32), None,
                           length=rounds)
    return np.asarray(subs)


# ---------------------------------------------------------------------------
# The apply: banded SoftSort in row blocks.
# ---------------------------------------------------------------------------

def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


@jax.custom_vjp
def _dot_bf16x3(a, b):
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)
    dot = functools.partial(jnp.dot, precision=HIGHEST)
    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _dot_bf16x3_fwd(a, b):
    return _dot_bf16x3(a, b), (a, b)


def _dot_bf16x3_bwd(res, g):
    a, b = res
    return _dot_bf16x3(g, b.T), _dot_bf16x3(a.T, g)


_dot_bf16x3.defvjp(_dot_bf16x3_fwd, _dot_bf16x3_bwd)


def _matmul(precision: str):
    if precision == "highest":
        return functools.partial(jnp.dot, precision=HIGHEST)
    if precision == "high":
        return _dot_bf16x3
    raise ValueError(f"precision {precision!r}")


def softsort_apply(w, x, tau, k: int, precision: str = "highest",
                   block: int = 512):
    """(P @ x, colsum(P)) with P = softmax_rows(-|sort(w)_i - w_j| / tau)
    over the pairs whose ranks differ by at most ``k``.  Rows and
    columns are both taken in sorted order; the column sums go back to
    the original columns."""
    n, d = x.shape
    k = min(k, n - 1)
    blk = min(block, n)
    nb = -(-n // blk)
    width = blk + 2 * k
    perm = jnp.argsort(jax.lax.stop_gradient(w))
    ws = w[perm]
    xs = x[perm]
    pad_hi = nb * blk - n + k
    ws_p = jnp.pad(ws, (k, pad_hi))
    xs_p = jnp.pad(xs, ((k, pad_hi), (0, 0)))
    rank_p = jnp.arange(-k, n + pad_hi)
    mm = _matmul(precision)

    @jax.checkpoint
    def body(colsum, i):
        r0 = i * blk
        rows = r0 + jnp.arange(blk)
        wr = jax.lax.dynamic_slice(ws_p, (r0 + k,), (blk,))
        wc = jax.lax.dynamic_slice(ws_p, (r0,), (width,))
        xc = jax.lax.dynamic_slice(xs_p, (r0, 0), (width, d))
        cr = jax.lax.dynamic_slice(rank_p, (r0,), (width,))
        valid = ((jnp.abs(cr[None, :] - rows[:, None]) <= k)
                 & (cr[None, :] >= 0) & (cr[None, :] < n)
                 & (rows[:, None] < n))
        s = jnp.where(valid, -jnp.abs(wr[:, None] - wc[None, :]) / tau,
                      -jnp.inf)
        m = jnp.max(s, axis=1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.where(valid, jnp.exp(s - m), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=1, keepdims=True), 1e-30)
        y = mm(p, xc)
        part = jax.lax.dynamic_slice(colsum, (r0,), (width,)) + p.sum(0)
        colsum = jax.lax.dynamic_update_slice(colsum, part, (r0,))
        return colsum, y

    colsum_p, ys = jax.lax.scan(body, jnp.zeros(nb * blk + 2 * k,
                                                jnp.float32),
                                jnp.arange(nb))
    y = ys.reshape(nb * blk, d)[:n]
    c_sorted = colsum_p[k:k + n]
    c = jnp.zeros(n, jnp.float32).at[perm].set(c_sorted)
    return y, c


# ---------------------------------------------------------------------------
# Loss (eq. 2) and one outer round.
# ---------------------------------------------------------------------------

def grid_loss(y, colsum, x, hw, norm, lambda_s, lambda_sigma):
    """Neighbour term + lambda_s * colsum constraint (eq. 3) +
    lambda_sigma * spread term (eq. 4)."""
    h, w = hw
    g = y.reshape(h, w, -1)
    dh = jnp.sqrt(jnp.sum(jnp.square(g[:, 1:] - g[:, :-1]), -1) + 1e-12)
    dv = jnp.sqrt(jnp.sum(jnp.square(g[1:, :] - g[:-1, :]), -1) + 1e-12)
    nbr = (dh.mean() + dv.mean()) / (2.0 * norm)
    stoch = jnp.mean(jnp.square(colsum - 1.0))
    sx = jnp.std(x, axis=0)
    sy = jnp.std(y, axis=0)
    spread = jnp.mean(jnp.abs(sx - sy) / (sx + 1e-12))
    return nbr + lambda_s * stoch + lambda_sigma * spread


def outer_round(x, order, sub, tau_r, norm, *, hw, sort: dict, k: int,
                precision: str = "highest"):
    """One round from ``order`` with this round's key ``sub``.  Returns
    (order after the round, loss of the last inner step)."""
    n = x.shape[0]
    shuf = jax.random.permutation(sub, n)
    inv = jnp.argsort(shuf)
    x_shuf = x[order][shuf]
    steps = int(sort["inner_steps"])
    b1, b2, lr = sort["b1"], sort["b2"], sort["lr"]
    ramp = sort["inner_tau_ramp"]

    def loss_fn(w, tau):
        y_shuf, colsum = softsort_apply(w, x_shuf, tau, k, precision)
        return grid_loss(y_shuf[inv], colsum, x_shuf, hw, norm,
                         sort["lambda_s"], sort["lambda_sigma"])

    def inner(i, carry):
        w, mu, nu, _ = carry
        frac = i.astype(jnp.float32) / max(steps - 1, 1)
        tau_i = tau_r * (ramp + (1.0 - ramp) * frac)
        loss, g = jax.value_and_grad(loss_fn)(w, tau_i)
        t = i.astype(jnp.float32) + 1.0
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        step = lr * (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t))
                                            + 1e-8)
        return w - step, mu, nu, loss

    w0 = jnp.arange(n, dtype=jnp.float32)
    w, _, _, loss = jax.lax.fori_loop(
        0, steps, inner,
        (w0, jnp.zeros_like(w0), jnp.zeros_like(w0), jnp.float32(0.0)))
    sort_idx = jnp.argsort(w)
    g = jnp.zeros(n, jnp.int32).at[shuf].set(shuf[sort_idx])
    return order[g], loss


@functools.partial(jax.jit, static_argnames=("hw", "sort_items", "k",
                                             "precision"))
def _rounds(x, order, subs, taus, norm, *, hw, sort_items, k, precision):
    sort = dict(sort_items)

    def step(o, st):
        sub, tau = st
        o, loss = outer_round(x, o, sub, tau, norm, hw=hw, sort=sort, k=k,
                              precision=precision)
        return o, (o, loss)

    with jax.default_matmul_precision("highest"):
        _, (orders, losses) = jax.lax.scan(step, order, (subs, taus))
    return orders, losses


class Reference:
    """The reference for one configuration: ``rounds(x, order, key,
    start, count)`` replays ``count`` rounds from global round ``start``
    given the chained key the program held at that round."""

    def __init__(self, sort: dict, precision: str = "highest",
                 seg_len: int = 1):
        self.sort = dict(sort)
        self.precision = precision
        self.seg_len = int(seg_len)
        self.taus = tau_schedule(self.sort)
        self._items = tuple(sorted(
            (a, b) for a, b in self.sort.items()
            if isinstance(b, (int, float, str)) or b is None))

    def rounds(self, x, order, key, start: int, count: int, norm=None):
        """Returns (orders after each round (count, N), losses (count,))."""
        x = jnp.asarray(x, jnp.float32)
        n, d = x.shape
        side = int(round(n ** 0.5))
        hw = (side, n // side)
        if norm is None:
            norm = mean_pair_distance(x)
        subs = round_keys(key, count)
        taus = jnp.asarray(self.taus[start:start + count])
        switch = banded_from_round(self.sort, n, self.seg_len)
        k_band = band_width(self.sort, n)
        out_o, out_l = [], []
        # Rounds before the switch score every pair, after it the band.
        for lo, hi, k in ((start, min(switch, start + count), n - 1),
                          (max(switch, start), start + count, k_band)):
            if hi <= lo:
                continue
            o, l = _rounds(x, jnp.asarray(order, jnp.int32),
                           jnp.asarray(subs[lo - start:hi - start]),
                           taus[lo - start:hi - start], norm, hw=hw,
                           sort_items=self._items, k=k,
                           precision=self.precision)
            order = o[-1]
            out_o.append(np.asarray(o))
            out_l.append(np.asarray(l))
        return np.concatenate(out_o), np.concatenate(out_l)
