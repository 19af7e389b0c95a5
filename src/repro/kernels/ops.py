"""jit'd public wrappers for the SoftSort-apply kernel tiers.

``softsort_apply(w, x, tau)`` returns ``(P_soft @ x, column_sums(P_soft))``
in O(N * block) memory with a custom VJP that runs BOTH directions in
Pallas.  The forward is one fused online-softmax sweep plus a colsum
reduction (two ``pallas_call``s); it hands ``(perm, m, l, y)`` to
the backward as residuals, so the backward neither re-sorts nor
re-derives the softmax normalizers — it streams TWO Pallas passes
(a fused delta+dws row sweep, then the transposed-grid ``dx = P^T @
dy`` + ``dw``/``dtau`` column reductions) that never materialize a
``(B, chunk, N)`` temporary in HBM.  Exact, but still O(N^2) compute:
every key pair is scored.

Mixed precision (``compute_dtype``): every kernel wrapper accepts
``compute_dtype`` ("float32" default, or "bfloat16").  At bf16 the
payload-sided arrays (x, the dy/dc cotangents, the saved y residual,
and the dx gradient) are cast ONCE here before entering the kernels, so
every payload block fetched from HBM is half the bytes, scores are
rounded to bf16 in-kernel, and every MXU matmul takes bf16 inputs —
while the KEYS stay float32 (they are the paper's N parameters;
quantizing them collapses unit rank gaps into ties above N = 256), as
do the softmax stats, every accumulator (f32 VMEM scratch where the
HBM form is bf16), the (m, l) residuals and the key/tau gradients
(``preferred_element_type=jnp.float32`` everywhere).  The public
forward output and every gradient are returned upcast to the primals'
dtypes, so the trainer's loss and Adam math are untouched f32 whatever
the kernel precision.  Measured parity envelope: EXPERIMENTS.md §Perf.

Block sizes: ``block_rows``/``block_cols``/``block`` default to None,
which consults the committed autotune table
(``repro.kernels.autotune.lookup_blocks`` — per (tier, N, d, K, dtype,
backend) winners from the kernel-bench timing harness) and falls back
to the safe hardcoded 256-square tiling on a miss.  An explicit integer
always wins over the table.

``softsort_apply_banded(w, x, tau, band)`` is the O(N * K) tier on top:
both matrix axes are gathered into sorted-rank order, only the
width-(2K+1) diagonal band is scored (out-of-band mass exactly zero,
analytically bounded by ``core.softsort.band_tail_bound``), and the
payload rides d-on-sublanes so small paper-scale d stops paying the
128-lane pad.  Same custom-VJP structure — band-grid forward sweep +
colsum, two band-grid backward passes over the forward's operands and
its ``(perm, m, l, y)`` residuals — with the key gradient's row and
column components summed and moved back through the saved permutation
by ``lax.sort``.  The engine
dispatcher (``core.shufflesoftsort``) runs dense while tau is hot and
switches to this path once the tail bound clears its epsilon.

See ``repro.kernels.softsort_apply`` for the kernel structure and
EXPERIMENTS.md §Perf for the measured pass-count / HBM traffic wins
(fused-over-v1, and banded-over-fused), which retired the earlier claim
that a hand backward "would add risk without a roofline win": with
residual reuse it is a straight HBM-traffic win.

Shape convention (batched throughput path, used by
``shuffle_soft_sort_batched`` and the serving layer):

  * unbatched — ``w: (N,)``, ``x: (N, d)``  ->  ``y: (N, d)``, ``c: (N,)``
  * batched   — ``w: (B, N)``, ``x: (B, N, d)``  ->  ``y: (B, N, d)``,
    ``c: (B, N)``; every batch instance is an independent SoftSort with
    a shared scalar ``tau``.

Internally everything runs batched: the unbatched call is the B = 1
special case, so there is exactly one kernel code path.  Kernels run
with the batch as the outermost grid dimension (``interpret=True`` on
the CPU backend; other non-TPU backends are refused), which keeps the
whole train step — forward AND backward — on the kernel tier.

``softsort_apply_v1`` preserves the previous design (three forward
passes, chunked ``lax.scan`` jnp backward that re-sorts and re-softmaxes
from scratch) purely as the benchmark baseline for
``benchmarks/kernel_bench.py``; production callers should never use it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.softsort_apply import (
    band_block,
    softsort_apply_bwd_banded_pallas,
    softsort_apply_bwd_pallas,
    softsort_apply_fwd_banded_pallas,
    softsort_apply_fwd_pallas,
    softsort_apply_fwd_pallas_v1,
)

_LANE = 128      # TPU lane width: pad d and pick Bc as multiples
_SUBLANE = 8


def _round_up(v: int, m: int) -> int:
    return (v + m - 1) // m * m


def _on_tpu() -> bool:
    """True on a TPU backend; False on the CPU backend, where the Pallas
    kernels run in interpret mode.  Any other backend raises: these are
    Mosaic TPU kernels, and interpreting them there would hide that the
    kernel tier never ran."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernel tier runs on 'tpu' (or interpreted on "
            f"'cpu'); the default backend is {backend!r}")
    return backend == "tpu"


def _block_geometry(n: int, d: int, block_rows: int, block_cols: int):
    """Resolve (br, bc, padded N, padded d) exactly as the forward does —
    the backward re-derives the same geometry from the same statics, so
    residual shapes always line up."""
    br = min(block_rows, _round_up(n, _SUBLANE))
    bc = min(block_cols, _round_up(n, _LANE))
    np_ = _round_up(n, max(br, bc))
    # Re-derive block sizes that tile the padded length exactly.
    br = min(br, np_)
    bc = min(bc, np_)
    dp = _round_up(d, _LANE)
    return br, bc, np_, dp


def _cd(compute_dtype) -> jnp.dtype:
    """Resolve the compute-dtype knob (a hashable string on the configs
    and custom_vjp statics) to a jnp dtype, validating the choice."""
    dt = jnp.dtype(compute_dtype)
    assert dt in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)), (
        f"compute_dtype must be float32 or bfloat16, got {compute_dtype}")
    return dt


def _pad_operands(wb, xb, n, np_, dp, perm=None, cd=jnp.float32):
    """Pad (B, N)/(B, N, d) operands to kernel tiles.  Pad rows of ws are
    masked out of every reduction in-kernel, pad cols of w are masked via
    the score mask, x pads with zeros.  Pass the forward's saved ``perm``
    to gather the sorted keys without re-running argsort (the backward
    path).  ``cd`` is the kernel compute dtype: the PAYLOAD is cast
    HERE, once, so at bf16 its HBM blocks are half-width; the keys stay
    f32 (see the kernels' precision contract)."""
    bsz = wb.shape[0]
    d = xb.shape[-1]
    pad_n = np_ - n
    if perm is None:
        perm = jnp.argsort(jax.lax.stop_gradient(wb), axis=-1)
    ws = jnp.take_along_axis(wb, perm, axis=-1)
    ws_p = jnp.pad(ws, ((0, 0), (0, pad_n))).reshape(bsz, np_, 1)
    w_p = jnp.pad(wb, ((0, 0), (0, pad_n))).reshape(bsz, 1, np_)
    x_p = jnp.pad(xb.astype(jnp.float32), ((0, 0), (0, pad_n), (0, dp - d)))
    return (perm, ws_p.astype(jnp.float32), w_p.astype(jnp.float32),
            x_p.astype(cd))


def softsort_apply(w, x, tau, block_rows: int | None = None,
                   block_cols: int | None = None,
                   bwd_chunk: int = 256, descending: bool = False,
                   compute_dtype: str = "float32"):
    """Fused (P_soft @ x, colsum(P_soft)); w: (N,) or (B, N), tau scalar.

    ``block_rows``/``block_cols`` default to None = consult the
    committed autotune table for this (N, d, dtype, backend), falling
    back to the safe 256-square tiling on a miss; an explicit int always
    wins.  ``bwd_chunk`` is accepted for API stability but unused: the
    backward is a Pallas kernel tiled by (block_rows, block_cols), not a
    chunked jnp scan.  ``descending`` matches ``softsort_matrix(...,
    descending=True)``: reversing the sorted keys only reverses the row
    order of P, so it is a flip of y (colsum is row-order invariant) —
    applied outside the custom VJP, where autodiff handles it.
    ``compute_dtype`` ("float32"/"bfloat16") selects the kernel score/
    payload precision — see the module docstring's precision contract.
    """
    if block_rows is None or block_cols is None:
        from repro.kernels.autotune import lookup_blocks
        br_t, bc_t = lookup_blocks(
            "fused", n=w.shape[-1], d=x.shape[-1], dtype=compute_dtype)
        block_rows = block_rows or br_t
        block_cols = block_cols or bc_t
    y, c = _softsort_apply_dense(w, x, tau, block_rows, block_cols,
                                 bwd_chunk, compute_dtype)
    if descending:
        y = jnp.flip(y, axis=-2)
    return y, c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _softsort_apply_dense(w, x, tau, block_rows: int = 256,
                          block_cols: int = 256, bwd_chunk: int = 256,
                          compute_dtype: str = "float32"):
    (y, c), _ = _fwd_impl(w, x, tau, block_rows, block_cols, compute_dtype)
    return y, c


def _fwd_impl(w, x, tau, block_rows, block_cols, compute_dtype):
    batched = w.ndim == 2
    wb = w if batched else w[None]
    xb = x if batched else x[None]
    bsz, n = wb.shape
    d = xb.shape[-1]
    assert xb.shape == (bsz, n, d), (w.shape, x.shape)
    br, bc, np_, dp = _block_geometry(n, d, block_rows, block_cols)
    perm, ws_p, w_p, x_p = _pad_operands(wb, xb, n, np_, dp,
                                         cd=_cd(compute_dtype))
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1, 1)

    y_p, c_p, m, l = softsort_apply_fwd_pallas(
        ws_p, w_p, x_p, tau_arr,
        n=n, br=br, bc=bc, interpret=not _on_tpu())
    # The kernel emits y in the compute dtype; the public output is
    # upcast so downstream loss math stays f32, while the residual
    # keeps the compute-dtype copy (half the residual HBM at bf16).
    y, c = y_p[:, :n, :d], c_p[:, 0, :n]
    y_out = y.astype(jnp.float32)
    out = (y_out, c) if batched else (y_out[0], c[0])
    # The y residual is the SLICED (B, N, d) output, not the lane-padded
    # kernel buffer: dp = round_up(d, 128) would inflate residual HBM by
    # dp/d (16x at the paper's d=8); the backward re-pads it with zeros
    # alongside x for the cost of an O(N d) pad.
    return out, (perm, m, l, y)


def _fwd_rule(w, x, tau, block_rows, block_cols, bwd_chunk, compute_dtype):
    out, (perm, m, l, y) = _fwd_impl(w, x, tau, block_rows, block_cols,
                                     compute_dtype)
    # Residuals: primals plus (perm, m, l, y) — everything the backward
    # needs to skip the argsort and the softmax-stats recomputation.
    return out, (w, x, jnp.asarray(tau, jnp.float32), perm, m, l, y)


def _bwd_rule(block_rows, block_cols, bwd_chunk, compute_dtype, res, cot):
    del bwd_chunk                      # legacy knob of the jnp-scan backward
    w, x, tau, perm, m, l, y = res
    dy, dc = cot
    batched = w.ndim == 2
    wb = w if batched else w[None]
    xb = x if batched else x[None]
    yb = y                             # saved in batched (B, N, d) form
    dyb = dy if batched else dy[None]
    dcb = dc if batched else dc[None]
    bsz, n = wb.shape
    d = xb.shape[-1]
    br, bc, np_, dp = _block_geometry(n, d, block_rows, block_cols)
    pad_n = np_ - n

    # Same padded operand layout as the forward (the sorted keys are
    # re-gathered through the SAVED perm — a cheap O(B N) gather, no
    # argsort here); cotangent pads are zero so pad slots contribute
    # nothing to any reduction.  Cotangents ride the compute dtype like
    # the payload; the y residual already does (saved straight from the
    # kernel), while m/l and the gradient accumulators stay f32.
    cd = _cd(compute_dtype)
    _, ws_p, w_p, x_p = _pad_operands(wb, xb, n, np_, dp, perm=perm, cd=cd)
    y_p = jnp.pad(yb, ((0, 0), (0, pad_n), (0, dp - d)))
    dy_p = jnp.pad(dyb.astype(jnp.float32),
                   ((0, 0), (0, pad_n), (0, dp - d))).astype(cd)
    dc_p = jnp.pad(dcb.astype(jnp.float32),
                   ((0, 0), (0, pad_n))).reshape(bsz, 1, np_).astype(cd)
    tau_arr = tau.reshape(1, 1)

    dws, dw_cols, dx_p, dtau_cols = softsort_apply_bwd_pallas(
        ws_p, w_p, x_p, tau_arr,
        m, l, y_p, dy_p, dc_p,
        n=n, br=br, bc=bc, interpret=not _on_tpu())

    dws = dws[:, :n, 0]                                  # (B, N) sorted rows
    dw = dw_cols[:, 0, :n]                               # (B, N) column part
    # Scatter the sorted-row gradient back through the saved permutation.
    dw = dw.at[jnp.arange(bsz)[:, None], perm].add(dws)
    dx = dx_p[:, :n, :d]
    dtau = jnp.sum(dtau_cols)
    if not batched:
        dw, dx = dw[0], dx[0]
    return dw.astype(w.dtype), dx.astype(x.dtype), dtau


_softsort_apply_dense.defvjp(_fwd_rule, _bwd_rule)


# --------------------------------------------------------------------------
# Banded tier: O(N * K) windowed apply in sorted-rank coordinates.
# --------------------------------------------------------------------------

def _band_geometry(n: int, d: int, block: int, itemsize: int = 4):
    """Resolve (square block edge, padded N, sublane-padded d) for the
    banded kernels — shared by forward and backward so residual shapes
    always line up.  Blocks are square (the band offset arithmetic wants
    one edge length) and 128-aligned; the payload pads d to the 8-row
    SUBLANE quantum instead of the 128-lane quantum because it is
    carried transposed (see kernels docstring).  A wide payload shrinks
    the edge until the kernels' working set fits the default scoped
    VMEM (``band_block``: 256 stays 256 at d 50, becomes 128 at d 4096);
    ``itemsize`` is the compute dtype's."""
    dsub = _round_up(max(d, 1), _SUBLANE)
    blk = band_block(min(block, _round_up(n, _LANE)), dsub, itemsize)
    np_ = _round_up(n, blk)
    return blk, np_, dsub


# The banded wrapper moves its N-length arrays (the keys, the column sums,
# their cotangent and the key gradient) between column and rank order as
# operands of ``lax.sort``, not by gathers and scatters.  On a TPU v5e a
# gather or scatter costs per index: timed per train step (PERF.md
# section 5), the sorts save about a fifth of it at B 8 from N 256 up,
# and cost up to a fifth more at B 1 below N 1024.
def _rank_order(wb):
    """(perm, ws) of keys ``wb`` (B, N): the stable argsort along N, which
    orders ties as ``jnp.argsort`` does, and the keys in that order,
    carried through the same sort."""
    iota = jax.lax.broadcasted_iota(jnp.int32, wb.shape, 1)
    ws, perm = jax.lax.sort((jax.lax.stop_gradient(wb), iota), dimension=1,
                            num_keys=1, is_stable=True)
    return perm, ws


def _to_rank(wb, v):
    """``v`` (B, N) from column into rank order, ``v[perm]`` per row for
    ``_rank_order(wb)``'s ``perm``: a stable sort keyed by ``wb`` lays
    ``v`` out the same way."""
    return jax.lax.sort((wb, v), dimension=1, num_keys=1, is_stable=True)[1]


def _to_columns(perm, v):
    """``v`` (B, N) from rank back into column order, ``out[perm[r]] =
    v[r]`` per row: a sort keyed by the permutation itself."""
    return jax.lax.sort((perm, v), dimension=1, num_keys=1)[1]


def softsort_apply_banded(w, x, tau, band: int, block: int | None = None,
                          descending: bool = False,
                          compute_dtype: str = "float32"):
    """Banded (P_soft @ x, colsum(P_soft)) in O(N * K) compute and HBM
    traffic; w: (N,) or (B, N), tau scalar, ``band`` = K the static band
    half-width in rank space.

    Kernel twin of ``repro.core.softsort.softsort_apply_banded`` — the
    identical truncated math (out-of-band mass exactly zero, bounded by
    ``core.softsort.band_tail_bound``), with forward AND backward as
    band-grid Pallas passes reusing the fused tier's online-softmax +
    residual-saving custom_vjp design.  ``band >= N - 1`` covers every
    pair, so it delegates to the exact fused dense path.  ``block``
    defaults to None = the autotuned square block edge for this
    (N, d, K, dtype, backend), hardcoded-256 fallback; ``compute_dtype``
    as in ``softsort_apply``.
    """
    n = w.shape[-1]
    band = int(band)
    assert band >= 1, band
    if band >= n - 1:
        return softsort_apply(w, x, tau, descending=descending,
                              compute_dtype=compute_dtype)
    if block is None:
        from repro.kernels.autotune import lookup_blocks
        block, _ = lookup_blocks("banded", n=n, d=x.shape[-1], k=band,
                                 dtype=compute_dtype)
    y, c = _softsort_apply_banded(w, x, tau, band, int(block),
                                  compute_dtype)
    if descending:
        y = jnp.flip(y, axis=-2)
    return y, c


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _softsort_apply_banded(w, x, tau, band: int, block: int,
                           compute_dtype: str = "float32"):
    (y, c), _ = _fwd_impl_banded(w, x, tau, band, block, compute_dtype)
    return y, c


def _fwd_impl_banded(w, x, tau, band, block, compute_dtype):
    batched = w.ndim == 2
    wb = w if batched else w[None]
    xb = x if batched else x[None]
    bsz, n = wb.shape
    d = xb.shape[-1]
    assert xb.shape == (bsz, n, d), (w.shape, x.shape)
    blk, np_, dsub = _band_geometry(n, d, block,
                                    _cd(compute_dtype).itemsize)
    # Both matrix axes in rank order, padded to kernel tiles: wr (B, 1,
    # Np), wc (B, Np, 1), xt (B, dsub, Np).  Pad slots are masked
    # in-kernel via the rank bounds, so the pad value is irrelevant.  The
    # PAYLOAD is cast to the kernel compute dtype; the keys stay f32 (see
    # ``_pad_operands``).
    perm, ws = _rank_order(wb)
    ws_p = jnp.pad(ws.astype(jnp.float32), ((0, 0), (0, np_ - n)))
    wr, wc = ws_p.reshape(bsz, 1, np_), ws_p.reshape(bsz, np_, 1)
    xs = jnp.take_along_axis(xb.astype(jnp.float32), perm[..., None],
                             axis=1)
    xt = jnp.pad(xs, ((0, 0), (0, np_ - n), (0, dsub - d))).transpose(
        0, 2, 1).astype(_cd(compute_dtype))
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1, 1)

    y_t, c_s, m, l = softsort_apply_fwd_banded_pallas(
        wr, wc, xt, tau_arr,
        n=n, k=band, blk=blk, interpret=not _on_tpu())
    y = y_t[:, :d, :n].transpose(0, 2, 1)                # (B, N, d), cd
    y_out = y.astype(jnp.float32)
    # Column sums come back in rank order; move them to original columns.
    c = _to_columns(perm, c_s[:, :n, 0])
    out = (y_out, c) if batched else (y_out[0], c[0])
    # The backward reuses the kernels' operands.  Same residual economy
    # as the dense tier: y is saved SLICED and untransposed (and in the
    # compute dtype); the backward re-pads/re-transposes it for O(N d).
    return out, (perm, wr, wc, xt, m, l, y)


def _fwd_rule_banded(w, x, tau, band, block, compute_dtype):
    out, res = _fwd_impl_banded(w, x, tau, band, block, compute_dtype)
    # x itself is not needed again (``xt`` holds it in rank order); an
    # empty array carries its dtype for the payload gradient.
    return out, (w, jnp.zeros((0,), x.dtype),
                 jnp.asarray(tau, jnp.float32)) + res


def _bwd_rule_banded(band, block, compute_dtype, res, cot):
    w, x_dtype, tau, perm, wr, wc, xt, m, l, y = res
    dy, dc = cot
    batched = w.ndim == 2
    wb = w if batched else w[None]
    dyb = dy if batched else dy[None]
    dcb = dc if batched else dc[None]
    bsz, n = wb.shape
    d = dyb.shape[-1]
    blk, np_, dsub = _band_geometry(n, d, block,
                                    _cd(compute_dtype).itemsize)
    pad_n = np_ - n

    # Mirror the forward's padded transposed layout (no argsort here);
    # cotangent pads are zero so pad slots contribute nothing to any
    # reduction.  Cotangents ride in the compute dtype.
    cd = _cd(compute_dtype)

    def to_t(a, dt=jnp.float32):                         # (B, N, d) pads
        return jnp.pad(a.astype(jnp.float32),
                       ((0, 0), (0, pad_n), (0, dsub - d))).transpose(
                           0, 2, 1).astype(dt)

    yt, dyt = to_t(y, cd), to_t(dyb, cd)
    # colsum cotangent into rank order (c[perm[r]] = c_sorted[r]).
    dc_s = _to_rank(wb, dcb.astype(jnp.float32))
    dc_p = jnp.pad(dc_s, ((0, 0), (0, pad_n))).reshape(
        bsz, np_, 1).astype(cd)

    dws_row, dws_col, dxt, dtau_cols = softsort_apply_bwd_banded_pallas(
        wr, wc, xt, tau.reshape(1, 1), m, l, yt, dyt, dc_p,
        n=n, k=band, blk=blk, interpret=not _on_tpu())

    # Both matrix axes are sorted keys here, so the key gradient has a
    # row and a column component; sum them in rank order, then move the
    # sum to column order (and scatter the payload gradient likewise).
    dws = dws_row[:, 0, :n] + dws_col[:, :n, 0]          # (B, N)
    dw = _to_columns(perm, dws)
    bidx = jnp.arange(bsz)[:, None]
    dxs = dxt[:, :d, :n].transpose(0, 2, 1)              # (B, N, d)
    dx = jnp.zeros((bsz, n, d), jnp.float32).at[bidx, perm].add(dxs)
    dtau = jnp.sum(dtau_cols)
    if not batched:
        dw, dx = dw[0], dx[0]
    return dw.astype(w.dtype), dx.astype(x_dtype.dtype), dtau


_softsort_apply_banded.defvjp(_fwd_rule_banded, _bwd_rule_banded)


# --------------------------------------------------------------------------
# v1 baseline: split three-pass forward + chunked jnp-scan backward.
# Benchmark-only (benchmarks/kernel_bench.py measures fused vs this); the
# backward re-sorts and re-normalizes from scratch and materializes
# (B, chunk, N) temporaries — exactly the HBM traffic the fused path
# eliminates.
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def softsort_apply_v1(w, x, tau, block_rows: int = 256,
                      block_cols: int = 256, bwd_chunk: int = 256):
    """Previous-generation (P_soft @ x, colsum(P_soft)) — baseline only."""
    return _fwd_impl_v1(w, x, tau, block_rows, block_cols)


def _fwd_impl_v1(w, x, tau, block_rows, block_cols):
    batched = w.ndim == 2
    wb = w if batched else w[None]
    xb = x if batched else x[None]
    bsz, n = wb.shape
    d = xb.shape[-1]
    assert xb.shape == (bsz, n, d), (w.shape, x.shape)
    br, bc, np_, dp = _block_geometry(n, d, block_rows, block_cols)
    _, ws_p, w_p, x_p = _pad_operands(wb, xb, n, np_, dp)
    tau_arr = jnp.asarray(tau, jnp.float32).reshape(1, 1)

    y_p, c_p = softsort_apply_fwd_pallas_v1(
        ws_p, w_p, x_p, tau_arr,
        n=n, br=br, bc=bc, interpret=not _on_tpu())
    y, c = y_p[:, :n, :d], c_p[:, 0, :n]
    return (y, c) if batched else (y[0], c[0])


def _fwd_rule_v1(w, x, tau, block_rows, block_cols, bwd_chunk):
    y, c = _fwd_impl_v1(w, x, tau, block_rows, block_cols)
    return (y, c), (w, x, jnp.asarray(tau, jnp.float32))


def _bwd_rule_v1(block_rows, block_cols, bwd_chunk, res, cot):
    w, x, tau = res
    dy, dc = cot
    batched = w.ndim == 2
    wb = w if batched else w[None]
    xb = x if batched else x[None]
    dyb = dy if batched else dy[None]
    dcb = dc if batched else dc[None]
    bsz, n = wb.shape
    d = xb.shape[-1]
    chunk = min(bwd_chunk, n)
    # Pad the row dimension so chunks tile evenly; padded rows get zero
    # cotangent so they contribute nothing.
    np_ = _round_up(n, chunk)
    pad = np_ - n

    perm = jnp.argsort(jax.lax.stop_gradient(wb), axis=-1)
    ws = jnp.take_along_axis(wb, perm, axis=-1)
    big = jnp.max(jax.lax.stop_gradient(ws)) + 1.0 if n else 0.0
    ws_p = jnp.pad(ws, ((0, 0), (0, pad)), constant_values=big)
    dy_p = jnp.pad(dyb.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))

    row_valid = (jnp.arange(np_) < n).astype(jnp.float32)

    nb = np_ // chunk
    # Scan over row blocks; batch stays a vectorized leading dim inside
    # each step, so peak live memory is O(B * chunk * N).
    ws_blocks = ws_p.reshape(bsz, nb, chunk).transpose(1, 0, 2)
    dy_blocks = dy_p.reshape(bsz, nb, chunk, d).transpose(1, 0, 2, 3)
    valid_blocks = row_valid.reshape(nb, chunk)

    xf = xb.astype(jnp.float32)
    dcf = dcb.astype(jnp.float32)

    def body(carry, blk):
        dw_cols, dx, dtau = carry
        ws_b, dy_b, valid_b = blk      # (B, chunk), (B, chunk, d), (chunk,)
        delta = ws_b[:, :, None] - wb[:, None, :]          # (B, chunk, N)
        s = -jnp.abs(delta) / tau
        p = jax.nn.softmax(s, axis=-1)
        # dP_ij = dy_i . x_j + dc_j   (padded rows are not rows of P: mask)
        dp = jnp.einsum("bcd,bnd->bcn", dy_b, xf) + dcf[:, None, :]
        dsum = jnp.sum(p * dp, axis=-1, keepdims=True)
        ds = p * (dp - dsum) * valid_b[None, :, None]      # (B, chunk, N)
        p = p * valid_b[None, :, None]     # mask dx contribution too
        sgn = jnp.sign(delta)
        dws_b = jnp.sum(ds * (-sgn), axis=-1) / tau        # (B, chunk)
        dw_cols = dw_cols + jnp.sum(ds * sgn, axis=1) / tau
        dx = dx + jnp.einsum("bcn,bcd->bnd", p, dy_b)
        dtau = dtau + jnp.sum(ds * (-s)) / tau
        return (dw_cols, dx, dtau), dws_b

    init = (jnp.zeros_like(wb, jnp.float32), jnp.zeros_like(xf),
            jnp.zeros((), jnp.float32))
    (dw_cols, dx, dtau), dws_stack = jax.lax.scan(
        body, init, (ws_blocks, dy_blocks, valid_blocks))
    dws = dws_stack.transpose(1, 0, 2).reshape(bsz, np_)[:, :n]
    # Scatter the sorted-row gradient back through the permutation.
    dw = dw_cols.at[jnp.arange(bsz)[:, None], perm].add(dws)
    if not batched:
        dw, dx = dw[0], dx[0]
    return dw.astype(w.dtype), dx.astype(x.dtype), dtau


softsort_apply_v1.defvjp(_fwd_rule_v1, _bwd_rule_v1)
