"""Fused flash-style SoftSort-apply Pallas TPU kernels (batched, fwd + bwd).

Computes, without ever materializing the (N, N) soft permutation matrix,
for every instance b of a leading batch axis:

    P[b]_ij   = softmax_j( -|sort(w[b])_i - w[b]_j| / tau )
    y[b]      = P[b] @ x[b]          (B, N, d)
    colsum[b] = sum_i P[b]_ij        (B, N)

Structure is exactly flash attention with an L1-distance score and the
sorted keys playing the role of queries.

Forward — ONE online-softmax sweep (FlashAttention-2 style) plus the
colsum reduction, two ``pallas_call``s total, so the score block is
computed exactly twice and the softmax stats never round-trip to HBM
mid-forward:

  * ``_fwd_fused_kernel`` — streaming row max ``m``, denominator ``l``
    AND the un-normalized y accumulator in one pass (grid = (B, Ni, Nj),
    j innermost; m/l are revisited output blocks and y accumulates in a
    float32 VMEM *scratch* buffer — both live on-chip across the column
    sweep, the TPU sequential-grid idiom).  Each column block rescales
    the running y by ``exp(m_prev - m_new)``; the final ``1/l`` is
    applied once at the last column block, where y is written out ONCE
    in the compute dtype.  ``m`` and ``l`` are kernel *outputs*: the
    backward reuses them as residuals instead of re-deriving the
    softmax.
  * ``_colsum_kernel``    — exact P block = exp(s - m)/l with the i/j
    grid axes transposed (j outer, i inner) so the colsum block
    accumulates over row blocks.

Backward — TWO Pallas passes driven by the ``custom_vjp`` in
``repro.kernels.ops``, which saves ``(perm, m, l, y)`` from the
forward so no pass re-sorts or re-normalizes.  With
``dP_ij = dy_i . x_j + dc_j`` and ``ds = P * (dP - D)`` where
``D_i = sum_j P_ij dP_ij``:

  * ``_bwd_dws_delta_kernel`` — row grid: ONE sweep fuses the old
    delta pass into the dws pass.  It accumulates three row vectors —
    ``D_i = dy_i . y_i + (P @ dc)_i`` (the first term is flash
    attention's delta trick: ``sum_j P_ij (dy_i . x_j) = dy_i . y_i``
    because y was saved; only the colsum cotangent needs a streamed
    ``P @ dc``), ``A_i = sum_j P_ij dP_ij sgn_ij`` and ``S_i = sum_j
    P_ij sgn_ij`` (A and S in VMEM scratch) — and combines them at the
    last column block:
    ``dws_i = -sum_j ds_ij sgn_ij / tau = -(A_i - D_i S_i) / tau``
    (the D-dependent part of ds factors out of the row reduction, so
    dws never needs a completed D mid-sweep).  One fewer full re-score
    of the tile space than the previous 3-pass design, and D is still
    emitted for the pass below.
  * ``_bwd_dx_kernel``    — transposed grid (j outer, i inner):
    ``dx_j = sum_i P_ij dy_i`` (a (Bc, Br) x (Br, d) MXU contraction,
    accumulated in f32 scratch, written once in the compute dtype),
    plus the column-indexed reductions ``dw_cols_j = sum_i ds_ij
    sgn_ij / tau`` and a per-column ``dtau`` partial (here ds needs
    D_i per summand, so this pass genuinely consumes the finished D).

No (B, chunk, N) ``delta``/``p``/``dp``/``ds`` temporaries ever touch
HBM — every score/probability block is consumed inside its VMEM tile.

Mixed precision (``cd``, the compute dtype — f32 or bf16, threaded from
``ops``' ``compute_dtype``):

  * KEYS STAY FLOAT32.  The keys are the paper's N learnable
    parameters; quantizing them to bf16 collapses unit rank gaps into
    ties above N = 256 (bf16 integers are exact only to 256) and was
    measured to blow the key-gradient parity up to ~0.5 relative.  Key
    vectors are O(N) bytes — negligible against the payload — so f32
    keys cost nothing and keep rank resolution exact.
  * SCORES are computed from the f32 keys and then ROUNDED to ``cd``
    (``.astype(cd)``), so the bf16 tier sees genuinely bf16 scores —
    but with error proportional to the score's own magnitude, not the
    key magnitude.  In the trainer's shuffled-arange regime the scores
    are small integer multiples of 1/tau and round exactly.
  * PAYLOAD-SIDED ARRAYS (x, dy, dc, the saved y residual, and the dx
    gradient output) live in ``cd`` in HBM — at bf16 every payload
    block moved is half the bytes, which is where the measured traffic
    reduction comes from — and every matmul takes cd inputs with
    ``preferred_element_type=jnp.float32``: f32 MXU accumulation.  At
    ``cd == float32`` the matmuls ask for HIGHEST precision, so Mosaic
    does not round their f32 operands to bf16 (``_mxu_precision``).
    The one matvec (``P @ dc`` in the dense backward) contracts its
    cd-rounded operands in f32, because Mosaic refuses it in bf16.
  * EVERYTHING LOAD-BEARING STAYS F32: the online-softmax max/exp/sum,
    the m/l stats and residuals, D, every VMEM accumulator (the y and
    dx accumulators are explicit f32 scratch when their HBM form is
    cd), and the key/tau gradients (dws, dw_cols, dtau).

``cd == float32`` reproduces the previous all-f32 kernels bit-for-bit.
The bf16 parity envelope is measured in EXPERIMENTS.md §Perf and gated
by ``tests/test_precision.py`` / ``tools/check_bench.py``.

The batch axis is the OUTERMOST grid dimension: each instance is an
independent sweep over its own (Ni, Nj) tile space, so the accumulator
idiom above is untouched — b changes only after an instance's tiles are
exhausted.  Instances share one scalar ``tau`` (the trainer anneals a
single schedule across the whole batch).  The batch block size is
``None`` (squeezed), so the kernels themselves see 2-D blocks.

VMEM working set per step ~ Br*Bc (scores) + Bc*d (x block) + Br*d
(y/dy blocks + the f32 y scratch) floats; with the default Br = Bc =
256, d <= 512 this is well under the ~16 MB/core budget and independent
of B.  Block shapes are (8k, 128m)-aligned so the MXU sees aligned
contractions; the autotune table (``repro.kernels.autotune``) picks
per-shape block sizes within that constraint.  The banded tier carries
the whole payload width in each block, so it sizes its block edge from
d (``band_block``: 256 at d 50, 128 at d 4096) and raises Mosaic's
scoped-VMEM limit only where that edge's working set exceeds the
default (``band_vmem_bytes``).

All kernels mask columns >= n (true length) with -inf scores and rows
>= n out of every column-indexed reduction, so the wrapper may pad N up
to block multiples with arbitrary finite values.  ``tau`` arrives as a
(1, 1) array so it can be a traced value inside jit without
retriggering compilation.

The v1 split forward (separate stats + apply passes, three
``pallas_call``s) is kept at the bottom as the benchmark baseline for
``benchmarks/kernel_bench.py`` — it is what PR 1/2 shipped (f32 only),
and the fused-vs-v1 rows in BENCH_kernels.json quantify the win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mxu_precision(dtype):
    """Matmul precision for operands of ``dtype``: HIGHEST for f32,
    whose operands Mosaic's default rounds to bf16 for a single MXU
    pass (that put the f32 tier ~3e-3 relative off the f32 oracle on a
    TPU v5e), and the default for bf16, where one pass is exact and
    Mosaic refuses HIGHEST.  Interpret mode on CPU computes f32 matmuls
    in f32 either way."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _score(ws_blk, w_blk, inv_tau, cd):
    # (Br, 1) x (1, Bc) -> (Br, Bc) L1 scores, scaled.  Keys are always
    # f32 (see module docstring); the finished score is rounded to the
    # compute dtype and upcast, so the bf16 tier's scores carry bf16
    # precision relative to the SCORE scale while the softmax math
    # downstream stays f32.  cd == f32 is the exact identity.
    s = -jnp.abs(ws_blk - w_blk) * inv_tau
    return s.astype(cd).astype(jnp.float32)


def _col_mask(j, bc, n):
    col_ids = j * bc + jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1)
    return col_ids < n


def _row_mask(i, br, n):
    row_ids = i * br + jax.lax.broadcasted_iota(jnp.int32, (br, 1), 0)
    return row_ids < n


# --------------------------------------------------------------------------
# Forward: fused online-softmax sweep + colsum.
# --------------------------------------------------------------------------

def _fwd_fused_kernel(ws_ref, w_ref, x_ref, tau_ref, y_ref, m_ref, l_ref,
                      acc_ref, *, n: int, bc: int, nj: int, cd):
    j = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    s = _score(ws_ref[...], w_ref[...], inv_tau, cd)           # (Br, Bc)
    s = jnp.where(_col_mask(j, bc, n), s, NEG_INF)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[...]                                        # (Br, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    p_un = jnp.exp(s - m_new)                                  # un-normalized
    l_ref[...] = l_ref[...] * correction + jnp.sum(
        p_un, axis=-1, keepdims=True)
    m_ref[...] = m_new
    # Payload matmul inputs in the compute dtype, accumulation pinned to
    # the f32 VMEM scratch by preferred_element_type — the MXU contract.
    acc_ref[...] = acc_ref[...] * correction + jnp.dot(
        p_un.astype(cd), x_ref[...],
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _normalize():
        y_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(y_ref.dtype)


def _colsum_kernel(ws_ref, w_ref, tau_ref, m_ref, l_ref, c_ref,
                   *, n: int, br: int, bc: int, cd):
    # Grid is (B, Nj, Ni): i innermost so the c block accumulates in VMEM.
    j = pl.program_id(1)
    i = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    s = _score(ws_ref[...], w_ref[...], inv_tau, cd)
    s = jnp.where(_col_mask(j, bc, n), s, NEG_INF)
    p = jnp.exp(s - m_ref[...]) / jnp.maximum(l_ref[...], 1e-30)
    p = jnp.where(_row_mask(i, br, n), p, 0.0)                 # mask pad rows

    @pl.when(i == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.sum(p, axis=0, keepdims=True)


def softsort_apply_fwd_pallas(
    ws: jnp.ndarray,      # (B, Np, 1) sorted keys (rows), padded, f32
    w: jnp.ndarray,       # (B, 1, Np) unsorted keys (cols), padded, f32
    x: jnp.ndarray,       # (B, Np, dp) payload, padded, compute dtype
    tau: jnp.ndarray,     # (1, 1) — shared across the batch
    *,
    n: int,               # true length
    br: int,
    bc: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused forward: (y (B, Np, dp) in the compute dtype, colsum
    (B, 1, Np), m, l (B, Np, 1) f32).

    Two ``pallas_call``s: the fused online-softmax sweep and the
    transposed-grid colsum reduction.  ``m``/``l`` are returned so the
    backward can reuse them as residuals; the compute dtype is inferred
    from ``x.dtype`` (the wrapper casts operands once).
    """
    bsz, np_, dp = x.shape
    ni, nj = np_ // br, np_ // bc
    f32 = jnp.float32
    cd = x.dtype

    y, m, l = pl.pallas_call(
        functools.partial(_fwd_fused_kernel, n=n, bc=bc, nj=nj, cd=cd),
        grid=(bsz, ni, nj),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # ws rows
            pl.BlockSpec((None, 1, bc), lambda b, i, j: (b, 0, j)),   # w cols
            pl.BlockSpec((None, bc, dp), lambda b, i, j: (b, j, 0)),  # x block
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),             # tau
        ],
        out_specs=[
            pl.BlockSpec((None, br, dp), lambda b, i, j: (b, i, 0)),  # y
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # l
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, np_, dp), cd),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
        ],
        scratch_shapes=[pltpu.VMEM((br, dp), f32)],       # y accumulator
        interpret=interpret,
        name="softsort_dense_fwd",
    )(ws, w, x, tau)

    colsum = pl.pallas_call(
        functools.partial(_colsum_kernel, n=n, br=br, bc=bc, cd=cd),
        grid=(bsz, nj, ni),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # ws
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # w
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0)),             # tau
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # l
        ],
        out_specs=pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1, np_), f32),
        interpret=interpret,
        name="softsort_dense_colsum",
    )(ws, w, tau, m, l)

    return y, colsum, m, l


# --------------------------------------------------------------------------
# Backward: two Pallas passes over the saved residuals.
# --------------------------------------------------------------------------

def _p_block(ws_ref, w_ref, m_ref, l_ref, inv_tau, j, bc, n, cd):
    """Exact normalized P block from the saved softmax stats (no re-max,
    no re-sum) — the residual-reuse core of the backward.  Scores are
    quantized exactly as the forward quantized them, so exp(s - m)/l
    reconstructs the forward's P bit-for-bit per compute dtype."""
    s = _score(ws_ref[...], w_ref[...], inv_tau, cd)
    s = jnp.where(_col_mask(j, bc, n), s, NEG_INF)
    p = jnp.exp(s - m_ref[...]) / jnp.maximum(l_ref[...], 1e-30)
    return s, p


def _bwd_dws_delta_kernel(ws_ref, w_ref, x_ref, tau_ref, m_ref, l_ref,
                          dy_ref, y_ref, dc_ref, d_ref, dws_ref,
                          a_ref, srow_ref, *, n: int, bc: int, nj: int, cd):
    """Fused delta + dws row-grid sweep (the 3->2 backward-pass merge).

    Accumulates, per row block over the column blocks:
      D_i = dy_i . y_i + sum_j P_ij dc_j       (delta, emitted for the
                                                transposed pass)
      A_i = sum_j P_ij dP_ij sgn_ij            (f32 scratch)
      S_i = sum_j P_ij sgn_ij                  (f32 scratch)
    and combines at the last column block:
      dws_i = -sum_j ds_ij sgn_ij / tau = -(A_i - D_i * S_i) / tau
    — the D-dependent half of ds = P (dP - D) factors out of the row
    reduction, so dws never needs a finished D mid-sweep."""
    j = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    _, p = _p_block(ws_ref, w_ref, m_ref, l_ref, inv_tau, j, bc, n, cd)
    dp = jax.lax.dot_general(
        dy_ref[...], x_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32) + dc_ref[...]
    sgn = jnp.sign(ws_ref[...] - w_ref[...])

    @pl.when(j == 0)
    def _init():
        d_ref[...] = jnp.sum(dy_ref[...].astype(jnp.float32)
                             * y_ref[...].astype(jnp.float32),
                             axis=-1, keepdims=True)
        a_ref[...] = jnp.zeros_like(a_ref)
        srow_ref[...] = jnp.zeros_like(srow_ref)

    # The one-row RHS makes this a matvec, which Mosaic refuses with
    # bf16 operands (it emits a bf16 -> f32 vector.broadcast); the
    # operands are therefore rounded to cd and contracted in f32.  The
    # products of cd values are exact in f32, so this is the bf16 MXU
    # math, and at cd == f32 both casts are no-ops.
    d_ref[...] += jax.lax.dot_general(
        p.astype(cd).astype(jnp.float32), dc_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_mxu_precision(jnp.float32),
        preferred_element_type=jnp.float32)
    a_ref[...] += jnp.sum(p * dp * sgn, axis=-1, keepdims=True)
    srow_ref[...] += jnp.sum(p * sgn, axis=-1, keepdims=True)

    @pl.when(j == nj - 1)
    def _combine():
        dws_ref[...] = -(a_ref[...] - d_ref[...] * srow_ref[...]) \
            * inv_tau


def _bwd_dx_kernel(ws_ref, w_ref, x_ref, tau_ref, m_ref, l_ref, dy_ref,
                   dc_ref, d_ref, dx_ref, dwc_ref, dtc_ref, acc_ref,
                   *, n: int, br: int, bc: int, ni: int, cd):
    """Transposed grid (B, Nj, Ni): per column block accumulate
    dx_j = P^T @ dy (f32 scratch, written once in the compute dtype),
    dw_cols_j = sum_i ds * sgn / tau, and the per-column dtau partial
    sum_i ds * (-s) / tau."""
    j = pl.program_id(1)
    i = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    s, p = _p_block(ws_ref, w_ref, m_ref, l_ref, inv_tau, j, bc, n, cd)
    p = jnp.where(_row_mask(i, br, n), p, 0.0)      # pad rows are not rows of P
    # dP_ij = dy_i . x_j + dc_j
    dp = jax.lax.dot_general(
        dy_ref[...], x_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32) + dc_ref[...]
    ds = p * (dp - d_ref[...])                                  # (Br, Bc)
    sgn = jnp.sign(ws_ref[...] - w_ref[...])

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dwc_ref[...] = jnp.zeros_like(dwc_ref)
        dtc_ref[...] = jnp.zeros_like(dtc_ref)

    acc_ref[...] += jax.lax.dot_general(
        p.astype(cd), dy_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32)                     # (Bc, dp)
    dwc_ref[...] += jnp.sum(ds * sgn, axis=0, keepdims=True) * inv_tau
    # s = -|delta|/tau  =>  d s / d tau = -s / tau; masked cols have
    # ds == 0 exactly, and NEG_INF is finite, so 0 * (-NEG_INF) == 0.
    dtc_ref[...] += jnp.sum(ds * (-s), axis=0, keepdims=True) * inv_tau

    @pl.when(i == ni - 1)
    def _flush():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


def softsort_apply_bwd_pallas(
    ws: jnp.ndarray,      # (B, Np, 1) sorted keys (rows), padded, f32
    w: jnp.ndarray,       # (B, 1, Np) unsorted keys (cols), padded, f32
    x: jnp.ndarray,       # (B, Np, dp) payload, padded, compute dtype
    tau: jnp.ndarray,     # (1, 1)
    m: jnp.ndarray,       # (B, Np, 1) saved row maxes, f32
    l: jnp.ndarray,       # (B, Np, 1) saved row denominators, f32
    y: jnp.ndarray,       # (B, Np, dp) saved forward output, compute dtype
    dy: jnp.ndarray,      # (B, Np, dp) cotangent of y, compute dtype
    dc: jnp.ndarray,      # (B, 1, Np) cotangent of colsum, compute dtype
    *,
    n: int,
    br: int,
    bc: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused backward from saved residuals — TWO Pallas passes.

    Pass 1 (row grid) fuses the old delta pass into the dws pass: one
    sweep emits D (consumed by pass 2) AND dws.  Pass 2 (transposed
    grid) produces the column-indexed dx / dw_cols / dtau_cols.

    Returns (dws (B, Np, 1) f32 — gradient w.r.t. the SORTED keys, to
    be scattered through ``perm`` by the caller; dw_cols (B, 1, Np)
    f32; dx (B, Np, dp) in the compute dtype; dtau_cols (B, 1, Np) f32
    — per-column dtau partials, summed to a scalar by the caller).
    """
    bsz, np_, dp = x.shape
    ni, nj = np_ // br, np_ // bc
    f32 = jnp.float32
    cd = x.dtype

    row_spec = pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0))
    col_spec = pl.BlockSpec((None, 1, bc), lambda b, i, j: (b, 0, j))
    tau_spec = pl.BlockSpec((1, 1), lambda b, i, j: (0, 0))

    # Fused delta+dws row-grid sweep; the A/S partial sums live in f32
    # VMEM scratch and never touch HBM.
    delta, dws = pl.pallas_call(
        functools.partial(_bwd_dws_delta_kernel, n=n, bc=bc, nj=nj, cd=cd),
        grid=(bsz, ni, nj),
        in_specs=[
            row_spec,                                                 # ws
            col_spec,                                                 # w
            pl.BlockSpec((None, bc, dp), lambda b, i, j: (b, j, 0)),  # x
            tau_spec,                                                 # tau
            row_spec,                                                 # m
            row_spec,                                                 # l
            pl.BlockSpec((None, br, dp), lambda b, i, j: (b, i, 0)),  # dy
            pl.BlockSpec((None, br, dp), lambda b, i, j: (b, i, 0)),  # y
            col_spec,                                                 # dc
        ],
        out_specs=[row_spec, row_spec],                    # D, dws
        out_shape=[
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
        ],
        scratch_shapes=[pltpu.VMEM((br, 1), f32),          # A
                        pltpu.VMEM((br, 1), f32)],         # S
        interpret=interpret,
        name="softsort_dense_bwd_delta",
    )(ws, w, x, tau, m, l, dy, y, dc)

    # Transposed grid: j outer, i inner, so the column-indexed outputs
    # (dx via scratch, dw_cols, dtau_cols) accumulate in VMEM.
    dx, dw_cols, dtau_cols = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, n=n, br=br, bc=bc, ni=ni, cd=cd),
        grid=(bsz, nj, ni),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # ws
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # w
            pl.BlockSpec((None, bc, dp), lambda b, j, i: (b, j, 0)),  # x
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0)),             # tau
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # l
            pl.BlockSpec((None, br, dp), lambda b, j, i: (b, i, 0)),  # dy
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # dc
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # D
        ],
        out_specs=[
            pl.BlockSpec((None, bc, dp), lambda b, j, i: (b, j, 0)),  # dx
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # dw_cols
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # dtau
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, np_, dp), cd),
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
        ],
        scratch_shapes=[pltpu.VMEM((bc, dp), f32)],        # dx accumulator
        interpret=interpret,
        name="softsort_dense_bwd_dx",
    )(ws, w, x, tau, m, l, dy, dc, delta)

    return dws, dw_cols, dx, dtau_cols


# --------------------------------------------------------------------------
# Banded tier: O(N * K) windowed kernels in sorted-rank coordinates.
#
# The wrapper (ops.softsort_apply_banded) gathers BOTH matrix axes into
# sorted-key order, so the soft permutation matrix P~ is diagonally
# dominant in rank space and only the width-(2K+1) band around the
# diagonal is scored — out-of-band entries are treated as exactly zero
# (neglected mass bounded by core.softsort.band_tail_bound).  Each row
# block i therefore touches only the nbj = 2*ceil(K/blk) + 1 column
# blocks u = i - off .. i + off, shrinking the grid from (N/blk)^2 to
# (N/blk) * nbj cells per pass; edge blocks clip their index maps into
# range and mask themselves out entirely.
#
# Two layout changes vs the dense kernels above, both HBM-traffic wins
# at the paper's small payload widths (d = 3..50):
#
#   * scores live TRANSPOSED, (bc, br) with matrix columns on sublanes
#     and rows on lanes, so the running softmax stats m/l are (1, br)
#     lane vectors and every reduction stays a lane-wise op;
#   * the payload is carried transposed, (dsub, Np) with dsub =
#     round_up(d, 8) on SUBLANES — padding d to the 8-sublane quantum
#     instead of the 128-lane quantum cuts payload blocks 16x at d = 8
#     (the (bc, d) @ -> y contraction becomes x_t @ p_un on the MXU).
#
# Same online-softmax + residual-saving custom_vjp structure as the
# fused dense tier: one forward sweep emitting (y_t, m, l), a
# transposed-grid colsum, and TWO backward passes (fused delta+dws row
# sweep, then the column-indexed dx/dw/dtau pass — the same 3->2 merge
# as the dense tier, one fewer full re-score of the band).  Because
# both axes are sorted, the key gradient has a row AND a column
# component here — the wrapper sums them before scattering through the
# saved perm.  Mixed precision follows the dense tier's contract: keys
# f32, scores rounded to the compute dtype, payload-sided arrays
# (xt, dyt, dc, the yt residual, the dxt output) in the compute dtype,
# stats/accumulators f32.
# --------------------------------------------------------------------------


# Mosaic's default scoped-VMEM limit on a TPU v5e.
SCOPED_VMEM_DEFAULT = 16 * 2**20


def band_vmem_bytes(dsub: int, blk: int, itemsize: int) -> int:
    """VMEM working set of the widest banded pass at block edge ``blk``.

    ``_bwd_band_dcol`` double-buffers three (dsub, blk) payload blocks
    (xt, dyt in; dxt out) in the compute dtype and holds two more in f32
    (the dxt accumulator and the product added to it); besides those,
    about a dozen (blk, blk) f32 arrays (scores, probabilities, their
    products and masks, the key columns padded to the lanes).  The
    other passes hold fewer payload blocks."""
    return dsub * blk * (6 * itemsize + 2 * 4) + 12 * blk * blk * 4


def band_block(block: int, dsub: int, itemsize: int) -> int:
    """The banded kernels' block edge: ``block`` halved, down to the lane
    width 128, while the working set exceeds the default scoped VMEM.
    At d 50 that keeps 256; at d 4096 it gives 128."""
    blk = block
    while blk > 128 and band_vmem_bytes(dsub, blk,
                                        itemsize) > SCOPED_VMEM_DEFAULT:
        blk //= 2
    return blk


def band_max_width(itemsize: int) -> int:
    """The widest payload (a multiple of the 8-row sublane) the block
    rule holds: at the 128 edge, its scoped limit (half again the
    working set) stays within half of a v5e core's 128 MiB of VMEM.
    10728 rows in float32; wider payloads fail to compile."""
    fixed = band_vmem_bytes(0, 128, itemsize)
    per_row = band_vmem_bytes(1, 128, itemsize) - fixed
    need = 64 * 2**20 * 2 // 3
    return (need - fixed) // per_row // 8 * 8


def _band_compiler_params(dsub: int, blk: int, cd):
    """Mosaic's default where the working set fits it; else a limit of
    half again the working set, for the compiler's own temporaries."""
    need = band_vmem_bytes(dsub, blk, jnp.dtype(cd).itemsize)
    if need <= SCOPED_VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=need + need // 2)


def _band_mask(i, u, blk: int, k: int, n: int):
    """(bc, br) validity of a banded score block: |rank_col - rank_row|
    <= K, both ranks real (not padding), both block ids in range (a
    clipped edge block computes its UNCLIPPED ids here, so it masks
    itself out entirely instead of double-counting the block it was
    clamped onto)."""
    rows = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
    cols = u * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
    return ((jnp.abs(cols - rows) <= k)
            & (cols >= 0) & (cols < n) & (rows >= 0) & (rows < n))


def _score_t(wc_blk, wr_blk, inv_tau, cd):
    # (Bc, 1) x (1, Br) -> (Bc, Br) transposed L1 scores, scaled.
    # Same precision contract as ``_score``: f32 keys, score rounded to
    # the compute dtype, f32 out for the softmax stats.
    s = -jnp.abs(wc_blk - wr_blk) * inv_tau
    return s.astype(cd).astype(jnp.float32)


def _fwd_band_kernel(wr_ref, wc_ref, xt_ref, tau_ref, y_ref, m_ref, l_ref,
                     acc_ref, *, n: int, k: int, blk: int, off: int,
                     nbj: int, cd):
    i = pl.program_id(1)
    jj = pl.program_id(2)
    u = i - off + jj                              # unclipped column block
    inv_tau = 1.0 / tau_ref[0, 0]
    mask = _band_mask(i, u, blk, k, n)
    s = jnp.where(mask, _score_t(wc_ref[...], wr_ref[...], inv_tau, cd),
                  NEG_INF)

    @pl.when(jj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[...]                                        # (1, Br)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    # The explicit mask (not just exp(s - m)) keeps a fully-masked block
    # exact: there m_new stays NEG_INF and exp(s - m_new) would be
    # exp(0) = 1 per masked slot.
    p_un = jnp.where(mask, jnp.exp(s - m_new), 0.0)            # (Bc, Br)
    l_ref[...] = l_ref[...] * correction + jnp.sum(
        p_un, axis=0, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * correction + jax.lax.dot_general(
        xt_ref[...], p_un.astype(cd),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32)                    # (dsub, Br)

    @pl.when(jj == nbj - 1)
    def _normalize():
        y_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(y_ref.dtype)


def _p_band_block(wr_ref, wc_ref, m_ref, l_ref, inv_tau, mask, cd):
    """Exact normalized transposed P~ block from the saved stats, fully
    masked (band + padding + clipped edge blocks) so garbage stats on
    masked rows can never leak.  Scores quantized exactly as the
    forward's."""
    s = jnp.where(mask, _score_t(wc_ref[...], wr_ref[...], inv_tau, cd),
                  NEG_INF)
    p = jnp.where(mask, jnp.exp(s - m_ref[...])
                  / jnp.maximum(l_ref[...], 1e-30), 0.0)
    return s, p


def _colsum_band_kernel(wr_ref, wc_ref, tau_ref, m_ref, l_ref, c_ref,
                        *, n: int, k: int, blk: int, off: int, cd):
    # Grid (B, Nj, nbi): column block j outer, band row step ii inner so
    # the (Bc, 1) colsum block accumulates in VMEM.
    j = pl.program_id(1)
    ii = pl.program_id(2)
    iu = j - off + ii                             # unclipped row block
    inv_tau = 1.0 / tau_ref[0, 0]
    mask = _band_mask(iu, j, blk, k, n)
    _, p = _p_band_block(wr_ref, wc_ref, m_ref, l_ref, inv_tau, mask, cd)

    @pl.when(ii == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    c_ref[...] += jnp.sum(p, axis=1, keepdims=True)


def softsort_apply_fwd_banded_pallas(
    wr: jnp.ndarray,      # (B, 1, Np) sorted keys (matrix rows), f32
    wc: jnp.ndarray,      # (B, Np, 1) sorted keys (matrix cols), f32
    xt: jnp.ndarray,      # (B, dsub, Np) payload, sorted + transposed, cd
    tau: jnp.ndarray,     # (1, 1) — shared across the batch
    *,
    n: int,               # true length
    k: int,               # band half-width in rank space
    blk: int,             # square block edge (multiple of 128)
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Banded forward: (y_t (B, dsub, Np) in the compute dtype, colsum
    (B, Np, 1) in rank order, m, l (B, 1, Np) f32).  Two
    ``pallas_call``s over (N/blk) * nbj grids instead of (N/blk)^2."""
    bsz, dsub, np_ = xt.shape
    ni = np_ // blk
    off = -(-k // blk)
    nbj = 2 * off + 1
    f32 = jnp.float32
    cd = xt.dtype
    params = _band_compiler_params(dsub, blk, cd)

    def _col(b, i, jj):
        return jnp.clip(i - off + jj, 0, ni - 1)

    y_t, m, l = pl.pallas_call(
        functools.partial(_fwd_band_kernel, n=n, k=k, blk=blk, off=off,
                          nbj=nbj, cd=cd),
        grid=(bsz, ni, nbj),
        in_specs=[
            pl.BlockSpec((None, 1, blk), lambda b, i, jj: (b, 0, i)),  # wr
            pl.BlockSpec((None, blk, 1),
                         lambda b, i, jj: (b, _col(b, i, jj), 0)),     # wc
            pl.BlockSpec((None, dsub, blk),
                         lambda b, i, jj: (b, 0, _col(b, i, jj))),     # xt
            pl.BlockSpec((1, 1), lambda b, i, jj: (0, 0)),             # tau
        ],
        out_specs=[
            pl.BlockSpec((None, dsub, blk), lambda b, i, jj: (b, 0, i)),
            pl.BlockSpec((None, 1, blk), lambda b, i, jj: (b, 0, i)),  # m
            pl.BlockSpec((None, 1, blk), lambda b, i, jj: (b, 0, i)),  # l
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, dsub, np_), cd),
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
        ],
        scratch_shapes=[pltpu.VMEM((dsub, blk), f32)],     # y accumulator
        interpret=interpret,
        compiler_params=params,
        name="softsort_band_fwd",
    )(wr, wc, xt, tau)

    colsum = pl.pallas_call(
        functools.partial(_colsum_band_kernel, n=n, k=k, blk=blk, off=off,
                          cd=cd),
        grid=(bsz, ni, nbj),
        in_specs=[
            pl.BlockSpec((None, 1, blk),
                         lambda b, j, ii: (b, 0, _col(b, j, ii))),     # wr
            pl.BlockSpec((None, blk, 1), lambda b, j, ii: (b, j, 0)),  # wc
            pl.BlockSpec((1, 1), lambda b, j, ii: (0, 0)),             # tau
            pl.BlockSpec((None, 1, blk),
                         lambda b, j, ii: (b, 0, _col(b, j, ii))),     # m
            pl.BlockSpec((None, 1, blk),
                         lambda b, j, ii: (b, 0, _col(b, j, ii))),     # l
        ],
        out_specs=pl.BlockSpec((None, blk, 1), lambda b, j, ii: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, np_, 1), f32),
        interpret=interpret,
        compiler_params=params,
        name="softsort_band_colsum",
    )(wr, wc, tau, m, l)

    return y_t, colsum, m, l


def _bwd_band_dws_delta_kernel(wr_ref, wc_ref, xt_ref, tau_ref, m_ref,
                               l_ref, dyt_ref, yt_ref, dc_ref, d_ref,
                               dws_ref, a_ref, srow_ref,
                               *, n: int, k: int, blk: int, off: int,
                               nbj: int, cd):
    """Fused delta + dws_row band sweep (the banded 3->2 merge), row
    grid (B, Ni, nbj), everything in the (Bc, Br) transposed layout:

      D_i = dy_i . y_i + sum_{r in band} P~_ir dc~_r   (delta, emitted)
      A_i = sum_r P~_ir dP~_ir sgn_ir                  (f32 scratch)
      S_i = sum_r P~_ir sgn_ir                         (f32 scratch)

    combined at the last band block into
      dws_row_i = -(A_i - D_i * S_i) / tau
    — one band re-score instead of the previous delta + dws pair."""
    i = pl.program_id(1)
    jj = pl.program_id(2)
    u = i - off + jj
    inv_tau = 1.0 / tau_ref[0, 0]
    mask = _band_mask(i, u, blk, k, n)
    _, p = _p_band_block(wr_ref, wc_ref, m_ref, l_ref, inv_tau, mask, cd)
    # dP~_ir = dy_i . xs_r + dc~_r, in (Bc, Br) transposed layout.
    dp = jax.lax.dot_general(
        xt_ref[...], dyt_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32) + dc_ref[...]
    sgn = jnp.sign(wr_ref[...] - wc_ref[...])

    @pl.when(jj == 0)
    def _init():
        d_ref[...] = jnp.sum(dyt_ref[...].astype(jnp.float32)
                             * yt_ref[...].astype(jnp.float32),
                             axis=0, keepdims=True)
        a_ref[...] = jnp.zeros_like(a_ref)
        srow_ref[...] = jnp.zeros_like(srow_ref)

    d_ref[...] += jax.lax.dot_general(
        dc_ref[...], p.astype(cd),
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32)                    # (1, Br)
    a_ref[...] += jnp.sum(p * dp * sgn, axis=0, keepdims=True)
    srow_ref[...] += jnp.sum(p * sgn, axis=0, keepdims=True)

    @pl.when(jj == nbj - 1)
    def _combine():
        dws_ref[...] = -(a_ref[...] - d_ref[...] * srow_ref[...]) \
            * inv_tau


def _bwd_band_dcol_kernel(wr_ref, wc_ref, xt_ref, tau_ref, m_ref, l_ref,
                          dyt_ref, dc_ref, d_ref, dxt_ref, dwc_ref, dtc_ref,
                          acc_ref, *, n: int, k: int, blk: int, off: int,
                          nbj: int, cd):
    """Column grid (B, Nj, nbi): per column block accumulate
    dxs_t_r = sum_i P~_ir dy_i (f32 scratch, written once in the
    compute dtype), dws_col_r = sum_i ds_ir sgn_ir / tau, and the
    per-column dtau partial."""
    j = pl.program_id(1)
    ii = pl.program_id(2)
    iu = j - off + ii
    inv_tau = 1.0 / tau_ref[0, 0]
    mask = _band_mask(iu, j, blk, k, n)
    s, p = _p_band_block(wr_ref, wc_ref, m_ref, l_ref, inv_tau, mask, cd)
    # dP~_ir = dy_i . xs_r + dc~_r, in (Bc, Br) transposed layout.
    dp = jax.lax.dot_general(
        xt_ref[...], dyt_ref[...],
        dimension_numbers=(((0,), (0,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32) + dc_ref[...]
    ds = p * (dp - d_ref[...])                                 # (Bc, Br)
    sgn = jnp.sign(wr_ref[...] - wc_ref[...])                  # ws_i - ws_r

    @pl.when(ii == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        dwc_ref[...] = jnp.zeros_like(dwc_ref)
        dtc_ref[...] = jnp.zeros_like(dtc_ref)

    acc_ref[...] += jax.lax.dot_general(
        dyt_ref[...], p.astype(cd),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=_mxu_precision(cd),
        preferred_element_type=jnp.float32)                    # (dsub, Bc)
    dwc_ref[...] += jnp.sum(ds * sgn, axis=1, keepdims=True) * inv_tau
    # ds == 0 exactly on masked slots and NEG_INF is finite, so the
    # 0 * (-NEG_INF) products below are exact zeros.
    dtc_ref[...] += jnp.sum(ds * (-s), axis=1, keepdims=True) * inv_tau

    @pl.when(ii == nbj - 1)
    def _flush():
        dxt_ref[...] = acc_ref[...].astype(dxt_ref.dtype)


def softsort_apply_bwd_banded_pallas(
    wr: jnp.ndarray,      # (B, 1, Np) sorted keys (rows), padded, f32
    wc: jnp.ndarray,      # (B, Np, 1) sorted keys (cols), padded, f32
    xt: jnp.ndarray,      # (B, dsub, Np) payload, sorted + transposed, cd
    tau: jnp.ndarray,     # (1, 1)
    m: jnp.ndarray,       # (B, 1, Np) saved row maxes, f32
    l: jnp.ndarray,       # (B, 1, Np) saved row denominators, f32
    yt: jnp.ndarray,      # (B, dsub, Np) saved forward output, transposed, cd
    dyt: jnp.ndarray,     # (B, dsub, Np) cotangent of y, transposed, cd
    dc: jnp.ndarray,      # (B, Np, 1) cotangent of colsum, rank order, cd
    *,
    n: int,
    k: int,
    blk: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Banded backward from saved residuals — TWO band-grid passes.

    Pass 1 (row band grid) fuses the old delta pass into the dws_row
    pass: one band sweep emits D (consumed by pass 2) AND dws_row.
    Pass 2 (column band grid) produces the column-indexed dxs_t /
    dws_col / dtau_cols.

    Returns (dws_row (B, 1, Np), dws_col (B, Np, 1) — the key gradient's
    row and column components, both f32 and in RANK order, summed and
    scattered through ``perm`` by the caller; dxs_t (B, dsub, Np) —
    payload gradient in rank order, transposed, in the compute dtype;
    dtau_cols (B, Np, 1) f32)."""
    bsz, dsub, np_ = xt.shape
    ni = np_ // blk
    off = -(-k // blk)
    nbj = 2 * off + 1
    f32 = jnp.float32
    cd = xt.dtype
    params = _band_compiler_params(dsub, blk, cd)

    def _col(b, i, jj):
        return jnp.clip(i - off + jj, 0, ni - 1)

    # Row-aligned operand specs (row grid: i outer, jj band step inner).
    row_keys = pl.BlockSpec((None, 1, blk), lambda b, i, jj: (b, 0, i))
    row_pay = pl.BlockSpec((None, dsub, blk), lambda b, i, jj: (b, 0, i))
    band_cols = pl.BlockSpec((None, blk, 1),
                             lambda b, i, jj: (b, _col(b, i, jj), 0))
    band_pay = pl.BlockSpec((None, dsub, blk),
                            lambda b, i, jj: (b, 0, _col(b, i, jj)))
    band_keys = pl.BlockSpec((None, 1, blk),
                             lambda b, i, jj: (b, 0, _col(b, i, jj)))
    tau_spec = pl.BlockSpec((1, 1), lambda b, i, jj: (0, 0))

    # Fused delta+dws_row band sweep; A/S partial sums in f32 scratch.
    delta, dws_row = pl.pallas_call(
        functools.partial(_bwd_band_dws_delta_kernel, n=n, k=k, blk=blk,
                          off=off, nbj=nbj, cd=cd),
        grid=(bsz, ni, nbj),
        in_specs=[row_keys, band_cols, band_pay, tau_spec, row_keys,
                  row_keys, row_pay, row_pay, band_cols],
        out_specs=[row_keys, row_keys],                    # D, dws_row
        out_shape=[
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
            jax.ShapeDtypeStruct((bsz, 1, np_), f32),
        ],
        scratch_shapes=[pltpu.VMEM((1, blk), f32),         # A
                        pltpu.VMEM((1, blk), f32)],        # S
        interpret=interpret,
        compiler_params=params,
        name="softsort_band_bwd_delta",
    )(wr, wc, xt, tau, m, l, dyt, yt, dc)

    # Column grid (j outer, band row step inner): the column-indexed
    # outputs (dxs_t via scratch, dws_col, dtau_cols) accumulate in VMEM.
    col_keys = pl.BlockSpec((None, blk, 1), lambda b, j, ii: (b, j, 0))
    col_pay = pl.BlockSpec((None, dsub, blk), lambda b, j, ii: (b, 0, j))
    dxt, dwc, dtc = pl.pallas_call(
        functools.partial(_bwd_band_dcol_kernel, n=n, k=k, blk=blk,
                          off=off, nbj=nbj, cd=cd),
        grid=(bsz, ni, nbj),
        in_specs=[band_keys, col_keys, col_pay, tau_spec, band_keys,
                  band_keys, band_pay, col_keys, band_keys],
        out_specs=[col_pay, col_keys, col_keys],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, dsub, np_), cd),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
        ],
        scratch_shapes=[pltpu.VMEM((dsub, blk), f32)],     # dxt accumulator
        interpret=interpret,
        compiler_params=params,
        name="softsort_band_bwd_dcol",
    )(wr, wc, xt, tau, m, l, dyt, dc, delta)

    return dws_row, dwc, dxt, dtc


# --------------------------------------------------------------------------
# v1 split forward (stats + apply + colsum, three pallas_calls) — kept as
# the measured baseline for benchmarks/kernel_bench.py.  Not used by the
# production path; f32 only.
# --------------------------------------------------------------------------

def _stats_kernel(ws_ref, w_ref, tau_ref, m_ref, l_ref, *, n: int, bc: int):
    j = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    s = _score(ws_ref[...], w_ref[...], inv_tau, jnp.float32)  # (Br, Bc)
    s = jnp.where(_col_mask(j, bc, n), s, NEG_INF)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    m_prev = m_ref[...]                                        # (Br, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * correction + jnp.sum(
        jnp.exp(s - m_new), axis=-1, keepdims=True)
    m_ref[...] = m_new


def _apply_kernel(ws_ref, w_ref, x_ref, tau_ref, m_ref, l_ref, y_ref,
                  *, n: int, bc: int):
    j = pl.program_id(2)
    inv_tau = 1.0 / tau_ref[0, 0]
    s = _score(ws_ref[...], w_ref[...], inv_tau, jnp.float32)
    s = jnp.where(_col_mask(j, bc, n), s, NEG_INF)
    p = jnp.exp(s - m_ref[...]) / jnp.maximum(l_ref[...], 1e-30)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y_ref[...] += jnp.dot(p, x_ref[...], preferred_element_type=jnp.float32)


def softsort_apply_fwd_pallas_v1(
    ws: jnp.ndarray,
    w: jnp.ndarray,
    x: jnp.ndarray,
    tau: jnp.ndarray,
    *,
    n: int,
    br: int,
    bc: int,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """v1 baseline forward: three passes (stats, apply, colsum), scores
    computed three times, m/l round-tripping through HBM between passes.
    Returns (y (B, Np, dp), colsum (B, 1, Np))."""
    bsz, np_, dp = x.shape
    ni, nj = np_ // br, np_ // bc
    f32 = jnp.float32

    m, l = pl.pallas_call(
        functools.partial(_stats_kernel, n=n, bc=bc),
        grid=(bsz, ni, nj),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # ws rows
            pl.BlockSpec((None, 1, bc), lambda b, i, j: (b, 0, j)),   # w cols
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),             # tau
        ],
        out_specs=[
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # l
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
            jax.ShapeDtypeStruct((bsz, np_, 1), f32),
        ],
        interpret=interpret,
    )(ws, w, tau)

    y = pl.pallas_call(
        functools.partial(_apply_kernel, n=n, bc=bc),
        grid=(bsz, ni, nj),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # ws
            pl.BlockSpec((None, 1, bc), lambda b, i, j: (b, 0, j)),   # w
            pl.BlockSpec((None, bc, dp), lambda b, i, j: (b, j, 0)),  # x block
            pl.BlockSpec((1, 1), lambda b, i, j: (0, 0)),             # tau
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, i, j: (b, i, 0)),   # l
        ],
        out_specs=pl.BlockSpec((None, br, dp), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, np_, dp), f32),
        interpret=interpret,
    )(ws, w, x, tau, m, l)

    colsum = pl.pallas_call(
        functools.partial(_colsum_kernel, n=n, br=br, bc=bc,
                          cd=jnp.float32),
        grid=(bsz, nj, ni),
        in_specs=[
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # ws
            pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),   # w
            pl.BlockSpec((1, 1), lambda b, j, i: (0, 0)),             # tau
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # m
            pl.BlockSpec((None, br, 1), lambda b, j, i: (b, i, 0)),   # l
        ],
        out_specs=pl.BlockSpec((None, 1, bc), lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, 1, np_), f32),
        interpret=interpret,
    )(ws, w, tau, m, l)

    return y, colsum
