"""The work one SoftSort apply needs, counted from its shapes.

A roofline share divides the least time the chip could take for a call
by the time the call took.  The least time comes from the operations and
bytes the mathematics needs, never from what an implementation happens
to move: block sizes, padding, pass counts and recomputation do not
count, so a kernel change cannot move the yardstick.

The apply computes, for B independent instances of N keys ``w`` and an
(N, d) payload ``x`` at temperature ``tau``,

    s_ij = -|sort(w)_i - w_j| / tau        (only |rank_j - i| <= K: banded)
    P    = softmax_rows(s)
    y    = P @ x,   c = colsum(P)

and its gradient takes (dy, dc) to (dw, dx, dtau).  Per scored pair:

    forward   scores 3 (sub, abs, scale), softmax 5 (max, sub, exp, sum,
              normalise), y 2d (multiply-add per feature), colsum 1
    backward  dP = dy_i . x_j + dc_j   2d + 1
              dx = P^T dy              2d
              ds = P (dP - D_i)        4   (D_i accumulate 2, ds 2)
              dw row + column parts    4   (sign, scale, two sums)
              dtau                     2

Bytes are the operands and results each pass must read or write once:
keys and colsum in f32, payload-sided arrays in the compute dtype, the
outputs ``y``/``dx``/``dw`` in f32.
"""
from __future__ import annotations

FWD_PER_PAIR = 9          # 3 score + 5 softmax + 1 colsum
FWD_PER_PAIR_D = 2        # y = P @ x
BWD_PER_PAIR = 11         # 1 (dP bias) + 4 (ds) + 4 (dw) + 2 (dtau)
BWD_PER_PAIR_D = 4        # dP dot + dx = P^T dy

DTYPE_BYTES = {"float32": 4, "bfloat16": 2}


def pairs(n: int, k: int | None) -> int:
    """Scored (row, column) pairs of one instance: N^2 dense, or the
    |i - j| <= K band of an N x N matrix, edges truncated."""
    if k is None or k >= n - 1:
        return n * n
    return n * (2 * k + 1) - k * (k + 1)


def apply_flops(b: int, n: int, k: int | None, d: int,
                grad: bool = True) -> int:
    """Operations of one apply over B instances; ``grad`` adds the
    backward that the train step's value_and_grad runs."""
    per = FWD_PER_PAIR + FWD_PER_PAIR_D * d
    if grad:
        per += BWD_PER_PAIR + BWD_PER_PAIR_D * d
    return b * pairs(n, k) * per


def apply_bytes(b: int, n: int, d: int, dtype: str = "float32",
                grad: bool = True) -> int:
    """HBM bytes one apply over B instances must move at the least:
    forward reads w, x and writes y, c; backward reads w, x, dy, dc and
    writes dw, dx."""
    pay = DTYPE_BYTES[dtype]
    fwd = n * 4 + n * d * pay + n * d * 4 + n * 4
    bwd = n * 4 + n * d * pay + n * d * pay + n * 4 + n * 4 + n * d * 4
    return b * (fwd + (bwd if grad else 0))


def roofline_seconds(flops: float, nbytes: float,
                     peak: dict) -> tuple[float, str]:
    """Least time for the counted work on a chip with ``peak``
    (``flops_per_s``, ``hbm_bytes_per_s``), and which bound sets it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
