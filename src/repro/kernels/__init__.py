# Pallas TPU kernel layer for the paper's compute hot spot: the fused
# SoftSort apply (P_soft @ x, colsum(P_soft)) streamed flash-attention
# style — forward AND backward — plus the flash attention used by the
# LM serving workloads.
#
#   ops.py              — public custom-VJP wrappers ``softsort_apply``
#                         (exact, O(N^2) compute streamed in O(N*block)
#                         memory) and ``softsort_apply_banded`` (O(N*K)
#                         compute AND traffic: both axes gathered into
#                         sorted-rank order, only a width-(2K+1) band
#                         scored, tail mass bounded by
#                         ``core.softsort.band_tail_bound``); both accept
#                         (N,)/(N, d) or batched (B, N)/(B, N, d), a
#                         ``compute_dtype`` ("float32"/"bfloat16" —
#                         bf16 scores/payload, f32 keys/stats/
#                         accumulators), and save (perm, m, l, y)
#                         residuals so the backward never re-sorts or
#                         re-normalizes.  Block sizes default to the
#                         committed autotune table.
#                         ``softsort_apply_v1`` keeps the previous
#                         3-pass-fwd / jnp-scan-bwd design as the
#                         benchmark baseline (benchmarks/kernel_bench.py)
#   softsort_apply.py   — the kernels: fused online-softmax forward
#                         (2 pallas_calls) + 2-pass backward (the delta
#                         pass is merged into the dws sweep; batch =
#                         outermost grid dim everywhere), plus the banded
#                         variants whose grids visit only the
#                         2*ceil(K/blk)+1 band blocks per row block
#   autotune.py         — per-(N, d, K, dtype, backend) block-size
#                         search + the committed ``autotune_table.json``
#                         consulted at dispatch (hardcoded fallback)
#   ref.py              — O(N^2) pure-jnp oracle the tests assert against
#
# Kernels self-select ``interpret=True`` on CPU, so this package works
# (slowly) on CPU — CI exercises exactly that path.
from repro.kernels.autotune import lookup_blocks  # noqa: F401
from repro.kernels.ops import (  # noqa: F401
    softsort_apply,
    softsort_apply_banded,
    softsort_apply_v1,
)
from repro.kernels.ref import softsort_apply_ref  # noqa: F401
