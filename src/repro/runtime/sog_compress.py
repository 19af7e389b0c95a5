"""Self-organizing checkpoint compression — the paper's SOG story applied
to LM checkpoints.

Each 2-D weight (D, F) is treated as F column vectors of width D;
ShuffleSoftSort arranges them on a grid maximizing neighbour correlation
(storing only the F permutation indices — the paper's N-parameter
claim), then the permuted tensor is int8-quantized, delta-encoded along
the sorted order and deflated.  Correlated columns (the common case in
trained nets: duplicated/co-adapted features) compress measurably better
after sorting; the permutation costs 4F bytes.

Tensors of one shape are compressed as a group (``compress_group``): one
``shuffle_soft_sort_batched`` call sorts all of them on their whole
columns through the kernel tier and its banded apply, then each sorted
tensor is encoded on the host.  A checkpoint is a sequence of such
groups (``compress_checkpoint``), each as large as the device's memory
allows (``plan_group``); one tensor is a group of one
(``sog_compress_tensor``).  Columns wider than the banded kernels hold
are sorted on evenly spaced rows of theirs, and coded whole.

This is an opt-in codec for CheckpointManager-style storage; round-trip
is exact at the int8 quantization level.
"""
from __future__ import annotations

import concurrent.futures
import os
import zlib
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.shufflesoftsort import (
    ShuffleSoftSortConfig,
    shuffle_soft_sort_batched,
)
from repro.kernels.softsort_apply import band_max_width

# Device bytes a group takes per byte of its float32 sort features: the
# engine program's arguments and temporaries (10x to 11.4x in the v5e
# compile, from 8 tensors of 4096 x 4096 to one of 262144 columns 625
# wide) and the codec's stacked copy.
WORKING_SET = 12


def _grid_hw(n: int) -> tuple[int, int]:
    """Near-square sorting grid for n columns; h * w >= n.

    Prefers an exact factorization when a near-square one exists
    (aspect ratio <= 2, no padding).  Otherwise — prime n, or n whose
    largest divisor <= sqrt(n) is tiny — walking h down degenerates
    toward a 1 x n grid whose "neighborhood" is a line, which defeats
    the 2-D neighbor loss entirely.  For those n we return a padded
    ceil(sqrt) x ceil grid instead (h * w - n < h extra cells); callers
    pad the feature rows and drop pad indices from the returned
    permutation (see ``compress_group``).
    """
    h = int(np.sqrt(n))
    while n % h:
        h -= 1
    if n // h <= 2 * h:
        return h, n // h
    h = int(np.ceil(np.sqrt(n)))
    return h, int(np.ceil(n / h))


def device_budget() -> int:
    """Bytes one group may take on the device: half of the default
    device's memory (``memory_stats()["bytes_limit"]``), or of a v5e's
    16 GiB where the backend reports none."""
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 16 * 2**30)) // 2


def plan_group(d: int, f: int, budget: int) -> tuple[int, int]:
    """(width, tensors) for (D, F) tensors: how many rows of each column
    are sorted on, and how many tensors one ``compress_group`` call sorts
    within ``budget`` bytes of device memory.  The width is D, cut to
    the widest payload the banded kernels hold (``band_max_width``) and
    to what one tensor's working set (``WORKING_SET`` x its features)
    leaves of the budget."""
    h, w = _grid_hw(f)
    per_row = WORKING_SET * h * w * 4
    width = max(1, min(d, band_max_width(4), budget // per_row))
    return width, max(1, budget // (per_row * width))


def codec_config(rounds: int = 80) -> ShuffleSoftSortConfig:
    """The codec's anneal: ``rounds`` rounds of 4 inner steps on the
    Pallas kernel tier, banded once the anneal is cold enough."""
    return ShuffleSoftSortConfig(rounds=rounds, inner_steps=4,
                                 use_kernel=True, band="auto")


def _quantize(w: np.ndarray) -> tuple[np.ndarray, float]:
    scale = float(np.max(np.abs(w))) / 127.0 + 1e-12
    return np.clip(np.round(w / scale), -127, 127).astype(np.int8), scale


def _encode(q: np.ndarray) -> bytes:
    # delta along the sorted (column) axis (first row kept verbatim via a
    # zero prepend), wrapped mod 256 — lossless for int8 payloads — then
    # deflate.
    delta = np.diff(q.astype(np.int16), axis=0,
                    prepend=np.zeros((1, q.shape[1]), np.int16))
    return zlib.compress(delta.astype(np.int8).tobytes(), level=6)


def _baseline_bytes(w: np.ndarray) -> int:
    """Deflated size of ``w`` (D, F) coded as the codec would code it
    with its columns unsorted."""
    q, _ = _quantize(w)
    return len(_encode(q.T))


def _blob(w: np.ndarray, rows: np.ndarray | None, order: np.ndarray,
          losses: np.ndarray, baseline) -> dict:
    """Quantize, delta-code and deflate the columns of ``w`` (D, F) in
    ``order``: ``rows`` (M, D) are the sorted whole columns (None:
    gathered here from ``w``), ``order`` (M,) their columns, pad entries
    (>= F) included; ``baseline`` is the future of
    ``_baseline_bytes(w)``."""
    d, f = w.shape
    real = order < f
    if not real.all():
        order = order[real]
        rows = None if rows is None else rows[real]
    if rows is None:
        rows = w.T[order]
    q_sorted, scale = _quantize(rows)                    # (F, D) sorted
    payload = _encode(q_sorted)
    return {
        "payload": payload,
        "perm": order.astype(np.int32),
        "scale": scale,
        "shape": (d, f),
        "bytes": len(payload) + 4 * f,                  # + stored permutation
        "baseline_bytes": baseline.result(),
        "raw_bytes": w.nbytes,
        "losses": losses,
    }


def compress_group(ws: Sequence, *, cfg: ShuffleSoftSortConfig,
                   keys) -> list[dict]:
    """Compress G same-shape 2-D tensors (D, F) -> G blob dicts.

    All G are sorted in one ``shuffle_soft_sort_batched`` call on the
    grid ``_grid_hw(F)``, each on its whole columns ``w.T`` (F, D) with
    instance key ``keys[g]`` ((G, 2) uint32), one restart each; columns
    wider than ``plan_group``'s width are sorted on that many evenly
    spaced rows, and the caller keeps G within its tensor count.  Then
    each sorted tensor is quantized, delta-coded and deflated on the host,
    the G in parallel threads (zlib and numpy release the GIL).  Each
    blob carries the unsorted baseline size so callers can report the
    SOG gain (coded in the same threads while the device sorts), and
    the anneal's per-round loss (``losses``, (R,)) for auditing; neither
    is part of what ``sog_decompress_tensor`` reads.

    Host spans in the profiler's trace: ``compress.group`` (args
    ``tensors``, ``n``, ``d``, the width sorted on) around the call,
    holding ``compress.sort`` (the engine call, with its ``sort.*``
    spans) and ``compress.encode``.
    """
    ws = [np.asarray(jax.device_get(w), np.float32) for w in ws]
    if not ws or ws[0].ndim != 2 or any(w.shape != ws[0].shape
                                        for w in ws):
        raise ValueError(f"compress_group needs same-shape 2-D tensors, "
                         f"got {[w.shape for w in ws]}")
    d, f = ws[0].shape
    hw = _grid_hw(f)
    m = hw[0] * hw[1]
    width, _ = plan_group(d, f, device_budget())
    rows = np.arange(width) * d // width
    span = jax.profiler.TraceAnnotation
    workers = min(len(ws), os.cpu_count() or 1)
    with span("compress.group", tensors=len(ws), n=f, d=width), \
            concurrent.futures.ThreadPoolExecutor(workers) as pool:
        # The unsorted baseline needs no order, so it is coded while the
        # device sorts.
        baselines = [pool.submit(_baseline_bytes, w) for w in ws]
        # Columns as features, transposed on the device.
        feats = jnp.swapaxes(jnp.stack(
            [jnp.asarray(w if width == d else w[rows]) for w in ws]), 1, 2)
        if m > f:
            # Padded grid (f prime or near-prime): replicate trailing
            # columns as pad features — maximally correlated with real
            # columns, so they cluster beside their twins without
            # distorting the layout — then drop the pad indices, leaving
            # a permutation of the real f columns in grid-scan order.
            feats = jnp.concatenate([feats, feats[:, f - (m - f):]], axis=1)
        with span("compress.sort"):
            res = shuffle_soft_sort_batched(feats, hw, cfg, keys=keys)
        # Cut columns are coded whole, gathered on the host.
        sorted_ = res.sorted if width == d else [None] * len(ws)
        with span("compress.encode"):
            return list(pool.map(_blob, ws, sorted_, res.order,
                                 res.losses, baselines))


def sog_compress_tensor(w, *, sort_rounds: int = 120, key=None) -> dict:
    """Compress one 2-D tensor (D, F) -> blob dict: a group of one."""
    key = key if key is not None else jax.random.PRNGKey(0)
    return compress_group([w], cfg=codec_config(sort_rounds),
                          keys=np.asarray(key)[None])[0]


def sog_decompress_tensor(blob: dict) -> np.ndarray:
    d, f = blob["shape"]
    raw = zlib.decompress(blob["payload"])
    delta = np.frombuffer(raw, np.int8).reshape(f, d).astype(np.int32)
    q = np.cumsum(delta, axis=0).astype(np.int8)   # mod-256 wrap == exact
    wt = q.astype(np.float32) * blob["scale"]            # (F, D) sorted
    out = np.empty_like(wt)
    out[blob["perm"]] = wt                               # invert permutation
    return out.T                                         # (D, F)


def compress_checkpoint(params: Any, *, min_cols: int = 64,
                        sort_rounds: int = 80) -> dict:
    """Compress every >=2-D weight in a param pytree; returns stats and
    the blobs.  Tensors are flattened to 2-D (leading dims merged) and
    grouped by that shape, in leaf order, as many per ``compress_group``
    call as ``plan_group`` fits in ``device_budget()``.  Leaf i's key is
    the i-th split of ``PRNGKey(7)`` over the compressed leaves, whatever
    its group."""
    flat, treedef = jax.tree.flatten(params)
    groups: dict[tuple, list] = {}
    key = jax.random.PRNGKey(7)
    for i, leaf in enumerate(flat):
        shape = np.shape(leaf)
        if len(shape) >= 2 and shape[-1] >= min_cols:
            key, sub = jax.random.split(key)
            shape2 = (int(np.prod(shape[:-1])), shape[-1])
            groups.setdefault(shape2, []).append((i, np.asarray(sub)))
    cfg = codec_config(sort_rounds)
    budget = device_budget()
    blobs: list = [None] * len(flat)
    for shape2, members in groups.items():
        _, size = plan_group(*shape2, budget)
        for lo in range(0, len(members), size):
            idx, keys = zip(*members[lo:lo + size])
            ws = [np.asarray(jax.device_get(flat[i]),
                             np.float32).reshape(shape2) for i in idx]
            for i, blob in zip(idx, compress_group(ws, cfg=cfg,
                                                   keys=np.stack(keys))):
                blobs[i] = blob
    stats = {"sog_bytes": 0, "baseline_bytes": 0, "raw_bytes": 0}
    for blob in filter(None, blobs):
        stats["sog_bytes"] += blob["bytes"]
        stats["baseline_bytes"] += blob["baseline_bytes"]
        stats["raw_bytes"] += blob["raw_bytes"]
    stats["gain_vs_baseline"] = (
        stats["baseline_bytes"] / max(stats["sog_bytes"], 1))
    stats["ratio_vs_raw"] = stats["raw_bytes"] / max(stats["sog_bytes"], 1)
    return {"blobs": blobs, "treedef": treedef, "stats": stats}
