"""Seeded stand-ins for an MoE checkpoint's expert projections.

Trained weight matrices have a low effective rank: their columns share
a few directions and differ by small independent parts.  Each tensor
here is drawn so, from a numpy ``Generator`` so that any whole-number
seed works.
"""
from __future__ import annotations

import numpy as np


def low_rank(rows: int, cols: int, rank: int, noise: float, std: float,
             rng: np.random.Generator) -> np.ndarray:
    """(rows, cols) float32 whose column j is ``U z_j + e_j``: ``U``
    (rows, rank) is the tensor's own factor, ``z_j`` standard normal,
    ``e_j`` noise at ``noise`` times the factor's scale; the whole is
    scaled to standard deviation ``std``."""
    u = rng.standard_normal((rows, rank), dtype=np.float32)
    z = rng.standard_normal((rank, cols), dtype=np.float32)
    w = u @ z
    w *= 1.0 / np.sqrt(rank)
    w += noise * rng.standard_normal((rows, cols), dtype=np.float32)
    w *= std / w.std()
    return w
