"""Clustered feature vectors standing for an image catalogue (paper
section IV-A: 50-dim low-level visual features of product images).

Copied from ``examples/image_grid_sorting.py`` (``synthetic_catalog``),
drawn from a numpy ``Generator`` so that any whole-number seed works.
"""
from __future__ import annotations

import numpy as np


def catalog(n: int, d: int, clusters: int, spread: float,
            rng: np.random.Generator) -> np.ndarray:
    """(n, d) float32: ``clusters`` category centres, each item a centre
    plus isotropic noise of scale ``spread``."""
    centers = rng.standard_normal((clusters, d)) * 2.0
    labels = rng.integers(0, clusters, n)
    x = centers[labels] + spread * rng.standard_normal((n, d))
    return x.astype(np.float32)
