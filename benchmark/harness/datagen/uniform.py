"""Uniform dense bin codes and a 0/1 label: the data of every configuration
accepted so far.

Numpy only, so the parent and the device worker make the same bytes without
sharing a file.  Bin codes are drawn uniformly; the label follows two of
the features plus noise, so a tree finds one strong split, a smooth one and
then noise — near-ties included, which is what the comparison has to live
with.  The codes come back in the narrowest unsigned type that holds
``bins``; the worker widens them to the int32 the program's kernels take.
"""

from __future__ import annotations

import numpy as np


def make(rows: int, features: int, bins: int, seed: int):
    """``(codes[rows, features], y[rows] float32)`` for any whole ``seed``
    (the driver's run a little past 2**31)."""
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF)
    dtype = np.uint8 if bins <= 256 else np.uint16
    codes = rng.integers(0, bins, size=(rows, features), dtype=dtype)
    step = (codes[:, 0] > bins // 2).astype(np.float32)
    slope = np.float32(2.56 / bins) * codes[:, 1].astype(np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    y = (step + slope + noise > 1.5).astype(np.float32)
    return codes, y
