"""Dense bin codes in which one reserved code is tied over many rows.

What ``uniform.py`` cannot show: a feature whose rows pile up on one code —
the bin a missing value, a zero count or a clipped tail is given — so that a
split there sends a sliver one way and the rest the other, and a parent's two
children are unequal.  Features 0 and 1 are as ``uniform`` has them.  Feature
``f >= 2`` holds the reserved code ``code`` in ``shares[(f - 2) % len(shares)]``
per cent of its rows (plus the reserved code's own share of the uniform
rest); its other rows are uniform over all codes.  The label is
``uniform``'s — a step on feature 0, a slope on feature 1, noise — moved by
``weight`` where feature ``label_on[0]`` holds the reserved code and by
``-weight`` where feature ``label_on[1]`` does not, so that trees split on
the tie itself.  Numpy only; the same seed gives the same bytes.
"""

from __future__ import annotations

import numpy as np


def make(rows: int, features: int, bins: int, seed: int, *, shares: list,
         label_on: list, weight: float, code: int = 0):
    """``(codes[rows, features], y[rows] float32)`` for any whole ``seed``."""
    if not 0 <= code < bins or any(not 2 <= f < features for f in label_on):
        raise ValueError(f"code {code} of {bins} bins, label_on {label_on} of "
                         f"features 2..{features - 1}")
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF)
    dtype = np.uint8 if bins <= 256 else np.uint16
    codes = rng.integers(0, bins, size=(rows, features), dtype=dtype)
    for f in range(2, features):
        share = shares[(f - 2) % len(shares)]
        if share:
            tied = rng.random(rows, dtype=np.float32) < np.float32(share / 100)
            codes[tied, f] = code
    step = (codes[:, 0] > bins // 2).astype(np.float32)
    slope = np.float32(2.56 / bins) * codes[:, 1].astype(np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    first, second = (codes[:, f] == code for f in label_on)
    moved = np.float32(weight) * (first.astype(np.float32)
                                  - (~second).astype(np.float32))
    y = (step + slope + noise + moved > 1.5).astype(np.float32)
    return codes, y
