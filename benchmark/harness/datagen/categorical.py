"""Bin codes of the Criteo schema with the ID fields as categories.

The schema is the Criteo Display Advertising Challenge sample's, as the
XGBoost paper's terabyte job has it (KDD'16, Table 2 and sec. 6.6): the 13
integer fields first, then the 26 categorical fields C1-C26, each as its own
category code, then a count of each field's category, then dense features.

* Integer features: uniform codes over ``0..bins-1``.
* Categorical field ``c``: a category is drawn from a Zipf law of exponent
  ``exponent`` over ``vocabulary`` categories; the ``bins - 1`` most frequent
  take codes ``0..bins-2`` in an order drawn from the seed (a hash of the
  category, not its rank), and code ``bins - 1`` is "every other category,
  and empty": the tail past them, and ``cat_shares[c]`` per cent of the rows
  where the field is empty.
* Its count: a monotone bucket of the category's frequency, ``bins - 1 -
  rank`` for the most frequent ``bins - 1`` and 0 for "other", so that a
  threshold on a count is a run of frequency ranks.
* The features past them: uniform codes.

The label is ``uniform``'s — a step on feature ``label_on[0]``, a slope on
feature ``label_on[1]``, noise — plus, on each field of ``effect_on``
(feature indices), ``weight`` times an effect a category drawn from the
seed, standard normal and so not monotone in frequency (0 on "other"): the
best split there is a set of codes that no threshold on any feature forms.
Numpy only; the same seed gives the same bytes.
"""

from __future__ import annotations

import numpy as np


def make(rows: int, features: int, bins: int, seed: int, *, cat_shares: list,
         n_int: int, exponent: float, vocabulary: int, label_on: list,
         effect_on: list, weight: float):
    """``(codes[rows, features] uint8, y[rows] float32)`` for any whole
    ``seed``."""
    n_cat = len(cat_shares)
    fields = range(n_int, n_int + n_cat)
    if bins > 256 or features < n_int + 2 * n_cat or vocabulary < bins or any(
            not 0 <= f < features for f in label_on) or any(
            f not in fields for f in effect_on):
        raise ValueError(f"{features} features x {bins} bins hold no {n_int} "
                         f"integer and 2 x {n_cat} categorical ones in a byte "
                         f"a code over {vocabulary} categories, or label_on "
                         f"{label_on} / effect_on {effect_on} lie outside them")
    rng = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF)
    other = bins - 1
    codes = rng.integers(0, bins, size=(rows, features), dtype=np.uint8)
    cdf = np.cumsum(1.0 / np.arange(1, vocabulary + 1) ** exponent)
    cdf = (cdf / cdf[-1]).astype(np.float32)
    count_of = np.zeros(bins, np.uint8)              # a field's code -> its count
    effects = {}
    for c, share in enumerate(cat_shares):
        rank = np.searchsorted(cdf, rng.random(rows, dtype=np.float32))
        code_of = rng.permutation(other).astype(np.uint8)   # rank -> code
        col = np.where(rank < other, code_of[np.minimum(rank, other - 1)], other)
        if share:
            col[rng.random(rows, dtype=np.float32) < np.float32(share / 100)] = other
        count_of[code_of] = other - np.arange(other)
        count_of[other] = 0
        codes[:, n_int + c] = col
        codes[:, n_int + n_cat + c] = count_of[col]
        effect = rng.standard_normal(bins).astype(np.float32)
        effect[other] = 0
        effects[n_int + c] = effect
    step = (codes[:, label_on[0]] > bins // 2).astype(np.float32)
    slope = np.float32(2.56 / bins) * codes[:, label_on[1]].astype(np.float32)
    noise = rng.standard_normal(rows, dtype=np.float32)
    moved = sum(effects[f][codes[:, f]] for f in effect_on)
    y = (step + slope + noise + np.float32(weight) * moved > 1.5).astype(np.float32)
    return codes, y
