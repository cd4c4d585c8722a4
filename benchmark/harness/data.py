"""The benchmark's data: quantised features and labels from ``--seed``.

The yardstick's own copy of ``bench.make_data`` (numpy only, so the parent
and the device worker make the same bytes without sharing a file).  Bin
codes are drawn uniformly; the label follows two of the features plus
noise, so a tree finds one strong split, a smooth one and then noise —
near-ties included, which is what the comparison has to live with.  The
codes come back in the narrowest unsigned type that holds ``bins``; the
worker widens them to the int32 the program's kernels take.
"""

from __future__ import annotations

import numpy as np


def make_data(rows: int, features: int, bins: int, seed: int):
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


def block_host(x: np.ndarray, block: int) -> np.ndarray:
    """Rows padded with zeros to whole blocks, ``(blocks, block, width)``:
    the layout ``rabit_tpu.ops.boost.block_rows`` gives, made on the host so
    that a sharded matrix never passes through one device on its way to
    four."""
    n = x.shape[0]
    x = x.reshape(n, -1)
    pad = -n % block
    if pad:
        x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
    return x.reshape(-1, block, x.shape[1])
