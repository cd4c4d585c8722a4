"""What one boosting round has to do, whatever does it: the algorithm's
operations and bytes, and the least time a chip could take for them.

A histogram pass over ``n`` rows reads every row's bin codes (one byte a
code up to 256 bins, two beyond), its gradient, its hessian and its node
id, writes the node id of the next level, does one addition of ``g`` and
one of ``h`` for every code, and writes the histogram.  Level 0 reads no
node id.  The leaf pass reads the codes, the node id and the margin and
writes the margin.  Nothing here knows of one-hot contractions, padding or
kernels: a program that does the pass another way has the same count.
``issued_onehot_flops`` is the one exception, an implementation's count,
which is printed beside the metrics and is never one.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = json.loads(Path(__file__).with_name("peaks.json").read_text())


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device that is not in the table is an error."""
    row = PEAKS.get(device_kind)
    if not isinstance(row, dict):
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/harness/peaks.json: add the row with its "
                       "source, never a default")
    return row


def code_bytes(bins: int) -> int:
    return 1 if bins <= 256 else 2


def hist_pass(n: int, features: int, bins: int, level: int) -> dict:
    """One histogram pass at ``level`` (0 is the root) over ``n`` rows."""
    per_row = features * code_bytes(bins) + 4 + 4 + 4 + (4 if level else 0)
    written = (2 ** level) * features * bins * 2 * 4
    return {"adds": 2 * n * features, "bytes": n * per_row + written}


def leaf_pass(n: int, features: int, bins: int) -> dict:
    return {"adds": n, "bytes": n * (features * code_bytes(bins) + 4 + 4 + 4)}


def hist_passes(n: int, features: int, bins: int, depth: int) -> dict:
    passes = [hist_pass(n, features, bins, d) for d in range(depth)]
    return {k: sum(p[k] for p in passes) for k in ("adds", "bytes")}


def round_work(n: int, features: int, bins: int, depth: int) -> dict:
    h, leaf = hist_passes(n, features, bins, depth), leaf_pass(n, features, bins)
    return {k: h[k] + leaf[k] for k in ("adds", "bytes")}


def least_seconds(work: dict, device_kind: str, chips: int = 1) -> float:
    """The larger of operations over peak and bytes over peak, the work
    spread over ``chips``.  At these counts the bytes bound it (HBM)."""
    p = peaks(device_kind)
    return max(work["adds"] / p["bf16_flops_per_s"],
               work["bytes"] / p["hbm_bytes_per_s"]) / chips


def issued_onehot_flops(n: int, features: int, bins: int, depth: int) -> float:
    """What a one-hot contraction on the MXU issues for a round's histogram
    passes: ``2 * n * M * F * B_eff`` a pass, ``M`` the gradient matrix's
    columns (g and h, hi and lo plane, a node) padded to the MXU's 128 rows
    and ``B_eff`` the bins padded to 128 lanes.  An implementation's count."""
    b_eff = -(-bins // 128) * 128
    total = 0.0
    for d in range(depth):
        m = -(-(4 * 2 ** d) // 128) * 128
        total += 2.0 * n * m * features * b_eff
    return total
