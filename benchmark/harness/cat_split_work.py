"""What the categorical split search of one level has to do, whatever does
it: the count that ``work.py`` has for the histogram passes, for the search
that finds each node's best set of codes (``cat_split_d<d>``).

A level of ``nodes`` nodes reads its combined histogram of the categorical
features once — ``C x bins`` gradient and hessian sums a node, float32 — and
writes a node's set (eight 32-bit words), feature, code count and gain.
Nothing here knows of lanes, sorting networks, grid steps or kernels.
"""

from __future__ import annotations

#: bytes a node written: the set's eight words, its feature, its prefix, its gain
NODE_OUT = 8 * 4 + 3 * 4


def cat_split_level(nodes: int, categorical: int, bins: int) -> dict:
    lanes = nodes * categorical * bins
    return {"adds": 2 * lanes, "bytes": lanes * 2 * 4 + nodes * NODE_OUT}


def cat_split_levels(categorical: int, bins: int, levels) -> dict:
    """The searches of the given levels together (level ``d``: ``2**d``
    nodes)."""
    each = [cat_split_level(2 ** d, categorical, bins) for d in levels]
    return {k: sum(w[k] for w in each) for k in ("adds", "bytes")}
