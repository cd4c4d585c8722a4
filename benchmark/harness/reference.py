"""The plain reference: histogram GBDT rounds in numpy and float64.

Imports nothing of the program.  One level is a ``bincount`` per feature of
the gradients and of the hessians, a cumulative sum, XGBoost's gain
``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)`` with the child-weight floor, and
the first best ``(feature, bin)`` of each node; a leaf is
``-eta * G / (H + l)`` of the rows routed to it.

It runs in two ways with the same code:

* ``follow=None`` — free: it grows its own trees.  With ``gh_dtype`` set it
  rounds the gradients to that type first, which makes it the *control*:
  the reference in the program's place, one precision below the stated one.
* ``follow=(feature, threshold, leaf)`` — it is given the trees that the
  timed path produced and follows their splits (as a served model's
  reference follows the served tokens), and reads at every node by how much
  the gain of the split taken lies below its own best, and at every tree how
  far the leaves taken lie from its own.  Its margin moves by its OWN leaves,
  so it stays the exact result of the trees' structure.

A configuration names this file under ``reference`` and the harness calls
three functions of it, each given the configuration as it is run: ``follow``
(``run.py``, after the window), ``free`` (``tools/control.py``: the reference
in the program's place) and ``first_numbers`` (``worker.py``, after each of
the first rounds).  A deployment with another schema brings a copy of this
file with those three, and ``margins_of`` and ``logloss`` for the control.

Features are spread over forked processes (the matrix is shared copy on
write; each process routes the rows itself and only the per-node winners
cross a pipe), because a level is 2 x features bincounts over every row.
"""

from __future__ import annotations

import multiprocessing
import os
from typing import NamedTuple

import numpy as np


class Params(NamedTuple):
    depth: int
    bins: int
    eta: float
    lam: float
    min_child_weight: float


class Followed(NamedTuple):
    """What following the program's first trees read."""

    logloss: list          # after each round, float64
    margin_norm: list      # l2 norm of the margin after each round
    gain_gap: list         # per round: widest (best - taken) / children score
    leaf_gap: list         # per round: rms(leaf taken - own) / rms(own)
    split_differs: list    # per round: nodes where taken != own best
    feature: np.ndarray    # own trees, for a free run: [rounds, depth, 2^(d-1)]
    threshold: np.ndarray
    leaf: np.ndarray       # [rounds, 2^depth]


def logloss(margin: np.ndarray, y: np.ndarray) -> float:
    total = 0.0
    for s, e in _blocks(len(margin)):
        m = np.asarray(margin[s:e], np.float64)
        total += float(np.sum(np.logaddexp(0.0, m) - y[s:e] * m))
    return total / len(margin)


def _score(a, b, lam):
    return a * a / (b + lam)


#: rows a block: every temporary of the hot loops is this long and comes
#: from the allocator's heap again and again, where whole columns of ten
#: million rows would be mapped and unmapped a thousand times a round
BLOCK = 1 << 18


def _blocks(n: int):
    return [(s, min(s + BLOCK, n)) for s in range(0, n, BLOCK)]


def _level_candidates(codes, feats, node, n_nodes, g, h, p: Params, taken):
    """For this process's features: each node's best ``(gain, flat index)``
    with the children score there, and the gain at the split ``taken``
    (``-inf`` where the taken feature is another process's)."""
    B = p.bins
    best_gain = np.full(n_nodes, -np.inf)
    best_flat = np.zeros(n_nodes, np.int64)
    best_child = np.zeros(n_nodes)
    taken_gain = np.full(n_nodes, -np.inf)
    hist = np.zeros((len(feats), 2, n_nodes * B))
    for s, e in _blocks(codes.shape[0]):
        base = node[s:e] * B
        for i, f in enumerate(feats):
            seg = base + codes[s:e, f]
            hist[i, 0] += np.bincount(seg, weights=g[s:e], minlength=n_nodes * B)
            hist[i, 1] += np.bincount(seg, weights=h[s:e], minlength=n_nodes * B)
    rows = np.arange(n_nodes)
    for i, f in enumerate(feats):
        hg, hh = hist[i].reshape(2, n_nodes, B)
        GL, HL = np.cumsum(hg, -1), np.cumsum(hh, -1)
        G, H = GL[:, -1:], HL[:, -1:]
        GR, HR = G - GL, H - HL
        child = _score(GL, HL, p.lam) + _score(GR, HR, p.lam)
        gain = child - _score(G, H, p.lam)
        valid = (HL >= p.min_child_weight) & (HR >= p.min_child_weight)
        gain = np.where(valid, gain, -np.inf)
        b = np.argmax(gain, -1)
        better = gain[rows, b] > best_gain      # ties keep the lower feature
        best_gain = np.where(better, gain[rows, b], best_gain)
        best_flat = np.where(better, f * B + b, best_flat)
        best_child = np.where(better, child[rows, b], best_child)
        if taken is not None:
            mine = taken[0] == f
            taken_gain = np.where(mine, gain[rows, taken[1]], taken_gain)
    return best_gain, best_flat, best_child, taken_gain


def _merge(parts):
    """The winners over all processes' features: highest gain, and on a tie
    the lowest flat index, which is what one argmax over all would give."""
    gain = np.stack([q[0] for q in parts])
    flat = np.stack([q[1] for q in parts])
    order = np.lexsort((flat, -gain), axis=0)[0]
    cols = np.arange(gain.shape[1])
    taken = np.max(np.stack([q[3] for q in parts]), 0)
    return (gain[order, cols], flat[order, cols],
            np.stack([q[2] for q in parts])[order, cols], taken)


def _round_to(x: np.ndarray, dtype) -> np.ndarray:
    return x.astype(np.float32).astype(dtype).astype(np.float64)


def _rounds(codes, y, p: Params, rounds: int, follow, gh_dtype, feats,
            exchange) -> Followed:
    """The rounds as every process runs them.  ``exchange(part)`` hands this
    process's candidates to the others and returns all of them merged."""
    n = codes.shape[0]
    half = 2 ** (p.depth - 1)
    margin = np.zeros(n, np.float64)
    out = Followed([], [], [], [], [],
                   np.zeros((rounds, p.depth, half), np.int32),
                   np.zeros((rounds, p.depth, half), np.int32),
                   np.zeros((rounds, 2 ** p.depth), np.float64))
    g, h = np.empty(n), np.empty(n)
    node = np.empty(n, np.int64)
    for r in range(rounds):
        for s, e in _blocks(n):
            if gh_dtype is not None:
                # the control keeps its margin in the program's float32 too
                margin[s:e] = margin[s:e].astype(np.float32)
            prob = 1.0 / (1.0 + np.exp(-margin[s:e]))
            g[s:e], h[s:e] = prob - y[s:e], prob * (1.0 - prob)
            if gh_dtype is not None:
                g[s:e] = _round_to(g[s:e], gh_dtype)
                h[s:e] = _round_to(h[s:e], gh_dtype)
        node[:] = 0
        gap, differs = 0.0, 0
        for d in range(p.depth):
            n_nodes = 2 ** d
            taken = None
            if follow is not None:
                taken = (follow[0][r, d, :n_nodes].astype(np.int64),
                         follow[1][r, d, :n_nodes].astype(np.int64))
            best_gain, best_flat, best_child, taken_gain = exchange(
                _level_candidates(codes, feats, node, n_nodes, g, h, p, taken))
            feat, thr = best_flat // p.bins, best_flat % p.bins
            out.feature[r, d, :n_nodes] = feat
            out.threshold[r, d, :n_nodes] = thr
            if taken is not None:
                live = np.isfinite(best_gain)
                with np.errstate(invalid="ignore"):   # -inf less -inf
                    short = np.where(live, best_gain - taken_gain, 0.0)
                scale = np.where(live, np.abs(best_child), 1.0) + 1e-300
                gap = max(gap, float(np.max(short / scale)))
                differs += int(np.sum((taken[0] != feat) | (taken[1] != thr)))
                feat, thr = taken
            for s, e in _blocks(n):
                at = node[s:e]
                went_right = codes[s:e][np.arange(e - s), feat[at]] > thr[at]
                node[s:e] = at * 2 + went_right
        leaf_g = np.bincount(node, weights=g, minlength=2 ** p.depth)
        leaf_h = np.bincount(node, weights=h, minlength=2 ** p.depth)
        leaf = -p.eta * leaf_g / (leaf_h + p.lam)
        out.leaf[r] = leaf
        if follow is not None:
            d_leaf = np.asarray(follow[2][r], np.float64) - leaf
            out.leaf_gap.append(float(np.sqrt(np.mean(d_leaf ** 2))
                                      / max(np.sqrt(np.mean(leaf ** 2)), 1e-300)))
            out.gain_gap.append(gap)
            out.split_differs.append(differs)
        norm2 = 0.0
        for s, e in _blocks(n):
            margin[s:e] += leaf[node[s:e]]
            norm2 += float(np.dot(margin[s:e], margin[s:e]))
        out.logloss.append(logloss(margin, y))
        out.margin_norm.append(float(np.sqrt(norm2)))
    return out


def _child(conn, codes, y, p, rounds, follow, gh_dtype, feats):
    def exchange(part):
        conn.send(part)
        return conn.recv()

    try:
        _rounds(codes, y, p, rounds, follow, gh_dtype, feats, exchange)
    finally:
        conn.close()


def default_procs() -> int:
    return max(1, min(14, (os.cpu_count() or 2) // 2))


def boost_rounds(codes: np.ndarray, y: np.ndarray, p: Params, rounds: int,
                 follow=None, gh_dtype=None, procs: int | None = None) -> Followed:
    """``rounds`` boosting rounds from a zero margin; see the module text.

    Forks ``procs - 1`` helpers (the caller must hold no thread that a fork
    would tear) and joins every one of them before it returns."""
    features = codes.shape[1]
    procs = max(1, min(procs or default_procs(), features))
    split = np.array_split(np.arange(features), procs)
    y = np.asarray(y, np.float64)
    ctx = multiprocessing.get_context("fork")
    conns, kids = [], []
    for feats in split[1:]:
        here, there = ctx.Pipe()
        kid = ctx.Process(target=_child, daemon=True, args=(
            there, codes, y, p, rounds, follow, gh_dtype, list(feats)))
        kid.start()
        there.close()
        conns.append(here)
        kids.append(kid)

    def exchange(part):
        merged = _merge([part] + [c.recv() for c in conns])
        for c in conns:
            c.send(merged)
        return merged

    try:
        return _rounds(codes, y, p, rounds, follow, gh_dtype, list(split[0]),
                       exchange)
    finally:
        for c in conns:
            c.close()
        for kid in kids:
            kid.join(60)
            if kid.is_alive():
                kid.kill()
                kid.join()


def _params(config: dict) -> Params:
    return Params(config["max_depth"], config["max_bin"], config["eta"],
                  config["lambda"], config["min_child_weight"])


def follow(config: dict, codes, y, forest, rounds: int) -> Followed:
    """The reference following the first ``rounds`` trees of the timed path:
    ``forest`` holds the tables of the program's ``Forest`` in its order,
    here ``(feature, threshold, leaf)``."""
    return boost_rounds(codes, y, _params(config), rounds, follow=tuple(forest))


def free(config: dict, codes, y, rounds: int, gh_dtype=None) -> tuple:
    """The trees the reference grows by itself (``gh_dtype``: with gradients
    and hessians rounded to that type), as the program's ``Forest`` would
    hold them: its tables in its order and its types."""
    own = boost_rounds(codes, y, _params(config), rounds, gh_dtype=gh_dtype)
    return own.feature, own.threshold, own.leaf.astype(np.float32)


def first_numbers(margin: np.ndarray, y: np.ndarray) -> dict:
    """What the worker notes after each of its first rounds, from the margin
    it committed: the numbers ``Followed`` holds under the same names."""
    m, y64 = margin.astype(np.float64), y.astype(np.float64)
    return {"logloss": float(np.mean(np.logaddexp(0.0, m) - y64 * m)),
            "margin_norm": float(np.sqrt(np.sum(m * m)))}


def margins_of(codes: np.ndarray, feature, threshold, leaf):
    """The margin after each of the given trees, from a zero margin: what a
    program that grew them has added up, on every row."""
    n, depth = codes.shape[0], feature.shape[1]
    margin = np.zeros(n, np.float64)
    out = []
    for r in range(feature.shape[0]):
        leaves = np.asarray(leaf[r], np.float64)
        for s, e in _blocks(n):
            node = np.zeros(e - s, np.int64)
            for d in range(depth):
                f, t = feature[r, d][node], threshold[r, d][node]
                node = node * 2 + (codes[s:e][np.arange(e - s), f] > t)
            margin[s:e] += leaves[node]
        out.append(margin.copy())
    return out
