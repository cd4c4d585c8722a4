"""The plain reference of a deployment whose ID fields are categories:
histogram GBDT rounds in numpy and float64, a split on a categorical feature
sending a SET of codes left (LightGBM's and XGBoost's partition split:
LightGBM docs, Advanced-Topics.rst, "Categorical Feature Support", after
Fisher 1958; XGBoost >= 1.6 docs, "Categorical Data").

A copy of ``reference.py`` changed where the trees' semantics differ.
Imports nothing of the program.  The configuration's ``program`` group names
the categorical features (``categorical``); the others are split by
``code > thr`` as ``reference.py`` splits them.  One level is a ``bincount``
per feature of the gradients and of the hessians over every code.  For node
``n`` and categorical feature ``f``, let ``(G_c, H_c)`` be the sums of code
``c`` among the node's rows; the candidates are the PRESENT codes,
``H_c > 0``:

* the present codes are ordered by ``k_c = G_c / (H_c + l)`` ascending, ties
  by code ascending;
* each prefix of that order of size ``1 .. K' - 1`` (``K'`` present codes) is
  a left set ``S``, with ``GL = sum_{c in S} G_c`` and ``HL`` likewise;
* its gain is XGBoost's, ``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``, under the
  child-weight floor ``HL, HR >= min_child_weight``;
* a row goes LEFT iff its code is in ``S``; an absent code is in no set, so
  it goes right.

The winner of a node over all features is the highest gain, then the lowest
feature, then the lowest bin (numeric) or the shortest prefix (categorical):
the flat index ``f * bins + j``, ``j`` the bin or the prefix's size less one,
is the tie order.  A set is kept as the program's ``Forest.sets`` keeps it:
eight int32 words, code ``c`` at bit ``c & 31`` of word ``c >> 5``, a tree's
table ``[WORDS, 2^depth]`` with node ``i`` of level ``d`` at lane ``2^d - 1 +
i`` (``level_words``), and the threshold of a categorical node is 0; a node
whose set is empty is split by its threshold.  A leaf is ``-eta * G / (H +
l)`` of the rows routed to it.

Where XGBoost and LightGBM differ: no one-hot mode for small fields
(``max_cat_to_onehot``), no cap on the set (``max_cat_threshold``) and no
smoothing of the order (``cat_smooth``, ``cat_l2``): every prefix is scored
exactly.

Following the program's trees, a split TAKEN is held to the child-weight
floor with float32's rounding, ``HL``, ``HR`` >= ``min_child_weight`` *
(1 - 2^-20) (``SLACK``): where a node's child holds a few rows whose
hessians sum to the floor in the program's float32 and a hair under it in
float64, neither side is wrong (PERF.md).  The reference's own
best split keeps the hard floor.

It runs in two ways with the same code:

* ``follow=None`` — free: it grows its own trees.  With ``gh_dtype`` set it
  rounds the gradients to that type first, which makes it the *control*:
  the reference in the program's place, one precision below the stated one.
* ``follow=(feature, threshold, leaf, sets)`` — it is given the trees that
  the timed path produced and follows their splits, and reads at every node
  by how much the gain of the split taken (the set the program took, scored
  on its own histogram) lies below its own best, and at every tree how far
  the leaves taken lie from its own.  Its margin moves by its OWN leaves, so
  it stays the exact result of the trees' structure.

A configuration names this file under ``reference`` and the harness calls
three functions of it, each given the configuration as it is run: ``follow``
(``run.py``, after the window), ``free`` (``tools/control_categorical.py``:
the reference in the program's place) and ``first_numbers`` (``worker.py``,
after each of the first rounds).

Features are spread over forked processes (the matrix is shared copy on
write; each process routes the rows itself and only the per-node winners
cross a pipe), because a level is 2 x features bincounts over every row.
"""

from __future__ import annotations

import multiprocessing
import os
from typing import NamedTuple

import numpy as np

#: a split taken is valid to the floor times (1 - SLACK)
SLACK = 2.0 ** -20
#: int32 words of a set: 256 codes
WORDS = 8


class Params(NamedTuple):
    depth: int
    bins: int
    eta: float
    lam: float
    min_child_weight: float
    categorical: frozenset


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
    sets: np.ndarray       # [rounds, WORDS, 2^depth] int32


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


def ratio(G, H, lam):
    """The order of a node's present codes: ``G / (H + l)``."""
    return G / (H + lam)


def set_bits(words: np.ndarray, bins: int) -> np.ndarray:
    """``[..., bins]`` bool: whether each code lies in each set."""
    c = np.arange(bins)
    w = np.asarray(words, np.int64)[..., c >> 5] & 0xFFFFFFFF
    return ((w >> (c & 31)) & 1) == 1


def set_words(member: np.ndarray) -> np.ndarray:
    """``[..., WORDS]`` int32 from ``[..., bins]`` bool (``bins`` <= 256)."""
    bits = np.zeros(member.shape[:-1] + (32 * WORDS,), np.uint64)
    bits[..., :member.shape[-1]] = member
    weights = np.uint64(1) << np.arange(32, dtype=np.uint64)
    w = (bits.reshape(*member.shape[:-1], WORDS, 32) * weights).sum(-1)
    return w.astype(np.uint32).view(np.int32)


def level_words(tree_sets, d: int) -> np.ndarray:
    """Level ``d``'s sets ``[2^d, WORDS]`` of a tree's table."""
    return np.asarray(tree_sets)[:, 2 ** d - 1: 2 ** (d + 1) - 1].T


def went_right(code, thr, words, bins: int):
    """Whether a row with ``code`` on its node's feature goes right: where
    the node has a set, iff the code is not in it (bit ``code & 31`` of word
    ``code >> 5``); else where the code is above ``thr``."""
    w = np.take_along_axis(np.asarray(words, np.int64), (code >> 5)[:, None]
                           .astype(np.int64), 1)[:, 0] & 0xFFFFFFFF
    inside = ((w >> (code & 31)) & 1) == 1
    return np.where(np.any(words != 0, -1), ~inside, code > thr)


def _level_candidates(codes, feats, node, n_nodes, g, h, p: Params, taken,
                      order):
    """For this process's features: each node's best ``(gain, flat index,
    children score, set)`` and the gain at the split ``taken`` (``-inf``
    where the taken feature is another process's)."""
    B = p.bins
    best_gain = np.full(n_nodes, -np.inf)
    best_flat = np.zeros(n_nodes, np.int64)
    best_child = np.zeros(n_nodes)
    best_set = np.zeros((n_nodes, WORDS), np.int32)
    taken_gain = np.full(n_nodes, -np.inf)
    hist = np.zeros((len(feats), 2, n_nodes * B))
    for s, e in _blocks(codes.shape[0]):
        base = node[s:e] * B
        for i, f in enumerate(feats):
            seg = base + codes[s:e, f]
            hist[i, 0] += np.bincount(seg, weights=g[s:e], minlength=n_nodes * B)
            hist[i, 1] += np.bincount(seg, weights=h[s:e], minlength=n_nodes * B)
    rows = np.arange(n_nodes)
    pos = np.arange(B)
    floor = p.min_child_weight
    for i, f in enumerate(feats):
        hg, hh = hist[i].reshape(2, n_nodes, B)
        G, H = hg.sum(-1, keepdims=True), hh.sum(-1, keepdims=True)
        if f in p.categorical:
            present = hh > 0
            with np.errstate(divide="ignore", invalid="ignore"):
                key = np.where(present, order(hg, hh, p.lam), np.inf)
            by = np.argsort(key, axis=-1, kind="stable")     # ties by code
            GL = np.cumsum(np.take_along_axis(hg, by, -1), -1)
            HL = np.cumsum(np.take_along_axis(hh, by, -1), -1)
            ok = pos < present.sum(-1, keepdims=True) - 1
        else:
            GL, HL = np.cumsum(hg, -1), np.cumsum(hh, -1)
            ok = True
        GR, HR = G - GL, H - HL
        child = _score(GL, HL, p.lam) + _score(GR, HR, p.lam)
        gain = child - _score(G, H, p.lam)
        hard = np.where(ok & (HL >= floor) & (HR >= floor), gain, -np.inf)
        j = np.argmax(hard, -1)                 # the bin, or the prefix less one
        better = hard[rows, j] > best_gain      # ties keep the lower feature
        best_gain = np.where(better, hard[rows, j], best_gain)
        best_flat = np.where(better, f * B + j, best_flat)
        best_child = np.where(better, child[rows, j], best_child)
        if f in p.categorical:
            member = np.zeros((n_nodes, B), bool)
            np.put_along_axis(member, by, pos <= j[:, None], -1)
            best_set = np.where(better[:, None], set_words(member), best_set)
        else:
            best_set = np.where(better[:, None], 0, best_set)
        if taken is not None:
            # the split the program took, scored on this histogram: its set,
            # or its threshold where it has none
            t_feat, t_thr, t_words = taken
            is_set = np.any(t_words != 0, -1)[:, None]
            left = np.where(is_set, set_bits(t_words, B), pos <= t_thr[:, None])
            gl, hl = (hg * left).sum(-1), (hh * left).sum(-1)
            g0, h0 = G[:, 0], H[:, 0]
            soft = floor * (1.0 - SLACK)
            mine = np.where((hl >= soft) & (h0 - hl >= soft),
                            _score(gl, hl, p.lam) + _score(g0 - gl, h0 - hl, p.lam)
                            - _score(g0, h0, p.lam), -np.inf)
            taken_gain = np.where(t_feat == f, mine, taken_gain)
    return best_gain, best_flat, best_child, taken_gain, best_set


def _merge(parts):
    """The winners over all processes' features: highest gain, and on a tie
    the lowest flat index, which is what one argmax over all would give."""
    gain = np.stack([q[0] for q in parts])
    flat = np.stack([q[1] for q in parts])
    order = np.lexsort((flat, -gain), axis=0)[0]
    cols = np.arange(gain.shape[1])
    taken = np.max(np.stack([q[3] for q in parts]), 0)
    return (gain[order, cols], flat[order, cols],
            np.stack([q[2] for q in parts])[order, cols], taken,
            np.stack([q[4] for q in parts])[order, cols])


def _round_to(x: np.ndarray, dtype) -> np.ndarray:
    return x.astype(np.float32).astype(dtype).astype(np.float64)


def _rounds(codes, y, p: Params, rounds: int, follow, gh_dtype, feats,
            exchange, order) -> Followed:
    """The rounds as every process runs them.  ``exchange(part)`` hands this
    process's candidates to the others and returns all of them merged."""
    n = codes.shape[0]
    half = 2 ** (p.depth - 1)
    margin = np.zeros(n, np.float64)
    out = Followed([], [], [], [], [],
                   np.zeros((rounds, p.depth, half), np.int32),
                   np.zeros((rounds, p.depth, half), np.int32),
                   np.zeros((rounds, 2 ** p.depth), np.float64),
                   np.zeros((rounds, WORDS, 2 ** p.depth), np.int32))
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
            at_level = slice(n_nodes - 1, 2 * n_nodes - 1)
            taken = None
            if follow is not None:
                taken = (follow[0][r, d, :n_nodes].astype(np.int64),
                         follow[1][r, d, :n_nodes].astype(np.int64),
                         level_words(follow[3][r], d).astype(np.int32))
            best_gain, best_flat, best_child, taken_gain, best_set = exchange(
                _level_candidates(codes, feats, node, n_nodes, g, h, p, taken,
                                  order))
            feat, j = best_flat // p.bins, best_flat % p.bins
            is_cat = np.isin(feat, list(p.categorical)) & np.isfinite(best_gain)
            thr = np.where(is_cat, 0, j)
            words = np.where(is_cat[:, None], best_set, 0).astype(np.int32)
            out.feature[r, d, :n_nodes] = feat
            out.threshold[r, d, :n_nodes] = thr
            out.sets[r][:, at_level] = words.T
            if taken is not None:
                live = np.isfinite(best_gain)
                with np.errstate(invalid="ignore"):   # -inf less -inf
                    short = np.where(live, best_gain - taken_gain, 0.0)
                scale = np.where(live, np.abs(best_child), 1.0) + 1e-300
                gap = max(gap, float(np.max(short / scale)))
                feat, thr, words = taken
                own_words = level_words(out.sets[r], d)
                differs += int(np.sum((feat != out.feature[r, d, :n_nodes])
                                      | (thr != out.threshold[r, d, :n_nodes])
                                      | np.any(words != own_words, -1)))
            for s, e in _blocks(n):
                at = node[s:e]
                node[s:e] = at * 2 + went_right(
                    codes[s:e][np.arange(e - s), feat[at]], thr[at], words[at],
                    p.bins)
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


def _child(conn, codes, y, p, rounds, follow, gh_dtype, feats, order):
    def exchange(part):
        conn.send(part)
        return conn.recv()

    try:
        _rounds(codes, y, p, rounds, follow, gh_dtype, feats, exchange, order)
    finally:
        conn.close()


def default_procs() -> int:
    return max(1, min(14, (os.cpu_count() or 2) // 2))


def boost_rounds(codes: np.ndarray, y: np.ndarray, p: Params, rounds: int,
                 follow=None, gh_dtype=None, procs: int | None = None,
                 order=ratio) -> Followed:
    """``rounds`` boosting rounds from a zero margin; see the module text.
    ``order(G, H, l)`` is the key the present codes of a categorical feature
    are sorted by (``ratio``; ``tools/control_categorical.py`` plants
    another).

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
            there, codes, y, p, rounds, follow, gh_dtype, list(feats), order))
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
                       exchange, order)
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
                  config["lambda"], config["min_child_weight"],
                  frozenset(config["program"].get("categorical", ())))


def follow(config: dict, codes, y, forest, rounds: int) -> Followed:
    """The reference following the first ``rounds`` trees of the timed path:
    ``forest`` holds the tables of the program's ``Forest`` in its order,
    here ``(feature, threshold, leaf, sets)``."""
    return boost_rounds(codes, y, _params(config), rounds, follow=tuple(forest))


def free(config: dict, codes, y, rounds: int, gh_dtype=None,
         order=ratio) -> tuple:
    """The trees the reference grows by itself (``gh_dtype``: with gradients
    and hessians rounded to that type; ``order``: see ``boost_rounds``), as
    the program's ``Forest`` would hold them: its tables in its order and
    its types."""
    own = boost_rounds(codes, y, _params(config), rounds, gh_dtype=gh_dtype,
                       order=order)
    return own.feature, own.threshold, own.leaf.astype(np.float32), own.sets


def first_numbers(margin: np.ndarray, y: np.ndarray) -> dict:
    """What the worker notes after each of its first rounds, from the margin
    it committed: the numbers ``Followed`` holds under the same names."""
    m, y64 = margin.astype(np.float64), y.astype(np.float64)
    return {"logloss": float(np.mean(np.logaddexp(0.0, m) - y64 * m)),
            "margin_norm": float(np.sqrt(np.sum(m * m)))}


def margins_of(codes: np.ndarray, feature, threshold, leaf, sets,
               bins: int = 256, route=went_right):
    """The margin after each of the given trees, from a zero margin: what a
    program that grew them has added up, on every row (``route``: the
    routing, ``went_right``'s signature)."""
    n, depth = codes.shape[0], feature.shape[1]
    margin = np.zeros(n, np.float64)
    out = []
    for r in range(feature.shape[0]):
        leaves = np.asarray(leaf[r], np.float64)
        for s, e in _blocks(n):
            node = np.zeros(e - s, np.int64)
            for d in range(depth):
                f, t = feature[r, d][node], threshold[r, d][node]
                w = np.asarray(sets[r])[:, 2 ** d - 1 + node].T
                node = node * 2 + route(
                    codes[s:e][np.arange(e - s), f], t, w, bins)
            margin[s:e] += leaves[node]
        out.append(margin.copy())
    return out
