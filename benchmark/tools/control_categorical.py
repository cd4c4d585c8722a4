"""The control and the planted faults of a deployment whose ID fields are
categories (``harness/reference_categorical.py``), read as a run would read
them.

``python3 benchmark/tools/control_categorical.py --config criteo-1tb-cat-share
--seeds 1 2 3`` makes the configuration's data from each seed with its own
generator and, with the reference it names, puts in the program's place

* ``control``            the plain reference with gradients and hessians
                         rounded to the configuration's ``control_precision``,
                         as ``tools/control.py`` has it,
* ``set_moved``          the reference's own trees with ONE category moved
                         across ONE set: at the deepest level of tree 1 that
                         has a categorical split, the categorical node with
                         the most rows, its present category of the largest
                         ``|G_c|``; tree 1's leaves grown on the changed tree,
* ``order_by_gradient``  the reference's own trees grown with the present
                         codes ordered by ``G_c`` alone, not by ``G_c / (H_c +
                         lambda)``,
* ``word_shifted``       the reference's own trees, routed as a program that
                         reads a code's bit from word ``(code >> 5) + 1`` of
                         its node's set (past the last word, none): tree 1's
                         leaves grown and every margin added up so,

and prints, one JSON line a seed and a case, the numbers that
``harness/compare.py`` would hold against the configuration's limits.  All of
this is numpy on the host, and the benchmark's own runs never call it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

from harness import compare, data as bdata, deployment  # noqa: E402

CASES = ("control", "set_moved", "order_by_gradient", "word_shifted")
ROUNDS = 3
#: a row's hessian in round 1, from a zero margin
ROW = 0.25


def _levels(reference, codes, forest, bins: int, route):
    """Each row's node at every level ``0..depth`` of tree 1."""
    feature, threshold, sets = forest[0][0], forest[1][0], forest[3][0]
    n = codes.shape[0]
    node, out = np.zeros(n, np.int64), []
    for d in range(feature.shape[0]):
        out.append(node)
        col = codes[np.arange(n), feature[d][node]]
        node = node * 2 + route(col, threshold[d][node],
                                sets[:, 2 ** d - 1 + node].T, bins)
    return out + [node]


def grow_leaves(reference, config: dict, codes, y, forest, route) -> None:
    """Tree 1's leaves grown on tree 1 as it is now and routed by ``route``
    (round 1: ``g = 0.5 - y``, ``h = ROW``), as a program that took its
    splits would (in place)."""
    leaf = _levels(reference, codes, forest, config["max_bin"], route)[-1]
    n_leaves = 2 ** config["max_depth"]
    forest[2][0] = (-config["eta"]
                    * np.bincount(leaf, 0.5 - y.astype(np.float64), n_leaves)
                    / (ROW * np.bincount(leaf, minlength=n_leaves)
                       + config["lambda"]))


def move_category(reference, config: dict, codes, y, forest) -> dict:
    """One category moved across one set of tree 1 (in place); returns
    where."""
    bins = config["max_bin"]
    levels = _levels(reference, codes, forest, bins, reference.went_right)
    sets = forest[3][0]
    for d in reversed(range(config["max_depth"])):
        own = reference.level_words(sets, d)
        cat = np.any(own != 0, -1)
        if cat.any():
            break
    else:
        raise ValueError("tree 1 has no categorical split")
    rows = np.where(cat, np.bincount(levels[d], minlength=2 ** d), -1)
    k = int(np.argmax(rows))
    at = levels[d] == k
    col = codes[at, forest[0][0][d, k]]
    G = np.bincount(col, weights=0.5 - y[at].astype(np.float64), minlength=bins)
    c = int(np.argmax(np.where(np.bincount(col, minlength=bins) > 0,
                               np.abs(G), -1.0)))
    sets[c >> 5, 2 ** d - 1 + k] ^= np.array(1 << (c & 31), np.uint32).view(
        np.int32)
    return {"level": d, "node": k, "code": c}


def shifted(reference):
    """A routing that reads a code's bit from word ``(code >> 5) + 1``."""
    def went_right(code, thr, words, bins):
        at = (code >> 5).astype(np.int64) + 1
        w = np.take_along_axis(
            np.asarray(words, np.int64), np.minimum(at, reference.WORDS - 1)[:, None],
            1)[:, 0] & 0xFFFFFFFF
        inside = (at < reference.WORDS) & (((w >> (code & 31)) & 1) == 1)
        return np.where(np.any(words != 0, -1), ~inside, code > thr)
    return went_right


def in_programs_place(case: str, reference, config: dict, codes, y):
    """What a worker's ``first`` would hold had this case been the program,
    and what the case notes of itself."""
    bins, note, route = config["max_bin"], {}, reference.went_right
    if case == "control":
        import ml_dtypes

        forest = reference.free(
            config, codes, y, ROUNDS,
            gh_dtype=getattr(ml_dtypes, config["control_precision"]))
    elif case == "order_by_gradient":
        forest = reference.free(config, codes, y, ROUNDS,
                                order=lambda G, H, lam: G)
    else:
        forest = reference.free(config, codes, y, ROUNDS)
        if case == "set_moved":
            note["moved"] = move_category(reference, config, codes, y, forest)
        elif case == "word_shifted":
            route = shifted(reference)
        else:
            raise ValueError(case)
        grow_leaves(reference, config, codes, y, forest, route)
    margins = reference.margins_of(codes, *forest, bins=bins, route=route)
    return {"forest": [a.tolist() for a in forest],
            "logloss": [reference.logloss(m.astype(np.float32), y) for m in margins],
            "margin_norm": [float(np.sqrt(np.sum(m.astype(np.float32).astype(np.float64) ** 2)))
                            for m in margins]}, note


def read_case(case: str, config: dict, limits: dict, seed: int, rows: int | None = None):
    if rows:
        config = {**config, "rows": rows}
    codes, y = bdata.draw(config, seed)
    reference = deployment.reference_of(config)
    first, note = in_programs_place(case, reference, config, codes, y)
    followed = reference.follow(
        config, codes, y, [np.asarray(a) for a in first["forest"]], ROUNDS)
    ev = {"lives": [{"first": first, "version": ROUNDS}], "rounds": [],
          "traffic": {"check_rounds": ROUNDS}}
    return compare.numbers(ev, followed, limits), followed.split_differs, note


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--cases", nargs="+", default=list(CASES))
    ap.add_argument("--rows", type=int, default=None)
    args = ap.parse_args()
    path = HERE / "configs" / f"{args.config}.json"
    config = json.loads(path.read_text())
    limits = json.loads(path.with_suffix(".limits.json").read_text())
    for seed in args.seeds:
        for case in args.cases:
            compared, differs, note = read_case(case, config, limits, seed,
                                                args.rows)
            print(json.dumps({
                "config": args.config, "seed": seed, "case": case,
                "correct": compare.correct(compared),
                "compared": {n: [v, lim] for n, v, lim in compared},
                "split_differs": differs, **note}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
