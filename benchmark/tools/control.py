"""The control and the planted faults, read as a run would read them.

``python3 benchmark/tools/control.py --config <name> --seeds 1 2 3`` makes
the configuration's data from each seed with the configuration's own
generator and, with the reference it names, puts in the program's place

* ``control``        the plain reference with gradients and hessians rounded
                     to the configuration's ``control_precision`` (bfloat16:
                     one plane where the program contracts a hi and a lo one),
* ``half_batch``     the reference grown on every other row, its trees
                     applied to all rows (half of the batch left out),
* ``no_exchange``    the reference grown on the first quarter of the rows
                     (a shard that never summed with the others),
* ``state_unchanged`` the reference whose second round returned its state
                     as it got it (tree and margin of round 1 again),

and prints, one JSON line a seed and a case, the numbers that
``harness/compare.py`` would hold against the configuration's limits.  All
of it is numpy on the host; the benchmark's own runs never call it.  The
upper readings of ``PERF.md`` come from here, and ``tests/test_control.py``
runs it at a size a test can hold.
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

CASES = ("control", "half_batch", "no_exchange", "state_unchanged")


def in_programs_place(case: str, reference, config: dict, codes, y, rounds: int):
    """What a worker's ``first`` would hold had this case been the program."""
    if case == "control":
        import ml_dtypes

        forest = reference.free(
            config, codes, y, rounds,
            gh_dtype=getattr(ml_dtypes, config["control_precision"]))
    elif case == "half_batch":
        forest = reference.free(config, codes[::2], y[::2], rounds)
    elif case == "no_exchange":
        q = codes.shape[0] // 4
        forest = reference.free(config, codes[:q], y[:q], rounds)
    elif case == "state_unchanged":
        forest = reference.free(config, codes, y, rounds)
        for a in forest:
            a[1] = 0          # round 2 left the forest's slot as it was
    else:
        raise ValueError(case)
    margins = reference.margins_of(codes, *forest)   # a zeroed tree adds nought
    return {"forest": [a.tolist() for a in forest],
            "logloss": [reference.logloss(m.astype(np.float32), y) for m in margins],
            "margin_norm": [float(np.sqrt(np.sum(m.astype(np.float32).astype(np.float64) ** 2)))
                            for m in margins]}


def read_case(case: str, config: dict, limits: dict, seed: int, rows: int | None = None):
    if rows:
        config = {**config, "rows": rows}
    codes, y = bdata.draw(config, seed)
    reference = deployment.reference_of(config)
    rounds = 3
    first = in_programs_place(case, reference, config, codes, y, rounds)
    followed = reference.follow(
        config, codes, y, [np.asarray(a) for a in first["forest"]], rounds)
    ev = {"lives": [{"first": first, "version": rounds}], "rounds": [],
          "traffic": {"check_rounds": rounds}}
    compared = compare.numbers(ev, followed, limits)
    return compared, followed.split_differs


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
            compared, differs = read_case(case, config, limits, seed, args.rows)
            print(json.dumps({
                "config": args.config, "seed": seed, "case": case,
                "correct": compare.correct(compared),
                "compared": {n: [v, lim] for n, v, lim in compared},
                "split_differs": differs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
