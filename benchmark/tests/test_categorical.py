"""The deployment with Criteo's ID fields as categories
(``criteo-1tb-cat-share``): its generator gives each field a Zipf-skewed
category code, its count a monotone bucket of the category's frequency and
the empty share to the "other" code; its reference following its own free
run reads nought to float64's last bits; and the control and the planted faults of
``tools/control_categorical.py`` come out as not correct under the
configuration's limits, at a size a test holds."""

import json

import numpy as np
import pytest

from conftest import BENCH, load_file
from harness import compare, data as bdata, deployment

control = load_file(BENCH / "tools" / "control_categorical.py")
PATH = BENCH / "configs" / "criteo-1tb-cat-share.json"
CONFIG = json.loads(PATH.read_text())
LIMITS = json.loads(PATH.with_suffix(".limits.json").read_text())
ROWS = 60000


def test_the_generator_skews_the_fields_and_buckets_their_counts():
    config = {**CONFIG, "rows": 200000}
    seed = 2**31 + 40
    codes, y = bdata.draw(config, seed)
    again = bdata.draw(config, seed)
    assert np.array_equal(codes, again[0]) and np.array_equal(y, again[1])
    assert codes.dtype == np.uint8 and 0.2 < y.mean() < 0.8
    data = CONFIG["data"]
    n_int, n_cat = data["n_int"], len(data["cat_shares"])
    assert CONFIG["program"]["categorical"] == list(range(n_int, n_int + n_cat))
    for c, share in enumerate(data["cat_shares"]):
        field = codes[:, n_int + c]
        count = codes[:, n_int + n_cat + c]
        freq = np.bincount(field, minlength=256)
        # the most frequent code takes some tenth of the present rows, and
        # the codes are not in frequency order
        assert 0.05 < freq[:255].max() / max(freq[:255].sum(), 1) < 0.2
        assert not np.all(np.diff(freq[:255]) <= 0)
        # "other and empty" holds the empty share and the tail
        assert freq[255] / len(field) >= share / 100 - 0.01
        # a count is one value a code, higher for a more frequent code
        pairs = np.unique(np.stack([field, count], 1), axis=0)
        assert len(pairs) == len(np.unique(field))
        top = np.argsort(-freq[:255])[:6]
        by_code = dict(pairs.tolist())
        assert [by_code[int(t)] for t in top] == sorted(
            (by_code[int(t)] for t in top), reverse=True)
        assert by_code.get(255, 0) == 0


def test_the_reference_following_itself_reads_nought():
    config = {**CONFIG, "rows": ROWS}
    codes, y = bdata.draw(config, 11)
    reference = deployment.reference_of(config)
    p = reference._params(config)
    free = reference.boost_rounds(codes, y, p, 3)
    assert free.sets.any()
    followed = reference.boost_rounds(codes, y, p, 3, follow=(
        free.feature, free.threshold, free.leaf, free.sets))
    # the set taken is summed in code order, the best in the key's: float64's
    # last bits apart
    assert max(followed.gain_gap) < 1e-12 and followed.leaf_gap == [0.0] * 3
    assert followed.split_differs == [0] * 3


@pytest.mark.parametrize("case", control.CASES)
@pytest.mark.parametrize("seed", [11, 3000000011])
def test_case_fails(case, seed):
    compared, _, note = control.read_case(case, CONFIG, LIMITS, seed, ROWS)
    assert not compare.correct(compared)
    if case == "set_moved":
        assert note["moved"]["level"] >= 0
