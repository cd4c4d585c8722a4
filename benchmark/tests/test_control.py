"""The control — the reference in the program's place, one precision below
the configuration's — and the faults planted in the reference come out as
not correct under the configuration's own limits, at a size a test holds;
the reference following its own free run reads nought."""

import json

import numpy as np
import pytest

from conftest import BENCH, load_file
from harness import compare, data as bdata, reference

control = load_file(BENCH / "tools" / "control.py")
CONFIG = json.loads((BENCH / "configs" / "higgs-10m5-quarter.json").read_text())
LIMITS = json.loads((BENCH / "configs" / "higgs-10m5-quarter.limits.json").read_text())
ROWS = 60000


@pytest.mark.parametrize("case", control.CASES)
@pytest.mark.parametrize("seed", [11, 3000000011, 13])
def test_case_fails(case, seed):
    compared, _ = control.read_case(case, CONFIG, LIMITS, seed, ROWS)
    assert not compare.correct(compared)


#: ``control.py --config epsilon-400k --seeds 77 --rows 2048`` as the parent
#: of PR 34 read it, when the tool still called ``make_data`` and ``Params``
#: itself (my host run, PR 34); ``compare.UNREADABLE`` where the split taken
#: is no valid candidate under Epsilon's child-weight floor
EPSILON_BEFORE = {
    "control": (0.0, 0.0025113784537797107, 5.988421783465028e-05,
                0.0007753211864198078),
    "half_batch": (1e308, 0.513200081344197, 0.007009053977785642,
                   0.13217138055850097),
    "no_exchange": (1e308, 1.4347697787834288, 0.002563437566308789,
                    0.31233755660944457),
    "state_unchanged": (1e308, 1.0, 0.00458087586663056, 0.09171140979641132),
}


@pytest.mark.parametrize("case", control.CASES)
def test_through_the_configurations_entries_the_tool_reads_what_it_read(case):
    path = BENCH / "configs" / "epsilon-400k.json"
    compared, _ = control.read_case(
        case, json.loads(path.read_text()),
        json.loads(path.with_suffix(".limits.json").read_text()), 77, 2048)
    got = {name: v for name, v, _limit in compared}
    assert [got[n] for n in ("gain_gap", "leaf_gap", "logloss_gap",
                             "margin_norm_gap")] \
        == pytest.approx(EPSILON_BEFORE[case], rel=1e-9)
    assert not compare.correct(compared)


def test_reference_following_itself_reads_nought():
    codes, y = bdata.make_data(ROWS, 28, 256, 5)
    p = reference.Params(6, 256, 0.3, 1.0, 1.0)
    free = reference.boost_rounds(codes, y, p, 3, procs=3)
    one = reference.boost_rounds(codes, y, p, 3, procs=1)
    assert np.array_equal(free.feature, one.feature)       # any process count
    assert np.array_equal(free.leaf, one.leaf)
    fol = reference.boost_rounds(codes, y, p, 3, follow=(
        free.feature, free.threshold, free.leaf))
    assert fol.gain_gap == [0.0] * 3 and fol.leaf_gap == [0.0] * 3
    assert fol.split_differs == [0] * 3
    assert fol.logloss == free.logloss


def test_same_seed_same_data_and_large_seeds_work():
    a = bdata.make_data(1000, 28, 256, 2**31 + 12345)
    b = bdata.make_data(1000, 28, 256, 2**31 + 12345)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert not np.array_equal(a[0], bdata.make_data(1000, 28, 256, 1)[0])
    assert a[0].dtype == np.uint8 and 0.2 < a[1].mean() < 0.8
