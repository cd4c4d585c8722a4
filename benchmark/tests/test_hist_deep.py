"""The two readers of the deep histogram levels (level 6 on, where the
stacked gradient matrix passes one MXU tile) on a hand-made table."""

import pytest

import run
from harness import work

CONFIG = {"rows": 1000, "features": 67, "max_bin": 256, "max_depth": 8}
DEVICE = {"kind": "TPU v5 lite", "count": 1}


def ev(ops, rounds=2):
    return {"trace": {"rounds": rounds, "ops": ops}, "config": CONFIG,
            "device": DEVICE}


def test_levels_six_and_up_are_summed():
    ops = {"hist_level0.1": [2, 9.0], "hist_level_d5.1": [2, 9.0],
           "hist_level_d6.1": [2, 0.012], "hist_sibling_d6.1": [2, 9.0],
           "hist_level_d7.1": [2, 0.020], "hist_level_d10.3": [2, 0.004],
           "route_level_d8.1": [2, 9.0], "fusion.6": [2, 9.0]}
    assert run.load_reader("kernel.hist_deep_ms").read(ev(ops)) == \
        pytest.approx(1e3 * 0.036 / 2)
    passes = [work.hist_pass(1000, 67, 256, d) for d in (6, 7)]
    need = sum(p["bytes"] for p in passes) / 819e9      # the bytes bound it
    assert run.load_reader("kernel.hist_deep_roofline").read(ev(ops)) == \
        pytest.approx(100 * need / (0.036 / 2))


@pytest.mark.parametrize("name", ["kernel.hist_deep_ms",
                                  "kernel.hist_deep_roofline"])
def test_nothing_to_read_is_none(name):
    read = run.load_reader(name).read
    assert read(ev({"hist_level_d5.1": [2, 1.0], "fusion.6": [2, 1.0]})) is None
    assert read({"trace": None, "config": CONFIG, "device": DEVICE}) is None
