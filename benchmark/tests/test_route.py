"""The readers of the routing passes and of the round's XLA side on a
hand-made table, and the routing pass's count by hand."""

import pytest

import run
from harness import route_work

CONFIG = {"rows": 1000, "features": 2000, "max_bin": 64, "max_depth": 8}
DEVICE = {"kind": "TPU v5 lite", "count": 1}
OPS = {"hist_level0.1": [2, 0.100], "hist_level_d7.1": [2, 0.300],
       "route_level_d1.1": [2, 0.004], "route_level_d8.1": [2, 0.006],
       "route_margin_d8.2": [2, 0.002], "fusion.6": [2, 0.050],
       "copy.3": [2, 0.030], "reroute.1": [2, 9.0]}


def ev(ops, rounds=2, busy_s=0.5):
    return {"trace": {"rounds": rounds, "ops": ops, "busy_s": busy_s},
            "config": CONFIG, "device": DEVICE}


def test_route_pass_by_hand():
    # 1,000 rows of 2,000 one-byte codes, the node id in and out
    assert route_work.route_pass(1000, 2000, 64) == {
        "adds": 1000, "bytes": 1000 * (2000 + 4 + 4)}
    assert route_work.route_pass(10, 3, 1024)["bytes"] == 10 * (6 + 8)
    assert route_work.route_passes(1000, 2000, 64, 8)["bytes"] == 8 * 2008000


def test_routing_operations_are_summed_and_held_to_their_bytes():
    assert run.load_reader("kernel.route_ms").read(ev(OPS)) == \
        pytest.approx(1e3 * 0.012 / 2)
    # two levels are named (1 and 8): two passes, the bytes bound them
    need = 2 * 2008000 / 819e9
    assert run.load_reader("kernel.route_roofline").read(ev(OPS)) == \
        pytest.approx(100 * need / (0.012 / 2))


def test_outside_the_kernels_is_what_is_left_of_the_device_time():
    # busy 0.5 s of two rounds, 0.4 in histograms, 0.012 in routing
    assert run.load_reader("step.outside_kernels_ms").read(ev(OPS)) == \
        pytest.approx(1e3 * (0.5 - 0.412) / 2)


@pytest.mark.parametrize("name", ["kernel.route_ms", "kernel.route_roofline",
                                  "step.outside_kernels_ms"])
def test_nothing_to_read_is_none(name):
    read = run.load_reader(name).read
    assert read(ev({"fusion.6": [2, 1.0], "copy.3": [2, 1.0]})) is None
    assert read({"trace": None, "config": CONFIG, "device": DEVICE}) is None
