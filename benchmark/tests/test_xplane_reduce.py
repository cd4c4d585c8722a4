"""The trace -> table reduction on hand-made intervals (nanoseconds)."""

import pytest

from harness import xplane

RULES = {**xplane.RULES}


def raw_two_rounds():
    host = [("round", 0, 100), ("margin_d2h", 100, 110), ("checkpoint", 110, 150),
            ("round", 150, 250), ("margin_d2h", 250, 260), ("checkpoint", 260, 300)]
    dev = [("level_kernel.1", 10, 60), ("fusion.2", 60, 80),
           ("all-reduce.3", 80, 90),
           ("level_kernel.1", 160, 210), ("fusion.2", 210, 230),
           ("all-reduce.3", 225, 240)]
    return {"devices": {"/device:TPU:0": dev}, "host": host}


def test_union_subtract_and_self_times():
    assert xplane.union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 20]]) == [[0, 2], [3, 5]]
    nested = [("while", 0, 100), ("body.1", 10, 40), ("body.2", 50, 90)]
    assert sorted(xplane.self_times(nested)) == [
        ("body.1", 30), ("body.2", 40), ("while", 30)]


def test_busy_idle_ops_and_exposed_collective():
    t = xplane.reduce(raw_two_rounds(), RULES)
    assert t["devices"] == 1 and t["rounds"] == 2
    assert t["window_s"] == pytest.approx(300e-9)
    # round 1: 10..90 busy; round 2: 160..240 busy (225..230 counted once)
    assert t["busy_s"] == pytest.approx(160e-9)
    assert t["ops"]["level_kernel.1"] == [2, pytest.approx(100e-9)]
    assert t["collective_s"] == pytest.approx(25e-9)
    # 80..90 alone, and of 225..240 only 230..240 with nothing else running
    assert t["collective_exposed_s"] == pytest.approx(20e-9)
    gaps = dict(t["idle_gaps"])
    # 0..10 and 150..160 under "round"; 90..160 mostly under "checkpoint";
    # 240..300 mostly under "checkpoint"
    assert gaps["round"] == pytest.approx(10e-9)
    assert gaps["checkpoint"] == pytest.approx(130e-9)
    assert sum(gaps.values()) == pytest.approx(t["window_s"] - t["busy_s"])


def test_devices_are_averaged():
    raw = raw_two_rounds()
    raw["devices"]["/device:TPU:1"] = [("level_kernel.1", 10, 50)]
    t = xplane.reduce(raw, RULES)
    assert t["devices"] == 2
    assert t["busy_s"] == pytest.approx((160e-9 + 40e-9) / 2)
    assert t["ops"]["level_kernel.1"] == [1.5, pytest.approx(70e-9)]


def test_nothing_on_the_device_reads_nothing():
    assert xplane.reduce({"devices": {"/device:TPU:0": []}, "host": []}, RULES) is None


def test_readers_return_nothing_without_a_trace_and_never_zero():
    import run

    ev = {"trace": None, "config": {"rows": 1000, "features": 28, "max_bin": 256,
                                    "max_depth": 6},
          "device": {"kind": "TPU v5 lite", "count": 1}}
    for name in ("kernel.hist_roofline", "step.round_mfu", "step.device_ms",
                 "device.idle_pct", "ici.psum_exposed_ms"):
        assert run.load_reader(name).read(ev) is None
    ev["trace"] = xplane.reduce(raw_two_rounds(), RULES)
    ev["trace"]["ops"] = {"fusion.2": [2, 1e-3]}       # no histogram kernel ran
    assert run.load_reader("kernel.hist_roofline").read(ev) is None
    assert run.load_reader("step.round_mfu").read(ev) > 0


@pytest.mark.parametrize("text,short", [
    ("%hist_level.9 = (f32[64,28,256,2]{3,2,1,0:T(8,128)}, s32[2564,1024,1]{2,1,0:T(8,128)}) "
     "custom-call(f32[2564,1024,28]{2,1,0:T(8,128)} %copy.308)", "hist_level.9"),
    # a lax.psum as the v5e's trace shows it (PR 24): named after the primitive
    ("%psum.42 = f32[1,28,256,2]{2,1,3,0:T(8,128)S(1)} all-reduce(%pad_maximum_fusion.5), "
     "channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%region_0.1", "all-reduce:psum.42"),
    ("%all-reduce.3 = f32[8]{0:T(128)} all-reduce(f32[8]{0:T(128)} %x)", "all-reduce.3"),
    ("%reshape.906 = f32[2564,1024,1]{2,1,0:T(8,128)} reshape(f32[2625536]{0:T(1024)S(1)} %p)",
     "reshape.906"),
    ("barrier-cores", "barrier-cores"),
])
def test_short_names_put_a_collectives_opcode_in_front(text, short):
    import re

    coll = re.compile(RULES["collective"])
    assert xplane.short_name(text, coll) == short
    assert bool(coll.search(short)) == ("all-reduce" in short)
