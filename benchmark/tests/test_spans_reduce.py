"""``harness/spans.py`` on hand-made intervals: self time, a gap split over
nested spans, idle to the deepest span, stats summed.  No jax."""

import pytest

from harness import spans

MS = 1_000_000


def span(name, a, b, **stats):
    return (name, a * MS, b * MS, stats)


def one_round(t0):
    """round [0, 100), checkpoint [100, 160) holding rabit.checkpoint
    [102, 158) with pickle [102, 110), commit [110, 114) and spill
    [114, 156) with two encodes and two writes."""
    return [
        span("round", t0, t0 + 100),
        span("checkpoint", t0 + 100, t0 + 160),
        span("rabit.checkpoint", t0 + 102, t0 + 158),
        span("rabit.checkpoint.pickle", t0 + 102, t0 + 110, nbytes=1000),
        span("rabit.checkpoint.commit", t0 + 110, t0 + 114, nbytes=1000),
        span("rabit.checkpoint.spill", t0 + 114, t0 + 156),
        span("rabit.spill.encode", t0 + 114, t0 + 120, raw=100, encoded=10),
        span("rabit.spill.write", t0 + 120, t0 + 124, bytes=26),
        span("rabit.spill.encode", t0 + 125, t0 + 150, raw=900, encoded=800),
        span("rabit.spill.write", t0 + 150, t0 + 155, bytes=816),
    ]


@pytest.fixture
def raw():
    main = one_round(0) + one_round(160)
    return {"devices": {"/device:TPU:0": [(2 * MS, 98 * MS),
                                          (161 * MS, 259 * MS)]},
            "threads": {"python3#0": main}}


def test_window_rounds_and_counts(raw):
    t = spans.reduce(raw)
    assert t["devices"] == 1 and t["rounds"] == 2
    assert t["window_s"] == pytest.approx(0.320)
    assert t["spans"]["rabit.spill.encode"]["count"] == 4
    assert t["spans"]["rabit.checkpoint"]["total_s"] == pytest.approx(0.112)


def test_self_time_is_the_interval_less_the_children_of_the_same_thread(raw):
    s = spans.reduce(raw)["spans"]
    # checkpoint 60 ms a round holds rabit.checkpoint's 56
    assert s["checkpoint"]["self_s"] == pytest.approx(2 * 0.004)
    # rabit.checkpoint 56 = pickle 8 + commit 4 + spill 42 + 2 of its own
    assert s["rabit.checkpoint"]["self_s"] == pytest.approx(2 * 0.002)
    # spill 42 = 6 + 4 + 25 + 5 + 2 of its own (the gap at 124, the tail)
    assert s["rabit.checkpoint.spill"]["self_s"] == pytest.approx(2 * 0.002)
    assert s["rabit.spill.encode"]["self_s"] == pytest.approx(2 * 0.031)


def test_stats_are_summed(raw):
    s = spans.reduce(raw)["spans"]
    assert s["rabit.spill.encode"]["raw"] == 2000
    assert s["rabit.spill.encode"]["encoded"] == 1620
    assert s["rabit.spill.write"]["bytes"] == 2 * 842
    assert "raw" not in s["round"]


def test_a_gap_is_split_over_the_spans_that_share_it(raw):
    """The device idles from 98 to 161 in the first round: one gap, which
    xplane.reduce hands whole to ``checkpoint``; here every stretch of it
    goes to the deepest span open then."""
    t = spans.reduce(raw)
    idle = t["idle_by_span"]
    assert sum(idle.values()) == pytest.approx(t["idle_s"])
    assert t["idle_s"] == pytest.approx((2 + 63 + 61) / 1e3)
    # round: 0-2 and 98-100 in the first round, 160-161 and 259-260 in
    # the second
    assert idle["round"] == pytest.approx(0.006)
    assert idle["rabit.spill.encode"] == pytest.approx(2 * 0.031)
    assert idle["rabit.spill.write"] == pytest.approx(2 * 0.009)
    assert idle["rabit.checkpoint.pickle"] == pytest.approx(2 * 0.008)
    assert idle["rabit.checkpoint.spill"] == pytest.approx(2 * 0.002)
    assert idle["checkpoint"] == pytest.approx(2 * 0.004)
    assert spans.NO_SPAN not in idle


def test_idle_goes_to_the_deepest_span_on_whichever_thread():
    """A callback thread: the main thread waits in ``round`` while the
    device waits for the host inside gbdt.cross > engine_hop >
    rabit.allreduce."""
    raw = {"devices": {"d": [(0, 10 * MS), (30 * MS, 100 * MS)]},
           "threads": {
               "python3#0": [span("round", 0, 100),
                             span("checkpoint", 100, 101)],
               "callback#1": [span("gbdt.cross", 8, 32, level=4),
                              span("engine_hop", 12, 28),
                              span("rabit.allreduce", 13, 27, nbytes=64)]}}
    t = spans.reduce(raw)
    idle = t["idle_by_span"]
    assert idle["gbdt.cross"] == pytest.approx(0.004)      # 10-12, 28-30
    assert idle["engine_hop"] == pytest.approx(0.002)      # 12-13, 27-28
    assert idle["rabit.allreduce"] == pytest.approx(0.014)
    assert idle["checkpoint"] == pytest.approx(0.001)
    assert "round" not in idle
    assert t["spans"]["gbdt.cross"]["self_s"] == pytest.approx(0.008)
    assert t["spans"]["round"]["self_s"] == pytest.approx(0.100)  # other thread
    assert spans.per_call_ms(t, "rabit.allreduce") == pytest.approx(14.0)


def test_devices_are_averaged_and_no_span_takes_what_is_left():
    raw = {"devices": {"a": [(0, 50 * MS)], "b": [(0, 100 * MS)]},
           "threads": {"t#0": [span("round", 0, 40),
                               span("checkpoint", 60, 100)]}}
    t = spans.reduce(raw)
    assert t["devices"] == 2 and t["idle_s"] == pytest.approx(0.025)
    assert t["idle_by_span"] == {"checkpoint": pytest.approx(0.020),
                                 spans.NO_SPAN: pytest.approx(0.005)}


def test_nothing_to_read():
    assert spans.reduce({"devices": {}, "threads": {}}) is None
    assert spans.reduce({"devices": {"d": [(0, 5)]},
                         "threads": {"t#0": [span("round", 0, 1)]}}) is None
    assert spans.per_round_ms(None, "rabit.checkpoint.pickle") is None
    t = spans.reduce({"devices": {"d": [(0, 5)]}, "threads": {
        "t#0": [span("round", 0, 1), span("checkpoint", 1, 2)]}})
    assert t is not None and not spans.program_spans(t)
    assert spans.per_round_ms(t, "rabit.checkpoint.pickle") is None
    assert spans.per_call_ms(t, "gbdt.cross") is None
    assert spans.per_round_ms(t, "checkpoint") == pytest.approx(1.0)
