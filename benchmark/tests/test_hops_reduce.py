"""``harness/hops.py`` on hand-made intervals: a device operation goes to
the hop it belongs to, a hop with no device operation is dropped and
counted, the phases add up, the jitter is by level over rounds, ``beside``
is what ran between the sends and the receive.  No jax."""

import pytest

import run
from harness import hops, xplane

MS = 1_000_000
DEVICE = "/device:TPU:0"
READERS = ("engine.hop_to_host_ms", "engine.hop_to_device_ms",
           "engine.hop_copy_ms", "engine.hop_jitter_ms")


def op(name, a, b):
    return (name, a * MS, b * MS)


def span(name, a, b, **stats):
    return (name, a * MS, b * MS, stats)


def hop(t, level, version, wait=0.0, gap=0.0, nbytes=4096):
    """One hop from ``t``: two sends [t, t+1), [t+1, t+2); after ``gap`` the
    receive, to t+12+gap+wait; ``gbdt.cross`` [t+3, t+9) with its five
    children: copies 1 + 1 + 0.5 + 2, engine 1, 0.5 of its own."""
    r = t + gap
    ops = [op("pure_callback.7", t, t + 1), op("pure_callback.8", t + 1, t + 2),
           op("pure_callback.9", r + 2, r + 12 + wait)]
    fields = {"version": version, "nbytes": nbytes}
    program = [
        span("gbdt.cross", r + 3, r + 9, level=level, **fields),
        span("gbdt.cross.in", r + 3, r + 4, **fields),
        span("rabit.allreduce.copy_in", r + 4, r + 5, **fields),
        span("rabit.allreduce", r + 5, r + 6, seqno=0, **fields),
        span("rabit.allreduce.copy_out", r + 6, r + 6.5, **fields),
        span("gbdt.cross.out", r + 7, r + 9, **fields),
    ]
    return ops, program


def trace(hops_of_round):
    """Rounds of 110 ms: ``round`` [0, 100), ``checkpoint`` [100, 110); a
    kernel of 8 ms after every hop."""
    raw = {"devices": {DEVICE: []}, "async": {}, "host": [], "program": []}
    for k, made in enumerate(hops_of_round):
        t = 110 * k
        raw["host"] += [op("round", t, t + 100), op("checkpoint", t + 100, t + 110)]
        for j, kw in enumerate(made):
            ops, program = hop(t + 5 + 30 * j, version=k, **kw)
            end = max(e[2] for e in ops) / MS
            raw["devices"][DEVICE] += ops + [op(f"hist_level_d{j}.1", end, end + 8)]
            raw["program"] += program
    return raw


LEVELS = [{"level": 1}, {"level": 2}, {"level": -1, "nbytes": 64}]


@pytest.fixture
def raw():
    second = [dict(LEVELS[0]), dict(LEVELS[1], wait=5.0), dict(LEVELS[2], wait=1.5)]
    return trace([LEVELS, second])


def test_one_row_a_hop_with_its_round_level_and_bytes(raw):
    t = hops.reduce(raw)
    assert (t["devices"], t["rounds"], t["dropped"]) == (1, 2, 0)
    assert t["window_s"] == pytest.approx(0.220)
    assert [(r["round"], r["level"], r["nbytes"], r["version"])
            for r in t["hops"]] == [(0, 1, 4096, 0), (0, 2, 4096, 0), (0, -1, 64, 0),
                                    (1, 1, 4096, 1), (1, 2, 4096, 1), (1, -1, 64, 1)]
    assert all(r["device_ops"] == 3 for r in t["hops"])
    first = t["hops"][0]
    assert (first["t0"], first["t1"], first["t2"], first["t3"]) == pytest.approx(
        (0.005, 0.008, 0.014, 0.017))
    assert first["spans"] == pytest.approx({
        "gbdt.cross.in": 0.001, "rabit.allreduce.copy_in": 0.001,
        "rabit.allreduce": 0.001, "rabit.allreduce.copy_out": 0.0005,
        "gbdt.cross.out": 0.002})


def test_the_phases_add_up(raw):
    t = hops.reduce(raw)
    for r in t["hops"]:
        assert r["to_host_s"] == pytest.approx(0.003)
        assert r["copy_s"] == pytest.approx(0.0045)
        assert r["engine_s"] == pytest.approx(0.001)
        assert r["other_s"] == pytest.approx(0.0005)
        assert r["callback_s"] == pytest.approx(
            r["copy_s"] + r["engine_s"] + r["other_s"]) == pytest.approx(0.006)
        assert r["to_host_s"] + r["callback_s"] + r["to_device_s"] == pytest.approx(
            r["t3"] - r["t0"]) == pytest.approx(r["hop_s"])
        assert sum(r[p] for p in hops.PHASES) == pytest.approx(r["hop_s"])
    assert [r["to_device_s"] for r in t["hops"]] == pytest.approx(
        [0.003, 0.003, 0.003, 0.003, 0.008, 0.0045])


def test_the_rows_device_operations_are_engine_hop_device_ms(raw):
    """The kernels between the hops are no hop's; the hops' operations sum
    to what ``engine.hop_device_ms`` reads from the accepted reduction."""
    t = hops.reduce(raw)
    assert t["device_ops"] == 18
    assert sum(r["device_s"] for r in t["hops"]) == pytest.approx(t["device_s"])
    assert t["device_s"] == pytest.approx((6 * 12 + 5 + 1.5) / 1e3)
    accepted = run.load_reader("engine.hop_device_ms").read(
        {"trace": xplane.reduce(raw)})
    assert accepted * t["rounds"] == pytest.approx(1e3 * t["device_s"])


def test_jitter_is_the_widest_range_of_one_level_over_the_rounds(raw):
    t = hops.reduce(raw)
    assert {k: len(v) for k, v in hops.by_level(t["hops"]).items()} == {
        1: 2, 2: 2, -1: 2}
    assert hops.jitter_ms(t) == pytest.approx(5.0)       # level 2: 12 and 17 ms
    assert hops.mean_ms(hops.rows(t, paired=True), "to_device_s") == pytest.approx(
        (4 * 3 + 8 + 4.5) / 6)
    assert hops.mean_ms(hops.rows(t), "copy_s") == pytest.approx(4.5)
    one = hops.reduce(trace([LEVELS]))
    assert one["rounds"] == 1 and hops.jitter_ms(one) is None


def test_beside_is_what_ran_between_the_sends_and_the_receive():
    raw = trace([[dict(LEVELS[0], gap=4.0), LEVELS[1]]])
    # the sends end at 7 ms, the receive starts at 11: a kernel of 1.5 ms in
    # between, one that starts before the gap and one inside the receive
    raw["devices"][DEVICE] += [op("route_level_d6.1", 8, 9.5),
                               op("fusion.2", 6.5, 7.25), op("fusion.3", 12, 13)]
    t = hops.reduce(raw)
    assert [r["beside_s"] for r in t["hops"]] == pytest.approx([0.00175, 0.0])
    assert t["hops"][0]["to_host_s"] == pytest.approx(0.007)
    assert t["hops"][0]["hop_s"] == pytest.approx(0.016)
    assert t["hops"][0]["device_s"] == pytest.approx(0.012)


def test_a_hop_that_nothing_on_the_device_opens_is_dropped_and_counted(raw):
    # a gbdt.cross with no operation at all, one whose only operation starts
    # after it has opened, and one outside the window
    raw["program"] += [span("gbdt.cross", 95, 97, level=64, version=0),
                       span("gbdt.cross", 208, 214, level=128, version=1),
                       span("gbdt.cross", 230, 232, level=1, version=2)]
    raw["devices"][DEVICE] += [op("pure_callback.9", 209, 216)]
    t = hops.reduce(raw)
    assert t["dropped"] == 2 and len(t["hops"]) == 6 and t["clock_shift_s"] == 0
    assert 64 not in hops.by_level(t["hops"]) and 128 not in hops.by_level(t["hops"])
    assert t["device_ops"] == 19
    assert t["device_s"] - sum(r["device_s"] for r in t["hops"]) == pytest.approx(0.007)


def test_the_devices_clock_is_moved_until_no_answer_comes_before_it_was_sent(raw):
    """A session that lays the device's clock 4 ms early: every receive of
    12 ms then ends 1 ms before its ``gbdt.cross`` closes.  The least shift
    that mends that is 1 ms: the quickest answers read 0, the slow ones what
    they took more, ``to_host`` what is left, and T3 - T0 nothing else."""
    true = hops.reduce(raw)
    raw["devices"][DEVICE] = [(n, a - 4 * MS, b - 4 * MS)
                              for n, a, b in raw["devices"][DEVICE]]
    t = hops.reduce(raw)
    assert true["clock_shift_s"] == 0 and t["clock_shift_s"] == pytest.approx(0.001)
    assert t["dropped"] == 0 and len(t["hops"]) == 6
    assert [r["to_device_s"] for r in t["hops"]] == pytest.approx(
        [0, 0, 0, 0, 0.005, 0.0015], abs=1e-12)
    for r, want in zip(t["hops"], true["hops"]):
        assert r["to_host_s"] == pytest.approx(0.006)
        assert r["hop_s"] == pytest.approx(want["hop_s"])
        assert r["device_s"] == pytest.approx(want["device_s"])
        assert r["to_host_s"] + r["callback_s"] + r["to_device_s"] == (
            pytest.approx(r["hop_s"]))
        assert r["t0"] == pytest.approx(want["t0"] - 0.003)


def test_a_trace_with_no_hop_on_a_device_keeps_the_spans_side(raw):
    """The CPU rehearsal may hold no ``pure_callback`` operation, and the
    parent of PR 36 has ``gbdt.cross`` and ``rabit.allreduce`` alone."""
    raw["devices"][DEVICE] = [e for e in raw["devices"][DEVICE]
                              if not e[0].startswith("pure_callback")]
    t = hops.reduce(raw)
    assert t["device_ops"] == 0 and t["dropped"] == 0 and len(t["hops"]) == 6
    assert hops.rows(t, paired=True) == [] and hops.jitter_ms(t) is None
    assert hops.mean_ms(hops.rows(t, paired=True), "to_host_s") is None
    assert hops.mean_ms(hops.rows(t), "copy_s") == pytest.approx(4.5)
    assert all("t0" not in r and r["callback_s"] == pytest.approx(0.006)
               for r in t["hops"])

    raw["program"] = [(n, a, b, {k: v for k, v in st.items() if n != "gbdt.cross"
                                 or k != "nbytes"})
                      for n, a, b, st in raw["program"]
                      if n in ("gbdt.cross", "rabit.allreduce")]
    old = hops.reduce(raw)
    assert hops.mean_ms(hops.rows(old), "copy_s") is None
    assert [r["nbytes"] for r in old["hops"]] == [4096, 4096, 64] * 2
    assert all(r["other_s"] == pytest.approx(0.005) for r in old["hops"])


def test_no_window_or_no_hop_reads_nothing(raw):
    assert hops.reduce({**raw, "program": []}) is None
    assert hops.reduce({**raw, "host": []}) is None
    ev = {"cell": {"name": "no-such-cell"}}
    assert hops.table(ev) is None
    for name in READERS:
        assert run.load_reader(name).read(ev) is None
