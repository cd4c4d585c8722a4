"""The four metrics that read a hop phase by phase, in the traced rehearsal
of their cell: the CPU, 6,000 rows, kernels interpreted, through the
launcher (``test_spans_rehearsal.py``'s pattern).  On a CPU the host
function runs inside ONE ``pure_callback`` operation of an XLA thread, so a
hop has a device side there too; the values are a CPU's and are held to
nothing but being there and adding up."""

import json

import pytest

from conftest import ROOT
from harness import hops, spans
from test_rehearsal import check_line, rehearse, traced_seconds

M = json.loads((ROOT / "BENCHMARK.json").read_text())
CELL = next(c for c in M["workloads"] if c["name"] == "higgs-quarter.engine-hop")
PAIRED = ("engine.hop_to_host_ms", "engine.hop_to_device_ms",
          "engine.hop_jitter_ms")


def test_the_hop_readers_are_listed_in_the_engine_hop_cell_alone():
    for name in PAIRED + ("engine.hop_copy_ms",):
        (m,) = [m for m in M["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL["name"]] and m["layer"] == "engine"
        assert (m["source"], m["moves"], m["unit"], m["better"]) == (
            "device_trace", "round_p50_ms", "ms", "lower")


def test_hop_metrics_read_in_the_traced_rehearsal(capsys):
    line = rehearse(capsys, CELL["name"], trace=1,
                    seconds=traced_seconds(CELL, {}))
    names = {m["name"] for m in M["per_layer"] if CELL["name"] in m["workloads"]}
    check_line(line, CELL, names)
    got = {n: v["value"] for n, v in line["metrics"].items()}
    assert got["engine.hop_copy_ms"] > 0
    assert all(got[n] >= 0 for n in PAIRED if n in got)

    t = hops.table({"cell": CELL})
    rows = t["hops"]
    config = json.loads((ROOT / ".bench_runs" / CELL["name"] / "spec.json")
                        .read_text())["config"]
    depth, f, b = config["max_depth"], config["features"], config["max_bin"]
    assert t["rounds"] == line["traced_rounds"]
    assert len(rows) + t["dropped"] == (depth + 1) * t["rounds"]
    assert len(rows) == spans.table({"cell": CELL})["spans"]["gbdt.cross"]["count"] - t["dropped"]
    built = [2 ** k if k < 5 else 2 ** (k - 1) for k in range(depth)]
    want = dict(zip([2 ** k for k in range(depth)] + [-1],
                    [4 * n * f * b * 2 for n in built] + [4 * 2 ** depth * 2]))
    for r in rows:
        assert set(r["spans"]) == set(hops.COPIES) | {hops.ENGINE}
        assert r["nbytes"] == want[r["level"]]
        assert 0 < r["copy_s"] and 0 < r["engine_s"] and 0 <= r["other_s"]
        assert r["copy_s"] + r["engine_s"] + r["other_s"] == pytest.approx(
            r["callback_s"])
    assert len({r["version"] for r in rows}) == t["rounds"]   # one a round
    if not t["dropped"]:
        assert got["engine.hop_copy_ms"] + got["engine.allreduce_ms"] <= (
            got["engine.callback_ms"] * (1 + 1e-9))
    paired = hops.rows(t, paired=True)
    if paired:
        assert got["engine.hop_to_host_ms"] == pytest.approx(
            hops.mean_ms(paired, "to_host_s"))
        for r in paired:
            assert r["to_host_s"] + r["callback_s"] + r["to_device_s"] == (
                pytest.approx(r["hop_s"]))
        # on a CPU the XLA threads share one plane and the host function's
        # spans nest in the operation, so its self time is not held to
        # ``engine.hop_device_ms`` here: the recorded v5e trace is
        assert sum(r["device_s"] for r in paired) <= t["device_s"] * (1 + 1e-9)
