"""The seven metrics that read the program's own spans, in the traced
rehearsal of each of their cells: the CPU, 6,000 rows, kernels interpreted,
through the launcher (``test_rehearsal.py``'s pattern).  The values are a
CPU's and are held to nothing but being there and adding up."""

import json

import pytest

from conftest import ROOT
from harness import spans
from test_rehearsal import check_line, rehearse

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("api.ckpt_pickle_ms", "api.ckpt_commit_ms", "api.ckpt_encode_ms",
       "api.ckpt_write_ms", "engine.allreduce_ms", "engine.callback_ms",
       "device.idle_unattributed_pct")


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_span_metrics_read_in_their_cells(capsys, cell):
    kill = {"kill_after_commit": 5} if "kill" in cell["traffic"] else {}
    line = rehearse(capsys, cell["name"], trace=1, traffic=kill,
                    seconds=14 if kill else 8)
    names = {m["name"] for m in M["per_layer"] if cell["name"] in m["workloads"]}
    check_line(line, cell, names)
    want = {m["name"] for m in M["per_layer"]
            if m["name"] in NEW and cell["name"] in m["workloads"]}
    assert len(want) >= 3 and want <= set(line["metrics"])
    got = {n: line["metrics"][n]["value"] for n in want}
    assert all(v >= 0 for v in got.values())
    assert all(got[n] > 0 for n in want - {"device.idle_unattributed_pct"})

    t = spans.table({"cell": cell})
    assert spans.program_spans(t) and t["rounds"] == line["traced_rounds"]
    # the same window and the same idle time as the accepted reduction
    assert t["window_s"] == pytest.approx(line["device"]["window_s"])
    assert sum(t["idle_by_span"].values()) == pytest.approx(
        line["device"]["window_s"] - line["device"]["busy_s"], rel=1e-6)
    s = t["spans"]
    assert s["rabit.checkpoint"]["count"] == t["rounds"]
    inside = sum(s[n]["total_s"] for n in s
                 if n.startswith("rabit.checkpoint."))
    assert inside <= s["rabit.checkpoint"]["total_s"] <= s["checkpoint"]["total_s"]
    if kill:
        assert s["rabit.spill.encode"]["count"] == 2 * t["rounds"]
        assert 0 < s["rabit.spill.encode"]["encoded"] <= s["rabit.spill.encode"]["raw"] * 1.01
        assert s["rabit.spill.write"]["bytes"] > s["rabit.spill.encode"]["encoded"]
    if cell["traffic"] == "engine-hop":
        hops = s["rabit.allreduce"]["count"]
        assert hops == s["gbdt.cross"]["count"] == 7 * t["rounds"]
        assert got["engine.allreduce_ms"] <= got["engine.callback_ms"]


def test_a_program_without_spans_reads_nothing(tmp_path):
    """The parent commit's traces hold the worker's four spans alone: the
    readers then return nothing, and do not raise."""
    import run

    t = {"devices": 1, "window_s": 1.0, "rounds": 2, "idle_s": 0.1,
         "spans": {"round": {"count": 2, "total_s": 0.8, "self_s": 0.8},
                   "checkpoint": {"count": 2, "total_s": 0.2, "self_s": 0.2}},
         "idle_by_span": {"checkpoint": 0.1}}
    ev = {"cell": {"name": "no-such-cell"}}
    assert spans.table(ev) is None
    for name in NEW:
        assert run.load_reader(name).read(ev) is None
    assert not spans.program_spans(t)
