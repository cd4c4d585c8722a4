"""Every cell at a tiny size on the CPU, kernels interpreted, through the
launcher — the kill and the resume included — and the proof that a cell, a
configuration and a per-layer metric are added by files and entries alone.

A rehearsal's last line names ``cpu`` and carries ``"rehearsal": true``; it
can never pass for a chip run, and no device metric is read from it."""

import json
import shutil

import pytest

import run
from conftest import BENCH, ROOT, bench_bytes

M = json.loads((ROOT / "BENCHMARK.json").read_text())
CPU_TRACE = {"device_plane": "^/host:CPU$", "op_lines": ["^tf_XLA"]}
#: rows of a rehearsal, a configuration: the width is the configuration's, so
#: at 2,000 features all sixteen feature tiles are rehearsed, and there an
#: interpreted round is 2.6 s a row block on a CPU (6,000 rows: 24 s a round)
ROWS = {"epsilon-400k": 2048}
#: the window of a traced rehearsal, a configuration: ``trace_skip`` rounds
#: (three at most) and a traced one or more have to fit; Epsilon's take 5 s
TRACED_S = {"epsilon-400k": 30}


def rows_of(workload, manifest=None):
    m = json.loads(manifest.read_text()) if manifest else M
    config = next((c["config"] for c in m["workloads"] if c["name"] == workload),
                  None)
    return ROWS.get(config, 6000)


def traced_seconds(cell, kill):
    return TRACED_S.get(cell["config"], 14 if kill else 8)


def rehearse(capsys, workload, trace=0, seconds=6, traffic=None, plant=None,
             manifest=None, seed=3000000019):
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  rehearsal={"rows": rows_of(workload, manifest),
                             "traffic": traffic or {},
                             "plant": {"trace_rules": CPU_TRACE, **(plant or {})}},
                  manifest=manifest)
    assert rc == 0
    captured = capsys.readouterr()
    line = json.loads(captured.out.strip().splitlines()[-1])
    line["summary"] = next(json.loads(s) for s in captured.err.splitlines()
                           if s.startswith('{"rounds_in_window"'))
    return line


def check_line(line, cell, names):
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert list(line)[-2:] == ["compared", "summary"]   # the line's last, ours
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    assert line["device"]["count"] == cell["chips"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) <= names
    for v, lim in line["compared"].values():
        assert v <= lim
    assert line["correct"] is True


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_end_to_end(capsys, cell):
    kill = {"kill_after_commit": 5} if "kill" in cell["traffic"] else {}
    line = rehearse(capsys, cell["name"], traffic=kill, seconds=12 if kill else 6)
    want = {m["name"] for m in M["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}
    check_line(line, cell, want)
    # a tail needs twenty rounds, which a loaded CPU may not reach in time
    assert want - {"round_p95_ms"} <= set(line["metrics"]) <= want
    if kill:
        assert line["compared"]["resume_mismatch"] == [0, 0]
    assert set(line["compared"]) == {
        "gain_gap", "leaf_gap", "logloss_gap", "margin_norm_gap",
        "commit_mismatch"} | ({"resume_mismatch"} if kill else set())
    if cell["traffic"] == "engine-hop":
        # depth + 1 hops a round, at every width
        depth = next(json.loads((ROOT / c["file"]).read_text())["max_depth"]
                     for c in M["configs"] if c["name"] == cell["config"])
        trees, _capacity = line["summary"]["trees_of_capacity"]
        assert line["summary"]["engine_hops"] == (depth + 1) * trees


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_traced(capsys, cell):
    kill = {"kill_after_commit": 5} if "kill" in cell["traffic"] else {}
    line = rehearse(capsys, cell["name"], trace=1, traffic=kill,
                    seconds=traced_seconds(cell, kill))
    names = {m["name"] for m in M["per_layer"] if cell["name"] in m["workloads"]}
    check_line(line, cell, names)
    # shares of the chip's peak are not read from a CPU: the table has no row
    assert not {n for n in line["metrics"] if "roofline" in n or "mfu" in n}
    assert {"api.checkpoint_ms", "compile.cache_load_s", "step.device_ms",
            "device.idle_pct"} <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"] and line["traced_rounds"] > 0
    if kill:
        assert {"launch.respawn_s", "api.restore_s",
                "compile.cache_load_s.resume"} <= set(line["metrics"])
    if cell["traffic"] == "engine-hop":
        # the hops' transfers are device operations, counted as busy time
        hop = line["metrics"]["engine.hop_device_ms"]["value"]
        assert 0 < hop < line["metrics"]["step.device_ms"]["value"]


def test_adding_a_cell_a_configuration_and_a_metric_takes_only_files(capsys, tmp_path):
    """README's worked example: cell 4 (engine-hop) on a configuration of its
    own with a per-layer metric of its own — new files, new entries, and no
    edit to a file that exists."""
    before = bench_bytes()
    added = [BENCH / "configs" / "zz-dummy.json",
             BENCH / "configs" / "zz-dummy.limits.json",
             BENCH / "metrics" / "zz.hops_a_round.py"]
    try:
        quarter = BENCH / "configs" / "higgs-10m5-quarter.json"
        body = json.loads(quarter.read_text())
        body["source"] += " (dummy)"
        added[0].write_text(json.dumps(body))
        shutil.copy(quarter.with_suffix(".limits.json"), added[1])
        added[2].write_text(
            '"""Engine hops a round (a count)."""\n'
            'UNIT, SOURCE, LAYER, MOVES = "hops", "program_counter", "engine", '
            '"round_p50_ms"\n\n\n'
            'def read(ev):\n'
            '    n = sum(life["hops"][1] for life in ev["lives"])\n'
            '    return n / (ev["traffic"]["check_rounds"] + len(ev["rounds"])) or None\n')
        m = json.loads(json.dumps(M))
        name = "dummy.engine-hop"
        m["configs"].append({"name": "zz-dummy", "source": body["source"],
                             "file": "benchmark/configs/zz-dummy.json",
                             "reduced": ["rows"], "why": "test"})
        m["workloads"].append({"name": name, "config": "zz-dummy",
                               "traffic": "engine-hop", "chips": 1, "why": "test"})
        for kind, mname, unit in (("engine", "engine.hop_ms", "ms"),
                                  ("engine", "zz.hops_a_round", "hops")):
            m["per_layer"].append({
                "name": mname, "unit": unit, "better": "lower",
                "source": "host_clock" if unit == "ms" else "program_counter",
                "layer": kind, "moves": "round_p50_ms", "workloads": [name]})
        for e in m["end_to_end"]:
            if "workloads" in e:
                e["workloads"].append(name)
        manifest = tmp_path / "BENCHMARK.json"
        manifest.write_text(json.dumps(m))
        line = rehearse(capsys, name, trace=1, seconds=6, manifest=manifest)
        assert line["correct"] is True
        assert line["metrics"]["zz.hops_a_round"]["value"] == 7   # depth + 1
        assert line["metrics"]["engine.hop_ms"]["value"] > 0
    finally:
        for p in added:
            p.unlink(missing_ok=True)
    assert bench_bytes() == before


def test_the_kept_cell_epsilon_engine_hop_takes_only_entries(capsys, tmp_path):
    """``epsilon.engine-hop`` was left out at PR 33 for its spread on the chip
    (PERF.md, sections 2 and 7).  It is data alone: one entry under
    ``workloads`` and its name in the lists of the readers of
    ``epsilon.fused-armed`` and of ``higgs-quarter.engine-hop``; the hybrid
    round then runs Epsilon's sixteen feature tiles and nine hops a round."""
    m = json.loads(json.dumps(M))
    name = "epsilon.engine-hop"
    if all(c["name"] != name for c in m["workloads"]):
        m["workloads"].append({"name": name, "config": "epsilon-400k",
                               "traffic": "engine-hop", "chips": 1, "why": "test"})
        for e in m["per_layer"]:
            if {"epsilon.fused-armed", "higgs-quarter.engine-hop"} & set(e["workloads"]) \
                    and e["name"] != "engine.hop_round_p95_ms":
                e["workloads"].append(name)
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(m))
    cell = m["workloads"][-1]
    line = rehearse(capsys, name, trace=1, seconds=traced_seconds(cell, {}),
                    manifest=manifest)
    names = {e["name"] for e in m["per_layer"] if name in e["workloads"]}
    check_line(line, cell, names)
    assert line["correct"] is True
    assert {"engine.hop_ms", "engine.callback_ms", "engine.allreduce_ms",
            "step.device_ms", "api.checkpoint_ms"} <= set(line["metrics"])
    trees, _capacity = line["summary"]["trees_of_capacity"]
    assert line["summary"]["engine_hops"] == 9 * trees      # depth 8, the leaves'
    hop = line["metrics"]["engine.hop_device_ms"]["value"]
    assert 0 < hop < line["metrics"]["step.device_ms"]["value"]
