"""``BENCHMARK.json`` against the contract's letter, and against the files
that the harness finds by the names in it."""

import json
import re

import pytest

import run
from conftest import BENCH, ROOT

M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = {c["name"]: c for c in M["workloads"]}
E2E = {m["name"]: m for m in M["end_to_end"]}


def reports(metric: dict) -> set:
    return set(metric.get("workloads", CELLS))


def test_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert M["paths"] == ["benchmark"] and M["command"][1].startswith("benchmark/")
    assert 1 <= M["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", M["configs"] + M["workloads"] + M["end_to_end"]
                         + M["per_layer"], ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in SOURCES
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
                and "\t" not in entry[key]


def test_no_name_twice():
    for group in (M["configs"], M["workloads"], M["end_to_end"] + M["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs_are_files_of_their_own_and_used():
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))
    used = {c["config"] for c in M["workloads"]}
    for c in M["configs"]:
        assert c["name"] in used and c["file"].startswith("benchmark/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert (ROOT / c["file"]).with_suffix(".limits.json").exists()
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not re.search(r"(_dim|_rank)$", key)
            assert key in body and f"{key}_published" in body


@pytest.mark.parametrize("entry", M["configs"], ids=lambda e: e["name"])
def test_a_configuration_names_its_data_its_reference_and_its_switches(entry):
    """The three keys ``harness/deployment.py`` reads, each a file under the
    benchmark's own directories with the functions the harness calls."""
    from harness import deployment

    body = json.loads((ROOT / entry["file"]).read_text())
    files = {"data": body["data"]["file"], "reference": body["reference"]}
    for path in files.values():
        assert any(path.startswith(d + "/") for d in M["paths"]), path
        assert (ROOT / path).is_file(), path
    assert callable(deployment.module_at(files["data"]).make)
    reference = deployment.module_at(files["reference"])
    for name in ("follow", "free", "first_numbers"):
        assert callable(getattr(reference, name)), name
    assert isinstance(body["program"]["block_rows"], int)


def test_cells_find_their_traffic_and_four_chip_cells_are_few():
    for c in M["workloads"]:
        assert c["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{c['traffic']}.json").exists()
    four = [c for c in M["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)


def test_end_to_end_metrics():
    assert "setup_s" in E2E and "workloads" not in E2E["setup_s"]
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert reports(m) <= set(CELLS)
    for cell in CELLS:
        assert sum(cell in reports(m) for m in M["end_to_end"]) >= 2


def test_every_cell_of_a_layer_metric_reports_the_metric_it_moves():
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in E2E
        assert reports(m) <= reports(E2E[m["moves"]]), m["name"]
    for cell in CELLS:
        assert any(cell in reports(m) for m in M["per_layer"])


@pytest.mark.parametrize("m", M["end_to_end"] + M["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader_that_says_the_same(m):
    reader = run.load_reader(m["name"])
    assert reader.UNIT == m["unit"] and reader.SOURCE == m["source"]
    if "layer" in m:
        assert reader.LAYER == m["layer"] and reader.MOVES == m["moves"]
    assert callable(reader.read) and reader.__doc__


def test_a_share_of_a_peak_is_named_for_what_it_is():
    names = [m["name"] for m in M["per_layer"]]
    assert any(n.endswith("_roofline") for n in names)
    assert any("mfu" in re.split(r"[._\-]", n) for n in names)
    for m in M["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"
