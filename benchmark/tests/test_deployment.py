"""A configuration names its data, its reference and its program's switches
(``harness/deployment.py``): the accepted generator still makes the bytes it
made, a second one ties a reserved code, and a deployment with a generator
and a copy of the reference of its own runs from files and entries alone."""

import hashlib
import json
import shutil

import numpy as np
import pytest

import run
from conftest import BENCH, ROOT, bench_bytes
from harness import data as bdata, deployment
from test_rehearsal import M, rehearse

UNIFORM = "benchmark/harness/datagen/uniform.py"
TIES = "benchmark/harness/datagen/ties.py"
TIED = {"file": TIES, "shares": [0, 5, 20, 40, 60, 80, 95],
        "label_on": [3, 8], "weight": 4.0}
#: sha256 of ``make_data``'s codes and labels at the parent of PR 34, when it
#: was the one generator (my host run, PR 34): a seed past 2**31, Criteo's and
#: Epsilon's widths, and a bin count that takes uint16
BYTES_BEFORE = {
    (1000, 28, 256, 7):
        "2324e06f957932879142b4dde076d05ab13ee5502d26d06d428e011b5e36e34e",
    (4096, 67, 256, 2**31 + 12345):
        "0f17b5bf2eec8a9d0c35aab7838e567babe347e161ce240d0d3e3be12bc6df68",
    (2048, 2000, 64, 3000000019):
        "b185054b1032d27d2a82ced63fafea561b8afc0c3f84e1785bd8f3f9ab743bb5",
    (300, 5, 1024, 11):
        "5234d297963ca716266b0ffb5df5eb859a08dbedb00b6cb19b59ea60f9c918df",
}


def sized(rows, features, bins, **more):
    return {"rows": rows, "features": features, "max_bin": bins, **more}


@pytest.mark.parametrize("size", BYTES_BEFORE, ids=str)
def test_uniform_makes_the_bytes_make_data_made(size):
    rows, features, bins, seed = size
    codes, y = bdata.draw(sized(rows, features, bins, data={"file": UNIFORM}), seed)
    assert hashlib.sha256(codes.tobytes() + y.tobytes()).hexdigest() \
        == BYTES_BEFORE[size]
    again = bdata.make_data(*size)            # the name tests and tools keep
    assert np.array_equal(again[0], codes) and np.array_equal(again[1], y)


def test_ties_holds_the_reserved_code_in_the_shares_it_is_given():
    size = sized(40000, 67, 256, data=TIED)
    seed = 2**31 + 77
    codes, y = bdata.draw(size, seed)
    same = bdata.draw(size, seed)
    assert np.array_equal(codes, same[0]) and np.array_equal(y, same[1])
    plain, _ = bdata.draw({**size, "data": {"file": UNIFORM}}, seed)
    assert np.array_equal(codes[:, :2], plain[:, :2])   # as uniform has them
    assert codes.dtype == np.uint8 and 0.2 < y.mean() < 0.8
    held = (codes == 0).mean(0)
    for f in range(2, 67):
        share = TIED["shares"][(f - 2) % 7] / 100
        assert abs(held[f] - (share + (1 - share) / 256)) < 0.01, f
    # the label follows the tie: nearly every row that holds the code at
    # feature 3 is a 1, nearly every row that lacks it at feature 8 a 0
    assert y[codes[:, 3] == 0].mean() > 0.95 > 0.05 > y[codes[:, 8] != 0].mean()
    other = bdata.draw({**size, "data": {**TIED, "code": 255}}, seed)[0]
    assert (other[:, 8] == 255).mean() > 0.9 > 0.1 > (other[:, 8] == 0).mean()


def deployment_in(tmp_path, name, traffic="fused-armed", **changes):
    """A manifest in ``tmp_path`` with one more configuration (Criteo's sizes
    and limits, ``changes`` over them; ``None`` drops a key) and one cell of
    it: files and entries, nothing under ``benchmark/`` touched."""
    criteo = BENCH / "configs" / "criteo-1tb-share.json"
    body = {**json.loads(criteo.read_text()), **changes}
    body = {k: v for k, v in body.items() if v is not None}
    file = tmp_path / f"{name}.json"
    file.write_text(json.dumps(body))
    shutil.copy(criteo.with_suffix(".limits.json"), file.with_suffix(".limits.json"))
    m = json.loads(json.dumps(M))
    cell = f"{name}.{traffic}"
    m["configs"].append({"name": name, "source": body["source"], "file": str(file),
                         "reduced": ["rows"], "why": "test"})
    m["workloads"].append({"name": cell, "config": name, "traffic": traffic,
                           "chips": 1, "why": "test"})
    for e in m["end_to_end"] + m["per_layer"]:
        if "criteo-share.fused-armed" in e.get("workloads", []):
            e["workloads"].append(cell)
    manifest = tmp_path / "BENCHMARK.json"
    manifest.write_text(json.dumps(m))
    return manifest, cell, body


def sent_one_way(codes, feature, threshold, tree):
    """The smaller share of its rows that the root and each of its two
    children send one way, read from the codes and the tree's tables."""
    node = np.zeros(len(codes), np.int64)
    shares = []
    for d in range(2):
        right = codes[np.arange(len(codes)), feature[tree][d][node]] \
            > threshold[tree][d][node]
        for k in range(2 ** d):
            here = node == k
            shares.append(min(right[here].mean(), 1 - right[here].mean()))
        node = node * 2 + right
    return shares


def test_a_deployment_with_its_own_data_and_reference_takes_only_files(capsys, tmp_path):
    """README's second worked example: Criteo's sizes on ``ties.py`` with a
    copy of the reference placed outside the repository.  The run is
    ``correct`` under Criteo's limits, and its first trees split on the tie:
    unequal children, which uniform codes never gave the derived levels."""
    before = bench_bytes()
    copy = tmp_path / "reference_of_its_own.py"
    shutil.copy(BENCH / "harness" / "reference.py", copy)
    manifest, cell, body = deployment_in(tmp_path, "criteo-ties", data=TIED,
                                         reference=str(copy))
    line = rehearse(capsys, cell, seconds=6, manifest=manifest)
    assert line["correct"] is True and line["attempted"] > 0
    for v, lim in line["compared"].values():
        assert v <= lim
    first = json.loads((ROOT / ".bench_runs" / cell / "life0.json").read_text())["first"]
    feature, threshold, _leaf = (np.asarray(a) for a in first["forest"])
    codes, _ = bdata.draw({**body, "rows": 6000}, 3000000019)   # rehearse's
    for tree in range(3):
        assert min(sent_one_way(codes, feature, threshold, tree)) < 0.10, tree
    assert bench_bytes() == before


@pytest.mark.parametrize("key", deployment.NAMES)
def test_a_configuration_without_the_key_fails_naming_it(tmp_path, key):
    manifest, cell, _ = deployment_in(tmp_path, "criteo-less", **{key: None})
    with pytest.raises(deployment.ConfigError, match=f"names no '{key}'"):
        run.main(["--workload", cell, "--seed", "5", "--seconds", "2"],
                 rehearsal={"rows": 6000}, manifest=manifest)


def test_a_switch_the_program_lacks_fails_naming_its_fields(capsys, tmp_path):
    manifest, cell, _ = deployment_in(
        tmp_path, "criteo-switch", program={"block_rows": 1024, "no_such_switch": 1})
    with pytest.raises(run.RunFailure):
        run.main(["--workload", cell, "--seed", "5", "--seconds", "2"],
                 rehearsal={"rows": 6000}, manifest=manifest)
    said = capsys.readouterr().err
    assert "ConfigError" in said and "no_such_switch" in said
    assert "n_features" in said and "mxu_i8" in said     # GBDTConfig's fields
