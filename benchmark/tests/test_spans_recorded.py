"""``harness/spans.py`` on a trace recorded on the v5e by PR 25's own chip
run: two traced rounds of ``higgs-quarter.kill-resume``'s second life (seed
2147484001, from the committed files alone), with the program's spans in it
and the kernels under their new names.  Read in a child process, so that
this test process stays free of jax like the benchmark's parent."""

import json
import subprocess
import sys

import pytest

from conftest import BENCH
from harness import spans

TRACE = BENCH / "tests" / "data" / "kill-resume-spans.v5e.xplane.pb"
CODE = """
import json, sys
sys.path.insert(0, sys.argv[1])
from harness import spans, xplane
old = xplane.reduce(xplane.read(sys.argv[2]))
raw = spans.read(sys.argv[2])
print(json.dumps({"table": spans.reduce(raw), "old": old,
                  "threads": {k: len(v) for k, v in raw["threads"].items()}}))
"""
CHILDREN = ("rabit.checkpoint.pickle", "rabit.checkpoint.commit",
            "rabit.checkpoint.spill")
LEAVES = ("rabit.checkpoint.pickle", "rabit.checkpoint.commit",
          "rabit.spill.encode", "rabit.spill.write", "rabit.spill.dirsync")


@pytest.fixture(scope="module")
def read():
    assert TRACE.stat().st_size < 1_000_000
    r = subprocess.run([sys.executable, "-c", CODE, str(BENCH), str(TRACE)],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_the_window_is_the_accepted_reductions(read):
    t, old = read["table"], read["old"]
    assert t["devices"] == 1 and t["rounds"] == old["rounds"] == 2
    assert t["window_s"] == pytest.approx(old["window_s"], rel=1e-12)
    assert t["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"], rel=1e-9)
    # device.idle_pct x window = the sum of idle by span, to rounding
    assert sum(t["idle_by_span"].values()) == pytest.approx(t["idle_s"], rel=1e-9)
    assert 0.66 < t["idle_s"] / t["window_s"] < 0.68                 # 67 % idle
    # the worker's spans read as the accepted reduction reads them
    for name, (count, seconds) in old["spans"].items():
        assert t["spans"][name]["count"] == count
        assert t["spans"][name]["total_s"] == pytest.approx(seconds)
    assert len(read["threads"]) == 1                                 # one thread


def test_the_kernels_new_names_still_match_the_accepted_patterns(read):
    import re

    ops = read["old"]["ops"]
    hist = [n for n in ops if re.search(r"^hist_level", n)]
    assert sorted(hist) == ["hist_level0.1"] + [f"hist_level_d{d}.1"
                                                for d in range(1, 6)]
    assert "route_level_d6.1" in ops and not re.search(r"^hist_level",
                                                       "route_level_d6.1")
    took = sum(ops[n][1] for n in hist) / 2
    assert 0.152 < took < 0.153                # 152.4 ms a round, as in PR 24


def test_the_spans_of_a_spilled_commit(read):
    s = read["table"]["spans"]
    assert s["rabit.checkpoint"]["count"] == 2
    for name in CHILDREN + ("rabit.spill.prune",):
        assert s[name]["count"] == 2, name
    for name in ("rabit.spill.encode", "rabit.spill.write",
                 "rabit.spill.dirsync"):
        assert s[name]["count"] == 4, name                 # global and local file
    # the forest's and the margin's pickles, the wrapper included: 11.4 MB
    assert s["rabit.checkpoint.pickle"]["nbytes"] == 22793014
    assert s["rabit.spill.encode"]["raw"] == 22793014
    # zlib takes 14 % off both files together (the forest's is mostly zeros)
    assert 0.85 < s["rabit.spill.encode"]["encoded"] / 22793014 < 0.87
    assert s["rabit.spill.write"]["bytes"] == s["rabit.spill.encode"]["encoded"] + 4 * 16
    # the deflate is the commit: 319 of 339 ms a round
    assert 0.318 < s["rabit.spill.encode"]["total_s"] / 2 < 0.321
    assert 0.010 < s["rabit.spill.write"]["total_s"] / 2 < 0.013
    assert s["rabit.spill.encode"]["total_s"] > 0.94 * s["checkpoint"]["total_s"]


def test_self_times_and_the_sum_of_the_leaves(read):
    s = read["table"]["spans"]
    whole = s["checkpoint"]["total_s"]
    assert s["checkpoint"]["self_s"] < 2e-4 and s["rabit.checkpoint"]["self_s"] < 5e-4
    assert s["rabit.checkpoint.spill"]["self_s"] < 2e-3        # the prune has its own
    inside = sum(s[n]["total_s"] for n in CHILDREN)
    assert inside == pytest.approx(
        s["rabit.checkpoint"]["total_s"] - s["rabit.checkpoint"]["self_s"])
    # ISSUE 25: pickle + commit + encode + write within 5 % of the worker's span
    leaves = sum(s[n]["total_s"] for n in LEAVES)
    assert 0.95 * whole < leaves < whole
    assert spans.per_round_ms(read["table"], "rabit.spill.write",
                              "rabit.spill.dirsync") == pytest.approx(
        1e3 * (s["rabit.spill.write"]["total_s"]
               + s["rabit.spill.dirsync"]["total_s"]) / 2)


def test_idle_goes_to_the_deepest_span(read):
    t = read["table"]
    idle, s = t["idle_by_span"], t["spans"]
    # the device runs nothing while the host deflates: all of it is idle
    for name in ("rabit.spill.encode", "rabit.spill.write", "margin_d2h",
                 "rabit.checkpoint.pickle"):
        assert idle[name] == pytest.approx(s[name]["total_s"], rel=1e-6), name
    assert max(idle, key=idle.get) == "rabit.spill.encode"
    # what the accepted reduction hands whole to "checkpoint" is now named
    assert dict(read["old"]["idle_gaps"])["checkpoint"] > 0.99 * t["idle_s"]
    assert idle["checkpoint"] < 1e-4
    lost = sum(idle.get(n, 0.0) for n in
               (spans.NO_SPAN, "round", "checkpoint", "rabit.checkpoint",
                "rabit.checkpoint.spill"))
    assert 100 * lost / t["window_s"] < 1.0          # device.idle_unattributed_pct
    assert spans.program_spans(t)
