"""The reduction on traces recorded on the v5e: twelve rounds of
``higgs-quarter.fused-armed`` (PR 24's first traced run, seed 3000000102)
and twelve of ``higgs-full.dp4-armed`` on four chips (seed 514).  Each is read with ``jax.profiler.ProfileData`` in a child process, so that
this test process stays free of jax like the benchmark's parent."""

import json
import subprocess
import sys

import pytest

from conftest import BENCH

TRACE = BENCH / "tests" / "data" / "fused-armed.v5e.xplane.pb"
TRACE_DP4 = BENCH / "tests" / "data" / "dp4-armed.v5e.xplane.pb"
CODE = """
import json, sys
sys.path.insert(0, sys.argv[1])
from harness import xplane
raw = xplane.read(sys.argv[2])
print(json.dumps({"table": xplane.reduce(raw), "planes": sorted(raw["devices"]),
                  "host": sorted({e[0] for e in raw["host"]})}))
"""


def reduce_in_a_child(trace):
    r = subprocess.run([sys.executable, "-c", CODE, str(BENCH), str(trace)],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def reduced():
    return reduce_in_a_child(TRACE)


@pytest.fixture(scope="module")
def reduced_dp4():
    return reduce_in_a_child(TRACE_DP4)


def test_planes_and_spans(reduced):
    assert reduced["planes"] == ["/device:TPU:0"]
    assert reduced["host"] == ["checkpoint", "margin_d2h", "round"]


def test_busy_idle_and_rounds(reduced):
    t = reduced["table"]
    assert t["devices"] == 1 and t["rounds"] == 12
    assert 2.0 < t["window_s"] < 2.3
    assert 0.93 < t["busy_s"] / t["window_s"] < 0.97          # 5 % idle
    assert 169 < 1e3 * t["busy_s"] / t["rounds"] < 172        # 170.4 ms a round
    gaps = dict(t["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(t["window_s"] - t["busy_s"], rel=1e-9)
    assert max(gaps, key=gaps.get) == "margin_d2h"
    assert t["collective_s"] == 0.0                           # one chip


def test_kernels_by_their_short_names(reduced):
    ops = reduced["table"]["ops"]
    hist = {k: v for k, v in ops.items() if k.startswith("hist_level")}
    assert sorted(hist) == ["hist_level.5", "hist_level.6", "hist_level.7",
                            "hist_level.8", "hist_level.9", "hist_level0.1"]
    assert all(v[0] == 12 for v in hist.values())             # once a round
    a_round = 1e3 * sum(v[1] for v in hist.values()) / 12
    assert 150 < a_round < 155                                # of 170 ms
    assert sum(v[1] for v in ops.values()) == pytest.approx(
        reduced["table"]["busy_s"], rel=1e-6)                 # self times add up


def test_readers_on_the_recorded_table(reduced):
    import run

    ev = {"trace": reduced["table"],
          "config": {"rows": 2625000, "features": 28, "max_bin": 256, "max_depth": 6},
          "device": {"kind": "TPU v5 lite", "count": 1}}
    roof = run.load_reader("kernel.hist_roofline").read(ev)
    mfu = run.load_reader("step.round_mfu").read(ev)
    assert 0.5 < roof < 0.6 and 0.5 < mfu < 0.6 and mfu < roof * 1.1
    assert run.load_reader("device.idle_pct").read(ev) == pytest.approx(5.0, abs=0.5)
    assert run.load_reader("ici.psum_exposed_ms").read(ev) is None


def test_four_chips_are_averaged_and_the_psum_is_found(reduced_dp4):
    assert reduced_dp4["planes"] == [f"/device:TPU:{i}" for i in range(4)]
    t = reduced_dp4["table"]
    assert t["devices"] == 4 and t["rounds"] == 12
    assert 169 < 1e3 * t["busy_s"] / t["rounds"] < 172        # a chip, not four
    assert 0.55 < t["busy_s"] / t["window_s"] < 0.62          # 41 % idle
    assert max(dict(t["idle_gaps"]), key=dict(t["idle_gaps"]).get) == "checkpoint"
    psums = sorted(k for k in t["ops"] if k.startswith("all-reduce:psum"))
    assert len(psums) == 6 and all(t["ops"][k][0] == 12 for k in psums)  # one a level
    assert 0 < t["collective_exposed_s"] <= t["collective_s"] < 0.01 * t["busy_s"]


def test_collective_reader_on_the_recorded_dp4_table(reduced_dp4):
    import run

    ev = {"trace": reduced_dp4["table"],
          "config": {"rows": 10500000, "features": 28, "max_bin": 256, "max_depth": 6},
          "device": {"kind": "TPU v5 lite", "count": 4}}
    assert run.load_reader("ici.psum_exposed_ms").read(ev) == pytest.approx(0.098, abs=0.01)
    roof = run.load_reader("kernel.hist_roofline").read(ev)
    assert 0.5 < roof < 0.6 and run.load_reader("step.round_mfu").read(ev) < roof
