"""``harness/hops.py`` on a trace recorded on the v5e by PR 36's own chip
run: two traced rounds of ``higgs-quarter.engine-hop`` (seed 2147536003,
``trace_rounds`` 2), with the hop's six spans in it beside the
``pure_callback`` operations; the two ``TpuHostTransferManagerSendThread``
lines of the host's plane (442 KB of the runtime's own events, which no
reader of the benchmark looks at: the three reductions read the same tables
with and without them) are cut out to keep the file under 1 MB.  Read in a
child process, so that this test process stays free of jax like the
benchmark's parent."""

import json
import subprocess
import sys

import pytest

import run
from conftest import BENCH
from harness import hops

TRACE = BENCH / "tests" / "data" / "engine-hop-hops.v5e.xplane.pb"
CODE = """
import json, sys
sys.path.insert(0, sys.argv[1])
from harness import hops, xplane
raw = hops.read(sys.argv[2])
print(json.dumps({"hops": hops.reduce(raw), "old": xplane.reduce(raw)}))
"""
#: the tags of a round's seven hops, and the float32 bytes of each: a
#: histogram [nodes built, 28, 256, 2] a level (level 5 sends the built half
#: of its 32 nodes), then the leaves' masses [64, 2]
LEVELS = [1, 2, 4, 8, 16, 32, -1]
NBYTES = [4 * n * 28 * 256 * 2 for n in (1, 2, 4, 8, 16, 16)] + [4 * 64 * 2]


@pytest.fixture(scope="module")
def read():
    assert TRACE.stat().st_size < 1_000_000
    r = subprocess.run([sys.executable, "-c", CODE, str(BENCH), str(TRACE)],
                       capture_output=True, text=True, timeout=120,
                       env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_fourteen_hops_of_seven_levels(read):
    t = read["hops"]
    assert (t["devices"], t["rounds"], t["dropped"]) == (1, 2, 0)
    assert t["rounds"] == read["old"]["rounds"]
    assert t["window_s"] == pytest.approx(read["old"]["window_s"], rel=1e-12)
    rows = t["hops"]
    assert [(r["round"], r["level"]) for r in rows] == [
        (k, level) for k in (0, 1) for level in LEVELS]
    assert [r["nbytes"] for r in rows] == NBYTES * 2
    assert len({r["version"] for r in rows}) == 2          # one a round
    assert list(hops.by_level(rows)) == LEVELS
    assert all(len(v) == 2 for v in hops.by_level(rows).values())


def test_a_hop_is_sends_a_host_function_and_a_receive(read):
    for r in read["hops"]["hops"]:
        assert r["device_ops"] == 6       # two sends, a receive, three markers
        assert 0 < r["t0"] < r["t1"] < r["t2"] <= r["t3"]
        assert set(r["spans"]) == set(hops.COPIES) | {hops.ENGINE}
        assert r["beside_s"] == 0.0          # nothing runs beside a transfer yet


def test_the_phases_add_up(read):
    rows = read["hops"]["hops"]
    for r in rows:
        assert r["to_host_s"] + r["callback_s"] + r["to_device_s"] == (
            pytest.approx(r["hop_s"], rel=1e-9))
        assert r["copy_s"] + r["engine_s"] + r["other_s"] == pytest.approx(
            r["callback_s"], rel=1e-9)
        assert sum(r[p] for p in hops.PHASES) == pytest.approx(r["hop_s"])
        assert min(r[p] for p in hops.PHASES) >= 0
    # ISSUE 36: the three means are the mean T3 - T0 (within 5 % asked, exact)
    paired = hops.rows(read["hops"], paired=True)
    assert len(paired) == 14
    whole = sum(hops.mean_ms(paired, k)
                for k in ("to_host_s", "callback_s", "to_device_s"))
    assert whole == pytest.approx(hops.mean_ms(paired, "hop_s"), rel=1e-9)


def test_the_rows_device_operations_are_engine_hop_device_ms(read):
    t, old = read["hops"], read["old"]
    mine = sum(s for name, (_, s) in old["ops"].items()
               if name.startswith("pure_callback"))
    assert sum(r["device_s"] for r in t["hops"]) == pytest.approx(mine, rel=1e-9)
    assert t["device_s"] == pytest.approx(mine, rel=1e-9)
    assert t["device_ops"] == sum(r["device_ops"] for r in t["hops"])
    accepted = run.load_reader("engine.hop_device_ms").read({"trace": old})
    assert accepted * t["rounds"] == pytest.approx(1e3 * t["device_s"], rel=1e-9)


def test_the_two_clocks_are_laid_together_by_the_quickest_answer(read):
    """In this session the leaves' receive of round 0 ends 0.085 ms before
    its ``gbdt.cross`` closes: the device's clock is moved by that, and that
    hop's answer reads 0."""
    t = read["hops"]
    assert 0.00008 < t["clock_shift_s"] < 0.00009
    quickest = min(t["hops"], key=lambda r: r["to_device_s"])
    assert (quickest["round"], quickest["level"]) == (0, -1)
    assert quickest["to_device_s"] == pytest.approx(0.0, abs=1e-12)
    # the way down is most of a hop, the way up grows with the bytes
    rows = hops.rows(t, paired=True)
    assert 2.5 < hops.mean_ms(rows[7:], "to_host_s") < 3.5
    up = {k: hops.mean_ms(v, "to_device_s") for k, v in hops.by_level(rows).items()}
    assert up[-1] < up[1] < up[2] < up[4] < up[8] < up[16] and up[16] > 1.2


def test_a_slow_hop_is_a_round_a_level_and_a_phase(read):
    """The first traced round's level-4 hop took 127.8 ms where its twin of
    the next round took 6.8: 115 ms of it inside ``gbdt.cross.in``, the
    operand made a numpy array — a stall of the host, named."""
    t = read["hops"]
    assert 120 < hops.jitter_ms(t) < 122
    slow = max(t["hops"], key=lambda r: r["hop_s"])
    assert (slow["round"], slow["level"], slow["nbytes"]) == (0, 16, 917504)
    assert max(hops.PHASES, key=lambda p: slow[p]) == "copy_s"
    assert max(slow["spans"], key=slow["spans"].get) == "gbdt.cross.in"
    assert 0.114 < slow["spans"]["gbdt.cross.in"] < 0.116
    others = [r for r in t["hops"] if r is not slow]
    assert max(r["hop_s"] for r in others) < 0.007
    assert max(r["copy_s"] for r in others) < 0.0015
