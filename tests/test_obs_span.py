"""obs.span — the one span primitive (doc/observability.md, "Spans on the
profiler's clock"): nesting, the round's version, one flight-recorder event
and one histogram observation a span, a TraceAnnotation on the profiler's
clock iff jax is already imported, and what it costs when nobody traces."""

from __future__ import annotations

import glob
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import rabit_tpu as rt
from rabit_tpu import obs
from rabit_tpu.obs import trace
from rabit_tpu.obs.events import Event
from rabit_tpu.tracker.launcher import LocalCluster

REPO = Path(__file__).resolve().parent.parent
WORKER = str(REPO / "tests" / "workers" / "recover_worker.py")


@pytest.fixture
def ring():
    obs.get_recorder().clear()
    yield obs.get_recorder()
    obs.get_recorder().clear()


def spans_of(ring, prefix=""):
    return [e.fields for e in ring.snapshot()
            if e.kind == "span" and e.fields["name"].startswith(prefix)]


# -- the primitive -------------------------------------------------------------

def test_nesting_gives_parent_and_version_rides(ring):
    with obs.span("t.outer", version=7, nbytes=10):
        with obs.span("t.inner", level=3):
            with obs.span("t.leaf"):
                pass
        with obs.span("t.other", version=9):
            pass
    got = {f["name"]: f for f in spans_of(ring, "t.")}
    assert [f["name"] for f in spans_of(ring, "t.")] == [
        "t.leaf", "t.inner", "t.other", "t.outer"]     # one event, at the end
    assert got["t.outer"]["parent"] is None and got["t.outer"]["version"] == 7
    assert got["t.inner"]["parent"] == "t.outer"
    assert got["t.leaf"]["parent"] == "t.inner"
    assert got["t.inner"]["version"] == got["t.leaf"]["version"] == 7
    assert got["t.other"]["version"] == 9 and got["t.other"]["parent"] == "t.outer"
    assert got["t.outer"]["nbytes"] == 10 and got["t.inner"]["level"] == 3


def test_version_defaults_to_the_collective_epoch(ring):
    obs.collective_epoch(41)
    try:
        with obs.span("t.epoch"):
            pass
    finally:
        obs.collective_epoch(0)
    assert spans_of(ring, "t.epoch")[0]["version"] == 41


def test_one_event_with_t0_and_seconds_and_one_histogram_observation(ring):
    hist = obs.get_registry().histogram("t.timed_seconds")
    before = hist.count
    t_before = time.time()
    with obs.span("t.timed") as sp:
        time.sleep(0.01)
        sp.set(encoded=5, version=3)
    (f,) = spans_of(ring, "t.timed")
    assert 0.009 < f["seconds"] < 1.0
    assert t_before <= f["t0"] <= time.time() - f["seconds"] + 1e-3
    assert f["encoded"] == 5 and f["version"] == 3
    assert hist.count == before + 1 and hist.vmax >= 0.009


def test_a_span_that_raises_still_closes(ring):
    with pytest.raises(ValueError):
        with obs.span("t.boom"):
            with obs.span("t.inside"):
                raise ValueError("x")
    assert [f["name"] for f in spans_of(ring, "t.")] == ["t.inside", "t.boom"]
    with obs.span("t.after"):
        pass
    assert spans_of(ring, "t.after")[0]["parent"] is None   # the stack unwound


def test_collective_keeps_its_events_and_gains_a_span(ring):
    rt.init()
    try:
        rt.allreduce(np.arange(6, dtype=np.float32), rt.SUM)
    finally:
        rt.finalize()
    kinds = [e.fields["name"] if e.kind == "span" else e.kind
             for e in ring.snapshot()
             if e.kind in ("op_begin", "span", "op_end")]
    assert kinds == ["rabit.allreduce.copy_in", "op_begin", "rabit.allreduce",
                     "op_end", "rabit.allreduce.copy_out"]
    begin, end = (next(e.fields for e in ring.snapshot() if e.kind == k)
                  for k in ("op_begin", "op_end"))
    assert set(begin) == {"op", "nbytes", "cache_key", "version", "seqno"}
    assert set(end) == set(begin) | {"seconds"}
    (f,) = [f for f in spans_of(ring) if f["name"] == "rabit.allreduce"]
    assert (f["version"], f["seqno"], f["nbytes"]) == (
        begin["version"], begin["seqno"], 24)
    assert obs.get_registry().ops["allreduce"].calls >= 1


# -- where the spans are: checkpoint, spill, load -------------------------------

SOLO = """
import sys
import numpy as np
import rabit_tpu as rt
from rabit_tpu import obs
assert "jax" not in sys.modules, "import rabit_tpu brought jax"
rt.init(rabit_checkpoint_dir=sys.argv[1])
rt.allreduce(np.arange(4, dtype=np.float32), rt.SUM)
rt.checkpoint({"trees": list(range(100))}, np.arange(5000, dtype=np.float32))
rt.finalize()
rt.init(rabit_checkpoint_dir=sys.argv[1])
version, g, l = rt.load_checkpoint(with_local=True)
rt.finalize()
assert version == 1 and g["trees"][-1] == 99 and l.shape == (5000,)
names = [e.fields["name"] for e in obs.get_recorder().snapshot()
         if e.kind == "span"]
print("JAX", "jax" in sys.modules)
print("SPANS", " ".join(names))
"""


def test_solo_checkpoint_spans_and_no_jax_import(tmp_path):
    r = subprocess.run([sys.executable, "-c", SOLO, str(tmp_path / "ck")],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = dict(line.split(" ", 1) for line in r.stdout.splitlines()
               if line.startswith(("JAX", "SPANS")))
    assert out["JAX"] == "False"
    names = out["SPANS"].split()
    for want in ("rabit.allreduce", "rabit.checkpoint",
                 "rabit.checkpoint.pickle", "rabit.checkpoint.commit",
                 "rabit.checkpoint.spill", "rabit.spill.encode",
                 "rabit.spill.write", "rabit.spill.dirsync",
                 "rabit.spill.prune", "rabit.load_checkpoint", "rabit.load.engine",
                 "rabit.load.disk", "rabit.load.disk.read",
                 "rabit.load.unpickle"):
        assert want in names, want
    assert names.count("rabit.spill.encode") == 2      # global and local file
    assert "rabit.checkpoint.publish" not in names     # no publisher, no span


def test_checkpoint_fields_and_parents(ring, tmp_path):
    rt.init(rabit_checkpoint_dir=str(tmp_path / "ck"))
    try:
        rt.checkpoint({"a": 1}, np.zeros(2000, np.float32))
        rt.checkpoint({"a": 2})
    finally:
        rt.finalize()
    first = [f for f in spans_of(ring, "rabit.") if f["version"] == 1]
    by = {}
    for f in first:
        by.setdefault(f["name"], []).append(f)
    top = by["rabit.checkpoint"][0]
    assert top["parent"] is None and top["nbytes_local"] > 8000
    assert top["nbytes_global"] > 0
    for name in ("pickle", "commit", "spill"):
        assert by[f"rabit.checkpoint.{name}"][0]["parent"] == "rabit.checkpoint"
    for name in ("encode", "write", "dirsync"):
        assert [f["parent"] for f in by[f"rabit.spill.{name}"]] == [
            "rabit.checkpoint.spill"] * 2
    assert [f["parent"] for f in by["rabit.spill.prune"]] == [
        "rabit.checkpoint.spill"]
    enc = by["rabit.spill.encode"]
    assert enc[0]["raw"] == top["nbytes_global"]
    assert enc[1]["raw"] == top["nbytes_local"]
    # both are under the probe's size and both come out smaller: the codec
    # named is the one applied, the probe is the whole blob's ratio
    assert all(0 < f["encoded"] < f["raw"] and f["codec"] == "zlib"
               and f["probe"] == round(f["encoded"] / f["raw"], 4) for f in enc)
    assert enc[1]["probe"] < 0.05
    assert by["rabit.spill.write"][1]["bytes"] > enc[1]["encoded"]
    second = [f for f in spans_of(ring, "rabit.spill.encode")
              if f["version"] == 2]
    assert len(second) == 1                            # no local model, one file
    inside = sum(f["seconds"] for f in first
                 if f["parent"] == "rabit.checkpoint")
    assert inside <= top["seconds"] + 1e-5


# -- on the profiler's clock ----------------------------------------------------

def test_spans_land_in_the_profilers_trace(tmp_path):
    """jax imported and a profiler session on: every span is a
    TraceAnnotation in the .xplane.pb, children inside their parents, the
    fields as stats."""
    import jax  # noqa: F401  (the suite's conftest imported it already)
    from jax.profiler import ProfileData

    from rabit_tpu.profile import xla_trace

    ck = str(tmp_path / "ck")
    with xla_trace(str(tmp_path / "tr")):
        rt.init(rabit_checkpoint_dir=ck)
        try:
            rt.allreduce(np.arange(8, dtype=np.float32), rt.SUM)
            rt.checkpoint({"forest": np.zeros(500)},
                          np.arange(20000, dtype=np.float32))
        finally:
            rt.finalize()
        rt.init(rabit_checkpoint_dir=ck)
        try:
            assert rt.load_checkpoint(with_local=True)[0] == 1
        finally:
            rt.finalize()
    (path,) = glob.glob(str(tmp_path / "tr" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("rabit."):
                    found.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))

    def inside(child, parent):
        (pa, pb, _), = found[parent]
        return all(pa <= a and b <= pb for a, b, _ in found[child])

    for child in ("rabit.checkpoint.pickle", "rabit.checkpoint.commit",
                  "rabit.checkpoint.spill", "rabit.spill.encode",
                  "rabit.spill.write", "rabit.spill.dirsync"):
        assert inside(child, "rabit.checkpoint"), child
    for child in ("rabit.load.engine", "rabit.load.disk",
                  "rabit.load.disk.read", "rabit.load.unpickle"):
        assert inside(child, "rabit.load_checkpoint"), child
    assert len(found["rabit.spill.encode"]) == 2
    enc = found["rabit.spill.encode"][1][2]
    assert enc["raw"] > 80000 and 0 < enc["encoded"] and enc["codec"] == "zlib"
    assert 0 < enc["probe"] < 0.75          # set inside the window, a stat all the same
    assert found["rabit.spill.write"][1][2]["bytes"] > enc["encoded"]
    stats = found["rabit.allreduce"][0][2]
    assert stats["nbytes"] == 32 and stats["seqno"] == 0
    assert found["rabit.checkpoint"][0][2]["version"] == 1
    assert found["rabit.load_checkpoint"][0][2]["version"] == 1


def test_one_site_opens_a_trace_annotation():
    sites = [p for p in (REPO / "rabit_tpu").rglob("*.py")
             if "TraceAnnotation(" in p.read_text()]
    assert [p.relative_to(REPO).as_posix() for p in sites] == [
        "rabit_tpu/obs/__init__.py"]


def test_off_cost_is_microseconds():
    """jax imported, no profiler session: a span is a context manager, one
    event and one histogram observation — some three flight-recorder events
    of a span's shape (3.3 on a quiet host, where a span is 6.4-7.5 us and
    an event 2.2), and held under five.  The two are timed back to back in
    the same short batches and compared batch by batch, the median of the
    ratios taken: a host loaded by five other workers slows both alike, so
    no microsecond is named.  What that buys (ISSUE 36): an in-memory
    commit holds three spans and a spilled one eleven; a hop holds six
    (``gbdt.cross``, ``rabit.allreduce`` and the two copies of each), so
    the seven hops and the commit of ``higgs-quarter.engine-hop``'s round
    are 45 spans, 0.3 ms of its 187.76 ms."""
    import gc
    import statistics

    import jax  # noqa: F401

    def spans(n):
        for _ in range(n):
            with obs.span("t.cost", nbytes=1):
                pass

    def events(n):
        for _ in range(n):
            obs.record_event("span", name="t.ref", t0=1.5, seconds=0.5,
                             parent=None, version=0, nbytes=1)

    def took(fn, n=500):
        t = time.perf_counter()
        fn(n)
        return (time.perf_counter() - t) / n

    gc.disable()
    try:
        spans(200), events(200)
        batches = [(took(spans), took(events)) for _ in range(40)]
    finally:
        gc.enable()
    ratio = statistics.median(a / b for a, b in batches)
    assert ratio < 5, (
        f"a span is {ratio:.2f} events; the quietest batch: "
        f"{min(a for a, _ in batches) * 1e6:.2f} us a span, "
        f"{min(b for _, b in batches) * 1e6:.2f} us an event")


# -- the operator's view: trace_tool export, the launcher ------------------------

def test_trace_tool_export_draws_span_slices(tmp_path, capsys):
    obs_dir = tmp_path / "obs"
    obs_dir.mkdir()
    events = [
        Event(10.30, "span", {"name": "rabit.checkpoint.pickle", "t0": 10.10,
                              "seconds": 0.2, "parent": "rabit.checkpoint",
                              "version": 4, "nbytes": 99}),
        Event(10.90, "span", {"name": "rabit.checkpoint", "t0": 10.10,
                              "seconds": 0.8, "parent": None, "version": 4}),
        Event(10.95, "checkpoint_commit", {"version": 4, "nbytes": 99}),
    ]
    head = Event(11.0, "flight_dump", {"reason": "exit", "rank": 0, "pid": 7,
                                       "dump_seq": 1, "n_events": 3,
                                       "dropped": 0, "task_id": "0"})
    (obs_dir / "flight-rank0-pid7-n1-exit.jsonl").write_text(
        "\n".join(e.to_json() for e in [head] + events) + "\n")
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import trace_tool
    finally:
        sys.path.pop(0)
    assert trace_tool.main(["export", str(obs_dir)]) == 0
    out = json.loads(capsys.readouterr().out)
    doc = json.loads(Path(out["trace"]).read_text())
    assert trace.validate_chrome_trace(doc) == []
    slices = [e for e in doc["traceEvents"] if e.get("cat") == "span"]
    assert [e["name"] for e in slices] == ["rabit.checkpoint",
                                           "rabit.checkpoint.pickle"]
    outer, inner = slices
    assert outer["ts"] == 0.0 and outer["dur"] == pytest.approx(8e5)
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["args"] == {"parent": "rabit.checkpoint", "version": 4,
                             "nbytes": 99}
    assert "parent" not in outer["args"] and outer["pid"] == 0


def test_a_killed_worker_leaves_one_worker_respawn_event():
    cluster = LocalCluster(2, max_restarts=3, quiet=True)
    t0 = time.time()
    assert cluster.run([sys.executable, WORKER, "rabit_engine=mock",
                        "niter=2", "mock=1,1,1,0"], timeout=120) == 0
    respawns = [e for e in cluster.events if e.get("kind") == "worker_respawn"]
    assert len(respawns) == 1
    (ev,) = respawns
    assert ev["task"] == "1" and ev["attempt"] == 1
    assert t0 <= ev["died_at"] <= ev["spawned_at"] <= time.time()
    assert cluster.restarts["1"] == 1
