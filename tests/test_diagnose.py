"""Diagnosis plane (ISSUE 18): HealthMonitor detection rules + hysteresis,
the tracker's _diag_tick wiring (scrape incidents section, incident
events, the repair feed), chaos ground-truth attribution (injected
slow_link -> degraded-link incident naming the link; injected compute
straggler -> compute-straggler incident naming the rank; clean run ->
zero incidents), and the per-round critical-path engine against
synthetic span timelines with known gates."""

from __future__ import annotations

import json
import os
import time

import pytest

from rabit_tpu.chaos import run_elastic_schedule
from rabit_tpu.config import Config
from rabit_tpu.obs import stream
from rabit_tpu.obs.critical import (critical_path_report, fold_critical_path,
                                    ring_prev)
from rabit_tpu.obs.diagnose import (DIAG_SCHEMA, INCIDENT_CLASSES,
                                    HealthMonitor)
from rabit_tpu.obs.events import Event
from rabit_tpu.obs.metrics import MetricsRegistry
from rabit_tpu.obs.top import scrape
from rabit_tpu.obs.trace import JobTrace
from rabit_tpu.tracker import protocol as P
from rabit_tpu.tracker.tracker import Tracker


# -- helpers ------------------------------------------------------------------

def rollup(n_folds: int, links=()) -> dict:
    """A rendered-rollup stand-in: cumulative (count, wait-sum) link rows."""
    return {"n_folds": n_folds,
            "links": [{"src": str(s), "dst": str(d), "count": c, "sum": w}
                      for (s, d, c, w) in links]}


def fast_monitor(**over) -> HealthMonitor:
    args = {"rabit_diag_min_wait_sec": "0.05"}
    args.update({k: str(v) for k, v in over.items()})
    return HealthMonitor(Config([f"{k}={v}" for k, v in args.items()]))


# -- HealthMonitor: wait-shape rules ------------------------------------------

def test_concentration_opens_degraded_link_at_second_window():
    hm = fast_monitor()
    opened, _ = hm.observe(0.0, rollup(1, [(0, 1, 10, 1.0)]), {})
    assert opened == []  # one window of evidence indicts nobody
    opened, _ = hm.observe(1.0, rollup(2, [(0, 1, 20, 2.0)]), {})
    assert len(opened) == 1
    inc = opened[0]
    assert inc.cls == "degraded-link"
    assert inc.subject == {"src": 0, "dst": 1}
    assert inc.evidence[-1]["rule"] == "link-wait-concentration"
    assert inc.evidence[-1]["share"] == pytest.approx(1.0)
    doc = hm.render()
    assert doc["schema"] == DIAG_SCHEMA and doc["n_opened"] == 1
    assert doc["open"][0]["class"] in INCIDENT_CLASSES


def test_even_two_link_split_never_opens():
    """The dominance gate: a 2-link world's natural ~50/50 clean split
    cannot cross the share threshold alone."""
    hm = fast_monitor()
    for i in range(1, 8):
        opened, _ = hm.observe(float(i), rollup(
            i, [(0, 1, 4 * i, 0.5 * i), (1, 0, 4 * i, 0.5 * i)]), {})
        assert opened == []
    assert hm.render()["n_opened"] == 0


def test_below_min_wait_is_noise():
    hm = fast_monitor()
    for i in range(1, 6):
        opened, _ = hm.observe(float(i), rollup(
            i, [(0, 1, 2 * i, 0.004 * i)]), {})
        assert opened == []


def test_hole_opens_compute_straggler_naming_the_rank():
    """Spread wait with a near-zero hole at one incoming link: the hole's
    DST entered late every round — the compute straggler."""
    hm = fast_monitor()
    links = lambda i: [(3, 0, 4 * i, 0.4 * i), (0, 1, 4 * i, 0.4 * i),
                       (1, 2, 4 * i, 0.001 * i), (2, 3, 4 * i, 0.4 * i)]
    opened, _ = hm.observe(0.0, rollup(1, links(1)), {})
    assert opened == []
    opened, _ = hm.observe(1.0, rollup(2, links(2)), {})
    assert len(opened) == 1
    inc = opened[0]
    assert inc.cls == "compute-straggler"
    assert inc.subject == {"rank": 2}
    ev = inc.evidence[-1]
    assert ev["rule"] == "link-wait-hole"
    assert ev["hole_link"] == [1, 2]


def test_self_report_attributes_rotating_wait():
    """The steady-state degraded-link shape: the delay bubble circulates
    so cumulative link waits equalize — a worker link_degraded
    self-report names the link, the sustained window wait carries the
    streak.  Quorum-sourced flags are straggler evidence, not link
    attribution, and must be ignored."""
    hm = fast_monitor()
    uniform = lambda i: [(0, 1, 4 * i, 0.3 * i), (1, 2, 4 * i, 0.3 * i),
                         (2, 0, 4 * i, 0.3 * i)]
    report = {"kind": "link_degraded", "rank": 2, "src": 1, "dst": 2,
              "wait": 0.35, "share": 0.77}
    quorum_flag = {"kind": "link_degraded", "rank": 0, "src": 2, "dst": 0,
                   "via": "quorum"}
    opened, _ = hm.observe(0.0, rollup(1, uniform(1)),
                           {"events_delta": [report, quorum_flag]})
    assert opened == []
    opened, _ = hm.observe(1.0, rollup(2, uniform(2)), {})
    assert len(opened) == 1
    inc = opened[0]
    assert inc.cls == "degraded-link"
    assert inc.subject == {"src": 1, "dst": 2}  # the report, not the flag
    ev = inc.evidence[-1]
    assert ev["rule"] == "link-wait-attributed"
    assert ev["reported_share"] == pytest.approx(0.77)


def test_attribution_clears_when_wait_symptom_heals():
    """After repair the window wait drops under the floor: the standing
    attribution is stale and the incident resolves after the quiet run."""
    hm = fast_monitor(rabit_diag_resolve_windows=2)
    uniform = lambda i: [(0, 1, 4 * i, 0.3 * i), (1, 2, 4 * i, 0.3 * i)]
    report = {"kind": "link_degraded", "src": 1, "dst": 2, "wait": 0.3,
              "share": 0.6}
    hm.observe(0.0, rollup(1, uniform(1)), {"events_delta": [report]})
    opened, _ = hm.observe(1.0, rollup(2, uniform(2)), {})
    assert opened and opened[0].cls == "degraded-link"
    # healed: folds keep arriving, waits stay flat (zero window wait)
    resolved = []
    for i in range(3, 7):
        _, res = hm.observe(float(i), rollup(i, uniform(2)), {})
        resolved += res
    assert len(resolved) == 1
    assert resolved[0].subject == {"src": 1, "dst": 2}
    assert resolved[0].resolved_ts is not None
    doc = hm.render()
    assert doc["n_resolved"] == 1 and doc["open"] == []
    assert doc["recent"][0]["id"] == resolved[0].to_doc()["id"]


def test_wait_streak_freezes_without_fresh_folds():
    """No new folds means no wait evidence either way: an open wait-shape
    incident must not flap on a heartbeat hiccup."""
    hm = fast_monitor(rabit_diag_resolve_windows=2)
    hm.observe(0.0, rollup(1, [(0, 1, 10, 1.0)]), {})
    opened, _ = hm.observe(1.0, rollup(2, [(0, 1, 20, 2.0)]), {})
    assert len(opened) == 1
    for i in range(10):  # frozen: same n_folds, far past resolve_windows
        _, resolved = hm.observe(2.0 + i, rollup(2, [(0, 1, 20, 2.0)]), {})
        assert resolved == []
    assert len(hm.open_incidents()) == 1


# -- HealthMonitor: control-plane rules ---------------------------------------

def test_preemption_storm_from_one_burst():
    """Three leases expiring in ONE window must still open (rolling sum
    over the recent windows, not per-window thresholds)."""
    hm = fast_monitor()
    burst = [{"kind": "lease_expired", "task_id": str(t)} for t in range(3)]
    opened, _ = hm.observe(0.0, rollup(0), {"events_delta": burst})
    assert opened == []
    opened, _ = hm.observe(1.0, rollup(0), {"events_delta": []})
    assert len(opened) == 1
    inc = opened[0]
    assert inc.cls == "preemption-storm"
    assert inc.subject == {"n_expired": 3}
    assert inc.evidence[-1]["tasks"] == []  # this window had none
    assert inc.evidence[-1]["n_expired"] == 3


def test_single_death_is_not_a_storm():
    hm = fast_monitor()
    for i in range(6):
        ev = [{"kind": "lease_expired", "task_id": "1"}] if i == 0 else []
        opened, _ = hm.observe(float(i), rollup(0), {"events_delta": ev})
        assert opened == []


def test_tracker_saturation_opens_then_resolves():
    hm = fast_monitor(rabit_diag_resolve_windows=2)
    hm.observe(0.0, rollup(0), {"messages_dropped": 5})
    opened, _ = hm.observe(1.0, rollup(0), {"messages_dropped": 5})
    assert opened and opened[0].cls == "tracker-saturation"
    assert opened[0].subject == {"dropped": 5}
    resolved = []
    for i in range(2, 7):  # drops stop growing -> rolling sum decays
        _, res = hm.observe(float(i), rollup(0), {"messages_dropped": 5})
        resolved += res
    assert len(resolved) == 1 and resolved[0].cls == "tracker-saturation"


def test_lost_relay_opens_and_relay_up_resolves():
    hm = fast_monitor(rabit_diag_resolve_windows=2)
    hm.observe(0.0, rollup(0), {"events_delta": [
        {"kind": "relay_lost", "relay": "r0"}]})
    opened, _ = hm.observe(1.0, rollup(0), {"events_delta": []})
    assert opened and opened[0].cls == "lost-relay"
    assert opened[0].subject == {"relay": "r0"}
    resolved = []
    for i in range(2, 6):
        ev = [{"kind": "relay_up", "relay": "r0"}] if i == 2 else []
        _, res = hm.observe(float(i), rollup(0), {"events_delta": ev})
        resolved += res
    assert len(resolved) == 1 and resolved[0].subject == {"relay": "r0"}


def test_disabled_monitor_observes_nothing():
    hm = HealthMonitor(Config(["rabit_diag_enable=0"]))
    opened, resolved = hm.observe(0.0, rollup(5, [(0, 1, 10, 9.0)]),
                                  {"events_delta": [
                                      {"kind": "lease_expired",
                                       "task_id": "1"}] * 5})
    assert opened == [] and resolved == []
    doc = hm.render()
    assert doc["enabled"] is False and doc["n_opened"] == 0


# -- tracker wiring: _diag_tick, scrape exposition, incident events ----------

def _ship_waits(addr, src, waits, reg):
    for w in waits:
        stream.stream_observe("link_wait_seconds", w, registry=reg,
                              src=0, dst=1)
    delta = src.take()
    snap = {"schema": 1, "rank": 1, "task_id": "1", "counters": {},
            "histograms": {}, "delta": delta}
    ack = P.tracker_rpc(addr[0], addr[1], P.CMD_METRICS, "1",
                        message=json.dumps(snap), timeout=5.0, retries=1)
    assert ack == P.ACK


def test_tracker_diag_tick_opens_and_scrape_serves_incident(monkeypatch):
    """Concentrated link-wait deltas shipped to a live tracker must open
    a degraded-link incident from the lease-monitor thread and surface
    it in the CMD_OBS scrape's top-level incidents digest, with the
    incident_opened event in the job event log."""
    monkeypatch.setenv("RABIT_TPU_RABIT_DIAG_WINDOW_SEC", "0.1")
    tracker = Tracker(world_size=2, quiet=True).start()
    try:
        reg = MetricsRegistry()
        src = stream.DeltaSource(reg)
        deadline = time.monotonic() + 15
        doc = None
        while time.monotonic() < deadline:
            _ship_waits((tracker.host, tracker.port), src, [0.2, 0.2], reg)
            doc = scrape(tracker.host, tracker.port, registry=False)
            if doc["incidents"]["n_open"]:
                break
            time.sleep(0.15)
        assert doc is not None and doc["incidents"]["n_open"] == 1
        inc = doc["incidents"]["open"][0]
        assert inc["class"] == "degraded-link"
        assert inc["subject"] == {"src": 0, "dst": 1}
        assert inc["job"] == ""  # job-stamped in the flattened digest
        # the per-job section carries the full monitor exposition
        jdoc = doc["jobs"][""]["incidents"]
        assert jdoc["schema"] == DIAG_SCHEMA and jdoc["n_opened"] == 1
        kinds = [e["kind"] for e in tracker.events]
        assert kinds.count("incident_opened") == 1
    finally:
        tracker.stop()


# -- chaos ground truth: the acceptance scenarios -----------------------------

def test_chaos_slow_link_one_incident_names_link_and_repairs():
    """Injected slow link (1, 2): exactly one degraded-link incident
    naming that link, and the repair rewave fires from the incident
    feed (the worker report alone no longer flags the link — the
    hysteresis-gated monitor does)."""
    r = run_elastic_schedule(11, world=3, schedule="ring",
                             slow_link=(1, 2, 0.15), repair=True, niter=12,
                             deadline_sec=60.0)
    assert r.outcome == "completed"
    inc = r.incidents
    assert inc["n_opened"] == 1
    every = inc["open"] + inc["recent"]
    assert len(every) == 1
    assert every[0]["class"] == "degraded-link"
    assert every[0]["subject"] == {"src": 1, "dst": 2}
    assert any(e["rule"] == "link-wait-attributed"
               for e in every[0]["evidence"])
    assert r.n_repaired >= 1  # the rewave fired from the incident feed


def test_chaos_straggler_one_incident_names_rank():
    """Injected compute straggler rank 2: the wait table spreads with a
    hole at (1, 2) and the monitor indicts rank 2 — not a link."""
    r = run_elastic_schedule(903, world=4, straggler=(2, 0.4), niter=10,
                             deadline_sec=60.0)
    assert r.outcome == "completed"
    inc = r.incidents
    assert inc["n_opened"] == 1
    every = inc["open"] + inc["recent"]
    assert every[0]["class"] == "compute-straggler"
    assert every[0]["subject"] == {"rank": 2}


def test_chaos_clean_run_opens_zero_incidents():
    """The false-positive gate: an undisturbed schedule must not open
    anything."""
    r = run_elastic_schedule(4242, world=3, niter=4, deadline_sec=40.0)
    assert r.outcome == "completed"
    assert r.incidents["n_opened"] == 0
    assert r.incidents["open"] == []


# -- critical-path engine: synthetic ground truth -----------------------------

def _round_events(events_by_rank, seqno, begins, ends, op="allreduce"):
    for rank, b in begins.items():
        events_by_rank.setdefault(rank, []).append(
            Event(b, "op_begin", {"op": op, "version": 0, "seqno": seqno}))
    for rank, e in ends.items():
        events_by_rank[rank].append(
            Event(e, "op_end", {"op": op, "version": 0, "seqno": seqno}))


def _job(events_by_rank, telemetry=None) -> JobTrace:
    return JobTrace(ranks={r: sorted(evs, key=lambda e: e.ts)
                           for r, evs in events_by_rank.items()},
                    telemetry=telemetry)


def test_critical_path_names_injected_link_gate():
    """Rounds where rank 2 drains long after everyone arrived: excess
    drain >> entry skew, the gate is rank 2's incoming planned-ring
    link (1, 2), and the streamed rollup join carries the independent
    witness."""
    evs: dict = {}
    t = 100.0
    for seq in range(4):  # clean baseline rounds
        _round_events(evs, seq, {r: t for r in range(3)},
                      {r: t + 0.01 for r in range(3)})
        t += 1.0
    for seq in range(4, 7):  # degraded-link rounds: dst drains +0.5s
        _round_events(evs, seq, {r: t for r in range(3)},
                      {0: t + 0.01, 1: t + 0.01, 2: t + 0.5})
        t += 1.0
    tele = {"stream": {"links": [
        {"src": 1, "dst": 2, "count": 12, "sum": 1.45}]}}
    rep = critical_path_report(_job(evs, tele))
    assert rep["rounds_analyzed"] == 7
    assert rep["rounds_by_gate"] == {"compute": 0, "link": 3, "balanced": 4}
    top = rep["top_gating_links"][0]
    assert (top["src"], top["dst"]) == (1, 2)
    assert top["rounds"] == 3
    assert top["cost_s"] == pytest.approx(3 * 0.49, abs=0.02)
    assert top["streamed_wait_s"] == pytest.approx(1.45)
    assert rep["top_gating_ranks"] == []


def test_critical_path_names_injected_compute_gate():
    """Rounds where rank 2 enters 0.4s late and everyone drains fast:
    entry skew >> excess drain, the gate is rank 2's compute."""
    evs: dict = {}
    t = 50.0
    for seq in range(2):  # clean rounds
        _round_events(evs, seq, {r: t for r in range(3)},
                      {r: t + 0.01 for r in range(3)})
        t += 1.0
    for seq in range(2, 6):  # straggler rounds
        _round_events(evs, seq, {0: t, 1: t, 2: t + 0.4},
                      {0: t + 0.41, 1: t + 0.41, 2: t + 0.41})
        t += 1.0
    rep = critical_path_report(_job(evs))
    assert rep["rounds_by_gate"] == {"compute": 4, "link": 0, "balanced": 2}
    top = rep["top_gating_ranks"][0]
    assert top["rank"] == 2 and top["rounds"] == 4
    assert top["cost_s"] == pytest.approx(4 * 0.4, abs=0.02)
    assert rep["top_gating_links"] == []
    worst = rep["worst_rounds"][0]
    assert worst["gate"] == "compute" and worst["rank"] == 2


def test_critical_path_excludes_recovery_affected_rounds():
    """A round overlapping a recovery wave is costed as recovery, not
    attributed to a rank/link (restart latency must not crown a
    restarted rank as the straggler)."""
    evs: dict = {}
    _round_events(evs, 0, {0: 10.0, 1: 10.0}, {0: 10.01, 1: 10.01})
    _round_events(evs, 1, {0: 20.0, 1: 20.0}, {0: 20.01, 1: 20.6})
    tele = {"events": [{"ts": 19.9, "kind": "lease_expired", "task_id": "1"}],
            "waves": [{"epoch": 1, "ts": 20.5}]}
    rep = critical_path_report(_job(evs, tele))
    assert rep["rounds_recovery_affected"] == 1
    assert rep["rounds_analyzed"] == 1
    assert rep["rounds_by_gate"]["link"] == 0
    assert rep["recovery_waves"] == [
        {"start_s": 19.9, "end_s": 20.5, "cost_s": 0.6}]
    assert rep["recovery_cost_s"] == pytest.approx(0.6)


def test_ring_prev_cyclic_over_participants():
    assert ring_prev(0, [0, 1, 2]) == 2
    assert ring_prev(2, [0, 1, 2]) == 1
    assert ring_prev(3, [0, 3, 5]) == 0
    assert ring_prev(0, [0, 3, 5]) == 5


def test_fold_critical_path_rewrites_telemetry(tmp_path):
    obs_dir = str(tmp_path)
    with open(os.path.join(obs_dir, "telemetry.json"), "w") as f:
        json.dump({"events": [], "world_size": 2}, f)
    rep = {"schema": 1, "rounds_analyzed": 3,
           "top_gating_links": [{"src": 0, "dst": 1}],
           "top_gating_ranks": []}
    path = fold_critical_path(obs_dir, rep)
    assert path is not None
    with open(path) as f:
        doc = json.load(f)
    assert doc["critical_path"]["rounds_analyzed"] == 3
    folded = [e for e in doc["events"]
              if e["kind"] == "critical_path_folded"]
    assert len(folded) == 1
    assert folded[0]["rounds"] == 3 and folded[0]["links"] == 1
    # no telemetry file -> no fold, no crash
    assert fold_critical_path(str(tmp_path / "absent"), rep) is None
