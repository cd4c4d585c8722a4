"""Straggler-tolerant K-of-N partial allreduce (ISSUE 8,
doc/partial_allreduce.md).

Layers covered, bottom-up:

* the quorum policy math (fraction/count specs, elastic re-derivation,
  loud failures on typos) and the config resolve seam;
* the wire pieces: tagged block frames, the MAGIC_SKIP handshake frame
  pair;
* the tracker-side :class:`~rabit_tpu.quorum.table.QuorumTable`:
  decide-once records, the outstanding-correction ledger, late-delivery
  events, exclusion streaks, and the drop-with-evidence epoch boundary;
* executor e2e (in-thread elastic workers against a real tracker):
  quorum=1.0 == legacy bitwise, a straggler excluded with its
  corrections landing exactly, the catch-up skip bounding staleness,
  replay-after-recovery bitwise identity with a correction in flight,
  and the i8-codec composition with a per-element bound check (the
  ISSUE 5-style accuracy gate);
* the chaos ``straggler`` compute fault + the seeded tier-1 fuzz
  campaign mixing straggler + quorum + kill faults
  (heal-then-must-converge and correction-accounting asserts live
  inside ``run_elastic_schedule``);
* the CI gates: ``consensus_bench --quorum-ablation`` (live-rank
  rounds/sec must shed the injected straggler) and the trace_tool
  ``--flag-links`` loop (offline straggler report -> live tracker
  repair arming).
"""

import json
import socket
import threading
import time

import numpy as np
import pytest

from rabit_tpu import quorum
from rabit_tpu.chaos import run_elastic_schedule
from rabit_tpu.config import Config
from rabit_tpu.elastic.client import ElasticWorker
from rabit_tpu.elastic.rebalance import shard_slice
from rabit_tpu.quorum import QuorumTable, parse_spec, quorum_count
from rabit_tpu.tracker import protocol as P
from rabit_tpu.tracker.tracker import Tracker


# -- policy -------------------------------------------------------------------

def test_quorum_count_specs():
    assert quorum_count(8, "") == 8          # off = exact
    assert quorum_count(8, "1.0") == 8       # full fraction = exact
    assert quorum_count(8, "0.75") == 6
    assert quorum_count(3, "0.6") == 2
    assert quorum_count(3, "0.67") == 3      # ceil crosses the world
    assert quorum_count(8, "6") == 6         # integer literal = COUNT
    assert quorum_count(8, "1") == 1
    assert quorum_count(4, "100") == 4       # clamped to world
    # elastic re-derivation: same spec, different world
    assert quorum_count(6, "0.5") == 3
    assert quorum_count(2, "0.5") == 1


def test_quorum_spec_validation():
    for bad in ("1.5", "0", "-2", "0.0", "fast", "0x2"):
        with pytest.raises(ValueError):
            parse_spec(bad)
    with pytest.raises(ValueError):
        parse_spec("")
    with pytest.raises(ValueError):
        quorum_count(0, "1")


def test_quorum_resolve_config():
    knobs = quorum.resolve(Config(["rabit_quorum=0.75",
                                   "rabit_quorum_wait_sec=0.2",
                                   "rabit_quorum_flag_after=5"]))
    assert knobs == {"quorum": "0.75", "wait_sec": 0.2, "flag_after": 5}
    assert quorum.resolve(Config([]))["quorum"] == ""
    with pytest.raises(ValueError):
        quorum.resolve(Config(["rabit_quorum=nope"]))


# -- wire ---------------------------------------------------------------------

def test_block_frame_roundtrip():
    data = P.put_block_frame(7, 2, b"\x01\x02\x03")
    assert P.read_block_frame(data) == (7, 2, b"\x01\x02\x03")
    assert P.read_block_frame(P.put_block_frame(0, 0, b"")) == (0, 0, b"")
    with pytest.raises(ValueError):
        P.read_block_frame(b"\x00\x00\x00")  # too short for the tag


def test_skip_frame_roundtrip():
    a, b = socket.socketpair()
    try:
        a.sendall(P.put_skip_frame(3, 9, 12))
        assert P.get_u32(b) == P.MAGIC_SKIP
        assert P.read_skip_frame(b) == (3, 9, 12)
    finally:
        a.close()
        b.close()


# -- the tracker-side table ---------------------------------------------------

def test_quorum_table_decides_once():
    t = QuorumTable("2")
    rec, events, flags = t.report(0, 1, 3, have=[0], held=[])
    assert rec["decided"] is False and rec["k"] == 2
    assert events == [] and flags == []
    rec, events, _ = t.report(0, 1, 3, have=[0, 1], held=[])
    assert rec["decided"] is True
    assert rec["excluded"] == [2] and rec["corrections"] == []
    assert any(e["kind"] == "quorum_met" for e in events)
    # a later (fuller) report gets the SAME frozen record — the
    # determinism contract
    rec2, events2, _ = t.report(0, 1, 3, have=[0, 1, 2], held=[])
    assert rec2 is rec
    assert not any(e["kind"] == "quorum_met" for e in events2)
    assert t.outstanding() == [(1, 2, 3)]


def test_quorum_table_corrections_and_late_events():
    t = QuorumTable("2")
    t.report(0, 1, 3, have=[0, 1], held=[])           # excludes 2
    # first mention of the delivered late block -> contribution_late
    rec, events, _ = t.report(0, 2, 3, have=[0, 1], held=[[1, 2]])
    kinds = [e["kind"] for e in events]
    assert "contribution_late" in kinds
    assert "correction_folded" in kinds
    assert rec["corrections"] == [[1, 2]]
    assert (1, 2, 3) not in t.outstanding()
    # the same held mention again: no duplicate late event
    _, events2, _ = t.report(0, 2, 3, have=[0, 1, 2], held=[[1, 2]])
    assert not any(e["kind"] == "contribution_late" for e in events2)
    # held pairs never excluded are ignored, not folded
    rec3, _, _ = t.report(0, 3, 3, have=[0, 1, 2], held=[[1, 0]])
    assert rec3["corrections"] == []


def test_quorum_table_streak_flags_once():
    t = QuorumTable("2", flag_after=3)
    flags_seen = []
    for v in range(1, 6):
        _, _, flags = t.report(0, v, 3, have=[0, 1], held=[])
        flags_seen.append(flags)
    # rank 2 late in rounds 1..5: flagged exactly once, at the third
    assert flags_seen == [[], [], [2], [], []]
    # a round it participates in resets the streak
    t2 = QuorumTable("2", flag_after=2)
    t2.report(0, 1, 3, have=[0, 1], held=[])
    _, _, f = t2.report(0, 2, 3, have=[0, 2], held=[])
    assert f == []  # 2 participated; 1's streak only at 1


def test_quorum_table_epoch_change_drops_with_world():
    t = QuorumTable("2")
    t.report(0, 1, 3, have=[0, 1], held=[])
    t.report(0, 2, 3, have=[1, 2], held=[])
    dropped = t.epoch_changed(1)
    assert dropped == [(1, 2, 3), (2, 0, 3)]
    assert t.outstanding() == []
    # the old epoch's records are pruned: the redone round gets a fresh
    # decision under the new epoch
    rec, _, _ = t.report(1, 1, 2, have=[0, 1], held=[])
    assert rec["decided"] is True and rec["excluded"] == []


def test_tracker_quorum_handler_and_stale_epoch():
    tracker = Tracker(3, quiet=True, quorum="2").start()
    try:
        ep = tracker.elastic.epoch
        reply = P.tracker_rpc(tracker.host, tracker.port, P.CMD_QUORUM,
                              "0", message=json.dumps(
                                  {"epoch": ep, "v": 1, "have": [0, 1],
                                   "held": []}))
        assert reply["decided"] is True and reply["excluded"] == [2]
        assert any(e["kind"] == "quorum_met" for e in tracker.events)
        stale = P.tracker_rpc(tracker.host, tracker.port, P.CMD_QUORUM,
                              "0", message=json.dumps(
                                  {"epoch": ep + 7, "v": 1, "have": [0, 1],
                                   "held": []}))
        assert stale["decided"] is False and stale.get("stale_epoch")
    finally:
        tracker.stop()


def test_tracker_without_quorum_reports_disabled():
    tracker = Tracker(2, quiet=True).start()
    try:
        reply = P.tracker_rpc(tracker.host, tracker.port, P.CMD_QUORUM,
                              "0", message=json.dumps(
                                  {"epoch": 0, "v": 1, "have": [0],
                                   "held": []}))
        assert reply["decided"] is False and reply.get("disabled")
    finally:
        tracker.stop()


# -- executor e2e -------------------------------------------------------------

def _histogram_job(world, n_bins=8, iter_sleep=0.01, straggler=None,
                   delay=0.0, heal=10 ** 9, dtype=np.int64):
    n_rows = 8 * world
    data = (np.arange(n_rows, dtype=np.int64) * 5) % n_bins

    def contribution(version, w, r):
        time.sleep(iter_sleep)
        if straggler is not None and r == straggler and version <= heal:
            time.sleep(delay)
        shard = data[shard_slice(n_rows, w, r)]
        return np.bincount(shard, minlength=n_bins).astype(dtype) * version

    def per_contribution(version, w, r):
        shard = data[shard_slice(n_rows, w, r)]
        return np.bincount(shard, minlength=n_bins).astype(dtype) * version

    def expected(niter):
        return sum(np.bincount(data, minlength=n_bins).astype(dtype) * v
                   for v in range(1, niter + 1))

    return contribution, per_contribution, expected


def _run_workers(tracker, world, contribution, niter, fails=None, **kw):
    results, lock = {}, threading.Lock()

    def run_one(w):
        res = w.run()
        with lock:
            results[w.task_id] = res

    fails = fails or {}
    workers = [ElasticWorker((tracker.host, tracker.port), str(i),
                             contribution, niter, wave_timeout=10.0,
                             link_timeout=5.0, deadline_sec=40.0,
                             fail=fails.get(str(i)), **kw)
               for i in range(world)]
    threads = [threading.Thread(target=run_one, args=(w,), daemon=True)
               for w in workers]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=50.0)
        assert not th.is_alive(), "worker thread hung"
    return results


def _adjusted_expected(tracker, expected, per_contribution):
    """Closed form minus every contribution the exclusion records name
    as never-folded — the exact single-epoch accounting."""
    qm = [e for e in tracker.events if e["kind"] == "quorum_met"]
    folded = {(e["src_version"], e["rank"]) for e in tracker.events
              if e["kind"] == "correction_folded"}
    adjusted = expected.copy()
    for e in qm:
        for r in e["excluded"]:
            if (e["version"], r) not in folded:
                adjusted = adjusted - per_contribution(e["version"],
                                                       e["world"], r)
    return adjusted


def test_e2e_quorum_full_is_bitwise_legacy():
    """quorum=1.0 runs the quorum wire (tagged frames, per-round
    records) but never excludes: results must be bitwise identical to
    the legacy exact path."""
    world, niter = 3, 4
    contribution, _per, expected = _histogram_job(world)
    states = {}
    for spec in ("", "1.0"):
        tracker = Tracker(world, quiet=True, quorum=spec).start()
        try:
            results = _run_workers(tracker, world, contribution, niter,
                                   quorum=spec)
        finally:
            tracker.stop()
        for tid, res in results.items():
            assert res.completed, f"{spec!r}/{tid}: {res.error}"
        states[spec] = results["0"].state
        if spec:
            assert results["0"].quorum_rounds == niter
            assert not [e for e in tracker.events
                        if e["kind"] == "quorum_met"]
    assert np.array_equal(states[""], expected(niter))
    assert np.array_equal(states[""], states["1.0"])


def test_e2e_straggler_excluded_and_corrections_land():
    """The tentpole's happy path: a healed straggler is excluded while
    slow, the late blocks it computed land as corrections, rounds it
    skipped while catching up are accounted exactly by the records, and
    every rank holds identical bits."""
    world, niter = 3, 8
    contribution, per, expected = _histogram_job(
        world, straggler=2, delay=0.4, heal=3)
    tracker = Tracker(world, quiet=True, quorum="0.6",
                      quorum_flag_after=0).start()
    try:
        results = _run_workers(tracker, world, contribution, niter,
                               quorum="0.6", quorum_wait=0.12)
    finally:
        tracker.stop()
    for tid, res in results.items():
        assert res.completed, f"{tid}: {res.error}"
    states = [results[t].state for t in sorted(results)]
    for s in states[1:]:
        assert np.array_equal(states[0], s), "cross-rank divergence"
    qm = [e for e in tracker.events if e["kind"] == "quorum_met"]
    assert qm and all(e["excluded"] == [2] for e in qm)
    # the straggler's computed-but-late blocks DELIVERED and folded
    assert [e for e in tracker.events if e["kind"] == "contribution_late"]
    assert [e for e in tracker.events if e["kind"] == "correction_folded"]
    # the exclusion records account exactly for everything that folded
    adjusted = _adjusted_expected(tracker, expected(niter), per)
    assert np.array_equal(states[0], adjusted)
    # healed + caught up: the straggler participates again by the final
    # rounds — no exclusions at the end of the job
    assert max(e["version"] for e in qm) < niter
    # nothing dropped (no membership wave ran)
    assert not [e for e in tracker.events
                if e["kind"] == "correction_dropped"]


def test_e2e_persistent_straggler_skips_and_tracks_median():
    """A persistent 8x straggler: the catch-up skip bounds its lag, the
    live ranks' cadence tracks the median (not the tail), and the
    accounting is exact for what the records excluded."""
    world, niter = 3, 10
    contribution, per, expected = _histogram_job(
        world, iter_sleep=0.02, straggler=2, delay=0.16)
    tracker = Tracker(world, quiet=True, quorum="0.6",
                      quorum_flag_after=0).start()
    try:
        results = _run_workers(tracker, world, contribution, niter,
                               quorum="0.6", quorum_wait=0.1)
    finally:
        tracker.stop()
    for tid, res in results.items():
        assert res.completed, f"{tid}: {res.error}"
    states = [results[t].state for t in sorted(results)]
    for s in states[1:]:
        assert np.array_equal(states[0], s)
    # the straggler skipped contributing to rounds the group had moved
    # past — that is what bounds the staleness
    assert results["2"].skipped_contributions > 0
    adjusted = _adjusted_expected(tracker, expected(niter), per)
    assert np.array_equal(states[0], adjusted)
    # live-rank cadence: generous 4x bar (the straggler's 0.18s rounds
    # would blow it 9x; CI scheduler noise will not)
    ct = results["0"].commit_times
    cadence = (ct[niter - 1] - ct[1]) / (niter - 2)
    assert cadence < 4 * 0.02, f"live cadence {cadence:.3f}s tracks the tail"


def test_e2e_replay_after_recovery_with_correction_in_flight():
    """A rank dies while the straggler's correction is outstanding: the
    recovery wave drops the ledger with evidence (correction_dropped),
    survivors converge to identical bits, and the state sits inside the
    exact accounting sandwich."""
    world, niter = 3, 6
    contribution, per, expected = _histogram_job(
        world, straggler=1, delay=0.35, heal=2)
    tracker = Tracker(world, quiet=True, quorum="0.6", quorum_flag_after=0,
                      shrink_after_sec=1.5, promote_after_sec=0.1).start()
    try:
        results = _run_workers(tracker, world, contribution, niter,
                               fails={"2": ("die", 3)},
                               quorum="0.6", quorum_wait=0.12)
    finally:
        tracker.stop()
    survivors = [results[t] for t in ("0", "1")]
    for res in survivors:
        assert res.completed, f"{res.task_id}: {res.error}"
        assert res.final_version == niter
    assert np.array_equal(survivors[0].state, survivors[1].state), \
        "replay after recovery diverged bitwise"
    # the wave happened (task 2's death shrank the world)
    waves = [e for e in tracker.events if e["kind"] == "wave"]
    assert len(waves) >= 2
    # accounting sandwich: every potentially-missing contribution comes
    # from the quorum_met records; nothing folds twice
    qm = [e for e in tracker.events if e["kind"] == "quorum_met"]
    folded = {(e["src_version"], e["rank"]) for e in tracker.events
              if e["kind"] == "correction_folded"}
    floor = expected(niter).copy()
    for e in qm:
        for r in e["excluded"]:
            if (e["version"], r) not in folded:
                floor = floor - per(e["version"], e["world"], r)
    assert np.all(survivors[0].state <= expected(niter))
    assert np.all(survivors[0].state >= floor)


def test_e2e_quorum_i8_codec_accuracy_gate():
    """The composition gate (quorum + i8 — the median-tracking fast
    path): folds stay bitwise identical ACROSS ranks, and the final
    state matches the exact-f32 record-adjusted closed form within the
    documented i8 bound, summed per folded block (the test_compress.py
    per-histogram shape)."""
    world, niter = 3, 6
    contribution, per, expected = _histogram_job(
        world, straggler=2, delay=0.3, heal=2, dtype=np.float32)
    tracker = Tracker(world, quiet=True, quorum="0.6",
                      quorum_flag_after=0).start()
    try:
        results = _run_workers(tracker, world, contribution, niter,
                               quorum="0.6", quorum_wait=0.12, codec="i8")
    finally:
        tracker.stop()
    for tid, res in results.items():
        assert res.completed, f"{tid}: {res.error}"
    states = [results[t].state for t in sorted(results)]
    for s in states[1:]:
        assert np.array_equal(states[0], s), "i8+quorum cross-rank skew"
    # per-element bound: each folded block contributes at most
    # (0.5/127) * its block max of decode error (doc/compression.md)
    qm = [e for e in tracker.events if e["kind"] == "quorum_met"]
    folded = {(e["src_version"], e["rank"]) for e in tracker.events
              if e["kind"] == "correction_folded"}
    missing = {(e["version"], r) for e in qm for r in e["excluded"]}
    missing -= folded
    adjusted = expected(niter).astype(np.float64)
    bound = 0.0
    for v in range(1, niter + 1):
        for r in range(world):
            block = per(v, world, r)
            if (v, r) in missing:
                adjusted = adjusted - block
            else:
                bound += (0.5 / 127.0) * float(np.max(np.abs(block))) * 1.001
    err = np.max(np.abs(states[0].astype(np.float64) - adjusted))
    assert err <= bound, f"i8+quorum err {err} over summed bound {bound}"


def test_e2e_persistent_late_rank_feeds_repair():
    """quorum_flag_after consecutive exclusions arm the SAME avoid-set
    machinery as a slow link: the tracker flags the straggler's
    incoming ring link and the CMD_EPOCH poll asks for a rewave."""
    world, niter = 3, 8
    contribution, _per, _expected = _histogram_job(
        world, iter_sleep=0.02, straggler=2, delay=0.2)
    tracker = Tracker(world, quiet=True, quorum="0.6",
                      quorum_flag_after=3).start()
    try:
        results = _run_workers(tracker, world, contribution, niter,
                               quorum="0.6", quorum_wait=0.1)
        flagged = [e for e in tracker.events
                   if e["kind"] == "link_degraded"
                   and e.get("via") == "quorum"]
        assert flagged and flagged[0]["dst"] == 2
    finally:
        tracker.stop()
    # the armed repair resolved through an ordinary rewave: the job
    # still completes on every rank
    for tid, res in results.items():
        assert res.completed, f"{tid}: {res.error}"


# -- chaos fault + fuzz campaign ---------------------------------------------

def test_chaos_straggler_fault_clean_arm():
    r = run_elastic_schedule(901, world=3, straggler=(2, 0.3, 3),
                             quorum="0.6", niter=6, deadline_sec=40.0)
    assert r.outcome == "completed"
    assert r.quorum == "0.6" and r.straggler == (2, 0.3, 3)
    assert r.n_quorum_met >= 1


def test_chaos_straggler_without_quorum_still_converges():
    """The compute fault alone (legacy path): every round waits out the
    straggler, bits stay the exact closed form."""
    r = run_elastic_schedule(910, world=3, straggler=(1, 0.2, 2),
                             niter=4, deadline_sec=40.0)
    assert r.outcome == "completed" and r.n_quorum_met == 0


#: the tier-1 case of the campaign below: world 4, a straggler healed at
#: version 3, quorum 0.5 and the sampled faults
CAMPAIGN = dict(world=4, straggler=(2, 0.25, 3), quorum="0.5", niter=5,
                mix_faults=True)


def test_fuzz_straggler_quorum_kill_campaign():
    """One seed of the campaign mixing straggler + quorum + kill faults:
    heal-then-must-converge, cross-rank bitwise identity, and the
    correction accounting (exact single-epoch, sandwich across waves) are
    asserted inside run_elastic_schedule.  Seed 9303 draws the whole mix
    (rank 3 killed at version 2, while the straggler still lags; a spare
    that arrives at once and is promoted) and is done in 1.5 s; the sweep
    over seeds is the ``slow`` twin's.  Five seeds in a row, each under a
    wall-clock deadline, failed at the driver beside five other xdist
    workers and never alone (ROADMAP.md D11): tier-1 keeps the mechanism,
    not the sweep."""
    r = run_elastic_schedule(9303, deadline_sec=90.0, **CAMPAIGN)
    assert r.outcome == "completed", f"seed 9303: {r}"


@pytest.mark.slow
def test_fuzz_straggler_quorum_kill_campaign_slow():
    """The acceptance sweep: 20 seeds across worlds/specs/delays, and the
    five seeds tier-1 ran before it kept one."""
    for i, seed in enumerate(range(9400, 9420)):
        world = 3 + (i % 2)
        spec = ("0.5", "0.6", "2")[i % 3]
        r = run_elastic_schedule(seed, world=world,
                                 straggler=(world - 1, 0.2 + 0.1 * (i % 2),
                                            3),
                                 quorum=spec, niter=5, mix_faults=True,
                                 deadline_sec=60.0)
        assert r.outcome == "completed", f"seed {seed}: {r}"
    for seed in range(9300, 9305):
        r = run_elastic_schedule(seed, deadline_sec=60.0, **CAMPAIGN)
        assert r.outcome == "completed", f"seed {seed}: {r}"


# -- CI gates -----------------------------------------------------------------

def test_consensus_bench_quorum_ablation_gate():
    """The acceptance shape at tier-1 scale: quorum off tracks the 8x
    straggler's cadence, quorum on sheds it (generous CI bars; the
    RESULTS capture carries the tight 1.3x number)."""
    from tools.consensus_bench import quorum_ablation

    out = quorum_ablation(world=3, niter=15, iter_sleep=0.02,
                          straggler_factor=8.0)
    assert out["arms"]["straggler_on"]["n_quorum_met"] >= 1
    assert out["off_cadence_vs_base"] > 3.0, out
    assert out["on_cadence_vs_base"] < 2.5, out
    assert (out["arms"]["straggler_on"]["cadence_s"]
            < 0.5 * out["arms"]["straggler_off"]["cadence_s"]), out


def test_trace_tool_flag_links_arms_repair():
    """The PR 7 open loop closed: a straggler report's implied link,
    pushed through --flag-links, lands as a link_degraded event and
    arms the repair rewave — the byte-identical live-report path."""
    from tools.trace_tool import flag_links_from_report

    tracker = Tracker(3, quiet=True).start()
    try:
        # flags persist as TASK pairs — commit a wave so ranks resolve
        tracker.elastic.commit({"0": 0, "1": 1, "2": 2}, 3)
        report = {"per_rank": {"0": {"lateness_share": 0.05},
                               "1": {"lateness_share": 0.1},
                               "2": {"lateness_share": 0.8}}}
        telemetry = {"world_size": 3,
                     "events": [{"kind": "schedule_planned",
                                 "ring_order": [0, 1, 2]}]}
        links = flag_links_from_report(
            report, telemetry, f"{tracker.host}:{tracker.port}")
        assert links == [(1, 2)]
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            degraded = [e for e in tracker.events
                        if e["kind"] == "link_degraded"]
            if degraded:
                break
            time.sleep(0.02)
        assert degraded and degraded[0]["src"] == 1 \
            and degraded[0]["dst"] == 2
        info = P.tracker_rpc(tracker.host, tracker.port, P.CMD_EPOCH,
                             "0", message="0")
        assert info["rewave"] is True
    finally:
        tracker.stop()


def test_api_quorum_policy_seam():
    """api.init resolves the quorum keys: a policy event when enabled, a
    loud ValueError on a typo'd spec."""
    import rabit_tpu as rt
    from rabit_tpu import obs

    rt.init(["rabit_quorum=0.75"])
    try:
        evs = [e for e in obs.get_recorder().snapshot()
               if e.kind == "quorum_policy"]
        assert evs and evs[-1].fields["quorum"] == "0.75"
    finally:
        rt.finalize()
    with pytest.raises(ValueError):
        rt.init(["rabit_quorum=not-a-spec"])
    rt.finalize()
