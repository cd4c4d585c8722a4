"""Multi-tenant service bench — N concurrent jobs, one control plane.

Evidence for the doc/service.md claims: a single
:class:`~rabit_tpu.service.CollectiveService` (plus a shared relay
tier) serves N CONCURRENT jobs, and one job's chaos cannot stall its
neighbors.  Three arms, all in-process (thread workers, real sockets —
the recovery_bench/chaos harness shape):

* **clean** — N jobs admitted concurrently (per-job workers dialing
  through the shared relays), measuring jobs/sec, per-job wall-clock,
  and the p50/p99 BOOTSTRAP latency under admission churn (per worker:
  check-in to first contribution call);
* **chaos** — the same N jobs with one VICTIM job injected with a
  straggler storm (one rank's every contribution delayed by
  ``--straggle`` seconds — the compute-side chaos fault) or worker
  kills (a rank dies silently mid-run and a replacement re-checks-in;
  ``--chaos kill``).  Every NEIGHBOR job must complete bitwise-identical
  to the closed form, and — the isolation bar — its wall-clock must
  stay within ``--bar`` (default 1.2x) of its own clean-arm run;
* **pooled** — ``--pool P`` warm pooled workers serving ``--pool-jobs``
  successive pool-filled fits (doc/service.md "Pooled workers"),
  measuring fits/sec on a warm pool and the leases-per-worker reuse;
* **observed** — ``--observed`` re-runs the clean scenario with the live
  telemetry plane attached (doc/observability.md): a ``--scrape-hz``
  CMD_OBS scraper polling the service plus a follow-mode trace exporter
  tailing the periodic flight spills, asserting job wall-clocks and boot
  p99 stay within ``--obs-bar`` (default 1.05x) of the unobserved clean
  arm — observation must be provably cheap — and that the diagnosis
  plane (HealthMonitor, doc/observability.md) opens ZERO incidents on
  the clean fleet: the false-positive gate.

Every record is one JSON line with ``"bench": "service"``.  ``--smoke`` shrinks every arm to CI size and relaxes the
wall-clock isolation assert to evidence-only (CPU-oversubscribed CI
machines cannot hold a 1.2x timing bar honestly); completion + bitwise
identity are asserted in every mode.  The legacy-wire guarantee is
asserted at startup: an empty job key produces byte-for-byte the
single-job hello.
"""

from __future__ import annotations

import argparse
import io
import json
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from rabit_tpu import obs  # noqa: E402
from rabit_tpu.config import Config  # noqa: E402
from rabit_tpu.elastic.client import ElasticWorker  # noqa: E402
from rabit_tpu.obs import trace as obs_trace  # noqa: E402
from rabit_tpu.obs.top import scrape as obs_scrape  # noqa: E402
from rabit_tpu.relay import Relay  # noqa: E402
from rabit_tpu.service import CollectiveService, PooledWorker  # noqa: E402
from rabit_tpu.tracker import protocol as P  # noqa: E402


def assert_legacy_wire_identical() -> None:
    """The tentpole wire contract (doc/service.md): an empty job key is
    byte-identical to the legacy hello — asserted against real encoded
    bytes, not by construction."""
    class _Sink:
        def __init__(self):
            self.buf = io.BytesIO()

        def sendall(self, data):
            self.buf.write(data)

    legacy, empty, keyed = _Sink(), _Sink(), _Sink()
    P.send_hello(legacy, P.CMD_START, "7", prev_rank=2, listen_port=9999)
    P.send_hello(empty, P.CMD_START, "7", prev_rank=2, listen_port=9999,
                 job="")
    P.send_hello(keyed, P.CMD_START, "7", prev_rank=2, listen_port=9999,
                 job="jx")
    assert empty.buf.getvalue() == legacy.buf.getvalue(), \
        "empty job key changed the wire bytes"
    assert keyed.buf.getvalue() != legacy.buf.getvalue()


def expected_state(world: int, niter: int, width: int = 8) -> np.ndarray:
    """Closed form of the deterministic workload: contribution(v, w, r)
    = v*(r+1)*ones, folded over all ranks and summed over versions."""
    ranks = world * (world + 1) // 2
    vers = niter * (niter + 1) // 2
    return np.full(width, ranks * vers, np.int64)


class JobRun:
    """One job's worker fleet + measurements."""

    def __init__(self, key: str, world: int, niter: int, sleep: float,
                 addr: "tuple[str, int]", deadline: float,
                 straggler: "tuple[int, float] | None" = None,
                 kill: "tuple[int, int] | None" = None):
        self.key = key
        self.world = world
        self.niter = niter
        self.results: dict[str, "object"] = {}
        self.boot_lat: list[float] = []
        self.wall = -1.0
        self._lock = threading.Lock()
        self._addr = addr
        self._deadline = deadline
        self._sleep = sleep
        self._straggler = straggler  # (rank, extra_sleep_s)
        self._kill = kill            # (rank, at_version)

    def _contribution(self, rank_hint: "list[float]"):
        sleep, straggler = self._sleep, self._straggler

        def contribution(v: int, world: int, rank: int) -> np.ndarray:
            if rank_hint[0] < 0:
                rank_hint[0] = time.monotonic()  # first work = booted
            time.sleep(sleep)
            if straggler is not None and rank == straggler[0]:
                time.sleep(straggler[1])
            return np.full(8, v * (rank + 1), np.int64)

        return contribution

    def _run_worker(self, i: int, fail: "tuple | None" = None) -> None:
        t0 = time.monotonic()
        first = [-1.0]
        w = ElasticWorker(self._addr, str(i), self._contribution(first),
                          self.niter, job=self.key,
                          deadline_sec=self._deadline,
                          rpc_timeout=2.0, wave_timeout=20.0, fail=fail)
        res = w.run()
        with self._lock:
            key = f"{i}" + ("+respawn" if fail is None and
                            f"{i}" in self.results else "")
            self.results[key] = res
            if first[0] > 0:
                self.boot_lat.append(first[0] - t0)

    def run(self) -> "JobRun":
        t0 = time.monotonic()
        threads = []
        for i in range(self.world):
            fail = None
            if self._kill is not None and i == self._kill[0]:
                fail = ("die", self._kill[1])
            threads.append(threading.Thread(
                target=self._run_worker, args=(i,), kwargs={"fail": fail},
                daemon=True))
        for t in threads:
            t.start()
        if self._kill is not None:
            # the replacement life: re-checks-in after the silent death
            # and rides the recovery wave (the launcher-restart shape)
            rank, at = self._kill

            def respawn():
                time.sleep(0.3 + 0.2 * at)
                self._run_worker(rank)

            t = threading.Thread(target=respawn, daemon=True)
            t.start()
            threads.append(t)
        for t in threads:
            t.join(timeout=self._deadline + 10)
        self.wall = time.monotonic() - t0
        return self

    def bitwise_ok(self) -> bool:
        exp = expected_state(self.world, self.niter)
        done = [r for r in self.results.values()
                if getattr(r, "completed", False)]
        if not done:
            return False
        return all(r.state is not None and np.array_equal(r.state, exp)
                   for r in done)

    def completed(self) -> bool:
        byrank = {}
        for r in self.results.values():
            if getattr(r, "completed", False):
                byrank[r.task_id] = r
        return len(byrank) >= self.world - (1 if self._kill else 0)


def pctl(vals: list[float], q: float) -> float:
    if not vals:
        return -1.0
    return float(np.percentile(np.asarray(vals, np.float64), q))


def run_fleet(jobs: list[JobRun], stagger: float) -> float:
    t0 = time.monotonic()
    threads = []
    for j in jobs:
        threads.append(threading.Thread(target=j.run, daemon=True))
        threads[-1].start()
        time.sleep(stagger)  # admission churn, not a synchronized burst
    for t in threads:
        t.join()
    return time.monotonic() - t0


def bench_service(n_jobs: int, world: int, niter: int, sleep: float,
                  relays: int, chaos: str, straggle: float, bar: float,
                  pool: int, pool_jobs: int, deadline: float,
                  assert_isolation: bool, stagger: float = 0.05,
                  observed: bool = False, obs_bar: float = 1.05,
                  scrape_hz: float = 1.0,
                  obs_dir: str = "") -> list[dict]:
    assert_legacy_wire_identical()
    records: list[dict] = []
    if observed and not obs_dir:
        obs_dir = tempfile.mkdtemp(prefix="rabit-obs-bench-")
    svc = CollectiveService(quiet=True, obs_dir=obs_dir or None).start()
    tier = [Relay((svc.host, svc.port), relay_id=f"r{i}",
                  flush_sec=0.05).start() for i in range(relays)]

    def addr_for(i: int) -> tuple[str, int]:
        if not tier:
            return (svc.host, svc.port)
        r = tier[i % len(tier)]
        return (r.host, r.port)

    base = dict(bench="service", jobs=n_jobs, world=world, niter=niter,
                relays=relays, sleep_s=sleep)

    # -- clean arm ---------------------------------------------------------
    for key in [f"clean{i}" for i in range(n_jobs)]:
        svc.admit(key, world)
    clean = [JobRun(f"clean{i}", world, niter, sleep, addr_for(i), deadline)
             for i in range(n_jobs)]
    wall = run_fleet(clean, stagger)
    boots = [b for j in clean for b in j.boot_lat]
    ok = all(j.completed() and j.bitwise_ok() for j in clean)
    rec = dict(base, mode="clean", wall_s=round(wall, 3),
               jobs_per_sec=round(n_jobs / wall, 3),
               boot_p50_ms=round(pctl(boots, 50) * 1e3, 3),
               boot_p99_ms=round(pctl(boots, 99) * 1e3, 3),
               job_walls_s=[round(j.wall, 3) for j in clean],
               bitwise_ok=ok, completed=ok)
    records.append(rec)
    assert ok, "clean arm: a job failed to complete bitwise-identically"

    # -- chaos arm: one victim, N-1 neighbors ------------------------------
    if chaos != "none":
        kill = (1, max(2, niter // 2)) if chaos == "kill" else None
        strag = (1, straggle) if chaos == "straggler" else None
        for i in range(n_jobs):
            svc.admit(f"chaos{i}", world)
        fleet = []
        for i in range(n_jobs):
            fleet.append(JobRun(
                f"chaos{i}", world, niter, sleep, addr_for(i), deadline,
                straggler=strag if i == 0 else None,
                kill=kill if i == 0 else None))
        wall = run_fleet(fleet, stagger)
        neighbors = fleet[1:]
        ratios = [(n.wall / c.wall) for n, c in zip(neighbors, clean[1:])
                  if c.wall > 0]
        n_ok = all(j.completed() and j.bitwise_ok() for j in neighbors)
        victim = fleet[0]
        rec = dict(base, mode="chaos", chaos=chaos,
                   straggle_s=(straggle if strag else 0.0),
                   wall_s=round(wall, 3),
                   victim_wall_s=round(victim.wall, 3),
                   victim_completed=victim.completed(),
                   victim_bitwise_ok=victim.bitwise_ok(),
                   neighbor_walls_s=[round(j.wall, 3) for j in neighbors],
                   neighbor_ratio_max=round(max(ratios), 3) if ratios
                   else -1.0,
                   neighbor_ratio_bar=bar,
                   neighbors_bitwise_ok=n_ok,
                   isolation_asserted=assert_isolation)
        records.append(rec)
        assert n_ok, "chaos arm: a NEIGHBOR job lost completion/bitwise " \
                     "identity — isolation broken"
        if assert_isolation and ratios:
            assert max(ratios) <= bar, (
                f"chaos arm: neighbor wall-clock {max(ratios):.2f}x its "
                f"clean run (> {bar}x) — noisy neighbor not isolated")

    # -- pooled arm --------------------------------------------------------
    if pool > 0:
        workers = [PooledWorker((svc.host, svc.port), f"w{i}",
                                lambda v, w, r: np.full(
                                    8, v * (r + 1), np.int64),
                                niter, deadline_sec=deadline)
                   for i in range(pool)]
        threads = [p.start_thread() for p in workers]
        time.sleep(0.3)
        t0 = time.monotonic()
        fits_ok = 0
        for i in range(pool_jobs):
            part = svc.admit(f"fit{i}", min(world, pool), pooled=True)
            if part.wait(deadline):
                fits_ok += 1
        pool_wall = time.monotonic() - t0
        for p in workers:
            p.stop()
        for t in threads:
            t.join(timeout=10)
        leases = [sum(1 for r in p.results if r.promoted) for p in workers]
        exp = expected_state(min(world, pool), niter)
        fits_bitwise = all(
            np.array_equal(r.state, exp)
            for p in workers for r in p.results if r.completed)
        rec = dict(base, mode="pooled", pool=pool, pool_jobs=pool_jobs,
                   fits_completed=fits_ok,
                   fits_per_sec=round(fits_ok / pool_wall, 3)
                   if pool_wall > 0 else -1.0,
                   leases_per_worker=leases,
                   fits_bitwise_ok=fits_bitwise)
        records.append(rec)
        assert fits_ok == pool_jobs and fits_bitwise, \
            "pooled arm: a pool-filled fit failed"

    # -- observed arm: the clean scenario + live telemetry attached --------
    if observed:
        # Periodic flight-ring spill in THIS process (the workers are
        # in-thread), so the follow exporter has live rings to tail
        # (doc/observability.md "Live telemetry plane").
        obs.configure(Config([f"rabit_obs_dir={obs_dir}",
                              "rabit_obs_spill_sec=0.5"]), rank=0)
        for i in range(n_jobs):
            svc.admit(f"obs{i}", world)
        fleet = [JobRun(f"obs{i}", world, niter, sleep, addr_for(i),
                        deadline) for i in range(n_jobs)]
        stop = threading.Event()
        scr = {"n": 0, "errors": 0, "lat": [], "live_max": 0,
               "incidents_max": 0}
        follow = {"rounds": 0, "events": 0, "error": ""}

        def scraper():
            while not stop.is_set():
                t0 = time.monotonic()
                try:
                    doc = obs_scrape(svc.host, svc.port)
                    scr["lat"].append(time.monotonic() - t0)
                    scr["n"] += 1
                    scr["live_max"] = max(
                        scr["live_max"],
                        len(doc.get("service", {}).get("live", [])))
                    scr["incidents_max"] = max(
                        scr["incidents_max"],
                        int(doc.get("incidents", {}).get("n_open", 0)))
                except Exception:  # noqa: BLE001 — observation is best-effort
                    scr["errors"] += 1
                stop.wait(1.0 / max(scrape_hz, 0.1))

        def follower():
            def on_round(n, doc):
                follow["rounds"] = n
                follow["events"] = len(doc.get("traceEvents", []))

            try:
                obs_trace.export_follow(obs_dir, interval=1.0,
                                        should_stop=stop.is_set,
                                        on_round=on_round)
            except Exception as e:  # noqa: BLE001 — recorded, never fatal
                follow["error"] = f"{type(e).__name__}: {e}"

        watchers = [threading.Thread(target=scraper, daemon=True),
                    threading.Thread(target=follower, daemon=True)]
        for t in watchers:
            t.start()
        wall = run_fleet(fleet, stagger)
        stop.set()
        for t in watchers:
            t.join(timeout=10)
        boots = [b for j in fleet for b in j.boot_lat]
        clean_boots = [b for j in clean for b in j.boot_lat]
        ratios = [(o.wall / c.wall) for o, c in zip(fleet, clean)
                  if c.wall > 0]
        p99_ratio = (pctl(boots, 99) / pctl(clean_boots, 99)
                     if pctl(clean_boots, 99) > 0 else -1.0)
        ok = all(j.completed() and j.bitwise_ok() for j in fleet)
        rec = dict(base, mode="observed", scrape_hz=scrape_hz,
                   wall_s=round(wall, 3),
                   jobs_per_sec=round(n_jobs / wall, 3),
                   boot_p50_ms=round(pctl(boots, 50) * 1e3, 3),
                   boot_p99_ms=round(pctl(boots, 99) * 1e3, 3),
                   boot_p99_ratio=round(p99_ratio, 3),
                   job_wall_ratio_max=round(max(ratios), 3) if ratios
                   else -1.0,
                   overhead_bar=obs_bar,
                   overhead_asserted=assert_isolation,
                   scrapes=scr["n"], scrape_errors=scr["errors"],
                   scrape_p99_ms=round(pctl(scr["lat"], 99) * 1e3, 3),
                   live_jobs_max=scr["live_max"],
                   incidents_open_max=scr["incidents_max"],
                   follow_rounds=follow["rounds"],
                   follow_trace_events=follow["events"],
                   follow_error=follow["error"],
                   bitwise_ok=ok, completed=ok)
        records.append(rec)
        assert ok, "observed arm: a job failed to complete " \
                   "bitwise-identically under observation"
        assert scr["n"] > 0 and scr["errors"] == 0, \
            f"observed arm: scraper failed ({scr['errors']} error(s))"
        assert not follow["error"], \
            f"observed arm: follow exporter failed: {follow['error']}"
        assert scr["incidents_max"] == 0, (
            f"observed arm: HealthMonitor opened {scr['incidents_max']} "
            f"incident(s) on a CLEAN run — diagnosis false positive")
        if assert_isolation:
            assert ratios and max(ratios) <= obs_bar, (
                f"observed arm: job wall-clock {max(ratios):.3f}x its "
                f"unobserved run (> {obs_bar}x) — observation is not cheap")
            assert 0 < p99_ratio <= obs_bar, (
                f"observed arm: boot p99 {p99_ratio:.3f}x the unobserved "
                f"arm (> {obs_bar}x) — observation is not cheap")

    tele = svc.build_telemetry()
    records.append(dict(base, mode="summary",
                        wire_legacy_identical=True,
                        service=tele.get("service", {}),
                        relay_stats=[dict(r.stats) for r in tier]))
    for r in tier:
        r.stop()
    svc.stop()
    return records


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=8,
                    help="concurrent jobs per arm (acceptance floor: 8)")
    ap.add_argument("--world", type=int, default=3)
    ap.add_argument("--niter", type=int, default=8)
    ap.add_argument("--sleep", type=float, default=0.15,
                    help="seconds of 'compute' per round per worker — "
                         "the full-mode default keeps each job's wall "
                         "in the seconds range so the 1.2x isolation "
                         "bar measures the service, not scheduler "
                         "jitter")
    ap.add_argument("--relays", type=int, default=2,
                    help="shared relay tier size (0 = direct)")
    ap.add_argument("--chaos", default="straggler",
                    choices=("straggler", "kill", "none"))
    ap.add_argument("--straggle", type=float, default=0.4,
                    help="straggler storm: extra seconds per round on "
                         "the victim job's rank 1")
    ap.add_argument("--bar", type=float, default=1.2,
                    help="neighbor wall-clock isolation bar (x clean)")
    ap.add_argument("--pool", type=int, default=3,
                    help="pooled workers for the pooled arm (0 skips)")
    ap.add_argument("--pool-jobs", type=int, default=4,
                    help="successive pool-filled fits")
    ap.add_argument("--deadline", type=float, default=90.0)
    ap.add_argument("--observed", action="store_true",
                    help="re-run the clean scenario with a live CMD_OBS "
                         "scraper + follow-mode trace exporter attached "
                         "and hold the overhead bar")
    ap.add_argument("--obs-bar", type=float, default=1.05,
                    help="observed-arm overhead bar (x the unobserved "
                         "clean arm, walls and boot p99)")
    ap.add_argument("--scrape-hz", type=float, default=1.0,
                    help="observed-arm scrape cadence")
    ap.add_argument("--obs-dir", default="",
                    help="observability dir for the observed arm "
                         "(default: a fresh temp dir)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI size: fewer rounds, isolation recorded but "
                         "not asserted (oversubscribed machines)")
    args = ap.parse_args(argv)

    if args.smoke:
        args.world = min(args.world, 2)
        args.niter = min(args.niter, 2)
        args.sleep = min(args.sleep, 0.03)
        args.straggle = min(args.straggle, 0.3)
        args.pool = min(args.pool, 2)
        args.pool_jobs = min(args.pool_jobs, 2)
        args.deadline = min(args.deadline, 45.0)

    records = bench_service(
        n_jobs=args.jobs, world=args.world, niter=args.niter,
        sleep=args.sleep, relays=args.relays, chaos=args.chaos,
        straggle=args.straggle, bar=args.bar, pool=args.pool,
        pool_jobs=args.pool_jobs, deadline=args.deadline,
        assert_isolation=not args.smoke, observed=args.observed,
        obs_bar=args.obs_bar, scrape_hz=args.scrape_hz,
        obs_dir=args.obs_dir)
    for rec in records:
        print(json.dumps(rec, sort_keys=True), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
