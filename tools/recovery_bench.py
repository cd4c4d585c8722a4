"""Recovery-latency benchmark (BASELINE.md target: "Recovery latency ...
checkpoint-recover under induced preemption").

Runs the self-verifying recovery workload (tests/workers/recover_worker.py,
10k floats x 3 iterations — the reference's model_recover_10_10k scenario
shape) under the local cluster twice per world size: clean, and with a mock
death at (rank 1, version 1, seq 1).  The difference is the end-to-end cost
of detecting the death, restarting the worker, re-bootstrapping the mesh,
replaying lost results, and serving the checkpoint.

Prints one JSON line per world size:
  {"world": N, "clean_s": ..., "failure_s": ..., "recovery_overhead_s": ...}

``--elastic`` switches to the elastic-membership mode (doc/elasticity.md):
seeded promote/shrink/grow scenarios with in-process ``ElasticWorker``
threads against an elastic tracker, reporting the spare-promotion-latency
vs. shrink-wave-latency curve per world size — every number derived from
structured tracker events (``spare_promoted`` / ``world_shrunk`` /
``world_grown`` timestamps), no stdout scraping.

``--scale-sweep`` switches to the simulated-world control-plane sweep
(tools/scale_sweep.py, doc/scaling.md): recovery-wave latency under
heartbeat load at worlds 512-8192, thread-per-connection vs reactor vs
relayed — the recovery half of the RESULTS/scale_sweep.jsonl curve
(bootstrap rides along; ``tools/consensus_bench.py --scale-sweep`` is the
same sweep).

``--failover`` switches to the HA-failover mode (doc/ha.md): per world
size, an in-thread elastic job with a warm standby gets its PRIMARY
TRACKER killed abruptly mid-run (``Tracker.kill()``, the in-process
SIGKILL), with and without a relay tier in front.  Rows report the
takeover latency (kill -> ``tracker_failover``) and the recovery
latency (kill -> the first wave/commit progress after the takeover),
all from structured events.

``--blob-mb B [B ...]`` switches to the checkpoint-serve-scaling mode
(round-5 verdict #3): the worker carries a B-MiB content-verified blob in
its global model, so the restarted rank's recovery streams a realistic
model payload (the XGBoost-forest regime) instead of 64 bytes.  Rows then
report serve bytes and the effective restore bandwidth
(serve_bytes / protocol latency — a lower bound, the window also spans
re-bootstrap + consensus).  The reference streams recovery through its
chunked data loops for exactly this regime
(/root/reference/src/allreduce_robust.cc:861-973).
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from rabit_tpu.tracker.launcher import LocalCluster, cpu_worker_env  # noqa: E402

WORKER = str(REPO / "tests" / "workers" / "recover_worker.py")


def run_once(world: int, extra: list[str], timeout: float | None = None,
             max_restarts: int = 5):
    """Returns (wall_s, protocol_latency_s|None, events|None,
    detect_latency_s|None, resume_latency_s|None).  Protocol latency =
    from the launcher observing the death to the restarted worker's state
    being recovered from peers (the recovered_at stamp recover_worker
    prints) — the death-detect -> re-bootstrap -> consensus ->
    checkpoint-serve path itself, without Python interpreter startup
    noise.  Resume latency = launch -> the LAST rank's resumed-from-disk
    stamp (the whole-job durable-resume path); None unless the run
    resumed from a rabit_checkpoint_dir spill.  Defaults (mock engine —
    identical to robust when no mock= kill spec is given — 10k floats,
    3 iters) are listed first; argv is last-match-wins in both the
    worker and the engine config, so anything in ``extra`` overrides."""
    cmd = [sys.executable, WORKER, "rabit_engine=mock", "ndata=10000",
           "niter=3", *extra]
    cluster = LocalCluster(world, max_restarts=max_restarts, quiet=True,
                           extra_env=cpu_worker_env())
    t0w = time.time()
    t0 = time.perf_counter()
    if timeout is None:
        # Scale with world: on an oversubscribed host, wall time grows
        # ~linearly in worker count (world 32 on this single-core container
        # already takes ~90 s — a flat 180 s left <2x headroom).
        timeout = max(180.0, world * 12.0)
    rc = cluster.run(cmd, timeout=timeout)
    dt = time.perf_counter() - t0
    if rc != 0 or any(r != 0 for r in cluster.returncodes.values()):
        raise RuntimeError(f"cluster failed: rc={rc} {cluster.returncodes}")
    # Structured events throughout (the stdout-scraping this tool used to
    # do is what rabit_tpu.profile's deprecated parsers served): the
    # tracker converts the workers' recovered_at / resumed-from-disk
    # stamps into worker_recovered / disk_resume events at CMD_PRINT
    # ingest (rabit_tpu.obs.events.event_from_stats_line).
    resume_stamps = [ev["at"] for ev in cluster.events
                     if ev["kind"] == "disk_resume" and "at" in ev]
    resume_latency = (max(resume_stamps) - t0w) if resume_stamps else None
    latency = None
    stamps = [ev["recovered_at"] for ev in cluster.events
              if ev["kind"] == "worker_recovered" and "recovered_at" in ev]
    if stamps and cluster.death_times:
        latency = min(stamps) - cluster.death_times[0]
    # Kill -> first survivor notices (EOF cascade / stall timeout), the
    # latency role the reference's unused OOB urgent-byte path targeted.
    # Structured events (cluster.events): the tracker converts the robust
    # engine's failure_detected / recover_stats prints into typed events —
    # no stdout scraping (the old profile.parse_stats_line facade was
    # removed in PR 5; the ingest parser lives in rabit_tpu.obs.events).
    detect = None
    detects = [ev["at"] for ev in cluster.events
               if ev["kind"] == "failure_detected" and "at" in ev]
    if detects and cluster.death_times:
        detect = min(detects) - cluster.death_times[0]
    # Protocol-event counters from the restarted worker's LoadCheckPoint
    # (rabit_recover_stats=1): version>0 identifies the recovered life —
    # first lives report version=0.  Scheduling-independent, unlike wall
    # time at oversubscribed world sizes.
    events = None
    for ev in cluster.events:
        if ev["kind"] != "recover_stats" or ev.get("version", 0) <= 0:
            continue
        events = {
            "summary_rounds": ev["summary_rounds"],
            "table_rounds": ev["table_rounds"],
            "serve_bytes": ev["serve_bytes"],
        }
        if "summary_depth" in ev:  # measured critical-path structure
            events["summary_depth"] = ev["summary_depth"]
            events["table_hops"] = ev["table_hops"]
        break
    return dt, latency, events, detect, resume_latency


def world_sweep(worlds: list[int]) -> None:
    for world in worlds:
        clean = min(run_once(world, [])[0] for _ in range(2))
        fails = [
            run_once(world, ["mock=1,1,1,0", "rabit_recover_stats=1"])
            for _ in range(2)
        ]
        failure = min(f[0] for f in fails)
        lats = [f[1] for f in fails if f[1] is not None]
        events = next((f[2] for f in fails if f[2] is not None), None)
        detects = [f[3] for f in fails if f[3] is not None]
        rec = {
            "world": world,
            "clean_s": round(clean, 3),
            "failure_s": round(failure, 3),
            "recovery_overhead_s": round(failure - clean, 3),
            "protocol_recovery_latency_s":
                round(min(lats), 3) if lats else None,
            "detect_latency_s": round(min(detects), 3) if detects else None,
        }
        if events is not None:
            rec.update(
                recover_summary_rounds=events["summary_rounds"],
                recover_table_rounds=events["table_rounds"],
                recover_serve_bytes=events["serve_bytes"],
            )
            if "summary_depth" in events:
                rec.update(recover_summary_depth=events["summary_depth"],
                           recover_table_hops=events["table_hops"])
        print(json.dumps(rec), flush=True)


def blob_sweep(blob_mbs: list[float], worlds: list[int]) -> None:
    for world in worlds:
        for blob_mb in blob_mbs:
            fails = [
                run_once(world,
                         [f"blob_mb={blob_mb}", "mock=1,1,1,0",
                          "rabit_recover_stats=1"])
                for _ in range(2)
            ]
            lats = [f[1] for f in fails if f[1] is not None]
            events = next((f[2] for f in fails if f[2] is not None), None)
            lat = min(lats) if lats else None
            rec = {
                "blob_mb": blob_mb,
                "world": world,
                "failure_s": round(min(f[0] for f in fails), 3),
                "protocol_recovery_latency_s":
                    round(lat, 3) if lat else None,
            }
            if events is not None:
                rec["recover_serve_bytes"] = events["serve_bytes"]
                if lat:
                    rec["restore_bandwidth_mb_s"] = round(
                        events["serve_bytes"] / (1 << 20) / lat, 1)
            print(json.dumps(rec), flush=True)


def resume_sweep(blob_mbs: list[float], worlds: list[int]) -> None:
    """Whole-job (durable) resume timing — the preemption shape §4's
    in-job rows cannot see: every worker dies, in-memory state is gone,
    and a FRESH cluster resumes from the rabit_checkpoint_dir spill.

    Per row: job 1 runs niter=4 and exits cleanly at stop_at=2 (the
    aligned whole-job stop), job 2 resumes on the same directory and
    finishes.  resume_latency_s = job-2 launch -> the last rank's
    resumed-from-disk stamp (spans interpreter boot, bootstrap, the
    resume consensus, and the per-rank disk read — compare §4's ~0.25 s
    in-job floor, which shares the boot+bootstrap terms).  fresh_wall_s
    (the same 4-iteration job from scratch) isolates what resuming COSTS
    over a cold boot at each payload size; what it SAVES is the skipped
    iterations, negligible at this toy shape and the whole point at real
    per-iteration costs."""
    niter, stop_at = 4, 2
    for world in worlds:
        for blob_mb in blob_mbs:
            blob = [f"blob_mb={blob_mb}"] if blob_mb else []
            fresh = run_once(world, [f"niter={niter}", *blob])[0]
            with tempfile.TemporaryDirectory() as d:
                store = [f"rabit_checkpoint_dir={d}"]
                job1 = run_once(
                    world, [f"niter={niter}", f"stop_at={stop_at}",
                            *blob, *store])[0]
                wall, _, _, _, resume_latency = run_once(
                    world, [f"niter={niter}", *blob, *store],
                    max_restarts=0)
                if resume_latency is None:
                    raise RuntimeError("job 2 did not resume from disk")
            print(json.dumps({
                "mode": "durable_resume", "world": world,
                "blob_mb": blob_mb, "resumed_at_version": stop_at,
                "niter": niter,
                "fresh_wall_s": round(fresh, 3),
                "job1_wall_s": round(job1, 3),
                "resume_wall_s": round(wall, 3),
                "resume_latency_s": round(resume_latency, 3),
            }), flush=True)


def _elastic_once(world: int, *, with_spare: bool, grow_back: bool,
                  shrink_after_sec: float, niter: int = 6,
                  iter_sleep: float = 0.05, kill_version: int = 2,
                  deadline_sec: float = 45.0) -> dict:
    """One elastic scenario (doc/elasticity.md): kill rank-1's worker at
    ``kill_version``; with a spare parked the tracker must promote it
    within one wave, without one the wave closes shrunk after
    ``shrink_after_sec`` (and grows back when a late spare arrives, when
    ``grow_back``).  Latencies are death -> the membership event's ``ts``,
    both sides structured: the death instant is the dying worker thread's
    return (an ElasticWorker with fail=("die", v) returns the moment it
    dies), the membership instants are tracker-event timestamps."""
    import threading

    import numpy as np

    from rabit_tpu.elastic.client import ElasticWorker
    from rabit_tpu.elastic.rebalance import shard_slice
    from rabit_tpu.tracker.tracker import Tracker

    n_rows, n_bins = 8 * world, 8
    data = np.arange(n_rows) % n_bins

    def contribution(version, w, r):
        time.sleep(iter_sleep)
        rows = data[shard_slice(n_rows, w, r)]
        return np.bincount(rows, minlength=n_bins).astype(np.int64) * version

    tracker = Tracker(world, quiet=True, shrink_after_sec=shrink_after_sec,
                      promote_after_sec=0.05).start()
    addr = (tracker.host, tracker.port)
    death_at = {}

    def run_worker(w: ElasticWorker) -> None:
        w.run()
        if w.fail is not None:
            death_at[w.task_id] = time.time()

    workers = [
        ElasticWorker(addr, str(i), contribution, niter,
                      heartbeat_sec=0.1, wave_timeout=15.0,
                      link_timeout=1.0, deadline_sec=deadline_sec,
                      fail=("die", kill_version) if i == 1 else None)
        for i in range(world)
    ]
    threads = [threading.Thread(target=run_worker, args=(w,), daemon=True)
               for w in workers]
    # A grow-back spare parks just after the shrink deadline would have
    # passed — the next version boundary's CMD_EPOCH poll sees the pool
    # and re-waves.
    spare_delay = 0.0 if with_spare else (shrink_after_sec + 0.5
                                          if grow_back else None)

    def run_spare() -> None:
        if spare_delay:
            time.sleep(spare_delay)
        run_worker(ElasticWorker(addr, "s0", contribution, niter, spare=True,
                                 heartbeat_sec=0.1, wave_timeout=15.0,
                                 link_timeout=1.0,
                                 deadline_sec=deadline_sec))

    spare_th = (threading.Thread(target=run_spare, daemon=True)
                if spare_delay is not None else None)
    try:
        for th in threads:
            th.start()
        if spare_th is not None:
            spare_th.start()
        for th in threads:
            th.join(timeout=deadline_sec + 5.0)
            if th.is_alive():
                raise TimeoutError(f"elastic bench world={world}: hang")
    finally:
        tracker.stop()
        if spare_th is not None:
            spare_th.join(timeout=10.0)
    t_death = death_at.get("1")

    def first_ts(kind):
        return next((e["ts"] for e in tracker.events if e["kind"] == kind),
                    None)

    lat = lambda ts: (round(ts - t_death, 3)
                      if ts is not None and t_death is not None else None)
    return {
        "promote_latency_s": lat(first_ts("spare_promoted")),
        "shrink_latency_s": lat(first_ts("world_shrunk")),
        "grow_latency_s": lat(first_ts("world_grown")),
        "epochs": [{"epoch": we.epoch, "world": we.world_size}
                   for we in tracker.elastic.history],
    }


def _failover_once(world: int, *, relays: int, kill_at: float = 0.8,
                   niter: int = 10, iter_sleep: float = 0.12,
                   takeover_sec: float = 0.5,
                   deadline_sec: float = 60.0) -> dict:
    """One HA failover scenario (doc/ha.md): an in-thread elastic job
    with a warm standby, the primary killed abruptly at ``kill_at``.
    Latencies come from structured events: takeover = kill ->
    ``tracker_failover`` ts, recovery = kill -> the first post-failover
    progress (a wave closed on the standby, and the first worker commit
    after the cut).  The last rank dies a few versions AFTER the
    tracker kill, so the survivors MUST re-wave on the promoted standby
    (shrink) — the takeover is load-bearing, not incidental: a bench
    run that completes proves the failover carried a recovery wave."""
    import threading

    import numpy as np

    from rabit_tpu.elastic.client import ElasticWorker
    from rabit_tpu.elastic.rebalance import shard_slice
    from rabit_tpu.ha import Journal, Standby
    from rabit_tpu.relay import Relay
    from rabit_tpu.tracker.tracker import Tracker

    n_rows, n_bins = 8 * world, 8
    data = np.arange(n_rows) % n_bins

    def contribution(version, w, r):
        time.sleep(iter_sleep)
        rows = data[shard_slice(n_rows, w, r)]
        return np.bincount(rows, minlength=n_bins).astype(np.int64) * version

    expected = sum(np.bincount(data, minlength=n_bins).astype(np.int64) * v
                   for v in range(1, niter + 1))
    die_at = max(2, int(round(kill_at / iter_sleep)) + 2)  # post-failover
    tracker_kwargs = dict(quiet=True, promote_after_sec=0.05,
                          shrink_after_sec=0.8)
    tracker = Tracker(world, journal=Journal(None),
                      **tracker_kwargs).start()
    addr = (tracker.host, tracker.port)
    standby = Standby(primary=addr, takeover_sec=takeover_sec,
                      poll_sec=0.05,
                      tracker_kwargs=tracker_kwargs).start()
    addrs = [addr, (standby.host, standby.port)]
    relay_objs = [Relay(addrs, relay_id=f"relay{i}", flush_sec=0.1,
                        quiet=True).start() for i in range(relays)]

    def worker_target(i: int):
        if not relay_objs:
            return addrs
        r = relay_objs[i % len(relay_objs)]
        return (r.host, r.port)

    results = {}

    def run_worker(w):
        results[w.task_id] = w.run()

    workers = [ElasticWorker(worker_target(i), str(i), contribution, niter,
                             heartbeat_sec=0.15, wave_timeout=15.0,
                             link_timeout=2.0, deadline_sec=deadline_sec,
                             fail=(("die", die_at) if i == world - 1
                                   else None))
               for i in range(world)]
    threads = [threading.Thread(target=run_worker, args=(w,), daemon=True)
               for w in workers]
    t_kill = None
    try:
        for th in threads:
            th.start()
        time.sleep(kill_at)
        t_kill = time.time()
        t_kill_mono = time.monotonic()
        tracker.kill()
        for th in threads:
            th.join(timeout=deadline_sec + 10.0)
            if th.is_alive():
                raise TimeoutError(f"failover bench world={world}: hang")
    finally:
        standby.stop()
        tracker.stop()
        for r in relay_objs:
            r.stop()
    for res in results.values():
        if res.died:
            continue  # the scheduled post-failover death
        if not res.completed or not np.array_equal(res.state, expected):
            raise RuntimeError(f"failover bench world={world}: worker "
                               f"{res.task_id} wrong/incomplete "
                               f"({res.error!r})")
    promoted = standby.tracker
    events = list(tracker.events) + (list(promoted.events)
                                     if promoted is not None else [])
    t_failover = next((e["ts"] for e in events
                       if e["kind"] == "tracker_failover"), None)
    post_waves = [e["ts"] for e in events
                  if e["kind"] == "wave" and e["ts"] > (t_failover or 1e18)]
    # first commit strictly after the kill (monotonic clock, same basis
    # as the workers' commit_times)
    post_commits = [ts for res in results.values()
                    for ts in res.commit_times.values()
                    if ts > t_kill_mono]
    rec = {
        "mode": "ha_failover", "world": world, "relays": relays,
        "kill_at_s": kill_at, "takeover_sec": takeover_sec,
        "takeover_latency_s": (round(t_failover - t_kill, 3)
                               if t_failover is not None else None),
        "first_wave_after_s": (round(min(post_waves) - t_kill, 3)
                               if post_waves else None),
        "first_commit_after_s": (round(min(post_commits) - t_kill_mono, 3)
                                 if post_commits else None),
        # exactly ONE expected: the scheduled post-failover death's
        # lease, expired BY THE STANDBY (proof the re-armed lease table
        # still detects failures after the cut); more would be live
        # ranks suspected spuriously
        "n_lease_expired": sum(
            1 for e in events if e["kind"] == "lease_expired"),
    }
    return rec


def failover_sweep(worlds: list[int]) -> list[dict]:
    """The --failover mode: kill-the-primary latency rows, direct and
    through a relay tier, per world size."""
    out = []
    for world in worlds:
        for relays in (0, 1):
            rec = _failover_once(world, relays=relays)
            out.append(rec)
            print(json.dumps(rec), flush=True)
    return out


def elastic_sweep(worlds: list[int],
                  shrink_after_sec: float = 1.0) -> list[dict]:
    """The promotion-vs-shrink curve: per world size, the same induced
    death handled by a parked spare (promotion latency) and by the shrink
    deadline + a late grow-back (shrink/grow latencies)."""
    out = []
    for world in worlds:
        promote = _elastic_once(world, with_spare=True, grow_back=False,
                                shrink_after_sec=shrink_after_sec)
        # Slower, longer job so version boundaries remain AFTER the shrink
        # for the grow-back wave to land on.
        shrink = _elastic_once(world, with_spare=False, grow_back=True,
                               shrink_after_sec=shrink_after_sec,
                               niter=16, iter_sleep=0.15)
        rec = {
            "mode": "elastic", "world": world,
            "shrink_after_sec": shrink_after_sec,
            "promote_latency_s": promote["promote_latency_s"],
            "promote_epochs": promote["epochs"],
            "shrink_latency_s": shrink["shrink_latency_s"],
            "grow_latency_s": shrink["grow_latency_s"],
            "shrink_epochs": shrink["epochs"],
        }
        out.append(rec)
        print(json.dumps(rec), flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("worlds", nargs="*", type=int, default=None)
    ap.add_argument("--blob-mb", nargs="+", type=float, default=None,
                    help="checkpoint-serve scaling mode: blob sizes in MiB")
    ap.add_argument("--resume", action="store_true",
                    help="durable whole-job resume timing mode (combine "
                         "with --blob-mb for payload scaling; blob 0 rows "
                         "come from plain --resume)")
    ap.add_argument("--elastic", action="store_true",
                    help="elastic-membership mode: spare-promotion vs "
                         "shrink-wave latency per world size "
                         "(doc/elasticity.md)")
    ap.add_argument("--failover", action="store_true",
                    help="HA failover mode: primary-tracker kill -> "
                         "standby takeover / first post-failover "
                         "progress latency, with and without relays "
                         "(doc/ha.md)")
    ap.add_argument("--shrink-after", type=float, default=1.0,
                    help="elastic mode's rabit_shrink_after_sec")
    ap.add_argument("--scale-sweep", action="store_true",
                    help="simulated-world recovery/bootstrap wave sweep "
                         "(doc/scaling.md; worlds from the positional "
                         "args, default 512 1024 2048 4096)")
    args = ap.parse_args()
    if args.scale_sweep:
        from tools.scale_sweep import scale_sweep

        scale_sweep(args.worlds or [512, 1024, 2048, 4096])
    elif args.failover:
        failover_sweep(args.worlds or [2, 4])
    elif args.elastic:
        elastic_sweep(args.worlds or [2, 4], args.shrink_after)
    elif args.resume:
        resume_sweep(args.blob_mb or [0.0], args.worlds or [4])
    elif args.blob_mb:
        blob_sweep(args.blob_mb, args.worlds or [4])
    else:
        world_sweep(args.worlds or [4, 8])


if __name__ == "__main__":
    main()
