"""Local cluster launcher — the cluster-in-a-box test harness.

Capability parity with ``dmlc-submit --cluster local --num-workers N
--local-num-attempt R`` (reference test harness, test/test.mk:14-38): runs
the tracker in-process, spawns N copies of a worker command as local
processes with the tracker's address in their environment, and restarts any
worker that dies (nonzero exit) up to ``max_restarts`` times — which is how
multi-node fault tolerance is tested on one machine.

Self-healing (doc/fault_tolerance.md): the tracker's heartbeat-lease
failure detector calls back into the launcher when a worker goes silent
(``on_suspect``), and the launcher SIGKILLs the suspect — converting a
SILENT hang (frozen process, preempted VM) into the ordinary death shape
the restart path and the engines' wave-based recovery already handle.

Elastic worlds (doc/elasticity.md): ``--spares K`` additionally launches K
hot-spare processes (task ids ``s0..s{K-1}``, ``rabit_spare=1`` in their
config environment) that park in the tracker's pool; ``--shrink-after S``
lets recovery waves close shrunk when the pool is empty past S seconds.
Spares are not restarted when they die and do not gate job completion.

Bookkeeping is keyed by TASK ID (``restarts``/``returncodes`` dicts), not
by spawn order: late-joining spares and shrunk worlds have no stable dense
index, and the old fixed-size lists would IndexError the moment task
``s0`` died or a world closed below its launch size.

Usage:
    python -m rabit_tpu.tracker.launcher --num-workers 4 \
        [--max-restarts 20] [--spares K] -- python worker_prog.py [args...]
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time

from rabit_tpu.tracker.tracker import Tracker


def cpu_worker_env() -> dict[str, str]:
    """PYTHONPATH for spawned workers: the inherited one with the repo
    root on it, so a worker script anywhere can ``import rabit_tpu``."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    parts = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep) if p]
    if repo not in parts:
        parts.insert(0, repo)
    return {"PYTHONPATH": os.pathsep.join(parts)}


def spare_task_id(i: int) -> str:
    """Task id of the i-th hot spare (workers use the dense ``str(i)``
    launcher numbering; spares must NOT — a spare is outside the dense
    rank space until the tracker promotes it)."""
    return f"s{i}"


class LocalCluster:
    def __init__(
        self,
        num_workers: int,
        max_restarts: int = 0,
        quiet: bool = False,
        extra_env: dict[str, str] | None = None,
        spares: int = 0,
        shrink_after_sec: float = 0.0,
        schedule: str = "auto",
        sched_mesh: str = "",
        relays: int = 0,
        relay_flush_sec: float = 0.25,
        standby: bool = False,
        ha_journal: str = "",
        takeover_sec: float = 1.0,
        job: str = "",
    ):
        self.num_workers = num_workers
        self.max_restarts = max_restarts
        self.quiet = quiet
        self.extra_env = extra_env or {}
        self.num_spares = int(spares)
        self.shrink_after_sec = float(shrink_after_sec)
        self.schedule = schedule
        self.sched_mesh = sched_mesh
        #: hierarchical relay tier (doc/scaling.md): R in-process relay
        #: nodes between the workers and the tracker; workers are
        #: sharded round-robin across them (worker i -> relay i % R), so
        #: the root tracker serves O(R) connections instead of O(N).
        #: 0 = direct (the wire bytes workers see are identical).
        self.num_relays = int(relays)
        self.relay_flush_sec = float(relay_flush_sec)
        self.relays: list = []
        #: HA control plane (doc/ha.md): standby=True runs a warm
        #: standby tracker in-process — the primary journals every
        #: control-plane mutation (to ha_journal when set, else an
        #: in-memory journal streamed over CMD_JOURNAL), workers get
        #: both addresses in rabit_tracker_addrs, and a primary death
        #: (run(kill_tracker_after=...) or a real crash) fails the job
        #: over within takeover_sec instead of killing it.
        self.use_standby = bool(standby)
        self.ha_journal = str(ha_journal or "")
        self.takeover_sec = float(takeover_sec)
        #: multi-tenant job key (doc/service.md): exported to the
        #: workers as rabit_job_key so they prefix their wire task ids
        #: — point the cluster at a CollectiveService and the whole run
        #: becomes one tenant of it.  Empty = legacy ids, byte-identical.
        self.job = str(job)
        self.standby = None
        self._worker_addrs: list[tuple[str, int]] = []
        #: per-task restart / last-returncode bookkeeping, keyed by TASK ID
        #: (workers "0".."N-1", spares "s0".."sK-1") — dicts, not spawn-
        #: order lists, so elastic membership cannot index out of range.
        self.restarts: dict[str, int] = {
            str(i): 0 for i in range(num_workers)}
        self.returncodes: dict[str, int | None] = {
            str(i): None for i in range(num_workers)}
        for i in range(self.num_spares):
            self.restarts[spare_task_id(i)] = 0
            self.returncodes[spare_task_id(i)] = None
        self.messages: list[str] = []  # tracker print log of the last run
        # Structured observability of the last run (doc/observability.md):
        # tracker events (bootstrap/recovery waves, recover_stats converted
        # from prints) and the job-level telemetry document — what tools/
        # consume instead of scraping self.messages.
        self.events: list[dict] = []
        self.telemetry: dict | None = None
        # time.time() at each observed worker death (recovery-latency
        # benchmarks diff these against worker-reported recovery stamps).
        # Preemptions are stamped when the SIGKILL is confirmed delivered —
        # including via the deferred-reap path — not when the restart branch
        # later reaps them, so benchmark latencies start at the actual kill.
        self.death_times: list[float] = []
        # how many scheduled preemptions were actually delivered (a target
        # that already exited cleanly is left alone and not counted)
        self.preempts_delivered = 0
        # how many scheduled SIGSTOP wedges landed (silent-hang injection),
        # and time.time() at each — liveness tests diff these against the
        # tracker's lease_expired timestamps for detection latency
        self.wedges_delivered = 0
        self.wedge_times: list[float] = []
        # task ids the tracker's lease monitor suspected; drained by the
        # poll loop, which SIGKILLs them (the monitor thread never touches
        # procs{} directly — all process state stays on the run() thread)
        self._suspects: list[str] = []
        self._suspect_lock = threading.Lock()
        # task ids whose death was already stamped into death_times by the
        # preemption/suspect path (the reap branch must not stamp them
        # twice — including a promoted spare later reaped dead)
        self._death_stamped: set[str] = set()

    def _on_suspect(self, task_id: str) -> None:
        """Tracker lease-monitor callback (runs on the monitor thread)."""
        with self._suspect_lock:
            self._suspects.append(task_id)

    def _target_addr(self, tracker: Tracker, task_id: str) -> tuple[str, int]:
        """The coordination address this task dials: the tracker, or its
        round-robin relay (stable per task id, so a restarted life lands
        on the same relay)."""
        if not self.relays:
            return tracker.host, tracker.port
        try:
            idx = int(task_id.lstrip("s"))
        except ValueError:
            idx = sum(task_id.encode())
        relay = self.relays[idx % len(self.relays)]
        return relay.host, relay.port

    def _spawn(self, cmd: list[str], tracker: Tracker,
               task_id: str, spare: bool = False) -> subprocess.Popen:
        host, port = self._target_addr(tracker, task_id)
        env = dict(os.environ)
        env.update(self.extra_env)
        env.update(
            DMLC_TRACKER_URI=host,
            DMLC_TRACKER_PORT=str(port),
            DMLC_TASK_ID=task_id,
            DMLC_NUM_ATTEMPT=str(self.restarts[task_id]),
        )
        if spare:
            # config layer 2 (rabit_tpu/config.py): RABIT_TPU_* env wins
            # over defaults, so the worker sees rabit_spare=1 without
            # touching its argv.
            env["RABIT_TPU_RABIT_SPARE"] = "1"
        if self.job:
            env["RABIT_TPU_RABIT_JOB_KEY"] = self.job
        if self._worker_addrs and not self.relays:
            # The HA failover list (doc/ha.md): direct workers rotate
            # through primary-then-standby; relayed workers keep their
            # relay address — the relay's channel rotates for them.
            env["RABIT_TPU_RABIT_TRACKER_ADDRS"] = ",".join(
                f"{h}:{p}" for h, p in self._worker_addrs)
        return subprocess.Popen(cmd, env=env)

    def run(
        self,
        cmd: list[str],
        timeout: float = 300.0,
        preempt: list[tuple[float, int]] | None = None,
        wedge: list[tuple[float, int]] | None = None,
        kill_tracker_after: float | None = None,
    ) -> int:
        """Run ``cmd`` x num_workers (+ spares) under a fresh tracker.
        Returns 0 when every primary worker exited cleanly; raises on
        restart-budget exhaustion or timeout.

        ``preempt`` schedules abrupt external deaths: ``[(delay_s, rank),
        ...]`` SIGKILLs that worker ``delay_s`` seconds after launch,
        wherever it happens to be — mid-collective, mid-bootstrap, inside a
        checkpoint.  This is the TPU-VM-preemption failure shape (BASELINE
        north star: "checkpoint-recover under induced preemption"), the
        complement of the mock engine's deterministic kill points.  The
        killed worker is restarted from the normal budget like any other
        death.

        ``wedge`` schedules SILENT hangs: ``[(delay_s, rank), ...]``
        SIGSTOPs that worker instead — no exit, no TCP error, its sockets
        stay open and its peers just block.  With heartbeat leases enabled
        (``rabit_heartbeat_sec`` on the workers) the tracker suspects the
        frozen worker, this launcher SIGKILLs it, and the hang becomes an
        ordinary recoverable death.

        ``kill_tracker_after`` (needs ``standby=True`` to be survivable)
        kills the PRIMARY TRACKER abruptly that many seconds in —
        ``Tracker.kill()``, the in-process SIGKILL: every socket drops
        with no goodbye.  The warm standby replays the journal, takes
        over within ``takeover_sec``, and the workers fail over via
        their ``rabit_tracker_addrs`` rotation (doc/ha.md)."""
        tracker_kwargs = dict(quiet=self.quiet,
                              on_suspect=self._on_suspect,
                              shrink_after_sec=self.shrink_after_sec,
                              schedule=self.schedule,
                              sched_mesh=self.sched_mesh)
        journal = None
        if self.use_standby:
            if self.ha_journal:
                journal = self.ha_journal
            else:
                from rabit_tpu.ha import Journal

                journal = Journal(None)
        tracker = Tracker(self.num_workers, journal=journal,
                          **tracker_kwargs).start()
        self.messages = tracker.messages
        self.events = tracker.events
        self._worker_addrs = []
        if self.use_standby:
            from rabit_tpu.ha import Standby

            self.standby = Standby(
                primary=(tracker.host, tracker.port),
                takeover_sec=self.takeover_sec,
                journal=self.ha_journal or None,
                tracker_kwargs=tracker_kwargs,
                quiet=self.quiet).start()
            self._worker_addrs = [(tracker.host, tracker.port),
                                  (self.standby.host, self.standby.port)]
        if self.num_relays > 0:
            from rabit_tpu.relay import Relay

            relay_target = (self._worker_addrs
                            or (tracker.host, tracker.port))
            self.relays = [
                Relay(relay_target, relay_id=f"relay{i}",
                      flush_sec=self.relay_flush_sec,
                      quiet=self.quiet).start()
                for i in range(self.num_relays)
            ]
        primaries = [str(i) for i in range(self.num_workers)]
        procs: dict[str, subprocess.Popen | None] = {
            t: self._spawn(cmd, tracker, t) for t in primaries}
        for i in range(self.num_spares):
            sid = spare_task_id(i)
            procs[sid] = self._spawn(cmd, tracker, sid, spare=True)
        start = time.monotonic()
        deadline = start + timeout
        pending = sorted(preempt or [], key=lambda p: p[0], reverse=True)
        wedges = sorted(wedge or [], key=lambda p: p[0], reverse=True)
        reap_pending: set[str] = set()  # killed, reap deferred to poll loop
        tracker_killed = False
        try:
            while True:
                if time.monotonic() > deadline:
                    raise TimeoutError(f"cluster did not finish within {timeout}s")
                if (kill_tracker_after is not None and not tracker_killed
                        and time.monotonic() - start >= kill_tracker_after):
                    tracker_killed = True
                    tracker.kill()
                    if not self.quiet:
                        print("[launcher] primary tracker KILLED "
                              "(abrupt; standby takeover pending)",
                              flush=True)
                while pending and time.monotonic() - start >= pending[-1][0]:
                    _, idx = pending[-1]
                    tid = str(idx)
                    proc = procs.get(tid)
                    if proc is not None and proc.poll() is not None:
                        # Target died but hasn't been reaped/restarted yet:
                        # keep the entry queued so the kill lands on the
                        # restarted life instead of being silently dropped.
                        break
                    pending.pop()
                    if proc is None:
                        continue  # finished cleanly — nothing to preempt
                    proc.kill()
                    killed_at = time.time()
                    # kill() on a child that exited between the poll()
                    # above and here is a silent no-op; only count the
                    # preemption as delivered when the reaped status shows
                    # the SIGKILL actually landed (returncode -9).  The
                    # wait here is deliberately short so a slow-to-reap
                    # child can't stall other scheduled preemptions or the
                    # deadline check; a pending reap is counted later from
                    # the poll loop's observed returncode.
                    try:
                        rc = proc.wait(timeout=0.5)
                        if rc == -signal.SIGKILL:
                            self.preempts_delivered += 1
                            # Stamp the death at the kill, not at the later
                            # restart reap — recovery-latency benchmarks
                            # measure from the real preemption instant.
                            self.death_times.append(killed_at)
                            self._death_stamped.add(tid)
                    except subprocess.TimeoutExpired:
                        reap_pending.add(tid)
                    if not self.quiet:
                        print(f"[launcher] preempted worker {tid} "
                              f"(SIGKILL)", flush=True)
                while wedges and time.monotonic() - start >= wedges[-1][0]:
                    _, idx = wedges[-1]
                    wedges.pop()
                    proc = procs.get(str(idx))
                    if proc is None or proc.poll() is not None:
                        continue  # already gone — nothing to freeze
                    proc.send_signal(signal.SIGSTOP)
                    self.wedges_delivered += 1
                    self.wedge_times.append(time.time())
                    if not self.quiet:
                        print(f"[launcher] wedged worker {idx} (SIGSTOP)",
                              flush=True)
                with self._suspect_lock:
                    suspects, self._suspects = self._suspects, []
                for task_id in suspects:
                    proc = procs.get(task_id)
                    if proc is None or proc.poll() is not None:
                        continue  # already dead/finished; nothing to heal
                    # Convert the silent hang into a death: SIGKILL works on
                    # stopped processes too, peers get TCP resets, and the
                    # normal restart/recovery path below takes over.  Stamp
                    # the death here (once — the reap branch checks the
                    # stamp), so spare-promotion latency benchmarks measure
                    # from the confirmed kill even for tasks that are never
                    # restarted (spares, shrunk-away ranks).
                    proc.kill()
                    self.death_times.append(time.time())
                    self._death_stamped.add(task_id)
                    if not self.quiet:
                        print(f"[launcher] worker {task_id} suspected by "
                              f"lease monitor: SIGKILL to force recovery",
                              flush=True)
                alive = 0
                for tid, proc in list(procs.items()):
                    if proc is None:
                        continue
                    is_spare = not tid.isdigit()
                    ret = proc.poll()
                    if ret is not None and tid in reap_pending:
                        reap_pending.discard(tid)
                        if ret == -signal.SIGKILL:
                            self.preempts_delivered += 1
                            # Deferred-reap preemptions must land in
                            # death_times too; reap time is the closest
                            # observable stamp left.
                            self.death_times.append(time.time())
                            self._death_stamped.add(tid)
                    if ret is None:
                        if not is_spare:
                            alive += 1
                    elif ret == 0:
                        self.returncodes[tid] = 0
                        procs[tid] = None
                    elif is_spare:
                        # A dead spare is not restarted and does not gate
                        # completion: the pool shrank, nothing more.
                        self.returncodes[tid] = ret
                        procs[tid] = None
                        if tid not in self._death_stamped:
                            self.death_times.append(time.time())
                            self._death_stamped.add(tid)
                        if not self.quiet:
                            print(f"[launcher] spare {tid} died "
                                  f"(code {ret}); pool shrank", flush=True)
                    else:
                        # Worker died: the reference tracker restarts it and
                        # peers recover (doc/guide.md:338-374).
                        died_at = time.time()   # poll() first said so
                        self.returncodes[tid] = ret
                        if self.restarts[tid] >= self.max_restarts:
                            raise RuntimeError(
                                f"worker {tid} died with code {ret}; restart "
                                f"budget ({self.max_restarts}) exhausted"
                            )
                        self.restarts[tid] += 1
                        if tid in self._death_stamped:
                            self._death_stamped.discard(tid)
                        else:
                            self.death_times.append(time.time())
                        if not self.quiet:
                            print(
                                f"[launcher] worker {tid} died (code {ret}); "
                                f"restart {self.restarts[tid]}/{self.max_restarts}",
                                flush=True,
                            )
                        procs[tid] = self._spawn(cmd, tracker, tid)
                        # kill -> noticed is death_times against the
                        # worker's own stamp, noticed -> spawned is here,
                        # spawned -> the new life's main is the worker's
                        self.events.append({
                            "ts": round(died_at, 6),
                            "kind": "worker_respawn",
                            "task": tid,
                            "attempt": self.restarts[tid],
                            "died_at": round(died_at, 6),
                            "spawned_at": round(time.time(), 6),
                        })
                        alive += 1
                if alive == 0:
                    return 0
                time.sleep(0.02)
        finally:
            for proc in procs.values():
                if proc is not None and proc.poll() is None:
                    proc.kill()
                    proc.wait()
            for relay in self.relays:
                relay.stop()
            self.relays = []
            promoted = (self.standby.tracker
                        if self.standby is not None
                        and self.standby.promoted.is_set() else None)
            if promoted is not None:
                # The promoted standby is the job's tracker of record:
                # its stop() (inside standby.stop) flushes telemetry,
                # and the job timeline is the primary's events up to
                # the cut plus the standby's from takeover.
                self.standby.stop()
                tracker.stop()
                self.telemetry = promoted.telemetry
                self.events = list(tracker.events) + list(promoted.events)
            else:
                if self.standby is not None:
                    self.standby.stop()
                tracker.stop()  # also flushes telemetry.json (idempotent)
                self.telemetry = tracker.telemetry


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--num-workers", "-n", type=int, required=True)
    ap.add_argument("--max-restarts", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=300.0)
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument(
        "--spares", type=int, default=0, metavar="K",
        help="launch K hot-spare processes (rabit_spare=1; task ids "
             "s0..s{K-1}) that park in the tracker's pool and are promoted "
             "into dead ranks' slots (doc/elasticity.md)",
    )
    ap.add_argument(
        "--relays", type=int, default=0, metavar="R",
        help="interpose R relay nodes between the workers and the "
             "tracker (hierarchical fan-out; workers shard round-robin "
             "across them — doc/scaling.md).  0 = direct",
    )
    ap.add_argument(
        "--shrink-after", type=float, default=0.0, metavar="SEC",
        help="let a recovery wave close SHRUNK when no spare fills the "
             "hole within SEC seconds (0 = legacy block-until-full)",
    )
    ap.add_argument(
        "--schedule", default="auto", choices=("auto", "tree", "ring",
                                               "swing"),
        help="collective schedule the tracker plans per epoch "
             "(rabit_schedule; doc/scheduling.md)",
    )
    ap.add_argument(
        "--sched-mesh", default="", metavar="RxC[:nowrap]",
        help="mesh-model dims for schedule planning (rabit_sched_mesh; "
             "empty = near-square auto dims)",
    )
    ap.add_argument(
        "--standby", action="store_true",
        help="run a warm-standby tracker in-process: the primary "
             "journals every control-plane mutation, workers get both "
             "addresses in rabit_tracker_addrs, and a primary tracker "
             "death fails over within --takeover-sec (doc/ha.md)",
    )
    ap.add_argument(
        "--ha-journal", default="", metavar="PATH",
        help="durable journal file for the HA control plane (default: "
             "the rabit_ha_journal config key; empty = in-memory, "
             "streamed to the standby over CMD_JOURNAL)",
    )
    ap.add_argument(
        "--takeover-sec", type=float, default=None, metavar="SEC",
        help="the standby's takeover lease (default: the "
             "rabit_ha_takeover_sec config key)",
    )
    ap.add_argument(
        "--job", default="", metavar="KEY",
        help="multi-tenant job key (rabit_job_key; doc/service.md): "
             "workers prefix their task ids with KEY/ so a "
             "CollectiveService routes them to this job's partition "
             "(default: the rabit_job_key config key)",
    )
    ap.add_argument(
        "--kill-tracker-after", type=float, default=None, metavar="SEC",
        help="ABRUPTLY kill the primary tracker SEC seconds in (the "
             "in-process SIGKILL; pair with --standby to prove the "
             "failover, omit --standby to prove the job loss)",
    )
    ap.add_argument(
        "--preempt", action="append", default=[], metavar="DELAY:RANK",
        help="SIGKILL worker RANK DELAY seconds after launch, wherever it "
             "happens to be (repeatable; induced-preemption testing)",
    )
    ap.add_argument(
        "--wedge", action="append", default=[], metavar="DELAY:RANK",
        help="SIGSTOP worker RANK DELAY seconds after launch — a silent "
             "hang with no exit and no TCP error (repeatable; pair with "
             "rabit_heartbeat_sec on the workers so the lease detector "
             "converts the hang into a restart)",
    )
    ap.add_argument("cmd", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("worker command required after --")

    def parse_schedule(entries: list[str], flag: str) -> list[tuple[float, int]]:
        out = []
        for s in entries:
            try:
                delay, rank = s.split(":")
                out.append((float(delay), int(rank)))
            except ValueError:
                ap.error(f"{flag} wants DELAY:RANK pairs, got {s!r}")
            if not 0 <= out[-1][1] < args.num_workers:
                ap.error(f"{flag} rank {out[-1][1]} outside "
                         f"0..{args.num_workers - 1}")
        return out

    preempt = parse_schedule(args.preempt, "--preempt")
    wedge = parse_schedule(args.wedge, "--wedge")
    from rabit_tpu.config import Config

    cfg = Config()
    ha_journal = args.ha_journal or cfg.get("rabit_ha_journal", "") or ""
    takeover = (args.takeover_sec if args.takeover_sec is not None
                else float(cfg.get("rabit_ha_takeover_sec", "1.0")
                           or "1.0"))
    cluster = LocalCluster(args.num_workers, args.max_restarts,
                           quiet=args.quiet, spares=args.spares,
                           shrink_after_sec=args.shrink_after,
                           schedule=args.schedule,
                           sched_mesh=args.sched_mesh,
                           relays=args.relays,
                           standby=args.standby,
                           ha_journal=ha_journal,
                           takeover_sec=takeover,
                           job=args.job or cfg.get("rabit_job_key", "")
                           or "")
    return cluster.run(cmd, timeout=args.timeout, preempt=preempt,
                       wedge=wedge,
                       kill_tracker_after=args.kill_tracker_after)


if __name__ == "__main__":
    sys.exit(main())
