"""delivery_bench — evidence for the model-delivery plane (doc/delivery.md).

A live writer job (a real :class:`rabit_tpu.delivery.Publisher` committing
a new snapshot every ``--round-sec``) against a selector-simulated
subscriber swarm, ``scale_sweep``-style: ONE process stands in for
10^4-10^5 subscribers by driving per-subscriber CMD_SUB polls (and a few
real full-fetch Subscriber threads) through a tier of relays, so the
bench measures serving behavior at fleet scale without a fleet.

Arms (``--arm all`` is the default):

* ``swarm`` — N simulated subscribers poll the version line through R
  relays while the writer publishes; reports snapshot propagation
  p50/p99 (publish -> a subscriber's poll observes the version), poll
  failure count, and the WRITER-CADENCE tax: rounds/s with the swarm
  attached vs the same writer unobserved (bar: >= 0.95x); then, at no
  wall-clock bar, how many subscribers converge on the writer's last
  digest (``converged``: all of them) and whether a real one fetches it.
* ``dedup`` — T publishers (tenants) commit IDENTICAL bytes as T grows
  1 -> 8; reports the root-uplink wire bytes per tenant count (bar:
  <= 1.2x the single-tenant bytes — content addressing ships the blob
  once).
* ``failover`` — a journaled primary + warm standby; the tracker is
  killed mid-stream.  The standby must restore the version line from
  the journal (``snapshot_published`` records), the writer and every
  subscriber rotate via the address list, and all subscribers converge
  on the post-failover digest with ZERO spurious errors.

Output: one JSON line per arm, each tagged ``{"bench": "delivery"}``.
``--smoke`` shrinks every knob.
"""

from __future__ import annotations

import argparse
import errno
import json
import selectors
import socket
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from rabit_tpu.delivery import Publisher, Subscriber, digest_of  # noqa: E402
from rabit_tpu.ha import Journal, Standby  # noqa: E402
from rabit_tpu.relay import Relay  # noqa: E402
from rabit_tpu.tracker import protocol as P  # noqa: E402
from rabit_tpu.tracker.tracker import Tracker  # noqa: E402
from tools.scale_sweep import raise_fd_limit  # noqa: E402


def _pct(vals: list[float], q: float) -> float | None:
    if not vals:
        return None
    vals = sorted(vals)
    return vals[min(int(q * (len(vals) - 1)), len(vals) - 1)]


def _sub_poll_bytes(task_id: str) -> bytes:
    return (P.put_u32(P.MAGIC_HELLO) + P.put_u32(P.CMD_SUB) + P.put_i32(-1)
            + P.put_str(task_id) + P.put_str("{}"))


class _Poll:
    """One in-flight simulated CMD_SUB poll (connect -> write -> drain
    to EOF -> parse the version out of the JSON reply)."""

    __slots__ = ("sock", "sub", "out", "buf", "connected")

    def __init__(self, sock, sub: int, out: bytes):
        self.sock = sock
        self.sub = sub
        self.out = bytearray(out)
        self.buf = bytearray()
        self.connected = False


def _drive_shard(targets: list[tuple[str, int]], subs: range,
                 duration_sec: float, poll_sec: float,
                 publish_ts: dict[int, float],
                 stop: threading.Event | None, out: list) -> None:
    """One swarm shard: selector-drive a contiguous slice of simulated
    subscribers, each polling the version line every ``poll_sec``
    (phase-staggered) against its round-robin target.  ``publish_ts``
    maps version -> monotonic publish time (the writer fills it); the
    first poll of each subscriber that OBSERVES a version records the
    propagation latency.  Appends a stats dict to ``out``; its ``seen`` is
    the newest version each subscriber observed inside the window."""
    sel = selectors.DefaultSelector()
    t0 = time.monotonic()
    deadline = t0 + duration_sec
    next_poll = {i: t0 + (i % 997) / 997.0 * poll_sec for i in subs}
    seen: dict[int, int] = dict.fromkeys(subs, 0)
    inflight: dict[int, _Poll] = {}
    lat: list[float] = []
    polls = failures = 0

    def _open(sub: int) -> None:
        nonlocal failures
        try:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setblocking(False)
        except OSError:
            failures += 1
            return
        p = _Poll(sock, sub, _sub_poll_bytes(f"sw{sub}"))
        try:
            rc = sock.connect_ex(targets[sub % len(targets)])
        except OSError:
            sock.close()
            failures += 1
            return
        if rc not in (0, errno.EINPROGRESS, errno.EWOULDBLOCK):
            sock.close()
            failures += 1
            return
        try:
            sel.register(sock, selectors.EVENT_WRITE, p)
        except (ValueError, KeyError, OSError):
            sock.close()
            failures += 1
            return
        inflight[sub] = p

    def _close(p: _Poll, ok: bool) -> None:
        nonlocal polls, failures
        try:
            sel.unregister(p.sock)
        except (KeyError, ValueError):
            pass
        p.sock.close()
        inflight.pop(p.sub, None)
        if not ok:
            failures += 1
            return
        polls += 1
        # reply: u32 ACK + u32 len + JSON line
        if len(p.buf) >= 8:
            try:
                line = json.loads(p.buf[8:].decode())
                v = int(line.get("version", 0))
            except (ValueError, UnicodeDecodeError):
                return
            if v > seen[p.sub]:
                seen[p.sub] = v
                ts = publish_ts.get(v)
                if ts is not None:
                    lat.append(time.monotonic() - ts)

    while time.monotonic() < deadline and not (stop and stop.is_set()):
        now = time.monotonic()
        for sub, t_next in next_poll.items():
            if t_next <= now and sub not in inflight:
                next_poll[sub] = now + poll_sec
                _open(sub)
        for key, mask in sel.select(0.02):
            p: _Poll = key.data
            if not p.connected and mask & selectors.EVENT_WRITE:
                err = p.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
                if err:
                    _close(p, ok=False)
                    continue
                p.connected = True
            if p.out and mask & selectors.EVENT_WRITE:
                try:
                    n = p.sock.send(p.out)
                    del p.out[:n]
                except BlockingIOError:
                    pass
                except OSError:
                    _close(p, ok=False)
                    continue
                if not p.out:
                    try:
                        sel.modify(p.sock, selectors.EVENT_READ, p)
                    except (ValueError, KeyError, OSError):
                        _close(p, ok=False)
                    continue
            if mask & selectors.EVENT_READ:
                try:
                    data = p.sock.recv(1 << 16)
                except BlockingIOError:
                    continue
                except OSError:
                    _close(p, ok=False)
                    continue
                if data:
                    p.buf += data
                else:
                    _close(p, ok=True)
    for p in list(inflight.values()):
        _close(p, ok=False)
    sel.close()
    out.append({"polls": polls, "failures": failures, "lat": lat,
                "seen": seen})


def drive_swarm(targets: list[tuple[str, int]], n_subs: int,
                duration_sec: float, poll_sec: float,
                publish_ts: dict[int, float],
                stop: threading.Event | None = None,
                shards: int = 8) -> dict:
    """Drive ``n_subs`` simulated subscribers split across ``shards``
    selector threads (socket syscalls release the GIL, so sharding is
    what lets one process stand in for 10^4-10^5 pollers).  Returns
    aggregate polls/failures/latency percentiles and every subscriber's
    newest ``seen`` version."""
    shards = max(1, min(shards, n_subs))
    per = (n_subs + shards - 1) // shards
    out: list[dict] = []
    threads = [threading.Thread(
        target=_drive_shard,
        args=(targets, range(lo, min(lo + per, n_subs)), duration_sec,
              poll_sec, publish_ts, stop, out), daemon=True)
        for lo in range(0, n_subs, per)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(duration_sec + 60)
    lat = [x for s in out for x in s["lat"]]
    return {"polls": sum(s["polls"] for s in out),
            "failures": sum(s["failures"] for s in out),
            "seen": {i: v for s in out for i, v in s["seen"].items()},
            "n_lat": len(lat),
            "prop_p50_ms": (_pct(lat, 0.50) or 0.0) * 1e3,
            "prop_p99_ms": (_pct(lat, 0.99) or 0.0) * 1e3}


def _writer(pub: Publisher, rounds: int, round_sec: float, size: int,
            publish_ts: dict[int, float], out: dict,
            start_version: int = 0) -> None:
    """The live writer job: one publish per round at the training
    cadence, each round's bytes distinct (a real model delta)."""
    t0 = time.monotonic()
    done = 0
    for r in range(rounds):
        blob = bytes([r & 0xFF]) * size
        v = start_version + r + 1
        try:
            pub.publish(v, blob, epoch=1)
        except ConnectionError:
            continue
        publish_ts[v] = time.monotonic()
        out["digest"] = digest_of(blob)
        done += 1
        t_next = t0 + (r + 1) * round_sec
        time.sleep(max(t_next - time.monotonic(), 0.0))
    out["rounds"] = done
    out["seconds"] = time.monotonic() - t0
    out["rounds_per_sec"] = done / max(out["seconds"], 1e-9)


def run_swarm(n_subs: int, n_relays: int, rounds: int, round_sec: float,
              size: int, poll_sec: float, shards: int = 8) -> dict:
    raise_fd_limit(n_subs // 4 + 256)
    tr = Tracker(1, quiet=True).start()
    relays = [Relay((tr.host, tr.port), relay_id=f"r{i}",
                    flush_sec=min(poll_sec / 2, 0.25)).start()
              for i in range(n_relays)]
    targets = [(r.host, r.port) for r in relays]
    duration = rounds * round_sec + 2 * poll_sec
    try:
        # unobserved baseline: the same writer, nobody watching
        base: dict = {}
        _writer(Publisher(tr.host, tr.port, task_id="w-base"),
                rounds, round_sec, size, {}, base)
        # observed: swarm attached (plus one real full-fetch verifier)
        publish_ts: dict[int, float] = {}
        obs: dict = {}
        stop = threading.Event()
        fetch_errors = [0]
        fetched = [0]

        def _verify():
            sub = Subscriber(targets[0][0], targets[0][1],
                             task_id="verify", poll_sec=poll_sec)
            while not stop.is_set():
                try:
                    line = sub.poll()
                    if int(line.get("version", 0)) > sub.seen_version:
                        _l, blob = sub.fetch(line, deadline_sec=duration)
                        if digest_of(blob) != line["digest"]:
                            fetch_errors[0] += 1
                        else:
                            fetched[0] += 1
                except (ConnectionError, LookupError, TimeoutError):
                    fetch_errors[0] += 1
                time.sleep(poll_sec)

        wt = threading.Thread(
            target=_writer,
            args=(Publisher(tr.host, tr.port, task_id="w-obs"),
                  rounds, round_sec, size, publish_ts, obs),
            kwargs={"start_version": rounds}, daemon=True)
        vt = threading.Thread(target=_verify, daemon=True)
        wt.start()
        vt.start()
        swarm = drive_swarm(targets, n_subs, duration, poll_sec,
                            publish_ts, shards=shards)
        wt.join(duration + 30)
        stop.set()
        vt.join(5)
        # The window judges how fast; whether delivery converges is judged
        # after it, at no deadline a loaded machine can miss: a subscriber
        # the window starved polls on until it names the writer's last
        # digest, and a real one fetches those bytes.
        final = max(publish_ts, default=0)

        def _lands(i: int) -> bool:
            sub = Subscriber(*targets[i % len(targets)], task_id=f"sw{i}",
                             poll_sec=poll_sec)
            try:
                return sub.wait_for(final)["digest"] == obs.get("digest")
            except (ConnectionError, TimeoutError):
                return False

        converged = sum(v >= final or _lands(i)
                        for i, v in swarm.pop("seen").items())
        try:   # fetch() holds the bytes to the digest of the line it returns
            line, _blob = Subscriber(*targets[0], task_id="verify",
                                     poll_sec=poll_sec).fetch()
            final_fetch_ok = line["digest"] == obs.get("digest")
        except (ConnectionError, LookupError, TimeoutError):
            final_fetch_ok = False
        cadence = (obs.get("rounds_per_sec", 0.0)
                   / max(base.get("rounds_per_sec", 1e-9), 1e-9))
        return {
            "bench": "delivery", "arm": "swarm", "subs": n_subs,
            "relays": n_relays, "rounds": rounds, "round_sec": round_sec,
            "snapshot_bytes": size, **swarm,
            "fetches_verified": fetched[0], "fetch_errors": fetch_errors[0],
            "writer_rounds": obs.get("rounds", 0), "converged": converged,
            "final_fetch_ok": final_fetch_ok,
            "writer_rounds_per_sec": round(obs.get("rounds_per_sec", 0.0), 3),
            "unobserved_rounds_per_sec": round(
                base.get("rounds_per_sec", 0.0), 3),
            "writer_cadence_ratio": round(cadence, 4),
            "round_ms": round_sec * 1e3,
        }
    finally:
        for r in relays:
            r.stop()
        tr.stop()


def run_dedup(size: int, tenant_counts: tuple[int, ...] = (1, 2, 4, 8)
              ) -> dict:
    """Root-uplink wire bytes as tenants-per-identical-snapshot grows:
    content addressing must keep the uplink flat (<= 1.2x the
    single-tenant bytes), because only the first publisher of a digest
    uploads."""
    rows = []
    blob = b"\xa5" * size
    for t in tenant_counts:
        tr = Tracker(1, quiet=True).start()
        try:
            uplink = 0
            for i in range(t):
                pub = Publisher(tr.host, tr.port, task_id=f"tenant{i}")
                reply = pub.publish(i + 1, blob, epoch=1)
                # uplink cost: the line RPC always; the blob only when
                # the tracker did not already hold the digest
                uplink += 256 + pub.uploads * size
                assert reply["digest"] == digest_of(blob)
            rows.append({"tenants": t, "uplink_bytes": uplink,
                         "snaps_held": len(tr._snaps)})
        finally:
            tr.stop()
    base = rows[0]["uplink_bytes"]
    worst = max(r["uplink_bytes"] / base for r in rows)
    return {"bench": "delivery", "arm": "dedup", "snapshot_bytes": size,
            "rows": rows, "worst_uplink_ratio": round(worst, 4),
            "dedup_ok": worst <= 1.2}


def run_failover(n_subs: int, rounds: int, round_sec: float,
                 size: int, poll_sec: float) -> dict:
    """Kill the tracker mid-stream: the standby restores the version
    line from the journal, the writer and the (real) subscribers rotate
    addresses, and every subscriber converges on the post-failover
    digest with zero spurious errors."""
    journal = str(Path(tempfile.mkdtemp(prefix="delivery_ha_")) /
                  "journal.bin")
    tr = Tracker(1, quiet=True, journal=journal, ha_tick_sec=0.05).start()
    standby = Standby(journal_path=journal, takeover_sec=0.6,
                      poll_sec=0.05, standby_id="delivery-standby").start()
    addrs = [(tr.host, tr.port), (standby.host, standby.port)]
    subs = [Subscriber(tr.host, tr.port, task_id=f"ha-sub{i}",
                       addrs=addrs, timeout=2.0, retries=8,
                       poll_sec=poll_sec) for i in range(n_subs)]
    errors = 0
    try:
        pub = Publisher(tr.host, tr.port, task_id="ha-writer",
                        addrs=addrs, timeout=2.0, retries=8)
        pre_blob = b"\x01" * size
        pub.publish(1, pre_blob, epoch=1)
        for s in subs:
            line, blob = s.fetch(deadline_sec=10.0)
            if blob != pre_blob:
                errors += 1
        tr.journal.flush(5.0)
        t_kill = time.monotonic()
        tr.kill()
        if not standby.wait_promoted(10.0):
            raise RuntimeError("standby never promoted")
        promoted = standby.tracker
        t_takeover = time.monotonic() - t_kill
        # the journaled line survived the primary
        restored = dict(promoted._delivery or {})
        line_restored = restored.get("version") == 1
        # the writer's next publishes land on the standby via rotation
        # (the byte store is process state — the re-publish re-feeds it)
        post_blob = b"\x02" * size
        for r in range(rounds):
            pub.publish(2 + r, post_blob if r == rounds - 1
                        else b"\x03" * size, epoch=1)
        want = digest_of(post_blob)
        converged = 0
        for s in subs:
            try:
                line = s.wait_for(rounds + 1, deadline_sec=15.0)
                _l, blob = s.fetch(line, deadline_sec=15.0)
                if line["digest"] == want and blob == post_blob:
                    converged += 1
                else:
                    errors += 1
            except (ConnectionError, TimeoutError, LookupError):
                errors += 1
        return {"bench": "delivery", "arm": "failover", "subs": n_subs,
                "takeover_sec": round(t_takeover, 3),
                "line_restored": line_restored,
                "converged": converged, "subscriber_errors": errors,
                "failover_ok": (line_restored and errors == 0
                                and converged == n_subs)}
    finally:
        standby.stop()
        tr.stop()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python tools/delivery_bench.py",
        description="model-delivery plane bench: subscriber swarm, "
                    "dedup uplink, tracker failover (doc/delivery.md)")
    ap.add_argument("--arm", default="all",
                    choices=["all", "swarm", "dedup", "failover"])
    ap.add_argument("--subs", type=int, default=10_000,
                    help="simulated subscribers (swarm arm)")
    ap.add_argument("--relays", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=6,
                    help="writer publishes per arm")
    ap.add_argument("--round-sec", type=float, default=5.0,
                    help="writer cadence — one training round (at the "
                         "10^4-subscriber regime a round is seconds)")
    ap.add_argument("--size", type=int, default=1 << 20,
                    help="snapshot bytes per publish")
    ap.add_argument("--poll-sec", type=float, default=2.0)
    ap.add_argument("--shards", type=int, default=8,
                    help="swarm selector threads")
    ap.add_argument("--ha-subs", type=int, default=8,
                    help="real full-fetch subscribers (failover arm)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: tiny swarm, short rounds")
    args = ap.parse_args(argv)
    if args.smoke:
        args.subs = min(args.subs, 200)
        args.rounds = min(args.rounds, 4)
        args.round_sec = min(args.round_sec, 0.4)
        args.size = min(args.size, 64 << 10)
        args.poll_sec = min(args.poll_sec, 0.15)
        args.ha_subs = min(args.ha_subs, 4)

    ok = True
    if args.arm in ("all", "swarm"):
        rec = run_swarm(args.subs, args.relays, args.rounds,
                        args.round_sec, args.size, args.poll_sec,
                        shards=args.shards)
        # acceptance: propagation p99 under one training round, writer
        # cadence within 5% of unobserved
        rec["prop_ok"] = rec["prop_p99_ms"] < args.round_sec * 1e3
        rec["cadence_ok"] = rec["writer_cadence_ratio"] >= 0.95
        ok &= rec["prop_ok"] and rec["cadence_ok"]
        print(json.dumps(rec), flush=True)
    if args.arm in ("all", "dedup"):
        rec = run_dedup(args.size)
        ok &= rec["dedup_ok"]
        print(json.dumps(rec), flush=True)
    if args.arm in ("all", "failover"):
        rec = run_failover(args.ha_subs, args.rounds, args.round_sec,
                           args.size, args.poll_sec)
        ok &= rec["failover_ok"]
        print(json.dumps(rec), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
