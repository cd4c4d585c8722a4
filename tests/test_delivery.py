"""Model-delivery plane (ISSUE 20, doc/delivery.md): the checkpoint
line as a content-addressed snapshot CDN.

Layers covered, bottom-up:

* wire units: CMD_SNAP frame round-trips over a socketpair and the
  bytes-level parser;
* publish/subscribe against a live tracker: line registration, chunked
  digest-verified fetch, cross-publisher digest dedup (identical bytes
  ship once — the ``have`` bit), catch-up semantics (a late subscriber
  converges on the NEWEST version, intermediate versions not replayed);
* the api seam: ``_publish_commit`` registers the committed blob and
  pins the published version in the durable store;
* the relay tier: fetch-through-relay is byte-identical to a direct
  fetch, the first fetch proxies and later fetches hit the digest cache,
  and the LRU byte budget (``rabit_relay_cache_bytes``) evicts
  unreferenced digests with ``blob_cache_evicted`` evidence;
* store retention: ``rabit_checkpoint_keep`` prunes old versions, a
  pinned (published) version survives pruning;
* HA: a mid-stream tracker kill — the standby restores the version line
  from the journal and every subscriber converges on the post-failover
  digest with zero errors (``tools/delivery_bench.py`` failover arm);
* scale: the writer's cadence with a 1k simulated subscriber swarm
  attached (tier-1, relaxed margin — the strict 0.95x bar is
  delivery_bench's), and the 10^4 acceptance swarm (slow).
"""

import socket
import time

import pytest

from rabit_tpu.delivery import CHUNK_BYTES, Publisher, Subscriber, digest_of
from rabit_tpu.relay import Relay
from rabit_tpu.store import CheckpointStore
from rabit_tpu.tracker import protocol as P
from rabit_tpu.tracker.tracker import Tracker
from tools.delivery_bench import run_dedup, run_failover, run_swarm


# -- wire units ---------------------------------------------------------------

def test_snap_frame_round_trip():
    digest = digest_of(b"model-bytes")
    a, b = socket.socketpair()
    try:
        a.sendall(P.put_snap_frame(digest, 1 << 20, 4096, b"\x7f" * 512))
        a.sendall(P.put_snap_frame("", 0, 0, b""))  # the absence frame
        assert P.read_snap_frame(b) == (digest, 1 << 20, 4096,
                                        b"\x7f" * 512)
        assert P.read_snap_frame(b) == ("", 0, 0, b"")
    finally:
        a.close()
        b.close()


def test_snap_frame_from_bytes():
    digest = digest_of(b"x")
    frame = P.put_snap_frame(digest, 100, 25, b"chunk")
    assert P.snap_frame_from_bytes(frame) == (digest, 100, 25, b"chunk")


# -- publish / subscribe against a live tracker -------------------------------

def test_publish_poll_fetch_direct():
    tr = Tracker(1, quiet=True).start()
    try:
        blob = bytes(range(256)) * 41  # not a multiple of the chunk size
        pub = Publisher(tr.host, tr.port, task_id="w0")
        reply = pub.publish(3, blob, epoch=2)
        assert reply["version"] == 3
        assert reply["digest"] == digest_of(blob)
        assert pub.uploads == 1

        sub = Subscriber(tr.host, tr.port, task_id="s0",
                         chunk_bytes=1000, poll_sec=0.05)
        line = sub.poll()
        assert (line["version"], line["epoch"]) == (3, 2)
        got_line, got = sub.fetch(line)
        assert got == blob
        assert got_line["size"] == len(blob)
        assert sub.seen_version == 3
    finally:
        tr.stop()


def test_digest_dedup_second_publisher_skips_upload():
    tr = Tracker(1, quiet=True).start()
    try:
        blob = b"\xab" * 4096
        first = Publisher(tr.host, tr.port, job="jobA", task_id="w0")
        second = Publisher(tr.host, tr.port, job="jobB", task_id="w0")
        r1 = first.publish(1, blob)
        r2 = second.publish(1, blob)
        assert not r1.get("have") and first.uploads == 1
        assert r2.get("have") and second.uploads == 0
        assert second.dedup_skips == 1
        # one digest-keyed copy held, regardless of publisher count
        assert list(tr._snaps) == [digest_of(blob)]
    finally:
        tr.stop()


def test_subscriber_catch_up_converges_on_newest():
    tr = Tracker(1, quiet=True).start()
    try:
        pub = Publisher(tr.host, tr.port, task_id="w0")
        for v in (1, 2, 3):
            pub.publish(v, bytes([v]) * 2048)
        # a subscriber that slept through v1/v2 wakes to the line naming
        # v3; the intermediate versions are not replayed
        sub = Subscriber(tr.host, tr.port, task_id="late", poll_sec=0.05)
        line = sub.wait_for(deadline_sec=5.0)
        assert line["version"] == 3
        _line, blob = sub.fetch(line)
        assert blob == b"\x03" * 2048
        with pytest.raises(TimeoutError):
            sub.wait_for(99, deadline_sec=0.2)
    finally:
        tr.stop()


def test_api_publish_seam_registers_and_pins(tmp_path):
    """api._publish_commit — the checkpoint-commit seam: the committed
    blob's line lands on the tracker and the published version is pinned
    in the durable store."""
    from rabit_tpu import api

    class _Eng:
        def version_number(self):
            return 2

    tr = Tracker(1, quiet=True).start()
    store = CheckpointStore(str(tmp_path), rank=0, keep=2)
    old = (api._publisher, api._ckpt_store, api._ckpt_base)
    try:
        api._publisher = Publisher(tr.host, tr.port, task_id="pub-0")
        api._ckpt_store = store
        api._ckpt_base = 10
        blob = b"committed-model" * 100
        api._publish_commit(_Eng(), blob)
        assert tr._delivery["version"] == 12  # base + engine version
        assert tr._delivery["digest"] == digest_of(blob)
        assert store._pinned == {12}
    finally:
        api._publisher, api._ckpt_store, api._ckpt_base = old
        tr.stop()


# -- the relay tier -----------------------------------------------------------

def test_fetch_through_relay_matches_direct():
    tr = Tracker(1, quiet=True).start()
    relay = Relay((tr.host, tr.port), relay_id="r0", flush_sec=0.05).start()
    try:
        blob = b"\xcd" * (64 << 10)
        Publisher(tr.host, tr.port, task_id="w0").publish(1, blob)

        direct = Subscriber(tr.host, tr.port, task_id="d0", poll_sec=0.05)
        relayed = Subscriber(relay.host, relay.port, task_id="r0",
                             poll_sec=0.05)
        line = relayed.wait_for(1, deadline_sec=5.0)
        _l, via_relay = relayed.fetch(line)
        assert via_relay == direct.fetch()[1] == blob
        assert relay.stats["snap_proxies"] == 1
        # the digest is now relay-cached: a second fetch is a pure hit
        relayed.fetch(line)
        assert relay.stats["snap_cache_hits"] >= 1
    finally:
        relay.stop()
        tr.stop()


def test_relay_cache_budget_evicts_unreferenced(monkeypatch):
    monkeypatch.setenv("RABIT_TPU_RABIT_RELAY_CACHE_BYTES", "150000")
    tr = Tracker(1, quiet=True).start()
    relay = Relay((tr.host, tr.port), relay_id="r0", flush_sec=0.05).start()
    try:
        assert relay._cache_budget == 150000
        pub = Publisher(tr.host, tr.port, task_id="w0")
        sub = Subscriber(relay.host, relay.port, task_id="s0",
                         poll_sec=0.05)
        blob_a, blob_b = b"\x01" * 100_000, b"\x02" * 100_000
        pub.publish(1, blob_a)
        assert sub.fetch(sub.wait_for(1, deadline_sec=5.0))[1] == blob_a
        # v2 supersedes v1: the old digest loses its reference and the
        # budget (150k < 200k) forces it out when v2's bytes land
        pub.publish(2, blob_b)
        assert sub.fetch(sub.wait_for(2, deadline_sec=5.0))[1] == blob_b
        deadline = time.monotonic() + 5.0
        while (digest_of(blob_a) in relay._digest_blobs
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert digest_of(blob_a) not in relay._digest_blobs
        assert digest_of(blob_b) in relay._digest_blobs
        assert relay.stats["evictions"] >= 1
        reasons = {e["reason"] for e in relay.events
                   if e.get("kind") == "blob_cache_evicted"}
        assert reasons & {"superseded", "lru"}
    finally:
        relay.stop()
        tr.stop()


# -- store retention ----------------------------------------------------------

def test_store_retention_window_and_pin(tmp_path):
    store = CheckpointStore(str(tmp_path), rank=0, keep=2)
    for v in (1, 2, 3, 4):
        store.save(v, b"g%d" % v, None)
    assert store._versions == [3, 4]  # keep=2 window

    store.pin(3)
    store.save(5, b"g5", None)
    store.save(6, b"g6", None)
    # the pinned version survives pruning; the unpinned window is still 2
    assert store._versions == [3, 5, 6]
    assert store.load_global(3) == b"g3"

    # pinning a newer version releases the older pin, which then prunes
    store.pin(6)
    store.save(7, b"g7", None)
    assert 3 not in store._versions


# -- HA: mid-stream tracker failover ------------------------------------------

def test_failover_restores_line_and_converges():
    rec = run_failover(n_subs=2, rounds=2, round_sec=0.1,
                       size=8192, poll_sec=0.05)
    assert rec["line_restored"], rec
    assert rec["subscriber_errors"] == 0, rec
    assert rec["converged"] == 2, rec
    assert rec["failover_ok"], rec


# -- scale: the subscriber swarm ----------------------------------------------

def test_dedup_uplink_flat_as_tenants_grow():
    rec = run_dedup(size=32 << 10, tenant_counts=(1, 4))
    assert rec["dedup_ok"], rec
    assert all(r["snaps_held"] == 1 for r in rec["rows"])


def test_writer_cadence_with_1k_swarm():
    rec = run_swarm(n_subs=1000, n_relays=2, rounds=3, round_sec=0.4,
                    size=64 << 10, poll_sec=0.15, shards=4)
    assert rec["polls"] > 0 and rec["n_lat"] > 0, rec
    # What holds whatever else loads the machine: the writer published
    # every round, every subscriber converged on its last digest, a real
    # one fetched those bytes.  The wall-clock bars (the writer's cadence,
    # the share of polls a starved process leaves unfinished) are
    # test_swarm_10k_acceptance's, on quiet hardware.
    assert rec["writer_rounds"] == 3, rec
    assert rec["converged"] == 1000 and rec["final_fetch_ok"], rec


@pytest.mark.slow
def test_swarm_10k_acceptance():
    rec = run_swarm(n_subs=10_000, n_relays=2, rounds=6, round_sec=5.0,
                    size=1 << 20, poll_sec=2.0, shards=8)
    assert rec["prop_p99_ms"] < 5_000.0, rec   # p99 < one training round
    assert rec["writer_cadence_ratio"] >= 0.95, rec
    assert rec["failures"] <= rec["polls"] * 0.02, rec
    assert rec["fetch_errors"] == 0, rec


def test_chunking_covers_default_window():
    # the default window is sane: positive, and a fetch with a tiny
    # window still reassembles exactly (covered above); this guards the
    # constant against accidental zero/negative edits
    assert CHUNK_BYTES > 0
    assert Subscriber("127.0.0.1", 1, chunk_bytes=0).chunk_bytes == 1
