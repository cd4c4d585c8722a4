"""Kill-and-recover integration tests.

The reference proves its fault tolerance with a scenario matrix run under a
local process cluster (``/root/reference/test/test.mk:14-38``, mechanism in
SURVEY.md §4 Tier 2): self-verifying workers linked against the mock engine
die at exact (rank, version, seqno, trial) points, the launcher restarts
them, and the restarted process must recover state from peers and keep every
closed-form check passing.  This file replicates that matrix against the
native robust engine.
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

from rabit_tpu.tracker.launcher import LocalCluster

WORKER = str(Path(__file__).parent / "workers" / "recover_worker.py")


def run_cluster(
    nworkers: int,
    worker_args: list[str],
    max_restarts: int = 10,
    timeout: float = 120.0,
) -> LocalCluster:
    cmd = [sys.executable, WORKER, "rabit_engine=mock", *worker_args]
    cluster = LocalCluster(nworkers, max_restarts=max_restarts, quiet=True)
    assert cluster.run(cmd, timeout=timeout) == 0
    assert all(rc == 0 for rc in cluster.returncodes.values())
    return cluster


# Op layout per iteration (see recover_worker.py): seq 0 = MAX allreduce,
# seq 1/2 = broadcast len/payload, seq 3 = SUM allreduce, seq 4 = allgather.


def test_no_failure_robust():
    """Sanity: the robust engine with no deaths behaves like the base one."""
    cluster = run_cluster(4, ["niter=3"], max_restarts=0)
    assert all(n == 0 for n in cluster.restarts.values())


def test_single_death():
    """One worker dies mid-iteration and recovers (reference
    model_recover_10_10k)."""
    cluster = run_cluster(4, ["niter=3", "mock=0,1,1,0"])
    assert cluster.restarts["0"] == 1


def test_death_at_first_op():
    """Death at the very first collective of version 0."""
    run_cluster(4, ["niter=3", "mock=2,0,0,0"])


def test_die_same_seqno():
    """Several workers die at the same operation (reference die_same:
    mock=0,0,1,0 mock=1,1,1,0 mock=0,1,1,0 mock=4,1,1,0 mock=9,1,1,0)."""
    run_cluster(
        6,
        ["niter=3", "mock=0,0,1,0;1,1,1,0;0,1,1,0;4,1,1,0;5,1,1,0"],
    )


def test_die_hard():
    """A worker dies, restarts, and is killed again while catching up
    (reference die_hard: mock=1,1,1,0 + mock=1,1,1,1 — the second entry
    fires on the restarted life)."""
    cluster = run_cluster(4, ["niter=3", "mock=1,1,1,0;1,1,1,1"])
    assert cluster.restarts["1"] == 2


def test_ring_path_recovery():
    """Force every allreduce onto the ring algorithm and recover (reference
    model_recover exercises rabit_reduce_ring_mincount=1)."""
    run_cluster(
        4,
        ["niter=3", "ndata=2048", "rabit_reduce_ring_mincount=1",
         "mock=3,1,0,0"],
    )


def test_local_checkpoint_recovery():
    """Per-rank local models ring-replicate and restore (reference
    local_recover_10_10k).  Both models carry a 256 KiB ndarray, pickled
    out of band: the ring replicates a frame's pieces as the engine joined
    them, and the restarted rank unpickles what its successors held."""
    cluster = run_cluster(4, ["niter=4", "local=1", "array_kb=256",
                              "mock=2,2,3,0"])
    assert cluster.restarts["2"] == 1


@pytest.mark.parametrize("args", [
    ["mock=1,2,1,0"],
    ["local=1", "mock=0,1,4,0;3,3,0,0"],
    ["local=1", "mock=1,1,-3,0"],
    ["array_kb=32", "mock=2,2,0,0"],
], ids=["global", "local, two deaths", "commit window", "arrays in band"])
def test_restarted_worker_is_served_a_frame(args):
    """A restarted worker gets its peers' copy of a checkpoint frame — the
    head, the pickle and the out-of-band buffers the committing rank handed
    the engine in pieces — and unpickles it: arrays equal, float32 and
    writable (the worker checks), after the committing rank has overwritten
    its own."""
    cluster = run_cluster(4, ["niter=4", "array_kb=256", *args])
    assert sum(cluster.restarts.values()) == args[-1].count(";") + 1


def test_local_model_zero_replicas():
    """rabit_local_replica=0: local models are checkpointed but not
    replicated — valid config, must not trip the consistency check."""
    cluster = run_cluster(
        4, ["niter=3", "local=1", "rabit_local_replica=0"], max_restarts=0
    )
    assert all(n == 0 for n in cluster.restarts.values())


def test_local_checkpoint_double_death():
    """Two deaths with local models: replicas must still cover both."""
    run_cluster(5, ["niter=4", "local=1", "mock=1,2,3,0;3,2,3,0"])


def test_lazy_checkpoint_recovery():
    """LazyCheckPoint defers serialization until a failure needs the blob
    (reference lazy_recover)."""
    run_cluster(4, ["niter=3", "lazy=1", "mock=1,2,0,0"])


def test_bootstrap_cache_replay():
    """A restarted worker replays its pre-load_checkpoint broadcast from the
    bootstrap cache (reference rabit_bootstrap_cache=1 scenarios)."""
    run_cluster(
        4,
        ["niter=3", "preload_op=1", "rabit_bootstrap_cache=1",
         "mock=1,1,3,0"],
    )


def test_death_before_first_checkpoint():
    """Restart before any checkpoint exists: full replay of version 0 from
    peers' replay logs."""
    run_cluster(4, ["niter=3", "preload_op=1", "rabit_bootstrap_cache=1",
                    "mock=2,0,3,0"])


def test_reduced_replica_budget():
    """Recovery still works when each result is kept by ~2 ranks only
    (exercises the rotating-replica drop rule)."""
    run_cluster(
        6,
        ["niter=3", "rabit_global_replica=2", "mock=1,1,2,0"],
    )


def test_death_at_checkpoint_entry():
    """A worker dies right as it enters CheckPoint while peers wait at the
    phase-1 barrier (seqno spec -1)."""
    run_cluster(4, ["niter=3", "mock=1,1,-1,0"])


def test_death_at_load_checkpoint_entry():
    """A restarted worker dies again at its LoadCheckPoint (seqno -2, trial
    1: second life)."""
    run_cluster(4, ["niter=3", "mock=2,1,0,0;2,0,-2,1"])


def test_death_in_commit_window():
    """Death after the checkpoint phase-1 barrier but before
    replication/commit (seqno -3) — the split-commit window where some peers
    may already hold version v+1."""
    run_cluster(4, ["niter=3", "local=1", "mock=1,1,-3,0"])


def test_death_in_commit_window_global_only():
    run_cluster(4, ["niter=3", "mock=2,2,-3,0"])


def test_staggered_overlapping_recoveries():
    """Two ranks die at different seqnos of the same version so one is
    still catching up (replaying seqnos) while the other is being served
    its checkpoint / syncing through the ack barrier — the window where
    the seqno election must ignore ack-barrier ranks' reset seqno."""
    run_cluster(5, ["niter=4", "mock=1,1,1,0;2,1,3,0"])


def test_many_iterations_many_deaths():
    """Staggered deaths across iterations and ranks."""
    run_cluster(
        4,
        ["niter=5", "mock=0,1,0,0;1,2,3,0;2,3,4,0;3,4,1,0"],
        max_restarts=10,
        timeout=180.0,
    )


def test_reference_scale_10_workers_10k():
    """The reference's canonical CI gate shape (test/test.mk:14-38 +
    scripts/travis_runtest.sh): 10 workers x 10k floats x 3 iterations
    under a 20-restart budget, with multi-rank deaths at the
    model_recover_10_10k kill points plus a die-hard second kill."""
    cluster = run_cluster(
        10,
        ["niter=3", "ndata=10000",
         "mock=0,0,1,0;1,1,1,0;4,1,1,0;9,1,1,0;1,1,1,1"],
        max_restarts=20,
        timeout=240.0,
    )
    assert cluster.restarts["1"] == 2  # die-hard: killed again on life 2


def test_recover_stats_lines():
    """rabit_recover_stats=1 emits the protocol-event evidence the
    recovery bench consumes: a failure_detected stamp from a survivor and
    the restarted worker's recover_stats counters at a nonzero version —
    consumed as structured tracker events (the profile-level stdout
    parsers are deprecated, see doc/observability.md)."""
    cluster = run_cluster(
        4, ["niter=3", "mock=1,1,1,0", "rabit_recover_stats=1"])
    detected = [e for e in cluster.events
                if e["kind"] == "failure_detected" and "at" in e]
    assert detected, f"no failure_detected event in {cluster.events}"

    stats = [e for e in cluster.events
             if e["kind"] == "recover_stats" and e.get("version", 0) > 0]
    assert stats, f"no recovered-life recover_stats event in {cluster.events}"

    fields = stats[0]
    assert fields["summary_rounds"] >= 1
    assert fields["serve_bytes"] > 0
    # Measured critical-path structure (round-5 verdict #4): the summary's
    # per-op merge depth is bounded by twice the binary-heap height — far
    # below the table's W-1 ring hops at scale.
    import math
    depth_per_op = fields["summary_depth"] / fields["summary_rounds"]
    assert 1 <= depth_per_op <= 2 * math.ceil(math.log2(4)) + 1, fields
    if fields["table_rounds"] > 0:
        hops_per_table = fields["table_hops"] / fields["table_rounds"]
        assert hops_per_table == 3, fields  # world 4 ring: W-1 hops
