"""Native engine tests: solo-mode ABI roundtrip, then real multi-process
clusters under the local tracker (the reference's tier-2 integration
pattern, SURVEY.md section 4, minus fault injection which the robust engine
tests add)."""

import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
WORKER = REPO / "tests" / "workers" / "basic_worker.py"


@pytest.fixture(scope="module")
def native_lib():
    from rabit_tpu.engine.native import load_lib

    return load_lib()


def test_native_solo_roundtrip(native_lib):
    """Solo mode through the C ABI in-process (native lib auto-selects its
    C++ EmptyEngine when no tracker is configured)."""
    import rabit_tpu as rt

    rt.init(rabit_engine="native")
    assert rt.get_rank() == 0
    assert rt.get_world_size() == 1
    x = np.arange(8, dtype=np.float32)
    np.testing.assert_array_equal(rt.allreduce(x, rt.SUM), x)
    assert rt.broadcast({"k": 1}, 0) == {"k": 1}
    rt.checkpoint({"model": [1, 2]})
    assert rt.version_number() == 1
    version, model = rt.load_checkpoint()
    assert (version, model) == (1, {"model": [1, 2]})
    rt.tracker_print("native solo ok")
    rt.finalize()


def test_native_checkpoint_takes_pieces(native_lib):
    """`NativeEngine.checkpoint` through the gather entry of the C ABI
    (`TrtCheckPointPieces`): pieces of any bytes-like kind, read-only and
    empty ones included, are copied once, in order, into the engine's own
    strings; a whole blob is one piece; no local piece is no local model;
    and nothing of the caller's is referenced once the call has returned."""
    import sys

    import rabit_tpu as rt
    from rabit_tpu import api

    rt.init(rabit_engine="native")
    eng = api._engine
    arr = np.arange(50_000, dtype=np.float32)
    ro = np.arange(7, dtype=np.int64)
    ro.flags.writeable = False
    refs = sys.getrefcount(arr), sys.getrefcount(ro)
    pieces = (b"head", bytearray(b"|body|"), memoryview(arr).cast("B"), b"",
              memoryview(ro).cast("B"))
    eng.checkpoint(pieces, [b"lo", b"cal"])
    del pieces
    assert (sys.getrefcount(arr), sys.getrefcount(ro)) == refs
    want = b"head|body|" + arr.tobytes() + ro.tobytes()
    arr[:] = 0                                    # the caller's, overwritten
    assert eng.load_checkpoint() == (1, want, b"local")
    eng.checkpoint(b"whole", None)
    assert eng.load_checkpoint() == (2, b"whole", None)
    eng.checkpoint([b"g"], [b"", b""])            # no byte in it: no local model
    assert eng.load_checkpoint() == (3, b"g", None)
    rt.finalize()


def run_cluster(num_workers, worker_args=(), max_restarts=0, timeout=90,
                extra_env=None):
    import os

    from rabit_tpu.tracker.launcher import LocalCluster

    env = {"PYTHONPATH": f"{REPO}:{os.environ.get('PYTHONPATH', '')}"}
    env.update(extra_env or {})
    cluster = LocalCluster(num_workers, max_restarts=max_restarts, quiet=True,
                           extra_env=env)
    args = list(map(str, worker_args))
    if not any(a.startswith("rabit_engine=") for a in args):
        args.append("rabit_engine=base")
    cmd = [sys.executable, str(WORKER), *args]
    rc = cluster.run(cmd, timeout=timeout)
    assert rc == 0
    return cluster


@pytest.mark.parametrize("world", [2, 3, 5, 8])
def test_cluster_collectives(world):
    run_cluster(world)


def test_cluster_large_payload_ring_path():
    # counts > reduce_ring_mincount exercise the ring allreduce
    run_cluster(4, worker_args=[100_000])


def test_cluster_reduce_buffer_budget():
    """A tiny rabit_reduce_buffer forces sub-chunked staging on both the
    tree and ring paths (reference 256MB ring-buffer flow control,
    allreduce_base.h:298-398) without changing any result."""
    run_cluster(4, worker_args=[100_000, "rabit_reduce_buffer=4K",
                                "rabit_reduce_ring_mincount=1"])
    run_cluster(3, worker_args=[50_000, "rabit_reduce_buffer=1K"])


def test_cluster_tiny_world():
    run_cluster(1)


def test_tracker_assigns_stable_ranks():
    """Direct tracker protocol exercise: two bootstrap waves keep task->rank
    mapping (re-admission of a restarted worker)."""
    import socket as pysock

    from rabit_tpu.tracker import protocol as P
    from rabit_tpu.tracker.tracker import Tracker

    tracker = Tracker(world_size=2, quiet=True).start()

    def boot(task_id, cmd=P.CMD_START):
        s = pysock.create_connection((tracker.host, tracker.port))
        P.send_hello(s, cmd, task_id, listen_port=50000)
        return s

    a, b = boot("a"), boot("b")
    asg_a = P.Assignment.recv(a)
    asg_b = P.Assignment.recv(b)
    assert {asg_a.rank, asg_b.rank} == {0, 1}
    assert asg_a.world_size == 2 and asg_a.epoch == 0
    assert asg_a.peers[asg_b.rank][1] == 50000
    a.close(); b.close()

    # second wave: same task ids -> same ranks, epoch bumped
    b2, a2 = boot("b", P.CMD_RECOVER), boot("a", P.CMD_RECOVER)
    asg_a2 = P.Assignment.recv(a2)
    asg_b2 = P.Assignment.recv(b2)
    assert asg_a2.rank == asg_a.rank and asg_b2.rank == asg_b.rank
    assert asg_a2.epoch == 1
    a2.close(); b2.close()
    tracker.stop()


def test_tracker_topology():
    from rabit_tpu.tracker import protocol as P

    assert P.tree_topology(0, 7) == (-1, [1, 2])
    assert P.tree_topology(1, 7) == (0, [3, 4])
    assert P.tree_topology(3, 7) == (1, [])
    assert P.tree_topology(2, 4) == (0, [])
