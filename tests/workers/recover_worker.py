"""Self-verifying fault-tolerance workload.

Mirrors the reference's integration test programs
(``/root/reference/test/model_recover.cc``, ``local_recover.cc``,
``lazy_recover.cc``): each iteration computes MAX/SUM allreduces, a
broadcast, and an allgather whose expected values are known in closed form
and checks every element, then checkpoints.  Run under the local cluster
launcher with ``mock=rank,version,seqno,trial`` args, the process is killed
at exactly those points, restarted by the launcher, and must recover its
model from peers and still produce correct results.

Worker args (k=v on the command line, all also forwarded to the engine):
    ndata=N        elements per collective (default 100)
    niter=N        iterations == checkpoints (default 3)
    local=1        also checkpoint a per-rank local model
    lazy=1         use lazy_checkpoint
    preload_op=1   run a keyed broadcast before load_checkpoint
                   (exercises the bootstrap cache)
    sleep=S        sleep S seconds per iteration — gives the run a
                   machine-independent minimum duration so timed external
                   preemptions (tests/test_preemption.py) reliably land
                   mid-work on hosts of any speed
    straggler=R    rank R additionally sleeps straggler_sleep seconds
                   (default 0.25) before each iteration's first collective
                   — a deterministic injected straggler whose arrival skew
                   the cross-rank trace analytics must attribute to R
                   (tools/trace_tool.py report, tests/test_trace.py)
    blob_mb=F      carry an F-MiB byte blob inside the global model, with
                   closed-form content per version so a recovered blob is
                   verified byte-for-byte — sizes the checkpoint-serve path
                   like a real forest/model (tools/recovery_bench.py
                   --blob-mb; the reference streams recovery through its
                   chunked data loops for exactly this regime,
                   allreduce_robust.cc:861-973)
    array_kb=K     carry a float32 ndarray of K KiB in the global model
                   ("weights") and, with local=1, another in the local one
                   ("state"), closed-form per version (and rank): large
                   enough, they are pickled out of band, so the frame a
                   peer serves a restarted worker has buffers behind its
                   pickle (rabit_tpu/api.py); what loads must be equal,
                   float32 and writable
    stop_at=K      every worker exits cleanly right after checkpoint K —
                   simulates a whole-job preemption for the durable-spill
                   resume tests (pair with rabit_checkpoint_dir=...)
    codec=NAME     self-check the f32 MAX allreduce against the codec's
                   closed-form reference fold (rabit_tpu.compress
                   .reference_allreduce) instead of the exact expectation —
                   pair with rabit_compress_allreduce=NAME (+ a small
                   rabit_compress_min_bytes) so the engine actually
                   compresses.  The check is EXACT (np.array_equal): a
                   compressed collective's delivery, including a
                   post-recovery replay, must be bitwise identical to the
                   deterministic reference fold.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

import rabit_tpu as rt


def getarg(name: str, default: str) -> str:
    # Last match wins, matching the config layer's argv semantics
    # (rabit_tpu/config.py layer 3): a caller can append overrides after
    # defaults and both the engine and the workload agree on the value.
    for a in reversed(sys.argv[1:]):
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return default


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(
            f"[{rt.get_rank()}] self-check failed: {what}"
        )


def main() -> int:
    ndata = int(getarg("ndata", "100"))
    niter = int(getarg("niter", "3"))
    blob_mb = float(getarg("blob_mb", "0"))
    pause = float(getarg("sleep", "0"))
    straggler = int(getarg("straggler", "-1"))
    straggler_sleep = float(getarg("straggler_sleep", "0.25"))

    def blob_for(ver: int) -> bytes:
        # Deterministic per-version content: recovery must reproduce the
        # exact bytes, so a truncated/corrupted serve cannot pass.
        return bytes([ver & 0xFF]) * int(blob_mb * (1 << 20))
    array_kb = int(getarg("array_kb", "0"))

    def array_for(ver: int, who: int) -> np.ndarray:
        return (np.arange(array_kb * 256) % 1000 + 1000 * ver + who
                ).astype(np.float32)

    def check_array(got, ver: int, who: int, what: str) -> None:
        want = array_for(ver, who)
        check(isinstance(got, np.ndarray) and got.dtype == want.dtype
              and np.array_equal(got, want), f"{what} at version {ver}")
        check(got.flags.writeable, f"{what} came back read-only")
        got += 1.0  # ours to write: nothing else reads this memory

    stop_at = int(getarg("stop_at", "0"))
    use_local = getarg("local", "0") == "1"
    use_lazy = getarg("lazy", "0") == "1"
    preload_op = getarg("preload_op", "0") == "1"
    codec = getarg("codec", "")

    rt.init()
    rank = rt.get_rank()
    world = rt.get_world_size()

    if preload_op:
        # A collective issued before load_checkpoint: replayed from the
        # bootstrap cache when this process is a restart (reference
        # README.md:25-28).
        cfg = rt.broadcast({"seed": 42, "ndata": ndata} if rank == 0 else None, 0)
        check(cfg == {"seed": 42, "ndata": ndata}, f"preload broadcast {cfg}")

    if use_local:
        version, model, lmodel = rt.load_checkpoint(with_local=True)
    else:
        version, model = rt.load_checkpoint()
        lmodel = None
    first_life = int(os.environ.get("DMLC_NUM_ATTEMPT", "0")) == 0
    if version == 0:
        model = {"iter": 0, "history": []}
        lmodel = {"rank": rank, "iter": 0}
    elif use_local and lmodel is None and first_life:
        # Documented disk-resume degradation (doc/guide.md, "Surviving
        # whole-job preemption"): a FIRST-LIFE rank killed between the
        # commit barrier and its local disk save resumes at the consensus
        # version with local_model=None and must REBUILD rank-local state,
        # not assert.  Restarted lives (DMLC_NUM_ATTEMPT > 0) are excluded
        # on purpose: within a running job the in-memory ring replicas
        # must serve local state, so a None there is a replication
        # regression this workload should still crash on.
        lmodel = {"rank": rank, "iter": version}
        rt.tracker_print(f"[{rank}] rebuilt local state at version {version}")
    check(model["iter"] == version, f"model vs version {version}")
    if blob_mb and version > 0:
        check(model.get("blob") == blob_for(version),
              f"blob mismatch at version {version}")
    if use_local:
        check(lmodel["rank"] == rank, f"local model {lmodel} not mine")
    if array_kb and version > 0:
        check_array(model["weights"], version, -1, "global weights")
        if use_local and "state" in lmodel:
            check_array(lmodel["state"], version, rank, "local state")
    if not first_life:
        # Restarted life: stamp the moment state was recovered from peers
        # (tools/recovery_bench.py diffs this against the launcher's
        # observed death time for protocol-level recovery latency).
        rt.tracker_print(
            f"[{rank}] recovered_at={time.time():.6f} version={version}"
        )
    elif version > 0:
        # First life yet version > 0: state came off the durable spill
        # (rabit_checkpoint_dir) — the resume tests assert this marker so
        # they cannot pass vacuously by retraining from scratch.  The ts
        # lets tools/recovery_bench.py --resume time the whole-job resume
        # path the way recovered_at times in-job recovery.
        rt.tracker_print(
            f"[{rank}] resumed from disk at version {version} "
            f"ts={time.time():.6f}")

    for it in range(version, niter):
        if pause:
            time.sleep(pause)
        if rank == straggler:
            # Injected straggler: everyone else reaches the MAX allreduce
            # and waits here — the arrival-skew signature trace analytics
            # must pin on this rank.
            time.sleep(straggler_sleep)
        # MAX: data[i] = rank + i + it  ->  world-1 + i + it
        a = (np.arange(ndata) + rank + it).astype(np.float32)
        out = rt.allreduce(a, rt.MAX)
        if codec:
            # Compressed path (policy from the engine args): the expected
            # value is the codec's reference fold over every rank's known
            # contribution — bitwise, including after recovery replay.
            from rabit_tpu.compress import reference_allreduce

            expect = reference_allreduce(
                [(np.arange(ndata) + r + it).astype(np.float32)
                 for r in range(world)],
                rt.MAX, codec)
        else:
            expect = (np.arange(ndata) + world - 1 + it).astype(np.float32)
        check(np.array_equal(out, expect), f"iter {it} max {out[:4]}")

        # broadcast an object from a rotating root
        root = it % world
        msg = {"iter": it, "root": root}
        got = rt.broadcast(msg if rank == root else None, root)
        check(got == msg, f"iter {it} bcast {got}")

        # SUM: data[i] = i + rank + it -> world*(i+it) + world*(world-1)/2
        a = (np.arange(ndata) + rank + it).astype(np.float64)
        out = rt.allreduce(a, rt.SUM)
        expect = (world * (np.arange(ndata) + it) + world * (world - 1) / 2
                  ).astype(np.float64)
        check(np.array_equal(out, expect), f"iter {it} sum {out[:4]}")

        # allgather of a per-rank vector
        g = rt.allgather(np.array([rank, it, rank * it], np.int64))
        expect = np.array([[r, it, r * it] for r in range(world)], np.int64)
        check(np.array_equal(g, expect), f"iter {it} allgather {g}")

        # Rebind a FRESH model object instead of mutating in place: the
        # lazy-checkpoint contract serializes on demand, and the engine may
        # still serve the PREVIOUS version (through the previous call's
        # callback) during this checkpoint's pre-commit consensus — an
        # in-place mutation here would be served as stale bytes of the old
        # version (same window as the reference's global_lazycheck).
        model = {"iter": it + 1, "history": model["history"] + [it]}
        if blob_mb:
            model["blob"] = blob_for(it + 1)
        if array_kb:
            model["weights"] = array_for(it + 1, -1)
        if use_local:
            lmodel = {"rank": rank, "iter": it + 1}
            if array_kb:
                lmodel["state"] = array_for(it + 1, rank)
            rt.checkpoint(model, lmodel)
        elif use_lazy:
            rt.lazy_checkpoint(model)
        else:
            rt.checkpoint(model)
        if array_kb and not use_lazy:
            # the caller may overwrite its arrays as soon as the commit has
            # returned: a peer must be served what was committed
            model["weights"][:] = -7.0
            if use_local:
                lmodel["state"][:] = -7.0
        check(rt.version_number() == it + 1, "version after checkpoint")
        if stop_at and it + 1 == stop_at:
            # Whole-job preemption simulation: every worker reaches this
            # same version and exits together, cleanly.
            check(model["history"] == list(range(stop_at)),
                  f"history at stop {model['history']}")
            rt.tracker_print(f"[{rank}] stopping at version {stop_at}")
            rt.finalize()
            return 0

    check(model["history"] == list(range(niter)), f"history {model['history']}")
    rt.tracker_print(f"[{rank}] all {niter} iterations verified")
    rt.finalize()
    return 0


if __name__ == "__main__":
    sys.exit(main())
