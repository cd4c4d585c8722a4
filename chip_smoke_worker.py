"""The trainer ``chip_smoke.py`` starts through the launcher: a user program
in the shape of ``tests/workers/gbdt_worker.py`` that trains on whatever
accelerator JAX brings up — and refuses to train on anything else.

One process, one life of one phase.  Every round is one jitted boosting
step on the device followed by one ``rabit_tpu.checkpoint`` with the forest
as the global model and the margin as the local model, so a killed worker
resumes byte-identically (a margin rebuilt by re-predicting sums the leaves
in another order).  Arguments are rabit-style ``key=value``:

    mode=fused|hybrid|dp   the round: fused Pallas kernels on one device;
                           ``train_round_hybrid``, the same kernels with
                           the engine hop as a host callback; or the fused
                           round under ``shard_map`` over every device,
                           followed by the same rounds on one of them for
                           comparison
    rows= rounds= seed=    data from ``bench.make_data(rows, seed)``
    kill_after=K           first life only: SIGKILL self after commit K
    out=DIR tag=NAME       results: DIR/NAME.jsonl (one line per life) and
                           DIR/NAME_forest.npz
    margins=1              also DIR/NAME_margins.npy, every round's margin
    rehearse=1             chip_smoke.py --rehearse: accept a CPU and run
                           the kernels in the Pallas interpreter
"""

import functools
import hashlib
import json
import os
import signal
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench  # noqa: E402  (numpy only: the data and the size constants)
import rabit_tpu as rabit  # noqa: E402
from rabit_tpu._platform import enable_persistent_cache  # noqa: E402

BLOCK = 1024  # row block of the fused kernels (ops.boost.block_rows)


def getarg(name: str, default: str) -> str:
    for a in reversed(sys.argv[1:]):
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    return default


def block_host(x: np.ndarray) -> np.ndarray:
    """``ops.boost.block_rows`` on the host, so a sharded matrix never
    passes through one device on its way to four."""
    n = x.shape[0]
    x = x.reshape(n, -1)
    pad = -n % BLOCK
    if pad:
        x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
    return x.reshape(-1, BLOCK, x.shape[1])


def forest_digest(forest) -> str:
    h = hashlib.sha256()
    for a in forest:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class CacheCounter:
    """Persistent-compilation-cache requests and hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def main() -> int:
    mode = getarg("mode", "fused")
    rows = int(getarg("rows", str(bench.N_ROWS)))
    rounds = int(getarg("rounds", "6"))
    seed = int(getarg("seed", "0"))
    kill_after = int(getarg("kill_after", "0"))
    rehearse = getarg("rehearse", "0") == "1"
    save_margins = getarg("margins", "0") == "1"
    out = Path(getarg("out", "."))
    tag = getarg("tag", mode)
    life = int(os.environ.get("DMLC_NUM_ATTEMPT", "0"))
    t_start = time.time()

    enable_persistent_cache()  # before the first compile, in every life
    import jax
    import jax.numpy as jnp

    from rabit_tpu.models import gbdt

    if rehearse:
        # A tiny CPU compile can finish under the cache's one-second floor;
        # the rehearsal is of the cache path too, so cache everything.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not rehearse:
        print(f"chip_smoke_worker: JAX came up on {device['platform']}, not "
              "a TPU; refusing to train", file=sys.stderr, flush=True)
        return 3
    cache = CacheCounter()

    xb, y = bench.make_data(rows, seed)
    cfg = gbdt.GBDTConfig(
        n_features=bench.N_FEATURES, n_trees=rounds, depth=bench.DEPTH,
        n_bins=bench.N_BINS, learning_rate=bench.LR, reg_lambda=bench.LAM,
        # bench.cpu_round, the reference, has no child-weight floor
        min_child_weight=0.0,
    )

    rabit.init()
    version, forest_np, margin_np = rabit.load_checkpoint(with_local=True)
    line = {"phase": tag, "mode": mode, "life": life, "device": device,
            "rows": rows, "features": cfg.n_features, "bins": cfg.n_bins,
            "depth": cfg.depth, "rounds": rounds, "seed": seed,
            "resumed_at_version": version,
            "cache_dir": jax.config.jax_compilation_cache_dir}

    hops = [0]
    if mode == "fused":
        place = jnp.asarray
        data = (place(block_host(xb)), place(y))
        step = jax.jit(functools.partial(
            gbdt.train_round_fused, cfg=cfg, interpret=rehearse))
    elif mode == "hybrid":
        def engine_hop(a: np.ndarray) -> np.ndarray:
            hops[0] += 1
            return rabit.allreduce(np.asarray(a, np.float32), rabit.SUM)

        place = jnp.asarray
        data = (place(xb), place(y))
        step = jax.jit(functools.partial(
            gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=engine_hop,
            interpret=rehearse))
    elif mode == "dp":
        from jax.sharding import NamedSharding, PartitionSpec as P

        from rabit_tpu.parallel import create_mesh

        mesh = create_mesh(("dp",), devices=devs)
        n_dev = len(devs)
        if rows % n_dev:
            raise SystemExit(f"rows={rows} do not split over {n_dev} devices")
        per = rows // n_dev
        by_row = NamedSharding(mesh, P("dp"))
        place = lambda a: jax.device_put(a, by_row)
        xb3 = jax.device_put(
            np.concatenate([block_host(xb[i * per:(i + 1) * per])
                            for i in range(n_dev)]),
            NamedSharding(mesh, P("dp", None, None)))
        data = (xb3, place(y))
        spec = gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()),
                               margin=P("dp"), round=P())
        step = jax.jit(jax.shard_map(
            functools.partial(gbdt.train_round_dp_fused, cfg=cfg,
                              interpret=rehearse),
            mesh=mesh, in_specs=(spec, P("dp", None, None), P("dp")),
            out_specs=spec, check_vma=False))
    else:
        raise SystemExit(f"unknown mode {mode!r}")

    if version == 0:
        state = gbdt.init_state(cfg, rows)
        state = state._replace(margin=place(np.asarray(state.margin)))
    else:
        state = gbdt.TrainState(
            forest=gbdt.Forest(*(jnp.asarray(a) for a in forest_np)),
            margin=place(margin_np),
            round=jnp.asarray(version, jnp.int32))

    if mode == "dp":
        # Code that has only met a virtual CPU mesh may put everything on
        # the first device: check the placement before training on it.
        for name, arr, want in (("xb3", data[0], -(-per // BLOCK)),
                                ("margin", state.margin, per)):
            shards = arr.addressable_shards
            where = {s.device for s in shards}
            got = sorted({s.data.shape[0] for s in shards})
            if len(shards) != n_dev or len(where) != n_dev or got != [want]:
                raise SystemExit(
                    f"{name}: {len(shards)} shards on {len(where)} devices "
                    f"with leading sizes {got}; want {n_dev} on {n_dev} "
                    f"with {want}")
        line["shards"] = {"devices": n_dev, "rows_per_device": per,
                          "blocks_per_device": -(-per // BLOCK)}

    t0 = time.perf_counter()
    lowered = step.lower(state, *data)
    hits_before = cache.hits
    compiled = lowered.compile()
    line["compile_s"] = time.perf_counter() - t0  # tracing included
    line["compile_cache_hit"] = cache.hits > hits_before  # this program's
    line["cache_requests"], line["cache_hits"] = cache.requests, cache.hits

    margins = []
    run_s = ckpt_s = 0.0
    for t in range(version, rounds):
        t0 = time.perf_counter()
        state = compiled(state, *data)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        if t == version:
            line["first_round_done_at"] = time.time()
        forest_host = tuple(np.asarray(a) for a in state.forest)
        margin_host = np.asarray(state.margin)
        rabit.checkpoint(forest_host, margin_host)
        t2 = time.perf_counter()
        run_s += t1 - t0
        ckpt_s += t2 - t1
        if rabit.version_number() != t + 1:
            raise SystemExit(f"version {rabit.version_number()} after "
                             f"commit {t + 1}")
        if save_margins:
            margins.append(margin_host)
        if life == 0 and kill_after == t + 1:
            # Preemption: no exception, no exit handler, no finalize.
            line.update(run_s=run_s, checkpoint_s=ckpt_s,
                        killed_after_commit=kill_after,
                        killed_at=time.time())
            _append(out / f"{tag}.jsonl", line)
            os.kill(os.getpid(), signal.SIGKILL)
    line.update(run_s=run_s, checkpoint_s=ckpt_s,
                rounds_this_life=rounds - version,
                engine_hops=hops[0],
                forest_sha256=forest_digest(forest_host))
    np.savez(out / f"{tag}_forest.npz", feature=forest_host[0],
             threshold=forest_host[1], leaf=forest_host[2])
    if save_margins:
        np.save(out / f"{tag}_margins.npy", np.stack(margins))

    if mode == "dp":
        line["compared_with"] = "the same rounds on one of the devices"
        line["compare"] = _one_device_rounds(
            cfg, xb, y, rounds, rehearse, devs[0], forest_host)

    rabit.finalize()
    line["wall_s"] = time.time() - t_start
    _append(out / f"{tag}.jsonl", line)
    return 0


def _one_device_rounds(cfg, xb, y, rounds, rehearse, dev,
                       sharded_forest) -> dict:
    """The reference of the sharded phase: the same rounds with the whole
    data on one device, in this process, after the sharded run."""
    import jax

    from rabit_tpu.models import gbdt

    step = jax.jit(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=rehearse))
    state = jax.device_put(gbdt.init_state(cfg, len(y)), dev)
    data = (jax.device_put(block_host(xb), dev), jax.device_put(y, dev))
    t0 = time.perf_counter()
    compiled = step.lower(state, *data).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(rounds):
        state = compiled(state, *data)
    jax.block_until_ready(state)
    run_s = time.perf_counter() - t0
    one = tuple(np.asarray(a) for a in state.forest)
    return {"compile_s": compile_s, "run_s": run_s,
            **compare_forests(sharded_forest, one)}


#: Leaves of two runs whose histograms were summed in another order (or by
#: another kernel) agree to float32 round-off of the sums they come from.
LEAF_RTOL, LEAF_ATOL = 1e-3, 1e-4


def compare_forests(a, b) -> dict:
    """Equal splits, leaves within tolerance; ``a``/``b`` are (feature,
    threshold, leaf) over the same number of trees."""
    splits_equal = bool(np.array_equal(a[0], b[0])
                        and np.array_equal(a[1], b[1]))
    diff = np.abs(np.asarray(a[2], np.float64) - np.asarray(b[2], np.float64))
    bound = LEAF_ATOL + LEAF_RTOL * np.abs(np.asarray(b[2], np.float64))
    leaves_ok = bool(np.all(diff <= bound))
    return {"splits_equal": splits_equal,
            "max_leaf_diff": float(diff.max()),
            "leaf_tolerance": {"rtol": LEAF_RTOL, "atol": LEAF_ATOL},
            "ok": splits_equal and leaves_ok}


def _append(path: Path, line: dict) -> None:
    with open(path, "a") as f:
        f.write(json.dumps(line) + "\n")
        f.flush()
        os.fsync(f.fileno())


if __name__ == "__main__":
    sys.exit(main())
