"""The benchmark's device process: one training job with recovery armed.

``run.py`` starts it through ``python -m rabit_tpu.tracker.launcher -n 1``;
it is the only process of a run that touches JAX.  One life of it is what
a user's trainer is (``guide/hybrid_gbdt.py`` is the model): ``init``,
``load_checkpoint``, then per round one jitted round of
``rabit_tpu.models.gbdt`` fenced by ``block_until_ready`` and one
``rabit_tpu.checkpoint(forest, margin)``.  A round counts when its commit
has returned.

Everything that differs between cells arrives as data in ``spec=<file>``:
the configuration's sizes with what it names (its data's generator, its
reference, its program's switches: ``harness/deployment.py``), the traffic's
loop parameters, the seed, the window's length.  The first ``check_rounds``
rounds run in set-up through the window's own call on the window's own
state, and what they produced is what ``run.py`` compares with the plain
reference; the numbers noted after each are the reference's own
``first_numbers``.  The window's clock, the per-round stamps, the counters
and (with ``trace``) the profiler's trace reduced to a table go to
``<out>/life<k>.json``; this file decides nothing about a metric or about
``correct``.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import signal
import sys
import time
from pathlib import Path

T_MAIN = time.time()  # the first line this life runs after the imports above

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

from harness import data as bdata, deployment  # noqa: E402

#: a restarted life reads the first life's window from here
WINDOW_FILE = "window.json"


def getarg(name: str) -> str:
    for a in reversed(sys.argv[1:]):
        if a.startswith(name + "="):
            return a.split("=", 1)[1]
    raise SystemExit(f"worker: no {name}= argument")


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def write_json(path: Path, obj: dict) -> None:
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class Compilations:
    """Compile requests, persistent-cache hits and backend compiles, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = self.backend_compiles = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "hits": self.hits,
                "backend_compiles": self.backend_compiles}


def main() -> int:
    spec = json.loads(Path(getarg("spec")).read_text())
    out = Path(spec["out"])
    cfg_in, traffic = spec["config"], spec["traffic"]
    rehearse = spec.get("rehearse") or {}
    life = int(os.environ.get("DMLC_NUM_ATTEMPT", "0"))
    stamps = {"main": T_MAIN}

    from rabit_tpu._platform import enable_persistent_cache

    enable_persistent_cache()  # before the first compile, in every life
    import jax
    import jax.numpy as jnp

    import rabit_tpu as rabit
    from rabit_tpu.models import gbdt

    if rehearse:
        # a tiny CPU compile finishes under the cache's one-second floor
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    stamps["devices"] = time.time()
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want or device["count"] != spec["chips"]:
        print(f"benchmark worker: JAX came up on {device}, the cell needs "
              f"{spec['chips']} x {want}; refusing to run",
              file=sys.stderr, flush=True)
        return 3
    compilations = Compilations()

    rows = cfg_in["rows"]
    block = cfg_in["program"]["block_rows"]
    codes, y = bdata.draw(cfg_in, spec["seed"])
    xb = codes.astype(np.int32)
    del codes
    stamps["data"] = time.time()
    cfg = deployment.gbdt_config(cfg_in, gbdt.GBDTConfig)
    reference = deployment.reference_of(cfg_in)
    interpret = bool(rehearse.get("interpret"))
    fault = rehearse.get("fault")
    if fault:
        import importlib.util   # tests only: see tests/faults.py

        fspec = importlib.util.spec_from_file_location(
            "bench_faults", HERE / "tests" / "faults.py")
        faults = importlib.util.module_from_spec(fspec)
        fspec.loader.exec_module(faults)
        faults.before_trace(fault, gbdt)

    hop_s = [0.0, 0]
    kind = traffic["round"]
    place = jnp.asarray     # one chip; the sharded round places by row
    if kind == "fused":
        data = (place(bdata.block_host(xb, block)), place(y))
        step = jax.jit(functools.partial(
            gbdt.train_round_fused, cfg=cfg, interpret=interpret))
    elif kind == "hybrid":
        def engine_hop(a: np.ndarray) -> np.ndarray:
            t = time.time()
            with jax.profiler.TraceAnnotation("engine_hop"):
                r = rabit.allreduce(np.asarray(a, np.float32), rabit.SUM)
            hop_s[0] += time.time() - t
            hop_s[1] += 1
            return r

        data = (place(xb), place(y))
        # interpret: the rehearsal runs the chip's program, the fused kernels
        step = jax.jit(functools.partial(
            gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=engine_hop,
            interpret=interpret))
    elif kind == "dp_fused":
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
            np.concatenate([bdata.block_host(xb[i * per:(i + 1) * per], block)
                            for i in range(n_dev)]),
            NamedSharding(mesh, P("dp", None, None)))
        data = (xb3, place(y))
        sspec = gbdt.TrainState(
            forest=gbdt.Forest(*(P() for _ in gbdt.Forest._fields)),
            margin=P("dp"), round=P())
        step = jax.jit(jax.shard_map(
            functools.partial(gbdt.train_round_dp_fused, cfg=cfg,
                              interpret=interpret),
            mesh=mesh, in_specs=(sspec, P("dp", None, None), P("dp")),
            out_specs=sspec, check_vma=False))
        # code that has only met a virtual CPU mesh may put everything on
        # the first device: check the placement before training on it
        shards = xb3.addressable_shards
        if (len({s.device for s in shards}) != n_dev
                or {s.data.shape[0] for s in shards} != {-(-per // block)}):
            raise SystemExit("xb3 is not one equal shard a device")
    else:
        raise SystemExit(f"unknown round {kind!r}")
    del xb
    jax.block_until_ready(data)
    stamps["placed"] = time.time()

    overrides = {}
    if traffic.get("spill"):
        overrides["rabit_checkpoint_dir"] = str(out / "ckpt")
    rabit.init(**overrides)
    version, forest_np, margin_np = rabit.load_checkpoint(with_local=True)
    restored = None
    if fault:
        margin_np = faults.after_restore(fault, margin_np)
    if version == 0:
        state = gbdt.init_state(cfg, rows)
        state = state._replace(margin=place(np.asarray(state.margin)))
    else:
        restored = {"version": version,
                    "state_digest": digest(*forest_np, margin_np)}
        state = gbdt.TrainState(
            forest=gbdt.Forest(*(jnp.asarray(a) for a in forest_np)),
            margin=place(margin_np),
            round=jnp.asarray(version, jnp.int32))
    jax.block_until_ready(state)
    stamps["restored"] = time.time()

    before = compilations.snapshot()
    compiled = step.lower(state, *data).compile()
    after = compilations.snapshot()
    analysis = compiled.memory_analysis()
    program_bytes = {k: int(getattr(analysis, k + "_in_bytes", 0) or 0)
                     for k in ("argument_size", "output_size", "temp_size",
                               "alias_size", "generated_code_size",
                               "peak_memory")} if analysis else {}
    if fault:
        compiled = faults.wrap_step(fault, compiled)
    stamps["compiled"] = time.time()
    compile_info = {
        "seconds": stamps["compiled"] - stamps["restored"],  # tracing included
        "hit": after["hits"] > before["hits"],
        "backend_compiles": after["backend_compiles"] - before["backend_compiles"]}

    rounds = []        # [start, fenced, copied, committed] a round, time.time()
    last = {}

    def one_round():
        """The call the window drives — and set-up, for the first rounds."""
        nonlocal state, version
        t0 = time.time()
        with jax.profiler.TraceAnnotation("round"):
            state = compiled(state, *data)
            jax.block_until_ready(state)
        t1 = time.time()
        with jax.profiler.TraceAnnotation("margin_d2h"):
            forest_host = tuple(np.asarray(a) for a in state.forest)
            margin_host = np.asarray(state.margin)
        t2 = time.time()
        with jax.profiler.TraceAnnotation("checkpoint"):
            rabit.checkpoint(forest_host, margin_host)
        t3 = time.time()
        version += 1
        if rabit.version_number() != version:
            raise SystemExit(f"version {rabit.version_number()} after "
                             f"commit {version}")
        last.update(forest=forest_host, margin=margin_host)
        return [t0, t1, t2, t3]

    first = None
    window_path = out / WINDOW_FILE
    if not window_path.exists():
        # Set-up's last part: the first rounds, on the object the window gets.
        first = {}
        for _ in range(traffic["check_rounds"]):
            one_round()
            for name, v in reference.first_numbers(last["margin"], y).items():
                first.setdefault(name, []).append(v)
        k = traffic["check_rounds"]
        first["forest"] = [np.asarray(a[:k]).tolist() for a in last["forest"]]
        stamps["warm"] = time.time()
        window = {"start": time.time(), "seconds": spec["seconds"]}
        write_json(window_path, window)
    else:
        window = json.loads(window_path.read_text())
        # this life's first round is warm-up and window at once
        stamps["warm"] = time.time()
    t_end = window["start"] + window["seconds"]
    in_window = compilations.snapshot()

    kill_after = traffic.get("kill_after_commit") if life == 0 else None
    tracing = bool(spec["trace"]) and not kill_after   # a killed life's trace dies with it
    trace_dir = out / f"trace{life}"
    trace_from = traffic["trace_skip"]
    trace_to = trace_from + traffic["trace_rounds"]
    trace_on = False
    traced = None
    while time.time() < t_end and version < cfg.n_trees:
        if tracing and len(rounds) == trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            trace_on = True
        rounds.append(one_round())
        if "first_fenced" not in stamps:
            stamps["first_fenced"] = rounds[-1][1]
        if trace_on and len(rounds) == trace_to:
            jax.profiler.stop_trace()
            trace_on = False
            traced = [trace_from, trace_to]
        if kill_after and version == kill_after:
            # Preemption: no exception, no exit handler, no finalize.
            write_json(out / f"life{life}.json", {
                "life": life, "device": device, "stamps": stamps,
                "compile": compile_info, "rounds": rounds, "first": first,
                "window": window, "hops": hop_s,
                "killed": {
                    "at": time.time(), "after_commit": version,
                    "state_digest": digest(*last["forest"], last["margin"]),
                    "trees_digest": digest(*(a[:version]
                                             for a in last["forest"]))}})
            os.kill(os.getpid(), signal.SIGKILL)
    if trace_on:  # the window closed inside the traced rounds
        jax.profiler.stop_trace()
        traced = [trace_from, len(rounds)]
    window_compiles = {k: v - in_window[k]
                       for k, v in compilations.snapshot().items()}
    stats = [d.memory_stats() or {} for d in devs]
    # live arrays plus what the runtime reserved for executables' temporaries
    peak = max(s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
               for s in stats)
    line = {
        "life": life, "device": device, "stamps": stamps,
        "compile": compile_info, "window_compiles": window_compiles,
        "rounds": rounds, "first": first, "window": window, "hops": hop_s,
        "restored": restored, "version": version, "memory_peak_bytes": peak,
        "traced_rounds": traced, "program_bytes": program_bytes,
        "memory_stats": stats[0],
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }
    if restored and last:
        # the trees the first life committed, as this life ends with them
        k = restored["version"]
        line["trees_digest_at_restore"] = digest(*(a[:k] for a in last["forest"]))
    if traced:
        from harness import xplane

        # the raw trace stays under <out> until the next run clears it
        line["trace"] = xplane.reduce_dir(trace_dir, rehearse.get("trace_rules"))
    rabit.finalize()
    write_json(out / f"life{life}.json", line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
