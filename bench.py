"""Benchmark: XGBoost-style histogram boosting rounds/sec on TPU.

The driving workload from BASELINE.md ("XGBoost hist rounds/sec ...
Higgs-1M") on a Higgs-shaped synthetic dataset: 1M rows x 28 features,
256 bins, depth-6 trees.  The TPU number is the full jitted train_round
(histogram build + split search + row routing + leaf fit); the baseline is
the same algorithm on the host CPU with numpy bincount histograms — the
CPU hist-method reference the targets table names.

Driver contract: prints ONE JSON line on stdout
    {"metric", "value", "unit", "vs_baseline"}
measured on a TPU, or exits non-zero and prints no record:

  * the parent process NEVER imports jax (a parent that has touched JAX
    holds the chip).  The device benchmark runs in ONE child process
    (``bench.py --device-worker``) under one timeout, and goes first;
  * the child refuses to run on anything but a TPU, and a raced program
    that fails on the device fails the child.  There is no CPU or numpy
    stand-in for the device number;
  * the numpy baseline is measured in-parent on a 1/8 row subsample and
    scaled (bincount is linear in rows);
  * the CPU riders (counts and control-plane latencies, never device
    metrics) run only after the device child has finished on a TPU;
  * progress lines go to stderr, flushed, so partial runs are diagnosable.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

N_ROWS = 1_000_000
N_FEATURES = 28
N_BINS = 256
DEPTH = 6
TPU_ROUNDS = 8
LAM = 1.0
LR = 0.3

T_START = time.time()
TPU_CHILD_TIMEOUT = 900.0  # the child compiles + times THREE configs
                           # (bf16, int8, winner-with-fused-final); the
                           # whole round at this row count compiles in
                           # about two minutes cold (ROADMAP S3)
# Codec ablation (ISSUE 5): one CPU child times the same hist rounds per
# wire codec (f32 vs bf16x2 vs i8x2 vs i8) and reports rounds/sec +
# allreduce raw/wire bytes — the compression trajectory.
# RABIT_BENCH_CODEC_ABLATION=0 skips it.
CODEC_ABLATION = os.environ.get("RABIT_BENCH_CODEC_ABLATION", "1") != "0"
CODEC_ROWS = int(os.environ.get("RABIT_BENCH_CODEC_ROWS", "150000"))
CODEC_ROUNDS = 2
CODEC_CHILD_TIMEOUT = 210.0
CODECS_RACED = ("identity", "bf16x2", "i8x2", "i8")
# Elastic membership bench (ISSUE 6): one CPU child runs the seeded
# promote/shrink/grow scenarios (tools/recovery_bench.py --elastic) and
# reports the spare-promotion-latency vs shrink-wave-latency curve from
# structured tracker events.  Cheap (~15s, no jax import);
# RABIT_BENCH_ELASTIC=0 skips it.
ELASTIC_BENCH = os.environ.get("RABIT_BENCH_ELASTIC", "1") != "0"
ELASTIC_CHILD_TIMEOUT = 120.0
# Schedule ablation (ISSUE 7): the planner's cost-model curve (pure, ~0s)
# plus the live chaos slow_link repair A/B (tools/consensus_bench.py) in
# a CPU child — the topology/degraded-link trajectory.
# RABIT_BENCH_SCHED=0 skips it.
SCHED_BENCH = os.environ.get("RABIT_BENCH_SCHED", "1") != "0"
SCHED_CHILD_TIMEOUT = 120.0
# Quorum ablation (ISSUE 8): rounds/sec under an injected 8x compute
# straggler, quorum off vs on vs on+i8 (tools/consensus_bench.py
# --quorum-ablation; doc/partial_allreduce.md) in a CPU child — the
# straggler-tolerance trajectory.  ~10s; RABIT_BENCH_QUORUM=0 skips it.
QUORUM_BENCH = os.environ.get("RABIT_BENCH_QUORUM", "1") != "0"
QUORUM_CHILD_TIMEOUT = 180.0
# Control-plane scale sweep (ISSUE 9): simulated-world bootstrap/
# recovery/liveness load against the thread-per-connection, reactor,
# and relayed serving paths (tools/scale_sweep.py; doc/scaling.md) in a
# CPU child.  The driver runs the SMALL worlds (the full 4096-8192 curve
# is the durable RESULTS/scale_sweep.jsonl anchor); RABIT_BENCH_SCALE=0
# skips it.
SCALE_BENCH = os.environ.get("RABIT_BENCH_SCALE", "1") != "0"
SCALE_CHILD_TIMEOUT = 240.0
SCALE_WORLDS = os.environ.get("RABIT_BENCH_SCALE_WORLDS", "512 1024")
# HA failover (ISSUE 10): primary-tracker kill -> standby takeover /
# first post-failover wave latency, direct and relayed
# (tools/recovery_bench.py --failover; doc/ha.md) in a CPU child;
# RABIT_BENCH_HA=0 skips it.
HA_BENCH = os.environ.get("RABIT_BENCH_HA", "1") != "0"
HA_CHILD_TIMEOUT = 180.0
# Fused-vs-host A/B (ISSUE 11): the in-XLA fused encode->ppermute->
# decode-fold graph (rabit_tpu/engine/fused.py) against the numpy host
# transport, per codec, on a virtual CPU mesh in a child — the "does the
# fusion pay for itself off-TPU" arm (gate: fused no slower than host at
# >=1 MiB payloads).  RABIT_BENCH_FUSED=0 skips it.
FUSED_BENCH = os.environ.get("RABIT_BENCH_FUSED", "1") != "0"
# Multi-tenant service bench (ISSUE 12): N concurrent jobs through one
# CollectiveService + shared relay tier — jobs/sec, p99 bootstrap
# latency, noisy-neighbor isolation under a straggler storm, pooled-
# worker fit throughput (tools/service_bench.py --smoke;
# doc/service.md) in a CPU child; RABIT_BENCH_SERVICE=0 skips it.
SERVICE_BENCH = os.environ.get("RABIT_BENCH_SERVICE", "1") != "0"
SERVICE_CHILD_TIMEOUT = 180.0
# Live telemetry plane (ISSUE 16): one CMD_OBS scrape taken MID-RUN of a
# real 2-rank elastic job (``--obs-worker``; doc/observability.md "Live
# telemetry plane") — scrape latency, fold/link evidence, and the
# streamed-delta round trip, so every driver record carries live-plane
# evidence.  ~5s; RABIT_BENCH_OBS=0 skips it.
OBS_BENCH = os.environ.get("RABIT_BENCH_OBS", "1") != "0"
OBS_CHILD_TIMEOUT = 90.0
# Model-delivery plane (ISSUE 20): the snapshot-CDN smoke
# (tools/delivery_bench.py --smoke; doc/delivery.md) — a live writer
# against a simulated subscriber swarm through relays (propagation
# p50/p99, writer-cadence ratio), the cross-tenant dedup uplink row, and
# a mid-stream tracker failover — in a CPU child;
# RABIT_BENCH_DELIVERY=0 skips it.
DELIVERY_BENCH = os.environ.get("RABIT_BENCH_DELIVERY", "1") != "0"
DELIVERY_CHILD_TIMEOUT = 180.0
# Regression sentinel (ISSUE 18): every driver record carries the
# high-water verdict over the existing BENCH_*/RESULTS trajectory
# (tools/bench_sentinel.py), so a silent perf erasure is a flagged
# regression in the new record itself, not something a human diffs by
# hand.  Pure file reads, no wall cost; RABIT_BENCH_SENTINEL=0 skips it.
SENTINEL_BENCH = os.environ.get("RABIT_BENCH_SENTINEL", "1") != "0"
FUSED_CHILD_TIMEOUT = 180.0
FUSED_WORLD = 4
FUSED_ELEMS = 1 << 18  # 1 MiB of f32 — the acceptance bar's payload floor
FUSED_CODECS = ("i8", "bf16x2")


def log(msg):
    print(f"[bench +{time.time() - T_START:5.1f}s] {msg}", file=sys.stderr, flush=True)


def make_data(n_rows, seed=0):
    rng = np.random.RandomState(seed)
    xb = rng.randint(0, N_BINS, size=(n_rows, N_FEATURES), dtype=np.int32)
    logits = (xb[:, 0] > 128).astype(np.float32) + 0.01 * xb[:, 1]
    y = (logits + rng.randn(n_rows) > 1.5).astype(np.float32)
    return xb, y


def cpu_round(xb, y, margin):
    """The same hist algorithm in numpy — one boosting round on the host."""
    n, F = xb.shape
    p = 1.0 / (1.0 + np.exp(-margin))
    g, h = p - y, p * (1 - p)
    node = np.zeros(n, np.int64)
    feat_col = np.arange(F, dtype=np.int64)[None, :]
    for d in range(DEPTH):
        n_nodes = 1 << d
        seg = (node[:, None] * F + feat_col) * N_BINS + xb
        seg = seg.reshape(-1)
        nseg = n_nodes * F * N_BINS
        hg = np.bincount(seg, weights=np.repeat(g, F), minlength=nseg).reshape(n_nodes, F, N_BINS)
        hh = np.bincount(seg, weights=np.repeat(h, F), minlength=nseg).reshape(n_nodes, F, N_BINS)
        GL, HL = np.cumsum(hg, -1), np.cumsum(hh, -1)
        G, H = GL[..., -1:], HL[..., -1:]
        score = lambda a, b: a * a / (b + LAM)
        gain = score(GL, HL) + score(G - GL, H - HL) - score(G, H)
        flat = gain.reshape(n_nodes, -1)
        best = np.argmax(flat, -1)
        feat, thr = best // N_BINS, best % N_BINS
        fsel = feat[node]
        xv = xb[np.arange(n), fsel]
        node = node * 2 + (xv > thr[node])
    leaf_g = np.bincount(node, weights=g, minlength=1 << DEPTH)
    leaf_h = np.bincount(node, weights=h, minlength=1 << DEPTH)
    leaf = -LR * leaf_g / (leaf_h + LAM)
    return margin + leaf[node]


def bench_cpu_scaled(n_rows):
    """Per-round numpy time at n_rows, measured on a 1/8 subsample.

    cpu_round is dominated by the O(n*F) segment build + bincount, linear
    in rows, so subsample-and-scale is a fair estimate and ~8x cheaper
    than the full-size run that sank round 1's wall clock.
    """
    sub = max(n_rows // 8, 1)
    xb, y = make_data(sub, seed=1)
    margin = np.zeros(sub, np.float32)
    margin = cpu_round(xb, y, margin)  # warm caches / allocators
    t0 = time.perf_counter()
    margin = cpu_round(xb, y, margin)
    per_round_sub = time.perf_counter() - t0
    return per_round_sub * (n_rows / sub)


# --------------------------------------------------------------------------
# Device-worker child: the only code path that touches jax.
# --------------------------------------------------------------------------

def device_worker(n_rows, n_rounds):
    import functools

    from rabit_tpu._platform import enable_persistent_cache

    # The three raced configs each compile for about two minutes cold.
    enable_persistent_cache()

    import jax
    import jax.numpy as jnp

    from rabit_tpu.models import gbdt
    from rabit_tpu.ops import boost

    devs = jax.devices()
    plat = devs[0].platform
    log(f"worker: backend up: {plat} ({devs[0].device_kind}) x{len(devs)}")
    if plat != "tpu":
        # No stand-in: a number from another backend is never written
        # under the TPU metric's name.
        raise SystemExit(f"bench device worker: JAX came up on {plat!r}, "
                         "not a TPU")
    xb, y = make_data(n_rows)
    base_cfg = gbdt.GBDTConfig(
        n_features=N_FEATURES, n_trees=n_rounds + 2, depth=DEPTH,
        n_bins=N_BINS, learning_rate=LR, reg_lambda=LAM,
    )
    xb3, _ = boost.block_rows(jnp.asarray(xb))
    y_d = jnp.asarray(y)

    def time_mode(cfg, mxu_label):
        step = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg),
                       donate_argnums=0)
        state = gbdt.init_state(cfg, n_rows)
        log(f"worker: compiling train_round_fused (mxu_i8={cfg.mxu_i8}, "
            f"fused_final={cfg.fused_final}) ...")
        state = jax.block_until_ready(step(state, xb3, y_d))  # compile + warm
        log(f"worker: compiled; timing {n_rounds} rounds")
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            state = step(state, xb3, y_d)
        jax.block_until_ready(state)
        dt = (time.perf_counter() - t0) / n_rounds
        log(f"worker: {mxu_label}: {dt * 1e3:.1f} ms/round")
        return dt

    # Three raced programs; any of them failing on the device fails the
    # child.  The int8-rate contraction (GBDTConfig.mxu_i8) usually wins on
    # the MXU-issue-bound level passes; the third race is the final leaf
    # pass (routing kernel + XLA leaf gather, the default, against the
    # fused route+margin kernel, GBDTConfig.fused_final) on the winning
    # MXU mode.
    i8_cfg = base_cfg._replace(mxu_i8=True)
    dt, dt_i8 = time_mode(base_cfg, "bf16"), time_mode(i8_cfg, "i8")
    best_cfg, dt_best = (i8_cfg, dt_i8) if dt_i8 < dt else (base_cfg, dt)
    ff_cfg = best_cfg._replace(fused_final=True)
    dt_ff = time_mode(ff_cfg, "fused-final")
    if dt_ff < dt_best:
        best_cfg, dt_best = ff_cfg, dt_ff
    rec = {"device_time": dt_best, "platform": plat,
           "device_kind": devs[0].device_kind, "device_count": len(devs),
           "mxu": "i8" if best_cfg.mxu_i8 else "bf16"}
    if best_cfg.fused_final:
        rec["final"] = "fused"
    print(json.dumps(rec), flush=True)


def codec_worker(n_rows, n_rounds):
    """Child (forced CPU): time the hook-based hist boosting round once
    per wire codec and print one JSON line per codec.  All codecs share
    one process so the eager compute path is identical; only the
    allreduce codec changes between runs."""
    from rabit_tpu._platform import force_cpu_platform

    force_cpu_platform(1)

    import jax.numpy as jnp

    import rabit_tpu as rt
    from rabit_tpu.models import gbdt

    xb, y = make_data(n_rows)
    log(f"codec worker: {n_rows} rows x {N_FEATURES} feats, "
        f"{n_rounds} timed rounds per codec")
    rt.init([], rabit_compress_min_bytes=1)
    cfg = gbdt.GBDTConfig(
        n_features=N_FEATURES, n_trees=n_rounds + 1, depth=DEPTH,
        n_bins=N_BINS, learning_rate=LR, reg_lambda=LAM,
    )
    xb_d, y_d = jnp.asarray(xb), jnp.asarray(y)
    f32_line = None
    for codec in CODECS_RACED:
        arg = None if codec == "identity" else codec

        def hook(hist):
            return jnp.asarray(rt.allreduce(np.asarray(hist), rt.SUM,
                                            codec=arg))

        hist_fn = lambda xb_, g, h, node, nn, nb: hook(
            gbdt.node_histograms(xb_, g, h, node, nn, nb))
        state = gbdt.init_state(cfg, n_rows)
        state = gbdt.train_round(state, xb_d, y_d, cfg, hist_fn, hook)  # warm
        rt.reset_collective_stats()
        t0 = time.perf_counter()
        for _ in range(n_rounds):
            state = gbdt.train_round(state, xb_d, y_d, cfg, hist_fn, hook)
        np.asarray(state.margin)  # fence
        dt = (time.perf_counter() - t0) / n_rounds
        reg = rt.collective_stats().registry.snapshot()
        raw = reg["ops"]["allreduce"]["nbytes"]
        wire = reg["counters"].get("compress_wire_bytes_total", 0) or raw
        acc = float(np.mean((np.asarray(state.margin) > 0) == y))
        line = {
            "codec": "f32" if codec == "identity" else codec,
            "rounds_per_sec": round(1.0 / dt, 4),
            "allreduce_raw_bytes": int(raw),
            "allreduce_wire_bytes": int(wire),
            "accuracy": round(acc, 5),
        }
        if f32_line is None:
            f32_line = line
        line["bytes_reduction_vs_f32"] = round(
            f32_line["allreduce_wire_bytes"] / wire, 3)
        line["rounds_per_sec_vs_f32"] = round(
            line["rounds_per_sec"] / f32_line["rounds_per_sec"], 3)
        log(f"codec {line['codec']}: {line['rounds_per_sec']:.3f} rounds/s, "
            f"{raw}->{wire} B ({line['bytes_reduction_vs_f32']}x)")
        print(json.dumps(line), flush=True)
    rt.finalize()


def run_codec_ablation(timeout=CODEC_CHILD_TIMEOUT):
    """Run the codec child; returns the per-codec JSON lines (possibly
    partial on timeout — each line lands the moment it is measured)."""
    cmd = [sys.executable, os.path.abspath(__file__), "--codec-worker",
           str(CODEC_ROWS), str(CODEC_ROUNDS)]
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        stdout, stderr, rc = r.stdout, r.stderr, r.returncode
    except subprocess.TimeoutExpired as te:
        to_text = lambda v: (v.decode(errors="replace")
                             if isinstance(v, bytes) else (v or ""))
        stdout, stderr, rc = to_text(te.stdout), to_text(te.stderr), None
        log(f"codec ablation child timed out after {timeout:.0f}s; "
            "keeping the lines it already measured")
    for line in stderr.splitlines():
        print(line, file=sys.stderr, flush=True)
    if rc not in (0, None):
        tail = stderr.strip().splitlines()[-3:]
        log(f"codec ablation child rc={rc}: {' | '.join(tail)}")
    lines = []
    for line in stdout.strip().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "codec" in rec:
            lines.append(rec)
    return lines


def run_elastic_bench(timeout=ELASTIC_CHILD_TIMEOUT):
    """Run the elastic-membership scenarios (tools/recovery_bench.py
    --elastic) in a child; returns the per-world JSON lines (possibly
    empty on timeout/failure — the elastic curve must never cost the main
    metric its line)."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "recovery_bench.py"),
           "--elastic", "2", "4"]
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        stdout, rc = r.stdout, r.returncode
    except subprocess.TimeoutExpired as te:
        stdout = (te.stdout.decode(errors="replace")
                  if isinstance(te.stdout, bytes) else (te.stdout or ""))
        rc = None
        log(f"elastic bench child timed out after {timeout:.0f}s; "
            "keeping the lines it already measured")
    if rc not in (0, None):
        log(f"elastic bench child rc={rc}")
    lines = []
    for line in stdout.strip().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and rec.get("mode") == "elastic":
            lines.append(rec)
    return lines


def run_sched_bench(timeout=SCHED_CHILD_TIMEOUT):
    """Schedule ablation lines: the in-process planner cost-model curve
    (pure, instant) plus the live slow_link repair A/B in a child
    (threads + sleeps; a child so a hung run cannot stall the driver).
    Returns the JSON records, possibly without the e2e line on
    timeout/failure."""
    from tools.consensus_bench import schedule_ablation

    lines = list(schedule_ablation())
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "consensus_bench.py"),
           "--slow-link-e2e"]
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "slow_link_e2e":
                    lines.append(rec)
        else:
            log(f"slow_link e2e child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"slow_link e2e child timed out after {timeout:.0f}s")
    return lines


def run_quorum_bench(timeout=QUORUM_CHILD_TIMEOUT):
    """Quorum ablation record (tools/consensus_bench.py
    --quorum-ablation) in a child: live elastic workers + an injected
    compute straggler (threads + sleeps; a child so a hung run cannot
    stall the driver).  Returns the record list, empty on
    timeout/failure — the curve must never cost the main metric."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "consensus_bench.py"),
           "--quorum-ablation"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "quorum_ablation":
                    lines.append(rec)
        else:
            log(f"quorum ablation child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"quorum ablation child timed out after {timeout:.0f}s")
    return lines


def run_scale_bench(timeout=SCALE_CHILD_TIMEOUT):
    """Scale-sweep records (tools/consensus_bench.py --scale-sweep) in a
    child: simulated worlds, no real workers (sockets + one selector
    loop; a child so a hung arm cannot stall the driver).  Returns the
    record list, empty on timeout/failure."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "consensus_bench.py"),
           "--scale-sweep", "--scale-worlds", *SCALE_WORLDS.split()]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "scale_sweep":
                    lines.append(rec)
        else:
            log(f"scale sweep child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"scale sweep child timed out after {timeout:.0f}s")
    return lines


def run_ha_bench(timeout=HA_CHILD_TIMEOUT):
    """HA failover records (tools/recovery_bench.py --failover) in a
    child: in-thread elastic workers + a warm standby + an abrupt
    primary kill (threads + sleeps; a child so a hung run cannot
    stall the driver).  Returns the record list, empty on
    timeout/failure."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "recovery_bench.py"),
           "--failover", "2", "4"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("mode") == "ha_failover":
                    lines.append(rec)
        else:
            log(f"ha failover child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"ha failover child timed out after {timeout:.0f}s")
    return lines


def run_service_bench(timeout=SERVICE_CHILD_TIMEOUT):
    """Multi-tenant service records (tools/service_bench.py --smoke) in
    a child: one CollectiveService, 8 concurrent jobs, a shared relay
    tier, a straggler-stormed victim job, and a pooled-worker arm
    (threads + real sockets; a child so a hung run cannot stall the
    driver).  Returns the record list, empty on timeout/failure."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "service_bench.py"), "--smoke",
           "--observed"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "service":
                    lines.append(rec)
        else:
            log(f"service bench child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"service bench child timed out after {timeout:.0f}s")
    return lines


def run_delivery_bench(timeout=DELIVERY_CHILD_TIMEOUT):
    """Model-delivery records (tools/delivery_bench.py --smoke) in a
    child: a live writer publishing snapshots against a selector-driven
    subscriber swarm through two relays, the tenants-x-identical-bytes
    dedup uplink row, and a mid-stream tracker failover (threads + real
    sockets; a child so a hung run cannot stall the driver).  Returns
    the record list, empty on timeout/failure."""
    cmd = [sys.executable,
           os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "delivery_bench.py"), "--smoke"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "delivery":
                    lines.append(rec)
        else:
            log(f"delivery bench child rc={r.returncode}")
    except subprocess.TimeoutExpired:
        log(f"delivery bench child timed out after {timeout:.0f}s")
    return lines


def obs_worker():
    """Child (no jax): live telemetry plane smoke.  A real 2-rank elastic
    run against an in-thread tracker; while the round is still running the
    driver takes ONE ``CMD_OBS`` scrape (rabit_tpu.obs.top.scrape), after
    shipping the global registry's streamed-metric delta window the
    workers produced so far — the full worker->tracker->scrape loop, live,
    not post-hoc.  Prints one ``{"bench": "live_metrics"}`` JSON line."""
    from rabit_tpu.elastic.client import ElasticWorker
    from rabit_tpu.obs import stream as obs_stream
    from rabit_tpu.obs.top import scrape
    from rabit_tpu.tracker import protocol as TP
    from rabit_tpu.tracker.tracker import Tracker

    # ~30 rounds x 50ms keeps the job alive for seconds: a finished plain
    # tracker stops serving, so the scrape must land genuinely mid-run.
    world, niter = 2, 30
    tracker = Tracker(world_size=world, quiet=True).start()
    src = obs_stream.DeltaSource()  # the run streams into the global registry
    results = {}

    def contribution(v, w, r):
        time.sleep(0.05)
        return np.full(8, v * (r + 1), np.int64)

    def run(i):
        w = ElasticWorker((tracker.host, tracker.port), str(i), contribution,
                          niter, deadline_sec=60.0, rpc_timeout=2.0,
                          wave_timeout=20.0)
        results[i] = w.run()

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(world)]
    for t in threads:
        t.start()
    time.sleep(0.5)  # mid-run: rounds are still in flight
    alive_at_scrape = sum(t.is_alive() for t in threads)
    delta = src.take()
    shipped = False
    if delta is not None:
        snap = {"schema": 1, "rank": 0, "task_id": "0", "counters": {},
                "histograms": {}, "delta": delta}
        try:
            shipped = TP.tracker_rpc(
                tracker.host, tracker.port, TP.CMD_METRICS, "0",
                message=json.dumps(snap), timeout=5.0, retries=1) == TP.ACK
        except (TP.TrackerUnreachable, ValueError):
            shipped = False
    t0 = time.perf_counter()
    doc = scrape(tracker.host, tracker.port)
    scrape_ms = (time.perf_counter() - t0) * 1e3
    for t in threads:
        t.join(timeout=90)
    completed = len(results) == world and all(
        getattr(r, "completed", False) for r in results.values())
    tracker.stop()
    job = doc.get("jobs", {}).get("", {})
    rolled = job.get("stream", {})
    line = {
        "bench": "live_metrics",
        "schema": doc.get("schema"),
        "scrape_ms": round(scrape_ms, 3),
        "workers_alive_at_scrape": alive_at_scrape,
        "world": job.get("world"),
        "epoch": job.get("epoch"),
        "delta_shipped": shipped,
        "n_folds": rolled.get("n_folds", 0),
        "links": len(rolled.get("links", [])),
        "wire_bytes": obs_stream.wire_bytes_by_codec(
            rolled.get("total", {"counters": {}})),
        "completed": completed,
    }
    log(f"live_metrics: scrape {scrape_ms:.1f} ms mid-run "
        f"({alive_at_scrape} workers live, {line['n_folds']} fold(s), "
        f"{line['links']} link(s))")
    print(json.dumps(line), flush=True)


def run_obs_bench(timeout=OBS_CHILD_TIMEOUT):
    """Live-telemetry scrape evidence (``--obs-worker``) in a child
    (threads + real sockets; a child so a hung run cannot stall the
    driver).  Returns the record list, empty on timeout/failure — the
    live-plane evidence must never cost the main metric its line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--obs-worker"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "live_metrics":
                    lines.append(rec)
        else:
            tail = (r.stderr or "").strip().splitlines()[-3:]
            log(f"live metrics child rc={r.returncode}: {' | '.join(tail)}")
    except subprocess.TimeoutExpired:
        log(f"live metrics child timed out after {timeout:.0f}s")
    return lines


def run_device_child(timeout=TPU_CHILD_TIMEOUT):
    """The one device process.  Returns its result line; raises
    SystemExit (non-zero) when it did not finish on a TPU."""
    cmd = [sys.executable, os.path.abspath(__file__), "--device-worker",
           str(N_ROWS), str(TPU_ROUNDS)]
    try:
        r = subprocess.run(
            cmd, timeout=timeout, stdout=subprocess.PIPE, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired:
        raise SystemExit(f"bench: device child timed out after {timeout:.0f}s")
    if r.returncode != 0:
        raise SystemExit(f"bench: device child exited {r.returncode}; "
                         "no TPU measurement, no record")
    res = None
    for line in r.stdout.strip().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict) and "device_time" in rec:
            res = rec
    if res is None or res.get("platform") != "tpu":
        raise SystemExit(f"bench: device child left no TPU result ({res})")
    return res


def fused_worker(world, n_elems, n_iters):
    """Child (forced CPU, virtual ``world``-device mesh): time the fused
    in-XLA allreduce graph against the numpy host transport per codec and
    print one JSON line per codec.  The host arm measures ONE rank's real
    compute cost (encode + W decodes + rank-order fold) over a loopback
    engine; the fused arm runs the whole jitted graph (all W ranks' work,
    parallelized over the device threads).  Each line also carries the
    bitwise-parity verdict against the closed-form reference fold."""
    from rabit_tpu._platform import force_cpu_platform

    force_cpu_platform(world)

    from rabit_tpu import compress
    from rabit_tpu.compress import transport
    from rabit_tpu.config import Config
    from rabit_tpu.engine import fused as F
    from rabit_tpu.engine.base import SUM

    class _Loopback:
        """Minimal engine stand-in: rank 0 of a W-world where every rank
        contributed the same bytes — per-rank host-path cost is exact."""

        def get_world_size(self):
            return world

        def allreduce(self, data, op, prepare_fun=None, cache_key=None):
            return data

        def allgather(self, data, cache_key=None):
            return np.tile(np.asarray(data), world)

    rng = np.random.RandomState(11)
    contribs = [(rng.randn(n_elems) * 20).astype(np.float32)
                for _ in range(world)]
    mesh = F.local_mesh(world)
    order = F.plan_ring_order(world, Config([]))
    garr = F.place_contributions(mesh, contribs)
    loop_eng = _Loopback()
    for codec_name in FUSED_CODECS:
        codec = compress.get_codec(codec_name)
        ref = transport.reference_allreduce(contribs, SUM, codec)
        fn = F.build_fused_allreduce(mesh, order, SUM, codec, n_elems)
        out = np.asarray(fn(garr))  # compile + warm
        fused_ok = bool(np.array_equal(out[0], ref))
        t0 = time.perf_counter()
        for _ in range(n_iters):
            np.asarray(fn(garr))
        fused_s = (time.perf_counter() - t0) / n_iters
        host = transport.host_allreduce(loop_eng, contribs[0], SUM, codec)
        host_ok = bool(np.array_equal(
            host, transport.reference_allreduce([contribs[0]] * world, SUM,
                                                codec)))
        t0 = time.perf_counter()
        for _ in range(n_iters):
            transport.host_allreduce(loop_eng, contribs[0], SUM, codec)
        host_s = (time.perf_counter() - t0) / n_iters
        line = {
            "bench": "fused_ab",
            "codec": codec_name,
            "world": world,
            "payload_bytes": int(4 * n_elems),
            "fused_s": round(fused_s, 6),
            "host_s": round(host_s, 6),
            "fused_vs_host": round(host_s / fused_s, 3),
            "fused_bitwise_ok": fused_ok,
            "host_bitwise_ok": host_ok,
        }
        log(f"fused A/B {codec_name}: fused {fused_s * 1e3:.2f} ms vs host "
            f"{host_s * 1e3:.2f} ms ({line['fused_vs_host']}x), "
            f"parity={'ok' if fused_ok else 'BROKEN'}")
        print(json.dumps(line), flush=True)


def run_fused_bench(timeout=FUSED_CHILD_TIMEOUT):
    """Fused-vs-host A/B lines (``--fused-worker``) in a child (it pins a
    virtual multi-device CPU platform, which must happen in a fresh
    process).  Returns the record list, empty on timeout/failure — the
    arm must never cost the main metric its line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--fused-worker",
           str(FUSED_WORLD), str(FUSED_ELEMS), "5"]
    lines = []
    try:
        r = subprocess.run(cmd, timeout=timeout, capture_output=True,
                           text=True)
        if r.returncode == 0:
            for line in r.stdout.strip().splitlines():
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec, dict) and rec.get("bench") == "fused_ab":
                    lines.append(rec)
        else:
            tail = (r.stderr or "").strip().splitlines()[-3:]
            log(f"fused A/B child rc={r.returncode}: {' | '.join(tail)}")
    except subprocess.TimeoutExpired:
        log(f"fused A/B child timed out after {timeout:.0f}s")
    return lines


def codec_pareto(codec_lines):
    """The allreduce-bytes x rounds/s frontier over the codec-ablation
    lines: one row per codec, ``on_frontier`` true when no other codec has
    both fewer wire bytes and at least the throughput (the wire/throughput
    trade-off as ONE record instead of two disjoint columns)."""
    rows = []
    for line in codec_lines:
        if "allreduce_wire_bytes" not in line or "rounds_per_sec" not in line:
            continue
        rows.append({
            "codec": line.get("codec", "?"),
            "allreduce_wire_bytes": int(line["allreduce_wire_bytes"]),
            "rounds_per_sec": float(line["rounds_per_sec"]),
        })
    for row in rows:
        row["on_frontier"] = not any(
            (o["allreduce_wire_bytes"] <= row["allreduce_wire_bytes"]
             and o["rounds_per_sec"] >= row["rounds_per_sec"]
             and (o["allreduce_wire_bytes"] < row["allreduce_wire_bytes"]
                  or o["rounds_per_sec"] > row["rounds_per_sec"]))
            for o in rows if o is not row)
    return rows


def sentinel_verdict():
    """The bench-sentinel verdict over the repo's recorded trajectory
    (tools/bench_sentinel.py), or None when skipped/unavailable — the
    sentinel must never fail the bench it is auditing."""
    if not SENTINEL_BENCH:
        return None
    root = os.path.dirname(os.path.abspath(__file__))
    try:
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "bench_sentinel", os.path.join(root, "tools",
                                           "bench_sentinel.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.verdict(root)
    except Exception:
        return None


def main():
    log(f"dataset: {N_ROWS} rows x {N_FEATURES} feats, {N_BINS} bins, depth {DEPTH}")
    # Numpy baseline FIRST: it is a ~2s subsample-and-scale measurement, and
    # taking it before any child exists means it never contends with the TPU
    # child's host-CPU-heavy compile phase (which would inflate the baseline
    # and flatter vs_baseline).
    baseline_1m = bench_cpu_scaled(N_ROWS)
    log(f"numpy baseline: {baseline_1m * 1e3:.1f} ms/round at {N_ROWS} rows")
    # The device child goes before every CPU rider: when it does not finish
    # on a TPU the run ends here, non-zero, with no record.
    res = run_device_child()
    device_time = res["device_time"]
    log(f"device per-round: {device_time * 1e3:.1f} ms on {res['platform']}")
    rec = {
        "metric": "gbdt_hist_rounds_per_sec_1M_rows",
        "value": round(1.0 / device_time, 3),
        "unit": "rounds/s",
        "vs_baseline": round(baseline_1m / device_time, 3),
        "platform": res["platform"],
        "device_kind": res["device_kind"],
        "device_count": res["device_count"],
        "mxu": res["mxu"],
        "rows_measured": N_ROWS,
    }
    if "final" in res:
        # The winning configuration must be reproducible from the artifact:
        # "final": "fused" marks a GBDTConfig(fused_final=True) win by the
        # challenger race; absent means the default (fused_final=False, the
        # XLA-gather final pass).
        rec["final"] = res["final"]
    riders = (
        (CODEC_ABLATION, "codec_ablation", run_codec_ablation),
        (ELASTIC_BENCH, "elastic", run_elastic_bench),
        (SCHED_BENCH, "schedule_ablation", run_sched_bench),
        (QUORUM_BENCH, "quorum_ablation", run_quorum_bench),
        (SCALE_BENCH, "scale_sweep", run_scale_bench),
        (HA_BENCH, "ha_failover", run_ha_bench),
        (FUSED_BENCH, "fused_ab", run_fused_bench),
        (SERVICE_BENCH, "service", run_service_bench),
        (OBS_BENCH, "live_metrics", run_obs_bench),
        (DELIVERY_BENCH, "delivery", run_delivery_bench),
    )
    for enabled, key, run in riders:
        if not enabled:
            continue
        lines = run()
        log(f"{key}: {len(lines)} line(s)")
        if lines:
            rec[key] = lines
    if "codec_ablation" in rec:
        rec["codec_pareto"] = codec_pareto(rec["codec_ablation"])
    sv = sentinel_verdict()
    if sv is not None:
        rec["sentinel"] = sv
    rec["wall_s"] = round(time.time() - T_START, 1)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--device-worker":
        device_worker(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--codec-worker":
        codec_worker(int(sys.argv[2]), int(sys.argv[3]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--codec-ablation":
        # Standalone trajectory: one JSON line per codec on stdout (the
        # same lines main() embeds under "codec_ablation"), the Pareto
        # frontier row the driver record carries, and the fused-vs-host
        # A/B arm (RABIT_BENCH_FUSED=0 skips it here too).
        lines = run_codec_ablation()
        for rec in lines:
            print(json.dumps(rec), flush=True)
        if lines:
            print(json.dumps({"codec_pareto": codec_pareto(lines)}),
                  flush=True)
        if FUSED_BENCH:
            for rec in run_fused_bench():
                print(json.dumps(rec), flush=True)
    elif len(sys.argv) > 1 and sys.argv[1] == "--obs-worker":
        obs_worker()
    elif len(sys.argv) > 1 and sys.argv[1] == "--fused-worker":
        fused_worker(int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--fused-ab":
        for rec in run_fused_bench():
            print(json.dumps(rec), flush=True)
    else:
        main()
