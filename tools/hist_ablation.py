#!/usr/bin/env python
"""Histogram-kernel ablation on the bench workload shape (1M x 28 x 256).

Times the node_histograms implementations (pallas MXU contraction and its
int8-rate variant / onehot XLA matmul / scatter segment_sum —
rabit_tpu/ops/hist.py) per tree level, plus the fused boost kernels'
route+hist level step and the WHOLE fused boosting round (records
train_round_fused{,_i8} with a rounds_per_sec field), each in both bf16
and int8 MXU forms, so the committed numbers say WHERE the round time
goes (round-2 verdict: "nobody can tell whether routing or the histogram
contraction dominates") and tie the kernel split to the headline metric.

Run on the chip (fresh process):
    python tools/hist_ablation.py [--rows 1000000] [--json-out f.jsonl]
Use --cpu for a harness smoke test on small shapes.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))


def timed(fn, *args, n=5):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--feats", type=int, default=28)
    ap.add_argument("--bins", type=int, default=256)
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="only the pallas bf16-vs-i8 hist kernels at the "
                         "deepest level")
    ap.add_argument("--whole-round-only", action="store_true",
                    help="only the train_round_fused {bf16,i8} x "
                         "{fused,xla}-final whole-round rows — the "
                         "GBDTConfig.fused_final decision experiment")
    ap.add_argument("--json-out", default="")
    args = ap.parse_args()

    if args.cpu:
        from rabit_tpu._platform import force_cpu_platform

        force_cpu_platform(1)
        args.rows = min(args.rows, 20_000)

    from rabit_tpu._platform import enable_persistent_cache

    # Repeat captures (knob sweeps) skip the minutes-long whole-round
    # compile per config; timing loops only ever measure runs.
    enable_persistent_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from rabit_tpu.ops import boost, hist

    plat = jax.devices()[0].platform
    print(f"# platform={plat} rows={args.rows} feats={args.feats} "
          f"bins={args.bins}", file=sys.stderr, flush=True)
    rng = np.random.RandomState(0)
    xb = jnp.asarray(
        rng.randint(0, args.bins, size=(args.rows, args.feats)), jnp.int32)
    g = jnp.asarray(rng.randn(args.rows), jnp.float32)
    h = jnp.asarray(rng.rand(args.rows), jnp.float32)

    records = []

    def emit(rec):
        rec.update(platform=plat, rows=args.rows, feats=args.feats,
                   bins=args.bins)
        records.append(rec)
        print(json.dumps(rec), flush=True)

    impls = {
        "scatter": hist.node_histograms_scatter,
        "onehot": hist.node_histograms_onehot,
    }
    focused = args.quick or args.whole_round_only
    if focused:
        if plat != "tpu":
            print("--quick/--whole-round-only benchmark only the Pallas "
                  "TPU kernels; no TPU backend is active", file=sys.stderr)
            return 2
        impls = {}
    if plat == "tpu" and not args.whole_round_only:
        impls["pallas"] = hist.node_histograms_pallas
        impls["pallas_i8"] = functools.partial(
            hist.node_histograms_pallas, mxu_i8=True)
    depths = (args.depth - 1,) if args.quick else (0, args.depth - 1)
    for d in depths:
        n_nodes = 1 << d
        node = jnp.asarray(rng.randint(0, n_nodes, size=args.rows), jnp.int32)
        for name, fn in impls.items():
            f = jax.jit(functools.partial(
                fn, n_nodes=n_nodes, n_bins=args.bins))
            dt = timed(f, xb, g, h, node, n=3 if args.quick else 5)
            emit({"kernel": f"hist_{name}", "n_nodes": n_nodes,
                  "ms": round(dt * 1e3, 3)})

    # Fused route+hist level step vs the hist alone: the difference is the
    # routing cost the fused kernel folds into the same HBM pass.
    xb3 = None
    if plat == "tpu" and not focused:
        xb3, _ = boost.block_rows(xb)
        g3, _ = boost.block_rows(g)
        h3, _ = boost.block_rows(h)
        for d in (1, args.depth - 1):
            n_nodes = 1 << (d - 1)
            node3 = jnp.asarray(
                rng.randint(0, n_nodes, size=g3.shape), jnp.int32)
            # level-(d-1) split tables, shape [2**(d-1)] (boost.hist_level)
            feat = jnp.asarray(
                rng.randint(0, args.feats, size=1 << (d - 1)), jnp.int32)
            thr = jnp.asarray(
                rng.randint(0, args.bins, size=1 << (d - 1)), jnp.int32)
            for i8 in (False, True):
                f = jax.jit(functools.partial(
                    boost.hist_level, depth=d, n_bins=args.bins, mxu_i8=i8))
                dt = timed(f, xb3, node3, g3, h3, feat, thr)
                emit({"kernel": "fused_route+hist" + ("_i8" if i8 else ""),
                      "level": d, "n_nodes_out": 1 << d,
                      "ms": round(dt * 1e3, 3)})

        # Final-pass comparison: routing-only (round-3 shape, followed by a
        # host-level leaf gather) vs the round-4 fused route+margin kernel.
        n_prev = 1 << (args.depth - 1)
        featd = jnp.asarray(
            rng.randint(0, args.feats, size=n_prev), jnp.int32)
        thrd = jnp.asarray(rng.randint(0, args.bins, size=n_prev), jnp.int32)
        node3d = jnp.asarray(rng.randint(0, n_prev, size=g3.shape), jnp.int32)
        leaf = jnp.asarray(rng.randn(1 << args.depth), jnp.float32)
        f_route = jax.jit(functools.partial(boost.route_level,
                                            depth=args.depth))
        dt = timed(f_route, xb3, node3d, featd, thrd)
        emit({"kernel": "route_level", "depth": args.depth,
              "ms": round(dt * 1e3, 3)})

        def route_then_gather(xb3_, node3_, feat_, thr_, leaf_):
            n3 = boost.route_level(xb3_, node3_, feat_, thr_,
                                   depth=args.depth)
            node = boost.unblock_rows(n3, args.rows)
            return leaf_[node]

        dt = timed(jax.jit(route_then_gather), xb3, node3d, featd, thrd, leaf)
        emit({"kernel": "route_level+leaf_gather", "depth": args.depth,
              "ms": round(dt * 1e3, 3)})
        m3 = jnp.zeros_like(g3)
        f_rm = jax.jit(functools.partial(boost.route_margin_level,
                                         depth=args.depth))
        dt = timed(f_rm, xb3, node3d, m3, featd, thrd, leaf)
        emit({"kernel": "route_margin_level", "depth": args.depth,
              "ms": round(dt * 1e3, 3)})

    # Whole fused round, {bf16, i8} x {fused, xla}-final — ties the
    # per-kernel numbers to the headline rounds/s metric in one
    # provenance-consistent run, and decides GBDTConfig.fused_final.
    if plat == "tpu" and not args.quick:
        from rabit_tpu.models import gbdt

        if xb3 is None:
            xb3, _ = boost.block_rows(xb)
        y = jnp.asarray(rng.randint(0, 2, size=args.rows), jnp.float32)
        def whole_round(tag, **kw):
            cfg = gbdt.GBDTConfig(n_features=args.feats, n_trees=8,
                                  depth=args.depth, n_bins=args.bins, **kw)
            step = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg))
            state = gbdt.init_state(cfg, args.rows)
            dt = timed(step, state, xb3, y, n=4)
            emit({"kernel": tag, "depth": args.depth,
                  "ms": round(dt * 1e3, 3),
                  "rounds_per_sec": round(1.0 / dt, 2)})

        for i8 in (False, True):
            for ff in (True, False):
                whole_round("train_round_fused" + ("_i8" if i8 else "")
                            + ("" if ff else "_xlafinal"),
                            mxu_i8=i8, fused_final=ff)
        if args.whole_round_only:
            # The VPU/MXU overlap experiment (GBDTConfig.r_split, see
            # ops/boost.py _accum) — only in the focused mode, to keep the
            # full ablation short.
            for i8 in (False, True):
                whole_round("train_round_fused" + ("_i8" if i8 else "")
                            + "_rsplit2", mxu_i8=i8, r_split=2)

    if args.json_out:
        out = Path(args.json_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
