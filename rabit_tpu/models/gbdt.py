"""Histogram gradient-boosted decision trees — the flagship workload.

Distributed XGBoost's histogram aggregation is the workload rabit exists for
(reference doc/guide.md:130-140: each worker builds per-feature gradient
histograms over its data shard and Allreduces them every tree level;
BASELINE.json: "XGBoost hist tree_method gradient-histogram allreduce").
This module is that workload rebuilt TPU-first:

* features are quantized to ``n_bins`` integer bins once, up front;
* every boosting round grows one depth-``D`` tree level-wise; per level the
  (node, feature, bin) gradient/hessian histograms are one ``segment_sum``
  — a static-shape scatter-add XLA maps onto the TPU — and ONE fused
  ``psum`` across the data-parallel mesh axis (the rabit Allreduce);
* histogram work is additionally shardable across a feature-parallel mesh
  axis: each position histograms its feature slice, then one
  ``all_gather`` reassembles — 2-D (dp, fp) parallelism;
* everything is jit-compiled with static shapes: the level loop is unrolled
  (depth is a compile-time constant), rows carry a node index updated by
  gathers, no data-dependent control flow.

The functional core (``train_round``, ``predict``) is pure and shardable;
``GBDT`` wraps it for host numpy users, including the rabit-classic
deployment where each process holds a shard and histograms are combined
with ``engine.allreduce`` over the native TCP engine.
"""

from __future__ import annotations

import functools
import itertools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rabit_tpu import obs
from rabit_tpu.obs.stream import series_name


class GBDTConfig(NamedTuple):
    """Static hyperparameters (hashable: usable as a jit static arg)."""

    n_features: int
    n_trees: int = 20
    depth: int = 6
    n_bins: int = 256
    learning_rate: float = 0.3
    reg_lambda: float = 1.0
    min_child_weight: float = 1.0
    objective: str = "logistic"  # "logistic" | "squared"
    # Run the histogram contraction at int8 MXU rate (2x bf16 on
    # v5e-class chips) via a two-plane fixed-point split of the gradient
    # matrix; ~2^-14-of-block-max round-off (ops/boost.py _encode_i8)
    # vs ~2^-16-relative for the
    # default hi/lo-bf16 split.  Honored by every TPU Pallas dispatch —
    # fused and hook-based rounds alike; non-TPU backends (exact-f32
    # scatter) ignore it.
    mxu_i8: bool = False
    # Final leaf pass of train_round_fused: True runs the fused Pallas
    # route+margin kernel (ops/boost.py route_margin_level); False runs
    # the routing-only kernel and leaves ``margin += leaf[node]`` to XLA
    # (a gather of every row from a 2**depth-entry table).  Both are exact.
    # Figures of an older chip at 1,000,000 rows x 28, depth 6, not
    # re-measured (RESULTS/final_pass.jsonl): XLA-final won whole-round in
    # both MXU modes (73.8 vs 78.1 ms bf16, 77.3 vs 78.7 ms i8), so False is
    # the default.  No tool races the fused kernel any more, and ROADMAP.md
    # D2 deletes it once the benchmark stops passing this field.  The
    # benchmark's cells (PERF.md section 5) run 2.6M rows; at depth 8 the
    # XLA gather is 21.5 ms of a round.
    fused_final: bool = False
    # Split each row block into this many independent sub-contractions in
    # the level kernels' histogram accumulation (ops/boost.py _accum):
    # sub-block i's MXU matmul has no dependency on sub-block i+1's VPU
    # indicator build, giving Mosaic explicit overlap room.  Must divide
    # the row block (1024); results are added in f32.  Default 1 = current
    # single-contraction form; >1 is the on-chip ablation's experiment.
    r_split: int = 1


class Forest(NamedTuple):
    """A stack of perfect binary trees in level order.

    ``feature``/``threshold``: [n_trees, depth, 2**(depth-1)] — level d of a
    tree uses the first 2**d entries; thresholds are bin ids (go right when
    ``bin > threshold``).  ``leaf``: [n_trees, 2**depth] leaf weights.
    Untrained trees are all-zero and contribute nothing to predictions.
    """

    feature: jax.Array
    threshold: jax.Array
    leaf: jax.Array


class TrainState(NamedTuple):
    forest: Forest
    margin: jax.Array  # [rows_this_shard] current boosting margin
    round: jax.Array   # scalar int32: trees built so far


def init_forest(cfg: GBDTConfig) -> Forest:
    max_nodes = 2 ** (cfg.depth - 1)
    return Forest(
        feature=jnp.zeros((cfg.n_trees, cfg.depth, max_nodes), jnp.int32),
        threshold=jnp.zeros((cfg.n_trees, cfg.depth, max_nodes), jnp.int32),
        leaf=jnp.zeros((cfg.n_trees, 2 ** cfg.depth), jnp.float32),
    )


def init_state(cfg: GBDTConfig, n_rows: int) -> TrainState:
    return TrainState(
        forest=init_forest(cfg),
        margin=jnp.zeros(n_rows, jnp.float32),
        round=jnp.zeros((), jnp.int32),
    )


# -- quantization ----------------------------------------------------------


def compute_bin_edges(X: np.ndarray, n_bins: int) -> np.ndarray:
    """Per-feature quantile cut points, [n_features, n_bins - 1] (host-side,
    once per dataset — the 'sketch' phase of hist tree_method)."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(np.asarray(X, np.float32), qs, axis=0).T.astype(np.float32)


def quantize(X: jax.Array, edges: jax.Array) -> jax.Array:
    """Map features to integer bins in [0, n_bins): bin = #edges <= x."""
    find = lambda col, e: jnp.searchsorted(e, col, side="right")
    return jax.vmap(find, in_axes=(1, 0), out_axes=1)(X, edges).astype(jnp.int32)


# -- gradients -------------------------------------------------------------


def gradients(cfg: GBDTConfig, margin: jax.Array, y: jax.Array):
    if cfg.objective == "logistic":
        p = jax.nn.sigmoid(margin)
        return p - y, p * (1.0 - p)
    if cfg.objective == "squared":
        return margin - y, jnp.ones_like(margin)
    raise ValueError(f"unknown objective {cfg.objective}")


# -- histograms (the hot op) ----------------------------------------------


def node_histograms(
    xb: jax.Array, g: jax.Array, h: jax.Array, node: jax.Array,
    n_nodes: int, n_bins: int, mxu_i8: bool = False
) -> jax.Array:
    """Per-(node, feature, bin) gradient/hessian sums: [n_nodes, F, B, 2].

    Dispatches to the backend-appropriate kernel in ``rabit_tpu.ops.hist``:
    a Pallas MXU one-hot-contraction kernel on TPU (~17x the scatter-add
    path; int8-rate variant under ``mxu_i8``), exact-f32 segment_sum
    elsewhere.  This is the TPU-native form of the reference workload's
    per-level histogram build (doc/guide.md:130-140).
    """
    from rabit_tpu.ops import hist as _hist

    return _hist.node_histograms(xb, g, h, node, n_nodes, n_bins,
                                 mxu_i8=mxu_i8)


def best_splits(hist: jax.Array, cfg: GBDTConfig):
    """Best (feature, bin, gain) per node from summed histograms.

    Standard XGBoost gain: GL^2/(HL+λ) + GR^2/(HR+λ) − G^2/(H+λ), split
    candidates are 'bin <= b goes left', invalid when either side's hessian
    mass is under min_child_weight.
    """
    g, h = hist[..., 0], hist[..., 1]            # [nodes, F, B]
    GL, HL = jnp.cumsum(g, -1), jnp.cumsum(h, -1)
    G, H = GL[..., -1:], HL[..., -1:]
    GR, HR = G - GL, H - HL
    score = lambda a, b: a * a / (b + cfg.reg_lambda)
    gain = score(GL, HL) + score(GR, HR) - score(G, H)
    valid = (HL >= cfg.min_child_weight) & (HR >= cfg.min_child_weight)
    gain = jnp.where(valid, gain, -jnp.inf)
    flat = gain.reshape(gain.shape[0], -1)
    best = jnp.argmax(flat, axis=-1)
    best_gain = jnp.take_along_axis(flat, best[:, None], -1)[:, 0]
    n_bins = hist.shape[2]
    return (
        (best // n_bins).astype(jnp.int32),
        (best % n_bins).astype(jnp.int32),
        best_gain,
    )


def split_child_masses(hist: jax.Array, feat: jax.Array, thr: jax.Array) -> jax.Array:
    """Leaf (g, h) masses read off the parent histogram at the chosen split
    — XGBoost's histogram identity (children sums = split cumsums), so the
    leaf fit needs no extra row pass over the data.  ``hist`` is the final
    level's COMBINED [n_nodes, F, B, 2] histogram; returns [2*n_nodes, 2]
    interleaved (left_0, right_0, left_1, right_1, ...) in leaf order
    (leaf = 2*node + went_right)."""
    g, h = hist[..., 0], hist[..., 1]                  # [nodes, F, B]
    GL, HL = jnp.cumsum(g, -1), jnp.cumsum(h, -1)
    G, H = GL[..., -1], HL[..., -1]                    # [nodes, F]
    n_nodes = hist.shape[0]
    rows = jnp.arange(n_nodes)
    gl = GL[rows, feat, thr]
    hl = HL[rows, feat, thr]
    gt = G[rows, feat]
    ht = H[rows, feat]
    left = jnp.stack([gl, hl], -1)                     # [nodes, 2]
    right = jnp.stack([gt - gl, ht - hl], -1)
    return jnp.stack([left, right], axis=1).reshape(2 * n_nodes, 2)


def smaller_child(hist: jax.Array, feat: jax.Array, thr: jax.Array) -> jax.Array:
    """Which child of each node to BUILD at a level that derives the other
    from ``parent - built`` (ops.boost.hist_plan): 1 where the right child's
    hessian mass at the chosen split, ``H - HL``, is under the left's
    (ties: left), ``[n_nodes]`` int32.  Read off the COMBINED histogram, so
    every shard and a replay choose alike.  The lighter child is built
    because a derived node carries its parent's absolute summation error: on
    the heavier child that is at most its own error to a small factor, on a
    child of a few rows it would be many times its own."""
    h = hist[jnp.arange(hist.shape[0]), feat, :, 1]           # [nodes, B]
    left = jnp.arange(h.shape[1]) <= thr[:, None]
    hl = jnp.sum(jnp.where(left, h, 0.0), -1)
    hr = jnp.sum(jnp.where(left, 0.0, h), -1)
    return (hr < hl).astype(jnp.int32)


# -- training --------------------------------------------------------------


def _hist_local(xb, g, h, node, n_nodes, n_bins, mxu_i8=False):
    return node_histograms(xb, g, h, node, n_nodes, n_bins, mxu_i8=mxu_i8)


def train_round(
    state: TrainState,
    xb: jax.Array,
    y: jax.Array,
    cfg: GBDTConfig,
    hist_fn: Callable[..., jax.Array] | None = None,
    combine_leaf: Callable[[jax.Array], jax.Array] = lambda gh: gh,
) -> TrainState:
    """Grow one tree on (this shard of) the data and append it to the forest.

    ``hist_fn(xb, g, h, node, n_nodes, n_bins) -> [n_nodes, F, B, 2]`` is
    the histogram-build-and-allreduce hook: plain local histograms for
    single-shard training; histograms + ``lax.psum`` over the dp axis inside
    shard_map; a feature-sliced build + psum + all_gather for 2-D (dp, fp);
    or an engine.allreduce callback in the rabit-classic multi-process
    deployment.  These hooks are the ONLY communication points — exactly the
    reference workload's Allreduce placement (doc/guide.md:130-140).
    """
    if hist_fn is None:
        hist_fn = functools.partial(_hist_local, mxu_i8=cfg.mxu_i8)
    n, F = xb.shape
    max_nodes = 2 ** (cfg.depth - 1)
    g, h = gradients(cfg, state.margin, y)
    node = jnp.zeros(n, jnp.int32)
    feats, thrs = [], []
    for d in range(cfg.depth):
        n_nodes = 2 ** d
        with jax.named_scope(f"level{d}"):
            hist = hist_fn(xb, g, h, node, n_nodes, cfg.n_bins)
            feat, thr, _gain = best_splits(hist, cfg)
            feats.append(jnp.zeros(max_nodes, jnp.int32).at[:n_nodes].set(feat))
            thrs.append(jnp.zeros(max_nodes, jnp.int32).at[:n_nodes].set(thr))
            # Route every row one level down: right iff bin > threshold.
            fsel = feat[node]                                        # [n]
            xv = jnp.take_along_axis(xb, fsel[:, None], 1)[:, 0]
            node = node * 2 + (xv > thr[node]).astype(jnp.int32)
    # Leaf weights from summed per-leaf gradient mass.
    from rabit_tpu.ops import hist as _hist

    n_leaves = 2 ** cfg.depth
    with jax.named_scope("leaf"):
        leaf_gh = _hist.segment_sum(jnp.stack([g, h], -1), node, n_leaves)
        leaf_gh = combine_leaf(leaf_gh)  # [n_leaves, 2] allreduce
        leaf = (-cfg.learning_rate * leaf_gh[:, 0]
                / (leaf_gh[:, 1] + cfg.reg_lambda))
        margin = state.margin + leaf[node]
    t = state.round
    forest = Forest(
        feature=lax.dynamic_update_index_in_dim(
            state.forest.feature, jnp.stack(feats), t, 0
        ),
        threshold=lax.dynamic_update_index_in_dim(
            state.forest.threshold, jnp.stack(thrs), t, 0
        ),
        leaf=lax.dynamic_update_index_in_dim(state.forest.leaf, leaf, t, 0),
    )
    return TrainState(forest=forest, margin=margin, round=t + 1)


def train_round_dp(state, xb, y, cfg, dp_axis: str = "dp", fp_axis: str | None = None):
    """train_round wired for shard_map: rows sharded over ``dp_axis``; when
    ``fp_axis`` is given (rows replicated across it), each fp position
    histograms only its F/fp feature slice — the compute splits — then one
    psum over dp and one all_gather over fp reassemble the global
    histogram."""
    if fp_axis is None:
        hist_fn = lambda xb, g, h, node, n_nodes, n_bins: lax.psum(
            node_histograms(xb, g, h, node, n_nodes, n_bins,
                            mxu_i8=cfg.mxu_i8), dp_axis
        )
        combine_leaf = lambda gh: lax.psum(gh, dp_axis)
    else:
        fp_size = lax.axis_size(fp_axis)
        f_local = cfg.n_features // fp_size
        fp_idx = lax.axis_index(fp_axis)

        def hist_fn(xb, g, h, node, n_nodes, n_bins):
            x_slice = lax.dynamic_slice_in_dim(xb, fp_idx * f_local, f_local, 1)
            sl = node_histograms(x_slice, g, h, node, n_nodes, n_bins,
                                 mxu_i8=cfg.mxu_i8)
            sl = lax.psum(sl, dp_axis)
            return lax.all_gather(sl, fp_axis, axis=1, tiled=True)

        # every fp copy sees the same rows: reduce leaves over dp only.
        combine_leaf = lambda gh: lax.psum(gh, dp_axis)
    return train_round(state, xb, y, cfg, hist_fn, combine_leaf)


def train_round_fused(
    state: TrainState,
    xb3: jax.Array,
    y: jax.Array,
    cfg: GBDTConfig,
    combine: Callable[[jax.Array], jax.Array] = lambda x: x,
    interpret: bool = False,
    combine_leaf: Callable[[jax.Array], jax.Array] | None = None,
) -> TrainState:
    """One boosting round via the fused Pallas kernels (ops.boost): routing,
    split lookup, and histogram accumulation run in one streaming pass per
    level, so rows cross HBM depth+1 times per round (depth histogram
    passes + one routing-only leaf pass) instead of ~3x depth.  A matrix
    wider than one tile of codes (``ops.boost.TILE_FEATS``) is swept once a
    feature tile a level, and routed in a pass of its own a level.

    Up to level 4 every node's histogram is accumulated.  From level 5 on
    (where every node built would stack a full MXU tile of gradient matrix:
    ``ops.boost.hist_plan``, from the shape alone) the kernel accumulates
    ONE child of every parent — the one with the smaller hessian mass at the
    parent's split, by the combined histogram (``smaller_child``) — that
    half crosses ``combine``, and the siblings are the previous level's
    combined histogram less it, in float32 (``ops.boost.derive_siblings``);
    ``best_splits`` then scans every node as before.

    When the round is lowered, each level leaves one ``gbdt.hist_plan`` span
    with what ``ops.boost.hist_plan`` reckoned for its kernel
    (``nodes_built``, ``nodes_derived``, ``lanes_a_feature``,
    ``regs_a_broadcast``), the gauge
    ``gbdt_hist_rows_streamed_per_round`` takes rows x (tile sweeps of every
    level + routing passes), ``gbdt_hist_nodes_derived_per_round`` the
    nodes a round reads off a subtraction (16 at depth 6, 112 at depth 8),
    ``gbdt_hist_feats_a_register`` the features that share a 128-lane
    register of the indicator (2 at up to 64 bins, else 1), and
    ``gbdt_hist_regs_a_broadcast`` the registers one lane broadcast of a
    word of four packed codes serves (2 at up to 64 bins, 4 at up to 128, 8
    at 256).

    ``xb3`` is the pre-blocked quantized matrix from ``ops.boost.block_rows``
    (built once per fit).  ``combine`` is the histogram allreduce hook
    (one call per level; leaf masses derive from the last combined
    histogram via split_child_masses, so there is no leaf collective)
    (e.g. ``lambda a: lax.psum(a, 'dp')`` under shard_map) — the same single
    communication point per level as the reference workload.  It is handed
    ``[2**d, F, B, 2]`` at a level built whole and ``[2**(d-1), F, B, 2]``,
    the built children in parent order, at a derived one: half the bytes.

    ``combine_leaf``, where given, is one more collective a round, for a
    deployment whose collective sequence has a leaf hop (train_round_hybrid):
    it is handed this shard's LOCAL children's masses of the last level,
    ``[2**depth, 2]`` — linear in the histogram, so the sum over shards is
    what the combined histogram gives — and returns the global ones.
    Sending the combined histogram's masses would count every shard's
    world times.  (The local histogram of a derived level is the local
    parents' less the local built children's, kept only for this hook.)
    """
    from rabit_tpu.ops import boost

    n = y.shape[0]
    block = xb3.shape[1]  # row-block size is fixed by how xb3 was blocked
    max_nodes = 2 ** (cfg.depth - 1)
    g, h = gradients(cfg, state.margin, y)
    g3, _ = boost.block_rows(g, block)
    h3, _ = boost.block_rows(h, block)
    if g3.shape[0] != xb3.shape[0]:
        raise ValueError(
            f"train_round_fused: {n} rows block into {g3.shape[0]} blocks of "
            f"{block}, but xb3 has {xb3.shape[0]} blocks — a dp shard's row "
            "count must match its pre-blocked feature matrix or rows would be "
            "silently mispaired with gradients"
        )

    with jax.named_scope("level0"):
        local = boost.hist_level0(xb3, g3, h3, n_bins=cfg.n_bins,
                                  interpret=interpret, mxu_i8=cfg.mxu_i8,
                                  r_split=cfg.r_split)
        hist = combine(local)
        feat, thr, _ = best_splits(hist, cfg)
    feats = [jnp.zeros(max_nodes, jnp.int32).at[:1].set(feat)]
    thrs = [jnp.zeros(max_nodes, jnp.int32).at[:1].set(thr)]
    node3 = jnp.zeros_like(g3, shape=g3.shape, dtype=jnp.int32)
    nodes_derived = 0
    for d in range(1, cfg.depth):
        plan = boost.hist_plan(xb3.shape[2], cfg.n_bins, d, block)
        nodes_derived += plan.nodes_derived
        level = functools.partial(
            boost.hist_level, xb3, node3, g3, h3, feat, thr, depth=d,
            n_bins=cfg.n_bins, interpret=interpret, mxu_i8=cfg.mxu_i8,
            r_split=cfg.r_split)
        with jax.named_scope(f"level{d}"), obs.span(
                "gbdt.hist_plan", level=d, nodes_built=plan.nodes_built,
                nodes_derived=plan.nodes_derived,
                m_rows=plan.m_rows, m_tiles=plan.m_tiles,
                feat_tiles=plan.feat_tiles, tile_feats=plan.tile_feats,
                lanes_a_feature=plan.lanes_a_feature,
                regs_a_broadcast=plan.regs_a_broadcast,
                acc_block_bytes=plan.acc_block_bytes,
                vmem_bytes=plan.vmem_bytes):
            if plan.nodes_derived:
                # one child a parent through the kernel and the collective,
                # its sibling from the parents' combined histogram after it
                built_right = smaller_child(hist, feat, thr)
                built, node3 = level(built_right)
                hist = boost.derive_siblings(hist, combine(built), built_right)
                if combine_leaf is not None:
                    local = boost.derive_siblings(local, built, built_right)
            else:
                local, node3 = level()
                hist = combine(local)
            feat, thr, _ = best_splits(hist, cfg)
        feats.append(jnp.zeros(max_nodes, jnp.int32).at[: 2 ** d].set(feat))
        thrs.append(jnp.zeros(max_nodes, jnp.int32).at[: 2 ** d].set(thr))
    # a sweep a feature tile a level, the root's included; the leaves'
    # routing pass, and one a level below the root where routing is a pass
    # of its own (more tiles than one)
    root = boost.hist_plan(xb3.shape[2], cfg.n_bins, 0, block)
    tiles = root.feat_tiles
    passes = cfg.depth * tiles + (1 if tiles == 1 else cfg.depth)
    obs.get_registry().gauge("gbdt_hist_rows_streamed_per_round").set(
        passes * xb3.shape[0] * block)
    obs.get_registry().gauge("gbdt_hist_nodes_derived_per_round").set(
        nodes_derived)
    obs.get_registry().gauge("gbdt_hist_feats_a_register").set(
        max(1, 128 // root.lanes_a_feature))
    obs.get_registry().gauge("gbdt_hist_regs_a_broadcast").set(
        root.regs_a_broadcast)
    # Leaf (g, h) masses come straight off the final combined histogram
    # (split_child_masses) — already globally reduced, so no leaf collective
    # and no histogram work in the last row pass (depth collectives per
    # round, not depth+1, unless the caller's sequence has a leaf hop:
    # ``combine_leaf``).  The last pass routes rows to their leaves and
    # applies ``margin += leaf[node]`` either inside one fused kernel
    # (cfg.fused_final) or as a routing kernel plus an XLA gather from the
    # 2**depth-entry leaf table — the gather form measured faster
    # whole-round in both MXU modes and is the default; see the
    # GBDTConfig.fused_final docstring (RESULTS/final_pass.jsonl).
    with jax.named_scope("leaf"):
        if combine_leaf is None:
            leaf_gh = split_child_masses(hist, feat, thr)
        else:
            leaf_gh = combine_leaf(split_child_masses(local, feat, thr))
        leaf = (-cfg.learning_rate * leaf_gh[:, 0]
                / (leaf_gh[:, 1] + cfg.reg_lambda))
        if cfg.fused_final:
            margin3, _ = boost.block_rows(state.margin, block)
            margin3, _node3 = boost.route_margin_level(
                xb3, node3, margin3, feat, thr, leaf, depth=cfg.depth,
                interpret=interpret)
            margin = boost.unblock_rows(margin3, n)
        else:
            node3 = boost.route_level(xb3, node3, feat, thr, depth=cfg.depth,
                                      interpret=interpret)
            margin = state.margin + leaf[boost.unblock_rows(node3, n)]
    t = state.round
    forest = Forest(
        feature=lax.dynamic_update_index_in_dim(
            state.forest.feature, jnp.stack(feats), t, 0
        ),
        threshold=lax.dynamic_update_index_in_dim(
            state.forest.threshold, jnp.stack(thrs), t, 0
        ),
        leaf=lax.dynamic_update_index_in_dim(state.forest.leaf, leaf, t, 0),
    )
    return TrainState(forest=forest, margin=margin, round=t + 1)


def train_round_hybrid(
    state: TrainState,
    xb: jax.Array,
    y: jax.Array,
    cfg: GBDTConfig,
    mesh=None,
    dp_axis: str = "dp",
    engine_allreduce: Callable[[np.ndarray], np.ndarray] | None = None,
    interpret: bool = False,
) -> TrainState:
    """One boosting round for the HYBRID deployment: the device's data plane
    married to the fault-tolerant native engine (the reference's recovery
    seam, allreduce_robust.cc:687-725, which round-2's review named the last
    first-order gap).

    The whole round is ONE jitted XLA program, and the cross-worker hop
    crosses the robust TCP engine through a host callback between a level's
    histogram and the next level's routing.  The callbacks are ordered by
    data dependence — level d's combined histogram feeds level d+1's
    routing — so every worker issues the identical deterministic collective
    sequence, depth + 1 hops a round (a histogram a level — from level 5
    on the built half of it, ``train_round_fused`` — then the leaves'
    masses), which is exactly what lets the robust engine's replay log
    serve byte-identical results to a worker recovering mid-round.

    Which kernels do the local work is read from the call, no option:

    * on a TPU (or with ``interpret``, for the tests) and no ``mesh``, the
      fused kernels: ``train_round_fused``'s level loop with the hop as its
      per-level hook and the local children's masses of the last level as
      the leaf hop.  A 2-D ``xb`` ``[n, F]`` is blocked in the graph, every
      round; a 3-D one is taken as ``ops.boost.block_rows`` left it, so a
      caller that blocks once a fit pays nothing;
    * anywhere else, and with a ``mesh`` (local histograms under
      ``shard_map`` with an in-graph ``psum`` over the intra-host devices,
      the callback outside it: once a process, never once a device),
      ``train_round``: the standalone histogram, XLA's gather routing and a
      ``segment_sum`` for the leaves.

    The counter ``gbdt_hybrid_round_lowered_total{path=fused|xla}`` says
    which, once a lowering.

    ``engine_allreduce`` is a host fn ``np.ndarray -> np.ndarray`` (e.g.
    ``lambda a: rabit_tpu.allreduce(a, rt.SUM)``); None means solo (the
    callback is omitted entirely, keeping the program pure for dryruns).
    """

    def cross(a: jax.Array, tag: int) -> jax.Array:
        if engine_allreduce is None:
            return a
        # `tag` is a per-call-site constant operand: two levels of one
        # round can produce IDENTICAL histograms (degenerate shards), and
        # pure_callback's contract would let XLA CSE the two "pure" calls
        # into one host call — desynchronizing the engine's collective
        # sequence across workers.  Distinct constant operands make the
        # calls distinct HLO ops, so each level's engine hop always fires.
        # (io_callback(ordered=True) would be the canonical primitive, but
        # XLA's SPMD partitioner rejects side-effecting ops with the
        # replicated shardings this program needs.)
        def host(x, _t):
            # the host side of one hop, device->host copy to the result's
            # copy back: what the device waits for beyond the engine's
            # call, the two copies under names of their own
            # (doc/observability.md, "A hop's five phases")
            n = x.nbytes    # the payload one way
            with obs.span("gbdt.cross", level=tag, nbytes=n):
                with obs.span("gbdt.cross.in", nbytes=n):
                    a = np.asarray(x)
                out = engine_allreduce(a)
                with obs.span("gbdt.cross.out", nbytes=n):
                    return np.asarray(out, dtype=x.dtype)

        return jax.pure_callback(
            host,
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            a,
            np.int32(tag),
        )

    cross_leaf = functools.partial(cross, tag=-1)
    fused = mesh is None and (interpret or jax.default_backend() == "tpu")
    obs.get_registry().counter(series_name(
        "gbdt_hybrid_round_lowered_total",
        path="fused" if fused else "xla")).inc()
    from rabit_tpu.ops import boost

    if fused:
        xb3 = xb if xb.ndim == 3 else boost.block_rows(xb)[0]
        # the tag is the level's node count, 2**level: unique per level (the
        # payload's own node count is not: a derived level sends half)
        levels = itertools.count()
        return train_round_fused(
            state, xb3, y, cfg, combine=lambda a: cross(a, 2 ** next(levels)),
            interpret=interpret, combine_leaf=cross_leaf)
    if xb.ndim == 3:
        xb = boost.unblock_rows(xb, y.shape[0])
    if mesh is None:
        hist_fn = lambda xb_, g, h, node, nn, nb: cross(
            node_histograms(xb_, g, h, node, nn, nb, mxu_i8=cfg.mxu_i8), nn
        )
    else:
        from jax.sharding import PartitionSpec as P

        def hist_fn(xb_, g, h, node, nn, nb):
            local = jax.shard_map(
                lambda a, b, c, d: lax.psum(
                    node_histograms(a, b, c, d, nn, nb, mxu_i8=cfg.mxu_i8),
                    dp_axis
                ),
                mesh=mesh,
                in_specs=(P(dp_axis, None), P(dp_axis), P(dp_axis), P(dp_axis)),
                out_specs=P(),
                check_vma=False,
            )(xb_, g, h, node)
            return cross(local, nn)  # nn = 2**level: unique per level

    return train_round(state, xb, y, cfg, hist_fn, cross_leaf)


def train_round_dp_fused(state, xb3, y, cfg, dp_axis: str = "dp",
                         interpret: bool = False, wire_i8: bool = False,
                         wire_block: int = 256):
    """train_round_fused wired for shard_map: row blocks sharded over
    ``dp_axis`` (shard xb3 on its leading block dim, margin/y on rows); one
    psum per tree level (leaf masses ride the last one) — communication
    placement to train_round_dp, with the fused kernels doing the local
    work.

    ``wire_i8=True`` ships each level's histogram allreduce over the
    quantized int8-wire ring (parallel.ring_allreduce_quantized, ~2x fewer
    ICI/DCN bytes at ~2^-16-of-block-max accuracy per hop) instead of
    ``lax.psum`` — the bandwidth-bound-regime option for large
    feature x bin spaces or DCN-crossing dp axes.  Lossy but structurally
    rank-consistent: every rank (owner included) decodes each chunk's
    identical wire bytes at the identical program point, so the reduced
    histograms — and hence best_splits argmax decisions, even on exact
    ties — are bitwise identical across ranks; the forests cannot
    silently diverge.  Keep exact psum where results must also be
    byte-identical to a serial replay (the robust replay contract).
    Requires the flattened per-level histogram (2^d * F * n_bins * 2
    floats) divisible by dp_size * wire_block."""
    if wire_i8:
        from rabit_tpu.parallel import ring_allreduce_quantized

        def combine(a):
            return ring_allreduce_quantized(
                a.reshape(-1), dp_axis, block=wire_block).reshape(a.shape)
    else:
        combine = lambda a: lax.psum(a, dp_axis)
    return train_round_fused(state, xb3, y, cfg, combine=combine,
                             interpret=interpret)


# -- prediction ------------------------------------------------------------


def predict_margin(forest: Forest, xb: jax.Array, cfg: GBDTConfig) -> jax.Array:
    """Sum of leaf values over all trees; [n].  Untrained (zero) trees
    contribute 0, so this is valid mid-training."""
    n = xb.shape[0]

    def one_tree(margin, tree):
        feature, threshold, leaf = tree
        pos = jnp.zeros(n, jnp.int32)
        for d in range(cfg.depth):
            f = feature[d][pos]
            thr = threshold[d][pos]
            xv = jnp.take_along_axis(xb, f[:, None], 1)[:, 0]
            pos = pos * 2 + (xv > thr).astype(jnp.int32)
        return margin + leaf[pos], None

    margin, _ = lax.scan(one_tree, jnp.zeros(n, jnp.float32), forest)
    return margin


def predict_proba(forest: Forest, xb: jax.Array, cfg: GBDTConfig) -> jax.Array:
    return jax.nn.sigmoid(predict_margin(forest, xb, cfg))


# -- elastic sharding -------------------------------------------------------


def elastic_shard(X: np.ndarray, y: np.ndarray, world: int,
                  rank: int) -> tuple[np.ndarray, np.ndarray]:
    """This rank's rows of the FULL dataset under the elastic dense
    partition (rabit_tpu.elastic.rebalance) — the shard-rebalance hook of
    the histogram deployment.  When the world resizes, every surviving
    rank re-cuts with the new ``(world, rank)`` and the per-shard
    histogram sums keep covering the whole dataset around the hole; wire
    it to ``rabit_tpu.api.register_rebalance`` so the re-cut runs at every
    adopted epoch (doc/elasticity.md)."""
    from rabit_tpu.elastic.rebalance import shard_slice

    sl = shard_slice(len(X), world, rank)
    return X[sl], y[sl]


# -- host-facing wrapper ---------------------------------------------------


class GBDT:
    """Numpy-in, numpy-out trainer.

    ``engine_allreduce``: optional host allreduce hook (e.g. the native TCP
    engine's) — the rabit-classic distributed deployment where each process
    trains on its own shard and only histograms cross the wire.
    """

    def __init__(self, engine_allreduce: Callable[[np.ndarray], np.ndarray] | None = None, **hyper):
        self._hyper = hyper
        self._engine_allreduce = engine_allreduce
        self.cfg: GBDTConfig | None = None
        self.forest: Forest | None = None
        self.edges: np.ndarray | None = None

    def fit(self, X: np.ndarray, y: np.ndarray, warm_state: TrainState | None = None):
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        self.cfg = GBDTConfig(n_features=X.shape[1], **self._hyper)
        self.edges = compute_bin_edges(X, self.cfg.n_bins)
        xb = quantize(jnp.asarray(X), jnp.asarray(self.edges))
        state = warm_state or init_state(self.cfg, X.shape[0])

        if self._engine_allreduce is None:
            if jax.default_backend() == "tpu":
                from rabit_tpu.ops import boost

                xb3, _ = boost.block_rows(xb)
                step = jax.jit(functools.partial(train_round_fused, cfg=self.cfg))
                for _ in range(self.cfg.n_trees):
                    state = step(state, xb3, jnp.asarray(y))
            else:
                step = jax.jit(functools.partial(train_round, cfg=self.cfg))
                for _ in range(self.cfg.n_trees):
                    state = step(state, xb, jnp.asarray(y))
        else:
            # Histograms leave the device, cross the engine (TCP/XLA), and
            # come back — the exact reference call pattern.
            hook = lambda hist: jnp.asarray(self._engine_allreduce(np.asarray(hist)))
            hist_fn = lambda xb, g, h, node, n_nodes, n_bins: hook(
                node_histograms(xb, g, h, node, n_nodes, n_bins,
                                mxu_i8=self.cfg.mxu_i8)
            )
            for _ in range(self.cfg.n_trees):
                state = train_round(state, xb, jnp.asarray(y), self.cfg, hist_fn, hook)
        self.forest = jax.tree.map(np.asarray, state.forest)
        self._state = state
        return self

    def fit_shard(self, X: np.ndarray, y: np.ndarray, world: int,
                  rank: int, warm_state: TrainState | None = None):
        """Elastic-deployment fit: train on this rank's dense shard of the
        FULL dataset (``elastic_shard``).  After a world resize, call again
        with the new ``(world, rank)`` (and the recovered ``warm_state``)
        — the re-cut shard plus the engine-allreduce hook keep histogram
        sums covering every row at any world size."""
        Xs, ys = elastic_shard(X, y, world, rank)
        return self.fit(Xs, ys, warm_state=warm_state)

    def predict_margin(self, X: np.ndarray) -> np.ndarray:
        if self.forest is None:
            raise RuntimeError("GBDT.predict called before fit")
        xb = quantize(jnp.asarray(np.asarray(X, np.float32)), jnp.asarray(self.edges))
        fn = jax.jit(functools.partial(predict_margin, cfg=self.cfg))
        return np.asarray(fn(jax.tree.map(jnp.asarray, self.forest), xb))

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-self.predict_margin(X)))

    def predict(self, X: np.ndarray) -> np.ndarray:
        if self.cfg.objective == "logistic":
            return (self.predict_margin(X) > 0).astype(np.int32)
        return self.predict_margin(X)
