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


class _Hyper(NamedTuple):
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
    # Split each row block into this many independent sub-contractions in
    # the level kernels' histogram accumulation (ops/boost.py _accum):
    # sub-block i's MXU matmul has no dependency on sub-block i+1's VPU
    # indicator build, giving Mosaic explicit overlap room.  Must divide
    # the row block (1024); results are added in f32.  Default 1 = current
    # single-contraction form; >1 is the on-chip ablation's experiment.
    r_split: int = 1
    # Code n_bins - 1 is a missing value, and every split learns which way
    # it goes (XGBoost's sparsity-aware split finding, KDD'16 sec. 3.4, Alg.
    # 3): a threshold scores twice, the missing mass left and right
    # (ops/boost.py split_scan, a Pallas kernel a level), and the direction
    # rides the threshold's DEFAULT_LEFT bit (Forest).  The present values
    # take codes 0..n_bins-2 (compute_bin_edges, quantize).  Codes have to
    # fit a byte: n_bins <= 256.  False: dense codes, ``bin > threshold``.
    missing: bool = False
    # The features whose codes are categories, not ordered bins: a split on
    # one sends a SET of codes left (LightGBM's and XGBoost's partition
    # split, Fisher 1958): the present codes (positive hessian mass) ordered
    # by G / (H + lambda), ties by code, every prefix of that order scored
    # (ops/boost.py cat_split, a Pallas kernel a level), the best kept as a
    # 256-bit set (Forest.sets) and tested by membership in every routing
    # pass.  The other features keep ``bin > threshold``.  Any sequence of
    # indices, kept as a sorted tuple (a JSON list is no jit static
    # argument).  Codes have to fit a byte, and the missing code is not run
    # with it.
    categorical: tuple = ()


class GBDTConfig(_Hyper):
    """Static hyperparameters (hashable: usable as a jit static arg)."""

    __slots__ = ()

    def __new__(cls, *args, **kw):
        cfg = super().__new__(cls, *args, **kw)
        cats = tuple(sorted({int(f) for f in cfg.categorical}))
        if cats and not (0 <= cats[0] and cats[-1] < cfg.n_features
                         and cfg.n_bins <= 256 and not cfg.missing):
            raise ValueError(
                f"GBDTConfig.categorical {list(cats)}: indices of the "
                f"{cfg.n_features} features, codes that fit a byte (n_bins "
                f"{cfg.n_bins} <= 256), and no missing code")
        return cfg if cats == cfg.categorical else cfg._replace(categorical=cats)


class Forest(tuple):
    """A stack of perfect binary trees in level order.

    ``feature``/``threshold``: [n_trees, depth, 2**(depth-1)] — level d of a
    tree uses the first 2**d entries; thresholds are bin ids (go right when
    ``bin > threshold``).  Under ``GBDTConfig.missing`` a threshold also
    carries its split's default direction: ``thr | 256`` (``ops.boost.
    DEFAULT_LEFT``) sends the missing code left, and the bin is ``thr &
    255`` (``ops.boost.went_right``); dense, no threshold reaches 256.
    ``leaf``: [n_trees, 2**depth] leaf weights.  Untrained trees are
    all-zero and contribute nothing to predictions.

    ``sets`` (under ``GBDTConfig.categorical`` alone): [n_trees, 8,
    2**depth] int32, word ``w`` of node ``i`` of level ``d`` at ``[t, w,
    2**d - 1 + i]`` (the last lane unused): a categorical split's set of
    codes, bit ``c & 31`` of word ``c >> 5`` set where code ``c`` goes left
    (its threshold is 0), zeros at a numeric node.  The nodes lie along the
    last axis: with the words there ([n_trees, 255, 8]) the chip's copy to
    the host came back transposed, not contiguous, 4.0 ms to copy and 6.6
    to pickle a commit where this layout takes 1.2 and 0.05 (PERF.md
    section 6).  ``node_sets`` reads a level's.  A forest without
    categorical features is the three tables alone, so its pytree, its
    jitted rounds and its checkpoint are what they were; iterating it gives
    its tables in this order.
    """

    _fields = ("feature", "threshold", "leaf")
    __slots__ = ()

    def __new__(cls, feature, threshold, leaf, sets=None):
        tables = (feature, threshold, leaf)
        return super().__new__(cls, tables if sets is None else (*tables, sets))

    def __getnewargs__(self):
        return tuple(self)

    feature = property(lambda self: self[0])
    threshold = property(lambda self: self[1])
    leaf = property(lambda self: self[2])
    sets = property(lambda self: self[3] if len(self) > 3 else None)


jax.tree_util.register_pytree_node(
    Forest, lambda f: (tuple(f), None), lambda _, tables: Forest(*tables))


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
        sets=jnp.zeros((cfg.n_trees, 8, 2 ** cfg.depth), jnp.int32)
        if cfg.categorical else None,
    )


def init_state(cfg: GBDTConfig, n_rows: int) -> TrainState:
    return TrainState(
        forest=init_forest(cfg),
        margin=jnp.zeros(n_rows, jnp.float32),
        round=jnp.zeros((), jnp.int32),
    )


# -- quantization ----------------------------------------------------------


def compute_bin_edges(X: np.ndarray, n_bins: int,
                      missing: bool = False) -> np.ndarray:
    """Per-feature quantile cut points, [n_features, n_bins - 1] (host-side,
    once per dataset — the 'sketch' phase of hist tree_method).  With
    ``missing`` the present (non-NaN) values are cut into ``n_bins - 1``
    bins, [n_features, n_bins - 2], and code ``n_bins - 1`` is left to NaN
    (``quantize``); a feature with no present value gets edges 0."""
    X = np.asarray(X, np.float32)
    if not missing:
        qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
        return np.quantile(X, qs, axis=0).T.astype(np.float32)
    qs = np.linspace(0.0, 1.0, n_bins)[1:-1]
    X = np.where(np.isnan(X).all(0), np.float32(0), X)
    return np.nanquantile(X, qs, axis=0).T.astype(np.float32)


def quantize(X: jax.Array, edges: jax.Array, missing: bool = False,
             categorical: tuple = ()) -> jax.Array:
    """Map features to integer bins in [0, n_bins): bin = #edges <= x.  With
    ``missing`` (edges from ``compute_bin_edges(..., missing=True)``) a NaN
    takes the top code, ``edges.shape[1] + 1`` = n_bins - 1.  A
    ``categorical`` column holds category codes and keeps them: a code of
    ``n_bins - 1`` or more, a negative one or a NaN is folded into the last
    code, ``n_bins - 1`` ("every other category")."""
    find = lambda col, e: jnp.searchsorted(e, col, side="right")
    codes = jax.vmap(find, in_axes=(1, 0), out_axes=1)(X, edges).astype(jnp.int32)
    if missing:
        codes = jnp.where(jnp.isnan(X), edges.shape[1] + 1, codes)
    if categorical:
        cats = jnp.asarray(categorical)
        top = edges.shape[1]
        v = X[:, cats]
        own = jnp.where(jnp.isnan(v) | (v < 0) | (v >= top), top,
                        jnp.floor(jnp.nan_to_num(v)))
        codes = codes.at[:, cats].set(own.astype(jnp.int32))
    return codes


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
    if cfg.categorical:     # their splits are sets: cat_split's
        valid = valid & ~jnp.isin(jnp.arange(hist.shape[1]),
                                  jnp.asarray(cfg.categorical))[:, None]
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


def _missing_code(cfg: GBDTConfig):
    """The code that means missing (``n_bins - 1``), or None where the codes
    are dense."""
    return cfg.n_bins - 1 if cfg.missing else None


def level_splits(hist: jax.Array, cfg: GBDTConfig, level: int,
                 interpret: bool = False):
    """A level's splits ``(feature, threshold, gain, sets)``: ``best_splits``
    where the codes are dense, the two-way scan of ``ops.boost.split_scan``
    (one Pallas kernel a level, ``split_scan_d<level>``) under
    ``cfg.missing``; ``sets`` is None but under ``cfg.categorical``.

    There the numeric features' best split (``best_splits``) and the
    categorical features' (``ops.boost.cat_split``, one Pallas kernel a
    level, ``cat_split_d<level>``) meet: the higher gain wins, then the
    lower feature, and a node's set ``[nodes, 8]`` is its categorical
    winner's, zeros where a numeric split won (threshold 0 where a set
    did).  A node with no valid candidate gets feature 0, threshold 0 and
    gain ``-inf``, as ``best_splits`` gives it."""
    from rabit_tpu.ops import boost

    if cfg.categorical:
        feat, thr, gain = best_splits(hist, cfg)
        cats = jnp.asarray(cfg.categorical)
        _count_cat_split()
        slot, _prefix, cat_gain, words = boost.cat_split(
            hist[:, cats], depth=level, reg_lambda=cfg.reg_lambda,
            min_child_weight=cfg.min_child_weight, interpret=interpret)
        cat_feat = cats[slot]
        wins = (cat_gain > gain) | ((cat_gain == gain) & (cat_feat < feat))
        return (jnp.where(wins, cat_feat, feat), jnp.where(wins, 0, thr),
                jnp.maximum(cat_gain, gain),
                jnp.where(wins[:, None], words, 0))
    if not cfg.missing:
        return (*best_splits(hist, cfg), None)
    return (*boost.split_scan(hist, depth=level, reg_lambda=cfg.reg_lambda,
                              min_child_weight=cfg.min_child_weight,
                              interpret=interpret), None)


def _count_split_scan(cfg: GBDTConfig) -> None:
    """``gbdt_split_scan_lowered_total{directions=1|2}``: a round lowered, by
    how many ways a candidate threshold is scored."""
    obs.get_registry().counter(series_name(
        "gbdt_split_scan_lowered_total",
        directions="2" if cfg.missing else "1")).inc()


def _count_cat_split() -> None:
    """``gbdt_cat_split_lowered_total``: a ``cat_split_d<d>`` kernel lowered,
    depth of them a round."""
    obs.get_registry().counter("gbdt_cat_split_lowered_total").inc()


def _route_words(thr, sets):
    """The level's routing sets where it has categorical features
    (``ops.boost.route_words``), else None."""
    if sets is None:
        return None
    from rabit_tpu.ops import boost

    return boost.route_words(thr, sets)


def _goes_left(n_bins: int, thr: jax.Array, missing: bool,
               words=None) -> jax.Array:
    """``[nodes, n_bins]``: whether a code goes left at each node's split
    (``words``: the level's routing sets, ``_route_words``)."""
    if words is not None:
        from rabit_tpu.ops import boost

        return boost.set_bits(words, n_bins)
    if not missing:
        return jnp.arange(n_bins) <= thr[:, None]
    from rabit_tpu.ops import boost

    return ~boost.went_right(jnp.arange(n_bins), thr[:, None], n_bins - 1)


def split_child_masses(hist: jax.Array, feat: jax.Array, thr: jax.Array,
                       missing: bool = False, words=None) -> jax.Array:
    """Leaf (g, h) masses read off the parent histogram at the chosen split
    — XGBoost's histogram identity (children sums = split cumsums), so the
    leaf fit needs no extra row pass over the data.  ``hist`` is the final
    level's COMBINED [n_nodes, F, B, 2] histogram; returns [2*n_nodes, 2]
    interleaved (left_0, right_0, left_1, right_1, ...) in leaf order
    (leaf = 2*node + went_right).  With ``missing`` the top code's mass goes
    to the child the threshold's default direction names; given the level's
    routing sets (``words``) the codes of a node's set go left."""
    n_nodes = hist.shape[0]
    if missing or words is not None:
        sel = hist[jnp.arange(n_nodes), feat]             # [nodes, B, 2]
        left = _goes_left(hist.shape[2], thr, missing, words)[..., None]
        return jnp.stack([jnp.sum(jnp.where(left, sel, 0.0), 1),
                          jnp.sum(jnp.where(left, 0.0, sel), 1)],
                         axis=1).reshape(2 * n_nodes, 2)
    g, h = hist[..., 0], hist[..., 1]                  # [nodes, F, B]
    GL, HL = jnp.cumsum(g, -1), jnp.cumsum(h, -1)
    G, H = GL[..., -1], HL[..., -1]                    # [nodes, F]
    rows = jnp.arange(n_nodes)
    gl = GL[rows, feat, thr]
    hl = HL[rows, feat, thr]
    gt = G[rows, feat]
    ht = H[rows, feat]
    left = jnp.stack([gl, hl], -1)                     # [nodes, 2]
    right = jnp.stack([gt - gl, ht - hl], -1)
    return jnp.stack([left, right], axis=1).reshape(2 * n_nodes, 2)


def smaller_child(hist: jax.Array, feat: jax.Array, thr: jax.Array,
                  missing: bool = False, words=None) -> jax.Array:
    """Which child of each node to BUILD at a level that derives the other
    from ``parent - built`` (ops.boost.hist_plan): 1 where the right child's
    hessian mass at the chosen split, ``H - HL``, is under the left's
    (ties: left), ``[n_nodes]`` int32.  Read off the COMBINED histogram, so
    every shard and a replay choose alike.  The lighter child is built
    because a derived node carries its parent's absolute summation error: on
    the heavier child that is at most its own error to a small factor, on a
    child of a few rows it would be many times its own.  With ``missing``
    the top code's mass lies on its default direction's side; given the
    level's routing sets (``words``) a node's set is its left child's."""
    h = hist[jnp.arange(hist.shape[0]), feat, :, 1]           # [nodes, B]
    left = _goes_left(h.shape[1], thr, missing, words)
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

    Under ``cfg.missing`` each level's splits come from the split-scan
    kernel (``level_splits``), interpreted off a TPU; under
    ``cfg.categorical`` the categorical features' from ``cat_split``, and a
    row goes left at a node with a set iff its code is in it.
    """
    from rabit_tpu.ops import boost

    if hist_fn is None:
        hist_fn = functools.partial(_hist_local, mxu_i8=cfg.mxu_i8)
    missing_code = _missing_code(cfg)
    _count_split_scan(cfg)
    interpret = jax.default_backend() != "tpu"
    n, F = xb.shape
    max_nodes = 2 ** (cfg.depth - 1)
    g, h = gradients(cfg, state.margin, y)
    node = jnp.zeros(n, jnp.int32)
    feats, thrs, sets = [], [], []
    for d in range(cfg.depth):
        n_nodes = 2 ** d
        with jax.named_scope(f"level{d}"):
            hist = hist_fn(xb, g, h, node, n_nodes, cfg.n_bins)
            feat, thr, _gain, level_sets = level_splits(hist, cfg, d, interpret)
            feats.append(jnp.zeros(max_nodes, jnp.int32).at[:n_nodes].set(feat))
            thrs.append(jnp.zeros(max_nodes, jnp.int32).at[:n_nodes].set(thr))
            words = _route_words(thr, level_sets)
            if words is not None:
                sets.append(level_sets)
            # Route every row one level down: right iff bin > threshold
            # (the missing code by the split's default direction; a code
            # outside its node's set, where the level has sets).
            fsel = feat[node]                                        # [n]
            xv = jnp.take_along_axis(xb, fsel[:, None], 1)[:, 0]
            node = node * 2 + boost.went_right(
                xv, thr[node], missing_code,
                None if words is None else words[node]).astype(jnp.int32)
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
        sets=_tree_sets(state.forest, sets, t),
    )
    return TrainState(forest=forest, margin=margin, round=t + 1)


def _tree_sets(forest: Forest, levels: list, t):
    """The forest's set table with tree ``t``'s levels' sets (each ``[2**d,
    8]``) written in, or None where it has none."""
    if not levels:
        return None
    tree = jnp.concatenate(levels + [jnp.zeros((1, 8), jnp.int32)]).T
    return lax.dynamic_update_index_in_dim(forest.sets, tree, t, 0)


def node_sets(tree_sets, level: int):
    """Level ``level``'s sets ``[2**level, 8]`` of one tree's set table
    ``[8, 2**depth]`` (``Forest.sets[t]``)."""
    return tree_sets[:, 2 ** level - 1: 2 ** (level + 1) - 1].T


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
    passes + one leaf pass that routes them and adds their leaves to the
    margin, ``ops.boost.route_margin_level``) instead of ~3x depth.  A matrix
    wider than one tile of codes (``ops.boost.TILE_FEATS``) is swept once a
    feature tile a level, and routed in a pass of its own a level.

    Up to level 2 every node's histogram is accumulated.  From level 3 on
    (where one child a parent stacks fewer padded rows of gradient matrix
    than every node: ``ops.boost.hist_plan``, from the shape alone) the
    kernel accumulates ONE child of every parent — the one with the smaller
    hessian mass at the parent's split, by the combined histogram
    (``smaller_child``) — that half crosses ``combine``, and the siblings
    are the previous level's combined histogram less it, in float32
    (``ops.boost.derive_siblings``); ``best_splits`` then scans every node
    as before.

    When the round is lowered, each level leaves one ``gbdt.hist_plan`` span
    with what ``ops.boost.hist_plan`` reckoned for its kernel
    (``nodes_built``, ``nodes_derived``, ``lanes_a_feature``,
    ``regs_a_broadcast``, ``mxu_holds``: ``"indicator"`` or, from a full
    MXU tile of stacked rows on, ``"gradients"``), the gauge
    ``gbdt_hist_rows_streamed_per_round`` takes rows x (tile sweeps of every
    level + routing passes), ``gbdt_hist_nodes_derived_per_round`` the
    nodes a round reads off a subtraction (28 at depth 6, 124 at depth 8),
    ``gbdt_hist_feats_a_register`` the features that share a 128-lane
    register of the indicator (2 at up to 64 bins, else 1), and
    ``gbdt_hist_regs_a_broadcast`` the registers one lane broadcast of a
    word of four packed codes serves (2 at up to 64 bins, 4 at up to 128, 8
    at 256), and ``gbdt_hist_levels_gradients_held`` the levels whose kernel
    holds the gradient planes in the MXU (2 at depth 8, 0 at depth 6).

    ``xb3`` is the pre-blocked quantized matrix from ``ops.boost.block_rows``
    (built once per fit).  ``combine`` is the histogram allreduce hook
    (one call per level; leaf masses derive from the last combined
    histogram via split_child_masses, so there is no leaf collective)
    (e.g. ``lambda a: lax.psum(a, 'dp')`` under shard_map) — the same single
    communication point per level as the reference workload.  It is handed
    ``[2**d, F, B, 2]`` at a level built whole and ``[2**(d-1), F, B, 2]``,
    the built children in parent order, at a derived one: half the bytes.

    Under ``cfg.missing`` every level's splits come from the split-scan
    kernel (``level_splits``: ``split_scan_d<d>``), the span says
    ``missing=True``, and every routing pass, the built child's choice and
    the leaves' masses send the missing code by each split's default
    direction.  Under ``cfg.categorical`` every level's categorical
    features are searched by ``cat_split_d<d>`` beside the numeric scan, the
    span says how many (``categorical``), and every routing pass, the built
    child's choice and the leaves' masses test a node's set (the level's
    ``ops.boost.route_words``), the kernels given the set table only then;
    the tree's sets go to ``Forest.sets``.

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

    missing_code = _missing_code(cfg)
    _count_split_scan(cfg)
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
        feat, thr, _, level_sets = level_splits(hist, cfg, 0, interpret)
    feats = [jnp.zeros(max_nodes, jnp.int32).at[:1].set(feat)]
    thrs = [jnp.zeros(max_nodes, jnp.int32).at[:1].set(thr)]
    sets = [] if level_sets is None else [level_sets]
    words = _route_words(thr, level_sets)
    node3 = jnp.zeros_like(g3, shape=g3.shape, dtype=jnp.int32)
    nodes_derived = held = 0
    for d in range(1, cfg.depth):
        plan = boost.hist_plan(xb3.shape[2], cfg.n_bins, d, block,
                               mxu_i8=cfg.mxu_i8, r_split=cfg.r_split,
                               categorical=len(cfg.categorical))
        nodes_derived += plan.nodes_derived
        held += plan.mxu_holds == "gradients"
        level = functools.partial(
            boost.hist_level, xb3, node3, g3, h3, feat, thr, words=words,
            depth=d, n_bins=cfg.n_bins, interpret=interpret,
            mxu_i8=cfg.mxu_i8, r_split=cfg.r_split, missing_code=missing_code)
        with jax.named_scope(f"level{d}"), obs.span(
                "gbdt.hist_plan", level=d, nodes_built=plan.nodes_built,
                nodes_derived=plan.nodes_derived,
                m_rows=plan.m_rows, m_tiles=plan.m_tiles,
                feat_tiles=plan.feat_tiles, tile_feats=plan.tile_feats,
                lanes_a_feature=plan.lanes_a_feature,
                regs_a_broadcast=plan.regs_a_broadcast,
                mxu_holds=plan.mxu_holds,
                acc_block_bytes=plan.acc_block_bytes,
                vmem_bytes=plan.vmem_bytes, missing=cfg.missing,
                categorical=plan.categorical):
            if plan.nodes_derived:
                # one child a parent through the kernel and the collective,
                # its sibling from the parents' combined histogram after it
                built_right = smaller_child(hist, feat, thr, cfg.missing,
                                            words)
                built, node3 = level(built_right)
                hist = boost.derive_siblings(hist, combine(built), built_right)
                if combine_leaf is not None:
                    local = boost.derive_siblings(local, built, built_right)
            else:
                local, node3 = level()
                hist = combine(local)
            feat, thr, _, level_sets = level_splits(hist, cfg, d, interpret)
        feats.append(jnp.zeros(max_nodes, jnp.int32).at[: 2 ** d].set(feat))
        thrs.append(jnp.zeros(max_nodes, jnp.int32).at[: 2 ** d].set(thr))
        words = _route_words(thr, level_sets)
        if words is not None:
            sets.append(level_sets)
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
    obs.get_registry().gauge("gbdt_hist_levels_gradients_held").set(held)
    # Leaf (g, h) masses come straight off the final combined histogram
    # (split_child_masses) — already globally reduced, so no leaf collective
    # and no histogram work in the last row pass (depth collectives per
    # round, not depth+1, unless the caller's sequence has a leaf hop:
    # ``combine_leaf``).  The last pass routes rows to their leaves and
    # adds each row's leaf to the margin in one kernel, at every depth: XLA
    # gathered every row from the 2**depth-entry table at some 12 cycles a
    # row past 128 entries (21.5 ms a Criteo round of 2.6M rows at 256
    # leaves), and even from 64 entries, a chain of selects, the routing
    # kernel's node ids went back through HBM first (8.7 against 5.5 ms at
    # HIGGS's 2.6M rows; PERF.md section 5).
    with jax.named_scope("leaf"):
        if combine_leaf is None:
            leaf_gh = split_child_masses(hist, feat, thr, cfg.missing, words)
        else:
            leaf_gh = combine_leaf(
                split_child_masses(local, feat, thr, cfg.missing, words))
        leaf = (-cfg.learning_rate * leaf_gh[:, 0]
                / (leaf_gh[:, 1] + cfg.reg_lambda))
        margin = boost.route_margin_level(
            xb3, node3, state.margin, feat, thr, leaf, words,
            depth=cfg.depth, interpret=interpret, missing_code=missing_code)
    t = state.round
    forest = Forest(
        feature=lax.dynamic_update_index_in_dim(
            state.forest.feature, jnp.stack(feats), t, 0
        ),
        threshold=lax.dynamic_update_index_in_dim(
            state.forest.threshold, jnp.stack(thrs), t, 0
        ),
        leaf=lax.dynamic_update_index_in_dim(state.forest.leaf, leaf, t, 0),
        sets=_tree_sets(state.forest, sets, t),
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
    sequence, depth + 1 hops a round (a histogram a level — from level 3
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
    which, once a lowering.  ``cfg.missing`` and ``cfg.categorical`` are
    honoured on both paths but the mesh form, which refuses them: their
    split search would be a Pallas kernel outside ``shard_map``, which
    Mosaic cannot partition.

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
    if cfg.missing and mesh is not None:
        raise ValueError("train_round_hybrid: GBDTConfig.missing is not run "
                         "with a mesh (the split-scan kernel would lie "
                         "outside shard_map); pass mesh=None")
    if cfg.categorical and mesh is not None:
        raise ValueError("train_round_hybrid: GBDTConfig.categorical is not "
                         "run with a mesh (the cat_split kernel would lie "
                         "outside shard_map); pass mesh=None")
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
    contribute 0, so this is valid mid-training.  Under ``cfg.missing`` the
    missing code goes by each split's default direction; with the forest's
    set table (``cfg.categorical``) a code in its node's set goes left."""
    from rabit_tpu.ops import boost

    n = xb.shape[0]
    missing_code = _missing_code(cfg)

    def one_tree(margin, tree):
        feature, threshold, leaf, *sets = tree
        pos = jnp.zeros(n, jnp.int32)
        for d in range(cfg.depth):
            f = feature[d][pos]
            thr = threshold[d][pos]
            xv = jnp.take_along_axis(xb, f[:, None], 1)[:, 0]
            words = None
            if sets:
                words = boost.route_words(threshold[d][: 2 ** d],
                                          node_sets(sets[0], d))[pos]
            pos = pos * 2 + boost.went_right(
                xv, thr, missing_code, words).astype(jnp.int32)
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

    def fit(self, X: np.ndarray, y: np.ndarray, warm_state: TrainState | None = None,
            categorical_features=()):
        """``categorical_features``: the columns of ``X`` that hold category
        codes (whole numbers; ``quantize`` folds what lies outside
        ``0..n_bins-2`` into the last code), split by sets of codes
        (``GBDTConfig.categorical``)."""
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        hyper = dict(self._hyper)
        if len(categorical_features):
            hyper["categorical"] = categorical_features
        self.cfg = GBDTConfig(n_features=X.shape[1], **hyper)
        self.edges = compute_bin_edges(X, self.cfg.n_bins, self.cfg.missing)
        xb = quantize(jnp.asarray(X), jnp.asarray(self.edges), self.cfg.missing,
                      self.cfg.categorical)
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
        xb = quantize(jnp.asarray(np.asarray(X, np.float32)),
                      jnp.asarray(self.edges), self.cfg.missing,
                      self.cfg.categorical)
        fn = jax.jit(functools.partial(predict_margin, cfg=self.cfg))
        return np.asarray(fn(jax.tree.map(jnp.asarray, self.forest), xb))

    def predict_proba(self, X: np.ndarray) -> np.ndarray:
        return 1.0 / (1.0 + np.exp(-self.predict_margin(X)))

    def predict(self, X: np.ndarray) -> np.ndarray:
        if self.cfg.objective == "logistic":
            return (self.predict_margin(X) > 0).astype(np.int32)
        return self.predict_margin(X)
