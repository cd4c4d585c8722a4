"""``GBDTConfig.categorical``: a split on a categorical feature sends a SET
of codes left — the present codes ordered by ``G / (H + lambda)``, every
prefix scored (``ops.boost.cat_split``, a Pallas kernel a level) — and every
routing pass tests membership, on the CPU at a small size: 4,096 rows x 7
features x 16 codes (three of them categorical), depth 4, seeded; the
routing kernels at 256 codes.

The plain reference is the benchmark's own copy for this deployment,
``benchmark/harness/reference_categorical.py`` (numpy, float64), loaded by
its path."""

from __future__ import annotations

import functools
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rabit_tpu import parallel as rp
from rabit_tpu.models import gbdt
from rabit_tpu.ops import boost

_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


reference = _load(_ROOT / "benchmark" / "harness" / "reference_categorical.py",
                  "bench_reference_categorical")

N, F, B, DEPTH, ROUNDS = 4096, 7, 16, 4, 3
CATS = (2, 4, 5)
#: the benchmark's limits for the Criteo deployment with categories
LIMITS = json.loads((_ROOT / "benchmark" / "configs"
                     / "criteo-1tb-cat-share.limits.json").read_text())


def _data(seed=5, n=N):
    """Codes uniform over ``0..B-1``, feature 5's skewed to its low codes;
    the label steps on feature 0 and moves with an effect a code of features
    2 and 5 that no threshold orders."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B, size=(n, F)).astype(np.int32)
    codes[:, 5] = np.minimum(rng.geometric(0.2, n) - 1, B - 1)
    effect = rng.standard_normal((2, B))
    y = ((codes[:, 0] > 7) + effect[0, codes[:, 2]] + effect[1, codes[:, 5]]
         + rng.standard_normal(n) > 0.8).astype(np.float32)
    return codes, y


def _cfg(rounds=ROUNDS, depth=DEPTH, cats=CATS):
    return gbdt.GBDTConfig(n_features=F, n_trees=rounds, depth=depth,
                           n_bins=B, learning_rate=0.3, categorical=cats)


def _params(cfg):
    return reference.Params(cfg.depth, cfg.n_bins, cfg.learning_rate,
                            cfg.reg_lambda, cfg.min_child_weight,
                            frozenset(cfg.categorical))


def _train(step, cfg, n, *data):
    s = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s = step(s, *data)
    return jax.tree.map(np.asarray, s)


def _fused(cfg, codes, y, block=1024):
    xb3, _ = boost.block_rows(jnp.asarray(codes), block)
    return _train(jax.jit(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=True)), cfg, len(y), xb3,
        jnp.asarray(y))


def _xla(cfg, codes, y):
    return _train(jax.jit(functools.partial(gbdt.train_round, cfg=cfg)),
                  cfg, len(y), jnp.asarray(codes), jnp.asarray(y))


@pytest.mark.parametrize("seed", [5, 6, 7])
@pytest.mark.parametrize("path", ["train_round", "train_round_fused"])
def test_rounds_are_the_references_trees(path, seed):
    """Three rounds, both paths, three seeds: every node's feature,
    threshold and set is the float64 reference's own, the reference
    following them reads a gain gap of nought (each set taken scores the
    reference's best gain) and leaves within the benchmark's limit, and the
    leaves and margin are the reference's to float32 (and the hi/lo split
    of the fused kernels).  Sets are chosen on at least two features and
    hold more than one code."""
    codes, y = _data(seed)
    cfg = _cfg()
    got = (_xla if path == "train_round" else _fused)(cfg, codes, y)
    free = reference.boost_rounds(codes.astype(np.uint8), y, _params(cfg),
                                  ROUNDS, procs=1)
    np.testing.assert_array_equal(got.forest.feature, free.feature)
    np.testing.assert_array_equal(got.forest.threshold, free.threshold)
    np.testing.assert_array_equal(got.forest.sets, free.sets)
    np.testing.assert_allclose(got.forest.leaf, free.leaf, rtol=1e-4,
                               atol=1e-6)
    followed = reference.boost_rounds(
        codes.astype(np.uint8), y, _params(cfg), ROUNDS, procs=1,
        follow=tuple(got.forest))
    assert max(followed.gain_gap) <= 1e-12
    assert max(followed.leaf_gap) <= LIMITS["leaf_gap"]
    assert followed.split_differs == [0] * ROUNDS
    bits = reference.set_bits(np.swapaxes(got.forest.sets, 1, 2), B)
    has = bits.any(-1)
    assert has.sum() > 3 and (bits.sum(-1) > 1).any()
    on = {int(got.forest.feature[r, d, k]) for r in range(ROUNDS)
          for d in range(DEPTH) for k in range(2 ** d)
          if has[r, 2 ** d - 1 + k]}
    assert len(on) >= 2 and on <= set(CATS)
    margin = reference.margins_of(codes, *got.forest, bins=B)[-1]
    np.testing.assert_allclose(got.margin, margin, rtol=1e-5, atol=1e-5)


def test_predict_margin_is_the_references():
    """``predict_margin`` routes the committed trees by their sets to the
    reference's margins, on the rows it trained on and on fresh rows."""
    codes, y = _data()
    cfg = _cfg()
    got = _fused(cfg, codes, y)
    forest = jax.tree.map(jnp.asarray, got.forest)
    assert isinstance(forest, gbdt.Forest) and forest.sets is not None
    for rows in (codes, _data(seed=9, n=1000)[0]):
        want = reference.margins_of(rows, *got.forest, bins=B)[-1]
        predicted = gbdt.predict_margin(forest, jnp.asarray(rows), cfg)
        np.testing.assert_allclose(np.asarray(predicted), want, rtol=1e-5,
                                   atol=1e-5)


def _membership_case(f, n_nodes=4, nb=2, R=256, seed=3):
    """Codes at 256 bins with 0, 31, 32, 255 in every feature's column,
    nodes with sets of every word and nodes with thresholds."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 256, size=(nb, R, f)).astype(np.int32)
    codes[:, :4, :] = np.array([0, 31, 32, 255])[:, None]
    node = rng.integers(0, n_nodes, size=(nb, R, 1)).astype(np.int32)
    feat = rng.integers(0, f, size=n_nodes).astype(np.int32)
    thr = rng.integers(0, 256, size=n_nodes).astype(np.int32)
    sets = rng.integers(-2 ** 31, 2 ** 31, size=(n_nodes, 8)).astype(np.int32)
    sets[0] = 0                                     # a numeric node
    sets[1] = 0
    sets[1, 0] = 1 - 2 ** 31                        # codes 0 and 31
    sets[1, 1] = 1                                  # code 32
    sets[1, 7] = -2 ** 31                           # code 255
    thr[sets.any(-1)] = 0
    xv = np.take_along_axis(codes, feat[node], 2)
    words = np.asarray(boost.route_words(jnp.asarray(thr), jnp.asarray(sets)))
    right = np.asarray(boost.went_right(
        jnp.asarray(xv[..., 0]), jnp.asarray(thr)[node[..., 0]],
        words=jnp.asarray(words)[node[..., 0]]))[..., None]
    want = node * 2 + right
    inside = reference.set_bits(sets, 256)[node[..., 0], xv[..., 0]][..., None]
    ref_right = np.where(sets.any(-1)[node], ~inside, xv > thr[node])
    np.testing.assert_array_equal(right, ref_right)
    assert right[:, :4][node[:, :4] == 1].sum() == 0   # 0, 31, 32, 255 stay left
    return (jnp.asarray(codes), jnp.asarray(node), jnp.asarray(feat),
            jnp.asarray(thr), jnp.asarray(words), want)


@pytest.mark.parametrize("kernel", ["route", "route_tiled", "route_margin",
                                    "route_margin_tiled", "level",
                                    "level_tiled", "level_held"])
def test_every_routing_kernel_tests_membership(kernel):
    """``route_level`` (one tile of codes, and the tiled pass past
    ``TILE_FEATS``), ``route_margin_level`` (both ways too), and
    ``hist_level``'s own routing (one tile, tiled, and the held form from a
    full MXU tile of stacked rows) send a code left iff it is in its node's
    set — bit ``c & 31`` of word ``c >> 5``, codes 0, 31, 32 and 255
    included — and a node without a set by its threshold: what the XLA
    path's ``went_right`` gives, and the reference's."""
    wide = kernel.endswith("tiled")
    n_nodes = 32 if kernel == "level_held" else 4
    x3, n3, feat, thr, words, want = _membership_case(
        130 if wide else 7, n_nodes=n_nodes)
    depth = n_nodes.bit_length()
    if kernel.startswith("route_margin"):
        # a row's leaf is its node: the margin from zero says where it went
        leaf = jnp.arange(2 * n_nodes, dtype=jnp.float32)
        got = boost.route_margin_level(
            x3, n3, jnp.zeros(n3.size), feat, thr, leaf, words, depth=depth,
            interpret=True).reshape(n3.shape)
    elif kernel.startswith("route"):
        got = boost.route_level(x3, n3, feat, thr, words, depth=depth,
                                interpret=True)
    else:
        g = jnp.ones(n3.shape, jnp.float32)
        built = (jnp.zeros(n_nodes, jnp.int32)
                 if boost.hist_plan(x3.shape[2], 256, depth, 256).nodes_derived
                 else None)
        _, got = boost.hist_level(x3, n3, g, g, feat, thr, built, words,
                                  depth=depth, n_bins=256, interpret=True)
        if kernel == "level_held":
            assert boost.hist_plan(7, 256, depth, 256).mxu_holds == "gradients"
    np.testing.assert_array_equal(np.asarray(got), want)


#: a node of Criteo's first tree (criteo-1tb-cat-share, seed 2147540102,
#: level 7, node 117, field C9): its rows of each code, label 0 and label 1.
#: 13 codes share the key 0.4 and the best set ends inside them
NODE_N0 = [
    12, 2, 1, 5, 3, 4, 41, 4, 1, 11, 20, 11, 4, 2, 3, 34, 7, 2, 11, 2,
    9, 10, 24, 30, 1, 3, 2, 0, 4, 10, 6, 2, 2, 3, 10, 6, 11, 20, 34, 7,
    28, 2, 137, 10, 3, 5, 3, 3, 4, 5, 5, 1, 43, 3, 8, 3, 9, 3, 63, 2, 5,
    234, 9, 18, 3, 7, 33, 2, 5, 1, 14, 16, 6, 4, 1, 6, 3, 2, 3, 12, 5,
    2, 187, 2, 9, 14, 6, 6, 20, 4, 1, 3, 7, 7, 5, 7, 8, 5, 4, 4, 8, 3,
    7, 1, 6, 2, 88, 6, 14, 8, 7, 3, 1, 24, 6, 4, 16, 2, 7, 21, 7, 3, 14,
    6, 0, 3, 3, 3, 2, 2, 2, 128, 3, 4, 61, 2, 5, 3, 1, 1, 68, 9, 2, 3,
    105, 5, 5, 8, 3, 3, 2, 1, 4, 4, 2, 2, 1, 11, 1, 9, 1, 5, 3, 4, 0, 4,
    8, 8, 7, 27, 14, 352, 1, 6, 2, 765, 8, 12, 22, 4, 2, 0, 5, 11, 2, 5,
    6, 1, 1, 13, 5, 13, 7, 8, 6, 2, 2, 4, 2, 4, 3, 6, 12, 5, 3, 20, 3,
    6, 4, 8, 55, 26, 0, 4, 2, 4, 2, 6, 12, 30, 5, 0, 2, 34, 2, 12, 2, 2,
    3, 2, 4, 2, 2, 1, 0, 5, 0, 17, 14, 56, 8, 6, 12, 1, 49, 4, 5, 5, 1,
    3, 2, 2, 8, 6, 17, 1475]
NODE_N1 = [
    5, 0, 1, 0, 0, 2, 8, 2, 2, 4, 1, 1, 0, 1, 0, 6, 1, 1, 0, 1, 2, 2, 1,
    4, 3, 0, 3, 1, 0, 2, 0, 2, 2, 1, 1, 0, 2, 4, 7, 1, 9, 1, 26, 0, 1,
    2, 3, 1, 1, 1, 1, 1, 5, 3, 3, 0, 1, 1, 14, 3, 3, 38, 0, 6, 1, 0, 4,
    1, 1, 1, 4, 0, 1, 0, 0, 0, 0, 0, 2, 2, 2, 0, 35, 0, 2, 2, 0, 0, 2,
    0, 1, 0, 1, 3, 2, 1, 0, 0, 7, 0, 1, 1, 3, 0, 1, 1, 14, 0, 2, 1, 1,
    0, 1, 6, 0, 1, 10, 0, 1, 7, 0, 1, 9, 4, 2, 0, 0, 1, 0, 1, 0, 21, 0,
    0, 11, 0, 1, 0, 1, 1, 15, 1, 0, 1, 19, 0, 1, 0, 0, 1, 0, 0, 1, 3, 1,
    0, 2, 2, 0, 3, 0, 3, 0, 0, 1, 1, 1, 0, 0, 1, 0, 52, 0, 3, 0, 147, 0,
    0, 4, 1, 3, 1, 0, 0, 1, 0, 0, 0, 0, 2, 0, 3, 1, 0, 2, 0, 0, 0, 1, 1,
    0, 4, 3, 0, 1, 4, 1, 4, 0, 0, 12, 1, 0, 2, 0, 1, 0, 1, 2, 4, 1, 2,
    1, 7, 0, 0, 0, 0, 1, 2, 0, 0, 0, 1, 0, 1, 2, 5, 0, 10, 0, 1, 1, 0,
    6, 0, 0, 0, 2, 0, 0, 0, 2, 1, 3, 301]


def test_cat_split_ties_absence_and_the_merge_with_numeric():
    """Hand-made nodes: two codes of equal key order by code, and the
    shorter prefix wins a tie of gains; a code with no rows is in no set;
    ``level_splits`` takes the higher gain, then the lower feature, whether
    numeric or categorical, and a node with no valid candidate gets feature
    0, threshold 0, gain ``-inf`` and no set."""
    cfg = gbdt.GBDTConfig(n_features=3, depth=2, n_bins=B, categorical=(1,))
    hist = np.zeros((3, 3, B, 2), np.float32)
    # node 0: on the categorical feature, codes 3 and 9 low (equal keys),
    # codes 5 and 12 high, code 7 absent; the numeric features flat
    hist[0, :, :, 1] = 1.0
    hist[0, :, :, 0] = 0.0
    hist[0, 1, [3, 9], 0] = -4.0
    hist[0, 1, [5, 12], 0] = 4.0
    hist[0, 1, 7] = 0.0
    # node 1: the same split on a numeric feature 0 (bins 0-3 low) and on
    # the categorical feature 1 (codes 0-3 low): equal gains, feature 0
    hist[1, :, :, 1] = 1.0
    hist[1, 0, :4, 0] = hist[1, 1, :4, 0] = -3.0
    hist[1, 0, 4:8, 0] = hist[1, 1, 4:8, 0] = 1.5
    # node 2: no rows at all
    feat, thr, gain, sets = map(np.asarray, gbdt.level_splits(
        jnp.asarray(hist), cfg, 1, interpret=True))
    left = reference.set_bits(sets, B)
    assert (feat[0], thr[0]) == (1, 0)
    assert set(np.flatnonzero(left[0])) == {3, 9}
    assert not left[0, 7]
    assert (feat[1], thr[1]) == (0, 3) and not left[1].any()
    assert (feat[2], thr[2], gain[2]) == (0, 0, -np.inf) and not left[2].any()
    # the kernel alone: with codes 3 and 9 only low, the tie of their keys
    # goes by code, so a prefix of one is code 3 — the two-code set scores
    # higher and wins, and a set of {9} alone never appears
    slot, prefix, g, words = map(np.asarray, boost.cat_split(
        jnp.asarray(hist[:1, 1:2]), depth=0, reg_lambda=1.0,
        min_child_weight=1.0, interpret=True))
    assert (slot[0], prefix[0]) == (0, 1) and g[0] == gain[0]
    np.testing.assert_array_equal(words, sets[:1])
    # {3, 9} and its complement among the present codes score alike: the
    # shorter prefix is taken
    np.testing.assert_allclose(g[0], 64 / 3 + 64 / 14, rtol=1e-6)
    # a first tree's sums are small multiples of 0.25, and equal ratios of
    # unequal masses tie: at this node of Criteo's first tree the best set
    # ends inside a group of 13 codes of key 0.4, which goes by code (the
    # kernel compares cross products, not quotients, which the chip's divide
    # does not round alike: PERF.md section 6)
    n0, n1 = np.asarray(NODE_N0, np.float64), np.asarray(NODE_N1, np.float64)
    G, H = 0.5 * (n0 - n1), 0.25 * (n0 + n1)
    _, prefix, gain, words = map(np.asarray, boost.cat_split(
        jnp.asarray(np.stack([G, H], -1)[None, None], jnp.float32), depth=7,
        reg_lambda=1.0, min_child_weight=1.0, interpret=True))
    key = np.where(H > 0, G / (H + 1.0), np.inf)
    by = np.argsort(key, kind="stable")
    assert prefix[0] == 50 and np.sum(key == key[by[50]]) == 13
    assert key[by[51]] == key[by[50]] and key[by[46]] == key[by[50]]
    assert set(np.flatnonzero(reference.set_bits(words, 256)[0])) == set(by[:51])
    np.testing.assert_allclose(gain[0], 89.22819481231545, rtol=1e-6)


def test_child_masses_from_a_set_feed_the_built_child_at_depth_7():
    """``split_child_masses`` and ``smaller_child`` read a child's mass off
    the node's set; at depth 7 the fused round builds one child a parent
    from level 3 on by them and derives its sibling, and the reference
    following its trees finds every split taken its best to float32 and its
    leaves its own, within the benchmark's limits."""
    rng = np.random.default_rng(2)
    hist = rng.random((4, 3, B, 2)).astype(np.float32)
    feat = jnp.asarray([1, 0, 1, 2], jnp.int32)
    thr = jnp.asarray([0, 6, 0, 2], jnp.int32)
    sets = np.zeros((4, 8), np.int32)
    sets[0, 0], sets[2, 0] = 0b1010_0000_0000_0110, 0b1
    words = boost.route_words(thr, jnp.asarray(sets))
    masses = np.asarray(gbdt.split_child_masses(jnp.asarray(hist), feat, thr,
                                                words=words))
    left = reference.set_bits(np.asarray(words), B)
    sel = hist[np.arange(4), np.asarray(feat)]
    want = np.stack([(sel * left[..., None]).sum(1),
                     (sel * ~left[..., None]).sum(1)], 1).reshape(8, 2)
    np.testing.assert_allclose(masses, want, rtol=1e-6)
    built = np.asarray(gbdt.smaller_child(jnp.asarray(hist), feat, thr,
                                          words=words))
    np.testing.assert_array_equal(built, want[1::2, 1] < want[0::2, 1])
    codes, y = _data(n=8192)
    cfg = _cfg(rounds=2, depth=7)
    assert boost.hist_plan(F, B, 6, 1024).nodes_derived
    fused = _fused(cfg, codes, y)
    assert reference.set_bits(np.swapaxes(fused.forest.sets, 1, 2)[:, 63:127],
                              B).any()
    followed = reference.boost_rounds(
        codes.astype(np.uint8), y, _params(cfg), 2, procs=1,
        follow=tuple(fused.forest))
    assert max(followed.gain_gap) <= LIMITS["gain_gap"]
    assert max(followed.leaf_gap) <= LIMITS["leaf_gap"]


def test_dp_fused_round_is_the_fused_round():
    """Rows over eight virtual devices under ``shard_map``: the psum'd
    histograms' search and every routing pass give the single-device round's
    trees and sets."""
    codes, y = _data(n=8 * 512)
    cfg = _cfg(rounds=2)
    mesh = rp.create_mesh(("dp",))
    spec = gbdt.TrainState(forest=gbdt.Forest(P(), P(), P(), P()),
                           margin=P("dp"), round=P())
    xb3, _ = boost.block_rows(jnp.asarray(codes), 256)
    step = jax.jit(jax.shard_map(
        functools.partial(gbdt.train_round_dp_fused, cfg=cfg, interpret=True),
        mesh=mesh, in_specs=(spec, P("dp", None, None), P("dp")),
        out_specs=spec, check_vma=False))
    got = _train(step, cfg, len(y), xb3, jnp.asarray(y))
    want = _fused(cfg, codes, y, block=256)
    for a, b in zip(got.forest[:2] + got.forest[3:], want.forest[:2] + want.forest[3:]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_allclose(got.forest.leaf, want.forest.leaf, rtol=1e-4,
                               atol=1e-6)


def test_hybrid_round_honours_the_switch_and_its_mesh_form_refuses_it():
    """On the fused kernels the hybrid round with an identity hop is the
    fused round byte for byte; with a mesh it refuses the switch by name."""
    codes, y = _data(n=2048)
    cfg = _cfg(rounds=2)
    got = _train(jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=lambda a: a,
        interpret=True)), cfg, len(y), jnp.asarray(codes), jnp.asarray(y))
    want = _fused(cfg, codes, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.tobytes() == b.tobytes()
    with pytest.raises(ValueError, match="GBDTConfig.categorical"):
        jax.jit(functools.partial(
            gbdt.train_round_hybrid, cfg=cfg, interpret=True,
            mesh=rp.create_mesh(("dp",)))).lower(
            gbdt.init_state(cfg, len(y)), jnp.asarray(codes), jnp.asarray(y))


def test_fit_with_categorical_features():
    """The user entry: ``quantize`` keeps a categorical column's codes and
    folds what lies outside ``0..n_bins-2`` into the last code, and
    ``GBDT.fit(..., categorical_features=...)`` learns a label that is a set
    of categories no threshold forms."""
    rng = np.random.default_rng(7)
    X = rng.standard_normal((3000, 3)).astype(np.float32)
    X[:, 1] = rng.integers(0, 15, 3000)
    X[:5, 1] = [-1, 15, 16, 40, np.nan]
    y = np.isin(X[:, 1], [2, 11, 5, 13]).astype(np.float32)
    edges = gbdt.compute_bin_edges(X, 16)
    xb = np.asarray(gbdt.quantize(jnp.asarray(X), jnp.asarray(edges),
                                  categorical=(1,)))
    assert list(xb[:5, 1]) == [15, 15, 15, 15, 15]
    np.testing.assert_array_equal(xb[5:, 1], X[5:, 1])
    model = gbdt.GBDT(n_trees=3, depth=2, n_bins=16).fit(
        X, y, categorical_features=[1])
    assert model.cfg.categorical == (1,)
    assert (model.predict(X) == y).mean() == 1.0
    assert model.forest.feature[0, 0, 0] == 1 and model.forest.sets[0][:, 0].any()


def test_the_configuration_file_gives_a_config_that_jits():
    """``deployment.gbdt_config`` on ``criteo-1tb-cat-share.json`` passes
    its JSON list through, and the config holds it as a sorted tuple: it
    hashes, it is a jit static argument, and the round lowers at a small
    size with 26 categorical features."""
    import sys

    sys.path.insert(0, str(_ROOT / "benchmark"))
    from harness import deployment

    config = json.loads((_ROOT / "benchmark" / "configs"
                         / "criteo-1tb-cat-share.json").read_text())
    cfg = deployment.gbdt_config(config, gbdt.GBDTConfig)
    assert cfg.categorical == tuple(range(13, 39)) and hash(cfg)
    assert cfg == deployment.gbdt_config(config, gbdt.GBDTConfig)
    with pytest.raises(ValueError, match="categorical"):
        gbdt.GBDTConfig(n_features=4, categorical=[4])
    with pytest.raises(ValueError, match="categorical"):
        gbdt.GBDTConfig(n_features=4, categorical=[1], missing=True)
    small = cfg._replace(n_trees=1, depth=3)
    n = 2048
    jit = jax.jit(gbdt.train_round_fused, static_argnames=("cfg", "interpret"))
    jit.lower(gbdt.init_state(small, n),
              jnp.zeros((n // 1024, 1024, 67), jnp.int32),
              jnp.zeros(n, jnp.float32), cfg=small, interpret=True)


def test_the_set_table_round_trips_through_checkpoint():
    """``rabit_tpu.checkpoint`` of the forest's tables and the margin, as
    the benchmark's worker commits them, and ``load_checkpoint`` give the
    set table back bit for bit, and ``Forest(*tables)`` is a forest with
    sets again."""
    import rabit_tpu as rt

    codes, y = _data(n=2048)
    got = _fused(_cfg(rounds=2), codes, y)
    tables = tuple(np.asarray(a) for a in got.forest)
    assert len(tables) == 4 and tables[3].dtype == np.int32
    rt.init([])
    try:
        rt.checkpoint(tables, got.margin)
        version, back, margin = rt.load_checkpoint(with_local=True)
    finally:
        rt.finalize()
    assert version == 1 and len(back) == 4
    for a, b in zip(back, tables):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    assert margin.tobytes() == got.margin.tobytes()
    again = gbdt.Forest(*back)
    assert again.sets is not None and again.sets.tobytes() == tables[3].tobytes()
    dense = gbdt.init_forest(gbdt.GBDTConfig(n_features=3, n_trees=2, depth=3))
    assert len(dense) == 3 and dense.sets is None
