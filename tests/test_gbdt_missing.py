"""``GBDTConfig.missing``: code ``n_bins - 1`` is a missing value and every
split learns which way it goes (XGBoost's sparsity-aware split finding,
KDD'16 Alg. 3), on the CPU at a small size: 4,096 rows x 7 features x 16
bins, missing on 0 to 90 % of a feature's rows, depth 4, seeded.

The plain reference is the benchmark's own copy for this deployment,
``benchmark/harness/reference_missing.py`` (numpy, float64), loaded by its
path."""

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

_PATH = (pathlib.Path(__file__).resolve().parents[1] / "benchmark" / "harness"
         / "reference_missing.py")
_SPEC = importlib.util.spec_from_file_location("bench_reference_missing", _PATH)
reference = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(reference)

N, F, B, DEPTH, ROUNDS = 4096, 7, 16, 4, 3
#: per cent of each feature's rows that are missing
SHARES = [0, 10, 30, 50, 70, 90, 20]
#: the benchmark's limits for the Criteo deployment
LIMITS = json.loads(_PATH.parents[1].joinpath(
    "configs", "criteo-1tb-missing-share.limits.json").read_text())


def _data(seed=5, n=N):
    """Present codes uniform over ``0..B-2``, the top code missing on
    ``SHARES`` of the rows; the label steps on feature 0 and moves with the
    missing mass of features 2 (up) and 4 (down)."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, B - 1, size=(n, F)).astype(np.int32)
    for f, share in enumerate(SHARES):
        codes[rng.random(n) < share / 100, f] = B - 1
    y = ((codes[:, 0] > 7) + 0.8 * (codes[:, 2] == B - 1)
         - 0.8 * (codes[:, 4] == B - 1) + rng.standard_normal(n)
         > 0.8).astype(np.float32)
    return codes, y


def _cfg(missing=True, rounds=ROUNDS, depth=DEPTH):
    return gbdt.GBDTConfig(n_features=F, n_trees=rounds, depth=depth,
                           n_bins=B, learning_rate=0.3, missing=missing)


def _params(cfg):
    return reference.Params(cfg.depth, cfg.n_bins, cfg.learning_rate,
                            cfg.reg_lambda, cfg.min_child_weight)


def _train(step, cfg, n, *data):
    s = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s = step(s, *data)
    return jax.tree.map(np.asarray, s)


def _fused(cfg, codes, y):
    xb3, _ = boost.block_rows(jnp.asarray(codes), 1024)
    return _train(jax.jit(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=True)), cfg, len(y), xb3,
        jnp.asarray(y))


@pytest.mark.parametrize("path", ["train_round", "train_round_fused"])
def test_rounds_are_the_references_trees(path):
    """Three rounds, both paths: every node's ``(feature, threshold,
    direction)`` is the float64 reference's own, its leaves and the margin
    are the reference's to float32 (and the hi/lo split of the fused
    kernels), and ``predict_margin`` adds up what the round did.  Both
    directions are learned."""
    codes, y = _data()
    cfg = _cfg()
    if path == "train_round":
        got = _train(jax.jit(functools.partial(gbdt.train_round, cfg=cfg)),
                     cfg, N, jnp.asarray(codes), jnp.asarray(y))
    else:
        got = _fused(cfg, codes, y)
    free = reference.boost_rounds(codes.astype(np.uint8), y, _params(cfg),
                                  ROUNDS, procs=1)
    np.testing.assert_array_equal(got.forest.feature, free.feature)
    np.testing.assert_array_equal(got.forest.threshold, free.threshold)
    np.testing.assert_allclose(got.forest.leaf, free.leaf, rtol=1e-4,
                               atol=1e-6)
    left = got.forest.threshold >= boost.DEFAULT_LEFT
    assert left.any() and (~left & (got.forest.feature > 0)).any()
    margin = reference.margins_of(codes, *got.forest, bins=B)[-1]
    np.testing.assert_allclose(got.margin, margin, rtol=1e-5, atol=1e-5)
    predicted = gbdt.predict_margin(jax.tree.map(jnp.asarray, got.forest),
                                    jnp.asarray(codes), cfg)
    np.testing.assert_allclose(np.asarray(predicted), margin, rtol=1e-5,
                               atol=1e-5)


def test_a_flipped_direction_fails_the_comparison():
    """The planted fault: the reference following the fused round's trees
    reads nought, and with ONE node's direction flipped — of tree 1's last
    level, the node whose rows hold the most missing codes on its feature
    among those whose split keeps present codes on both sides: a split
    still valid, only worse — reads a gain gap past the benchmark's
    limit."""
    codes, y = _data()
    cfg = _cfg()
    got = _fused(cfg, codes, y)
    forest = tuple(np.array(a) for a in got.forest)
    followed = reference.boost_rounds(codes.astype(np.uint8), y, _params(cfg),
                                      ROUNDS, follow=forest, procs=1)
    assert max(followed.gain_gap) <= LIMITS["gain_gap"]
    assert max(followed.leaf_gap) <= LIMITS["leaf_gap"]
    feature, threshold = forest[0][0], forest[1][0]
    node = np.zeros(N, np.int64)
    for d in range(DEPTH - 1):
        node = node * 2 + reference.went_right(
            codes[np.arange(N), feature[d][node]], threshold[d][node], B)
    d = DEPTH - 1
    missing = np.bincount(node, minlength=2 ** d,
                          weights=codes[np.arange(N), feature[d][node]] == B - 1)
    missing[(threshold[d, :2 ** d] & 255) >= B - 2] = -1
    threshold[d, int(np.argmax(missing))] ^= boost.DEFAULT_LEFT
    flipped = reference.boost_rounds(codes.astype(np.uint8), y, _params(cfg),
                                     ROUNDS, follow=forest, procs=1)
    assert LIMITS["gain_gap"] < max(flipped.gain_gap) < 1e308


@pytest.mark.parametrize("wide", [False, True], ids=["one_tile", "tiled"])
def test_routing_sends_the_missing_code_by_the_bit(wide):
    """``route_level`` (the kernel that routes in one tile of codes, and
    the tiled pass past ``TILE_FEATS`` features), ``route_margin_level``
    and ``hist_level``'s own routing send the top code left where the
    threshold carries ``DEFAULT_LEFT`` and right where it does not, and
    every other code by ``code > thr & 255``."""
    rng = np.random.default_rng(3)
    f, nb, R = (130 if wide else 7), 2, 256
    codes = rng.integers(0, B, size=(nb, R, f)).astype(np.int32)
    codes[:, ::3, :] = B - 1
    node = rng.integers(0, 4, size=(nb, R, 1)).astype(np.int32)
    feat = jnp.asarray(rng.integers(0, f, size=4), jnp.int32)
    thr = jnp.asarray([3, 3 | boost.DEFAULT_LEFT, 14 | boost.DEFAULT_LEFT, 0],
                      jnp.int32)
    xv = np.take_along_axis(codes, np.asarray(feat)[node], 2)
    t = np.asarray(thr)[node]
    want = node * 2 + np.where(xv == B - 1, t < 256, xv > (t & 255))
    assert (want[xv == B - 1] % 2 == 0).any() and (want[xv == B - 1] % 2).any()
    x3, n3 = jnp.asarray(codes), jnp.asarray(node)
    got = boost.route_level(x3, n3, feat, thr, depth=3, interpret=True,
                            missing_code=B - 1)
    np.testing.assert_array_equal(np.asarray(got), want)
    leaf = jnp.arange(8, dtype=jnp.float32)     # a row's leaf is its node
    margin = boost.route_margin_level(
        x3, n3, jnp.zeros(nb * R), feat, thr, leaf, depth=3,
        interpret=True, missing_code=B - 1)
    np.testing.assert_array_equal(np.asarray(margin), want.reshape(-1))
    g = jnp.ones((nb, R, 1), jnp.float32)
    built = jnp.asarray([0, 1, 1, 0], jnp.int32)     # level 3 builds half
    _, got = boost.hist_level(x3, n3, g, g, feat, thr, built, depth=3,
                              n_bins=B, interpret=True, missing_code=B - 1)
    np.testing.assert_array_equal(np.asarray(got), want)
    # dense, the bit is no part of the threshold's meaning: no such table
    dense = boost.route_level(x3, n3, feat, thr & 255, depth=3, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(dense), node * 2 + (xv > (t & 255)))


def test_split_scan_scores_both_directions_and_breaks_ties_in_order():
    """One node, hand-made: the missing mass decides the direction; with no
    missing mass the direction is right; "present against missing" is a
    candidate; a node with no valid candidate gets feature 0, threshold 0
    and gain -inf, as ``best_splits`` gives it."""
    hist = np.zeros((4, 2, B, 2), np.float32)
    hist[..., 1] = 1.0                       # a row a bin, hessian 1
    hist[0, 1, :4, 0] = -2.0                 # bins 0-3 of feature 1 low
    hist[0, 1, B - 1, 0] = -2.0              # and the missing code with them
    hist[1, 1, :4, 0] = -2.0
    hist[1, 1, B - 1, 0] = 2.0               # the missing code high: right
    hist[2, :, B - 1, 0] = 3.0               # only the missing mass differs
    hist[3] = 0.0                            # no rows: no candidate
    feat, thr, gain = map(np.asarray, boost.split_scan(
        jnp.asarray(hist), depth=2, reg_lambda=1.0, min_child_weight=1.0,
        interpret=True))
    assert (feat[0], thr[0]) == (1, 3 | boost.DEFAULT_LEFT)
    assert (feat[1], thr[1]) == (1, 3)
    assert (feat[2], thr[2]) == (0, B - 2)   # present | missing, missing right
    assert (feat[3], thr[3], gain[3]) == (0, 0, -np.inf)
    # the same gains as best_splits where the missing lane is empty
    dense = hist[:2].copy()
    dense[:, :, B - 1] = 0.0
    _, t, gd = map(np.asarray, gbdt.best_splits(jnp.asarray(dense), _cfg()))
    _, t2, gm = map(np.asarray, boost.split_scan(
        jnp.asarray(dense), depth=0, reg_lambda=1.0, min_child_weight=1.0,
        interpret=True))
    np.testing.assert_array_equal(t2, t)
    np.testing.assert_allclose(gm, gd, rtol=1e-6)


def test_dense_forests_carry_no_threshold_at_256():
    """With the switch off the top code is a bin like any: no threshold
    reaches ``DEFAULT_LEFT``, on data where the switch learns directions."""
    codes, y = _data()
    dense = _fused(_cfg(missing=False), codes, y)
    assert (dense.forest.threshold < boost.DEFAULT_LEFT).all()
    assert (_fused(_cfg(), codes, y).forest.threshold
            >= boost.DEFAULT_LEFT).any()


def test_dp_fused_round_is_the_fused_round():
    """Rows over eight virtual devices under ``shard_map``: the psum'd
    histograms' scan and every routing pass give the single-device round's
    trees."""
    codes, y = _data(n=8 * 512)
    cfg = _cfg(rounds=2)
    mesh = rp.create_mesh(("dp",))
    spec = gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()), margin=P("dp"),
                           round=P())
    xb3, _ = boost.block_rows(jnp.asarray(codes), 256)
    step = jax.jit(jax.shard_map(
        functools.partial(gbdt.train_round_dp_fused, cfg=cfg, interpret=True),
        mesh=mesh, in_specs=(spec, P("dp", None, None), P("dp")),
        out_specs=spec, check_vma=False))
    got = _train(step, cfg, len(y), xb3, jnp.asarray(y))
    want = _train(jax.jit(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=True)), cfg, len(y), xb3,
        jnp.asarray(y))
    np.testing.assert_array_equal(got.forest.feature, want.forest.feature)
    np.testing.assert_array_equal(got.forest.threshold, want.forest.threshold)
    np.testing.assert_allclose(got.forest.leaf, want.forest.leaf, rtol=1e-4,
                               atol=1e-6)


def test_hybrid_round_honours_the_switch_and_its_mesh_form_refuses_it():
    """On the fused kernels the hybrid round with an identity hop is the
    fused round byte for byte; with a mesh it refuses the switch by name at
    lowering."""
    codes, y = _data(n=2048)
    cfg = _cfg(rounds=2)
    got = _train(jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=lambda a: a,
        interpret=True)), cfg, len(y), jnp.asarray(codes), jnp.asarray(y))
    want = _fused(cfg, codes, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.tobytes() == b.tobytes()
    with pytest.raises(ValueError, match="GBDTConfig.missing"):
        jax.jit(functools.partial(
            gbdt.train_round_hybrid, cfg=cfg, interpret=True,
            mesh=rp.create_mesh(("dp",)))).lower(
            gbdt.init_state(cfg, len(y)), jnp.asarray(codes), jnp.asarray(y))


def test_fit_on_an_x_with_nans():
    """The user entry: ``compute_bin_edges`` cuts the present values into
    ``n_bins - 1`` bins, ``quantize`` gives a NaN the top code, and
    ``GBDT.fit`` learns from where a value is missing: the label is the
    missingness of feature 1 and nothing else."""
    rng = np.random.default_rng(7)
    X = rng.standard_normal((2000, 4)).astype(np.float32)
    y = (rng.random(2000) < 0.4).astype(np.float32)
    X[y == 1, 1] = np.nan
    X[rng.random(2000) < 0.2, 2] = np.nan
    X[:, 3] = np.nan                                  # a feature never present
    edges = gbdt.compute_bin_edges(X, 16, missing=True)
    assert edges.shape == (4, 14) and np.isfinite(edges).all()
    xb = np.asarray(gbdt.quantize(jnp.asarray(X), jnp.asarray(edges), True))
    assert ((xb == 15) == np.isnan(X)).all() and xb[~np.isnan(X)].max() <= 14
    model = gbdt.GBDT(n_trees=3, depth=2, n_bins=16, missing=True).fit(X, y)
    assert (model.predict(X) == y).mean() == 1.0
    assert model.forest.feature[0, 0, 0] == 1
