"""GBDT flagship tests: learning on synthetic data, quantization, and
sharded (dp and dp×fp) training matching single-shard training exactly."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rabit_tpu import parallel as rp
from rabit_tpu.models import gbdt


def make_synth(n=2000, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    # nonlinear decision rule: interactions + threshold
    logits = X[:, 0] * X[:, 1] + np.sin(X[:, 2] * 2) + 0.5 * (X[:, 3] > 0.3)
    y = (logits > 0).astype(np.float32)
    return X, y


def test_quantize_roundtrip():
    X = np.array([[0.0], [1.0], [2.0], [3.0], [4.0]], np.float32)
    edges = gbdt.compute_bin_edges(X, n_bins=4)
    assert edges.shape == (1, 3)
    xb = np.asarray(gbdt.quantize(jnp.asarray(X), jnp.asarray(edges)))
    assert xb.min() >= 0 and xb.max() <= 3
    assert (np.diff(xb[:, 0]) >= 0).all()  # monotone


def test_split_child_masses_matches_routed_sums():
    """The histogram identity behind the routing-only leaf pass: children's
    (g, h) masses read off the parent histogram at the chosen split must
    equal direct segment sums over the routed rows."""
    rng = np.random.RandomState(3)
    n, F, B, n_nodes = 512, 5, 16, 4
    xb = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n), jnp.float32)
    node = jnp.asarray(rng.randint(0, n_nodes, size=n), jnp.int32)
    feat = jnp.asarray(rng.randint(0, F, size=n_nodes), jnp.int32)
    thr = jnp.asarray(rng.randint(0, B, size=n_nodes), jnp.int32)

    hist = gbdt.node_histograms(xb, g, h, node, n_nodes, B)
    masses = np.asarray(gbdt.split_child_masses(hist, feat, thr))

    # direct: route rows and sum per leaf
    fsel = np.asarray(feat)[np.asarray(node)]
    xv = np.asarray(xb)[np.arange(n), fsel]
    leaf = np.asarray(node) * 2 + (xv > np.asarray(thr)[np.asarray(node)])
    expect = np.zeros((2 * n_nodes, 2), np.float64)
    np.add.at(expect[:, 0], leaf, np.asarray(g, np.float64))
    np.add.at(expect[:, 1], leaf, np.asarray(h, np.float64))
    np.testing.assert_allclose(masses, expect, rtol=1e-5, atol=1e-5)


def test_gbdt_learns():
    X, y = make_synth()
    model = gbdt.GBDT(n_trees=15, depth=4, n_bins=64, learning_rate=0.4).fit(X, y)
    acc = (model.predict(X) == y).mean()
    assert acc > 0.93, f"train accuracy {acc}"


def test_gbdt_squared_objective():
    rng = np.random.RandomState(1)
    X = rng.randn(500, 5).astype(np.float32)
    y = (2 * X[:, 0] - X[:, 1]).astype(np.float32)
    model = gbdt.GBDT(n_trees=20, depth=3, n_bins=64, objective="squared",
                      learning_rate=0.5).fit(X, y)
    mse = float(np.mean((model.predict(X) - y) ** 2))
    assert mse < 0.4, f"mse {mse}"


def test_predict_mid_training_zero_trees():
    cfg = gbdt.GBDTConfig(n_features=4, n_trees=3, depth=3)
    forest = gbdt.init_forest(cfg)
    xb = jnp.zeros((7, 4), jnp.int32)
    out = np.asarray(gbdt.predict_margin(forest, xb, cfg))
    np.testing.assert_array_equal(out, np.zeros(7))


def test_engine_allreduce_hook_called():
    X, y = make_synth(n=300, f=4)
    calls = []

    def fake_allreduce(arr):
        calls.append(arr.shape)
        return arr

    model = gbdt.GBDT(engine_allreduce=fake_allreduce, n_trees=2, depth=3,
                      n_bins=32).fit(X, y)
    # depth histogram calls + 1 leaf call per tree
    assert len(calls) == 2 * (3 + 1)
    assert model.predict(X).shape == (300,)


@pytest.mark.parametrize("use_fp", [False, True])
def test_sharded_training_matches_single(use_fp):
    n, f = 1024, 8
    X, y = make_synth(n=n, f=f, seed=3)
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=3, depth=4, n_bins=32)
    edges = gbdt.compute_bin_edges(X, cfg.n_bins)
    xb = np.asarray(gbdt.quantize(jnp.asarray(X), jnp.asarray(edges)))

    # single-shard reference
    state = gbdt.init_state(cfg, n)
    step = jax.jit(functools.partial(gbdt.train_round, cfg=cfg))
    for _ in range(cfg.n_trees):
        state = step(state, jnp.asarray(xb), jnp.asarray(y))
    ref_forest = jax.tree.map(np.asarray, state.forest)
    ref_margin = np.asarray(state.margin)

    if use_fp:
        mesh = rp.create_mesh(("dp", "fp"), shape=(4, 2))
        in_specs = (
            gbdt.TrainState(
                forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
            ),
            P("dp", None),   # rows sharded over dp, features full (repl. over fp)
            P("dp"),
        )
        out_specs = gbdt.TrainState(
            forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
        )
        fn = jax.shard_map(
            functools.partial(gbdt.train_round_dp, cfg=cfg, dp_axis="dp", fp_axis="fp"),
            mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
        )
    else:
        mesh = rp.create_mesh(("dp",))
        fn = jax.shard_map(
            functools.partial(gbdt.train_round_dp, cfg=cfg, dp_axis="dp"),
            mesh=mesh,
            in_specs=(
                gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()),
                P("dp", None),
                P("dp"),
            ),
            out_specs=gbdt.TrainState(
                forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
            ),
            check_vma=False,
        )

    sstate = gbdt.init_state(cfg, n)
    sfn = jax.jit(fn)
    for _ in range(cfg.n_trees):
        sstate = sfn(sstate, jnp.asarray(xb), jnp.asarray(y))

    got_forest = jax.tree.map(np.asarray, sstate.forest)
    np.testing.assert_array_equal(got_forest.feature, ref_forest.feature)
    np.testing.assert_array_equal(got_forest.threshold, ref_forest.threshold)
    np.testing.assert_allclose(got_forest.leaf, ref_forest.leaf, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(sstate.margin), ref_margin, rtol=1e-4)


def _bench_like(rng, n, f, bins):
    """Bin codes uniform, the label from two features plus noise: what
    benchmark/harness/data.py draws."""
    xb = rng.randint(0, bins, size=(n, f))
    y = ((xb[:, 0] > bins // 2) + 2.56 / bins * xb[:, 1] + rng.randn(n)
         > 1.5).astype(np.float32)
    return jnp.asarray(xb, jnp.int32), jnp.asarray(y)


#: (n, features, bins, depth, row block, rounds, eta).  "criteo" is the
#: shape of benchmark/configs/criteo-1tb-share.json on three row blocks:
#: 17,152 histogram lanes, 128 parents at the last level, 256 leaves.  It
#: runs ONE round: from a zero margin g is +-0.5 and h 0.25, every sum is
#: exact in float32 in both paths, and the splits must be equal node for
#: node.  From a warm margin candidate splits that part a node's rows alike
#: tie exactly in real numbers and each path's rounding picks its own:
#: test_fused_round_from_a_warm_margin_follows_float64 covers those rounds.
#: "tiled" is wider than one tile of codes (ops.boost.TILE_FEATS): three
#: feature tiles, the last one ragged (300 = 2 x 128 + 44), eight row blocks
#: a tile sweep, routing a pass of its own a level; one round, as "criteo".
FUSED_SHAPES = {
    "small": (600, 5, 16, 3, 256, 3, 0.3),
    "criteo": (2500, 67, 256, 8, 1024, 1, 0.1),
    "tiled": (2000, 300, 64, 5, 256, 1, 0.1),
}


@pytest.mark.parametrize("shape", FUSED_SHAPES)
@pytest.mark.parametrize("whole_blocks", [False, True],
                         ids=["ragged", "whole_blocks"])
def test_train_round_fused_matches_reference(whole_blocks, shape):
    """The fused Pallas round (ops.boost, run via the Pallas interpreter on
    CPU) must grow the exact same trees as the hook-based train_round —
    with a ragged last row block, and with the rows cut to whole blocks,
    where the leaves' pass (``ops.boost.route_margin_level``: routing and
    the margin's lookup in one kernel) takes the margin with no pad."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(3)
    n, f, bins, depth, block, rounds, eta = FUSED_SHAPES[shape]
    if whole_blocks:
        n -= n % block
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=rounds, depth=depth,
                          n_bins=bins, learning_rate=eta)
    if shape == "small":
        xb = jnp.asarray(rng.randint(0, cfg.n_bins, size=(n, f)), jnp.int32)
        y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    else:
        xb, y = _bench_like(rng, n, f, bins)
    xb3, _ = boost.block_rows(xb, block)

    ref_step = jax.jit(functools.partial(gbdt.train_round, cfg=cfg))
    fused_step = functools.partial(gbdt.train_round_fused, cfg=cfg, interpret=True)
    s_ref = gbdt.init_state(cfg, n)
    s_f = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s_ref = ref_step(s_ref, xb, y)
        s_f = fused_step(s_f, xb3, y)

    fr = jax.tree.map(np.asarray, s_ref.forest)
    ff = jax.tree.map(np.asarray, s_f.forest)
    np.testing.assert_array_equal(ff.feature, fr.feature)
    np.testing.assert_array_equal(ff.threshold, fr.threshold)
    assert len(np.unique(ff.feature[0, depth - 1])) > 1   # the last level split
    # hi/lo-bf16 leaf sums carry ~2^-16-relative error vs the exact-f32 path
    np.testing.assert_allclose(ff.leaf, fr.leaf, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_f.margin), np.asarray(s_ref.margin), rtol=1e-3, atol=1e-5
    )


def _follow_round(cfg, xb, g, h, feature, threshold):
    """One round's tree followed in float64 on the same gradients: the
    widest (best gain - gain of the split taken) / children score over all
    nodes, the leaves the tree's own rows give, and each row's leaf."""
    xb = np.asarray(xb)
    (n, F), B = xb.shape, cfg.n_bins
    g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
    score = lambda a, b: a * a / (b + cfg.reg_lambda)
    node, gap = np.zeros(n, np.int64), 0.0
    for d in range(cfg.depth):
        k, at = 2 ** d, np.arange(2 ** d)
        hg, hh = (np.stack([np.bincount(node * B + xb[:, f], weights=w,
                                        minlength=k * B).reshape(k, B)
                            for f in range(F)], 1) for w in (g, h))
        GL, HL = np.cumsum(hg, -1), np.cumsum(hh, -1)     # [k, F, B]
        G, H = GL[..., -1:], HL[..., -1:]
        child = score(GL, HL) + score(G - GL, H - HL)
        valid = (HL >= cfg.min_child_weight) & (H - HL >= cfg.min_child_weight)
        gain = np.where(valid, child - score(G, H), -np.inf).reshape(k, -1)
        best = gain.argmax(-1)
        ft, th = feature[d, :k].astype(np.int64), threshold[d, :k].astype(np.int64)
        live = np.isfinite(gain[at, best])
        with np.errstate(invalid="ignore"):               # -inf less -inf
            short = np.where(live, gain[at, best] - gain[at, ft * B + th], 0.0)
        scale = np.where(live, child.reshape(k, -1)[at, best], 1.0)
        gap = max(gap, float(np.max(short / scale)))
        node = 2 * node + (xb[np.arange(n), ft[node]] > th[node])
    k = 2 ** cfg.depth
    leaf = -cfg.learning_rate * np.bincount(node, weights=g, minlength=k) / (
        np.bincount(node, weights=h, minlength=k) + cfg.reg_lambda)
    return gap, leaf, node


@pytest.mark.parametrize("f,depth,bins,n,rounds", [
    (28, 6, 256, 16384, 3), (67, 8, 256, 16384, 3), (300, 6, 64, 16384, 3),
    (2000, 8, 64, 2048, 2)], ids=["higgs", "criteo", "tiled", "epsilon"])
def test_fused_round_from_a_warm_margin_follows_float64(f, depth, bins, n,
                                                        rounds):
    """Three rounds on sixteen row blocks, so the second and third start
    from a margin that is not zero and no sum is exact: every split the
    fused round takes is the float64 scatter histogram's best along the
    same tree (an exact tie apart), and its leaves and margin are that
    tree's to the hi/lo-bf16 error.  Readings at this seed: gain gap 0.0,
    leaves 2.7e-6 of their rms, margin 9.4e-7.  "tiled" is three feature
    tiles with a ragged last one (ops.boost.TILE_FEATS).  From level 3 on
    half of each level's nodes are read off parent - sibling
    (ops.boost.hist_plan): levels 3 to 5 in "higgs" and "tiled", 3 to 7 in
    "criteo" and "epsilon" — the benchmark's widths; "epsilon" (sixteen
    feature tiles, an interpreted round 10 s) is two rounds on two row
    blocks."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(3)
    xb, y = _bench_like(rng, n, f, bins)
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=rounds, depth=depth,
                          n_bins=bins, learning_rate=0.1)
    xb3, _ = boost.block_rows(xb, 1024)
    assert boost.hist_plan(f, bins, depth - 1, 1024).nodes_derived
    step = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                                     interpret=True))
    s = gbdt.init_state(cfg, n)
    for r in range(rounds):
        before = np.asarray(s.margin, np.float64)
        g, h = gbdt.gradients(cfg, s.margin, y)
        s = step(s, xb3, y)
        forest = jax.tree.map(np.asarray, s.forest)
        gap, leaf, node = _follow_round(cfg, xb, g, h, forest.feature[r],
                                        forest.threshold[r])
        assert gap <= 1e-6
        assert np.sqrt(np.mean((forest.leaf[r] - leaf) ** 2)) <= \
            2e-5 * np.sqrt(np.mean(leaf ** 2))
        np.testing.assert_allclose(np.asarray(s.margin), before + leaf[node],
                                   rtol=0, atol=1e-5)
    assert np.abs(before).max() > 0.05      # the last round started warm


def _rare_flags(rng, n, f_rare, f_noise, bins):
    """Codes whose best splits are lopsided: ``f_rare`` features are 0 on
    99 % of the rows and 1..bins-1 on the rest, and the label leans hard on
    each flag, so down the tree's main chain every level cuts about 1 % of
    a node's rows off to the right; ``f_noise`` uniform features for the
    small nodes to split on.  (benchmark/harness/data.py draws uniform
    codes alone: children come out balanced there.)"""
    flag = rng.rand(n, f_rare) < 0.01
    rare = np.where(flag, rng.randint(1, bins, size=(n, f_rare)), 0)
    xb = np.concatenate([rare, rng.randint(0, bins, size=(n, f_noise))], 1)
    logit = -0.5 + flag @ (3.0 * (-1.0) ** np.arange(f_rare))
    y = (rng.rand(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    return jnp.asarray(xb, jnp.int32), jnp.asarray(y)


@functools.lru_cache(maxsize=None)
def _lopsided_round():
    """Depth 7 on sixteen row blocks of ``_rare_flags`` codes: two rounds
    from a zero margin, then a third, eager, whose level hook keeps what
    crosses it; ``(cfg, xb, g, h, forest, crossed)``."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(41)
    n, f_rare, f, bins, depth, block = 16384, 6, 8, 32, 7, 1024
    xb, y = _rare_flags(rng, n, f_rare, f - f_rare, bins)
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=3, depth=depth, n_bins=bins,
                          learning_rate=0.3)
    xb3, _ = boost.block_rows(xb, block)
    warm = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                                     interpret=True))
    s = gbdt.init_state(cfg, n)
    for _ in range(2):
        s = warm(s, xb3, y)
    assert np.abs(np.asarray(s.margin)).max() > 0.05
    g, h = gbdt.gradients(cfg, s.margin, y)
    crossed = []

    def hook(a):
        crossed.append(np.asarray(a))
        return a

    s = gbdt.train_round_fused(s, xb3, y, cfg, combine=hook, interpret=True)
    return cfg, xb, g, h, jax.tree.map(np.asarray, s.forest), crossed


@pytest.mark.parametrize("level", [3, 4, 5, 6])
def test_derived_levels_build_the_smaller_child_on_lopsided_splits(level):
    """Depth 7 on sixteen row blocks of ``_rare_flags`` codes from a warm
    margin, the third round eager so that the level hook sees what crosses
    it.  Levels 3 to 6 send ONE child a parent through the hook, the one
    with the smaller hessian mass by the float64 scatter histogram along
    the same tree — 1 % of the parent's rows down the main chain, a right
    child there.  Every node's histogram at those levels, built or
    parent - built (after as many derived ancestors as the level has above
    level 2), is the float64 one to the hi/lo split's round-off of the
    node's OWN mass (2.5e-6, 3.4e-6, 5.6e-6 and 5.6e-6 read at levels 3 to
    6); built the other way round (the heavier child through the kernel,
    exact), the main chain's light child is off by 20 to 110 times what it
    is built (3.9e-5 against 3.6e-7 of its mass at level 3, 5.2e-5 against
    6.6e-7 at level 5, on sixteen row blocks; the error is the parent's
    float32 summation error, and grows with the rows).  The leaves
    are the tree's own within the bound of
    test_fused_round_from_a_warm_margin_follows_float64."""
    from rabit_tpu.ops import boost

    cfg, xb, g, h, forest, crossed = _lopsided_round()
    n, f, bins = xb.shape[0], cfg.n_features, cfg.n_bins
    assert [a.shape[0] for a in crossed] == [1, 2, 4, 4, 8, 16, 32]

    gap, leaf, _ = _follow_round(cfg, xb, g, h, forest.feature[2],
                                 forest.threshold[2])
    assert gap <= 1e-6
    assert np.sqrt(np.mean((forest.leaf[2] - leaf) ** 2)) <= \
        2e-5 * np.sqrt(np.mean(leaf ** 2))

    # the same tree in float64: each level's nodes, histograms and masses
    xn, g64, h64 = np.asarray(xb), np.asarray(g, np.float64), \
        np.asarray(h, np.float64)
    node, whole = np.zeros(n, np.int64), crossed[0]
    for d in range(1, level + 1):
        ft, th = forest.feature[2, d - 1], forest.threshold[2, d - 1]
        node = 2 * node + (xn[np.arange(n), ft[node]] > th[node])
        k = 2 ** d
        if boost.hist_plan(f, bins, d, 1024).nodes_derived == 0:
            assert d < 3
            whole = crossed[d]
            continue
        built = crossed[d]
        built_right = np.asarray(gbdt.smaller_child(
            jnp.asarray(whole), jnp.asarray(ft[: k // 2]),
            jnp.asarray(th[: k // 2])))
        parents = whole
        whole = np.asarray(boost.derive_siblings(
            jnp.asarray(parents), jnp.asarray(built), jnp.asarray(built_right)))
    ref = np.stack([np.stack([
        np.bincount(node * bins + xn[:, j], weights=w,
                    minlength=k * bins).reshape(k, bins)
        for j in range(f)], 1) for w in (g64, h64)], -1)   # [k, F, B, 2]
    mass = np.bincount(node, weights=h64, minlength=k)
    pairs = mass.reshape(-1, 2)
    want_right = pairs[:, 1] < pairs[:, 0]
    np.testing.assert_array_equal(built_right, want_right)
    np.testing.assert_allclose(built[:, 0, :, 1].sum(-1), pairs.min(1),
                               rtol=1e-5)
    light = 2 * np.arange(k // 2) + built_right
    light = light[(pairs.min(1) < 0.02 * pairs.sum(1)) & (pairs.min(1) >= 1)]
    assert len(light)                         # the main chain's light child
    err = np.abs(whole - ref).max((1, 2, 3)) / np.maximum(mass, 1.0)
    assert err.max() <= 1e-5
    # the other way round: the heavier child built, the lighter derived
    heavy = ref[2 * np.arange(k // 2) + (1 - built_right)].astype(np.float32)
    flipped = np.asarray(boost.derive_siblings(
        jnp.asarray(parents), jnp.asarray(heavy),
        jnp.asarray(1 - built_right)))
    bad = np.abs(flipped - ref).max((1, 2, 3)) / np.maximum(mass, 1.0)
    assert (bad[light] >= 10 * err[light]).all()
    assert want_right[0]                      # the main chain cuts to the right


def test_fused_round_at_the_higgs_shape_is_bitwise_what_it_was():
    """F = 28, 256 bins, depth 6: ``hist_plan`` asks nothing of Mosaic that
    it did not give before, so two rounds give the bytes they gave before
    the plan existed (sha256 recorded on the parent of PR 27, same seed)."""
    import hashlib

    from rabit_tpu.ops import boost

    rng = np.random.RandomState(7)
    n, f, bins = 2500, 28, 256
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=2, depth=6, n_bins=bins)
    xb = jnp.asarray(rng.randint(0, bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    xb3, _ = boost.block_rows(xb, 1024)
    step = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                                     interpret=True))
    s = gbdt.init_state(cfg, n)
    for _ in range(2):
        s = step(s, xb3, y)
    h = hashlib.sha256()
    for a in (*s.forest, s.margin):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    assert h.hexdigest() == (
        "f6334d571d86ccc31f3b9a2cc1f3c75cbb16f0716cc8591335cdf20565689d2c")


def test_hist_plan():
    """The plan alone.  HIGGS (F 28, depth 6): every node is
    built up to level 2; from level 3 on (where one child a parent stacks
    fewer padded rows of gradient matrix than every node: 16 against 32 at
    level 3) one child a parent is, and m_pad, the accumulator block and
    the VMEM ask are those of the level above.  Every HIGGS level stacks
    less than a full tile, so every one packs the codes four a word (matmul
    groups of ``GROUP_WORDS`` words: 4,096 lanes), and level 5 asks Mosaic
    for the stack that the packed kernel's matmul results take (five blocks
    more); level 4's 32 stacked rows fit the default.  Criteo (F 67, depth
    8): one accumulator block a level, over 68 feature slots (whole words)
    where the level packs (up to level 5) and over the 67 features from a
    full tile of stacked rows on, where the MXU holds the gradient planes
    and the block is the transposed ``(67 x 256, m_pad)``, its node lanes
    whole registers (level 6's 64 take 128): 8.4 MiB at levels 6 and 7,
    every kernel inside what it may ask for.  The halved block lets levels
    8 and 9 through (16.75 and 33.5 MiB: depths 9 and 10); level 10 is
    refused by name."""
    from rabit_tpu.ops import boost

    assert boost._pick_fc(28, 256, True) == 4 * boost.GROUP_WORDS == 16
    assert boost._pick_fc(28, 256, False) == 7
    for d in range(1, 6):
        p = boost.hist_plan(28, 256, d, 1024)
        built = 2 ** d if d < 3 else 2 ** (d - 1)
        assert (p.nodes_built, p.nodes_derived) == (built, 2 ** d - built)
        assert p.m_pad == max(8, 2 * built)
        assert p.acc_block_bytes == p.m_pad * 28 * 256 * 4
        assert p.packed and p.regs_a_broadcast == 8
        assert (p.vmem_bytes <= boost.VMEM_DEFAULT) == (d <= 4)
    assert boost.hist_plan(28, 256, 2, 1024).m_rows == boost.MXU_ROWS // 8
    assert boost.hist_plan(28, 256, 3, 1024).m_rows == boost.MXU_ROWS // 8
    assert boost.hist_plan(28, 256, 4, 1024).m_rows == boost.MXU_ROWS // 4
    assert boost.hist_plan(28, 256, 5, 1024).m_rows == boost.MXU_ROWS // 2
    assert boost.hist_plan(28, 256, 0, 1024).nodes_derived == 0

    # level: (nodes built, nodes derived, m_pad, MXU tiles of M)
    want = {2: (4, 0, 8, 1), 3: (4, 4, 8, 1), 4: (8, 8, 16, 1),
            5: (16, 16, 32, 1), 6: (32, 32, 64, 1), 7: (64, 64, 128, 2),
            8: (128, 128, 256, 4)}
    for d in range(1, 10):
        p = boost.hist_plan(67, 256, d, 1024)
        assert p.packed == (d <= 5) == (p.m_rows < boost.MXU_ROWS)
        assert p.mxu_holds == ("indicator" if d <= 5 else "gradients")
        assert p.acc_block_bytes == (p.m_pad * 68 if p.packed else
                                     max(p.m_pad, 128) * 67) * 256 * 4
        assert p.acc_block_bytes + (5 << 20) < p.vmem_bytes <= boost.VMEM_MOST
        if d in want:
            assert (p.nodes_built, p.nodes_derived, p.m_pad, p.m_tiles) == want[d]
        if d >= 3:      # the rows every node of the level above stacks
            assert (p.m_pad, p.nodes_built) == (2 * 2 ** (d - 1), 2 ** (d - 1))
    assert boost.hist_plan(67, 256, 7, 1024).acc_block_bytes == 128 * 67 * 1024
    assert boost.hist_plan(67, 256, 7, 1024).vmem_bytes > boost.VMEM_DEFAULT
    assert boost.hist_plan(67, 256, 0, 1024).vmem_bytes > boost.VMEM_DEFAULT

    with pytest.raises(ValueError, match=r"level 10 of F=67 .*bytes") as e:
        boost.hist_plan(67, 256, 10, 1024)
    assert str(boost.VMEM_MOST) in str(e.value)
    for f, d in ((28, 5), (67, 7), (128, 3)):
        p = boost.hist_plan(f, 256, d, 1024)
        assert (p.tile_feats, p.feat_tiles) == (f, 1)


def test_hist_plan_tiles_a_wide_matrix():
    """Wider than one tile of codes the plan walks the features in tiles of
    128, the last one ragged, and reckons ONE tile's accumulator block,
    counted twice: Epsilon (F 2000, 64 bins at 64 lanes a feature, two
    features a register, depth 8) is sixteen tiles a level, and with one
    child a parent built from level 3 on a 4 MiB block and 20.5 MiB asked
    at level 7 (8 and 28 at 128 lanes a feature, before PR 35; 16 and 44
    with every node built besides) — a width whose one-block accumulator
    nothing holds.  From level 6 on the MXU holds the gradient planes: the
    block is the transposed ``(128 x 64, m_pad)``, its node lanes whole
    registers (level 6's 64 take 128: 4 MiB, not 2), and the ``[128,
    1024]`` int32 scratch the codes are transposed into is counted, 0.5
    MiB; so level 6 is the first to pass Mosaic's default.  The halved
    lanes let levels 8 and 9 through (8 and 16 MiB blocks, 28.5 and 44.5
    MiB: depths 9 and 10); level 10 is refused by name, with the tile's
    block in the text.  At 65 bins and more a feature takes whole registers
    and the groups and blocks are what they were."""
    from rabit_tpu.ops import boost

    assert boost._pick_tile_fc(64) == 2 * boost._pick_tile_fc(128) == 32
    assert boost._pick_tile_fc(256) == 8
    assert boost.hist_plan(129, 64, 3, 1024).feat_tiles == 2
    for d in range(10):
        p = boost.hist_plan(2000, 64, d, 1024)
        assert (p.tile_feats, p.feat_tiles, p.lanes_a_feature) == (128, 16, 64)
        built = 2 ** d if d < 3 else 2 ** (d - 1)
        assert (p.nodes_built, p.nodes_derived) == (built, 2 ** d - built)
        assert p.m_pad == max(8, 2 * built)
        held = d >= 6
        assert p.mxu_holds == ("gradients" if held else "indicator")
        node_lanes = max(p.m_pad, 128) if held else p.m_pad
        assert p.acc_block_bytes == node_lanes * 128 * 64 * 4
        assert p.vmem_bytes == (2 * p.acc_block_bytes + 2 * 4 * 1024 * 4 * 128
                                + boost.VMEM_STACK + (512 << 10) * held)
        wide = boost.hist_plan(2000, 128, d, 1024) if d < 9 else None
        if wide:
            assert wide.acc_block_bytes == node_lanes * 128 * 128 * 4
            assert wide[:3] + wide[5:7] == p[:3] + p[5:7]
    assert p.acc_block_bytes == 16 << 20 and p.vmem_bytes == 89 << 19
    p = boost.hist_plan(2000, 64, 7, 1024)
    assert p.acc_block_bytes == 4 << 20 and p.vmem_bytes == 41 << 19
    assert boost.hist_plan(2000, 128, 7, 1024)[3:5] == (8 << 20, 57 << 19)
    assert boost.VMEM_DEFAULT < p.vmem_bytes <= boost.VMEM_MOST
    assert boost.hist_plan(2000, 64, 6, 1024)[3:5] == (4 << 20, 41 << 19)
    assert boost.hist_plan(2000, 64, 5, 1024).vmem_bytes == 14 << 20
    with pytest.raises(ValueError, match=r"level 10 of F=2000 .*128-feature "
                                         r"tile is 33554432 bytes"):
        boost.hist_plan(2000, 64, 10, 1024)
    with pytest.raises(ValueError, match=r"level 9 of F=2000 features x 128 "
                                         r"bins .*tile is 33554432 bytes"):
        boost.hist_plan(2000, 128, 9, 1024)


@pytest.mark.parametrize("d", [0, 3])
def test_tiled_histogram_is_bitwise_the_one_block_histogram(d, monkeypatch):
    """F = 300 at 64 bins, eight row blocks: three feature tiles, the last
    one of 44 features, against the same level as ONE accumulator block
    (what the plan gives with a tile as wide as the matrix).  A lane's sum
    over the rows does not depend on which tile holds the lane, nor on how
    many features share a matmul, so the two are equal bit for bit; and the
    tiled routing pass moves every row where the one-block kernel does.
    Level 3 builds one child a parent, the one a random ``built_right``
    names, in both forms."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(29 + d)
    n, F, B, block = 2048, 300, 64, 256
    xb = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n), jnp.float32)
    xb3, g3, h3 = (boost.block_rows(a, block)[0] for a in (xb, g, h))
    if d == 0:
        level = functools.partial(boost.hist_level0.__wrapped__, xb3, g3, h3,
                                  n_bins=B, interpret=True)
    else:
        n_prev = 1 << (d - 1)
        node3, _ = boost.block_rows(
            jnp.asarray(rng.randint(0, n_prev, size=n), jnp.int32), block)
        # splits in every tile, the ragged one's last feature among them
        feat = jnp.asarray([0, 299, 130, 255][:n_prev], jnp.int32)
        thr = jnp.asarray(rng.randint(8, B - 8, size=n_prev), jnp.int32)
        right = jnp.asarray(rng.randint(0, 2, size=n_prev), jnp.int32)
        assert boost.hist_plan(F, B, d, block).nodes_derived == n_prev
        level = functools.partial(boost.hist_level.__wrapped__, xb3, node3, g3,
                                  h3, feat, thr, right, depth=d, n_bins=B,
                                  interpret=True)
    assert boost.hist_plan(F, B, d, block).feat_tiles == 3
    tiled = level()
    monkeypatch.setattr(boost, "TILE_FEATS", 512)
    assert boost.hist_plan(F, B, d, block).feat_tiles == 1
    one_block = level()
    for a, b in zip(jax.tree.leaves(tiled), jax.tree.leaves(one_block)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hist = tiled if d == 0 else tiled[0]
    built = boost.hist_plan(F, B, d, block).nodes_built
    assert hist.shape == (built, F, B, 2)
    assert np.asarray(hist)[..., 1].sum(-1).min() > 0     # every node got rows


def test_tiled_round_at_the_child_weight_floor_follows_the_reference():
    """``min_child_weight`` 100 (Epsilon's min_sum_hessian_in_leaf) on
    4,096 rows x 300 features: from a zero margin a row weighs 0.25, so the
    root's extreme bins are invalid candidates, at level 3 a node of some
    512 rows has no valid candidate at all, and the floor decides every
    level.  The first round is the plain reference's
    (benchmark/harness/reference.py, numpy float64, the same floor) node for
    node; a second, from a warm margin, is followed by it within the
    benchmark's limits."""
    import importlib.util
    import pathlib

    from rabit_tpu.ops import boost

    path = pathlib.Path(__file__).resolve().parents[1] / \
        "benchmark" / "harness" / "reference.py"
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)

    rng = np.random.RandomState(5)
    n, f, bins, depth, rounds = 4096, 300, 64, 4, 2
    xb, y = _bench_like(rng, n, f, bins)
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=rounds, depth=depth,
                          n_bins=bins, learning_rate=0.1,
                          min_child_weight=100.0)
    xb3, _ = boost.block_rows(xb, 1024)
    step = jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                                     interpret=True))
    s = gbdt.init_state(cfg, n)
    for _ in range(rounds):
        s = step(s, xb3, y)
    forest = jax.tree.map(np.asarray, s.forest)
    params = reference.Params(depth, bins, 0.1, 1.0, 100.0)
    codes, y64 = np.asarray(xb).astype(np.uint8), np.asarray(y, np.float64)
    free = reference.boost_rounds(codes, y64, params, 1, procs=1)
    np.testing.assert_array_equal(forest.feature[0], free.feature[0])
    np.testing.assert_array_equal(forest.threshold[0], free.threshold[0])
    # the floor bit: the root split off the extreme bins, level 3 not at all
    assert 8 <= forest.threshold[0, 0, 0] < bins - 8
    assert not forest.feature[0, 3].any() and not forest.threshold[0, 3].any()
    followed = reference.boost_rounds(codes, y64, params, rounds, procs=1,
                                      follow=tuple(forest))
    assert max(followed.gain_gap) <= 2e-5 and max(followed.leaf_gap) <= 1e-4
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(s.margin, np.float64)),
        followed.margin_norm[-1], rtol=5e-6)



@pytest.mark.parametrize("d", [4, 6, 7, 2])
def test_hist_level_at_the_criteo_width_matches_scatter(d):
    """Levels 2, 4, 6 and 7 at 67 features x 256 bins, one accumulator
    block of 17,152 lanes.  The kernel routes as the tables say.  Level 2
    builds every node: its histogram is the scatter reference's at the
    routed nodes.  Levels 4, 6 and 7 build ONE child a parent (32, 128 and
    256 stacked rows of gradient matrix: a quarter of an MXU tile, one and
    two), the one ``built_right`` names: the histogram is the scatter
    reference's at those children, in parent order, and ``derive_siblings``
    with the parents' histogram gives the level whole, in node order."""
    from rabit_tpu.ops import boost, hist as H

    rng = np.random.RandomState(13 + d)
    n, F, B, block = 2048, 67, 256, 1024
    n_prev = 1 << (d - 1)
    xb = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n), jnp.float32)
    node = jnp.asarray(rng.randint(0, n_prev, size=n), jnp.int32)
    feat = jnp.asarray(rng.randint(0, F, size=n_prev), jnp.int32)
    thr = jnp.asarray(rng.randint(0, B, size=n_prev), jnp.int32)
    blocked = [boost.block_rows(a, block)[0] for a in (xb, node, g, h)]
    derived = boost.hist_plan(F, B, d, block).nodes_derived
    assert derived == (n_prev if d >= 3 else 0)
    built_right = rng.randint(0, 2, size=n_prev) if derived else None
    hist, node_out = boost.hist_level(
        *blocked, feat, thr,
        None if built_right is None else jnp.asarray(built_right, jnp.int32),
        depth=d, n_bins=B, interpret=True)
    assert hist.shape == (2 * n_prev - derived, F, B, 2)
    routed = boost.unblock_rows(node_out, n)
    went_right = np.asarray(xb)[np.arange(n), np.asarray(feat)[node]] > \
        np.asarray(thr)[node]
    np.testing.assert_array_equal(np.asarray(routed),
                                  2 * np.asarray(node) + went_right)
    ref = np.asarray(H.node_histograms_scatter(xb, g, h, routed, 2 * n_prev, B))
    if not derived:
        np.testing.assert_allclose(np.asarray(hist), ref, rtol=1e-4, atol=1e-4)
        with pytest.raises(ValueError, match=f"level {d} builds {2 * n_prev} "
                                             f"of {2 * n_prev} nodes"):
            boost.hist_level(*blocked, feat, thr, jnp.zeros(n_prev, jnp.int32),
                             depth=d, n_bins=B, interpret=True)
        return
    assert 0 < built_right.sum() < n_prev
    np.testing.assert_allclose(
        np.asarray(hist), ref[2 * np.arange(n_prev) + built_right],
        rtol=1e-4, atol=1e-4)
    parents = H.node_histograms_scatter(xb, g, h, node, n_prev, B)
    whole = boost.derive_siblings(parents, hist, jnp.asarray(built_right))
    np.testing.assert_allclose(np.asarray(whole), ref, rtol=1e-4, atol=2e-4)
    with pytest.raises(ValueError, match=rf"builds {n_prev} of {2 * n_prev} "
                                         rf"nodes.*built_right \[{n_prev}\]"):
        boost.hist_level(*blocked, feat, thr, depth=d, n_bins=B, interpret=True)


def _numpy_histogram(xb, g, h, node, n_nodes, bins):
    """[n_nodes, F, bins, 2] float64 sums of g and h a (node, feature, code)."""
    n, f = xb.shape
    ref = np.zeros((n_nodes, f, bins, 2))
    cols = np.broadcast_to(np.arange(f), (n, f))
    np.add.at(ref, (node[:, None], cols, xb, 0), g[:, None])
    np.add.at(ref, (node[:, None], cols, xb, 1), h[:, None])
    return ref


@pytest.mark.parametrize("tiling", ["one-tile", "tiled"])
@pytest.mark.parametrize("d", [0, 2, 5], ids=["root", "built", "derived"])
@pytest.mark.parametrize("F,bins", [(5, 64), (6, 33), (7, 17), (130, 64),
                                    (131, 64), (28, 256), (67, 256), (5, 256),
                                    (6, 128), (130, 256)])
def test_two_features_a_register_match_a_numpy_histogram(F, bins, d, tiling,
                                                         monkeypatch):
    """The kernels pack the codes four a word at every width
    (ops.boost._accum); at up to 64 bins a feature takes 64 lanes and two
    share a register (ops.boost._bins_eff), at 65 to 128 bins it takes one
    register and at 256 two, the two of a byte sharing its and: the root, a
    level with every node built and a derived one (one child a parent)
    against numpy float64 sums, as ONE accumulator block and walked in
    feature tiles.  The counts are odd and no whole words (a last word of
    one, two or three features), fewer bins than lanes (33, 17), HIGGS's
    and Criteo's widths (28: seven whole words; 67: a 68th slot), and the
    ragged last tile holds one, two or three features: of 130 and 131
    behind a whole tile of 128, of the others behind tiles of 4
    (``TILE_FEATS`` cut for the test)."""
    from rabit_tpu.ops import boost

    one_tile = tiling == "one-tile"
    if one_tile == (F > boost.TILE_FEATS):
        monkeypatch.setattr(boost, "TILE_FEATS", 512 if one_tile else 4)
    rng = np.random.RandomState(F + bins + d)
    n, block = 512, 256
    plan = boost.hist_plan(F, bins, d, block)
    lanes = 64 if bins <= 64 else 128 if bins <= 128 else 256
    assert plan.lanes_a_feature == lanes
    assert plan.regs_a_broadcast == {64: 2, 128: 4, 256: 8}[lanes]
    assert (plan.feat_tiles == 1) == one_tile
    assert plan.acc_block_bytes == plan.m_pad * 4 * lanes * (
        -(-F // 4) * 4 if one_tile else plan.tile_feats)
    xb = rng.randint(0, bins, size=(n, F))
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    hist, ref = _level_and_numpy(boost, plan, xb, g, h, rng, bins, d, block)
    assert hist.shape == ref.shape == (plan.nodes_built, F, bins, 2)
    np.testing.assert_allclose(np.asarray(hist), ref, rtol=1e-4, atol=1e-4)
    assert np.abs(ref).sum((0, 2, 3)).min() > 0     # every feature has mass


def _level_and_numpy(boost, plan, xb, g, h, rng, bins, d, block):
    """Level ``d``'s kernel over ``xb`` (interpreted; routed through random
    split tables below the root, the last feature among them) and the numpy
    float64 histogram of the nodes it builds."""
    n, F = xb.shape
    xb3, g3, h3 = (boost.block_rows(jnp.asarray(a), block)[0]
                   for a in (xb.astype(np.int32), g, h))
    if d == 0:
        hist = boost.hist_level0.__wrapped__(xb3, g3, h3, n_bins=bins,
                                             interpret=True)
        return hist, _numpy_histogram(xb, g, h, np.zeros(n, int), 1, bins)
    n_prev = 2 ** (d - 1)
    node = rng.randint(0, n_prev, size=n)
    # splits on the last feature (the odd one's half register) too
    feat = np.r_[F - 1, rng.randint(0, F, size=n_prev - 1)]
    thr = rng.randint(0, bins, size=n_prev)
    built_right = rng.randint(0, 2, size=n_prev) if plan.nodes_derived else None
    hist, node_out = boost.hist_level.__wrapped__(
        xb3, boost.block_rows(jnp.asarray(node, jnp.int32), block)[0],
        g3, h3, jnp.asarray(feat, jnp.int32), jnp.asarray(thr, jnp.int32),
        None if built_right is None else jnp.asarray(built_right, jnp.int32),
        depth=d, n_bins=bins, interpret=True)
    routed = 2 * node + (xb[np.arange(n), feat[node]] > thr[node])
    np.testing.assert_array_equal(
        np.asarray(boost.unblock_rows(node_out, n)), routed)
    ref = _numpy_histogram(xb, g, h, routed, 2 * n_prev, bins)
    if plan.nodes_derived:
        ref = ref[2 * np.arange(n_prev) + built_right]
    return hist, ref


@pytest.mark.parametrize("d", [0, 2, 5], ids=["root", "built", "derived"])
@pytest.mark.parametrize("F", [8, 67, 130], ids=["words", "criteo", "tiled"])
def test_byte_3_of_a_word_meets_the_sign_bit(F, d):
    """256 bins, every code 255 or 128 (one of the two a row, the same in
    every slot): each word of four packed codes is 0xFFFFFFFF or 0x80808080,
    so byte 3's mask (255 << 24) and its keys (bins 128 to 255, shifted into
    the sign bit) are met in every word, and a word compares as a negative
    int32.  All the mass lies in bins 128 and 255 of every feature, in the
    numpy sums' amounts; a key that overflowed, or a mask that lost its top
    bit, would leave byte 3's features (the last quarter of the slots)
    empty or put their mass elsewhere."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(300 + F + d)
    n, block, bins = 512, 256, 256
    plan = boost.hist_plan(F, bins, d, block)
    assert plan.regs_a_broadcast == 8
    xb = np.repeat(np.where(rng.rand(n, 1) < 0.5, 255, 128), F, axis=1)
    # a few rows of mixed words, so that routing below the root has two sides
    xb[::7] = rng.choice([128, 255], size=xb[::7].shape)
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    hist, ref = _level_and_numpy(boost, plan, xb, g, h, rng, bins, d, block)
    hist = np.asarray(hist)
    np.testing.assert_allclose(hist, ref, rtol=1e-4, atol=1e-4)
    others = np.delete(hist, [128, 255], axis=2)
    assert not others.any()
    last_quarter = hist[:, -(-F // 4) * 3:]            # byte 3's features
    assert np.abs(last_quarter[:, :, [128, 255], 1]).sum((0, 2)).min() > 0


@pytest.mark.parametrize("bins,lanes,regs", [(64, 64, 2), (65, 128, 4),
                                             (128, 128, 4), (256, 256, 8)])
def test_lanes_a_feature_follow_the_bins(bins, lanes, regs):
    """``_bins_eff``: 64 lanes a feature at up to 64 bins, else the bins
    padded to whole 128-lane registers; the matmul groups and the plan's
    blocks follow it.  Below a full MXU tile of stacked rows the kernel
    packs the codes four a word, and the registers one lane broadcast
    serves are four features': 2, 4, 8; its groups are whole words of four
    slots, seven at up to 64 bins, ``GROUP_WORDS`` above.  From a full tile
    on (level 6) the MXU holds the gradient planes at every width: nothing
    is packed, a feature's bins lie down the sublanes of the transposed
    block and one sublane broadcast of a row of codes serves its
    ``lanes / 8`` registers (8, 16, 32), in groups of 32, 16 and 8
    features (2,048 rows of indicator a dot at every width).  Under
    ``mxu_i8`` the MXU holds the indicator there as before: the 64-lane kernel packs and the wider ones broadcast a code
    column a feature, one register or two.  Codes wider than a byte are
    never packed."""
    from rabit_tpu.ops import boost

    assert boost._bins_eff(bins) == lanes
    assert boost._bins_eff(bins - 1 if bins != 65 else 127) == lanes
    assert boost._pick_fc(2000, bins, False) == 1792 // lanes
    assert boost._pick_fc(2000, bins, True) == (
        28 if lanes == 64 else 4 * boost.GROUP_WORDS)
    assert boost._pick_fc(8, bins, True) == 8
    p = boost.hist_plan(100, bins, 3, 1024)
    assert (p.lanes_a_feature, p.regs_a_broadcast, p.packed) == (lanes, regs, True)
    assert p.acc_block_bytes == p.m_pad * 100 * lanes * 4
    assert boost.hist_plan(99, bins, 3, 1024).acc_block_bytes == p.acc_block_bytes
    full = boost.hist_plan(99, bins, 6, 1024)
    assert full.m_rows == boost.MXU_ROWS
    assert (full.mxu_holds, full.packed, full.regs_a_broadcast) == (
        "gradients", False, lanes // 8)
    assert full.acc_block_bytes == 128 * 99 * lanes * 4
    assert boost._pick_held_fc(bins) == {64: 32, 128: 16, 256: 8}[lanes]
    i8 = boost.hist_plan(99, bins, 6, 1024, mxu_i8=True)
    assert (i8.mxu_holds, i8.packed, i8.regs_a_broadcast) == (
        ("indicator", True, 2) if lanes == 64 else
        ("indicator", False, lanes // 128))
    assert i8.acc_block_bytes == i8.m_pad * (100 if i8.packed else 99) * lanes * 4
    wide = boost.hist_plan(100, 257, 3, 1024)
    assert (wide.lanes_a_feature, wide.regs_a_broadcast, wide.packed) == (
        384, 3, False)


@pytest.mark.parametrize("F,last", [(28, 5), (67, 9)], ids=["higgs", "criteo"])
def test_hist_plan_at_256_bins_is_field_for_field_what_it_was(F, last):
    """What PRs 37, 39 and 41 moved of the plan at 256 bins, and nothing
    else.  Levels 3 and 4 build one child a parent, so they take
    level 2's and level 3's block.  Below a full MXU tile of stacked rows
    the kernel packs the codes four a
    word (``regs_a_broadcast`` 8): HIGGS's 28 features are seven whole
    words, so its block is what it was, and Criteo's grows by the 68th slot
    alone; both ask five blocks more of VMEM for the packed kernel's stack.
    From a full tile on (levels 6 to 9 of Criteo) the MXU holds the
    gradient planes (PR 39): the block is ``(F x 256, m_pad)`` with its node
    lanes whole registers, the row blocks and the stack are what the
    parent of PR 35 reckoned, the ``[128, 1024]`` int32 scratch of the
    transposed codes comes on top, and ``regs_a_broadcast`` is 32, a
    sublane broadcast a feature."""
    from rabit_tpu.ops import boost

    for d in range(last + 1):
        built = 2 ** d if d < 3 else 2 ** (d - 1)
        m_pad = max(8, 2 * built)
        packed, held = d <= 5, d >= 6
        acc = (F * max(m_pad, 128) if held else
               m_pad * (-(-F // 4) * 4 if packed else F)) * 256 * 4
        vmem = acc + 2 * 4 * 1024 * (128 + 4 * 128) + boost.VMEM_STACK + (
            5 * acc if packed else 0) + 4 * 1024 * 128 * held
        assert boost.hist_plan(F, 256, d, 1024) == boost.HistPlan(
            level=d, nodes_derived=2 ** d - built, m_pad=m_pad,
            acc_block_bytes=acc, vmem_bytes=vmem, tile_feats=F, feat_tiles=1,
            lanes_a_feature=256, regs_a_broadcast=32 if held else 8,
            mxu_holds="gradients" if held else "indicator")
    # HIGGS, level 5: the block's 917,504 bytes as they were, 19,136,512 asked
    # (14,548,992 + five blocks)
    assert boost.hist_plan(28, 256, 5, 1024)[:7] == (
        5, 16, 32, 917504, 19136512, 28, 1)
    # Criteo, level 5: 32 x 68 x 256 x 4 = 2,228,224 bytes (2,195,456 at 67
    # slots), 27,000,832 asked; level 7, the gradients held, is the parent's
    # block and 22,413,312 bytes asked, and 524,288 more for the scratch
    assert boost.hist_plan(67, 256, 5, 1024)[:7] == (
        5, 16, 32, 2228224, 27000832, 67, 1)
    assert boost.hist_plan(67, 256, 7, 1024)[:7] == (
        7, 64, 128, 8781824, 22413312 + 524288, 67, 1)


def _kernel_jaxpr(fn, *shapes):
    """The jaxpr of the (last) Pallas kernel that ``fn`` traces to."""
    calls = [e for e in jax.make_jaxpr(fn)(*shapes).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    return calls[-1].params["jaxpr"]


def _count_eqns(jaxpr, pick, into=None):
    """Equations of ``jaxpr`` and of every jaxpr nested in it, by ``pick``'s
    name for them (None: not counted)."""
    import collections

    into = collections.Counter() if into is None else into
    for e in jaxpr.eqns:
        name = pick(e)
        if name:
            into[name] += 1
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count_eqns(sub, pick, into)
    return into


def _build_eqn(e):
    """roll, dot_general, and the lane broadcast of a column of packed
    words: an ``and`` of a one-lane operand with a register-wide mask."""
    name = e.primitive.name
    if name in ("roll", "dot_general"):
        return name
    if name == "and" and e.outvars[0].aval.shape[-1:] == (128,) and any(
            getattr(v.aval, "shape", ())[-1:] == (1,) for v in e.invars):
        return "lane_broadcast"
    return None


@pytest.mark.parametrize("d", [0, 5])
def test_the_epsilon_kernels_are_the_parent_of_pr_37s(d):
    """The control: at Epsilon's shape (F 2,000 x 64 bins, 391 row blocks)
    the packed build was there already, and PR 37, which packs at every
    width, changes nothing of it.  ``hist_plan`` is field for field what it
    was at levels 0 and 5 (level 7, where the MXU holds the gradients since
    PR 39, is ``test_held_kernel_streams_the_indicator_through_the_planes``'s),
    and the traced tile kernel has the parent's
    equations: a tile of 128 features is four matmul groups of eight words,
    so 3 rolls a block, 4 ``dot_general`` and 64 lane broadcasts (one a
    word, serving two registers each: ``regs_a_broadcast`` 2) — counted off
    the parent's jaxpr (commit 54925f3) and off this tree's alike.  The
    whole jaxpr's digest (tests/test_round_jaxpr.py's own) is that
    parent's at level 0; level 5's was re-recorded when the built-child
    table came to hold the built children's node ids, one compare a lane
    against a row's node (``_gradient_matrix``), where it had held the
    side a parent builds, and again when the level's routing pass
    (``route_level``) came to write the routed node ids over the ids it
    reads (``input_output_aliases``)."""
    from rabit_tpu.ops import boost
    from tests.test_round_jaxpr import digest

    nb, R, F, B = 391, 1024, 2000, 64
    built = 2 ** d if d < 5 else 2 ** (d - 1)
    m_pad = max(8, 2 * built)
    acc = m_pad * 128 * 64 * 4
    plan = boost.hist_plan(F, B, d, R)
    assert plan == boost.HistPlan(
        level=d, nodes_derived=2 ** d - built, m_pad=m_pad,
        acc_block_bytes=acc,
        vmem_bytes=2 * acc + 2 * 4 * R * 4 * 128 + boost.VMEM_STACK,
        tile_feats=128, feat_tiles=16, lanes_a_feature=64, regs_a_broadcast=2)
    assert plan.packed
    sds = jax.ShapeDtypeStruct
    rows = lambda dt: sds((nb, R, 1), dt)
    if d == 0:
        fn = functools.partial(boost.hist_level0.__wrapped__, n_bins=B)
        shapes = [sds((nb, R, F), jnp.int32), rows(jnp.float32),
                  rows(jnp.float32)]
    else:
        fn = functools.partial(boost.hist_level.__wrapped__, depth=d, n_bins=B)
        tab = sds((2 ** (d - 1),), jnp.int32)
        shapes = [sds((nb, R, F), jnp.int32), rows(jnp.int32),
                  rows(jnp.float32), rows(jnp.float32), tab, tab] + (
                      [tab] if plan.nodes_derived else [])
    assert _count_eqns(_kernel_jaxpr(fn, *shapes), _build_eqn) == {
        "roll": 3, "dot_general": 4, "lane_broadcast": 64}
    # level 0 recorded on 54925f3, level 5 on this tree; this file's shapes
    assert digest(fn, *shapes) == {0: "fc67337e22ece3c0",
                                   5: "69eef720bed12dc6"}[d]


def _level_shapes(nb, R, F, d, derived):
    """``hist_level``'s arguments at a shape, as ShapeDtypeStructs."""
    sds = jax.ShapeDtypeStruct
    rows = lambda dt: sds((nb, R, 1), dt)
    tab = sds((2 ** (d - 1),), jnp.int32)
    return [sds((nb, R, F), jnp.int32), rows(jnp.int32), rows(jnp.float32),
            rows(jnp.float32), tab, tab] + ([tab] if derived else [])


@pytest.mark.parametrize("d", [6, 7])
@pytest.mark.parametrize("shape", ["criteo", "epsilon"])
def test_held_kernel_streams_the_indicator_through_the_planes(shape, d):
    """From a full MXU tile of stacked rows on (levels 6 and 7 at depth 8)
    the MXU holds the gradient planes: at Criteo's shape (one block of 67
    features x 256 bins) and Epsilon's (tiles of 128 features x 64 bins)
    the traced kernel transposes its block of codes once, rolls nothing,
    pays no lane broadcast, compares one ``(B_eff, R)`` register block a
    feature (a row of codes broadcast down the sublanes against a sublane
    iota) and contracts each group of ``_pick_held_fc`` features as
    ``[k * B_eff, R] @ [R, 2 m]``, the planes the right-hand operand, the
    one the MXU holds.  The whole groups (8 of 8 features at Criteo, 4 of
    32 at Epsilon) are traced one after another where the block's MXU work
    fits a grid step (Criteo's level 6, Epsilon's tile) and as a loop of
    two steps of four groups at Criteo's level 7, whose unrolled step would
    outgrow the instruction memory (``_groups_a_body``; PERF.md section 5,
    PR 39); the ragged last group (Criteo's last 3 features) is traced
    after them."""
    from rabit_tpu.ops import boost

    nb, F, B = {"criteo": (2560, 67, 256), "epsilon": (391, 2000, 64)}[shape]
    R = 1024
    plan = boost.hist_plan(F, B, d, R)
    be = plan.lanes_a_feature
    assert (plan.mxu_holds, plan.packed, plan.regs_a_broadcast) == (
        "gradients", False, be // 8)
    assert plan.m_rows == 2 ** (d + 1) >= boost.MXU_ROWS
    fc = boost._pick_held_fc(B)
    feats = plan.tile_feats
    jaxpr = _kernel_jaxpr(
        functools.partial(boost.hist_level.__wrapped__, depth=d, n_bins=B),
        *_level_shapes(nb, R, F, d, True))

    def pick(e):
        name = e.primitive.name
        if name == "dot_general":
            lhs, rhs = (v.aval.shape for v in e.invars)
            assert e.params["dimension_numbers"] == (((1,), (0,)), ((), ()))
            assert rhs == (R, 2 * plan.m_pad) and lhs[1] == R
            return name
        if name == "eq" and e.outvars[0].aval.shape == (be, R):
            return "sublane_broadcast"
        if name in ("scan", "while"):
            return f"loop of {e.params.get('length')}"
        return _build_eqn(e) or (name if name == "transpose" else None)

    full, last = divmod(feats, fc)
    assert (full, last) == {"criteo": (8, 3), "epsilon": (4, 0)}[shape]
    per = 4 if (shape, d) == ("criteo", 7) else full
    loop = {f"loop of {full // per}": 1} if per < full else {}
    assert _count_eqns(jaxpr, pick) == {
        "transpose": 1, **loop, "dot_general": per + (last > 0),
        "sublane_broadcast": per * fc + last}


@pytest.mark.parametrize("full,cycles,want", [
    (8, 4096, 8), (8, 8192, 4), (4, 8192, 4), (4, 16384, 2), (4, 4096, 4),
    (9, 8192, 3), (7, 8192, 1), (1, 65536, 1)],
    ids=["criteo-d6", "criteo-d7", "epsilon-d7", "criteo-d7-of-16",
         "epsilon-d6", "nine", "prime", "one-huge"])
def test_groups_a_body_keeps_a_step_inside_the_instruction_memory(
        full, cycles, want):
    """``_groups_a_body``: the most whole groups a loop step that divide the
    block's groups and, with one group more, stay within
    ``HELD_STEP_CYCLES`` of the MXU — all of them (no loop) at Criteo's
    level 6 and Epsilon's tile, four of eight at Criteo's level 7 (two
    steps), one where nothing else divides or fits."""
    from rabit_tpu.ops import boost

    per = boost._groups_a_body(full, cycles)
    assert per == want and full % per == 0
    assert per == 1 or (per + 1) * cycles <= boost.HELD_STEP_CYCLES


@pytest.mark.parametrize("F,bins,d,n", [
    (67, 256, 6, 1024), (67, 256, 7, 1024), (2000, 64, 6, 512),
    (2000, 64, 7, 512)], ids=["criteo-6", "criteo-7", "epsilon-6", "epsilon-7"])
def test_held_sums_are_numpy_and_the_parents_kernel(F, bins, d, n,
                                                    monkeypatch):
    """Where the MXU holds the gradient planes (levels 6 and 7) the sums
    of a derived level, routed through random split tables, are numpy's
    float64 ones at the real widths on a few row blocks — Criteo's one
    block of 67 features x 256 bins, Epsilon's sixteen tiles of 64-bin
    features, the ragged last one of 80 — and bit for bit what the parent's
    kernel (the MXU holding the indicator, ``_holds_gradients`` off) gives
    on the same rows: the products are exact in both forms, and the
    interpreter adds a lane's rows in the same order."""
    from rabit_tpu.ops import boost

    block = 256
    plan = boost.hist_plan(F, bins, d, block)
    assert plan.mxu_holds == "gradients" and plan.nodes_derived
    assert F % plan.tile_feats == (80 if F == 2000 else 0)
    rng = np.random.RandomState(F + d)
    xb = rng.randint(0, bins, size=(n, F))
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    hist, ref = _level_and_numpy(boost, plan, xb, g, h,
                                 np.random.RandomState(d), bins, d, block)
    assert hist.shape == ref.shape == (2 ** (d - 1), F, bins, 2)
    np.testing.assert_allclose(np.asarray(hist), ref, rtol=1e-4, atol=1e-4)
    assert np.abs(ref).sum((0, 2, 3)).min() > 0     # every feature has mass
    monkeypatch.setattr(boost, "_holds_gradients", lambda *a, **k: False)
    parent = boost.hist_plan(F, bins, d, block)
    assert parent.mxu_holds == "indicator"
    was, _ = _level_and_numpy(boost, parent, xb, g, h,
                              np.random.RandomState(d), bins, d, block)
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(was))


@pytest.mark.parametrize("d", [6, 7])
def test_held_levels_route_the_missing_code_and_sum_codes_0_128_255(
        d, monkeypatch):
    """Criteo's width with ``missing_code`` 255 at the two held levels,
    derived (``built_right``): codes 0, 128 and 255 lie in every feature
    (the transposed compare's first, middle and last sublane register of a
    feature: twenty rows each, the same code in every feature), a fifth of
    the rows are missing in every feature, and half
    the thresholds send the missing code left.  The routing is
    ``went_right``'s, the sums numpy's at the built children, and both the
    parent's kernel's bit for bit."""
    from rabit_tpu.ops import boost

    F, B, n, block = 67, 256, 1024, 256
    rng = np.random.RandomState(70 + d)
    xb = rng.randint(0, B, size=(n, F))
    xb[rng.rand(n) < 0.2] = B - 1
    xb[:60] = np.tile([0, 128, 255], 20)[:, None]
    g, h = rng.randn(n).astype(np.float32), rng.rand(n).astype(np.float32)
    n_prev = 2 ** (d - 1)
    node = rng.randint(0, n_prev, size=n)
    feat = rng.randint(0, F, size=n_prev)
    thr = rng.randint(0, B - 1, size=n_prev) | np.where(
        rng.rand(n_prev) < 0.5, boost.DEFAULT_LEFT, 0)
    right = rng.randint(0, 2, size=n_prev)
    args = [boost.block_rows(jnp.asarray(a), block)[0] for a in (
        xb.astype(np.int32), node.astype(np.int32), g, h)] + [
        jnp.asarray(a, jnp.int32) for a in (feat, thr, right)]

    def level():
        return boost.hist_level.__wrapped__(*args, depth=d, n_bins=B,
                                            interpret=True, missing_code=B - 1)

    assert boost.hist_plan(F, B, d, block).mxu_holds == "gradients"
    hist, node_out = level()
    xv, t = xb[np.arange(n), feat[node]], thr[node]
    routed = 2 * node + np.where(xv == B - 1, t < boost.DEFAULT_LEFT,
                                 xv > (t & 255))
    np.testing.assert_array_equal(
        np.asarray(boost.unblock_rows(node_out, n)), routed)
    ref = _numpy_histogram(xb, g, h, routed, 2 * n_prev, B)[
        2 * np.arange(n_prev) + right]
    np.testing.assert_allclose(np.asarray(hist), ref, rtol=1e-4, atol=1e-4)
    assert (np.abs(ref[..., [0, 128, 255], 1]).sum(0) > 0).all()
    monkeypatch.setattr(boost, "_holds_gradients", lambda *a, **k: False)
    was, was_node = level()
    np.testing.assert_array_equal(np.asarray(hist), np.asarray(was))
    np.testing.assert_array_equal(np.asarray(node_out), np.asarray(was_node))


def test_hist_plan_holds_the_gradients_from_a_full_tile():
    """``mxu_holds`` is ``"gradients"`` exactly where the level's built
    nodes stack a full MXU tile (``m_rows >= MXU_ROWS``: level 6 on), at
    every width and bin count, one block or tiled; nothing is packed there.
    ``mxu_i8`` and ``r_split > 1`` keep the indicator held, and their plan
    is field for field the one the indicator form reckons."""
    from rabit_tpu.ops import boost

    for F in (5, 28, 67, 130, 2000):
        for bins in (16, 64, 128, 256, 300):
            for d in range(9):
                try:
                    p = boost.hist_plan(F, bins, d, 1024)
                except ValueError:
                    assert d >= 8
                    continue
                held = p.m_rows >= boost.MXU_ROWS
                assert (p.mxu_holds == "gradients") == held == (d >= 6)
                assert not (held and p.packed)
                for kw in ({"mxu_i8": True}, {"r_split": 2}):
                    q = boost.hist_plan(F, bins, d, 1024, **kw)
                    assert q.mxu_holds == "indicator"
                    assert q.packed == boost._packed(bins, q.m_rows)
                    if not held:
                        assert q == p


def test_a_register_count_of_the_packed_build_at_256_bins():
    """At HIGGS's shape and 256 bins the level kernel rolls the block three
    times, contracts its seven words of four codes in two groups and
    broadcasts a word once a BYTE in the
    jaxpr — 28 ``and`` equations, which Mosaic folds to one broadcast a
    word, as it did the two of a 64-lane word — each feeding two compares:
    56 registers for 28 features at 256 lanes, eight a word.  The seven
    words go in groups of ``GROUP_WORDS``: 4 + 3."""
    from rabit_tpu.ops import boost

    sds = jax.ShapeDtypeStruct
    rows = lambda dt: sds((4, 1024, 1), dt)
    tab = sds((4,), jnp.int32)
    jaxpr = _kernel_jaxpr(
        functools.partial(boost.hist_level.__wrapped__, depth=3, n_bins=256),
        sds((4, 1024, 28), jnp.int32), rows(jnp.int32), rows(jnp.float32),
        rows(jnp.float32), tab, tab, tab)
    assert _count_eqns(jaxpr, _build_eqn) == {
        "roll": 3, "dot_general": -(-7 // boost.GROUP_WORDS),
        "lane_broadcast": 28}
    def register(e):
        """A compare of two register-wide operands: one of the indicator."""
        if e.primitive.name == "eq" and all(
                v.aval.shape == (1024, 128) for v in e.invars):
            return "register"

    assert _count_eqns(jaxpr, register) == {"register": 56}


@pytest.mark.parametrize("F,bins,depth,tiles",
                         [(67, 256, 8, 1), (2000, 64, 8, 16), (28, 256, 6, 1)],
                         ids=["criteo", "epsilon", "higgs"])
def test_hist_plan_span_and_rows_streamed_gauge(F, bins, depth, tiles):
    """Lowering the round at the Criteo shape leaves one ``gbdt.hist_plan``
    span a level with what the plan reckoned, and the gauge takes rows x
    passes over the row grid: depth histogram passes and the leaves'.  At
    the Epsilon shape (lowering only) the span says sixteen tiles of 128
    features and ONE tile's block, and the gauge counts a sweep a tile a
    level and a routing pass a level.  ``lanes_a_feature`` is the bins' (64
    at Epsilon's 64 bins, 256 at 256) and the gauge
    ``gbdt_hist_feats_a_register`` 2 and 1; ``regs_a_broadcast`` is the
    registers one lane broadcast serves — 2 at Epsilon's 64 lanes a feature,
    at 256 bins 8 where the level packs the codes (below a full MXU tile of
    stacked rows: up to level 5) — and from level 6 on, where the MXU holds
    the gradients (``mxu_holds``), the registers one sublane broadcast
    serves, 8 and 32; the gauge ``gbdt_hist_regs_a_broadcast`` is the
    root's: 2 and 8, and ``gbdt_hist_levels_gradients_held`` counts the held
    levels: 2 at depth 8, 0 at HIGGS's depth 6.  ``nodes_derived`` is 0 up to level
    2 and half the level's nodes from level 3 on, and the gauge
    ``gbdt_hist_nodes_derived_per_round`` their sum: 4 + 8 + 16 = 28 at the
    HIGGS shape, 28 + 32 + 64 = 124 at depth 8."""
    import time

    from rabit_tpu import obs
    from rabit_tpu.ops import boost

    n, block = 2048, 1024
    cfg = gbdt.GBDTConfig(n_features=F, n_trees=1, depth=depth, n_bins=bins)
    t0 = time.time()
    jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg, interpret=True)
            ).lower(gbdt.init_state(cfg, n),
                    jnp.zeros((n // block, block, F), jnp.int32),
                    jnp.zeros(n, jnp.float32))
    gauge = obs.get_registry().gauge("gbdt_hist_rows_streamed_per_round")
    assert gauge.value == (depth + 1 if tiles == 1 else depth * tiles + depth) * n
    derived = obs.get_registry().gauge("gbdt_hist_nodes_derived_per_round")
    assert derived.value == {6: 28, 8: 124}[depth]
    # two features a 128-lane register at 64 bins, one feature two at 256
    feats = obs.get_registry().gauge("gbdt_hist_feats_a_register")
    assert feats.value == {64: 2, 256: 1}[bins]
    regs = obs.get_registry().gauge("gbdt_hist_regs_a_broadcast")
    assert regs.value == {64: 2, 256: 8}[bins]
    held = obs.get_registry().gauge("gbdt_hist_levels_gradients_held")
    assert held.value == {6: 0, 8: 2}[depth]
    spans = [e.fields for e in obs.get_recorder().snapshot()
             if e.ts >= t0 and e.kind == "span"
             and e.fields.get("name") == "gbdt.hist_plan"]
    assert [s["level"] for s in spans] == list(range(1, depth))
    for s in spans:
        plan = boost.hist_plan(F, bins, s["level"], block)
        assert (s["nodes_built"], s["m_rows"], s["m_tiles"],
                s["acc_block_bytes"], s["vmem_bytes"]) == (
            plan.nodes_built, plan.m_rows, plan.m_tiles,
            plan.acc_block_bytes, plan.vmem_bytes)
        assert (s["feat_tiles"], s["tile_feats"]) == (tiles, min(F, 128))
        assert s["lanes_a_feature"] == bins == plan.lanes_a_feature
        held = s["level"] >= 6
        assert s["mxu_holds"] == plan.mxu_holds == (
            "gradients" if held else "indicator")
        assert s["regs_a_broadcast"] == plan.regs_a_broadcast == (
            bins // 8 if held else 2 if bins == 64 else 8)
        assert s["nodes_derived"] == (2 ** (s["level"] - 1)
                                      if s["level"] >= 3 else 0)
        assert s["nodes_built"] + s["nodes_derived"] == 2 ** s["level"]
    assert spans[-1]["nodes_built"] == 2 ** (depth - 2)
    assert spans[-1]["m_tiles"] == {6: 1, 8: 2}[depth]
    if tiles > 1:
        assert spans[-1]["acc_block_bytes"] == 128 * 128 * 64 * 4


@pytest.mark.parametrize("kw,want", [
    ({}, 2), ({"mxu_i8": True}, 0), ({"r_split": 2}, 0)],
    ids=["bf16", "i8", "rsplit"])
def test_levels_gradients_held_gauge_at_the_criteo_shape(kw, want):
    """At the Criteo shape (F 67 x 256 bins, depth 8) lowering the round
    sets ``gbdt_hist_levels_gradients_held`` to the levels whose kernel
    holds the gradient planes in the MXU, levels 6 and 7; under
    ``mxu_i8`` or ``r_split > 1`` the indicator stays held at every level
    and the gauge reads 0, and each span says so."""
    import time

    from rabit_tpu import obs

    n, block, F = 2048, 1024, 67
    cfg = gbdt.GBDTConfig(n_features=F, n_trees=1, depth=8, n_bins=256, **kw)
    t0 = time.time()
    jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg, interpret=True)
            ).lower(gbdt.init_state(cfg, n),
                    jnp.zeros((n // block, block, F), jnp.int32),
                    jnp.zeros(n, jnp.float32))
    gauge = obs.get_registry().gauge("gbdt_hist_levels_gradients_held")
    assert gauge.value == want
    holds = [e.fields["mxu_holds"] for e in obs.get_recorder().snapshot()
             if e.ts >= t0 and e.kind == "span"
             and e.fields.get("name") == "gbdt.hist_plan"]
    assert holds == ["indicator"] * (7 - want) + ["gradients"] * want


def test_train_round_fused_i8_matches_reference():
    """The int8-MXU fused round must pick the same splits as the exact
    hook-based round (histogram quantization error ~2^-13 of block max is
    far below split-gain gaps on this data) and leaves must agree to the
    fixed-point tolerance."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(3)
    n, f = 600, 5
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=3, depth=3, n_bins=16,
                          mxu_i8=True)
    cfg_ref = cfg._replace(mxu_i8=False)
    xb = jnp.asarray(rng.randint(0, cfg.n_bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    xb3, _ = boost.block_rows(xb, 256)

    ref_step = jax.jit(functools.partial(gbdt.train_round, cfg=cfg_ref))
    i8_step = functools.partial(gbdt.train_round_fused, cfg=cfg, interpret=True)
    s_ref = gbdt.init_state(cfg_ref, n)
    s_i8 = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s_ref = ref_step(s_ref, xb, y)
        s_i8 = i8_step(s_i8, xb3, y)

    fr = jax.tree.map(np.asarray, s_ref.forest)
    fi = jax.tree.map(np.asarray, s_i8.forest)
    np.testing.assert_array_equal(fi.feature, fr.feature)
    np.testing.assert_array_equal(fi.threshold, fr.threshold)
    np.testing.assert_allclose(fi.leaf, fr.leaf, rtol=5e-3, atol=5e-3)


def test_hist_impls_agree():
    """scatter / onehot histogram implementations agree to f32 accuracy."""
    from rabit_tpu.ops import hist as H

    rng = np.random.RandomState(1)
    n, F, B, nn = 500, 4, 16, 4
    xb = jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n), jnp.float32)
    node = jnp.asarray(rng.randint(0, nn, size=n), jnp.int32)
    ref = np.asarray(H.node_histograms_scatter(xb, g, h, node, nn, B))
    got = np.asarray(H.node_histograms_onehot(xb, g, h, node, nn, B))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # the TPU-default Pallas kernel, via the interpreter
    got_p = np.asarray(
        H.node_histograms_pallas(xb, g, h, node, nn, B, block_rows=256,
                                 interpret=True)
    )
    np.testing.assert_allclose(got_p, ref, rtol=1e-4, atol=1e-4)
    # the int8-MXU variant: two-plane fixed-point split, error bounded by
    # ~2^-13 of the block max per element
    got_i8 = np.asarray(
        H.node_histograms_pallas(xb, g, h, node, nn, B, block_rows=256,
                                 interpret=True, mxu_i8=True)
    )
    np.testing.assert_allclose(got_i8, ref, rtol=2e-2, atol=2e-2)
    # and the leaf-fit segment_sum matmul path
    vals = jnp.stack([g, h], -1)
    np.testing.assert_allclose(
        np.asarray(H.segment_sum(vals, node, nn, impl="matmul")),
        np.asarray(H.segment_sum(vals, node, nn, impl="scatter")),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("mxu_i8", [False, True])
def test_hist_level_rsplit_matches(mxu_i8):
    """The r_split sub-contraction form of the level kernel (the VPU/MXU
    overlap experiment, ops/boost.py _accum) must produce the same
    histograms and routing as the single-contraction default — the split
    only reassociates the f32 row sum."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(11)
    n, F, B, d = 512, 5, 16, 2
    n_prev = 1 << (d - 1)
    xb3, _ = boost.block_rows(
        jnp.asarray(rng.randint(0, B, size=(n, F)), jnp.int32), 256)
    g3, _ = boost.block_rows(jnp.asarray(rng.randn(n), jnp.float32), 256)
    h3, _ = boost.block_rows(jnp.asarray(rng.rand(n), jnp.float32), 256)
    node3 = jnp.asarray(rng.randint(0, n_prev, size=g3.shape), jnp.int32)
    feat = jnp.asarray(rng.randint(0, F, size=n_prev), jnp.int32)
    thr = jnp.asarray(rng.randint(0, B, size=n_prev), jnp.int32)
    ref_h, ref_n = boost.hist_level(xb3, node3, g3, h3, feat, thr, depth=d,
                                    n_bins=B, interpret=True, mxu_i8=mxu_i8)
    got_h, got_n = boost.hist_level(xb3, node3, g3, h3, feat, thr, depth=d,
                                    n_bins=B, interpret=True, mxu_i8=mxu_i8,
                                    r_split=2)
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(ref_n))
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(ref_h),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="divide the row block"):
        boost.hist_level(xb3, node3, g3, h3, feat, thr, depth=d, n_bins=B,
                         interpret=True, r_split=3)
    with pytest.raises(ValueError, match="divide the row block"):
        boost.hist_level0(xb3, g3, h3, n_bins=B, interpret=True, r_split=0)


def test_train_round_dp_fused_matches_dp():
    """The fused dp round (Pallas interpreter under shard_map on the CPU
    mesh) must grow the same trees as the hook-based train_round_dp."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(5)
    ndev = 8
    n, f = 128 * 2 * ndev, 5  # 2 row blocks of 128 per device
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=2, depth=3, n_bins=16)
    xb = jnp.asarray(rng.randint(0, cfg.n_bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    mesh = rp.create_mesh(("dp",))

    ref_fn = jax.shard_map(
        functools.partial(gbdt.train_round_dp, cfg=cfg),
        mesh=mesh,
        in_specs=(
            gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()),
            P("dp", None), P("dp"),
        ),
        out_specs=gbdt.TrainState(
            forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
        ),
        check_vma=False,
    )
    xb3, _ = boost.block_rows(xb, 128)
    fused_fn = jax.shard_map(
        functools.partial(gbdt.train_round_dp_fused, cfg=cfg, interpret=True),
        mesh=mesh,
        in_specs=(
            gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()),
            P("dp", None, None), P("dp"),
        ),
        out_specs=gbdt.TrainState(
            forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
        ),
        check_vma=False,
    )

    s_ref = gbdt.init_state(cfg, n)
    s_f = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s_ref = ref_fn(s_ref, xb, y)
        s_f = fused_fn(s_f, xb3, y)
    fr = jax.tree.map(np.asarray, s_ref.forest)
    ff = jax.tree.map(np.asarray, s_f.forest)
    np.testing.assert_array_equal(ff.feature, fr.feature)
    np.testing.assert_array_equal(ff.threshold, fr.threshold)
    np.testing.assert_allclose(ff.leaf, fr.leaf, rtol=1e-3, atol=1e-5)


def test_train_round_dp_fused_wire_i8_close_to_exact():
    """The int8-wire histogram allreduce (wire_i8) must grow trees whose
    leaves match the exact-psum fused round to quantization tolerance —
    and, with identical wire bytes decoded on every rank, identical split
    tables (rank-consistent argmax)."""
    from rabit_tpu.ops import boost

    rng = np.random.RandomState(11)
    ndev = 8
    n, f = 128 * ndev, 4
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=2, depth=3, n_bins=16)
    xb = jnp.asarray(rng.randint(0, cfg.n_bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    mesh = rp.create_mesh(("dp",))
    specs = dict(
        in_specs=(
            gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()),
            P("dp", None, None), P("dp"),
        ),
        out_specs=gbdt.TrainState(
            forest=gbdt.Forest(P(), P(), P()), margin=P("dp"), round=P()
        ),
        check_vma=False,
    )
    xb3, _ = boost.block_rows(xb, 128)
    exact = jax.shard_map(
        functools.partial(gbdt.train_round_dp_fused, cfg=cfg, interpret=True),
        mesh=mesh, **specs)
    # flat level-0 hist = f * n_bins * 2 = 128 floats; 8 chunks of 16
    wired = jax.shard_map(
        functools.partial(gbdt.train_round_dp_fused, cfg=cfg, interpret=True,
                          wire_i8=True, wire_block=16),
        mesh=mesh, **specs)

    s_e = gbdt.init_state(cfg, n)
    s_w = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s_e = exact(s_e, xb3, y)
        s_w = wired(s_w, xb3, y)
    fe = jax.tree.map(np.asarray, s_e.forest)
    fw = jax.tree.map(np.asarray, s_w.forest)
    np.testing.assert_array_equal(fw.feature, fe.feature)
    np.testing.assert_array_equal(fw.threshold, fe.threshold)
    np.testing.assert_allclose(fw.leaf, fe.leaf, rtol=1e-3, atol=1e-3)


# -- the hybrid round on the fused kernels ---------------------------------


def _hybrid_case(n=2500, f=5, bins=16, depth=3, rounds=2, seed=13):
    rng = np.random.RandomState(seed)
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=rounds, depth=depth,
                          n_bins=bins)
    xb = jnp.asarray(rng.randint(0, bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    return cfg, xb, y


def _train(step, cfg, n, *data):
    s = gbdt.init_state(cfg, n)
    for _ in range(cfg.n_trees):
        s = step(s, *data)
    return jax.tree.map(np.asarray, s)


@pytest.mark.parametrize("codes", ["2d", "3d", "3d-xla"])
def test_hybrid_round_with_an_identity_hop_is_the_fused_round(codes):
    """With the hop an identity (a world of one) the hybrid round on the
    fused kernels gives the bytes of ``train_round_fused`` on the same
    blocked data, whether it is handed the codes ``[n, F]`` and blocks them
    in the graph or ``[nb, 1024, F]`` blocked already.  Off the fused path
    blocked codes are unblocked and give the bytes the 2-D ones give."""
    from rabit_tpu.ops import boost

    cfg, xb, y = _hybrid_case()
    n = y.shape[0]
    xb3, _ = boost.block_rows(xb)
    interpret = codes != "3d-xla"
    hybrid = jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=lambda a: a,
        interpret=interpret))
    got = _train(hybrid, cfg, n, xb if codes == "2d" else xb3, y)
    if interpret:
        want = _train(jax.jit(functools.partial(
            gbdt.train_round_fused, cfg=cfg, interpret=True)), cfg, n, xb3, y)
    else:
        want = _train(hybrid, cfg, n, xb, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.tobytes() == b.tobytes()
    assert np.abs(got.forest.leaf).max() > 0


@pytest.mark.parametrize("depth", [3, 6])
def test_hybrid_round_hops_depth_plus_one_times_in_order(depth):
    """The collective sequence the engine's replay log is written against:
    a histogram ``[2**d, F, B, 2]`` a level, then the leaves' masses
    ``[2**depth, 2]``, each under a tag of its own, ``2**level``.  At depth
    6 the sequence keeps its length, order and tags, and levels 3 to 5 send
    the built half of their nodes, ``[4 | 8 | 16, F, B, 2]`` under tags 8,
    16 and 32."""
    import time

    from rabit_tpu import obs

    cfg, xb, y = _hybrid_case(rounds=1, depth=depth)
    seen = []

    def hop(a):
        seen.append(a.shape)
        return a

    t0 = time.time()
    s = jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=hop,
        interpret=True))(gbdt.init_state(cfg, y.shape[0]), xb, y)
    jax.block_until_ready(s)
    d, f, b = cfg.depth, cfg.n_features, cfg.n_bins
    assert seen == [(2 ** k if k < 3 else 2 ** (k - 1), f, b, 2)
                    for k in range(d)] + [(2 ** d, 2)]
    tags = [e.fields["level"] for e in obs.get_recorder().snapshot()
            if e.ts >= t0 and e.kind == "span"
            and e.fields.get("name") == "gbdt.cross"]
    assert tags == [2 ** k for k in range(d)] + [-1]


@pytest.mark.parametrize("depth", [3, 6])
def test_hybrid_round_leaf_hop_is_right_in_a_world_of_two(depth):
    """A hop that returns ``2 * a`` is a world of two identical shards.  The
    forest must be the fused round's on the rows twice over: the leaf hop
    carries the LOCAL children's masses, so the engine's sum is the global
    mass and not the world's multiple of it.  At depth 6 the last level is
    derived: its local histogram is the local parents' less the local built
    children's, and the built child is chosen off the combined one."""
    from rabit_tpu.ops import boost

    cfg, xb, y = _hybrid_case(n=2048, depth=depth)
    n = y.shape[0]
    got = _train(jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, engine_allreduce=lambda a: 2 * a,
        interpret=True)), cfg, n, xb, y)
    xb3, _ = boost.block_rows(jnp.concatenate([xb, xb]))
    want = _train(jax.jit(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=True)), cfg, 2 * n, xb3,
        jnp.concatenate([y, y]))
    np.testing.assert_array_equal(got.forest.feature, want.forest.feature)
    np.testing.assert_array_equal(got.forest.threshold, want.forest.threshold)
    np.testing.assert_allclose(got.forest.leaf, want.forest.leaf,
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(got.margin, want.margin[:n],
                               rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("how,path", [
    ("interpret", "fused"), ("cpu", "xla"), ("mesh", "xla")])
def test_hybrid_round_counts_its_lowerings_by_path(how, path):
    """``gbdt_hybrid_round_lowered_total{path=...}`` moves by one a
    lowering, under the path the round took: the fused kernels on a TPU or
    under ``interpret``, ``train_round`` on any other backend and wherever
    a mesh is given."""
    from rabit_tpu import obs
    from rabit_tpu.obs.stream import series_name

    cfg, xb, y = _hybrid_case(n=2048, rounds=1)
    kw = {"interpret": how != "cpu"}
    if how == "mesh":
        kw["mesh"] = rp.create_mesh(("dp",))
    read = lambda p: obs.get_registry().counter(series_name(
        "gbdt_hybrid_round_lowered_total", path=p)).value
    before = {p: read(p) for p in ("fused", "xla")}
    jax.jit(functools.partial(gbdt.train_round_hybrid, cfg=cfg, **kw)).lower(
        gbdt.init_state(cfg, y.shape[0]), xb, y)
    other = "xla" if path == "fused" else "fused"
    assert read(path) == before[path] + 1
    assert read(other) == before[other]


@pytest.mark.parametrize("missing", [False, True])
def test_rounds_count_their_split_scans_by_directions(missing):
    """``gbdt_split_scan_lowered_total{directions=...}`` moves by one a
    lowering of ``train_round_fused`` and of ``train_round``: under "2"
    where ``GBDTConfig.missing`` scores every threshold with the missing
    mass on either side (``ops.boost.split_scan``), under "1" where the
    codes are dense; and every ``gbdt.hist_plan`` span says which."""
    import time

    from rabit_tpu import obs
    from rabit_tpu.obs.stream import series_name
    from rabit_tpu.ops import boost

    cfg, xb, y = _hybrid_case(n=2048, rounds=1, depth=4)
    cfg = cfg._replace(missing=missing)
    read = lambda k: obs.get_registry().counter(series_name(
        "gbdt_split_scan_lowered_total", directions=k)).value
    mine, other = ("2", "1") if missing else ("1", "2")
    before = {k: read(k) for k in ("1", "2")}
    t0 = time.time()
    state = gbdt.init_state(cfg, y.shape[0])
    jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                              interpret=True)).lower(
        state, boost.block_rows(xb)[0], y)
    jax.jit(functools.partial(gbdt.train_round, cfg=cfg)).lower(state, xb, y)
    assert read(mine) == before[mine] + 2 and read(other) == before[other]
    spans = [e.fields for e in obs.get_recorder().snapshot()
             if e.ts >= t0 and e.kind == "span"
             and e.fields.get("name") == "gbdt.hist_plan"]
    assert [s["missing"] for s in spans] == [missing] * (cfg.depth - 1)


@pytest.mark.parametrize("cats", [(), (1, 3)], ids=["dense", "categorical"])
def test_rounds_count_their_cat_splits_and_the_plan_says_how_many(cats):
    """``gbdt_cat_split_lowered_total`` moves by one a ``cat_split_d<d>``
    kernel lowered — a level of ``train_round_fused`` and of ``train_round``
    each, under ``GBDTConfig.categorical`` — and not at all where no
    feature is categorical; every ``gbdt.hist_plan`` span's ``categorical``
    says how many features the level's routing tests by membership, as
    ``ops.boost.hist_plan`` reckons it."""
    import time

    from rabit_tpu import obs
    from rabit_tpu.ops import boost

    cfg, xb, y = _hybrid_case(n=2048, rounds=1, depth=4)
    cfg = gbdt.GBDTConfig(**{**cfg._asdict(), "categorical": list(cats)})
    assert cfg.categorical == cats
    read = lambda: obs.get_registry().counter(
        "gbdt_cat_split_lowered_total").value
    before = read()
    t0 = time.time()
    state = gbdt.init_state(cfg, y.shape[0])
    jax.jit(functools.partial(gbdt.train_round_fused, cfg=cfg,
                              interpret=True)).lower(
        state, boost.block_rows(xb)[0], y)
    jax.jit(functools.partial(gbdt.train_round, cfg=cfg)).lower(state, xb, y)
    assert read() == before + (2 * cfg.depth if cats else 0)
    spans = [e.fields for e in obs.get_recorder().snapshot()
             if e.ts >= t0 and e.kind == "span"
             and e.fields.get("name") == "gbdt.hist_plan"]
    assert [s["categorical"] for s in spans] == [len(cats)] * (cfg.depth - 1)
    assert [boost.hist_plan(5, 16, d, 1024, categorical=len(cats)).categorical
            for d in range(1, cfg.depth)] == [len(cats)] * (cfg.depth - 1)
    assert (state.forest.sets is None) == (not cats)
