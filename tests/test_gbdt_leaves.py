"""The leaves' pass of the fused round: ``margin + leaf[leaf node]``.

At every depth the lookup runs inside the routing kernel
(``ops.boost.route_margin_level``: the rows' one-hot against three bfloat16
planes of the leaf table on the MXU, the margin lane-dense, no node ids
written), where the parent routed in one kernel and gathered in XLA.  The
margin is XLA's gather and add, bit for bit."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rabit_tpu.models import gbdt
from rabit_tpu.ops import boost

R = 1024


def _case(F, depth, *, missing=False, cats=False, seed=0):
    """Two row blocks, the last one ragged; a leaf table with -0.0, a
    subnormal, a tiny and a large entry among normal ones."""
    rng = np.random.default_rng(seed)
    nb, n_prev = 2, 2 ** (depth - 1)
    n = nb * R - 37
    xb3 = jnp.asarray(rng.integers(0, 256, (nb, R, F)), jnp.int32)
    node3 = jnp.asarray(rng.integers(0, n_prev, (nb, R, 1)), jnp.int32)
    feat = jnp.asarray(rng.integers(0, F, n_prev), jnp.int32)
    thr = rng.integers(0, 255, n_prev)
    if missing:
        thr |= rng.integers(0, 2, n_prev) * boost.DEFAULT_LEFT
    words = None
    if cats:
        sets = rng.integers(-2 ** 31, 2 ** 31, (n_prev, boost.SET_WORDS))
        sets[rng.integers(0, 2, n_prev) == 0] = 0      # numeric nodes too
        words = boost.route_words(jnp.asarray(thr, jnp.int32),
                                  jnp.asarray(sets, jnp.int32))
    leaf = (rng.standard_normal(2 ** depth) * 0.1).astype(np.float32)
    leaf[:4] = (-0.0, 1e-45, 1e-30, 3.0e5)
    margin = jnp.asarray(rng.standard_normal(n), jnp.float32)
    return (xb3, node3, margin, feat, jnp.asarray(thr, jnp.int32),
            jnp.asarray(leaf), words)


def _leaf_of(xb3, node3, n, feat, thr, words, missing_code=None):
    """Each of the ``n`` rows' leaf, routed by ``went_right`` in XLA."""
    codes = xb3.reshape(-1, xb3.shape[2])[:n]
    node = node3.reshape(-1)[:n]
    xv = jnp.take_along_axis(codes, feat[node][:, None], 1)[:, 0]
    right = boost.went_right(xv, thr[node], missing_code,
                             None if words is None else words[node])
    return 2 * node + right.astype(jnp.int32)


def _reference(xb3, node3, margin, feat, thr, leaf, words, *,
               missing_code=None):
    """``margin + leaf[leaf node]`` in XLA: the parent's leaves' pass, with
    no kernel."""
    return margin + leaf[_leaf_of(xb3, node3, margin.shape[0], feat, thr,
                                  words, missing_code)]


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("wide", [False, True], ids=["one_tile", "tiled"])
@pytest.mark.parametrize("depth", [6, 7, 8], ids=["64", "128", "256"])
def test_leaves_pass_is_the_gather_bit_for_bit(depth, wide):
    """At 64, 128 and 256 leaves, in one tile of codes and past
    ``TILE_FEATS`` features, with a ragged last block: the kernel's margin
    is XLA's gather from the table and add, float32 bit for bit."""
    args = _case(130 if wide else 28, depth, seed=depth)
    got = boost.route_margin_level(*args, depth=depth, interpret=True)
    assert got.shape == args[2].shape
    np.testing.assert_array_equal(_bits(got), _bits(_reference(*args)))


@pytest.mark.parametrize("wide", [False, True], ids=["one_tile", "tiled"])
@pytest.mark.parametrize("switch", ["missing", "categorical"])
def test_leaves_pass_routes_missing_codes_and_sets_bit_for_bit(switch, wide):
    """Under ``GBDTConfig.missing`` (the default-direction bit) and with
    categorical sets (the set table), at Criteo's 256 leaves: the same
    bits as XLA's routing, gather and add."""
    missing = switch == "missing"
    args = _case(130 if wide else 67, 8, missing=missing,
                 cats=not missing, seed=11)
    code = 255 if missing else None
    got = boost.route_margin_level(*args, depth=8, interpret=True,
                                   missing_code=code)
    want = _reference(*args, missing_code=code)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_leaf_planes_add_up_to_the_leaf():
    """The three bfloat16 planes of ``_leaf_planes`` add up, as the kernel
    adds them, to the leaf bit for bit — any finite float32 of either sign
    from 2**-100 up, where no plane is subnormal (the chip and XLA's CPU
    flush those; a smaller leaf is lost in any margin above 2**-76 either
    way) — and zero to +0.0 (a margin is never -0.0, so adding either zero
    gives it back)."""
    rng = np.random.default_rng(5)
    bits = rng.integers(0, 2 ** 32, 8192, dtype=np.uint64).astype(np.uint32)
    leaf = bits.view(np.float32)
    leaf = leaf[np.isfinite(leaf) & (np.abs(leaf) >= 2.0 ** -100)]
    leaf = np.concatenate([leaf, np.float32([0.0, -0.0, 2.0 ** -100])])
    planes = np.asarray(boost._leaf_planes(jnp.asarray(leaf), leaf.size),
                        np.float32)
    assert not planes[3:].any()                 # no leaf's code is set
    got = (planes[0] + planes[1]) + planes[2]
    nonzero = leaf != 0
    np.testing.assert_array_equal(_bits(got[nonzero]), _bits(leaf[nonzero]))
    np.testing.assert_array_equal(_bits(got[~nonzero]), 0)


@pytest.mark.parametrize("wide", [False, True], ids=["one_tile", "tiled"])
@pytest.mark.parametrize("reached", [False, True],
                         ids=["unreached", "reached"])
def test_leaves_pass_keeps_leaves_that_are_not_finite(reached, wide):
    """A NaN, +inf or -inf in the leaf table (an empty leaf reads 0/0 at
    ``reg_lambda`` 0) reaches only the rows in that leaf, as XLA's gather
    has it: with the odd leaves where no row lands every margin is the
    gather's bit for bit, and where rows land in them those rows read NaN,
    +inf and -inf and every other row is the gather's bit for bit."""
    xb3, node3, margin, feat, thr, leaf, words = _case(
        130 if wide else 28, 8, seed=23)
    node3 = node3 & -2              # no row is below an odd level-7 node
    if reached:
        at = np.unique(np.asarray(_leaf_of(xb3, node3, margin.shape[0],
                                           feat, thr, words)))[:3]
    else:
        at = np.array([2, 7, 10])   # leaves of level-7 nodes 1, 3 and 5
    leaf = np.asarray(leaf).copy()
    leaf[at] = (np.nan, np.inf, -np.inf)
    leaf = jnp.asarray(leaf)
    got = np.asarray(boost.route_margin_level(
        xb3, node3, margin, feat, thr, leaf, words, depth=8, interpret=True))
    want = np.asarray(_reference(xb3, node3, margin, feat, thr, leaf, words))
    ok = np.isfinite(want)
    assert ok.all() != reached
    np.testing.assert_array_equal(_bits(got[ok]), _bits(want[ok]))
    np.testing.assert_array_equal(got[~ok], want[~ok])      # NaN == NaN
    if reached:
        assert (np.isnan(got).any() and np.isposinf(got).any()
                and np.isneginf(got).any())


@pytest.mark.parametrize("depth", [6, 8])
def test_fused_round_at_reg_lambda_0_matches_train_round(depth):
    """With ``reg_lambda`` 0 an empty leaf (below a node that no split
    could part, whose fallback split sends every row one way) reads
    -lr * 0 / 0, NaN: the fused round keeps it in the forest as
    ``train_round`` does, and the margin stays finite in both, since no
    row is in that leaf.  One round from a zero margin, where both rounds
    split alike (see ``FUSED_SHAPES`` in ``test_gbdt.py``)."""
    rng = np.random.RandomState(7)
    n, f, bins = 700, 5, 16
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=1, depth=depth, n_bins=bins,
                          reg_lambda=0.0)
    xb = jnp.asarray(rng.randint(0, bins, size=(n, f)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=n), jnp.float32)
    xb3, _ = boost.block_rows(xb, R)
    s_ref = gbdt.train_round(gbdt.init_state(cfg, n), xb, y, cfg)
    s_f = gbdt.train_round_fused(gbdt.init_state(cfg, n), xb3, y, cfg,
                                 interpret=True)
    fr = jax.tree.map(np.asarray, s_ref.forest)
    ff = jax.tree.map(np.asarray, s_f.forest)
    np.testing.assert_array_equal(ff.feature, fr.feature)
    np.testing.assert_array_equal(ff.threshold, fr.threshold)
    assert np.isnan(fr.leaf).any()                  # the case is there
    np.testing.assert_array_equal(np.isnan(ff.leaf), np.isnan(fr.leaf))
    np.testing.assert_allclose(ff.leaf, fr.leaf, rtol=1e-3, atol=1e-5)
    m_f, m_ref = np.asarray(s_f.margin), np.asarray(s_ref.margin)
    assert np.isfinite(m_ref).all() and np.isfinite(m_f).all()
    np.testing.assert_allclose(m_f, m_ref, rtol=1e-3, atol=1e-5)


@pytest.mark.parametrize("depth", [6, 7, 8])
def test_leaves_pass_looks_up_in_the_routing_kernel_at_every_depth(depth):
    """At 64, 128 and 256 leaves alike the fused round's leaves' pass is one
    kernel, ``route_margin_d<depth>`` (a ``route_`` op for the routing
    pass's readers), no ``route_level_d<depth>`` and no XLA gather."""
    cfg = gbdt.GBDTConfig(n_features=5, n_trees=1, depth=depth, n_bins=16)
    n = 2 * R - 9
    xb3 = jnp.zeros((2, R, 5), jnp.int32)
    jaxpr = jax.make_jaxpr(functools.partial(
        gbdt.train_round_fused, cfg=cfg, interpret=True))(
        gbdt.init_state(cfg, n), xb3, jnp.zeros(n, jnp.float32))
    text = str(jaxpr)
    assert f"route_margin_d{depth}" in text
    assert f"route_level_d{depth}" not in text
    assert not [e for e in jaxpr.eqns if e.primitive.name == "gather"
                and e.outvars[0].aval.size >= n]        # none of every row
