"""Fused Pallas kernels for one GBDT boosting round on TPU.

The hook-based ``models.gbdt.train_round`` makes one pass over the rows per
level for histograms plus separate passes for routing, leaf fit, and margin
update — each a round-trip through HBM.  These kernels fuse a level's work
into a single streaming pass per row block:

* ``hist_level0``   — histogram at the root (no routing needed).
* ``hist_level``    — route rows one level down through the parent split
  table (split lookup + feature select + compare, all in VMEM) and
  histogram at the new nodes, emitting the updated node ids as a second
  output.  From level 5 on it histograms ONE child of every parent, the one
  a table names, and ``derive_siblings`` reads the other off the parents'
  histogram — XGBoost's and LightGBM's subtraction trick, at the levels
  where it shortens the matmul.
* ``route_level``   — route to the leaves, no histogram: the leaves' (g, h)
  masses are read off the last level's histogram.

The histogram itself is the one-hot MXU contraction of ``ops.hist``: the
row block's gradient matrix L (one g column + one h column per node) is
contracted against per-feature bin indicators built in VMEM; f32 gradients
are split hi/lo into two bfloat16 matmuls (error ~2^-16-relative).  The
lanes a feature takes in the indicator follow the bins (``_bins_eff``):
64 at up to 64 bins, two features a register, else whole 128-lane
registers.  What bounds the build of a level below a full MXU tile of
stacked rows is the lane broadcast of a code column, so there — where the
codes fit a byte (up to 256 bins); at 64 lanes a feature at every level —
the kernel packs the block's codes four a word first and ONE broadcast
serves four features: two registers at 64 lanes a feature, four at 128,
eight at 256 (``_accum``, ``_packed``, ``HistPlan.regs_a_broadcast``).  From
a full tile on the MXU's streaming hides the build, and at 128 and 256
lanes a feature a code column is broadcast a feature, as it always was.
The rule reads ``n_bins`` and the level's stacked rows alone; no option
chooses.

``hist_plan`` reckons what a level asks of the chip from the shape alone.
The stacked gradient matrix has 4 rows a node built (g and h, hi and lo
plane); up to 64 of them a level costs the same, the indicator build hiding
the MXU, and from a full 128-row tile on the cost follows the rows.  So a
level whose nodes would stack a tile or more — 4 * 2**level >= ``MXU_ROWS``:
level 5, at any width — builds half of them, one child a parent, with the
matmul, the accumulator block and the VMEM of the level above; no option
chooses.  Up to ``TILE_FEATS`` features (one 128-lane tile of blocked codes)
the accumulator ``(2 * nodes built, F * B_eff)`` float32 (F to whole
words of four feature slots where the level packs the codes) is ONE block held
across the row grid, and routing rides the histogram's sweep.  A wider matrix is
walked in feature tiles: a grid over (feature tile, row block), the row
axis innermost, one tile's accumulator block resident across its row
sweep; the feature a node splits on may lie in any tile, so routing is a
pass of its own there (``route_level``, its code block tiled too), once a
level.  The plan asks Mosaic for the scoped VMEM that the blocks take
where they pass its default, or refuses the shape by name where no tiling
holds it.

All wrappers take pre-blocked arrays (nb, R, ...) so padding/reshaping
happens once per fit, not once per level.  ``interpret=True`` runs the
kernels in the Pallas interpreter, which is how the CPU test suite checks
them against the reference ``train_round`` (tests/test_gbdt.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DN = (((0,), (0,)), ((), ()))  # contract dim 0 vs dim 0


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _check_r_split(R: int, r_split: int) -> None:
    if r_split < 1 or R % r_split:
        raise ValueError(
            f"r_split={r_split} must be >= 1 and divide the row block {R}")


def _bins_eff(n_bins: int) -> int:
    """Lanes a feature takes in the indicator matrix and the accumulator:
    64 at up to 64 bins, so that two features share one 128-lane register,
    else the bins padded to whole registers (128 at 65 to 128 bins, 256 at
    256).  The pad columns never match a bin id, so they stay zero."""
    return 64 if n_bins <= 64 else _round_up(n_bins, 128)


def _packed(n_bins: int, m_rows: int) -> bool:
    """Whether a kernel over ``m_rows`` stacked rows of gradient matrix
    builds its indicator from words of four packed codes (``_accum``), one
    lane broadcast for four features.  The codes have to fit a byte (up to
    256 bins).  At 64 lanes a feature it always does (PR 35: the words won
    at every level).  At 128 and 256 lanes a broadcast already serves one
    and two registers, and packing pays where the build bounds the kernel:
    below a full MXU tile of stacked rows.  From a full tile on the MXU's
    streaming hides the build and the packed kernel is slower (its matmul
    results spill: ``hist_plan``): F = 67 x 256 bins x 2,621,440 rows,
    kernels alone, a code column a feature | packed: 46.8 | 35.2 ms at 16
    stacked rows, 46.9 | 36.7 at 32, 48.3 | 46.2 at 64, 70.5 | 78.4 at 128,
    150.2 | 158.3 at 256; F = 28: 24.1 | 19.4 at 32, 25.3 | 25.4 at 64 (my
    chip runs, PR 37)."""
    return n_bins <= 256 and (_bins_eff(n_bins) < 128 or m_rows < MXU_ROWS)


def _block_feats(n_feat: int, packed: bool) -> int:
    """Feature slots of an accumulator block over ``n_feat`` features: whole
    words of four where the kernel packs the codes (28 stay 28, 67 become
    68)."""
    return _round_up(n_feat, 4) if packed else n_feat


#: words of four packed codes a matmul group of a one-block kernel above 64
#: bins (``_pick_fc``)
GROUP_WORDS = 4


def _pick_fc(n_feat: int, n_bins: int, packed: bool) -> int:
    """Feature slots per matmul group of a one-block kernel.  A code column
    a feature: N = fc * bins_eff ~ 1792 lanes (14 features at up to 128
    bins, 7 at 256).  Packed, whole words of four: 28 slots (seven words,
    1792 lanes) at up to 64 bins; above, ``GROUP_WORDS`` words (4096 lanes
    at 256 bins).  Kernels alone at F = 67 x 256 bins x 2,621,440 rows,
    groups of 1 | 2 | 3 | 4 | 6 | 9 | 17 words: 36.7 | 35.2 | 33.7 | 32.9 |
    40.4 | 40.4 | 41.0 ms at 16 stacked rows, 37.7 | 36.7 | 35.0 | 35.0 |
    40.1 | 40.1 | 43.0 at 32, and 46.2 at 64 whatever the group; at F = 28
    groups of 1 | 2 | 4 | 7: - | 18.3 | 18.5 | 18.5 at 16 rows, 20.6 |
    19.4 | 19.4 | 19.7 at 32 (my chip runs, PR 37)."""
    be = _bins_eff(n_bins)
    if not packed:
        return min(n_feat, max(1, 1792 // be))
    return min(n_feat, 4 * (7 if be < 128 else GROUP_WORDS))


def _pick_tile_fc(n_bins: int) -> int:
    """Features per matmul group inside a ``TILE_FEATS``-feature tile (N =
    2048 lanes: 32 at up to 64 bins, 16 at up to 128, 8 at 256), a power of
    two so that the groups of a tile are all whole.  At F = 2000 x 64 bins
    x 400,384 rows, 64 lanes a feature, single kernels: groups of 32 (2048
    lanes) took 74.2 | 78.5 | 174.6 ms at levels 0 | 4 | 7, groups of 16
    (1024 lanes) 83.0 | 89.7 | 189.0 (my chip run, PR 35); at 128 lanes a
    feature groups of 16 had been 6 % faster than of 8 at levels 0-4 and
    4 % at level 7, groups of 4 12 % slower (my chip run, PR 31)."""
    return max(1, 2048 // _bins_eff(n_bins))


#: features of one tile of blocked codes (its 128 lanes): up to here a level
#: is one block, beyond it the kernels walk the features a tile at a time
TILE_FEATS = 128
#: rows of one MXU tile: the stacked gradient matrix (hi and lo plane, g and
#: h, a node: 4 * 2**level rows) fills it at level 5
MXU_ROWS = 128
#: scoped VMEM Mosaic gives one kernel on the v5e unless asked for more
VMEM_DEFAULT = 16 << 20
#: the most ``hist_level`` asks for: half of the v5e's 128 MiB
VMEM_MOST = 64 << 20
#: room for the kernel's own stack beyond its blocks: twice the most the
#: compiler has reported (3.0 MiB at level 7, 4.0 at level 8 of F = 67, and
#: under 2.6 wherever the default limit held a kernel; PR 27)
VMEM_STACK = 8 << 20


class HistPlan(NamedTuple):
    """What one level's ``hist_level`` asks of the chip.  A level whose
    nodes, all built, would stack a full MXU tile of gradient matrix or more
    is *derived*: one child of every parent is accumulated and its sibling
    is the parent's histogram less the built one's (``derive_siblings``), so
    level d runs the matmul, the accumulator block and the VMEM of level
    d - 1."""

    level: int
    nodes_derived: int    # nodes read off parent - sibling: half a derived level, else 0
    m_pad: int            # accumulator rows: g and h of every node BUILT
    acc_block_bytes: int  # ONE tile's (m_pad, tile_feats * B_eff) float32 block
    vmem_bytes: int       # what one tile's kernel takes: hist_plan's text
    tile_feats: int       # features a tile: all of F where one tile holds them
    feat_tiles: int       # tiles a level; each sweeps every row block
    lanes_a_feature: int  # 64 at up to 64 bins (two features a register), else 128, 256, ...
    regs_a_broadcast: int  # registers of the indicator one lane broadcast serves

    @property
    def nodes_built(self) -> int:
        return 2 ** self.level - self.nodes_derived

    @property
    def m_rows(self) -> int:
        """Rows of the stacked (two-plane) gradient matrix."""
        return 2 * self.m_pad

    @property
    def m_tiles(self) -> int:
        return -(-self.m_rows // MXU_ROWS)

    @property
    def packed(self) -> bool:
        """Whether the level's kernel packs the codes four a word
        (``_packed``): then one lane broadcast serves four features' registers
        (2 at 64 lanes a feature, 4 at 128, 8 at 256), else one feature's."""
        return self.regs_a_broadcast * 128 == 4 * self.lanes_a_feature


def hist_plan(n_feat: int, n_bins: int, level: int, block_rows: int) -> HistPlan:
    """A level's feature tiles, one tile's accumulator block and the scoped
    VMEM its kernel takes, from the shape alone; ValueError for a shape no
    tiling holds.

    Up to ``TILE_FEATS`` features are one tile.  ``vmem_bytes`` then bounds
    what Mosaic calls the kernel's scoped allocation: the accumulator once
    (its block index never changes along the row grid), the row blocks twice
    — codes at 128 lanes, node in and out, g and h at one lane padded to 128
    — and ``VMEM_STACK``.  (F = 67, level 8: 16.75 + 5 MiB of blocks,
    Mosaic's own "21.75M", 24.75 with its stack.)  The MXU walks M a 128-row
    tile at a time by itself.

    A level below a full MXU tile of stacked rows packs the codes four a
    word (``_packed``: at more than 64 bins levels 0 to 5): its
    accumulator's features are whole words of four slots (``_block_feats``:
    68 for 67), and at 128 and 256 lanes a feature the kernel's stack grows
    with the block, every matmul group's result live at once: Mosaic's
    scoped count read 4.4 to 4.8 times the block over the 5 MiB of a level
    with a small one (F = 67: 16.0 MiB at level 5 for a 2.1 MiB block; F =
    28: 9.2 for 0.9; my compile, PR 37), so five blocks more are asked for.

    A wider matrix goes in tiles of ``TILE_FEATS`` features, the last one
    ragged.  A tile's accumulator block is counted twice (its index moves
    with the tile, so one is written back while the next fills), the row
    blocks twice — a tile's codes, node, g and h; routing is a pass of its
    own — and ``VMEM_STACK``.  (F = 2000 at 64 bins, 64 lanes a feature,
    level 7: 4 MiB a block, 20 MiB; at 65 to 128 bins 8 MiB and 28 MiB.)

    From the level whose every node, built, stacks ``MXU_ROWS`` rows of
    gradient matrix (4 * 2**level: level 5 at any width) half the nodes are
    built and half derived; below it M is padded to the tile anyway and
    every node is built."""
    derived = 2 ** (level - 1) if level >= 1 and 4 * 2 ** level >= MXU_ROWS else 0
    m_pad = _round_up(2 * (2 ** level - derived), 8)
    tile = min(n_feat, TILE_FEATS)
    tiles = -(-n_feat // tile)
    be = _bins_eff(n_bins)
    packed = _packed(n_bins, 2 * m_pad)
    acc = m_pad * _block_feats(tile, packed) * be * 4
    if tiles == 1:
        blocks = acc + 2 * 4 * block_rows * (_round_up(n_feat, 128) + 4 * 128)
    else:
        blocks = 2 * acc + 2 * 4 * block_rows * (tile + 3 * 128)
    need = blocks + VMEM_STACK + (5 * acc if packed and be >= 128 else 0)
    if need > VMEM_MOST:
        raise ValueError(
            f"hist_plan: level {level} of F={n_feat} features x {n_bins} bins "
            f"at {block_rows}-row blocks does not fit: the accumulator block "
            f"of one {tile}-feature tile is {acc} bytes and the kernel needs "
            f"{need} of {VMEM_MOST} bytes of VMEM (a depth of {level + 1} is "
            "one level too many)")
    return HistPlan(level, derived, m_pad, acc, need, tile, tiles, be,
                    (4 if packed else 1) * be // 128)


def _encode_bf16(L):
    """Hi/lo-bf16 split of the f32 gradient matrix (~2^-16-relative error).

    The two halves share ONE matmul, stacked along M: the MXU pads M to a
    full 128-row tile anyway, and the stack fills one at level 5 (4 * 32
    rows), so up to there two separate matmuls each waste >= half the tile
    — packing them halves the level's MXU passes (~1.4x whole-round at
    1,000,000 rows x 28 on an older chip).  From level 5 on one child a
    parent is built (``hist_plan``), so level 5 stacks half a tile again,
    level 6 one and level 7 two (``HistPlan.m_tiles``).  The result splits
    back and sums in f32, bitwise identical to the two-matmul form."""
    lhi = L.astype(jnp.bfloat16)
    llo = (L - lhi.astype(jnp.float32)).astype(jnp.bfloat16)
    l2 = jnp.concatenate([lhi, llo], axis=1)
    m = L.shape[1]
    decode = lambda acc2: acc2[:m] + acc2[m:]
    return l2, jnp.bfloat16, jnp.float32, decode


def _encode_i8(L):
    """Two-plane int8 fixed-point split, running the MXU at int8 rate (2x
    the bf16 issue rate on v5e-class chips): L is split against the block
    max into two int8 planes (14-bit fixed point, error ~2^-14 of the
    block max — a little tail precision traded for double MXU
    throughput), stacked along M into ONE s8 x s8 -> s32 matmul.

    The scale needs no power-of-two rounding: any scale >= max|L| keeps
    |x| <= 1 (+1 ulp from the reciprocal multiply, far inside the int8
    headroom: |a| <= 64, |b| <= 65 vs the 127 limit), and the ~ulp
    rounding of x and of the f32 decode is negligible against the 2^-14
    quantization step.  (An exact exponent-field split via scalar bitcast
    does NOT lower through Mosaic — tpu.bitcast wants vectors.)"""
    m = L.shape[1]
    amax = jnp.max(jnp.abs(L))
    # Floor at the smallest NORMAL f32: keeps the all-zero-block guard
    # (1/tiny is finite) without zeroing tiny-but-nonzero blocks, and
    # 1/scale can never flush to a subnormal zero on hardware.
    scale = jnp.maximum(amax, jnp.float32(1.1754944e-38))
    x = L * (1.0 / scale)
    a = jnp.round(x * 64.0)                      # |a| <= 64
    b = jnp.round((x - a * (1.0 / 64.0)) * 8192.0)  # residual <~ 2^-7 => |b| <= 65
    l2 = jnp.concatenate([a, b], axis=1).astype(jnp.int8)

    def decode(acc2):
        # |acc| <= R * 64 = 2^16 — exact in int32 and in the f32 convert.
        hi = acc2[:m].astype(jnp.float32)
        lo = acc2[m:].astype(jnp.float32)
        return (hi * (1.0 / 64.0) + lo * (1.0 / 8192.0)) * scale

    return l2, jnp.int8, jnp.int32, decode


def _accum(xb_blk, L, out_ref, *, n_bins: int, n_feat: int, fc: int, i8: bool,
           packed: bool, r_split: int = 1, feats_left=None):
    """out_ref[m, f*Beff+b] += sum_r L[r, m] * [xb_blk[r, f] == b], via the
    MXU: the encoded gradient planes are contracted against per-feature-
    group bin-indicator matrices built in VMEM.  Where the kernel packs the
    codes (``packed``: ``_packed``) ``n_feat`` counts the block's feature
    SLOTS, whole words of four, slot 4 * q + b of ``out_ref`` is feature
    b * n_feat / 4 + q (``_cut_lanes`` puts them back in order), and
    ``xb_blk`` is whole 128-lane tiles of codes, each in 0..255.

    ``r_split > 1`` splits the row block into that many independent
    sub-contractions per feature group (raw accumulators summed, one
    decode; bitwise identical to the unsplit path for i8, f32-sum
    reassociation only for bf16) — an overlap experiment: sub-block i's
    matmul (MXU) has no data dependency on sub-block i+1's indicator
    build (VPU), giving Mosaic's scheduler explicit room to run them
    concurrently.  The indicator rebuild was modeled as co-dominant with
    the int8-rate matmul (older chip figure, not re-measured); the
    ablation's rsplit rows (RESULTS/final_pass.jsonl) measured no gain.

    ``feats_left`` is for a feature tile (``xb_blk`` holds its ``n_feat``
    code lanes): the matrix's features from this tile's first one on, a
    scalar of the grid.  A group that starts at or past it — the ragged
    last tile's tail, whose code lanes hold nothing — is skipped; lanes
    past it inside a group fall into columns the wrapper cuts off."""
    be = _bins_eff(n_bins)
    l2, onehot_dtype, acc_dtype, decode = (_encode_i8 if i8 else _encode_bf16)(L)
    r = xb_blk.shape[0]
    rs = r // r_split
    # The indicator compare runs at i32 lane width BY TARGET CONSTRAINT,
    # not choice: narrow codes (int8 4/lane, bf16 2/lane) would cut the
    # co-dominant VPU rebuild (~3.7 ms/level at 1,000,000 rows x 28, an
    # older chip's figure) 2-4x, but the chip's Mosaic
    # rejects sub-32-bit vector compares — "Target does not support this
    # comparison" on vector<...xi8> cmpi AND vector<...xbf16> cmpf
    # (RESULTS/narrow_compare_rejection.txt; the local jax.export gate
    # accepts both, so only on-chip compiles catch this).
    unit = 1            # features a lane broadcast serves
    if packed:
        # The lane broadcast of a code column bounds a level of up to one
        # MXU tile (3 cycles an (8 rows, 1 column) whatever the lanes it
        # feeds: PERF.md section 5), so ONE broadcast serves four features:
        # the block's codes are packed four a 32-bit word here, in VMEM —
        # word q holds, a byte each, feature slots q, s + q, 2s + q, 3s + q
        # of the block (s = n_feat / 4): the block rolled along its lanes
        # three times, shifted and or-ed, no shuffle a code — and a register
        # is one and and one compare of the word's broadcast against keys
        # shifted once a kernel (never the word).  At 64 lanes a feature a
        # register holds two bytes: lanes 0-63 pick bytes 0 | 2 and match
        # them with the lane's bin, lanes 64-127 bytes 1 | 3 (two registers
        # a broadcast).  At 128 and 256 lanes a register is one byte's (four
        # and eight registers a broadcast), and at 256 the byte's two
        # registers, bins 0-127 and 128-255, share its and.  Byte 3 reaches
        # the sign bit (mask 255 << 24, keys of bins >= 128): the shifts run
        # on int32 lanes and wrap, the compare is an equality of bit
        # patterns, and no Python int is shifted.  Lanes past the matrix
        # (the last lanes of a block wider than the codes, the ragged last
        # tile) hold anything, and a shifted word of them spills into higher
        # bytes only: slots further past it still.  (Rolls, not lane slices:
        # the slice at 2s compiles, and on the chip gives wrong bytes,
        # silently; PR 35.)
        unit = 4
        lanes = min(be, 128)
        b_iota = lax.broadcasted_iota(jnp.int32, (rs, 128), 1)
        s, width = n_feat // unit, xb_blk.shape[1]
        words = xb_blk
        for b in range(1, unit):
            words = words | (pltpu.roll(xb_blk, width - b * s, 1) << (8 * b))
        shift = jnp.where(b_iota >= lanes, 8, 0)
        steps = range(0, 32, 8 * 128 // lanes)      # a register's (first) byte

        def key_of(p, hi):
            bins = b_iota & (lanes - 1)
            return jnp.left_shift(bins + hi if hi else bins, shift + p)

        masks = [jnp.left_shift(255, shift + p) for p in steps]
        keys = [[key_of(p, hi) for hi in range(0, be, 128)] for p in steps]
    else:
        b_iota = lax.broadcasted_iota(jnp.int32, (rs, be), 1)

    def indicator(lo, gi, k):
        if not packed:
            return [xb_blk[lo : lo + rs, f : f + 1] == b_iota
                    for f in range(gi, gi + k)]
        cols = []
        for q in range(gi, gi + k):
            for mask, regs in zip(masks, keys):
                a = words[lo : lo + rs, q : q + 1] & mask
                cols += [a == key for key in regs]
        return cols

    def group(gi, k):
        # Sum the RAW accumulators across sub-blocks and decode once:
        # decode is linear, so this is bitwise identical to the unsplit
        # path for i8 (int32 adds commute exactly) and costs one decode
        # per group instead of r_split.
        acc2 = None
        for s in range(r_split):
            lo = s * rs
            onehot = jnp.concatenate(
                indicator(lo, gi, k), axis=1).astype(onehot_dtype)
            part = lax.dot_general(l2[lo : lo + rs], onehot, _DN,
                                   preferred_element_type=acc_dtype)
            acc2 = part if acc2 is None else acc2 + part
        out_ref[:, gi * unit * be : (gi + k) * unit * be] += decode(acc2)

    # a group is ``fc`` features: so many code columns, or words of four
    for gi in range(0, n_feat // unit, fc // unit):
        k = min(fc // unit, n_feat // unit - gi)
        if feats_left is None:
            group(gi, k)
        else:
            pl.when(gi < feats_left)(functools.partial(group, gi, k))


def _gradient_matrix(node, g, h, *, n_nodes: int, m_pad: int, built_row=None):
    """L[r, m]: g_r at column node_r, h_r at column n_nodes+node_r.

    At a derived level ``built_row`` is the ``(1, m_pad)`` lane table of
    ``_built_table`` and the columns are the PARENTS: a row lies in column
    ``node_r >> 1`` iff it went to the child its parent builds
    (``node_r & 1 == built_right[node_r >> 1]``), else in none — a compare
    against the table along the lanes, no lookup a row."""
    r = node.shape[0]
    m_iota = lax.broadcasted_iota(jnp.int32, (r, m_pad), 1)
    is_g = m_iota < n_nodes
    idx = jnp.where(is_g, m_iota, m_iota - n_nodes)
    if built_row is None:
        sel = node == idx
    else:
        sel = ((node >> 1) == idx) & ((node & 1) == built_row)
    sel = sel & (m_iota < 2 * n_nodes)
    val = jnp.where(is_g, g, h)  # (R,1) -> (R, m_pad)
    return jnp.where(sel, val, 0.0)


def _built_table(built_right, m_pad: int):
    """``built_right`` ``[n]`` (1 where a parent builds its right child) as
    the kernels take it: row 0 of an ``(8, lanes)`` int32 table holds it
    twice, once under the g columns of the gradient matrix and once under
    the h columns."""
    n = built_right.shape[0]
    tab = jnp.zeros((8, _round_up(m_pad, 128)), jnp.int32)
    return tab.at[0, : 2 * n].set(jnp.tile(built_right.astype(jnp.int32), 2))


def _split_of(node, feat_row, thr_row, *, p_pad: int):
    """(feat[node], thr[node]) — the split-table lookup via lane-masked
    reductions (no gathers)."""
    r = node.shape[0]
    p_iota = lax.broadcasted_iota(jnp.int32, (r, p_pad), 1)
    pm = node == p_iota  # (R, P) one-hot over parent nodes
    fsel = jnp.sum(jnp.where(pm, feat_row, 0), axis=1, keepdims=True)
    tsel = jnp.sum(jnp.where(pm, thr_row, 0), axis=1, keepdims=True)
    return fsel, tsel


def _route(xb_blk, node, feat_row, thr_row, *, p_pad: int, n_feat: int):
    """node' = 2*node + [x[feat[node]] > thr[node]] — split-table lookup and
    feature select via lane-masked reductions (no gathers)."""
    fsel, tsel = _split_of(node, feat_row, thr_row, p_pad=p_pad)
    f_iota = lax.broadcasted_iota(jnp.int32, (node.shape[0], n_feat), 1)
    xv = jnp.sum(jnp.where(f_iota == fsel, xb_blk, 0), axis=1, keepdims=True)
    return node * 2 + (xv > tsel).astype(jnp.int32)


# -- level 0: histogram at the root ----------------------------------------


def _level0_kernel(xb_ref, g_ref, h_ref, out_ref, *, n_bins, n_feat, fc, i8,
                   packed, r_split=1):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    r = g_ref.shape[1]
    node = jnp.zeros((r, 1), jnp.int32)
    L = _gradient_matrix(node, g_ref[0], h_ref[0], n_nodes=1, m_pad=8)
    _accum(xb_ref[0], L, out_ref, n_bins=n_bins, n_feat=n_feat, fc=fc, i8=i8,
           packed=packed, r_split=r_split)


# -- level d >= 1: route + histogram ---------------------------------------


def _level_kernel(xb_ref, node_ref, g_ref, h_ref, feat_ref, thr_ref, *refs,
                  n_nodes, n_bins, n_feat, m_pad, p_pad, fc, i8, packed,
                  r_split=1):
    """``refs``: at a derived level the built-child table, then the
    accumulator (``n_nodes`` built nodes) and the routed node ids."""
    *built_ref, out_ref, node_out_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    node = _route(xb_ref[0], node_ref[0], feat_ref[0:1], thr_ref[0:1],
                  p_pad=p_pad, n_feat=xb_ref.shape[2])
    node_out_ref[0] = node
    L = _gradient_matrix(
        node, g_ref[0], h_ref[0], n_nodes=n_nodes, m_pad=m_pad,
        built_row=built_ref[0][0:1, :m_pad] if built_ref else None)
    _accum(xb_ref[0], L, out_ref, n_bins=n_bins, n_feat=n_feat, fc=fc, i8=i8,
           packed=packed, r_split=r_split)


# -- one feature tile of a level wider than TILE_FEATS: histogram only ------


def _tile_kernel(xb_ref, *refs, n_feat, tile, n_nodes, n_bins, m_pad, fc, i8,
                 packed, r_split=1):
    """Grid (feature tile, row block), the rows innermost: ``out_ref`` is
    this tile's accumulator block, zeroed at its first row block.  The rows
    come routed: ``refs`` is their node ids (not at the root, where every
    row is at node 0), at a derived level the built-child table, then g, h
    and the output."""
    *lead, g_ref, h_ref, out_ref = refs

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    node = (lead[0][0] if lead
            else jnp.zeros((g_ref.shape[1], 1), jnp.int32))
    L = _gradient_matrix(
        node, g_ref[0], h_ref[0], n_nodes=n_nodes, m_pad=m_pad,
        built_row=lead[1][0:1, :m_pad] if len(lead) > 1 else None)
    _accum(xb_ref[0], L, out_ref, n_bins=n_bins, n_feat=tile, fc=fc, i8=i8,
           packed=packed, r_split=r_split,
           feats_left=n_feat - pl.program_id(0) * tile)


# -- routing-only pass (leaf assignment without histogramming) -------------


def _route_kernel(xb_ref, node_ref, feat_ref, thr_ref, node_out_ref, *,
                  p_pad, n_feat):
    node_out_ref[0] = _route(xb_ref[0], node_ref[0], feat_ref[0:1],
                             thr_ref[0:1], p_pad=p_pad, n_feat=n_feat)


def _route_tile_kernel(xb_ref, node_ref, feat_ref, thr_ref, node_out_ref,
                       fsel_ref, tsel_ref, *, p_pad, tile):
    """Grid (row block, feature tile), the tiles innermost: the code of the
    feature a row's node splits on lies in one tile, so the lane-masked sums
    of all tiles add up to it in ``node_out_ref``, which the last tile turns
    into the node one level down.  The split-table lookup is the row
    block's, made at its first tile and kept in scratch.  (Lanes past F in
    the ragged last tile match no feature.)"""
    t = pl.program_id(1)
    node = node_ref[0]

    @pl.when(t == 0)
    def _first():
        fsel_ref[...], tsel_ref[...] = _split_of(
            node, feat_ref[0:1], thr_ref[0:1], p_pad=p_pad)
        node_out_ref[0] = jnp.zeros_like(node)

    f_iota = lax.broadcasted_iota(jnp.int32, (node.shape[0], tile), 1)
    node_out_ref[0] += jnp.sum(
        jnp.where(f_iota == fsel_ref[...] - t * tile, xb_ref[0], 0), axis=1,
        keepdims=True)

    @pl.when(t == pl.num_programs(1) - 1)
    def _turn():
        node_out_ref[0] = node * 2 + (
            node_out_ref[0] > tsel_ref[...]).astype(jnp.int32)


# -- final pass: route to leaves + margin update in one kernel -------------


def _route_margin_kernel(xb_ref, node_ref, margin_ref, feat_ref, thr_ref,
                         leaf_ref, margin_out_ref, node_out_ref, *,
                         p_pad, l_pad, n_feat):
    node = _route(xb_ref[0], node_ref[0], feat_ref[0:1], thr_ref[0:1],
                  p_pad=p_pad, n_feat=n_feat)
    node_out_ref[0] = node
    # margin += leaf[node] without a gather: the leaf table is tiny (64
    # entries at depth 6, 256 at depth 8), so the same lane-masked
    # reduction as _route's split lookup replaces XLA's gather of every row
    # from a small table.
    r = node.shape[0]
    l_iota = lax.broadcasted_iota(jnp.int32, (r, l_pad), 1)
    lv = jnp.sum(jnp.where(node == l_iota, leaf_ref[0:1], 0.0), axis=1,
                 keepdims=True)
    margin_out_ref[0] = margin_ref[0] + lv


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def route_margin_level(xb3, node3, margin3, feat, thr, leaf, *, depth: int,
                       interpret: bool = False):
    """Final fused pass: route rows through the level-(depth-1) split tables
    to their leaves AND apply the margin update ``margin += leaf[node]`` in
    the same streaming pass.  Returns (margin3', leaf_node3).  Replaces
    route_level + a host-level gather: XLA lowers a gather of every row
    from a 2**depth-entry table poorly on TPU, while the in-kernel
    lane-masked sum is a few VPU ops per row."""
    nb, R, F = xb3.shape
    if F > TILE_FEATS:
        # wider than one tile of codes: the tiled routing pass, and the
        # leaf lookup left to XLA
        node3 = route_level(xb3, node3, feat, thr, depth=depth,
                            interpret=interpret)
        return margin3 + leaf[node3], node3
    n_prev = 2 ** (depth - 1)
    n_leaves = 2 ** depth
    p_pad = _round_up(n_prev, 128)
    l_pad = _round_up(n_leaves, 128)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    leafp = jnp.zeros((8, l_pad), jnp.float32).at[0, :n_leaves].set(leaf)
    return pl.pallas_call(
        functools.partial(_route_margin_kernel, p_pad=p_pad, l_pad=l_pad,
                          n_feat=F),
        grid=(nb,),
        in_specs=[
            _blk(R, F), _blk(R, 1), _blk(R, 1),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
            pl.BlockSpec((8, l_pad), lambda i: (0, 0)),
        ],
        out_specs=[_blk(R, 1), _blk(R, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((nb, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((nb, R, 1), jnp.int32),
        ],
        interpret=interpret,
        name=f"route_margin_d{depth}",
    )(xb3, node3, margin3, featp, thrp, leafp)


@functools.partial(jax.jit, static_argnames=("depth", "interpret"))
def route_level(xb3, node3, feat, thr, *, depth: int, interpret: bool = False):
    """Route rows one level down through the level-(depth-1) split tables —
    no histogram: the leaf (g, h) masses are read off the final level's
    histogram instead (models.gbdt.split_child_masses), so the last row
    pass only needs the leaf assignment for the margin update."""
    nb, R, F = xb3.shape
    n_prev = 2 ** (depth - 1)
    p_pad = _round_up(n_prev, 128)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    if F > TILE_FEATS:
        tab = pl.BlockSpec((8, p_pad), lambda i, t: (0, 0))
        row = pl.BlockSpec((1, R, 1), lambda i, t: (i, 0, 0))
        return pl.pallas_call(
            functools.partial(_route_tile_kernel, p_pad=p_pad, tile=TILE_FEATS),
            grid=(nb, -(-F // TILE_FEATS)),
            in_specs=[
                pl.BlockSpec((1, R, TILE_FEATS), lambda i, t: (i, 0, t)),
                row, tab, tab,
            ],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((nb, R, 1), jnp.int32),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.int32)] * 2,
            interpret=interpret,
            name=f"route_level_d{depth}",
        )(xb3, node3, featp, thrp)
    return pl.pallas_call(
        functools.partial(_route_kernel, p_pad=p_pad, n_feat=F),
        grid=(nb,),
        in_specs=[
            _blk(R, F), _blk(R, 1),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
        ],
        out_specs=_blk(R, 1),
        out_shape=jax.ShapeDtypeStruct((nb, R, 1), jnp.int32),
        interpret=interpret,
        name=f"route_level_d{depth}",
    )(xb3, node3, featp, thrp)


# -- host wrappers (pre-blocked (nb, R, .) arrays) -------------------------


_blk = lambda R, k: pl.BlockSpec((1, R, k), lambda i: (i, 0, 0))


def _vmem_params(plan: HistPlan):
    """Ask Mosaic for the plan's scoped VMEM where its default may not hold
    the kernel; nothing where it does."""
    if plan.vmem_bytes <= VMEM_DEFAULT:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=plan.vmem_bytes)


def _one_block(n_feat: int, n_bins: int, packed: bool):
    """What a kernel with ONE accumulator block over all ``n_feat`` features
    takes: the code lanes of its block — where it packs the codes whole
    128-lane tiles (what a block of them fills in VMEM anyway), so that
    ``_accum`` rolls whole registers; the lanes past the matrix hold
    anything, as the ragged last tile's do — the block's feature slots, its
    lanes and the feature slots a matmul group."""
    slots = _block_feats(n_feat, packed)
    return (_round_up(n_feat, 128) if packed else n_feat, slots,
            slots * _bins_eff(n_bins), _pick_fc(slots, n_bins, packed))


def _cut_lanes(out, n_feat: int, n_bins: int, tile: int, packed: bool):
    """A kernel's ``(m, lanes)`` accumulator as ``(m, F, n_bins)``: the pad
    lanes of every feature, and the feature slots past F that fill the last
    word or the ragged last tile, cut off.  From a kernel that packs the
    codes a tile of ``tile`` feature slots comes word by word (``_accum``):
    slot 4 * q + b holds feature b * tile / 4 + q, and is put back in the
    features' order here, in the pass that cuts."""
    be = _bins_eff(n_bins)
    if packed:
        out = out.reshape(out.shape[0], -1, tile // 4, 4, be).swapaxes(2, 3)
    return out.reshape(out.shape[0], -1, be)[:, :n_feat, :n_bins]


def _hist_tiles(plan: HistPlan, xb3, node3, g3, h3, *, n_bins, interpret,
                mxu_i8, r_split, name, built=()):
    """A level wider than one tile: the ``(m_pad, F, B)`` sums of rows that
    come routed (``node3`` None at the root; ``built`` holds the built-child
    table of a derived level), a sweep of every row block a feature tile.
    The kernel's output is whole tiles wide; the ragged last tile's tail is
    cut off here."""
    nb, R, F = xb3.shape
    be = plan.lanes_a_feature
    tile, tiles, m_pad = plan.tile_feats, plan.feat_tiles, plan.m_pad
    row = pl.BlockSpec((1, R, 1), lambda t, i: (i, 0, 0))
    rows = [a for a in (node3, g3, h3) if a is not None]
    specs = [row] * len(rows)
    rows[1:1] = built
    specs[1:1] = [pl.BlockSpec(b.shape, lambda t, i: (0, 0)) for b in built]
    out = pl.pallas_call(
        functools.partial(
            _tile_kernel, n_feat=F, tile=tile, n_nodes=plan.nodes_built,
            n_bins=n_bins, m_pad=m_pad, fc=_pick_tile_fc(n_bins), i8=mxu_i8,
            packed=plan.packed, r_split=r_split),
        grid=(tiles, nb),
        in_specs=[pl.BlockSpec((1, R, tile), lambda t, i: (i, 0, t))] + specs,
        out_specs=pl.BlockSpec((m_pad, tile * be), lambda t, i: (0, t)),
        out_shape=jax.ShapeDtypeStruct((m_pad, tiles * tile * be), jnp.float32),
        interpret=interpret,
        name=name,
        compiler_params=_vmem_params(plan),
    )(xb3, *rows)
    return _cut_lanes(out, F, n_bins, tile, plan.packed)


@functools.partial(
    jax.jit, static_argnames=("n_bins", "interpret", "mxu_i8", "r_split")
)
def hist_level0(xb3, g3, h3, *, n_bins: int, interpret: bool = False,
                mxu_i8: bool = False, r_split: int = 1):
    """Root histogram; [1, F, B, 2].  ``r_split``: see _accum."""
    nb, R, F = xb3.shape
    _check_r_split(R, r_split)
    plan = hist_plan(F, n_bins, 0, R)
    if plan.feat_tiles > 1:
        out = _hist_tiles(plan, xb3, None, g3, h3, n_bins=n_bins,
                          interpret=interpret, mxu_i8=mxu_i8, r_split=r_split,
                          name="hist_level0")
        return jnp.stack([out[0:1], out[1:2]], axis=-1)
    codes, slots, lanes, fc = _one_block(F, n_bins, plan.packed)
    out = pl.pallas_call(
        functools.partial(_level0_kernel, n_bins=n_bins, n_feat=slots, fc=fc,
                          i8=mxu_i8, packed=plan.packed, r_split=r_split),
        grid=(nb,),
        in_specs=[_blk(R, codes), _blk(R, 1), _blk(R, 1)],
        out_specs=pl.BlockSpec((8, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, lanes), jnp.float32),
        interpret=interpret,
        name="hist_level0",
    )(xb3, g3, h3)
    out = _cut_lanes(out, F, n_bins, slots, plan.packed)
    return jnp.stack([out[0:1], out[1:2]], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "n_bins", "interpret", "mxu_i8", "r_split"),
)
def hist_level(xb3, node3, g3, h3, feat, thr, built_right=None, *, depth: int,
               n_bins: int, interpret: bool = False, mxu_i8: bool = False,
               r_split: int = 1):
    """Route one level down and histogram; returns
    ([nodes built, F, B, 2], node3').  ``feat``/``thr`` are the level-(depth-1)
    split tables, shape [2**(depth-1)].  ``r_split``: see _accum.

    Up to level 4 every node is built: ``[2**depth, F, B, 2]``.  From the
    level ``hist_plan`` derives (5, at any width) the histogram is of ONE
    child a parent, ``[2**(depth-1), F, B, 2]`` in parent order:
    ``built_right`` ``[2**(depth-1)]`` says which (1: the right one;
    ``models.gbdt.smaller_child`` picks the lighter), and a row that went to
    the other child is routed and summed nowhere.  The caller gets the
    siblings from the parents' histogram (``derive_siblings``), after its
    collective.  The routed node ids and the kernel's name are a built
    level's.

    Wider than ``TILE_FEATS`` features routing and histogram are two
    kernels: ``route_level``'s tiled pass, then a histogram sweep a feature
    tile (``hist_plan``).  The kernel asks for ``hist_plan``'s scoped VMEM
    where Mosaic's default might not hold it (F = 67 at every level, F = 28
    at levels 4 and 5, since the packed kernels' stack is counted), and is
    the same kernel elsewhere.  Compiled on its own, a 16.75 MiB
    accumulator block at F = 67 (level 8 now, level 7 with every node built)
    is refused at the default, on the chip too; inside the whole round XLA
    keeps the accumulator in VMEM itself and the default holds, and asking
    costs the round nothing (PR 27)."""
    nb, R, F = xb3.shape
    _check_r_split(R, r_split)
    plan = hist_plan(F, n_bins, depth, R)
    n_nodes, m_pad = plan.nodes_built, plan.m_pad
    n_prev = 2 ** (depth - 1)
    if bool(plan.nodes_derived) != (built_right is not None):
        raise ValueError(
            f"hist_level: level {depth} builds {n_nodes} of {2 ** depth} nodes, "
            "so it takes built_right " + (
                f"[{n_prev}]" if plan.nodes_derived else "None"))
    built = [] if built_right is None else [_built_table(built_right, m_pad)]
    if plan.feat_tiles > 1:
        node_out = route_level(xb3, node3, feat, thr, depth=depth,
                               interpret=interpret)
        out = _hist_tiles(plan, xb3, node_out, g3, h3, n_bins=n_bins,
                          interpret=interpret, mxu_i8=mxu_i8, r_split=r_split,
                          name=f"hist_level_d{depth}", built=built)
        hist = jnp.stack([out[:n_nodes], out[n_nodes : 2 * n_nodes]], axis=-1)
        return hist, node_out
    p_pad = _round_up(n_prev, 128)
    codes, slots, lanes, fc = _one_block(F, n_bins, plan.packed)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    out, node_out = pl.pallas_call(
        functools.partial(
            _level_kernel, n_nodes=n_nodes, n_bins=n_bins, n_feat=slots,
            m_pad=m_pad, p_pad=p_pad, fc=fc, i8=mxu_i8, packed=plan.packed,
            r_split=r_split,
        ),
        grid=(nb,),
        in_specs=[
            _blk(R, codes), _blk(R, 1), _blk(R, 1), _blk(R, 1),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
        ] + [pl.BlockSpec(b.shape, lambda i: (0, 0)) for b in built],
        out_specs=[
            pl.BlockSpec((m_pad, lanes), lambda i: (0, 0)),
            _blk(R, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, lanes), jnp.float32),
            jax.ShapeDtypeStruct((nb, R, 1), jnp.int32),
        ],
        interpret=interpret,
        name=f"hist_level_d{depth}",
        compiler_params=_vmem_params(plan),
    )(xb3, node3, g3, h3, featp, thrp, *built)
    out = _cut_lanes(out, F, n_bins, slots, plan.packed)
    hist = jnp.stack([out[:n_nodes], out[n_nodes : 2 * n_nodes]], axis=-1)
    return hist, node_out


def derive_siblings(parents, built, built_right):
    """A derived level whole: ``built`` ``[n, F, B, 2]`` is one child of each
    of the ``n`` parents (``built_right``: 1 where it is the right one) and
    its sibling is ``parents - built``, in float32 on the accumulators'
    decoded sums; ``[2 * n, F, B, 2]`` in node order (2 * parent + went
    right).  Both operands are sums over the same rows' hi/lo planes, so the
    split's round-off cancels and the difference carries float32 summation
    error alone, the parent's in absolute terms."""
    right = built_right.astype(bool)[:, None, None, None]
    other = parents - built
    return jnp.stack([jnp.where(right, other, built),
                      jnp.where(right, built, other)],
                     axis=1).reshape(2 * built.shape[0], *built.shape[1:])


# -- blocking helpers -------------------------------------------------------


def block_rows(x, block: int = 1024):
    """Pad a [n, ...] array with zeros to a block multiple and reshape to
    (nb, block, k) for the fused kernels.  Returns (blocked, n)."""
    n = x.shape[0]
    n_pad = _round_up(n, block)
    if x.ndim == 1:
        x = x[:, None]
    if n_pad != n:
        x = jnp.pad(x, ((0, n_pad - n), (0, 0)))
    return x.reshape(n_pad // block, block, x.shape[1]), n


def unblock_rows(x3, n: int):
    """Inverse of block_rows for [nb, R, 1] -> [n]."""
    return x3.reshape(-1)[:n] if x3.shape[-1] == 1 else x3.reshape(-1, x3.shape[-1])[:n]
