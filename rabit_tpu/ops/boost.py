"""Fused Pallas kernels for one GBDT boosting round on TPU.

The hook-based ``models.gbdt.train_round`` makes one pass over the rows per
level for histograms plus separate passes for routing, leaf fit, and margin
update — each a round-trip through HBM.  These kernels fuse a level's work
into a single streaming pass per row block:

* ``hist_level0``   — histogram at the root (no routing needed).
* ``hist_level``    — route rows one level down through the parent split
  table (split lookup + feature select + compare, all in VMEM) and
  histogram at the new nodes, emitting the updated node ids as a second
  output.  From level 3 on it histograms ONE child of every parent, the one
  a table names, and ``derive_siblings`` reads the other off the parents'
  histogram — XGBoost's and LightGBM's subtraction trick, at the levels
  where it shortens the matmul.
* ``route_level``   — route one level down, no histogram: a level's pass of
  its own where the codes are wider than one tile.
* ``route_margin_level`` — the leaves' pass: route to the leaves and add
  each row's leaf to the margin, no node ids written (the leaves' (g, h)
  masses are read off the last level's histogram).

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
eight at 256 (``_accum``, ``_packed``, ``HistPlan.regs_a_broadcast``).  There
the MXU holds the indicator, a 128 x 128 tile of it loaded for every
128-row tile of stacked gradient matrix streamed through it.  From a full
tile of stacked rows on the MXU holds the gradient planes instead and the
indicator, built transposed — a feature's bins down the sublanes, one
sublane broadcast of a row of codes for all its registers — streams
through them (``_holds_gradients``, ``_accum_held``): the same work of the
MXU for a seventh of the tile loads at Criteo's level 7, its feature groups
a loop where the unrolled grid step would outgrow the instruction memory
(``_groups_a_body``).  The rules
read ``n_bins`` and the level's stacked rows alone; no option chooses.

``hist_plan`` reckons what a level asks of the chip from the shape alone.
The stacked gradient matrix has 4 rows a node built (g and h, hi and lo
plane), padded to whole sublane tiles (16 rows at up to 4 nodes), and the
MXU charges some 27 cycles a 128 x 128 tile of the operand it holds and one
more a stacked row streamed through it (PERF.md section 5, "What they
separate": 59 | 92 | 165 cycles a tile at 32 | 64 | 128 rows), so the rows
cost time at every level.  So a level where building half its nodes
stacks fewer padded rows than building them all — level 3 on, at any
width — builds half of them, one child a parent, with the matmul, the
accumulator block and the VMEM of the level above; no option chooses.
Up to ``TILE_FEATS`` features (one 128-lane tile of blocked codes) the
accumulator ``(2 * nodes built, F * B_eff)`` float32 (F to whole words of
four feature slots where the level packs the codes) is ONE block held
across the row grid, and routing rides the histogram's sweep.  A wider
matrix is walked in feature tiles: a grid over (feature tile, row block),
the row axis innermost, one tile's accumulator block resident across its
row sweep; the feature a node splits on may lie in any tile, so routing is
a pass of its own there (``route_level``, its code block tiled too), once a
level.  The plan asks Mosaic for the scoped VMEM that the blocks take where
they pass its default, or refuses the shape by name where no tiling holds
it.

Categorical features (``GBDTConfig.categorical``) split by sets of codes
(``cat_split``, one kernel a level, ``cat_split_d<d>``).  For node n and
categorical feature f let (G_c, H_c) be the gradient and hessian sums of
code c = 0..255 among n's rows; the candidates are the present codes,
H_c > 0.  They are ordered by k_c = G_c / (H_c + lambda) ascending, ties by
code ascending; each prefix of that order of size 1 .. K' - 1 (K' present
codes) is a left set S, GL = sum_{c in S} G_c and HL likewise, scored by
XGBoost's gain GL^2/(HL+lambda) + GR^2/(HR+lambda) - G^2/(H+lambda) under
the floor HL, HR >= min_child_weight; the best prefix is n's candidate on
f.  A row goes LEFT iff its code is in S — an absent code is in no set and
goes right — and numeric features keep ``code > thr``.  A node's winner
over all features: the highest gain, then the lowest feature, then the
lowest bin (numeric) or the shortest prefix (categorical).  A set is eight
int32 words, code c at bit c & 31 of word c >> 5 (``SET_WORDS``); the
routing kernels take a level's sets as a table only where the
configuration has categorical features (``route_words``, ``_set_table``).

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
    chip runs, PR 37).  Where the MXU holds the gradient planes
    (``_holds_gradients``: from a full tile on) nothing is packed."""
    return n_bins <= 256 and (_bins_eff(n_bins) < 128 or m_rows < MXU_ROWS)


def _holds_gradients(m_rows: int, mxu_i8: bool = False, r_split: int = 1) -> bool:
    """Whether a kernel over ``m_rows`` stacked rows of gradient matrix
    contracts with the gradient planes as the operand the MXU holds and the
    indicator, transposed, as the one it streams (``_accum_held``).  The
    MXU charges some 27 cycles to load a 128 x 128 tile of the operand it
    holds and one a row streamed through it (PERF.md section 5, PR 37).
    Holding the indicator, a block of F features x B_eff lanes loads
    R / 128 x F x B_eff / 128 tiles and streams ``m_rows`` through each
    (Criteo: 1,072 loads a block for 128 or 256 rows); holding the
    gradients, it loads R / 128 x ``m_rows`` / 128 tiles a matmul group
    (8 or 16) and streams the group's features x B_eff rows (2,048: 144
    loads a block at Criteo's level 7, 72 at level 6).  The work of the MXU is
    the same; the loads go where the rows streamed fill a tile or more:
    ``m_rows >= MXU_ROWS``, the line ``_packed`` draws.  The int8 planes and
    ``r_split > 1`` keep the indicator held: no cell runs them."""
    return m_rows >= MXU_ROWS and not mxu_i8 and r_split == 1


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


def _pick_held_fc(n_bins: int) -> int:
    """Features a matmul group where the MXU holds the gradient planes
    (``_accum_held``): 2,048 rows of indicator a dot at every width — 32
    features at 64 lanes a feature, 16 at 128, 8 at 256.  Kernels alone
    (``_dev/time39.py``; my chip runs, PR 39), ms.  F = 67 x 256 bins x
    2,621,440 rows, every group unrolled, groups of 2 | 4 | 6 | 8 | 12 | 16
    features: level 6 66.2 | 65.4 | 64.6 | 63.7 | 63.6 | 63.6 (the parent
    70.5); one group a loop step, 4 | 6 | 8 | 16: level 6 74.7 | 72.2 | 70.9
    | 68.9, level 7 129.5 | 128.1 | 127.2 | 126.1 (the parent 150.3).  F =
    2,000 x 64 bins x 400,384 rows, unrolled, groups of 4 | 8 | 16 | 24 |
    32: level 6 112.6 | 94.3 | 85.2 | 83.3 | 81.2 (the parent 101.7), level
    7 163.7 | 152.9 | 147.5 | 147.2 | 145.8 (162.2); 64 features and more
    a group run out of VMEM.  How many groups a loop step takes is
    ``_groups_a_body``'s."""
    return max(1, 2048 // _bins_eff(n_bins))


#: MXU cycles of work that the groups traced into one step of a held
#: kernel's loop may hold with one group more: a grid step is about as many
#: bundles as its MXU has cycles, and past 65,536 bundles, the instruction
#: memory, it runs slower than its schedule (``_accum_held``; PR 39)
HELD_STEP_CYCLES = 49_152


def _groups_a_body(full: int, group_cycles: int) -> int:
    """Whole feature groups of ``group_cycles`` MXU cycles each traced into
    one step of ``_accum_held``'s loop: the most that divides ``full`` and,
    with one group more (the ragged last group, the routing, the
    transposes), stays within ``HELD_STEP_CYCLES``; ``full`` (no loop)
    where the block fits.  Criteo's level 6 (8 groups of 4,096 cycles) and
    Epsilon's tile (4 of 4,096 or 8,192) are unrolled whole, Criteo's level
    7 (8 of 8,192) is a loop of two steps of four groups."""
    return max(k for k in range(1, full + 1) if full % k == 0 and (
        k == 1 or (k + 1) * group_cycles <= HELD_STEP_CYCLES))


#: features of one tile of blocked codes (its 128 lanes): up to here a level
#: is one block, beyond it the kernels walk the features a tile at a time
TILE_FEATS = 128
#: rows of one MXU tile: the stacked gradient matrix (hi and lo plane, g and
#: h, a node built: 4 * 2**(level - 1) rows from level 3 on) fills it at
#: level 6
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
    """What one level's ``hist_level`` asks of the chip.  A level where
    building one child a parent stacks fewer padded rows of gradient matrix
    than building every node (level 3 on: the MXU charges a stacked row at
    every level) is *derived*: one child of every parent is
    accumulated and its sibling is the parent's histogram less the built
    one's (``derive_siblings``), so level d runs the matmul, the accumulator
    block and the VMEM of level d - 1.

    ``mxu_holds`` says which operand the MXU holds: ``"indicator"`` below a
    full tile of stacked rows (``m_rows < MXU_ROWS``: levels 0 to 5), the
    gradient matrix streamed through its tiles, and ``"gradients"`` from a
    full tile on (``_holds_gradients``: levels 6 and 7 of Criteo and
    Epsilon), the indicator built transposed and streamed through the
    gradient planes; the accumulator block is then ``(F * B_eff, m_pad)``,
    a feature's bins down the sublanes, and ``regs_a_broadcast`` counts the
    registers one SUBLANE broadcast of a row of codes serves (``B_eff /
    8``: 32 at 256 bins, 8 at 64)."""

    level: int
    nodes_derived: int    # nodes read off parent - sibling: half a derived level, else 0
    m_pad: int            # accumulator rows: g and h of every node BUILT
    acc_block_bytes: int  # ONE tile's (m_pad, tile_feats * B_eff) float32 block
    vmem_bytes: int       # what one tile's kernel takes: hist_plan's text
    tile_feats: int       # features a tile: all of F where one tile holds them
    feat_tiles: int       # tiles a level; each sweeps every row block
    lanes_a_feature: int  # 64 at up to 64 bins (two features a register), else 128, 256, ...
    regs_a_broadcast: int  # registers of the indicator one broadcast of codes serves
    mxu_holds: str = "indicator"  # the operand the MXU holds: or "gradients"
    categorical: int = 0  # categorical features: the routing tests membership in a set

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
        return (self.mxu_holds == "indicator"
                and self.regs_a_broadcast * 128 == 4 * self.lanes_a_feature)


def hist_plan(n_feat: int, n_bins: int, level: int, block_rows: int, *,
              mxu_i8: bool = False, r_split: int = 1,
              categorical: int = 0) -> HistPlan:
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

    From the level where half the nodes built stack fewer padded rows of
    gradient matrix than all of them (4 or 2 nodes both pad to 16 rows, so
    level 3 at any width) half the nodes are built and half derived.  Below
    a full tile M is not padded to it: the MXU charges some 27 cycles a
    128 x 128 tile of indicator and one more a stacked row, and a derived
    level costs what a built level with the same built nodes costs
    (PERF.md sections 5 and 6).

    From the level whose BUILT nodes stack a full MXU tile (level 6, at
    any width) the MXU holds the gradient planes (``_holds_gradients``;
    ``mxu_i8`` and ``r_split > 1`` keep the indicator held): the block is
    ``(tile_feats * B_eff, m_pad)``, its m_pad lanes counted whole 128-lane
    registers (level 6's 64 take the VMEM of 128), codes come in at 128
    lanes as where they are packed, and the kernel transposes them into a
    ``[128, block_rows]`` int32 scratch, counted too.  (F = 67, level 7:
    8.4 MiB of block, 5 of row blocks, 0.5 of scratch, 21.9 MiB with the
    stack.)

    ``categorical`` is passed through: the features whose splits send a set
    of codes left (``GBDTConfig.categorical``), so that the level's routing
    tests membership (``_went_right_by_set``); it changes no block."""
    # g and h of the nodes built, in whole sublane tiles of 8 rows: one
    # child a parent where that is fewer rows than every node's
    derived = 2 ** (level - 1) if level >= 1 and _round_up(
        2 * 2 ** (level - 1), 8) < _round_up(2 * 2 ** level, 8) else 0
    m_pad = _round_up(2 * (2 ** level - derived), 8)
    tile = min(n_feat, TILE_FEATS)
    tiles = -(-n_feat // tile)
    be = _bins_eff(n_bins)
    held = _holds_gradients(2 * m_pad, mxu_i8, r_split)
    packed = not held and _packed(n_bins, 2 * m_pad)
    if held:
        acc = tile * be * _round_up(m_pad, 128) * 4
    else:
        acc = m_pad * _block_feats(tile, packed) * be * 4
    if tiles == 1:
        blocks = acc + 2 * 4 * block_rows * (_round_up(n_feat, 128) + 4 * 128)
    else:
        blocks = 2 * acc + 2 * 4 * block_rows * (tile + 3 * 128)
    need = blocks + VMEM_STACK + (5 * acc if packed and be >= 128 else 0) + (
        4 * block_rows * _round_up(tile, 128) if held else 0)
    if need > VMEM_MOST:
        raise ValueError(
            f"hist_plan: level {level} of F={n_feat} features x {n_bins} bins "
            f"at {block_rows}-row blocks does not fit: the accumulator block "
            f"of one {tile}-feature tile is {acc} bytes and the kernel needs "
            f"{need} of {VMEM_MOST} bytes of VMEM (a depth of {level + 1} is "
            "one level too many)")
    if held:
        return HistPlan(level, derived, m_pad, acc, need, tile, tiles, be,
                        be // 8, "gradients", categorical)
    return HistPlan(level, derived, m_pad, acc, need, tile, tiles, be,
                    (4 if packed else 1) * be // 128, categorical=categorical)


def _encode_bf16(L):
    """Hi/lo-bf16 split of the f32 gradient matrix (~2^-16-relative error).

    The two halves share ONE matmul, stacked along M: the MXU loads a tile
    of the operand it holds once for both planes (some 27 cycles a 128 x
    128 tile, and one a stacked row streamed: PERF.md section 5),
    where two matmuls would load it twice — packing them halved the level's
    MXU passes (~1.4x whole-round at 1,000,000 rows x 28 on an older chip).
    From level 3 on one child a parent is built (``hist_plan``), so levels
    3, 4 and 5 stack 16, 32 and 64 rows, level 6 a tile and level 7 two
    (``HistPlan.m_tiles``).  The result splits back and sums in f32,
    bitwise identical to the two-matmul form.

    ``l2`` is ``[R, 2 * m]``, rows on sublanes.  Up to level 5 the MXU holds
    the indicator and these planes stream through it, M rows a tile; from
    level 6 on it holds these planes themselves (``_accum_held``: R / 128 x
    2m / 128 tiles a block) and ``decode`` is not used: the planes then lie
    along the result's lanes."""
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


def _accum_held(xb_blk, L, out_ref, codes_t, *, n_bins: int, n_feat: int,
                fc: int, feats_left=None):
    """out_ref[f*Beff+b, m] += sum_r [xb_blk[r, f] == b] * L[r, m]: the
    histogram transposed, with the hi/lo gradient planes ``[R, 2m]`` as the
    operand the MXU holds and the indicator, built transposed, as the one
    it streams (``_holds_gradients``).  The block's codes ``[R, lanes]`` go
    through the XLU once into ``codes_t`` ``[lanes, R]``; a feature's row of
    codes, broadcast down the sublanes, is compared with a sublane iota of
    its bins, so one sublane broadcast serves all ``B_eff / 8`` registers of
    the feature (32 at 256 bins) and no lane broadcast is paid.  A group of
    ``fc`` features stacks ``fc * B_eff`` rows of indicator on the sublanes
    and is one ``jnp.dot`` against the planes; the planes' two halves are
    summed per block, as ``_encode_bf16``'s decode does.  Natural feature
    order: no codes packed.  ``feats_left``: as ``_accum``'s.

    The whole groups are traced one after another, as many a step of a
    ``fori_loop`` as ``_groups_a_body`` allows (all of them, and no loop,
    where the block's work fits), the ragged last group after them.  A
    grid step is about as many bundles as the MXU has cycles of work in
    it, and past 65,536 bundles, the instruction memory's size, a step
    runs slower than its schedule: Criteo's level 7 every group unrolled
    read 122 ms at 64,097 bundles and 146–157 at 65,995–72,968, by group
    size and missing code (PERF.md section 5, PR 39).  A loop of one group
    a step loses the overlap of one group's build with the last one's
    matmuls at every back edge: 127.2 ms at level 7, and 70.9 at level 6
    against 63.7 unrolled (my chip runs, PR 39)."""
    be = _bins_eff(n_bins)
    r, m = L.shape
    l2 = _encode_bf16(L)[0]
    codes_t[...] = xb_blk.T
    bins = lax.broadcasted_iota(jnp.int32, (be, r), 0)

    def group(f0, k):
        onehot = jnp.concatenate(
            [codes_t[pl.ds(f0 + j, 1), :] == bins for j in range(k)],
            axis=0).astype(jnp.bfloat16)
        acc2 = jnp.dot(onehot, l2, preferred_element_type=jnp.float32)
        at = f0 * be if isinstance(f0, int) else pl.multiple_of(f0 * be, be)
        out_ref[pl.ds(at, k * be), :] += acc2[:, :m] + acc2[:, m:]

    def live(f0, k):
        if feats_left is None:
            group(f0, k)
        else:
            pl.when(f0 < feats_left)(functools.partial(group, f0, k))

    full, last = divmod(n_feat, fc)
    if full:
        # MXU cycles a group: its indicator rows through every K-tile of
        # rows and N-tile of planes, four MXUs
        per = _groups_a_body(full, fc * be * (r // 128) * -(-2 * m // 128) // 4)

        def step(i, carry):
            for j in range(per):
                live((i * per + j) * fc, fc)
            return carry

        if per == full:
            step(0, None)
        else:
            lax.fori_loop(0, full // per, step, 0)
    if last:
        live(full * fc, last)


def _accum(xb_blk, L, out_ref, *, n_bins: int, n_feat: int, fc: int, i8: bool,
           packed: bool, r_split: int = 1, feats_left=None, codes_t=None):
    """out_ref[m, f*Beff+b] += sum_r L[r, m] * [xb_blk[r, f] == b], via the
    MXU: the encoded gradient planes are contracted against per-feature-
    group bin-indicator matrices built in VMEM, the MXU holding the
    indicator.  Given ``codes_t`` (a ``[lanes, R]`` int32 scratch) the MXU
    holds the planes instead and ``out_ref`` is the transposed block
    (``_accum_held``).  Where the kernel packs the
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
    if codes_t is not None:
        return _accum_held(xb_blk, L, out_ref, codes_t, n_bins=n_bins,
                           n_feat=n_feat, fc=fc, feats_left=feats_left)
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
    p iff it went to the child p builds, ``node_r == 2 p + built_right[p]``,
    else in none — ONE compare against the table along the lanes, as many
    as a level built whole pays, and no lookup a row.  (Comparing the
    parent and the side apart, ``node_r >> 1`` and ``node_r & 1``, gave the
    same sums in 24,139 bundles a grid step at Criteo's level 4 against
    19,942: PERF.md section 5.)"""
    r = node.shape[0]
    m_iota = lax.broadcasted_iota(jnp.int32, (r, m_pad), 1)
    is_g = m_iota < n_nodes
    idx = jnp.where(is_g, m_iota, m_iota - n_nodes)
    if built_row is None:
        sel = node == idx
    else:
        sel = node == built_row
    sel = sel & (m_iota < 2 * n_nodes)
    val = jnp.where(is_g, g, h)  # (R,1) -> (R, m_pad)
    return jnp.where(sel, val, 0.0)


def _built_table(built_right, m_pad: int):
    """``built_right`` ``[n]`` (1 where a parent builds its right child) as
    the kernels take it: row 0 of an ``(8, lanes)`` int32 table holds the
    built children's node ids, ``2 p + built_right[p]``, twice, once under
    the g columns of the gradient matrix and once under the h columns, and
    -1, which no row's node is, in the lanes past them."""
    n = built_right.shape[0]
    child = 2 * jnp.arange(n, dtype=jnp.int32) + built_right.astype(jnp.int32)
    tab = jnp.full((8, _round_up(m_pad, 128)), -1, jnp.int32)
    return tab.at[0, : 2 * n].set(jnp.tile(child, 2))


def _split_of(node, feat_row, thr_row, *, p_pad: int):
    """(feat[node], thr[node]) — the split-table lookup via lane-masked
    reductions (no gathers)."""
    r = node.shape[0]
    p_iota = lax.broadcasted_iota(jnp.int32, (r, p_pad), 1)
    pm = node == p_iota  # (R, P) one-hot over parent nodes
    fsel = jnp.sum(jnp.where(pm, feat_row, 0), axis=1, keepdims=True)
    tsel = jnp.sum(jnp.where(pm, thr_row, 0), axis=1, keepdims=True)
    return fsel, tsel


#: the bit of a threshold that sends the missing code left (a split that
#: learned its default direction: ``split_scan``); the bin is ``thr & 255``
DEFAULT_LEFT = 256


def went_right(xv, thr, missing_code=None, words=None):
    """Whether a row whose split feature holds code ``xv`` goes right at a
    node of threshold ``thr``: ``xv > thr`` where the codes are dense
    (``missing_code`` None); with a missing code, that code goes the way the
    threshold's ``DEFAULT_LEFT`` bit says and any other code goes right where
    it is above ``thr & 255``.  Elementwise, in a kernel or in XLA alike;
    booleans are and-ed, never selected (Mosaic refuses a select of i1
    vectors), which holds because a threshold's bin lies below the missing
    code.

    ``words`` (XLA only): the row's node's routing set ``[..., SET_WORDS]``
    (``route_words``), where the configuration has categorical features;
    then the row goes right iff its code is NOT in the set: bit ``xv & 31``
    of word ``xv >> 5`` is clear."""
    if words is not None:
        word = jnp.take_along_axis(words, (xv >> 5)[..., None], -1)[..., 0]
        return (jnp.right_shift(word, xv & 31) & 1) == 0
    if missing_code is None:
        return xv > thr
    return (xv > (thr & (DEFAULT_LEFT - 1))) & ~(
        (xv == missing_code) & (thr >= DEFAULT_LEFT))


#: int32 words of a node's set of codes: 256 bits, a bit a code of a byte
SET_WORDS = 8


def route_words(thr, sets):
    """The sets a level's routing tests, ``[n, SET_WORDS]`` int32: a node's
    own set where it has one (a categorical split sends the codes of its set
    left), else the codes ``0..thr`` that ``code > thr`` keeps left, so that
    every node of a level with categorical features routes by membership
    alone.  ``sets`` holds zeros at a numeric node (``Forest.sets``)."""
    lo = 32 * jnp.arange(SET_WORDS, dtype=jnp.int32)
    n_in = jnp.clip(thr[:, None] + 1 - lo, 0, 32)
    prefix = jnp.where(n_in >= 32, -1, jnp.left_shift(
        jnp.int32(1), jnp.minimum(n_in, 31)) - 1)
    return jnp.where(jnp.any(sets != 0, axis=1, keepdims=True), sets, prefix)


def set_bits(words, n_bins: int):
    """``[n, n_bins]`` bool: whether each code lies in each row's set."""
    c = jnp.arange(n_bins)
    return (jnp.right_shift(words[:, c >> 5], c & 31) & 1) == 1


def _set_table(words, p_pad: int):
    """A level's routing sets (``route_words``) as the kernels take them: row
    ``n`` of a ``(p_pad, 128)`` bfloat16 table holds node ``n``'s set a byte
    a lane (lane ``j``: codes ``8j..8j+7``), every byte exact in
    bfloat16."""
    j = jnp.arange(4 * SET_WORDS)
    byts = jnp.right_shift(words[:, j >> 2], 8 * (j & 3)) & 255
    return jnp.zeros((p_pad, 128), jnp.bfloat16).at[
        :words.shape[0], :4 * SET_WORDS].set(byts.astype(jnp.bfloat16))


def _set_lookup(node, feat_row, tab, *, p_pad: int):
    """``(feat[node], the node's set)`` a row where the level has sets: the
    rows' one-hot over the nodes, built once, picks the feature by a
    lane-masked sum and the set's bytes (lanes 0..31 of an ``(R, 128)``
    float32) against the set table on the MXU — an exact pick, one term a
    lane, where a lane-masked sum a word would cost eight passes over ``(R,
    p_pad)``.  No threshold is read: a numeric node's threshold is its set
    (``route_words``)."""
    pm = node == lax.broadcasted_iota(jnp.int32, (node.shape[0], p_pad), 1)
    fsel = jnp.sum(jnp.where(pm, feat_row, 0), axis=1, keepdims=True)
    return fsel, jnp.dot(pm.astype(jnp.bfloat16), tab,
                         preferred_element_type=jnp.float32)


def _went_right_by_set(xv, byts):
    """Whether code ``xv`` is NOT in the row's set: bit ``xv & 7`` of byte
    ``xv >> 3`` of its ``_set_lookup`` row, which is bit ``xv & 31`` of word
    ``xv >> 5``."""
    lane = lax.broadcasted_iota(jnp.int32, byts.shape, 1)
    byte = jnp.sum(jnp.where(lane == (xv >> 3), byts, 0.0), axis=1,
                   keepdims=True)
    return (jnp.right_shift(byte.astype(jnp.int32), xv & 7) & 1) == 0


def _route(xb_blk, node, feat_row, thr_row, *, p_pad: int, n_feat: int,
           missing_code=None, set_tab=None):
    """node' = 2*node + [x[feat[node]] goes right] (``went_right``) —
    split-table lookup and feature select via lane-masked reductions (no
    gathers).  Given the level's ``set_tab`` (``_set_table``) the row goes
    right iff its code is not in its node's set (``_set_lookup``)."""
    if set_tab is not None:
        fsel, byts = _set_lookup(node, feat_row, set_tab, p_pad=p_pad)
    else:
        fsel, tsel = _split_of(node, feat_row, thr_row, p_pad=p_pad)
    f_iota = lax.broadcasted_iota(jnp.int32, (node.shape[0], n_feat), 1)
    xv = jnp.sum(jnp.where(f_iota == fsel, xb_blk, 0), axis=1, keepdims=True)
    if set_tab is not None:
        return node * 2 + _went_right_by_set(xv, byts).astype(jnp.int32)
    return node * 2 + went_right(xv, tsel, missing_code).astype(jnp.int32)


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
                  held=False, r_split=1, missing_code=None, cats=False):
    """``refs``: with categorical features (``cats``) the level's set table
    (``_set_table``), at a derived level the built-child table, then the
    accumulator (``n_nodes`` built nodes), the routed node ids and, where
    the MXU holds the gradients (``held``), the codes' transposed scratch."""
    *refs, codes_t = refs if held else (*refs, None)
    set_ref, *refs = refs if cats else (None, *refs)
    *built_ref, out_ref, node_out_ref = refs

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    node = _route(xb_ref[0], node_ref[0], feat_ref[0:1], thr_ref[0:1],
                  p_pad=p_pad, n_feat=xb_ref.shape[2], missing_code=missing_code,
                  set_tab=None if set_ref is None else set_ref[...])
    node_out_ref[0] = node
    L = _gradient_matrix(
        node, g_ref[0], h_ref[0], n_nodes=n_nodes, m_pad=m_pad,
        built_row=built_ref[0][0:1, :m_pad] if built_ref else None)
    _accum(xb_ref[0], L, out_ref, n_bins=n_bins, n_feat=n_feat, fc=fc, i8=i8,
           packed=packed, r_split=r_split, codes_t=codes_t)


# -- one feature tile of a level wider than TILE_FEATS: histogram only ------


def _tile_kernel(xb_ref, *refs, n_feat, tile, n_nodes, n_bins, m_pad, fc, i8,
                 packed, held=False, r_split=1):
    """Grid (feature tile, row block), the rows innermost: ``out_ref`` is
    this tile's accumulator block, zeroed at its first row block.  The rows
    come routed: ``refs`` is their node ids (not at the root, where every
    row is at node 0), at a derived level the built-child table, then g, h,
    the output and, where the MXU holds the gradients (``held``), the
    codes' transposed scratch."""
    *refs, codes_t = refs if held else (*refs, None)
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
           feats_left=n_feat - pl.program_id(0) * tile, codes_t=codes_t)


# -- routing-only passes (no histogram) ------------------------------------


def _route_tile_kernel(xb_ref, node_ref, feat_ref, thr_ref, *refs, p_pad, tile,
                       missing_code=None, cats=False, leaves=False):
    """Grid (row block, feature tile), the tiles innermost: the code of the
    feature a row's node splits on lies in one tile, so the lane-masked sums
    of all tiles add up to it in the node ids' output block, which the last
    tile turns into the node one level down.  The split-table lookup is the
    row block's, made at its first tile and kept in scratch.  (Lanes past F in
    the ragged last tile match no feature.)  With categorical features
    (``cats``) ``refs`` begins with the level's set table and its scratch
    ends with one that keeps the row block's sets (``_set_lookup``).  The
    leaves' pass (``leaves``: ``route_margin_level``) takes the leaf planes
    and the margin after the set table and writes the margin plus each
    row's leaf (``_leaf_row``) in place of the node ids, the codes' sum
    kept in one more scratch."""
    set_ref, *refs = refs if cats else (None, *refs)
    if leaves:
        (planes_ref, margin_ref, out_ref, fsel_ref, tsel_ref, *byts_ref,
         xv_ref) = refs
        at = Ellipsis
    else:
        out_ref, fsel_ref, tsel_ref, *byts_ref = refs
        xv_ref, at = out_ref, 0
    t = pl.program_id(1)
    node = node_ref[0]

    @pl.when(t == 0)
    def _first():
        if cats:
            fsel_ref[...], byts_ref[0][...] = _set_lookup(
                node, feat_ref[0:1], set_ref[...], p_pad=p_pad)
        else:
            fsel_ref[...], tsel_ref[...] = _split_of(
                node, feat_ref[0:1], thr_ref[0:1], p_pad=p_pad)
        xv_ref[at] = jnp.zeros_like(node)

    f_iota = lax.broadcasted_iota(jnp.int32, (node.shape[0], tile), 1)
    xv_ref[at] += jnp.sum(
        jnp.where(f_iota == fsel_ref[...] - t * tile, xb_ref[0], 0), axis=1,
        keepdims=True)

    @pl.when(t == pl.num_programs(1) - 1)
    def _turn():
        twice = node * 2
        if cats:
            right = _went_right_by_set(xv_ref[at], byts_ref[0][...])
        else:
            right = went_right(xv_ref[at], tsel_ref[...], missing_code)
        if leaves:
            out_ref[0] = margin_ref[0] + _leaf_row(
                twice + right.astype(jnp.int32), planes_ref[...])
        else:
            out_ref[0] = twice + right.astype(jnp.int32)


# -- the leaves' pass: route to the leaves + margin update in one kernel ---


def _leaf_planes(leaf, l_pad: int):
    """The leaf table as the leaves' pass takes it: rows 0, 1 and 2 of an
    ``(8, l_pad)`` bfloat16 table are three planes whose float32 sum is
    each finite leaf bit for bit — its sign, exponent and first 7
    significand bits, then the next 8 of what is left, then the rest — cut
    by masking the bits, so no rounding of a convert is in them.  (From
    2**-100 down a plane would be subnormal, which the chip flushes; such a
    leaf is lost in any margin above 2**-76 either way.)  A leaf that is not
    finite (an empty leaf at ``reg_lambda`` 0 reads 0/0) is 0 in the planes,
    since the MXU's 0 x NaN would reach every row, and row 3 holds its code:
    1 for +inf, 2 for -inf, 3 for NaN, 0 for a finite leaf."""
    top = lambda x: lax.bitcast_convert_type(
        lax.bitcast_convert_type(x, jnp.int32) & -65536, jnp.float32)
    finite = jnp.isfinite(leaf)
    x = jnp.where(finite, leaf, 0.0)
    hi = top(x)
    mid = top(x - hi)
    lo = x - hi - mid
    code = jnp.where(finite, 0.0,
                     jnp.where(jnp.isnan(leaf), 3.0,
                               jnp.where(leaf > 0, 1.0, 2.0)))
    return jnp.zeros((8, l_pad), jnp.bfloat16).at[:4, :leaf.shape[0]].set(
        jnp.stack([hi, mid, lo, code]).astype(jnp.bfloat16))


def _leaf_row(node, planes):
    """``leaf[node]`` along the lanes, ``(1, R)``, for the ``(R, 1)`` node ids
    of a row block: the rows' one-hot over the leaves against the planes
    (``_leaf_planes``) on the MXU, contracted on the leaves (``q @ k.T``'s
    form) so that the rows come out along the lanes.  Each plane's sum has
    one nonzero term, so it is the plane's value, and the three add up to
    the leaf exactly; a row whose leaf is not finite takes it from its
    code."""
    pm = node == lax.broadcasted_iota(
        jnp.int32, (node.shape[0], planes.shape[1]), 1)
    v = lax.dot_general(planes, pm.astype(jnp.float32).astype(jnp.bfloat16),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    code = v[3:4]
    odd = jnp.where(code == 1.0, jnp.inf,
                    jnp.where(code == 2.0, -jnp.inf, jnp.nan))
    return jnp.where(code == 0.0, (v[0:1] + v[1:2]) + v[2:3], odd)


def _route_margin_kernel(xb_ref, node_ref, feat_ref, thr_ref, *refs, p_pad,
                         n_feat, missing_code=None):
    """``refs``: the set table where the level has categorical features
    (``_set_table``), then the leaf planes, the margin in and the margin
    out."""
    *set_ref, planes_ref, margin_ref, out_ref = refs
    node = _route(xb_ref[0], node_ref[0], feat_ref[0:1], thr_ref[0:1],
                  p_pad=p_pad, n_feat=n_feat, missing_code=missing_code,
                  set_tab=set_ref[0][...] if set_ref else None)
    out_ref[0] = margin_ref[0] + _leaf_row(node, planes_ref[...])


@functools.partial(jax.jit,
                   static_argnames=("depth", "interpret", "missing_code"))
def route_margin_level(xb3, node3, margin, feat, thr, leaf, words=None, *,
                       depth: int, interpret: bool = False, missing_code=None):
    """The leaves' pass: route rows through the level-(depth-1) split tables
    to their leaves and return ``margin + leaf[leaf node]``, ``[n]`` float32,
    bit for bit what XLA's gather and add give.  The leaf node ids are not
    written: the kernel reads the codes and the node ids and moves the
    margin lane-dense, a ``(1, R)`` row a block (``_leaf_row``), where an XLA
    gather of every row from a 256-entry table costs some 12 cycles a row
    and one of up to 64 entries (a chain of selects) still reads the node
    ids back.  Wider than one tile of codes the tiled routing pass does it
    at its last tile; in one tile ``_route_margin_kernel`` is 0.3 to 1.5 ms
    faster than that pass at 2.6M rows (PERF.md section 5).  ``missing_code``: see ``went_right``; ``words``: the
    level's routing sets (``route_words``) where the configuration has
    categorical features."""
    nb, R, F = xb3.shape
    n = margin.shape[0]
    n_prev = 2 ** (depth - 1)
    p_pad = _round_up(n_prev, 128)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    planes = _leaf_planes(leaf, _round_up(2 ** depth, 128))
    sets = [] if words is None else [_set_table(words, p_pad)]
    margin3 = jnp.pad(margin, (0, nb * R - n)).reshape(nb, 1, R)
    name = f"route_margin_d{depth}"
    if F > TILE_FEATS:
        out = _route_tiles(xb3, node3, featp, thrp, sets, (planes, margin3),
                           name=name, interpret=interpret,
                           missing_code=missing_code)
    else:
        fixed = lambda b: pl.BlockSpec(b.shape, lambda i: (0, 0))
        row = pl.BlockSpec((1, 1, R), lambda i: (i, 0, 0))
        out = pl.pallas_call(
            functools.partial(_route_margin_kernel, p_pad=p_pad, n_feat=F,
                              missing_code=missing_code),
            grid=(nb,),
            in_specs=[_blk(R, F), _blk(R, 1), fixed(featp), fixed(thrp)]
            + [fixed(b) for b in sets] + [fixed(planes), row],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct(margin3.shape, jnp.float32),
            interpret=interpret,
            name=name,
        )(xb3, node3, featp, thrp, *sets, planes, margin3)
    return out.reshape(-1)[:n]


@functools.partial(jax.jit,
                   static_argnames=("depth", "interpret", "missing_code"))
def route_level(xb3, node3, feat, thr, words=None, *, depth: int,
                interpret: bool = False, missing_code=None):
    """Route rows one level down through the level-(depth-1) split tables —
    no histogram: a level's routing pass where the codes are wider than one
    tile (``hist_level``), a grid over (row block, feature tile); at up to
    ``TILE_FEATS`` features the tile is the codes' width.  The leaves' pass
    is ``route_margin_level``.  ``missing_code``: see ``went_right``;
    ``words``: the level's routing sets (``route_words``) where the
    configuration has categorical features."""
    nb, R, F = xb3.shape
    n_prev = 2 ** (depth - 1)
    p_pad = _round_up(n_prev, 128)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    sets = [] if words is None else [_set_table(words, p_pad)]
    return _route_tiles(xb3, node3, featp, thrp, sets,
                        name=f"route_level_d{depth}", interpret=interpret,
                        missing_code=missing_code)


def _route_tiles(xb3, node3, featp, thrp, sets, leaves=(), *, name: str,
                 interpret: bool, missing_code):
    """``_route_tile_kernel`` over (row block, feature tile), a tile
    ``TILE_FEATS`` codes wide or the codes' width where they are narrower:
    the node ids one level down, written over ``node3`` as ``hist_level``'s
    are, or given ``leaves`` (the leaf planes and the margin ``(nb, 1, R)``)
    the margin plus each row's leaf."""
    nb, R, F = xb3.shape
    tile = min(F, TILE_FEATS)
    fixed = lambda b: pl.BlockSpec(b.shape, lambda i, t: (0, 0))
    rows = lambda shape: pl.BlockSpec(shape, lambda i, t: (i, 0, 0))
    out = (1, 1, R) if leaves else (1, R, 1)
    return pl.pallas_call(
        functools.partial(_route_tile_kernel, p_pad=featp.shape[1],
                          tile=tile, missing_code=missing_code,
                          cats=bool(sets), leaves=bool(leaves)),
        grid=(nb, -(-F // tile)),
        in_specs=[
            pl.BlockSpec((1, R, tile), lambda i, t: (i, 0, t)),
            rows((1, R, 1)), fixed(featp), fixed(thrp),
        ] + [fixed(b) for b in sets] + (
            [fixed(leaves[0]), rows(out)] if leaves else []),
        out_specs=rows(out),
        out_shape=jax.ShapeDtypeStruct(
            (nb, *out[1:]), jnp.float32 if leaves else jnp.int32),
        scratch_shapes=[pltpu.VMEM((R, 1), jnp.int32)] * 2 + [
            pltpu.VMEM((R, 128), jnp.float32) for _ in sets] + (
            [pltpu.VMEM((R, 1), jnp.int32)] if leaves else []),
        input_output_aliases={} if leaves else {1: 0},
        interpret=interpret,
        name=name,
    )(xb3, node3, featp, thrp, *sets, *leaves)


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


def _held_hists(out, plan: HistPlan, n_feat: int, n_bins: int):
    """A transposed accumulator ``(features * B_eff, m_pad)`` (the MXU held
    the gradients: ``_accum_held``) as the ``[nodes built, F, B, 2]`` the
    caller gets: the pad rows of every feature and the features past F cut
    off, g and h out of the node lanes, and one transpose (an XLA pass over
    the level's histogram, outside the kernel)."""
    n = plan.nodes_built
    o = out.reshape(-1, plan.lanes_a_feature, plan.m_pad)[:n_feat, :n_bins]
    return jnp.stack([o[..., :n], o[..., n : 2 * n]], axis=-1).transpose(
        2, 0, 1, 3)


def _hist_tiles(plan: HistPlan, xb3, node3, g3, h3, *, n_bins, interpret,
                mxu_i8, r_split, name, built=()):
    """A level wider than one tile: the ``[nodes built, F, B, 2]`` sums of
    rows that come routed (``node3`` None at the root; ``built`` holds the
    built-child table of a derived level), a sweep of every row block a
    feature tile.  The kernel's output is whole tiles wide (tall, where the
    MXU holds the gradients); the ragged last tile's tail is cut off
    here."""
    nb, R, F = xb3.shape
    be = plan.lanes_a_feature
    tile, tiles, m_pad = plan.tile_feats, plan.feat_tiles, plan.m_pad
    held = plan.mxu_holds == "gradients"
    row = pl.BlockSpec((1, R, 1), lambda t, i: (i, 0, 0))
    rows = [a for a in (node3, g3, h3) if a is not None]
    specs = [row] * len(rows)
    rows[1:1] = built
    specs[1:1] = [pl.BlockSpec(b.shape, lambda t, i: (0, 0)) for b in built]
    if held:
        acc, acc_at = (tile * be, m_pad), lambda t, i: (t, 0)
        fc, shape = _pick_held_fc(n_bins), (tiles * tile * be, m_pad)
    else:
        acc, acc_at = (m_pad, tile * be), lambda t, i: (0, t)
        fc, shape = _pick_tile_fc(n_bins), (m_pad, tiles * tile * be)
    out = pl.pallas_call(
        functools.partial(
            _tile_kernel, n_feat=F, tile=tile, n_nodes=plan.nodes_built,
            n_bins=n_bins, m_pad=m_pad, fc=fc, i8=mxu_i8,
            packed=plan.packed, held=held, r_split=r_split),
        grid=(tiles, nb),
        in_specs=[pl.BlockSpec((1, R, tile), lambda t, i: (i, 0, t))] + specs,
        out_specs=pl.BlockSpec(acc, acc_at),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile, R), jnp.int32)] if held else (),
        interpret=interpret,
        name=name,
        compiler_params=_vmem_params(plan),
    )(xb3, *rows)
    if held:
        return _held_hists(out, plan, F, n_bins)
    out = _cut_lanes(out, F, n_bins, tile, plan.packed)
    n = plan.nodes_built
    return jnp.stack([out[:n], out[n : 2 * n]], axis=-1)


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
        return _hist_tiles(plan, xb3, None, g3, h3, n_bins=n_bins,
                           interpret=interpret, mxu_i8=mxu_i8, r_split=r_split,
                           name="hist_level0")
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
    static_argnames=("depth", "n_bins", "interpret", "mxu_i8", "r_split",
                     "missing_code"),
)
def hist_level(xb3, node3, g3, h3, feat, thr, built_right=None, words=None, *,
               depth: int, n_bins: int, interpret: bool = False,
               mxu_i8: bool = False, r_split: int = 1, missing_code=None):
    """Route one level down and histogram; returns
    ([nodes built, F, B, 2], node3').  node3' is written over node3
    (``input_output_aliases``; XLA copies node3 first where the caller
    still reads it): the ids are ``(nb, R, 1)`` int32, which the chip tiles
    to 128 lanes, 1.34 GB at 2.6M rows, so a round holds one buffer of them
    and not one a level.
    ``feat``/``thr`` are the level-(depth-1) split tables, shape
    [2**(depth-1)].  ``r_split``: see _accum;
    ``missing_code``: see ``went_right``; ``words``: the level-(depth-1)
    routing sets (``route_words``) where the configuration has categorical
    features, taken by the kernel as a set table (``_set_table``) only
    then.

    Up to level 2 every node is built: ``[2**depth, F, B, 2]``.  From the
    level ``hist_plan`` derives (3, at any width) the histogram is of ONE
    child a parent, ``[2**(depth-1), F, B, 2]`` in parent order:
    ``built_right`` ``[2**(depth-1)]`` says which (1: the right one;
    ``models.gbdt.smaller_child`` picks the lighter), and a row that went to
    the other child is routed and summed nowhere.  The caller gets the
    siblings from the parents' histogram (``derive_siblings``), after its
    collective.  The routed node ids and the kernel's name are a built
    level's.  From the level whose built nodes stack a full MXU tile (6, at
    any width) the kernel holds the gradient planes in the MXU and
    accumulates the histogram transposed (``_accum_held``); the caller gets
    the same ``[nodes, F, B, 2]``, one XLA transpose of it later.

    Wider than ``TILE_FEATS`` features routing and histogram are two
    kernels: ``route_level``'s tiled pass, then a histogram sweep a feature
    tile (``hist_plan``).  The kernel asks for ``hist_plan``'s scoped VMEM
    where Mosaic's default might not hold it (F = 67 at every level, F = 28
    at level 5, since the packed kernels' stack is counted), and is
    the same kernel elsewhere.  Compiled on its own, a 16.75 MiB
    accumulator block at F = 67 (level 8 now, level 7 with every node built)
    is refused at the default, on the chip too; inside the whole round XLA
    keeps the accumulator in VMEM itself and the default holds, and asking
    costs the round nothing (PR 27)."""
    nb, R, F = xb3.shape
    _check_r_split(R, r_split)
    plan = hist_plan(F, n_bins, depth, R, mxu_i8=mxu_i8, r_split=r_split)
    n_nodes, m_pad = plan.nodes_built, plan.m_pad
    n_prev = 2 ** (depth - 1)
    if bool(plan.nodes_derived) != (built_right is not None):
        raise ValueError(
            f"hist_level: level {depth} builds {n_nodes} of {2 ** depth} nodes, "
            "so it takes built_right " + (
                f"[{n_prev}]" if plan.nodes_derived else "None"))
    built = [] if built_right is None else [_built_table(built_right, m_pad)]
    if plan.feat_tiles > 1:
        node_out = route_level(xb3, node3, feat, thr, words, depth=depth,
                               interpret=interpret, missing_code=missing_code)
        hist = _hist_tiles(plan, xb3, node_out, g3, h3, n_bins=n_bins,
                           interpret=interpret, mxu_i8=mxu_i8, r_split=r_split,
                           name=f"hist_level_d{depth}", built=built)
        return hist, node_out
    p_pad = _round_up(n_prev, 128)
    held = plan.mxu_holds == "gradients"
    if held:
        # the codes a whole 128-lane tile, transposed in the kernel; the
        # block (F * B_eff, m_pad), a feature's bins down the sublanes
        codes, slots = _round_up(F, 128), F
        acc, fc = (F * plan.lanes_a_feature, m_pad), _pick_held_fc(n_bins)
    else:
        codes, slots, lanes, fc = _one_block(F, n_bins, plan.packed)
        acc = (m_pad, lanes)
    featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
    thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
    sets = [] if words is None else [_set_table(words, p_pad)]
    out, node_out = pl.pallas_call(
        functools.partial(
            _level_kernel, n_nodes=n_nodes, n_bins=n_bins, n_feat=slots,
            m_pad=m_pad, p_pad=p_pad, fc=fc, i8=mxu_i8, packed=plan.packed,
            held=held, r_split=r_split, missing_code=missing_code,
            cats=bool(sets),
        ),
        grid=(nb,),
        in_specs=[
            _blk(R, codes), _blk(R, 1), _blk(R, 1), _blk(R, 1),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
            pl.BlockSpec((8, p_pad), lambda i: (0, 0)),
        ] + [pl.BlockSpec(b.shape, lambda i: (0, 0)) for b in sets + built],
        out_specs=[
            pl.BlockSpec(acc, lambda i: (0, 0)),
            _blk(R, 1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(acc, jnp.float32),
            jax.ShapeDtypeStruct((nb, R, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((codes, R), jnp.int32)] if held else (),
        interpret=interpret,
        name=f"hist_level_d{depth}",
        compiler_params=_vmem_params(plan),
        input_output_aliases={1: 1},
    )(xb3, node3, g3, h3, featp, thrp, *sets, *built)
    if held:
        return _held_hists(out, plan, F, n_bins), node_out
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


# -- split scan with a learned default direction ---------------------------


def _split_scan_kernel(g_ref, h_ref, out_ref, *, n_bins, reg_lambda,
                       min_child_weight):
    """One node: its ``(F, lanes)`` gradient and hessian histograms in, the
    best candidate's key and gain out, in lanes 0 and 1 of an ``(8, 128)``
    block.  Lane ``n_bins - 1`` is the missing code's mass; the present bins'
    prefix sums are a log-step scan along the lanes (a roll and an add a
    step), and threshold ``b`` scores twice, the missing mass right (key
    ``2 * (f * n_bins + b)``) and left (key + 1).  The best is the highest
    gain and, among equal gains, the lowest key: feature, then bin, then
    right before left."""
    g, h = g_ref[0], h_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
    miss = n_bins - 1
    gm = jnp.sum(jnp.where(lane == miss, g, 0.0), axis=1, keepdims=True)
    hm = jnp.sum(jnp.where(lane == miss, h, 0.0), axis=1, keepdims=True)
    G = jnp.sum(g, axis=1, keepdims=True)
    H = jnp.sum(h, axis=1, keepdims=True)
    GL = jnp.where(lane < miss, g, 0.0)
    HL = jnp.where(lane < miss, h, 0.0)
    step = 1
    while step < g.shape[1]:
        GL = GL + jnp.where(lane >= step, pltpu.roll(GL, step, 1), 0.0)
        HL = HL + jnp.where(lane >= step, pltpu.roll(HL, step, 1), 0.0)
        step *= 2
    score = lambda a, b: a * a / (b + reg_lambda)
    parent = score(G, H)

    def gain(gl, hl):
        ok = ((lane < miss) & (hl >= min_child_weight)
              & (H - hl >= min_child_weight))
        return jnp.where(ok, score(gl, hl) + score(G - gl, H - hl) - parent,
                         -jnp.inf)

    right, left = gain(GL, HL), gain(GL + gm, HL + hm)
    best = jnp.max(jnp.max(jnp.maximum(right, left), axis=1, keepdims=True),
                   axis=0, keepdims=True)
    feat = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    key = (2 * (feat * n_bins + lane)).astype(jnp.float32)
    first = jnp.minimum(jnp.where(right == best, key, jnp.inf),
                        jnp.where(left == best, key + 1.0, jnp.inf))
    first = jnp.min(jnp.min(first, axis=1, keepdims=True), axis=0,
                    keepdims=True)
    col = lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    out_ref[0] = jnp.where(col == 0, first, best)


@functools.partial(jax.jit, static_argnames=(
    "depth", "reg_lambda", "min_child_weight", "interpret"))
def split_scan(hist, *, depth: int, reg_lambda: float, min_child_weight: float,
               interpret: bool = False):
    """Best ``(feature, threshold, gain)`` a node of a level's COMBINED
    ``[nodes, F, B, 2]`` histogram whose code ``B - 1`` is the missing value
    (XGBoost's sparsity-aware split finding, KDD'16 Alg. 3): the missing
    lane leaves the ordered scan, and each threshold ``b`` in ``0..B-2``
    (present codes ``<= b`` go left) is scored with the missing mass on the
    right and on the left, under ``best_splits``' gain and child-weight
    floor.  ``threshold`` is ``b``, plus ``DEFAULT_LEFT`` where the missing
    code goes left (``went_right``).  Ties go to the lower feature, then the
    lower bin, then right before left; so where a node's missing mass is
    zero the direction is right, which is where code ``B - 1`` goes under
    any ``b``, and ``b = B - 2`` with the missing mass right is the split
    "present against missing".  A node with no valid candidate gets
    feature 0, threshold 0 and gain ``-inf``, as ``best_splits`` gives it.

    One kernel a level, ``split_scan_d<depth>``, a grid step a node; the bins
    are padded to whole 128-lane registers where they are fewer."""
    nodes, F, B, _ = hist.shape
    if not 2 <= B <= DEFAULT_LEFT or 2 * F * B > 1 << 24:
        raise ValueError(f"split_scan: {F} features x {B} bins: a learned "
                         f"default direction needs 2..{DEFAULT_LEFT} bins "
                         "(codes that fit a byte) and keys exact in float32")
    lanes = _round_up(B, 128)
    g, h = (jnp.pad(hist[..., k], ((0, 0), (0, 0), (0, lanes - B)))
            for k in (0, 1))
    block = pl.BlockSpec((1, F, lanes), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_split_scan_kernel, n_bins=B, reg_lambda=reg_lambda,
                          min_child_weight=min_child_weight),
        grid=(nodes,),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nodes, 8, 128), jnp.float32),
        interpret=interpret,
        name=f"split_scan_d{depth}",
    )(g, h)
    key = out[:, 0, 0].astype(jnp.int32)
    fb = key >> 1
    return (fb // B, fb % B + (key & 1) * DEFAULT_LEFT, out[:, 0, 1])


# -- categorical split: a set of codes goes left ---------------------------


def _bitonic(lane, before, *arrays):
    """Sort every row of ``arrays`` along the lanes (a power of two of them)
    by the order ``before(a, b)`` — whether element ``a`` (its values in
    ``arrays``, in order) precedes ``b`` — a total order: a bitonic network,
    log2(L) (log2(L) + 1) / 2 compare-exchange steps, each two rolls an
    array and selects, no gather."""
    n = lane.shape[1]
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            lower = (lane & j) == 0                  # the partner is lane + j
            # the lower lane keeps the lesser where the run ascends (lane & k
            # is 0), the greater where it descends: where bits j and k of the
            # lane agree, this lane keeps the lesser (no compare of booleans,
            # which Mosaic refuses)
            keep_min = (((lane // j) ^ (lane // k)) & 1) == 0
            part = [jnp.where(lower, pltpu.roll(a, n - j, 1), pltpu.roll(a, j, 1))
                    for a in arrays]
            less = before(arrays, part)
            take = (less & keep_min) | ~(less | keep_min)
            arrays = tuple(jnp.where(take, a, p) for a, p in zip(arrays, part))
            j //= 2
        k *= 2
    return arrays


def _cat_split_kernel(g_ref, h_ref, out_ref, *, reg_lambda, min_child_weight):
    """One node: the ``(slots, L)`` gradient and hessian histograms of its
    categorical features in (a row a feature, a lane a code; rows past the
    features and lanes past the codes hold zeros), the best candidate's key,
    gain and set out in an ``(8, 128)`` block: lane 0 of row 0 the key
    ``slot * L + prefix - 1``, lane 1 the gain, row 1 the set a byte a lane
    (lane ``j``: codes ``8j..8j+7``; exact in float32).

    A code is present where its hessian mass is positive.  The present codes
    are ordered by ``G / (H + lambda)`` ascending, ties by code, the absent
    ones last (``_bitonic``); two keys are compared by their cross products,
    ``G_a (H_b + lambda)`` against ``G_b (H_a + lambda)``, because the chip's
    float32 divide is not correctly rounded and splits ties: at Criteo's
    first tree, where the codes' sums are small multiples of 0.25, 13 codes
    of key 0.4 came out of it unequal and a node took a set 4 % short
    of its best gain (PERF.md section 6), where a product, rounded
    correctly, keeps equal ratios equal.  Prefix sums run along the sorted
    lanes as ``split_scan``'s do, and each prefix of 1 to K' - 1 present
    codes is a left set, scored by XGBoost's gain under the child-weight
    floor.  The best is the highest gain, then the lowest slot, then the
    shortest prefix."""
    g, h = g_ref[0], h_ref[0]
    n = g.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, g.shape, 1)
    G = jnp.sum(g, axis=1, keepdims=True)
    H = jnp.sum(h, axis=1, keepdims=True)
    k_present = jnp.sum((h > 0).astype(jnp.int32), axis=1, keepdims=True)

    def before(a, b):
        (ca, ga, ha), (cb, gb, hb) = a, b
        pa, pb = ha > 0, hb > 0
        lhs, rhs = ga * (hb + reg_lambda), gb * (ha + reg_lambda)
        by_key = (lhs < rhs) | ((lhs == rhs) & (ca < cb))
        return (pa & ~pb) | (pa & pb & by_key) | (~(pa | pb) & (ca < cb))

    code, GL, HL = _bitonic(lane, before, lane, g, h)
    step = 1
    while step < n:
        GL = GL + jnp.where(lane >= step, pltpu.roll(GL, step, 1), 0.0)
        HL = HL + jnp.where(lane >= step, pltpu.roll(HL, step, 1), 0.0)
        step *= 2
    score = lambda a, b: a * a / (b + reg_lambda)
    ok = ((lane < k_present - 1) & (HL >= min_child_weight)
          & (H - HL >= min_child_weight))
    gain = jnp.where(ok, score(GL, HL) + score(G - GL, H - HL) - score(G, H),
                     -jnp.inf)
    best = jnp.max(jnp.max(gain, axis=1, keepdims=True), axis=0,
                   keepdims=True)
    slot = lax.broadcasted_iota(jnp.int32, g.shape, 0)
    flat = (slot * n + lane).astype(jnp.float32)
    first = jnp.min(jnp.min(jnp.where(gain == best, flat, jnp.inf), axis=1,
                            keepdims=True), axis=0, keepdims=True)
    at = first.astype(jnp.int32)
    shift = n.bit_length() - 1
    member = (slot == (at >> shift)) & (lane <= (at & (n - 1)))
    bit = jnp.left_shift(jnp.int32(1), code & 7).astype(jnp.float32)
    mine = jnp.where(member, bit, 0.0)
    col = lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    row = lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 0)
    out = jnp.where((row == 0) & (col == 0), first,
                    jnp.where((row == 0) & (col == 1), best, 0.0))
    for j in range(n // 8):
        byte = jnp.sum(jnp.sum(jnp.where((code >> 3) == j, mine, 0.0), axis=1,
                               keepdims=True), axis=0, keepdims=True)
        out = jnp.where((row == 1) & (col == j), byte, out)
    out_ref[0] = out


@functools.partial(jax.jit, static_argnames=(
    "depth", "reg_lambda", "min_child_weight", "interpret"))
def cat_split(hist, *, depth: int, reg_lambda: float, min_child_weight: float,
              interpret: bool = False):
    """Best categorical split a node of a level's COMBINED histogram of the
    categorical features alone, ``[nodes, C, B, 2]``: ``(slot, prefix,
    gain, words)``, where ``slot`` indexes the C features, the left set is
    ``prefix`` + 1 codes and ``words`` ``[nodes, SET_WORDS]`` holds it (bit
    ``c & 31`` of word ``c >> 5``: code ``c`` goes left).  A code with no
    hessian mass is absent: in no set, so it goes right.  A node with no
    valid candidate has gain ``-inf`` (``models.gbdt.level_splits`` then
    takes the numeric candidate).

    The order is LightGBM's and XGBoost's partition search (Fisher 1958):
    present codes by ``G / (H + lambda)`` ascending, ties by code, and every
    prefix of that order scored exactly, with no cap on the set and no
    smoothing (``_cat_split_kernel``).  One kernel a level,
    ``cat_split_d<depth>``, a grid step a node; the codes are padded to 128
    or 256 lanes, the features to whole sublane tiles."""
    nodes, C, B, _ = hist.shape
    if not 2 <= B <= 32 * SET_WORDS:
        raise ValueError(f"cat_split: {C} features x {B} codes: a set holds "
                         f"2..{32 * SET_WORDS} codes (codes of a byte)")
    lanes = 128 if B <= 128 else 256
    slots = _round_up(C, 8)
    g, h = (jnp.pad(hist[..., k], ((0, 0), (0, slots - C), (0, lanes - B)))
            for k in (0, 1))
    block = pl.BlockSpec((1, slots, lanes), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_cat_split_kernel, reg_lambda=reg_lambda,
                          min_child_weight=min_child_weight),
        grid=(nodes,),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nodes, 8, 128), jnp.float32),
        interpret=interpret,
        name=f"cat_split_d{depth}",
    )(g, h)
    key = out[:, 0, 0].astype(jnp.int32)
    byts = jnp.zeros((nodes, 4 * SET_WORDS), jnp.int32).at[:, :lanes // 8].set(
        out[:, 1, :lanes // 8].astype(jnp.int32))
    words = jnp.zeros((nodes, SET_WORDS), jnp.int32)
    for b in range(4):
        words = words | jnp.left_shift(byts[:, b::4], 8 * b)
    return key // lanes, key % lanes, out[:, 0, 1], words


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
