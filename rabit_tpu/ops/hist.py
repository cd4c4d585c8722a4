"""Gradient-histogram kernels — the hot op of the flagship GBDT workload.

``hist[node, f, b] = sum_i [node_i == node][xb_i[f] == b] * (g_i, h_i)``

Implementations of the same contract:

* ``node_histograms_scatter`` — ``segment_sum`` (XLA scatter-add).  Exact
  f32, the portable reference; scatter serializes on TPU so it is the slow
  path there (and what the original bench measured at ~350-560 ms/level for
  1M x 28 x 256).
* ``node_histograms_onehot`` — one-hot matmul, pure XLA: a chunked
  ``lax.scan`` whose body contracts a (rows x 2*nodes) gradient matrix
  against a (rows x F*B) bin-indicator matrix.  Runs the FLOPs on the MXU
  on TPU and vectorizes fine on CPU.
* ``node_histograms_pallas`` — the same contraction as a Pallas TPU kernel:
  the indicator matrices are built in VMEM and never touch HBM, and the f32
  gradients are split hi/lo into two bfloat16 matmuls so the MXU runs at
  bf16 rate with ~f32 accuracy (error 2^-16-relative, vs 2^-8 for naive
  bf16).  ``mxu_i8=True`` switches the contraction to a two-plane int8
  fixed-point split (s8 x s8 -> s32, 2x the bf16 issue rate on
  v5e-class MXUs, error ~2^-14 of the block max: 14-bit fixed point,
  2^-13 quantization step, 2^-14 round-off — see ops/boost.py
  ``_encode_i8``).

``node_histograms`` dispatches: Pallas on TPU, scatter elsewhere (tests run
on the virtual CPU mesh and want exact-f32 determinism).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_DN = (((0,), (0,)), ((), ()))  # contract dim 0 against dim 0, no batch


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# -- scatter (reference) ----------------------------------------------------


def node_histograms_scatter(xb, g, h, node, n_nodes: int, n_bins: int):
    """Exact-f32 segment_sum implementation; [n_nodes, F, B, 2]."""
    n, F = xb.shape
    seg = (node[:, None] * F + jnp.arange(F)[None, :]) * n_bins + xb  # [n, F]
    gh = jnp.stack(
        [
            jnp.broadcast_to(g[:, None], (n, F)),
            jnp.broadcast_to(h[:, None], (n, F)),
        ],
        axis=-1,
    )  # [n, F, 2]
    hist = jax.ops.segment_sum(
        gh.reshape(-1, 2), seg.reshape(-1), num_segments=n_nodes * F * n_bins
    )
    return hist.reshape(n_nodes, F, n_bins, 2)


# -- one-hot matmul (pure XLA) ---------------------------------------------


def node_histograms_onehot(xb, g, h, node, n_nodes: int, n_bins: int,
                           block_rows: int = 8192):
    """One-hot-matmul implementation; [n_nodes, F, B, 2].

    Per row chunk: L[r, m] puts g (m < n_nodes) / h (m >= n_nodes) in the
    column of the row's node; Bo[r, f*B+b] indicates bin membership; the
    chunk's histogram is L^T @ Bo, accumulated in f32 across chunks.
    """
    n, F = xb.shape
    R = min(block_rows, _round_up(n, 128))
    n_pad = _round_up(n, R)
    if n_pad != n:
        pad = n_pad - n
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))  # zero g/h => padded rows contribute nothing
        node = jnp.pad(node, (0, pad))
    nb = n_pad // R

    def body(acc, sl):
        xbc, gc, hc, nodec = sl
        N = jax.nn.one_hot(nodec, n_nodes, dtype=jnp.float32)      # [R, nodes]
        L = jnp.concatenate([N * gc[:, None], N * hc[:, None]], 1)  # [R, 2*nodes]
        Bo = jax.nn.one_hot(xbc, n_bins, dtype=jnp.float32)         # [R, F, B]
        acc += lax.dot_general(
            L, Bo.reshape(R, F * n_bins), _DN,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return acc, None

    sl = (
        xb.reshape(nb, R, F),
        g.reshape(nb, R),
        h.reshape(nb, R),
        node.reshape(nb, R),
    )
    acc0 = jnp.zeros((2 * n_nodes, F * n_bins), jnp.float32)
    acc, _ = lax.scan(body, acc0, sl)
    acc = acc.reshape(2, n_nodes, F, n_bins)
    return jnp.stack([acc[0], acc[1]], axis=-1)


# -- Pallas TPU kernel ------------------------------------------------------


def _hist_kernel(xb_ref, node_ref, g_ref, h_ref, out_ref, *,
                 n_nodes: int, n_bins: int, m_pad: int, n_feat: int, fc: int,
                 i8: bool, packed: bool):
    from rabit_tpu.ops import boost

    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    L = boost._gradient_matrix(node_ref[0], g_ref[0], h_ref[0],
                               n_nodes=n_nodes, m_pad=m_pad)
    boost._accum(xb_ref[0], L, out_ref,
                 n_bins=n_bins, n_feat=n_feat, fc=fc, i8=i8, packed=packed)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "block_rows", "interpret", "mxu_i8"),
)
def node_histograms_pallas(xb, g, h, node, n_nodes: int, n_bins: int,
                           block_rows: int = 1024, interpret: bool = False,
                           mxu_i8: bool = False):
    """Pallas implementation; [n_nodes, F, B, 2].  Grid = row blocks: the
    whole (2*nodes, F*B) histogram stays resident in VMEM (1.8 MB at
    depth 6 / 28 features / 256 bins) while row blocks stream through; the
    gradient matrix L is built once per block and contracted against the
    bin-indicator matrices on the MXU (shared kernel helpers in ops.boost)."""
    from rabit_tpu.ops import boost

    n, F = xb.shape
    R = block_rows
    n_pad = _round_up(n, R)
    if n_pad != n:
        pad = n_pad - n
        xb = jnp.pad(xb, ((0, pad), (0, 0)))
        g = jnp.pad(g, (0, pad))
        h = jnp.pad(h, (0, pad))
        node = jnp.pad(node, (0, pad))
    m_pad = _round_up(2 * n_nodes, 8)
    nb = n_pad // R
    packed = boost._packed(n_bins, 2 * m_pad)
    codes, slots, lanes, fc = boost._one_block(F, n_bins, packed)

    out = pl.pallas_call(
        functools.partial(
            _hist_kernel, n_nodes=n_nodes, n_bins=n_bins, m_pad=m_pad,
            n_feat=slots, fc=fc, i8=mxu_i8, packed=packed,
        ),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, R, codes), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((m_pad, lanes), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, lanes), jnp.float32),
        interpret=interpret,
        name=f"node_histograms_n{n_nodes}",
    )(
        xb.reshape(nb, R, F),
        node.reshape(nb, R, 1),
        g.reshape(nb, R, 1),
        h.reshape(nb, R, 1),
    )

    out = boost._cut_lanes(out, F, n_bins, slots, packed)
    return jnp.stack([out[:n_nodes], out[n_nodes : 2 * n_nodes]], axis=-1)


# -- segment-sum-as-matmul (for small segment counts, e.g. leaf fit) -------


def segment_sum_matmul(values, seg, num_segments: int, block_rows: int = 8192):
    """``segment_sum(values, seg)`` as chunked one-hot matmuls; values
    [n, C] f32, seg [n] int32 -> [num_segments, C].  Beats scatter on TPU
    when num_segments is small (leaf-weight fit: 2**depth segments)."""
    n, C = values.shape
    R = min(block_rows, _round_up(n, 128))
    n_pad = _round_up(n, R)
    if n_pad != n:
        pad = n_pad - n
        values = jnp.pad(values, ((0, pad), (0, 0)))
        seg = jnp.pad(seg, (0, pad), constant_values=0)
        # padded rows land in segment 0 with zero value
    nb = n_pad // R

    def body(acc, sl):
        vc, sc = sl
        N = jax.nn.one_hot(sc, num_segments, dtype=jnp.float32)  # [R, S]
        acc += lax.dot_general(
            N, vc, _DN,
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        return acc, None

    acc0 = jnp.zeros((num_segments, C), jnp.float32)
    acc, _ = lax.scan(body, acc0, (values.reshape(nb, R, C), seg.reshape(nb, R)))
    return acc


# -- dispatchers ------------------------------------------------------------


def node_histograms(xb, g, h, node, n_nodes: int, n_bins: int,
                    impl: str | None = None, mxu_i8: bool = False):
    """Backend-appropriate histogram build; [n_nodes, F, B, 2].  With
    ``mxu_i8`` the TPU default becomes the int8-rate Pallas kernel (an
    explicit ``impl`` always wins)."""
    if impl is None:
        if jax.default_backend() == "tpu":
            impl = "pallas_i8" if mxu_i8 else "pallas"
        else:
            impl = "scatter"
    if impl == "pallas":
        return node_histograms_pallas(xb, g, h, node, n_nodes, n_bins)
    if impl == "pallas_i8":
        return node_histograms_pallas(xb, g, h, node, n_nodes, n_bins,
                                      mxu_i8=True)
    if impl == "onehot":
        return node_histograms_onehot(xb, g, h, node, n_nodes, n_bins)
    if impl == "scatter":
        return node_histograms_scatter(xb, g, h, node, n_nodes, n_bins)
    raise ValueError(f"unknown hist impl {impl!r}")


def segment_sum(values, seg, num_segments: int, impl: str | None = None):
    """Backend-appropriate segment_sum for small segment counts (leaf fit):
    one-hot matmul on TPU (scatter-add serializes there), XLA scatter
    elsewhere (exact f32)."""
    if impl is None:
        impl = "matmul" if jax.default_backend() == "tpu" else "scatter"
    if impl == "matmul":
        return segment_sum_matmul(values, seg, num_segments)
    if impl == "scatter":
        return jax.ops.segment_sum(values, seg, num_segments=num_segments)
    raise ValueError(f"unknown segment_sum impl {impl!r}")
