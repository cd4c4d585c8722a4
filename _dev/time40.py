"""The categorical split search's two forms and the membership test, single
kernels at the Criteo shape with its 26 categorical features.

* ``bitonic``: the library's ``ops.boost.cat_split`` (a bitonic network
  along the 256 lanes, prefix sums by rolls, the set's bytes written);
* ``pairwise``: the sort-free form, here only: a rank by pairwise compare,
  256 x 256 a node and feature, and the prefix sums as that 0/1 mask times
  the hi and lo bfloat16 planes of (G, H) on the MXU; it writes the best key
  and gain alone (no set), so it is the lighter of the two in what it
  writes.

Both at levels 0 to 7 on the same histograms (``[2**d, 26, 256, 2]``, a
random hessian mass on 85 % of the codes), their best gains and keys
compared; then ``hist_level`` at levels 6 and 7 and ``route_level`` at
level 8 with and without the level's set table, the routed node ids
compared with the XLA path's.

Each timing: one warm call, then a three-call trace; the kernel's own device
time is the ops named for it over three (``benchmark/harness/xplane.py``).

    python3 _dev/time40.py [OUT]      # on the chip; writes OUT (t40.json)

``T40_SMALL=1`` rehearses it on the CPU at two row blocks, interpreted.
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from harness import xplane  # noqa: E402
from rabit_tpu.ops import boost  # noqa: E402

OUT = sys.argv[1] if len(sys.argv) > 1 else "t40.json"
R, NB, F, B, C = 1024, 2560, 67, 256, 26
SMALL = bool(os.environ.get("T40_SMALL"))
if SMALL:
    NB = 2


def _pairwise_kernel(g_ref, h_ref, gt_ref, ht_ref, out_ref, *, n_cat,
                     reg_lambda, min_child_weight):
    """One node: for each categorical feature, code c's prefix is every
    present c' with (k_c', c') <= (k_c, c); its (GL, HL) and its size are
    the hi and lo planes (and a row of ones) times that mask, one dot; gains
    along the lanes; best key ``slot * L + prefix - 1`` and gain out."""
    n = g_ref.shape[2]
    sub = lax.broadcasted_iota(jnp.int32, (n, n), 0)       # c'
    lane = lax.broadcasted_iota(jnp.int32, (n, n), 1)      # c
    row8 = lax.broadcasted_iota(jnp.int32, (8, n), 0)
    best = first = None
    score = lambda a, b: a * a / (b + reg_lambda)
    for r in range(n_cat):
        g, h = g_ref[0, r:r + 1, :], h_ref[0, r:r + 1, :]        # (1, L): c
        gc, hc = gt_ref[0, :, r:r + 1], ht_ref[0, :, r:r + 1]    # (L, 1): c'
        pr = h > 0
        kr = jnp.where(pr, g / (h + reg_lambda), jnp.inf)
        kc = jnp.where(hc > 0, gc / (hc + reg_lambda), jnp.inf)
        # mask[c', c]: present c' at or before c in the order
        mask = ((kc < kr) | ((kc == kr) & (sub <= lane))) & (hc > 0)
        ghi = g.astype(jnp.bfloat16).astype(jnp.float32)
        hhi = h.astype(jnp.bfloat16).astype(jnp.float32)
        planes = jnp.where(row8 == 0, ghi, jnp.where(
            row8 == 1, g - ghi, jnp.where(row8 == 2, hhi, jnp.where(
                row8 == 3, h - hhi, jnp.where(row8 == 4, 1.0, 0.0)))))
        acc = jnp.dot(planes.astype(jnp.bfloat16), mask.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)         # (8, L)
        GL, HL = acc[0:1] + acc[1:2], acc[2:3] + acc[3:4]
        size = acc[4:5]
        K = jnp.sum(pr.astype(jnp.float32), axis=1, keepdims=True)
        G = jnp.sum(g, axis=1, keepdims=True)
        H = jnp.sum(h, axis=1, keepdims=True)
        ok = (pr & (size <= K - 1) & (HL >= min_child_weight)
              & (H - HL >= min_child_weight))
        gain = jnp.where(ok, score(GL, HL) + score(G - GL, H - HL)
                         - score(G, H), -jnp.inf)
        top = jnp.max(gain, axis=1, keepdims=True)
        key = jnp.min(jnp.where(gain == top, r * n + size - 1, jnp.inf),
                      axis=1, keepdims=True)
        if best is None:
            best, first = top, key
        else:
            first = jnp.where(top > best, key, first)
            best = jnp.maximum(best, top)
    c = lax.broadcasted_iota(jnp.int32, out_ref.shape[1:], 1)
    out_ref[0] = jnp.where(c == 0, first, best)


@functools.partial(jax.jit, static_argnames=("depth",))
def pairwise(hist, *, depth):
    nodes, n_cat, _, _ = hist.shape
    slots = -(-n_cat // 8) * 8
    g, h = (jnp.pad(hist[..., k], ((0, 0), (0, slots - n_cat), (0, 0)))
            for k in (0, 1))
    gt, ht = (jnp.pad(a, ((0, 0), (0, 128 - slots), (0, 0))).swapaxes(1, 2)
              for a in (g, h))
    block = pl.BlockSpec((1, slots, B), lambda i: (i, 0, 0))
    tblock = pl.BlockSpec((1, B, 128), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_pairwise_kernel, n_cat=n_cat, reg_lambda=1.0,
                          min_child_weight=1.0),
        grid=(nodes,), in_specs=[block, block, tblock, tblock],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nodes, 8, 128), jnp.float32),
        interpret=SMALL, name=f"cat_pairwise_d{depth}")(g, h, gt, ht)
    return out[:, 0, 0].astype(jnp.int32), out[:, 0, 1]


def histograms(d, seed=40):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed + d), 3)
    g = jax.random.normal(k1, (2 ** d, C, B), jnp.float32) * 3
    h = jax.random.uniform(k2, (2 ** d, C, B), jnp.float32) * 4
    h = jnp.where(jax.random.uniform(k3, h.shape) < 0.85, h, 0.0)
    return jax.block_until_ready(jnp.stack([jnp.where(h > 0, g, 0.0), h], -1))


def kernel_ms(fn, args, prefix):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    wall = (time.perf_counter() - t0) / 3 * 1e3
    tdir = tempfile.mkdtemp(prefix="t40_")
    with jax.profiler.trace(tdir):
        for _ in range(3):
            jax.block_until_ready(fn(*args))
    raw = xplane.read(sorted(glob.glob(f"{tdir}/**/*.xplane.pb",
                                       recursive=True))[-1])
    kern = sum((b - a) / 1e6 / 3 for events in raw["devices"].values()
               for name, a, b in events if name.startswith(prefix))
    return out, {"kernel_ms": kern, "wall_ms": wall}


def main():
    rows = []
    for d in range(8):
        hist = histograms(d)
        (slot, prefix, gain, _), bit = kernel_ms(functools.partial(
            boost.cat_split, depth=d, reg_lambda=1.0, min_child_weight=1.0,
            interpret=SMALL), (hist,), "cat_split")
        (key, pgain), pw = kernel_ms(functools.partial(pairwise, depth=d),
                                     (hist,), "cat_pairwise")
        same_key = bool(np.array_equal(np.asarray(slot * B + prefix),
                                       np.asarray(key)))
        gap = float(np.max(np.abs(np.asarray(gain) - np.asarray(pgain))
                           / np.maximum(np.abs(np.asarray(gain)), 1e-30)))
        rows.append({"level": d, "bitonic": bit, "pairwise": pw,
                     "same_key": same_key, "gain_rel_gap": gap})
        print("search", d, bit["kernel_ms"], pw["kernel_ms"], same_key, gap,
              flush=True)
    k = jax.random.split(jax.random.PRNGKey(41), 8)
    xb3 = jax.random.randint(k[0], (NB, R, F), 0, B, jnp.int32)
    g3 = jax.random.normal(k[1], (NB, R, 1), jnp.float32)
    h3 = jax.random.uniform(k[2], (NB, R, 1), jnp.float32)
    for d in (6, 7, 8):
        n = 2 ** (d - 1)
        node3 = jax.random.randint(k[3], (NB, R, 1), 0, n, jnp.int32)
        feat = jax.random.randint(k[4], (n,), 0, F, jnp.int32)
        thr = jax.random.randint(k[5], (n,), 0, B, jnp.int32)
        sets = jax.random.randint(k[6], (n, 8), -2 ** 31, 2 ** 31 - 1, jnp.int32)
        sets = jnp.where((jnp.arange(n) % 2 == 0)[:, None], sets, 0)
        words = boost.route_words(thr, sets)
        xv = jnp.take_along_axis(xb3, feat[node3], 2)[..., 0]
        want = node3[..., 0] * 2 + boost.went_right(
            xv, thr[node3[..., 0]], words=words[node3[..., 0]])
        for w in (None, words):
            if d < 8:
                built = jax.random.randint(k[7], (n,), 0, 2, jnp.int32)
                fn = jax.jit(functools.partial(
                    boost.hist_level, depth=d, n_bins=B, interpret=SMALL))
                (_, got), rec = kernel_ms(fn, (xb3, node3, g3, h3, feat, thr,
                                               built, w), f"hist_level_d{d}")
            else:
                fn = jax.jit(functools.partial(boost.route_level, depth=d,
                                               interpret=SMALL))
                got, rec = kernel_ms(fn, (xb3, node3, feat, thr, w),
                                     f"route_level_d{d}")
            rec.update(kernel=f"{'hist' if d < 8 else 'route'}_level_d{d}",
                       sets=w is not None)
            if w is not None:
                rec["routing_as_xla"] = bool(np.array_equal(
                    np.asarray(got)[..., 0], np.asarray(want)))
            rows.append(rec)
            print("routing", d, w is not None, rec, flush=True)
    with open(OUT, "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
