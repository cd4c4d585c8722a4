"""PR 39's single kernels: ``hist_level`` alone under jit at Criteo's and
Epsilon's shapes, levels 6 and 7, the parent's kernel (the MXU holds the
indicator) against the held form (the MXU holds the gradient planes) at
feature groups of 2 to 16 (32 to 128 at 64 bins) a dot (``PLAN``): the
library's (``loop``: ``boost._groups_a_body`` whole groups a step of a
``fori_loop``), ``body<k>`` (k groups a step), every group
unrolled (``unrolled``: calls 1 to 3), the hi and lo planes as two dots of
one 128-lane operand each (``split``), and the unrolled form's matmuls
alone (``alone``: group 0's indicator built once a block and streamed for
every group: wrong sums, the MXU's own time with the same shapes).  A
variant ending ``:miss`` routes with Criteo's missing code, as
``criteo-missing.fused-armed`` does, against the parent's kernel with it.
Call 1 (PR 39) timed groups of 4, 8, 16 (and 32) and a first ``alone`` that
the compiler folded to one dot a block.

Each variant: one warm call, then a three-call trace; the kernel's own
device time is the ``hist_level_d<d>`` ops' seconds over three
(``benchmark/harness/xplane.py``).  The held sums are compared with the
parent's: max |difference| and whether every float is the parent's.

    python3 _dev/time39.py PARENT [criteo|epsilon ...]  # on the chip; writes
                                                         # chiprun_out/t39/*.json

``PARENT`` is a checkout of PR 39's parent, whose kernels are read from it
(``mkdir -p P && git archive faad222 | tar -x -C P``).
``T39_SMALL=1`` rehearses it on the CPU at two row blocks, interpreted.
"""

from __future__ import annotations

import functools
import glob
import importlib.util
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

OUT = os.path.join(ROOT, "chiprun_out", "t39")
R = 1024
SHAPES = {"criteo": (2560, 67, 256), "epsilon": (391, 2000, 64)}
#: a rehearsal on the CPU: two row blocks, the kernels interpreted
SMALL = bool(os.environ.get("T39_SMALL"))
if SMALL:
    SHAPES = {"criteo": (2, 67, 256), "epsilon": (2, 300, 64)}


def _parent(checkout):
    path = os.path.join(checkout, "rabit_tpu", "ops", "boost.py")
    spec = importlib.util.spec_from_file_location("boost_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _matmul_alone(xb_blk, L, out_ref, codes_t, *, n_bins, n_feat, fc,
                  feats_left=None):
    """``boost._accum_held`` with group 0's indicator built once a block
    and streamed for every group, each group's dot under a condition the
    compiler cannot fold (so that no two dots are one): the held form's
    matmuls, wrong sums."""
    be = boost._bins_eff(n_bins)
    m = L.shape[1]
    l2 = boost._encode_bf16(L)[0]
    codes_t[...] = xb_blk.T
    bins = lax.broadcasted_iota(jnp.int32, (be, xb_blk.shape[0]), 0)
    k0 = min(fc, n_feat)
    onehot = jnp.concatenate([codes_t[f : f + 1, :] == bins for f in range(k0)],
                             axis=0).astype(jnp.bfloat16)
    live = pl.program_id(0) >= 0 if feats_left is None else None

    def group(f0, k):
        acc2 = jnp.dot(onehot[: k * be], l2, preferred_element_type=jnp.float32)
        out_ref[f0 * be : (f0 + k) * be, :] += acc2[:, :m] + acc2[:, m:]

    for f0 in range(0, n_feat, fc):
        k = min(fc, n_feat - f0)
        pl.when(live if feats_left is None else f0 < feats_left)(
            functools.partial(group, f0, k))


def _unrolled(xb_blk, L, out_ref, codes_t, *, n_bins, n_feat, fc,
              feats_left=None):
    """``boost._accum_held`` as calls 1 to 3 of PR 39 had it: every group
    traced in turn, no loop."""
    be = boost._bins_eff(n_bins)
    m = L.shape[1]
    l2 = boost._encode_bf16(L)[0]
    codes_t[...] = xb_blk.T
    bins = lax.broadcasted_iota(jnp.int32, (be, xb_blk.shape[0]), 0)

    def group(f0, k):
        onehot = jnp.concatenate(
            [codes_t[f : f + 1, :] == bins for f in range(f0, f0 + k)],
            axis=0).astype(jnp.bfloat16)
        acc2 = jnp.dot(onehot, l2, preferred_element_type=jnp.float32)
        out_ref[f0 * be : (f0 + k) * be, :] += acc2[:, :m] + acc2[:, m:]

    for f0 in range(0, n_feat, fc):
        k = min(fc, n_feat - f0)
        if feats_left is None:
            group(f0, k)
        else:
            pl.when(f0 < feats_left)(functools.partial(group, f0, k))


def _split_planes(xb_blk, L, out_ref, codes_t, *, n_bins, n_feat, fc,
                  feats_left=None):
    """``boost._accum_held`` with the hi and the lo plane two dots of one
    128-lane operand each, summed: right sums, one tile of N a dot."""
    be = boost._bins_eff(n_bins)
    m = L.shape[1]
    l2 = boost._encode_bf16(L)[0]
    codes_t[...] = xb_blk.T
    bins = lax.broadcasted_iota(jnp.int32, (be, xb_blk.shape[0]), 0)

    def group(f0, k):
        onehot = jnp.concatenate(
            [codes_t[f : f + 1, :] == bins for f in range(f0, f0 + k)],
            axis=0).astype(jnp.bfloat16)
        hi = jnp.dot(onehot, l2[:, :m], preferred_element_type=jnp.float32)
        lo = jnp.dot(onehot, l2[:, m:], preferred_element_type=jnp.float32)
        out_ref[f0 * be : (f0 + k) * be, :] += hi + lo

    for f0 in range(0, n_feat, fc):
        k = min(fc, n_feat - f0)
        if feats_left is None:
            group(f0, k)
        else:
            pl.when(f0 < feats_left)(functools.partial(group, f0, k))


def inputs(nb, F, B, d, seed=39):
    n_prev = 2 ** (d - 1)
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    xb3 = jax.random.randint(k[0], (nb, R, F), 0, B, jnp.int32)
    node3 = jax.random.randint(k[1], (nb, R, 1), 0, n_prev, jnp.int32)
    g3 = jax.random.normal(k[2], (nb, R, 1), jnp.float32)
    h3 = jax.random.uniform(k[3], (nb, R, 1), jnp.float32)
    feat = jax.random.randint(k[4], (n_prev,), 0, F, jnp.int32)
    thr = jax.random.randint(k[5], (n_prev,), 0, B, jnp.int32)
    right = jax.random.randint(k[6], (n_prev,), 0, 2, jnp.int32)
    return [jax.block_until_ready(a) for a in
            (xb3, node3, g3, h3, feat, thr, right)]


def kernel_ms(fn, args, d, tag):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(fn(*args))
    wall = (time.perf_counter() - t0) / 3 * 1e3
    tdir = tempfile.mkdtemp(prefix=f"t39_{tag}_")
    with jax.profiler.trace(tdir):
        for _ in range(3):
            jax.block_until_ready(fn(*args))
    raw = xplane.read(sorted(glob.glob(f"{tdir}/**/*.xplane.pb",
                                       recursive=True))[-1])
    ops = {}
    for events in raw["devices"].values():
        for name, a, b in events:
            ops[name] = ops.get(name, 0.0) + (b - a) / 1e6 / 3
    kern = sum(v for k, v in ops.items() if k.startswith(f"hist_level_d{d}"))
    return out, {"kernel_ms": kern, "wall_ms": wall,
                 "ops": sorted(ops.items(), key=lambda kv: -kv[1])[:8]}


#: (shape, level): the variants timed, "<form>:<features a group>[:miss]";
#: ``loop`` is the library's (``boost._groups_a_body`` groups a loop step),
#: ``body<k>`` the same with k groups a step (call 7 of PR 39; call 6 timed
#: one group a step at 4, 6, 8 and 16; calls 1 to 3 the unrolled form at
#: groups of 2 to 16, ``split`` of 4 to 16 and Epsilon's groups of 4 to 32:
#: PERF.md section 5)
PLAN = {
    ("criteo", 6): ["loop:8", "loop:16", "loop:8:miss"],
    ("criteo", 7): ["loop:8", "body2:8", "loop:16", "loop:8:miss"],
}
#: groups of 64 features (4,096 rows) and more at 64 bins, and ``split:64``,
#: run out of the kernel's VMEM at the plan's ask, as ``alone:16`` at Criteo's
#: level 7 (my compile, PR 39)
FORMS = {"alone": _matmul_alone, "split": _split_planes,
         "unrolled": _unrolled}


def main(checkout, which):
    os.makedirs(OUT, exist_ok=True)
    parent = _parent(checkout)
    held_accum, pick = boost._accum_held, boost._pick_held_fc
    body = boost._groups_a_body
    for shape in which:
        nb, F, B = SHAPES[shape]
        for d in (6, 7):
            args = inputs(nb, F, B, d)
            rows, refs = [], {}
            call = lambda mod, miss: jax.jit(functools.partial(
                mod.hist_level.__wrapped__, depth=d, n_bins=B,
                interpret=SMALL, missing_code=B - 1 if miss else None))
            for miss in sorted({v.endswith(":miss") for v in PLAN[(shape, d)]}):
                (ref, _), rec = kernel_ms(call(parent, miss), args, d,
                                          "parent")
                refs[miss] = np.asarray(ref)
                rows.append({"variant": "parent", "missing": miss, **rec})
                print(shape, d, "parent", "miss" * miss, rec["kernel_ms"],
                      rec["wall_ms"], flush=True)
            for v in PLAN[(shape, d)]:
                form, fc, *miss = v.split(":")
                miss = bool(miss)
                boost._pick_held_fc = lambda n_bins, k=int(fc): k
                boost._accum_held = FORMS.get(form, held_accum)
                boost._groups_a_body = (
                    (lambda full, c, k=int(form[4:]): k)
                    if form.startswith("body") else body)
                assert boost.hist_plan(F, B, d, R).mxu_holds == "gradients"
                (hist, _), rec = kernel_ms(call(boost, miss), args, d,
                                           form + fc)
                hist = np.asarray(hist)
                diff = float(np.abs(hist - refs[miss]).max())
                rec.update(variant=form, feats_a_group=int(fc), missing=miss,
                           max_abs_diff=diff,
                           bitwise=bool(np.array_equal(hist, refs[miss])))
                rows.append(rec)
                print(shape, d, form, fc, "miss" * miss, rec["kernel_ms"],
                      rec["wall_ms"], diff, rec["bitwise"], flush=True)
            boost._accum_held, boost._pick_held_fc = held_accum, pick
            boost._groups_a_body = body
            with open(os.path.join(OUT, f"{shape}_d{d}.json"), "w") as f:
                json.dump(rows, f, indent=1)
            del args


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2:] or sorted({s for s, _ in PLAN}))
