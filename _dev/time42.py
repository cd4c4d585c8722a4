"""The leaves' pass, ``margin += leaf[node]`` after the last routing pass,
in each candidate form, as single operations at Criteo's, HIGGS's and
Epsilon's shapes:

* ``a``: the parent's — ``route_level`` and XLA's gather,
  ``margin + leaf[unblock_rows(node3, n)]``;
* ``b``: the parent's ``route_margin_level`` — the lookup a lane-masked sum
  over the leaves inside the routing kernel, the margin blocked
  ``(nb, 1024, 1)`` around it;
* ``c64``, ``c128``: ``route_level`` and the lookup kept in XLA over tables
  no wider than 64 or 128 entries, ``node & 63`` (``& 127``) in each and a
  select by ``node >> 6`` (``>> 7``); ``c1hot``: an XLA one-hot sum over
  every leaf;
* ``d``: this tree's ``route_margin_level`` — routing and lookup in one
  kernel, the margin in and out lane-dense, no node ids written;
* ``dt``: the same pass through the tiled routing kernel
  (``_route_tiles``, one tile the codes' width), as ``route_margin_level``
  runs it past ``TILE_FEATS``.

Each timing: the function compiled with the layouts XLA picks for the
blocked inputs (so no call pays a copy of the codes or node ids that the
round does not), one warm call, then a three-call trace; the device time is
every operation of the call over three (``benchmark/harness/xplane.py``),
the form's relayouts included.  Every margin is compared with form ``a``'s,
bit for bit.

    python3 _dev/time42.py PARENT OUT [criteo criteo-missing criteo-cat higgs epsilon]

``T42_FORMS=a,d,dt`` times those forms alone.
writes ``OUT/<shape>.json``.  ``PARENT`` is a checkout of the parent tree
(``mkdir -p P && git archive 0fa7194 | tar -x -C P``).  ``T42_SMALL=1``
rehearses it on the CPU at two row blocks, interpreted.
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
from jax.experimental.layout import Format, Layout  # noqa: E402

from harness import xplane  # noqa: E402
from rabit_tpu.ops import boost  # noqa: E402

R = 1024
#: row blocks, features, depth, rows, switch
SHAPES = {"criteo": (2560, 67, 8, 2_621_440, None),
          "criteo-missing": (2560, 67, 8, 2_621_440, "missing"),
          "criteo-cat": (2560, 67, 8, 2_621_440, "cat"),
          "higgs": (2564, 28, 6, 2_625_000, None),
          "epsilon": (391, 2000, 8, 400_000, None)}
SMALL = bool(os.environ.get("T42_SMALL"))
if SMALL:
    SHAPES = {k: (2, f, d, 2 * R - 100, s) for k, (_, f, d, _, s)
              in SHAPES.items()}


def _parent(checkout):
    path = os.path.join(checkout, "rabit_tpu", "ops", "boost.py")
    spec = importlib.util.spec_from_file_location("boost_parent", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(nb, F, depth, n, switch, seed=42):
    rng = np.random.default_rng(seed)
    n_prev = 2 ** (depth - 1)
    xb3 = jnp.asarray(rng.integers(0, 256 if F < 2000 else 64, (nb, R, F)),
                      jnp.int32)
    node3 = jnp.asarray(rng.integers(0, n_prev, (nb, R, 1)), jnp.int32)
    feat = jnp.asarray(rng.integers(0, F, n_prev), jnp.int32)
    thr = jnp.asarray(rng.integers(0, 255, n_prev), jnp.int32)
    words = None
    if switch == "missing":
        thr = thr | jnp.asarray(rng.integers(0, 2, n_prev) * 256, jnp.int32)
    if switch == "cat":
        sets = rng.integers(-2 ** 31, 2 ** 31, (n_prev, 8))
        sets[rng.integers(0, 2, n_prev) == 0] = 0
        words = boost.route_words(thr, jnp.asarray(sets, jnp.int32))
    leaf = rng.standard_normal(2 ** depth).astype(np.float32) * 0.1
    leaf[:3] = (-0.0, 1e-30, 3.0)
    margin = jnp.asarray(rng.standard_normal(n), jnp.float32)
    return xb3, node3, margin, feat, thr, jnp.asarray(leaf), words


def _lookup(leaf, idx, width):
    """``leaf[idx]`` over tables of ``width`` entries: a gather from each,
    then a select by the table."""
    tabs = leaf.reshape(-1, width)
    lo, hi = idx & (width - 1), idx >> (width.bit_length() - 1)
    out = tabs[0][lo]
    for k in range(1, tabs.shape[0]):
        out = jnp.where(hi == k, tabs[k][lo], out)
    return out


def forms(parent, depth, missing_code):
    route = functools.partial(parent.route_level, depth=depth,
                              interpret=SMALL, missing_code=missing_code)

    def a(xb3, node3, margin, feat, thr, leaf, words):
        node3 = route(xb3, node3, feat, thr, words)
        return margin + leaf[parent.unblock_rows(node3, margin.shape[0])]

    def b(xb3, node3, margin, feat, thr, leaf, words):
        m3, _ = parent.block_rows(margin, R)
        m3, _ = parent.route_margin_level(
            xb3, node3, m3, feat, thr, leaf, words, depth=depth,
            interpret=SMALL, missing_code=missing_code)
        return parent.unblock_rows(m3, margin.shape[0])

    def c(width):
        def run(xb3, node3, margin, feat, thr, leaf, words):
            node3 = route(xb3, node3, feat, thr, words)
            idx = parent.unblock_rows(node3, margin.shape[0])
            if width is None:
                hot = idx[:, None] == jnp.arange(leaf.shape[0])
                return margin + jnp.sum(jnp.where(hot, leaf, 0.0), axis=1)
            return margin + _lookup(leaf, idx, width)
        return run

    def d(xb3, node3, margin, feat, thr, leaf, words):
        return boost.route_margin_level(
            xb3, node3, margin, feat, thr, leaf, words, depth=depth,
            interpret=SMALL, missing_code=missing_code)

    def dt(xb3, node3, margin, feat, thr, leaf, words):
        nb, _, F = xb3.shape
        n, n_prev = margin.shape[0], 2 ** (depth - 1)
        p_pad = boost._round_up(n_prev, 128)
        featp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(feat)
        thrp = jnp.zeros((8, p_pad), jnp.int32).at[0, :n_prev].set(thr)
        planes = boost._leaf_planes(leaf, boost._round_up(2 ** depth, 128))
        sets = [] if words is None else [boost._set_table(words, p_pad)]
        m3 = jnp.pad(margin, (0, nb * R - n)).reshape(nb, 1, R)
        out = boost._route_tiles(
            xb3, node3, featp, thrp, sets, (planes, m3),
            name=f"route_margin_d{depth}", interpret=SMALL,
            missing_code=missing_code)
        return out.reshape(-1)[:n]

    out = {"a": a, "b": b, "c64": c(64), "c128": c(128), "c1hot": c(None),
           "d": d, "dt": dt}
    if 2 ** depth <= 64:
        del out["c64"], out["c128"]
    return out


def device_ms(fn, args, tag):
    """``fn(*args)`` compiled with the blocked inputs in the layouts XLA
    picks; warm, its wall time over three calls and the device time of
    every operation over a traced three."""
    dev = jax.devices()[0]
    auto = Format(Layout.AUTO, jax.sharding.SingleDeviceSharding(dev))
    shard = [auto if a is not None and a.ndim == 3 else None for a in args]
    jf = jax.jit(fn, in_shardings=tuple(shard))
    t0 = time.perf_counter()
    compiled = jf.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    formats = compiled.input_formats[0]
    args = [a if a is None or f is None else jax.device_put(a, f)
            for a, f in zip(args, formats)]
    call = lambda: compiled(*args)
    out = jax.block_until_ready(call())
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(call())
    wall = (time.perf_counter() - t0) / 3 * 1e3
    tdir = tempfile.mkdtemp(prefix=f"t42_{tag}_")
    with jax.profiler.trace(tdir):
        for _ in range(3):
            jax.block_until_ready(call())
    ops = {}
    paths = sorted(glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True))
    if paths:
        raw = xplane.read(paths[-1])
        for events in raw["devices"].values():
            for name, t_a, t_b in events:
                ops[name] = ops.get(name, 0.0) + (t_b - t_a) / 1e6 / 3
    return out, {"device_ms": sum(ops.values()), "wall_ms": wall,
                 "compile_s": compile_s,
                 "ops": sorted(ops.items(), key=lambda kv: -kv[1])[:8]}


def main(checkout, out, which):
    os.makedirs(out, exist_ok=True)
    parent = _parent(checkout)
    for shape in which:
        nb, F, depth, n, switch = SHAPES[shape]
        args = inputs(nb, F, depth, n, switch)
        mc = 255 if switch == "missing" else None
        rows, want = [], None
        only = os.environ.get("T42_FORMS")
        for form, fn in forms(parent, depth, mc).items():
            if only and form not in only.split(","):
                continue
            if shape == "epsilon" and form in ("b", "c128", "c1hot"):
                continue
            if shape in ("criteo-missing", "criteo-cat") and form not in (
                    "a", "b", "c64", "d", "dt"):
                continue
            try:
                got, rec = device_ms(fn, args, f"{shape}_{form}")
            except Exception as e:  # noqa: BLE001 — report and go on
                rows.append({"form": form, "error": repr(e)[:400]})
                print(shape, form, "ERROR", repr(e)[:400], flush=True)
                continue
            bits = np.asarray(got).view(np.uint32)
            if want is None:
                want = bits
            rec["bitwise"] = bool(np.array_equal(bits, want))
            rows.append({"form": form, **rec})
            print(shape, form, round(rec["device_ms"], 3),
                  round(rec["wall_ms"], 3), rec["bitwise"],
                  rec["ops"][:4], flush=True)
        with open(os.path.join(out, f"{shape}.json"), "w") as f:
            json.dump(rows, f, indent=1)
        del args


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], sys.argv[3:] or list(SHAPES))
