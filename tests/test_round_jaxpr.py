"""The three rounds the benchmark's accepted cells run, as jaxprs, at the
HIGGS and the Criteo shape.

All thirteen were re-recorded on this tree: the leaves' pass of every
round at both shapes is one kernel, ``ops.boost.route_margin_level``
(``route_margin_d<depth>``), which routes the rows to their leaves and adds
each row's leaf to the margin — the margin in and out lane-dense, no node
ids written — where the parent's ``route_level`` wrote the node ids and
XLA gathered every row from the leaf table; and every level's kernel
(``hist_level``, ``route_level``) writes its routed node ids over the ids
it reads (``input_output_aliases``), so that the round holds one buffer of
them, 128 lanes a row on the chip, where XLA's buffer assignment placed
two or three.  The hybrid round's hops and what every level computes are
what they were.  They had been recorded
before when, from level 3 on
``ops.boost.hist_plan`` builds one child a parent and derives its sibling
(level 3 stacks 16 rows of gradient matrix where every node stacked 32,
level 4 32 where it stacked 64), so every round at both shapes changes at
levels 3 and 4 — the kernels of those levels take the built-child table
and accumulate half the nodes, ``smaller_child`` and ``derive_siblings``
come in after them, and the hybrid round's hops of those levels carry the
built half — and ``higgs-d5``, a depth-5 round at the HIGGS shape, is
levels 0 to 2 built and 3 and 4 derived.  At every derived level the
built-child table now holds the built children's node ids, one compare a
lane against a row's node (``ops.boost._gradient_matrix``,
``_built_table``), where it held the side a parent builds; levels 0 to 2
are the kernels they were.  Before,
the HIGGS and Criteo digests had been recorded when the kernels began to
pack the codes four a word at 256 bins below a full MXU tile of stacked
rows (``ops.boost._packed``), and the Criteo ones again when the MXU began
to hold the gradient planes from a full tile on
(``ops.boost._holds_gradients``, ``_accum_held``, ``_groups_a_body``).  The
round at the Epsilon shape, which packed already, is held at levels 0 and
5 to the kernels before that in tests/test_gbdt.py
(``test_the_epsilon_kernels_are_the_parent_of_pr_37s``).

``criteo-missing`` is the Criteo shape under ``GBDTConfig.missing``, as
``criteo-missing.fused-armed`` runs it; ``criteo-off`` is the Criteo shape
with ``categorical`` given as an empty JSON list, the switch off: its three
rounds are the plain Criteo digests, character for character — a round
with no categorical feature passes no set table to any kernel and traces
what it would without the switch.

The digests are of ``str(jax.make_jaxpr(...))`` with addresses stripped, by
this file's own ``digest``; tracing needs shapes only, so the real row
counts cost nothing.  A change that is meant to alter those programs
records new ones here and says so."""

from __future__ import annotations

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rabit_tpu.models import gbdt
from rabit_tpu.parallel import create_mesh

#: rows a chip, features, depth: benchmark/configs/higgs-10m5-quarter.json
#: (a chip's share of higgs-10m5-dp4.json) and criteo-1tb-share.json
SHAPES = {"higgs": (2_625_000, 28, 6), "criteo": (2_621_440, 67, 8),
          "higgs-d5": (2_625_000, 28, 5), "criteo-missing": (2_621_440, 67, 8),
          "criteo-off": (2_621_440, 67, 8)}
#: the switches of ``GBDTConfig`` a shape runs with
SWITCHES = {"criteo-missing": {"missing": True}, "criteo-off": {"categorical": []}}
WANT = {
    ("higgs", "fused"): "292ba3941487de1b",
    ("higgs", "hybrid"): "191630972d0db2a5",
    ("higgs", "dp_fused"): "8f57f21e7346ddde",
    ("criteo", "fused"): "6196c6407a638609",
    ("criteo", "hybrid"): "79a00463172f7715",
    ("criteo", "dp_fused"): "d243dd14e7ad8627",
    # levels 0-4 alone: 0-2 built, 3 and 4 derived
    ("higgs-d5", "fused"): "8d42e1648d07e42d",
    ("higgs-d5", "hybrid"): "5b1cea6122764cac",
    ("higgs-d5", "dp_fused"): "f7017e8b1f3a78cb",
    ("criteo-missing", "fused"): "959f25c807fe7f2c",
    ("criteo-off", "fused"): "6196c6407a638609",
    ("criteo-off", "hybrid"): "79a00463172f7715",
    ("criteo-off", "dp_fused"): "d243dd14e7ad8627",
}


def digest(fn, *args) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def traced(shape: str, which: str) -> str:
    n, f, depth = SHAPES[shape]
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=500, depth=depth, n_bins=256,
                          learning_rate=0.1, **SWITCHES.get(shape, {}))
    sds = jax.ShapeDtypeStruct
    if which == "dp_fused":
        # four shards of n rows under shard_map, as higgs-full.dp4-armed
        mesh = create_mesh(("dp",), devices=jax.devices()[:4])
        spec = gbdt.TrainState(forest=gbdt.Forest(P(), P(), P()),
                               margin=P("dp"), round=P())
        if shape.startswith("higgs"):
            n *= 4
        fn = jax.shard_map(
            functools.partial(gbdt.train_round_dp_fused, cfg=cfg), mesh=mesh,
            in_specs=(spec, P("dp", None, None), P("dp")), out_specs=spec,
            check_vma=False)
        codes = sds((4 * -(-(n // 4) // 1024), 1024, f), jnp.int32)
    elif which == "hybrid":
        fn = functools.partial(gbdt.train_round_hybrid, cfg=cfg, interpret=True,
                               engine_allreduce=lambda a: np.asarray(a))
        codes = sds((n, f), jnp.int32)
    else:
        fn = functools.partial(gbdt.train_round_fused, cfg=cfg)
        codes = sds((-(-n // 1024), 1024, f), jnp.int32)
    state = jax.eval_shape(lambda: gbdt.init_state(cfg, n))
    return digest(fn, state, codes, sds((n,), jnp.float32))


@pytest.mark.parametrize("shape,which", list(WANT), ids="-".join)
def test_one_tile_round_traces_to_the_jaxpr_it_was(shape, which):
    assert traced(shape, which) == WANT[(shape, which)]
