"""The three rounds the benchmark's accepted cells run, as jaxprs, at the
HIGGS and the Criteo shape.

Levels 0 to 4 are the program they were before any level was derived: a
depth-5 round at the HIGGS shape (every node of every level built) traces
to the jaxpr it traced to on the parent of PR 32 (commit 335edd8), character
for character — the ``higgs-d5`` digests, recorded there.  From level 5 on
``ops.boost.hist_plan`` builds one child a parent and derives its sibling
(PR 32), so the six whole rounds were re-recorded on PR 32's tree; before,
they were the parent of PR 31's (934b79c: one tile of features is the
program it was before the kernels walked features in tiles).

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
          "higgs-d5": (2_625_000, 28, 5)}
WANT = {
    ("higgs", "fused"): "3f3c80735c0daa3f",
    ("higgs", "hybrid"): "ef40f4892ec7bab6",
    ("higgs", "dp_fused"): "797a5f6d6d9030e5",
    ("criteo", "fused"): "a9d2175949153e23",
    ("criteo", "hybrid"): "0ad9a7e8afc1b35f",
    ("criteo", "dp_fused"): "59f0703b1f50c556",
    # levels 0-4 alone: the parent's, recorded on 335edd8
    ("higgs-d5", "fused"): "db3f2cfb968b83b9",
    ("higgs-d5", "hybrid"): "ffe80bf26b7e912b",
    ("higgs-d5", "dp_fused"): "ff079d267d88a086",
}


def digest(fn, *args) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def traced(shape: str, which: str) -> str:
    n, f, depth = SHAPES[shape]
    cfg = gbdt.GBDTConfig(n_features=f, n_trees=500, depth=depth, n_bins=256,
                          learning_rate=0.1)
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
