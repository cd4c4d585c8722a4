"""One tile is the program it was: up to ``ops.boost.TILE_FEATS`` features
the three rounds the benchmark's accepted cells run trace to the jaxpr they
traced to before the histogram kernels learned to walk features in tiles
(PR 31), character for character, at the HIGGS and the Criteo shape.

The digests are of ``str(jax.make_jaxpr(...))`` with addresses stripped,
recorded on the parent of PR 31 (commit 934b79c) by this file's own
``digest``; tracing needs shapes only, so the real row counts cost nothing.
A change that is meant to alter those programs records new ones here and
says so."""

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
SHAPES = {"higgs": (2_625_000, 28, 6), "criteo": (2_621_440, 67, 8)}
WANT = {
    ("higgs", "fused"): "8e9b34c90f1135e0",
    ("higgs", "hybrid"): "d6f63572b85c0b47",
    ("higgs", "dp_fused"): "a0a2a9645a4735dd",
    ("criteo", "fused"): "7c5b8649eb664600",
    ("criteo", "hybrid"): "b57665db1b6a94e2",
    ("criteo", "dp_fused"): "f2d5b964a5d4d24f",
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
        if shape == "higgs":
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
