"""The three rounds the benchmark's accepted cells run, as jaxprs, at the
HIGGS and the Criteo shape.

All nine were re-recorded on PR 37's tree: below a full MXU tile of stacked
rows (levels 0 to 5) the kernels now pack the codes four a word at 256 bins
too, one lane broadcast for eight registers (``ops.boost._packed``), and
take the codes in a block one 128-lane tile wide; levels 6 and 7 of the
Criteo shape are the kernels they were.  Before, levels 0 to 4 (the
``higgs-d5`` digests, a depth-5 round at the HIGGS shape with every node of
every level built) had been the parent of PR 32's (commit 335edd8),
character for character, and the six whole rounds PR 32's tree (from level
5 on ``ops.boost.hist_plan`` builds one child a parent and derives its
sibling).  The round at the Epsilon shape, which packed already, is held
to PR 37's parent in tests/test_gbdt.py
(``test_the_epsilon_kernels_are_the_parent_of_pr_37s``).

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
    ("higgs", "fused"): "7ca445915a49e1d2",
    ("higgs", "hybrid"): "ea8fedafbec8e0ef",
    ("higgs", "dp_fused"): "4f038790436455c1",
    ("criteo", "fused"): "a94cc66df5b20306",
    ("criteo", "hybrid"): "09bd272c7c645eb7",
    ("criteo", "dp_fused"): "5bd484934e5769a4",
    # levels 0-4 alone
    ("higgs-d5", "fused"): "152bd5e782d1855d",
    ("higgs-d5", "hybrid"): "ed8e1ee0100078f9",
    ("higgs-d5", "dp_fused"): "1ef785c9d0fd6356",
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
