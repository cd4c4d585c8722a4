"""The hybrid round as ``worker.py`` asks for it on the chip is the program
it was before the worker passed ``interpret`` (PR 33): there ``interpret``
is False, the default of ``train_round_hybrid``, and the jaxpr with the
keyword is the jaxpr without it, character for character, at the engine-hop
cell's shape and at Epsilon's (the kept cell's).  Traced in a child, on
``train_round_hybrid``'s TPU branch (the fused kernels): tracing needs shapes
only, and this process keeps free of jax for the rehearsals."""

import json
import subprocess
import sys

import pytest

from conftest import ROOT

M = json.loads((ROOT / "BENCHMARK.json").read_text())
CODE = r"""
import functools, json, re, sys
import jax, jax.numpy as jnp, numpy as np
from rabit_tpu.models import gbdt

sys.path.insert(0, "benchmark")
from harness import deployment

jax.default_backend = lambda: "tpu"     # the branch the chip takes
c = json.loads(sys.argv[1])
# as worker.py builds it: the sizes and the ``program`` group's switches
cfg = deployment.gbdt_config(c, gbdt.GBDTConfig)
assert cfg == gbdt.GBDTConfig(
    n_features=c["features"], n_trees=c["num_trees"], depth=c["max_depth"],
    n_bins=c["max_bin"], learning_rate=c["eta"], reg_lambda=c["lambda"],
    min_child_weight=c["min_child_weight"]), cfg
n = c["rows"]
sds = jax.ShapeDtypeStruct
state = jax.eval_shape(lambda: gbdt.init_state(cfg, n))


def text(**kw):
    fn = functools.partial(gbdt.train_round_hybrid, cfg=cfg,
                           engine_allreduce=lambda a: np.asarray(a), **kw)
    jaxpr = jax.make_jaxpr(fn)(state, sds((n, c["features"]), jnp.int32),
                               sds((n,), jnp.float32))
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


was, now = text(), text(interpret=False)
print(json.dumps({"same": was == now, "kernels": was.count("pallas_call"),
                  "hops": was.count("pure_callback"),
                  "interpreted": "interpret=True" in was}))
"""
#: the engine-hop cells' configurations and that of the cell kept for a later
#: PR, ``epsilon.engine-hop`` (PERF.md, section 7): it comes as data alone
HOP_CONFIGS = sorted({c["config"] for c in M["workloads"]
                      if c["traffic"] == "engine-hop"} | {"epsilon-400k"})


@pytest.mark.parametrize("config", HOP_CONFIGS)
def test_the_chips_hybrid_round_is_the_one_without_the_keyword(config):
    entry = next(c for c in M["configs"] if c["name"] == config)
    sizes = json.loads((ROOT / entry["file"]).read_text())
    r = subprocess.run([sys.executable, "-c", CODE, json.dumps(sizes)],
                       capture_output=True, text=True, cwd=ROOT,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": str(ROOT)})
    assert r.returncode == 0, r.stderr[-2000:]
    got = json.loads(r.stdout.strip().splitlines()[-1])
    assert got["same"] and not got["interpreted"]
    assert got["kernels"] > 0                      # the fused kernels
    assert got["hops"] == sizes["max_depth"] + 1   # a hop a level, the leaves'


def test_the_worker_passes_interpret_and_patches_nothing():
    src = (ROOT / "benchmark" / "worker.py").read_text()
    assert "hist.node_histograms =" not in src
    assert "engine_allreduce=engine_hop,\n            interpret=interpret))" in src
