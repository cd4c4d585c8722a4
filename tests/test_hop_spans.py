"""A hop from the inside (doc/observability.md, "A hop's five phases"): the
spans of one ``rabit_tpu.allreduce`` through ``train_round_hybrid``'s host
callback — ``gbdt.cross`` and its five children — and the two copies
around a bare ``rabit_tpu.allreduce``."""

from __future__ import annotations

import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import rabit_tpu as rt
from rabit_tpu import obs
from rabit_tpu.models import gbdt

#: a hop's spans under ``gbdt.cross``, in the order they open
CHILDREN = ["gbdt.cross.in", "rabit.allreduce.copy_in", "rabit.allreduce",
            "rabit.allreduce.copy_out", "gbdt.cross.out"]
N, F, BINS = 2048, 3, 8


@pytest.fixture
def ring():
    obs.get_recorder().clear()
    yield obs.get_recorder()
    obs.get_recorder().clear()


@pytest.fixture
def engine():
    rt.init()
    yield
    rt.finalize()


def spans_of(ring, *prefixes):
    return [e.fields for e in ring.snapshot() if e.kind == "span"
            and e.fields["name"].startswith(prefixes)]


def hybrid(depth, rounds):
    """A jitted hybrid round on the fused kernels, interpreted, whose hop
    is the engine's allreduce, and the state and data it starts from."""
    rng = np.random.RandomState(7)
    cfg = gbdt.GBDTConfig(n_features=F, n_trees=rounds, depth=depth,
                          n_bins=BINS)
    xb = jnp.asarray(rng.randint(0, BINS, size=(N, F)), jnp.int32)
    y = jnp.asarray(rng.randint(0, 2, size=N), jnp.float32)
    step = jax.jit(functools.partial(
        gbdt.train_round_hybrid, cfg=cfg, interpret=True,
        engine_allreduce=lambda a: rt.allreduce(a, rt.SUM)))
    return step, gbdt.init_state(cfg, N), xb, y


def payloads(depth):
    """The bytes one way of each hop of a round: a float32 histogram
    ``[nodes built, F, BINS, 2]`` a level (from level 5 on the built half),
    then the leaves' masses ``[2**depth, 2]``."""
    built = [2 ** k if k < 5 else 2 ** (k - 1) for k in range(depth)]
    return [4 * n * F * BINS * 2 for n in built] + [4 * 2 ** depth * 2]


@pytest.mark.parametrize("depth", [3, 6])
def test_a_hop_is_one_cross_and_its_five_children_in_order(ring, engine,
                                                           depth):
    rounds = 2
    step, state, xb, y = hybrid(depth, rounds)
    for _ in range(rounds):
        state = jax.block_until_ready(step(state, xb, y))
        rt.checkpoint(np.asarray(state.forest.leaf))    # the round's commit
    got = spans_of(ring, "gbdt.cross", "rabit.allreduce")
    per_hop = len(CHILDREN) + 1
    assert len(got) == rounds * (depth + 1) * per_hop     # depth + 1 hops a round
    hops = [got[i:i + per_hop] for i in range(0, len(got), per_hop)]
    for i, hop in enumerate(hops):
        *children, cross = hop               # a span's event is written as it closes
        assert cross["name"] == "gbdt.cross" and cross["parent"] is None
        assert [c["name"] for c in children] == CHILDREN
        assert all(c["parent"] == "gbdt.cross" for c in children)
        k = i % (depth + 1)
        assert cross["level"] == (2 ** k if k < depth else -1)
        want = payloads(depth)[k]
        assert [s["nbytes"] for s in hop] == [want] * per_hop
        # one version a round: the commit between two rounds moves it
        assert {s["version"] for s in hop} == {i // (depth + 1)}
        assert children[2]["seqno"] == k
        # the children lie one after another inside their parent
        starts = [c["t0"] for c in children]
        assert starts == sorted(starts) and cross["t0"] <= starts[0]
        assert sum(c["seconds"] for c in children) <= cross["seconds"] + 1e-5


def doubled(a):
    a *= 2


@pytest.mark.parametrize("how", ["plain", "prepare_fun", "codec"])
def test_the_two_copies_around_a_bare_allreduce(ring, engine, how):
    data = np.arange(4096, dtype=np.float32).reshape(64, 64)
    kw = {"prepare_fun": {"prepare_fun": doubled},
          "codec": {"codec": "bf16"}}.get(how, {})
    out = rt.allreduce(data, rt.SUM, **kw)
    assert out.shape == data.shape
    got = spans_of(ring, "rabit.allreduce")
    assert [s["name"] for s in got] == CHILDREN[1:4]
    assert all(s["parent"] is None and s["nbytes"] == data.nbytes
               and s["version"] == 0 for s in got)
    assert got[1].get("codec") == ("bf16" if how == "codec" else None)
    if how == "prepare_fun":     # run lazily, inside the engine's call
        assert out[0, 1] == 2.0 and data[0, 1] == 2.0


def test_an_allreduce_that_refuses_its_input_closes_its_span(ring, engine):
    with pytest.raises(TypeError):
        rt.allreduce(np.zeros(4, np.complex64), rt.SUM)
    with pytest.raises(ValueError):
        rt.allreduce(np.zeros(4, np.float32), 99)
    assert [s["name"] for s in spans_of(ring, "rabit.")] == [
        "rabit.allreduce.copy_in"] * 2
    rt.allreduce(np.zeros(4, np.float32), rt.SUM)
    assert spans_of(ring, "rabit.allreduce")[-1]["parent"] is None


def test_a_hops_spans_land_in_the_profilers_trace(tmp_path, engine):
    """Inside a profiler session every span of a hop is a TraceAnnotation
    in the ``.xplane.pb`` with its fields as stats, the children inside
    their ``gbdt.cross`` and one after another: what
    ``benchmark/harness/hops.py`` reads."""
    from jax.profiler import ProfileData

    from rabit_tpu.profile import xla_trace

    depth = 3
    step, state, xb, y = hybrid(depth, 1)
    jax.block_until_ready(step(state, xb, y))           # compiled outside
    with xla_trace(str(tmp_path / "tr")):
        jax.block_until_ready(step(state, xb, y))
    (path,) = glob.glob(str(tmp_path / "tr" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    found = sorted(
        (e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name.startswith(("gbdt.cross", "rabit.allreduce")))
    crosses = [s for s in found if s[2] == "gbdt.cross"]
    assert [c[3]["level"] for c in crosses] == [1, 2, 4, -1]
    assert [c[3]["nbytes"] for c in crosses] == payloads(depth)
    for a, b, _, stats in crosses:
        inside = [s for s in found if a <= s[0] and s[1] <= b
                  and s[2] != "gbdt.cross"]
        assert [s[2] for s in inside] == CHILDREN
        assert all(s[1] <= t[0] for s, t in zip(inside, inside[1:]))
        assert all(s[3]["nbytes"] == stats["nbytes"]
                   and s[3]["version"] == stats["version"] for s in inside)
        assert inside[2][3]["seqno"] >= 0
