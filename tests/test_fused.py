"""Fused in-XLA quantized collectives (rabit_tpu/engine/fused.py, ISSUE 11).

The bitwise parity gate: the fused encode→ppermute→decode-fold graph must
equal :func:`rabit_tpu.compress.transport.reference_allreduce` — the host
path's closed form — **bit for bit**, for every codec × {SUM, MAX} ×
{identity ring, swing, repaired ring} at worlds 2/4/8 on the virtual CPU
mesh, replicated identically on every rank, chunk-size independent, and
identical again after an elastic ``rebuild_mesh`` recompile.  A larger
sweep (MIN, more sizes, sub-chunked hops) runs under ``slow``.
"""

from __future__ import annotations

import numpy as np
import pytest

import rabit_tpu as rt
from rabit_tpu import compress
from rabit_tpu.compress import get_codec, reference_allreduce
from rabit_tpu.config import Config
from rabit_tpu.engine import fused
from rabit_tpu.engine.base import MAX, MIN, SUM
from rabit_tpu.engine.xla import XlaEngine
from rabit_tpu.sched import mesh_for_world, plan

CODECS = ("bf16", "bf16x2", "i8", "i8x2")


def _contribs(world, n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(n) * 50).astype(np.float32) for _ in range(world)]


def _schedules(world):
    """The gate's three ring layouts: the reference's identity ring, the
    PR 7 swing serpentine, and a deterministic degraded-link repair of the
    identity ring (at world 2 there is exactly one ring, so the repair
    plan is the honest residual — still a valid permutation)."""
    return {
        "identity": tuple(range(world)),
        "swing": plan(world, "swing", mesh_for_world(world)).ring_order,
        "repaired": plan(world, "ring", avoid={(0, 1)}).ring_order,
    }


@pytest.mark.parametrize("world", [2, 4, 8])
def test_fused_parity_gate(world):
    """fused ≡ reference host fold, bitwise, across codecs × ops ×
    schedules at this world — including the rank-order fold under
    permuted (swing/repaired) rings and the replicated-output contract
    (run_local asserts rank agreement internally)."""
    n = 700  # partial last block + slice padding both exercised
    contribs = _contribs(world, n, seed=world)
    for sname, order in _schedules(world).items():
        for cname in CODECS:
            for op in (SUM, MAX):
                out = fused.run_local(contribs, op, cname, ring_order=order)
                ref = reference_allreduce(contribs, op, cname)
                assert np.array_equal(out, ref), (sname, cname, op)


def test_fused_chunk_knob_parity():
    """rabit_fused_chunk_kib splits hop payloads into multiple ppermutes;
    parity is chunk-size independent (bytes are split, never re-encoded)."""
    contribs = _contribs(4, 5000, seed=3)
    ref = reference_allreduce(contribs, SUM, "i8x2")
    for chunk in (64, 1024, 1 << 22):
        out = fused.run_local(contribs, SUM, "i8x2", chunk_bytes=chunk)
        assert np.array_equal(out, ref), chunk


def test_fused_replay_identical_after_rebuild():
    """An elastic resize recompiles the fused graph from scratch
    (rebuild_mesh clears the cache); the recompiled graph must reproduce
    the original delivery bit for bit — the replay contract every other
    engine path already honours."""
    contribs = _contribs(4, 1200, seed=7)
    first = fused.run_local(contribs, SUM, "i8")
    again = fused.run_local(contribs, SUM, "i8")  # fresh build, same inputs
    assert np.array_equal(first, again)


def test_xla_rebuild_mesh_clears_fused_cache():
    """ISSUE 11 satellite: rebuild_mesh must drop the fused-graph cache
    (and its baked ring order) alongside _jits/_cjits — the ppermute
    tables pin the OLD world's device set."""
    eng = XlaEngine(Config(["rabit_tracker_uri=NULL"]))
    eng._rank, eng._world = 0, 3
    eng._mesh = object()
    eng._jits[2] = lambda x: x
    eng._cjits[("k",)] = (None, None)
    eng._fjits[(SUM, "i8", 64)] = lambda x: x
    eng._fused_order = (0, 2, 1)
    eng.rebuild_mesh()
    assert eng._fjits == {} and eng._fused_order is None
    assert eng._jits == {} and eng._cjits == {}
    eng._fjits[(SUM, "i8", 64)] = lambda x: x
    eng.shutdown()
    assert eng._fjits == {}


def test_fused_world1_short_circuit():
    """ISSUE 11 satellite: a single-process job must not build the mesh or
    compile anything for a no-op collective — the host transport serves
    the solo codec round trip directly."""
    eng = XlaEngine(Config([]))
    eng._rank, eng._world = 0, 1

    def _boom():  # pragma: no cover — the assertion IS the test
        raise AssertionError("mesh/jit built for a world-1 collective")

    eng._proc_mesh = _boom
    x = (np.random.RandomState(0).randn(2000) * 4).astype(np.float32)
    out = eng.allreduce_compressed(x, SUM, get_codec("i8"))
    assert np.array_equal(out, reference_allreduce([x], SUM, "i8"))
    assert eng._fjits == {} and eng._cjits == {}


def test_fused_active_gating():
    """fused_active mirrors the allreduce_compressed routing: on under
    auto for worlds > 1 and device codecs, off for world 1, byte codecs,
    BITOR-ish ops, and rabit_fused_allreduce=0; non-XLA engines always
    answer False."""
    from rabit_tpu.engine.base import BITOR
    from rabit_tpu.engine.empty import SoloEngine

    eng = XlaEngine(Config([]))
    eng._rank, eng._world = 0, 4
    assert eng.fused_active(get_codec("i8"), SUM)
    assert eng.fused_active(get_codec("bf16x2"), MAX)
    assert not eng.fused_active(get_codec("zlib"), SUM)  # host-only codec
    assert not eng.fused_active(get_codec("i8"), BITOR)
    eng._world = 1
    assert not eng.fused_active(get_codec("i8"), SUM)
    off = XlaEngine(Config(["rabit_fused_allreduce=0"]))
    off._rank, off._world = 0, 4
    assert not off.fused_active(get_codec("i8"), SUM)
    assert not SoloEngine(Config([])).fused_active(get_codec("i8"), SUM)


def test_fused_policy_resolution():
    pol = compress.configure(Config(["rabit_fused_allreduce=0",
                                     "rabit_fused_chunk_kib=64"]))
    try:
        assert pol.fused == "0"
        assert pol.fused_chunk_kib == 64
        with pytest.raises(ValueError, match="rabit_fused_allreduce"):
            compress.configure(Config(["rabit_fused_allreduce=banana"]))
    finally:
        compress.reset()
    assert compress.policy().fused == "auto"
    assert fused.chunk_bytes_from_config(
        Config(["rabit_fused_chunk_kib=8"])) == 8192
    assert fused.fused_mode(Config([])) is True
    assert fused.fused_mode(Config(["rabit_fused_allreduce=off"])) is False


def test_plan_ring_order_follows_schedule_config():
    """The ppermute table IS the planner's ring order: swing config yields
    the serpentine cycle, ring/tree keep the identity layout, and the
    planner being pure means every process derives the same table."""
    swing = fused.plan_ring_order(8, Config(["rabit_schedule=swing"]))
    assert sorted(swing) == list(range(8))
    assert swing == plan(8, "swing", mesh_for_world(8)).ring_order
    ident = fused.plan_ring_order(8, Config(["rabit_schedule=ring"]))
    assert ident == tuple(range(8))
    assert fused.plan_ring_order(8, Config(["rabit_schedule=swing"])) == swing


def test_collective_events_carry_fused_identity():
    """ISSUE 11 satellite: fused collectives carry fused=1 in the
    op_begin/op_end identity; host-path ops stay unmarked; the trace
    merger's spans and Perfetto args keep the flag."""
    from rabit_tpu import obs
    from rabit_tpu.obs import trace as T

    rt.init([], rabit_compress_min_bytes=1)
    try:
        obs.get_recorder().clear()
        with obs.collective("allreduce", 64, cache_key="k", codec="i8",
                            fused=True):
            pass
        x = np.arange(600, dtype=np.float32)
        rt.allreduce(x, rt.SUM, codec="i8")  # solo engine: host path
        evs = [e for e in obs.get_recorder().snapshot()
               if e.kind in ("op_begin", "op_end")]
        fused_evs = [e for e in evs if e.fields.get("fused") == 1]
        host_evs = [e for e in evs if "fused" not in e.fields]
        assert len(fused_evs) == 2 and len(host_evs) == 2
        spans = T.pair_ops(evs)
        assert [s.fused for s in spans] == [True, False]
    finally:
        rt.finalize()


def test_compress_policy_event_records_fused_keys():
    from rabit_tpu import obs

    rt.init(["rabit_fused_allreduce=1", "rabit_fused_chunk_kib=128"])
    try:
        pol = [e for e in obs.get_recorder().snapshot()
               if e.kind == "compress_policy"]
        assert pol and pol[-1].fields["fused"] == "1"
        assert pol[-1].fields["fused_chunk_kib"] == 128
    finally:
        rt.finalize()


def test_fused_builder_input_validation():
    mesh = fused.local_mesh(2)
    c = get_codec("i8")
    with pytest.raises(ValueError, match="permutation"):
        fused.build_fused_allreduce(mesh, (0, 0), SUM, c, 64)
    with pytest.raises(ValueError, match="devices"):
        fused.build_fused_allreduce(mesh, (0, 1, 2), SUM, c, 64)
    with pytest.raises(ValueError, match="n >= 1"):
        fused.build_fused_allreduce(mesh, (0, 1), SUM, c, 0)
    with pytest.raises(ValueError, match="fused op"):
        fused.build_fused_allreduce(mesh, (0, 1), 99, c, 64)
    with pytest.raises(ValueError, match="wire layout"):
        fused.segment_widths(get_codec("zlib"))


@pytest.mark.slow
def test_fused_parity_sweep_slow():
    """The larger sweep: MIN joins the op set, identity codec joins (the
    builder supports it even though the policy never routes lossless
    codecs here), more sizes including n=1 (pure padding) and exact
    block multiples, plus sub-chunked hops at every world."""
    for world in (2, 3, 8):
        scheds = _schedules(world)
        for n in (1, 256, 700):
            contribs = _contribs(world, n, seed=world * 100 + n)
            for sname, order in scheds.items():
                for cname in ("identity",) + CODECS:
                    for op in (SUM, MAX, MIN):
                        out = fused.run_local(contribs, op, cname,
                                              ring_order=order,
                                              chunk_bytes=512)
                        ref = reference_allreduce(contribs, op, cname)
                        assert np.array_equal(out, ref), (
                            world, n, sname, cname, op)
