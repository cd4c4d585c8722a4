"""Compressed collectives (rabit_tpu.compress): codec contract, transport,
policy, store frames, and the GBDT accuracy gate (ISSUE 5).

The codec contract under test (doc/compression.md): deterministic,
rank-symmetric encode; documented decode(encode(x)) error bounds; numpy
reference and in-graph JAX path produce the identical plane bytes; the
decoded delivery of a compressed collective is bitwise identical to the
closed-form reference fold on every rank and across replay (the replay
half lives in tests/test_fuzz_recover.py's compressed campaign)."""

from __future__ import annotations

import struct
import zlib

import numpy as np
import pytest

import rabit_tpu as rt
from rabit_tpu import compress
from rabit_tpu.compress import (
    BLOCK,
    CODECS,
    CodecMismatchError,
    get_codec,
    get_codec_by_id,
    reference_allreduce,
)
from rabit_tpu.compress import transport
from rabit_tpu.engine.base import BITOR, MAX, MIN, SUM

#: (codec, per-element bound fn(x, blockmax) -> abs tolerance)
_BOUNDS = {
    "bf16": lambda x, bm: 2.0 ** -8 * np.maximum(np.abs(x), 1e-30),
    "bf16x2": lambda x, bm: 2.0 ** -15 * np.maximum(np.abs(x), 1e-30),
    "i8": lambda x, bm: (0.5 / 127.0) * bm * 1.001,
    "i8x2": lambda x, bm: 2.0 ** -14 * bm * 1.001,
}


def _block_maxes(x: np.ndarray) -> np.ndarray:
    npad = -(-x.size // BLOCK) * BLOCK
    xp = np.zeros(npad, np.float32)
    xp[: x.size] = x
    return np.repeat(np.abs(xp.reshape(-1, BLOCK)).max(axis=1),
                     BLOCK)[: x.size]


@pytest.mark.parametrize("name", ["identity", "bf16", "bf16x2", "i8", "i8x2"])
@pytest.mark.parametrize("n", [1, 7, 256, 1000, 4096])
def test_codec_roundtrip_bounds(name, n):
    c = get_codec(name)
    x = (np.random.RandomState(n).randn(n) * 100).astype(np.float32)
    enc = c.encode(x)
    assert len(enc) == c.wire_len(n)
    assert enc == c.encode(x), "encode must be deterministic"
    dec = c.decode(enc, n)
    if name == "identity":
        assert np.array_equal(dec, x)
        return
    tol = _BOUNDS[name](x, _block_maxes(x))
    assert np.all(np.abs(dec - x) <= tol), (
        f"{name}: max err {np.abs(dec - x).max()} over documented bound")


@pytest.mark.parametrize("name", ["identity", "bf16", "bf16x2", "i8", "i8x2"])
def test_codec_jax_path_matches_numpy(name):
    """The in-graph path must produce the IDENTICAL plane bytes and the
    identical decode — the XLA engine's on-device fold and the numpy host
    transport are interchangeable per rank."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    c = get_codec(name)
    for n in (5, 256, 1000):
        x = (np.random.RandomState(n).randn(n) * 10).astype(np.float32)
        enc = c.encode(x)
        je = np.asarray(jax.jit(c.jax_encode)(jnp.asarray(x)))
        assert je.tobytes() == enc, f"{name}: jax encode differs at n={n}"
        jd = np.asarray(
            jax.jit(lambda p: c.jax_decode(p, n))(
                jnp.asarray(np.frombuffer(enc, np.uint8))))
        assert np.array_equal(jd, c.decode(enc, n)), (
            f"{name}: jax decode differs at n={n}")


def test_codec_nonfinite_saturates():
    for name in ("i8", "i8x2", "bf16", "bf16x2"):
        c = get_codec(name)
        x = np.array([np.nan, np.inf, -np.inf, 2.0, -3.0] + [1.0] * 300,
                     np.float32)
        dec = c.decode(c.encode(x), x.size)
        if name.startswith("i8"):
            assert np.all(np.isfinite(dec)), f"{name} leaked non-finite"


def test_zlib_byte_codec_and_registry():
    z = get_codec("zlib")
    blob = b"the quick brown fox " * 512
    assert z.decode_bytes(z.encode_bytes(blob)) == blob
    assert len(z.encode_bytes(blob)) < len(blob)
    # stable ids round-trip the registry
    for c in CODECS.values():
        assert get_codec_by_id(c.codec_id) is c
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("snappy")
    with pytest.raises(ValueError, match="unknown codec id"):
        get_codec_by_id(250)


def test_wire_frame_mismatch_detected():
    c8 = get_codec("i8x2")
    x = np.arange(300, dtype=np.float32)
    wire = transport.encode_wire(c8, x, deflate=True)
    # same bytes deframed as a different codec must fail loudly, not fold
    with pytest.raises(CodecMismatchError, match="disagree"):
        transport.decode_wire(get_codec("bf16"), wire, x.size, rank=3)
    # and the honest deframe round-trips through the deflate stage
    dec = transport.decode_wire(c8, wire, x.size, rank=0)
    assert np.array_equal(dec, c8.decode(c8.encode(x), x.size))


def test_policy_resolution_rules():
    from rabit_tpu.config import Config

    compress.configure(Config(["rabit_compress_allreduce=i8x2",
                               "rabit_compress_min_bytes=1024"]))
    try:
        f32, f64 = np.dtype(np.float32), np.dtype(np.float64)
        # policy applies: f32 SUM over the floor
        assert compress.resolve(None, f32, SUM, 4096).name == "i8x2"
        # floor: small payloads stay exact
        assert compress.resolve(None, f32, SUM, 512) is None
        # wrong dtype / BITOR fall through quietly under policy
        assert compress.resolve(None, f64, SUM, 4096) is None
        assert compress.resolve(None, f32, BITOR, 4096) is None
        # explicit codec wins over the floor
        assert compress.resolve("bf16", f32, MIN, 4).name == "bf16"
        # explicit identity forces the exact path
        assert compress.resolve("identity", f32, SUM, 4096) is None
        # explicit misuse is loud
        with pytest.raises(TypeError, match="float32"):
            compress.resolve("i8x2", f64, SUM, 4096)
        with pytest.raises(ValueError, match="BITOR"):
            compress.resolve("i8x2", f32, BITOR, 4096)
        with pytest.raises(ValueError, match="byte codec"):
            compress.resolve("zlib", f32, SUM, 4096)
    finally:
        compress.reset()


def test_configure_rejects_bad_names():
    from rabit_tpu.config import Config

    with pytest.raises(ValueError, match="unknown codec"):
        compress.configure(Config(["rabit_compress_allreduce=lz4"]))
    with pytest.raises(ValueError, match="lossy"):
        compress.configure(Config(["rabit_checkpoint_compress=i8"]))
    compress.reset()


def test_solo_allreduce_compressed_matches_reference():
    """World 1 still applies the codec round trip (encode -> gather ->
    decode), so solo runs see exactly the distributed wire's quantization
    and the metrics meter real wire bytes."""
    rt.init([], rabit_compress_min_bytes=1)
    try:
        x = (np.random.RandomState(0).randn(2000) * 40).astype(np.float32)
        for name in ("bf16", "bf16x2", "i8", "i8x2"):
            out = rt.allreduce(x, rt.SUM, codec=name)
            assert np.array_equal(out, reference_allreduce([x], rt.SUM, name))
        reg = rt.collective_stats().registry.snapshot()
        assert reg["counters"]["compress_raw_bytes_total"] > 0
        assert (reg["counters"]["compress_wire_bytes_total"]
                < reg["counters"]["compress_raw_bytes_total"])
        assert reg["histograms"]["compress_ratio_i8x2"]["count"] == 1
        assert "compress_encode_seconds_i8x2" in reg["histograms"]
    finally:
        rt.finalize()


def test_collective_events_carry_codec_identity():
    """The codec id joins the (version, seqno) collective identity in the
    flight recorder — the cross-rank mismatch detector's evidence."""
    from rabit_tpu import obs

    rt.init([], rabit_compress_min_bytes=1)
    try:
        obs.get_recorder().clear()
        x = np.arange(600, dtype=np.float32)
        rt.allreduce(x, rt.SUM, codec="i8x2")
        rt.allreduce(x, rt.SUM)
        evs = [e for e in obs.get_recorder().snapshot()
               if e.kind in ("op_begin", "op_end")]
        compressed = [e for e in evs if e.fields.get("codec") == "i8x2"]
        exact = [e for e in evs if "codec" not in e.fields]
        assert len(compressed) == 2  # begin + end of the compressed op
        assert len(exact) == 2       # the exact op's events stay unchanged
        assert compressed[0].fields["seqno"] != exact[0].fields["seqno"]
    finally:
        rt.finalize()


def test_compress_policy_event_recorded():
    from rabit_tpu import obs

    rt.init(["rabit_compress_allreduce=i8", "rabit_compress_min_bytes=64"])
    try:
        pol = [e for e in obs.get_recorder().snapshot()
               if e.kind == "compress_policy"]
        assert pol and pol[-1].fields["allreduce"] == "i8"
        assert pol[-1].fields["min_bytes"] == 64
        assert pol[-1].fields["checkpoint"] == "zlib"
    finally:
        rt.finalize()


def test_lazy_allreduce_codec_grouping():
    """Flush = one fused collective per (dtype, op, codec) group; the
    fused compressed buffer decodes exactly like the reference fold over
    the concatenation — two-plane codecs ride as planes of ONE buffer."""
    calls: list[tuple[int, int, str | None]] = []

    def spy(buf, op, codec=None):
        calls.append((buf.size, op, codec))
        from rabit_tpu import api

        return api.allreduce(buf, op, codec=codec)

    from rabit_tpu.fusion import LazyAllreduce

    rt.init([], rabit_compress_min_bytes=1)
    try:
        x = (np.random.RandomState(1).randn(900) * 30).astype(np.float32)
        lz = LazyAllreduce(spy)
        h1 = lz.add(x[:400], rt.SUM, codec="i8x2")
        h2 = lz.add(x[400:], rt.SUM, codec="i8x2")
        h3 = lz.add(np.arange(8, dtype=np.float32), rt.SUM)
        h4 = lz.add(np.arange(8, dtype=np.float32), rt.MAX, codec="bf16")
        lz.flush()
        assert calls == [(900, rt.SUM, "i8x2"), (8, rt.SUM, None),
                         (8, rt.MAX, "bf16")]
        fused = reference_allreduce([x], rt.SUM, "i8x2")
        got = np.concatenate([h1.get(), h2.get()])
        assert np.array_equal(got, fused)
        assert np.array_equal(h3.get(), np.arange(8, dtype=np.float32))
        assert np.array_equal(
            h4.get(), reference_allreduce(
                [np.arange(8, dtype=np.float32)], rt.MAX, "bf16"))
    finally:
        rt.finalize()


# -- durable store frames ----------------------------------------------------


def test_store_compressed_frame_roundtrip(tmp_path):
    from rabit_tpu.store import CheckpointStore

    s = CheckpointStore(str(tmp_path), 0)  # default codec: zlib
    blob = b"forest " * 4096
    s.save(5, blob, b"rank-local")
    on_disk = (tmp_path / "global_r0_v5.bin").read_bytes()
    assert on_disk[:4] == b"RTC2"
    assert len(on_disk) < len(blob), "frame did not compress"
    fresh = CheckpointStore(str(tmp_path), 0)
    assert fresh.load_global(5) == blob
    assert fresh.load_local(5) == b"rank-local"
    assert fresh.latest_valid() == 5


def test_store_torn_compressed_frame_rejected(tmp_path):
    from rabit_tpu.store import CheckpointStore

    s = CheckpointStore(str(tmp_path), 0)
    s.save(3, b"x" * 50000, None)
    path = tmp_path / "global_r0_v3.bin"
    raw = path.read_bytes()
    path.write_bytes(raw[: len(raw) // 2])  # torn mid-payload
    fresh = CheckpointStore(str(tmp_path), 0)
    assert not fresh.has(3)
    assert fresh.latest_valid() == 0
    # a flipped codec byte (header corruption the crc does not cover) must
    # also read as absent, not crash on a bogus decode
    s.save(4, b"y" * 1000, None)
    p4 = tmp_path / "global_r0_v4.bin"
    raw4 = bytearray(p4.read_bytes())
    raw4[4] = 200  # unknown codec id
    p4.write_bytes(bytes(raw4))
    assert not CheckpointStore(str(tmp_path), 0).has(4)


def test_store_legacy_rtc1_readback(tmp_path):
    """Frames written by pre-codec jobs (RTC1, no codec byte) must stay
    readable: a new job resumes an old job's spill unchanged."""
    from rabit_tpu.store import _HDR, _MAGIC, CheckpointStore

    legacy = b"old-job model"
    (tmp_path / "global_r0_v2.bin").write_bytes(
        _HDR.pack(_MAGIC, zlib.crc32(legacy), len(legacy)) + legacy)
    s = CheckpointStore(str(tmp_path), 0)
    assert s.has(2)
    assert s.load_global(2) == legacy
    # and an identity-codec store writes RTC1 exactly like the old code
    s_id = CheckpointStore(str(tmp_path), 1, codec="identity")
    s_id.save(2, legacy, None)
    raw = (tmp_path / "global_r1_v2.bin").read_bytes()
    assert raw[:4] == _MAGIC
    magic, crc, n = struct.unpack_from("<4sII", raw)
    assert raw[12:] == legacy and crc == zlib.crc32(legacy)


# -- the accuracy gate -------------------------------------------------------


def _higgs_shaped(n_rows, n_features, n_bins, seed=0):
    """A Higgs-shaped synthetic, scaled down."""
    rng = np.random.RandomState(seed)
    xb = rng.randint(0, n_bins, size=(n_rows, n_features), dtype=np.int32)
    logits = (xb[:, 0] > n_bins // 2).astype(np.float32) + 0.01 * xb[:, 1]
    y = (logits + rng.randn(n_rows) > 1.5).astype(np.float32)
    return xb.astype(np.float32), y


def test_gbdt_i8x2_matches_f32_within_bound():
    """The ISSUE 5 accuracy gate: GBDT on the Higgs-shaped synthetic with
    an i8x2 histogram allreduce must match the exact-f32 run within the
    2^-14 block-relative bound ops/boost.py documents — asserted directly
    on every level histogram of the first (identical-input) round, and
    end-to-end on eval accuracy."""
    from rabit_tpu.models.gbdt import GBDT

    X, y = _higgs_shaped(20000, 12, 64)
    rt.init([], rabit_compress_min_bytes=1)
    try:
        captured: list[tuple[np.ndarray, np.ndarray]] = []

        def hook_exact(hist):
            return rt.allreduce(np.asarray(hist), rt.SUM)

        def hook_i8x2(hist):
            a = np.asarray(hist)
            out = rt.allreduce(a, rt.SUM, codec="i8x2")
            captured.append((a, out))
            return out

        hyper = dict(n_trees=5, depth=4, n_bins=64, learning_rate=0.3)
        m_exact = GBDT(engine_allreduce=hook_exact, **hyper).fit(X, y)
        m_i8 = GBDT(engine_allreduce=hook_i8x2, **hyper).fit(X, y)

        # (a) every compressed histogram is within the documented bound of
        # the exact payload it encoded (world 1: the exact value IS the
        # input, so this checks the full wire round trip end to end)
        for raw, out in captured:
            flat = raw.reshape(-1)
            tol = 2.0 ** -14 * _block_maxes(flat) * 1.001
            assert np.all(np.abs(out.reshape(-1) - flat) <= tol)

        # (b) eval parity: the perturbation must not move evaluation
        # beyond noise (splits may tie-break differently; accuracy holds)
        acc_exact = float(np.mean(m_exact.predict(X) == y))
        acc_i8 = float(np.mean(m_i8.predict(X) == y))
        assert abs(acc_exact - acc_i8) <= 0.01, (acc_exact, acc_i8)

        # (c) the compressed run actually paid fewer wire bytes
        reg = rt.collective_stats().registry.snapshot()
        raw_b = reg["counters"]["compress_raw_bytes_total"]
        wire_b = reg["counters"]["compress_wire_bytes_total"]
        assert wire_b < raw_b
    finally:
        rt.finalize()
