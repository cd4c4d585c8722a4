"""The durable store picks the codec a blob, from a probe of the blob.

``rabit_checkpoint_compress`` names the codec the store MAY apply;
``CheckpointStore._write`` deflates a 64 KiB sample of a large blob first
and writes the blob raw (codec id 0 in the frame's codec byte) where the
sample does not lose a quarter (rabit_tpu/store.py, doc/compression.md).
The frame generations, the crc, the two fsyncs a file and what reads back
stay what they were.
"""

from __future__ import annotations

import os
import zlib

import numpy as np
import pytest

from rabit_tpu import obs, store
from rabit_tpu.compress import get_codec
from rabit_tpu.store import CheckpointStore

PROBE = store._PROBE_BYTES


def noise(n: int, seed: int = 0) -> bytes:
    """float32 values with full mantissas: a dense margin's bytes."""
    return np.random.default_rng(seed).normal(size=n // 4).astype(
        np.float32).tobytes()


def random_bytes(n: int) -> bytes:
    return np.random.default_rng(7).bytes(n)


class Counting:
    """The zlib codec, with every ``encode_bytes`` call's input length."""

    def __init__(self):
        inner = get_codec("zlib")
        self.name, self.codec_id = inner.name, inner.codec_id
        self._inner = inner
        self.calls: list[int] = []

    def encode_bytes(self, blob):
        self.calls.append(len(blob))
        return self._inner.encode_bytes(blob)


def counting_store(tmp_path):
    s = CheckpointStore(str(tmp_path), 0)
    s._codec = Counting()
    return s


def frame(tmp_path, version: int, kind: str = "global") -> bytes:
    return (tmp_path / f"{kind}_r0_v{version}.bin").read_bytes()


def counters() -> tuple[int, int]:
    c = obs.get_registry().snapshot()["counters"]
    return (c.get("spill_frames_raw_total", 0),
            c.get("spill_frames_encoded_total", 0))


def encode_spans() -> list[dict]:
    return [e.fields for e in obs.get_recorder().snapshot()
            if e.kind == "span" and e.fields["name"] == "rabit.spill.encode"]


# blob, codec id expected in the frame, encode_bytes calls expected
BLOBS = [
    pytest.param(noise(1 << 20), 0, [PROBE], id="float32 noise, 1 MiB"),
    pytest.param(noise(10_500_000) + b"\x2e" * 139, 0, [PROBE],
                 id="float32 noise, 10.5 MB, odd length"),
    pytest.param(noise(PROBE) + b"x", 0, [PROBE],
                 id="noise one byte over the probe"),
    pytest.param(np.zeros(224_000, np.float32).tobytes(), 1, [PROBE, 896_000],
                 id="a sparse forest"),
    pytest.param(b"forest " * 40_000, 1, [PROBE, 280_000], id="text"),
    # at or under the probe's size: encoded whole, the smaller form kept
    pytest.param(noise(PROBE), 1, [PROBE],
                 id="float32 noise of the probe's size"),
    pytest.param(b"forest " * 512, 1, [3584], id="small and compressible"),
    pytest.param(noise(4096), 1, [4096], id="small float32 noise"),
    pytest.param(random_bytes(4096), 0, [4096], id="small random bytes"),
    pytest.param(b"", 0, [0], id="empty"),
]


@pytest.mark.parametrize("blob,codec_id,calls", BLOBS)
def test_codec_follows_the_blob(tmp_path, blob, codec_id, calls):
    """The frame's codec byte, what the codec was run over, the span's
    fields and the two counters, a blob; and every blob reads back
    byte-identical from a fresh store."""
    s = counting_store(tmp_path)
    raw0, enc0 = counters()
    s.save(1, blob, None)
    raw = frame(tmp_path, 1)
    magic, got_id, crc, n = store._HDR2.unpack_from(raw)
    payload = raw[store._HDR2.size:]
    assert magic == b"RTC2" and got_id == codec_id
    assert len(payload) == n and zlib.crc32(payload) == crc
    assert s._codec.calls == calls          # never the whole of a blob it leaves raw
    if codec_id == 0:
        assert payload == blob
    else:
        assert len(payload) < len(blob) and zlib.decompress(payload) == blob
    assert counters() == (raw0 + (codec_id == 0), enc0 + (codec_id != 0))
    sp = encode_spans()[-1]
    assert sp["codec"] == ("zlib" if codec_id else "identity")
    assert sp["raw"] == len(blob) and sp["encoded"] == n
    if len(blob) > PROBE:
        assert (sp["probe"] <= store._PROBE_MAX_RATIO) == bool(codec_id)
    fresh = CheckpointStore(str(tmp_path), 0)
    assert fresh.load_global(1) == blob and fresh.latest_valid() == 1


def test_small_blob_keeps_the_smaller_form(tmp_path):
    """At or under the probe's size the blob is encoded whole: the codec
    stays even where it saves less than a quarter, and goes only where it
    saves nothing."""
    s = counting_store(tmp_path)
    floats = noise(PROBE // 2)                       # deflates to about 0.93
    s.save(1, floats, None)
    s.save(2, random_bytes(PROBE // 2), None)
    assert frame(tmp_path, 1)[4] == 1
    assert store._PROBE_MAX_RATIO < encode_spans()[-2]["probe"] < 1
    assert len(frame(tmp_path, 1)) < len(floats)
    assert frame(tmp_path, 2)[4] == 0 and encode_spans()[-1]["probe"] > 1
    assert len(frame(tmp_path, 2)) == store._HDR2.size + PROBE // 2


def test_probe_sample_is_spread_and_cut_without_a_copy():
    """Sixteen 4 KiB slices, the first and the last bytes of the blob
    among them; a blob whose compressible part the sample must meet."""
    blob = bytes(range(256)) * 4096                       # 1 MiB
    sample = store._probe_sample(blob)
    assert len(sample) == PROBE
    assert sample[:4096] == blob[:4096] and sample[-4096:] == blob[-4096:]
    # zeros everywhere but a noisy first and last 4 KiB: a sample of the
    # two ends alone would call it noise
    mostly = noise(4096) + bytes((1 << 20) - 8192) + noise(4096, seed=1)
    ratio = len(zlib.compress(store._probe_sample(mostly), 1)) / PROBE
    assert ratio < 0.2


@pytest.mark.parametrize("blob", [noise(1 << 20), b"forest " * 40_000,
                                  b"forest " * 512], ids=["raw", "zlib", "small"])
def test_same_blob_gives_the_same_frame(tmp_path, blob):
    a = CheckpointStore(str(tmp_path / "a"), 0)
    b = CheckpointStore(str(tmp_path / "b"), 0)
    a.save(1, blob, blob)
    a.save(2, blob, None, epoch=4)
    b.save(1, blob, blob)
    b.save(2, blob, None, epoch=4)
    for name in ("global_r0_v1.bin", "local_r0_v1.bin", "global_r0_v2.bin"):
        assert (tmp_path / "a" / name).read_bytes() == \
            (tmp_path / "b" / name).read_bytes()


@pytest.mark.parametrize("blob,codec_id", [(noise(1 << 20), 0),
                                           (b"forest " * 40_000, 1)],
                         ids=["raw", "zlib"])
def test_rtc3_keeps_the_epoch(tmp_path, blob, codec_id):
    s = CheckpointStore(str(tmp_path), 0)
    s.save(2, blob, blob, epoch=3)
    raw = frame(tmp_path, 2)
    magic, got_id, crc, n, epoch = store._HDR3.unpack_from(raw)
    assert (magic, got_id, epoch) == (b"RTC3", codec_id, 3)
    assert zlib.crc32(raw[store._HDR3.size:]) == crc
    fresh = CheckpointStore(str(tmp_path), 0)
    assert fresh.epoch_of(2) == 3
    assert fresh.load_global(2) == blob and fresh.load_local(2) == blob


#: frames the parent commit (PR 25) wrote for this blob, byte for byte:
#: RTC1 (``rabit_checkpoint_compress=""``), RTC2 and RTC3 (epoch 7) under zlib
RECORDED_BLOB = b"tpurabit checkpoint " * 6 + bytes(range(16))
RECORDED = {
    "RTC1": "525443317a87fdba88000000" + RECORDED_BLOB.hex(),
    "RTC2": "5254433201000000d54aad882f00000078012b29282d4a4cca2c5148ce484d"
            "ce2ec8cfcc2b5128a18318032313330b2b1b3b072717370f2f1f3f001ae62f6b",
    "RTC3": "5254433301000000d54aad882f0000000700000078012b29282d4a4cca2c51"
            "48ce484dce2ec8cfcc2b5128a18318032313330b2b1b3b072717370f2f1f3f"
            "001ae62f6b",
}


@pytest.mark.parametrize("generation", sorted(RECORDED))
def test_frames_recorded_from_the_parent_still_load(tmp_path, generation):
    (tmp_path / "global_r0_v3.bin").write_bytes(
        bytes.fromhex(RECORDED[generation]))
    s = CheckpointStore(str(tmp_path), 0)
    assert s.latest_valid() == 3 and s.load_global(3) == RECORDED_BLOB
    assert s.epoch_of(3) == (7 if generation == "RTC3" else 0)


@pytest.mark.parametrize("generation,codec", [("RTC1", ""), ("RTC2", "zlib")])
def test_compressible_blob_is_framed_as_the_parent_framed_it(
        tmp_path, generation, codec):
    """What does compress lands byte-identical to the parent's frame."""
    CheckpointStore(str(tmp_path), 0, codec=codec).save(3, RECORDED_BLOB, None)
    assert frame(tmp_path, 3).hex() == RECORDED[generation]


@pytest.mark.parametrize("cut", ["header", "payload", "one byte short",
                                 "a flipped byte"])
def test_torn_raw_frame_reads_as_absent(tmp_path, cut):
    blob = noise(1 << 20)
    s = CheckpointStore(str(tmp_path), 0)
    s.save(1, b"forest " * 40_000, None)
    s.save(2, blob, None)
    path = tmp_path / "global_r0_v2.bin"
    raw = path.read_bytes()
    assert raw[4] == 0
    torn = {"header": raw[:7], "payload": raw[:len(raw) // 2],
            "one byte short": raw[:-1],
            "a flipped byte": raw[:5000] + bytes([raw[5000] ^ 1]) + raw[5001:]}
    path.write_bytes(torn[cut])
    fresh = CheckpointStore(str(tmp_path), 0)
    assert not fresh.has(2)
    assert fresh.latest_valid() == 1        # the resume degrades to the older one


@pytest.mark.parametrize("codec", ["zlib", "identity"])
def test_save_fsyncs_each_file_and_the_directory_before_it_returns(
        tmp_path, monkeypatch, codec):
    """Two fsyncs a file, in order: the frame's, before the rename, then
    the directory's; both files of a commit, raw and deflated alike."""
    synced = []
    real_fsync, real_replace = os.fsync, os.replace

    def fsync(fd):
        st = os.fstat(fd)
        synced.append(("dir" if os.path.isdir(f"/proc/self/fd/{fd}")
                       else "file", st.st_size))
        return real_fsync(fd)

    def replace(src, dst):
        synced.append(("rename", os.path.basename(dst)))
        return real_replace(src, dst)

    monkeypatch.setattr(os, "fsync", fsync)
    monkeypatch.setattr(os, "replace", replace)
    s = CheckpointStore(str(tmp_path), 0, codec=codec)
    forest, margin = b"forest " * 40_000, noise(1 << 20)
    s.save(1, forest, margin)
    kinds = [k for k, _ in synced]
    assert kinds == ["file", "rename", "dir"] * 2
    assert synced[0][1] == len(frame(tmp_path, 1))            # whole frame synced
    assert synced[3][1] == len(frame(tmp_path, 1, "local"))
    assert [v for k, v in synced if k == "rename"] == [
        "global_r0_v1.bin", "local_r0_v1.bin"]
    assert not list(tmp_path.glob("*.tmp"))


def test_api_checkpoint_spills_margin_raw_and_forest_deflated(tmp_path):
    """Through ``rabit_tpu.checkpoint``: a dense float margin lands raw, a
    sparse forest deflated, and a second life loads both."""
    import rabit_tpu as rt

    forest = {"trees": np.zeros((500, 127), np.float32)}
    margin = np.frombuffer(noise(1 << 20), np.float32)
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    try:
        rt.checkpoint(forest, margin)
    finally:
        rt.finalize()
    by_raw = {f["raw"]: f for f in encode_spans()[-2:]}
    g, l = sorted(by_raw.values(), key=lambda f: f["raw"])
    assert (g["codec"], l["codec"]) == ("zlib", "identity")
    assert g["probe"] < 0.1 and l["probe"] > 0.85
    assert g["encoded"] < g["raw"] // 20 and l["encoded"] == l["raw"]
    assert frame(tmp_path, 1)[4] == 1 and frame(tmp_path, 1, "local")[4] == 0
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    try:
        version, got_forest, got_margin = rt.load_checkpoint(with_local=True)
    finally:
        rt.finalize()
    assert version == 1
    assert np.array_equal(got_forest["trees"], forest["trees"])
    assert got_margin.tobytes() == margin.tobytes()


# -- a blob handed over in pieces (PR 30) -----------------------------------


def in_pieces(blob: bytes) -> list:
    """The blob cut where a frame's pieces would not be so unkind: a
    one-byte head, a cut inside the probe's first slice, an empty piece, a
    piece that ends one byte before the blob does; kinds mixed."""
    cuts = sorted({0, min(1, len(blob)), min(1000, len(blob)),
                   len(blob) // 3, max(len(blob) - 1, 0), len(blob)})
    cuts.insert(len(cuts) // 2, cuts[len(cuts) // 2])     # an empty piece
    kinds = (bytes, bytearray, memoryview,
             lambda b: np.frombuffer(b, np.uint8).data)   # as PickleBuffer.raw()
    return [kinds[i % 4](blob[a:b])
            for i, (a, b) in enumerate(zip(cuts, cuts[1:]))]


@pytest.mark.parametrize("blob,codec_id,calls", BLOBS)
def test_pieces_are_framed_as_their_joined_bytes(tmp_path, blob, codec_id,
                                                 calls):
    """The probe samples, the crc runs over and the write takes the pieces
    where they lie; the file is the one the joined bytes give — same codec
    byte, same crc, same payload — the codec never sees the whole of a blob
    it leaves raw, and the span's fields are the same."""
    whole = CheckpointStore(str(tmp_path / "whole"), 0)
    whole.save(1, blob, blob, epoch=2)
    want = encode_spans()[-1]
    s = counting_store(tmp_path / "pieces")
    s.save(1, in_pieces(blob), tuple(in_pieces(blob)), epoch=2)
    assert s._codec.calls == calls * 2
    got = encode_spans()[-1]
    assert {k: got[k] for k in ("raw", "encoded", "codec", "probe")} == \
        {k: want[k] for k in ("raw", "encoded", "codec", "probe")}
    for kind in ("global", "local"):
        raw = frame(tmp_path / "pieces", 1, kind)
        assert raw == frame(tmp_path / "whole", 1, kind)
        assert raw[4] == codec_id
    fresh = CheckpointStore(str(tmp_path / "pieces"), 0)
    assert fresh.load_global(1) == blob and fresh.load_local(1) == blob


def test_probe_sample_of_pieces_is_the_joined_blobs():
    blob = noise(300_000) + bytes(200_000) + noise(524_289, seed=2)
    assert store._probe_sample(in_pieces(blob)) == store._probe_sample(blob)
    many = [blob[a:a + 997] for a in range(0, len(blob), 997)]
    assert store._probe_sample(many) == store._probe_sample(blob)


def test_a_write_keeps_nothing_of_the_callers_memory(tmp_path):
    """The caller overwrites its array when `save` has returned: what reads
    back, in this life too, is what was written (the memo of verified reads
    holds no view of a caller's memory)."""
    s = CheckpointStore(str(tmp_path), 0)
    margin = np.frombuffer(noise(1 << 20), np.float32).copy()
    want = margin.tobytes()
    s.save(1, (b"head", margin.data), [b"head", margin.data])
    margin[:] = 0.0
    assert s.load_global(1) == s.load_local(1) == b"head" + want
    assert s.has(1) and s.latest_valid() == 1


def test_api_spill_is_the_frame_the_engine_holds(tmp_path):
    """Through ``rabit_tpu.checkpoint`` with the spill on: each file's
    payload, its crc and the codec chosen are what the frame's joined bytes
    give — the bytes the engine keeps for a peer."""
    import rabit_tpu as rt
    from rabit_tpu import api

    forest = (np.zeros(40_000, np.int32), np.zeros(40_000, np.float32))
    margin = np.frombuffer(noise(1 << 20), np.float32)
    rt.init(rabit_checkpoint_dir=str(tmp_path / "job"))
    try:
        rt.checkpoint(forest, margin)
        _v, gblob, lblob = api._engine.load_checkpoint()
    finally:
        rt.finalize()
    assert gblob[:4] == lblob[:4] == api._FRAME_MAGIC
    CheckpointStore(str(tmp_path / "joined"), 0).save(1, gblob, lblob)
    for kind, codec_id in (("global", 1), ("local", 0)):
        raw = frame(tmp_path / "job", 1, kind)
        assert raw == frame(tmp_path / "joined", 1, kind) and raw[4] == codec_id
        _magic, _id, crc, n = store._HDR2.unpack_from(raw)
        assert zlib.crc32(raw[store._HDR2.size:]) == crc
    assert frame(tmp_path / "job", 1, "local")[store._HDR2.size:] == lblob
