"""Solo-mode API tests — parity with the reference's zero-config behavior
(engine.cc:71-82: an uninitialized process acts as rank 0 of world 1) and the
guide examples (guide/basic.py, guide/broadcast.py)."""

import numpy as np
import pytest

import rabit_tpu as rt


def test_uninitialized_defaults_to_solo():
    assert rt.get_rank() == 0
    assert rt.get_world_size() == 1
    assert not rt.is_distributed()


def test_init_finalize_solo():
    rt.init([])
    assert rt.get_rank() == 0
    assert rt.get_world_size() == 1
    rt.finalize()


def test_double_init_warns():
    rt.init([])
    with pytest.warns(UserWarning):
        rt.init([])
    rt.finalize()


def test_allreduce_identity_solo():
    rt.init([])
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = rt.allreduce(x, rt.SUM)
    np.testing.assert_array_equal(out, x)
    assert out.shape == (3, 4)
    rt.finalize()


def test_allreduce_ops_and_dtypes():
    rt.init([])
    for dtype in ["int8", "uint8", "int32", "uint32", "int64", "uint64", "float32", "float64"]:
        x = np.arange(5, dtype=dtype)
        for op in [rt.MAX, rt.MIN, rt.SUM, rt.BITOR]:
            if op == rt.BITOR and np.dtype(dtype).kind == "f":
                continue
            out = rt.allreduce(x, op)
            np.testing.assert_array_equal(out, x)
    rt.finalize()


def test_allreduce_rejects_bad_input():
    rt.init([])
    with pytest.raises(TypeError):
        rt.allreduce([1, 2, 3], rt.SUM)
    with pytest.raises(TypeError):
        rt.allreduce(np.array(["a"]), rt.SUM)
    rt.finalize()


def test_allreduce_prepare_fun_called():
    rt.init([])
    x = np.zeros(4, dtype=np.float64)
    called = []

    def prep(arr):
        called.append(True)
        arr[:] = 7.0

    out = rt.allreduce(x, rt.SUM, prepare_fun=prep)
    assert called == [True]
    np.testing.assert_array_equal(out, np.full(4, 7.0))
    rt.finalize()


def test_broadcast_object_solo():
    rt.init([])
    obj = {"s": "hello", "v": [1, 2, 3]}
    assert rt.broadcast(obj, 0) == obj
    with pytest.raises(ValueError):
        rt.broadcast(None, 0)
    rt.finalize()


def test_allgather_solo():
    rt.init([])
    x = np.arange(6, dtype=np.int32).reshape(2, 3)
    out = rt.allgather(x)
    assert out.shape == (1, 2, 3)
    np.testing.assert_array_equal(out[0], x)
    rt.finalize()


def test_checkpoint_roundtrip():
    rt.init([])
    version, model = rt.load_checkpoint()
    assert version == 0 and model is None

    rt.checkpoint({"weights": [1.0, 2.0]})
    assert rt.version_number() == 1
    version, model = rt.load_checkpoint()
    assert version == 1
    assert model == {"weights": [1.0, 2.0]}

    rt.checkpoint({"weights": [3.0]}, local_model={"rank_state": 42})
    version, gmodel, lmodel = rt.load_checkpoint(with_local=True)
    assert version == 2
    assert gmodel == {"weights": [3.0]}
    assert lmodel == {"rank_state": 42}
    rt.finalize()


def test_lazy_checkpoint():
    rt.init([])
    model = {"w": 1}
    rt.lazy_checkpoint(model)
    assert rt.version_number() == 1
    model["w"] = 2  # mutating before load is visible — lazy contract
    version, got = rt.load_checkpoint()
    assert version == 1 and got == {"w": 2}
    rt.finalize()


def test_tracker_print_solo(capsys):
    rt.init([])
    rt.tracker_print("hello tracker")
    assert "hello tracker" in capsys.readouterr().out
    rt.finalize()


def test_config_layering():
    from rabit_tpu.config import Config, parse_unit

    cfg = Config(["rabit_reduce_ring_mincount=1", "rabit_debug=1"])
    assert cfg.get_int("rabit_reduce_ring_mincount") == 1
    assert cfg.get_bool("rabit_debug")
    assert cfg.get_size("rabit_reduce_buffer") == 256 << 20
    assert parse_unit("1K") == 1024
    assert parse_unit("2M") == 2 << 20
    assert parse_unit("512") == 512
    # Watchdog is armed by default since round 3 (1800s); rabit_timeout=0
    # disables it.
    assert cfg.timeout_sec == 1800
    cfg2 = Config(["rabit_timeout=1", "rabit_timeout_sec=300"])
    assert cfg2.timeout_sec == 300
    assert Config(["rabit_timeout=0"]).timeout_sec == 0


def test_config_env_layering(monkeypatch):
    from rabit_tpu.config import Config

    monkeypatch.setenv("DMLC_TRACKER_URI", "10.0.0.1")
    monkeypatch.setenv("DMLC_TASK_ID", "7")
    monkeypatch.setenv("RABIT_TPU_RABIT_DEBUG", "1")
    cfg = Config([])
    assert cfg.get("rabit_tracker_uri") == "10.0.0.1"
    assert cfg.get("rabit_task_id") == "7"
    assert cfg.get_bool("rabit_debug")
    # argv overrides env
    cfg = Config(["rabit_tracker_uri=NULL"])
    assert cfg.get("rabit_tracker_uri") == "NULL"


# -- checkpoint frames: a model pickled out of band (PR 30) -----------------
#
# `checkpoint` hands the engine (and the store) a frame in pieces — a head,
# the protocol-5 pickle, and each large buffer where the caller's array
# holds it (rabit_tpu/api.py, doc/guide.md "Checkpoint blobs") — so that a
# commit copies a model once.  What loads is what today's loads: equal,
# writable, and nobody else's memory.

import base64  # noqa: E402
import pickle  # noqa: E402
import tracemalloc  # noqa: E402

from rabit_tpu import api, obs  # noqa: E402
from rabit_tpu.engine.empty import SoloEngine  # noqa: E402

OOB = api._OOB_MIN_BYTES


def pickle_spans() -> list[dict]:
    return [e.fields for e in obs.get_recorder().snapshot()
            if e.kind == "span"
            and e.fields["name"] == "rabit.checkpoint.pickle"]


def gbdt_state(rows: int = 100_000):
    """A forest's three arrays and a margin, as the trainer commits them."""
    rng = np.random.default_rng(3)
    forest = (rng.integers(0, 28, 31_500).astype(np.int32),
              rng.integers(0, 256, 31_500).astype(np.int32),
              rng.normal(size=32_000).astype(np.float32))
    return forest, rng.normal(size=rows).astype(np.float32)


@pytest.mark.parametrize("engine", ["empty", "native", "xla"])
def test_frame_roundtrip_and_the_caller_may_overwrite(engine):
    """(forest, margin) through checkpoint / load_checkpoint: values and
    dtypes equal, the arrays writable and their own; the caller overwrites
    its arrays as soon as `checkpoint` returns and the committed model is
    what loads; the engine holds the frame, and most of it went out of band."""
    rt.init(rabit_engine=engine)
    forest, margin = gbdt_state()
    want_f, want_m = tuple(a.copy() for a in forest), margin.copy()
    rt.checkpoint(forest, margin)
    margin[:] = -1.0                      # the next round's margin, at once
    for a in forest:
        a[:] = 0
    sp = pickle_spans()[-1]
    assert sp["buffers"] == 4
    assert sp["nbytes_oob"] == sum(a.nbytes for a in want_f) + want_m.nbytes
    assert 0 < sp["nbytes"] - sp["nbytes_oob"] < 2000
    version, got_f, got_m = rt.load_checkpoint(with_local=True)
    assert version == 1 and isinstance(got_f, tuple) and len(got_f) == 3
    for got, want in zip((*got_f, got_m), (*want_f, want_m)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, want)
        assert got.flags.writeable
        got[0] = 7                        # and writing one touches no other
    assert not any(np.shares_memory(a, b) for a in (*got_f, got_m)
                   for b in (*got_f, got_m) if a is not b)
    _v, gblob, lblob = api._engine.load_checkpoint()
    assert bytes(gblob[:4]) == bytes(lblob[:4]) == api._FRAME_MAGIC
    rt.finalize()


def test_readonly_array_stays_readonly_and_counters_count():
    """`np.asarray` of a device array is read-only; so is what loads, as a
    plain pickle would have it.  The registry counts both kinds of bytes."""
    rt.init([])
    c0 = obs.get_registry().snapshot()["counters"]
    margin = np.arange(OOB, dtype=np.float32)
    margin.flags.writeable = False
    rt.checkpoint({"round": 1}, margin)
    _v, g, got = rt.load_checkpoint(with_local=True)
    assert g == {"round": 1} and not got.flags.writeable
    np.testing.assert_array_equal(got, margin)
    c1 = obs.get_registry().snapshot()["counters"]
    sp = pickle_spans()[-1]
    assert (c1["checkpoint_bytes_oob_total"]
            - c0.get("checkpoint_bytes_oob_total", 0)) == 4 * OOB == sp["nbytes_oob"]
    assert (c1["checkpoint_bytes_inband_total"]
            - c0.get("checkpoint_bytes_inband_total", 0)) == sp["nbytes"] - 4 * OOB
    rt.finalize()


class Plain:
    """An object with no buffers."""

    def __init__(self, k):
        self.k, self.name = k, "plain" * k

    def __eq__(self, other):
        return (self.k, self.name) == (other.k, other.name)


def small_arrays():
    return [np.full(200, i, np.float32) for i in range(300)]


MODELS = [
    # model, buffers expected out of band
    pytest.param(lambda: np.arange(2 * OOB, dtype=np.float64)[::2], 0,
                 id="a non-contiguous array (numpy pickles a copy in band)"),
    pytest.param(lambda: np.arange(OOB, dtype=np.int16).reshape(256, -1).T, 1,
                 id="a transposed (Fortran-contiguous) array"),
    pytest.param(lambda: Plain(3), 0, id="an object with no buffers"),
    pytest.param(small_arrays, 0, id="300 small arrays: all in band"),
    pytest.param(lambda: small_arrays() + [np.zeros(OOB, np.uint8)], 1,
                 id="300 small arrays and one of the constant's size"),
    pytest.param(lambda: np.zeros(OOB - 1, np.uint8), 0,
                 id="one byte under the constant"),
    pytest.param(lambda: {"a": bytearray(b"x" * OOB), "b": None}, 0,
                 id="a bytearray (the pickler gives it no buffer)"),
    pytest.param(lambda: np.zeros((0, 4), np.float32), 0, id="an empty array"),
]


@pytest.mark.parametrize("engine", ["empty", "native"])
@pytest.mark.parametrize("make,buffers", MODELS)
def test_what_goes_out_of_band(engine, make, buffers):
    """What the pickler finds in the model decides, not a key: a contiguous
    buffer of the constant's size or more goes out of band, everything else
    stays in the pickle; every model loads equal, as global and as local."""
    rt.init(rabit_engine=engine)
    model = make()
    rt.checkpoint(model, model)
    assert pickle_spans()[-1]["buffers"] == 2 * buffers
    _v, g, l = rt.load_checkpoint(with_local=True)
    for got in (g, l):
        if isinstance(model, np.ndarray):
            assert got.dtype == model.dtype
            np.testing.assert_array_equal(got, model)
        elif isinstance(model, list):
            assert len(got) == len(model)
            assert all(np.array_equal(a, b) for a, b in zip(got, model))
        else:
            assert got == model
    rt.finalize()


# What the parent of PR 30 committed for ({"iter": 3, "forest": (int32[6],
# float32[6])}, float64[5] of 3.0) with the spill on, written by its code
# (tests' records, as RECORDED_BLOB in test_store_codec.py): the engine's
# two blobs — the global one inside the wrapper that carried the base, the
# local one a plain pickle — and the two files of version 3 (RTC2, zlib).
OLD_GLOBAL = base64.b64decode(
    "gAWVIwEAAAAAAACME19fcmFiaXRfdHB1X2NrcHQxX1+USwBCAgEAAIAFlfcAAAAAAAAAfZQo"
    "jARpdGVylEsDjAZmb3Jlc3SUjBNudW1weS5fY29yZS5udW1lcmljlIwLX2Zyb21idWZmZXKU"
    "k5QolhgAAAAAAAAAAAAAAAEAAAACAAAAAwAAAAQAAAAFAAAAlIwFbnVtcHmUjAVkdHlwZZST"
    "lIwCaTSUiYiHlFKUKEsDjAE8lE5OTkr/////Sv////9LAHSUYksGhZSMAUOUdJRSlGgFKJYY"
    "AAAAAAAAAAAAAADNzEw+zczMPpqZGT/NzEw/AACAP5RoCYwCZjSUiYiHlFKUKEsDaA1OTk5K"
    "/////0r/////SwB0lGJLBoWUaBB0lFKUhpR1LpSHlC4=")
OLD_LOCAL = base64.b64decode(
    "gAWVnAAAAAAAAACME251bXB5Ll9jb3JlLm51bWVyaWOUjAtfZnJvbWJ1ZmZlcpSTlCiWKAAA"
    "AAAAAAAAAAAAAAAIQAAAAAAAAAhAAAAAAAAACEAAAAAAAAAIQAAAAAAAAAhAlIwFbnVtcHmU"
    "jAVkdHlwZZSTlIwCZjiUiYiHlFKUKEsDjAE8lE5OTkr/////Sv////9LAHSUYksFhZSMAUOU"
    "dJRSlC4=")
OLD_FILES = {
    "global_r0_v3.bin":
        "UlRDMgEAAAAW7J8l5gAAAHgBa2CdqszIAAY9wvHxRYlJmSXxJQWl8cnZBSWG8fFTvBmcmIAK"
        "GlinfocoY6idotHDklmSWjTFm7mHLS2/KLW4ZEqPcF5pbkGlXnwykK8HZKcWZSZP6eGOTyvK"
        "z00qTUsDKp88RWOaBNQQEAWylwmImYGYBYhZgXhKDyvYICCdUlJZkArU1MOUaTKls6N9StAU"
        "DaCNjDZT/Pz8vP4DAZjwZiiZkuTN1jqlh9F5SglQUQYrqjVnz/jYnT1zxm7WTEl7INse6Bn7"
        "KRmcPUxpSMZm8GI3NEMAZGTblFK9Ke1T9ABgr2fz",
    "local_r0_v3.bin":
        "UlRDMgEAAADIvAc8eAAAAHgBa2CdOocBAnqE80pzCyr14pPzi1L1gOzUoszkKT3c8WlF+blJ"
        "pWlpqUVTJk/RmKYBVQ+hOBwI0VN6WMEGA+mUksqCVKAhPUxpFlM6O9qnBE3R8GbuYbSZ4ufn"
        "5/UfCMCEN0PJlCRv1tYpPYzOU0qAivQAbcwyxA==",
}


def check_old_model(version, g, l):
    assert version == 3 and g["iter"] == 3
    np.testing.assert_array_equal(g["forest"][0], np.arange(6, dtype=np.int32))
    np.testing.assert_array_equal(
        g["forest"][1], np.linspace(0, 1, 6).astype(np.float32))
    assert l.dtype == np.float64 and l.tolist() == [3.0] * 5
    assert l.flags.writeable and g["forest"][0].flags.writeable


def test_plain_pickles_of_an_older_build_still_load(tmp_path):
    """A peer that runs the parent's build serves the wrapper and a plain
    pickle: both load, byte for byte as before (and a lazy checkpoint's
    blob is a plain pickle to this day)."""
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    for _ in range(3):
        api._engine.checkpoint(OLD_GLOBAL, OLD_LOCAL)
    check_old_model(*rt.load_checkpoint(with_local=True))
    rt.finalize()
    rt.init([])                                   # store off: nothing to unwrap
    api._engine.checkpoint(pickle.dumps({"w": 1}, protocol=2))
    assert rt.load_checkpoint() == (1, {"w": 1})
    rt.finalize()


def test_spill_written_by_the_parent_resumes(tmp_path):
    """A spill directory the parent's code wrote: a fresh job resumes from
    it, at its version, and goes on committing frames beside the old file."""
    for name, b64 in OLD_FILES.items():
        (tmp_path / name).write_bytes(base64.b64decode(b64))
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    version, g, l = rt.load_checkpoint(with_local=True)
    check_old_model(version, g, l)
    rt.checkpoint(g, l + 1)
    assert rt.version_number() == 4
    rt.finalize()
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    version, g, l = rt.load_checkpoint(with_local=True)
    assert version == 4 and l.tolist() == [4.0] * 5 and g["iter"] == 3
    rt.finalize()


def test_restarted_worker_reads_the_base_from_the_frame(tmp_path):
    """With the spill on the job's base version rides in the frame's head
    (no second pickle around the global blob): a worker whose process state
    is gone reads it from the blob its engine serves."""
    rt.init(rabit_checkpoint_dir=str(tmp_path))
    api._ckpt_base = 40                   # as after resuming version 40
    rt.checkpoint({"w": 41})
    api._ckpt_base = 0                    # a restarted process starts empty
    assert rt.load_checkpoint() == (41, {"w": 41})
    assert api._ckpt_base == 40
    rt.finalize()


class KeepsNothing(SoloEngine):
    """An engine double that reads every piece and keeps none."""

    def checkpoint(self, global_blob, local_blob=None):
        from rabit_tpu.engine.base import blob_pieces

        self.seen = sum(memoryview(p).nbytes
                        for b in (global_blob, local_blob) if b is not None
                        for p in blob_pieces(b))
        self._version += 1


@pytest.mark.parametrize("spill", [False, True], ids=["memory", "spilled"])
def test_a_commit_allocates_nothing_of_the_models_size(tmp_path, spill):
    """The property the frame is for, with no chip: inside `checkpoint` of a
    48 MB array Python allocates less than a quarter of it — the engine's
    copy is the only one, and the store probes, sums and writes the array
    where it lies (float noise: the probe leaves it raw)."""
    margin = np.random.default_rng(0).normal(size=12_000_000).astype(np.float32)
    rt.init(**({"rabit_checkpoint_dir": str(tmp_path)} if spill else {}))
    api._engine = KeepsNothing(api._engine.config)
    rt.checkpoint(("forest",), margin)            # warm: imports, the store's files
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        rt.checkpoint(("forest",), margin)
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert api._engine.seen > margin.nbytes
    assert peak < margin.nbytes // 4, peak
    if spill:
        assert (tmp_path / "local_r0_v2.bin").stat().st_size > margin.nbytes
    rt.finalize()
