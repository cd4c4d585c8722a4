"""ctypes bridge to the native C++ engine (libtpurabit.so).

Capability parity with the reference's Python binding loader
(/root/reference/python/rabit.py:47-74) — but instead of three separately
linked libraries (librabit / librabit_mock / librabit_mpi) one library hosts
all backends and ``rabit_engine=empty|base|robust|mock`` picks at init time.
The library is auto-built from native/ on first use.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
from pathlib import Path
from typing import Callable

import numpy as np

from rabit_tpu.engine.base import DTYPE_ENUM, Engine, blob_pieces

_NATIVE_DIR = Path(__file__).resolve().parents[2] / "native"
_LIB_PATH = _NATIVE_DIR / "libtpurabit.so"
_lib = None
_lib_lock = threading.Lock()

_PREPARE_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p)
_REDUCE_CB = ctypes.CFUNCTYPE(
    None, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p
)
_SERIALIZE_CB = ctypes.CFUNCTYPE(
    ctypes.c_int, ctypes.c_void_p,
    ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint64),
)


class _BlobPiece(ctypes.Structure):
    """``TrtBlobPiece`` of the C ABI: one piece of a checkpoint blob."""

    _fields_ = [("data", ctypes.c_void_p), ("len", ctypes.c_uint64)]


def _piece_array(blob):
    """``(TrtBlobPiece array, count)`` over the memory of ``blob``'s pieces,
    none of it copied; (None, 0) for no blob.  The array keeps the pieces
    alive as long as it lives itself: until the call that reads it returns."""
    if blob is None:
        return None, 0
    # frombuffer reads any bytes-like piece, read-only ones included, and
    # says where its memory lies
    views = [np.frombuffer(p, np.uint8) for p in blob_pieces(blob)]
    arr = (_BlobPiece * len(views))(
        *((v.ctypes.data, v.nbytes) for v in views))
    arr._views = views
    return arr, len(views)


def _build_lib() -> None:
    proc = subprocess.run(
        ["make", "-C", str(_NATIVE_DIR), "-j4"],
        capture_output=True,
        text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native library build failed:\n{proc.stdout}\n{proc.stderr}"
        )


def load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not _LIB_PATH.exists():
            _build_lib()
        lib = ctypes.CDLL(str(_LIB_PATH))
        lib.TrtGetLastError.restype = ctypes.c_char_p
        lib.RabitInit.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_char_p)]
        lib.RabitAllreduce.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            _PREPARE_CB, ctypes.c_void_p,
        ]
        lib.RabitAllreduceKeyed.argtypes = lib.RabitAllreduce.argtypes + [
            ctypes.c_char_p
        ]
        lib.RabitBroadcast.argtypes = [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int]
        lib.RabitBroadcastKeyed.argtypes = lib.RabitBroadcast.argtypes + [
            ctypes.c_char_p
        ]
        lib.RabitAllgather.argtypes = [ctypes.c_void_p] + [ctypes.c_uint64] * 4
        lib.RabitAllgatherKeyed.argtypes = [ctypes.c_void_p] + [
            ctypes.c_uint64
        ] * 3 + [ctypes.c_char_p]
        lib.RabitCheckPoint.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64
        ]
        lib.TrtCheckPointPieces.argtypes = [
            ctypes.POINTER(_BlobPiece), ctypes.c_uint64,
            ctypes.POINTER(_BlobPiece), ctypes.c_uint64,
        ]
        lib.RabitLazyCheckPoint.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.TrtLazyCheckPointFn.argtypes = [_SERIALIZE_CB, ctypes.c_void_p]
        lib.RabitLoadCheckPoint.argtypes = [
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.POINTER(ctypes.c_char)),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.TrtAllreduceCustom.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64,
            _REDUCE_CB, ctypes.c_void_p, _PREPARE_CB, ctypes.c_void_p,
            ctypes.c_char_p,
        ]
        lib.RabitTrackerPrint.argtypes = [ctypes.c_char_p]
        lib.RabitGetProcessorName.argtypes = [
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint64), ctypes.c_uint64
        ]
        _lib = lib
        return lib


class NativeError(RuntimeError):
    pass


class NativeEngine(Engine):
    """Engine backed by the native library (TCP tree/ring collectives,
    robust recovery, mock fault injection)."""

    def __init__(self, config, kind: str = "native"):
        super().__init__(config)
        self._kind = kind
        self._lib = load_lib()

    def _check(self, rc: int, what: str) -> None:
        if rc != 0:
            msg = self._lib.TrtGetLastError().decode()
            # Bridge-side evidence: the error (mock kill, socket failure,
            # recovery abort) lands in the flight recorder before the
            # exception unwinds Python — a subsequent hang/SIGTERM dump
            # then carries it.
            self.obs_event("engine_error", what=what, error=msg)
            raise NativeError(f"{what} failed: {msg}")

    # -- lifecycle ---------------------------------------------------------

    def init(self) -> None:
        cfg = dict(self.config.as_dict())
        if self._kind != "native":
            cfg["rabit_engine"] = self._kind
        args = [f"{k}={v}".encode() for k, v in cfg.items()]
        arr = (ctypes.c_char_p * len(args))(*args)
        self.obs_event("engine_init", backend=self._kind)
        t0 = time.time()
        try:
            self._check(self._lib.RabitInit(len(args), arr), "init")
        except NativeError as exc:
            # Fail-fast diagnosis: a dead tracker surfaces from the native
            # bootstrap as a connect failure after its bounded
            # rabit_connect_retry backoff loop (socket.cc Connect).  Name
            # the address and the budget so the operator sees "tracker
            # gone", not a bare errno.
            if "connect to" in str(exc):
                uri = self.config.get("rabit_tracker_uri", "NULL")
                port = self.config.get("rabit_tracker_port", "9091")
                retry = self.config.get_int("rabit_connect_retry", 5)
                raise NativeError(
                    f"{exc} — tracker at {uri}:{port} unreachable after "
                    f"{retry + 1} backed-off connect attempts "
                    f"(rabit_connect_retry={retry}); is the tracker "
                    f"running?"
                ) from exc
            raise
        # (Re)bootstrap complete: the assignment is live.  Restarted lives
        # see DMLC_NUM_ATTEMPT > 0 — the recorder then shows the reconnect
        # wave this rank came back through.  The seconds field closes the
        # engine_init -> bootstrap_done span the trace exporter draws.
        self.obs_event(
            "bootstrap_done",
            rank=self.get_rank(),
            world=self.get_world_size(),
            attempt=int(os.environ.get("DMLC_NUM_ATTEMPT", "0") or "0"),
            seconds=round(time.time() - t0, 6),
        )

    def shutdown(self) -> None:
        self.obs_event("engine_shutdown", backend=self._kind)
        self._check(self._lib.RabitFinalize(), "finalize")

    def init_after_exception(self) -> None:
        self.obs_event("init_after_exception", backend=self._kind)
        self._check(self._lib.RabitInitAfterException(), "init_after_exception")

    def rebootstrap(self) -> None:
        """Re-bootstrap after a world-epoch change (rabit_tpu.elastic):
        finalize the engine and check in again, adopting whatever
        assignment — rank, world size, topology — the tracker's current
        epoch hands out.  The native collective core keeps its fixed-world
        contract WITHIN a bootstrap; resizing happens by re-entering one.
        In-memory checkpoint replay state does not survive the finalize —
        callers re-feed state from the durable store (rabit_checkpoint_dir)
        or an application-level blob, exactly like a whole-job resume.
        Invoked through ``rabit_tpu.api.rebootstrap``."""
        self.obs_event("epoch_changed", backend=self._kind,
                       world=self.get_world_size())
        self._check(self._lib.RabitFinalize(), "finalize")
        self.init()

    # -- topology ----------------------------------------------------------

    def get_rank(self) -> int:
        return self._lib.RabitGetRank()

    def get_world_size(self) -> int:
        return self._lib.RabitGetWorldSize()

    def is_distributed(self) -> bool:
        return bool(self._lib.RabitIsDistributed())

    def get_ring_prev_rank(self) -> int:
        return self._lib.RabitGetRingPrevRank()

    def get_host(self) -> str:
        buf = ctypes.create_string_buffer(256)
        length = ctypes.c_uint64()
        self._check(
            self._lib.RabitGetProcessorName(buf, ctypes.byref(length), 256),
            "get_processor_name",
        )
        return buf.value.decode()

    def tracker_print(self, msg: str) -> None:
        self._check(self._lib.RabitTrackerPrint(msg.encode()), "tracker_print")

    # -- collectives -------------------------------------------------------

    def allreduce(self, data, op, prepare_fun=None, cache_key=None):
        buf = np.ascontiguousarray(data)
        cb = _PREPARE_CB()
        if prepare_fun is not None:
            cb = _PREPARE_CB(lambda _arg: prepare_fun(buf))
        rc = self._lib.RabitAllreduceKeyed(
            buf.ctypes.data_as(ctypes.c_void_p), buf.size,
            DTYPE_ENUM[buf.dtype], op, cb, None,
            (cache_key or "").encode(),
        )
        self._check(rc, "allreduce")
        return buf

    def allreduce_fn(self, data, reduce_fn, prepare_fun=None, cache_key=None):
        buf = np.ascontiguousarray(data)
        count = buf.size
        itemsize = buf.dtype.itemsize

        def c_reduce(dst, src, n, _ctx):
            d = np.ctypeslib.as_array(
                ctypes.cast(dst, ctypes.POINTER(ctypes.c_uint8)), shape=(n * itemsize,)
            ).view(buf.dtype)
            s = np.ctypeslib.as_array(
                ctypes.cast(src, ctypes.POINTER(ctypes.c_uint8)), shape=(n * itemsize,)
            ).view(buf.dtype)
            d[...] = reduce_fn(d.copy(), s)

        rcb = _REDUCE_CB(c_reduce)
        pcb = _PREPARE_CB()
        if prepare_fun is not None:
            pcb = _PREPARE_CB(lambda _arg: prepare_fun(buf))
        rc = self._lib.TrtAllreduceCustom(
            buf.ctypes.data_as(ctypes.c_void_p), itemsize, count,
            rcb, None, pcb, None, (cache_key or "").encode(),
        )
        self._check(rc, "allreduce_custom")
        return buf

    def broadcast(self, data, root, cache_key=None):
        rank = self.get_rank()
        key = (cache_key or "").encode()
        # two-phase: length then payload (reference python/rabit.py:171-206)
        length = np.array([len(data) if rank == root and data is not None else 0],
                          np.uint64)
        self._check(
            self._lib.RabitBroadcastKeyed(
                length.ctypes.data_as(ctypes.c_void_p), 8, root, key
            ),
            "broadcast",
        )
        n = int(length[0])
        buf = np.zeros(n, np.uint8)
        if rank == root:
            buf[:] = np.frombuffer(data, np.uint8)
        if n > 0:
            self._check(
                self._lib.RabitBroadcastKeyed(
                    buf.ctypes.data_as(ctypes.c_void_p), n, root, key
                ),
                "broadcast",
            )
        return buf.tobytes()

    def allgather(self, data, cache_key=None):
        flat = np.ascontiguousarray(data).reshape(-1)
        world = self.get_world_size()
        rank = self.get_rank()
        nbytes = flat.nbytes
        out = np.zeros(world * flat.size, flat.dtype)
        out[rank * flat.size:(rank + 1) * flat.size] = flat
        self._check(
            self._lib.RabitAllgatherKeyed(
                out.ctypes.data_as(ctypes.c_void_p), out.nbytes,
                rank * nbytes, (rank + 1) * nbytes,
                (cache_key or "").encode(),
            ),
            "allgather",
        )
        return out

    # -- checkpointing -----------------------------------------------------

    def load_checkpoint(self):
        gptr = ctypes.POINTER(ctypes.c_char)()
        lptr = ctypes.POINTER(ctypes.c_char)()
        glen = ctypes.c_uint64()
        llen = ctypes.c_uint64()
        version = self._lib.RabitLoadCheckPoint(
            ctypes.byref(gptr), ctypes.byref(glen),
            ctypes.byref(lptr), ctypes.byref(llen),
        )
        if version < 0:
            raise NativeError(
                f"load_checkpoint failed: {self._lib.TrtGetLastError().decode()}"
            )
        if version == 0:
            return 0, None, None
        gblob = ctypes.string_at(gptr, glen.value) if glen.value else None
        lblob = ctypes.string_at(lptr, llen.value) if llen.value else None
        # Recovery phase evidence at the bridge: a version > 0 load means
        # this life's state was served by peers (the robust engine's
        # recover_stats print carries the protocol counters; the tracker
        # converts that line into a structured event — see
        # rabit_tpu.obs.events.event_from_stats_line).
        self.obs_event(
            "checkpoint_loaded", version=version,
            global_bytes=glen.value, local_bytes=llen.value,
        )
        return version, gblob, lblob

    def checkpoint(self, global_blob, local_blob=None):
        # The gather entry: the engine's copy into its own strings reads
        # the caller's memory piece by piece and is the only copy made;
        # it holds nothing of the caller's once the call has returned.
        garr, ng = _piece_array(global_blob)
        larr, nl = _piece_array(local_blob)
        self._check(self._lib.TrtCheckPointPieces(garr, ng, larr, nl),
                    "checkpoint")
        self.obs_event("version_bump", version=self.version_number())

    def lazy_checkpoint(self, get_global_blob: Callable[[], bytes]) -> None:
        # True lazy across the ABI (reference global_lazycheck,
        # allreduce_robust.cc:527-535): register a serialize-on-demand
        # callback, so pickling only happens if a failure actually needs the
        # blob.  Caller contract (same as the reference's, rabit.h:311-332):
        # the model behind get_global_blob must stay unchanged until the
        # next checkpoint — the callback can fire any time in that window,
        # including while the NEXT checkpoint's pre-commit consensus still
        # serves this version to a recovering peer.
        def _serialize(ctx, out_data, out_len):
            try:
                self._lazy_blob = get_global_blob()
                out_data[0] = self._lazy_blob
                out_len[0] = len(self._lazy_blob)
                return 0
            except Exception:
                return -1

        cb = _SERIALIZE_CB(_serialize)
        # Every callback the engine might still reference must stay alive:
        # the previous one until this registration has definitely replaced
        # it inside the engine — and both if the call fails partway.
        self._lazy_keepalive = getattr(self, "_lazy_keepalive", []) + [cb]
        self._check(self._lib.TrtLazyCheckPointFn(cb, None), "lazy_checkpoint")
        self._lazy_keepalive = [cb]

    def version_number(self):
        return self._lib.RabitVersionNumber()
