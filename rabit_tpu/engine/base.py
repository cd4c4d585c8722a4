"""Engine interface — the backend seam.

Capability parity with the reference's ``IEngine`` pure-virtual interface
(``/root/reference/include/rabit/internal/engine.h:32-209``): every backend
(solo, XLA/ICI, native TCP, native robust, mock) implements this surface and
the public API dispatches to a process-wide singleton.  Unlike the reference,
backend selection happens at *run time* from config (``rabit_engine=...``),
not at link time.
"""

from __future__ import annotations

import socket as _socket
from abc import ABC, abstractmethod
from typing import Any, Callable

import numpy as np

from rabit_tpu.config import Config

# Reduction op enum — wire/ABI compatible with the reference
# (python/rabit.py:83-86, engine.h mpi::OpType).
MAX = 0
MIN = 1
SUM = 2
BITOR = 3

_NUMPY_OPS: dict[int, Callable[[np.ndarray, np.ndarray], np.ndarray]] = {
    MAX: np.maximum,
    MIN: np.minimum,
    SUM: np.add,
    BITOR: np.bitwise_or,
}

# dtype enum — ABI compatible with the reference C API
# (python/rabit.py:209-218, c_api.cc:36-120).
DTYPE_ENUM = {
    np.dtype("int8"): 0,
    np.dtype("uint8"): 1,
    np.dtype("int32"): 2,
    np.dtype("uint32"): 3,
    np.dtype("int64"): 4,
    np.dtype("uint64"): 5,
    np.dtype("float32"): 6,
    np.dtype("float64"): 7,
}


def blob_pieces(blob) -> tuple:
    """A checkpoint blob as its pieces.  A blob crosses the engine seam
    either whole (``bytes``, ``bytearray``, ``memoryview``) or as a sequence
    of such pieces that, read in order, are the blob: ``rabit_tpu.checkpoint``
    pickles out of band and hands over a small head plus the memory of the
    caller's arrays, so that the engine's copy is the only one a commit
    makes (doc/guide.md, "Checkpoint blobs")."""
    if isinstance(blob, (bytes, bytearray, memoryview)):
        return (blob,)
    return tuple(blob)


def join_blob(blob) -> bytes:
    """The blob as one ``bytes``: the one copy an engine that keeps its
    checkpoints as ``bytes`` makes of it."""
    return b"".join(blob_pieces(blob))


def numpy_reduce(op: int, dst: np.ndarray, src: np.ndarray) -> np.ndarray:
    """Apply a builtin reduction op elementwise (reference: op::Reducer,
    rabit-inl.h:95-102)."""
    if op not in _NUMPY_OPS:
        raise ValueError(f"unknown reduction op {op}")
    return _NUMPY_OPS[op](dst, src)


class Engine(ABC):
    """Backend interface.  All buffers at this layer are numpy arrays or raw
    bytes; the XLA engine additionally accepts jax arrays."""

    def __init__(self, config: Config):
        self.config = config

    def obs_event(self, kind: str, /, **fields):
        """Record a structured engine-layer event into the process flight
        recorder (rabit_tpu.obs), tagged with the backend class.  Lazy
        import: base must stay importable before the obs package."""
        from rabit_tpu import obs

        return obs.record_event(kind, engine=type(self).__name__, **fields)

    # -- lifecycle ---------------------------------------------------------

    def init(self) -> None:
        """Connect/bootstrap.  Called once by ``rabit_tpu.init``."""

    def shutdown(self) -> None:
        """Graceful teardown.  Called by ``rabit_tpu.finalize``."""

    def init_after_exception(self) -> None:
        """Recover engine state after the caller caught an exception
        (reference: IEngine::InitAfterException)."""
        raise RuntimeError(f"{type(self).__name__} cannot recover from exceptions")

    # -- topology ----------------------------------------------------------

    @abstractmethod
    def get_rank(self) -> int: ...

    @abstractmethod
    def get_world_size(self) -> int: ...

    def is_distributed(self) -> bool:
        return self.get_world_size() > 1

    def get_host(self) -> str:
        return _socket.gethostname()

    def get_ring_prev_rank(self) -> int:
        """Rank of the ring predecessor (reference: GetRingPrevRank)."""
        world = self.get_world_size()
        return (self.get_rank() + world - 1) % world

    # -- collectives -------------------------------------------------------

    @abstractmethod
    def allreduce(
        self,
        data: np.ndarray,
        op: int,
        prepare_fun: Callable[[np.ndarray], None] | None = None,
        cache_key: str | None = None,
    ) -> np.ndarray:
        """In-place-semantics allreduce: returns the reduced array (same
        shape/dtype as ``data``).  ``prepare_fun`` is the lazy initializer:
        it must be invoked on ``data`` right before the reduction unless the
        result is served from recovery/replay (reference semantics,
        rabit.h:182-206)."""

    @abstractmethod
    def broadcast(self, data: bytes | None, root: int, cache_key: str | None = None) -> bytes:
        """Broadcast a byte string from ``root`` to everyone."""

    @abstractmethod
    def allgather(
        self,
        data: np.ndarray,
        cache_key: str | None = None,
    ) -> np.ndarray:
        """Gather equal-sized per-rank slices into one array: input is this
        rank's slice, output is the concatenation over ranks (built on the
        reference's slice-addressed ring allgather, engine.h:56-79)."""

    def allreduce_compressed(
        self,
        data: np.ndarray,
        op: int,
        codec,
        prepare_fun: Callable[[np.ndarray], None] | None = None,
        cache_key: str | None = None,
    ) -> np.ndarray:
        """Allreduce with a wire codec (rabit_tpu.compress): each rank's
        contribution crosses the engine encoded; every rank decodes and
        folds the gathered planes identically, so the result is bitwise
        identical on all ranks and bitwise reproducible under replay.

        Default implementation: the numpy host transport over this
        engine's own primitives (encode -> one framed allgather, plus a
        tiny size-agreement allreduce when the deflate stage makes wire
        sizes data-dependent).  Backends with an in-graph path override
        this (engine/xla.py runs encode/decode on-device so the flush
        stays one fused device collective).

        Unlike the exact path, ``prepare_fun`` runs eagerly — its output
        feeds the encoder — which is always semantically safe (skipping it
        on replay is an optimization, not a contract)."""
        from rabit_tpu import compress as _compress

        if prepare_fun is not None:
            prepare_fun(data)
        return _compress.host_allreduce(
            self, np.ascontiguousarray(data), op, codec,
            cache_key=cache_key,
            deflate=_compress.policy().wire_deflate,
        )

    def fused_active(self, codec, op) -> bool:
        """True when ``allreduce_compressed(codec, op)`` will run as one
        fused in-graph device collective (engine/fused.py) rather than the
        host transport.  The obs layer stamps ``fused=1`` into the
        collective identity from this answer, so Perfetto traces and the
        straggler analytics can tell the two data planes apart.  Only the
        XLA engine overrides this; everywhere else the host path is the
        only compressed path (``rabit_fused_allreduce`` is off elsewhere
        by construction)."""
        return False

    # -- custom reduction --------------------------------------------------

    def allreduce_fn(
        self,
        data: np.ndarray,
        reduce_fn: Callable[[np.ndarray, np.ndarray], np.ndarray],
        prepare_fun: Callable[[np.ndarray], None] | None = None,
        cache_key: str | None = None,
    ) -> np.ndarray:
        """Allreduce with a user reduction function (reference: Reducer /
        SerializeReducer, rabit.h:352-456).  Default implementation: gather
        all slices and fold locally — backends may override with a tree
        reduction of serialized states."""
        if prepare_fun is not None:
            prepare_fun(data)
        flat = np.ascontiguousarray(data).reshape(-1)
        gathered = self.allgather(flat, cache_key=cache_key)
        world = self.get_world_size()
        parts = gathered.reshape(world, *data.shape)
        acc = np.array(parts[0], copy=True)
        for i in range(1, world):
            acc = reduce_fn(acc, parts[i])
        return acc.astype(data.dtype).reshape(data.shape)

    # -- checkpoint / recovery --------------------------------------------

    @abstractmethod
    def load_checkpoint(self) -> tuple[int, bytes | None, bytes | None]:
        """Return (version, global_blob, local_blob); version 0 means no
        checkpoint exists yet."""

    @abstractmethod
    def checkpoint(self, global_blob, local_blob=None) -> None:
        """Commit an iteration: store blobs, bump version.  Each blob is
        bytes-like or a sequence of bytes-like pieces (:func:`blob_pieces`).
        The engine copies it into storage of its own and, once this
        returns, holds no reference into the caller's memory: the caller
        may overwrite its arrays at once."""

    def lazy_checkpoint(self, get_global_blob: Callable[[], bytes]) -> None:
        """Defer serialization until a failure actually needs the blob
        (reference: LazyCheckPoint, rabit.h:311-332).  Default: eager."""
        self.checkpoint(get_global_blob())

    @abstractmethod
    def version_number(self) -> int: ...

    # -- observability -----------------------------------------------------

    def tracker_print(self, msg: str) -> None:
        print(msg, end="" if msg.endswith("\n") else "\n", flush=True)


class ShutdownSignal(Exception):
    """Raised internally when the tracker orders shutdown."""
