"""Solo (single-process) engine.

Capability parity with the reference's EmptyEngine
(``/root/reference/src/engine_empty.cc:17-91``): rank 0, world size 1, all
collectives are identities — so single-process programs run with zero
configuration.  Unlike the reference's EmptyEngine (which aborts on
checkpoint calls in base-only builds), the solo engine keeps an in-memory
versioned checkpoint so the full API is exercisable without a cluster,
matching the robust engine's world==1 fast path
(allreduce_robust.cc:253-256, :488-490).
"""

from __future__ import annotations

from typing import Callable

import numpy as np

from rabit_tpu.engine.base import Engine, join_blob


class SoloEngine(Engine):
    def __init__(self, config):
        super().__init__(config)
        self._version = 0
        self._global_blob: bytes | None = None
        self._local_blob: bytes | None = None

    def get_rank(self) -> int:
        return 0

    def get_world_size(self) -> int:
        return 1

    def is_distributed(self) -> bool:
        return False

    def allreduce(self, data, op, prepare_fun=None, cache_key=None):
        if prepare_fun is not None:
            prepare_fun(data)
        return data

    def allreduce_fn(self, data, reduce_fn, prepare_fun=None, cache_key=None):
        if prepare_fun is not None:
            prepare_fun(data)
        return data

    def broadcast(self, data, root, cache_key=None):
        if root != 0:
            raise ValueError(f"broadcast root {root} out of range for world size 1")
        if data is None:
            raise ValueError("root must pass data to broadcast")
        return data

    def allgather(self, data: np.ndarray, cache_key=None) -> np.ndarray:
        return data

    def load_checkpoint(self):
        if self._global_blob is None and getattr(self, "_lazy_thunk", None) is not None:
            self._global_blob = bytes(self._lazy_thunk())
        return self._version, self._global_blob, self._local_blob

    def checkpoint(self, global_blob, local_blob=None) -> None:
        self._global_blob = join_blob(global_blob)
        self._local_blob = None if local_blob is None else join_blob(local_blob)
        self._version += 1

    def lazy_checkpoint(self, get_global_blob: Callable[[], bytes]) -> None:
        # Solo mode has no peers to recover from; keep the thunk, bump the
        # version, and only serialize if someone later loads.  Lazy
        # checkpoints carry no local model (reference contract: LazyCheckPoint
        # takes only the global model, rabit.h:311-332).
        self._lazy_thunk = get_global_blob
        self._global_blob = None
        self._local_blob = None
        self._version += 1

    def version_number(self) -> int:
        return self._version
