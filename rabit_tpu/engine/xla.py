"""XLA engine — cross-host collectives through JAX.

The engine-level (host numpy) API for multi-host TPU jobs launched with
``jax.distributed``: rank = process index, world = process count, and the
collectives ride XLA's DCN/ICI transport instead of the reference's
hand-rolled TCP loops.  This is the third backend the reference's engine
seam anticipated (engine_mpi.cc:20-101 as the proof the seam is swappable;
BASELINE.json north star).

The reduction itself runs ON DEVICE: each process contributes its array as
one shard of a global array laid out over a one-device-per-process mesh, and
a jitted reduction over the sharded axis with a replicated out-sharding
makes XLA emit the cross-host AllReduce (O(log W) / ring, XLA's choice) —
no allgather-then-host-fold.  Jit caching specializes per (shape, dtype)
automatically; one compiled executable per (op, shape, dtype) is reused for
the life of the process.

In-graph device collectives for SPMD programs live in ``rabit_tpu.parallel``;
this engine is the host-side control surface with the same semantics as the
other backends.
"""

from __future__ import annotations

import os
from typing import Callable

import numpy as np

from rabit_tpu.engine.base import (
    BITOR, MAX, MIN, SUM, Engine, join_blob, numpy_reduce,
)


class XlaEngine(Engine):
    def __init__(self, config):
        super().__init__(config)
        self._version = 0
        self._global_blob: bytes | None = None
        self._local_blob: bytes | None = None
        self._lazy_thunk: Callable[[], bytes] | None = None
        self._mesh = None
        self._jits: dict[int, Callable] = {}
        # compiled (encode, decode+fold) pairs of the compressed path,
        # per (op, codec, element count)
        self._cjits: dict[tuple, tuple[Callable, Callable]] = {}
        # compiled fused encode->ppermute->decode-fold graphs
        # (engine/fused.py), per (op, codec, element count)
        self._fjits: dict[tuple, Callable] = {}
        self._fused_order: tuple[int, ...] | None = None
        # rabit_fused_allreduce, resolved lazily at the first compressed
        # collective (None = not resolved yet)
        self._fused_on: bool | None = None

    def init(self) -> None:
        import jax

        # Multi-process bootstrap: honour the standard JAX cluster env vars
        # (as exported by tests/test_xla_engine.py or a real multi-host
        # launcher).  Config keys override env so a launcher can pass them
        # as argv k=v pairs.  Must run before any other jax call touches
        # the backend.
        # `or` fallback (not a .get default): the keys are declared in
        # config.DEFAULTS with empty sentinels, so a plain default arg
        # would never fire and the env vars would be shadowed.
        coord = (self.config.get("rabit_xla_coordinator", "")
                 or os.environ.get("JAX_COORDINATOR_ADDRESS", ""))
        nproc = int(
            self.config.get("rabit_xla_num_processes", "")
            or os.environ.get("JAX_NUM_PROCESSES", "0") or "0"
        )
        pid = (self.config.get("rabit_xla_process_id", "")
               or os.environ.get("JAX_PROCESS_ID", ""))
        any_set = bool(coord) or nproc > 0 or pid != ""
        all_set = bool(coord) and nproc > 0 and pid != ""
        if any_set and not all_set:
            # Half-set cluster config must fail loudly: silently skipping
            # initialize would leave this worker at world 1 computing local
            # results while its peers block waiting for it.
            raise RuntimeError(
                "incomplete jax.distributed settings: coordinator="
                f"{coord!r} num_processes={nproc} process_id={pid!r} — set "
                "all of JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / "
                "JAX_PROCESS_ID (or the rabit_xla_* config keys), or none"
            )
        if all_set and nproc > 1:
            try:
                jax.distributed.initialize(coord, nproc, int(pid))
            except RuntimeError as exc:
                # Only double-initialization (the application bootstrapped
                # jax.distributed itself) is benign — jax 0.9 phrases it
                # "distributed.initialize should only be called once."; a
                # dead coordinator or world mismatch must fail loudly, not
                # degrade to world 1.
                msg = str(exc).lower()
                if "only be called once" not in msg and "already initialized" not in msg:
                    raise
        self._rank = jax.process_index()
        self._world = jax.process_count()

    def shutdown(self) -> None:
        self._mesh = None
        self._jits.clear()
        self._cjits.clear()
        self._fjits.clear()
        self._fused_order = None

    def rebuild_mesh(self) -> None:
        """Adopt a resized world (rabit_tpu.elastic): drop every compiled
        artifact pinned to the old process mesh — the one-device-per-
        process Mesh, the jitted reduce fns, the compressed-path pairs —
        and re-read the process topology, so the next collective lowers
        against the current world.  Invoked through
        ``rabit_tpu.api.rebootstrap``."""
        import jax

        from rabit_tpu.parallel.mesh import resize_ring

        old_world = max(getattr(self, "_world", 1), 1)
        self._mesh = None
        self._jits.clear()
        self._cjits.clear()
        # the fused graphs bake the OLD world's ring order and device set
        # into their ppermute tables — stale after a resize
        self._fjits.clear()
        self._fused_order = None
        self._rank = jax.process_index()
        self._world = jax.process_count()
        delta = resize_ring(old_world, max(self._world, 1))
        self.obs_event("epoch_changed", world=self._world,
                       links_added=len(delta["added"]),
                       links_removed=len(delta["removed"]))

    def get_rank(self) -> int:
        return getattr(self, "_rank", 0)

    def get_world_size(self) -> int:
        return getattr(self, "_world", 1)

    # -- device-side reduction --------------------------------------------

    def _proc_mesh(self):
        """A 1-D mesh with exactly one device per process, ordered by
        process index — the engine's 'one shard per worker' data layout."""
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            per_proc: dict[int, object] = {}
            for d in jax.devices():
                if d.process_index not in per_proc or d.id < per_proc[d.process_index].id:
                    per_proc[d.process_index] = d
            devs = [per_proc[p] for p in sorted(per_proc)]
            if len(devs) != self._world:
                raise RuntimeError(
                    f"expected one device per process, got {len(devs)} for "
                    f"world {self._world}"
                )
            self._mesh = Mesh(np.array(devs), ("p",))
        return self._mesh

    def _reduce_fn(self, op: int):
        """Jitted reduce-over-shard-axis with replicated output: XLA lowers
        this to one cross-process AllReduce on the device interconnect."""
        if op not in self._jits:
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self._proc_mesh()

            if op == SUM:
                red = lambda x: jnp.sum(x, axis=0)
            elif op == MAX:
                red = lambda x: jnp.max(x, axis=0)
            elif op == MIN:
                red = lambda x: jnp.min(x, axis=0)
            elif op == BITOR:
                # Cross-process reduce computations are restricted to
                # sum/min/max on some backends (CPU Gloo rejects reduce-or),
                # so OR is lowered to per-bit-plane MAX: expand to bits,
                # max across processes, recombine (disjoint planes sum back
                # exactly) — same trick as parallel/collectives.py's BITOR.
                def red(x):
                    dt = x.dtype
                    nbits = dt.itemsize * 8
                    wide = jnp.uint64 if nbits > 32 else jnp.uint32
                    xu = x.astype(wide)
                    if nbits < 64:
                        xu = xu & np.array((1 << nbits) - 1, wide)
                    shifts = jnp.arange(nbits, dtype=wide)
                    bits = (xu[..., None] >> shifts) & np.array(1, wide)
                    planes = jnp.max(bits, axis=0)
                    return jnp.sum(planes << shifts, axis=-1, dtype=wide).astype(dt)
            else:
                raise ValueError(f"unknown reduction op {op}")
            self._jits[op] = jax.jit(
                red, out_shardings=NamedSharding(mesh, P())
            )
        return self._jits[op]

    def allreduce(self, data, op, prepare_fun=None, cache_key=None):
        if prepare_fun is not None:
            prepare_fun(data)
        if self.get_world_size() == 1:
            return data
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        arr = np.ascontiguousarray(data)
        if arr.dtype.itemsize == 8:
            # Under JAX's default 32-bit mode device_put canonicalizes
            # int64/float64 down to 32 bits — silent truncation.  64-bit
            # payloads take a bit-exact host path instead: ship the raw
            # bytes (uint8 survives canonicalization) and fold on host.
            gathered = self.allgather(arr.view(np.uint8).reshape(-1))
            parts = gathered.reshape(self._world, -1).view(arr.dtype)
            acc = np.array(parts[0], copy=True)
            for i in range(1, self._world):
                acc = numpy_reduce(op, acc, parts[i])
            return acc.reshape(arr.shape)
        mesh = self._proc_mesh()
        sharding = NamedSharding(mesh, P("p", *([None] * arr.ndim)))
        local = jax.device_put(arr[None], mesh.devices[self._rank])
        garr = jax.make_array_from_single_device_arrays(
            (self._world,) + arr.shape, sharding, [local]
        )
        out = self._reduce_fn(op)(garr)
        return np.asarray(out.addressable_data(0)).astype(arr.dtype)

    # -- compressed allreduce (in-graph) -----------------------------------

    def _compressed_fns(self, op: int, codec, n: int):
        """Jitted on-device (encode, decode+fold) pair.  The fold takes the
        process-sharded uint8 plane array and reduces the decoded shards
        with a replicated out-sharding, so XLA ships the ENCODED planes
        across DCN/ICI — one fused device collective per call — and every
        rank computes the identical replicated result."""
        key = (op, codec.name, n)
        if key not in self._cjits:
            import jax
            import jax.numpy as jnp
            from jax.sharding import NamedSharding, PartitionSpec as P

            mesh = self._proc_mesh()
            if op == SUM:
                red = lambda p: jnp.sum(p, axis=0)
            elif op == MAX:
                red = lambda p: jnp.max(p, axis=0)
            elif op == MIN:
                red = lambda p: jnp.min(p, axis=0)
            else:  # pragma: no cover — resolve() never routes BITOR here
                raise ValueError(f"unsupported compressed op {op}")

            def fold(g):
                return red(jax.vmap(lambda row: codec.jax_decode(row, n))(g))

            self._cjits[key] = (
                jax.jit(codec.jax_encode),
                jax.jit(fold, out_shardings=NamedSharding(mesh, P())),
            )
        return self._cjits[key]

    def fused_active(self, codec, op) -> bool:
        """True when :meth:`allreduce_compressed` will take the fused
        in-graph ppermute path for this (codec, op) — the obs layer stamps
        ``fused=1`` into the collective identity from this answer."""
        if self._fused_on is None:
            from rabit_tpu.engine.fused import fused_mode

            self._fused_on = fused_mode(self.config)
        return (self._fused_on and self.get_world_size() > 1
                and codec.has_jax and op in (SUM, MAX, MIN))

    def _fused_fn(self, op: int, codec, n: int):
        """Jitted fused encode→ppermute→decode-fold graph over the process
        mesh (engine/fused.py), the ppermute table taken from the PR 7
        planned ring order for this world."""
        key = (op, codec.name, n)
        if key not in self._fjits:
            from rabit_tpu.engine import fused as _fused

            mesh = self._proc_mesh()
            if self._fused_order is None:
                self._fused_order = _fused.plan_ring_order(
                    self._world, self.config)
            self._fjits[key] = _fused.build_fused_allreduce(
                mesh, self._fused_order, op, codec, n,
                chunk_bytes=_fused.chunk_bytes_from_config(self.config))
        return self._fjits[key]

    def allreduce_compressed(self, data, op, codec, prepare_fun=None,
                             cache_key=None):
        """On-device quantized allreduce.  Default (rabit_fused_allreduce
        auto/on): the fully fused path — ONE jitted graph runs encode, a
        chunked ppermute ring in the planned schedule order (reduce-scatter
        + allgather phases, hops carry quantized planes), and the
        rank-order decode-fold, bitwise identical to the host reference
        fold.  rabit_fused_allreduce=0 keeps the pre-fusion shape: jitted
        on-device encode + one XLA-chosen collective over packed planes +
        jitted decode-fold.  Falls back to the numpy host transport for
        solo worlds (no mesh/jit is ever built for a no-op collective),
        host-only codecs, and ops the device fold does not cover."""
        if prepare_fun is not None:
            prepare_fun(data)
        arr = np.ascontiguousarray(data)
        if (self.get_world_size() == 1 or not codec.has_jax
                or arr.dtype != np.float32 or op not in (SUM, MAX, MIN)):
            return super().allreduce_compressed(arr, op, codec,
                                                cache_key=cache_key)
        import jax
        import time as _time

        from rabit_tpu import compress as _compress
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = arr.size
        mesh = self._proc_mesh()
        if self.fused_active(codec, op):
            fn = self._fused_fn(op, codec, n)
            t0 = _time.perf_counter()
            sharding = NamedSharding(mesh, P("p", None))
            local = jax.device_put(arr.reshape(1, -1),
                                   mesh.devices[self._rank])
            garr = jax.make_array_from_single_device_arrays(
                (self._world, n), sharding, [local]
            )
            out = fn(garr)
            result = np.asarray(out.addressable_data(0)).reshape(arr.shape)
            # wire accounting: the ring moves (W-1)/W encoded chunk sets
            # per phase; meter the canonical per-rank encoded size so the
            # codec ratios stay comparable with the host path's meter
            _compress.observe(codec.name, raw=arr.nbytes,
                              wire=codec.wire_len(n),
                              encode_s=_time.perf_counter() - t0,
                              fused=True)
            return result
        encode, fold = self._compressed_fns(op, codec, n)
        t0 = _time.perf_counter()
        packed = encode(arr.reshape(-1))  # on the local device
        wire_len = codec.wire_len(n)
        sharding = NamedSharding(mesh, P("p", None))
        local = jax.device_put(packed[None], mesh.devices[self._rank])
        garr = jax.make_array_from_single_device_arrays(
            (self._world, wire_len), sharding, [local]
        )
        out = fold(garr)
        result = np.asarray(out.addressable_data(0)).reshape(arr.shape)
        _compress.observe(codec.name, raw=arr.nbytes, wire=wire_len,
                          encode_s=_time.perf_counter() - t0)
        return result

    def broadcast(self, data, root, cache_key=None):
        if self.get_world_size() == 1:
            if root != 0:
                raise ValueError(f"broadcast root {root} out of range")
            if data is None:
                raise ValueError("root must pass data to broadcast")
            return data
        from jax.experimental import multihost_utils as mhu

        is_root = self.get_rank() == root
        # Two-phase length-then-payload, like the reference binding
        # (python/rabit.py:171-206): all processes must present equal shapes.
        # Length rides as (hi, lo) int32 halves — JAX downcasts int64 arrays
        # under its default 32-bit config, which would wrap >=2GiB payloads.
        nbytes = len(data) if is_root and data is not None else 0
        length = np.array([nbytes >> 31, nbytes & 0x7FFFFFFF], np.int32)
        length = np.asarray(mhu.broadcast_one_to_all(length, is_source=is_root))
        buf = np.zeros((int(length[0]) << 31) | int(length[1]), np.uint8)
        if is_root:
            buf[:] = np.frombuffer(data, np.uint8)
        buf = np.asarray(mhu.broadcast_one_to_all(buf, is_source=is_root))
        return buf.tobytes()

    def allgather(self, data, cache_key=None):
        if self.get_world_size() == 1:
            return data
        from jax.experimental import multihost_utils as mhu

        return np.asarray(mhu.process_allgather(np.asarray(data))).reshape(-1)

    def load_checkpoint(self):
        if self._global_blob is None and self._lazy_thunk is not None:
            self._global_blob = bytes(self._lazy_thunk())
        return self._version, self._global_blob, self._local_blob

    def checkpoint(self, global_blob, local_blob=None):
        # Host-memory checkpoint per process; multi-host recovery of a
        # preempted VM is the native robust engine's job (hybrid deployment:
        # XLA data plane + robust TCP control plane).
        self._global_blob = join_blob(global_blob)
        self._local_blob = None if local_blob is None else join_blob(local_blob)
        self._lazy_thunk = None
        self._version += 1

    def lazy_checkpoint(self, get_global_blob):
        self._lazy_thunk = get_global_blob
        self._global_blob = None
        self._local_blob = None
        self._version += 1

    def version_number(self):
        return self._version
