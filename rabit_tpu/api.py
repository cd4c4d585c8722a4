"""Public module-level API.

Parity surface with the reference Python binding
(``/root/reference/python/rabit.py``) plus ``allgather`` and
``lazy_checkpoint`` which the reference exposes only at the C++ layer
(rabit.h:224-232, :311-332).  Objects are pickled for broadcast/checkpoint
exactly as the reference does (python/rabit.py:171-206, :320-351); allreduce
takes numpy arrays with the same dtype/op enums.

Caller-site capture: the reference records ``__builtin_FILE()/LINE()`` of the
caller as the bootstrap-cache key for every collective (rabit.h:29-37).  The
Python equivalent reads the caller frame via ``sys._getframe`` and passes
``file:line:function`` down to the engine as ``cache_key``.
"""

from __future__ import annotations

import pickle
import struct
import sys
from typing import Any, Callable, NamedTuple

import numpy as np

from rabit_tpu import compress, obs, quorum
from rabit_tpu.config import Config
from rabit_tpu.engine import create_engine
from rabit_tpu.engine.base import (
    MAX, MIN, SUM, BITOR, DTYPE_ENUM, Engine, join_blob,
)
from rabit_tpu.profile import GLOBAL_STATS, CollectiveStats

_engine: Engine | None = None
# Durable-spill state (rabit_checkpoint_dir): the store, and the user-visible
# version base when this job resumed a previous job's disk checkpoints.  The
# base also travels inside every checkpoint frame (_dumps/_unwrap), so a
# worker restarted mid-job recovers it from the peer-served blob rather than
# from process memory.
_ckpt_store = None
_ckpt_base = 0

# Delivery-plane publisher (rabit_tpu/delivery, doc/delivery.md): built at
# init() on rank 0 when rabit_delivery_publish=1, it registers every
# checkpoint commit as a content-addressed snapshot with the tracker.
_publisher = None

# Elastic-world state (rabit_tpu/elastic, doc/elasticity.md): the world
# epoch this process last adopted, and the shard-rebalance callbacks run
# when it changes.  The epoch is stamped into durable checkpoint frames
# (RTC3) so replay stays deterministic across a resize.
_world_epoch: dict = {"epoch": 0, "world_size": 1}
_rebalance_cbs: list[Callable[[dict, dict], None]] = []

# A checkpointed model is a FRAME (doc/guide.md, "Checkpoint blobs"): the
# model pickled with protocol 5, its large contiguous buffers (a numpy
# array's memory) left OUT OF BAND.  In order:
#
#   magic "\xffRTF" | buffer count u32 | job's base version u64 |
#   pickle length u64 | each buffer's length u64 ... | pickle | buffers ...
#
# (little-endian; 0xff opens no pickle of any protocol — one of protocol 2
# and later starts with 0x80 — so a frame is never taken for a plain pickle
# nor a plain pickle for a frame).  ``checkpoint`` never joins it: the engine
# and the store take it as pieces (the head packed below, the pickle, each
# buffer where the caller's array holds it) and the engine's copy into its
# own storage is the only copy of the model's bytes a commit makes.  A
# buffer under _OOB_MIN_BYTES stays in band, inside the pickle: below that a
# piece (a PickleBuffer, a pointer/length pair across the C ABI, an append)
# costs more than the copy it saves.
_FRAME_MAGIC = b"\xffRTF"
_FRAME_HEAD = struct.Struct("<4sIQQ")  # magic, buffers, base, pickle length
_OOB_MIN_BYTES = 64 << 10

# What an older build's spill put around the global blob to carry the base
# (a second pickle, copying it); still read, never written.
_WRAP_TAG = "__rabit_tpu_ckpt1__"


class _Frame(NamedTuple):
    pieces: tuple  # the head, the pickle, then each out-of-band buffer
    nbytes: int    # of all the pieces
    oob: int       # of the buffers

    @property
    def buffers(self) -> int:
        return len(self.pieces) - 2


def _dumps(model: Any, base: int = 0) -> _Frame:
    """``model`` as the pieces of a frame.  The buffers are views of the
    caller's memory, not copies."""
    bufs: list[memoryview] = []

    def in_band(pb: pickle.PickleBuffer) -> bool:
        try:
            view = pb.raw()
        except BufferError:  # not contiguous: the pickler's to deal with
            return True
        if view.nbytes < _OOB_MIN_BYTES:
            return True
        bufs.append(view)
        return False

    body = pickle.dumps(model, protocol=5, buffer_callback=in_band)
    sizes = [b.nbytes for b in bufs]
    head = (_FRAME_HEAD.pack(_FRAME_MAGIC, len(bufs), base, len(body))
            + struct.pack(f"<{len(bufs)}Q", *sizes))
    oob = sum(sizes)
    return _Frame((head, body, *bufs), len(head) + len(body) + oob, oob)


def _frame_parts(blob) -> tuple[int, memoryview, list[memoryview]] | None:
    """``(base, pickle, buffers)`` of a frame, as views of ``blob``; None
    for anything else (a plain pickle)."""
    view = memoryview(blob)
    if view[:4] != _FRAME_MAGIC:
        return None
    try:
        _magic, nbuf, base, nbody = _FRAME_HEAD.unpack_from(view)
        sizes = struct.unpack_from(f"<{nbuf}Q", view, _FRAME_HEAD.size)
    except struct.error as exc:
        raise ValueError("rabit_tpu: truncated checkpoint frame") from exc
    at = _FRAME_HEAD.size + 8 * nbuf
    if at + nbody + sum(sizes) != len(view):
        raise ValueError("rabit_tpu: checkpoint frame of the wrong length")
    body = view[at:at + nbody]
    at += nbody
    bufs = []
    for n in sizes:
        bufs.append(view[at:at + n])
        at += n
    return base, body, bufs


def _loads(blob) -> Any:
    """The model of a blob: a frame, or the plain pickle of a lazy
    checkpoint or an older build.  Each buffer is copied once, into a
    bytearray of its own, so the arrays that come back are writable and
    share nothing with the blob or with each other."""
    parts = _frame_parts(blob)
    if parts is None:
        return pickle.loads(blob)
    _base, body, bufs = parts
    return pickle.loads(body, buffers=[bytearray(b) for b in bufs])


def _unwrap(blob) -> tuple[int, Any]:
    """``(base, blob to unpickle)`` of a global blob written with the spill
    on: a frame carries the base in its head; an older build's wrapper is
    opened; plain blobs (store off) pass through."""
    parts = _frame_parts(blob)
    if parts is not None:
        return parts[0], blob
    try:
        obj = pickle.loads(blob)
    except Exception:  # noqa: BLE001 — not a pickle we wrote
        return 0, blob
    if isinstance(obj, tuple) and len(obj) == 3 and obj[0] == _WRAP_TAG:
        return int(obj[1]), obj[2]
    return 0, blob


def collective_stats() -> CollectiveStats:
    """Accumulated per-collective timing for this process (see
    rabit_tpu.profile; the Python-layer analogue of the reference's
    rabit_debug/report_stats observability).  The full registry — named
    counters/gauges/histograms — is ``collective_stats().registry`` or
    ``rabit_tpu.obs.get_registry()``."""
    return GLOBAL_STATS


def reset_collective_stats() -> None:
    GLOBAL_STATS.reset()


def _caller_key(depth: int = 2) -> str:
    frame = sys._getframe(depth)
    return f"{frame.f_code.co_filename}::{frame.f_lineno}::{frame.f_code.co_name}"


def _get_engine() -> Engine:
    """Return the active engine; like the reference (engine.cc:71-82), an
    uninitialized process gets a solo engine so single-process programs work
    with zero config."""
    global _engine
    if _engine is None:
        from rabit_tpu.engine.empty import SoloEngine

        _engine = SoloEngine(Config([]))
        # A zero-config engine is provisional: an explicit init() later may
        # still replace it (mirrors the reference's uninitialized static
        # engine, engine.cc:71-82).
        _engine._provisional = True
    return _engine


def init(args: list[str] | None = None, **overrides: Any) -> None:
    """Initialize the engine.  ``args`` are ``"key=value"`` strings (defaults
    to ``sys.argv[1:]``); keyword overrides win over args, args win over env
    vars (see rabit_tpu.config)."""
    global _engine
    if _engine is not None:
        if getattr(_engine, "_provisional", False):
            _engine = None
        else:
            import warnings

            warnings.warn("rabit_tpu.init ignored: already initialized", stacklevel=2)
            return
    if args is None:
        args = [a for a in sys.argv[1:] if "=" in a]
    args = [a.decode() if isinstance(a, bytes) else a for a in args]
    cfg = Config(args, {k: str(v) for k, v in overrides.items()})
    # Quorum policy (rabit_tpu/quorum, doc/partial_allreduce.md): resolve
    # BEFORE any engine spins up, so a typo'd rabit_quorum fails loudly
    # with nothing to tear down.
    qpol = quorum.resolve(cfg)
    _engine = create_engine(cfg)
    _engine.init()
    # Observability wiring: flight recorder capacity, hang/SIGTERM dump
    # paths (RABIT_OBS_DIR), metric shipping identity (see rabit_tpu.obs).
    obs.configure(cfg, rank=_engine.get_rank())
    # Compression policy (rabit_tpu/compress, doc/compression.md): the
    # rabit_compress_* keys resolve once per init; the resolved policy is
    # recorded so a cross-rank config skew is visible in the dumps.
    pol = compress.configure(cfg)
    obs.record_event(
        "compress_policy",
        allreduce=pol.allreduce or "identity",
        min_bytes=pol.min_bytes,
        wire_deflate=pol.wire_deflate,
        broadcast=pol.broadcast or "identity",
        checkpoint=pol.checkpoint or "identity",
        fused=pol.fused,
        fused_chunk_kib=pol.fused_chunk_kib,
    )
    # Record the resolved quorum policy so a cross-rank config skew is
    # visible in the dumps.  The engines' own collectives stay exact —
    # the quorum data plane is the tracker + schedule-aware executor
    # contract (ElasticWorker), the same seam the planned rings ride.
    if qpol["quorum"]:
        obs.record_event(
            "quorum_policy",
            quorum=qpol["quorum"],
            wait_sec=qpol["wait_sec"],
            flag_after=qpol["flag_after"],
        )
    obs.record_event(
        "engine_ready",
        engine=type(_engine).__name__,
        rank=_engine.get_rank(),
        world=_engine.get_world_size(),
    )
    global _ckpt_store, _ckpt_base, _world_epoch, _publisher
    _ckpt_base = 0
    _world_epoch = {"epoch": 0, "world_size": _engine.get_world_size()}
    ckpt_dir = cfg.get("rabit_checkpoint_dir", "") or ""
    if ckpt_dir and ckpt_dir != "NULL":
        from rabit_tpu.store import CheckpointStore

        _ckpt_store = CheckpointStore(ckpt_dir, _engine.get_rank(),
                                      codec=pol.checkpoint)
    else:
        _ckpt_store = None
    # Delivery plane (doc/delivery.md): rank 0 publishes each commit's
    # bytes content-addressed through the tracker.  Only the committing
    # rank publishes — every rank holds the same global blob, and N
    # identical publishes would be N redundant digest registrations.
    _publisher = None
    uri = cfg.get("rabit_tracker_uri", "NULL") or "NULL"
    if (cfg.get_bool("rabit_delivery_publish") and uri != "NULL"
            and _engine.get_rank() == 0):
        from rabit_tpu.delivery import Publisher
        from rabit_tpu.tracker.protocol import parse_addrs

        _publisher = Publisher(
            uri, cfg.get_int("rabit_tracker_port", 9091),
            job=cfg.get("rabit_job_key", "") or "",
            task_id=f"pub-{cfg.get('rabit_task_id', '0')}",
            addrs=parse_addrs(cfg.get("rabit_tracker_addrs", "") or ""),
        )


def finalize() -> None:
    """Shut down the engine (reference: RabitFinalize).  Ships the final
    metrics snapshot to the tracker first — the tracker keeps serving until
    every rank's shutdown handshake, so the snapshot always lands."""
    global _engine, _ckpt_store, _ckpt_base, _world_epoch, _publisher
    if _engine is not None:
        obs.ship_final_snapshot()
        obs.record_event("engine_finalize", engine=type(_engine).__name__)
        _engine.shutdown()
        _engine = None
        # rabit_trace_exit=1: leave this life's ring as a -exit flight dump
        # so the cross-rank trace merger has per-rank evidence of CLEAN runs
        obs.dump_final()
    compress.reset()
    _ckpt_store = None
    _ckpt_base = 0
    _world_epoch = {"epoch": 0, "world_size": 1}
    _publisher = None


def world_epoch() -> dict:
    """The world epoch this process last adopted: ``{"epoch", "world_size"}``
    (doc/elasticity.md).  Epoch 0 / the engine's world until an elastic
    resize is observed."""
    return dict(_world_epoch)


def register_rebalance(callback: Callable[[dict, dict], None]) -> None:
    """Register a shard-rebalance callback ``callback(old, new)`` invoked
    whenever this process adopts a new world epoch (``old``/``new`` are
    ``world_epoch()``-shaped dicts).  The GBDT histogram deployment re-cuts
    its data shard here (``models.gbdt.elastic_shard`` /
    ``elastic.rebalance.shard_slice``) so the fold keeps covering the whole
    dataset around the hole.  Callbacks must be idempotent; exceptions
    propagate to the notifier."""
    if callback not in _rebalance_cbs:
        _rebalance_cbs.append(callback)


def unregister_rebalance(callback: Callable[[dict, dict], None]) -> None:
    try:
        _rebalance_cbs.remove(callback)
    except ValueError:
        pass


def notify_world_change(epoch: int, world_size: int) -> None:
    """Adopt a new world epoch: record it (checkpoint frames stamp it from
    here), emit the ``epoch_changed``/``shard_rebalanced`` evidence, and
    run the registered rebalance callbacks."""
    global _world_epoch
    old = dict(_world_epoch)
    if epoch == old["epoch"] and world_size == old["world_size"]:
        return
    _world_epoch = {"epoch": int(epoch), "world_size": int(world_size)}
    obs.record_event("epoch_changed", epoch=int(epoch),
                     world=int(world_size), prev_world=old["world_size"])
    for cb in list(_rebalance_cbs):
        cb(old, dict(_world_epoch))
    if _rebalance_cbs:
        obs.record_event("shard_rebalanced", epoch=int(epoch),
                         callbacks=len(_rebalance_cbs))


def rebootstrap() -> dict:
    """Re-enter the tracker after a world-epoch change: the native engine
    finalizes and re-bootstraps (fresh assignment, possibly a different
    world), the XLA engine rebuilds its process mesh, and the adopted
    epoch is bumped so rebalance callbacks and checkpoint stamps follow.
    Returns the new ``world_epoch()``."""
    engine = _get_engine()
    if hasattr(engine, "rebootstrap"):
        engine.rebootstrap()
    elif hasattr(engine, "rebuild_mesh"):
        engine.rebuild_mesh()
    notify_world_change(_world_epoch["epoch"] + 1, engine.get_world_size())
    return world_epoch()


def get_rank() -> int:
    return _get_engine().get_rank()


def get_world_size() -> int:
    return _get_engine().get_world_size()


def is_distributed() -> bool:
    return _get_engine().is_distributed()


def tracker_print(msg: str) -> None:
    """Send a message to the tracker console (reference: TrackerPrint)."""
    if not isinstance(msg, str):
        msg = str(msg)
    _get_engine().tracker_print(msg)


def get_processor_name() -> str:
    return _get_engine().get_host()


def broadcast(data: Any, root: int) -> Any:
    """Broadcast any picklable object from ``root``.  Two-phase
    length-then-payload, like the reference (python/rabit.py:171-206).

    With ``rabit_compress_broadcast`` configured (e.g. ``zlib``), the
    pickled payload crosses the wire compressed behind a one-byte codec
    frame; payloads under ``rabit_compress_min_bytes`` ride as identity.
    The policy comes from the shared job config, so every rank frames and
    deframes symmetrically."""
    engine = _get_engine()
    key = _caller_key()
    rank = engine.get_rank()
    pol = compress.policy()
    bcodec = compress.get_codec(pol.broadcast) if pol.broadcast else None
    payload = None
    if rank == root:
        if data is None:
            raise ValueError("need to pass in data when broadcasting")
        payload = pickle.dumps(data, protocol=pickle.HIGHEST_PROTOCOL)
        if bcodec is not None:
            if len(payload) >= pol.min_bytes:
                wire = bcodec.encode_bytes(payload)
                compress.observe(bcodec.name, raw=len(payload),
                                 wire=len(wire))
                payload = bytes([bcodec.codec_id]) + wire
            else:
                payload = bytes([0]) + payload  # identity frame
    # Same timed/evented path as allreduce/allgather; a non-root only
    # learns the payload length from the wire, so the span's byte count is
    # set inside the window.
    with obs.collective(
        "broadcast", len(payload) if payload is not None else 0,
        cache_key=key, codec=bcodec.name if bcodec is not None else None,
    ) as span:
        out = engine.broadcast(payload, root, cache_key=key)
        span.nbytes = (len(payload) if payload is not None
                       else len(out) if out else 0)
    if rank == root:
        return data
    if bcodec is not None:
        out = bytes(out)
        out = compress.get_codec_by_id(out[0]).decode_bytes(out[1:])
    return pickle.loads(out)


def allreduce(
    data: np.ndarray,
    op: int,
    prepare_fun: Callable[[np.ndarray], None] | None = None,
    codec: str | None = None,
) -> np.ndarray:
    """Allreduce a numpy array.  ``op`` is one of MAX/MIN/SUM/BITOR.
    ``prepare_fun(data)`` is called lazily right before the reduction and is
    skipped when the result is recovered from a peer's replay buffer
    (reference semantics, python/rabit.py:220-263).

    ``codec`` selects a wire codec (rabit_tpu.compress; doc/compression.md)
    for this call: the payload crosses the engine encoded and every rank
    decodes/folds identically, trading the codec's documented error bound
    for wire bytes.  ``None`` applies the ``rabit_compress_allreduce``
    policy (float32, non-BITOR payloads of at least
    ``rabit_compress_min_bytes``); ``"identity"`` forces the exact path.
    On the compressed path ``prepare_fun`` runs eagerly — its output feeds
    the encoder."""
    # entry to the engine's call under one name, the result's reshape under
    # another: with the engine's own span between them, a hop's host side
    # in three parts (doc/observability.md, "A hop's five phases")
    if not isinstance(data, np.ndarray):
        raise TypeError("allreduce only takes numpy ndarrays")
    with obs.span("rabit.allreduce.copy_in", nbytes=data.nbytes):
        if data.dtype not in DTYPE_ENUM:
            raise TypeError(f"dtype {data.dtype} not supported")
        if op not in (MAX, MIN, SUM, BITOR):
            raise ValueError(f"unknown reduction op {op}")
        buf = data.flatten()  # always a fresh 1-D C-order copy
        shape = data.shape
        if prepare_fun is not None:
            orig_prepare = prepare_fun

            def prepare_fun(buf_view: np.ndarray) -> None:  # type: ignore[misc]
                orig_prepare(data)
                buf_view[...] = np.ascontiguousarray(data).reshape(-1)

        c = compress.resolve(codec, buf.dtype, op, buf.nbytes)
        key = _caller_key()
    # NOTE: the timed window includes a lazy prepare_fun's execution (it
    # runs inside the engine, interleaved with recovery decisions), so
    # expensive preparation shows up as allreduce latency in the stats.
    if c is None:
        with obs.collective("allreduce", buf.nbytes, cache_key=key):
            out = _get_engine().allreduce(
                buf, op, prepare_fun=prepare_fun, cache_key=key
            )
    else:
        engine = _get_engine()
        with obs.collective("allreduce", buf.nbytes, cache_key=key,
                            codec=c.name,
                            fused=engine.fused_active(c, op)):
            out = engine.allreduce_compressed(
                buf, op, c, prepare_fun=prepare_fun, cache_key=key
            )
    with obs.span("rabit.allreduce.copy_out", nbytes=buf.nbytes):
        return np.asarray(out).reshape(shape)


def allgather(data: np.ndarray) -> np.ndarray:
    """Gather this rank's array from every rank; returns shape
    ``(world_size,) + data.shape``."""
    if not isinstance(data, np.ndarray):
        raise TypeError("allgather only takes numpy ndarrays")
    engine = _get_engine()
    flat = np.ascontiguousarray(data).reshape(-1)
    key = _caller_key()
    with obs.collective("allgather", flat.nbytes, cache_key=key):
        out = engine.allgather(flat, cache_key=key)
    return np.asarray(out).reshape((engine.get_world_size(),) + data.shape)


def _disk_resume():
    """Fresh-cluster disk resume (store configured, engine version 0).

    Every first-life worker runs this IDENTICAL deterministic collective
    sequence (decisions depend only on collective results, which agree on
    all ranks), so the robust engine's replay contract holds; a worker
    restarted before the first checkpoint re-enters this same path, and
    one restarted after sees engine version > 0 and never comes here.

    Returns (base_version, gblob, lblob) — (0, None, None) when there is
    nothing on disk anywhere."""
    engine = _get_engine()
    mine = np.array([_ckpt_store.latest_valid()], np.int64)
    vmax = int(engine.allreduce(mine, MAX, cache_key="rabit_tpu.store::vmax")[0])
    if vmax <= 0:
        return 0, None, None
    have = int(_ckpt_store.has(vmax))
    all_have = int(
        engine.allreduce(np.array([have], np.int64), MIN,
                         cache_key="rabit_tpu.store::have")[0]
    )
    if all_have:
        return vmax, _ckpt_store.load_global(vmax), _ckpt_store.load_local(vmax)
    # Someone's disk copy is missing/stale: the lowest-ranked holder serves
    # the (rank-identical) global blob over a broadcast.  Rank-specific
    # local models cannot be served this way; a rank without its own file
    # resumes with local_model=None (warned below — the caller must be able
    # to rebuild rank-local state, see doc/guide.md "Durable spill").
    if not have:
        import warnings

        warnings.warn(
            f"rabit_tpu durable resume: rank {engine.get_rank()} has no "
            f"valid disk checkpoint for v{vmax} (killed between the commit "
            "barrier and its disk save?); the global model is served by a "
            "peer but any rank-local model is LOST — load_checkpoint will "
            "return local_model=None and the caller must rebuild it",
            stacklevel=3,
        )
    world = engine.get_world_size()
    root = int(
        engine.allreduce(
            np.array([engine.get_rank() if have else world], np.int64), MIN,
            cache_key="rabit_tpu.store::root")[0]
    )
    # The recovery/bootstrap blob crosses the wire zlib-compressed (both
    # ends run this same code, so no frame negotiation is needed; the
    # holder's own broadcast-return decompresses identically).
    zcodec = compress.get_codec("zlib")
    wireblob = engine.broadcast(
        zcodec.encode_bytes(_ckpt_store.load_global(vmax))
        if engine.get_rank() == root else None,
        root, cache_key="rabit_tpu.store::blob",
    )
    gblob = zcodec.decode_bytes(bytes(wireblob))
    compress.observe(zcodec.name, raw=len(gblob), wire=len(wireblob))
    obs.record_event("recovery_blob_compressed", raw=len(gblob),
                     wire=len(wireblob), version=vmax)
    lblob = _ckpt_store.load_local(vmax) if have else None
    return vmax, bytes(gblob), lblob


def load_checkpoint(with_local: bool = False):
    """Load the latest checkpoint.  Returns ``(version, global_model)`` or
    ``(version, global_model, local_model)``; version 0 means nothing has
    been checkpointed yet.  With ``rabit_checkpoint_dir`` configured, a
    fresh cluster first agrees on and resumes from the newest disk
    checkpoint (whole-job preemption durability)."""
    global _ckpt_base
    with obs.span("rabit.load_checkpoint") as sp:
        with obs.span("rabit.load.engine"):
            version, gblob, lblob = _get_engine().load_checkpoint()
        if _ckpt_store is not None:
            if version == 0:
                with obs.span("rabit.load.disk"):
                    vmax, dgblob, dlblob = _disk_resume()
                if vmax > 0:
                    # Resuming a PREVIOUS job: the file's version is the new
                    # base; the wrapper inside carries the old job's base and
                    # is discarded.
                    _ckpt_base = vmax
                    _, gblob = _unwrap(dgblob)
                    lblob = dlblob
                    version = vmax
            else:
                # Peer-served blob from the CURRENT job: its wrapper carries
                # this job's base (authoritative for a restarted worker, whose
                # process state starts empty).
                _ckpt_base, gblob = _unwrap(gblob)
                version = _ckpt_base + version
        sp.set(version=version)
        # Cross-rank collective numbering (obs/trace.py): landing on version
        # V resets the per-version seqno exactly like the survivors' commit
        # of V did, so a restarted worker resumes the shared (version, seqno)
        # line.
        obs.collective_epoch(version)
        obs.record_event("load_checkpoint", version=version,
                         recovered=version > 0)
        if version > 0:
            obs.get_registry().counter("load_checkpoint_recovered_total").inc()
        gmodel = lmodel = None
        if version > 0:
            with obs.span("rabit.load.unpickle",
                          nbytes=len(gblob or b"") + len(lblob or b"")):
                if gblob is not None:
                    gmodel = _loads(gblob)
                if with_local and lblob is not None:
                    lmodel = _loads(lblob)
    if with_local:
        return version, gmodel, lmodel
    return version, gmodel


def _note_commit(engine: Engine, nbytes: int) -> None:
    """Record one checkpoint commit (engine version bump) in the flight
    recorder and registry."""
    version = _ckpt_base + engine.version_number()
    obs.collective_epoch(version)
    obs.record_event("checkpoint_commit", version=version, nbytes=nbytes)
    reg = obs.get_registry()
    reg.counter("checkpoint_commits_total").inc()
    reg.gauge("checkpoint_version").set(version)


def checkpoint(global_model: Any, local_model: Any = None) -> None:
    """Commit an iteration: pickle and store the models, bump the version.
    ``local_model`` (rank-specific state) costs ring replication; prefer
    ``global_model`` (reference notes, python/rabit.py:320-351).  With
    ``rabit_checkpoint_dir`` configured, the committed blobs are also
    spilled to disk (whole-job preemption durability).

    A model's large arrays are not copied on the way: each is pickled out
    of band and read where it lies by the engine's copy (and the store's
    write), which is done when this returns — the caller may overwrite its
    arrays at once."""
    engine = _get_engine()
    # the spans' label: the version this commit makes (what is saved below
    # is named by the engine's own count, read after the commit)
    version = _ckpt_base + engine.version_number() + 1
    with obs.span("rabit.checkpoint", version=version) as sp:
        with obs.span("rabit.checkpoint.pickle") as pk:
            gframe = _dumps(global_model, _ckpt_base)
            lframe = (None if local_model is None
                      else _dumps(local_model, _ckpt_base))
            frames = (gframe,) if lframe is None else (gframe, lframe)
            gblob = gframe.pieces
            lblob = None if lframe is None else lframe.pieces
            nbytes = sum(f.nbytes for f in frames)
            oob = sum(f.oob for f in frames)
            pk.set(nbytes=nbytes, nbytes_oob=oob,
                   buffers=sum(f.buffers for f in frames))
        reg = obs.get_registry()
        reg.counter("checkpoint_bytes_oob_total").inc(oob)
        reg.counter("checkpoint_bytes_inband_total").inc(nbytes - oob)
        sp.set(nbytes_global=gframe.nbytes,
               nbytes_local=nbytes - gframe.nbytes)
        with obs.span("rabit.checkpoint.commit", nbytes=nbytes):
            engine.checkpoint(gblob, lblob)
            _note_commit(engine, gframe.nbytes)
        if _ckpt_store is not None:
            # Persist AFTER the commit barrier: live ranks' disk versions
            # can then skew by at most one, which the store's keep-2
            # retention covers.  The adopted world epoch rides in the frame
            # (RTC3) so a resume can tell which membership generation
            # produced each version — replay across a resize stays
            # deterministic (doc/elasticity.md).
            with obs.span("rabit.checkpoint.spill"):
                _ckpt_store.save(_ckpt_base + engine.version_number(), gblob,
                                 lblob, epoch=_world_epoch["epoch"])
        _publish_commit(engine, gblob)


def _publish_commit(engine: Engine, pieces: tuple) -> None:
    """Delivery-plane publish seam (doc/delivery.md): register the
    committed blob with the tracker AFTER commit (and after the durable
    spill, when on) so the plane only ever advertises bytes a resume
    could also serve.  Publishing is best-effort — a delivery outage
    must never fail the training job's commit.  The frame is joined into
    one ``bytes`` here, and only with a publisher configured."""
    if _publisher is None:
        return
    blob = join_blob(pieces)
    version = _ckpt_base + engine.version_number()
    with obs.span("rabit.checkpoint.publish", version=version,
                  nbytes=len(blob)):
        try:
            _publisher.publish(version, blob, epoch=_world_epoch["epoch"])
            if _ckpt_store is not None:
                # Pin what subscribers were just told about: the retention
                # prune must not race a fetch-in-flight of this version.
                _ckpt_store.pin(version)
            obs.record_event("snapshot_published", version=version,
                             nbytes=len(blob))
        except (ConnectionError, OSError, ValueError):
            pass


def lazy_checkpoint(global_model: Any) -> None:
    """Checkpoint without eager serialization: the model is only pickled if a
    failure actually needs the blob.  Contract (reference rabit.h:311-332):
    ``global_model`` must stay unchanged until the NEXT checkpoint call
    RETURNS — recovery during that next call's pre-commit consensus can
    still serve this version through this call's callback.  Rebind a fresh
    object per iteration rather than mutating in place.

    With ``rabit_checkpoint_dir`` configured this degrades to the eager
    path: disk durability requires the bytes at commit time."""
    if _ckpt_store is not None:
        checkpoint(global_model)
        return
    engine = _get_engine()
    engine.lazy_checkpoint(
        lambda: pickle.dumps(global_model, protocol=pickle.HIGHEST_PROTOCOL)
    )
    _note_commit(engine, 0)  # lazy: bytes unknown unless a failure asks


def version_number() -> int:
    """Checkpoint count.  When this job resumed disk checkpoints from a
    previous job, the resumed base is included — user code always sees one
    monotonically growing version line."""
    return _ckpt_base + _get_engine().version_number()
