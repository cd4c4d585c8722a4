"""rabit_tpu.compress — the codec subsystem (ISSUE 5 tentpole).

One registry of codecs with a single contract — deterministic,
rank-symmetric encode; documented decode(encode(x)) error bounds; a
pure-numpy reference plus an in-graph JAX path per codec — wired through
every data-plane seam:

* ``api.allreduce(..., codec=...)`` — per-call override, with a policy
  default (``rabit_compress_allreduce``) and a size floor
  (``rabit_compress_min_bytes``); the XLA engine runs the quantize /
  dequantize on-device so a fused flush stays one device collective,
  every other engine gets the numpy transport (compress.transport);
* ``fusion.LazyAllreduce`` — groups by (dtype, op, codec) so a flush is
  one collective per group and two-plane codecs ride as planes of the
  same fused buffer;
* ``store.CheckpointStore`` — a codec byte in the durable frame
  (``rabit_checkpoint_compress``, applied where a probe of the blob says
  it pays; old frames stay readable);
* ``api._disk_resume`` — peer-served recovery/bootstrap blobs cross the
  wire zlib-compressed.

Policy resolution (:func:`resolve`): an explicit ``codec=`` argument is
validated loudly (wrong dtype or a BITOR op raises); the config policy is
applied quietly only where it is sound — float32 payloads, non-BITOR ops,
at least ``rabit_compress_min_bytes`` bytes — and everything else falls
through uncompressed, so turning the knob on can never corrupt an exact
path.  See doc/compression.md for the codec table and the replay-safety
contract.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

from rabit_tpu.compress.codecs import (  # noqa: F401 (re-exports)
    BLOCK,
    CODECS,
    DEFLATE_LEVEL,
    Codec,
    get_codec,
    get_codec_by_id,
)
from rabit_tpu.compress.transport import (  # noqa: F401 (re-exports)
    CodecMismatchError,
    host_allreduce,
    observe,
    reference_allreduce,
)

#: codec names accepted as "no compression"
_OFF = ("", "identity", "off", "none", "0")


class Policy(NamedTuple):
    """Resolved ``rabit_compress_*`` configuration (one per init)."""

    allreduce: str = ""        # default codec for api.allreduce ("" = off)
    min_bytes: int = 1024      # policy floor: smaller payloads stay exact
    wire_deflate: bool = True  # lossless deflate stage on host wire bytes
    broadcast: str = ""        # byte codec for api.broadcast payloads
    checkpoint: str = "zlib"   # byte codec for durable store frames
    fused: str = "auto"        # rabit_fused_allreduce: auto|1|0 — the fused
                               # in-graph path (auto = on for XLA engines,
                               # off elsewhere; engine/fused.py)
    fused_chunk_kib: int = 256  # ppermute hop sub-chunk size (KiB)


_POLICY = Policy()


def policy() -> Policy:
    return _POLICY


def _numeric(name: str, what: str) -> str:
    if name in _OFF:
        return ""
    c = get_codec(name)  # raises on unknown names — a typo'd policy is loud
    if c.kind != "numeric":
        raise ValueError(f"{what}: codec {name!r} is a byte codec, not a "
                         f"numeric array codec")
    return name


#: accepted rabit_fused_allreduce spellings (doc/parameters.md)
_FUSED_MODES = ("auto", "1", "0", "on", "off", "true", "false", "yes", "no",
                "")


def _fused_mode(value: str) -> str:
    mode = value.strip().lower()
    if mode not in _FUSED_MODES:
        raise ValueError(
            f"rabit_fused_allreduce={value!r}: want auto, 1/on, or 0/off")
    return mode or "auto"


def _bytes_codec(name: str, what: str) -> str:
    if name in _OFF:
        return ""
    c = get_codec(name)
    if c.kind != "bytes" and not c.lossless:
        raise ValueError(f"{what}: codec {name!r} is lossy — byte blobs "
                         f"(checkpoints, broadcasts) need lossless codecs")
    return name


def configure(config) -> Policy:
    """Resolve the ``rabit_compress_*`` / ``rabit_checkpoint_compress``
    keys into the process policy (called by ``rabit_tpu.init``)."""
    global _POLICY
    _POLICY = Policy(
        allreduce=_numeric(
            config.get("rabit_compress_allreduce", "") or "",
            "rabit_compress_allreduce"),
        min_bytes=config.get_size("rabit_compress_min_bytes", 1024),
        wire_deflate=config.get_bool("rabit_compress_wire_deflate", True),
        broadcast=_bytes_codec(
            config.get("rabit_compress_broadcast", "") or "",
            "rabit_compress_broadcast"),
        checkpoint=_bytes_codec(
            config.get("rabit_checkpoint_compress", "zlib") or "",
            "rabit_checkpoint_compress"),
        fused=_fused_mode(
            config.get("rabit_fused_allreduce", "auto") or "auto"),
        fused_chunk_kib=config.get_int("rabit_fused_chunk_kib", 256),
    )
    return _POLICY


def reset() -> None:
    """Back to built-in defaults (used by tests and finalize)."""
    global _POLICY
    _POLICY = Policy()


def resolve(codec, dtype, op: int, nbytes: int) -> Codec | None:
    """The one gate deciding whether a collective is compressed.

    ``codec`` is the per-call argument (str | Codec | None).  Explicit
    requests are validated loudly; the policy default applies quietly only
    to float32, non-BITOR payloads of at least ``min_bytes`` bytes.
    Returns the codec to use, or None for the exact path."""
    from rabit_tpu.engine.base import BITOR

    if codec is not None:
        name = codec.name if isinstance(codec, Codec) else str(codec)
        if name in _OFF:
            return None
        c = get_codec(name)
        if c.kind != "numeric":
            raise ValueError(
                f"allreduce codec {name!r} is a byte codec; numeric "
                f"payloads take identity/bf16/bf16x2/i8/i8x2")
        if np.dtype(dtype) != np.float32:
            raise TypeError(
                f"codec {name!r} compresses float32 payloads only, got "
                f"{np.dtype(dtype)} — cast first or drop the codec")
        if op == BITOR and not c.lossless:
            raise ValueError(
                f"codec {name!r} is lossy; BITOR needs exact bits")
        return None if c.lossless else c
    p = _POLICY
    if not p.allreduce:
        return None
    if (np.dtype(dtype) != np.float32 or op == BITOR
            or nbytes < p.min_bytes):
        return None
    c = get_codec(p.allreduce)
    return None if c.lossless else c
