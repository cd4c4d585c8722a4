"""Durable checkpoint spill — surviving WHOLE-JOB preemption.

The reference keeps checkpoints in memory only (doc/guide.md:185: a
rejoiner pulls state from surviving peers), which covers single-worker
deaths but loses everything when ALL workers die at once — exactly what a
TPU-slice preemption does.  With ``rabit_checkpoint_dir`` set, every
committed checkpoint is also written to disk (atomic rename + directory
fsync, last two versions retained), and a FRESH cluster (engine consensus
version 0) agrees on the newest version every rank can serve and resumes
from it — including serving the global blob over a broadcast to ranks
whose disk copy is missing or stale.

This sits entirely ABOVE the engine seam (rabit_tpu.api), so it works
with every backend unchanged.  The resume base version travels INSIDE the
wrapped global blob, so a worker restarted later in the resumed job
recovers the base from the peer-served blob, not from process memory.
"""

from __future__ import annotations

import os
import re
import struct
import zlib
from bisect import bisect_right
from itertools import accumulate
from pathlib import Path

from rabit_tpu import obs
from rabit_tpu.engine.base import blob_pieces

_GLOBAL_RE = re.compile(r"^global_r(\d+)_v(\d+)\.bin$")
_KEEP = 2  # two-phase commit skews live ranks by at most one version
# (the default; rabit_checkpoint_keep raises it — a deeper window for
# slow consumers of the delivery plane, doc/delivery.md)
# File layout: magic + crc32 + payload length, then the payload.  A file
# that fails the check (torn by a crash the rename protocol could not
# cover, or bit-rotted) reads as ABSENT, so resume degrades to an older
# version or the holder-broadcast path instead of crashing on garbage.
#
# Three frame generations: RTC1 (uncompressed payload), RTC2, which adds
# a codec byte (rabit_tpu.compress ids) so spilled blobs can land compressed
# (rabit_checkpoint_compress, default zlib: the codec the store MAY apply —
# the byte is per frame, and a blob the probe below finds incompressible is
# written raw under codec id 0), and RTC3, which additionally
# records the WORLD EPOCH (rabit_tpu.elastic) the committing membership
# generation held — so a resume can tell which world size produced each
# version and replay stays deterministic across an elastic resize.  The
# crc covers the ENCODED payload — integrity is checked before any decode
# touches the bytes — and older frames stay readable forever.  RTC3 is
# written only when a nonzero epoch is recorded; epoch-0 jobs keep
# emitting the bytes-identical RTC1/RTC2 frames older readers know.
_MAGIC = b"RTC1"
_HDR = struct.Struct("<4sII")
_MAGIC2 = b"RTC2"
_HDR2 = struct.Struct("<4sBxxxII")  # magic, codec id, pad, crc, enc len
_MAGIC3 = b"RTC3"
_HDR3 = struct.Struct("<4sBxxxIII")  # ..., crc, enc len, world epoch

# The probe: before a blob larger than _PROBE_BYTES is encoded, a sample of
# it is — _PROBE_SLICES equal slices spread evenly over the blob, the first
# and the last included, no RNG (the same blob gives the same frame) — and
# the codec runs over the whole blob only where the sample's encoded/raw is
# at most _PROBE_MAX_RATIO: the codec must take a quarter off.  A dense
# float32 state does not pay: the flagship margin (10.5 MB) deflates to
# 0.931 of itself at 30 ms/MB beside a disk that takes the frame at 1 ms/MB
# (PERF.md, PR 25); its sample reads 0.93 once the rounds' sums have spread
# over the mantissa, a sparse forest's 0.02, and the line sits well away
# from both.  (A young margin, a sum of few leaf values, reads 0.3-0.7 and
# is deflated: on the flagship job the first twelve commits; PERF.md, PR 26.)
_PROBE_BYTES = 64 << 10
_PROBE_SLICES = 16
_PROBE_MAX_RATIO = 0.75


def _byte_views(blob) -> tuple:
    """A blob, whole or in pieces (``engine.base.blob_pieces``), as views
    of bytes: a length is a byte count and a slice copies nothing."""
    return tuple(memoryview(p).cast("B") for p in blob_pieces(blob))


def _probe_sample(blob) -> bytes:
    """The probe's sample of a blob larger than ``_PROBE_BYTES``, whole or
    in pieces, cut through memoryviews: the blob itself is neither joined
    nor copied, and the sample is the one its joined bytes would give."""
    pieces = _byte_views(blob)
    ends = list(accumulate(len(p) for p in pieces))
    size = _PROBE_BYTES // _PROBE_SLICES
    last = ends[-1] - size
    out = []
    for i in range(_PROBE_SLICES):
        a = i * last // (_PROBE_SLICES - 1)
        b = a + size
        k = bisect_right(ends, a)  # the piece that holds byte a
        while a < b:
            at = a - (ends[k] - len(pieces[k]))
            cut = pieces[k][at:at + b - a]
            out.append(cut)
            a += len(cut)
            k += 1
    return b"".join(out)


class CheckpointStore:
    def __init__(self, directory: str, rank: int, codec: str = "zlib",
                 keep: int | None = None):
        from rabit_tpu.compress import get_codec
        from rabit_tpu.config import Config

        self.dir = Path(directory)
        self.rank = rank
        self._codec = None if codec in ("", "identity") else get_codec(codec)
        # Retention window (rabit_checkpoint_keep): versions beyond the
        # newest ``keep`` prune after each successful commit — without
        # it the store directory grows one file pair per commit forever.
        if keep is None:
            keep = Config().get_int("rabit_checkpoint_keep", _KEEP)
        self._keep = max(int(keep), 1)
        # Pinned versions survive pruning regardless of age: the
        # delivery plane pins the latest PUBLISHED version so a
        # subscriber's fetch-in-flight never loses its bytes to a
        # concurrent commit (doc/delivery.md).
        self._pinned: set[int] = set()
        self.dir.mkdir(parents=True, exist_ok=True)
        # One directory scan at startup seeds the version list (and sweeps
        # tmp leftovers of crashed saves); after that, save() maintains it
        # in memory so the per-checkpoint hot path never lists the shared
        # directory — O(world^2) dirent reads per round on network
        # filesystems otherwise.
        self._versions: list[int] = []
        self._cache: dict[Path, bytes] = {}  # verified payloads by path
        for p in self.dir.iterdir():
            if p.suffix == ".tmp" and f"_r{rank}_" in p.name:
                p.unlink(missing_ok=True)
            m = _GLOBAL_RE.match(p.name)
            if m and int(m.group(1)) == rank:
                self._versions.append(int(m.group(2)))
        self._versions.sort()

    # -- paths --------------------------------------------------------------

    def _gpath(self, version: int) -> Path:
        return self.dir / f"global_r{self.rank}_v{version}.bin"

    def _lpath(self, version: int) -> Path:
        return self.dir / f"local_r{self.rank}_v{version}.bin"

    # -- writes -------------------------------------------------------------

    def save(self, version: int, gblob, lblob=None, epoch: int = 0) -> None:
        """Persist one committed checkpoint atomically; prune old versions.
        Each blob is bytes-like or a sequence of bytes-like pieces
        (``engine.base.blob_pieces``): the probe samples, the crc runs
        over and the file is written from the pieces where they lie, and
        nothing of them is referenced once this returns.
        A nonzero ``epoch`` (elastic worlds) is recorded in the frame
        header (RTC3) and read back by :meth:`epoch_of`."""
        self._write(self._gpath(version), gblob, epoch=epoch)
        if lblob is not None:
            self._write(self._lpath(version), lblob, epoch=epoch)
        if version not in self._versions:
            self._versions.append(version)
            self._versions.sort()
        # its own span: unlinking the two files of the version that falls
        # out of the window is milliseconds at 10 MB a file
        with obs.span("rabit.spill.prune"):
            self._prune()

    def pin(self, version: int) -> None:
        """Exempt ``version`` from pruning (and release every older
        pin): the delivery plane pins the latest published version so a
        fetch-in-flight never loses its bytes (doc/delivery.md)."""
        self._pinned = {v for v in self._pinned if v > version}
        self._pinned.add(version)
        self._prune()

    def _prune(self) -> None:
        unpinned = [v for v in self._versions if v not in self._pinned]
        while len(unpinned) > self._keep:
            v = unpinned.pop(0)
            self._versions.remove(v)
            for p in (self._gpath(v), self._lpath(v)):
                p.unlink(missing_ok=True)
                self._cache.pop(p, None)

    def _encode(self, pieces: tuple,
                raw: int) -> tuple[int, tuple, float | None]:
        """``(codec id, payload pieces, probe)``: the configured codec
        applied to the blob (``pieces``, ``raw`` bytes in all) where the
        probe says it pays — the one place the pieces are joined, beside a
        codec that costs thirty times the join — else the pieces as they
        are under codec id 0.  ``probe`` is the encoded/raw ratio the
        decision was taken on — a sample's, or the whole blob's where the
        blob is no larger than the probe — and None with no codec
        configured."""
        codec = self._codec
        if codec is None:
            return 0, pieces, None
        if raw <= _PROBE_BYTES:
            # no dearer than the probe: encode it whole, keep the smaller
            payload = codec.encode_bytes(b"".join(pieces))
            probe = len(payload) / max(raw, 1)
            keep = len(payload) < raw
        else:
            probe = len(codec.encode_bytes(
                _probe_sample(pieces))) / _PROBE_BYTES
            keep = probe <= _PROBE_MAX_RATIO
            payload = codec.encode_bytes(b"".join(pieces)) if keep else None
        if not keep:
            return 0, pieces, probe
        from rabit_tpu.compress import observe

        observe(codec.name, raw=raw, wire=len(payload))
        return codec.codec_id, (payload,), probe

    def _write(self, path: Path, blob, epoch: int = 0) -> None:
        pieces = _byte_views(blob)
        raw = sum(len(p) for p in pieces)
        with obs.span("rabit.spill.encode", raw=raw) as sp:
            codec_id, payload, probe = self._encode(pieces, raw)
            crc, encoded = 0, 0
            for p in payload:
                crc = zlib.crc32(p, crc)
                encoded += len(p)
            # the codec actually applied to this frame, and what decided it
            sp.set(encoded=encoded,
                   codec=self._codec.name if codec_id else "identity")
            if probe is not None:
                sp.set(probe=round(probe, 4))
        obs.get_registry().counter(
            "spill_frames_encoded_total" if codec_id
            else "spill_frames_raw_total").inc()
        if epoch > 0:
            # Elastic job: the frame carries the committing world epoch.
            # Codec id 0 (identity) keeps the layout uniform when the
            # store is configured uncompressed.
            header = _HDR3.pack(_MAGIC3, codec_id, crc, encoded, epoch)
        elif self._codec is None:
            header = _HDR.pack(_MAGIC, crc, encoded)
        else:
            header = _HDR2.pack(_MAGIC2, codec_id, crc, encoded)
        tmp = path.with_suffix(".tmp")
        with obs.span("rabit.spill.write", bytes=len(header) + encoded):
            with open(tmp, "wb") as f:
                f.write(header)
                for p in payload:
                    f.write(p)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)  # atomic: readers see old or new, never torn
        # The memo of verified reads holds nothing of a write: the pieces
        # are the caller's memory, which it may overwrite, and a copy kept
        # here would be a model a file.  A read in this life (the delivery
        # plane's; none on a trainer's path) goes to the disk and is checked.
        self._cache.pop(path, None)
        # The rename itself must survive a host crash too — fsync the
        # directory entry, or the "durable" newest version can vanish on
        # power loss while the prune of the older one persisted.
        with obs.span("rabit.spill.dirsync"):
            dfd = os.open(self.dir, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)

    # -- reads --------------------------------------------------------------

    def versions(self) -> list[int]:
        """This rank's persisted versions, ascending."""
        return list(self._versions)

    def latest_valid(self) -> int:
        """Newest version whose global blob passes the integrity check —
        what this rank may truthfully advertise to the resume consensus
        (advertising a corrupt file could elect an unservable vmax)."""
        for v in reversed(self._versions):
            if self.has(v):
                return v
        return 0

    def _read_checked(self, path: Path) -> bytes | None:
        """The DECODED payload, or None when missing/torn/corrupt.
        Verified reads are memoized so the resume path (latest_valid ->
        has -> load) does not re-read multi-MB blobs; writes/prunes drop
        what they make stale.  Both frame generations read back: RTC2 carries a
        codec byte (decode after the crc passes), RTC1 is the legacy
        uncompressed layout — a new job resumes an old job's spill
        unchanged."""
        if path in self._cache:
            return self._cache[path]
        with obs.span("rabit.load.disk.read") as sp:
            try:
                frame = path.read_bytes()
            except FileNotFoundError:
                return None
            blob = self._decode_frame(frame)
            sp.set(encoded=len(frame), raw=0 if blob is None else len(blob))
        if blob is None:
            print(f"[rabit_tpu] checkpoint store: ignoring unreadable blob "
                  f"{path} (missing/invalid RTC1/RTC2 header or crc "
                  f"mismatch)", flush=True)
            return None
        self._cache[path] = blob
        return blob

    @staticmethod
    def _decode_frame(raw: bytes) -> bytes | None:
        """The payload of one frame (any generation), decoded after its
        crc has passed; None for a torn or unknown one."""
        if len(raw) >= _HDR3.size and raw[:4] == _MAGIC3:
            _magic, codec_id, crc, n, _epoch = _HDR3.unpack_from(raw)
            enc = raw[_HDR3.size:]
        elif len(raw) >= _HDR2.size and raw[:4] == _MAGIC2:
            _magic, codec_id, crc, n = _HDR2.unpack_from(raw)
            enc = raw[_HDR2.size:]
        elif len(raw) >= _HDR.size and raw[:4] == _MAGIC:
            _magic, crc, n = _HDR.unpack_from(raw)
            codec_id, enc = 0, raw[_HDR.size:]
        else:
            return None
        if len(enc) != n or zlib.crc32(enc) != crc:
            return None
        if raw[:4] == _MAGIC:
            return enc
        from rabit_tpu.compress import get_codec_by_id

        try:
            return get_codec_by_id(codec_id).decode_bytes(enc)
        except (ValueError, zlib.error):
            return None  # unknown codec / stream the crc cannot vouch for

    def epoch_of(self, version: int) -> int:
        """World epoch recorded in the version's global frame (RTC3), 0
        for pre-elastic frames (RTC1/RTC2) or missing/torn files — the
        resume path uses it to tell which membership generation committed
        each version."""
        try:
            with open(self._gpath(version), "rb") as f:
                head = f.read(_HDR3.size)
        except OSError:
            return 0
        if len(head) >= _HDR3.size and head[:4] == _MAGIC3:
            return _HDR3.unpack_from(head)[4]
        return 0

    def has(self, version: int) -> bool:
        """True only for a version whose global blob passes the integrity
        check — the resume consensus must not promise bytes it cannot
        serve."""
        return version > 0 and self._read_checked(self._gpath(version)) is not None

    def load_global(self, version: int) -> bytes:
        blob = self._read_checked(self._gpath(version))
        if blob is None:
            raise RuntimeError(
                f"checkpoint store: global v{version} for rank {self.rank} "
                f"is missing or corrupt ({self._gpath(version)})"
            )
        return blob

    def load_local(self, version: int) -> bytes | None:
        return self._read_checked(self._lpath(version))
