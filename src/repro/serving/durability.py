"""Durability for the mutable index: write-ahead mutation log, atomic
snapshots, crash-point fault injection, and snapshot+replay recovery.

The paper's families make LSH parameters *small*, so a served index's
durable identity is tiny: the family config + the mutation history. This
module persists exactly that. ``DurableLSHService`` wraps every mutation
of ``LSHService`` in a write-ahead commit:

* **WAL** (``MutationLog``): an append-only log of mutation records —
  insert batches (stored as the raw items; replay re-hashes them through
  the fused ``hash_keys`` path), delete id-sets, and compact/rebalance
  epoch markers. Records are framed ``[u32 length][u32 crc32-of-head]
  [head][raw blobs]`` (each blob carries a 64-bit xor-fold in the head)
  at 4 KiB-aligned offsets in preallocated, prezeroed segments, written
  ``O_DIRECT`` + ``fdatasync`` where the filesystem allows (buffered +
  ``fdatasync`` otherwise) on a committer thread that overlaps the
  device-side apply — near-zero commit CPU, which is what holds the
  bench-ingest gate (WAL-on insert throughput within 10% of WAL-off)
  even on one core. A mutation returns only after *both* the sync and
  the apply complete, so an operation is committed iff its append
  completed, and a failed apply cancels its record. A torn tail (a final
  record damaged by a crash mid-append) is tolerated on replay; the same
  damage with intact records after it raises ``WalCorrupted`` — never a
  silent partial store.
* **Snapshots**: periodic atomic dumps of the ``SegmentStore`` (segment
  arrays + ``host_state()``), written with the ``training/checkpoint.py``
  idiom — temp dir, per-array crc32 manifest, fsync, ``os.rename`` — so a
  crash mid-snapshot never corrupts the last complete one. Each snapshot
  rotates the WAL; older segments and snapshots are pruned.
* **Recovery** (``recover()``): restore the latest complete snapshot,
  replay the WAL suffix. Because the whole mutation plane is
  deterministic (fused hashing, water-fill routing, sequence-order
  effective ids, stable sorts), the recovered store answers queries
  **bit-identically** to the uninterrupted process. ``max_deltas``
  auto-compactions are deliberately *not* logged — replayed inserts
  re-trigger them at exactly the same points.
* **Fault injection** (``FaultInjector``): named crash points at every
  durability boundary — ``pre_wal_append`` / ``post_wal_append`` (either
  side of the commit), ``mid_snapshot`` (between the array dump and the
  rename), ``pre_apply_swap`` (between the epoch-marker commit and the
  pointer flip) — drive the chaos-matrix tests, plus armable transient IO
  failures (``TransientIOError``) that the serving scheduler's ingest
  lane retries with bounded backoff.

Health states: ``"cold"`` (constructed), ``"serving"``, ``"recovering"``
(inside ``recover()``), ``"degraded"`` (a recovery failed, or the
scheduler marked the namespace down after exhausting retries). Any
request against a non-serving durable service raises the typed
``ServiceUnavailable`` instead of hanging or answering from a
possibly-inconsistent store.
"""

from __future__ import annotations

import base64
import dataclasses
import itertools
import json
import mmap
import os
import pickle
import re
import shutil
import struct
import time
import zlib
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any

import jax
import numpy as np

from repro import tracing
from repro.core.index import ShardedLSHIndex
from repro.core.segments import SegmentStore, ShardedSegment, TableSegment
from repro.serving.lsh_service import LSHService


# ---------------------------------------------------------------------------
# Typed errors
# ---------------------------------------------------------------------------


class DurabilityError(RuntimeError):
    """Base of the durability error family."""


class WalCorrupted(DurabilityError):
    """The WAL is damaged before its tail (bad checksum, truncated frame
    in a non-final segment, lsn discontinuity) — replay refuses to build
    a silently partial store."""


class RecoveryError(DurabilityError):
    """Recovery cannot produce a consistent store (no complete snapshot,
    config mismatch, snapshot corruption, missing log suffix)."""


class TransientIOError(OSError):
    """A retryable IO failure on the durability plane — the scheduler's
    ingest lane retries these with bounded exponential backoff."""


class ServiceUnavailable(RuntimeError):
    """The namespace is degraded/recovering; the request was shed instead
    of served from a possibly-inconsistent store."""


class InjectedCrash(RuntimeError):
    """A ``FaultInjector`` crash point fired — stands in for process
    death in the chaos tests (state past the fired boundary is lost)."""


# ---------------------------------------------------------------------------
# Fault injection
# ---------------------------------------------------------------------------


CRASH_POINTS = ("pre_wal_append", "post_wal_append", "mid_snapshot",
                "pre_apply_swap")


class FaultInjector:
    """Armable faults at the named durability boundaries.

    ``crash_at(point, after=k)`` raises ``InjectedCrash`` the (k+1)-th
    time ``point`` fires (then disarms); ``fail_transient(point, times)``
    raises ``TransientIOError`` the next ``times`` firings (the retry
    path's test hook). ``fired`` records every firing in order.
    """

    def __init__(self):
        self._crash: dict[str, int] = {}
        self._transient: dict[str, int] = {}
        self.fired: list[str] = []

    @staticmethod
    def _check(point: str) -> None:
        if point not in CRASH_POINTS:
            raise ValueError(f"unknown crash point {point!r}; expected one "
                             f"of {CRASH_POINTS}")

    def crash_at(self, point: str, after: int = 0) -> "FaultInjector":
        self._check(point)
        self._crash[point] = int(after)
        return self

    def fail_transient(self, point: str, times: int = 1) -> "FaultInjector":
        self._check(point)
        self._transient[point] = int(times)
        return self

    def fire(self, point: str) -> None:
        self.fired.append(point)
        left = self._transient.get(point, 0)
        if left > 0:
            self._transient[point] = left - 1
            raise TransientIOError(
                f"injected transient IO failure at {point!r}")
        if point in self._crash:
            if self._crash[point] > 0:
                self._crash[point] -= 1
            else:
                del self._crash[point]
                raise InjectedCrash(f"injected crash at {point!r}")


# ---------------------------------------------------------------------------
# Record payloads: pytrees <-> bytes
# ---------------------------------------------------------------------------

# A record payload is one JSON head (lsn, kind, pytree skeleton, per-leaf
# dtype/shape/byte-length/fold) followed by the leaves as concatenated
# raw little-endian blobs. The skeleton is the pytree with every leaf
# replaced by a placeholder string (jax treats None as an empty subtree,
# so None can't mark leaf sites); registered-dataclass formats like
# CPTensor/TTTensor pickle structurally.
#
# Integrity is two-tier, sized to the commit hot path on one core: the
# frame's crc32 covers only the (small) head section, and each blob
# carries a 64-bit xor-fold — one streaming pass at memory bandwidth
# instead of a crc over megabytes of items, still flipping on any single
# damaged burst (torn write, zeroed block, bit flip).

_LEAF = "__leaf__"
_HEAD = struct.Struct("<I")
_FRAME = struct.Struct("<II")    # record length + crc32 of the head section
_ALIGN = 4096                    # records start on direct-IO block bounds


class _BlobDamage(Exception):
    """A record's head validated but a blob's fold did not (torn or
    corrupted item data). Internal to ``read_wal``'s torn-tail logic."""


def _aligned(n: int) -> int:
    return (int(n) + _ALIGN - 1) // _ALIGN * _ALIGN


def _fold64(arr: np.ndarray) -> int:
    b = arr.reshape(-1).view(np.uint8)
    n = b.nbytes - b.nbytes % 8
    acc = int(np.bitwise_xor.reduce(b[:n].view(np.uint64))) if n else 0
    if b.nbytes > n:
        acc ^= int.from_bytes(
            bytes(b[n:]) + b"\0" * (8 - b.nbytes + n), "little")
    return acc


def _tree_to_blobs(tree) -> tuple[dict, list[np.ndarray]]:
    if tree is None:
        return {"skeleton": None, "leaves": []}, []
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    skeleton = jax.tree_util.tree_unflatten(treedef, [_LEAF] * len(leaves))
    blobs = [np.ascontiguousarray(np.asarray(leaf)) for leaf in leaves]
    head = {"skeleton": base64.b64encode(pickle.dumps(skeleton)).decode(),
            "leaves": [{"dtype": b.dtype.str, "shape": list(b.shape),
                        "len": int(b.nbytes), "fold": _fold64(b)}
                       for b in blobs]}
    return head, blobs


def _encode_record(lsn: int, kind: str, tree) -> tuple[bytes, list]:
    """-> (frame header + head section, raw blob arrays to follow it)."""
    head, blobs = _tree_to_blobs(tree)
    head.update(lsn=int(lsn), kind=kind)
    hb = json.dumps(head).encode()
    sect = _HEAD.pack(len(hb)) + hb
    length = len(sect) + sum(b.nbytes for b in blobs)
    return _FRAME.pack(length, zlib.crc32(sect)) + sect, blobs


def _decode_record(payload) -> tuple[int, str, Any]:
    """Decode one payload (head crc already verified by the caller);
    raises ``_BlobDamage`` on a blob fold mismatch."""
    (hlen,) = _HEAD.unpack_from(payload, 0)
    head = json.loads(bytes(payload[_HEAD.size:_HEAD.size + hlen]).decode())
    if head["skeleton"] is None:
        return int(head["lsn"]), head["kind"], None
    skeleton = pickle.loads(base64.b64decode(head["skeleton"]))
    treedef = jax.tree_util.tree_structure(skeleton)
    leaves, off = [], _HEAD.size + hlen
    for spec in head["leaves"]:
        # bytes() realigns the slice so the uint64 fold view is valid
        raw = np.frombuffer(bytes(payload[off:off + spec["len"]]),
                            dtype=np.dtype(spec["dtype"]))
        arr = raw.reshape(spec["shape"])
        if _fold64(arr) != spec["fold"]:
            raise _BlobDamage(f"blob checksum mismatch at payload "
                              f"offset {off}")
        leaves.append(arr)
        off += spec["len"]
    return (int(head["lsn"]), head["kind"],
            jax.tree_util.tree_unflatten(treedef, leaves))


# ---------------------------------------------------------------------------
# Write-ahead log
# ---------------------------------------------------------------------------

_WAL_RE = re.compile(r"wal_(\d{12})\.log")
_SNAP_RE = re.compile(r"snap_(\d{12})")


def _wal_files(directory: str) -> list[tuple[int, str]]:
    """(start_lsn, path) of every WAL segment, in lsn order."""
    out = []
    for name in os.listdir(directory):
        m = _WAL_RE.fullmatch(name)
        if m:
            out.append((int(m.group(1)), os.path.join(directory, name)))
    return sorted(out)


def _head_valid(data, off) -> bool:
    """Does a plausible record with a passing head crc start at off?"""
    if len(data) - off < _FRAME.size:
        return False
    length, crc = _FRAME.unpack_from(data, off)
    if length < _HEAD.size or off + _FRAME.size + length > len(data):
        return False
    (hlen,) = _HEAD.unpack_from(data, off + _FRAME.size)
    sect_end = off + _FRAME.size + _HEAD.size + hlen
    if _HEAD.size + hlen > length:
        return False
    return zlib.crc32(data[off + _FRAME.size:sect_end]) == crc


def _any_record_beyond(data, off) -> bool:
    """Scan aligned offsets strictly past ``off`` (the damaged record's
    start) for any valid-looking record — distinguishes a torn tail
    (nothing but zeros/garbage follows) from mid-log corruption (intact
    records follow the damage)."""
    off = _aligned(off + 1)
    while off < len(data):
        if _head_valid(data, off):
            return True
        off += _ALIGN
    return False


def read_wal(directory: str):
    """Scan every WAL segment -> (records, tail).

    Records sit at ``_ALIGN``-ed offsets; a zero length field marks the
    end of a prezeroed segment. ``records`` is ``[(lsn, kind, tree),
    ...]`` in commit order; ``tail`` is ``(path, valid_end)`` of the
    newest segment — the byte offset after its last whole record, where
    recovery resumes appending. A damaged final record *of the newest
    segment* (short frame, failed head checksum, failed blob fold) is a
    torn tail — a crash mid-append — and is dropped; the same damage with
    intact records after it, or in any older segment, raises
    ``WalCorrupted``, as does an lsn discontinuity between records.
    """
    files = _wal_files(directory)
    records: list[tuple[int, str, Any]] = []
    tail = None
    for idx, (start, path) in enumerate(files):
        last = idx == len(files) - 1
        with open(path, "rb") as f:
            data = f.read()
        view = memoryview(data)
        off = 0
        while len(data) - off >= _FRAME.size:
            length, crc = _FRAME.unpack_from(data, off)
            if length == 0:
                break                       # prezeroed tail: end of log
            end = off + _FRAME.size + length
            bad = None
            if length < _HEAD.size or end > len(data):
                bad = "truncated record"
            elif not _head_valid(data, off):
                bad = "checksum mismatch"
            else:
                try:
                    rec = _decode_record(view[off + _FRAME.size:end])
                except _BlobDamage as e:
                    bad = str(e)
            if bad is None:
                records.append(rec)
                off = _aligned(end)
                continue
            if last and not _any_record_beyond(data, off):
                break                       # torn tail: crash mid-append
            raise WalCorrupted(f"{path}: {bad} at offset {off}")
        if last:
            tail = (path, off)
    for (a, _, _), (b, _, _) in zip(records, records[1:]):
        if b != a + 1:
            raise WalCorrupted(f"lsn discontinuity: record {a} followed "
                               f"by {b}")
    return records, tail


_MIN_SEG = 256 * 1024            # first segment; sized up as records grow
_MAX_SEG = 64 * 1024 * 1024


class MutationLog:
    """One open WAL segment with an overlapped, near-zero-CPU commit.

    Segments are preallocated and prezeroed, records start on ``_ALIGN``
    boundaries, and appends go through ``O_DIRECT`` where the filesystem
    allows it (buffered + ``fdatasync`` otherwise) — with the extents
    already materialized, the per-commit ``fdatasync`` is a device flush
    with no metadata journaling, so almost the whole append is DMA/iowait
    the committer thread can hide under the caller's apply even on one
    core.

    ``begin`` fires ``pre_wal_append`` on the caller's thread (nothing is
    written if it faults) and hands the encode + write + sync to a single
    committer thread. ``finish`` joins the committer and fires
    ``post_wal_append`` — when it returns, the record survives process
    death. ``cancel`` rolls a begun record back out of the log (the apply
    failed, so the record must not replay). ``append`` is the plain
    synchronous composition for small records (epoch markers). On any
    failure mid-append the record's region is wound back to zeros so a
    retry never leaves a torn record *inside* the log. ``rotate(lsn)``
    starts a fresh segment (after a snapshot covering ``lsn``).
    """

    def __init__(self, directory: str, *, next_lsn: int,
                 path: str | None = None, append_at: int = 0,
                 injector: FaultInjector | None = None):
        self.directory = directory
        self.next_lsn = int(next_lsn)
        self.injector = injector or FaultInjector()
        self._committer = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="wal-commit")
        self._buf: mmap.mmap | None = None
        self._fd = None
        self._max_record = 0
        self._open_segment(
            path or os.path.join(directory,
                                 f"wal_{self.next_lsn:012d}.log"),
            append_at=append_at)

    # -- segment management --------------------------------------------------

    def _open_segment(self, path: str, *, append_at: int = 0,
                      min_size: int = 0) -> None:
        """Open ``path`` for appending at ``append_at`` (aligned up):
        anything beyond — a torn tail, a stale prezeroed area — is cut
        and re-zeroed out to the segment's preallocated size."""
        if self._fd is not None:
            os.close(self._fd)
        self._path = path
        self._off = _aligned(append_at)
        size = max(_MIN_SEG, _aligned(min_size), self._off,
                   _aligned(os.path.getsize(path))
                   if os.path.exists(path) else 0)
        with open(path, "r+b" if os.path.exists(path) else "w+b") as f:
            f.truncate(self._off)
            f.seek(self._off)
            left = size - self._off
            chunk = b"\0" * min(1 << 22, max(left, 1))
            while left > 0:
                left -= f.write(chunk[:min(len(chunk), left)])
            f.flush()
            os.fsync(f.fileno())
        self._size = size
        try:
            self._fd = os.open(path, os.O_WRONLY | os.O_DIRECT)
            self._direct = True
        except OSError:                      # filesystem without direct IO
            self._fd = os.open(path, os.O_WRONLY)
            self._direct = False

    def _staging(self, n: int) -> mmap.mmap:
        """A reusable page-aligned buffer of >= n bytes (direct IO needs
        block-aligned memory; mmap pages are)."""
        if self._buf is None or len(self._buf) < n:
            if self._buf is not None:
                self._buf.close()
            self._buf = mmap.mmap(-1, max(_aligned(n), _MIN_SEG))
        return self._buf

    def _wind_back(self, start: int, need: int) -> None:
        """Return the region of a failed/cancelled append to zeros."""
        try:
            if self._direct:
                buf = self._staging(need)
                buf[:need] = b"\0" * need
                os.pwrite(self._fd, memoryview(buf)[:need], start)
                os.fdatasync(self._fd)
            else:
                os.truncate(self._path, start)
        except OSError:
            pass
        self._off = start

    def _append_sync(self, kind: str, tree,
                     seq: int | None = None) -> tuple[int, int, int]:
        """Committer-thread body: -> (lsn, record offset, aligned size).
        One ``lsh.wal.append`` span carrying the caller's ``seq``, with a
        ``lsh.wal.sync`` child around the ``fdatasync``."""
        with tracing.span("lsh.wal.append", seq=seq, lsn=self.next_lsn,
                          kind=kind):
            return self._append_record(kind, tree)

    def _append_record(self, kind: str, tree) -> tuple[int, int, int]:
        frame, blobs = _encode_record(self.next_lsn, kind, tree)
        need = _aligned(len(frame) + sum(b.nbytes for b in blobs))
        self._max_record = max(self._max_record, need)
        if self._off + need > self._size:
            self._open_segment(
                os.path.join(self.directory,
                             f"wal_{self.next_lsn:012d}.log"),
                min_size=max(32 * need, min(32 * self._max_record,
                                            _MAX_SEG)))
        start = self._off
        try:
            if self._direct:
                buf = self._staging(need)
                buf[:len(frame)] = frame
                pos = len(frame)
                for b in blobs:
                    if b.nbytes:
                        buf[pos:pos + b.nbytes] = b.reshape(-1).view(
                            np.uint8).data
                        pos += b.nbytes
                buf[pos:need] = b"\0" * (need - pos)
                os.pwrite(self._fd, memoryview(buf)[:need], start)
            else:
                os.lseek(self._fd, start, os.SEEK_SET)
                os.write(self._fd, frame)
                for b in blobs:
                    if b.nbytes:
                        os.write(self._fd, b.reshape(-1).view(np.uint8).data)
            with tracing.span("lsh.wal.sync"):
                os.fdatasync(self._fd)
        except BaseException:
            self._wind_back(start, need)
            raise
        self._off = start + need
        lsn = self.next_lsn
        self.next_lsn += 1
        return lsn, start, need

    # -- commit protocol -----------------------------------------------------

    def begin(self, kind: str, tree) -> Future:
        """Start committing one record. Raises before touching the file
        on an armed ``pre_wal_append`` fault (the record is *not*
        committed); otherwise the write + sync proceed on the committer
        thread while the caller applies the mutation in memory. The
        committer's spans carry the caller's ``tracing.current_seq()``."""
        self.injector.fire("pre_wal_append")
        return self._committer.submit(self._append_sync, kind, tree,
                                      tracing.current_seq())

    def finish(self, token: Future) -> int:
        """Join a ``begin``; -> the record's lsn, now durable. An armed
        ``post_wal_append`` fault fires with the record already synced."""
        lsn, _, _ = token.result()
        self.injector.fire("post_wal_append")
        return lsn

    def cancel(self, token: Future) -> None:
        """Roll a begun record back out (the apply failed): if the
        committer got it onto disk, zero it back off; a committer failure
        already wound itself back (and is swallowed — the caller is
        re-raising the apply's error)."""
        try:
            _, start, need = token.result()
        except BaseException:
            return
        self._wind_back(start, need)
        self.next_lsn -= 1

    def append(self, kind: str, tree) -> int:
        """Synchronous commit of one record; returns its lsn."""
        return self.finish(self.begin(kind, tree))

    def rotate(self, lsn: int) -> None:
        path = os.path.join(self.directory, f"wal_{int(lsn):012d}.log")
        if path == self._path and self._off == 0:
            return                           # already a fresh, empty segment
        self._open_segment(path,
                           min_size=min(32 * self._max_record, _MAX_SEG))

    def close(self) -> None:
        if self._fd is not None:
            self._committer.shutdown(wait=True)
            os.close(self._fd)
            self._fd = None
            if self._buf is not None:
                self._buf.close()
                self._buf = None


# ---------------------------------------------------------------------------
# Atomic snapshots
# ---------------------------------------------------------------------------


def _service_config(svc: LSHService) -> dict:
    """The identity a snapshot is only valid for: family + index layout.
    Recovery compares this against the recovering service's own config and
    refuses on any mismatch — replay through a different family would
    silently produce a different index."""
    fam, index = svc.index.family, svc.index
    return {
        "index": type(index).__name__,
        "metric": index.metric,
        "seed": int(index.seed),
        "kind": fam.kind,
        "num_codes": int(fam.num_codes),
        "num_tables": int(fam.num_tables),
        "bucket_width": float(fam.bucket_width),
        "shards": int(getattr(index, "shards", 0)),
        "bucket_cap": index.bucket_cap,
        "max_deltas": int(index.max_deltas),
    }


def latest_snapshot(directory: str) -> int | None:
    """lsn of the newest *complete* snapshot (manifest present), if any."""
    if not os.path.isdir(directory):
        return None
    lsns = []
    for name in os.listdir(directory):
        m = _SNAP_RE.fullmatch(name)
        if m and os.path.exists(os.path.join(directory, name,
                                             "manifest.json")):
            lsns.append(int(m.group(1)))
    return max(lsns) if lsns else None


def write_snapshot(directory: str, lsn: int, svc: LSHService,
                   injector: FaultInjector | None = None) -> str:
    """Atomically dump the service's ``SegmentStore`` as of log position
    ``lsn`` (= number of WAL records the state includes). checkpoint.py's
    idiom: write everything into ``snap_<lsn>.tmp/``, fsync the crc32
    manifest, then one ``os.rename`` publishes it — a crash anywhere in
    between leaves only an ignored ``.tmp`` directory behind."""
    injector = injector or FaultInjector()
    store = svc.index.store
    state = store.host_state()
    name = f"snap_{int(lsn):012d}"
    tmp = os.path.join(directory, name + ".tmp")
    final = os.path.join(directory, name)
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    counter = itertools.count()

    def put(arr) -> dict:
        arr = np.asarray(arr)
        fname = f"arr_{next(counter):05d}.npy"
        np.save(os.path.join(tmp, fname), arr, allow_pickle=False)
        return {"file": fname,
                "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes())}

    manifest: dict = {"lsn": int(lsn), "config": _service_config(svc),
                      "seq_len": state["seq_len"],
                      "live_window": state["live_window"], "segments": []}
    for seg, pos in zip([store.base] + store.deltas, state["slot_pos"]):
        leaves, treedef = jax.tree_util.tree_flatten(seg.corpus)
        skeleton = jax.tree_util.tree_unflatten(treedef,
                                                [_LEAF] * len(leaves))
        entry = {"type": type(seg).__name__, "cap": int(seg.cap),
                 "keys": put(seg.keys), "sorted_keys": put(seg.sorted_keys),
                 "perm": put(seg.perm), "slot_pos": put(pos),
                 "corpus_skeleton": base64.b64encode(
                     pickle.dumps(skeleton)).decode(),
                 "corpus": [put(leaf) for leaf in leaves]}
        if isinstance(seg, ShardedSegment):
            entry["counts"] = [int(c) for c in seg.counts]
        manifest["segments"].append(entry)
    injector.fire("mid_snapshot")
    manifest["live_host"] = put(state["live_host"])
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def load_snapshot(directory: str, lsn: int, config: dict):
    """-> (segments, host_state) of snapshot ``lsn``, crc-verified.
    Raises ``RecoveryError`` on a config mismatch or corrupt array."""
    path = os.path.join(directory, f"snap_{int(lsn):012d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    diffs = {k: (manifest["config"].get(k), v) for k, v in config.items()
             if manifest["config"].get(k) != v}
    if diffs:
        raise RecoveryError(
            f"snapshot {path} was written by a differently-configured "
            f"service; mismatched (snapshot, live) fields: {diffs}")

    def get(ref: dict) -> np.ndarray:
        arr = np.load(os.path.join(path, ref["file"]), allow_pickle=False)
        if zlib.crc32(np.ascontiguousarray(arr).tobytes()) != ref["crc32"]:
            raise RecoveryError(
                f"snapshot corruption in {path}/{ref['file']}")
        return arr

    segs, slot_pos = [], []
    for entry in manifest["segments"]:
        skeleton = pickle.loads(base64.b64decode(entry["corpus_skeleton"]))
        treedef = jax.tree_util.tree_structure(skeleton)
        corpus = jax.tree_util.tree_unflatten(
            treedef, [jax.numpy.asarray(get(r)) for r in entry["corpus"]])
        keys = jax.numpy.asarray(get(entry["keys"]))
        sorted_keys = jax.numpy.asarray(get(entry["sorted_keys"]))
        perm = jax.numpy.asarray(get(entry["perm"]))
        if entry["type"] == "ShardedSegment":
            segs.append(ShardedSegment(
                keys=keys, sorted_keys=sorted_keys, perm=perm, corpus=corpus,
                cap=int(entry["cap"]), counts=tuple(entry["counts"])))
        else:
            segs.append(TableSegment(
                keys=keys, sorted_keys=sorted_keys, perm=perm, corpus=corpus,
                cap=int(entry["cap"])))
        slot_pos.append(get(entry["slot_pos"]))
    state = {"slot_pos": slot_pos, "live_host": get(manifest["live_host"]),
             "seq_len": int(manifest["seq_len"]),
             "live_window": bool(manifest["live_window"])}
    return segs, state


def _prune(directory: str, cover: int, keep_snapshots: int) -> None:
    """Drop snapshots beyond the newest ``keep_snapshots`` and every WAL
    segment that ends at or before the oldest kept snapshot."""
    snaps = sorted(
        int(m.group(1)) for name in os.listdir(directory)
        if (m := _SNAP_RE.fullmatch(name))
        and os.path.exists(os.path.join(directory, name, "manifest.json")))
    for lsn in snaps[:-keep_snapshots] if keep_snapshots else snaps:
        shutil.rmtree(os.path.join(directory, f"snap_{lsn:012d}"),
                      ignore_errors=True)
    oldest_kept = snaps[-keep_snapshots] if snaps else cover
    files = _wal_files(directory)
    for (start, path), (next_start, _) in zip(files, files[1:]):
        if next_start <= oldest_kept:
            os.remove(path)


# ---------------------------------------------------------------------------
# Durable service
# ---------------------------------------------------------------------------


class DurableLSHService(LSHService):
    """``LSHService`` whose mutations are write-ahead committed.

    ``build()`` starts a fresh durable identity under ``directory``
    (snapshot at lsn 0 + a new WAL); every ``insert``/``delete`` and
    every published swap appends an fsync'd record, overlapped with the
    in-memory apply but joined before the call returns — committed iff
    appended. Every ``snapshot_every`` records a new snapshot is written
    and the WAL rotated. ``recover()`` — on a freshly constructed,
    identically-configured instance, or in place on a degraded one —
    restores the latest complete snapshot and replays the log suffix,
    bit-identically.
    """

    def __init__(self, family, directory: str, *, snapshot_every: int = 512,
                 keep_snapshots: int = 2,
                 injector: FaultInjector | None = None, **kwargs):
        super().__init__(family, **kwargs)
        if int(snapshot_every) < 1:
            raise ValueError(
                f"snapshot_every must be >= 1, got {snapshot_every}")
        self.directory = str(directory)
        self.snapshot_every = int(snapshot_every)
        self.keep_snapshots = int(keep_snapshots)
        self.injector = injector or FaultInjector()
        self.health = "cold"
        self._log: MutationLog | None = None
        self._cover = 0          # lsn the latest snapshot covers

    # -- lifecycle ----------------------------------------------------------

    def build(self, corpus, batch_size: int = 2048) -> "DurableLSHService":
        """(Re)build from a corpus and start a fresh durable identity:
        prior snapshots/WAL under the directory belong to a corpus this
        instance no longer serves and are removed."""
        os.makedirs(self.directory, exist_ok=True)
        self._close_log()
        for name in os.listdir(self.directory):
            if _WAL_RE.fullmatch(name):
                os.remove(os.path.join(self.directory, name))
            elif _SNAP_RE.fullmatch(name) or _SNAP_RE.fullmatch(
                    name.removesuffix(".tmp")):
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)
        super().build(corpus, batch_size=batch_size)
        self._write_snapshot(0)
        self._cover = 0
        self._log = MutationLog(self.directory, next_lsn=0,
                                injector=self.injector)
        self.health = "serving"
        return self

    def close(self) -> None:
        self._close_log()

    def _close_log(self) -> None:
        if self._log is not None:
            self._log.close()
            self._log = None

    def _require_serving(self, what: str) -> None:
        if self.health != "serving":
            self.stats.unavailable += 1
            raise ServiceUnavailable(
                f"{what} rejected: durable service is {self.health!r} "
                "(recover() restores it to 'serving')")

    # -- write-ahead commit --------------------------------------------------

    def _commit(self, kind: str, tree) -> int:
        with tracing.span("lsh.wal.finish") as wait:
            lsn = self._log.append(kind, tree)
        self.stats.wal_ms += wait.seconds * 1e3
        self.stats.wal_appends += 1
        return lsn

    def _commit_overlapped(self, kind: str, tree, apply_fn) -> None:
        """Commit a record while ``apply_fn`` runs: the fsync proceeds on
        the committer thread under the device-side apply, and the caller
        returns only once both are done — externally the same
        commit-then-apply contract as ``_commit``, without paying the two
        latencies serially. An apply failure cancels the record (it must
        not replay); a commit failure after a successful apply leaves
        memory ahead of the log, so the service degrades rather than
        commit further ops on top of unlogged state.

        ``wal_ms`` takes the caller's two waits on the log: the hand-off
        (``lsh.wal.begin``) and the wait beyond the apply
        (``lsh.wal.finish``)."""
        with tracing.span("lsh.wal.begin") as begin:
            token = self._log.begin(kind, tree)
        try:
            apply_fn()
        except BaseException:
            self._log.cancel(token)
            raise
        try:
            with tracing.span("lsh.wal.finish") as wait:
                self._log.finish(token)
        except InjectedCrash:
            raise               # durable AND applied: consistent as it lies
        except BaseException:
            self.health = "degraded"
            raise
        self.stats.wal_ms += (begin.seconds + wait.seconds) * 1e3
        self.stats.wal_appends += 1

    def _maybe_snapshot(self) -> None:
        if self._log.next_lsn - self._cover >= self.snapshot_every:
            self.snapshot()

    def snapshot(self) -> "DurableLSHService":
        """Write a snapshot now, rotate the WAL, prune old state."""
        self._require_serving("snapshot")
        lsn = self._log.next_lsn
        self._write_snapshot(lsn)
        self._cover = lsn
        self._log.rotate(lsn)
        _prune(self.directory, lsn, self.keep_snapshots)
        return self

    def _write_snapshot(self, lsn: int) -> None:
        t0 = time.perf_counter()
        write_snapshot(self.directory, lsn, self, self.injector)
        self.stats.snapshot_ms += (time.perf_counter() - t0) * 1e3
        self.stats.snapshots += 1

    # -- mutations (logged) --------------------------------------------------

    def query_arrays(self, queries, topk: int = 10, **kwargs):
        self._require_serving("query")
        return super().query_arrays(queries, topk, **kwargs)

    def insert(self, batch, batch_size: int = 2048) -> "DurableLSHService":
        self._require_serving("insert")
        batch = np.asarray(batch)      # one materialization: log + apply
        self._commit_overlapped(
            "insert", batch,
            lambda: LSHService.insert(self, batch, batch_size=batch_size))
        self._maybe_snapshot()
        return self

    def delete(self, ids) -> int:
        self._require_serving("delete")
        ids = np.asarray(ids)
        out = []
        self._commit_overlapped(
            "delete", ids,
            lambda: out.append(LSHService.delete(self, ids)))
        self._maybe_snapshot()
        return out[0]

    def apply_swap(self, pending) -> "DurableLSHService":
        """Publish a prepared swap with an epoch marker ahead of the flip.
        The marker commits only after the same staleness check the flip
        itself enforces, so a record is never logged for a swap that then
        refuses to publish."""
        if pending is None:
            return self
        self._require_serving("apply_swap")
        store = self._mutable_index().store
        if (store is not pending.source
                or store.generation != pending.generation):
            return super().apply_swap(pending)   # the standard stale error
        self._commit(pending.kind, None)
        self.injector.fire("pre_apply_swap")
        super().apply_swap(pending)
        self._maybe_snapshot()
        return self

    # -- recovery ------------------------------------------------------------

    def recover(self) -> "DurableLSHService":
        """Restore the latest complete snapshot + replay the WAL suffix.

        Replays through the plain ``LSHService`` mutation path (no
        re-logging); the log's own torn tail, if any, is truncated before
        the WAL reopens for appends. On any failure the service lands in
        ``"degraded"`` and the error propagates — it never half-serves.
        """
        t0 = time.perf_counter()
        self.health = "recovering"
        self._close_log()
        try:
            lsn = latest_snapshot(self.directory)
            if lsn is None:
                raise RecoveryError(
                    f"no complete snapshot under {self.directory!r}; "
                    "nothing to recover from")
            segs, state = load_snapshot(self.directory, lsn,
                                        _service_config(self))
            self._install(segs, state)
            records, tail = read_wal(self.directory)
            expect = lsn
            for rec_lsn, kind, tree in records:
                if rec_lsn < lsn:
                    continue
                if rec_lsn != expect:
                    raise RecoveryError(
                        f"WAL gap: snapshot covers lsn {lsn}, expected "
                        f"record {expect} next but found {rec_lsn}")
                self._replay(kind, tree)
                expect += 1
            if tail is not None:
                path, valid_end = tail         # reopen past the last whole
                self._log = MutationLog(self.directory, next_lsn=expect,
                                        path=path, append_at=valid_end,
                                        injector=self.injector)
            else:
                self._log = MutationLog(self.directory, next_lsn=expect,
                                        injector=self.injector)
            self._cover = lsn
        except BaseException:
            self.health = "degraded"
            raise
        self.stats.recoveries += 1
        self.stats.recovery_ms += (time.perf_counter() - t0) * 1e3
        self.health = "serving"
        return self

    def _install(self, segs, state) -> None:
        index = self._mutable_index()
        index._reset_mutation_state()
        if isinstance(index, ShardedLSHIndex):
            from repro.distributed import index_sharding
            index.mesh, index.mesh_axis = index_sharding.resolve_mesh(
                int(index.shards))
            if index.mesh is not None:
                segs = [index._place_segment(s) for s in segs]
            index.store = SegmentStore.restore(segs, state,
                                               place=index._place())
            index._corpus = None
        else:
            index.store = SegmentStore.restore(segs, state)
        self.stats.reset_mutations()
        self._track_shards()

    def _replay(self, kind: str, tree) -> None:
        # Explicitly the base-class methods: replay must apply, not re-log.
        if kind == "insert":
            LSHService.insert(self, tree)
        elif kind == "delete":
            LSHService.delete(self, tree)
        elif kind == "compact":
            LSHService.apply_swap(self, LSHService.prepare_compact(self))
        elif kind == "rebalance":
            LSHService.apply_swap(self, LSHService.prepare_rebalance(self))
        else:
            raise RecoveryError(f"unknown WAL record kind {kind!r}")
