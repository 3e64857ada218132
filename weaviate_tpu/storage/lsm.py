"""LSM key-value store: memtables, WAL, sorted segments, compaction, blooms.

Reference: adapters/repos/db/lsmkv/ — Store/Bucket with four strategies
(strategies.go:22-25):

- "replace":    latest value wins (object store)
- "set":        per-key set of byte values with add/remove (legacy inverted)
- "map":        per-key map of subkey->value with per-pair tombstones
                (searchable inverted index with term frequencies)
- "roaringset": per-key bitmap with additions/deletions (filterable inverted
                index; lsmkv/roaringset/)

Same write path shape as the reference: mutation -> WAL append (commitlogger
.go) + memtable; flush -> sorted segment file + bloom sidecar
(segment_bloom_filters.go); reads merge memtable over segments newest-first;
compaction merges segment pairs (segment_group_compaction.go). Disk formats
are our own: segments carry a key-offset footer read at open, values are
fetched via mmap — no full segment load.
"""

from __future__ import annotations

import bisect
import io
import logging
import mmap
import os
import struct
import threading
import time
import zlib
from typing import Iterable, Iterator, Optional

import numpy as np

from weaviate_tpu.monitoring import perf
from weaviate_tpu.storage.bitmap import Bitmap

STRATEGY_REPLACE = "replace"
STRATEGY_SET = "set"
STRATEGY_MAP = "map"
STRATEGY_ROARINGSET = "roaringset"

STRATEGIES = (STRATEGY_REPLACE, STRATEGY_SET, STRATEGY_MAP, STRATEGY_ROARINGSET)

_SEG_MAGIC = b"WTSG"
_WAL_MAGIC = b"WTWL"   # v1: bare records, no per-record integrity
_WAL_MAGIC2 = b"WTW2"  # v2: <len u32><crc32 u32> framed records, skip-ahead replay
_WAL_MAX_REC = 1 << 26  # resync sanity bound: no legitimate record is >64 MiB
_TOMBSTONE = b"\x00__wt_tombstone__"
_MISSING = object()  # distinguishes absent map subkeys from None tombstones

# WAL record ops
_W_PUT = 1          # replace put / set add / map put
_W_DELETE = 2       # replace delete / set remove / map-pair delete / rs remove
_W_RS_ADD_MANY = 3  # roaringset bulk add
_W_RS_DEL_MANY = 4


class LsmError(RuntimeError):
    pass


def _write_frame(f, *parts: bytes) -> None:
    for p in parts:
        f.write(struct.pack("<I", len(p)))
        f.write(p)


def _read_frame(buf: memoryview, off: int) -> tuple[bytes, int]:
    (n,) = struct.unpack_from("<I", buf, off)
    off += 4
    return bytes(buf[off : off + n]), off + n


_BLOOM_MAGIC = b"WBLM"
_BLOOM_VERSION = 1


class BloomFilter:
    """Double-hashed bloom (segment_bloom_filters.go role).

    Hashes are blake2b (stdlib, C speed) — NEVER Python's builtin hash():
    that one is siphash-randomized PER PROCESS, so a bloom persisted by one
    process reads as noise in the next and ~99% of present keys report
    absent — silent loss of all flushed data across restarts. The bloom
    file is versioned; unversioned legacy files (written with the
    randomized hash) are discarded and rebuilt from the segment's key
    footer at open."""

    def __init__(self, n_items: int, bits_per_item: int = 10):
        self.m = max(64, n_items * bits_per_item)
        self.k = 7
        self.bits = np.zeros((self.m + 7) // 8, dtype=np.uint8)

    def _hashes(self, key: bytes):
        import hashlib

        d = hashlib.blake2b(key, digest_size=16).digest()
        h1 = int.from_bytes(d[:8], "little")
        h2 = int.from_bytes(d[8:], "little") | 1
        for i in range(self.k):
            yield (h1 + i * h2) % self.m

    def add(self, key: bytes) -> None:
        for h in self._hashes(key):
            self.bits[h >> 3] |= 1 << (h & 7)

    def __contains__(self, key: bytes) -> bool:
        return all(self.bits[h >> 3] & (1 << (h & 7)) for h in self._hashes(key))

    def to_bytes(self) -> bytes:
        return (_BLOOM_MAGIC + struct.pack("<H", _BLOOM_VERSION)
                + struct.pack("<QI", self.m, self.k) + self.bits.tobytes())

    @classmethod
    def from_bytes(cls, data: bytes) -> Optional["BloomFilter"]:
        """None for legacy/corrupt files — the caller rebuilds and rewrites."""
        if len(data) < 18 or data[:4] != _BLOOM_MAGIC:
            return None
        (ver,) = struct.unpack_from("<H", data, 4)
        if ver != _BLOOM_VERSION:
            return None
        m, k = struct.unpack_from("<QI", data, 6)
        b = cls.__new__(cls)
        b.m, b.k = m, k
        b.bits = np.frombuffer(data, dtype=np.uint8, offset=18).copy()
        return b


# -- memtables ---------------------------------------------------------------


class _MemReplace:
    """approx_bytes is maintained INCREMENTALLY on every mutation in all
    four memtable strategies: the flush check runs it once per write, so a
    recompute-on-read implementation turns bulk import into O(n^2) (the
    reference keeps a running size too, lsmkv memtable `size` field)."""

    def __init__(self):
        self.data: dict[bytes, bytes] = {}  # value or _TOMBSTONE
        self._bytes = 0
        # the native copy of `data` that packed point gets ask
        # (lsm_native.MemMirror), made by the first one that finds this
        # generation non-empty (Bucket._mem_layer); `mirror_refused`: none
        # could be made (no memory), and the general path serves this
        # generation's point gets
        self.mirror = None
        self.mirror_refused = False

    def put(self, k, v):
        old = self.data.get(k)
        if old is None:
            self._bytes += len(k) + len(v)
        else:
            self._bytes += len(v) - len(old)
        self.data[k] = v

    def delete(self, k):
        self.put(k, _TOMBSTONE)

    def get(self, k):
        return self.data.get(k)

    def __len__(self):
        return len(self.data)

    def approx_bytes(self):
        return self._bytes


class _MemSet:
    def __init__(self):
        self.adds: dict[bytes, set[bytes]] = {}
        self.dels: dict[bytes, set[bytes]] = {}
        self._bytes = 0

    def add(self, k, v):
        s = self.adds.get(k)
        if s is None:
            s = self.adds[k] = set()
            self._bytes += len(k)
        if v not in s:
            s.add(v)
            self._bytes += len(v)
        d = self.dels.get(k)
        if d is not None and v in d:
            d.discard(v)
            self._bytes -= len(v)

    def remove(self, k, v):
        d = self.dels.get(k)
        if d is None:
            d = self.dels[k] = set()
            self._bytes += len(k)
        if v not in d:
            d.add(v)
            self._bytes += len(v)
        s = self.adds.get(k)
        if s is not None and v in s:
            s.discard(v)
            self._bytes -= len(v)

    def __len__(self):
        return len(self.adds) + len(self.dels)

    def approx_bytes(self):
        return self._bytes


class _MemMap:
    def __init__(self):
        # key -> {subkey: value or None(=tombstone)}
        self.data: dict[bytes, dict[bytes, Optional[bytes]]] = {}
        self._bytes = 0

    def put(self, k, sub, v):
        m = self.data.get(k)
        if m is None:
            m = self.data[k] = {}
            self._bytes += len(k)
        old = m.get(sub, _MISSING)
        if old is _MISSING:
            self._bytes += len(sub) + len(v or b"")
        else:
            self._bytes += len(v or b"") - len(old or b"")
        m[sub] = v

    def delete_pair(self, k, sub):
        self.put(k, sub, None)

    def __len__(self):
        return len(self.data)

    def approx_bytes(self):
        return self._bytes


class _MemRoaring:
    """Mutable int-sets in the memtable (O(1) per doc id); the immutable
    sorted-array Bitmap exists only at read/flush boundaries — building a
    Bitmap per write would re-sort the whole key on every object imported
    (the reference's roaringset memtable mutates sroar bitmaps in place for
    the same reason)."""

    def __init__(self):
        self.adds: dict[bytes, set[int]] = {}
        self.dels: dict[bytes, set[int]] = {}
        self._bytes = 0

    def add_many(self, k, ids: Iterable[int]):
        ids = [int(i) for i in ids]
        a = self.adds.get(k)
        if a is None:
            a = self.adds[k] = set()
            self._bytes += len(k)
        before = len(a)
        a.update(ids)
        self._bytes += 8 * (len(a) - before)
        d = self.dels.get(k)
        if d is not None:
            before = len(d)
            d.difference_update(ids)
            self._bytes -= 8 * (before - len(d))

    def del_many(self, k, ids: Iterable[int]):
        ids = [int(i) for i in ids]
        d = self.dels.get(k)
        if d is None:
            d = self.dels[k] = set()
            self._bytes += len(k)
        before = len(d)
        d.update(ids)
        self._bytes += 8 * (len(d) - before)
        a = self.adds.get(k)
        if a is not None:
            before = len(a)
            a.difference_update(ids)
            self._bytes -= 8 * (before - len(a))

    def __len__(self):
        return len(self.adds) + len(self.dels)

    def approx_bytes(self):
        return self._bytes


# -- segments ----------------------------------------------------------------


class Segment:
    """Immutable sorted segment with footer key index, mmap-backed values.

    Layout: magic | strategy u8 | count u64 | entries... | footer | footer_off
    u64. Entry payloads are strategy-specific; the footer lists (key, offset,
    length) sorted by key.
    """

    def __init__(self, path: str):
        self.path = path
        self._native_handle: Optional[int] = None   # storage/lsm_native.py
        self._f = open(path, "rb")
        self._mm = mmap.mmap(self._f.fileno(), 0, access=mmap.ACCESS_READ)
        mv = memoryview(self._mm)
        if bytes(mv[:4]) != _SEG_MAGIC:
            raise LsmError(f"bad segment magic in {path}")
        self.strategy = STRATEGIES[mv[4]]
        (footer_off,) = struct.unpack_from("<Q", mv, len(mv) - 8)
        (count,) = struct.unpack_from("<Q", mv, footer_off)
        off = footer_off + 8
        self.keys: list[bytes] = []
        self.offsets: list[tuple[int, int]] = []
        for _ in range(count):
            k, off = _read_frame(mv, off)
            o, ln = struct.unpack_from("<QQ", mv, off)
            off += 16
            self.keys.append(k)
            self.offsets.append((o, ln))
        bloom_path = path + ".bloom"
        self.bloom: Optional[BloomFilter] = None
        if os.path.exists(bloom_path):
            with open(bloom_path, "rb") as bf:
                self.bloom = BloomFilter.from_bytes(bf.read())
        if self.bloom is None:
            # missing, legacy (process-randomized hashes), or corrupt bloom:
            # rebuild from the key footer so lookups stay correct AND fast
            self.bloom = BloomFilter(len(self.keys))
            for k in self.keys:
                self.bloom.add(k)
            tmp = bloom_path + ".tmp"
            with open(tmp, "wb") as bf:
                bf.write(self.bloom.to_bytes())
            os.replace(tmp, bloom_path)

    def get_raw(self, key: bytes) -> Optional[bytes]:
        if self.bloom is not None and key not in self.bloom:
            return None
        view = self.view_raw(key)
        return None if view is None else bytes(view)

    def view_raw(self, key: bytes) -> Optional[memoryview]:
        """The value as a view of the mapping (valid while the segment is
        open: a caller holds the bucket's lock and copies what it keeps),
        found by the key footer alone: it is in memory, and its bisect costs
        a microsecond where the bloom's seven probes cost twenty in Python.
        For a caller that asks every segment for one large value
        (roaring_get: a posting of 775k ids is 6 MB)."""
        i = bisect.bisect_left(self.keys, key)
        if i >= len(self.keys) or self.keys[i] != key:
            return None
        o, ln = self.offsets[i]
        return memoryview(self._mm)[o : o + ln]

    def items_raw(self) -> Iterator[tuple[bytes, bytes]]:
        for k, (o, ln) in zip(self.keys, self.offsets):
            yield k, bytes(self._mm[o : o + ln])

    def close(self):
        from weaviate_tpu.storage import lsm_native

        lsm_native.seg_close(self)
        self._mm.close()
        self._f.close()

    @staticmethod
    def write(path: str, strategy: str, items: list[tuple[bytes, bytes]]) -> None:
        """items must be sorted by key; values are pre-encoded payloads."""
        tmp = path + ".tmp"
        bloom = BloomFilter(len(items))
        with open(tmp, "wb") as f:
            f.write(_SEG_MAGIC + bytes([STRATEGIES.index(strategy)]))
            footer: list[tuple[bytes, int, int]] = []
            for k, payload in items:
                off = f.tell()
                f.write(payload)
                footer.append((k, off, len(payload)))
                bloom.add(k)
            footer_off = f.tell()
            f.write(struct.pack("<Q", len(footer)))
            for k, o, ln in footer:
                _write_frame(f, k)
                f.write(struct.pack("<QQ", o, ln))
            f.write(struct.pack("<Q", footer_off))
            f.flush()
            os.fsync(f.fileno())
        with open(tmp + ".bloom", "wb") as f:
            f.write(bloom.to_bytes())
        os.replace(tmp + ".bloom", path + ".bloom")
        os.replace(tmp, path)


# payload codecs per strategy ------------------------------------------------


def _enc_set(adds: set[bytes], dels: set[bytes]) -> bytes:
    out = io.BytesIO()
    out.write(struct.pack("<II", len(adds), len(dels)))
    for v in sorted(adds):
        _write_frame(out, v)
    for v in sorted(dels):
        _write_frame(out, v)
    return out.getvalue()


def _dec_set(payload: bytes) -> tuple[set[bytes], set[bytes]]:
    mv = memoryview(payload)
    na, nd = struct.unpack_from("<II", mv, 0)
    off = 8
    adds, dels = set(), set()
    for _ in range(na):
        v, off = _read_frame(mv, off)
        adds.add(v)
    for _ in range(nd):
        v, off = _read_frame(mv, off)
        dels.add(v)
    return adds, dels


def _enc_map(m: dict[bytes, Optional[bytes]]) -> bytes:
    out = io.BytesIO()
    out.write(struct.pack("<I", len(m)))
    for sub in sorted(m):
        v = m[sub]
        _write_frame(out, sub)
        out.write(b"\x01" if v is None else b"\x00")
        _write_frame(out, v or b"")
    return out.getvalue()


# Fixed-stride map payload view for the postings hot path: when every entry
# in a map payload is an 8-byte subkey + 4-byte value (the inverted-index
# posting shape: docid u64 -> tf f32), the frame layout is a constant
# 21 bytes/entry (4B keylen + 8B key + 1B tomb + 4B vallen + 4B val), so the
# whole payload decodes as ONE numpy structured-array view instead of a
# per-entry Python loop (_dec_map) — the difference between ~4 µs and ~2 ms
# on a df=4000 posting list. Tombstoned pairs are written with an EMPTY
# value frame (_enc_map), which breaks the stride; the total-length check
# catches that and the caller falls back to the generic decode.
_MAP_FIXED_STRIDE = 21


def _map_fixed_dt(key_dtype: str, val_dtype: str) -> np.dtype:
    return np.dtype({
        "names": ["kl", "k", "tomb", "vl", "v"],
        "formats": ["<u4", key_dtype, "u1", "<u4", val_dtype],
        "offsets": [0, 4, 12, 13, 17],
        "itemsize": _MAP_FIXED_STRIDE,
    })


_MAP_FIXED_DTS = {
    (k, v): _map_fixed_dt(k, v)
    for k in ("<u8", ">u8") for v in ("<f4", "<u4")
}


def _dec_map_fixed(payload: bytes, key_dtype: str = "<u8",
                   val_dtype: str = "<f4"):
    """-> (doc_ids u64, vals) views, or None when the payload is not
    uniformly 8-byte-key/4-byte-value (caller must fall back). Tombstoned
    pairs always fail the vl==4 check (their value frame is empty), so a
    successful decode contains live pairs only."""
    if len(payload) < 4:
        return None
    (n,) = struct.unpack_from("<I", payload, 0)
    if len(payload) != 4 + n * _MAP_FIXED_STRIDE:
        return None
    dt = _MAP_FIXED_DTS.get((key_dtype, val_dtype)) or \
        _map_fixed_dt(key_dtype, val_dtype)
    rec = np.frombuffer(payload, dtype=dt, count=n, offset=4)
    if n and not ((rec["kl"] == 8).all() and (rec["vl"] == 4).all()):
        return None
    return rec["k"], rec["v"]


def _dec_map(payload: bytes) -> dict[bytes, Optional[bytes]]:
    mv = memoryview(payload)
    (n,) = struct.unpack_from("<I", mv, 0)
    off = 4
    out: dict[bytes, Optional[bytes]] = {}
    for _ in range(n):
        sub, off = _read_frame(mv, off)
        tomb = mv[off]
        off += 1
        v, off = _read_frame(mv, off)
        out[sub] = None if tomb else v
    return out


def _enc_roaring(adds: Bitmap, dels: Bitmap) -> bytes:
    a, d = adds.to_bytes(), dels.to_bytes()
    return struct.pack("<II", len(a), len(d)) + a + d


def _dec_roaring_views(payload: memoryview) -> tuple[np.ndarray, np.ndarray]:
    """(additions, deletions) of one layer as VIEWS of its payload
    (slices of a view copy nothing): for the reader that joins the layers
    of a posting, whose join is the copy. Valid while the segment is open
    (the bucket's lock)."""
    la, ld = struct.unpack_from("<II", payload, 0)
    return (Bitmap.ids_view(payload[8 : 8 + la]),
            Bitmap.ids_view(payload[8 + la : 8 + la + ld]))


def _dec_roaring(payload) -> tuple[Bitmap, Bitmap]:
    adds, dels = _dec_roaring_views(memoryview(payload))
    return Bitmap(adds.copy(), _sorted=True), Bitmap(dels.copy(), _sorted=True)


# -- bucket ------------------------------------------------------------------


class Bucket:
    """One named LSM bucket (lsmkv.Bucket)."""

    def __init__(
        self,
        path: str,
        strategy: str,
        memtable_max_bytes: int = 16 * 1024 * 1024,
        sync_writes: bool = False,
    ):
        if strategy not in STRATEGIES:
            raise LsmError(f"unknown strategy {strategy!r}")
        self.path = path
        self.strategy = strategy
        self.memtable_max_bytes = memtable_max_bytes
        self.sync_writes = sync_writes
        self._last_write = time.monotonic()
        self._lock = threading.RLock()
        os.makedirs(path, exist_ok=True)
        self._segments: list[Segment] = []  # oldest..newest
        for name in sorted(os.listdir(path)):
            if name.endswith(".seg"):
                self._segments.append(Segment(os.path.join(path, name)))
        self._seg_counter = (
            max(
                (int(s.path.split("/")[-1].split(".")[0]) for s in self._segments),
                default=-1,
            )
            + 1
        )
        self._mem = self._new_memtable()
        self._wal_path = os.path.join(path, "bucket.wal")
        self._replay_wal()
        self._wal = open(self._wal_path, "ab")
        if self._wal.tell() == 0:
            self._wal.write(_WAL_MAGIC2)
            self._wal.flush()
            self._wal_v2 = True
        else:
            # append in the format the file already carries; v1 files keep
            # v1 records until the next memtable flush rotates them to v2
            with open(self._wal_path, "rb") as f:
                self._wal_v2 = f.read(4) == _WAL_MAGIC2
        # native multi_get lifetime protection: calls run OUTSIDE the bucket
        # lock on a snapshot of the segments (and of the memtable's mirror),
        # so compaction must retire (not close) segments, and a flush the
        # mirror, while any call is in flight
        self._native_inflight = 0
        self._retired: list = []  # Segment | lsm_native.MemMirror

    def _retire(self, handle) -> None:
        """Close a replaced segment or a flushed memtable's mirror, or park
        it until in-flight native reads drain (caller holds the bucket
        lock)."""
        if self._native_inflight > 0:
            self._retired.append(handle)
        else:
            handle.close()

    def _native_exit(self) -> None:
        """Leave the native-read critical section (caller holds the lock)."""
        self._native_inflight -= 1
        if self._native_inflight == 0 and self._retired:
            for h in self._retired:
                h.close()
            self._retired.clear()

    def _new_memtable(self):
        return {
            STRATEGY_REPLACE: _MemReplace,
            STRATEGY_SET: _MemSet,
            STRATEGY_MAP: _MemMap,
            STRATEGY_ROARINGSET: _MemRoaring,
        }[self.strategy]()

    # -- WAL -----------------------------------------------------------------

    @staticmethod
    def _wal_payload(rec) -> bytes:
        """op(1) nparts(1) then length-prefixed frames — the record body."""
        buf = io.BytesIO()
        buf.write(bytes([rec[0]]))
        buf.write(bytes([len(rec) - 1]))
        for p in rec[1:]:
            _write_frame(buf, p)
        return buf.getvalue()

    def _wal_encode(self, records) -> bytes:
        """v2 frames each record as <len u32><crc32 u32><payload>: the crc
        makes a flipped byte DETECTABLE, and the length lets replay resync
        past a damaged record instead of abandoning everything after it
        (corrupt_commit_logs_fixer.go:1 semantics). Files that still carry
        the v1 magic keep receiving bare v1 records — formats never mix
        within one file; every memtable flush rotates the file to v2."""
        out = io.BytesIO()
        for rec in records:
            payload = self._wal_payload(rec)
            if len(payload) > _WAL_MAX_REC:
                # replay's resync sanity bound would treat a larger record
                # as corruption and silently drop it on restart — refuse
                # loudly at write time instead (roaring bulk ops chunk
                # their id payloads below this, see roaring_add_many)
                raise LsmError(
                    f"WAL record of {len(payload)} bytes exceeds the "
                    f"{_WAL_MAX_REC}-byte record bound")
            if self._wal_v2:
                out.write(struct.pack("<II", len(payload), zlib.crc32(payload)))
            out.write(payload)
        return out.getvalue()

    def _wal_append(self, op: int, *parts: bytes) -> None:
        self._wal.write(self._wal_encode([(op, *parts)]))
        self._last_write = time.monotonic()
        if self.sync_writes:
            self._wal.flush()
            os.fsync(self._wal.fileno())

    def _wal_append_many(self, records) -> None:
        """Many (op, *parts) records in ONE file write (and one fsync when
        sync_writes) — batch imports append thousands of postings per call
        and per-record writes would dominate."""
        self._wal.write(self._wal_encode(records))
        self._last_write = time.monotonic()
        if self.sync_writes:
            self._wal.flush()
            os.fsync(self._wal.fileno())

    def _replay_wal(self) -> None:
        self.wal_replay_stats: dict = {}
        if not os.path.exists(self._wal_path):
            return
        with open(self._wal_path, "rb") as f:
            data = f.read()
        if data[:4] == _WAL_MAGIC2:
            self._replay_wal_v2(data)
            return
        if data[:4] != _WAL_MAGIC:
            return
        mv = memoryview(data)
        off = 4
        n = len(data)
        try:
            while off < n:
                op = mv[off]
                nparts = mv[off + 1]
                off += 2
                parts = []
                for _ in range(nparts):
                    p, off = _read_frame(mv, off)
                    parts.append(p)
                self._apply(op, parts)
        except (struct.error, IndexError, ValueError):
            return  # torn tail: replay what parsed

    def _replay_wal_v2(self, data: bytes) -> None:
        """Replay a crc-framed WAL, SKIPPING corrupt regions: on a bad
        length or crc mismatch, scan forward for the next offset whose
        framing parses and checksums (cheap pre-filters: sane length, valid
        op byte, plausible part count — only survivors pay a crc), apply
        everything after it, and report the skipped span instead of
        silently dropping the tail.

        A trailing invalid span with no valid record after it is an
        ordinary crash-torn TAIL, not corruption: it's counted separately
        (torn_tail_bytes) and not warned about. After any damage the file
        is HEALED in place — rewritten with only the valid records — so
        the same bytes are never re-scanned or re-warned on the next
        restart, and appends never land after dead bytes."""
        n = len(data)
        off = 4
        stats = self.wal_replay_stats
        valid_spans: list[tuple[int, int]] = []

        def _valid_at(pos: int) -> Optional[int]:
            """Record end if a valid v2 record starts at pos, else None."""
            if pos + 8 > n:
                return None
            ln, crc = struct.unpack_from("<II", data, pos)
            if not 2 <= ln <= min(_WAL_MAX_REC, n - pos - 8):
                return None
            body = data[pos + 8 : pos + 8 + ln]
            if body[0] not in (_W_PUT, _W_DELETE, _W_RS_ADD_MANY, _W_RS_DEL_MANY):
                return None
            if body[1] > 16:
                return None
            if zlib.crc32(body) != crc:
                return None
            return pos + 8 + ln

        buf = np.frombuffer(data, np.uint8)

        def _skip(start: int) -> Optional[int]:
            # vectorized candidate pre-filter (same shape as
            # VectorLog._resync_v2): a valid record has a legal op byte at
            # +8 and a plausible part count at +9, so one numpy pass per
            # 1 MiB window shortlists positions and only survivors pay the
            # length-sanity + crc check — a multi-MB damaged span costs
            # window scans, not per-byte Python iterations
            pos = start + 1
            hit = None
            last = n - 10  # a minimal record is 8 header + 2 body bytes
            while pos <= last and hit is None:
                win = min(pos + (1 << 20), last + 1)
                ops = buf[pos + 8 : win + 8]
                nparts = buf[pos + 9 : win + 9]
                cands = np.flatnonzero(
                    ((ops >= _W_PUT) & (ops <= _W_RS_DEL_MANY)) & (nparts <= 16))
                for idx in cands.tolist():
                    if _valid_at(pos + idx) is not None:
                        hit = pos + idx
                        break
                pos = win
            if hit is None:
                # nothing valid after: a torn tail, not mid-file corruption
                stats["torn_tail_bytes"] = stats.get("torn_tail_bytes", 0) + (n - start)
            else:
                stats["skipped_bytes"] = stats.get("skipped_bytes", 0) + (hit - start)
                stats["skipped_regions"] = stats.get("skipped_regions", 0) + 1
            return hit

        while off < n:
            end = _valid_at(off)
            if end is None:
                nxt = _skip(off)
                if nxt is None:
                    break
                off = nxt
                continue
            body = memoryview(data)[off + 8 : end]
            op, nparts = body[0], body[1]
            parts = []
            p_off = 2
            for _ in range(nparts):
                p, p_off = _read_frame(body, p_off)
                parts.append(p)
            self._apply(op, parts)
            valid_spans.append((off, end))
            off = end
        if stats.get("skipped_bytes"):
            logging.getLogger(__name__).warning(
                "WAL %s: skipped %d corrupt byte(s) across %d region(s) "
                "during replay; records inside the damage are lost, "
                "everything outside it was recovered",
                self._wal_path,
                stats["skipped_bytes"],
                stats.get("skipped_regions", 0),
            )
        if stats.get("skipped_bytes") or stats.get("torn_tail_bytes"):
            # heal: rewrite with only the valid records (atomic), so the
            # damage is scanned and reported exactly once
            tmp = self._wal_path + ".heal"
            with open(tmp, "wb") as f:
                f.write(_WAL_MAGIC2)
                for s, e in valid_spans:
                    f.write(data[s:e])
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._wal_path)

    def _apply(self, op: int, parts: list[bytes]) -> None:
        m = self._mem
        if self.strategy == STRATEGY_REPLACE:
            if op == _W_PUT:
                m.put(parts[0], parts[1])
            elif op == _W_DELETE:
                m.delete(parts[0])
        elif self.strategy == STRATEGY_SET:
            if op == _W_PUT:
                m.add(parts[0], parts[1])
            elif op == _W_DELETE:
                m.remove(parts[0], parts[1])
        elif self.strategy == STRATEGY_MAP:
            if op == _W_PUT:
                m.put(parts[0], parts[1], parts[2])
            elif op == _W_DELETE:
                m.delete_pair(parts[0], parts[1])
        else:  # roaringset
            ids = np.frombuffer(parts[1], dtype="<u8")
            if op == _W_RS_ADD_MANY:
                m.add_many(parts[0], ids)
            elif op == _W_RS_DEL_MANY:
                m.del_many(parts[0], ids)

    # -- writes --------------------------------------------------------------

    def put(self, key: bytes, value: bytes) -> None:
        assert self.strategy == STRATEGY_REPLACE
        if value == _TOMBSTONE:
            # the delete marker is in-band: storing its exact bytes as a
            # value would read back as "deleted" — silent data loss. No
            # production codec can produce it (storobj images start 0x01,
            # uuid values are 16 bytes); refuse loudly instead of losing it.
            raise LsmError("value collides with the reserved tombstone marker")
        with self._lock:
            self._wal_append(_W_PUT, key, value)
            self._mem.put(key, value)
            if self._mem.mirror is not None:
                self._mirror_put(key, value)
            self._maybe_flush()

    def put_many(self, pairs) -> None:
        """Batched replace puts: one lock, one WAL write (batch import)."""
        assert self.strategy == STRATEGY_REPLACE
        pairs = list(pairs)
        if not pairs:
            return
        if any(v == _TOMBSTONE for _, v in pairs):
            raise LsmError("value collides with the reserved tombstone marker")
        with self._lock:
            self._wal_append_many([(_W_PUT, k, v) for k, v in pairs])
            mput = self._mem.put
            for k, v in pairs:
                mput(k, v)
            for k, v in pairs:
                if self._mem.mirror is None:
                    break
                self._mirror_put(k, v)
            self._maybe_flush()

    def delete(self, key: bytes) -> None:
        assert self.strategy == STRATEGY_REPLACE
        with self._lock:
            self._wal_append(_W_DELETE, key)
            self._mem.delete(key)
            if self._mem.mirror is not None:
                self._mirror_put(key, _TOMBSTONE)
            self._maybe_flush()

    # what a memtable's mirror may hold in superseded records, over the
    # memtable's own bytes, before a fresh mirror is the cheaper one: a hot
    # key re-put for ever grows no memtable, so nothing else bounds it
    _MIRROR_DEAD_SLACK = 4 << 20

    def _mirror_put(self, key: bytes, value: bytes) -> None:
        """Keep the memtable's mirror in step with a put or a delete (the
        caller holds the lock and has changed the dict): a packed get that
        follows sees it. A mirror that ran out of memory, or holds more
        dead bytes than its memtable live ones, is retired; the next packed
        get makes a fresh one."""
        mem = self._mem
        m = mem.mirror
        if not m.put(key, value) or \
                m.dead > mem.approx_bytes() + self._MIRROR_DEAD_SLACK:
            mem.mirror = None
            self._retire(m)

    def _mem_layer(self):
        """The memtable's mirror for a packed get, made at the first one
        that finds this generation non-empty (the caller holds the lock and
        has seen `len(self._mem)`): a bucket nobody reads packed, every
        import, every build, a restart's WAL replay, never makes one. None
        where none can be made (no memory): asked once a generation."""
        from weaviate_tpu.storage import lsm_native

        mem = self._mem
        if mem.mirror is None and not mem.mirror_refused:
            mem.mirror = lsm_native.mem_mirror(mem.data)
            if mem.mirror is None:
                mem.mirror_refused = True
            else:
                perf.note_point_get(mirror_builds=1)
        return mem.mirror

    def set_add(self, key: bytes, value: bytes) -> None:
        assert self.strategy == STRATEGY_SET
        with self._lock:
            self._wal_append(_W_PUT, key, value)
            self._mem.add(key, value)
            self._maybe_flush()

    def set_remove(self, key: bytes, value: bytes) -> None:
        assert self.strategy == STRATEGY_SET
        with self._lock:
            self._wal_append(_W_DELETE, key, value)
            self._mem.remove(key, value)
            self._maybe_flush()

    def map_put(self, key: bytes, subkey: bytes, value: bytes) -> None:
        assert self.strategy == STRATEGY_MAP
        with self._lock:
            self._wal_append(_W_PUT, key, subkey, value)
            self._mem.put(key, subkey, value)
            self._maybe_flush()

    def map_put_many(self, items) -> None:
        """Batched map puts [(key, subkey, value)]: one lock, one WAL write
        — a batch import's per-term postings land together."""
        assert self.strategy == STRATEGY_MAP
        items = list(items)
        if not items:
            return
        with self._lock:
            self._wal_append_many([(_W_PUT, k, s, v) for k, s, v in items])
            mput = self._mem.put
            for k, s, v in items:
                mput(k, s, v)
            self._maybe_flush()

    def map_delete(self, key: bytes, subkey: bytes) -> None:
        assert self.strategy == STRATEGY_MAP
        with self._lock:
            self._wal_append(_W_DELETE, key, subkey)
            self._mem.delete_pair(key, subkey)
            self._maybe_flush()

    # u64 doc ids per roaring WAL record: 2M ids = 16 MiB, safely under the
    # replay record bound with headroom for the key frame
    _RS_IDS_PER_REC = 1 << 21

    @classmethod
    def _rs_recs(cls, op: int, key: bytes, a: np.ndarray):
        """Split one roaring bulk op into record-bound-sized WAL records —
        add/remove semantics are unchanged by splitting."""
        step = cls._RS_IDS_PER_REC
        if len(a) <= step:
            return [(op, key, a.tobytes())]
        return [(op, key, a[i : i + step].tobytes())
                for i in range(0, len(a), step)]

    def roaring_add_many(self, key: bytes, doc_ids: Iterable[int]) -> None:
        assert self.strategy == STRATEGY_ROARINGSET
        ids = np.fromiter(doc_ids, dtype="<u8")
        with self._lock:
            self._wal_append_many(self._rs_recs(_W_RS_ADD_MANY, key, ids))
            self._mem.add_many(key, ids)
            self._maybe_flush()

    def roaring_add_many_keys(self, items) -> None:
        """Batched roaring adds [(key, doc_ids)]: one lock, one WAL write —
        a batch import's per-token bitmaps land together."""
        assert self.strategy == STRATEGY_ROARINGSET
        staged = []
        for k, ids in items:
            a = (ids.astype("<u8", copy=False) if isinstance(ids, np.ndarray)
                 else np.fromiter(ids, dtype="<u8"))
            staged.append((k, a))
        if not staged:
            return
        with self._lock:
            self._wal_append_many(
                [r for k, a in staged
                 for r in self._rs_recs(_W_RS_ADD_MANY, k, a)])
            add = self._mem.add_many
            for k, a in staged:
                add(k, a)
            self._maybe_flush()

    def roaring_remove_many(self, key: bytes, doc_ids: Iterable[int]) -> None:
        assert self.strategy == STRATEGY_ROARINGSET
        ids = np.fromiter(doc_ids, dtype="<u8")
        with self._lock:
            self._wal_append_many(self._rs_recs(_W_RS_DEL_MANY, key, ids))
            self._mem.del_many(key, ids)
            self._maybe_flush()

    # -- reads ---------------------------------------------------------------

    @staticmethod
    def _seg_get(segs, key: bytes) -> Optional[bytes]:
        """Newest-first raw lookup across a segment list (tombstones NOT yet
        resolved — the caller maps _TOMBSTONE to None). One copy of the scan
        so read semantics cannot diverge between get/multi_get/fallbacks."""
        for seg in reversed(segs):
            v = seg.get_raw(key)
            if v is not None:
                return v
        return None

    def get(self, key: bytes) -> Optional[bytes]:
        """replace: newest value or None (tombstone-aware)."""
        assert self.strategy == STRATEGY_REPLACE
        with self._lock:
            v = self._mem.get(key)
            if v is None:
                v = self._seg_get(self._segments, key)
            return None if v is None or v == _TOMBSTONE else v

    def multi_get(self, keys) -> list[Optional[bytes]]:
        """Batched replace-strategy point gets — the serving path hydrates
        thousands of winners per batch. A None key yields None (missing
        upstream lookup), keeping caller indexing aligned.

        Memtable hits resolve in Python under one lock acquisition; segment
        misses then ride ONE native C call (GIL released, see
        storage/lsm_native.py) over a snapshot protected by the
        retire-until-idle contract, with the Python bisect reader as the
        fallback."""
        assert self.strategy == STRATEGY_REPLACE
        from weaviate_tpu.storage import lsm_native

        n = len(keys) if hasattr(keys, "__len__") else None
        out: list[Optional[bytes]] = []
        with self._lock:
            mem_get = self._mem.get
            segs = self._segments
            use_native = (n is None or n >= 16) and segs and lsm_native.available()
            if not use_native:
                for key in keys:
                    if key is None:
                        out.append(None)
                        continue
                    v = mem_get(key)
                    if v is None:
                        v = self._seg_get(segs, key)
                    out.append(None if v is None or v == _TOMBSTONE else v)
                return out
            miss_idx: list[int] = []
            miss_keys: list[bytes] = []
            for i, key in enumerate(keys):
                if key is None:
                    out.append(None)
                    continue
                v = mem_get(key)
                if v is None:
                    miss_idx.append(i)
                    miss_keys.append(key)
                    out.append(None)
                else:
                    out.append(None if v == _TOMBSTONE else v)
            if not miss_idx:
                return out
            snapshot = list(reversed(segs))  # newest first
            self._native_inflight += 1
        try:
            vals = lsm_native.multi_get(snapshot, miss_keys)
        finally:
            with self._lock:
                self._native_exit()
        if vals is None:  # native unavailable for a segment: Python reader
            with self._lock:
                for i, key in zip(miss_idx, miss_keys):
                    v = self._seg_get(self._segments, key)
                    out[i] = None if v is None or v == _TOMBSTONE else v
            return out
        for i, v in zip(miss_idx, vals):
            out[i] = v
        return out

    def multi_get_packed(self, key_buf, key_offs):
        """Packed-buffer batched point gets for the raw serving lane:
        keys live at key_offs[i]..key_offs[i+1] in key_buf (bytes or uint8
        array; zero-length = missing upstream) -> (value buffer, offsets,
        flags) straight from the native plane. A bucket that is being
        written serves exactly too, and as a quiet one does: the memtable
        (a value put, or a delete, since the last flush) is a layer the
        ONE native call asks before the segments, through a native mirror
        that `put` / `delete` keep in step (`_mem_layer`). The lock is held
        for the snapshot alone (the segments and the mirror, retired and
        never closed while this call is in flight) and no Python statement
        runs a key; the lane does not close on every reader while ONE
        writer keeps a memtable non-empty. None whenever the packed path
        cannot serve (no segments, native unavailable, or no memory for a
        written memtable's mirror: `/debug/perf` `point_get`
        `overlay_fallbacks` counts those) — the caller falls back to the
        general path. The values live in the calling thread's arena: valid
        until that thread's next packed call
        (lsm_native.multi_get_packed)."""
        assert self.strategy == STRATEGY_REPLACE
        from weaviate_tpu.storage import lsm_native

        with self._lock:
            if not self._segments or not lsm_native.available():
                return None
            mirror = None
            if len(self._mem):
                mirror = self._mem_layer()
                if mirror is None:
                    perf.note_point_get(overlay_fallbacks=1)
                    return None
            snapshot = list(reversed(self._segments))
            self._native_inflight += 1
        try:
            return lsm_native.multi_get_packed(snapshot, key_buf, key_offs,
                                               mirror)
        finally:
            with self._lock:
                self._native_exit()

    def set_get(self, key: bytes) -> set[bytes]:
        assert self.strategy == STRATEGY_SET
        with self._lock:
            out: set[bytes] = set()
            removed: set[bytes] = set()
            # oldest -> newest then memtable applies last; we walk newest-first
            # collecting, honoring newer deletions
            layers = []
            for seg in self._segments:
                raw = seg.get_raw(key)
                if raw is not None:
                    layers.append(_dec_set(raw))
            layers.append((set(self._mem.adds.get(key, set())), set(self._mem.dels.get(key, set()))))
            for adds, dels in layers:  # oldest -> newest
                out -= dels
                out |= adds
            return out

    def map_get(self, key: bytes) -> dict[bytes, bytes]:
        assert self.strategy == STRATEGY_MAP
        with self._lock:
            merged: dict[bytes, Optional[bytes]] = {}
            for seg in self._segments:
                raw = seg.get_raw(key)
                if raw is not None:
                    merged.update(_dec_map(raw))
            merged.update(self._mem.data.get(key, {}))
            return {k: v for k, v in merged.items() if v is not None}

    def map_get_arrays(self, key: bytes, key_dtype: str = "<u8",
                       val_dtype: str = "<f4"):
        """Postings fast path: map_get for uniformly (u64 subkey -> 4-byte
        value) shaped maps -> (doc_ids u64 ascending native-endian, vals),
        decoded with zero per-entry Python (see _dec_map_fixed). Returns
        None when ANY layer defeats the fixed-stride decode (odd-shaped
        entries or tombstoned pairs) — callers fall back to map_get. Merge
        semantics match map_get: later segments and the memtable override
        per doc.

        key_dtype ">u8" is the inverted-index posting layout: big-endian
        subkeys make the segment's byte-lexicographic sort order EQUAL the
        numeric doc-id order, so the hot decode skips its argsort."""
        assert self.strategy == STRATEGY_MAP
        val_native = np.dtype(val_dtype).newbyteorder("=")
        parts = []
        with self._lock:
            for seg in self._segments:
                raw = seg.get_raw(key)
                if raw is None:
                    continue
                dec = _dec_map_fixed(raw, key_dtype, val_dtype)
                if dec is None:
                    # odd shapes OR tombstoned pairs (empty value frames
                    # break the stride) — generic decode handles them
                    return None
                parts.append(dec)
            mem = self._mem.data.get(key)
            if mem:
                vals_view = mem.values()
                if None in vals_view:  # in-memtable tombstone: generic path
                    return None
                kj = b"".join(mem.keys())
                vj = b"".join(vals_view)
                # sum-length check only: every writer of map buckets in this
                # codebase writes uniform entry shapes per key, so a mixed
                # batch summing to exactly 8n/4n does not occur in practice
                if len(kj) != 8 * len(mem) or len(vj) != 4 * len(mem):
                    return None
                parts.append((np.frombuffer(kj, dtype=key_dtype),
                              np.frombuffer(vj, dtype=val_dtype)))
        if not parts:
            return (np.empty(0, dtype=np.uint64), np.empty(0, dtype=val_native))
        if len(parts) == 1:
            # rec["k"]/rec["v"] are stride-21 views into the payload; go
            # contiguous AND native-endian first — sorting/comparing through
            # the stride or a byteswap costs ~5x the copy
            ids = np.ascontiguousarray(parts[0][0]).astype(
                np.uint64, copy=False)
            vals = np.ascontiguousarray(parts[0][1]).astype(
                val_native, copy=False)
            # big-endian segment subkeys arrive numerically sorted (byte-lex
            # == numeric); little-endian ones usually do not — sort if needed
            if ids.size > 1 and not (ids[:-1] < ids[1:]).all():
                order = np.argsort(ids, kind="stable")
                ids, vals = ids[order], vals[order]
            return ids, vals
        ids = np.concatenate([p[0].astype(np.uint64, copy=False) for p in parts])
        vals = np.concatenate(
            [p[1].astype(val_native, copy=False) for p in parts])
        layer = np.concatenate(
            [np.full(p[0].shape, i, dtype=np.int32) for i, p in enumerate(parts)])
        order = np.lexsort((layer, ids))
        ids, vals = ids[order], vals[order]
        last = np.empty(ids.shape, dtype=bool)
        last[:-1] = ids[:-1] != ids[1:]
        last[-1] = True
        return ids[last], vals[last]

    def roaring_get(self, key: bytes) -> Bitmap:
        """The key's posting: oldest layer first, out = (out - deletions) |
        additions a layer, the memtable's on top. ONE read path for every
        caller, chosen by what the bucket observes: the segments are walked
        in one native pass (storage/lsm_native.py posting_get: outside the
        lock, on a snapshot the retire-until-idle contract protects, and
        keeping the GIL) where the library is loaded and no layer of the key
        deletes from an older one; else by `_roaring_walk`, which settles
        any layering. `/debug/perf` `postings` counts which served."""
        assert self.strategy == STRATEGY_ROARINGSET
        from weaviate_tpu.storage import lsm_native

        with self._lock:
            snapshot = list(self._segments)
            walk = perf.POSTING_MEMTABLE
            if snapshot:
                walk = (perf.POSTING_NATIVE if lsm_native.available()
                        else "no_library")
            if walk != perf.POSTING_NATIVE:
                out = self._roaring_walk(key)
                perf.note_posting(len(snapshot), len(out), walk)
                return out
            madds = self._mem.adds.get(key)
            mdels = self._mem.dels.get(key)
            madds = Bitmap(madds) if madds else None
            mdels = Bitmap(mdels) if mdels else None
            self._native_inflight += 1
        try:
            got = lsm_native.posting_get(snapshot, key)
        finally:
            with self._lock:
                self._native_exit()
        if isinstance(got, str):    # the reason this key is not the plane's
            with self._lock:
                out = self._roaring_walk(key)
                perf.note_posting(len(self._segments), len(out), got)
            return out
        ids, ascends, probes = got
        perf.note_posting(probes, len(ids), walk)
        out = Bitmap(ids, _sorted=ascends)
        if mdels is not None and len(out):
            out = out.and_not(mdels)
        if madds is not None:
            out = out.or_(madds) if len(out) else madds
        return out

    def _roaring_walk(self, key: bytes) -> Bitmap:
        """`roaring_get` in Python (caller holds the lock). The additions
        of consecutive layers without a deletion are merged in ONE pass (a
        posting spread over 30 segments is one sort, not 30 unions); a
        layer that deletes settles what came before it first."""
        layers: list[np.ndarray] = []

        def merged() -> Bitmap:
            if not layers:
                return Bitmap()
            # doc ids come from a counter, so the layers of a posting
            # follow each other: joined they ascend already, and the
            # check is one pass where the sort would be the whole cost.
            # The layers are views of the mappings; joining them is the
            # one copy (np.concatenate copies a single layer too)
            ids = np.concatenate(layers)
            return Bitmap(ids, _sorted=len(layers) == 1
                          or bool(np.all(ids[1:] > ids[:-1])))

        def settle(dels: Bitmap) -> None:
            layers[:] = [merged().and_not(dels).to_array()]

        for seg in self._segments:
            raw = seg.view_raw(key)
            if raw is not None:
                adds, dels = _dec_roaring_views(raw)
                if len(dels) and layers:
                    settle(Bitmap(dels, _sorted=True))
                if len(adds):
                    layers.append(adds)
        madds = self._mem.adds.get(key)
        mdels = self._mem.dels.get(key)
        if mdels and layers:
            settle(Bitmap(mdels))
        if madds:
            layers.append(Bitmap(madds).to_array())
        return merged()

    def keys(self) -> list[bytes]:
        """Sorted live keys across memtable + segments."""
        with self._lock:
            ks: set[bytes] = set()
            for seg in self._segments:
                ks.update(seg.keys)
            if self.strategy == STRATEGY_REPLACE:
                for k, v in self._mem.data.items():
                    ks.add(k)
                return sorted(k for k in ks if self.get(k) is not None)
            if self.strategy == STRATEGY_SET:
                ks.update(self._mem.adds)
                return sorted(k for k in ks if self.set_get(k))
            if self.strategy == STRATEGY_MAP:
                ks.update(self._mem.data)
                return sorted(k for k in ks if self.map_get(k))
            ks.update(self._mem.adds)
            return sorted(k for k in ks if len(self.roaring_get(k)))

    def cursor(self) -> Iterator[tuple[bytes, object]]:
        """Sorted range scan over live entries (lsmkv cursors)."""
        getter = {
            STRATEGY_REPLACE: self.get,
            STRATEGY_SET: self.set_get,
            STRATEGY_MAP: self.map_get,
            STRATEGY_ROARINGSET: self.roaring_get,
        }[self.strategy]
        for k in self.keys():
            yield k, getter(k)

    # -- flush / compaction --------------------------------------------------

    def _maybe_flush(self) -> None:
        if self._mem.approx_bytes() >= self.memtable_max_bytes:
            self.flush_memtable()

    def _encode_memtable(self) -> list[tuple[bytes, bytes]]:
        items: list[tuple[bytes, bytes]] = []
        if self.strategy == STRATEGY_REPLACE:
            items = sorted(self._mem.data.items())
        elif self.strategy == STRATEGY_SET:
            keys = set(self._mem.adds) | set(self._mem.dels)
            items = [
                (k, _enc_set(self._mem.adds.get(k, set()), self._mem.dels.get(k, set())))
                for k in sorted(keys)
            ]
        elif self.strategy == STRATEGY_MAP:
            items = [(k, _enc_map(m)) for k, m in sorted(self._mem.data.items())]
        else:
            keys = set(self._mem.adds) | set(self._mem.dels)
            items = [
                (k, _enc_roaring(Bitmap(self._mem.adds.get(k) or ()),
                                 Bitmap(self._mem.dels.get(k) or ())))
                for k in sorted(keys)
            ]
        return items

    def flush_memtable(self) -> None:
        with self._lock:
            if not len(self._mem):
                return
            items = self._encode_memtable()
            seg_path = os.path.join(self.path, f"{self._seg_counter:08d}.seg")
            Segment.write(seg_path, self.strategy, items)
            self._seg_counter += 1
            self._segments.append(Segment(seg_path))
            self._drop_mirror()
            self._mem = self._new_memtable()
            # truncate WAL (always rotates to the v2 crc-framed format)
            self._wal.close()
            self._wal = open(self._wal_path, "wb")
            self._wal.write(_WAL_MAGIC2)
            self._wal.flush()
            os.fsync(self._wal.fileno())
            self._wal_v2 = True

    def _drop_mirror(self) -> None:
        """Retire the memtable's mirror with its generation (the caller
        holds the lock): freed once no packed get that holds it is in
        flight."""
        m = getattr(self._mem, "mirror", None)
        if m is not None:
            self._mem.mirror = None
            self._retire(m)

    def segment_count(self) -> int:
        with self._lock:
            return len(self._segments)

    def compact_pair(self) -> bool:
        """Merge the two OLDEST segments into one — the incremental unit of
        the background cycle (reference: segment_group_compaction.go merges
        adjacent same-level pairs). The merged pair sits at the bottom of
        the stack, so tombstones/net-deletes can be dropped safely.

        Two invariants matter here:
        - the merged segment REPLACES the oldest pair member's FILENAME
          (write-then-rename), because restart loads segments in filename
          order — a fresh counter name would make the oldest data load as
          newest and resurrect stale/deleted keys;
        - the merge itself (decode + sorted rewrite) runs OUTSIDE the bucket
          lock — segments are immutable mmaps, so readers proceed; only the
          head snapshot and the final list swap are locked.
        -> True if a merge happened."""
        with self._lock:
            if len(self._segments) < 2:
                return False
            pair = self._segments[:2]
        items = self._merge_segment_items(pair)  # immutable inputs: lock-free
        tmp_path = pair[0].path + ".compact.tmp"
        Segment.write(tmp_path, self.strategy, items)
        with self._lock:
            if self._segments[:2] != pair:
                # the stack changed under us (drop/another compaction): abort
                try:
                    os.remove(tmp_path)
                    os.remove(tmp_path + ".bloom")
                except FileNotFoundError:
                    pass
                return False
            keep_path = pair[0].path
            for seg in pair:
                self._retire(seg)
            # bloom BEFORE segment: a crash in between pairs the old segment
            # with a new bloom (false positives only — harmless); the other
            # order pairs the merged segment with a stale bloom, turning
            # bloom misses into silent data loss
            try:
                os.replace(tmp_path + ".bloom", keep_path + ".bloom")
            except FileNotFoundError:
                pass
            os.replace(tmp_path, keep_path)
            os.remove(pair[1].path)
            try:
                os.remove(pair[1].path + ".bloom")
            except FileNotFoundError:
                pass
            self._segments = [Segment(keep_path)] + self._segments[2:]
            return True

    def _merge_segment_items(self, segments) -> list[tuple[bytes, bytes]]:
        """Net-merge `segments` (oldest first) per strategy, dropping
        tombstoned state — callers only merge bottom-of-stack runs."""
        merged: dict[bytes, bytes] = {}
        if self.strategy == STRATEGY_REPLACE:
            for seg in segments:
                merged.update(seg.items_raw())
            # drop tombstones: nothing older remains below this run
            items = sorted((k, v) for k, v in merged.items() if v != _TOMBSTONE)
        elif self.strategy == STRATEGY_SET:
            acc: dict[bytes, tuple[set, set]] = {}
            for seg in segments:
                for k, raw in seg.items_raw():
                    adds, dels = _dec_set(raw)
                    cur = acc.get(k, (set(), set()))
                    cur = (cur[0] - dels | adds, set())  # net state
                    acc[k] = cur
            items = sorted((k, _enc_set(a, d)) for k, (a, d) in acc.items() if a or d)
        elif self.strategy == STRATEGY_MAP:
            accm: dict[bytes, dict[bytes, Optional[bytes]]] = {}
            for seg in segments:
                for k, raw in seg.items_raw():
                    accm.setdefault(k, {}).update(_dec_map(raw))
            items = sorted(
                (k, _enc_map({s: v for s, v in m.items() if v is not None}))
                for k, m in accm.items()
                if any(v is not None for v in m.values())
            )
        else:
            accr: dict[bytes, Bitmap] = {}
            for seg in segments:
                for k, raw in seg.items_raw():
                    adds, dels = _dec_roaring(raw)
                    accr[k] = accr.get(k, Bitmap()).and_not(dels).or_(adds)
            items = sorted((k, _enc_roaring(bm, Bitmap())) for k, bm in accr.items() if len(bm))
        return items

    def compact(self) -> None:
        """Merge all segments into one (full compaction)."""
        with self._lock:
            if len(self._segments) < 2:
                return
            items = self._merge_segment_items(self._segments)
            seg_path = os.path.join(self.path, f"{self._seg_counter:08d}.seg")
            Segment.write(seg_path, self.strategy, items)
            self._seg_counter += 1
            old = self._segments
            self._segments = [Segment(seg_path)]
            for seg in old:
                self._retire(seg)
                os.remove(seg.path)
                try:
                    os.remove(seg.path + ".bloom")
                except FileNotFoundError:
                    pass

    def flush(self) -> None:
        with self._lock:
            self._wal.flush()
            os.fsync(self._wal.fileno())

    def count(self) -> int:
        return len(self.keys())

    def shutdown(self) -> None:
        with self._lock:
            self.flush_memtable()
            self._wal.close()
            for seg in self._segments:
                self._retire(seg)  # never munmap under an in-flight read
            self._segments = []

    def drop(self) -> None:
        with self._lock:
            try:
                self._wal.close()
            except Exception:
                pass
            for seg in self._segments:
                self._retire(seg)
            self._segments = []
            self._drop_mirror()
            import shutil

            shutil.rmtree(self.path, ignore_errors=True)

    def list_files(self) -> list[str]:
        with self._lock:
            out = [self._wal_path]
            for seg in self._segments:
                out.append(seg.path)
                if os.path.exists(seg.path + ".bloom"):
                    out.append(seg.path + ".bloom")
            return out


def overlay_packed(packed, newer: dict[int, bytes]):
    """(values, offsets, flags) of a packed point get with the values at
    the positions of `newer` replaced (`_TOMBSTONE`: that key is gone) ->
    a packed triple of its own buffer. The spans between the replaced
    positions are copied whole: a loop over `newer`, not over the keys."""
    vbuf, voffs, flags = packed
    n = len(flags)
    lens, flags = np.diff(voffs), flags.copy()
    for i, v in newer.items():
        gone = v == _TOMBSTONE
        lens[i], flags[i] = (0, 0) if gone else (len(v), 1)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=offs[1:])
    out = np.empty(int(offs[n]), np.uint8)
    prev = 0
    for i in sorted(newer):
        out[offs[prev]:offs[i]] = vbuf[voffs[prev]:voffs[i]]
        if flags[i]:
            out[offs[i]:offs[i + 1]] = np.frombuffer(newer[i], np.uint8)
        prev = i + 1
    out[offs[prev]:] = vbuf[voffs[prev]:voffs[n]]
    return out, offs, flags


class Store:
    """Named-bucket container (lsmkv.Store, store.go:111)."""

    # background cycle defaults (reference: cyclemanager-driven
    # segment_group_compaction.go); tunable via env
    MAX_SEGMENTS = int(os.environ.get("PERSISTENCE_LSM_MAX_SEGMENTS", "8"))
    COMPACTION_INTERVAL = float(os.environ.get("PERSISTENCE_LSM_COMPACTION_INTERVAL", "30"))

    def __init__(self, root: str, memtable_max_bytes: Optional[int] = None,
                 flush_idle_seconds: Optional[float] = None):
        """memtable_max_bytes: per-bucket default flush threshold
        (PERSISTENCE_MEMTABLES_MAX_SIZE_MB). flush_idle_seconds: the
        background cycle also flushes memtables with no writes for this
        long (PERSISTENCE_FLUSH_IDLE_MEMTABLES_AFTER; bounds WAL-replay
        time after a crash on a write-quiet shard)."""
        self.root = root
        self.memtable_max_bytes = memtable_max_bytes
        self.flush_idle_seconds = flush_idle_seconds
        os.makedirs(root, exist_ok=True)
        self._buckets: dict[str, Bucket] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._cycle_thread: Optional[threading.Thread] = None
        # held by backup/scale-out file copies: the compaction cycle must not
        # delete or replace segment files mid-copy (the reference's
        # pause-compaction window, adapters/repos/db/backup.go)
        self._compaction_gate = threading.Lock()

    def compaction_paused(self):
        """Context manager: block the compaction sweep for the duration."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            with self._compaction_gate:
                yield

        return _ctx()

    def start_compaction_cycle(self, interval: Optional[float] = None,
                               max_segments: Optional[int] = None) -> None:
        """Background per-bucket pair compaction: whenever a bucket's
        segment stack grows past max_segments, merge oldest pairs until it
        fits (segment_group_compaction.go's cycle, simplified to a single
        level)."""
        if self._cycle_thread is not None:
            return
        iv = interval if interval is not None else self.COMPACTION_INTERVAL
        max_segs = max_segments if max_segments is not None else self.MAX_SEGMENTS

        def loop():
            while not self._stop.wait(iv):
                # independent try blocks: a persistently-failing compaction
                # (corrupt segment) must not also disable idle flushing
                try:
                    self.compact_once(max_segs)
                except Exception:  # noqa: BLE001 — the cycle must survive
                    logging.getLogger(__name__).warning(
                        "lsm compaction cycle error", exc_info=True)
                try:
                    self.flush_idle_once()
                except Exception:  # noqa: BLE001
                    logging.getLogger(__name__).warning(
                        "lsm idle-flush cycle error", exc_info=True)

        self._cycle_thread = threading.Thread(
            target=loop, daemon=True, name="lsm-compaction"
        )
        self._cycle_thread.start()

    def sweep_in_flight(self) -> bool:
        """Is a compaction sweep (or a backup's pause) holding the gate
        right now? Racy by nature: a fact for a log line."""
        return self._compaction_gate.locked()

    def compact_once(self, max_segments: Optional[int] = None) -> int:
        """One compaction sweep (also the test/CLI entry): -> merges done."""
        max_segs = max_segments if max_segments is not None else self.MAX_SEGMENTS
        merges = 0
        with self._compaction_gate:
            for b in list(self._buckets.values()):
                while b.segment_count() > max_segs and b.compact_pair():
                    merges += 1
        return merges

    def flush_idle_once(self) -> int:
        """Flush memtables untouched for flush_idle_seconds (lsmkv's
        FlushAfterIdle cycle): bounds crash-recovery WAL replay on shards
        that went write-quiet. -> buckets flushed."""
        if not self.flush_idle_seconds:
            return 0
        now = time.monotonic()
        flushed = 0
        with self._compaction_gate:
            for b in list(self._buckets.values()):
                if len(b._mem) and now - b._last_write >= self.flush_idle_seconds:
                    b.flush_memtable()
                    flushed += 1
        return flushed

    def create_or_load_bucket(self, name: str, strategy: str, **kw) -> Bucket:
        with self._lock:
            b = self._buckets.get(name)
            if b is None:
                if self.memtable_max_bytes and "memtable_max_bytes" not in kw:
                    kw["memtable_max_bytes"] = self.memtable_max_bytes
                b = Bucket(os.path.join(self.root, name), strategy, **kw)
                self._buckets[name] = b
            elif b.strategy != strategy:
                raise LsmError(f"bucket {name} exists with strategy {b.strategy}")
            return b

    def bucket(self, name: str) -> Optional[Bucket]:
        return self._buckets.get(name)

    def flush_all(self) -> None:
        for b in list(self._buckets.values()):
            b.flush()

    def flush_memtables(self) -> None:
        """Flush every bucket's memtable to a segment (serving steady
        state — what the idle-flush cycle converges to)."""
        with self._compaction_gate:
            for b in list(self._buckets.values()):
                if len(b._mem):
                    b.flush_memtable()

    def shutdown(self) -> None:
        self._stop.set()
        for b in list(self._buckets.values()):
            b.shutdown()

    def drop(self) -> None:
        for b in list(self._buckets.values()):
            b.drop()
        import shutil

        shutil.rmtree(self.root, ignore_errors=True)

    def list_files(self) -> list[str]:
        out = []
        for b in self._buckets.values():
            out.extend(b.list_files())
        return out
