"""ctypes bridge to the native LSM read plane (native/lsm_get.cpp): the
replace-strategy point gets, and the roaring-set posting walk below them.

Batched replace-strategy point lookups over the mmap'd segment files with
the GIL released (ctypes semantics), so concurrent request hydrations
overlap instead of serializing. A batch is one C call (`lsm_multi_get`):
every key is located once (hashed once, one probe of each segment's hash
table, newest first), the values' total size is then known, and they are
copied once into an arena this thread already holds. Only when that arena
is too small does the bridge grow it and ask for the copy alone
(`lsm_copy`): nothing is searched twice and no multi-megabyte buffer is
mapped afresh a call.

A bucket that is being written hands the call its memtable as one more
layer, the newest (`MemMirror`: a native copy of the memtable's keys and
values, kept in step by the bucket's puts), so the call's answer is exact
without a Python statement a key and the values are still written once.

What a call did is counted in C and handed to the perf window
(`/debug/perf` `point_get`: `keys`, `segment_probes`, `key_compares`,
`arena_grows`, `mem_layer_calls`, `mem_keys`) while the tracer is up.

Reference analog: the compiled lsmkv segment readers under the batched
hydration seam entities/storobj/storage_object.go:211.

A filtered group's device operands (`GroupLists`) are two C calls a
group: `locate` (where each distinct allowList lies in the snapshot's docs,
how many slots it holds; keeps the GIL) and, once the plan is made, `fill`
(every gathered slot's int32 rows, every scanned slot's mask bits, written
in place into the caller's buffers; lets go of the GIL).

A posting (`posting_get`) is two C calls a key: the first finds the key in
every segment's hash table, oldest first, and says how many ids its layers
hold; the second copies them into one fresh uint64 array, the Bitmap's own,
and says whether they ascend as they stand. `intersect_sorted` is
`Bitmap.and_`'s pass over two postings. All of these keep the GIL
(`_load`). What the walk did reaches `/debug/perf` `postings`
through the bucket (`Bucket.roaring_get`).

Falls back cleanly: `multi_get` returns None, and `posting_get` the
reason, whenever the library or a segment handle is unavailable, and
callers use the Python reader.
"""

from __future__ import annotations

import ctypes
import logging
import threading
from typing import Optional, Sequence

import numpy as np

from weaviate_tpu import _native
from weaviate_tpu.monitoring import perf

_lib = None
_lib_failed = False
_lib_lock = threading.Lock()


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            lib = ctypes.CDLL(_native.ensure_built("lsmget"))
            lib.lsm_seg_open.restype = ctypes.c_void_p
            lib.lsm_seg_open.argtypes = [ctypes.c_char_p]
            lib.lsm_seg_close.restype = None
            lib.lsm_seg_close.argtypes = [ctypes.c_void_p]
            lib.lsm_multi_get.restype = ctypes.c_int64
            lib.lsm_multi_get.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int64,
                ctypes.c_void_p,
                ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64,
                ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int8), ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int64,
            ]
            lib.lsm_copy.restype = None
            lib.lsm_copy.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.POINTER(ctypes.c_ubyte),
            ]
            lib.lsm_key_hash.restype = ctypes.c_uint64
            lib.lsm_key_hash.argtypes = [ctypes.c_char_p, ctypes.c_int64]
            ptr, i64 = ctypes.c_void_p, ctypes.c_int64
            lib.lsm_mem_open.restype = ptr
            lib.lsm_mem_open.argtypes = []
            lib.lsm_mem_close.restype = None
            lib.lsm_mem_close.argtypes = [ptr]
            # the calls on postings, and a put into a memtable's mirror
            # (the writer's, under the bucket's lock), KEEP the GIL (a
            # PYFUNCTYPE prototype, where the library's own attributes
            # release it): most take microseconds, and a thread that lets
            # go of the GIL gets it back only when whichever thread took it
            # gives it up, up to a switch interval later (PERF.md section
            # 6, PR 31: the same copies through numpy, which lets go, cost
            # the filtered cell 65 ms a request). Addresses go as integers.
            for name, res, args in (
                    ("mem_put", i64,
                     (ptr, ctypes.c_char_p, i64, ctypes.c_char_p, i64)),
                    ("mem_stats", None, (ptr, ptr)),
                    ("posting_locate", i64,
                     (ptr, i64, ctypes.c_char_p, i64, ptr, ptr, ptr)),
                    ("posting_copy", i64, (ptr, ptr, i64, ptr)),
                    ("ids_gallop", i64, (ptr, i64, ptr, i64, ptr)),
                    ("bits_build", None, (ptr, i64, ctypes.c_uint64, ptr)),
                    ("bits_probe", i64,
                     (ptr, i64, ptr, ctypes.c_uint64, i64, ptr)),
                    ("group_locate", i64,
                     (ptr, ptr, i64, ptr, i64, i64, ptr, ptr, ptr))):
                setattr(lib, name, ctypes.PYFUNCTYPE(res, *args)(
                    ("lsm_" + name, lib)))
            # a group's fill is ONE call of milliseconds (megabytes of
            # ids walked, of rows and words written): it lets go of the
            # GIL, as the point gets do
            lib.lsm_group_fill.restype = None
            lib.lsm_group_fill.argtypes = [ptr, ptr, ptr, ptr, i64, i64,
                                           ptr, i64, ptr]
            _lib = lib
        except Exception as e:  # noqa: BLE001 — the Python reader serves
            _lib_failed = True
            logging.getLogger(__name__).warning(
                "native LSM point-get plane unavailable (%s: %s); point "
                "gets run in the Python reader", type(e).__name__, e)
        return _lib


def available() -> bool:
    return _load() is not None


def _as_u8_ptr(buf):
    """bytes or uint8 ndarray -> zero-copy c_ubyte pointer."""
    if isinstance(buf, np.ndarray):
        return buf.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte))
    return ctypes.cast(ctypes.c_char_p(buf), ctypes.POINTER(ctypes.c_ubyte))


_open_lock = threading.Lock()


def seg_handle(segment) -> int:
    """Native handle for a Segment (cached on the object; 0 = unusable).
    Must be called while the segment is known-open (bucket lock or
    in-flight protection held by the caller). Opening is serialized: two
    concurrent first-touches would otherwise double-open and leak one
    mmap+fd per race."""
    h = getattr(segment, "_native_handle", None)
    if h is None:
        with _open_lock:
            h = getattr(segment, "_native_handle", None)
            if h is None:
                lib = _load()
                h = 0
                if lib is not None:
                    h = lib.lsm_seg_open(segment.path.encode()) or 0
                segment._native_handle = h
    return h


def seg_close(segment) -> None:
    h = getattr(segment, "_native_handle", None)
    if h:
        lib = _load()
        if lib is not None:
            lib.lsm_seg_close(h)
    segment._native_handle = None


class MemMirror:
    """A REPLACE memtable as a layer `multi_get_packed` asks before the
    segments (native/lsm_get.cpp `lsm_mem_*`): the handle holds its OWN
    copy of every key and value put since it was made, in chunks that never
    move, so a packed get probes it outside the bucket's lock while the
    writer goes on. One thread puts at a time (the bucket's lock is held);
    `close` only once no call that was handed the mirror is in flight
    (`Bucket._native_inflight`). `dead` is the bytes of records that newer
    puts of their keys superseded: they stay held until `close`."""

    __slots__ = ("_lib", "_h", "dead")

    def __init__(self, lib, handle: int):
        self._lib, self._h, self.dead = lib, handle, 0

    def put(self, key: bytes, value: bytes) -> bool:
        """The memtable's word on `key` from now on (`_TOMBSTONE`: gone).
        False where memory ran out: the mirror no longer holds what its
        memtable holds and must not be asked again."""
        self.dead = self._lib.mem_put(self._h, key, len(key), value,
                                      len(value))
        return self.dead >= 0

    def stats(self) -> dict:
        out = np.empty(3, dtype=np.int64)
        self._lib.mem_stats(self._h, out.ctypes.data)
        return dict(zip(("keys", "held_bytes", "dead_bytes"), out.tolist()))

    def close(self) -> None:
        if self._h:
            self._lib.lsm_mem_close(self._h)
            self._h = 0

    __del__ = close   # a bucket dropped without `shutdown` (tests)


def mem_mirror(data: dict) -> Optional[MemMirror]:
    """A mirror of a REPLACE memtable's `data` (key -> value or
    `_TOMBSTONE`); None where the library or the memory for it is missing.
    The caller holds the bucket's lock: nothing is put meanwhile."""
    lib = _load()
    handle = lib.lsm_mem_open() if lib is not None else None
    if not handle:
        return None
    m = MemMirror(lib, handle)
    for k, v in data.items():
        if not m.put(k, v):
            m.close()
            return None
    return m


_ARENA_MIN = 1 << 16


class _Arena(threading.local):
    """This thread's value arena: it grows to the largest batch the thread
    has served and is then reused. A fresh array a call costs the served
    path a fifth of its rate (PERF.md, PR 28): megabytes of pages mapped,
    faulted in and unmapped again by every serving thread at once."""

    def __init__(self):
        self.buf = np.empty(0, dtype=np.uint8)

    def grow(self, need: int) -> np.ndarray:
        cap = _ARENA_MIN
        while cap < need:
            cap *= 2
        self.buf = np.empty(cap, dtype=np.uint8)
        return self.buf


_arena = _Arena()


def multi_get_packed(
    segments_newest_first: Sequence, key_buf, key_offs: np.ndarray,
    mem: Optional[MemMirror] = None,
) -> Optional[tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Packed-buffer batched gets: keys at key_offs[i]..key_offs[i+1] in
    key_buf (bytes or uint8 array; zero-length = missing upstream). ->
    (values uint8 array, offsets int64 [n+1], flags int8 [n]), or None =>
    Python fallback. The layout feeds the packed reply builder and
    call-chaining (one call's values are the next call's keys) without any
    per-value Python objects. `mem`: the memtable's mirror, asked before
    the segments inside the same call (its value is the answer, its
    tombstone a miss). Caller owns the segments' and the mirror's lifetime.

    LIFETIME: the values are a view of an arena this thread keeps, valid
    until this thread's next packed call. That call may take them as its
    key buffer (every key is located before the first value is copied
    over them); whoever else needs them uses them, or copies them out,
    before it, and never hands them to another thread."""
    lib = _load()
    if lib is None:
        return None
    handles = []
    for s in segments_newest_first:
        h = seg_handle(s)
        if not h:
            return None
        handles.append(h)
    n = len(key_offs) - 1
    key_offs = np.ascontiguousarray(key_offs, dtype=np.int64)
    out_offs = np.empty(n + 1, dtype=np.int64)
    flags = np.empty(n, dtype=np.int8)
    srcs = np.empty(n, dtype=np.uintp)
    stats = np.empty(3, dtype=np.int64)
    seg_arr = (ctypes.c_void_p * len(handles))(*handles)
    p_u8, p_i64 = ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_int64)
    srcs_ptr = srcs.ctypes.data_as(ctypes.POINTER(ctypes.c_void_p))
    offs_ptr = out_offs.ctypes.data_as(p_i64)
    arena, grew = _arena.buf, 0
    need = lib.lsm_multi_get(
        seg_arr, len(handles), mem._h if mem is not None else None,
        _as_u8_ptr(key_buf),
        key_offs.ctypes.data_as(p_i64), n, srcs_ptr, offs_ptr,
        flags.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
        stats.ctypes.data_as(p_i64), arena.ctypes.data_as(p_u8), arena.size)
    if need < 0:            # a segment that is no replace segment
        return None
    if need > arena.size:   # located, not copied: the copy alone, no search
        arena, grew = _arena.grow(need), 1
        lib.lsm_copy(srcs_ptr, offs_ptr, n, arena.ctypes.data_as(p_u8))
    perf.note_point_get(n, int(stats[0]), int(stats[1]), grew,
                        mem is not None, int(stats[2]))
    return arena[:need], out_offs, flags


def multi_get(segments_newest_first: Sequence,
              keys: Sequence[Optional[bytes]]) -> Optional[list[Optional[bytes]]]:
    """Batched point gets over a snapshot of segments (NEWEST first).
    None keys stay None. -> values list, or None => caller uses the Python
    reader. Thin wrapper over multi_get_packed: builds the packed key
    buffer, slices the value arena into per-key bytes."""
    n = len(keys)
    key_buf = b"".join(k or b"" for k in keys)
    lens = np.fromiter((0 if k is None else len(k) for k in keys),
                       dtype=np.int64, count=n)
    key_offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=key_offs[1:])
    packed = multi_get_packed(segments_newest_first, key_buf, key_offs)
    if packed is None:
        return None
    out, out_offs, flags = packed
    res: list[Optional[bytes]] = [None] * n
    offs = out_offs.tolist()
    data = bytes(out[: offs[n]])
    for i, f in enumerate(flags.tolist()):
        if f:
            res[i] = data[offs[i]:offs[i + 1]]
    return res


class _PostingScratch(threading.local):
    """This thread's per-segment outputs of `lsm_posting_locate` (where a
    layer's ids live, how many) and its two counters, with their
    addresses: grown to the deepest bucket the thread has read."""

    def __init__(self):
        self.cap = 0

    def ensure(self, n_segs: int) -> None:
        if n_segs <= self.cap:
            return
        self.cap = max(64, 2 * n_segs)
        self.srcs = np.empty(self.cap, dtype=np.uintp)
        self.counts = np.empty(self.cap, dtype=np.int64)
        self.stats = np.empty(2, dtype=np.int64)
        self.addrs = (self.srcs.ctypes.data, self.counts.ctypes.data,
                      self.stats.ctypes.data)


_posting_scratch = _PostingScratch()

def posting_get(segments_oldest_first: Sequence, key: bytes
                ) -> tuple[np.ndarray, bool, int] | str:
    """One key's additions over a snapshot of roaring-set segments (OLDEST
    first), joined in one fresh uint64 array -> (ids, whether they ascend
    strictly as they stand, segments probed). A string => the Python walk,
    and why: "no_library"; "deleting_layer", a layer deletes from what
    older layers added; "unreadable", a segment or a payload this plane
    could not parse. Caller owns segment lifetime
    (`Bucket._native_inflight`)."""
    lib = _load()
    if lib is None:
        return "no_library"
    handles = [s._native_handle for s in segments_oldest_first]
    if None in handles:
        handles = [seg_handle(s) for s in segments_oldest_first]
    if 0 in handles:
        return "unreadable"
    n = len(handles)
    sc = _posting_scratch
    sc.ensure(n)
    srcs, counts, stats = sc.addrs
    seg_arr = (ctypes.c_void_p * n)(*handles)
    total = lib.posting_locate(ctypes.addressof(seg_arr), n, key, len(key),
                               srcs, counts, stats)
    if total < 0:
        return "deleting_layer" if total == -1 else "unreadable"
    ids = np.empty(total, dtype=np.uint64)
    layers = int(sc.stats[0])
    ascends = not layers or bool(lib.posting_copy(
        srcs, counts, layers, ids.ctypes.data))
    return ids, ascends, int(sc.stats[1])


def intersect_sorted(small: np.ndarray, big: np.ndarray,
                     bits: Optional[tuple[int, np.ndarray]] = None
                     ) -> Optional[tuple[np.ndarray, Optional[tuple]]]:
    """The ids two ascending, unique uint64 arrays share (`small` the
    shorter, not empty), as a fresh array: the smaller probes a bitset
    over the larger's span, or gallops through the larger. `bits`: the
    larger's bitset (first id, words) where an earlier call made it. ->
    (ids, the larger's bitset where one was given or made, for the next
    filter that asks the same posting); None => the caller's numpy (no
    library).

    A bitset is made where that is the cheaper way for this call alone:
    a word-octet cleared or an id set costs one, a gallop's step (a
    dependent load and a branch) four, and an id of the smaller takes a
    step up and a step down for each doubling of the sizes' ratio."""
    lib = _load()
    if lib is None:
        return None
    small, big = np.ascontiguousarray(small), np.ascontiguousarray(big)
    na, nb = small.size, big.size
    if bits is None:
        base = int(big[0]) & ~63
        words = ((int(big[-1]) - base) >> 6) + 1
        if words // 8 + nb < 4 * na * (1 + (nb // na).bit_length()):
            bits = (base, np.zeros(words, dtype=np.uint64))
            lib.bits_build(big.ctypes.data, nb, base, bits[1].ctypes.data)
    out = np.empty(na, dtype=np.uint64)
    if bits is not None:
        n = lib.bits_probe(small.ctypes.data, na, bits[1].ctypes.data,
                           bits[0], bits[1].size, out.ctypes.data)
    else:
        n = lib.ids_gallop(small.ctypes.data, na, big.ctypes.data, nb,
                           out.ctypes.data)
    # the room the result did not need goes back, in place
    out.resize(n, refcheck=False)
    return out, bits


class GroupLists:
    """The distinct allowLists of a filtered group, located in a
    snapshot's `docs` (`slot_to_doc[:n]`, ascending strictly; `consecutive`
    where they have no gap): `sizes[l]` is how many store slots list l
    holds, `walked` the ids of all lists that lie in the docs' range. The
    plan needs no more; `fill` then writes the operands of the group's
    dispatches in one call. Holds what the addresses it passes point into."""

    __slots__ = ("_lib", "_ids", "_addrs", "_spans", "_docs", "_consecutive",
                 "sizes", "walked")

    def __init__(self, lib, ids: Sequence[np.ndarray], docs: np.ndarray,
                 consecutive: bool):
        n = len(ids)
        self._lib, self._ids, self._docs = lib, ids, docs
        self._consecutive = int(consecutive)
        self._addrs = np.fromiter((a.ctypes.data for a in ids), np.uintp, n)
        lens = np.fromiter((a.size for a in ids), np.int64, n)
        # lo, hi, sizes: one a list each
        self._spans = np.empty((3, n), dtype=np.int64)
        at = self._spans.ctypes.data
        self.walked = int(lib.group_locate(
            self._addrs.ctypes.data, lens.ctypes.data, n, docs.ctypes.data,
            docs.size, self._consecutive, at, at + 8 * n, at + 16 * n))
        self.sizes = self._spans[2]

    def fill(self, jobs: np.ndarray, sel: np.ndarray) -> None:
        """`jobs` int64 [n, 6] and `sel` int64, as native/lsm_get.cpp
        lsm_group_fill reads them: a gather bucket's rows and counts, or
        the masked scan's words, a job."""
        kind, height, width, nsel = jobs[:, 0], jobs[:, 3], jobs[:, 4], \
            jobs[:, 5]
        if (jobs.dtype != np.int64 or sel.dtype != np.int64
                or not jobs.flags.c_contiguous or nsel.sum() != sel.size
                or (nsel > height).any()
                or (sel.size and not 0 <= sel.min() <= sel.max()
                    < len(self._ids))
                # a row of words holds a bit for every slot of the docs
                or (width[kind == 1] * 32 < self._docs.size).any()):
            raise ValueError("group fill jobs do not fit their operands")
        at = self._spans.ctypes.data
        self._lib.lsm_group_fill(
            self._addrs.ctypes.data, at, at + 8 * len(self._ids),
            self._docs.ctypes.data, self._docs.size, self._consecutive,
            jobs.ctypes.data, len(jobs), sel.ctypes.data)


def group_locate(ids: Sequence, docs: np.ndarray,
                 consecutive: bool) -> GroupLists | str:
    """`GroupLists` over the allowLists' ascending id arrays and a
    snapshot's ascending int64 docs. A string => the caller's numpy, and
    why: "no_library"; "foreign_list", an allowList whose ids are no
    contiguous uint64 array."""
    lib = _load()
    if lib is None:
        return "no_library"
    if not all(isinstance(a, np.ndarray) and a.dtype == np.uint64
               and a.flags.c_contiguous for a in ids):
        return "foreign_list"
    if docs.dtype != np.int64 or not docs.flags.c_contiguous:
        raise ValueError("docs are a contiguous int64 array")
    return GroupLists(lib, ids, docs, consecutive)
