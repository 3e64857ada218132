"""The "hnsw" index type: native C++ graph engine behind the VectorIndex seam.

This is the CPU parity index mirroring the reference's Go HNSW
(adapters/repos/db/vector/hnsw/) — graph semantics live in native/hnsw.cpp;
this wrapper adds:
- dynamic ef (autoEfFromK, search.go:46: ef = k*factor clamped to [min,max])
- cosine = normalize-then-dot (cosine_dist.go, search.go:64)
- flat-search cutoff: allowLists smaller than flatSearchCutoff are brute
  forced over the allowList only (search.go:73-77 → flat_search.go)
- durability: snapshot (hnsw_save) + VectorLog delta replay — the analog of
  commit-log condensing (condensor.go): flush() persists a snapshot and
  truncates the delta log; restore = load snapshot, replay the delta.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from weaviate_tpu import _native
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.interface import AllowList, VectorIndex
from weaviate_tpu.index.tpu import VectorLog, restore_record
from weaviate_tpu.monitoring import tracing

_lib = None
_lib_lock = threading.Lock()


def _load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        lib = ctypes.CDLL(_native.ensure_built("hnsw"))
        u64p = ctypes.POINTER(ctypes.c_uint64)
        f32p = ctypes.POINTER(ctypes.c_float)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.hnsw_new.restype = ctypes.c_void_p
        lib.hnsw_new.argtypes = [ctypes.c_int32, ctypes.c_int32, ctypes.c_int32,
                                 ctypes.c_int32, ctypes.c_uint64]
        lib.hnsw_free.argtypes = [ctypes.c_void_p]
        lib.hnsw_add.argtypes = [ctypes.c_void_p, ctypes.c_uint64, f32p]
        lib.hnsw_add_batch.argtypes = [ctypes.c_void_p, ctypes.c_int64, u64p, f32p]
        lib.hnsw_delete.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.hnsw_delete.restype = ctypes.c_int32
        lib.hnsw_contains.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.hnsw_contains.restype = ctypes.c_int32
        lib.hnsw_size.argtypes = [ctypes.c_void_p]
        lib.hnsw_size.restype = ctypes.c_int64
        lib.hnsw_search.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int32, ctypes.c_int32,
                                    u64p, ctypes.c_int64, u64p, f32p]
        lib.hnsw_search.restype = ctypes.c_int32
        lib.hnsw_search_batch.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int32,
                                          ctypes.c_int32, ctypes.c_int32, u64p,
                                          ctypes.c_int64, u64p, f32p, i32p]
        lib.hnsw_flat_search.argtypes = [ctypes.c_void_p, f32p, ctypes.c_int32, u64p,
                                         ctypes.c_int64, u64p, f32p]
        lib.hnsw_flat_search.restype = ctypes.c_int32
        lib.hnsw_cleanup.argtypes = [ctypes.c_void_p]
        lib.hnsw_cleanup.restype = ctypes.c_int64
        lib.hnsw_node_count.argtypes = [ctypes.c_void_p]
        lib.hnsw_node_count.restype = ctypes.c_int64
        lib.hnsw_save.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.hnsw_save.restype = ctypes.c_int32
        lib.hnsw_load.argtypes = [ctypes.c_char_p]
        lib.hnsw_load.restype = ctypes.c_void_p
        _lib = lib
        return _lib


_METRIC_L2 = 0
_METRIC_DOT = 1


def _f32p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _u64p(a: Optional[np.ndarray]):
    if a is None or a.size == 0:
        return None
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


class HnswIndex(VectorIndex):
    def __init__(
        self,
        config: vi.HnswUserConfig,
        shard_path: str,
        shard_name: str = "",
        metrics=None,
        persist: bool = True,
        class_name: str = "",
    ):
        self.config = config
        self.metric = config.distance
        if self.metric in (vi.DISTANCE_MANHATTAN, vi.DISTANCE_HAMMING):
            raise vi.ConfigValidationError(
                f"hnsw native engine supports l2-squared/dot/cosine, not {self.metric}"
            )
        self.shard_path = shard_path
        self.shard_name = shard_name
        self.class_name = class_name  # before _restore (metric labels)
        self.metrics = metrics
        self._lib = _load_lib()
        self._lock = threading.RLock()
        self.dim: Optional[int] = None
        self._h = None
        self._cleanup_running = threading.Semaphore(1)  # one cycle at a time
        self._snapshot_path = os.path.join(shard_path, "hnsw.snapshot")
        self._log = VectorLog(os.path.join(shard_path, "hnsw.log")) if persist else None
        # what the restore did (`/debug/index` `restore`)
        self.last_restore: Optional[dict] = None
        if persist:
            self._restore()

    # -- internals -----------------------------------------------------------

    def _native_metric(self) -> int:
        return _METRIC_L2 if self.metric == vi.DISTANCE_L2 else _METRIC_DOT

    def _ensure_handle(self, dim: int) -> None:
        if self._h is None:
            self.dim = dim
            self._h = self._lib.hnsw_new(
                dim,
                self._native_metric(),
                self.config.max_connections,
                self.config.ef_construction,
                0x5EED,
            )

    def _prep(self, v: np.ndarray) -> np.ndarray:
        v = np.ascontiguousarray(v, dtype=np.float32)
        if self.metric == vi.DISTANCE_COSINE:
            n = float(np.linalg.norm(v))
            if n > 0:
                v = v / n
        return v

    def _restore(self) -> None:
        """Load the snapshot, replay the delta log into the graph. The
        stages carry the device indexes' names (index/tpu.py `_restore`):
        the graph's load and inserts are `land`; nothing is staged, grown,
        flushed or owed by a device here."""
        replay_stats: dict = {}
        rows = 0
        with tracing.stage("vector.restore", shard=self.shard_name) as st:
            sums = tracing.StageSums()
            sums.enter("land")
            if os.path.exists(self._snapshot_path):
                h = self._lib.hnsw_load(self._snapshot_path.encode())
                if h:
                    self._h = h
                    # dim is embedded in the snapshot; probe via a search no-op is
                    # overkill — store alongside
                    dim_file = self._snapshot_path + ".dim"
                    if os.path.exists(dim_file):
                        self.dim = int(open(dim_file).read().strip())
            for op, doc_id, vec in sums.timed(VectorLog.replay(
                    self._log.path, stats=replay_stats, sums=sums),
                    "log.parse"):
                rows += 1
                if op == "add":
                    v = np.asarray(vec, dtype=np.float32)  # already normalized at log time
                    self._ensure_handle(v.shape[0])
                    self._lib.hnsw_add(self._h, doc_id, _f32p(np.ascontiguousarray(v)))
                elif self._h is not None:
                    self._lib.hnsw_delete(self._h, doc_id)
            sums.leave()
            VectorLog.report_replay_stats(self._log.path, replay_stats)
            sums.publish()
            st.note(rows=rows)
        # `rows`: the delta's records (the snapshot's are the library's)
        self.last_restore = restore_record("graph", rows, st, sums,
                                           replay_stats)

    def _ef(self, k: int) -> int:
        ef = self.config.ef
        if ef != -1:
            return max(ef, k)
        # autoEfFromK (search.go:46)
        ef = k * self.config.dynamic_ef_factor
        ef = min(max(ef, self.config.dynamic_ef_min), self.config.dynamic_ef_max)
        return max(ef, k)

    # -- VectorIndex ---------------------------------------------------------

    def add(self, doc_id: int, vector: np.ndarray) -> None:
        v = self._prep(vector)
        with self._lock:
            if self.dim is not None and v.shape[0] != self.dim:
                raise ValueError(f"dim mismatch: index has {self.dim}, got {v.shape[0]}")
            self._ensure_handle(v.shape[0])
            if self._log is not None:
                self._log.append_add(int(doc_id), v)
            self._lib.hnsw_add(self._h, int(doc_id), _f32p(v))
            self._maybe_cleanup()  # re-adds tombstone the old node

    def add_batch(self, doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            vectors = np.ascontiguousarray(vectors / norms)
        ids = np.ascontiguousarray(np.asarray(doc_ids, dtype=np.uint64))
        with self._lock:
            if self.dim is not None and vectors.shape[1] != self.dim:
                raise ValueError(f"dim mismatch: index has {self.dim}, got {vectors.shape[1]}")
            self._ensure_handle(int(vectors.shape[1]))
            if self._log is not None:
                self._log.append_add_batch(ids, vectors)
            t0 = time.perf_counter()
            self._lib.hnsw_add_batch(self._h, len(ids), _u64p(ids), _f32p(vectors))
            self._obs_index("add", "graph_insert", t0, ops=len(ids))
            self._maybe_cleanup()  # re-adds tombstone the old nodes

    # tombstone pressure that triggers CleanUpTombstonedNodes inline (the
    # reference runs it on a cyclemanager timer, delete.go:177 — here the
    # write path that crosses the threshold pays for the cycle). Counted
    # natively (physical nodes - live), so re-add tombstones and tombstones
    # replayed from the log all count.
    _CLEANUP_MIN_TOMBS = 1024

    def _maybe_cleanup(self) -> None:
        """Kick the cleanup cycle off-thread when tombstone pressure crosses
        the threshold: the triggering write returns immediately instead of
        eating the O(n) repair inline (the reference's cyclemanager role).
        Searches still serialize with the cycle on the index lock — the
        native engine is single-writer by design — but no single caller is
        singled out to pay for it."""
        phys = int(self._lib.hnsw_node_count(self._h))
        live = int(self._lib.hnsw_size(self._h))
        if phys - live < max(self._CLEANUP_MIN_TOMBS, live):
            return
        if self._cleanup_running.acquire(blocking=False):
            def run():
                try:
                    # through cleanup_tombstones so background cycles land
                    # in the same metrics as explicit ones
                    self.cleanup_tombstones()
                finally:
                    self._cleanup_running.release()

            threading.Thread(target=run, daemon=True, name="hnsw-cleanup").start()

    def delete(self, *doc_ids: int) -> None:
        with self._lock:
            if self._h is None:
                return
            t0 = time.perf_counter()
            for d in doc_ids:
                if self._log is not None:
                    self._log.append_delete(int(d))
                self._lib.hnsw_delete(self._h, int(d))
            self._obs_index("delete", "tombstone", t0, ops=len(doc_ids))
            self._set_tombstone_gauge()
            self._maybe_cleanup()

    def cleanup_tombstones(self) -> int:
        """Reassign neighbors of deleted nodes, move the entrypoint, and
        physically remove them (delete.go:177-422). -> nodes removed."""
        with self._lock:
            if self._h is None:
                return 0
            t0 = time.perf_counter()
            removed = int(self._lib.hnsw_cleanup(self._h))
            self._obs_index("cleanup", "tombstone_cycle", t0)
            m = self.metrics
            if m is not None:
                cls, shard = self._metric_labels()
                m.vector_index_tombstone_cleanups.labels(cls, shard).inc()
            self._set_tombstone_gauge()
            return removed

    def _set_tombstone_gauge(self) -> None:
        """Gauge tracks live tombstone pressure: updated when tombstones are
        CREATED (delete) and after cleanup removes them — not only
        post-cleanup, where it would always read ~0."""
        m = self.metrics
        if m is None:
            return
        cls, shard = self._metric_labels()
        m.vector_index_tombstones.labels(cls, shard).set(
            max(0, self.node_count_locked() - len(self)))

    def node_count_locked(self) -> int:
        return int(self._lib.hnsw_node_count(self._h)) if self._h else 0

    def compact(self) -> None:
        """Uniform compaction surface with the TPU index: cleanup +
        condense the delta log into a fresh snapshot."""
        self.cleanup_tombstones()
        self.flush()

    def node_count(self) -> int:
        """Physical node count incl. tombstones (test/metrics surface)."""
        with self._lock:
            return int(self._lib.hnsw_node_count(self._h)) if self._h else 0

    def contains(self, doc_id: int) -> bool:
        with self._lock:
            return bool(self._h and self._lib.hnsw_contains(self._h, int(doc_id)))

    def __len__(self) -> int:
        with self._lock:
            return int(self._lib.hnsw_size(self._h)) if self._h else 0

    def distancer_name(self) -> str:
        return self.metric

    def search_by_vector(
        self, vector: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        q = self._prep(vector)
        with self._lock:
            if self._h is None:
                return np.zeros(0, np.uint64), np.zeros(0, np.float32)
            out_ids = np.zeros(k, dtype=np.uint64)
            out_d = np.zeros(k, dtype=np.float32)
            if allow_list is not None:
                allow = np.ascontiguousarray(allow_list.to_array(), dtype=np.uint64)
                if allow.size < self.config.flat_search_cutoff:
                    n = self._lib.hnsw_flat_search(
                        self._h, _f32p(q), k, _u64p(allow), allow.size, _u64p(out_ids), _f32p(out_d)
                    )
                else:
                    n = self._lib.hnsw_search(
                        self._h, _f32p(q), k, self._ef(k), _u64p(allow), allow.size,
                        _u64p(out_ids), _f32p(out_d),
                    )
            else:
                n = self._lib.hnsw_search(
                    self._h, _f32p(q), k, self._ef(k), None, 0, _u64p(out_ids), _f32p(out_d)
                )
            return out_ids[:n], out_d[:n]

    def search_by_vectors(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            vectors = np.ascontiguousarray(vectors / norms)
        b = vectors.shape[0]
        with self._lock:
            if self._h is None:
                return np.zeros((b, 0), np.uint64), np.zeros((b, 0), np.float32)
            if allow_list is not None and len(allow_list) < self.config.flat_search_cutoff:
                return super().search_by_vectors(vectors, k, allow_list)
            allow = None
            a_n = 0
            if allow_list is not None:
                allow = np.ascontiguousarray(allow_list.to_array(), dtype=np.uint64)
                a_n = allow.size
            out_ids = np.zeros((b, k), dtype=np.uint64)
            out_d = np.full((b, k), np.inf, dtype=np.float32)
            counts = np.zeros(b, dtype=np.int32)
            self._lib.hnsw_search_batch(
                self._h, _f32p(vectors), b, k, self._ef(k), _u64p(allow), a_n,
                _u64p(out_ids), _f32p(out_d),
                counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            )
            # mask out unfilled tails
            for i in range(b):
                if counts[i] < k:
                    out_d[i, counts[i]:] = np.inf
                    out_ids[i, counts[i]:] = np.iinfo(np.uint64).max
            return out_ids, out_d

    def search_by_vector_distance(
        self,
        vector: np.ndarray,
        target_distance: float,
        max_limit: int,
        allow_list: Optional[AllowList] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Iteratively double the limit (search.go:90-157)."""
        limit = 64
        while True:
            ids, dists = self.search_by_vector(vector, min(limit, max_limit), allow_list)
            if len(ids) == 0:
                return ids, dists
            if (dists > target_distance).any() or limit >= max_limit or len(ids) >= len(self):
                keep = dists <= target_distance
                return ids[keep][:max_limit], dists[keep][:max_limit]
            limit *= 2

    def update_user_config(self, updated: vi.HnswUserConfig) -> None:
        with self._lock:
            vi.validate_config_update(self.config, updated)
            self.config = updated

    def flush(self) -> None:
        """Snapshot + truncate the delta log (commit-log condense analog)."""
        with self._lock:
            if self._h is None:
                return
            if self._log is not None:
                tmp = self._snapshot_path + ".tmp"
                if self._lib.hnsw_save(self._h, tmp.encode()):
                    os.replace(tmp, self._snapshot_path)
                    with open(self._snapshot_path + ".dim", "w") as f:
                        f.write(str(self.dim))
                    self._log.rewrite([])
                self._log.flush()

    def drop(self) -> None:
        with self._lock:
            if self._h is not None:
                self._lib.hnsw_free(self._h)
                self._h = None
            self.dim = None
            if self._log is not None:
                self._log.close()
                for p in (self._log.path, self._snapshot_path, self._snapshot_path + ".dim"):
                    try:
                        os.remove(p)
                    except FileNotFoundError:
                        pass
                self._log = None

    def shutdown(self) -> None:
        with self._lock:
            self.flush()
            if self._log is not None:
                self._log.close()
            if self._h is not None:
                self._lib.hnsw_free(self._h)
                self._h = None

    def list_files(self) -> list[str]:
        out = []
        if self._log is not None:
            out.append(self._log.path)
        if os.path.exists(self._snapshot_path):
            out.extend([self._snapshot_path, self._snapshot_path + ".dim"])
        return out
