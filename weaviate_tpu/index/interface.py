"""The VectorIndex seam.

Reference: adapters/repos/db/vector_index.go:23-40. Everything above the
index (shard search, traverser, gRPC) passes (vector, k, allowList) down and
gets (ids, dists) back; nothing above sees index internals. Kept exactly so
here, with a batched twin (`search_by_vectors`) because the TPU path is
batch-first.
"""

from __future__ import annotations

import abc
from typing import Optional, Sequence

import numpy as np


class SnapshotRetired(RuntimeError):
    """A caller that kept an index snapshot asked for its device rows after
    a writer had overwritten that generation in place (index/tpu.py
    `_retire_snapshot`). Searches never see it: they dispatch on a snapshot
    only while they hold a pin on it."""


class AllowList(abc.ABC):
    """Filter result container (reference helpers/allow_list.go:19-29)."""

    @abc.abstractmethod
    def contains(self, doc_id: int) -> bool: ...

    @abc.abstractmethod
    def __len__(self) -> int: ...

    @abc.abstractmethod
    def to_array(self) -> np.ndarray:
        """Sorted uint64 array of allowed doc ids."""

    @abc.abstractmethod
    def contains_array(self, doc_ids: np.ndarray) -> np.ndarray:
        """Vectorized membership test -> bool array (device mask building)."""


class VectorIndex(abc.ABC):
    """Per-shard vector index (vector_index.go:23-40)."""

    # -- metric plumbing shared by the concrete indexes (hnsw metrics.go
    # parity); relies on self.shard_path / self.shard_name / self.metrics,
    # which every persistent index sets in __init__ --------------------------

    def _metric_labels(self) -> tuple[str, str]:
        """(class_name, shard_name). The owning Shard sets `class_name`
        after construction so labels match the shard-level families exactly
        (the on-disk dir is lowercased and would mislabel); the path-derived
        value is only the standalone-index fallback."""
        import os

        path = getattr(self, "shard_path", "") or ""
        cls = getattr(self, "class_name", "") or (
            os.path.basename(os.path.dirname(path.rstrip("/"))) or "")
        return cls, getattr(self, "shard_name", "") or os.path.basename(path)

    def _obs_index(self, op: str, step: str, t0: float, ops: int = 0) -> None:
        import time

        m = getattr(self, "metrics", None)
        if m is None:
            return
        cls, shard = self._metric_labels()
        m.vector_index_durations.labels(op, step, cls, shard).observe(
            (time.perf_counter() - t0) * 1000.0)
        if ops:
            m.vector_index_ops.labels(op, cls, shard).inc(ops)

    @abc.abstractmethod
    def add(self, doc_id: int, vector: np.ndarray) -> None: ...

    def add_batch(self, doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        for d, v in zip(doc_ids, vectors):
            self.add(int(d), v)

    def replace_batch(self, old_doc_ids: Sequence[int],
                      doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        """An upsert's two halves in one call: drop `old_doc_ids` (the
        previous versions, under the doc ids they were stored with) and add
        the batch. An index whose readers could see the gap between the two
        overrides it to make them one step (index/tpu.py); here they are
        the two calls they always were."""
        if len(old_doc_ids):
            self.delete(*old_doc_ids)
        if len(doc_ids):
            self.add_batch(doc_ids, vectors)

    @abc.abstractmethod
    def delete(self, *doc_ids: int) -> None: ...

    @abc.abstractmethod
    def search_by_vector(
        self, vector: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """-> (doc_ids uint64 [<=k], dists float32 [<=k]) sorted ascending."""

    def search_by_vectors(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched kNN [B, D] -> ([B, k] ids, [B, k] dists); default loops."""
        ids, ds = [], []
        for v in vectors:
            i, d = self.search_by_vector(v, k, allow_list)
            pad = k - len(i)
            if pad:
                # sentinel = uint64 max (matches the TPU index's -1 cast);
                # consumers must treat dist==inf rows as absent
                i = np.concatenate([i, np.full(pad, np.iinfo(np.uint64).max, np.uint64)])
                d = np.concatenate([d, np.full(pad, np.inf, np.float32)])
            ids.append(i)
            ds.append(d)
        return np.stack(ids), np.stack(ds)

    @abc.abstractmethod
    def search_by_vector_distance(
        self,
        vector: np.ndarray,
        target_distance: float,
        max_limit: int,
        allow_list: Optional[AllowList] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """All results within target_distance (search.go:90-157 semantics:
        iteratively double the limit until past the target distance)."""

    @abc.abstractmethod
    def update_user_config(self, updated) -> None: ...

    @abc.abstractmethod
    def flush(self) -> None:
        """Flush WAL/commit-log state to disk (SwitchCommitLogs analog)."""

    @abc.abstractmethod
    def drop(self) -> None: ...

    @abc.abstractmethod
    def shutdown(self) -> None: ...

    def post_startup(self) -> None:
        """Prefill device/cache state after restore (startup.go:169-174)."""

    def list_files(self) -> list[str]:
        """Files to include in a backup (hnsw/backup.go ListFiles)."""
        return []

    def contains(self, doc_id: int) -> bool:
        return False

    def __len__(self) -> int:
        return 0

    def distancer_name(self) -> str:
        return "l2-squared"

    # multi-vector/compression stats surface
    def compressed(self) -> bool:
        return False
