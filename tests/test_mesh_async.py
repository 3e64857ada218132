"""Mesh serving promotion (index/mesh.py MeshSnapshot): lock-free async
reads on the 8-virtual-device mesh.

The mesh twin of test_snapshot_reads.py, pinning the contracts the
serving promotion introduced:

1. bit-identical results — mesh snapshot reads (sync AND async
   two-phase) return exactly what a quiesced sync search returns on
   every read-path case: full scan, filtered masked scan, small
   allowList, PQ rescore tier, PQ codes-only tier, and a k wide enough
   that the cross-shard all-gather must merge candidates from every
   device;
2. zero index-lock acquisitions on a warmed async read, plus the fused
   one-fetch / zero-host-translation invariant (costmodel JGL015);
3. snapshot pinning — a dispatch enqueued before delete+compact
   finalizes with the pre-mutation snapshot's answer;
4. read-your-writes — a search immediately after add/delete republishes
   on the slow path and sees the write.
"""

import threading
import time

import numpy as np
import pytest

from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index.mesh import MeshVectorIndex
from weaviate_tpu.monitoring import costmodel, tracing
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16


def _mk_index(tmp_path, n=400, pq=None, seed=0, **cfg_extra):
    rng = np.random.default_rng(seed)
    # small-integer vectors: every L2 distance is exact integer arithmetic
    # in f32 regardless of accumulation order, so equality checks are exact
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    d = {"distance": "l2-squared", **cfg_extra}
    if pq is not None:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu_mesh", d)
    # compress() persists pq.npz even with persist=False, so the shard
    # directory must exist
    (tmp_path / "meshix").mkdir(parents=True, exist_ok=True)
    idx = MeshVectorIndex(cfg, str(tmp_path / "meshix"), persist=False,
                          initial_capacity_per_shard=64)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs, rng


def _case_queries(vecs, rng):
    return vecs[:6] + rng.integers(0, 2, (6, DIM)).astype(np.float32)


def _assert_identical(idx, q, k, allow=None):
    sync_ids, sync_d = idx.search_by_vectors(q, k, allow)
    fin = idx.search_by_vectors_async(q, k, allow)
    async_ids, async_d = fin()
    np.testing.assert_array_equal(sync_ids, async_ids)
    np.testing.assert_array_equal(sync_d, async_d)
    # and a repeat sync search (still quiesced) is bit-identical too
    again_ids, again_d = idx.search_by_vectors(q, k, allow)
    np.testing.assert_array_equal(sync_ids, again_ids)
    np.testing.assert_array_equal(sync_d, again_d)


# -- 1. bit-identical: async two-phase == quiesced sync ----------------------

def test_mesh_bit_identical_sync_async_uncompressed(tmp_path):
    idx, vecs, rng = _mk_index(tmp_path)
    q = _case_queries(vecs, rng)
    _assert_identical(idx, q, 5)                        # full scan
    allow = Bitmap(range(0, 300, 2))
    _assert_identical(idx, q, 5, allow)                 # filtered masked scan
    _assert_identical(idx, q, 5, Bitmap(range(0, 40)))  # small allowList
    # k wide enough that every device's local top-k contributes through
    # the all-gather + final select (400 rows over 8 shards = 50/shard)
    _assert_identical(idx, q, 48)


def test_mesh_bit_identical_sync_async_pq_tiers(tmp_path):
    for rescore in (True, False):
        sub = tmp_path / ("rs" if rescore else "codes")
        sub.mkdir()
        idx, vecs, rng = _mk_index(
            sub, pq={"enabled": False, "segments": 8, "centroids": 16,
                     "rescore": rescore})
        idx.compress()
        assert idx.compressed
        q = _case_queries(vecs, rng)
        _assert_identical(idx, q, 5)                    # PQ tier, unfiltered
        allow = Bitmap(range(0, 300, 2))
        _assert_identical(idx, q, 5, allow)             # PQ tier, filtered


# -- 2. lock-free reads: zero lock acquisitions + one fetch ------------------

class SpyLock:
    def __init__(self, inner):
        self.inner, self.count = inner, 0

    def acquire(self, *a, **kw):
        self.count += 1
        return self.inner.acquire(*a, **kw)

    def release(self):
        return self.inner.release()

    def __enter__(self):
        self.count += 1
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def test_mesh_async_read_takes_zero_index_locks_one_fetch(tmp_path):
    """The JGL015 invariant on the mesh: a warmed coalesced read acquires
    the mesh index lock ZERO times and fetches from device exactly once,
    with no host-side slot->doc translation (fused packed [B,3k])."""
    idx, vecs, rng = _mk_index(tmp_path)
    q = _case_queries(vecs, rng)
    idx.search_by_vectors(q, 5)  # publish + compile
    prev = tracing.get_tracer()
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    spy = SpyLock(idx._lock)
    idx._lock = spy
    try:
        fin = idx.search_by_vectors_async(q, 5)
        ids, dists = fin()
    finally:
        idx._lock = spy.inner
        tracing.configure(prev)
    assert ids.shape == (6, 5)
    assert spy.count == 0, "mesh async dispatch took the index lock"
    shape = fin.shape
    assert shape is not None
    assert shape.ndev == 8
    assert shape.fetches == 1
    assert costmodel.fused_invariant_ok(shape)


def test_mesh_search_step_refuses_a_host_translation(tmp_path):
    """mesh_search_step keeps its `fused` static for the benchmark's
    compile tests (ROADMAP.md Queue 3); the program translates on the
    device and nothing else, so anything but True is refused."""
    from weaviate_tpu.parallel.mesh_search import mesh_search_step

    idx, vecs, _ = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:4], 3)  # publish
    snap = idx._read_snapshot()[0]

    def step(fused):
        return mesh_search_step(
            snap.store, snap.sq_norms, snap.tombs, snap.counts_dev,
            snap.zero_words, vecs[:4], snap.slot_to_doc_dev, 3, idx.metric,
            False, False, False, fused, idx.mesh)

    assert np.asarray(step(True)).shape == (4, 9)  # [B, 3k]: doc ids inside
    for bad in (False, None, 1):
        with pytest.raises(ValueError, match="fused must be True"):
            step(bad)


def test_mesh_reader_never_blocks_on_writer_held_lock(tmp_path):
    idx, vecs, _ = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:4], 3)  # publish + compile
    holding = threading.Event()
    release = threading.Event()

    def writer():
        with idx._lock:
            holding.set()
            release.wait(3.0)

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    assert holding.wait(5.0)
    t0 = time.perf_counter()
    handle = idx.search_by_vectors_async(vecs[:4], 3)
    ids, _ = handle()
    elapsed = time.perf_counter() - t0
    release.set()
    w.join(timeout=10)
    assert ids.shape == (4, 3)
    assert elapsed < 1.0, (
        f"reader took {elapsed:.2f}s while a writer held the lock — "
        "the mesh snapshot fast path must not touch it")
    assert handle.lock_wait_ms == 0.0


# -- 3. snapshot pinning across delete + compact -----------------------------

def test_mesh_snapshot_pins_arrays_across_delete_and_compact(tmp_path):
    """A dispatch enqueued BEFORE a delete+compact finalizes AFTER it with
    the old snapshot's answer — the per-device slab rebuild cannot tear
    it (non-donated buffers pinned by the MeshSnapshot)."""
    idx, vecs, _ = _mk_index(tmp_path)
    q = vecs[:4].copy()
    expect_ids, expect_d = idx.search_by_vectors(q, 3)
    fin = idx.search_by_vectors_async(q, 3)  # enqueued on snapshot S
    for row in expect_ids:
        for doc in row:
            idx.delete(int(doc))
    idx.compact()
    got_ids, got_d = fin()  # finalizes against pinned snapshot S
    np.testing.assert_array_equal(got_ids, expect_ids)
    np.testing.assert_array_equal(got_d, expect_d)
    # a FRESH search sees the post-mutation state (winners gone)
    new_ids, _ = idx.search_by_vectors(q, 3)
    old = {int(x) for x in expect_ids.ravel()}
    assert not ({int(x) for x in new_ids.ravel()} & old)


# -- 4. read-your-writes through the slow-path republish ---------------------

def test_mesh_read_your_writes_after_staged_mutations(tmp_path):
    idx, vecs, _ = _mk_index(tmp_path, n=100)
    gen0 = idx.snapshot_gen
    v = np.full(DIM, 7.0, np.float32)
    idx.add(5000, v)
    ids, dists = idx.search_by_vectors(v[None, :], 1)
    assert int(ids[0, 0]) == 5000 and float(dists[0, 0]) == 0.0
    assert idx.snapshot_gen > gen0  # the read published a new snapshot
    idx.delete(5000)
    ids, dists = idx.search_by_vectors(v[None, :], 1)
    assert int(ids[0, 0]) != 5000
