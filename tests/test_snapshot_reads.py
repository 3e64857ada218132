"""Snapshot-isolated concurrent reads (index/tpu.py IndexSnapshot).

Pins the three contracts of the lock-free read plane:

1. no torn results — a reader racing inserts/deletes/compaction only ever
   sees ids that were live in SOME published snapshot, with distances that
   match the vector actually stored for that id;
2. bit-identical results — snapshot reads (sync AND async two-phase)
   return exactly what a quiesced sync search returns on the same data,
   on every read-path case: full scan, filtered masked scan,
   small-allowList gather, PQ rescore tier, PQ codes-only tier;
3. readers never block on a writer-held lock (timeout-guarded).

Kept bounded (thread counts, seconds) so the stress tier is '-m not slow'
safe for every CI run; crank _SECONDS up for a soak.
"""

import threading
import time

import numpy as np

from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index.tpu import TpuVectorIndex

_SECONDS = 1.5
DIM = 16


def _mk_index(tmp_path, n=400, pq=None, seed=0, **cfg_extra):
    rng = np.random.default_rng(seed)
    # small-integer vectors: every L2 distance is exact integer arithmetic
    # in f32 regardless of accumulation order, so equality checks are exact
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    d = {"distance": "l2-squared", **cfg_extra}
    if pq is not None:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu", d)
    idx = TpuVectorIndex(cfg, str(tmp_path / "snapix"), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs, rng


# -- 1. reader/writer stress: no torn results --------------------------------

def test_stress_concurrent_readers_writers_no_torn_results(tmp_path):
    """4 search threads against 3 insert/delete/compact threads on one
    index: every returned id must have been inserted by the time the
    search returned (live in some published snapshot — deleted ids may
    legitimately appear while an older snapshot serves), every distance
    must match the id's actual stored vector, and rows stay sorted."""
    n0 = 300
    idx, vecs, rng = _mk_index(tmp_path, n=n0)
    all_vecs = {i: vecs[i] for i in range(n0)}  # id -> vector ever stored
    next_id = [n0]
    deleted: list[int] = []
    book = threading.Lock()
    stop = threading.Event()
    errors: list[BaseException] = []

    def guard(fn):
        def go():
            try:
                while not stop.is_set():
                    fn()
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)
                stop.set()
        return go

    def inserter():
        with book:
            i = next_id[0]
            next_id[0] += 1
            v = np.random.default_rng(i).integers(
                -8, 8, DIM).astype(np.float32)
            all_vecs[i] = v
        idx.add(i, v)

    def deleter():
        with book:
            if len(deleted) >= n0 - 50:
                return
            target = deleted[-1] + 2 if deleted else 0
            if target >= n0:
                return
            deleted.append(target)
        idx.delete(target)

    def compactor():
        idx.compact()
        time.sleep(0.05)

    def searcher():
        q = np.random.default_rng(2).integers(
            -8, 8, (4, DIM)).astype(np.float32)
        ids, dists = idx.search_by_vectors(q, 5)
        with book:
            known = int(next_id[0])
        for row_ids, row_d in zip(ids, dists):
            valid = ~np.isinf(row_d)
            got_d = row_d[valid]
            # rows come back ascending — a torn merge would not
            assert np.all(np.diff(got_d) >= 0)
            for doc, dd in zip(row_ids[valid], got_d):
                doc = int(doc)
                # the id existed when the search returned (no snapshot
                # ever contained an id that was never inserted)...
                assert doc < known, f"id {doc} returned before insertion"
                with book:
                    v = all_vecs[doc]
                # ...and its distance is the distance to ITS vector for
                # one of the queries (integer-exact): a torn store read
                # would produce a distance matching no stored row
                true = ((q - v[None, :]) ** 2).sum(1)
                assert np.any(np.abs(true - dd) < 1e-3), (
                    f"id {doc}: returned distance {dd} matches no query "
                    "against its stored vector (torn read?)")

    workers = [inserter, inserter, deleter, compactor,
               searcher, searcher, searcher, searcher]
    threads = [threading.Thread(target=guard(w), daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    deadline = time.monotonic() + _SECONDS
    while time.monotonic() < deadline and not stop.is_set():
        time.sleep(0.02)
    stop.set()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "worker wedged (deadlock?)"
    if errors:
        raise errors[0]
    # recall parity after quiesce: the stressed index answers exactly like
    # a single-threaded brute force over its final live set
    idx.flush()
    live = sorted(set(all_vecs) - set(deleted))
    mat = np.stack([all_vecs[i] for i in live])
    q = np.random.default_rng(3).integers(-8, 8, (8, DIM)).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    for r in range(len(q)):
        true = np.sort(((mat - q[r]) ** 2).sum(1))[:5]
        np.testing.assert_allclose(np.sort(dists[r]), true, atol=1e-3)


# -- 2. readers never block on a writer-held lock ----------------------------

def test_reader_never_blocks_on_writer_held_lock(tmp_path):
    """A writer sitting on the index lock (the worst-case convoy pre-PR)
    must not delay a reader at all: the published snapshot serves the
    search lock-free. Timeout-guarded well under the hold time."""
    idx, vecs, _ = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:4], 3)  # publish + compile
    hold_s = 3.0
    holding = threading.Event()
    release = threading.Event()

    def writer():
        with idx._lock:
            holding.set()
            release.wait(hold_s)

    w = threading.Thread(target=writer, daemon=True)
    w.start()
    assert holding.wait(5.0)
    t0 = time.perf_counter()
    handle = idx.search_by_vectors_async(vecs[:4], 3)
    ids, dists = handle()
    elapsed = time.perf_counter() - t0
    release.set()
    w.join(timeout=10)
    assert ids.shape == (4, 3)
    assert elapsed < 1.0, (
        f"reader took {elapsed:.2f}s while a writer held the lock — "
        "the snapshot fast path must not touch it")
    # the fast path reports zero lock wait
    assert handle.lock_wait_ms == 0.0


# -- 3. bit-identical: snapshot/async reads == quiesced sync reads -----------

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


def test_bit_identical_sync_async_uncompressed_paths(tmp_path):
    from weaviate_tpu.storage.bitmap import Bitmap

    idx, vecs, rng = _mk_index(tmp_path)
    q = _case_queries(vecs, rng)
    _assert_identical(idx, q, 5)                       # full scan
    allow = Bitmap(range(0, 300, 2))
    idx.config.flat_search_cutoff = 0
    _assert_identical(idx, q, 5, allow)                # filtered masked scan
    idx.config.flat_search_cutoff = 10_000
    _assert_identical(idx, q, 5, allow)                # small-allow gather
    small = Bitmap(range(0, 40))
    _assert_identical(idx, q, 5, small)


def test_bit_identical_sync_async_pq_tiers(tmp_path):
    from weaviate_tpu.storage.bitmap import Bitmap

    for rescore in (True, False):
        sub = tmp_path / ("rs" if rescore else "codes")
        sub.mkdir()
        idx, vecs, rng = _mk_index(
            sub, pq={"enabled": False, "segments": 8, "centroids": 16,
                     "rescore": rescore})
        idx.compress()
        assert idx.compressed
        q = _case_queries(vecs, rng)
        _assert_identical(idx, q, 5)                   # PQ tier, unfiltered
        allow = Bitmap(range(0, 300, 2))
        idx.config.flat_search_cutoff = 0
        _assert_identical(idx, q, 5, allow)            # PQ tier, filtered
        idx.config.flat_search_cutoff = 10_000
        _assert_identical(idx, q, 5, Bitmap(range(0, 40)))  # gather under PQ


def test_pq_codes_only_async_is_lock_free_two_phase(tmp_path):
    """The PQ codes-only tier — pre-PR the documented sync fallback of
    search_by_vectors_async — now enqueues without touching the lock."""
    idx, vecs, rng = _mk_index(
        tmp_path, pq={"enabled": False, "segments": 8, "centroids": 16,
                      "rescore": False})
    idx.compress()
    assert idx.compressed and idx._rescore_dev is None
    q = _case_queries(vecs, rng)
    idx.search_by_vectors(q, 5)  # publish + compile

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

    spy = SpyLock(idx._lock)
    idx._lock = spy
    try:
        fin = idx.search_by_vectors_async(q, 5)
        ids, dists = fin()
    finally:
        idx._lock = spy.inner
    assert ids.shape == (6, 5)
    assert spy.count == 0, "codes-only async dispatch took the index lock"


def test_snapshot_pins_arrays_across_delete_and_compact(tmp_path):
    """A dispatch enqueued BEFORE a delete+compact finalizes AFTER it with
    the old snapshot's answer — the mutation cannot tear it."""
    idx, vecs, _ = _mk_index(tmp_path)
    q = vecs[:4].copy()
    expect_ids, expect_d = idx.search_by_vectors(q, 3)
    fin = idx.search_by_vectors_async(q, 3)  # enqueued on snapshot S
    # mutate heavily: delete the current winners, then compact (rebuilds
    # device state wholesale and refreshes the allow token)
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


def test_read_your_writes_after_staged_mutations(tmp_path):
    """The pre-read check: a search immediately after add/delete sees the
    write (flush + republish on the slow path), exactly like the old
    flush-under-lock behavior."""
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


# -- 4. shard satellite: allowList cache is LRU, not FIFO ---------------------

def test_allow_cache_lru_eviction_order(tmp_path):
    import uuid as uuidlib

    from weaviate_tpu.db.shard import Shard, filter_signature
    from weaviate_tpu.entities.filters import LocalFilter
    from weaviate_tpu.entities.schema import ClassDef, Property
    from weaviate_tpu.entities.storobj import StorObj

    cd = ClassDef(name="Lru", properties=[
        Property(name="n", data_type=["int"]),
    ], vector_index_type="hnsw_tpu")
    shard = Shard("s0", str(tmp_path / "lru"), cd,
                  parse_and_validate_config(
                      "hnsw_tpu", {"distance": "l2-squared"}))
    try:
        rng = np.random.default_rng(0)
        shard.put_batch([
            StorObj(class_name="Lru", uuid=str(uuidlib.UUID(int=i + 1)),
                    properties={"n": i},
                    vector=rng.standard_normal(DIM).astype(np.float32))
            for i in range(20)])

        def flt(i):
            return LocalFilter.from_dict(
                {"operator": "Equal", "path": ["n"], "valueInt": i})

        # fill the 16-entry cache in insertion order 0..15
        first = [shard.build_allow_list(flt(i)) for i in range(16)]
        # HIT filter 0: under LRU it moves to most-recently-used (the old
        # FIFO left it first in line for eviction)
        assert shard.build_allow_list(flt(0)) is first[0]
        # one more filter evicts exactly ONE entry: the least recently
        # used is now filter 1 — the hot filter 0 survives
        shard.build_allow_list(flt(16))
        sig = filter_signature
        assert sig(flt(0)) in shard._allow_cache
        assert sig(flt(1)) not in shard._allow_cache
        assert sig(flt(16)) in shard._allow_cache
        # and the hot filter still serves the SAME cached bitmap object
        assert shard.build_allow_list(flt(0)) is first[0]
    finally:
        shard.shutdown()
