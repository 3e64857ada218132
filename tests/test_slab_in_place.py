"""A slab too large for the device to hold twice (cohere-768-cos-10m-share at
a CPU's size): the memory ledger's device budget is set so that two
generations of the test's slab do NOT fit, and the index is held to exact
float32 brute force (benchmarks/references/exact_f32.py, at the benchmark's
tolerances: benchmarks/lib/check.py) through an import, a restart and writes
beside searches.

What is held (index/tpu.py `_ladder_capacity`, `_grow_slab`, `_in_place`,
`_retire_snapshot`, `_pin`; docs/memory.md "Growth", docs/concurrency.md
"When a write copies and when it goes in place"):

  (a) the import grows past the step at which a doubling would leave the
      budget and ends on a multiple of the scan chunk that fits it; a
      restart ends on the same capacity and answers exactly;
  (b) neither the restore nor a write makes a second whole generation of
      the slab: the ledger's transient peak, `/debug/perf` `writes` and the
      restart timeline's `slab_bytes_copied` say so;
  (c) searchers in a loop beside a writer that re-puts rows: every reply
      whole, every row in its old or in its new version, never missing and
      never twice, no search is handed a deleted array, and an acknowledged
      write is in the next search;
  (d) with room for two generations the answers are the same and the writes
      copy, as they always did;
  (e) what is retired is every snapshot that may hold the donated arrays,
      not the newest alone: a delete that copies the tombstone bits
      publishes a snapshot whose slab is its predecessor's (`ArrayLease`);
  (f) the host plane (breaker open, the auditor) reads the slab a piece at
      a time, never as a second slab on the device;
  (g) a restart under another budget comes back to the recorded capacity,
      and a grow through the host that fails leaves the rows served.
"""

import sys
import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from benchmarks.lib import check
from benchmarks.references import exact_f32
from weaviate_tpu.db import DB
from weaviate_tpu.entities.schema import ClassDef, Property
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import tpu as tpu_mod
from weaviate_tpu.index.interface import SnapshotRetired
from weaviate_tpu.index.tpu import _CHUNK, _SCAN_CHUNK, TpuVectorIndex
from weaviate_tpu.monitoring import memory, perf
from weaviate_tpu.serving import robustness

K, DIM, ROWS, POOL = 10, 16, 300_000, 64
# a slot is 73 B (row, doc-id words, tombstone): under this budget 2^17 rows
# double (3 x 9.6 MB), 2^18 rows cannot (3 x 19.1 MB) and the ladder goes on
# a quarter at a time, to three scan chunks
TIGHT = int(30e6 / 0.9)
ROOMY = int(400e6)


@pytest.fixture
def ledger():
    made = []

    def make(budget: int) -> memory.MemoryLedger:
        led = memory.configure(
            memory.MemoryLedger(device_budget_bytes=budget))
        made.append(led)
        return led

    yield make
    for led in made:
        memory.unconfigure(led)


@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(39)
    centres = rng.standard_normal((256, DIM)).astype(np.float32) * 2.0
    vecs = (centres[rng.integers(0, 256, ROWS)]
            + 0.35 * rng.standard_normal((ROWS, DIM))).astype(np.float32)
    picks = rng.choice(ROWS, POOL, replace=False)
    pool = (vecs[picks] + 0.05 * rng.standard_normal((POOL, DIM))
            ).astype(np.float32)
    top = exact_f32.TopK("cosine", pool, K)
    top.update(0, vecs)
    return vecs, pool, top.result()[0]


def _index(path, **kw) -> TpuVectorIndex:
    return TpuVectorIndex(
        parse_and_validate_config("hnsw_tpu", {"distance": "cosine"}),
        str(path), **kw)


def _import(idx, vecs) -> None:
    """The build's batches: 10,000 rows a `put_batch`."""
    for lo in range(0, len(vecs), 10_000):
        idx.add_batch(np.arange(lo, min(lo + 10_000, len(vecs))),
                      vecs[lo : lo + 10_000])


def _held_to_reference(idx, vecs, pool, gt_ids) -> None:
    ids, dists = idx.search_by_vectors(pool, K)
    out = check.check_window(
        exact_f32, "cosine", K, vecs, pool, gt_ids, np.arange(len(pool)),
        ids.astype(np.int64), dists)
    assert out["recall"] >= check.RECALL_BAR, out
    assert out["bad_distances"] == 0 and out["short_replies"] == 0, out
    assert out["unknown_rows"] == 0, out


def test_import_restart_and_search_on_a_ladder_that_fits(tmp_path, ledger,
                                                         corpus):
    """(a): fails on a growth rule that only doubles (capacity 2^19, a slab
    of 33.5 MB under a budget of 30)."""
    vecs, pool, gt_ids = corpus
    led = ledger(TIGHT)
    idx = _index(tmp_path / "ix")
    _import(idx, vecs)
    cap = idx.capacity
    assert cap % _SCAN_CHUNK == 0 and cap == 3 * _SCAN_CHUNK
    assert memory.array_bytes(idx._store) <= led.device_usable_bytes()
    assert idx.live == idx.n == ROWS
    _held_to_reference(idx, vecs, pool, gt_ids)
    idx.shutdown()
    del idx

    again = _index(tmp_path / "ix")
    assert again.capacity == cap and again.live == again.n == ROWS
    assert again.health()["writes"]["grows"] == 1     # asked for at once
    _held_to_reference(again, vecs, pool, gt_ids)
    again.shutdown()


def test_the_ladder_is_the_same_whichever_way_it_is_climbed(ledger):
    """Rung by rung (an import) and in one step (a restart): the same
    capacity for the same rows, doubling below the limit."""
    ledger(TIGHT)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "cosine"})
    for rows in (20_000, 131_073, 262_145, 300_000, 400_000, 600_000):
        a = TpuVectorIndex(cfg, "unused", persist=False)
        a.dim, a.capacity = DIM, 16_384
        for needed in range(16_384, rows, 10_000):
            a.capacity = a._ladder_capacity(needed)
        a.capacity = a._ladder_capacity(rows)
        b = TpuVectorIndex(cfg, "unused", persist=False)
        b.dim, b.capacity = DIM, 16_384
        assert b._ladder_capacity(rows) == a.capacity >= rows
        if rows <= 262_144:
            assert a.capacity & (a.capacity - 1) == 0          # a doubling
        else:
            assert a.capacity % _SCAN_CHUNK == 0
            assert a.capacity < 1.25 * rows + _SCAN_CHUNK


def test_no_second_generation_in_a_restore_or_a_write(tmp_path, ledger,
                                                      corpus):
    """(b)"""
    vecs, pool, gt_ids = corpus
    ledger(TIGHT)
    idx = _index(tmp_path / "ix")
    _import(idx, vecs)
    idx.shutdown()
    del idx

    perf.timeline_reset()
    led = ledger(TIGHT)            # a fresh one: the restart's own record
    tl = perf.startup_begin()
    try:
        idx = _index(tmp_path / "ix")
        doc = tl.summary()
    finally:
        perf.timeline_reset()
    slab = memory.array_bytes(idx._store)
    assert doc["slab_bytes"] == slab and doc["grows"] == 1
    # the one grow (16,384 rows to the final capacity) and no write's copy
    assert doc["slab_bytes_copied"] <= doc["slab_bytes"]
    assert led.summary()["write"]["cow_transient_peak_bytes"] < slab

    window = perf.configure(perf.PerfWindow(window_s=60.0))
    try:
        idx.search_by_vectors(pool, K)            # publishes a snapshot
        store = idx._store
        rows = np.arange(0, 3_000, 30)
        idx.replace_batch(rows.tolist(), ROWS + rows, vecs[rows])
        assert store.is_deleted()                 # given to the write
        idx.delete(int(ROWS + rows[0]))
        idx.add_batch(np.array([2 * ROWS]), vecs[rows[:1]])
        _held_to_reference_docs(idx, vecs, pool, gt_ids, rows)
        w = window.summary()["writes"]
    finally:
        perf.unconfigure(window)
    assert w["slab_bytes_copied"] == 0 and w["writes_copied"] == 0, w
    assert w["writes_in_place"] >= 2 and w["grows"] == 0, w
    assert w["reader_wait_ms"] >= 0.0
    assert led.summary()["write"]["cow_transient_peak_bytes"] < slab
    assert idx.capacity == 3 * _SCAN_CHUNK
    idx.shutdown()


def _held_to_reference_docs(idx, vecs, pool, gt_ids, re_put) -> None:
    """`_held_to_reference` where rows `re_put` answer to doc id ROWS + row
    (one of them, the first, re-put once more as 2 * ROWS)."""
    ids, dists = idx.search_by_vectors(pool, K)
    ids = ids.astype(np.int64)
    ids[ids == 2 * ROWS] = ROWS + re_put[0]
    moved = ids >= ROWS
    ids[moved] -= ROWS
    assert np.isin(ids[moved], re_put).all()
    out = check.check_window(
        exact_f32, "cosine", K, vecs, pool, gt_ids, np.arange(len(pool)),
        ids, dists)
    assert out["recall"] >= check.RECALL_BAR, out
    assert out["bad_distances"] == 0 and out["short_replies"] == 0, out


def test_with_room_for_two_generations_the_same_answers_and_a_copy(
        tmp_path, ledger, corpus):
    """(d)"""
    vecs, pool, gt_ids = corpus
    led = ledger(ROOMY)
    idx = _index(tmp_path / "ix", persist=False)
    _import(idx, vecs)
    assert idx.capacity == 1 << 19                  # doublings all the way
    _held_to_reference(idx, vecs, pool, gt_ids)
    store = idx._store
    rows = np.arange(0, 3_000, 30)
    idx.replace_batch(rows.tolist(), ROWS + rows, vecs[rows])
    assert not store.is_deleted()                   # copied: a reader's
    assert led.summary()["write"]["cow_transient_peak_bytes"] \
        >= memory.array_bytes(store)
    assert idx.health()["writes"]["writes_copied"] >= 1
    idx.delete(int(ROWS + rows[0]))
    idx.add_batch(np.array([2 * ROWS]), vecs[rows[:1]])
    _held_to_reference_docs(idx, vecs, pool, gt_ids, rows)


def test_a_search_enqueued_before_an_in_place_write_reads_the_old_rows(
        tmp_path, ledger):
    """The device runs its programs in order: a search that was enqueued on
    a generation gets that generation's rows though the write that follows
    overwrites them where they lie. What it must never get is a deleted
    array, and a search that starts after the write sees the write."""
    ledger(int(1.2e6 / 0.9))       # a 16,384 x 16 slab of 1 MB: no second
    idx = TpuVectorIndex(
        parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"}),
        str(tmp_path / "ix"), persist=False)
    rng = np.random.default_rng(5)
    vecs = rng.integers(-8, 8, (500, 16)).astype(np.float32)
    idx.add_batch(np.arange(500), vecs)
    vec_of = dict(zip(range(500), vecs))
    q = vecs[:8] + 0.25
    nxt = 1000
    for _ in range(20):
        before = idx.search_by_vectors(q, K)
        snap = idx._read_snapshot()[0]
        finalize = idx.search_by_vectors_async(q, K)
        # every row the queries found is re-put far away, in its own slot
        hit = np.unique(before[0].astype(np.int64))
        far = np.arange(nxt, nxt + len(hit))
        store = idx._store
        idx.replace_batch(hit.tolist(), far,
                          np.full((len(hit), 16), 90.0, np.float32))
        assert store.is_deleted() and snap.lease.retired
        ids, dists = finalize()
        np.testing.assert_array_equal(ids, before[0])
        np.testing.assert_array_equal(dists, before[1])
        # a caller that kept the snapshot is sent to the one that followed
        again, _ = idx._dispatch_search(snap, q, K)()
        assert not np.isin(again.astype(np.int64), hit).any()
        after, _ = idx.search_by_vectors(q, K)
        assert not np.isin(after.astype(np.int64), hit).any()
        # and back, under new doc ids, for the next round
        back = np.arange(nxt + len(hit), nxt + 2 * len(hit))
        idx.replace_batch(far.tolist(), back,
                          np.stack([vec_of.pop(int(d)) for d in hit]))
        vec_of.update(zip(back.tolist(), idx.host_rows(
            idx._read_snapshot()[0])[0][[idx._doc_to_slot[int(d)]
                                      for d in back]]))
        nxt += 2 * len(hit)
    assert idx.n == 500 and idx.health()["writes"]["writes_copied"] == 0


# -- (e): the snapshots that share the donated arrays -------------------------

def _small_l2(tmp_path, ledger, n=500):
    """A 16,384 x 16 slab of 1 MB under a budget with room for a copy of
    the 16 KB of tombstone bits and none for a second slab; integer rows:
    distances exact."""
    ledger(int(1.5e6 / 0.9))
    idx = TpuVectorIndex(
        parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"}),
        str(tmp_path / "ix"), persist=False)
    vecs = np.random.default_rng(5).integers(-8, 8, (n, 16)).astype(
        np.float32)
    idx.add_batch(np.arange(n), vecs)
    return idx, vecs


def _two_snapshots_on_one_slab(idx):
    """-> (S1, S2): S1 published, then a delete whose tombstone bits are
    COPIED (they fit) and published by a reader's slow path, so that S2 is
    the published snapshot and holds S1's slab."""
    s1 = idx._read_snapshot()[0]
    idx.delete(499)
    s2 = idx._read_snapshot()[0]
    assert s2 is not s1 and s2.store is s1.store
    assert s2.tombs is not s1.tombs and not s1.tombs.is_deleted()
    assert idx.health()["writes"]["writes_copied"] >= 1
    return s1, s2


def test_a_snapshot_kept_from_before_a_copying_delete_is_retired_with_the_slab(
        tmp_path, ledger):
    """The failing sequence of a protocol that retires the published
    snapshot alone: S1 is never retired, so `_pin(S1)` succeeds after the
    write donated the slab S1 shares with S2, and the dispatch dies with
    "Array has been deleted"."""
    idx, vecs = _small_l2(tmp_path, ledger)
    q = vecs[:8] + 0.25
    s1, s2 = _two_snapshots_on_one_slab(idx)
    hit = np.unique(idx.search_by_vectors(q, K)[0].astype(np.int64))
    idx.replace_batch(hit.tolist(), 1000 + hit,
                      np.full((len(hit), 16), 90.0, np.float32))
    assert s1.store.is_deleted()                  # given to the write
    for kept in (s1, s2):
        # a search that kept either is sent to the snapshot that followed
        ids, _ = idx._dispatch_search(kept, q, K)()
        assert not np.isin(ids.astype(np.int64), hit).any()
        assert idx._pin(kept, follow=False) is None
        with pytest.raises(SnapshotRetired):
            idx.host_rows(kept)                   # the auditor's shed
    assert s1.lease is s2.lease and s1.lease.retired
    assert idx._read_snapshot()[0].lease is not s1.lease
    np.testing.assert_array_equal(
        idx.search_by_vectors(q, K)[0], idx._dispatch_search(s1, q, K)()[0])


def test_a_writer_waits_for_a_pin_on_the_older_snapshot_of_the_slab(
        tmp_path, ledger):
    """A search already inside its enqueue on S1 when S2 (same slab) is
    the published one: the in-place write waits for it too."""
    idx, vecs = _small_l2(tmp_path, ledger)
    q = vecs[:8] + 0.25
    s1, s2 = _two_snapshots_on_one_slab(idx)
    before = idx._dispatch_search(s1, q, K)()
    assert idx._pin(s1) is s1                     # inside its enqueue
    hit = np.unique(before[0].astype(np.int64))
    done = threading.Event()

    def write():
        idx.replace_batch(hit.tolist(), 1000 + hit,
                          np.full((len(hit), 16), 90.0, np.float32))
        done.set()

    th = threading.Thread(target=write)
    th.start()
    try:
        deadline = time.monotonic() + 30.0
        while not s1.lease.retired and time.monotonic() < deadline:
            time.sleep(0.001)
        assert s1.lease.retired
        assert not done.wait(0.2)                 # the writer waits
        assert not s1.store.is_deleted()
        # the pinned search enqueues on the rows it was dispatched on,
        # again and nested (a group's dispatches share one snapshot)
        finalize = idx._enqueue_search(s1, q, K, None)
        assert idx._pin(s1) is s1
        idx._unpin(s1)
    finally:
        idx._unpin(s1)
        th.join(timeout=60.0)
    assert done.is_set() and s1.store.is_deleted()
    ids, dists = finalize()                       # enqueued before the write
    np.testing.assert_array_equal(ids, before[0])
    np.testing.assert_array_equal(dists, before[1])
    after, _ = idx.search_by_vectors(q, K)
    assert not np.isin(after.astype(np.int64), hit).any()


# -- (f): the host plane at a fill that has no room for a slice ---------------

def test_the_breakers_host_plane_reads_the_slab_a_piece_at_a_time(
        tmp_path, ledger, corpus, monkeypatch):
    """`snap.store[:n]` is a second slab on the device: the host plane
    fetches pieces of `_HOST_PIECE` rows and answers as the reference
    does, with the breaker open and the slab written in place before."""
    vecs, pool, gt_ids = corpus
    ledger(TIGHT)
    idx = _index(tmp_path / "ix", persist=False)
    _import(idx, vecs)
    idx.search_by_vectors(pool, K)                # a snapshot to retire
    rows = np.arange(0, 3_000, 30)
    store = idx._store
    idx.replace_batch(rows.tolist(), ROWS + rows, vecs[rows])
    assert store.is_deleted()
    pieces = []
    read = tpu_mod._read_rows
    monkeypatch.setattr(
        tpu_mod, "_read_rows",
        lambda store, lo: pieces.append(min(store.shape[0], tpu_mod._HOST_PIECE))
        or read(store, lo))
    breaker = robustness.configure_breaker(
        robustness.CircuitBreaker(failure_threshold=1, reset_timeout_s=600))
    try:
        breaker.record_failure(RuntimeError("device lost"))
        assert breaker.state() == robustness.STATE_OPEN
        ids, dists = idx.search_by_vectors_host(pool, K)
    finally:
        robustness.unconfigure_breaker(breaker)
    assert pieces and max(pieces) <= tpu_mod._HOST_PIECE
    assert sum(pieces) >= ROWS and len(pieces) == -(-ROWS // max(pieces))
    ids = ids.astype(np.int64)
    moved = ids >= ROWS
    ids[moved] -= ROWS
    out = check.check_window(
        exact_f32, "cosine", K, vecs, pool, gt_ids, np.arange(len(pool)),
        ids, dists)
    assert out["recall"] == 1.0 and out["bad_distances"] == 0, out
    assert memory.host_rows_cache_bytes(idx) >= ROWS * DIM * 4
    idx.release_host_fallback_cache()


# -- (g): the recorded capacity, and a grow that fails ------------------------

def test_a_restart_under_another_budget_comes_back_to_the_recorded_capacity(
        tmp_path, ledger, corpus):
    vecs, pool, gt_ids = corpus
    ledger(TIGHT)
    idx = _index(tmp_path / "ix")
    _import(idx, vecs)
    cap = idx.capacity
    assert cap == 3 * _SCAN_CHUNK
    assert open(tmp_path / "ix" / "capacity").read().split() == [str(cap)]
    assert str(tmp_path / "ix" / "capacity") in idx.list_files()
    idx.shutdown()
    del idx
    # under this budget the ladder alone would double to 2^19
    ledger(ROOMY)
    again = _index(tmp_path / "ix", persist=False)
    again.dim, again.capacity = DIM, 16_384
    assert again._ladder_capacity(ROWS) == 1 << 19
    again = _index(tmp_path / "ix")
    assert again.capacity == cap and again.live == ROWS
    _held_to_reference(again, vecs, pool, gt_ids)
    # growth after the restart is the ladder's again
    assert again._recorded == 0
    again.shutdown()
    del again
    # a record today's budget cannot hold, or one that cannot be read,
    # leaves the ladder to decide
    for text in (str(100 * cap), "not a number"):
        (tmp_path / "ix" / "capacity").write_text(text)
        ledger(TIGHT)
        third = _index(tmp_path / "ix")
        assert third.capacity == cap
        third.shutdown()
        del third


def test_a_grow_through_the_host_that_fails_leaves_the_rows_served(
        tmp_path, ledger, corpus, monkeypatch):
    """The old slab is given back before the new one is made: where that
    fails the rows go back into a slab of the old size and the write that
    asked for more fails alone."""
    vecs, pool, _ = corpus
    ledger(TIGHT)
    idx = _index(tmp_path / "ix", persist=False)
    n, full = 250_000, 1 << 18
    _import(idx, vecs[:n])
    assert idx.capacity == full
    want = idx.search_by_vectors(pool, K)
    land, calls = idx._land_slab, []

    def refuse_the_larger(cap, *rest):
        calls.append(cap)
        if cap > full:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return land(cap, *rest)

    more = np.arange(n, n + 20_000)
    monkeypatch.setattr(idx, "_land_slab", refuse_the_larger)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        idx.add_batch(more, vecs[more])
    assert calls == [3 * _SCAN_CHUNK, full]
    assert idx.capacity == full and idx._store.shape[0] == full
    got = idx.search_by_vectors(pool, K)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    monkeypatch.undo()
    idx.add_batch(more, vecs[more])
    assert idx.capacity == 3 * _SCAN_CHUNK and idx.live == n + 20_000


# -- (c): searchers beside a writer, through the shard ------------------------

def _uuid(row: int) -> str:
    return str(uuidlib.UUID(int=row + 1))


def _row(u: str) -> int:
    return int(u.replace("-", ""), 16) - 1


def _objs(rows, vecs):
    return [StorObj(class_name="Up", uuid=_uuid(int(r)),
                    properties={"bucket": int(r) % 10}, vector=vecs[int(r)])
            for r in rows]


def test_searchers_beside_a_writer_that_overwrites_in_place(tmp_path,
                                                            ledger):
    """(c): fails with "Array has been deleted" on a version whose writer
    donates without retiring the snapshot and waiting for its pins."""
    n, dim = 6_000, 32
    # 16,384 slots x (128 + 4 + 9) B = 2.3 MB: no room for a second
    led = ledger(int(3.0e6 / 0.9))
    rng = np.random.default_rng(7)
    centres = rng.standard_normal((64, dim)).astype(np.float32) * 2.0
    vecs = (centres[rng.integers(0, 64, n)]
            + 0.35 * rng.standard_normal((n, dim))).astype(np.float32)
    queries = (vecs[rng.integers(0, n, 64)]
               + 0.05 * rng.standard_normal((64, dim))).astype(np.float32)
    top = exact_f32.TopK("l2-squared", queries, K)
    top.update(0, vecs)
    want_ids = top.result()[0]

    db = DB(str(tmp_path / "data"))
    cls = db.add_class(
        ClassDef(name="Up", properties=[
            Property(name="bucket", data_type=["int"])],
            vector_index_type="hnsw_tpu"),
        parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"}))
    db.post_startup()
    assert not any(cls.put_batch(_objs(range(n), vecs)))
    index = cls.single_local_shard().vector_index
    stop, errors, counts = threading.Event(), [], [0, 0]

    def searcher():
        try:
            while not stop.is_set():
                replies = cls.object_vector_search(queries, K)
                for q, res in enumerate(replies):
                    got = [_row(r.obj.uuid) for r in res]
                    # whole, nothing twice, and (the re-put vectors never
                    # change) exactly the reference's rows: a row that is
                    # being re-put is there in its old or its new version
                    assert len(got) == K and len(set(got)) == K, got
                    assert got == list(want_ids[q]), (q, got)
                counts[0] += 1
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
            stop.set()

    def writer():
        wrng = np.random.default_rng(11)
        try:
            while not stop.is_set():
                rows = wrng.choice(n, 100, replace=False)
                assert not any(cls.put_batch(_objs(rows, vecs)))
                # read-your-writes: a row far from everything, then the
                # search for it, sent after the acknowledgement
                i = counts[1]
                probe = np.full(dim, 500.0 + i, np.float32)
                obj = StorObj(class_name="Up", uuid=_uuid(100_000 + i),
                              properties={"bucket": 0}, vector=probe)
                assert not any(cls.put_batch([obj]))
                found = cls.object_vector_search(probe[None, :], 1)[0]
                assert _row(found[0].obj.uuid) == 100_000 + i
                assert cls.delete_object(obj.uuid)
                counts[1] += 1
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
            stop.set()

    threads = [threading.Thread(target=searcher) for _ in range(4)] + [
        threading.Thread(target=writer)]
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for th in threads:
            th.start()
        # four seconds, or on a loaded machine as long as it takes for
        # both sides to have met often enough
        t_end = time.monotonic() + 4.0
        while time.monotonic() < t_end or (
                (counts[0] < 4 or counts[1] < 2) and not stop.is_set()
                and time.monotonic() < t_end + 56.0):
            time.sleep(0.05)
        stop.set()
        for th in threads:
            th.join(timeout=60.0)
    finally:
        sys.setswitchinterval(prev)
    assert not any(th.is_alive() for th in threads)
    if errors:
        raise errors[0]
    assert counts[0] >= 4 and counts[1] >= 2, counts
    h = index.health()
    # a delete alone may copy the 16 KB of tombstone bits (that fits); no
    # write copied the slab
    assert led.summary()["write"]["cow_transient_peak_bytes"] \
        < memory.array_bytes(index._store)
    assert h["writes"]["writes_in_place"] >= 2 * counts[1], h["writes"]
    assert h["capacity"] == 16_384 and h["slots"] <= h["live"] + 2 * _CHUNK
    db.shutdown()
