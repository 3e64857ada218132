"""A corpus that is re-imported while it is searched (cohere-768-cos-upsert at
a CPU's size): the program held to exact float32 brute force
(benchmarks/references/exact_f32.py) under a stream of re-puts, through
`class_index.put_batch` and the normal search path.

What is held, after every round of "re-put 100 rows, search 64 queries":

- the answers: ids equal brute force's in order, every distance inside its
  tolerance; deletes without a re-put are gone;
- the bounds of the stream (index/tpu.py `_place_rows`, `_live_runs`):
  `capacity` is what it was, `slots <= live + 2 x _CHUNK`, and after a
  restart the restore lands at most 1.05 x live rows and the log on disk is
  under 1.5 x the bytes of one add record a live row, however many writes
  were made;
- the guarantee (docs/concurrency.md): an acknowledged batch is visible to
  the next search, no reply names one uuid twice, a row being re-put is in
  every reply in its old or in its new version, and a search dispatched on a
  snapshot BEFORE a slot was handed out again returns the old rows.
"""

import os
import shutil
import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from benchmarks.lib import check
from benchmarks.references import exact_f32
from weaviate_tpu.config.config import IvfConfig
from weaviate_tpu.db import DB
from weaviate_tpu.entities.filters import LocalFilter
from weaviate_tpu.entities.schema import ClassDef, Property
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import tpu
from weaviate_tpu.index.tpu import _CHUNK, TpuVectorIndex

K, BUCKETS, BATCH = 10, 10, 100


def _uuid(row: int) -> str:
    return str(uuidlib.UUID(int=row + 1))


def _row(u: str) -> int:
    return int(u.replace("-", ""), 16) - 1


def _objs(rows, vecs):
    return [StorObj(class_name="Up", uuid=_uuid(int(r)),
                    properties={"bucket": int(r) % BUCKETS},
                    vector=vecs[int(r)]) for r in rows]


class Corpus:
    """A DB with one class, its rows, and which of them are there."""

    def __init__(self, path, metric, n, dim, seed=0):
        self.path, self.metric = str(path), metric
        rng = np.random.default_rng(seed)
        centres = rng.standard_normal((64, dim)).astype(np.float32) * 2.0
        self.vecs = (centres[rng.integers(0, 64, n)]
                     + 0.35 * rng.standard_normal((n, dim))).astype(np.float32)
        self.queries = (self.vecs[rng.integers(0, n, 64)]
                        + 0.05 * rng.standard_normal((64, dim))
                        ).astype(np.float32)
        self.alive = np.ones(n, bool)
        self._truth = (None, None)
        self.rng = rng
        self.open()
        for lo in range(0, n, 10_000):
            rows = range(lo, min(lo + 10_000, n))
            assert not any(self.cls.put_batch(_objs(rows, self.vecs)))

    def open(self, path=None):
        self.db = DB(path or self.path)
        self.cls = self.db.add_class(
            ClassDef(name="Up", properties=[
                Property(name="bucket", data_type=["int"])],
                vector_index_type="hnsw_tpu"),
            parse_and_validate_config("hnsw_tpu", {"distance": self.metric}))
        self.db.post_startup()

    @property
    def index(self) -> TpuVectorIndex:
        return self.cls.single_local_shard().vector_index

    def truth(self, allowed=None):
        """exact_f32's top K of every query over the rows that are there
        (and that `allowed` [Q, n] lets through)."""
        key = (self.alive.tobytes(), None if allowed is None
               else allowed.tobytes())
        if self._truth[0] != key:       # the re-put vectors never change
            top = exact_f32.TopK(self.metric, self.queries, K)
            mask = np.broadcast_to(
                self.alive, (len(self.queries), len(self.alive)))
            top.update(0, self.vecs,
                       mask if allowed is None else mask & allowed)
            self._truth = (key, top.result())
        return self._truth[1]

    def held_to_truth(self, replies, allowed=None):
        want_ids, want_d = self.truth(allowed)
        for q, res in enumerate(replies):
            got = [_row(r.obj.uuid) for r in res]
            want = [int(i) for i in want_ids[q] if i >= 0]
            assert got == want, (q, got, want)
            assert len(set(got)) == len(got)
            d = np.array([r.distance for r in res], np.float32)
            tol = check.DIST_RTOL * np.maximum(
                np.abs(want_d[q][: len(d)]), check.DIST_FLOOR) \
                + check.DIST_ATOL.get(self.metric, 0.0)
            assert np.all(np.abs(d - want_d[q][: len(d)]) <= tol), q

    def search_held(self):
        self.held_to_truth(self.cls.object_vector_search(self.queries, K))

    def re_put(self, count=BATCH):
        rows = self.rng.choice(np.flatnonzero(self.alive), count,
                               replace=False)
        assert not any(self.cls.put_batch(_objs(rows, self.vecs)))

    def delete(self, count):
        for r in self.rng.choice(np.flatnonzero(self.alive), count,
                                 replace=False):
            assert self.cls.delete_object(_uuid(int(r)))
            self.alive[r] = False

    def bounds_hold(self, capacity):
        h = self.index.health()
        live = int(self.alive.sum())
        assert h["live"] == live
        # never more than the build's (a restart packs the rows into the
        # smallest capacity that holds them, which can be less)
        assert h["capacity"] <= capacity
        assert h["slots"] <= live + 2 * _CHUNK
        assert h["slot_reuse_refused"] is None
        return h

    def restart(self, path=None):
        """Reopen (the same directory, or a copy of it) -> the restore."""
        self.open(path)
        live = int(self.alive.sum())
        restore = self.index.last_restore
        assert self.index.live == live
        assert restore["rows"] <= 1.05 * live, restore
        record = 17 + 4 * self.vecs.shape[1]
        assert self.index.health()["log"]["bytes"] < 1.5 * live * record
        assert os.path.getsize(os.path.join(
            self.index.shard_path, "vector.log")) < 1.5 * live * record
        return restore


@pytest.mark.parametrize("metric,n,dim,rounds", [
    ("cosine", 10_000, 64, 300),
    ("l2-squared", 6_000, 64, 180),
    ("cosine", 2_000, 768, 60),
], ids=["cosine-10000x64", "l2-6000x64", "cosine-2000x768"])
def test_the_stream_stays_exact_and_the_size_of_the_live_rows(
        tmp_path, metric, n, dim, rounds):
    """Three times the corpus re-put in batches of 100, a search after
    every batch, deletes without a re-put mixed in, a clean restart in the
    middle and at the end, and a kill with the log's tail torn."""
    c = Corpus(tmp_path / "data", metric, n, dim)
    capacity = c.index.capacity
    c.search_held()
    for rnd in range(rounds):
        c.re_put()
        if rnd % 20 == 7:
            c.delete(3)
        c.search_held()
        if rnd % 50 == 0:
            c.bounds_hold(capacity)
        if rnd == rounds // 2:
            c.db.shutdown()
            restore = c.restart()
            assert restore["log"]["dead_records"] > 0.9 * n
            c.search_held()
            c.bounds_hold(capacity)
    h = c.bounds_hold(capacity)
    assert h["writes"]["slots_reused"] >= rounds // 2 * BATCH - BATCH
    assert h["writes"]["grows"] <= 1        # the build's one doubling
    # a kill: what the disk holds after the last acknowledged batch, with
    # the tail of a write that never finished
    c.cls.flush()
    killed = str(tmp_path / "killed")
    shutil.copytree(c.path, killed)
    log = os.path.join(killed, os.path.relpath(
        os.path.join(c.index.shard_path, "vector.log"), c.path))
    with open(log, "ab") as f:
        f.write(b"\x01" + b"\x07" * 40)      # a torn add record
    c.db.shutdown()
    c.restart()
    c.search_held()
    c.bounds_hold(capacity)
    c.db.shutdown()
    c.restart(killed)
    c.search_held()
    c.bounds_hold(capacity)
    # and the stream goes on from the restored state
    c.re_put()
    c.search_held()
    c.db.shutdown()


def test_a_reused_slot_under_a_filter_and_in_a_filtered_group(tmp_path):
    c = Corpus(tmp_path / "data", "l2-squared", 4_000, 32)
    for _ in range(30):
        c.re_put()
    assert c.index.health()["writes"]["slots_reused"] == 30 * BATCH
    rows = np.arange(len(c.vecs))

    def flt(b):
        return LocalFilter.from_dict(
            {"operator": "Equal", "path": ["bucket"], "valueInt": b})

    # one filter for the whole batch: the masked scan and the gather
    for b in (0, 7):
        allowed = np.broadcast_to(rows % BUCKETS == b,
                                  (len(c.queries), len(rows)))
        c.held_to_truth(
            c.cls.object_vector_search(c.queries, K, flt(b)), allowed)
    # a group, every slot its own filter (or none), before and after more
    # slots change hands under the filters' cached slot lists
    for _ in range(2):
        wants = [None if q % 4 == 3 else q % BUCKETS
                 for q in range(len(c.queries))]
        allowed = np.stack([
            np.ones(len(rows), bool) if w is None else rows % BUCKETS == w
            for w in wants])
        done = c.cls.object_vector_search_multi_async(
            c.queries, K, [None if w is None else flt(w) for w in wants])
        replies = done()
        assert not any(isinstance(r, Exception) for r in replies)
        c.held_to_truth(replies, allowed)
        c.re_put()
    c.db.shutdown()


def test_a_search_dispatched_before_a_reuse_returns_the_old_rows(tmp_path):
    """The snapshot contract with reused slots: the device arrays are
    functional updates and the host's slot table is copied before a slot
    inside a published snapshot's prefix is rewritten."""
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = TpuVectorIndex(cfg, str(tmp_path / "ix"), persist=False)
    rng = np.random.default_rng(5)
    vecs = rng.integers(-8, 8, (500, 16)).astype(np.float32)
    idx.add_batch(np.arange(500), vecs)
    q = vecs[:8] + 0.25
    before = idx.search_by_vectors(q, K)
    snap = idx._read_snapshot()[0]
    finalize = idx.search_by_vectors_async(q, K)     # pins `snap`
    # every row the queries found is re-put under a new doc id with a
    # vector far away: each takes its old slot, in place
    hit = np.unique(before[0].astype(np.int64))
    idx.replace_batch(hit.tolist(), 1000 + hit,
                      np.full((len(hit), 16), 90.0, np.float32))
    assert idx.n == 500 and idx.health()["free_slots"] == 0
    assert snap.slot_to_doc is not idx._slot_to_doc      # copied, not torn
    ids, dists = finalize()
    np.testing.assert_array_equal(ids, before[0])
    np.testing.assert_array_equal(dists, before[1])
    # the host plane of the pinned snapshot reads the old docs too
    slots = np.array([idx._doc_to_slot[1000 + int(d)] for d in hit])
    np.testing.assert_array_equal(snap.slot_to_doc[slots], hit)
    # and a search sent now sees none of the old versions
    after, _ = idx.search_by_vectors(q, K)
    assert not np.isin(after.astype(np.int64), hit).any()


def test_a_reply_hydrated_after_the_re_put_still_names_the_row(tmp_path):
    """A search dispatched on the old doc ids and hydrated after the re-put
    took their lookup entries away: the reply names the rows (in their new
    version), it does not come back short."""
    c = Corpus(tmp_path / "data", "l2-squared", 2_000, 16)
    shard = c.cls.single_local_shard()
    want_ids, _ = c.truth()
    done = shard.object_vector_search_async(c.queries, K)
    found = np.unique(want_ids)
    assert not any(c.cls.put_batch(_objs(found, c.vecs)))
    replies = done()
    for q, res in enumerate(replies):
        assert [_row(r.obj.uuid) for r in res] == list(want_ids[q])
    # a row that is DELETED in between is gone, not resurrected
    done = shard.object_vector_search_async(c.queries, K)
    gone = int(want_ids[0, 0])
    assert c.cls.delete_object(_uuid(gone))
    assert gone not in [_row(r.obj.uuid) for r in done()[0]]
    c.db.shutdown()


def test_four_searchers_while_a_writer_re_puts(tmp_path):
    """Every reply has k results, none a uuid twice, recall 1.0 against
    brute force (the re-put vectors never change); and a batch the writer
    was acknowledged is in the very next search it sends."""
    import sys

    c = Corpus(tmp_path / "data", "l2-squared", 6_000, 32)
    want_ids, _ = c.truth()
    stop, errors, counts = threading.Event(), [], [0, 0]

    def searcher():
        try:
            while not stop.is_set():
                replies = c.cls.object_vector_search(c.queries, K)
                for q, res in enumerate(replies):
                    got = [_row(r.obj.uuid) for r in res]
                    assert len(got) == K and len(set(got)) == K, got
                    # the probes' rows lie far from every query
                    assert got == list(want_ids[q]), (q, got)
                counts[0] += 1
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors.append(e)
            stop.set()

    def writer():
        rng = np.random.default_rng(11)
        try:
            n = 0
            while not stop.is_set():
                rows = rng.choice(len(c.vecs), BATCH, replace=False)
                assert not any(c.cls.put_batch(_objs(rows, c.vecs)))
                # read-your-writes: a new row far from everything, then
                # the search for it, sent after the acknowledgement
                probe = np.full(32, 500.0 + n, np.float32)
                obj = StorObj(class_name="Up", uuid=_uuid(100_000 + n),
                              properties={"bucket": 0}, vector=probe)
                assert not any(c.cls.put_batch([obj]))
                top = c.cls.object_vector_search(probe[None, :], 1)[0]
                assert _row(top[0].obj.uuid) == 100_000 + n
                assert c.cls.delete_object(obj.uuid)
                n += 1
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
        time.sleep(4.0)
        stop.set()
        for th in threads:
            th.join(timeout=60.0)
    finally:
        sys.setswitchinterval(prev)
    assert not any(th.is_alive() for th in threads)
    if errors:
        raise errors[0]
    assert counts[0] >= 4 and counts[1] >= 2, counts
    h = c.index.health()
    assert h["slots"] <= h["live"] + 2 * _CHUNK
    c.db.shutdown()


def _index_with(tmp_path, n, **cfg):
    rng = np.random.default_rng(2)
    vecs = rng.integers(-50, 50, (n, 16)).astype(np.float32)
    idx = TpuVectorIndex(
        parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared",
                                               **cfg}),
        str(tmp_path / "ix"), persist=False)
    idx.add_batch(np.arange(n), vecs)
    return idx, vecs


def _re_put_at_index(idx, vecs, rounds):
    """Re-put rows under fresh doc ids -> doc id of each row."""
    doc_of = np.arange(len(vecs))
    rng = np.random.default_rng(9)
    nxt = len(vecs)
    for _ in range(rounds):
        rows = rng.choice(len(vecs), 50, replace=False)
        new = np.arange(nxt, nxt + 50)
        nxt += 50
        idx.replace_batch(doc_of[rows].tolist(), new, vecs[rows])
        doc_of[rows] = new
    return doc_of


def _brute_docs(vecs, doc_of, q, k):
    d = ((q[:, None, :] - vecs[None]) ** 2).sum(-1)
    return doc_of[np.argsort(d, axis=1, kind="stable")[:, :k]]


def test_a_compressed_index_refuses_reuse_and_says_so(tmp_path):
    idx, vecs = _index_with(tmp_path, 600)
    idx.config.pq.segments = 4
    idx.compress()
    doc_of = _re_put_at_index(idx, vecs, 4)
    h = idx.health()
    assert h["slot_reuse_refused"] == "compressed"
    assert h["writes"]["slots_reused"] == 0
    assert h["slots"] == 600 + 4 * 50 and h["live"] == 600
    assert h["tombstones"] == 200 == h["free_slots"]
    ids, _ = idx.search_by_vectors(vecs[:8], 1)
    np.testing.assert_array_equal(ids[:, 0].astype(np.int64), doc_of[:8])


def test_an_ivf_bucket_table_refuses_reuse_and_says_so(tmp_path):
    """The bucket table (here: the PCA prefilter's layout) names the
    partition of a slot's OLD row, so its index keeps appending."""
    token = tpu.set_ivf_config(IvfConfig(
        enabled=True, nlist=8, min_n=256, top_p=8, train_sample=4096,
        train_iters=4, pca_dim=8))
    try:
        idx, vecs = _index_with(tmp_path, 600)
        assert idx.health()["ivf"]["trained"] and not idx._ivf_tiled
        doc_of = _re_put_at_index(idx, vecs, 4)
        h = idx.health()
        assert h["slot_reuse_refused"] == "ivf_layout"
        assert h["writes"]["slots_reused"] == 0 and h["slots"] == 800
        ids, _ = idx.search_by_vectors(vecs[:8], 1)
        np.testing.assert_array_equal(ids[:, 0].astype(np.int64), doc_of[:8])
    finally:
        tpu.unset_ivf_config(token)


def test_a_tiled_ivf_layout_puts_a_re_put_into_its_partitions_free_slots(
        tmp_path):
    """The tiled layout hands a row a free slot of ITS partition's tile: a
    re-put of an unchanged vector lands where its old version lay (that
    slot is freed first), and the slots stay the tiles' whatever is
    written."""
    token = tpu.set_ivf_config(IvfConfig(
        enabled=True, nlist=8, min_n=256, top_p=8, train_sample=4096,
        train_iters=4))
    try:
        idx, vecs = _index_with(tmp_path, 600)
        assert idx.health()["ivf"]["layout"] == "tiles"
        slots0, gen0 = idx.health()["slots"], idx._ivf_gen
        parts0 = {d: s // idx._ivf_cap_p
                  for d, s in idx._doc_to_slot.items()}
        doc_of = _re_put_at_index(idx, vecs, 4)
        h = idx.health()
        assert h["slot_reuse_refused"] is None and idx._ivf_gen == gen0
        assert h["writes"]["slots_reused"] == 200
        assert h["slots"] == slots0 and h["live"] == 600
        assert h["free_slots"] == slots0 - 600 == h["tombstones"]
        # every re-put row lies in the partition its first version lay in
        for row in range(50):
            assert idx._doc_to_slot[int(doc_of[row])] // idx._ivf_cap_p \
                == parts0[row]
        ids, _ = idx.search_by_vectors(vecs[:8], 1)
        np.testing.assert_array_equal(ids[:, 0].astype(np.int64), doc_of[:8])
    finally:
        tpu.unset_ivf_config(token)


def test_compact_keeps_its_behaviour_with_free_slots(tmp_path):
    idx, vecs = _index_with(tmp_path, 600)
    idx.delete(*range(0, 100))
    idx.flush()
    assert idx.health()["free_slots"] == 100 == idx.health()["tombstones"]
    idx.compact()
    h = idx.health()
    assert (h["slots"], h["live"], h["free_slots"]) == (500, 500, 0)
    ids, _ = idx.search_by_vectors(vecs[100:108], 1)
    np.testing.assert_array_equal(ids[:, 0].astype(np.int64),
                                  np.arange(100, 108))


def test_the_collision_check_is_the_batchs_own_size(tmp_path, monkeypatch):
    """`add_batch` asks the doc->slot dict about its own ids, and never
    builds an array of every live doc."""
    idx, vecs = _index_with(tmp_path, 600)
    calls = []
    real = np.fromiter
    monkeypatch.setattr(np, "fromiter",
                        lambda *a, **kw: calls.append(a) or real(*a, **kw))
    idx.add_batch(np.arange(1000, 1100), vecs[:100])
    assert not calls
    # a colliding batch still takes the per-row path, and stays right
    idx.add_batch(np.arange(1050, 1150), vecs[100:200])
    assert idx.live == 600 + 150
    ids, _ = idx.search_by_vectors(vecs[100:101], 2)
    assert set(ids[0].astype(np.int64)) == {100, 1050}


def test_the_raw_lane_serves_exactly_while_a_writer_keeps_memtables_busy(
        tmp_path):
    """`Shard.raw_plane_ready` no longer asks for empty memtables: the
    packed point gets lay the memtable's newer word (a re-put's new image
    and doc id, a delete) over the segments' answer, and a doc id replaced
    after the dispatch still finds its object."""
    from weaviate_tpu.storage import lsm, lsm_native

    if not lsm_native.available():
        pytest.skip("the native point-get library did not build here")
    c = Corpus(tmp_path / "data", "l2-squared", 3_000, 16)
    shard = c.cls.single_local_shard()
    for b in (shard.objects, shard.docid_lookup):
        b.flush_memtable()
    for _ in range(3):
        c.re_put()
    c.delete(5)
    assert len(shard.objects._mem) and len(shard.docid_lookup._mem)
    assert shard.raw_plane_ready()
    ids, dists = shard.vector_index.search_by_vectors(c.queries, K)
    packed = shard.hydrate_raw_packed(ids, dists)
    assert packed is not None
    vbuf, voffs, vflags, flat_d, counts = packed
    images = [vbuf[voffs[i]:voffs[i + 1]].tobytes()
              for i in range(len(vflags)) if vflags[i]]
    general = shard._hydrate_batch(ids, dists, False)
    assert images == [r.raw_pristine() for rows in general for r in rows]
    assert vflags.all() and counts.tolist() == [K] * len(c.queries)
    c.held_to_truth(general)
    # ids found before a re-put and a delete, hydrated after them
    want_ids, _ = c.truth()
    found = np.unique(want_ids)
    assert not any(c.cls.put_batch(_objs(found[1:], c.vecs)))
    assert c.cls.delete_object(_uuid(int(found[0])))
    vbuf, voffs, vflags, _, _ = shard.hydrate_raw_packed(ids, dists)
    flat = ids[~np.isinf(dists)]
    rows_of = {int(d): _row(r.obj.uuid)
               for rows, drow in zip(general, ids) for r, d in zip(rows, drow)}
    for i, doc in enumerate(flat.tolist()):
        assert bool(vflags[i]) == (rows_of[doc] != int(found[0]))
    # the overlay itself: a value replaced, a key gone, the rest untouched
    buf = np.frombuffer(b"aaabbbbcc", np.uint8)
    out, offs, flags = lsm.overlay_packed(
        (buf, np.array([0, 3, 7, 7, 9]), np.array([1, 1, 0, 1], np.int8)),
        {1: b"XY", 2: b"new", 3: lsm._TOMBSTONE})
    assert out.tobytes() == b"aaaXYnew" and offs.tolist() == [0, 3, 5, 8, 8]
    assert flags.tolist() == [1, 1, 1, 0]
    c.db.shutdown()


def _version_value(version: int, key: bytes) -> bytes:
    """3.3 KB that are one version's or nobody's: a torn copy shows."""
    return bytes([version % 251]) * 3300 + key


def test_four_packed_readers_beside_one_writer_read_whole_values(tmp_path):
    """The memtable's mirror is probed with no lock while the writer puts:
    every value a packed get returns is a whole old or a whole new one,
    never torn, never missing; a get that starts after a put was
    acknowledged returns that put or a later one; a key that is deleted
    and put again reads as gone or whole; and the flushes and compactions
    the writer makes on the way retire segments and mirrors under the
    readers without freeing one that is still being read."""
    import struct
    import sys

    from weaviate_tpu.storage import lsm_native
    from weaviate_tpu.storage.lsm import STRATEGY_REPLACE, Bucket

    if not lsm_native.available():
        pytest.skip("the native point-get library did not build here")
    keys = [struct.pack("<Q", i) * 2 for i in range(300)]
    steady, flicker = keys[:250], keys[250:]
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    b.put_many((k, _version_value(0, k)) for k in keys)
    b.flush_memtable()
    acked = dict.fromkeys(steady, 0)     # the last version put() returned
    offs = np.arange(len(keys) + 1, dtype=np.int64) * 16
    key_buf = b"".join(keys)
    errors: list = []
    stop = threading.Event()
    reads = [0] * 4

    def reader(slot):
        try:
            while not stop.is_set():
                floor = [acked[k] for k in steady]
                packed = b.multi_get_packed(key_buf, offs)
                if packed is None:
                    # a segment of the snapshot was compacted away before
                    # its first native open (`lsm_native.seg_handle` opens
                    # by path): the general path's turn, not this test's
                    continue
                vbuf, voffs, flags = packed
                data, voffs = vbuf.tobytes(), voffs.tolist()
                for i, k in enumerate(keys):
                    v = data[voffs[i]:voffs[i + 1]]
                    if not flags[i]:
                        assert i >= 250 and not v, ("missing", i)
                        continue
                    assert len(v) == 3316 and v[3300:] == k and \
                        v[:3300] == v[:1] * 3300, ("torn", i)
                    if i < 250:
                        # versions only rise, so % 251 is compared over
                        # a window the writer cannot lap within one call
                        ahead = (v[0] - floor[i]) % 251
                        assert ahead < 125, ("stale", i, v[0], floor[i])
                reads[slot] += 1
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(repr(e))
            stop.set()

    threads = [threading.Thread(target=reader, args=(i,)) for i in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        for t in threads:
            t.start()
        rng = np.random.default_rng(7)
        deadline = time.monotonic() + 6.0
        mirrors, puts = set(), 0
        while time.monotonic() < deadline and not stop.is_set():
            k = steady[int(rng.integers(0, 250))]
            b.put(k, _version_value(acked[k] + 1, k))
            acked[k] += 1
            puts += 1
            f = flicker[int(rng.integers(0, 50))]
            if rng.random() < 0.5:
                b.delete(f)
            else:
                b.put(f, _version_value(puts, f))
            if b._mem.mirror is not None:
                mirrors.add(id(b._mem.mirror))
            if puts % 400 == 0:
                b.flush_memtable()
            if puts % 1500 == 0:
                b.compact_pair()
        stop.set()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
        stop.set()
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert min(reads) >= 3 and puts >= 800 and len(mirrors) >= 2
    with b._lock:
        assert b._native_inflight == 0 and not b._retired
    b.shutdown()


def test_a_flush_under_a_packed_get_retires_the_mirror_until_it_leaves(
        tmp_path, monkeypatch):
    """The memtable's mirror has the segments' contract: a flush while a
    packed get that holds it is in flight parks it, open, and the last
    reader to leave frees it."""
    from weaviate_tpu.storage import lsm_native
    from weaviate_tpu.storage.lsm import STRATEGY_REPLACE, Bucket

    if not lsm_native.available():
        pytest.skip("the native point-get library did not build here")
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    b.put(b"on-disk", b"old")
    b.flush_memtable()
    b.put(b"on-disk", b"new")
    b.put(b"mem-only", b"m")
    b.delete(b"gone")
    offs = np.array([0, 7, 15, 19], dtype=np.int64)
    assert b.multi_get_packed(b"on-diskmem-onlygone", offs) is not None
    mirror = b._mem.mirror
    seen = {}
    real = lsm_native.multi_get_packed

    def flush_inside(segs, key_buf, key_offs, mem=None):
        # after the snapshot, outside the lock, before the native call
        b.flush_memtable()
        b.put(b"mem-only", b"after the snapshot")
        seen.update(parked=list(b._retired), handle=mem._h, mem=mem,
                    segments=len(b._segments) - len(segs))
        return real(segs, key_buf, key_offs, mem)

    monkeypatch.setattr(lsm_native, "multi_get_packed", flush_inside)
    vbuf, voffs, flags = b.multi_get_packed(b"on-diskmem-onlygone", offs)
    assert seen["mem"] is mirror and seen["parked"] == [mirror]
    assert seen["handle"] != 0 and seen["segments"] == 1
    # the snapshot's answer: the retired memtable over the older segments
    assert vbuf.tobytes() == b"newm" and flags.tolist() == [1, 1, 0]
    assert mirror._h == 0 and not b._retired and b._native_inflight == 0
    monkeypatch.undo()
    vbuf, voffs, flags = b.multi_get_packed(b"on-diskmem-onlygone", offs)
    assert vbuf.tobytes() == b"newafter the snapshot"
    assert b._mem.mirror is not mirror
    b.shutdown()


def test_debug_perf_point_get_counts_the_memtable_layer(tmp_path):
    """`/debug/perf` `point_get`: the four counters of the memtable layer
    are there and read 0 while nothing is written; beside a writer a
    hydrate is two calls that asked a memtable, the first of each bucket's
    generation built its mirror, and no call was left to the general path."""
    from weaviate_tpu.monitoring import perf
    from weaviate_tpu.storage import lsm_native

    if not lsm_native.available():
        pytest.skip("the native point-get library did not build here")
    c = Corpus(tmp_path / "data", "l2-squared", 2_000, 16)
    shard = c.cls.single_local_shard()
    for b in (shard.objects, shard.docid_lookup):
        b.flush_memtable()
    ids, dists = shard.vector_index.search_by_vectors(c.queries, K)
    window = perf.configure(perf.PerfWindow(window_s=60.0))
    layer = ("mem_layer_calls", "mem_keys", "mirror_builds",
             "overlay_fallbacks")
    try:
        assert shard.hydrate_raw_packed(ids, dists) is not None
        pg = window.summary()["point_get"]
        assert pg["keys"] == 2 * ids.size
        assert [pg[k] for k in layer] == [0, 0, 0, 0]
        c.re_put()
        ids, dists = shard.vector_index.search_by_vectors(c.queries, K)
        window.clear()
        for _ in range(3):
            assert shard.hydrate_raw_packed(ids, dists) is not None
        pg = window.summary()["point_get"]
        assert pg["mem_layer_calls"] == 6 and pg["mirror_builds"] == 2
        assert pg["mem_keys"] > 0 and pg["overlay_fallbacks"] == 0
        c.held_to_truth(shard._hydrate_batch(ids, dists, False))
    finally:
        perf.unconfigure(window)
    c.db.shutdown()


def test_a_log_of_short_runs_replays_without_reading_what_follows(tmp_path):
    """A log of re-puts is thousands of short add runs between deletes:
    each run's checksums are taken over the run, not over every byte that
    follows it (which made the replay quadratic in the writes)."""
    from weaviate_tpu.index.tpu import VectorLog

    path = str(tmp_path / "vector.log")
    log = VectorLog(path)
    rng = np.random.default_rng(4)
    want = {}
    for run in range(300):
        ids = np.arange(run * 10, run * 10 + 10)
        vecs = rng.standard_normal((10, 8)).astype(np.float32)
        log.append_add_batch(ids, vecs)
        want.update(zip(ids.tolist(), vecs))
        log.append_delete(run * 10 + 3)
        del want[run * 10 + 3]
    log.close()
    summed = []
    real_sum = np.ndarray.sum

    class Counting(np.ndarray):
        def sum(self, *a, **kw):
            summed.append(self.size)
            return real_sum(self, *a, **kw)

    real_frombuffer = np.frombuffer
    try:
        np.frombuffer = lambda *a, **kw: real_frombuffer(*a, **kw).view(
            Counting)
        got, stats = {}, {}
        for op, ids, vecs in VectorLog.replay_batches(path, stats=stats):
            if op == "add":
                got.update(zip(np.asarray(ids).tolist(), np.asarray(vecs)))
            else:
                got.pop(int(ids))
    finally:
        np.frombuffer = real_frombuffer
    assert stats == {} and got.keys() == want.keys()
    assert all(np.array_equal(got[d], want[d]) for d in want)
    # bytes the replay summed: a small multiple of the log, not its square
    assert sum(summed) < 4 * os.path.getsize(path)


def test_debug_perf_writes_section_and_its_zero_cost_when_off(tmp_path):
    """`/debug/perf` `writes`: the six stages, the two spans over them and
    the counters, a window's; nothing is kept while the plane is down, and
    a restore's landing is not counted as serving writes."""
    from weaviate_tpu.monitoring import perf, tracing

    assert perf.get_window() is None
    tracing.write_stage("decode", 1.0)          # no tracer: nothing happens
    idx, vecs = _index_with(tmp_path, 600)
    window = perf.configure(perf.PerfWindow(window_s=60.0))
    try:
        assert "writes" not in window.summary()
        doc_of = _re_put_at_index(idx, vecs, 3)
        idx.delete(int(doc_of[0]))
        idx.search_by_vectors(vecs[:4], 1)       # the read after the write
        for stage, ms in (("decode", 3.0), ("lsm", 2.0), ("batch", 9.0)):
            perf.note_write_phase(stage, ms)
        out = window.summary()
        w = out["writes"]
        assert (w["rows"], w["batches"]) == (150, 3)
        assert w["slots_reused"] == 150 and w["slots_appended"] == 0
        assert w["tombstones_applied"] == 1 and w["grows"] == 0
        assert w["snapshots_published"] == 4     # three writes, one read
        assert w["slab_bytes_copied"] >= 3 * 16384 * 16 * 4
        assert 0 < w["upload_bytes"] < w["slab_bytes_copied"]
        assert w["batch_ms"]["p50_ms"] == 9.0
        assert w["index_held_ms"]["samples"] == 3
        assert set(w["phases"]) == set(perf.WRITE_PHASES)
        assert w["phases"]["device_write"]["samples"] == 3
        assert out["read_lock_waits"] == 1
        assert out["read_lock_wait_ms_sum"] >= 0.0
        assert set(perf.PHASES).isdisjoint(
            {"lsm", "index", "publish", "device_write"})
    finally:
        perf.unconfigure(window)
