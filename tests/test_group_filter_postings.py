"""A group's `filter` phase reads every distinct posting once (db/shard.py
object_vector_search_multi_async, inverted/searcher.py PostingMemo), a posting
is one native pass over the bucket's segments (storage/lsm.py
Bucket.roaring_get, native/lsm_get.cpp) and an intersection one native pass
over two postings (storage/bitmap.py Bitmap.and_): the same ids as the
evaluation of one filter at a time, the Python walk and numpy give, over
buckets whose layers miss keys, delete in the middle and have a memtable on
top."""

import sys
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.db.shard import Shard
from weaviate_tpu.entities.filters import FilterValidationError, LocalFilter
from weaviate_tpu.entities.schema import ClassDef, Property
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.inverted.index import filterable_bucket
from weaviate_tpu.monitoring import perf, tracing
from weaviate_tpu.storage import lsm_native
from weaviate_tpu.storage.bitmap import Bitmap

ROWS, DIM, TAGS, RARE = 360, 8, 12, 11
SEGMENTS = (1, 2, 30)

needs_library = pytest.mark.skipif(
    not lsm_native.available(),
    reason="native/lsm_get.cpp cannot be built here (no g++?): the Python "
           "walk serves every posting, and there is no native walk to hold "
           "to it")


def _equal(tag):
    return {"path": ["tags"], "operator": "Equal", "valueInt": int(tag)}


# every shape goes through the one evaluator; tag 0 and tag 1 are popular and
# shared by most of them, tag RARE is missing from most segments
WHERES = {
    "Equal": _equal(0),
    "And": {"operator": "And", "operands": [_equal(0), _equal(1)]},
    "Or": {"operator": "Or", "operands": [_equal(2), _equal(RARE)]},
    "Not": {"operator": "Not", "operands": [_equal(0)]},
    "ContainsAny": {"path": ["tags"], "operator": "ContainsAny",
                    "valueInt": [1, RARE]},
    "ContainsAll": {"path": ["tags"], "operator": "ContainsAll",
                    "valueInt": [0, 2]},
    "range": {"path": ["n"], "operator": "GreaterThan", "valueInt": 300},
}
HOLDS = {
    "Equal": lambda bag, n: 0 in bag,
    "And": lambda bag, n: 0 in bag and 1 in bag,
    "Or": lambda bag, n: 2 in bag or RARE in bag,
    "Not": lambda bag, n: 0 not in bag,
    "ContainsAny": lambda bag, n: 1 in bag or RARE in bag,
    "ContainsAll": lambda bag, n: 0 in bag and 2 in bag,
    "range": lambda bag, n: n > 300,
}


class Tagged:
    """A shard of ROWS objects with a bag of tags and a number each, written
    in `nseg` flushed batches: tag RARE is in a few of them only, some
    objects are deleted half way (a layer with deletions in the middle, from
    two segments on) and, after the last flush, some are added and some
    deleted (the memtable's adds and dels on top)."""

    def __init__(self, path, nseg):
        cd = ClassDef(name="Tagged", vector_index_type="hnsw_tpu", properties=[
            Property(name="tags", data_type=["int[]"]),
            Property(name="n", data_type=["int"])])
        self.shard = Shard("s0", str(path), cd, parse_and_validate_config(
            "hnsw_tpu", {"distance": "l2-squared"}))
        self.rng = np.random.default_rng(31)
        self.live: dict[str, tuple[list, int]] = {}   # uuid -> (bag, n)
        self._next = 0
        per = ROWS // nseg
        with self.shard.store.compaction_paused():
            for s in range(nseg):
                self.put(per, rare=s % 7 == 0)
                if s == nseg // 2:
                    self.delete(9)
                for bucket in self.shard.store._buckets.values():
                    if len(bucket._mem):
                        bucket.flush_memtable()
            self.put(7, rare=True)
            self.delete(5)
        self.tags = self.shard.store.bucket(filterable_bucket("tags"))
        assert self.tags.segment_count() == nseg

    def put(self, count, rare=False):
        objs = []
        for _ in range(count):
            bag = sorted({int(t) for t in self.rng.integers(
                0, TAGS - 1, self.rng.integers(1, 5)) // 2}
                | ({RARE} if rare and self.rng.random() < 0.3 else set()))
            u = str(uuidlib.UUID(int=self._next + 1))
            self.live[u] = (bag, self._next)
            objs.append(StorObj(
                class_name="Tagged", uuid=u,
                properties={"tags": bag, "n": self._next},
                vector=self.rng.standard_normal(DIM).astype(np.float32)))
            self._next += 1
        assert not any(self.shard.put_batch(objs))
        return objs

    def delete(self, count):
        for u in self.rng.choice(sorted(self.live), count, replace=False):
            assert self.shard.delete_object(str(u))
            del self.live[str(u)]

    def truth(self, holds) -> np.ndarray:
        """The doc ids of the live objects a filter holds for, read from
        what was written and not from any posting."""
        uuids = [u for u, (bag, n) in self.live.items() if holds(bag, n)]
        return np.sort(np.array(
            [o.doc_id for o in self.shard.multi_get(uuids)], dtype=np.uint64))

    def group(self, wheres):
        """The allowLists the group path hands the index for these slots."""
        seen = []

        def capture(q, k, allows):
            seen.append(list(allows))
            return None     # "one filter a dispatch": the shard hands back None

        real = self.shard.vector_index.search_by_vectors_multi_async
        self.shard.vector_index.search_by_vectors_multi_async = capture
        try:
            flts = [LocalFilter.from_dict(w) for w in wheres]
            q = np.zeros((len(flts), DIM), np.float32)
            assert self.shard.object_vector_search_multi_async(
                q, 3, flts) is None
        finally:
            self.shard.vector_index.search_by_vectors_multi_async = real
        return seen[0]


@pytest.fixture(scope="module", params=SEGMENTS,
                ids=[f"{n}-segments" for n in SEGMENTS])
def tagged(request, tmp_path_factory):
    t = Tagged(tmp_path_factory.mktemp(f"tagged{request.param}"),
               request.param)
    yield t
    t.shard.shutdown()


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)


@pytest.mark.parametrize("shape", sorted(WHERES))
def test_group_allow_lists_equal_one_filter_at_a_time(tagged, shape):
    """Slot by slot the group's allowList is FilterSearcher.doc_ids of the
    slot's filter alone, and the rows the filter holds for."""
    wheres = [WHERES[s] for s in sorted(WHERES)] + [WHERES[shape]] * 2
    allows = tagged.group(wheres)
    assert len(allows) == len(wheres)
    at = sorted(WHERES).index(shape)
    alone = tagged.shard.searcher.doc_ids(LocalFilter.from_dict(WHERES[shape]))
    want = tagged.truth(HOLDS[shape])
    assert len(want) > 0
    for slot in (at, len(wheres) - 2, len(wheres) - 1):
        assert np.array_equal(allows[slot].to_array(), alone.to_array())
        assert np.array_equal(allows[slot].to_array(), want)
    # equal filters are one evaluation: the same Bitmap
    assert allows[at] is allows[-1] is allows[-2]
    for slot, s in enumerate(sorted(WHERES)):   # and every other slot its own
        assert np.array_equal(allows[slot].to_array(), tagged.truth(HOLDS[s]))


def test_group_reads_each_distinct_posting_once(tagged, monkeypatch):
    """The phase's stats: the filters above ask tag 0 five times and read
    it once; no bitset an intersection made survives the phase."""
    noted = {}
    real = tracing.Stopwatch.note
    monkeypatch.setattr(
        tracing.Stopwatch, "note",
        lambda self, **stats: (noted.update(stats), real(self, **stats))[1])
    reads = []
    real_get = type(tagged.tags).roaring_get
    monkeypatch.setattr(
        type(tagged.tags), "roaring_get",
        lambda self, key: (reads.append((self, key)), real_get(self, key))[1])
    tagged.shard._allow_cache.clear()    # the tests before asked the same
    # (the range is left out: Bucket.keys() reads every posting of its own)
    shapes = [s for s in sorted(WHERES) if s != "range"]
    allows = tagged.group([WHERES[s] for s in shapes])
    # tags 0, 1, 2, RARE and the universe: no posting read twice
    assert len(reads) == len(set(reads)) == 5
    assert noted["filters"] == noted["distinct"] == len(shapes)
    assert noted["tags"] == len(reads)
    # leaf reads: And 2, ContainsAll 2, ContainsAny 2, Equal 1, Not 1 and
    # its universe, Or 2; tag 0 is asked four times, tags 1, 2 and RARE
    # twice each
    assert noted["memo_hits"] == 3 + 1 + 1 + 1
    assert noted["ids"] == sum(len(real_get(b, k)) for b, k in reads)
    assert all(a._bits is None for a in allows)


def test_write_between_two_groups_is_seen_by_the_second(tmp_path):
    """The memo does not outlive its phase: an object acknowledged after one
    group is in the next group's allowLists, a deleted one is out."""
    t = Tagged(tmp_path / "w", 2)
    try:
        wheres = [WHERES["Equal"], WHERES["And"], WHERES["Not"]]
        first = [a.to_array().copy() for a in t.group(wheres)]
        put = t.shard.put_object(StorObj(
            class_name="Tagged", uuid=str(uuidlib.UUID(int=10_001)),
            properties={"tags": [0, 1], "n": 10_000},
            vector=np.zeros(DIM, np.float32)))
        t.live[put.uuid] = ([0, 1], 10_000)
        gone = next(u for u, (bag, _) in t.live.items()
                    if 0 in bag and 1 in bag and u != put.uuid)
        gone_doc = t.shard.multi_get([gone])[0].doc_id
        assert t.shard.delete_object(gone)
        del t.live[gone]
        second = t.group(wheres)
        for allow, before, shape in zip(second, first, ("Equal", "And", "Not")):
            assert np.array_equal(allow.to_array(), t.truth(HOLDS[shape]))
            assert not np.array_equal(allow.to_array(), before) \
                or shape == "Not"
        assert put.doc_id in second[0].to_array()
        assert put.doc_id in second[1].to_array()
        assert gone_doc in first[1] and gone_doc not in second[1].to_array()
    finally:
        t.shard.shutdown()


def test_a_bad_filter_is_its_slots_own_error(tagged):
    """A slot whose filter is wrong carries its own error; the others are
    served under their own filters."""
    flts = [LocalFilter.from_dict(w) for w in (
        WHERES["And"],
        {"path": ["nope"], "operator": "Equal", "valueInt": 1},
        WHERES["Equal"])]
    q = tagged.rng.standard_normal((3, DIM)).astype(np.float32)
    done = tagged.shard.object_vector_search_multi_async(q, 5, flts)
    assert done is not None
    out = done()
    assert isinstance(out[1], FilterValidationError)
    for slot, shape in ((0, "And"), (2, "Equal")):
        want = tagged.truth(HOLDS[shape])
        got = [r.obj.doc_id for r in out[slot]]
        assert len(got) == min(5, len(want))
        assert set(got) <= set(want.tolist())


@needs_library
def test_native_walk_equals_python_walk(tagged):
    """Every key of every roaring-set bucket of the shard: the one-pass
    native walk (or what it hands to the Python walk) against the Python
    walk alone; the counters say which served."""
    prev = perf.get_window()
    w = perf.configure(perf.PerfWindow())
    try:
        keys = 0
        for bucket in tagged.shard.store._buckets.values():
            if bucket.strategy != "roaringset":
                continue
            with bucket._lock:
                every = {k for seg in bucket._segments for k in seg.keys}
                every |= set(bucket._mem.adds) | set(bucket._mem.dels)
            for key in sorted(every) + [b"\xffnot-a-key"]:
                with bucket._lock:
                    want = bucket._roaring_walk(key)
                got = bucket.roaring_get(key)
                assert np.array_equal(got.to_array(), want.to_array()), key
                ids = got.to_array()
                assert np.all(ids[1:] > ids[:-1])
                keys += 1
        body = w.summary()["postings"]
    finally:
        perf.configure(prev)
    assert body["keys"] == keys > TAGS
    assert body["native"] + body["fallback"] <= keys
    assert body["native"] > 0
    assert set(body["fallback_reasons"]) <= {"deleting_layer"}
    # deletions in the middle exist from two segments on: the tags the
    # deleted objects carried go to the Python walk, every other key is the
    # native walk's
    if tagged.tags.segment_count() > 1:
        assert body["fallback_reasons"]["deleting_layer"] > 0
    else:
        assert body["fallback"] == 0
    assert body["ids"] > 0 and body["segment_probes"] >= body["native"]


def test_python_walk_serves_without_the_library(tagged, monkeypatch):
    """Where the library does not load, the same function serves the same
    ids, and says so."""
    key = sorted(tagged.tags._segments[0].keys)[0]
    want = tagged.tags.roaring_get(key)
    monkeypatch.setattr(lsm_native, "_load", lambda: None)
    prev = perf.get_window()
    w = perf.configure(perf.PerfWindow())
    try:
        got = tagged.tags.roaring_get(key)
        body = w.summary()["postings"]
    finally:
        perf.configure(prev)
    assert np.array_equal(got.to_array(), want.to_array())
    assert body == {"keys": 1, "segment_probes": tagged.tags.segment_count(),
                    "ids": len(want), "native": 0, "fallback": 1,
                    "fallback_reasons": {"no_library": 1}}


def _ids(lo, hi, step=1):
    return np.arange(lo, hi, step, dtype=np.uint64)


AND_CASES = {
    "empty": (_ids(0, 0), _ids(0, 50)),
    "both-empty": (_ids(0, 0), _ids(0, 0)),
    "disjoint": (_ids(0, 1000, 2), _ids(1, 1001, 2)),
    "disjoint-ranges": (_ids(0, 300), _ids(5000, 9000)),
    "nested": (_ids(100, 200), _ids(0, 4000)),
    "equal": (_ids(7, 3000, 3), _ids(7, 3000, 3)),
    # a dense pair goes through the larger's bitset; a few ids in many, or
    # a sparse pair over a span no bitset should cover, gallop
    "few-in-many": (_ids(3, 400_000, 40_001), _ids(0, 400_000)),
    "bitset": (_ids(0, 200_000, 3), _ids(0, 200_000, 2)),
    "sparse": (_ids(0, 1 << 40, 1 << 27), _ids(0, 1 << 40, 1 << 26)),
    "bitset-off-zero": (_ids(1_000_003, 1_200_000, 3),
                        _ids(1_000_001, 1_200_000, 2)),
}


@pytest.mark.parametrize("library", ["native", "numpy"])
@pytest.mark.parametrize("case", sorted(AND_CASES))
def test_and_equals_intersect1d(case, library, monkeypatch):
    if library == "numpy":
        monkeypatch.setattr(lsm_native, "_load", lambda: None)
    elif not lsm_native.available():
        pytest.skip("native/lsm_get.cpp cannot be built here: numpy serves")
    a, b = AND_CASES[case]
    want = np.intersect1d(a, b)
    for x, y in ((a, b), (b, a)):
        left, right = Bitmap(x, _sorted=True), Bitmap(y, _sorted=True)
        got = left.and_(right)
        assert np.array_equal(got.to_array(), want)
        assert got.to_array().dtype == np.uint64
        # asked again, the larger answers from the bitset it kept, if any
        assert np.array_equal(left.and_(right).to_array(), want)
        assert np.array_equal(x, left.to_array())     # inputs untouched
        assert np.array_equal(y, right.to_array())


@needs_library
def test_the_bitset_is_the_larger_postings_and_is_asked_again():
    """A popular posting's bitset is made by the first intersection that
    pays for it and probed by the next, whatever that one's size."""
    big = Bitmap(_ids(0, 200_000, 2), _sorted=True)
    few = Bitmap(_ids(0, 200_000, 50_001), _sorted=True)
    assert np.array_equal(few.and_(big).to_array(),
                          np.intersect1d(few.to_array(), big.to_array()))
    assert big._bits is None            # four ids gallop: no bitset for them
    many = Bitmap(_ids(0, 200_000, 3), _sorted=True)
    want = np.intersect1d(many.to_array(), big.to_array())
    assert np.array_equal(many.and_(big).to_array(), want)
    assert big._bits is not None and many._bits is None
    assert np.array_equal(few.and_(big).to_array(),
                          np.intersect1d(few.to_array(), big.to_array()))
    outside = Bitmap(_ids(150_000, 900_000, 7), _sorted=True)
    assert np.array_equal(
        outside.and_(big).to_array(),
        np.intersect1d(outside.to_array(), big.to_array()))
    big.drop_bits()
    assert big._bits is None
    assert np.array_equal(many.and_(big).to_array(), want)


@needs_library
def test_point_gets_refuse_a_roaring_set_segment(tagged):
    """The native point-get plane serves replace segments: handed the
    handles of a roaring-set bucket it says "the Python reader's" and
    returns no payload as a value."""
    segs = list(reversed(tagged.tags._segments))
    assert all(lsm_native.seg_handle(s) for s in segs)
    key = sorted(segs[0].keys)[0]
    assert lsm_native.multi_get(segs, [key]) is None


@needs_library
def test_postings_read_while_compaction_retires_segments(tmp_path):
    """The native walk runs outside the bucket's lock on a snapshot of the
    segments: compact_pair retires the segments it replaces (it does not
    close them) while a walk is in flight, and every read is the posting."""
    import threading

    from weaviate_tpu.storage.lsm import STRATEGY_ROARINGSET, Bucket

    b = Bucket(str(tmp_path / "rs"), STRATEGY_ROARINGSET)
    want: dict[bytes, np.ndarray] = {}
    for layer in range(24):
        items = []
        for key in range(40):
            if (key + layer) % 3:
                ids = np.arange(layer * 1000 + key, layer * 1000 + 900, 7)
                items.append((b"k%02d" % key, ids))
                want[b"k%02d" % key] = np.concatenate(
                    [want.get(b"k%02d" % key, np.empty(0, np.int64)), ids])
        b.roaring_add_many_keys(items)
        b.flush_memtable()
    assert b.segment_count() == 24
    errors: list = []
    stop = threading.Event()

    def reader(seed):
        r = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                key = b"k%02d" % int(r.integers(0, 40))
                if not np.array_equal(b.roaring_get(key).to_array(),
                                      want[key].astype(np.uint64)):
                    errors.append(key)
                    return
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(repr(e))

    threads = [threading.Thread(target=reader, args=(s,)) for s in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # more hand-overs inside the walk
    try:
        for t in threads:
            t.start()
        while b.segment_count() > 1:
            assert b.compact_pair()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert b._native_inflight == 0 and not b._retired
    for key, ids in want.items():
        assert np.array_equal(b.roaring_get(key).to_array(),
                              ids.astype(np.uint64))
    b.shutdown()


# layers of one key, oldest first: (additions, deletions); the last is the
# memtable's. Hand-made, so that layers do not follow each other as the doc
# ids of a counter do.
LAYER_CASES = {
    "overlap": [([1, 5, 9], []), ([5, 9, 12], []), ([], [])],
    "descending": [([100, 200], []), ([3, 4], []), ([1], [])],
    "deletes-in-the-oldest-layer": [([7, 8], [1, 2]), ([9], []), ([], [])],
    "deleted-then-added-again": [([1, 2, 3], []), ([], [2]), ([2, 4], []),
                                 ([], [])],
    "deleted-in-the-memtable": [([1, 2, 3], []), ([6], []), ([], [1, 6])],
    "memtable-alone-deletes-all": [([4], []), ([], [4])],
    "only-deletions": [([], [3]), ([], [])],
}


@needs_library
@pytest.mark.parametrize("case", sorted(LAYER_CASES))
def test_native_walk_settles_hand_made_layers(tmp_path, case):
    from weaviate_tpu.storage.lsm import STRATEGY_ROARINGSET, Bucket

    b = Bucket(str(tmp_path / "rs"), STRATEGY_ROARINGSET)
    want: set = set()
    layers = LAYER_CASES[case]
    for at, (adds, dels) in enumerate(layers):
        if dels:
            b.roaring_remove_many(b"k", dels)
        if adds:
            b.roaring_add_many(b"k", adds)
        b.roaring_add_many(b"other", [1])     # every layer is a segment
        want = (want - set(dels)) | set(adds)
        if at < len(layers) - 1:
            b.flush_memtable()
    assert b.segment_count() == len(layers) - 1
    got = b.roaring_get(b"k")
    with b._lock:
        walked = b._roaring_walk(b"k")
    assert got.to_array().tolist() == walked.to_array().tolist() \
        == sorted(want)
    b.shutdown()


def test_postings_block_of_the_perf_window(monkeypatch):
    """`/debug/perf` `postings`: the calls of one second share an entry, the
    window forgets them with the rest, and a bucket with no segment is
    neither walk's."""
    import time

    now = [1000.25]
    monkeypatch.setattr(time, "monotonic", lambda: now[0])
    w = perf.PerfWindow(window_s=60.0)
    assert "postings" not in w.summary()
    for _ in range(300):
        w.note_posting(30, 1000, perf.POSTING_NATIVE)
    w.note_posting(0, 2, perf.POSTING_MEMTABLE)
    now[0] += 1.5
    w.note_posting(30, 5, "deleting_layer")
    w.note_posting(4, 1, "no_library")
    assert len(w._postings) == 2
    assert w.summary()["postings"] == {
        "keys": 303, "segment_probes": 9034, "ids": 300_008, "native": 300,
        "fallback": 2,
        "fallback_reasons": {"deleting_layer": 1, "no_library": 1}}
    now[0] += 60.0      # the first second has left the window
    assert w.summary()["postings"] == {
        "keys": 2, "segment_probes": 34, "ids": 6, "native": 0,
        "fallback": 2,
        "fallback_reasons": {"deleting_layer": 1, "no_library": 1}}
    w.clear()
    assert "postings" not in w.summary()
