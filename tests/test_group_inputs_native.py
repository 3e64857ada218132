"""A filtered group's device operands (index/group_inputs.py over
native/lsm_get.cpp lsm_group_locate / lsm_group_fill): every gather
bucket's rows and counts and the masked scan's words, built in one native
pass into pooled buffers. Every case runs with the library and without it
and is held to the single-filter path's numpy (`_allow_slots`,
`_slot_words`) as the oracle."""

import sys
import threading

import numpy as np
import pytest

from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import group_inputs, new_vector_index
from weaviate_tpu.index import tpu as tpu_index
from weaviate_tpu.monitoring import perf, tracing
from weaviate_tpu.storage import lsm_native
from weaviate_tpu.storage.bitmap import Bitmap

N, DIM, K, DOC0 = 6000, 16, 10, 1000
MODES = ["native", "fallback"]
LAYOUTS = ["consecutive", "gaps", "out_of_order"]


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)


@pytest.fixture
def library(request, monkeypatch):
    """"native": the library as built; "fallback": no library."""
    assert lsm_native.available()
    if request.param == "fallback":
        monkeypatch.setattr(lsm_native, "_load", lambda: None)
    return request.param


def _docs(layout: str, rng) -> np.ndarray:
    if layout == "consecutive":
        return np.arange(N) + DOC0
    if layout == "gaps":
        return np.sort(rng.choice(4 * N, N, replace=False)) + DOC0
    return rng.permutation(N) + DOC0


@pytest.fixture(scope="module")
def indexes(tmp_path_factory):
    """One index a doc layout -> (index, vecs by slot, docs by slot)."""
    rng = np.random.default_rng(33)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    made = {}
    for layout in LAYOUTS:
        vecs = rng.standard_normal((N, DIM)).astype(np.float32)
        docs = _docs(layout, rng)
        idx = new_vector_index(cfg, str(tmp_path_factory.mktemp(layout)), "s")
        idx.add_batch(docs, vecs)
        if layout == "out_of_order":
            # a re-added doc takes its old slot again (index/tpu.py
            # `_place_rows`): one slot, the new row in it
            vecs[7] += 1.0
            idx.add(int(docs[7]), vecs[7])
        made[layout] = (idx, vecs, docs)
    yield made
    for idx, _, _ in made.values():
        idx.shutdown()


def _inputs(idx, allows):
    snap = idx._read_snapshot()[0]
    return snap, group_inputs.GroupInputs(
        snap, allows, idx._allow_slots, tpu_index._slot_words)


def _oracle(idx, snap, allow):
    """(store slots, words) by the single-filter path's numpy, on an
    allowList of its own (the oracle's object cache is not the group's)."""
    slots = idx._allow_slots(snap, Bitmap(allow.to_array().copy(),
                                          _sorted=True))
    return slots, tpu_index._slot_words(slots, snap.capacity)


def _expect_reason(layout: str, library: str):
    if layout == "out_of_order":
        return group_inputs.DOC_ORDER
    return None if library == "native" else group_inputs.NO_LIBRARY


def _list_of_size(docs_sorted: np.ndarray, m: int, rng) -> Bitmap:
    """m docs of the index, with ids below its first doc and above its last
    (and, where docs have gaps, ids that fall into them) mixed in."""
    pick = rng.choice(docs_sorted, m, replace=False)
    below = np.arange(3) + 1
    above = int(docs_sorted[-1]) + 1 + np.arange(4)
    gaps = np.setdiff1d(np.arange(docs_sorted[0], docs_sorted[0] + 200),
                        docs_sorted)[:5]
    return Bitmap(np.concatenate([pick, below, above, gaps]))


SIZES = [0, 1, 31, 32, 33, 512, 513, 3000, N]   # capacity / 32 is 512


@pytest.mark.parametrize("m", SIZES)
@pytest.mark.parametrize("layout", LAYOUTS)
@pytest.mark.parametrize("library", MODES, indirect=True)
def test_a_list_of_every_size_as_rows_and_as_words(indexes, library, layout,
                                                   m):
    idx, _, docs = indexes[layout]
    rng = np.random.default_rng(m)
    allow = _list_of_size(np.unique(docs), m, rng)
    other = _list_of_size(np.unique(docs), 40, rng)
    snap, inputs = _inputs(idx, [allow, None, other, allow])
    assert snap.capacity == 16384 and snap.capacity // 32 == 512
    assert inputs.reason == _expect_reason(layout, library)
    assert inputs.list_of == [0, -1, 1, 0] and inputs.lists == 2
    want, want_words = _oracle(idx, snap, allow)

    def slots_of(a: Bitmap, picked: int) -> int:
        return picked

    assert inputs.sizes.tolist() == [slots_of(allow, m), slots_of(other, 40)]
    assert want.size == slots_of(allow, m)
    width = max(128, 1 << int(max(want.size, 1) - 1).bit_length())
    pool = group_inputs.OperandPool(4, threading.Lock())
    gathered, scanned = inputs.fill(pool, [
        (True, [0, 2, 3], 8, width), (False, [3, 0, 2], 4, 512)])
    rows, counts = gathered.arr, gathered.counts
    assert rows.dtype == np.int32 and rows.shape == (8, width)
    assert counts.tolist()[:3] == [want.size, 40, want.size]
    assert not counts[3:].any()
    for j in (0, 2):
        assert np.array_equal(rows[j, : want.size], want)
        assert not rows[j, want.size:].any()
    assert np.array_equal(rows[1, :40], _oracle(idx, snap, other)[0])
    assert not rows[1, 40:].any() and not rows[3:].any()
    words = scanned.arr
    assert words.dtype == np.uint32 and words.shape == (4, 512)
    assert np.array_equal(words[0], want_words)
    assert np.array_equal(words[1], want_words)
    assert np.array_equal(words[2], _oracle(idx, snap, other)[1])
    assert not words[3].any() and scanned.dirty == 3


@pytest.mark.parametrize("library", MODES, indirect=True)
def test_a_foreign_allow_list_is_served_by_numpy(indexes, library):
    class Foreign(Bitmap):
        __slots__ = ()

        def to_array(self):
            return self._ids.astype(np.int64)

    idx, _, docs = indexes["consecutive"]
    snap, inputs = _inputs(idx, [Foreign(docs[:50]), Bitmap(docs[10:90])])
    assert inputs.reason == (group_inputs.FOREIGN_LIST if library == "native"
                             else group_inputs.NO_LIBRARY)
    assert inputs.sizes.tolist() == [50, 80]
    (op,) = inputs.fill(group_inputs.OperandPool(4, threading.Lock()),
                        [(True, [1, 0], 2, 128)])
    assert op.counts.tolist() == [80, 50]
    assert np.array_equal(op.arr[0, :80], np.arange(10, 90))
    assert np.array_equal(op.arr[1, :50], np.arange(50))


@pytest.mark.parametrize("layout", ["consecutive", "gaps"])
@pytest.mark.parametrize("library", MODES, indirect=True)
def test_a_tombstoned_slot_stays_in_the_list_and_the_device_masks_it(
        tmp_path, library, layout):
    rng = np.random.default_rng(34)
    vecs = rng.standard_normal((N, DIM)).astype(np.float32)
    docs = _docs(layout, rng)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = new_vector_index(cfg, str(tmp_path), "s")
    try:
        idx.add_batch(docs, vecs)
        pick = [np.sort(rng.choice(N, m, replace=False)) for m in (60, 4000)]
        allows = [Bitmap(docs[p]) for p in pick]
        q = np.stack([vecs[p[0]] for p in pick])
        ids, _ = idx.search_by_vectors_multi_async(q, K, allows)()
        assert ids[:, 0].tolist() == [int(docs[p[0]]) for p in pick]
        idx.delete(*[int(docs[p[0]]) for p in pick])
        snap, inputs = _inputs(idx, allows)
        assert inputs.sizes.tolist() == [60, 4000]
        (op,) = inputs.fill(group_inputs.OperandPool(4, threading.Lock()),
                            [(True, [0], 1, 128)])
        assert np.array_equal(op.arr[0, :60], pick[0])   # dead slot and all
        ids, dists = idx.search_by_vectors_multi_async(q, K, allows)()
        for i, p in enumerate(pick):
            d = ((vecs[p[1:]] - q[i]) ** 2).sum(1)
            assert ids[i].tolist() == docs[p[1:]][np.argsort(
                d, kind="stable")[:K]].tolist()
    finally:
        idx.shutdown()


@pytest.mark.parametrize("layout", ["consecutive", "gaps"])
@pytest.mark.parametrize("library", MODES, indirect=True)
def test_a_reused_buffer_reads_as_a_fresh_one(indexes, library, layout):
    """Two groups in a row through the same pooled buffers, the second
    narrower and with fewer scanned slots: no stale bit, no stale row."""
    idx, _, docs = indexes[layout]
    rng = np.random.default_rng(35)
    wide = [Bitmap(rng.choice(docs, m, replace=False))
            for m in (500, 400, 300, 5000, 3000, 2000)]
    narrow = [Bitmap(rng.choice(docs, m, replace=False))
              for m in (7, 0, 900)]
    pool = group_inputs.OperandPool(4, threading.Lock())
    snap, first = _inputs(idx, wide)
    ops = first.fill(pool, [(True, [0, 1, 2], 4, 512),
                            (False, [3, 4, 5], 4, 512)])
    assert ops[0].counts.tolist() == [500, 400, 300, 0] and ops[1].dirty == 3
    for op in ops:
        pool.give(op)
    snap, second = _inputs(idx, narrow)
    again = second.fill(pool, [(True, [1, 0], 4, 512), (False, [2], 4, 512)])
    assert [a is b for a, b in zip(again, ops)] == [True, True]
    rows, counts, words = again[0].arr, again[0].counts, again[1].arr
    assert counts.tolist() == [0, 7, 0, 0]
    assert np.array_equal(rows[1, :7], _oracle(idx, snap, narrow[0])[0])
    assert not rows[0].any() and not rows[1, 7:].any() and not rows[2:].any()
    assert np.array_equal(words[0], _oracle(idx, snap, narrow[2])[1])
    assert not words[1:].any() and again[1].dirty == 1


@pytest.mark.parametrize("library", MODES, indirect=True)
def test_an_operand_is_not_handed_out_again_before_its_finalize(
        indexes, library, monkeypatch):
    """The upload may read the host buffer until the fetch: a group's
    operands go back to the pool in its finalize and no sooner."""
    idx, vecs, docs = indexes["consecutive"]
    rng = np.random.default_rng(36)
    allows = [Bitmap(rng.choice(docs, m, replace=False)) for m in (30, 5500)]
    q = vecs[:2] + 0.01
    want = idx.search_by_vectors_multi_async(q, K, allows)()
    idx._group_pool.clear()
    taken, given = [], []
    take, give = idx._group_pool.take, idx._group_pool.give

    def spy_take(kind, shape):
        got = take(kind, shape)
        taken.append(got[0])
        return got

    monkeypatch.setattr(idx._group_pool, "take", spy_take)
    monkeypatch.setattr(
        idx._group_pool, "give",
        lambda op, alive=None: (given.append(op), give(op, alive))[1])
    fin_a = idx.search_by_vectors_multi_async(q, K, allows)
    each = len(taken)
    fin_b = idx.search_by_vectors_multi_async(q, K, allows)
    assert each and len({id(op) for op in taken}) == len(taken) == 2 * each
    assert not given and idx._group_pool.nbytes() == 0
    for fin in (fin_a, fin_b):
        got = fin()
        assert np.array_equal(got[0], want[0])
        assert np.array_equal(got[1], want[1])
    assert {id(op) for op in given} == {id(op) for op in taken}
    fin_c = idx.search_by_vectors_multi_async(q, K, allows)
    assert len(taken) == 3 * each
    assert ({id(op) for op in taken[2 * each:]}
            <= {id(op) for op in taken[: 2 * each]})
    assert np.array_equal(fin_c()[0], want[0])
    # a finalize that fails before its fetch strands its operands
    before = idx._group_pool.nbytes()
    fin_d = idx.search_by_vectors_multi_async(q, K, allows)
    monkeypatch.setattr(tpu_index, "_fetch_packed", lambda *a: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        fin_d()
    assert idx._group_pool.nbytes() < before


@pytest.mark.parametrize("library", MODES, indirect=True)
def test_four_threads_at_once_give_the_single_thread_answers(indexes,
                                                             library):
    idx, vecs, docs = indexes["consecutive"]
    rng = np.random.default_rng(37)
    groups = []
    for g in range(4):
        sizes = rng.integers(1, 5000, 12)
        allows = [Bitmap(rng.choice(docs, m, replace=False)) for m in sizes]
        allows.append(allows[0])
        q = vecs[rng.integers(0, N, len(allows))] + 0.01
        groups.append((q, allows))
    want = [idx.search_by_vectors_multi_async(q, K, a)() for q, a in groups]
    got: list = [[] for _ in range(4)]
    gate = threading.Barrier(4, timeout=60)

    def serve(g):
        q, allows = groups[g]
        gate.wait()
        for _ in range(6):
            got[g].append(idx.search_by_vectors_multi_async(q, K, allows)())

    threads = [threading.Thread(target=serve, args=(g,)) for g in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for g in range(4):
        assert len(got[g]) == 6
        for ids, dists in got[g]:
            assert np.array_equal(ids, want[g][0])
            assert np.array_equal(dists, want[g][1])


def test_words_of_a_smaller_capacity_leave_the_pool():
    pool = group_inputs.OperandPool(4, threading.Lock())
    small, _ = pool.take(1, (4, 512))
    rows, _ = pool.take(0, (4, 128))
    pool.give(small)
    pool.give(rows)
    assert pool.nbytes() == small.nbytes + rows.nbytes
    grown, hit = pool.take(1, (4, 1024))
    assert not hit and pool.nbytes() == rows.nbytes
    assert pool.take(0, (4, 128)) == (rows, True)


def test_jobs_that_do_not_fit_their_operands_are_refused(indexes):
    idx, _, docs = indexes["consecutive"]
    snap, inputs = _inputs(idx, [Bitmap(docs[:40])])
    pool = group_inputs.OperandPool(4, threading.Lock())
    with pytest.raises(ValueError):    # words too narrow for the docs
        inputs.fill(pool, [(False, [0], 1, 64)])
    with pytest.raises(ValueError):    # more slots than rows
        inputs.fill(pool, [(True, [0, 0], 1, 128)])


@pytest.mark.parametrize("library", MODES, indirect=True)
def test_group_inputs_counters_after_a_group(indexes, library, monkeypatch):
    idx, vecs, docs = indexes["consecutive"]
    rng = np.random.default_rng(38)
    allows = [Bitmap(np.concatenate([rng.choice(docs, m, replace=False),
                                     [1, 2, 10 ** 9]]))
              for m in (20, 700, 5000)]
    allows += [allows[1], None]
    q = vecs[:5] + 0.01
    idx._group_pool.clear()
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    window = perf.configure(perf.PerfWindow(window_s=60.0))
    ended = []
    end = tracing.Phase.end
    monkeypatch.setattr(
        tracing.Phase, "end",
        lambda self, **stats: (ended.append((self.name, stats)),
                               end(self, **stats))[1])
    fin = idx.search_by_vectors_multi_async(q, K, allows)
    dispatches = sum(bool(s.extra) and "row_bucket" in s.extra
                     for s in fin.shapes)
    assert dispatches >= 1 and len(fin.shapes) == dispatches + 1
    fin()
    # the group's first `enqueue` interval says what it resolved
    enqueues = [st for name, st in ended if name == "enqueue"]
    assert enqueues[0]["lists"] == 3
    assert enqueues[0]["ids"] == (5720 if library == "native" else 5729)
    assert all("lists" not in st for st in enqueues[1:])
    idx.search_by_vectors_multi_async(q, K, allows)()
    got = window.summary()["group_inputs"]
    native = library == "native"
    assert got["groups"] == 2 and got["lists"] == 6
    # the ids that can be a doc of the snapshot; numpy counts the lists whole
    assert got["ids"] == 2 * (5720 if native else 5729)
    assert got["native"] == (2 if native else 0)
    assert got["fallback"] == (0 if native else 2)
    assert got["fallback_reasons"] == (
        {} if native else {group_inputs.NO_LIBRARY: 2})
    assert got["host_ms"] > 0
    assert got["pool_grows"] == got["pool_hits"] == dispatches


def test_the_numpy_twin_is_what_a_missing_library_gets(monkeypatch):
    monkeypatch.setattr(lsm_native, "_load", lambda: None)
    assert lsm_native.group_locate(
        [], np.zeros(0, np.int64), True) == group_inputs.NO_LIBRARY
