"""Property-based equivalence of the native LSM point-get plane
(native/lsm_get.cpp via storage/lsm_native.py) against the pure-Python
segment reader, under random operation sequences — puts, overwrites,
deletes, flush points, pair/full compactions. The native reader serves the
production hot path with the GIL released; any divergence from the Python
reader is silent data corruption, so the property IS the contract."""

import hashlib
import shutil
import struct
import tempfile
import threading
import uuid as uuidlib

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="optional dep not in this image")
from hypothesis import given, settings
from hypothesis import strategies as st

from weaviate_tpu.monitoring import perf
from weaviate_tpu.storage import lsm_native
from weaviate_tpu.storage.lsm import STRATEGY_REPLACE, Bucket

pytestmark = pytest.mark.skipif(
    not lsm_native.available(), reason="native lsm plane unavailable")

_KEYS = st.integers(min_value=0, max_value=40)


def _key(i: int) -> bytes:
    # mixed-length keys: bytewise order differs from numeric order for a
    # prefix-free-ness check of the binary search
    return (b"k" * (1 + i % 3)) + str(i).encode()


from weaviate_tpu.storage.lsm import _TOMBSTONE

# any value EXCEPT the reserved tombstone marker, which put() refuses
# loudly (storing it would read back as deleted — covered separately below)
_small_values = st.binary(min_size=0, max_size=64).filter(
    lambda v: v != _TOMBSTONE)
# 0 B to 8 KB: a batch's values run to megabytes, far past any first guess
# of the arena, and a key's value changes size between segments
_large_values = st.tuples(
    st.integers(0, 2**32 - 1),
    st.sampled_from([0, 1, 17, 700, 3300, 8192])).map(
        lambda t: hashlib.shake_128(struct.pack("<I", t[0])).digest(t[1])
    ).filter(lambda v: v != _TOMBSTONE)


def _ops(values):
    return st.lists(
        st.one_of(
            st.tuples(st.just("put"), _KEYS, values),
            st.tuples(st.just("del"), _KEYS, st.just(b"")),
            st.tuples(st.just("flush"), st.just(0), st.just(b"")),
            st.tuples(st.just("compact_pair"), st.just(0), st.just(b"")),
            st.tuples(st.just("compact"), st.just(0), st.just(b"")),
        ),
        min_size=1, max_size=60,
    )


def _python_multi_get(b, probe):
    """The Python reader on the same bucket state."""
    orig = lsm_native._lib, lsm_native._lib_failed
    lsm_native._lib, lsm_native._lib_failed = None, True
    try:
        return b.multi_get(probe)
    finally:
        lsm_native._lib, lsm_native._lib_failed = orig


def _offs(keys):
    """keys (None = missing upstream) -> (key buffer, n + 1 offsets)."""
    offs = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum([len(k or b"") for k in keys], out=offs[1:])
    return b"".join(k or b"" for k in keys), offs


def _packed(b, keys):
    """keys (None = missing upstream) through the packed plane -> values
    list, copied out of the arena at once."""
    got = b.multi_get_packed(*_offs(keys))
    assert got is not None
    vbuf, voffs, flags = got
    assert voffs[-1] == len(vbuf)
    data = vbuf.tobytes()
    return [data[voffs[i]:voffs[i + 1]] if flags[i] else None
            for i in range(len(keys))]


@pytest.mark.parametrize("values,examples", [
    (_small_values, 120), (_large_values, 40)], ids=["small", "to-8KB"])
def test_native_multi_get_equals_python_reader(values, examples):
    @settings(max_examples=examples, deadline=None)
    @given(ops=_ops(values), seed=st.integers(0, 2**32 - 1))
    def run(ops, seed):
        d = tempfile.mkdtemp(prefix="proplsm")
        try:
            b = Bucket(d + "/b", STRATEGY_REPLACE)
            model: dict[bytes, bytes] = {}
            for op, i, v in ops:
                if op == "put":
                    b.put(_key(i), v)
                    model[_key(i)] = v
                elif op == "del":
                    b.delete(_key(i))
                    model.pop(_key(i), None)
                elif op == "flush":
                    b.flush_memtable()
                elif op == "compact_pair":
                    b.compact_pair()
                else:
                    b.compact()
            # one final flush so the native plane (segments-only) can see
            # everything on the packed path too
            b.flush_memtable()
            probe = [_key(i) for i in range(45)] + [None, b"", b"missing"]
            got_native = b.multi_get(probe)
            assert got_native == _python_multi_get(b, probe)
            # and both agree with the reference model
            for k, v_n in zip(probe, got_native):
                if k is None or k == b"" or k == b"missing":
                    assert v_n is None
                else:
                    assert v_n == model.get(k), k
            # a serving batch: 2,560 keys with repeats, present and absent
            # ones mixed, missing-upstream slots in between, through the
            # packed plane the raw lane uses and through the list plane
            rng = np.random.default_rng(seed)
            batch = [None if i >= 48 else _key(i)
                     for i in rng.integers(0, 50, 2560).tolist()]
            want = [None if k is None else model.get(k) for k in batch]
            if b._segments:
                assert _packed(b, batch) == want
            assert b.multi_get(batch) == want
        finally:
            shutil.rmtree(d, ignore_errors=True)

    run()


def _segments_of(tmp_path, *segments):
    """A bucket with one segment per dict, oldest first (None = delete)."""
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    for seg in segments:
        for k, v in seg.items():
            if v is None:
                b.delete(k)
            else:
                b.put(k, v)
        b.flush_memtable()
    assert len(b._segments) == len(segments)
    return b


def _colliding_keys():
    """Two keys a one-entry segment's table cannot tell apart: the same
    slot (hash bit 0) and the same tag (the 31 bits a slot has left), found
    by a birthday search over the table's own hash."""
    lib = lsm_native._load()
    seen: dict[tuple[int, int], bytes] = {}
    for i in range(2_000_000):
        k = b"collide-%d" % i
        h = lib.lsm_key_hash(k, len(k))
        sig = (h & 1, h >> 33)
        if sig in seen:
            return seen[sig], k
        seen[sig] = k
    raise AssertionError("no colliding pair found")


def _counted(fn):
    """fn() under a fresh perf window -> (result, its point_get block)."""
    prev = perf.get_window()
    w = perf.configure(perf.PerfWindow())
    try:
        return fn(), w.summary().get("point_get")
    finally:
        perf.configure(prev)


CASES = {
    # a key that is a strict prefix of another, both ways round, and a
    # probe that is a prefix / an extension of a stored key
    "prefix": (
        [{b"ab": b"short", b"abc": b"long", b"abcd" * 4: b"longer"}],
        [b"ab", b"abc", b"a", b"abcd", b"abcd" * 4, b"abcd" * 4 + b"a"],
        [b"short", b"long", None, None, b"longer", None]),
    # present in three segments, a tombstone in the middle one: the newest
    # value wins; where the newest segment says nothing the tombstone
    # shadows the oldest value
    "tombstone-in-the-middle": (
        [{b"x": b"old", b"y": b"old-y", b"z": b"old-z"},
         {b"x": None, b"y": None},
         {b"x": b"new", b"w": b"only-new"}],
        [b"x", b"y", b"z", b"w"],
        [b"new", None, b"old-z", b"only-new"]),
    # zero-length keys mean "missing upstream"; zero-length VALUES are values
    "zero-length": (
        [{b"e": b"", b"f": b"v"}],
        [b"", b"e", None, b"f", b""],
        [None, b"", None, b"v", None]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_point_get_cases(tmp_path, case):
    segments, probe, want = CASES[case]
    b = _segments_of(tmp_path, *segments)
    assert _packed(b, probe) == want
    # the list plane sends 16 keys and more to the native reader
    assert b.multi_get(probe * 4) == want * 4
    assert _python_multi_get(b, probe * 4) == want * 4


def test_native_point_get_colliding_hashes(tmp_path):
    """Two keys with the same slot AND the same tag: only the full
    length-and-bytes compare tells them apart, and it must."""
    a, c = _colliding_keys()
    b = _segments_of(tmp_path, {a: b"value-of-a"}, {c: b"value-of-c"})
    got, pg = _counted(lambda: _packed(b, [a, c, a + b"x"]))
    assert got == [b"value-of-a", b"value-of-c", None]
    # newest first: `c` hits at once (1 probe, 1 compare); `a` meets c's
    # slot in the newer segment, is compared and refused there, and hits in
    # the older one (2 probes, 2 compares); the third key asks both
    assert pg["keys"] == 3 and pg["segment_probes"] == 5
    assert 3 <= pg["key_compares"] <= 5
    # alone in a segment, each still answers for itself only
    b2 = _segments_of(tmp_path / "2", {a: b"A"})
    assert _packed(b2, [c, a]) == [None, b"A"]
    b3 = _segments_of(tmp_path / "3", {a: b"A", c: b"C"})
    assert _packed(b3, [c, a]) == [b"C", b"A"]


def test_point_get_contract_one_probe_one_pass(tmp_path):
    """The contract, not the speed: 2,560 keys of 3.3 KB values over 14
    segments are each located once, with about one key compare a probe
    that hits and none where the table says no, and a second identical
    call finds its arena already there."""
    rng = np.random.default_rng(5)
    keys = [uuidlib.UUID(int=int(x)).bytes
            for x in rng.integers(1, 2**62, 2560)]
    val = {k: hashlib.shake_128(k).digest(3300) for k in keys}
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    per = -(-len(keys) // 14)
    for s in range(14):
        for k in keys[s * per:(s + 1) * per]:
            b.put(k, val[k])
        b.flush_memtable()
    assert len(b._segments) == 14
    batch = [keys[i] for i in rng.integers(0, len(keys), 2560)]

    def twice():
        first = _packed(b, batch)
        return first, _packed(b, batch)

    done = []
    t = threading.Thread(target=lambda: done.append(_counted(twice)))
    t.start()   # a thread of its own: its arena starts empty
    t.join(timeout=60)
    assert done, "the point-get thread did not finish"
    (first, second), pg = done[0]
    assert first == second == [val[k] for k in batch]
    assert pg["keys"] == 2 * 2560
    assert pg["keys"] <= pg["segment_probes"] <= 14 * pg["keys"]
    assert pg["key_compares"] <= 2 * pg["segment_probes"]
    # every hit costs one compare; a compare anywhere else is a tag shared
    # by chance (15 bits and more here): a handful in 5,120 lookups
    assert pg["keys"] <= pg["key_compares"] <= pg["keys"] + 64
    # 8.4 MB of values: the arena grew once, for the first call alone
    assert pg["arena_grows"] == 1


def test_chained_hydrate_equals_general_under_compaction(tmp_path):
    """hydrate_raw_packed (doc id -> uuid -> image, the first call's values
    the second call's keys, one arena a thread) returns the bytes
    _hydrate_batch returns, for the same ids, while four threads hydrate
    and compact_pair retires segments under them."""
    from weaviate_tpu.db.shard import Shard
    from weaviate_tpu.entities.schema import ClassDef, Property
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config

    dim, n, nseg = 64, 700, 7
    cd = ClassDef(name="Hyd", properties=[
        Property(name="t", data_type=["text"])], vector_index_type="hnsw_tpu")
    shard = Shard("s0", str(tmp_path / "hyd"), cd,
                  parse_and_validate_config("hnsw_tpu",
                                            {"distance": "l2-squared"}))
    rng = np.random.default_rng(3)
    objs = [StorObj(class_name="Hyd", uuid=str(uuidlib.UUID(int=i + 1)),
                    properties={"t": "x" * int(rng.integers(0, 3000))},
                    vector=rng.standard_normal(dim).astype(np.float32))
            for i in range(n)]
    for s in range(nseg):
        assert not any(shard.put_batch(objs[s::nseg]))
        for bucket in (shard.objects, shard.docid_lookup):
            bucket.flush_memtable()
    doc_ids = np.array([o.doc_id for o in shard.multi_get(
        [o.uuid for o in objs])], dtype=np.int64)
    shard.delete_object(objs[5].uuid)   # tombstones the batches meet
    for bucket in (shard.objects, shard.docid_lookup):
        bucket.flush_memtable()
    assert shard.raw_plane_ready()
    errors: list = []
    stop = threading.Event()

    def hydrator(seed):
        r = np.random.default_rng(seed)
        try:
            while not stop.is_set():
                ids = r.choice(doc_ids, (64, 10))
                dists = r.random((64, 10)).astype(np.float32)
                dists[r.random((64, 10)) < 0.05] = np.inf  # short replies
                out = shard.hydrate_raw_packed(ids, dists)
                if out is None:   # a memtable got busy: not this test
                    errors.append("packed plane declined")
                    return
                vbuf, voffs, vflags, flat_d, counts = out
                images = [vbuf[voffs[i]:voffs[i + 1]].tobytes()
                          for i in range(len(vflags)) if vflags[i]]
                general = shard._hydrate_batch(ids, dists, False)
                want = [res.raw_pristine() for rows in general for res in rows]
                if images != want or counts.tolist() != [
                        int(c) for c in (~np.isinf(dists)).sum(axis=1)]:
                    errors.append("packed and general hydration differ")
                    return
        except Exception as e:  # noqa: BLE001 — reported by the assert below
            errors.append(repr(e))

    threads = [threading.Thread(target=hydrator, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    merges = 0
    while shard.objects.compact_pair():
        merges += 1
    stop.set()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:3]
    assert merges >= nseg - 1
    with shard.objects._lock:
        assert shard.objects._native_inflight == 0
        assert not shard.objects._retired
    shard.shutdown()


# -- the memtable as the native call's newest layer --------------------------


def _overlay_oracle(b, key_buf, key_offs):
    """The packed get as plain Python serves it: the memtable's words read
    from its dict under the lock, a look-up a key, laid over the segments'
    answer in a buffer of its own (`overlay_packed`) -> copies of the
    triple."""
    from weaviate_tpu.storage.lsm import overlay_packed

    with b._lock:
        data, offs = b._mem.data, key_offs.tolist()
        newer = {i: data[key_buf[lo:hi]]
                 for i, (lo, hi) in enumerate(zip(offs, offs[1:]))
                 if hi > lo and key_buf[lo:hi] in data}
        packed = lsm_native.multi_get_packed(
            list(reversed(b._segments)), key_buf, key_offs)
        vbuf, voffs, flags = overlay_packed(packed, newer) if newer \
            else packed
        return vbuf.tobytes(), voffs.tolist(), flags.tolist()


def _held_to_the_oracle(b, keys):
    """One packed get of `keys` through the mirror, held to the overlay
    (values, offsets, flags) and to `Bucket.get` key for key."""
    key_buf, key_offs = _offs(keys)
    want = _overlay_oracle(b, key_buf, key_offs)
    vbuf, voffs, flags = b.multi_get_packed(key_buf, key_offs)
    assert (vbuf.tobytes(), voffs.tolist(), flags.tolist()) == want
    data = want[0]
    for i, k in enumerate(keys):
        v = b.get(k) if k else None
        assert (data[voffs[i]:voffs[i + 1]] if flags[i] else None) == v, k
    return flags


@pytest.mark.parametrize("seed", range(8))
def test_memtable_layer_equals_python_overlay(tmp_path, seed):
    """Random puts, overwrites, deletes, flushes and compactions, a packed
    get after every few of them: the ONE native call that asks the
    memtable's mirror first answers what the Python overlay answers
    (values, offsets, flags) and what `Bucket.get` answers, zero-length
    keys and absent ones in the batch, values of 0 B to 8 KB so that the
    thread's arena has to grow under the memtable's values too."""
    rng = np.random.default_rng(1000 + seed)
    sizes = [0, 1, 17, 700, 3300, 8192] if seed % 2 else [0, 3, 16, 64]
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    for i in range(0, 50, 3):   # something on disk: the packed path serves
        b.put(_key(i), b"first-%d" % i)
    b.flush_memtable()
    done: list = []

    def run():   # a thread of its own: its arena starts empty
        asked = mem_hits = 0
        for step in range(160):
            op = rng.choice(["put", "put", "put", "del", "flush",
                             "compact_pair", "compact"],
                            p=[.3, .3, .2, .12, .04, .02, .02])
            i = int(rng.integers(0, 50))
            if op == "put":
                b.put(_key(i), hashlib.shake_128(b"%d" % step).digest(
                    int(rng.choice(sizes))))
            elif op == "del":
                b.delete(_key(i))
            elif op == "flush":
                b.flush_memtable()
            elif op == "compact_pair":
                b.compact_pair()
            else:
                b.compact()
            if step % 4:
                continue
            keys = [None if j >= 54 else b"" if j >= 52 else _key(j)
                    for j in rng.integers(0, 56, 300).tolist()]
            had_mem = len(b._mem) > 0
            _, pg = _counted(lambda: _held_to_the_oracle(b, keys))
            # the oracle's segments-only call, then the bucket's own
            assert pg["mem_layer_calls"] == int(had_mem)
            assert pg["overlay_fallbacks"] == 0
            asked += had_mem
            mem_hits += pg["mem_keys"]
        done.append((asked, mem_hits))

    t = threading.Thread(target=run)
    t.start()
    t.join(timeout=300)
    assert not t.is_alive() and done
    assert done[0][0] > 10 and done[0][1] > 100   # the layer was exercised
    b.shutdown()
    assert b._mem.mirror is None and not b._retired


def test_memtable_layer_chains_one_arena_through_two_written_buckets(
        tmp_path):
    """`hydrate_raw_packed`'s shape: r1's values, a view of the thread's
    arena, are r2's keys, and r2's values overwrite them in that arena,
    with both buckets' memtables holding the newest word on some keys, a
    tombstone among them, and an arena that has to grow in the second call."""
    ids = [struct.pack("<Q", i) for i in range(400)]
    uuids = [uuidlib.UUID(int=i + 1).bytes for i in range(400)]
    lookup = Bucket(str(tmp_path / "lookup"), STRATEGY_REPLACE)
    objects = Bucket(str(tmp_path / "objects"), STRATEGY_REPLACE)
    image = {u: hashlib.shake_128(u).digest(3300) for u in uuids}
    lookup.put_many(zip(ids[:300], uuids[:300]))
    objects.put_many((u, image[u]) for u in uuids[:300])
    for b in (lookup, objects):
        b.flush_memtable()
    # since the flush: new rows, re-put rows (a new image), a deleted doc
    # id, a deleted object
    lookup.put_many(zip(ids[300:], uuids[300:]))
    for u in uuids[250:]:
        image[u] = hashlib.shake_128(u + b"2").digest(4000)
        objects.put(u, image[u])
    lookup.delete(ids[7])
    objects.delete(uuids[9])
    out: list = []

    def chain():
        key_buf, key_offs = _offs(ids)
        ubuf, uoffs, uflags = lookup.multi_get_packed(key_buf, key_offs)
        assert ubuf.base is lsm_native._arena.buf
        vbuf, voffs, vflags = objects.multi_get_packed(ubuf, uoffs)
        out.append((uflags.tolist(), vflags.tolist(), vbuf.tobytes(),
                    voffs.tolist()))

    _, pg = _counted(lambda: (t := threading.Thread(target=chain),
                              t.start(), t.join(timeout=60)))
    uflags, vflags, data, voffs = out[0]
    assert uflags == [int(i != 7) for i in range(400)]
    assert vflags == [int(i not in (7, 9)) for i in range(400)]
    for i, u in enumerate(uuids):
        if vflags[i]:
            assert data[voffs[i]:voffs[i + 1]] == image[u], i
    assert pg["mem_layer_calls"] == 2 and pg["mirror_builds"] == 2
    assert pg["mem_keys"] == (100 + 1) + (150 + 1)
    assert pg["arena_grows"] == 2   # 6.4 KB of uuids, then 1.4 MB of images
    for b in (lookup, objects):
        b.shutdown()


def test_no_mirror_for_an_empty_memtable_or_a_bucket_nobody_reads_packed(
        tmp_path, monkeypatch):
    """The cells that write nothing make today's native call, with a null
    layer, and a bucket that is only written (an import, a build, a
    restart's WAL replay) never makes a mirror: one comparison a put."""
    calls: list = []
    real = lsm_native.multi_get_packed
    monkeypatch.setattr(
        lsm_native, "multi_get_packed",
        lambda segs, kb, ko, mem=None: calls.append(mem) or real(
            segs, kb, ko, mem))
    made: list = []
    real_mirror = lsm_native.mem_mirror
    monkeypatch.setattr(lsm_native, "mem_mirror",
                        lambda data: made.append(1) or real_mirror(data))
    b = _segments_of(tmp_path, {b"a": b"1", b"b": b"2"})

    def quiet():
        assert _packed(b, [b"a", b"b", b"c"]) == [b"1", b"2", None]

    _, pg = _counted(quiet)
    assert calls == [None] and not made and b._mem.mirror is None
    assert {k: pg[k] for k in ("mem_layer_calls", "mem_keys", "mirror_builds",
                               "overlay_fallbacks")} == dict.fromkeys(
        ("mem_layer_calls", "mem_keys", "mirror_builds", "overlay_fallbacks"),
        0)
    # only written: 10,000 puts, deletes among them, a batch, a reopen
    for i in range(10_000):
        b.put(b"w%d" % i, b"v")
    b.put_many((b"m%d" % i, b"v") for i in range(100))
    b.delete(b"w5")
    assert b.get(b"w6") == b"v" and b.multi_get([b"w5", b"w7"] * 8) == [
        None, b"v"] * 8
    assert not made and b._mem.mirror is None
    b._wal.flush()
    again = Bucket(b.path + "-copy", STRATEGY_REPLACE)
    again.shutdown()
    shutil.copy(b._wal_path, again._wal_path)
    again = Bucket(again.path, STRATEGY_REPLACE)   # replays the WAL
    assert len(again._mem) == 10_100 and again._mem.mirror is None
    assert not made
    # the first packed reader makes ONE, later ones and the writes between
    # them none
    assert _packed(b, [b"w5", b"w6", b"a"]) == [None, b"v", b"1"]
    b.put(b"w6", b"v2")
    assert _packed(b, [b"w6"]) == [b"v2"]
    assert made == [1] and calls[-1] is b._mem.mirror is not None
    assert b._mem.mirror.stats()["keys"] == 10_100
    for x in (b, again):
        x.shutdown()


def test_a_mirror_that_cannot_be_made_leaves_the_general_path_serving(
        tmp_path, monkeypatch):
    """No handle (no memory): the packed plane declines that memtable
    generation's gets (asking once), the counter says so and the general
    reader answers; the next generation tries again."""
    b = _segments_of(tmp_path, {b"a": b"1", b"b": b"2", b"c": b"3"})
    b.put(b"a", b"new")
    b.delete(b"b")
    asked: list = []
    monkeypatch.setattr(lsm_native, "mem_mirror",
                        lambda data: asked.append(1))
    got, pg = _counted(lambda: [b.multi_get_packed(*_offs(keys))
                                for keys in ([b"a", b"b", b"c"], [b"a"])])
    assert got == [None, None] and asked == [1]
    assert pg["overlay_fallbacks"] == 2 and pg["mem_layer_calls"] == 0
    assert pg["mirror_builds"] == 0 and b._mem.mirror is None
    assert b.multi_get([b"a", b"b", b"c"]) == [b"new", None, b"3"]
    monkeypatch.undo()
    b.flush_memtable()
    b.put(b"c", b"newer")
    got, pg = _counted(lambda: _packed(b, [b"a", b"b", b"c"]))
    assert got == [b"new", None, b"newer"]
    assert pg["overlay_fallbacks"] == 0 and pg["mirror_builds"] == 1
    b.shutdown()


def test_a_hot_key_does_not_grow_its_mirror_for_ever(tmp_path):
    """A key re-put again and again grows no memtable, so no flush would
    ever free what its older versions hold in the mirror: the bucket
    retires a mirror whose dead bytes pass its memtable's live ones (plus
    the slack), and the next packed get makes a fresh one."""
    b = _segments_of(tmp_path, {b"cold": b"c"})
    b.put(b"hot", b"0")
    assert _packed(b, [b"hot"]) == [b"0"]
    first, value = b._mem.mirror, b"x" * 65_536
    rounds = Bucket._MIRROR_DEAD_SLACK // len(value) + 2
    for i in range(rounds):
        b.put(b"hot", value)
        if b._mem.mirror is not first:
            break
    assert b._mem.mirror is None and first._h == 0 and i >= rounds - 3
    assert _packed(b, [b"hot", b"cold"]) == [value, b"c"]
    assert b._mem.mirror.stats() == {
        "keys": 1, "held_bytes": b._mem.mirror.stats()["held_bytes"],
        "dead_bytes": 0}
    b.shutdown()


@pytest.mark.parametrize("sanitizer", ["thread", "address,undefined"])
def test_the_mirror_is_clean_under_a_sanitizer(tmp_path, sanitizer):
    """`native/lsm_mem_race.cpp`: one inserting thread beside four that
    probe with no lock while the table grows five times, built with the
    sanitizer: no report, no torn, missing or stale value. Skipped where
    the compiler has no such runtime, or the sanitizer cannot map its
    shadow memory on this kernel."""
    import os
    import subprocess

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "lsm_mem_race.cpp")
    exe = str(tmp_path / "race")
    built = subprocess.run(
        ["g++", "-std=c++17", "-O1", "-g", f"-fsanitize={sanitizer}",
         "-fno-sanitize-recover=all", "-o", exe, src, "-lpthread"],
        capture_output=True, text=True)
    if built.returncode:
        pytest.skip(f"no -fsanitize={sanitizer} here: {built.stderr[-200:]}")
    ran = subprocess.run([exe, "1.5"], capture_output=True, text=True,
                         timeout=120)
    if "unexpected memory mapping" in ran.stderr:
        pytest.skip("the sanitizer cannot map its shadow memory here")
    assert ran.returncode == 0 and ran.stdout.endswith("ok\n"), (
        ran.stdout[-300:], ran.stderr[-2000:])
    assert "Sanitizer" not in ran.stderr and "runtime error" not in ran.stderr


# the reserved-tombstone-value guard test lives in test_lsm.py: it has no
# native dependency and must run even where this module is skipped
