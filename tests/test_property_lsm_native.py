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


def _packed(b, keys):
    """keys (None = missing upstream) through the packed plane -> values
    list, copied out of the arena at once."""
    lens = np.array([len(k or b"") for k in keys], dtype=np.int64)
    offs = np.zeros(len(keys) + 1, dtype=np.int64)
    np.cumsum(lens, out=offs[1:])
    got = b.multi_get_packed(b"".join(k or b"" for k in keys), offs)
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
        assert not shard.objects._retired_segments
    shard.shutdown()


# the reserved-tombstone-value guard test lives in test_lsm.py: it has no
# native dependency and must run even where this module is skipped
