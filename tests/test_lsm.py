"""LSM store: strategies, WAL recovery, flush/segments, compaction, blooms.

Models the reference's lsmkv unit/integration tiers (strategy tests,
bucket_recover_from_wal.go behavior)."""

import pytest

from weaviate_tpu.storage.docid import Counter
from weaviate_tpu.storage.lsm import (
    STRATEGY_MAP,
    STRATEGY_REPLACE,
    STRATEGY_ROARINGSET,
    STRATEGY_SET,
    Bucket,
    LsmError,
    Store,
)


def test_replace_basic(tmp_path):
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    b.put(b"k1", b"v1")
    b.put(b"k2", b"v2")
    b.put(b"k1", b"v1b")
    assert b.get(b"k1") == b"v1b"
    b.delete(b"k2")
    assert b.get(b"k2") is None
    assert b.keys() == [b"k1"]


def test_replace_wal_recovery(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_REPLACE)
    b.put(b"a", b"1")
    b.delete(b"a")
    b.put(b"b", b"2")
    b.flush()
    # no shutdown — simulate crash
    b2 = Bucket(p, STRATEGY_REPLACE)
    assert b2.get(b"a") is None
    assert b2.get(b"b") == b"2"


def test_replace_segments_and_tombstones(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_REPLACE)
    b.put(b"x", b"old")
    b.flush_memtable()  # segment 1
    b.put(b"x", b"new")
    b.delete(b"y")
    b.flush_memtable()  # segment 2
    b.put(b"y", b"alive")
    assert b.get(b"x") == b"new"
    assert b.get(b"y") == b"alive"
    b.shutdown()
    b3 = Bucket(p, STRATEGY_REPLACE)
    assert b3.get(b"x") == b"new"
    assert b3.get(b"y") == b"alive"


def test_replace_compaction(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_REPLACE)
    for i in range(10):
        b.put(f"k{i}".encode(), f"v{i}".encode())
        if i % 3 == 0:
            b.flush_memtable()
    b.delete(b"k5")
    b.flush_memtable()
    assert len(b._segments) > 2
    b.compact()
    assert len(b._segments) == 1
    assert b.get(b"k5") is None
    assert b.get(b"k4") == b"v4"
    assert len(b.keys()) == 9


def test_set_strategy(tmp_path):
    b = Bucket(str(tmp_path / "b"), STRATEGY_SET)
    b.set_add(b"k", b"a")
    b.set_add(b"k", b"b")
    b.flush_memtable()
    b.set_remove(b"k", b"a")
    b.set_add(b"k", b"c")
    assert b.set_get(b"k") == {b"b", b"c"}
    b.compact()  # single segment is a no-op here but must not corrupt
    b.flush_memtable()
    b.compact()
    assert b.set_get(b"k") == {b"b", b"c"}


def test_map_strategy(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_MAP)
    b.map_put(b"term", b"doc1", b"tf=3")
    b.map_put(b"term", b"doc2", b"tf=1")
    b.flush_memtable()
    b.map_delete(b"term", b"doc1")
    b.map_put(b"term", b"doc3", b"tf=9")
    assert b.map_get(b"term") == {b"doc2": b"tf=1", b"doc3": b"tf=9"}
    b.shutdown()
    b2 = Bucket(p, STRATEGY_MAP)
    assert b2.map_get(b"term") == {b"doc2": b"tf=1", b"doc3": b"tf=9"}


def test_roaringset_strategy(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_ROARINGSET)
    b.roaring_add_many(b"color:red", [1, 2, 3, 100])
    b.flush_memtable()
    b.roaring_remove_many(b"color:red", [2])
    b.roaring_add_many(b"color:red", [200])
    got = b.roaring_get(b"color:red")
    assert sorted(got) == [1, 3, 100, 200]
    b.flush_memtable()
    b.compact()
    assert sorted(b.roaring_get(b"color:red")) == [1, 3, 100, 200]


def test_bloom_survives_cross_process_restart(tmp_path):
    """Persisted blooms must use a DETERMINISTIC hash: Python's builtin
    hash() is siphash-randomized per process, so a bloom written by one
    process read by another turns ~99% of present keys into false
    negatives — silent loss of all flushed data on real restarts (in-process
    reopens share the seed and never catch this)."""
    import subprocess
    import sys

    d = str(tmp_path / "b")
    write = (
        "import sys; sys.path.insert(0, %r)\n"
        "from weaviate_tpu.storage.lsm import Bucket, STRATEGY_REPLACE\n"
        "b = Bucket(%r, STRATEGY_REPLACE)\n"
        "[b.put(f'key{i}'.encode(), f'val{i}'.encode()) for i in range(200)]\n"
        "b.flush_memtable()\n"
    )
    read = (
        "import sys; sys.path.insert(0, %r)\n"
        "from weaviate_tpu.storage.lsm import Bucket, STRATEGY_REPLACE\n"
        "b = Bucket(%r, STRATEGY_REPLACE)\n"
        "missing = sum(1 for i in range(200)"
        " if b.get(f'key{i}'.encode()) is None)\n"
        "assert missing == 0, f'{missing}/200 keys lost across processes'\n"
    )
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONHASHSEED"}
    for code in (write % (repo, d), read % (repo, d)):
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]


def test_legacy_bloom_file_rebuilt(tmp_path):
    """A pre-versioning bloom file (or a corrupt one) must be discarded and
    rebuilt from the segment's key footer, not trusted."""
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_REPLACE)
    for i in range(50):
        b.put(f"k{i}".encode(), f"v{i}".encode())
    b.flush_memtable()
    seg_path = b._segments[-1].path
    # overwrite with a legacy-format file: raw m/k header, garbage bits
    import struct

    with open(seg_path + ".bloom", "wb") as f:
        f.write(struct.pack("<QI", 4096, 7) + b"\xaa" * 512)
    b2 = Bucket(p, STRATEGY_REPLACE)
    for i in range(50):
        assert b2.get(f"k{i}".encode()) == f"v{i}".encode()
    # and the rebuilt file is now versioned
    from weaviate_tpu.storage.lsm import BloomFilter

    with open(seg_path + ".bloom", "rb") as f:
        assert BloomFilter.from_bytes(f.read()) is not None


def test_native_multi_get_races_compaction(tmp_path):
    """The native point-get plane reads mmap'd segments OUTSIDE the bucket
    lock; compaction rewrites and retires segments concurrently. Hammer
    both: every read must return either the correct value — never garbage,
    never a crash — and retired segments must eventually close."""
    import threading

    from weaviate_tpu.storage import lsm_native

    if not lsm_native.available():
        pytest.skip("native lsm plane unavailable")
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE, memtable_max_bytes=1)
    n = 2000
    keys = [f"key-{i:05d}".encode() for i in range(n)]
    for i, k in enumerate(keys):
        b.put(k, b"v%d" % i)
    b.flush_memtable()
    errors: list = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            got = b.multi_get(keys)
            for i, v in enumerate(got):
                if v != b"v%d" % i:
                    errors.append((i, v))
                    return

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    # repeated pair compactions while readers are in flight
    for _ in range(30):
        if not b.compact_pair():
            break
    b.compact()
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[:3]
    with b._lock:
        assert b._native_inflight == 0
        assert not b._retired  # all retired segments were closed


def test_reserved_tombstone_value_refused(tmp_path):
    """Storing the in-band delete marker as a value would silently read
    back as deleted — the bucket must refuse it loudly (found by the
    native-plane property fuzzer before the guard existed). Pure-Python
    behavior: runs regardless of native availability."""
    from weaviate_tpu.storage.lsm import _TOMBSTONE

    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    with pytest.raises(LsmError):
        b.put(b"k", _TOMBSTONE)
    with pytest.raises(LsmError):
        b.put_many([(b"a", b"ok"), (b"k", _TOMBSTONE)])
    assert b.get(b"a") is None  # the batch was refused atomically


def test_wal_torn_tail(tmp_path):
    p = str(tmp_path / "b")
    b = Bucket(p, STRATEGY_REPLACE)
    b.put(b"good", b"1")
    b.flush()
    b._wal.close()
    with open(p + "/bucket.wal", "ab") as f:
        f.write(b"\x01\x02\xff\xff\xff")  # torn record
    b2 = Bucket(p, STRATEGY_REPLACE)
    assert b2.get(b"good") == b"1"


def test_cursor_sorted(tmp_path):
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE)
    for k in [b"c", b"a", b"b"]:
        b.put(k, k)
    b.flush_memtable()
    b.put(b"d", b"d")
    assert [k for k, _ in b.cursor()] == [b"a", b"b", b"c", b"d"]


def test_memtable_autoflush(tmp_path):
    b = Bucket(str(tmp_path / "b"), STRATEGY_REPLACE, memtable_max_bytes=100)
    for i in range(50):
        b.put(f"key{i:04d}".encode(), b"x" * 20)
    assert len(b._segments) > 0
    assert b.get(b"key0000") == b"x" * 20


def test_store_buckets(tmp_path):
    s = Store(str(tmp_path / "store"))
    obj = s.create_or_load_bucket("objects", STRATEGY_REPLACE)
    inv = s.create_or_load_bucket("inv", STRATEGY_ROARINGSET)
    obj.put(b"k", b"v")
    inv.roaring_add_many(b"p", [7])
    with pytest.raises(LsmError):
        s.create_or_load_bucket("objects", STRATEGY_SET)
    assert s.bucket("objects").get(b"k") == b"v"


def test_docid_counter(tmp_path):
    p = str(tmp_path / "cnt" / "counter.bin")
    c = Counter(p, reserve=10)
    ids = [c.get_and_inc() for _ in range(5)]
    assert ids == [0, 1, 2, 3, 4]
    first = c.get_and_inc_many(3)
    assert first == 5
    # crash-restart must never reuse
    c2 = Counter(p, reserve=10)
    assert c2.get_and_inc() >= 8


def test_idle_memtable_flush(tmp_path):
    """PERSISTENCE_FLUSH_IDLE_MEMTABLES_AFTER: the background cycle flushes
    write-quiet memtables so crash recovery never replays an old WAL
    (lsmkv FlushAfterIdle)."""
    import time as _t

    store = Store(str(tmp_path / "s"), memtable_max_bytes=1 << 30,
                  flush_idle_seconds=0.2)
    b = store.create_or_load_bucket("r", STRATEGY_REPLACE)
    assert b.memtable_max_bytes == 1 << 30  # store default propagated
    t0 = _t.monotonic()
    b.put(b"k", b"v")
    assert len(b._mem)  # still in the memtable
    # not idle yet — unless a CI stall already burned the window
    if _t.monotonic() - t0 < 0.2:
        assert store.flush_idle_once() == 0
    _t.sleep(0.25)
    assert store.flush_idle_once() >= 1 or not len(b._mem)
    assert not len(b._mem) and b.segment_count() >= 1
    assert b.get(b"k") == b"v"
    # fresh writes reset the idle clock
    t1 = _t.monotonic()
    b.put(b"k2", b"v2")
    if _t.monotonic() - t1 < 0.2:
        assert store.flush_idle_once() == 0
    store.shutdown()
