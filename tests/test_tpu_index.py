"""TpuVectorIndex: exact-recall search, tombstones, allowLists, persistence.

Models the reference's hnsw test tiers: recall fixtures (recall_test.go),
delete/tombstone behavior (delete.go tests), persistence round-trip
(persistence_integration_test.go)."""

import numpy as np
import pytest

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.storage.bitmap import Bitmap


def make_index(tmp_path, metric=vi.DISTANCE_L2, **kw):
    cfg = vi.HnswUserConfig.from_dict({"distance": metric, **kw}, "hnsw_tpu")
    return TpuVectorIndex(cfg, str(tmp_path))


def brute_force(vectors, q, k, metric):
    from weaviate_tpu.ops.distances import single_distance

    d = np.array([single_distance(q, v, metric) for v in vectors])
    order = np.argsort(d, kind="stable")[:k]
    return order, d[order]


@pytest.mark.parametrize("metric", [vi.DISTANCE_L2, vi.DISTANCE_COSINE, vi.DISTANCE_DOT])
def test_exact_recall(tmp_path, rng, metric):
    idx = make_index(tmp_path / metric, metric)
    vecs = rng.standard_normal((500, 24)).astype(np.float32)
    idx.add_batch(np.arange(500), vecs)
    q = rng.standard_normal(24).astype(np.float32)
    ids, dists = idx.search_by_vector(q, 10)
    want_ids, want_d = brute_force(vecs, q, 10, metric)
    assert set(ids.tolist()) == set(want_ids.tolist())
    np.testing.assert_allclose(np.sort(dists), np.sort(want_d), rtol=1e-3, atol=1e-3)


def test_allow_words_cache_invalidated_by_compact(tmp_path, rng):
    """The per-allowList packed-words cache is keyed on (token, n,
    capacity); compact() rebuilds the slot->doc mapping and can restore the
    SAME n and capacity after re-adds — a stale mask would then route other
    docs' allow bits to live slots. compact must refresh the token."""
    idx = make_index(tmp_path, flatSearchCutoff=0)
    vecs = rng.standard_normal((100, 8)).astype(np.float32)
    idx.add_batch(np.arange(100), vecs)
    idx.flush()
    allow = Bitmap(np.arange(0, 100, 2).astype(np.uint64))  # even docs
    q = vecs[10:18]  # docs that survive the upcoming delete of 0..9
    ids, _ = idx.search_by_vectors(q, 3, allow_list=allow)
    assert getattr(allow, "_words_cache", None) is not None  # cache primed
    # shift the mapping while restoring n and capacity exactly
    idx.delete(*range(10))
    idx.flush()
    idx.compact()
    idx.add_batch(np.arange(100, 110), rng.standard_normal((10, 8)).astype(np.float32))
    idx.flush()
    assert idx.n == 100  # the aliasing precondition this test exists for
    ids2, _ = idx.search_by_vectors(q, 3, allow_list=allow)
    sentinel = np.uint64(0xFFFFFFFFFFFFFFFF)
    flat = ids2.ravel()
    flat = flat[flat != sentinel]
    assert all(int(x) % 2 == 0 and int(x) < 100 for x in flat), flat
    # self-queries for surviving allowed docs still win
    for j in range(0, 8, 2):  # queries j are docs 10+j (even, alive)
        assert int(ids2[j][0]) == 10 + j


def test_batched_search(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((300, 16)).astype(np.float32)
    idx.add_batch(np.arange(300), vecs)
    qs = rng.standard_normal((7, 16)).astype(np.float32)
    ids, dists = idx.search_by_vectors(qs, 5)
    assert ids.shape == (7, 5)
    for bi in range(7):
        want_ids, _ = brute_force(vecs, qs[bi], 5, vi.DISTANCE_L2)
        assert set(ids[bi].tolist()) == set(want_ids.tolist())


def test_delete_tombstones(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((100, 8)).astype(np.float32)
    idx.add_batch(np.arange(100), vecs)
    q = vecs[7]
    ids, _ = idx.search_by_vector(q, 1)
    assert ids[0] == 7
    idx.delete(7)
    ids, _ = idx.search_by_vector(q, 3)
    assert 7 not in ids.tolist()
    assert len(idx) == 99
    assert not idx.contains(7)


def test_update_same_doc_id(tmp_path, rng):
    idx = make_index(tmp_path)
    v1 = np.ones(8, np.float32)
    v2 = -np.ones(8, np.float32)
    idx.add(1, v1)
    idx.add(1, v2)  # re-add = replace (reference deletes old docID first)
    ids, dists = idx.search_by_vector(v2, 2)
    assert ids[0] == 1
    assert len(idx) == 1
    np.testing.assert_allclose(dists[0], 0.0, atol=1e-4)


def test_allowlist_filtering(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((200, 8)).astype(np.float32)
    idx.add_batch(np.arange(200), vecs)
    allow = Bitmap([5, 50, 150])
    q = vecs[7]  # closest overall is 7, but it's not allowed
    ids, _ = idx.search_by_vector(q, 10, allow)
    assert set(ids.tolist()) <= {5, 50, 150}
    assert len(ids) == 3


def test_allowlist_large_path(tmp_path, rng):
    # force the full-scan masked path by setting the cutoff to 0
    idx = make_index(tmp_path, flatSearchCutoff=0)
    vecs = rng.standard_normal((100, 8)).astype(np.float32)
    idx.add_batch(np.arange(100), vecs)
    allow = Bitmap(np.arange(0, 100, 2))
    q = rng.standard_normal(8).astype(np.float32)
    ids, _ = idx.search_by_vector(q, 10, allow)
    assert all(i % 2 == 0 for i in ids.tolist())
    assert len(ids) == 10


def test_search_by_vector_distance(tmp_path, rng):
    idx = make_index(tmp_path)
    vecs = rng.standard_normal((100, 4)).astype(np.float32)
    idx.add_batch(np.arange(100), vecs)
    q = vecs[0]
    ids, dists = idx.search_by_vector_distance(q, 1.0, 100)
    assert (dists <= 1.0).all()
    # cross-check against brute force count
    from weaviate_tpu.ops.distances import single_distance

    want = sum(1 for v in vecs if single_distance(q, v, vi.DISTANCE_L2) <= 1.0)
    assert len(ids) == want


def test_persistence_roundtrip(tmp_path, rng):
    p = tmp_path / "shard"
    idx = make_index(p)
    vecs = rng.standard_normal((50, 8)).astype(np.float32)
    idx.add_batch(np.arange(50), vecs)
    idx.delete(3, 4)
    idx.shutdown()

    idx2 = make_index(p)
    idx2.post_startup()
    assert len(idx2) == 48
    q = vecs[10]
    ids, _ = idx2.search_by_vector(q, 1)
    assert ids[0] == 10
    ids, _ = idx2.search_by_vector(vecs[3], 5)
    assert 3 not in ids.tolist()


def test_bulk_replay_mixed_log_matches_prerestart(tmp_path, rng):
    """The vectorized replay (runs of adds parsed as one numpy view + bulk
    staging) must reproduce the EXACT pre-restart state for a log mixing
    adds, deletes, re-adds of deleted docs, duplicate doc ids within a run,
    and a torn tail."""
    from weaviate_tpu.index.tpu import VectorLog

    p = tmp_path / "shard"
    idx = make_index(p)
    vecs = rng.standard_normal((300, 8)).astype(np.float32)
    idx.add_batch(np.arange(300), vecs)
    idx.delete(*range(0, 40, 2))
    idx.add_batch(np.arange(10), vecs[100:110])  # re-add deleted + overwrite
    # in-batch duplicates; the LAST one carries a vector no other doc holds
    # (a shared vector would make the keep-last check a top_k tie-break)
    dup_vecs = rng.standard_normal((3, 8)).astype(np.float32)
    idx.add_batch(np.array([7, 7, 7]), dup_vecs)
    # a >=256-record run MIXING already-known docs (150..299: old slots must
    # tombstone via the per-record path) with fresh ones (300..429: bulk) —
    # exercises the known-filter and keep-mask slicing
    readd_vecs = rng.standard_normal((280, 8)).astype(np.float32)
    idx.add_batch(np.arange(150, 430), readd_vecs)
    idx.flush()
    live_ref = idx.live
    ids_ref, d_ref = idx.search_by_vectors(vecs[:16], 3)
    idx.shutdown()
    # torn tail: a half-written add record must be ignored, not crash
    with open(p / "vector.log", "ab") as f:
        f.write(b"\x01" + b"\x00" * 10)

    idx2 = make_index(p)
    assert idx2.live == live_ref
    ids2, d2 = idx2.search_by_vectors(vecs[:16], 3)
    np.testing.assert_allclose(d2, d_ref, atol=1e-5)
    # doc 7 carries its LAST duplicate's vector
    ids7, d7 = idx2.search_by_vector(dup_vecs[2], 1)
    assert ids7[0] == 7 and d7[0] < 1e-6
    # batch-run parser agrees record-for-record with the scalar parser
    flat = [(op, int(i), None if v is None else v.copy())
            for op, ids_, vv in VectorLog.replay_batches(str(p / "vector.log"))
            for i, v in (zip(ids_, vv) if op == "add" else [(ids_, None)])]
    scalar = list(VectorLog.replay(str(p / "vector.log")))
    assert len(flat) == len(scalar)
    for (o1, i1, v1), (o2, i2, v2) in zip(flat, scalar):
        assert o1 == o2 and i1 == i2
        if v1 is not None:
            np.testing.assert_array_equal(v1, v2)
    idx2.shutdown()


def test_compaction(tmp_path, rng):
    p = tmp_path / "shard"
    idx = make_index(p)
    vecs = rng.standard_normal((60, 8)).astype(np.float32)
    idx.add_batch(np.arange(60), vecs)
    idx.delete(*range(0, 30))
    idx.compact()
    assert len(idx) == 30
    ids, _ = idx.search_by_vector(vecs[45], 1)
    assert ids[0] == 45
    # compacted log replays correctly
    idx.shutdown()
    idx3 = make_index(p)
    assert len(idx3) == 30


def test_growth_past_min_capacity(tmp_path, rng):
    idx = make_index(tmp_path)
    n = 20000  # > _MIN_CAPACITY forces geometric growth
    vecs = rng.standard_normal((n, 8)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    ids, _ = idx.search_by_vector(vecs[n - 1], 1)
    assert ids[0] == n - 1
    assert len(idx) == n


@pytest.mark.parametrize("fused", [False, True], ids=["slots", "fused"])
@pytest.mark.parametrize("batch", [1, 8])
@pytest.mark.parametrize("metric", [vi.DISTANCE_L2, vi.DISTANCE_COSINE, vi.DISTANCE_DOT])
def test_scan_reads_only_live_chunks_of_a_part_full_slab(metric, batch, fused):
    """The scan's loop indexes the whole slab in place and walks
    `active_chunks` of its chunks: at a part-full slab (3 of 4 chunks live,
    n not a multiple of the chunk) the answers are exact float32 brute
    force's over the live, allowed, untombstoned rows, although every dead
    row would win if it were read (a copy of a query, or NaN)."""
    import jax
    import jax.numpy as jnp

    from weaviate_tpu.index import tpu
    from weaviate_tpu.ops.topk import unpack_fused, unpack_topk

    rng = np.random.default_rng(7)
    chunk, dim, k = tpu._SCAN_CHUNK, 8, 10
    cap, n = 4 * chunk, 2 * chunk + 12_345
    store = rng.standard_normal((cap, dim)).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        store /= np.linalg.norm(store, axis=1, keepdims=True)
    q = store[rng.integers(0, n, batch)] \
        + 0.05 * rng.standard_normal((batch, dim)).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    # the dead tail, inside the last live chunk and in the dead one: rows
    # that beat every live row for query 0, and NaN
    store[n::2] = q[0] * (100.0 if metric == vi.DISTANCE_DOT else 1.0)
    store[n + 1::2] = np.nan

    def exact(rows_ok):
        live = store[:n].astype(np.float64)
        qq = q.astype(np.float64)
        if metric == vi.DISTANCE_L2:
            d = ((qq[:, None, :] - live[None, :, :]) ** 2).sum(-1)
        else:
            d = -(qq @ live.T) if metric == vi.DISTANCE_DOT else 1.0 - qq @ live.T
        d[:, ~rows_ok] = np.inf
        ids = np.argsort(d, axis=1, kind="stable")[:, :k]
        return ids, np.take_along_axis(d, ids, axis=1)

    # tombstone each query's true nearest rows and a twentieth of the rest;
    # allow every other word's worth of rows at random
    tombs = np.zeros(cap, bool)
    tombs[exact(np.ones(n, bool))[0][:, :3].ravel()] = True
    tombs[rng.random(cap) < 0.05] = True
    allow = rng.random(cap) < 0.5
    words = np.packbits(allow.reshape(-1, 32), axis=1,
                        bitorder="little").view("<u4").ravel()
    want_ids, want_d = exact(~tombs[:n] & allow[:n])

    slots = np.arange(cap, dtype=np.uint64) * 7 + (1 << 32) + 3  # doc ids
    s2d = np.stack([slots & 0xFFFFFFFF, slots >> 32], 1).astype(np.uint32)
    sq = jnp.asarray((store ** 2).sum(1)) if metric == vi.DISTANCE_L2 else None
    args = (jnp.asarray(store), sq, jnp.asarray(tombs), n, jnp.asarray(q),
            jnp.asarray(words))
    statics = dict(k=k, metric=metric, use_allow=True, exact=False,
                   active_chunks=-(-n // chunk), rescore_r=40)
    if fused:
        ids, dists = unpack_fused(np.asarray(
            tpu._search_full_fused(*args, jnp.asarray(s2d), **statics)))
        want = slots[want_ids]
    else:
        # the body the one-chip program and (ROADMAP Queue 1 item 2) the
        # mesh share, jitted here: it returns slots
        scan = jax.jit(tpu._scan_full, static_argnames=tpu._SCAN_STATICS)
        dists, ids = unpack_topk(np.asarray(scan(*args, **statics)))
        want = want_ids
    np.testing.assert_array_equal(ids, want)
    # the benchmark's rule for a returned distance (benchmarks/lib/check.py)
    slack = np.maximum(1e-3 * np.abs(want_d), 1e-3)
    assert (np.abs(dists - want_d) <= slack).all()
