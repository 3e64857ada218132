"""The device dispatch (index/tpu.py): one program from scan to final doc
ids, zero host post-processing. It is the only way a result leaves the
device (PR 27 removed the host-translation twin of every program).

Pins its contracts:

1. bit-identity — on every tier (exact scan, filtered scan, PQ rescore,
   PQ codes-only, small-allowList gather compressed and not) the served
   ids and distances are EXACTLY what the tier's slot-returning body
   gives when the test jits it and translates its slots on the host
   through snap.slot_to_doc; sync == async; doc ids are not the slot
   numbers, 64-bit ids and the 2^64-1 "missing" id survive every tier's
   epilogue; target-distance widening matches exact brute force;
2. snapshot pinning — enqueue, then delete the winners and compact():
   finalize still returns the OLD snapshot's exact doc ids (the device
   translation table is pinned by the snapshot like every other device
   buffer);
3. the perf-ledger invariant — a dispatch records exactly ONE blocking
   fetch (costmodel.fused_invariant_ok; the window counts violations);
4. the satellites — the sorted doc->slot map is gone (gather resolves
   via a cached vectorized membership pass), the slot_to_doc COW copy is
   gone from the write path (append-only invariant), R_BUCKETS has one
   source of truth in config, and the enqueue staging pool reuses
   per-bucket host buffers.
"""

import inspect

import jax
import numpy as np
import pytest

from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import rescore_native, tpu
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import costmodel, perf, tracing
from weaviate_tpu.ops import gmin_scan, pq_gmin
from weaviate_tpu.ops.topk import unpack_topk
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)


def _mk_index(tmp_path, n=500, pq=None, seed=0, name="fx", ids=None,
              **cfg_extra):
    rng = np.random.default_rng(seed)
    # small-integer vectors: every L2 distance is exact integer arithmetic
    # in f32 regardless of accumulation order, so equality checks are exact
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    d = {"distance": "l2-squared", **cfg_extra}
    if pq is not None:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu", d)
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    ids = np.arange(n) if ids is None else ids
    idx.add_batch(ids.astype(np.int64), vecs)
    idx.flush()
    return idx, vecs


TIERS = ("exact", "filtered_scan", "gather", "pq_rescore", "pq_codes",
         "pq_gather")
_PQ = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16}


def _tier(tmp_path, tier, n=500, ids=None):
    """(index, vectors, allowList) of one read tier; `ids` [n] are the doc
    ids of the rows (default: the slot numbers), and the allowLists name
    the docs of rows 3, 7, 11 (and 401), whatever their ids."""
    ids = np.arange(n, dtype=np.uint64) if ids is None else ids
    if tier.startswith("pq"):
        rescore = tier == "pq_rescore"
        idx, vecs = _mk_index(tmp_path, n=n, name=tier, ids=ids,
                              pq={**_PQ, "rescore": rescore})
        assert idx.compressed
        assert (idx._rescore_dev is not None) == rescore
    else:
        idx, vecs = _mk_index(tmp_path, n=n, name=tier, ids=ids)
    allow = None
    if tier == "filtered_scan":
        # over the cutoff (so the masked full scan serves): every row's
        # doc, padded with ids no row has
        absent = np.arange(idx.config.flat_search_cutoff + 64,
                           dtype=np.uint64) + np.uint64(1 << 62)
        allow = Bitmap(np.concatenate([ids.astype(np.uint64), absent]))
    elif tier == "gather":
        allow = Bitmap(ids[[3, 7, 11, 401]].astype(np.uint64))
    elif tier == "pq_gather":
        allow = Bitmap(ids[[3, 7, 11]].astype(np.uint64))
    return idx, vecs, allow


def _tiers(tmp_path, n=500):
    """(name, index, vectors, allowList) of every read tier."""
    return [(t, *_tier(tmp_path, t, n)) for t in TIERS]


# -- 1. device translation == host translation of the same body ---------------

_GMIN_STATICS = ("use_allow", "k", "metric", "rg", "active_g", "interpret")
# every search program a tier above can reach, beside the traced body it
# translates: (module, program, body, the body's static arguments)
_PROGRAMS = (
    (tpu, "_search_full_fused", tpu._scan_full, tpu._SCAN_STATICS),
    (gmin_scan, "search_gmin_fused", gmin_scan.gmin_topk, _GMIN_STATICS),
    (pq_gmin, "search_pq_gmin_fused", pq_gmin.pq_gmin_topk, _GMIN_STATICS),
    (tpu, "_search_pq_recon_fused", tpu._pq_recon_topk,
     ("k", "r_chunk", "metric", "use_allow", "exact", "active_chunks",
      "do_rescore")),
    (tpu, "_search_pq_fused", tpu._pq_lut_topk,
     ("r", "use_allow", "exact", "active_chunks")),
    (tpu, "_score_rows_fused", tpu._score_rows_topk, ("k", "metric")),
    (tpu, "_search_gathered_fused", tpu._gathered_topk, ("k", "metric")),
)


def _spy_programs(monkeypatch):
    """Wrap every search program so that a dispatch records which program
    served and with what -> the list of (body, statics, args, kwargs), the
    arguments being the program's less its translation table."""
    calls = []
    for mod, name, body, statics in _PROGRAMS:
        real = getattr(mod, name)
        sig = getattr(real, "_plain", real)  # _ScanProgram holds two jits
        at = list(inspect.signature(sig).parameters).index("s2d")

        def spy(*args, _real=real, _at=at, _body=body, _statics=statics,
                **kwargs):
            assert len(args) > _at  # s2d is always passed by position
            calls.append((_body, _statics, args[:_at] + args[_at + 1:],
                          kwargs))
            return _real(*args, **kwargs)

        monkeypatch.setattr(mod, name, spy)
    return calls


def _host_translated(call, snap, b, k, q=None):
    """The reference: the tier's slot-returning body under jax.jit, its
    slots translated on the host through the snapshot's slot_to_doc. A
    compressed index's scan of its bf16 rows returns CANDIDATES (`q`
    given): their float32 distances from the snapshot's host rows, stably
    sorted, are the answer."""
    body, statics, args, kwargs = call
    kwargs = {kw: v for kw, v in kwargs.items() if kw != "with_slots"}
    out = jax.jit(body, static_argnames=statics)(*args, **kwargs)
    if isinstance(out, tuple):
        top, slots = (np.asarray(x) for x in out)
    else:  # _scan_full packs (dists | slots)
        top, slots = unpack_topk(np.asarray(out))
    top, slots = top[:b], slots[:b]
    if q is not None:
        # scored as the index scores them: the one native pass where its
        # library serves (its sums have their own order: tests/
        # test_rescore_native.py holds them to numpy's), numpy otherwise
        top, _ = rescore_native.distances(
            snap.host_vecs, slots, q.astype(np.float32), "l2-squared")
        if top is None:
            rows = snap.host_vecs[np.clip(slots, 0, None)]
            top = tpu._host_distances(rows, q.astype(np.float32),
                                      "l2-squared")
            top[slots < 0] = np.inf
        order = np.argsort(top, axis=1, kind="stable")
        top = np.take_along_axis(top, order, axis=1)
        slots = np.take_along_axis(slots, order, axis=1)
    ids = np.where(slots >= 0, snap.slot_to_doc[np.clip(slots, 0, None)], -1)
    return (ids.astype(np.uint64)[:, :k], top.astype(np.float32)[:, :k])


# a batch under 8 rows is refused by the Pallas group-min kernels, so the
# same tiers then run the lax.scan programs
@pytest.mark.parametrize("batch", [9, 3])
@pytest.mark.parametrize("tier", TIERS)
def test_fused_legacy_bit_identity_all_tiers_sync_and_async(
        tmp_path, monkeypatch, tier, batch):
    n = 500
    doc_ids = np.uint64(10_000) + np.uint64(3) * np.arange(n, dtype=np.uint64)
    idx, vecs, allow = _tier(tmp_path, tier, n, doc_ids)
    calls = _spy_programs(monkeypatch)
    q = vecs[:batch] + 0.01
    snap = idx._read_snapshot()[0]
    got_sync = idx.search_by_vectors(q, 10, allow)
    got_async = idx.search_by_vectors_async(q, 10, allow)()
    assert len(calls) == 2 and calls[0][0] is calls[1][0], calls
    served = {"exact": "_scan_full", "filtered_scan": "_scan_full",
              "pq_rescore": "_scan_full", "pq_codes": "_pq_recon_topk"}
    if batch < 8 and tier in served:
        assert calls[0][0].__name__ == served[tier]
    want = _host_translated(calls[0], snap, batch, got_sync[0].shape[1],
                            q if tier == "pq_rescore" else None)
    for got in (got_sync, got_async):
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert got_sync[0].dtype == np.uint64
    assert got_sync[1].dtype == np.float32
    found = got_sync[0][np.isfinite(got_sync[1])]
    assert found.size and np.isin(found, doc_ids).all()


def test_fused_target_distance_widening_matches_legacy(tmp_path):
    """The widening loop against exact brute force (integer vectors: every
    distance is exact in f32)."""
    idx, vecs = _mk_index(tmp_path)
    q = vecs[5] + np.float32(1.0)
    ids, dists = idx.search_by_vector_distance(q, 300.0, 64)
    d = ((vecs - q) ** 2).sum(1)
    inside = np.flatnonzero(d <= 300.0)
    assert 0 < inside.size < 64
    np.testing.assert_array_equal(dists, np.sort(d[inside]))
    assert set(ids.tolist()) == set(inside.tolist())


@pytest.mark.parametrize("tier", ["filtered_scan", "gather", "pq_rescore",
                                  "pq_codes", "pq_gather"])
def test_fused_missing_slots_carry_legacy_sentinel(tmp_path, tier):
    """Fewer matches than k: the missing columns read inf / 2^64-1
    (np.int64(-1) as uint64, the id the API has always carried there),
    through every tier's epilogue that a filter can starve."""
    idx, vecs, allow = _tier(tmp_path, tier)
    if allow is None or tier == "filtered_scan":
        # masked full scan with only 3 live matches (the rest are absent)
        cutoff = idx.config.flat_search_cutoff
        allow = Bitmap(np.array(
            [0, 1, 2] + list(range(10**6, 10**6 + cutoff + 50)),
            dtype=np.uint64))
        k, live = 8, 3
    else:
        # the gather tiers size k to the allowList; a tombstoned member
        # leaves its column empty
        idx.delete(7)
        idx.flush()
        k, live = len(allow), len(allow) - 1
    ids, dists = idx.search_by_vectors(vecs[:2] + 0.01, k, allow)
    assert ids.shape == (2, k)
    assert np.isfinite(dists[:, :live]).all()
    assert (ids[:, live:] == np.uint64(0xFFFFFFFFFFFFFFFF)).all()
    assert np.isinf(dists[:, live:]).all()


@pytest.mark.parametrize("tier", TIERS)
def test_fused_keeps_64bit_doc_ids(tmp_path, tier):
    """Doc ids above 2^32 (and above 2^63) survive the device translation
    table's two-word round trip bit-exactly through every tier's epilogue
    (jax may run with x64 disabled)."""
    n = 500
    doc_ids = np.uint64(2**40 + 1) + np.uint64(5) * np.arange(n, dtype=np.uint64)
    doc_ids[::2] += np.uint64(2**63)
    idx, vecs, allow = _tier(tmp_path, tier, n, doc_ids)
    ids, dists = idx.search_by_vectors(vecs[[3, 7, 11]], 3, allow)
    found = ids[np.isfinite(dists)]
    assert found.size >= 3 and np.isin(found, doc_ids).all()
    if allow is not None:
        assert np.isin(found, allow.to_array()).all()
    if not tier.startswith("pq") or tier == "pq_gather":
        # exact distances: every query is a stored row and finds its doc
        np.testing.assert_array_equal(ids[:, 0], doc_ids[[3, 7, 11]])


# -- 2. snapshot pinning across delete + compact ------------------------------


def test_fused_finalize_pins_snapshot_across_delete_compact(tmp_path):
    """Enqueue -> delete the winners + compact -> finalize returns the
    OLD snapshot's exact answer, on every tier (the PR-4 contract, now
    including the device slot->doc table)."""
    for name, idx, vecs, allow in _tiers(tmp_path):
        q = vecs[:4] + 0.01
        want = idx.search_by_vectors(q, 5, allow)
        fin = idx.search_by_vectors_async(q, 5, allow)
        winners = [int(x) for x in np.unique(want[0])
                   if x != 0xFFFFFFFFFFFFFFFF]
        idx.delete(*winners[:3])
        idx.compact()
        got = fin()
        np.testing.assert_array_equal(got[0], want[0], err_msg=name)
        np.testing.assert_array_equal(got[1], want[1], err_msg=name)
        # and a FRESH search sees the post-delete world
        fresh = idx.search_by_vectors(q, 5, allow)
        if winners[:3]:
            assert not set(winners[:3]) & {int(x) for x in fresh[0].ravel()}


# -- 3. the perf-ledger one-fetch invariant -----------------------------------


def _with_perf_window():
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    return perf.configure(perf.PerfWindow(window_s=60.0))


def _search_shape(idx, *args):
    """-> (ids, dists, the dispatch's shape off its handle)."""
    handle = idx.search_by_vectors_async(*args)
    ids, dists = handle()
    assert handle.shape is not None
    return ids, dists, handle.shape


def test_fused_invariant_one_fetch_zero_translation(tmp_path):
    win = _with_perf_window()
    for name, idx, vecs, allow in _tiers(tmp_path):
        ids, dists, shape = _search_shape(idx, vecs[:4] + 0.01, 5, allow)
        assert shape.fetches == 1, name
        assert costmodel.fused_invariant_ok(shape), name
        win.record_dispatch(shape, rows=4)
    s = win.summary()
    assert s["fused"]["dispatches"] == 6
    assert s["fused"]["violations"] == 0


def test_fused_invariant_violation_is_counted(tmp_path):
    win = _with_perf_window()
    shape = costmodel.DispatchShape(costmodel.TIER_EXACT, n=100, dim=DIM,
                                    batch=4, bytes_per_row=DIM * 4, k=5)
    shape.fetches = 2  # a second blocking fetch broke the contract
    assert not costmodel.fused_invariant_ok(shape)
    win.record_dispatch(shape, rows=4)
    assert win.summary()["fused"] == {"dispatches": 1, "violations": 1}


def test_fused_empty_gather_owes_no_fetch(tmp_path):
    """The empty-allowList gather early return runs no device work: zero
    fetches is NOT an invariant violation there (shape.n == 0)."""
    _with_perf_window()
    idx, vecs = _mk_index(tmp_path)
    allow = Bitmap(np.array([10**7, 10**7 + 1], dtype=np.uint64))
    ids, dists, shape = _search_shape(idx, vecs[:2], 5, allow)
    assert ids.shape == (2, 0)
    assert shape.fetches == 0 and shape.n == 0
    assert costmodel.fused_invariant_ok(shape)


# -- 4. satellites ------------------------------------------------------------


def test_sorted_map_is_gone_and_gather_slots_cache_on_allowlist(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    snap = idx._read_snapshot()[0]
    assert not hasattr(snap, "_sorted_map")
    assert not hasattr(snap, "sorted_doc_slots")
    allow = Bitmap(np.array([3, 7, 11], dtype=np.uint64))
    idx.search_by_vectors(vecs[:2], 3, allow)
    cached = allow._slots_cache
    assert cached[0] == (snap.allow_token, snap.n, snap.capacity)
    np.testing.assert_array_equal(cached[1], [3, 7, 11])
    # second search reuses the cached slots object
    idx.search_by_vectors(vecs[:2], 3, allow)
    assert allow._slots_cache[1] is cached[1]


def test_gather_cached_allowlist_never_returns_deleted_docs(tmp_path):
    """The review-caught staleness hole: the per-allowList slot cache's
    (allow_token, n, capacity) key does not change on deletes, so a
    REUSED AllowList object after a delete hits a stale slot list — the
    gather kernels must mask tombstones on device with the dispatching
    snapshot's own tombs (both tiers)."""
    for compress in (False, True):
        pq = ({"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16}
              if compress else None)
        idx, vecs = _mk_index(tmp_path, pq=pq,
                              name=f"stale{int(compress)}")
        allow = Bitmap(np.array([3, 7, 11], dtype=np.uint64))
        q = vecs[:2] + 0.01
        ids0, _ = idx.search_by_vectors(q, 3, allow)  # warms the cache
        assert 3 in {int(x) for x in ids0.ravel()}
        idx.delete(3)
        idx.flush()
        ids1, d1 = idx.search_by_vectors(q, 3, allow)  # same object
        got = {int(x) for x in ids1.ravel() if x != 2**64 - 1}
        assert got == {7, 11}, (compress, ids1, d1)


def test_gather_fully_deleted_filter_short_circuits_empty(tmp_path):
    """An allowList whose every match is tombstoned in the dispatching
    snapshot must return the (b, 0) empty shape with ZERO device work —
    even through a stale cached slot list (the short-circuit consults
    the snapshot's own host mirror per dispatch, never the cache)."""
    _with_perf_window()
    idx, vecs = _mk_index(tmp_path)
    allow = Bitmap(np.array([3, 7], dtype=np.uint64))
    q = vecs[:2] + 0.01
    idx.search_by_vectors(q, 3, allow)  # warm the slot cache
    idx.delete(3, 7)
    idx.flush()
    ids, dists, shape = _search_shape(idx, q, 3, allow)
    assert ids.shape == (2, 0) and dists.shape == (2, 0)
    assert shape.n == 0 and shape.fetches == 0


def test_gather_resolves_readded_doc_to_newest_slot(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    idx.delete(7)
    idx.add(7, np.full(DIM, 1.0, np.float32))
    allow = Bitmap(np.array([7], dtype=np.uint64))
    ids, dists = idx.search_by_vectors(np.ones((1, DIM), np.float32), 3,
                                       allow)
    # the old tombstoned slot is gathered but device-masked to the
    # sentinel; exactly ONE live hit survives — the re-added vector
    finite = np.isfinite(dists[0])
    assert finite.sum() == 1
    assert int(ids[0][finite][0]) == 7
    assert abs(float(dists[0][finite][0])) < 1e-6  # the NEW vector


def test_gather_old_pinned_snapshot_keeps_its_predelete_world(tmp_path):
    """The reverse staleness direction (review-caught): a dispatch pinned
    on an OLD snapshot must keep returning docs live in ITS world even
    when the shared slot cache was (re)computed after a delete — the
    cached list carries no tombstone knowledge; each dispatch's own
    device tombs mask decides."""
    idx, vecs = _mk_index(tmp_path)
    allow = Bitmap(np.array([3, 7, 11], dtype=np.uint64))
    q = vecs[:2] + 0.01
    snap_a = idx._read_snapshot()[0]
    idx.delete(3)
    idx.flush()  # publishes B; (allow_token, n, capacity) unchanged
    # warm the cache from B's world
    ids_b, _ = idx.search_by_vectors(q, 3, allow)
    assert 3 not in {int(x) for x in ids_b.ravel()}
    # a dispatch pinned on A consumes the same cache — doc 3 must be back
    ids_a, dists_a = idx._dispatch_search(snap_a, q, 3, allow)()
    assert 3 in {int(x) for x in ids_a.ravel()}


def test_slot_to_doc_cow_copy_dropped_host_tombs_kept(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    snap = idx._read_snapshot()[0]
    s2d_obj = snap.slot_to_doc
    # append within capacity: slot_to_doc mutates in place past snap.n —
    # NO copy (the append-only invariant), and the snapshot's prefix is
    # untouched
    idx.add(10_001, vecs[0])
    idx.flush()
    assert idx._slot_to_doc is s2d_obj
    assert idx._snap.slot_to_doc is s2d_obj
    # a delete still copy-on-writes the host tombstone mirror the old
    # snapshot pins
    tombs_obj = idx._host_tombs
    assert idx._snap.host_tombs is tombs_obj
    idx.delete(3)
    idx.flush()
    assert idx._host_tombs is not tombs_obj
    assert not snap.host_tombs[3]  # the pinned view never tore


def test_r_buckets_single_source_of_truth():
    from weaviate_tpu.config.config import RESCORE_R_BUCKETS
    from weaviate_tpu.serving import controller

    assert controller.R_BUCKETS is RESCORE_R_BUCKETS
    assert tpu.RESCORE_R_BUCKETS is RESCORE_R_BUCKETS
    assert RESCORE_R_BUCKETS[-1] == 128


def test_stage_pool_reuses_query_buffers(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    q = vecs[:3] + 0.01
    ids1, _ = idx.search_by_vectors(q, 5)
    key = (tpu._bucket_b(3), DIM)
    assert len(idx._stage_free.get(key, [])) == 1
    buf = idx._stage_free[key][0]
    ids2, _ = idx.search_by_vectors(q, 5)
    # same buffer went out and came back; results stay correct
    assert idx._stage_free[key][0] is buf
    np.testing.assert_array_equal(ids1, ids2)
    # the pool is bounded
    assert all(len(v) <= TpuVectorIndex._STAGE_POOL_CAP
               for v in idx._stage_free.values())


def test_stage_pool_ledger_component_and_drop(tmp_path):
    from weaviate_tpu.monitoring import memory

    idx, vecs = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:3] + 0.01, 5)
    comps = memory.index_host_components(idx)
    want = sum(b.nbytes for bufs in idx._stage_free.values() for b in bufs)
    assert want > 0 and comps["stage_buffers"] == want
    assert "stage_buffers" in memory.HOST_COMPONENTS
    idx.drop()
    assert idx._stage_free == {}
    assert "stage_buffers" not in memory.index_host_components(idx)


def test_prefetch_failure_strands_stage_buffer(tmp_path):
    """A finalize that fails BEFORE the blocking fetch must NOT return
    its staging buffer to the pool: the enqueued program may not have
    consumed the (possibly aliased, cpu backend) host memory yet, and a
    recycled buffer could corrupt a retried dispatch."""
    from weaviate_tpu.testing import faults

    idx, vecs = _mk_index(tmp_path)
    q = vecs[:3] + 0.01
    idx.search_by_vectors(q, 5)  # park one buffer
    key = (tpu._bucket_b(3), DIM)
    assert len(idx._stage_free[key]) == 1
    inj = faults.configure(faults.from_spec("index.tpu.finalize:device_error:times=1"))
    try:
        fin = idx.search_by_vectors_async(q, 5)  # checks the buffer out
        assert len(idx._stage_free[key]) == 0
        with pytest.raises(Exception):
            fin()
        # stranded, not recycled
        assert len(idx._stage_free[key]) == 0
    finally:
        faults.configure(None)
        del inj
    # a healthy dispatch parks a fresh buffer again
    idx.search_by_vectors(q, 5)
    assert len(idx._stage_free[key]) == 1


def test_drop_blocks_stage_buffer_reparking(tmp_path):
    """An in-flight dispatch finalizing AFTER drop() must not re-park
    its staging buffer into the cleared pool (stage_buffers must read 0
    after drop; a re-created index may use a different dim)."""
    idx, vecs = _mk_index(tmp_path)
    fin = idx.search_by_vectors_async(vecs[:3] + 0.01, 5)
    idx.drop()
    fin()
    assert idx._stage_free == {}
