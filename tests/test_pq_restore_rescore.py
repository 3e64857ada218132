"""A compressed `hnsw_tpu` shard: how it restarts, where its returned
distances come from, when a declared class compresses, and what it records.

- the reference comparison: a declared-pq class, served through the shard
  after a clean restart, held to the benchmark's own rule
  (benchmarks/lib/check.py: recall@10 at the bar, every returned distance
  within 1e-3 relative of the float64 distance of the row returned) for
  cosine, l2-squared and dot; and its twin, in which the rescoring step
  answers with the distances of the bf16 rows, FAILS the distance rule;
- restore replays straight into the compressed form: no float32 slab, no
  slab fetched back, the same answers as before the restart, live == rows;
- a pq.npz this build cannot use still serves uncompressed, counted;
- a declared class compresses at its `trainingLimit` and says so;
- the `rescore` phase and counters: one a compressed dispatch, none on an
  uncompressed one.
"""

import os
import uuid as uuidlib

import jax
import numpy as np
import pytest

from benchmarks.lib import check
from benchmarks.references import exact_f32
from weaviate_tpu.config import Config
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index import tpu
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import memory, perf, tracing
from weaviate_tpu.monitoring.metrics import get_metrics

ROWS, DIM, K, POOL = 20_000, 64, 10, 64
PQ = {"enabled": True, "segments": 8, "centroids": 256, "trainingLimit": 2000}


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)
    memory.configure(None)


def _corpus(seed=0):
    """Clustered rows and queries that are a stored row plus noise (the
    benchmark's own model), with the reference's exact ground truth."""
    rng = np.random.default_rng(seed)
    centres = rng.standard_normal((64, DIM)).astype(np.float32) * 2.0
    rows = (centres[rng.integers(0, 64, ROWS)]
            + rng.standard_normal((ROWS, DIM)).astype(np.float32))
    pool = (rows[rng.choice(ROWS, POOL, replace=False)]
            + 0.05 * rng.standard_normal((POOL, DIM)).astype(np.float32))
    return rows, pool


def _truth(metric, rows, pool):
    top = exact_f32.TopK(metric, pool, K)
    for s in range(0, ROWS, 8192):
        top.update(s, rows[s:s + 8192])
    return top.ids


def _app(path, tracing_on=False):
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.tracing.enabled = tracing_on
    cfg.tracing.sample_rate = 1.0
    return App(config=cfg, data_path=path)


def _import(app, metric, rows):
    from weaviate_tpu.entities.storobj import StorObj

    app.schema.add_class({
        "class": "Pq", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": metric, "pq": dict(PQ)},
        "properties": [{"name": "bucket", "dataType": ["int"]}]})
    idx = app.db.get_index("Pq")
    for s in range(0, ROWS, 5000):
        idx.put_batch([
            StorObj(class_name="Pq", uuid=str(uuidlib.UUID(int=i + 1)),
                    properties={"bucket": i % 10}, vector=rows[i])
            for i in range(s, s + 5000)])
    return idx


def _served(app, pool):
    """-> (row ids [POOL, K], distances [POOL, K]) through the shard's
    batched search, the hydrate included."""
    shard = app.db.get_index("Pq").single_local_shard()
    hits = shard.object_vector_search(pool, K)
    ids = np.array([[uuidlib.UUID(h.obj.uuid).int - 1 for h in row]
                    for row in hits], np.int64)
    dists = np.array([[h.distance for h in row] for row in hits], np.float32)
    return ids, dists


def _bf16_row_distances(self, snap, q, packed, b, k, shape):
    """The rescoring step as it was before this test's subject: the top k
    by the distances to the bf16-rounded rows."""
    ids, _, slots = tpu.unpack_fused_slots(packed[:b])
    rows = np.asarray(snap.rescore_dev)[np.maximum(slots, 0)].astype(
        np.float32)
    d = tpu._host_distances(rows, q[:b], self.metric)
    d[slots < 0] = np.inf
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(d, order, axis=1).astype(np.float32))


@pytest.mark.parametrize("metric", ["cosine", "l2-squared", "dot"])
def test_restarted_compressed_shard_against_the_reference(
        tmp_path, monkeypatch, metric):
    rows, pool = _corpus()
    gt = _truth(metric, rows, pool)
    path = str(tmp_path / "data")
    app = _app(path)
    vidx = _import(app, metric, rows).single_local_shard().vector_index
    assert vidx.compressed and vidx._store is None
    app.shutdown()

    app = _app(path)
    try:
        vidx = app.db.get_index("Pq").single_local_shard().vector_index
        assert vidx.compressed and vidx.live == ROWS
        assert vidx.last_restore["mode"] == "compressed"
        qidx = np.arange(POOL)
        got_ids, got_dists = _served(app, pool)
        res = check.check_window(exact_f32, metric, K, rows, pool, gt, qidx,
                                 got_ids, got_dists)
        assert res["recall"] >= check.RECALL_BAR, res
        assert res["bad_distances"] == 0 and res["short_replies"] == 0, res

        # the twin: the same replies with the bf16 rows' distances fail the
        # SAME rule, so the tolerance bites
        monkeypatch.setattr(TpuVectorIndex, "_rescore_f32",
                            _bf16_row_distances)
        bad_ids, bad_dists = _served(app, pool)
        twin = check.check_window(exact_f32, metric, K, rows, pool, gt, qidx,
                                  bad_ids, bad_dists)
        assert twin["bad_distances"] > 0, twin
    finally:
        app.shutdown()


def _index(path, metric="l2-squared", pq=PQ, **kw):
    d = {"distance": metric}
    if pq is not None:
        d["pq"] = dict(pq)
    return TpuVectorIndex(vi.HnswUserConfig.from_dict(d, "hnsw_tpu"), path,
                          **kw)


def _filled(path, rows, metric="l2-squared"):
    idx = _index(path, metric)
    for s in range(0, ROWS, 5000):
        idx.add_batch(np.arange(s, s + 5000), rows[s:s + 5000])
    idx.flush()
    assert idx.compressed
    return idx


def test_restore_never_holds_the_f32_slab_and_fetches_nothing_back(
        tmp_path, monkeypatch):
    rows, pool = _corpus(1)
    path = str(tmp_path / "shard")
    idx = _filled(path, rows)
    want = idx.search_by_vectors(pool, K)
    idx.shutdown()

    led = memory.configure(memory.MemoryLedger())
    seen: list[dict] = []
    real_stamp = memory.MemoryLedger.stamp_device

    def stamp(self, owner, comps):
        seen.append(dict(comps))
        return real_stamp(self, owner, comps)

    monkeypatch.setattr(memory.MemoryLedger, "stamp_device", stamp)
    fetched: list[tuple] = []
    real_asarray = np.asarray

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array) and a.ndim == 2 and a.shape[1] == DIM:
            fetched.append(a.shape)
        return real_asarray(a, *args, **kw)

    monkeypatch.setattr(np, "asarray", asarray)
    # the log replays in several runs, and none of them publishes a
    # snapshot: one would pin a third generation of the slab
    monkeypatch.setattr(tpu, "_REPLAY_RUN_MAX", tpu._CHUNK)
    published = []
    real_publish = TpuVectorIndex._publish_snapshot

    def publish(self):
        published.append(self._restoring)
        return real_publish(self)

    monkeypatch.setattr(TpuVectorIndex, "_publish_snapshot", publish)
    idx2 = _index(path)
    idx2.post_startup()
    monkeypatch.setattr(np, "asarray", real_asarray)

    assert published == [False]
    assert idx2.compressed and idx2.live == ROWS == len(idx2)
    assert idx2._store is None and fetched == []
    # the ledger's components from the first stamp to ready: no float32
    # slab at all, the compressed ones at their bytes at the end
    assert seen and all(c.get("store", 0) == 0 for c in seen)
    assert all(c.get("store", 0) <= tpu._MIN_CAPACITY * DIM * 4
               for c in seen)
    cap = idx2.capacity
    assert seen[-1]["rescore_store"] == cap * DIM * 2
    assert seen[-1]["pq_codes"] == cap * PQ["segments"]
    assert led.device_components().get("store", 0) == 0
    assert memory.index_host_components(idx2)["host_vecs"] == cap * DIM * 4
    r = idx2.last_restore
    assert r["mode"] == "compressed" and r["rows"] == ROWS
    assert r["chunks_encoded"] >= -(-ROWS // tpu._CHUNK)
    assert idx2.health()["restore"] == r
    got = idx2.search_by_vectors(pool, K)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    # rows written after the restart land in the compressed form too
    idx2.add_batch(np.arange(ROWS, ROWS + 300), rows[:300] + 1.0)
    idx2.flush()
    assert idx2._store is None and idx2.live == ROWS + 300
    idx2.shutdown()


def test_uncompressed_restore_says_so(tmp_path):
    rows, _ = _corpus(2)
    path = str(tmp_path / "plain")
    idx = _index(path, pq=None)
    idx.add_batch(np.arange(9000), rows[:9000])
    idx.flush()
    idx.shutdown()
    idx2 = _index(path, pq=None)
    assert not idx2.compressed and idx2._store is not None
    assert idx2.last_restore["mode"] == "uncompressed"
    assert idx2.last_restore["chunks_encoded"] == 0
    assert idx2.last_restore["rows"] == 9000
    idx2.shutdown()


@pytest.mark.parametrize("damage", ["corrupt", "other_dim"])
def test_unusable_codebook_serves_uncompressed_and_is_counted(
        tmp_path, damage):
    rows, pool = _corpus(3)
    path = str(tmp_path / "shard")
    idx = _filled(path, rows)
    idx.shutdown()
    pq_path = os.path.join(path, "pq.npz")
    if damage == "corrupt":
        with open(pq_path, "r+b") as f:
            f.truncate(os.path.getsize(pq_path) // 2)
    else:
        from weaviate_tpu.compress.pq import ProductQuantizer

        other = ProductQuantizer(dim=32, segments=8, centroids=16,
                                 metric="l2-squared")
        other.fit(rows[:512, :32])
        other.save(pq_path)
    counter = get_metrics().device_fallbacks.labels(
        component="index.tpu.restore", reason="pq_codebook_rejected")
    before = counter._value.get()
    idx2 = _index(path)
    assert not idx2.compressed and idx2._store is not None
    assert idx2.live == ROWS
    assert counter._value.get() == before + 1
    assert idx2.last_restore["mode"] == "uncompressed"
    ids, _ = idx2.search_by_vectors(rows[:4], 1)
    np.testing.assert_array_equal(ids[:, 0], np.arange(4, dtype=np.uint64))
    idx2.shutdown()


def test_declared_class_compresses_at_its_training_limit(tmp_path):
    rows, _ = _corpus(4)
    idx = _index(str(tmp_path / "s"), persist=False)
    assert idx.config.pq.training_limit == 2000
    assert idx.config.to_dict()["pq"]["trainingLimit"] == 2000
    idx.add_batch(np.arange(1999), rows[:1999])
    idx.flush()
    assert not idx.compressed and idx.health()["pq"] is None
    idx.add_batch(np.arange(1999, 2600), rows[1999:2600])
    idx.flush()
    assert idx.compressed
    # fitted on trainingLimit of the rows that were there, not on 256
    assert idx.health()["pq"]["trained_rows"] == 2000

    # the documented default, parsed and echoed; below 1 is refused
    d = vi.HnswUserConfig.from_dict({"pq": {"enabled": True}}, "hnsw_tpu")
    assert d.pq.training_limit == 100_000
    assert d.to_dict()["pq"]["trainingLimit"] == 100_000
    with pytest.raises(vi.ConfigValidationError):
        vi.parse_and_validate_config(
            "hnsw_tpu", {"pq": {"enabled": True, "trainingLimit": 0}})

    # an explicit enable after import fits on what is there
    late = _index(str(tmp_path / "late"), pq=None, persist=False)
    late.add_batch(np.arange(700), rows[:700])
    late.update_user_config(vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared",
         "pq": {"enabled": True, "segments": 8, "centroids": 16}},
        "hnsw_tpu"))
    assert late.compressed and late.health()["pq"]["trained_rows"] == 700


def test_trained_rows_survive_a_restart(tmp_path):
    rows, _ = _corpus(5)
    path = str(tmp_path / "shard")
    idx = _filled(path, rows)
    trained = idx.health()["pq"]["trained_rows"]
    assert trained == 2000
    idx.shutdown()
    idx2 = _index(path)
    assert idx2.health()["pq"]["trained_rows"] == trained
    idx2.shutdown()


def test_compaction_of_a_compressed_index_stays_compressed(tmp_path,
                                                           monkeypatch):
    rows, pool = _corpus(6)
    idx = _filled(str(tmp_path / "shard"), rows)
    codebook = idx._pq.codebook.copy()
    idx.delete(*range(0, 4000, 2))
    idx.flush()
    fetched = []
    real_asarray = np.asarray

    def asarray(a, *args, **kw):
        if isinstance(a, jax.Array) and a.ndim == 2 and a.shape[1] == DIM:
            fetched.append(a.shape)
        return real_asarray(a, *args, **kw)

    monkeypatch.setattr(np, "asarray", asarray)
    idx.compact()
    monkeypatch.setattr(np, "asarray", real_asarray)
    assert idx.compressed and idx._store is None and fetched == []
    assert idx.live == idx.n == ROWS - 2000
    np.testing.assert_array_equal(idx._pq.codebook, codebook)
    ids, dists = idx.search_by_vectors(pool, K)
    assert not (set(range(0, 4000, 2)) & set(ids.ravel().tolist()))
    want = exact_f32.pair_distances(
        "l2-squared", rows[ids.astype(np.int64)], pool[:, None, :])
    np.testing.assert_allclose(dists, want, rtol=1e-4)
    idx.shutdown()


def test_rescore_phase_and_counters_once_a_compressed_dispatch(tmp_path):
    rows, pool = _corpus(7)
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    win = perf.configure(perf.PerfWindow(window_s=60.0))
    win.capture_begin()
    idx = _filled(str(tmp_path / "pq"), rows)
    plain = _index(str(tmp_path / "plain"), pq=None, persist=False)
    plain.add_batch(np.arange(3000), rows[:3000])
    plain.flush()

    handle = plain.search_by_vectors_async(pool[:8], K)
    handle()
    shape = handle.shape
    assert shape.rescore_ms < 0 and "rescore" not in shape.ledger()
    win.record_dispatch(shape, rows=8)
    assert "rescore" not in win.summary()

    r = idx._candidate_depth(K, idx.n)
    assert r == 40
    for _ in range(3):
        handle = idx.search_by_vectors_async(pool[:8], K)
        handle()
        shape = handle.shape
        assert shape.fetches == 1 and shape.rescore_ms >= 0
        led = shape.ledger()
        assert led["rescore"] == shape.rescore_ms
        assert led["gather_hop"] <= shape.finalize_ms - shape.device_ms
        win.record_dispatch(shape, rows=8)
    s = win.summary()
    assert s["rescore"]["dispatches"] == 3
    assert s["rescore"]["rows"] == 3 * 8 * r
    assert s["rescore"]["bytes"] == 3 * 8 * r * DIM * 4
    assert 0 <= s["rescore"]["promoted"] <= 3 * 8 * K
    assert s["phases"]["rescore"]["samples"] == 3
    assert s["tiers"] == {"pq_rescore_bf16": 3, "exact_scan": 1}
    win.capture_end(0, 1, {})
    names = [x[0] for x in win.last_capture()["intervals"]]
    assert names.count("rescore") == 3
    # a rescore interval closes inside its dispatch's gather_hop
    at = names.index("rescore")
    assert names[at - 1] == "device_wait" and names[at + 1] == "gather_hop"

    # raw ADC distances (pq.rescore=false) keep their semantics: no rescore
    raw = _index(str(tmp_path / "raw"), pq={**PQ, "rescore": False},
                 persist=False)
    raw.add_batch(np.arange(3000), rows[:3000])
    raw.flush()
    assert raw.compressed and raw._rescore_dev is None
    handle = raw.search_by_vectors_async(pool[:8], K)
    handle()
    assert handle.shape.rescore_ms < 0
    assert win.summary()["rescore"]["dispatches"] == 3


def test_rescoring_reorders_by_float32_and_counts_what_it_moved(tmp_path):
    """Two rows that bf16 cannot tell apart and float32 can: the scan hands
    both over, the host's float32 rows decide, `promoted` says so."""
    win = perf.configure(perf.PerfWindow(window_s=60.0))
    rng = np.random.default_rng(8)
    rows = rng.standard_normal((3000, DIM)).astype(np.float32) * 4.0
    near, nearer = rows[10].copy(), rows[10].copy()
    near[0] += 2.0 ** -9      # both round to the same bf16 row ...
    nearer[0] += 2.0 ** -10   # ... and differ in float32
    rows[11], rows[12] = near, nearer
    idx = _index(str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(3000), rows)
    idx.flush()
    assert idx.compressed
    q = rows[10:11].copy()
    q[0, 0] += 2.0 ** -10     # the query is `nearer` itself
    ids, dists = idx.search_by_vectors(q, 3)
    assert ids[0, 0] == 12 and dists[0, 0] == 0.0
    want = exact_f32.pair_distances("l2-squared", rows[ids[0].astype(int)], q)
    np.testing.assert_allclose(dists[0], want, rtol=1e-5, atol=1e-12)
    assert win.summary()["rescore"]["dispatches"] == 1
