"""Product quantization: encoders, LUT math, compressed index search.

Reference test model: ssdhelpers/product_quantization_test.go (encode/decode
roundtrip, LUT distance vs exact), hnsw recall_test.go:137 (recall bar).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from weaviate_tpu.compress.pq import ProductQuantizer, build_lut, lut_scan_block
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.tpu import TpuVectorIndex


def _cfg(**pq_kwargs):
    d = {"distance": "l2-squared"}
    if pq_kwargs:
        # a class that declares pq compresses at its trainingLimit; these
        # tests import a few hundred rows
        d["pq"] = {"trainingLimit": 256, **pq_kwargs}
    return vi.HnswUserConfig.from_dict(d)


@pytest.fixture()
def data():
    rng = np.random.default_rng(7)
    # clustered data so PQ codebooks have structure to find
    centers = rng.standard_normal((8, 32)) * 5.0
    x = centers[rng.integers(0, 8, 2000)] + rng.standard_normal((2000, 32))
    return x.astype(np.float32)


def test_kmeans_roundtrip_error(data):
    pq = ProductQuantizer(dim=32, segments=8, centroids=64, metric="l2-squared")
    pq.fit(data)
    codes = pq.encode(data)
    assert codes.shape == (2000, 8) and codes.dtype == np.uint8
    recon = pq.decode(codes)
    # quantization must beat the trivial all-mean reconstruction by a lot
    mse = np.mean((recon - data) ** 2)
    mse_mean = np.mean((data - data.mean(0)) ** 2)
    assert mse < 0.25 * mse_mean


def test_tile_encoder_roundtrip(data):
    pq = ProductQuantizer(
        dim=32, segments=32, centroids=32, metric="l2-squared",
        encoder=vi.PQ_ENCODER_TILE, distribution=vi.PQ_DISTRIBUTION_NORMAL)
    pq.fit(data)
    recon = pq.decode(pq.encode(data))
    mse = np.mean((recon - data) ** 2)
    mse_mean = np.mean((data - data.mean(0)) ** 2)
    assert mse < 0.25 * mse_mean


def test_tile_requires_scalar_segments():
    with pytest.raises(vi.ConfigValidationError):
        ProductQuantizer(dim=32, segments=8, centroids=16, metric="l2-squared",
                         encoder=vi.PQ_ENCODER_TILE)


def test_lut_distance_matches_decoded_distance(data):
    """Asymmetric LUT-sum distance == exact distance to the decoded vector
    (the defining property of the reference's DistanceLookUpTable)."""
    pq = ProductQuantizer(dim=32, segments=8, centroids=64, metric="l2-squared")
    pq.fit(data)
    codes = pq.encode(data[:128])
    q = data[500:504]
    lut = build_lut(jnp.asarray(q), jnp.asarray(pq.codebook), "l2-squared")
    d_lut = np.asarray(lut_scan_block(jnp.asarray(codes.astype(np.int32)), lut))
    recon = pq.decode(codes)
    d_exact = ((q[:, None, :] - recon[None, :, :]) ** 2).sum(-1)
    np.testing.assert_allclose(d_lut, d_exact, rtol=1e-3, atol=1e-2)


def test_lut_dot_and_cosine(data):
    pq = ProductQuantizer(dim=32, segments=8, centroids=64, metric="dot")
    pq.fit(data)
    codes = pq.encode(data[:64])
    q = data[100:102]
    lut = build_lut(jnp.asarray(q), jnp.asarray(pq.codebook), "dot")
    d_lut = np.asarray(lut_scan_block(jnp.asarray(codes.astype(np.int32)), lut))
    recon = pq.decode(codes)
    np.testing.assert_allclose(d_lut, -(q @ recon.T), rtol=1e-3, atol=1e-2)


def test_save_load_roundtrip(tmp_path, data):
    pq = ProductQuantizer(dim=32, segments=8, centroids=64, metric="l2-squared")
    pq.fit(data)
    p = str(tmp_path / "pq.npz")
    pq.save(p)
    pq2 = ProductQuantizer.load(p)
    np.testing.assert_array_equal(pq.encode(data[:50]), pq2.encode(data[:50]))


# -- compressed index ---------------------------------------------------------

def _recall(idx, data, queries, k=10):
    ids, _ = idx.search_by_vectors(queries, k)
    d = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    truth = np.argsort(d, axis=1)[:, :k]
    hits = sum(len(set(ids[i].tolist()) & set(truth[i].tolist())) for i in range(len(queries)))
    return hits / (len(queries) * k)


def test_compressed_index_recall(tmp_path, data):
    cfg = _cfg(enabled=False, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    # explicit compression via config update (compress.go trigger)
    new = vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared", "pq": {"enabled": True, "segments": 8, "centroids": 64}})
    idx.update_user_config(new)
    assert idx.compressed
    queries = data[:32]
    rec = _recall(idx, data, queries)
    assert rec >= 0.95, f"compressed recall {rec}"


def test_compressed_no_rescore_lower_recall_still_works(tmp_path, data):
    cfg = _cfg(enabled=True, segments=8, centroids=64, rescore=False)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    assert idx.compressed
    rec = _recall(idx, data, data[:16])
    assert rec >= 0.3  # raw PQ distances: approximate by design (8x4-dim
    # segments, 64 centroids => coarse cells; rescore=True is the default)


def test_compressed_filtered_search(tmp_path, data):
    from weaviate_tpu.storage.bitmap import Bitmap

    cfg = _cfg(enabled=True, segments=8, centroids=64)
    cfg.flat_search_cutoff = 10  # force the bitmap path, not the gather path
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    assert idx.compressed
    allow = Bitmap(np.arange(0, len(data), 2).astype(np.uint64))
    ids, _ = idx.search_by_vectors(data[:8], 5, allow)
    valid = ids[ids != np.uint64(0xFFFFFFFFFFFFFFFF)]
    assert (valid % 2 == 0).all()


def test_compressed_gather_path(tmp_path, data):
    from weaviate_tpu.storage.bitmap import Bitmap

    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    allow = Bitmap(np.arange(100).astype(np.uint64))  # < flatSearchCutoff
    ids, dists = idx.search_by_vector(data[50], 5, allow)
    assert ids[0] == 50 and dists[0] < 1e-3


def test_compressed_delete_and_update(tmp_path, data):
    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    idx.delete(0)
    ids, _ = idx.search_by_vector(data[0], 3)
    assert 0 not in ids.tolist()
    # re-add under a new vector
    idx.add(0, data[1])
    ids, dists = idx.search_by_vector(data[1], 2)
    assert {0, 1} <= set(ids.tolist())


def test_compressed_persistence_restore(tmp_path, data):
    path = str(tmp_path / "shard")
    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, path)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    assert idx.compressed
    ids_before, _ = idx.search_by_vector(data[3], 5)
    idx.shutdown()

    idx2 = TpuVectorIndex(_cfg(enabled=True, segments=8, centroids=64), path)
    assert idx2.compressed  # codebook reloaded from pq.npz
    ids_after, _ = idx2.search_by_vector(data[3], 5)
    np.testing.assert_array_equal(ids_before, ids_after)
    idx2.shutdown()


def test_pq_immutable_disable(tmp_path, data):
    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(512), data[:512])
    idx.flush()
    off = _cfg(enabled=False, segments=8, centroids=64)
    with pytest.raises(vi.ConfigValidationError):
        idx.update_user_config(off)


def test_pq_enable_rejection_does_not_stick(tmp_path, data):
    """segments that don't divide dims reject the pq-enable update — and the
    rejected config must not stick, or _flush_pending's declarative trigger
    would re-raise on every later add/search."""
    idx = TpuVectorIndex(_cfg(), str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(512), data[:512])
    bad = _cfg(enabled=True, segments=7, centroids=64)  # 7 ∤ 32
    with pytest.raises(vi.ConfigValidationError):
        idx.update_user_config(bad)
    assert not idx.config.pq.enabled
    idx.add_batch(np.arange(512, 560), data[512:560])
    ids, _ = idx.search_by_vector(data[0], 5)
    assert ids[0] == 0


def test_pq_rescore_serves_from_store_scan(tmp_path, data):
    """With rescore enabled the bf16 row copy is already in HBM, so the
    fast scan runs straight over it (codes are write/restart-side only) —
    results must match exact numpy within bf16 tolerance."""
    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(1000), data[:1000])
    idx.flush()
    assert idx.compressed and idx._rescore_dev is not None
    q = data[:32] + 0.001 * np.random.default_rng(1).standard_normal((32, 32)).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    d = ((q[:, None, :] - data[None, :1000, :]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :5]
    hit = np.mean([len(set(ids[i].tolist()) & set(want[i].tolist())) / 5
                   for i in range(32)])
    assert hit >= 0.96
    np.testing.assert_array_equal(ids[:, 0], np.arange(32, dtype=np.uint64))
    # distances come from the bf16 row copy, not the PQ approximation
    np.testing.assert_allclose(dists[:, 0], d[np.arange(32), ids[:, 0].astype(int)],
                               rtol=2e-2, atol=2e-2)


def test_pq_manhattan_rides_store_scan(tmp_path):
    """manhattan compressed search rides the bf16 rescore-store scan (the
    old 131-QPS LUT gather path is gone for it)."""
    rng = np.random.default_rng(5)
    base = rng.standard_normal((600, 32)).astype(np.float32)
    cfg = vi.HnswUserConfig.from_dict(
        {"distance": "manhattan",
         "pq": {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 32}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / "man"), persist=False)
    idx.add_batch(np.arange(600), base)
    idx.flush()
    assert idx.compressed
    ids, dists = idx.search_by_vectors(base[:8], 3)
    np.testing.assert_array_equal(ids[:, 0], np.arange(8, dtype=np.uint64))
    d = np.abs(base[:8, None, :] - base[None, :, :]).sum(-1)
    want = np.argsort(d, axis=1)[:, :3]
    for i in range(8):
        assert len(set(ids[i].tolist()) & set(want[i].tolist())) >= 2


def test_pq_hamming_rejected(tmp_path):
    """hamming + kmeans-PQ has no meaningful ADC (mean centroids fail every
    exact-equality test) — compress must refuse, not mis-rank."""
    cfg = vi.HnswUserConfig.from_dict(
        {"distance": "hamming",
         "pq": {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 32}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / "ham"), persist=False)
    rng = np.random.default_rng(5)
    idx.add_batch(np.arange(600), rng.integers(0, 4, (600, 32)).astype(np.float32))
    ids, _ = idx.search_by_vectors(
        rng.integers(0, 4, (8, 32)).astype(np.float32), 3)
    # declarative trigger auto-disables (invalid-config path) and the
    # uncompressed hamming scan keeps serving
    assert not idx.compressed and not idx.config.pq.enabled
    assert ids.shape == (8, 3)


def test_pq_async_dispatch_matches_sync(tmp_path, data):
    """The async serving dispatch pipelines PQ-with-rescore (bf16 store
    scan) instead of degrading to a blocking search; results match sync."""
    cfg = _cfg(enabled=True, segments=8, centroids=64)
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(1000), data[:1000])
    idx.flush()
    assert idx.compressed
    q = data[:32]
    fin = idx.search_by_vectors_async(q, 5)
    ids_a, d_a = fin()
    ids_s, d_s = idx.search_by_vectors(q, 5)
    np.testing.assert_array_equal(ids_a, ids_s)
    np.testing.assert_allclose(d_a, d_s, rtol=1e-5)
    # codes-only tier still answers (synchronously) through the same API
    cfg2 = _cfg(enabled=True, segments=8, centroids=64, rescore=False)
    idx2 = TpuVectorIndex(cfg2, str(tmp_path / "s2"), persist=False)
    idx2.add_batch(np.arange(1000), data[:1000])
    idx2.flush()
    assert idx2.compressed and idx2._rescore_dev is None
    fin2 = idx2.search_by_vectors_async(q, 5)
    ids2, _ = fin2()
    assert ids2.shape == (32, 5)


def test_persisted_rejected_pq_serves_uncompressed(tmp_path, data):
    """A pq.npz this build refuses (e.g. a hamming codebook persisted by an
    older build) must not make the shard unloadable — restore logs a warning
    and serves uncompressed."""
    path = str(tmp_path / "shard")
    cfg = vi.HnswUserConfig.from_dict({"distance": "hamming"}, "hnsw_tpu")
    rng = np.random.default_rng(2)
    base = rng.integers(0, 4, (300, 32)).astype(np.float32)
    idx = TpuVectorIndex(cfg, path)
    idx.add_batch(np.arange(300), base)
    idx.flush()
    idx.shutdown()
    import os

    np.savez(os.path.join(path, "pq"), codebook=np.zeros((8, 32, 4), np.float32),
             dim=32, segments=8, centroids=32, metric="hamming",
             encoder="kmeans", distribution="log-normal")
    idx2 = TpuVectorIndex(cfg, path)
    assert not idx2.compressed and idx2.n == 300
    ids, _ = idx2.search_by_vector(base[5], 3)
    assert ids[0] == 5
    idx2.shutdown()


def test_pq_declared_invalid_auto_disables(tmp_path, data):
    """pq declared at class creation with segments that turn out not to
    divide dims (unknowable before the first import) auto-disables with a
    warning at the compression threshold instead of erroring every
    subsequent add/search."""
    cfg = _cfg(enabled=True, segments=7, centroids=64)  # 7 ∤ 32
    idx = TpuVectorIndex(cfg, str(tmp_path / "s"), persist=False)
    idx.add_batch(np.arange(512), data[:512])  # crosses the 256 threshold
    ids, _ = idx.search_by_vector(data[0], 5)  # search flushes -> triggers
    assert ids[0] == 0
    assert not idx.config.pq.enabled and not idx.compressed
    idx.add_batch(np.arange(512, 560), data[512:560])
    ids, _ = idx.search_by_vector(data[1], 5)
    assert ids[0] == 1


def test_compressed_large_k(tmp_path, rng):
    """Regression: k larger than the per-chunk candidate quota must widen
    the pool instead of crashing the final top_k."""
    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.index.tpu import TpuVectorIndex

    cfg = vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared",
         "pq": {"enabled": False, "segments": 8, "centroids": 64}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path), persist=False)
    data = rng.standard_normal((2000, 16)).astype(np.float32)
    idx.add_batch(np.arange(2000), data)
    idx.compress()
    ids, dists = idx.search_by_vectors(data[:4], 300)
    assert ids.shape[1] == 300
    assert ids[0][0] == 0 and dists[0][0] < 1.0


def test_rescore_false_warns_at_config_time(caplog):
    """pq.rescore=false is a measured 4x recall drop (codes-only recall@10
    0.24 vs 0.99 rescored) — the config parse must say so loudly while
    still accepting the opt-in (VERDICT r4 item 6). Rate-limited: a fleet
    restart parses one config per shard, and one warning per minute says
    everything N copies would."""
    import logging

    from weaviate_tpu.entities import vectorindex as vi_mod

    vi_mod._rescore_warn_last[0] = 0.0  # reset the process-wide rate limit
    with caplog.at_level(logging.WARNING, logger="weaviate_tpu.entities.vectorindex"):
        cfg = _cfg(enabled=True, segments=8, rescore=False)
    assert cfg.pq.rescore is False  # still legal — a warning, not an error
    assert any("rescore" in r.message and "recall" in r.message
               for r in caplog.records)

    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="weaviate_tpu.entities.vectorindex"):
        # within the rate-limit window: a second rescore=False parse is quiet
        _cfg(enabled=True, segments=8, rescore=False)
        _cfg(enabled=True, segments=8, rescore=True)
        _cfg(enabled=False, rescore=False)  # pq off: nothing to warn about
    assert not [r for r in caplog.records if "rescore" in r.message]
