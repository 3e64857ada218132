"""Fused PQ-ADC group-min kernel (ops/pq_gmin.py) vs the legacy
reconstruction scan and exact-ADC numpy ground truth — interpret mode on
the CPU mesh (the compiled Mosaic path is exercised on real TPU by
chip_smoke.py, same contract as the dense kernel's tests)."""

import numpy as np
import pytest

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.ops import pq_gmin
from weaviate_tpu.storage.bitmap import Bitmap


def _mk_pq_index(tmp_path, metric=vi.DISTANCE_L2, n=2000, d=32, segments=8,
                 centroids=32, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    cfg = vi.HnswUserConfig.from_dict(
        {"distance": metric,
         "pq": {"enabled": True, "trainingLimit": 256, "segments": segments,
                "centroids": centroids, "rescore": False}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / "pqg"), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    assert idx.compressed and idx._rescore_dev is None
    return idx, vecs, rng


def _exact_adc(idx, q, k, metric):
    """Ground truth from the actual reconstructions: ADC distance order."""
    codes = np.asarray(idx._codes[: idx.n])
    recon = idx._pq.decode(codes)
    if metric == vi.DISTANCE_L2:
        d = ((q[:, None, :] - recon[None, :, :]) ** 2).sum(-1)
    elif metric == vi.DISTANCE_DOT:
        d = -(q @ recon.T)
    else:
        d = 1.0 - q @ recon.T
    return d


@pytest.mark.parametrize("metric", [vi.DISTANCE_L2, vi.DISTANCE_DOT,
                                    vi.DISTANCE_COSINE])
def test_pq_gmin_matches_exact_adc(tmp_path, metric):
    idx, vecs, rng = _mk_pq_index(tmp_path, metric)
    q = rng.standard_normal((16, vecs.shape[1])).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    ids, dists = idx.search_by_vectors(q, 5)
    # the fused kernel actually served (validated shape, separate domain)
    assert idx._pqg_state._gmin_validated and not idx._pqg_state._gmin_broken
    d = _exact_adc(idx, q, 5, metric)
    want_ids = np.argsort(d, axis=1, kind="stable")[:, :5]
    want_d = np.sort(d, axis=1)[:, :5]
    for i in range(len(q)):
        # ADC ties are common at coarse codebooks: compare distances and
        # demand heavy id overlap
        np.testing.assert_allclose(dists[i], want_d[i], rtol=1e-2, atol=1e-2)
        assert len(set(int(x) for x in ids[i]) &
                   set(int(x) for x in want_ids[i])) >= 4


def test_pq_gmin_matches_legacy_recon_path(tmp_path):
    """The fused kernel and the legacy reconstruction scan are two
    implementations of the same ADC tier: same winners on the same index."""
    idx, vecs, rng = _mk_pq_index(tmp_path, n=3000)
    q = vecs[:12] + 0.01 * rng.standard_normal((12, vecs.shape[1])).astype(np.float32)
    ids_fused, d_fused = idx.search_by_vectors(q, 5)
    assert idx._pqg_state._gmin_validated
    idx._pqg_state._gmin_broken = True  # force the legacy path
    ids_legacy, d_legacy = idx.search_by_vectors(q, 5)
    idx._pqg_state._gmin_broken = False
    for i in range(len(q)):
        assert set(int(x) for x in ids_fused[i]) == set(int(x) for x in ids_legacy[i]), i
        np.testing.assert_allclose(np.sort(d_fused[i]), np.sort(d_legacy[i]),
                                   rtol=1e-2, atol=1e-2)


def test_pq_gmin_tombstones_and_filter(tmp_path):
    idx, vecs, rng = _mk_pq_index(tmp_path, n=2000)
    for doc in range(0, 40, 2):
        idx.delete(doc)
    idx.flush()
    q = vecs[:16] + 0.005 * rng.standard_normal((16, vecs.shape[1])).astype(np.float32)
    idx.config.flat_search_cutoff = 0  # force the masked full-scan path
    allow = Bitmap(np.arange(200).astype(np.uint64))
    ids, _ = idx.search_by_vectors(q, 5, allow_list=allow)
    assert idx._pqg_state._gmin_validated
    sentinel = np.uint64(0xFFFFFFFFFFFFFFFF)
    flat = ids.ravel()
    flat = flat[flat != sentinel]
    assert all(int(x) < 200 for x in flat)
    assert all(int(x) % 2 == 1 or int(x) >= 40 for x in flat)


def test_pq_gmin_small_batch_uses_legacy(tmp_path):
    idx, vecs, _ = _mk_pq_index(tmp_path, n=1500)
    ids, _ = idx.search_by_vectors(vecs[:2], 3)  # b < 8
    assert not idx._pqg_state._gmin_validated
    assert ids.shape == (2, 3)


def test_pq_gmin_large_centroids_uses_legacy(tmp_path):
    """uint16 codebooks (centroids > 256) stay on the recon scan."""
    idx, vecs, rng = _mk_pq_index(tmp_path, n=1500, centroids=300)
    q = vecs[:16]
    ids, _ = idx.search_by_vectors(q, 3)
    assert not idx._pqg_state._gmin_validated
    assert ids.shape[0] == 16


def test_pq_gmin_failure_separate_from_dense(tmp_path, monkeypatch):
    """A failing PQ kernel must not disable the dense gmin path (separate
    failure domains)."""
    idx, vecs, rng = _mk_pq_index(tmp_path, n=1500)

    def boom(*a, **k):
        raise RuntimeError("mosaic says no")

    monkeypatch.setattr(pq_gmin, "search_pq_gmin_fused", boom)
    q = vecs[:16]
    ids, _ = idx.search_by_vectors(q, 3)  # falls back, still answers
    assert ids.shape[0] == 16
    assert idx._pqg_state._gmin_shape_broken
    assert not idx._gmin_broken and not idx._gmin_shape_broken


def test_cb_chunks_roundtrip():
    """build_cb_chunks block-diagonal layout reconstructs exactly."""
    rng = np.random.default_rng(3)
    m, c, ds = 12, 16, 4  # m % mseg != 0 exercises the ragged tail
    cb = rng.standard_normal((m, c, ds)).astype(np.float32)
    mseg = min(pq_gmin._MSEG, m)
    chunks = pq_gmin.build_cb_chunks(cb, mseg)
    codes = rng.integers(0, c, (20, m))
    want = np.concatenate([cb[s, codes[:, s]] for s in range(m)], axis=1)
    nchunks = chunks.shape[0]
    pad = nchunks * mseg - m
    codes_p = np.pad(codes, ((0, 0), (0, pad)))
    got = np.zeros((20, m * ds), np.float32)
    for t in range(nchunks):
        oh = np.zeros((20, mseg * c), np.float32)
        for s in range(mseg):
            oh[np.arange(20), s * c + codes_p[:, t * mseg + s]] = 1.0
        got += oh @ chunks[t]
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_plan_tiles_pq_respects_budget():
    from weaviate_tpu.ops.gmin_scan import _VMEM_BUDGET

    # SIFT1M serving shape
    qb, scg, mseg, fp = pq_gmin.plan_tiles_pq(16384, 128, 65536, 16, 32, 256)
    assert fp <= _VMEM_BUDGET and qb >= 64 and scg >= 64
    # pathologically wide vectors must still plan under budget or shrink
    qb2, scg2, _, fp2 = pq_gmin.plan_tiles_pq(512, 2048, 4096, 16, 512, 256)
    assert qb2 >= 64 and scg2 >= 64
