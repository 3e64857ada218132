"""OPQ rotation (TPU extension): learned orthogonal rotation before product
quantization (OPQ-NP, Ge et al. 2013). The reference's PQ segments the raw
dims; on correlated data that concentrates variance in few segments and
raw-ADC recall collapses. The rotation decorrelates segments — fitted once,
persisted with the codebook, applied to queries as one tiny device matmul
inside the jitted ADC paths."""

import numpy as np
import pytest

from weaviate_tpu.compress.pq import ProductQuantizer
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.tpu import TpuVectorIndex

DIM = 32


def correlated_data(n=4000, dim=DIM, latent=6, seed=0):
    """Strongly cross-segment-correlated vectors: a low-rank mix + noise —
    the case plain dim-order segmentation quantizes worst."""
    rng = np.random.default_rng(seed)
    z = rng.standard_normal((n, latent)).astype(np.float32)
    mix = rng.standard_normal((latent, dim)).astype(np.float32)
    return z @ mix + 0.05 * rng.standard_normal((n, dim)).astype(np.float32)


def test_opq_rotation_orthogonal_and_persistent(tmp_path):
    data = correlated_data()
    pq = ProductQuantizer(DIM, 8, 16, vi.DISTANCE_L2,
                          rotation=vi.PQ_ROTATION_OPQ)
    pq.fit(data)
    r = pq.rotation_matrix
    assert r is not None and r.shape == (DIM, DIM)
    np.testing.assert_allclose(r @ r.T, np.eye(DIM), atol=1e-4)
    # encode/decode round-trip happens in the original space
    codes = pq.encode(data[:64])
    recon = pq.decode(codes)
    assert recon.shape == (64, DIM)
    # persistence carries the rotation; reload encodes identically
    p = str(tmp_path / "opq.npz")
    pq.save(p)
    pq2 = ProductQuantizer.load(p)
    assert pq2.rotation == vi.PQ_ROTATION_OPQ
    np.testing.assert_allclose(pq2.rotation_matrix, r, atol=1e-6)
    np.testing.assert_array_equal(pq2.encode(data[:64]), codes)


def test_opq_reduces_quantization_error():
    data = correlated_data(seed=3)
    plain = ProductQuantizer(DIM, 8, 16, vi.DISTANCE_L2)
    plain.fit(data)
    opq = ProductQuantizer(DIM, 8, 16, vi.DISTANCE_L2,
                           rotation=vi.PQ_ROTATION_OPQ)
    opq.fit(data)
    err_plain = np.mean((data - plain.decode(plain.encode(data))) ** 2)
    err_opq = np.mean((data - opq.decode(opq.encode(data))) ** 2)
    # the rotation exists to shrink exactly this; demand a real margin
    assert err_opq < 0.9 * err_plain, (err_opq, err_plain)


def test_opq_validation():
    with pytest.raises(vi.ConfigValidationError):
        ProductQuantizer(DIM, 8, 16, vi.DISTANCE_MANHATTAN,
                         rotation=vi.PQ_ROTATION_OPQ)
    with pytest.raises(vi.ConfigValidationError):
        ProductQuantizer(DIM, DIM, 16, vi.DISTANCE_L2,
                         encoder=vi.PQ_ENCODER_TILE,
                         rotation=vi.PQ_ROTATION_OPQ)
    with pytest.raises(vi.ConfigValidationError):
        ProductQuantizer(DIM, 8, 16, vi.DISTANCE_L2, rotation="spin")


def _codes_only_recall(tmp_path, name, rotation, data, queries):
    cfg = vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared",
         "pq": {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 16,
                "rescore": False, "rotation": rotation}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    assert idx.compressed
    ids, _ = idx.search_by_vectors(queries, 10)
    assert idx._pqg_state._gmin_validated  # fused kernel served
    d = ((queries[:, None, :] - data[None, :, :]) ** 2).sum(-1)
    want = np.argsort(d, axis=1)[:, :10]
    hits = sum(len(set(ids[i].tolist()) & set(want[i].tolist()))
               for i in range(len(queries)))
    idx.drop()
    return hits / (len(queries) * 10)


def test_opq_codes_only_recall_beats_plain(tmp_path, rng):
    """End to end through the fused codes kernel: OPQ must beat plain PQ
    recall on correlated data (the whole point of the rotation)."""
    data = correlated_data(seed=7)
    queries = data[:16] + 0.01 * rng.standard_normal((16, DIM)).astype(np.float32)
    rec_plain = _codes_only_recall(tmp_path, "plain", "none", data, queries)
    rec_opq = _codes_only_recall(tmp_path, "opq", "opq", data, queries)
    assert rec_opq >= rec_plain, (rec_opq, rec_plain)
    assert rec_opq >= 0.5, rec_opq


def test_opq_restart_serves_from_persisted_rotation(tmp_path, rng):
    data = correlated_data(seed=11, n=1500)
    cfg = vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared",
         "pq": {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 16,
                "rescore": False, "rotation": "opq"}}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / "r"), persist=True)
    idx.add_batch(np.arange(len(data)), data)
    idx.flush()
    q = data[:8]
    ids_ref, d_ref = idx.search_by_vectors(q, 3)
    idx.shutdown()

    idx2 = TpuVectorIndex(cfg, str(tmp_path / "r"), persist=True)
    idx2.post_startup()
    assert idx2.compressed and idx2._pq.rotation_matrix is not None
    ids2, d2 = idx2.search_by_vectors(q, 3)
    np.testing.assert_array_equal(ids2, ids_ref)
    np.testing.assert_allclose(d2, d_ref, rtol=1e-3, atol=1e-3)
    idx2.drop()


def test_opq_mesh_codes_only(tmp_path, rng):
    """The mesh codes kernel applies the same rotation per shard."""
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config
    from weaviate_tpu.index.mesh import MeshVectorIndex

    data = correlated_data(seed=13, n=2000, dim=16)
    config = parse_and_validate_config(
        "hnsw_tpu_mesh", {"distance": "l2-squared"})
    idx = MeshVectorIndex(config, str(tmp_path / "m"),
                          initial_capacity_per_shard=1024)
    idx.add_batch(np.arange(len(data)), data)
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared",
         "pq": {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 16,
                "rescore": False, "rotation": "opq"}}))
    assert idx.compressed and idx._pq.rotation_matrix is not None
    q = data[:8] + 0.001 * rng.standard_normal((8, 16)).astype(np.float32)
    ids, d = idx.search_by_vectors(q, 3)
    assert idx._pqg_state._gmin_validated
    for i in range(8):
        assert int(ids[i][0]) == i, (i, ids[i])
