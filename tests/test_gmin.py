"""Fused group-min fast-scan kernel (ops/gmin_scan.py) vs the legacy
lax.scan kernel and exact numpy ground truth — interpret mode on the CPU
mesh (the compiled Mosaic path is exercised on real TPU by chip_smoke.py
and the benchmark's sift cell)."""

import numpy as np
import pytest

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.storage.bitmap import Bitmap


def _mk_index(tmp_path, metric, n=600, d=32, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    cfg = vi.HnswUserConfig.from_dict({"distance": metric}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path / metric), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs, rng


def _planned(idx, b, k):
    """The plan of a dispatch of `b` queries at depth `k` (index/plan.py)."""
    from weaviate_tpu.index.plan import plan_search

    snap = idx._read_snapshot()[0]
    return plan_search(idx._plan_view(snap), b, idx.padded_width(b),
                       min(k, snap.live))


def _exact(vecs, q, k, metric):
    if metric == vi.DISTANCE_L2:
        d = ((q[:, None, :] - vecs[None, :, :]) ** 2).sum(-1)
    elif metric == vi.DISTANCE_DOT:
        d = -(q @ vecs.T)
    else:
        qn = q / np.linalg.norm(q, axis=1, keepdims=True)
        d = 1.0 - qn @ vecs.T
    return np.argsort(d, axis=1, kind="stable")[:, :k], np.sort(d, axis=1)[:, :k]


@pytest.mark.parametrize("metric", [vi.DISTANCE_L2, vi.DISTANCE_DOT, vi.DISTANCE_COSINE])
def test_gmin_matches_exact(tmp_path, metric):
    idx, vecs, rng = _mk_index(tmp_path, metric)
    q = rng.standard_normal((16, vecs.shape[1])).astype(np.float32)
    assert _planned(idx, 16, 10).program == "gmin"
    ids, dists = idx.search_by_vectors(q, 10)
    assert not idx._gmin_broken  # the fused path actually ran
    gt_ids, gt_d = _exact(vecs, q, 10, metric)
    for i in range(len(q)):
        assert set(ids[i].tolist()) == set(gt_ids[i].tolist())
    np.testing.assert_allclose(dists, gt_d, rtol=1e-3, atol=1e-3)


def test_gmin_tombstones_and_filter(tmp_path):
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2)
    n = len(vecs)
    # tombstone the even docs
    for doc in range(0, 40, 2):
        idx.delete(doc)
    idx.flush()
    q = vecs[:16] + 0.01 * rng.standard_normal((16, vecs.shape[1])).astype(np.float32)
    # allowList: docs 0..99 only -> live allowed = odd docs < 40 + 40..99
    allow = Bitmap(range(100))
    idx.config.flat_search_cutoff = 0  # force the masked full-scan path
    ids, _ = idx.search_by_vectors(q, 5, allow_list=allow)
    assert not idx._gmin_broken
    flat = ids.ravel()
    flat = flat[flat != np.uint64(0xFFFFFFFFFFFFFFFF)]
    assert all(int(x) < 100 for x in flat)
    assert all(int(x) % 2 == 1 or int(x) >= 40 for x in flat)
    # query i's nearest live allowed doc is itself (odd/40+) or its
    # neighborhood; exact check against numpy over the allowed live set
    live_allowed = np.array([d for d in range(100) if not (d < 40 and d % 2 == 0)])
    dd = ((q[:, None, :] - vecs[live_allowed][None, :, :]) ** 2).sum(-1)
    want = live_allowed[np.argsort(dd, axis=1)[:, :5]]
    for i in range(len(q)):
        assert set(int(x) for x in ids[i]) == set(int(x) for x in want[i])


def test_gmin_small_batch_uses_legacy(tmp_path):
    idx, vecs, _ = _mk_index(tmp_path, vi.DISTANCE_L2, n=50)
    assert _planned(idx, 4, 10).program == "scan"  # b < 8 -> legacy
    ids, _ = idx.search_by_vectors(vecs[:2], 3)
    assert ids.shape == (2, 3)


def test_gmin_async_path(tmp_path):
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2)
    q = vecs[:32] + 0.001 * rng.standard_normal((32, vecs.shape[1])).astype(np.float32)
    fin = idx.search_by_vectors_async(q, 1)
    ids, _ = fin()
    assert not idx._gmin_broken
    np.testing.assert_array_equal(ids.ravel(), np.arange(32, dtype=np.uint64))


def test_gmin_per_shape_fallback(tmp_path, monkeypatch):
    """A Mosaic rejection on one compiled shape falls back to the legacy
    kernel for THAT shape only; other shapes keep the fused path. Only
    repeated distinct-shape failures with zero successes disable the path
    (a restart may make an oversized batch the first-ever query)."""
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2)
    real = idx._search_full_gmin

    def failing(snap, q, kk, allow_words, *a, **k):
        if q.shape[0] >= 64:  # "over VMEM budget" for big batches
            raise RuntimeError("Mosaic: scoped vmem limit exceeded")
        return real(snap, q, kk, allow_words, *a, **k)

    monkeypatch.setattr(idx, "_search_full_gmin", failing)
    big = rng.standard_normal((64, vecs.shape[1])).astype(np.float32)
    ids, _ = idx.search_by_vectors(big, 5)  # first-ever query fails
    assert ids.shape == (64, 5)
    assert not idx._gmin_broken and len(idx._gmin_shape_broken) == 1
    # a small shape still compiles and validates the fused path
    ids, _ = idx.search_by_vectors(big[:16], 5)
    assert idx._gmin_validated and not idx._gmin_broken
    # the broken shape stays on the legacy kernel without re-raising
    ids, _ = idx.search_by_vectors(big, 5)
    assert ids.shape == (64, 5) and len(idx._gmin_shape_broken) == 1


def test_gmin_disables_after_repeated_distinct_failures(tmp_path, monkeypatch):
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2)
    monkeypatch.setattr(
        idx, "_search_full_gmin",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("platform broken")))
    q = rng.standard_normal((16, vecs.shape[1])).astype(np.float32)
    for k in (3, 5, 7):  # three distinct compiled shapes all fail
        ids, _ = idx.search_by_vectors(q, k)
        assert ids.shape == (16, k)  # legacy kernel answered
    assert idx._gmin_broken and not idx._gmin_validated


def test_vmem_tile_plan():
    """plan_tiles keeps every shape under the 12 MB budget by shrinking the
    store tile (then the query tile); fits_vmem refuses only when even the
    smallest tiling is over (a kernel over Mosaic's scoped-VMEM limit is a
    compile error — this is the gate that keeps one from reaching it)."""
    from weaviate_tpu.ops import gmin_scan as gs

    # SIFT-shaped: full 512x512 tiles fit
    qb, scg, fp = gs.plan_tiles(16384, 128, 65536, 16, 4)
    assert (qb, scg) == (512, 512) and fp <= gs._VMEM_BUDGET
    # d=768 with a full slab: the f32 store block alone (16*128*768*4 =
    # 6.3 MB, double-buffered) is over budget even at the smallest tiling —
    # the index must fall back to the legacy scan rather than compile it...
    assert not gs.fits_vmem(4096, 768, 4096, 16, 4)
    # ...but the bf16 rescore store (PQ serving) fits at a shrunk tile
    qb2, scg2, fp2 = gs.plan_tiles(4096, 768, 4096, 16, 2)
    assert scg2 < 512 and fp2 <= gs._VMEM_BUDGET
    assert gs.fits_vmem(4096, 768, 4096, 16, 2)
    # and a part-full slab (active_g=4) fits even at f32
    assert gs.fits_vmem(4096, 768, 4096, 4, 4)
    # absurdly wide vectors: refuse instead of compiling a wedge
    assert not gs.fits_vmem(512, 65536, 1024, 16, 4)
    # every plan is a power-of-two divisor of the padded dims
    for d in (32, 128, 256, 512, 1024, 2048):
        qb, scg, fp = gs.plan_tiles(1024, d, 1024, 16, 4)
        assert 1024 % qb == 0 and 1024 % scg == 0
        assert scg >= 128 and qb >= 64  # lane-width / sublane floors hold


def test_gmin_wide_vectors_adaptive_tiles(tmp_path, monkeypatch):
    """d=768 forces a reduced store tile; the kernel must stay correct
    (interpret mode) at the adapted tiling. Since PR 40 the index runs the
    lax.scan program at this width (gmin_scan.kernel_serves: the kernel is
    the slower one there), so the test hands the kernel every shape that
    compiles, as the index did before."""
    from weaviate_tpu.ops import gmin_scan as gs

    monkeypatch.setattr(gs, "kernel_serves", gs.fits_vmem)
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2, n=700, d=768)
    q = vecs[:16] + 0.001 * rng.standard_normal((16, 768)).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    assert idx._gmin_validated and not idx._gmin_broken
    np.testing.assert_array_equal(ids[:, 0], np.arange(16, dtype=np.uint64))


def test_gmin_uneven_rescore_block(tmp_path):
    """b=3072 (a 1024-multiple bucket NOT divisible by the 2048 rescore
    block) exercises the ceil-split + pad path."""
    idx, vecs, rng = _mk_index(tmp_path, vi.DISTANCE_L2, n=400, d=16)
    q = np.repeat(vecs[:25], 84, axis=0)  # 2100 queries -> bucket 3072
    assert len(q) == 2100
    ids, dists = idx.search_by_vectors(q, 1)
    assert not idx._gmin_broken
    want = np.repeat(np.arange(25, dtype=np.uint64), 84)
    np.testing.assert_array_equal(ids.ravel(), want)
    np.testing.assert_allclose(dists.ravel(), 0.0, atol=1e-4)


def test_gmin_block_rescore_equals_strided(tmp_path):
    """The [ncols, G*D] block-gather rescore (round-5 gather fix: rg
    contiguous slices per query instead of rg*G scattered rows) must be
    bit-identical to the strided-take path it replaces."""
    import jax.numpy as jnp

    from weaviate_tpu.ops import gmin_scan

    rng = np.random.default_rng(3)
    n, d, b, k = 700, 32, 64, 10
    cap = 16384
    store = np.zeros((cap, d), np.float32)
    store[:n] = rng.standard_normal((n, d)).astype(np.float32)
    sq = jnp.asarray((store.astype(np.float64) ** 2).sum(1).astype(np.float32))
    store_j = jnp.asarray(store)
    tombs = np.zeros(cap, bool)
    tombs[5:50:7] = True  # some tombstones
    q = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    words = jnp.zeros((cap // 32,), jnp.uint32)
    args = (store_j, sq, jnp.asarray(tombs), n, q, words, False,
            k, "l2-squared", 8, 1, True)
    d0, i0 = gmin_scan.gmin_topk(*args)
    blk = gmin_scan.build_rescore_blocks(store_j)
    d1, i1 = gmin_scan.gmin_topk(*args, blk)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))


def test_pq_gmin_block_rescore_equals_strided(tmp_path):
    """Codes twin of the block-rescore equivalence check."""
    import jax.numpy as jnp

    from weaviate_tpu.compress.pq import ProductQuantizer
    from weaviate_tpu.ops import pq_gmin

    rng = np.random.default_rng(4)
    n, d, b, k = 900, 32, 64, 10
    cap = 16384
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    pq = ProductQuantizer(dim=d, segments=8, centroids=16, metric="l2-squared")
    pq.fit(vecs)
    codes = np.zeros((cap, 8), np.uint8)
    codes[:n] = pq.encode(vecs)
    recon = pq.decode(codes[:n])
    rn = np.zeros(cap, np.float32)
    rn[:n] = (recon.astype(np.float64) ** 2).sum(1).astype(np.float32)
    cb_chunks = jnp.asarray(
        pq_gmin.build_cb_chunks(pq.codebook, 8), jnp.bfloat16)
    flat_cb = jnp.asarray(pq.codebook.reshape(-1, pq.codebook.shape[2]))
    codes_j = jnp.asarray(codes)
    q = jnp.asarray(rng.standard_normal((b, d)), jnp.float32)
    words = jnp.zeros((cap // 32,), jnp.uint32)
    args = (codes_j, jnp.asarray(rn), jnp.zeros((cap,), bool), n, q,
            cb_chunks, flat_cb, words, False, k, "l2-squared", 8, 1, True,
            None)
    d0, i0 = pq_gmin.pq_gmin_topk(*args)
    blk = pq_gmin.build_codes_blocks(codes_j)
    d1, i1 = pq_gmin.pq_gmin_topk(*args, blk)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
