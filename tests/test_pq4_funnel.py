"""4-bit Quick-ADC scan plane + three-stage re-ranking funnel
(ops/pq4.py, pq.bits=4 in index/tpu.py and index/mesh.py).

Pins the funnel PR's contracts:

1. FUNNEL == EXACT when the budgets cover the candidate set (rc >= n):
   stage 3 reports exact distances, so on tie-free integer data the
   funnel's answer equals the exact scan's — per tier (full store, IVF,
   mesh), sync == async.
2. The OPQ rotation is a real rotation (orthonormal round-trip), it
   lowers quantization error on correlated data, and the 4-bit ladder is
   fit in the SAME rotated space as the 8-bit one (pinned matrix).
3. Snapshot pinning: a dispatch enqueued before re-compress/compact
   answers from the OLD generation's arrays.
4. Composition: the funnel serves under IVF probing, filters,
   tombstones, and the mesh's per-device scan.
5. Disabled mode (bits=8) is zero-hop: no funnel entry point runs.
6. The satellites: pack/unpack layout, byte-LUT math, VMEM tile
   planning, plan_funnel floors, controller funnel-budget ladder,
   costmodel stage attribution, perf tier tallies, memory-ledger
   components, health()["pq"]["funnel"], graftlint frozensets.
"""

import numpy as np
import pytest

from weaviate_tpu.compress.pq import pack_codes4, unpack_codes4
from weaviate_tpu.config.config import (
    PQ4_FUNNEL_C_BUCKETS,
    PQ4_FUNNEL_RESCORE_BUCKETS,
    IvfConfig,
)
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import tpu
from weaviate_tpu.index.plan import funnel_budgets
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import costmodel, memory, perf, quality, tracing
from weaviate_tpu.ops import pq4 as pq4_ops
from weaviate_tpu.serving import controller
from weaviate_tpu.serving.controller import (
    KNOB_FUNNEL_C,
    KNOB_FUNNEL_RESCORE,
    ControlPlane,
)
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16
SENTINEL = np.uint64(0xFFFFFFFFFFFFFFFF)


@pytest.fixture(autouse=True)
def _reset_globals():
    saved = controller._plane
    controller._plane = None
    yield
    controller._plane = saved
    tpu.set_ivf_config(None)
    tracing.configure(None)
    perf.configure(None)
    memory.configure(None)


PQ4 = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 32, "bits": 4,
       "rescore": True, "rotation": "opq"}


def _mk_index(tmp_path, n=256, seed=0, name="f4", pq=PQ4, **cfg_extra):
    """Small-integer vectors: every L2 distance is exact integer
    arithmetic in f32/bf16 regardless of accumulation order, so
    funnel-vs-exact equality checks are exact (the fused-dispatch test
    convention). n <= 256 keeps rc (top rescore bucket) >= live rows: the
    funnel budgets cover everything and stage 3 IS the exact answer.
    n == 256 is also the declarative-compress threshold floor."""
    rng = np.random.default_rng(seed)
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    # exactTopK: stage-1 keeps are lax.top_k, so with budgets >= live rows
    # the funnel is a complete scan (approx_min_k recall is the
    # benchmark's domain, not an equality pin's)
    d = {"distance": "l2-squared", "exactTopK": True, **cfg_extra}
    if pq is not None:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu", d)
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    if pq is not None and pq.get("bits") == 4:
        assert idx.compressed and idx._codes4 is not None
        assert idx._pq4 is not None and idx._pq4.centroids == 16
    return idx, vecs


def _brute(vecs, q, k):
    d = ((vecs - q) ** 2).sum(1)
    order = np.argsort(d, kind="stable")[:k]
    return order, d[order]


# -- 1. funnel == exact when the budgets cover the set ------------------------


@pytest.mark.parametrize("lane", ["sync", "async"])
def test_funnel_matches_exact_fused_legacy_sync_async(tmp_path, lane):
    idx, vecs = _mk_index(tmp_path)
    q = (vecs[:12] + 0.25).astype(np.float32)
    if lane == "sync":
        ids, dists = idx.search_by_vectors(q, 5)
    else:
        ids, dists = idx.search_by_vectors_async(q, 5)()
    for i in range(len(q)):
        want_ids, want_d = _brute(vecs, q[i], 5)
        np.testing.assert_allclose(dists[i], want_d, rtol=0, atol=1e-4,
                                   err_msg=f"{lane} q{i}")
        assert {int(x) for x in ids[i]} == {int(x) for x in want_ids}, \
            (lane, i)
    # the lanes bit-agree (same program, same snapshot)
    ref_ids, ref_d = idx.search_by_vectors(q, 5)
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(dists, ref_d)


def test_funnel_dispatches_on_the_pq_adc4_tier(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    assert idx.dispatch_tier(idx._read_snapshot()[0]) == costmodel.TIER_PQ_ADC4
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    win = perf.configure(perf.PerfWindow(window_s=60.0))
    handle = idx.search_by_vectors_async(vecs[:8] + 0.25, 5)
    handle()
    shape = handle.shape
    assert shape is not None and shape.tier == costmodel.TIER_PQ_ADC4
    assert shape.bytes_per_row == idx._pq4.segments // 2
    assert shape.extra["funnel_c"] >= shape.extra["funnel_rescore"] >= 5
    win.record_dispatch(shape, rows=8)
    assert win.summary()["tiers"].get(costmodel.TIER_PQ_ADC4) == 1


def test_funnel_respects_filters_and_tombstones(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    for doc in range(0, 40, 2):
        idx.delete(doc)
    idx.flush()
    idx.config.flat_search_cutoff = 0  # stay on the masked full-scan path
    allow = Bitmap(np.arange(120).astype(np.uint64))
    ids, _ = idx.search_by_vectors(vecs[:12] + 0.25, 5, allow_list=allow)
    flat = ids.ravel()
    flat = flat[flat != SENTINEL]
    assert all(int(x) < 120 for x in flat)
    assert all(int(x) % 2 == 1 or int(x) >= 40 for x in flat)


def test_funnel_composes_with_ivf_probe(tmp_path):
    """top_p = all partitions + budgets >= n: the probed funnel equals
    the exact answer; a real filter composes through the probe."""
    tpu.set_ivf_config(IvfConfig(enabled=True, nlist=8, min_n=64, top_p=8,
                                 train_sample=4096, train_iters=4))
    idx, vecs = _mk_index(tmp_path, name="ivf4")
    assert idx._ivf_centroids is not None  # trained at import
    q = (vecs[:10] + 0.25).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    for i in range(len(q)):
        want_ids, want_d = _brute(vecs, q[i], 5)
        np.testing.assert_allclose(dists[i], want_d, rtol=0, atol=1e-4)
        assert {int(x) for x in ids[i]} == {int(x) for x in want_ids}
    allow = Bitmap(np.arange(100, 200).astype(np.uint64))
    ids_f, _ = idx.search_by_vectors(q, 5, allow_list=allow)
    flat = ids_f.ravel()
    flat = flat[flat != SENTINEL]
    assert flat.size and all(100 <= int(x) < 200 for x in flat)


def test_funnel_snapshot_pins_across_recompress_and_compact(tmp_path):
    """Enqueue -> delete winners + compact (which re-encodes BOTH
    ladders) -> finalize answers from the OLD snapshot's codes4/opq."""
    idx, vecs = _mk_index(tmp_path)
    q = (vecs[:4] + 0.25).astype(np.float32)
    want = idx.search_by_vectors(q, 5)
    fin = idx.search_by_vectors_async(q, 5)
    winners = [int(x) for x in np.unique(want[0]) if x != SENTINEL]
    idx.delete(*winners[:3])
    idx.compact()
    assert idx._codes4 is not None  # the 4-bit ladder survived compact
    got = fin()
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    fresh = idx.search_by_vectors(q, 5)
    assert not set(winners[:3]) & {int(x) for x in fresh[0].ravel()}


def test_funnel_on_mesh_parity_filters_and_append(tmp_path, rng):
    """The mesh's per-device funnel: compress-to-4-bit parity vs brute
    force, filtered search, post-compress append, delete, and the pq4
    device slabs in the per-device ledger components."""
    import os

    from weaviate_tpu.index.mesh import MeshVectorIndex

    config = parse_and_validate_config(
        "hnsw_tpu_mesh", {"distance": "l2-squared", "exactTopK": True})
    os.makedirs(tmp_path / "m4", exist_ok=True)  # codebook save target
    idx = MeshVectorIndex(config, str(tmp_path / "m4"), persist=False,
                          initial_capacity_per_shard=64)
    vecs = rng.integers(-8, 8, (400, DIM)).astype(np.float32)
    idx.add_batch(np.arange(400), vecs)
    idx.flush()
    idx.update_user_config(parse_and_validate_config(
        "hnsw_tpu_mesh",
        {"distance": "l2-squared", "exactTopK": True, "pq": PQ4}))
    assert idx.compressed and idx._codes4 is not None
    assert idx._pq4 is not None and idx._pq4.centroids == 16
    comps = idx._memory_components()
    assert comps["pq4_codes"] > 0 and comps["opq_rot"] > 0

    q = (vecs[:10] + 0.25).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 5)
    for i in range(len(q)):
        want_ids, want_d = _brute(vecs, q[i], 5)
        np.testing.assert_allclose(dists[i], want_d, rtol=0, atol=1e-4)
        assert {int(x) for x in ids[i]} == {int(x) for x in want_ids}

    allow = Bitmap(range(100, 200))
    ids_f, _ = idx.search_by_vectors(vecs[150][None, :] + 0.25, 3,
                                     allow_list=allow)
    assert int(ids_f[0][0]) == 150
    assert all(100 <= int(x) < 200 for x in ids_f[0] if x != SENTINEL)

    nv = rng.integers(-8, 8, DIM).astype(np.float32) * 5.0
    idx.add(9999, nv)
    idx.flush()
    ids2, _ = idx.search_by_vector(nv, 1)
    assert int(ids2[0]) == 9999

    idx.delete(int(ids[0][0]))
    ids3, _ = idx.search_by_vectors(q[:1], 3)
    assert int(ids[0][0]) not in [int(x) for x in ids3[0]]
    idx.shutdown()


def test_bits8_mode_never_touches_the_funnel(tmp_path, monkeypatch):
    """Disabled mode (the default 8-bit ladder) is zero-hop: no funnel
    entry point may run, and no 4-bit slabs exist."""
    def boom(*a, **k):
        raise AssertionError("funnel entry point touched in bits=8 mode")

    for name in ("search_pq4_funnel_fused", "search_ivf_pq4_fused",
                 "pq4_funnel_topk", "ivf_pq4_topk", "plan_funnel"):
        monkeypatch.setattr(pq4_ops, name, boom)
    pq8 = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 32, "rescore": True}
    idx, vecs = _mk_index(tmp_path, pq=pq8, name="no4")
    assert idx._codes4 is None and idx._pq4 is None
    assert idx._opq_rot_dev is None
    ids, _ = idx.search_by_vectors(vecs[:8] + 0.25, 5)
    assert ids.shape == (8, 5)
    assert "funnel" not in idx.health()["pq"]


# -- 2. OPQ rotation ----------------------------------------------------------


def _correlated(rng, n=1200, d=DIM):
    """Anisotropic, cross-segment-correlated data: where OPQ helps."""
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0]
    scales = np.linspace(3.0, 0.1, d)
    return (rng.standard_normal((n, d)) * scales) @ basis.T


def test_opq_rotation_roundtrip_and_recall_improves(rng):
    from weaviate_tpu.compress.pq import ProductQuantizer
    from weaviate_tpu.entities import vectorindex as vi

    vecs = _correlated(rng).astype(np.float32)

    def fit(rotation):
        pq = ProductQuantizer(DIM, 4, 32, vi.DISTANCE_L2,
                              vi.PQ_ENCODER_KMEANS, "normal", rotation)
        pq.fit(vecs)
        return pq

    plain, opq = fit(vi.PQ_ROTATION_NONE), fit(vi.PQ_ROTATION_OPQ)
    r = opq.rotation_matrix
    assert r is not None and r.shape == (DIM, DIM)
    np.testing.assert_allclose(r @ r.T, np.eye(DIM), atol=1e-4)

    def recon_err(pq):
        recon = pq.decode(pq.encode(vecs))  # decode maps back to input space
        return float(((vecs - recon) ** 2).sum(1).mean())

    assert recon_err(opq) < recon_err(plain) * 0.9  # real improvement

    # the 4-bit ladder pins the 8-bit ladder's rotation: same basis
    pq4 = ProductQuantizer(DIM, 4, 16, vi.DISTANCE_L2,
                           vi.PQ_ENCODER_KMEANS, "normal",
                           vi.PQ_ROTATION_NONE)
    pq4.fit(vecs, rotation_matrix=opq.rotation_matrix)
    np.testing.assert_array_equal(pq4.rotation_matrix, opq.rotation_matrix)
    # rotation_dev() is total: identity when nothing was fitted
    ident = plain.rotation_dev()
    np.testing.assert_allclose(np.asarray(ident), np.eye(DIM), atol=1e-6)


def test_opq_index_applies_rotation_at_dispatch(tmp_path):
    """The index stores the rotation once ([D, D] device constant) and
    ranks in rotated space — searching still finds raw-space neighbors."""
    idx, vecs = _mk_index(tmp_path, name="rot")
    assert idx._opq_rot_dev is not None
    comps = idx._memory_components()
    assert comps["opq_rot"] == DIM * DIM * 4
    ids, _ = idx.search_by_vectors(vecs[:6] + 0.25, 1)
    np.testing.assert_array_equal(ids[:, 0], np.arange(6))


# -- 3. ops-level satellites --------------------------------------------------


def test_pack_unpack_roundtrip(rng):
    codes = rng.integers(0, 16, (50, 12)).astype(np.uint8)
    packed = pack_codes4(codes)
    assert packed.shape == (50, 6) and packed.dtype == np.uint8
    # layout: byte j = seg j | seg (mb + j) << 4
    np.testing.assert_array_equal(packed[:, 0] & 15, codes[:, 0])
    np.testing.assert_array_equal(packed[:, 0] >> 4, codes[:, 6])
    np.testing.assert_array_equal(unpack_codes4(packed), codes)
    with pytest.raises(ValueError):
        pack_codes4(codes[:, :11])  # odd M never packs


def test_byte_lut_matches_per_segment_sum(rng):
    import jax.numpy as jnp

    m, ds = 6, 4
    cb = rng.standard_normal((m, 16, ds)).astype(np.float32)
    q = rng.standard_normal((3, m * ds)).astype(np.float32)
    lut = np.asarray(pq4_ops.byte_lut(jnp.asarray(q), jnp.asarray(cb)))
    codes = rng.integers(0, 16, (20, m)).astype(np.uint8)
    packed = pack_codes4(codes)
    got = lut[:, (np.arange(m // 2) * 256)[None, :] + packed.astype(np.int64)
              ].sum(-1)
    qs = q.reshape(3, m, ds)
    # straightforward reference: sum of per-segment q.centroid dots
    want = np.zeros((3, 20), np.float32)
    for b in range(3):
        for r in range(20):
            want[b, r] = sum(
                qs[b, s] @ cb[s, codes[r, s]] for s in range(m))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_plan_tiles_pq4_respects_budget():
    from weaviate_tpu.ops.gmin_scan import _VMEM_BUDGET

    qb, scg, mseg, fp = pq4_ops.plan_tiles_pq4(16384, 128, 65536, 16, 16)
    assert fp <= _VMEM_BUDGET and qb >= 64 and scg >= 64
    qb2, scg2, _, _ = pq4_ops.plan_tiles_pq4(512, 2048, 4096, 16, 256)
    assert qb2 >= 64 and scg2 >= 64


def test_plan_funnel_floors_and_caps():
    c_top = PQ4_FUNNEL_C_BUCKETS[-1]
    rc_top = PQ4_FUNNEL_RESCORE_BUCKETS[-1]
    # big index, top budgets: C = c_cap, rc = rc_cap
    rg4, rc = pq4_ops.plan_funnel(10, 1 << 20, c_top, rc_top)
    assert rg4 * 16 == c_top and rc == rc_top
    # tiny index: both stages clamp to what exists
    rg4, rc = pq4_ops.plan_funnel(10, 64, c_top, rc_top)
    assert rg4 == 4 and rc == 64
    # k deeper than the cut: rc floors at k (never starves coverage)
    rg4, rc = pq4_ops.plan_funnel(300, 1 << 20, c_top, rc_top)
    assert rc == 300
    # k deeper than the whole stage-1 keep: rc collapses to the keep
    rg4, rc = pq4_ops.plan_funnel(100, 80, c_top, rc_top)
    assert rg4 == 5 and rc == 80


# -- 4. the controller's funnel-budget ladder ---------------------------------


def _plane(**overrides) -> ControlPlane:
    return ControlPlane(start=False, **overrides)


def test_funnel_caps_cut_back_off_and_revert():
    p = _plane(hold_ticks=1, recall_floor=0.98, recall_slack=0.015,
               recall_backoff_margin=0.005)
    sense = {"ewma": 1.0}
    p._sense_recall = lambda: sense["ewma"]
    c_top, c_next = PQ4_FUNNEL_C_BUCKETS[-1], PQ4_FUNNEL_C_BUCKETS[-2]
    r_top, r_next = (PQ4_FUNNEL_RESCORE_BUCKETS[-1],
                     PQ4_FUNNEL_RESCORE_BUCKETS[-2])
    p.tick(), p.tick()
    assert p._read(KNOB_FUNNEL_C, c_top) < c_top
    assert p._read(KNOB_FUNNEL_RESCORE, r_top) < r_top
    # near the floor: back off immediately
    sense["ewma"] = 0.982
    depth_c = p._read(KNOB_FUNNEL_C, c_top)
    p.tick()
    assert p._read(KNOB_FUNNEL_C, c_top) > depth_c
    # signal loss: revert to the static max
    p._sense_recall = lambda: None
    p.tick()
    assert p._read(KNOB_FUNNEL_C, c_top) == c_top
    assert p._read(KNOB_FUNNEL_RESCORE, r_top) == r_top
    # summary reports both ladder positions
    b = p.summary()["controllers"]["budget"]
    assert b["funnel_c_cap"] == c_top and b["funnel_rescore_cap"] == r_top
    assert c_next < c_top and r_next < r_top  # ladder really has rungs


def test_funnel_caps_hold_while_sampling_paused():
    p = _plane(hold_ticks=1, recall_min_samples=2)
    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.5, start_workers=False))
    try:
        for _ in range(4):
            auditor.window.record("exact_scan", 1.0, 1.0, 0.0, 1, 0.0)
        p.tick(), p.tick()
        c_top = PQ4_FUNNEL_C_BUCKETS[-1]
        held = p._read(KNOB_FUNNEL_C, c_top)
        assert held < c_top
        p._pause_sampling()
        for _ in range(3):
            p.tick()
        assert p._read(KNOB_FUNNEL_C, c_top) == held  # held, not moved
    finally:
        quality.unconfigure(auditor)


def test_funnel_readers_default_and_never_raise():
    c_top = PQ4_FUNNEL_C_BUCKETS[-1]
    assert controller.funnel_c_cap(c_top) == c_top  # no plane: default
    assert controller.funnel_rescore_cap(64) == 64
    p = controller.configure(_plane())
    p._set_knob(KNOB_FUNNEL_C, PQ4_FUNNEL_C_BUCKETS[0], "t")
    p._set_knob(KNOB_FUNNEL_RESCORE, PQ4_FUNNEL_RESCORE_BUCKETS[0], "t")
    assert controller.funnel_c_cap(c_top) == PQ4_FUNNEL_C_BUCKETS[0]
    # the cap may only CUT: it never raises a smaller configured default
    assert controller.funnel_c_cap(128) == 128
    assert controller.funnel_rescore_cap(16) == 16


def test_funnel_knobs_bucket_snapped_and_journaled():
    p = _plane()
    assert p._set_knob(KNOB_FUNNEL_C, 999999, "t") == PQ4_FUNNEL_C_BUCKETS[-1]
    assert p._set_knob(KNOB_FUNNEL_C, 1, "t") == PQ4_FUNNEL_C_BUCKETS[0]
    for v in PQ4_FUNNEL_C_BUCKETS:
        assert p._set_knob(KNOB_FUNNEL_C, v, "t") == v
    for v in PQ4_FUNNEL_RESCORE_BUCKETS:
        assert p._set_knob(KNOB_FUNNEL_RESCORE, v, "t") == v
    # actuations ride the shared journal path (same _set_knob ->
    # _journal_actuation as every other knob): the /debug deque carries
    # each funnel-budget move attributed to its controller
    knobs_seen = {r["knob"] for r in p._recent}
    assert {KNOB_FUNNEL_C, KNOB_FUNNEL_RESCORE} <= knobs_seen
    assert p._recent[-1]["controller"] == "t"


def test_index_budget_floor_ignores_starving_caps(tmp_path):
    """A cap too shallow for this query's k lapses to the static max —
    the controller may only cut work, never break coverage."""
    idx, _ = _mk_index(tmp_path, name="floor")
    p = controller.configure(_plane())
    p._set_knob(KNOB_FUNNEL_C, PQ4_FUNNEL_C_BUCKETS[0], "t")      # 256
    p._set_knob(KNOB_FUNNEL_RESCORE, PQ4_FUNNEL_RESCORE_BUCKETS[0], "t")
    rg4, rc = funnel_budgets(100, 100000)  # 4k > 256, 2k > 32
    assert rg4 * 16 == PQ4_FUNNEL_C_BUCKETS[-1]
    assert rc == PQ4_FUNNEL_RESCORE_BUCKETS[-1]
    rg4, rc = funnel_budgets(10, 100000)   # caps respected when sane
    assert rg4 * 16 == PQ4_FUNNEL_C_BUCKETS[0]
    assert rc == PQ4_FUNNEL_RESCORE_BUCKETS[0]


# -- 5. monitoring satellites -------------------------------------------------


def test_costmodel_funnel_stage_attribution():
    shape = costmodel.DispatchShape(
        costmodel.TIER_PQ_ADC4, n=100000, dim=64, batch=8, bytes_per_row=8,
        k=10, extra={"funnel_c": 4096, "funnel_rescore": 256,
                     "funnel_stage2_bytes_per_row": 16,
                     "funnel_stage3_bytes_per_row": 128})
    want = 100000 * 8 + 8 * (4096 * 16 + 256 * 128)
    assert shape.bytes() == want
    # stage attribution is per QUERY and tier-gated: other tiers ignore it
    other = costmodel.DispatchShape(
        costmodel.TIER_PQ_CODES, n=100000, dim=64, batch=8, bytes_per_row=16,
        extra={"funnel_c": 4096, "funnel_stage2_bytes_per_row": 16})
    assert other.bytes() == 100000 * 16


def test_memory_ledger_accounts_pq4_components(tmp_path):
    ledger = memory.configure(memory.MemoryLedger(
        metrics=__import__("weaviate_tpu.monitoring.metrics",
                           fromlist=["noop_metrics"]).noop_metrics()))
    idx, _ = _mk_index(tmp_path, name="led")
    comps = idx._memory_components()
    for name in ("pq4_codes", "pq4_norms", "opq_rot"):
        assert name in memory.DEVICE_COMPONENTS  # bounded gauge labels
        assert comps[name] > 0
    # bit-exact: the 4-bit slab is M/2 bytes per capacity row
    assert comps["pq4_codes"] == idx.capacity * idx._pq4.segments // 2


def test_health_reports_funnel_ladder_state(tmp_path):
    idx, vecs = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:8] + 0.25, 5)
    pq_h = idx.health()["pq"]
    assert pq_h["bits"] == 4 and pq_h["opq"] is True
    f = pq_h["funnel"]
    assert f["c_cap"] == PQ4_FUNNEL_C_BUCKETS[-1]
    assert f["rescore_cap"] == PQ4_FUNNEL_RESCORE_BUCKETS[-1]
    assert f["dispatches"] >= 1
    assert (f["mean_stage1_rows"] >= f["mean_stage2_survivors"]
            >= f["mean_stage3_survivors"] >= 5)


# -- 6. graftlint frozensets --------------------------------------------------


def test_graftlint_covers_pq4_snapshot_fields_and_funnel_knobs():
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.graftlint import analyze_source

    src = (
        "import jax, jax.numpy as jnp\n"
        "class Idx:\n"
        "    def _enable(self, c4, n4, r):\n"
        "        self._codes4 = jax.device_put(jnp.asarray(c4))\n"
        "        self._recon_norms4 = jax.device_put(jnp.asarray(n4))\n"
        "        self._opq_rot_dev = jax.device_put(jnp.asarray(r))\n"
    )
    hits = [f.code for f in analyze_source(
        src, "weaviate_tpu/index/fake_index.py")]
    assert hits.count("JGL012") == 3
    stamped = src + "        self._stamp_memory()\n"
    assert "JGL012" not in [f.code for f in analyze_source(
        stamped, "weaviate_tpu/index/fake_index.py")]

    knob_src = (
        "def f(p):\n"
        "    p._knobs['funnel_c_cap'] = 256\n"
        "    p._knobs['funnel_rescore_cap'] = 32\n"
    )
    hits = [f.code for f in analyze_source(
        knob_src, "weaviate_tpu/usecases/fake_host.py")]
    assert hits.count("JGL014") == 2
    assert "JGL014" not in [f.code for f in analyze_source(
        knob_src, "weaviate_tpu/serving/controller.py")]
