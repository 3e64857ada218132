"""Partition-pruned search: the clustered IVF scan plane (ROADMAP item 3).

Pins the IVF PR's contracts:

1. ``top_p = all partitions`` is equivalent to the flat path on
   every read tier — exact, filtered scan, PQ rescore, PQ codes-only —
   sync == async: distances BIT-equal, ids equal up to
   reordering inside exact-distance tie groups (on tie-free data that is
   bit-identity; the helper degenerates to array_equal there);
2. disabled IVF is a true zero-hop no-op: nothing trains, no device
   slabs exist, the dispatch gate is one comparison;
3. snapshot isolation survives the recluster lifecycle: a dispatch
   enqueued on an old snapshot answers from the OLD layout even when a
   recluster + compact replaces every IVF array underneath it (the PR-4
   torn-read pin, extended to partition tables);
4. the padded-bucket layout keeps jit shapes stable across inserts, the
   probe respects deletes/re-adds/filters through the flat kernels' own
   masking semantics, and the new device slabs are ledger-accounted
   bit-equal to their buffers' nbytes;
5. the ``ivf_top_p`` controller knob is the second recall-guarded
   budget: bucket-snapped, cut only under measured recall slack,
   reverted on ANY signal loss (a paused auditor reads as no-signal).
"""

import numpy as np
import pytest

from weaviate_tpu.config.config import (ConfigError, IVF_TOP_P_BUCKETS,
                                        IvfConfig, load_config)
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import plan as plan_mod
from weaviate_tpu.index import tpu
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import memory, perf, tracing
from weaviate_tpu.ops import ivf as ivf_ops
from weaviate_tpu.serving import controller
from weaviate_tpu.serving.controller import KNOB_IVF_TOP_P, ControlPlane
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16


@pytest.fixture(autouse=True)
def _reset_globals(monkeypatch):
    # these tests pin what the probed programs ANSWER, at sizes where a
    # batch's partitions hold more rows than the whole store: the choice by
    # bytes (index/plan.py probed_reads_less, tests/test_ivf_tiles.py) is
    # set aside, so a layout always takes its probed program
    monkeypatch.setattr(plan_mod, "PROBED_ROW_COST", 0.0)
    monkeypatch.setattr(plan_mod, "GATHERED_ROW_COST", 0.0)
    yield
    tpu.set_ivf_config(None)
    tracing.configure(None)
    perf.configure(None)
    controller.configure(None)


def _ivf(**kw) -> IvfConfig:
    base = dict(enabled=True, nlist=8, min_n=256, top_p=8,
                train_sample=4096, train_iters=4)
    base.update(kw)
    return IvfConfig(**base)


def _mk_index(tmp_path, n=600, pq=None, seed=3, name="ivfx", spread=100,
              **cfg_extra):
    """Integer vectors: every distance is exact integer arithmetic in
    f32 (and in bf16 products), so cross-kernel equality checks are
    exact; a wide value range keeps distance ties rare."""
    rng = np.random.default_rng(seed)
    vecs = rng.integers(-spread, spread, (n, DIM)).astype(np.float32)
    d = {"distance": "l2-squared", **cfg_extra}
    if pq is not None:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu", d)
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs


def _assert_tiles_complete(idx, docs):
    """The tiled layout's invariant: every live doc in exactly one slot,
    the slot's tile its partition (`slot // cap_p`), every other slot free
    (tombstoned) on the host and on the device, the counts in step."""
    snap = idx._read_snapshot()[0]
    nlist, cap_p, _ = snap.ivf_meta
    docs = sorted(docs)
    s2d = snap.slot_to_doc[: snap.n]
    held = np.flatnonzero(~snap.host_tombs[: snap.n])
    assert sorted(s2d[held].tolist()) == docs
    assert sorted(idx._doc_to_slot) == docs
    assert all(idx._doc_to_slot[int(s2d[s])] == s for s in held)
    np.testing.assert_array_equal(np.asarray(snap.tombs)[: snap.n],
                                  snap.host_tombs[: snap.n])
    fills = np.bincount(held // cap_p, minlength=nlist)
    np.testing.assert_array_equal(idx._ivf_free_n, cap_p - fills)
    assert fills.max() <= cap_p and int(fills.sum()) == len(docs)
    # what a tile does not hold is zero rows
    empty = np.flatnonzero(snap.host_tombs[: snap.n])
    assert not np.asarray(snap.store)[empty[:64]].any()


def assert_tie_equiv(got, want, msg=""):
    """Distances must be BIT-equal; ids must match exactly wherever the
    distance is unique, and as a set inside an exact-tie group (selection
    order within a tie is unspecified — on tie-free data this is
    array_equal)."""
    np.testing.assert_array_equal(got[1], want[1], err_msg=msg)
    for r in range(want[1].shape[0]):
        gd, gi, wi = want[1][r], got[0][r], want[0][r]
        for v in np.unique(gd):
            sel = gd == v
            assert set(gi[sel].tolist()) == set(wi[sel].tolist()), \
                f"{msg}: tie-group mismatch row {r} dist {v}"


# -- 1. top_p = all ≡ flat, every tier, sync+async ----------------------------


def _tier(tmp_path, tier, n=600):
    """(index, vectors, allowList) of one read tier."""
    pq = None
    if tier.startswith("pq"):
        pq = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16,
              "rescore": tier == "pq_rescore"}
    idx, vecs = _mk_index(tmp_path, n=n, name=tier, exactTopK=True, pq=pq)
    if pq is not None:
        assert idx.compressed
        assert (idx._rescore_dev is not None) == pq["rescore"]
    allow = None
    if tier == "filtered_scan":
        allow = Bitmap(np.arange(0, idx.config.flat_search_cutoff + 64,
                                 dtype=np.uint64))
    return idx, vecs, allow


@pytest.mark.parametrize("tier", ["exact", "filtered_scan", "pq_rescore",
                                  "pq_codes"])
def test_top_p_all_matches_flat_all_tiers_sync_async(tmp_path, tier):
    tpu.set_ivf_config(_ivf())  # trains at import time (min_n < n)
    idx, vecs, allow = _tier(tmp_path, tier)
    # the uncompressed tiers keep the store in partition order (tiles), the
    # compressed ones a bucket table over slots in order of arrival
    assert idx._ivf_meta is not None
    assert idx._ivf_tiled == (not tier.startswith("pq"))
    assert (idx._ivf_buckets is None) == idx._ivf_tiled
    q = vecs[:9] + np.float32(1.0)
    # top_p=8 == nlist: every partition probed
    i_sync = idx.search_by_vectors(q, 10, allow)
    i_async = idx.search_by_vectors_async(q, 10, allow)()
    tpu.set_ivf_config(None)  # flat control on the same index
    flat = idx.search_by_vectors(q, 10, allow)
    assert_tie_equiv(i_sync, flat, tier + " sync")
    assert_tie_equiv(i_async, flat, tier + " async")
    assert i_sync[0].dtype == np.uint64
    assert i_sync[1].dtype == np.float32


def test_ivf_target_distance_matches_flat(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path, exactTopK=True)
    q = vecs[5] + np.float32(1.0)
    ids_i, d_i = idx.search_by_vector_distance(q, 3000.0, 64)
    tpu.set_ivf_config(None)
    ids_f, d_f = idx.search_by_vector_distance(q, 3000.0, 64)
    np.testing.assert_array_equal(d_i, d_f)
    assert set(ids_i.tolist()) == set(ids_f.tolist())
    assert len(ids_i) > 0


# -- 2. disabled = zero-hop no-op ---------------------------------------------


def test_ivf_disabled_is_true_noop(tmp_path):
    idx, vecs = _mk_index(tmp_path)  # no settings anywhere
    assert idx._ivf_centroids is None
    assert idx._ivf_buckets is None
    snap = idx._read_snapshot()[0]
    assert snap.ivf_buckets is None
    assert idx._ivf_plan(snap, 10) is None
    comps = idx._memory_components()
    assert not any(k.startswith("ivf") for k in comps)
    st = idx.ivf_stats()
    assert st["dispatches"] == 0
    h = idx.health()["ivf"]
    assert h == {"enabled": False, "trained": False}


def test_ivf_enabled_below_min_n_does_not_train(tmp_path):
    tpu.set_ivf_config(_ivf(min_n=100000))
    idx, _ = _mk_index(tmp_path)
    assert idx._ivf_centroids is None
    ids, _d = idx.search_by_vectors(np.zeros(DIM, np.float32)[None], 5)
    assert ids.shape == (1, 5)


def test_ivf_skips_non_matmul_metrics(tmp_path):
    tpu.set_ivf_config(_ivf())
    rng = np.random.default_rng(0)
    vecs = rng.integers(0, 2, (600, DIM)).astype(np.float32)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "manhattan"})
    idx = TpuVectorIndex(cfg, str(tmp_path / "man"), persist=False)
    idx.add_batch(np.arange(600), vecs)
    idx.flush()
    assert idx._ivf_centroids is None  # never trains
    ids, _ = idx.search_by_vectors(vecs[:3], 5)
    assert ids.shape[0] == 3


# -- 3. training / layout invariants ------------------------------------------


def test_training_publishes_a_complete_layout(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path)
    snap = idx._read_snapshot()[0]
    assert snap.ivf_centroids is not None and snap.ivf_tiled
    assert snap.ivf_buckets is None          # the table is slot // cap_p
    nlist, cap_p, gen = snap.ivf_meta
    assert nlist == 8 and gen == 1
    assert snap.n == nlist * cap_p and snap.store.shape[0] >= snap.n
    # every live row lies in exactly one partition's tile, the tiles fill
    # from the front, and every other slot is a tombstoned (free) one
    _assert_tiles_complete(idx, range(600))


def test_bucket_shapes_stay_stable_across_small_inserts(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path)
    cap_p0 = idx._ivf_meta[1]
    gen0 = idx._ivf_gen
    rng = np.random.default_rng(9)
    extra = rng.integers(-100, 100, (16, DIM)).astype(np.float32)
    idx.add_batch(np.arange(600, 616), extra)
    idx.flush()
    # incremental assignment, no retrain, same tile width: the search
    # program's jit key (top_p, cap_p, the store's shape) is unchanged
    assert idx._ivf_gen == gen0
    assert idx._ivf_meta[1] == cap_p0
    # and the new rows took free slots of their partitions' tiles: the
    # layout stays COMPLETE, every row (old and new) in exactly one tile
    _assert_tiles_complete(idx, range(616))
    # ...so the new rows are immediately findable through the probe
    ids, _ = idx.search_by_vectors(extra[:3], 1)
    assert ids[:, 0].tolist() == [600, 601, 602]


def test_growth_triggers_recluster(tmp_path):
    tpu.set_ivf_config(_ivf(retrain_growth=0.5))
    idx, vecs = _mk_index(tmp_path)
    gen0 = idx._ivf_gen
    rng = np.random.default_rng(11)
    more = rng.integers(-100, 100, (400, DIM)).astype(np.float32)
    idx.add_batch(np.arange(1000, 1400), more)  # 600 -> 1000 rows >= 1.5x
    idx.flush()
    assert idx._ivf_gen == gen0 + 1
    assert idx._ivf_trained_n == 1000


def test_ivf_respects_deletes_and_readds(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path, exactTopK=True)
    q = vecs[7][None, :].astype(np.float32)
    ids, _ = idx.search_by_vectors(q, 3)
    winner = int(ids[0, 0])
    assert winner == 7
    idx.delete(7)
    ids2, _ = idx.search_by_vectors(q, 3)
    assert 7 not in ids2[0].tolist()
    # re-add with a fresh vector: the NEWEST slot must serve it
    idx.add(7, vecs[7])
    ids3, _ = idx.search_by_vectors(q, 3)
    assert int(ids3[0, 0]) == 7


def test_small_allowlist_keeps_the_gather_tier(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path, exactTopK=True)
    before = idx.ivf_stats()["dispatches"]
    allow = Bitmap(np.array([3, 7, 11, 401], dtype=np.uint64))
    q = vecs[:4] + np.float32(1.0)
    got = idx.search_by_vectors(q, 4, allow)
    tpu.set_ivf_config(None)
    flat = idx.search_by_vectors(q, 4, allow)
    assert_tie_equiv(got, flat, "gather")
    # the gather tier never went through the probe
    assert idx.ivf_stats()["dispatches"] == before


def test_probe_prunes_and_keeps_recall_on_clustered_data(tmp_path):
    rng = np.random.default_rng(1)
    n = 4000
    centers = rng.standard_normal((64, DIM)).astype(np.float32) * 8
    vecs = (centers[rng.integers(0, 64, n)]
            + 0.3 * rng.standard_normal((n, DIM)).astype(np.float32))
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    tpu.set_ivf_config(_ivf(nlist=64, top_p=8, min_n=512))
    idx = TpuVectorIndex(cfg, str(tmp_path / "clu"), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    q = vecs[:32] + np.float32(0.01)
    # exact numpy ground truth (NOT the flat scan: on near-duplicate
    # clustered data the flat bf16 fast pass loses ~15% recall to L2
    # cancellation, while the IVF candidate pass scores survivors at
    # exact f32 — the probe must be measured against the truth)
    d = ((q ** 2).sum(1)[:, None] - 2.0 * q @ vecs.T
         + (vecs ** 2).sum(1)[None, :])
    gt = np.argsort(d, axis=1)[:, :10]
    ids, _ = idx.search_by_vectors(q, 10)
    rec = np.mean([len(set(a) & set(b)) / 10
                   for a, b in zip(ids.tolist(), gt.tolist())])
    assert rec >= 0.95
    st = idx.ivf_stats()
    assert st["probed_fraction"] is not None and st["probed_fraction"] < 1.0


def test_pca_prefilter_cuts_candidates_and_keeps_recall(tmp_path):
    tpu.set_ivf_config(_ivf(pca_dim=8))
    idx, vecs = _mk_index(tmp_path, n=1200, name="pca")
    snap = idx._read_snapshot()[0]
    assert snap.ivf_pca_proj is not None and snap.ivf_pca_rows is not None
    plan = idx._ivf_plan(snap, 10)
    assert plan is not None and plan[1] > 0  # prefilter active
    assert plan[1] < plan[0] * snap.ivf_meta[1]  # ...and actually cuts
    q = vecs[:16] + np.float32(1.0)
    ids, _ = idx.search_by_vectors(q, 10)
    tpu.set_ivf_config(None)
    flat_ids, _ = idx.search_by_vectors(q, 10)
    rec = np.mean([len(set(a) & set(b)) / 10
                   for a, b in zip(ids.tolist(), flat_ids.tolist())])
    assert rec >= 0.9


# -- 4. snapshot isolation across the recluster lifecycle ---------------------


def test_enqueued_dispatch_survives_recluster_and_compact(tmp_path):
    """The PR-4 torn-read pin, extended to partition tables: enqueue on
    an old snapshot, then delete the winners, force a recluster AND a
    compact underneath — finalize must return the OLD layout's exact
    answer."""
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path, exactTopK=True)
    q = vecs[:5] + np.float32(1.0)
    expected = idx.search_by_vectors(q, 10)
    fin = idx.search_by_vectors_async(q, 10)  # enqueued on the OLD snap
    winners = set(int(i) for i in expected[0][:, 0])
    idx.delete(*winners)
    rng = np.random.default_rng(21)
    more = rng.integers(-100, 100, (600, DIM)).astype(np.float32)
    idx.add_batch(np.arange(2000, 2600), more)  # growth => recluster
    idx.compact()                               # and a full rebuild
    assert idx._ivf_gen >= 2
    got = fin()
    assert_tie_equiv(got, expected, "pinned snapshot")


def test_compact_reclusters_on_the_dense_slot_space(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path)
    gen0 = idx._ivf_gen
    idx.delete(*range(0, 200))
    idx.compact()
    assert idx._ivf_gen == gen0 + 1
    _assert_tiles_complete(idx, range(200, 600))  # the live rows, complete
    ids, _ = idx.search_by_vectors(vecs[300][None], 3)
    assert int(ids[0, 0]) == 300


# -- 5. observability: health, ledger, costmodel, stats -----------------------


def test_health_reports_partition_layout(tmp_path):
    tpu.set_ivf_config(_ivf())
    idx, vecs = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:3], 5)
    h = idx.health()["ivf"]
    assert h["enabled"] and h["trained"]
    assert h["nlist"] == 8 and h["last_recluster_gen"] == 1
    b = h["buckets"]
    assert b["fill_min"] >= 0 and b["fill_max"] <= h["bucket_capacity"]
    assert 0.0 <= b["padding_waste"] < 1.0
    assert len(b["fill_histogram"]) == 8
    assert sum(b["fill_histogram"]) == h["nlist"]
    assert b["imbalance"] >= 1.0
    p = h["probes"]
    # probed_fraction is device work vs the flat scan and may exceed 1.0
    # on padding-heavy tiny layouts — that is the honest number telling
    # the operator IVF is not yet worth it at this corpus size
    assert p["dispatches"] >= 1 and p["probed_fraction"] > 0


def test_new_slabs_are_ledger_accounted_bit_equal(tmp_path):
    tpu.set_ivf_config(_ivf(pca_dim=8))
    idx, _ = _mk_index(tmp_path, name="led")
    comps = idx._memory_components()
    for name, arr in (("ivf_centroids", idx._ivf_centroids),
                      ("ivf_buckets", idx._ivf_buckets),
                      ("ivf_pca_proj", idx._ivf_pca_proj),
                      ("ivf_pca_rows", idx._ivf_pca_rows)):
        assert name in memory.DEVICE_COMPONENTS
        assert comps[name] == arr.nbytes  # bit-equal, analytic
    # the HOST twins (centroid matrix, PCA basis, assignment mirror)
    # are a ledger component too — /debug/memory must not underreport
    # the write path's resident state
    assert "ivf_host" in memory.HOST_COMPONENTS
    host = memory.index_host_components(idx)
    assert host["ivf_host"] == (idx._ivf_centroids_host.nbytes
                                + idx._ivf_pca_host.nbytes
                                + idx._ivf_assign.nbytes)
    # drop() releases every slab from the accounting
    idx.drop()
    comps = idx._memory_components()
    assert not any(k.startswith("ivf") for k in comps)
    assert "ivf_host" not in memory.index_host_components(idx)


def test_top_p_snap_extends_beyond_the_ladder():
    """A large-nlist layout legitimately probes hundreds of partitions:
    past the ladder's 128 top the snap continues on pow2 octaves (still
    bounded jit shapes) instead of silently collapsing the probe."""
    snap = tpu._snap_top_p
    assert snap(5) == 4
    assert snap(128) == 128
    assert snap(300) == 256
    assert snap(4096) == 4096
    assert snap(5000) == 4096
    # beyond the ladder entirely (explicitly-configured giant nlist):
    # pow2 octaves keep the static set bounded
    assert snap(10000) == 8192


def test_dispatch_shape_carries_probed_aware_flops(tmp_path):
    tpu.set_ivf_config(_ivf(nlist=8, top_p=2))
    idx, vecs = _mk_index(tmp_path, n=2000, name="shape")
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    try:
        handle = idx.search_by_vectors_async(vecs[:4], 10)
        handle()
        shape = handle.shape
        assert shape is not None
        nlist, cap_p, _ = idx._ivf_meta
        probed = 2 * cap_p + nlist        # a query's rows, not snap.n
        assert probed < 2000
        # what the program reads: each of the 4 padded queries its own
        # tiles, the centroids once (PR 44: `n` was one query's rows)
        assert shape.n == 4 * 2 * cap_p + nlist
        assert shape.n == shape.extra["ivf_rows_read"]
        d = shape.describe()
        assert d["ivf"] is True
        assert d["ivf_top_p"] == 2
        assert 0 < d["probed_fraction"] < 1.0
        assert shape.flops() == int(round(2.0 * 4 * probed * DIM))
    finally:
        tracing.configure(None)


# -- 6. the ivf_top_p controller knob (second recall-guarded budget) ----------


def _plane(**overrides) -> ControlPlane:
    return ControlPlane(start=False, **overrides)


def test_ivf_budget_cuts_on_slack_and_backs_off():
    p = _plane(hold_ticks=2, recall_floor=0.98, recall_slack=0.015,
               recall_backoff_margin=0.005)
    sense = {"ewma": 1.0}
    p._sense_recall = lambda: sense["ewma"]
    top = IVF_TOP_P_BUCKETS[-1]
    p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) == top  # held one tick
    p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) == IVF_TOP_P_BUCKETS[-2]
    # near the floor: back off immediately, no hysteresis on restores
    sense["ewma"] = 0.982
    p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) == top


def test_ivf_budget_reverts_on_signal_loss_and_paused_auditor():
    top = IVF_TOP_P_BUCKETS[-1]
    p = _plane(hold_ticks=1)
    p._sense_recall = lambda: 1.0
    p.tick(), p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) < top
    # a PAUSED sample gate is no-signal for the probe budget (unlike the
    # rescore cap's hold): the knob reverts to the configured default
    p._sampling_paused = True
    p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) == top
    p._sampling_paused = False
    p.tick(), p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) < top
    p._sense_recall = lambda: None
    p.tick()
    assert p._read(KNOB_IVF_TOP_P, top) == top


def test_deep_k_widens_the_probe_for_coverage(tmp_path):
    """A k deeper than the probed candidate set would starve selection:
    the plan widens up the bucket ladder until ~4k candidates are
    covered, no matter what the config or controller cap says."""
    tpu.set_ivf_config(_ivf(nlist=8, top_p=1))
    idx, vecs = _mk_index(tmp_path, name="deepk")
    snap = idx._read_snapshot()[0]
    cap_p = snap.ivf_meta[1]
    assert idx._ivf_plan(snap, 10)[0] == 1          # shallow k: as asked
    deep_k = cap_p  # 4k = 4*cap_p > 1*cap_p: must widen
    top_p = idx._ivf_plan(snap, deep_k)[0]
    assert top_p * cap_p >= min(4 * deep_k, 8 * cap_p)
    ids, dists = idx.search_by_vectors(vecs[:2], deep_k)
    assert ids.shape[1] >= min(deep_k, 600)


def test_ivf_top_p_cap_reader_is_clamped_and_bucket_snapped():
    assert controller.ivf_top_p_cap(8) == 8  # no plane: default
    p = _plane()
    controller.configure(p)
    try:
        p._set_knob(KNOB_IVF_TOP_P, 5, "budget")  # snaps to 4
        assert controller.ivf_top_p_cap(8) == 4
        assert controller.ivf_top_p_cap(2) == 2   # never exceeds default
    finally:
        controller.configure(None)


def test_controller_cap_steers_the_live_probe_count(tmp_path):
    tpu.set_ivf_config(_ivf(nlist=8, top_p=8))
    idx, vecs = _mk_index(tmp_path, name="steer")
    snap = idx._read_snapshot()[0]
    assert idx._ivf_plan(snap, 10)[0] == 8
    p = _plane()
    controller.configure(p)
    try:
        p._set_knob(KNOB_IVF_TOP_P, 2, "budget")
        assert idx._ivf_plan(snap, 10)[0] == 2
        # the cut path still serves correct results
        ids, _ = idx.search_by_vectors(vecs[:3], 5)
        assert ids.shape == (3, 5)
    finally:
        controller.configure(None)
    assert idx._ivf_plan(snap, 10)[0] == 8  # plane gone: static again


def test_budget_summary_reports_both_caps():
    p = _plane(hold_ticks=1)
    p._sense_recall = lambda: 1.0
    p.tick(), p.tick()
    s = p.summary()["controllers"]["budget"]
    assert s["rescore_r_cap"] < 128
    assert s["ivf_top_p_cap"] < IVF_TOP_P_BUCKETS[-1]
    p.revert_all("test")
    s = p.summary()["controllers"]["budget"]
    assert s["ivf_top_p_cap"] == IVF_TOP_P_BUCKETS[-1]


# -- 7. config / settings plumbing --------------------------------------------


def test_ivf_env_parse_and_validation():
    env = {"IVF_ENABLED": "true", "IVF_NLIST": "64", "IVF_TOP_P": "4",
           "IVF_MIN_N": "1000", "IVF_PCA_DIM": "8",
           "IVF_TRAIN_SAMPLE": "8192", "IVF_TRAIN_ITERS": "3",
           "IVF_RETRAIN_GROWTH": "0.25"}
    cfg = load_config(env)
    assert cfg.ivf.enabled and cfg.ivf.nlist == 64
    assert cfg.ivf.top_p == 4 and cfg.ivf.pca_dim == 8
    assert cfg.ivf.train_iters == 3 and cfg.ivf.retrain_growth == 0.25
    for bad in ({"IVF_NLIST": "-1"}, {"IVF_TOP_P": "-2"},
                {"IVF_MIN_N": "0"}, {"IVF_PCA_DIM": "-1"},
                {"IVF_PREFILTER_C": "-1"}, {"IVF_TRAIN_SAMPLE": "8"},
                {"IVF_TRAIN_ITERS": "0"}, {"IVF_RETRAIN_GROWTH": "0"}):
        with pytest.raises(ConfigError):
            load_config({"IVF_ENABLED": "true", **bad})


def test_ivf_settings_env_fallback_and_token_revert(monkeypatch):
    tok = tpu.set_ivf_config(None)  # clear cached env parse
    assert tpu.ivf_settings() is None
    monkeypatch.setenv("IVF_ENABLED", "true")
    monkeypatch.setenv("IVF_NLIST", "32")
    tpu.set_ivf_config(None)  # drop cache: revert means re-read
    s = tpu.ivf_settings()
    assert s is not None and s.nlist == 32
    # an override wins over the env; its token reverts only itself
    tok = tpu.set_ivf_config(IvfConfig(enabled=False))
    assert tpu.ivf_settings() is None
    tok2 = tpu.set_ivf_config(IvfConfig(enabled=True, nlist=4))
    tpu.unset_ivf_config(tok)  # stale token: the newer override survives
    assert tpu.ivf_settings().nlist == 4
    tpu.unset_ivf_config(tok2)
    assert tpu.ivf_settings().nlist == 32  # back to the env


def test_kmeans_helpers_are_deterministic_and_complete():
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((1000, 8)).astype(np.float32)
    c1 = ivf_ops.kmeans_fit(rows, 16, iters=4, seed=7)
    c2 = ivf_ops.kmeans_fit(rows, 16, iters=4, seed=7)
    np.testing.assert_array_equal(c1, c2)
    assign = ivf_ops.assign_partitions(rows, c1)
    assert assign.shape == (1000,) and assign.min() >= 0 \
        and assign.max() < 16
    buckets, fills = ivf_ops.build_buckets(assign, 16)
    assert buckets.shape[1] % 128 == 0
    assert int(fills.sum()) == 1000
    got = np.sort(buckets[buckets >= 0])
    np.testing.assert_array_equal(got, np.arange(1000))
    # pinned cap_p is kept while it still fits
    b2, _ = ivf_ops.build_buckets(assign, 16, cap_p=buckets.shape[1])
    assert b2.shape == buckets.shape
    proj = ivf_ops.pca_fit(rows, 4)
    assert proj.shape == (8, 4) and proj.dtype == np.float32
