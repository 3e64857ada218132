"""The tiled layout of the partition-pruned tier (ops/ivf.py "the tiled
layout", index/tpu.py `_ivf_train_tiles`): an uncompressed index keeps its
ONE copy of the rows in partition order and a probe reads whole tiles.

1. the device program, handed a layout, returns what a plain numpy IVF of
   thirty lines returns on the same layout, row for row: through the tile
   read, with deletes, with an allowList, at top_p = 1, 64 and all, for
   cosine, l2 and dot;
2. `plan_search` takes the probed program where it reads fewer bytes than
   the flat one and the flat one elsewhere, and the declined dispatches are
   counted;
3. the layout is durable state: written at a training and at a clean
   shutdown, read back by a restart that trains nothing, also after a kill
   right after the publish;
4. an import in 10,000-row batches leaves every live row in exactly one
   partition's tile, and the device holds one copy of the rows.
"""

import os

import numpy as np
import pytest

from weaviate_tpu.config.config import IvfConfig
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import plan as plan_mod
from weaviate_tpu.index import tpu
from weaviate_tpu.index.plan import PlanView, plan_search, probed_reads_less
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import costmodel, perf, tracing
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16
METRICS = {"cosine": "cosine", "l2": "l2-squared", "dot": "dot"}


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tpu.set_ivf_config(None)
    tracing.configure(None)
    perf.configure(None)


def _ivf(**kw) -> IvfConfig:
    base = dict(enabled=True, nlist=8, min_n=256, top_p=2,
                train_sample=4096, train_iters=4)
    base.update(kw)
    return IvfConfig(**base)


def _clustered(n, seed=1, centers=64):
    rng = np.random.default_rng(seed)
    c = rng.standard_normal((centers, DIM)).astype(np.float32) * 4
    return (c[rng.integers(0, centers, n)]
            + 0.5 * rng.standard_normal((n, DIM)).astype(np.float32))


def _index(path, metric="l2-squared", persist=False, **cfg):
    return TpuVectorIndex(
        parse_and_validate_config("hnsw_tpu", {"distance": metric, **cfg}),
        str(path), persist=persist)


# -- 1. the plain reference of the probed answer -------------------------------


def numpy_ivf(q, rows, assign, centroids, live, allowed, top_p, k, metric):
    """A plain IVF: the `top_p` partitions by exact centroid distance, the
    exact top k of their live, allowed rows -> (row ids, distances).
    `rows` as the index stores them (cosine: normalized), `assign` [n] the
    partition of each row, `live` / `allowed` [n] bool."""
    q = q.astype(np.float64)
    if metric == "cosine":
        q = q / np.linalg.norm(q)

    def dist(x):
        x = x.astype(np.float64)
        if metric == "l2-squared":
            return ((x - q) ** 2).sum(-1)
        return (1.0 if metric == "cosine" else 0.0) - x @ q

    probed = np.argsort(dist(centroids), kind="stable")[:top_p]
    cand = np.flatnonzero(np.isin(assign, probed) & live & allowed)
    d = dist(rows[cand])
    order = np.argsort(d, kind="stable")[:k]
    return cand[order], d[order]


def _layout_of(idx, n):
    """(stored rows [n, D], partition [n], live [n]) by doc id, read from
    the index's own layout: a doc's partition is its slot's tile."""
    cap_p = idx._ivf_meta[1]
    store = np.asarray(idx._store)
    rows = np.zeros((n, DIM), np.float32)
    assign = np.full(n, -1)
    live = np.zeros(n, bool)
    for doc, slot in idx._doc_to_slot.items():
        rows[doc], assign[doc], live[doc] = store[slot], slot // cap_p, True
    return rows, assign, live


@pytest.mark.parametrize("top_p", [1, 64, 96])
@pytest.mark.parametrize("name", ["cosine", "l2", "dot"])
def test_device_program_equals_the_numpy_ivf(tmp_path, monkeypatch, name,
                                             top_p):
    """Deletes, re-adds and an allowList included; 96 is all partitions."""
    monkeypatch.setattr(plan_mod, "PROBED_ROW_COST", 0.0)  # probed, always
    metric = METRICS[name]
    n, k = 6000, 10
    vecs = _clustered(n, seed=7)
    tpu.set_ivf_config(_ivf(nlist=96, top_p=top_p, min_n=n))
    # flatSearchCutoff 0: an allowList masks the scan, whatever its length
    idx = _index(tmp_path / name, metric, exactTopK=True, flatSearchCutoff=0)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    assert idx._ivf_tiled and idx._ivf_meta[0] == 96
    idx.delete(*range(0, 300, 3))                 # 100 tombstones
    idx.add(3, vecs[3])                           # one comes back
    idx.flush()
    rows, assign, live = _layout_of(idx, n)
    assert int(live.sum()) == n - 99
    cent = idx._ivf_centroids_host
    q = vecs[5:25] + np.float32(0.05)
    allow_ids = np.arange(0, n, 2, dtype=np.uint64)
    for allow in (None, Bitmap(allow_ids)):
        allowed = np.ones(n, bool)
        if allow is not None:
            allowed[:] = False
            allowed[allow_ids.astype(np.int64)] = True
        for qq in q:                               # one query a dispatch
            ids, dists = idx.search_by_vectors(qq[None], k, allow)
            want, wd = numpy_ivf(qq, rows, assign, cent, live, allowed,
                                 top_p, k, metric)
            assert ids[0].tolist() == want.tolist()
            np.testing.assert_allclose(dists[0], wd, rtol=1e-4, atol=1e-4)
    assert idx.ivf_stats()["dispatches"] == 2 * len(q)


def test_a_batch_answers_like_its_queries_alone(tmp_path, monkeypatch):
    monkeypatch.setattr(plan_mod, "PROBED_ROW_COST", 0.0)
    vecs = _clustered(3000)
    tpu.set_ivf_config(_ivf(nlist=16, top_p=4))
    idx = _index(tmp_path / "b", exactTopK=True)
    idx.add_batch(np.arange(3000), vecs)
    idx.flush()
    q = vecs[:7] + np.float32(0.01)
    ids, d = idx.search_by_vectors(q, 5)           # b = 7, padded to 16
    for i in range(7):
        one = idx.search_by_vectors(q[i][None], 5)
        assert ids[i].tolist() == one[0][0].tolist()
        np.testing.assert_array_equal(d[i], one[1][0])


# -- 2. the choice by bytes -----------------------------------------------------


def _view(n, nlist, cap_p, top_p, live=None, gathered=False):
    class _Cfg:
        flat_search_cutoff = 40000
        exact_topk = True

    class _Programs:
        def kernel_serves(self, *shape):
            return False

    class _Kernels:
        _gmin_broken = False

    return PlanView(
        config=_Cfg(), metric="cosine", programs=_Programs(),
        kernels=_Kernels(), component="test", n=n, live=live or n, dim=768,
        ndev=1, slab=n, fill=n, itemsize=4, compressed=False,
        ivf_meta=(nlist, cap_p), ivf_probe=lambda k: (top_p, 0),
        ivf_gathered=gathered)


@pytest.mark.parametrize("b, probed", [(1, True), (4, True), (16, False)])
def test_a_gathered_layout_pays_a_gathered_rows_price(b, probed):
    """A bucket table's probe gathers its rows by slot, 7 streamed rows a
    row where a tile read in place costs 2.8 (the parent's served program on
    the chip: 0.757 ms a query for the flat scan's 4.45): it serves one
    query and four, and a batch of 16, which tiles still probe, takes the
    flat program."""
    nlist, cap_p, top_p = 4096, 384, 64
    n = nlist * cap_p
    view = _view(n, nlist, cap_p, top_p, live=1_000_000, gathered=True)
    p = plan_search(view, b, b, 10)
    assert (p.ivf is not None) is probed and p.ivf_declined is not probed
    assert probed_reads_less(b, 1, top_p, cap_p, nlist, n,
                             gathered=True) is probed
    assert probed_reads_less(b, 1, top_p, cap_p, nlist, n)   # as tiles


def test_plan_takes_the_probed_program_below_the_crossover_and_the_flat_above():
    nlist, cap_p, top_p = 4096, 384, 64
    n = nlist * cap_p
    view = _view(n, nlist, cap_p, top_p, live=1_000_000)
    # the crossover in queries: where b x top_p x cap_p + nlist passes n
    cross = (n - nlist) / (plan_mod.PROBED_ROW_COST * top_p * cap_p)
    below = max(b for b in tpu._B_BUCKETS if b < cross)
    above = min(b for b in tpu._B_BUCKETS if b > cross)
    p = plan_search(view, below, below, 10)
    assert p.ivf == (top_p, 0) and not p.ivf_declined
    # `rows` is what the program reads: every padded query's own tiles and
    # the centroids once (PR 44: it was one query's, whatever the width)
    assert p.rows == below * top_p * cap_p + nlist and p.program is None
    assert p.extra["ivf_rows_read"] == p.rows
    assert p.extra["ivf_base_rows"] == 1_000_000
    p = plan_search(view, above, above, 10)
    assert p.ivf is None and p.ivf_declined
    assert p.tier == costmodel.TIER_EXACT and p.rows == n
    assert p.program == "scan" and p.extra is None
    # one query always probes; the cell's batch of 256 never does
    assert probed_reads_less(1, 1, top_p, cap_p, nlist, n)
    assert not probed_reads_less(256, 1, top_p, cap_p, nlist, n)
    # a layout whose probe covers the store is the flat program's
    assert not probed_reads_less(1, 1, nlist, cap_p, nlist, n)


def test_declined_dispatches_are_counted_and_answer_from_the_flat_program(
        tmp_path):
    vecs = _clustered(3000)
    tpu.set_ivf_config(_ivf(nlist=16, top_p=2))
    idx = _index(tmp_path / "d")
    idx.add_batch(np.arange(3000), vecs)
    idx.flush()
    nlist, cap_p, _ = idx._ivf_meta
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    window = perf.configure(perf.PerfWindow())
    one = idx.search_by_vectors_async(vecs[:1], 5)
    one()
    assert one.plan.ivf is not None and idx.scan_programs.ivf_declined == 0
    window.record_dispatch(one.shape)
    wide = idx.search_by_vectors_async(vecs[:64], 5)   # 64 x 2 x cap_p > n
    got = wide()
    assert wide.plan.ivf is None and wide.plan.ivf_declined
    assert wide.plan.program in ("scan", "gmin")   # a full-store program
    assert idx.scan_programs.ivf_declined == 1
    assert idx.ivf_stats()["dispatches"] == 1
    assert got[0][:, 0].tolist() == list(range(64))
    window.record_dispatch(wide.shape)
    block = window.summary()["ivf"]
    assert block == {
        "dispatches": 1, "probed_rows": 2 * cap_p + nlist,
        "base_rows": 3000, "top_p": 2, "nlist": nlist, "cap_p": cap_p,
        "padding_share": round(1.0 - 3000 / (nlist * cap_p), 4)}


# -- 3. the layout is durable state ---------------------------------------------


def _trained_on_disk(path, n=3000, **ivf):
    vecs = _clustered(n, seed=5)
    tpu.set_ivf_config(_ivf(nlist=16, top_p=4, **ivf))
    idx = _index(path, persist=True)
    for s in range(0, n, 1000):
        idx.add_batch(np.arange(s, s + 1000), vecs[s:s + 1000])
    return idx, vecs


def _train_spans():
    return [s for t in tracing.get_tracer().snapshot()
            for s in _walk(t["root"]) if s["name"] == "ivf.train"]


def _walk(span):
    yield span
    for c in span.get("children", ()):
        yield from _walk(c)


def test_a_training_inside_a_sampled_write_is_a_span_with_its_pieces(
        tmp_path):
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    vecs = _clustered(1000)
    tpu.set_ivf_config(_ivf())
    idx = _index(tmp_path / "t")
    with tracing.request("rest", "batch_objects"):
        idx.add_batch(np.arange(1000), vecs)
    (span,) = _train_spans()
    assert span["attrs"] == {"rows": 1000, "nlist": 8,
                             "cap_p": idx._ivf_meta[1]}
    assert [c["name"] for c in span["children"]] == [
        "ivf.train." + p for p in ("fit", "assign", "layout", "upload",
                                   "persist")]
    assert sum(c["duration_ms"] for c in span["children"]) \
        <= span["duration_ms"] + 1.0


def test_a_clean_restart_reads_the_layout_and_trains_nothing(tmp_path):
    idx, vecs = _trained_on_disk(tmp_path / "s")
    assert idx._ivf_trains >= 1 and os.path.exists(idx._ivf_path)
    idx.delete(7, 8)
    idx.add_batch(np.arange(5000, 5100), vecs[:100] + np.float32(0.25))
    q = vecs[100:120] + np.float32(0.01)
    before = [idx.search_by_vectors(x[None], 5) for x in q]
    meta, slots = idx._ivf_meta, dict(idx._doc_to_slot)
    idx.shutdown()
    assert not idx._ivf_unsaved

    tracing.configure(tracing.Tracer(sample_rate=1.0))
    again = _index(tmp_path / "s", persist=True)
    again.post_startup()
    assert again._ivf_trains == 0 and again._ivf_tiled
    assert again._ivf_meta == meta and again._doc_to_slot == slots
    assert again.health()["ivf"]["restore"] == {
        "placed": 3098, "assigned": 0}
    assert "ivf" in again.last_restore["stages"]
    for x, want in zip(q, before):
        with tracing.request("grpc", "Search"):
            got = again.search_by_vectors(x[None], 5)
        assert got[0].tolist() == want[0].tolist()
        np.testing.assert_array_equal(got[1], want[1])
    assert len(tracing.get_tracer().snapshot()) == len(q)
    # the first search ran the probed program, and no training ran before
    # or inside it
    assert again.ivf_stats()["dispatches"] == len(q)
    assert again._ivf_trains == 0 and _train_spans() == []


def test_killed_after_the_publish_the_layout_is_there_and_answers_as_before(
        tmp_path):
    """No shutdown: the file is the last training's, the rows written after
    it are assigned by the restart, on the host, from the same centroids."""
    idx, vecs = _trained_on_disk(tmp_path / "k")
    gen = idx._ivf_gen
    idx.add_batch(np.arange(5000, 5040), vecs[:40] + np.float32(0.25))
    assert idx._ivf_gen == gen and idx._ivf_unsaved   # no training since
    q = vecs[200:215] + np.float32(0.01)
    before = [idx.search_by_vectors(x[None], 5) for x in q]
    idx._log.flush()           # the acknowledged writes are in the log
    idx._log.close()           # ...and the process is gone

    again = _index(tmp_path / "k", persist=True)
    again.post_startup()
    assert again._ivf_trains == 0 and again._ivf_gen == gen
    assert again.health()["ivf"]["restore"] == {
        "placed": 3000, "assigned": 40}
    assert again.live == 3040
    for x, want in zip(q, before):
        got = again.search_by_vectors(x[None], 5)
        assert got[0].tolist() == want[0].tolist()
        np.testing.assert_array_equal(got[1], want[1])


def test_a_restart_without_a_layout_trains_inside_the_restore(tmp_path):
    idx, vecs = _trained_on_disk(tmp_path / "m")
    idx.shutdown()
    os.remove(idx._ivf_path)
    again = _index(tmp_path / "m", persist=True)
    # trained already, before post_startup and before any search
    assert again._ivf_trains == 1 and again._ivf_tiled
    assert again.last_restore["stages"]["ivf"] > 0
    again.post_startup()
    ids, _ = again.search_by_vectors(vecs[9][None], 1)
    assert ids[0, 0] == 9 and again._ivf_trains == 1
    assert os.path.exists(again._ivf_path)


def test_a_restart_with_the_plane_off_ignores_the_layout(tmp_path):
    idx, vecs = _trained_on_disk(tmp_path / "o")
    idx.shutdown()
    tpu.set_ivf_config(None)
    again = _index(tmp_path / "o", persist=True)
    again.post_startup()
    assert not again._ivf_tiled and again._ivf_meta is None
    assert again.n == again.live == 3000
    ids, _ = again.search_by_vectors(vecs[9][None], 1)
    assert ids[0, 0] == 9


# -- 4. writes into the layout, and what the device holds -----------------------


def test_an_import_in_10000_row_batches_leaves_every_row_in_one_partition(
        tmp_path):
    """From empty to 20 x IVF_MIN_N, the trigger's own schedule."""
    min_n, n = 2000, 40000
    vecs = _clustered(n, seed=11, centers=256)
    tpu.set_ivf_config(_ivf(nlist=0, top_p=0, min_n=min_n))
    idx = _index(tmp_path / "imp", "cosine")
    for s in range(0, n, 10000):
        idx.add_batch(np.arange(s, s + 10000), vecs[s:s + 10000])
    idx.flush()
    # a training at 10k, 20k and 30k (each 1.5x the last), larger tiles
    # wherever a batch found fewer free slots than it has rows
    assert 4 <= idx._ivf_trains <= 6 and idx._ivf_trained_n == 30000
    snap = idx._read_snapshot()[0]
    nlist, cap_p, _ = snap.ivf_meta
    held = np.flatnonzero(~snap.host_tombs[: snap.n])
    assert sorted(snap.slot_to_doc[held].tolist()) == list(range(n))
    assert len(set(idx._doc_to_slot.values())) == n
    fills = np.bincount(held // cap_p, minlength=nlist)
    np.testing.assert_array_equal(idx._ivf_free_n, cap_p - fills)
    # one copy of the rows: no second row-sized component beside the store
    comps = idx._memory_components()
    row_sized = [c for c, b in comps.items() if b >= comps["store"] // 4]
    assert row_sized == ["store"] and "ivf_buckets" not in comps
    assert comps["store"] == snap.capacity * DIM * 4
    # and they are all found
    ids, _ = idx.search_by_vectors(vecs[-3:], 1)
    assert ids[:, 0].tolist() == [n - 3, n - 2, n - 1]


def test_a_full_partition_spills_to_the_next_nearest_and_a_full_layout_grows(
        tmp_path):
    vecs = _clustered(1200, seed=2, centers=8)
    tpu.set_ivf_config(_ivf(nlist=8, top_p=8, retrain_growth=100.0))
    idx = _index(tmp_path / "full", exactTopK=True)
    idx.add_batch(np.arange(600), vecs[:600])
    idx.flush()
    nlist, cap_p, gen = idx._ivf_meta
    # one point, over and over: its partition fills, then its neighbours
    # (100 rows: under the growth that would make the tiles anew)
    hot = np.repeat(vecs[:1], 100, axis=0)
    idx.add_batch(np.arange(10000, 10000 + len(hot)), hot)
    idx.flush()
    assert idx._ivf_gen == gen                    # spilled, not retrained
    parts = {idx._doc_to_slot[d] // cap_p
             for d in range(10000, 10000 + len(hot))}
    assert len(parts) >= 2 and int(idx._ivf_free_n.min()) == 0
    ids, d = idx.search_by_vectors(vecs[:1], 200)
    assert set(range(10000, 10000 + len(hot))) <= set(ids[0].tolist())
    # more rows than all the tiles hold: a new layout with room, at once
    room = int(idx._ivf_free_n.sum())
    more = _clustered(room + 50, seed=4, centers=8)
    idx.add_batch(np.arange(20000, 20000 + len(more)), more)
    idx.flush()
    assert idx._ivf_gen == gen + 1 and idx._ivf_meta[1] > cap_p
    assert idx.live == 600 + len(hot) + len(more)
    assert len(set(idx._doc_to_slot.values())) == idx.live


def test_compress_turns_the_tiles_into_a_bucket_table_over_the_same_slots(
        tmp_path, monkeypatch):
    monkeypatch.setattr(plan_mod, "GATHERED_ROW_COST", 0.0)  # probed, always
    vecs = np.random.default_rng(3).integers(
        -100, 100, (900, DIM)).astype(np.float32)
    tpu.set_ivf_config(_ivf())
    idx = _index(tmp_path / "c", persist=True, exactTopK=True)
    idx.add_batch(np.arange(900), vecs)
    idx.flush()
    assert idx._ivf_tiled and os.path.exists(idx._ivf_path)
    slots = dict(idx._doc_to_slot)
    idx.update_user_config(parse_and_validate_config("hnsw_tpu", {
        "distance": "l2-squared", "exactTopK": True,
        "pq": {"enabled": True, "trainingLimit": 256, "segments": 4,
               "centroids": 16}}))
    assert idx.compressed and not idx._ivf_tiled
    assert idx._doc_to_slot == slots and not os.path.exists(idx._ivf_path)
    buckets = np.asarray(idx._read_snapshot()[0].ivf_buckets)
    assert sorted(buckets[buckets >= 0].tolist()) == sorted(slots.values())
    ids, _ = idx.search_by_vectors(vecs[:4], 1)
    assert ids[:, 0].tolist() == [0, 1, 2, 3]
    assert idx.ivf_stats()["dispatches"] == 1

