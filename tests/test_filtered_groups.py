"""A group of slots, each under its own filter, in a bounded number of
dispatches (index/tpu.py search_by_vectors_multi_async and what carries it:
the shard's one `filter` phase, the traverser's grouping, gRPC BatchSearch).
Every slot's answer is held to numpy brute force under its own mask and to the
per-slot path (one filter a dispatch), on seeded rows and tags."""

import json
import urllib.request
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import new_vector_index
from weaviate_tpu.index import tpu as tpu_index
from weaviate_tpu.monitoring import costmodel, perf, tracing
from weaviate_tpu.storage.bitmap import Bitmap

N, DIM, K, DOC0 = 6000, 48, 10, 1000


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)


def _brute(vecs, q, mask, k):
    d = ((vecs.astype(np.float64) - q[None].astype(np.float64)) ** 2).sum(1)
    d = np.where(mask, d, np.inf)
    order = np.argsort(d, kind="stable")[:k]
    order = order[np.isfinite(d[order])]
    return order, d[order]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    rng = np.random.default_rng(30)
    vecs = rng.standard_normal((N, DIM)).astype(np.float32)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = new_vector_index(cfg, str(tmp_path_factory.mktemp("idx")), "s")
    idx.add_batch(np.arange(N) + DOC0, vecs)
    yield idx, vecs, rng
    idx.shutdown()


@pytest.fixture
def small_launch(monkeypatch):
    """At a test's size a scan of the whole slab costs less than one more
    program's launch and its words are a few bytes, so every plan is the
    scan alone. With the launch made small, the host slow and a gathered row
    as cheap as a streamed one, the hand-over lies inside the index, as it
    does at real sizes."""
    monkeypatch.setattr(costmodel, "LAUNCH_S", 1e-6)
    monkeypatch.setattr(costmodel, "HOST_BYTES_PER_S", 2e7)
    monkeypatch.setattr(costmodel, "GATHER_ROW_SLOWDOWN", 1.0)


@pytest.fixture(params=["native", "no_library"])
def library(request, monkeypatch):
    """A group's device operands by the native pass, and by numpy where
    the library is absent (index/group_inputs.py): the same answers."""
    from weaviate_tpu.storage import lsm_native

    assert lsm_native.available()
    if request.param == "no_library":
        monkeypatch.setattr(lsm_native, "_load", lambda: None)
    return request.param


def _allow(rows):
    return Bitmap(np.asarray(rows, np.int64) + DOC0)


def _hand_over(idx) -> int:
    """The largest filter the plan still gathers when it is alone in a
    group of small filters (the hand-over point of this index's size)."""
    snap = idx._read_snapshot()[0]
    top = tpu_index.gather_max_rows(snap.dim, snap.capacity)
    best = 0
    for m in range(64, N + 1, 64):
        sizes = [8] * 7 + [m]
        plan, _ = costmodel.plan_filtered_group(
            sizes, [tpu_index._gather_row_bucket(x) for x in sizes], snap.n,
            snap.capacity, snap.dim * 4, top)
        if not plan[-1]:
            best = m
    return best


def _check(idx, vecs, q, allows, masks, k=K):
    fin = idx.search_by_vectors_multi_async(q, k, allows)
    ids, dists = fin()
    for i, (allow, mask) in enumerate(zip(allows, masks)):
        want, want_d = _brute(vecs, q[i], mask, k)
        got = np.isfinite(dists[i])
        assert got.sum() == len(want) == min(k, int(mask.sum())), i
        assert np.array_equal(ids[i][got].astype(np.int64) - DOC0, want), i
        np.testing.assert_allclose(dists[i][got], want_d, rtol=1e-5, atol=1e-6)
        one_i, one_d = idx.search_by_vectors(q[i:i + 1], k, allow)
        keep = np.isfinite(one_d[0])
        assert np.array_equal(one_i[0][keep], ids[i][got]), i
        # the per-slot scan's own matmul form is a few ulp off near zero
        np.testing.assert_allclose(one_d[0][keep], dists[i][got],
                                   rtol=1e-4, atol=1e-5)
    return fin


def test_every_size_of_filter_in_one_group(corpus, small_launch, library):
    """Allowed rows 0, 1, under k, around the row buckets, one under and one
    over the hand-over point, every row; the same filter twice; a slot
    without a filter."""
    idx, vecs, rng = corpus
    edge = _hand_over(idx)
    assert 128 < edge < N, edge      # both tiers serve at this size
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    window = perf.configure(perf.PerfWindow(window_s=60.0))
    sizes = [0, 1, K - 3, 127, 128, 129, 512, 513, edge - 1, edge,
             edge + 1, min(edge + 64, N), N]
    allows, masks = [], []
    for m in sizes:
        rows = np.sort(rng.choice(N, m, replace=False))
        mask = np.zeros(N, bool)
        mask[rows] = True
        allows.append(_allow(rows))
        masks.append(mask)
    allows += [allows[4], None]
    masks += [masks[4], np.ones(N, bool)]
    q = vecs[rng.integers(0, N, len(allows))] \
        + 0.05 * rng.standard_normal((len(allows), DIM)).astype(np.float32)
    fin = _check(idx, vecs, q, allows, masks)
    tiers = {s.tier for s in fin.shapes}
    assert {costmodel.TIER_GATHER, costmodel.TIER_EXACT} <= tiers
    built = window.summary()["group_inputs"]
    assert built["groups"] == 1 and built["lists"] == len(sizes)
    assert built["native"] == (library == "native")
    assert built["fallback_reasons"] == (
        {} if library == "native" else {"no_library": 1})


def test_no_cliff_at_the_old_cut_off_nor_at_the_hand_over():
    """The deployment's geometry on the chip's peaks (2M x 192 f32, capacity
    2^21, a group of 256): a slot a row under and a row over the old
    constant (flat_search_cutoff, 40,000) is served alike, and where the
    model itself hands a slot from the gather to the scan the group's
    modelled cost moves by a few percent."""
    n, cap, row = 2_000_000, 1 << 21, 192 * 4
    top = tpu_index.gather_max_rows(192, cap)
    v5e = costmodel.TPU_V5E

    def serve(m):
        sizes = [90] * 200 + [1500] * 40 + [200_000] * 15 + [m]
        buckets = [tpu_index._gather_row_bucket(x) for x in sizes]
        return costmodel.plan_filtered_group(sizes, buckets, n, cap, row,
                                             top, backend=v5e)

    (under, c_under), (over, c_over) = serve(39_999), serve(40_001)
    assert under == over and c_under == c_over
    assert not any(under[:240]) and all(under[240:255])
    last = [serve(m) for m in (128, 512, 2048, 8192, 32768, 131072)]
    flips = [i for i in range(1, len(last))
             if last[i][0][-1] != last[i - 1][0][-1]]
    assert len(flips) == 1          # gathered below one size, scanned above
    at = flips[0]
    assert abs(last[at][1] - last[at - 1][1]) <= 0.1 * last[at - 1][1]


def test_a_tombstoned_row_inside_a_filter_and_a_write_between_searches(
        tmp_path, library):
    rng = np.random.default_rng(31)
    vecs = rng.standard_normal((N, DIM)).astype(np.float32)
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = new_vector_index(cfg, str(tmp_path), "s")
    try:
        idx.add_batch(np.arange(N) + DOC0, vecs)
        rows = [np.sort(rng.choice(N, m, replace=False))
                for m in (40, 700, 5000)]
        allows = [_allow(r) for r in rows]
        masks = []
        for r in rows:
            m = np.zeros(N, bool)
            m[r] = True
            masks.append(m)
        # each query IS a row of its filter: that row must win, then die
        q = np.stack([vecs[r[0]] for r in rows])
        fin = _check(idx, vecs, q, allows, masks)
        assert [int(i) - DOC0 for i in fin()[0][:, 0]] == [r[0] for r in rows]
        idx.delete(*[int(r[0]) + DOC0 for r in rows])
        for m, r in zip(masks, rows):
            m[r[0]] = False
        fin = _check(idx, vecs, q, allows, masks)
        assert all(int(i) - DOC0 != r[0]
                   for i, r in zip(fin()[0][:, 0], rows))
        # a write between two searches: the SAME allowList objects resolve
        # against the new slot layout (their cached slots are keyed by it)
        more = rng.standard_normal((50, DIM)).astype(np.float32)
        idx.add_batch(np.arange(50) + DOC0 + N, more)
        wider = [Bitmap(np.concatenate([a.to_array(),
                                        np.arange(50, dtype=np.uint64)
                                        + DOC0 + N])) for a in allows]
        all_vecs = np.concatenate([vecs, more])
        masks2 = [np.concatenate([m, np.ones(50, bool)]) for m in masks]
        q2 = np.stack([more[0], more[1], more[2]])
        ids, dists = idx.search_by_vectors_multi_async(q2, K, wider)()
        for i in range(3):
            want, _ = _brute(all_vecs, q2[i], masks2[i], K)
            assert np.array_equal(ids[i].astype(np.int64) - DOC0, want)
        # and the old objects still answer for the old rows alone
        _check(idx, all_vecs, q, allows,
               [np.concatenate([m, np.zeros(50, bool)]) for m in masks])
    finally:
        idx.shutdown()


def test_docs_that_do_not_ascend_with_their_slots(tmp_path):
    """A library caller that adds out of order and re-adds a doc: the
    filter's rows are found through the snapshot's sorted order, and the
    re-added doc's dead slot is masked on the device."""
    rng = np.random.default_rng(32)
    n = 3000
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    docs = rng.permutation(n) + DOC0
    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = new_vector_index(cfg, str(tmp_path), "s")
    try:
        idx.add_batch(docs, vecs)
        moved = rng.standard_normal(DIM).astype(np.float32)
        idx.add(int(docs[7]), moved)          # a second slot for docs[7]
        vecs = vecs.copy()
        vecs[7] = moved
        snap = idx._read_snapshot()[0]
        assert not snap.docs_ascending
        by_doc = {int(d): i for i, d in enumerate(docs)}
        for m in (1, 9, 300, n):
            pick = np.sort(rng.choice(n, m, replace=False))
            pick[0] = 7
            pick = np.unique(pick)
            allow = Bitmap(docs[pick])
            q = np.stack([moved, vecs[pick[-1]]])
            ids, dists = idx.search_by_vectors_multi_async(
                q, K, [allow, allow])()
            mask = np.zeros(n, bool)
            mask[pick] = True
            for i in range(2):
                want, want_d = _brute(vecs, q[i], mask, K)
                got = np.isfinite(dists[i])
                assert [by_doc[int(d)] for d in ids[i][got]] == want.tolist()
                np.testing.assert_allclose(dists[i][got], want_d,
                                           rtol=1e-5, atol=1e-6)
    finally:
        idx.shutdown()


def test_slot_words_are_the_packed_mask():
    from weaviate_tpu.storage.bitmap import pack_allow_words

    rng = np.random.default_rng(33)
    cap = 1 << 15
    for m in (0, 1, 31, 32, 33, 5000, cap):
        slots = np.sort(rng.choice(cap, m, replace=False)).astype(np.int32)
        mask = np.zeros(cap, bool)
        mask[slots] = True
        assert np.array_equal(tpu_index._slot_words(slots, cap),
                              pack_allow_words(mask, cap))


def test_a_filter_is_resolved_by_lookups_not_by_a_pass_over_the_rows(
        corpus, monkeypatch):
    """Neither tier's input side asks the allowList about every live row."""
    idx, vecs, rng = corpus

    def no_pass(self, doc_ids):
        raise AssertionError("a membership pass over the live rows")

    monkeypatch.setattr(Bitmap, "contains_array", no_pass)
    monkeypatch.setattr("weaviate_tpu.storage.bitmap.allowed_mask", no_pass)
    rows = np.sort(rng.choice(N, 5500, replace=False))
    q = vecs[:1]
    ids, _ = idx.search_by_vectors(q, K, _allow(rows[:50]))      # gather
    assert ids.shape[0] == 1
    idx.config.flat_search_cutoff, keep = 10, idx.config.flat_search_cutoff
    try:
        idx.search_by_vectors(q, K, _allow(rows))                # masked scan
    finally:
        idx.config.flat_search_cutoff = keep
    idx.search_by_vectors_multi_async(
        np.repeat(q, 2, 0), K, [_allow(rows[:50]), _allow(rows)])()


# -- the served path ----------------------------------------------------------

ROWS, TAGS = 4000, 300


def _tagged_app(tmp_path, tweak=None):
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.tracing.enabled = True
    cfg.tracing.sample_rate = 1.0
    if tweak is not None:
        tweak(cfg)
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Tagged", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tags", "dataType": ["int[]"]}]})
    rng = np.random.default_rng(34)
    vecs = rng.standard_normal((ROWS, 32)).astype(np.float32)
    p = 1.0 / np.arange(1, TAGS + 1)
    bags = [sorted(set(rng.choice(TAGS, rng.integers(1, 7), p=p / p.sum())
                       .tolist())) for _ in range(ROWS)]
    app.db.get_index("Tagged").put_batch([
        StorObj(class_name="Tagged", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tags": bags[i]}, vector=vecs[i])
        for i in range(ROWS)])
    return app, vecs, bags, rng


def _where(tags):
    one = [{"path": ["tags"], "operator": "Equal", "valueInt": int(t)}
           for t in tags]
    return one[0] if len(one) == 1 else {"operator": "And", "operands": one}


def _batch(sv, reqs):
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb

    class Ctx:
        def abort(self, *a):
            raise AssertionError(a)

        def invocation_metadata(self):
            return ()

        def set_trailing_metadata(self, *a):
            pass

    got = sv.BatchSearch(pb.BatchSearchRequest(requests=reqs), Ctx())
    return pb.BatchSearchReply.FromString(
        got if isinstance(got, (bytes, bytearray)) else got.SerializeToString())


# one gather program a row bucket the index serves at this size, one masked
# scan, one plain scan for slots without a filter
def _dispatch_bound(app) -> int:
    snap = next(iter(app.db.get_index("Tagged").shards.values())) \
        .vector_index._read_snapshot()[0]
    top, buckets, r = tpu_index.gather_max_rows(snap.dim, snap.capacity), 0, 128
    while r <= top:
        buckets, r = buckets + 1, r * 4
    return buckets + 2


def test_grpc_batch_of_256_filters_is_a_bounded_number_of_dispatches(
        tmp_path, small_launch):
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server import RestServer
    from weaviate_tpu.server.grpc_server import SearchServicer

    app, vecs, bags, rng = _tagged_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        sv = SearchServicer(app)
        picks = rng.integers(0, ROWS, 256)
        asks = []
        for i, row in enumerate(picks):   # 256 slots, 256 different filters
            bag = bags[row]
            asks.append(tuple(bag[:2]) if len(bag) > 1 and i % 2
                        else (bag[0], int(TAGS + i)) if i % 17 == 0
                        else (bag[0],))
        asks = [a if a not in asks[:i] else a + (a[0],)
                for i, a in enumerate(asks)]
        reqs = [pb.SearchRequest(
            class_name="Tagged", limit=K, where_json=json.dumps(_where(a)),
            near_vector=pb.NearVectorParams(vector=vecs[row].tolist()))
            for a, row in zip(asks, picks)]
        reply = _batch(sv, reqs)
        disallowed = 0
        for a, row, one in zip(asks, picks, reply.replies):
            assert not one.error_message
            mask = np.array([all(t in bag for t in a) for bag in bags])
            want, want_d = _brute(vecs, vecs[row], mask, K)
            got = [uuidlib.UUID(x.id).int - 1 for x in one.results]
            disallowed += sum(not mask[g] for g in got)
            assert got == want.tolist()
            np.testing.assert_allclose([x.distance for x in one.results],
                                       want_d, rtol=1e-5, atol=1e-6)
        assert disallowed == 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/perf", timeout=30) as r:
            body = json.loads(r.read())
        assert 1 <= body["dispatches"] <= _dispatch_bound(app), body
        assert body["rows"] >= 200     # slots whose filter allows no row ride no dispatch
        assert body["rows"] / body["dispatches"] > 20
        assert set(body["tier_rows"]) == set(body["tiers"])
        gathered = body["tier_rows"].get(costmodel.TIER_GATHER, 0)
        assert 0 < gathered < 256 * ROWS
        if costmodel.TIER_EXACT in body["tiers"]:
            assert body["tier_rows"][costmodel.TIER_EXACT] == \
                body["tiers"][costmodel.TIER_EXACT] * ROWS
        flt = body["phases"]["filter"]
        assert flt["samples"] == 1     # one `filter` phase the group
    finally:
        srv.stop()
        app.shutdown()


def test_mixed_slots_equal_filters_and_one_bad_filter(tmp_path):
    """Slots with and without a filter share a request; equal filters are
    evaluated once; a slot whose filter is wrong carries its own error and
    the others are served."""
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import SearchServicer

    app, vecs, bags, rng = _tagged_app(tmp_path)
    try:
        shard = next(iter(app.db.get_index("Tagged").shards.values()))
        built = []
        real = shard.build_allow_list
        shard.build_allow_list = \
            lambda flt, memo=None: built.append(flt) or real(flt, memo)
        sv = SearchServicer(app)
        rows = rng.integers(0, ROWS, 24)
        reqs, asks = [], []
        for i, row in enumerate(rows):
            ask = None if i % 3 == 0 else (bags[rows[i % 4]][0],)
            asks.append(ask)
            r = pb.SearchRequest(
                class_name="Tagged", limit=K,
                near_vector=pb.NearVectorParams(vector=vecs[row].tolist()))
            if ask is not None:
                r.where_json = json.dumps(_where(ask))
            reqs.append(r)
        reqs.append(pb.SearchRequest(
            class_name="Tagged", limit=K,
            where_json=json.dumps({"path": ["nope"], "operator": "Equal",
                                   "valueInt": 1}),
            near_vector=pb.NearVectorParams(vector=vecs[0].tolist())))
        reply = _batch(sv, reqs)
        assert len(built) == len({a for a in asks if a is not None}) + 1
        for ask, row, one in zip(asks, rows, reply.replies):
            assert not one.error_message
            mask = np.array([ask is None or ask[0] in bag for bag in bags])
            want, _ = _brute(vecs, vecs[row], mask, K)
            assert [uuidlib.UUID(x.id).int - 1 for x in one.results] \
                == want.tolist()
        assert "nope" in reply.replies[-1].error_message
    finally:
        app.shutdown()


def _coalescer_on(cfg):
    cfg.coalescer.enabled = True


def _group_dispatch_fails(cfg):
    cfg.robustness.fault_injection = \
        "db.shard.search_group:device_error:times=inf"


@pytest.mark.parametrize("tweak", [_coalescer_on, _group_dispatch_fails],
                         ids=["coalescer_on", "group_dispatch_fails"])
def test_where_no_group_is_formed_every_filtered_slot_is_served_by_itself(
        tmp_path, tweak):
    """The per-slot path on inputs that really take it: a group whose
    dispatch fails before the device is reached falls back slot by slot
    (same answers, one query a dispatch, no host fallback and the breaker
    closed). With the coalescer on (PR 44: the default) a wide group keeps
    the group path, whatever the coalescer is, and it is a request of ONE
    filtered slot that takes the coalescer's per-signature lane."""
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import SearchServicer
    from weaviate_tpu.serving import robustness

    app, vecs, bags, rng = _tagged_app(tmp_path, tweak)
    try:
        sv = SearchServicer(app)
        rows = rng.integers(0, ROWS, 12)
        reqs = [pb.SearchRequest(
            class_name="Tagged", limit=K,
            where_json=json.dumps(_where((bags[row][0],))),
            near_vector=pb.NearVectorParams(vector=vecs[row].tolist()))
            for row in rows]
        reply = _batch(sv, reqs)
        for row, one in zip(rows, reply.replies):
            assert not one.error_message
            mask = np.array([bags[row][0] in bag for bag in bags])
            want, _ = _brute(vecs, vecs[row], mask, K)
            assert [uuidlib.UUID(x.id).int - 1 for x in one.results] \
                == want.tolist()
        s = perf.get_window().summary()
        if tweak is _group_dispatch_fails:
            assert s["dispatches"] == s["rows"] == 12
        else:
            # one group: its slots share their dispatches, and none of them
            # went through a lane
            assert s["rows"] == 12 and s["dispatches"] < 12
            assert s["group_inputs"]["groups"] == 1
            assert app.coalescer.stats()["dispatches"] == 0
            # ONE filtered slot a request: the first sighting of its
            # signature goes direct, the second rides the signature's lane
            for _ in range(2):
                one = _batch(sv, reqs[:1]).replies[0]
                assert not one.error_message
                mask = np.array([bags[rows[0]][0] in bag for bag in bags])
                want, _ = _brute(vecs, vecs[rows[0]], mask, K)
                assert [uuidlib.UUID(x.id).int - 1 for x in one.results] \
                    == want.tolist()
            st = app.coalescer.stats()
            assert st["bypass"].get("cold_filter") == 1
            assert st["dispatches"] == 1 and st["requests"] == 1
        br = robustness.get_breaker()
        assert br is None or br.allow()
    finally:
        app.shutdown()
