"""Cross-request query coalescer (serving/coalescer.py) + its wiring.

The fixtures use SMALL-INTEGER-valued vectors on purpose: every distance is
then exact integer arithmetic in float32 regardless of accumulation order,
so a query's results are bit-identical whether it rides a 1-wide direct
dispatch or a coalesced [B, D] batch — which is exactly the contract these
tests pin (coalesced == uncoalesced, not merely close).
"""

import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config
from weaviate_tpu.entities.filters import LocalFilter
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.serving.coalescer import (
    CoalescerShutdownError,
    QueryCoalescer,
)
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 400, 16, 5


def _mk_app(tmp_path, enabled=True, window_ms=200.0, max_batch=256,
            max_request_rows=16, vecs=None):
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = enabled
    cfg.coalescer.window_ms = window_ms
    cfg.coalescer.max_batch = max_batch
    cfg.coalescer.max_request_rows = max_request_rows
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Co", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tag", "dataType": ["text"]}],
    })
    if vecs is None:
        rng = np.random.default_rng(11)
        vecs = rng.integers(-8, 8, (N, DIM)).astype(np.float32)
    idx = app.db.get_index("Co")
    idx.put_batch([
        StorObj(class_name="Co", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i])
        for i in range(N)])
    return app, idx, vecs


def _tie_free_queries(vecs, count, mask=None, depth=None):
    """Queries whose top-(K+8) exact distances (over `mask`ed docs) are all
    distinct. Integer-valued vectors make every distance exact in f32, but
    a TIE straddling the top-k boundary is resolved by selection order —
    which legitimately differs between a 1-wide and a coalesced dispatch —
    so the bit-identical comparison only stands on tie-free queries."""
    pool = vecs if mask is None else vecs[mask]
    depth = depth or K + 8
    out = []
    i = 0
    while len(out) < count:
        q = vecs[i] + 0.5
        i += 1
        d = np.sort(((pool - q) ** 2).sum(1))[:depth]
        if len(np.unique(d)) == len(d):
            out.append(q)
    return out


def _line_vecs():
    """Docs on an integer line: every pairwise distance to a x.25 query is
    unique AND exact in f32 — for the tests that need full-depth tie-free
    orderings (target-distance widening)."""
    v = np.zeros((N, DIM), np.float32)
    v[:, 0] = np.arange(N, dtype=np.float32)
    return v


def _rows(results):
    return [(r.obj.uuid, r.distance) for r in results]


def test_threaded_single_queries_bit_identical(tmp_path):
    """N concurrent single-query Gets through the serving path coalesce into
    shared dispatches AND return exactly what the direct path returns."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        queries = _tie_free_queries(vecs, 12)
        expected = [
            _rows(idx.object_vector_search(q, K)[0]) for q in queries]
        got = [None] * len(queries)
        barrier = threading.Barrier(len(queries))

        def run(i):
            barrier.wait()
            got[i] = _rows(app.traverser.get_class(GetParams(
                class_name="Co", near_vector={"vector": queries[i].tolist()},
                limit=K)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert got == expected
        st = app.coalescer.stats()
        assert st["requests"] == len(queries)
        # barrier-released threads land within one 200 ms window: the lane
        # must actually merge them (strictly fewer dispatches than requests)
        assert 1 <= st["dispatches"] < len(queries)
    finally:
        app.shutdown()


def test_deadline_flush_fires_under_low_load(tmp_path):
    """A lone request must not wait for a full bucket: the deadline window
    flushes a 1-deep lane."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=50.0)
    try:
        q = _tie_free_queries(vecs, 1)[0]
        t0 = time.monotonic()
        res = app.traverser.get_class(GetParams(
            class_name="Co", near_vector={"vector": q.tolist()}, limit=K))
        elapsed = time.monotonic() - t0
        assert _rows(res) == _rows(idx.object_vector_search(q, K)[0])
        st = app.coalescer.stats()
        assert st == {**st, "dispatches": 1, "requests": 1, "rows": 1}
        assert elapsed < 10.0  # deadline flush, not a hang
    finally:
        app.shutdown()


def test_full_bucket_flush_fires_under_high_load(tmp_path):
    """When a lane's rows fill the batch bucket it flushes IMMEDIATELY —
    long before a (deliberately huge) deadline window."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=30_000.0, max_batch=4,
                             max_request_rows=4)
    try:
        queries = _tie_free_queries(vecs, 4)
        expected = [_rows(idx.object_vector_search(q, K)[0]) for q in queries]
        got = [None] * 4
        barrier = threading.Barrier(4)

        def run(i):
            barrier.wait()
            got[i] = _rows(app.traverser.get_class(GetParams(
                class_name="Co", near_vector={"vector": queries[i].tolist()},
                limit=K)))

        threads = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=20)
        elapsed = time.monotonic() - t0
        assert got == expected
        assert elapsed < 20.0  # nowhere near the 30 s window
        st = app.coalescer.stats()
        assert st["dispatches"] == 1
        assert st["requests"] == 4
    finally:
        app.shutdown()


def test_oversize_request_bypasses_queue(tmp_path):
    """A request wider than max_request_rows takes the direct path (counted
    with reason=oversize) and still returns correct results."""
    app, idx, vecs = _mk_app(tmp_path, max_batch=8, max_request_rows=2)
    try:
        params = [GetParams(class_name="Co",
                            near_vector={"vector": q.tolist()},
                            limit=K)
                  for q in _tie_free_queries(vecs, 6)]
        res = app.traverser.get_class_batched(params)
        assert not any(isinstance(r, Exception) for r in res)
        for p, r in zip(params, res):
            direct = idx.object_vector_search(
                np.asarray(p.near_vector["vector"], np.float32), K)[0]
            assert _rows(r) == _rows(direct)
        st = app.coalescer.stats()
        assert st["bypass"].get("oversize", 0) >= 1
        assert st["dispatches"] == 0  # the whole group went direct
    finally:
        app.shutdown()


def test_unique_allowlist_filter_bypasses(tmp_path):
    """A filter with no stable signature (per-request allowList) can never
    share a lane: submit refuses it and counts the reason."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        flt = LocalFilter.from_dict(
            {"operator": "Equal", "path": ["tag"], "valueText": "even"})
        flt.to_dict = lambda: (_ for _ in ()).throw(TypeError("no sig"))
        assert app.coalescer.submit(shard, vecs[0], K, flt=flt) is None
        assert app.coalescer.stats()["bypass"].get("unique_allow_list") == 1
    finally:
        app.shutdown()


class _SpyLock:
    """Counts every acquisition of the wrapped index lock while delegating,
    so a test can pin that a code path is genuinely lock-free."""

    def __init__(self, inner):
        self.inner = inner
        self.count = 0

    def acquire(self, *a, **kw):
        self.count += 1
        return self.inner.acquire(*a, **kw)

    def release(self):
        return self.inner.release()

    def __enter__(self):
        self.count += 1
        return self.inner.__enter__()

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)


def test_filtered_coalesced_dispatch_never_takes_index_lock(tmp_path):
    """The acceptance spy for the snapshot read plane: a FILTERED coalesced
    dispatch rides the async two-phase path end to end — enqueue, device
    work, finalize, hydration — without a single acquisition of the
    per-index lock (pre-PR, filtered lanes fell back to the sync path that
    held it across the whole dispatch)."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        vidx = shard.vector_index

        def mk_flt():
            return LocalFilter.from_dict(
                {"operator": "Equal", "path": ["tag"], "valueText": "even"})

        queries = _tie_free_queries(vecs, 6, mask=np.arange(N) % 2 == 0)
        expected = [
            _rows(idx.object_vector_search(q, K, flt=mk_flt())[0])
            for q in queries]
        # warm: publishes the snapshot, seeds the filter-signature recency
        # (a cold signature would bypass) and the allowList cache
        app.traverser.get_class(GetParams(
            class_name="Co", near_vector={"vector": queries[0].tolist()},
            filters=mk_flt(), limit=K))
        base = app.coalescer.stats()
        spy = _SpyLock(vidx._lock)
        vidx._lock = spy
        try:
            got = [None] * len(queries)
            barrier = threading.Barrier(len(queries))

            def run(i):
                barrier.wait()
                got[i] = _rows(app.traverser.get_class(GetParams(
                    class_name="Co",
                    near_vector={"vector": queries[i].tolist()},
                    filters=mk_flt(), limit=K)))

            threads = [threading.Thread(target=run, args=(i,))
                       for i in range(len(queries))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            vidx._lock = spy.inner
        assert got == expected  # bit-identical through the lock-free path
        st = app.coalescer.stats()
        # the filtered requests went THROUGH the queue (no sync fallback,
        # no new bypasses) and actually merged into coalesced dispatches
        assert st["requests"] - base["requests"] == len(queries)
        assert st["bypass"] == base["bypass"]
        assert st["dispatches"] - base["dispatches"] < len(queries)
        assert spy.count == 0, (
            f"filtered coalesced dispatch acquired the index lock "
            f"{spy.count} time(s) — the snapshot read plane must be "
            "lock-free")
    finally:
        app.shutdown()


def test_shared_filter_lane_coalesces_and_matches_direct(tmp_path):
    """Filtered queries with the SAME filter signature share a lane once the
    signature is warm (a COLD first sighting goes direct — a one-off filter
    must not pay the window for a singleton lane); results equal the direct
    filtered path exactly."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        def mk_flt():
            # fresh object per request, same content — the serving shape
            return LocalFilter.from_dict(
                {"operator": "Equal", "path": ["tag"], "valueText": "even"})

        queries = _tie_free_queries(vecs, 8, mask=np.arange(N) % 2 == 0)
        expected = [
            _rows(idx.object_vector_search(q, K, flt=mk_flt())[0])
            for q in queries]

        # first sighting is cold: bypasses with zero queue hops
        warm = app.traverser.get_class(GetParams(
            class_name="Co", near_vector={"vector": queries[0].tolist()},
            filters=mk_flt(), limit=K))
        assert _rows(warm) == expected[0]
        assert app.coalescer.stats()["bypass"].get("cold_filter") == 1
        assert app.coalescer.stats()["requests"] == 0
        got = [None] * len(queries)
        barrier = threading.Barrier(len(queries))

        def run(i):
            barrier.wait()
            got[i] = _rows(app.traverser.get_class(GetParams(
                class_name="Co", near_vector={"vector": queries[i].tolist()},
                filters=mk_flt(), limit=K)))

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert got == expected
        for rows in got:  # the filter actually applied
            for u, _ in rows:
                assert (uuidlib.UUID(u).int - 1) % 2 == 0
        st = app.coalescer.stats()
        assert st["requests"] == len(queries)
        assert st["dispatches"] < len(queries)
    finally:
        app.shutdown()


def test_overflow_request_flushes_standing_lane_first(tmp_path):
    """A request that would push a lane past max_batch flushes the standing
    lane and starts fresh — no dispatch may exceed its padding bucket (that
    would compile a shape the direct path never uses)."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=30.0, max_batch=4, max_request_rows=4)
        try:
            w1 = co.submit(shard, vecs[:3], K)   # 3-row lane, queued
            # +4 would overflow the 4-row bucket: the standing 3-row lane
            # must flush AS-IS and this request fill a fresh lane (which is
            # itself full at 4 rows, so both dispatch despite the 30 s
            # window never expiring)
            w2 = co.submit(shard, vecs[3:7], K)
            r1, r2 = w1(), w2()
            assert len(r1) == 3 and len(r2) == 4
            st = co.stats()
            assert st["dispatches"] == 2
            assert st["rows"] == 7
            assert st["mean_rows_per_dispatch"] <= 4  # bucket never exceeded
        finally:
            co.shutdown()
    finally:
        app.shutdown()


def test_wrong_dim_request_fails_alone(tmp_path):
    """Dim is part of the lane key: a malformed-dimension request gets its
    own lane and fails by itself instead of poisoning the concatenated
    batch of its would-be lane-mates."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=0.05, max_batch=64, max_request_rows=4)
        try:
            good = [co.submit(shard, vecs[i], K) for i in range(3)]
            bad = co.submit(shard, np.zeros(DIM * 2, np.float32), K)
            for w in good:
                assert len(w()) == 1 and len(w()[0]) == K
            with pytest.raises(Exception):
                bad()
        finally:
            co.shutdown()
    finally:
        app.shutdown()


def test_dispatch_exception_wakes_every_waiter(tmp_path):
    """An injected dispatch failure must propagate to EVERY queued waiter —
    no request may hang on a dead batch."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=0.05, max_batch=64, max_request_rows=4)
        try:
            boom = RuntimeError("injected dispatch failure")

            def exploding(*a, **kw):
                raise boom

            shard.object_vector_search_async = exploding
            waiters = [co.submit(shard, vecs[i], K) for i in range(6)]
            assert all(w is not None for w in waiters)
            errs = []
            for w in waiters:
                with pytest.raises(RuntimeError) as ei:
                    w()
                errs.append(ei.value)
            assert all(e is boom for e in errs)
        finally:
            co.shutdown()
            del shard.object_vector_search_async  # restore the class method
    finally:
        app.shutdown()


def test_shutdown_wakes_queued_waiters(tmp_path):
    """Waiters queued behind a never-expiring window get a shutdown error
    instead of hanging."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=60.0, max_batch=64, max_request_rows=4)
        w = co.submit(shard, vecs[0], K)
        assert w is not None
        co.shutdown()
        with pytest.raises(CoalescerShutdownError):
            w()
        # post-shutdown admission refuses instead of queueing forever
        assert co.submit(shard, vecs[1], K) is None
        assert co.stats()["bypass"].get("shutdown") == 1
    finally:
        app.shutdown()


def test_disabled_by_config_is_true_noop(tmp_path):
    """enabled=False => no coalescer object anywhere on the read path (zero
    queue hops), results unchanged."""
    app, idx, vecs = _mk_app(tmp_path, enabled=False)
    try:
        assert app.coalescer is None
        assert app.explorer.coalescer is None
        assert app.explorer._coalesce_submit(idx, vecs[:1], K, None,
                                             False) is None
        q = vecs[3] + 0.5
        res = app.traverser.get_class(GetParams(
            class_name="Co", near_vector={"vector": q.tolist()}, limit=K))
        assert _rows(res) == _rows(idx.object_vector_search(q, K)[0])
    finally:
        app.shutdown()


def test_grpc_search_coalesces_across_requests(tmp_path):
    """End to end over real gRPC: concurrent single-query Searches coalesce
    and the replies equal the direct path byte for byte."""
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient

    app, idx, vecs = _mk_app(tmp_path)
    srv = GrpcServer(app, port=0, max_workers=16)
    srv.start()
    try:
        queries = _tie_free_queries(vecs, 8)
        expected = [_rows(idx.object_vector_search(q, K)[0]) for q in queries]
        got = [None] * len(queries)
        barrier = threading.Barrier(len(queries))

        def run(i):
            cl = SearchClient(f"127.0.0.1:{srv.port}")
            try:
                barrier.wait()
                rep = cl.search(pb.SearchRequest(
                    class_name="Co", limit=K,
                    near_vector=pb.NearVectorParams(
                        vector=queries[i].tolist())))
                got[i] = [(r.id, r.distance) for r in rep.results]
            finally:
                cl.close()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert got == expected
        assert app.coalescer.stats()["requests"] >= len(queries)
    finally:
        srv.stop()
        app.shutdown()


def test_rest_graphql_batch_concurrent_slots(tmp_path):
    """h_graphql_batch runs slots concurrently when coalescing is on; the
    envelope and results match the serial (disabled) path."""
    import json
    import urllib.request

    from weaviate_tpu.server.rest import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        rest_queries = _tie_free_queries(vecs, 4)
        gq = ("query($v: [Float]) { Get { Co(nearVector: {vector: $v}, "
              "limit: 5) { _additional { id distance } } } }")
        body = json.dumps([
            {"query": gq, "variables": {"v": q.tolist()}}
            for q in rest_queries
        ]).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/graphql/batch", data=body,
            headers={"Content-Type": "application/json"})
        out = json.loads(urllib.request.urlopen(req, timeout=30).read())
        assert len(out) == 4
        for q, one in zip(rest_queries, out):
            assert "errors" not in one, one
            hits = one["data"]["Get"]["Co"]
            direct = idx.object_vector_search(q, K)[0]
            assert [h["_additional"]["id"] for h in hits] == \
                [r.obj.uuid for r in direct]
    finally:
        srv.stop()
        app.shutdown()


def test_metrics_registered_and_observed(tmp_path):
    """The coalescer metric families exist in the app registry and a
    coalesced dispatch lands in them (occupancy, wait, depth)."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=30.0)
    try:
        app.traverser.get_class(GetParams(
            class_name="Co", near_vector={"vector": vecs[0].tolist()},
            limit=K))
        # the waiter wakes at result SCATTER, a few statements before the
        # dispatch thread books the lane and observes these histograms —
        # wait for the observation to land instead of racing it
        deadline = time.monotonic() + 5.0
        text = app.metrics.expose().decode()
        while "weaviate_coalescer_batch_requests_count 1.0" not in text \
                and time.monotonic() < deadline:
            time.sleep(0.001)
            text = app.metrics.expose().decode()
        assert "weaviate_coalescer_batch_requests_count 1.0" in text
        assert "weaviate_coalescer_batch_rows_count 1.0" in text
        assert "weaviate_coalescer_wait_ms_count 1.0" in text
        assert "weaviate_coalescer_queue_depth 0.0" in text
    finally:
        app.shutdown()


def test_target_distance_branch_is_batched_and_identical(tmp_path):
    """Satellite: Shard.object_vector_search(target_distance=...) routes all
    rows through batched dispatches and matches the per-row
    search_by_vector_distance results exactly."""
    app, idx, vecs = _mk_app(tmp_path, enabled=False, vecs=_line_vecs())
    try:
        shard = idx.single_local_shard()
        q = np.zeros((6, DIM), np.float32)
        q[:, 0] = np.array([3.25, 100.25, 250.25, 399.25, 17.25, 0.25])
        target = 120.0 ** 2  # wide enough to force a widening round
        calls = {"n": 0}
        orig = shard.vector_index.search_by_vectors_async

        def counting(*a, **kw):
            calls["n"] += 1
            return orig(*a, **kw)

        shard.vector_index.search_by_vectors_async = counting
        try:
            out = shard.object_vector_search(
                q, 50, None, target_distance=target)
        finally:
            del shard.vector_index.search_by_vectors_async
        per_row = [shard.vector_index.search_by_vector_distance(
            row, target, 50) for row in q]
        assert 1 <= calls["n"] < len(q)  # batched, not one chain per row
        for rows, (pids, pdists) in zip(out, per_row):
            assert [uuidlib.UUID(r.obj.uuid).int - 1 for r in rows] == \
                [int(i) for i in pids]
            assert [r.distance for r in rows] == pdists.tolist()
            assert all(r.distance <= target for r in rows)
    finally:
        app.shutdown()


def test_coalescer_config_env_parsing():
    from weaviate_tpu.config import ConfigError, load_config

    cfg = load_config({
        "QUERY_COALESCER_ENABLED": "true",
        "QUERY_COALESCER_WINDOW_MS": "3.5",
        "QUERY_COALESCER_MAX_BATCH": "64",
        "QUERY_COALESCER_MAX_REQUEST_ROWS": "8",
    })
    assert cfg.coalescer.enabled is True
    assert cfg.coalescer.window_ms == 3.5
    assert cfg.coalescer.max_batch == 64
    assert cfg.coalescer.max_request_rows == 8
    # PR 44: on by default, and with no clock
    assert load_config({}).coalescer.enabled is True
    assert load_config({}).coalescer.window_ms == 0.0
    assert load_config(
        {"QUERY_COALESCER_ENABLED": "false"}).coalescer.enabled is False
    with pytest.raises(ConfigError):
        load_config({"QUERY_COALESCER_MAX_BATCH": "1"})
    with pytest.raises(ConfigError):
        load_config({"QUERY_COALESCER_WINDOW_MS": "-1"})
    with pytest.raises(ConfigError):
        load_config({"QUERY_COALESCER_MAX_REQUEST_ROWS": "500"})
