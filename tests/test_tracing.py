"""End-to-end request tracing (monitoring/tracing.py) + its wiring.

The two acceptance-critical properties pinned here:

  1. ATTRIBUTION IDENTITY — a coalesced multi-request run yields traces
     where each rider's attributed device time sums exactly to the
     dispatch's device span (shares are rows_i/actual_rows over the REAL
     rows; padding overhead is reported separately as padding_waste, never
     smeared into shares).

  2. DISABLED = ZERO TRACING WORK — with TRACING_ENABLED unset, the
     serving hot path creates no Span, no Trace, no DispatchRecord, and
     never consults the Tracer (spied by replacing the classes on the
     module; serving code reaches them through module-global lookups, so a
     single construction would trip the spy).

Plus trace propagation across every coalescer edge: bypass lanes,
wrong-dim isolation, dispatch error, shutdown — each must CLOSE or
annotate the rider traces, never leak an open span.
"""

import json
import logging
import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config
from weaviate_tpu.entities.filters import LocalFilter
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.monitoring import perf, tracing
from weaviate_tpu.serving.coalescer import (
    CoalescerShutdownError,
    QueryCoalescer,
)
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 400, 16, 5


@pytest.fixture(autouse=True)
def _reset_global_tracer():
    """Tests install process-global tracers; never let one leak across."""
    yield
    tracing.configure(None)
    perf.configure(None)


def _mk_app(tmp_path, tracing_on=True, coalesce=True, window_ms=200.0,
            sample_rate=1.0, ring_size=256, slow_ms=0.0):
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = coalesce
    cfg.coalescer.window_ms = window_ms
    cfg.tracing.enabled = tracing_on
    cfg.tracing.sample_rate = sample_rate
    cfg.tracing.ring_size = ring_size
    cfg.tracing.slow_query_threshold_ms = slow_ms
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Tr", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tag", "dataType": ["text"]}],
    })
    rng = np.random.default_rng(11)
    vecs = rng.integers(-8, 8, (N, DIM)).astype(np.float32)
    idx = app.db.get_index("Tr")
    idx.put_batch([
        StorObj(class_name="Tr", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i])
        for i in range(N)])
    return app, idx, vecs


def _walk_spans(span_dict):
    yield span_dict
    for c in span_dict.get("children", []):
        yield from _walk_spans(c)


def _dispatch_spans(trace_dicts):
    """All 'dispatch' attribution spans across a list of trace dicts."""
    out = []
    for tr in trace_dicts:
        for s in _walk_spans(tr["root"]):
            if s["name"] == "dispatch":
                out.append(s)
    return out


def _get(app, vec, flt=None, limit=K):
    return app.traverser.get_class(GetParams(
        class_name="Tr", near_vector={"vector": vec.tolist()},
        filters=flt, limit=limit))


# -- the attribution identity (acceptance criterion) --------------------------

def test_coalesced_attribution_identity(tmp_path):
    """Concurrent single-query requests coalesce into shared dispatches;
    every rider's trace carries a dispatch span whose device_ms share sums
    (across the dispatch's riders) to the dispatch's device span."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        n_req = 10
        barrier = threading.Barrier(n_req)

        def run(i):
            with tracing.request("test", f"q{i}"):
                barrier.wait()
                _get(app, vecs[i] + 0.5)

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        snap = app.tracer.snapshot()
        assert len(snap) == n_req
        by_dispatch: dict = {}
        for d in _dispatch_spans(snap):
            by_dispatch.setdefault(d["attrs"]["dispatch_id"], []).append(
                d["attrs"])
        assert by_dispatch, "no dispatch spans attributed"
        coalesced = [v for v in by_dispatch.values() if len(v) > 1]
        assert coalesced, "requests never shared a dispatch"
        total_riders = 0
        for riders in by_dispatch.values():
            total_riders += len(riders)
            device_total = riders[0]["dispatch_device_ms"]
            # the identity: rider device shares sum to the dispatch span
            assert sum(a["device_ms"] for a in riders) == pytest.approx(
                device_total, rel=1e-9)
            # shares over ACTUAL rows (each request here is one row)
            assert len(riders) == riders[0]["actual_rows"]
            assert sum(a["share"] for a in riders) == pytest.approx(
                1.0, rel=1e-6)
            # padding slack is reported, not smeared into the shares
            assert riders[0]["padded_rows"] >= riders[0]["actual_rows"]
            waste = riders[0]["padding_waste"]
            assert waste == pytest.approx(
                1.0 - riders[0]["actual_rows"] / riders[0]["padded_rows"],
                abs=1e-4)
        assert total_riders == n_req  # every request attributed exactly once
    finally:
        app.shutdown()


def test_dispatch_facts_padded_jit_and_queue_wait(tmp_path):
    """A traced request records the dispatch facts: padded width from the
    index's bucket, the first-sighting-of-this-jit-shape bit (True once,
    False after), occupancy, and the lane queue wait."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=30.0)
    try:
        for i in range(2):
            with tracing.request("test", f"q{i}"):
                _get(app, vecs[i] + 0.5)
        d1, d2 = _dispatch_spans(app.tracer.snapshot())
        a1, a2 = d1["attrs"], d2["attrs"]
        assert a1["padded_rows"] == idx.single_local_shard() \
            .vector_index.padded_width(1)
        assert a1["jit_shape_first_seen"] is True
        assert a2["jit_shape_first_seen"] is False  # same (padded, k) shape
        assert a1["coalesced"] is True and a1["lane_requests"] == 1
        # the deadline flush means the lone request waited ~the window
        assert a1["queue_wait_ms"] >= 10.0
        assert {"device_search", "hydrate"} <= {
            c["name"] for c in d1["children"]}
        # snapshot read-plane facts: the generation the dispatch read and
        # its lock wait (0.0 = the lock-free fast path; the import already
        # published, so neither dispatch pays the read-your-writes flush)
        vidx = idx.single_local_shard().vector_index
        assert a1["snapshot_gen"] == vidx.snapshot_gen
        assert a1["lock_wait_ms"] == 0.0 and a2["lock_wait_ms"] == 0.0
    finally:
        app.shutdown()


def test_jit_shape_registered_even_for_untraced_dispatches(tmp_path):
    """Shape registration must see EVERY dispatch while the tracer is up:
    under sampling the compile-paying dispatch is usually unsampled, and
    the next sampled dispatch of the warm shape must NOT read first-seen."""
    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    try:
        # no request context: rec is None, but the dispatch registers
        idx.object_vector_search(vecs[0] + 0.5, K)
        with tracing.request("test", "q"):
            _get(app, vecs[1] + 0.5)
        d = _dispatch_spans(app.tracer.snapshot())
        assert len(d) == 1
        assert d[0]["attrs"]["jit_shape_first_seen"] is False
    finally:
        app.shutdown()


# -- disabled => zero tracing work on the serving path ------------------------

def test_disabled_serving_path_makes_zero_tracing_calls(tmp_path, monkeypatch):
    """TRACING_ENABLED unset: serving requests (direct AND coalesced paths,
    gRPC end to end) must construct no Span/Trace/DispatchRecord and never
    call Tracer.start_request — spied by replacing the module-global
    classes every call site resolves at call time."""
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient

    app, idx, vecs = _mk_app(tmp_path, tracing_on=False)
    calls = []

    def spy(name):
        def boom(*a, **kw):
            calls.append(name)
            raise AssertionError(f"tracing.{name} touched while disabled")
        return boom

    monkeypatch.setattr(tracing, "Span", spy("Span"))
    monkeypatch.setattr(tracing, "Trace", spy("Trace"))
    monkeypatch.setattr(tracing, "DispatchRecord", spy("DispatchRecord"))
    monkeypatch.setattr(tracing.Tracer, "start_request",
                        spy("Tracer.start_request"))
    # the intervals ride the same switch: no Phase, no profiler
    # annotation, nothing offered to the perf window's capture log
    monkeypatch.setattr(tracing, "Phase", spy("Phase"))
    monkeypatch.setattr(tracing, "_TraceMe", spy("TraceAnnotation"))
    monkeypatch.setattr(perf, "note_interval", spy("perf.note_interval"))
    monkeypatch.setattr(perf, "note_phase", spy("perf.note_phase"))
    srv = GrpcServer(app, port=0, max_workers=8)
    srv.start()
    try:
        assert app.tracer is None
        assert tracing.get_tracer() is None
        # coalesced lane
        res = _get(app, vecs[0] + 0.5)
        assert len(res) == K
        # direct path (coalescer bypass via oversize batched group)
        out = app.traverser.get_class_batched([
            GetParams(class_name="Tr",
                      near_vector={"vector": (vecs[i] + 0.5).tolist()},
                      limit=K)
            for i in range(20)])
        assert not any(isinstance(r, Exception) for r in out)
        # gRPC end to end (the handler wrap + request-id metadata path)
        cl = SearchClient(f"127.0.0.1:{srv.port}")
        try:
            rep = cl.search(pb.SearchRequest(
                class_name="Tr", limit=K,
                near_vector=pb.NearVectorParams(
                    vector=(vecs[1] + 0.5).tolist())))
            assert len(rep.results) == K
            # and the batch twin, whose entry has a decode and an encode
            # half of its own
            brep = cl.batch_search(pb.BatchSearchRequest(requests=[
                pb.SearchRequest(class_name="Tr", limit=K,
                                 near_vector=pb.NearVectorParams(
                                     vector=(vecs[i] + 0.5).tolist()))
                for i in range(3)]))
            assert [len(r.results) for r in brep.replies] == [K] * 3
        finally:
            cl.close()
        assert calls == []
    finally:
        srv.stop()
        app.shutdown()


def test_unsampled_request_serves_with_no_trace(tmp_path):
    """sample_rate=0: the tracer exists but every request is sampled out —
    serving still works, the ring stays empty, no span context leaks."""
    app, idx, vecs = _mk_app(tmp_path, sample_rate=0.0)
    try:
        with tracing.request("test", "q") as tr:
            assert tr is None
            assert tracing.current_span() is None
            res = _get(app, vecs[0] + 0.5)
        assert len(res) == K
        assert app.tracer.snapshot() == []
    finally:
        app.shutdown()


# -- propagation across every coalescer edge ----------------------------------

def test_bypass_lane_annotates_trace_and_records_direct_dispatch(tmp_path):
    """A cold-filter bypass annotates the trace with the reason AND the
    direct-path dispatch that serves it still records its phase spans
    (including the filter phase)."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        flt = LocalFilter.from_dict(
            {"operator": "Equal", "path": ["tag"], "valueText": "even"})
        with tracing.request("test", "cold") as tr:
            res = _get(app, vecs[0] + 0.5, flt=flt)
        assert len(res) == K
        doc = app.tracer.snapshot()[0]
        spans = list(_walk_spans(doc["root"]))
        tv = [s for s in spans if s["name"] == "traverser.get_class"][0]
        assert tv["attrs"]["coalescer_bypass"] == "cold_filter"
        d = [s for s in spans if s["name"] == "dispatch"]
        assert len(d) == 1 and d[0]["attrs"].get("coalesced") is not True
        assert {"filter", "device_search", "hydrate"} <= {
            c["name"] for c in d[0]["children"]}
        assert doc["duration_ms"] is not None  # root closed
    finally:
        app.shutdown()


def test_wrong_dim_fails_alone_and_lane_mates_attribute(tmp_path):
    """Dim isolation: the malformed request's trace gets the coalescer
    error annotation; its would-be lane-mates still get clean attribution."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=0.05, max_batch=64, max_request_rows=4)
        try:
            waits, traces = [], []

            def submit(vec, name):
                with tracing.request("test", name) as tr:
                    traces.append(tr)
                    return co.submit(shard, vec, K)

            for i in range(3):
                waits.append(submit(vecs[i], f"good{i}"))
            bad_wait = submit(np.zeros(DIM * 2, np.float32), "bad")
            for w in waits:
                assert len(w()) == 1
            with pytest.raises(Exception):
                bad_wait()
            time.sleep(0.1)  # annotation lands before the waiter wakes,
            # but the good lanes' finish() may still be in flight
            docs = {t.name: t.to_dict() for t in traces}
            assert "coalescer_error" in docs["bad"]["root"]["attrs"]
            for i in range(3):
                d = _dispatch_spans([docs[f"good{i}"]])
                assert len(d) == 1
                assert "coalescer_error" not in \
                    docs[f"good{i}"]["root"].get("attrs", {})
        finally:
            co.shutdown()
    finally:
        app.shutdown()


def test_dispatch_error_annotates_and_direct_retry_traces(tmp_path):
    """An injected dispatch failure: the rider trace carries the coalescer
    error AND the retry marker AND the direct dispatch that re-served it —
    the doubled device work is visible, not silent."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=30.0)
    try:
        shard = idx.single_local_shard()
        boom = RuntimeError("injected dispatch failure")

        def exploding(*a, **kw):
            raise boom

        shard.object_vector_search_async = exploding
        try:
            with tracing.request("test", "q") as tr:
                res = _get(app, vecs[0] + 0.5)
            assert len(res) == K  # served by the direct retry
        finally:
            del shard.object_vector_search_async
        doc = app.tracer.snapshot()[0]
        spans = list(_walk_spans(doc["root"]))
        tv = [s for s in spans if s["name"] == "traverser.get_class"][0]
        assert "coalescer_error" in tv["attrs"]
        assert "coalescer_retry_direct" in tv["attrs"]
        d = _dispatch_spans([doc])
        assert len(d) == 1 and d[0]["attrs"].get("coalesced") is not True
    finally:
        app.shutdown()


def test_shutdown_annotates_queued_waiters(tmp_path):
    """Waiters queued at shutdown: the trace records the shutdown, the
    waiter raises, and the request trace still closes."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        shard = idx.single_local_shard()
        co = QueryCoalescer(window_s=60.0, max_batch=64, max_request_rows=4)
        with tracing.request("test", "q") as tr:
            w = co.submit(shard, vecs[0], K)
            assert w is not None
            co.shutdown()
            with pytest.raises(CoalescerShutdownError):
                w()
        doc = app.tracer.snapshot()[0]
        assert "coalescer_shutdown" in doc["root"]["attrs"]
        assert doc["duration_ms"] is not None
    finally:
        app.shutdown()


# -- exposure surfaces --------------------------------------------------------

def test_debug_traces_endpoint_and_request_id_headers(tmp_path):
    """REST: traceparent honored (trace joins the caller's trace id),
    X-Request-Id echoed on success AND error replies, /debug/traces serves
    the ring behind the data-plane authorizer."""
    import urllib.error
    import urllib.request

    from weaviate_tpu.server.rest import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        tp = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
        gq = ("{ Get { Tr(nearVector: {vector: %s}, limit: 3) "
              "{ _additional { id } } } }" % (vecs[0] + 0.5).tolist())
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/graphql",
            data=json.dumps({"query": gq}).encode(),
            headers={"Content-Type": "application/json",
                     "traceparent": tp, "X-Request-Id": "rid-42"})
        resp = urllib.request.urlopen(req, timeout=30)
        assert resp.headers.get("X-Request-Id") == "rid-42"
        assert "errors" not in json.loads(resp.read())
        # error envelope carries a (generated) request id too
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/v1/not-a-route", timeout=10)
        assert ei.value.headers.get("X-Request-Id")
        # a traced response EMITS the server's traceparent: same trace id
        # as the inbound header, this server's own (fresh) span id
        resp_tp = tracing.parse_traceparent(resp.headers.get("traceparent"))
        assert resp_tp is not None
        assert resp_tp[0] == "ab" * 16 and resp_tp[1] != "cd" * 8
        dbg = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/debug/traces?limit=5",
            timeout=10).read())
        assert dbg["enabled"] is True and dbg["count"] >= 1
        tr = dbg["traces"][-1]
        assert tr["trace_id"] == "ab" * 16
        assert tr["parent_span_id"] == "cd" * 8
        assert tr["request_id"] == "rid-42"
        assert tr["kind"] == "rest"
        # the graphql span nests under the rest root
        names = {s["name"] for s in _walk_spans(tr["root"])}
        assert {"request", "graphql.get", "traverser.get_class",
                "dispatch"} <= names
    finally:
        srv.stop()
        app.shutdown()


def test_grpc_trailing_request_id_and_trace(tmp_path):
    """gRPC: x-request-id honored and echoed as trailing metadata; the
    trace records kind=grpc with the inbound traceparent's trace id."""
    import grpc

    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import GrpcServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = GrpcServer(app, port=0, max_workers=8)
    srv.start()
    try:
        tp = "00-" + "12" * 16 + "-" + "34" * 8 + "-01"
        ch = grpc.insecure_channel(f"127.0.0.1:{srv.port}")
        call = ch.unary_unary(
            "/weaviatetpu.v1.Weaviate/Search",
            request_serializer=pb.SearchRequest.SerializeToString,
            response_deserializer=pb.SearchReply.FromString)
        rep, info = call.with_call(
            pb.SearchRequest(class_name="Tr", limit=K,
                             near_vector=pb.NearVectorParams(
                                 vector=(vecs[0] + 0.5).tolist())),
            metadata=(("x-request-id", "grid-9"), ("traceparent", tp)))
        ch.close()
        assert len(rep.results) == K
        md = dict(info.trailing_metadata() or ())
        assert md.get("x-request-id") == "grid-9"
        out_tp = tracing.parse_traceparent(md.get("traceparent"))
        assert out_tp is not None and out_tp[0] == "12" * 16
        doc = app.tracer.snapshot()[-1]
        assert doc["kind"] == "grpc"
        assert doc["trace_id"] == "12" * 16
        assert doc["request_id"] == "grid-9"
    finally:
        srv.stop()
        app.shutdown()


def test_grpc_batch_trace_is_a_waterfall_with_decode_and_encode(tmp_path):
    """A gRPC BatchSearch root carries `entry.decode` and `entry.encode`
    children around the traverser's span, every span that ran has its
    `start_ms` from the root's start, the two halves are ledger phases of
    /debug/perf, and the benchmark's `trace_span` reader still reads the
    root less ALL its children (so `entry_self_ms` now leaves out what
    decode and encode name)."""
    from benchmarks.lib.spec import Spec
    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient

    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    srv = GrpcServer(app, port=0, max_workers=8)
    srv.start()
    cl = SearchClient(f"127.0.0.1:{srv.port}")
    try:
        rep = cl.batch_search(pb.BatchSearchRequest(requests=[
            pb.SearchRequest(class_name="Tr", limit=K,
                             near_vector=pb.NearVectorParams(
                                 vector=(vecs[i] + 0.5).tolist()))
            for i in range(4)]))
        assert [len(r.results) for r in rep.replies] == [K] * 4
        doc = app.tracer.snapshot()[-1]
        assert (doc["kind"], doc["name"]) == ("grpc", "BatchSearch")
        root = doc["root"]
        assert root["start_ms"] == 0.0
        kids = root["children"]
        # memtable-resident rows: the raw lane looked at the request and
        # declined, the general path decoded it again
        assert [c["name"] for c in kids] == [
            "entry.decode", "entry.decode", "traverser.get_class_batched",
            "entry.encode"]
        assert kids[0]["attrs"] == {"lane": "raw"}
        # a waterfall: each child starts after the one before it ended,
        # inside the root
        t = 0.0
        for c in kids:
            assert c["start_ms"] >= t - 1e-3
            t = c["start_ms"] + c["duration_ms"]
        assert t <= root["duration_ms"] + 1e-3
        # attribution spans (a dispatch's share) are not intervals
        d = _dispatch_spans([doc])
        assert d and all("start_ms" not in s for s in d)
        # the reader: root less all children, as before
        reader = Spec().reader("trace_span")
        got = reader.read({"traces": {"traces": [doc]}, "client": {}},
                          kind="grpc", names=["BatchSearch"])
        want = root["duration_ms"] - sum(c["duration_ms"] for c in kids)
        assert got == pytest.approx(want, abs=1e-6)
        assert 0.0 <= got < root["duration_ms"] - kids[2]["duration_ms"]
        # and the ledger has the two halves as phases of their own; of the
        # two decodes only the one whose rows were served
        phases = app.perf_window.summary()["phases"]
        assert phases["decode"]["samples"] == 1
        assert phases["decode"]["p50_ms"] == pytest.approx(
            kids[1]["duration_ms"], abs=2e-3)
        assert phases["encode"]["samples"] == 1
        assert phases["encode"]["p50_ms"] == pytest.approx(
            kids[3]["duration_ms"], abs=2e-3)
        # flushed to segments the raw lane serves: no traverser, the
        # dispatch hangs under the root between the two halves
        shard = idx.single_local_shard()
        for b in (shard.objects, shard.docid_lookup):
            b.flush_memtable()
        if shard.raw_plane_ready():
            cl.batch_search(pb.BatchSearchRequest(requests=[
                pb.SearchRequest(class_name="Tr", limit=K,
                                 near_vector=pb.NearVectorParams(
                                     vector=(vecs[i] + 0.5).tolist()))
                for i in range(4)]))
            kids = app.tracer.snapshot()[-1]["root"]["children"]
            assert [c["name"] for c in kids] == [
                "entry.decode", "dispatch", "entry.encode"]
            assert app.perf_window.summary()["phases"]["decode"][
                "samples"] == 2
            # the hydrate was two native point-get calls of 4 x K keys
            # (doc id -> uuid, uuid -> image), every one a hit
            pg = app.perf_window.summary()["point_get"]
            assert pg["keys"] == 2 * 4 * K
            assert pg["keys"] <= min(pg["key_compares"], pg["segment_probes"])
            # one slot with an offset: the raw lane reads that off the
            # request's bytes and declines, the general path decodes and
            # serves, and the ledger takes that one sample, not two
            cl.batch_search(pb.BatchSearchRequest(requests=[
                pb.SearchRequest(class_name="Tr", limit=K, offset=i % 2,
                                 near_vector=pb.NearVectorParams(
                                     vector=(vecs[i] + 0.5).tolist()))
                for i in range(4)]))
            kids = app.tracer.snapshot()[-1]["root"]["children"]
            assert [c["name"] for c in kids] == [
                "entry.decode", "entry.decode",
                "traverser.get_class_batched", "entry.encode"]
            assert kids[0]["attrs"] == {"lane": "raw"}
            assert app.perf_window.summary()["phases"]["decode"][
                "samples"] == 3
        # the single Search has both halves too
        cl.search(pb.SearchRequest(
            class_name="Tr", limit=K,
            near_vector=pb.NearVectorParams(vector=(vecs[1] + 0.5).tolist())))
        kids = app.tracer.snapshot()[-1]["root"]["children"]
        assert [c["name"] for c in kids] == [
            "entry.decode", "traverser.get_class", "entry.encode"]
    finally:
        cl.close()
        srv.stop()
        app.shutdown()


def test_slow_query_log_emits_full_span_tree(tmp_path, caplog):
    """A trace over the threshold logs ONE structured JSON line with the
    whole span tree on the weaviate_tpu.slowquery logger."""
    app, idx, vecs = _mk_app(tmp_path, slow_ms=0.0001)
    try:
        with caplog.at_level(logging.WARNING, logger="weaviate_tpu.slowquery"):
            with tracing.request("test", "slow-one"):
                _get(app, vecs[0] + 0.5)
        lines = [r.getMessage() for r in caplog.records
                 if r.name == "weaviate_tpu.slowquery"]
        assert len(lines) == 1
        doc = json.loads(lines[0])
        assert doc["slow_query"] is True and doc["name"] == "slow-one"
        assert any(s["name"] == "dispatch"
                   for s in _walk_spans(doc["root"]))
    finally:
        app.shutdown()


def test_ring_buffer_is_bounded(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, ring_size=4, window_ms=10.0)
    try:
        for i in range(9):
            with tracing.request("test", f"q{i}"):
                _get(app, vecs[i] + 0.5)
        snap = app.tracer.snapshot()
        assert len(snap) == 4
        assert [t["name"] for t in snap] == ["q5", "q6", "q7", "q8"]
    finally:
        app.shutdown()


def test_trace_metrics_exposed(tmp_path):
    """Exemplar counters land in the app's Metrics registry."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=10.0)
    try:
        with tracing.request("test", "q"):
            _get(app, vecs[0] + 0.5)
        text = app.metrics.expose().decode()
        assert 'weaviate_traces_total{kind="test",outcome="ok"} 1.0' in text
        assert 'weaviate_trace_phase_ms_count{phase="device_search"} 1.0' \
            in text
        assert 'weaviate_trace_phase_ms_count{phase="queue_wait"} 1.0' in text
        assert 'weaviate_trace_dispatch_rows_total{kind="actual"} 1.0' in text
        assert 'weaviate_trace_dispatch_rows_total{kind="padded"} 1.0' in text
    finally:
        app.shutdown()


def test_tracing_config_env_parsing():
    from weaviate_tpu.config import ConfigError, load_config

    cfg = load_config({
        "TRACING_ENABLED": "true",
        "TRACING_SAMPLE_RATE": "0.25",
        "TRACING_RING_SIZE": "64",
        "SLOW_QUERY_THRESHOLD_MS": "250",
    })
    assert cfg.tracing.enabled is True
    assert cfg.tracing.sample_rate == 0.25
    assert cfg.tracing.ring_size == 64
    assert cfg.tracing.slow_query_threshold_ms == 250.0
    assert load_config({}).tracing.enabled is False
    with pytest.raises(ConfigError):
        load_config({"TRACING_SAMPLE_RATE": "1.5"})
    with pytest.raises(ConfigError):
        load_config({"TRACING_RING_SIZE": "0"})


def test_request_id_cleaning_blocks_header_injection():
    """An inbound X-Request-Id is echoed into a response header: CR/LF and
    non-printables must never survive, and an empty/garbage id is replaced
    with a generated one."""
    assert tracing.clean_request_id("abc-123") == "abc-123"
    assert tracing.clean_request_id(
        "evil\r\nSet-Cookie: x=1") == "evilSet-Cookie:x=1"
    assert len(tracing.clean_request_id("x" * 500)) == 128
    for empty in (None, "", "   ", "\r\n"):
        rid = tracing.clean_request_id(empty)
        assert rid and len(rid) == 32  # generated


def test_traceparent_parsing_rejects_malformed():
    good = "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    assert tracing.parse_traceparent(good) == ("ab" * 16, "cd" * 8, "01")
    for bad in (None, "", "garbage", "00-xyz-abc-01",
                "01-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # wrong version
                "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",   # zero trace id
                "00-" + "ab" * 16 + "-" + "0" * 16 + "-01"):  # zero parent
        assert tracing.parse_traceparent(bad) is None


def test_a_stage_is_a_phase_only_while_the_tracer_is_up(monkeypatch):
    """`tracing.stage` and a restore's `StageSums` are `wv/startup.*`
    annotations with the tracer up and bare stamps with it down; either
    way they measure."""
    from weaviate_tpu.monitoring import perf

    made = []
    real = tracing.Phase.__init__

    def spy(self, name, **stats):
        made.append((name, stats))
        real(self, name, **stats)

    monkeypatch.setattr(tracing.Phase, "__init__", spy)

    def one_restart():
        with tracing.stage("vector.restore", shard="s0") as st:
            sums = tracing.StageSums()
            sums.enter("stage")
            for _ in sums.timed(iter(range(3)), "log.parse"):
                with tracing.piece_of(sums, "land", 16384, rows=8):
                    sums.enter("grow", capacity=32768)
                    sums.leave(32768)
            sums.leave()
            sums.publish()
        assert st.seconds > 0 and sums.seconds("land") > 0
        return sums

    assert tracing.get_tracer() is None
    one_restart()
    assert made == []
    t = tracing.configure(tracing.Tracer())
    try:
        sums = one_restart()
    finally:
        tracing.unconfigure(t)
    names = [n for n, _ in made]
    assert names[0] == "startup.vector.restore"
    assert names.count("startup.land") == 3 == names.count("startup.grow")
    assert names.count("startup.stage") == 1
    assert "startup.log.parse" not in names    # a pair of stamps a step
    assert dict(made)["startup.grow"] == {"capacity": 32768}
    assert sums._pieces == {"stage": 1, "log.parse": 4, "land": 3, "grow": 3}
    assert perf.startup() is None              # nobody began a timeline

