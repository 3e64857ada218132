"""Multi-tenant fairness and isolation (the PR-6 tentpole): tenant
identity end to end, weighted-fair admission (DRR + per-tenant budgets +
per-tenant shed estimates), bounded-cardinality per-tenant metrics, the
allowList cache's per-tenant share bound, and the abusive-tenant storm
journey on the fault harness.

Journeys run against the REAL serving stack (App + coalescer + shard +
index) like tests/test_robustness.py; timing assertions are deliberately
loose functional bounds (a tight 2x-p99 isolation claim needs a quiet
host and a cell of the benchmark, not a shared CI runner).
"""

import http.client
import json
import logging
import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.monitoring import tracing
from weaviate_tpu.monitoring.metrics import TenantLabeler, noop_metrics
from weaviate_tpu.serving import robustness
from weaviate_tpu.testing import faults
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 300, 16, 5


@pytest.fixture(autouse=True)
def _reset_globals():
    """Tests install process-global tracers/metrics; never leak across."""
    yield
    tracing.configure(None)


# -- unit: tenant identity ----------------------------------------------------


def test_validate_tenant_id_accepts_and_rejects():
    assert robustness.validate_tenant_id(None) is None
    assert robustness.validate_tenant_id("") is None
    assert robustness.validate_tenant_id("  ") is None
    assert robustness.validate_tenant_id(" acme-prod_1 ") == "acme-prod_1"
    for bad in ("two words", "crlf\r\nInjected: 1", "tab\there",
                "bß", "x" * 65,
                # reserved system identities: "other" is the aggregate
                # metric bucket, "multi" the merged-dispatch trace tag —
                # a client claiming either would hide inside the aggregate
                "other", "Multi"):
        with pytest.raises(ValueError):
            robustness.validate_tenant_id(bad)


def test_tenant_scope_and_effective_tenant():
    assert robustness.current_tenant() is None
    # no explicit identity: the queried class name is the accounting key
    assert robustness.effective_tenant("Cls") == "Cls"
    with robustness.tenant_scope("t1"):
        assert robustness.current_tenant() == "t1"
        assert robustness.effective_tenant("Cls") == "t1"
        with robustness.tenant_scope(None):  # None scope = no-op
            assert robustness.current_tenant() == "t1"
    assert robustness.current_tenant() is None


# -- unit: bounded tenant labels ----------------------------------------------


def test_tenant_labeler_top_k_plus_other():
    lab = TenantLabeler(top_k=2)
    assert lab.observe("a") == "a"
    assert lab.observe("b") == "b"
    assert lab.observe("c") == "other"     # set full, c is not heavier
    assert lab.label_for("a") == "a" and lab.label_for("c") == "other"
    # c becomes genuinely heavy: it displaces the weakest labeled tenant
    for _ in range(10):
        last = lab.observe("c")
    assert last == "c"
    assert lab.label_for("c") == "c"
    assert "other" in (lab.label_for("a"), lab.label_for("b"))


def test_tenant_labeler_lifetime_cardinality_and_memory_bounded():
    lab = TenantLabeler(top_k=4, max_tracked=64)
    seen = set()
    for i in range(1000):
        t = f"tenant-{i}"
        # escalating traffic so promotion pressure is constant
        for _ in range(i % 7 + 1):
            seen.add(lab.observe(t))
    # lifetime label values are hard-capped at 3*top_k (+ "other")
    assert len(seen) <= 3 * 4 + 1 and "other" in seen
    assert len(lab._counts) <= 64 + 4  # pruned to max_tracked + labeled


def test_metrics_cardinality_bounded_under_1k_distinct_tenants():
    """1000 distinct tenant ids shedding through the robustness helpers
    mint a bounded set of label values in the exposition, not 1000."""
    m = noop_metrics()
    robustness.set_metrics(m)
    try:
        for i in range(1000):
            robustness.count_tenant_shed(f"t{i}", "queue_full")
            robustness.count_tenant_deadline(f"t{i}")
        exposed = m.expose().decode()
        labels = set()
        for line in exposed.splitlines():
            if line.startswith("weaviate_tenant_requests_shed_total{"):
                for part in line.split("{", 1)[1].split("}")[0].split(","):
                    k, _, v = part.partition("=")
                    if k == "tenant":
                        labels.add(v.strip('"'))
        top_k = m.tenant_labels.top_k
        assert 0 < len(labels) <= 3 * top_k + 1
        assert "other" in labels
    finally:
        robustness.unset_metrics(m)


# -- fixtures -----------------------------------------------------------------


def _mk_app(tmp_path, *, coalesce=True, window_ms=30.0, max_queued_rows=4096,
            fraction=0.5, weights=None, wait_timeout_s=30.0,
            max_request_rows=16, tracing_on=False, slow_ms=0.0, n=N):
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = coalesce
    cfg.coalescer.window_ms = window_ms
    cfg.coalescer.max_queued_rows = max_queued_rows
    cfg.coalescer.max_request_rows = max_request_rows
    cfg.coalescer.wait_timeout_s = wait_timeout_s
    cfg.tenancy.max_queued_rows_fraction = fraction
    cfg.tenancy.weights = dict(weights or {})
    cfg.tracing.enabled = tracing_on
    cfg.tracing.slow_query_threshold_ms = slow_ms
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Fa", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tag", "dataType": ["text"]}],
    })
    rng = np.random.default_rng(29)
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    idx = app.db.get_index("Fa")
    idx.put_batch([
        StorObj(class_name="Fa", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i])
        for i in range(n)])
    return app, idx, vecs


def _get(app, vec, limit=K):
    return app.traverser.get_class(GetParams(
        class_name="Fa", near_vector={"vector": vec.tolist()}, limit=limit))


# -- weighted-fair admission --------------------------------------------------


def test_lane_key_includes_tenant_and_default_is_class_name(tmp_path):
    """Two tenants' identical queries land in SEPARATE lanes (isolation);
    anonymous requests account to the class name."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=5000.0)
    try:
        co = app.coalescer
        shard = idx.single_local_shard()
        assert co.submit(shard, vecs[0], K) is not None
        with robustness.tenant_scope("paying-tenant"):
            assert co.submit(shard, vecs[1], K) is not None
        with co._lock:
            tenants = sorted(ln.tenant for ln in co._lanes.values())
        assert tenants == ["Fa", "paying-tenant"]
        st = co.stats()["tenants"]
        assert st["Fa"]["rows_in_system"] == 1
        assert st["paying-tenant"]["rows_in_system"] == 1
    finally:
        app.shutdown()


def test_drr_order_honors_weights(tmp_path):
    """Deficit-round-robin drains due lanes 2:1 for a weight-2 tenant."""
    from weaviate_tpu.serving.coalescer import _Lane

    app, idx, vecs = _mk_app(tmp_path, weights={"heavy": 2.0})
    try:
        co = app.coalescer

        def lane(tenant, rows):
            ln = _Lane(None, None, None, K, False, 0.0, 256, tenant=tenant,
                       tenant_label=tenant)
            ln.rows = rows
            return ln

        due = [lane("heavy", co.max_batch) for _ in range(4)] \
            + [lane("light", co.max_batch) for _ in range(4)]
        with co._lock:
            co._drr_cursor = 0
            order = [ln.tenant for ln in co._drr_order(due)]
        # per DRR round: heavy's deficit covers 2 full lanes, light's 1
        assert order == ["heavy", "heavy", "light", "heavy", "heavy",
                         "light", "light", "light"]
        # rotation start advances across cycles: the same tenant does not
        # structurally go first every flush
        due2 = [lane("heavy", co.max_batch), lane("light", co.max_batch)]
        with co._lock:
            order2 = [ln.tenant for ln in co._drr_order(due2)]
        assert order2[0] == "light"
        # single-tenant due lists keep FIFO order untouched
        due3 = [lane("only", 1), lane("only", 2), lane("only", 3)]
        with co._lock:
            assert [ln.rows for ln in co._drr_order(due3)] == [1, 2, 3]
    finally:
        app.shutdown()


def test_tenant_budget_sheds_abuser_not_light(tmp_path):
    """With other tenants waiting, a tenant beyond its row-budget share
    sheds (`tenant_budget`) while the others keep admitting; alone, the
    same tenant may use the whole queue."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=5000.0, max_queued_rows=8,
                             fraction=0.5, max_request_rows=2)
    try:
        co = app.coalescer
        shard = idx.single_local_shard()
        assert co._tenant_row_cap == 4
        with robustness.tenant_scope("abuser"):
            for i in range(4):
                assert co.submit(shard, vecs[i], K) is not None
            # no one else waiting: the cap does NOT fire (a lone tenant
            # may fill the queue)
            assert co.submit(shard, vecs[4], K) is not None
        with robustness.tenant_scope("light"):
            assert co.submit(shard, vecs[5], K) is not None
        with robustness.tenant_scope("abuser"):
            with pytest.raises(robustness.OverloadedError) as ei:
                co.submit(shard, vecs[6], K)
            assert "tenant_budget" in str(ei.value)
        # the light tenant still admits against ITS budget
        with robustness.tenant_scope("light"):
            assert co.submit(shard, vecs[7], K) is not None
        st = co.stats()
        assert st["tenants"]["abuser"]["shed"] == {"tenant_budget": 1}
        assert st["tenants"]["light"]["shed"] == {}
        # per-tenant accounting is visible in /metrics under the bounded
        # tenant labels (the satellite contract)
        exposed = app.metrics.expose().decode()
        assert ('weaviate_tenant_requests_shed_total'
                '{reason="tenant_budget",tenant="abuser"} 1.0') in exposed
        assert 'tenant="light"' in exposed  # admitted-requests counter
    finally:
        app.shutdown()


def test_per_tenant_shed_estimate_spares_light_tenants(tmp_path):
    """Deadline-unreachable shedding uses the TENANT'S own backlog: a
    deadline request from a tenant with an empty queue admits even while
    another tenant has a deep backlog (the old global estimate shed it)."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=5000.0, max_queued_rows=64,
                             fraction=1.0)
    try:
        co = app.coalescer
        shard = idx.single_local_shard()
        with co._lock:
            # a known drain rate so the estimator is armed: 100 rows/s
            co._ewma_rows_per_s = 100.0
            co._tenant_state("abuser").ewma_rows_per_s = 100.0
        with robustness.tenant_scope("abuser"):
            for i in range(40):
                assert co.submit(shard, vecs[i % 8], K) is not None
            # 40 rows / 100 rows/s = 400 ms backlog >> a 50 ms deadline
            with robustness.deadline_scope(50.0):
                with pytest.raises(robustness.OverloadedError) as ei:
                    co.submit(shard, vecs[0], K)
            assert "deadline_unreachable" in str(ei.value)
        # same deadline, different tenant, empty backlog: admits
        with robustness.tenant_scope("light"):
            with robustness.deadline_scope(50.0):
                assert co.submit(shard, vecs[1], K) is not None
    finally:
        app.shutdown()


# -- tenant tags: REST -> trace -> slow-query log -----------------------------


def _rest(port, method, path, body=None, headers=None, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = json.dumps(body).encode() if body is not None else None
        hdrs = {"Content-Type": "application/json", **(headers or {})}
        conn.request(method, path, body=data, headers=hdrs)
        resp = conn.getresponse()
        payload = resp.read()
        return resp.status, dict(resp.getheaders()), \
            json.loads(payload) if payload else None
    finally:
        conn.close()


def _gql_near(vec):
    return ('{ Get { Fa(limit: %d, nearVector: {vector: %s}) '
            '{ tag _additional { distance } } } }'
            % (K, json.dumps([float(x) for x in vec])))


class _SlowLines(logging.Handler):
    """Keeps the slow-query lines that name `needle` and says when the
    first has come."""

    def __init__(self, needle: str):
        super().__init__(logging.WARNING)
        self.needle = needle
        self.lines: list = []
        self.seen = threading.Event()

    def emit(self, record):
        line = record.getMessage()
        if self.needle in line:
            self.lines.append(line)
            self.seen.set()


def test_tenant_tag_propagates_rest_to_trace_to_slow_log(tmp_path):
    """X-Tenant-Id rides the contextvar into the trace root, the
    coalescer admission annotation, the dispatch record — and lands in
    the slow-query JSON line, so 'whose query was slow' is answerable."""
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, tracing_on=True, slow_ms=0.0001)
    srv = RestServer(app, port=0)
    srv.start()
    slow = logging.getLogger("weaviate_tpu.slowquery")
    seen = _SlowLines("tenant-42")
    slow.addHandler(seen)
    level = slow.level
    slow.setLevel(logging.WARNING)
    try:
        st, hdrs, out = _rest(
            srv.port, "POST", "/v1/graphql",
            {"query": _gql_near(vecs[0])},
            headers={"X-Tenant-Id": "tenant-42"})
        assert st == 200 and "errors" not in out
        # the REST handler writes the response INSIDE its trace scope:
        # Tracer.finish (ring append, then the slow-log line) runs on the
        # handler thread after the client already has its reply. The line
        # is the last thing finish does for this request, so waiting for it
        # is waiting for the ring too (reading the ring at once lost that
        # race on a loaded worker)
        assert seen.seen.wait(30.0), "no slow-query line for tenant-42"
        traces = app.tracer.snapshot()
        mine = [t for t in traces
                if t["root"].get("attrs", {}).get("tenant") == "tenant-42"]
        assert mine, f"no trace tagged tenant-42 in {len(traces)} traces"
        # the tag reaches span level too (admission annotation or the
        # graphql.get span), not just the root attr
        def walk(s):
            yield s
            for c in s.get("children", []):
                yield from walk(c)
        spans = list(walk(mine[-1]["root"]))
        assert any(s.get("attrs", {}).get("tenant") == "tenant-42"
                   for s in spans)
        docs = [json.loads(ln) for ln in seen.lines]
        assert any(d["root"].get("attrs", {}).get("tenant") == "tenant-42"
                   for d in docs)
    finally:
        slow.setLevel(level)
        slow.removeHandler(seen)
        srv.stop()
        app.shutdown()


def test_tenant_header_injection_rejected(tmp_path):
    """An injection-shaped X-Tenant-Id is REJECTED (400), never cleaned
    into an accounting key; gRPC metadata gets INVALID_ARGUMENT."""
    import grpc

    from weaviate_tpu.grpcapi import weaviate_pb2 as pb
    from weaviate_tpu.server import RestServer
    from weaviate_tpu.server.grpc_server import GrpcServer, SearchClient

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    gsrv = GrpcServer(app, port=0)
    gsrv.start()
    cl = SearchClient(f"127.0.0.1:{gsrv.port}")
    try:
        st, _, out = _rest(srv.port, "POST", "/v1/graphql",
                           {"query": _gql_near(vecs[0])},
                           headers={"X-Tenant-Id": "two words"})
        assert st == 400
        assert "tenant" in out["error"][0]["message"]
        st, _, _ = _rest(srv.port, "POST", "/v1/graphql",
                         {"query": _gql_near(vecs[0])},
                         headers={"X-Tenant-Id": "x" * 65})
        assert st == 400
        # a VALID tenant header serves normally
        st, _, out = _rest(srv.port, "POST", "/v1/graphql",
                           {"query": _gql_near(vecs[0])},
                           headers={"X-Tenant-Id": "fine-1"})
        assert st == 200 and "errors" not in out
        req = pb.SearchRequest(
            class_name="Fa", limit=K,
            near_vector=pb.NearVectorParams(vector=vecs[0].tolist()))
        with pytest.raises(grpc.RpcError) as ei:
            cl.search(req, metadata=(("x-tenant-id", "two words"),))
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        rep = cl.search(req, metadata=(("x-tenant-id", "fine-2"),))
        assert len(rep.results) == K
    finally:
        cl.close()
        gsrv.stop()
        srv.stop()
        app.shutdown()


# -- allowList cache: per-tenant share bound ----------------------------------


def test_allow_cache_bounds_each_tenants_share(tmp_path):
    """An abusive tenant issuing unique filters evicts ITS OWN oldest
    entries once it dominates the cache — another tenant's hot entry
    survives a 20-unique-filter storm (the old global LRU evicted it)."""
    from weaviate_tpu.db.shard import Shard, filter_signature
    from weaviate_tpu.entities.filters import LocalFilter
    from weaviate_tpu.entities.schema import ClassDef, Property
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config

    cd = ClassDef(name="Ten", properties=[
        Property(name="n", data_type=["int"]),
    ], vector_index_type="hnsw_tpu")
    shard = Shard("s0", str(tmp_path / "ten"), cd,
                  parse_and_validate_config(
                      "hnsw_tpu", {"distance": "l2-squared"}))
    try:
        rng = np.random.default_rng(1)
        shard.put_batch([
            StorObj(class_name="Ten", uuid=str(uuidlib.UUID(int=i + 1)),
                    properties={"n": i},
                    vector=rng.standard_normal(DIM).astype(np.float32))
            for i in range(40)])

        def flt(i):
            return LocalFilter.from_dict(
                {"operator": "Equal", "path": ["n"], "valueInt": i})

        with robustness.tenant_scope("victim"):
            hot = shard.build_allow_list(flt(0))
        # the abusive tenant floods the 16-entry cache with unique filters
        with robustness.tenant_scope("abuser"):
            for i in range(1, 21):
                shard.build_allow_list(flt(i))
        # the victim's entry SURVIVED (same cached Bitmap object), and the
        # abuser's share is bounded at the cache cap minus other tenants
        assert filter_signature(flt(0)) in shard._allow_cache
        with robustness.tenant_scope("victim"):
            assert shard.build_allow_list(flt(0)) is hot
        owners = [t for (_, _, t) in shard._allow_cache.values()]
        assert owners.count("abuser") <= 15
        assert owners.count("victim") == 1
        # single-tenant behavior is untouched plain LRU (pinned by
        # tests/test_snapshot_reads.py::test_allow_cache_lru_eviction_order)
    finally:
        shard.shutdown()


def test_coalesced_filtered_allow_cache_attributes_lane_tenant(tmp_path):
    """A coalesced FILTERED dispatch builds its allowList on the dispatch
    pool, where the request's ContextVars don't follow — the lane's
    explicit tenant handoff must attribute the cache entry to the
    submitting tenant, not the class-name fallback (mis-attribution
    would pool every coalesced entry under one bucket and void the
    per-tenant share bound)."""
    from weaviate_tpu.db.shard import filter_signature
    from weaviate_tpu.entities.filters import LocalFilter

    app, idx, vecs = _mk_app(tmp_path, window_ms=40.0)
    try:
        shard = idx.single_local_shard()
        flt = LocalFilter.from_dict({
            "path": ["tag"], "operator": "Equal", "valueText": "even"})
        with robustness.tenant_scope("filt-tenant"):
            # first sighting: cold signature bypasses (direct path,
            # serving thread) and warms the recency map
            app.traverser.get_class(GetParams(
                class_name="Fa", filters=flt,
                near_vector={"vector": vecs[0].tolist()}, limit=K))
            # invalidate the cached entry so the next query REBUILDS it
            shard.put_batch([StorObj(
                class_name="Fa", uuid=str(uuidlib.UUID(int=9000)),
                properties={"tag": "odd"}, vector=vecs[1])])
            # hot signature now queues: the allowList is rebuilt on the
            # dispatch pool under the lane's tenant scope
            app.traverser.get_class(GetParams(
                class_name="Fa", filters=flt,
                near_vector={"vector": vecs[2].tolist()}, limit=K))
        entry = shard._allow_cache.get(filter_signature(flt))
        assert entry is not None
        assert entry[2] == "filt-tenant", entry[2]
    finally:
        app.shutdown()


# -- fault point + abusive-tenant storm journey -------------------------------


def test_admit_fault_point_fires_before_queue_state(tmp_path):
    """serving.coalescer.admit: an injected failure at admission raises to
    the caller and strands nothing (no queued rows, no tenant rows)."""
    app, idx, vecs = _mk_app(tmp_path, window_ms=5000.0)
    inj = faults.configure(faults.FaultInjector())
    try:
        co = app.coalescer
        shard = idx.single_local_shard()
        inj.plan("serving.coalescer.admit", "device_error", times=1)
        with pytest.raises(faults.InjectedDeviceError):
            co.submit(shard, vecs[0], K)
        assert inj.fired("serving.coalescer.admit") == 1
        with co._lock:
            assert co._queued_rows == 0 and not co._lanes
        # the next admission serves normally
        assert co.submit(shard, vecs[0], K) is not None
    finally:
        faults.unconfigure(inj)
        app.shutdown()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_abusive_tenant_storm_light_tenants_stay_isolated(tmp_path):
    """The acceptance journey scaled to tier-1: an abusive tenant floods
    the admission queue while the fault harness slows every lane dispatch
    (a seeded storm). Light tenants: every request completes correctly,
    ZERO of them shed, and their p99 stays under a loose absolute bound —
    while the abusive tenant absorbs the shedding on its own label."""
    # cap = max(int(16 * 0.125), max_request_rows) = 2 queued rows for any
    # one tenant while others wait — far below the 10 abusive in-flight
    # requests, so the abuser structurally MUST shed while light traffic
    # is live
    app, idx, vecs = _mk_app(tmp_path, window_ms=5.0, max_queued_rows=16,
                             fraction=0.125, max_request_rows=2,
                             wait_timeout_s=20.0)
    inj = faults.configure(faults.FaultInjector(seed=31))
    try:
        # the storm: every coalesced lane dispatch stalls 15 ms — queue
        # pressure without device flakiness, deterministic via the seed
        inj.plan("serving.coalescer.dispatch", "stall", times=None,
                 stall_s=0.015)
        expected = {i: [(r.obj.uuid, r.distance) for r in _get(app, vecs[i])]
                    for i in range(4)}

        stop = threading.Event()
        abusive_out = {"ok": 0, "shed": 0, "other": 0}
        ab_lock = threading.Lock()

        def abuse(tid):
            rng = np.random.default_rng(tid)
            with robustness.tenant_scope("abuser"):
                while not stop.is_set():
                    qi = int(rng.integers(0, 4))
                    try:
                        _get(app, vecs[qi])
                        key = "ok"
                    except robustness.OverloadedError:
                        key = "shed"
                        time.sleep(0.001)  # don't starve the 2-core host
                    except Exception:  # noqa: BLE001 — outcome accounting
                        key = "other"
                    with ab_lock:
                        abusive_out[key] += 1

        PER = 10
        light_lat = {"light-1": [], "light-2": []}
        light_err = []

        def light(tenant):
            with robustness.tenant_scope(tenant):
                for j in range(PER):
                    qi = j % 4
                    t0 = time.monotonic()
                    try:
                        got = [(r.obj.uuid, r.distance)
                               for r in _get(app, vecs[qi])]
                        if got != expected[qi]:
                            light_err.append((tenant, "wrong answer"))
                    except Exception as e:  # noqa: BLE001 — recorded
                        light_err.append((tenant, f"{type(e).__name__}: {e}"))
                    light_lat[tenant].append(time.monotonic() - t0)
                    time.sleep(0.005)

        abusers = [threading.Thread(target=abuse, args=(i,), daemon=True)
                   for i in range(10)]
        lights = [threading.Thread(target=light, args=(t,))
                  for t in light_lat]
        # lights first: their queued rows make "others are waiting" true
        # from the abusive burst's very first submit — the budget cap
        # (2 rows) then sheds the 10-deep abusive burst structurally
        for t in lights:
            t.start()
        time.sleep(0.1)
        for t in abusers:
            t.start()
        for t in lights:
            t.join(timeout=60)
        stop.set()
        for t in abusers:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in abusers + lights), "hang"

        # light tenants: complete, correct, unshed
        assert light_err == []
        assert all(len(v) == PER for v in light_lat.values())
        st = app.coalescer.stats()
        for t in light_lat:
            assert sum(st["tenants"].get(t, {}).get(
                "shed", {}).values()) == 0, st["tenants"]
        # the abuser absorbed real shedding on ITS label
        ab_shed = sum(st["tenants"]["abuser"]["shed"].values())
        assert abusive_out["shed"] > 0 and ab_shed > 0
        assert abusive_out["other"] == 0
        # loose absolute tail bound: stalled dispatches are 15 ms and the
        # abuser's backlog is budget-capped, so a light request never
        # waits out a deep queue (a CI-safe bound)
        for t, lat in light_lat.items():
            p99 = float(np.percentile(np.asarray(lat), 99))
            assert p99 < 5.0, f"{t} p99 {p99:.2f}s under storm"
        exposed = app.metrics.expose().decode()
        assert 'weaviate_tenant_requests_shed_total' in exposed
        assert 'tenant="abuser"' in exposed
    finally:
        faults.unconfigure(inj)
        app.shutdown()


# -- config surface -----------------------------------------------------------


def test_tenancy_config_parsing_and_validation():
    from weaviate_tpu.config.config import ConfigError, load_config

    cfg = load_config({"TENANT_WEIGHTS": "acme=4, beta=2.5",
                       "TENANT_MAX_QUEUED_ROWS_FRACTION": "0.25",
                       "TENANT_METRICS_TOP_K": "5"})
    assert cfg.tenancy.weights == {"acme": 4.0, "beta": 2.5}
    assert cfg.tenancy.max_queued_rows_fraction == 0.25
    assert cfg.tenancy.metrics_top_k == 5
    for bad in ({"TENANT_WEIGHTS": "noweight"},
                {"TENANT_WEIGHTS": "a=zero"},
                {"TENANT_WEIGHTS": "a=-1"},
                {"TENANT_MAX_QUEUED_ROWS_FRACTION": "0"},
                {"TENANT_MAX_QUEUED_ROWS_FRACTION": "1.5"},
                {"TENANT_METRICS_TOP_K": "0"}):
        with pytest.raises(ConfigError):
            load_config(bad)
