"""Online quality observability (monitoring/quality.py): the shadow
recall auditor, /debug/quality + /debug/index, and the always-on health
gauges.

The acceptance-critical invariants pinned here:

  1. GROUND-TRUTH AGREEMENT — on tie-free integer data the audited live
     answer matches the exact host plane bit-for-bit, so every audit
     scores recall 1.0 / RBO 1.0 / relerr 0.0 across the exact, PQ, and
     gather tiers (online recall against exact ground truth, in
     miniature and deterministic).
  2. SNAPSHOT PINNING — an audit that runs AFTER deletes published a new
     generation still compares against the generation the live dispatch
     read; the same audit against the CURRENT state would score < 1.
  3. SUBORDINATION — drop-not-queue admission sheds (counted) beyond the
     concurrency budget, and an over-budget host scan aborts on the
     audit deadline; neither touches the live path.
  4. DISABLED = ZERO AUDIT WORK — with the sample rate 0 the serving
     path constructs no audit objects (spy-pinned, the tracing/perf
     contract).
  5. DEGRADATION ALERTS — the per-tier EWMA fires the counter once per
     transition and the log at most once per interval.
"""

import json
import logging
import threading
import urllib.request
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config, load_config
from weaviate_tpu.entities.filters import LocalFilter
from weaviate_tpu.monitoring import costmodel, quality
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 400, 16, 5


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    quality.configure(None)


def _mk_app(tmp_path, sample_rate=1.0, coalesce=False, pq=False, n=N,
            **quality_kw):
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = coalesce
    cfg.quality.audit_sample_rate = sample_rate
    for k, v in quality_kw.items():
        setattr(cfg.quality, k, v)
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    cls = {"class": "Ql", "vectorIndexType": "hnsw_tpu",
           "vectorIndexConfig": {"distance": "l2-squared"},
           "properties": [{"name": "tag", "dataType": ["text"]}]}
    if pq:
        cls["vectorIndexConfig"]["pq"] = {
            "enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16}
    app.schema.add_class(cls)
    rng = np.random.default_rng(11)
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    idx = app.db.get_index("Ql")
    idx.put_batch([
        StorObj(class_name="Ql", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i])
        for i in range(n)])
    return app, idx, vecs


def _tie_free_queries(vecs, count):
    out, i = [], 0
    while len(out) < count:
        q = vecs[i] + 0.5
        i += 1
        d = np.sort(((vecs - q) ** 2).sum(1))[: K + 8]
        if len(np.unique(d)) == len(d):
            out.append(q)
    return out


# -- scoring math -------------------------------------------------------------


def test_recall_rbo_relerr_on_identical_and_disjoint_lists():
    ids = [3, 1, 4, 2, 5][:K]
    assert quality.recall_at_k([3, 1, 4], [3, 1, 4], 3) == 1.0
    assert quality.recall_at_k([9, 9, 9], [1, 2, 3], 3) == 0.0
    assert quality.recall_at_k([1, 2], [], 3) == 1.0  # nothing to miss
    assert quality.rank_biased_overlap(ids, ids, K) == pytest.approx(1.0)
    assert quality.rank_biased_overlap([1, 2, 3], [7, 8, 9], 3) == 0.0
    assert quality.relative_distance_error([1.0, 2.0], [1.0, 2.0]) == 0.0
    assert quality.relative_distance_error([1.1, 2.0], [1.0, 2.0]) == \
        pytest.approx(0.05)


def test_rbo_penalizes_order_recall_does_not():
    a, b = [1, 2, 3, 4, 5], [5, 4, 3, 2, 1]
    assert quality.recall_at_k(a, b, 5) == 1.0
    rbo = quality.rank_biased_overlap(a, b, 5)
    assert 0.0 < rbo < 1.0  # same set, wrong order: visible only to RBO


def test_score_batch_trims_inf_padding():
    live_ids = np.array([[1, 2, 3, 0, 0]], dtype=np.uint64)
    live_d = np.array([[0.1, 0.2, 0.3, np.inf, np.inf]], np.float32)
    host_ids = np.array([[1, 2, 3, 0, 0]], dtype=np.uint64)
    host_d = np.array([[0.1, 0.2, 0.3, np.inf, np.inf]], np.float32)
    rec, rbo, err = quality.score_batch(live_ids, live_d, host_ids,
                                        host_d, 5)
    assert (rec, rbo, err) == (1.0, 1.0, 0.0)


# -- end-to-end: live searches audit to recall 1.0 ----------------------------


def test_auditor_scores_live_traffic_exact_tier(tmp_path):
    app, idx, vecs = _mk_app(tmp_path)
    try:
        queries = _tie_free_queries(vecs, 4)
        for q in queries:
            res = app.traverser.get_class(GetParams(
                class_name="Ql", near_vector={"vector": q.tolist()},
                limit=K))
            assert len(res) == K
            assert app.quality_auditor.drain(20)  # audit each before next
        s = app.quality_auditor.summary()
        tier = s["tiers"][costmodel.TIER_EXACT]
        assert tier["audits"] == 4
        assert tier["recall_mean"] == 1.0
        assert tier["rbo_mean"] == 1.0
        assert tier["distance_relerr_mean"] == 0.0
        assert s["online_recall"] == 1.0
        assert s["audits"]["shed"] == 0 and s["audits"]["error"] == 0
        text = app.metrics.expose().decode()
        assert 'weaviate_recall_at_k{tier="exact_scan"} 1.0' in text
        assert "weaviate_quality_audits_total" in text
    finally:
        app.shutdown()


def test_auditor_covers_pq_and_filtered_gather_tiers(tmp_path):
    """Both PQ tiers' twin: integer data is bf16-exact, so even the
    compressed fast-scan path audits to recall 1.0; a filtered search
    below flat_search_cutoff audits the gather tier with the SAME
    allowList the live dispatch used."""
    app, idx, vecs = _mk_app(tmp_path, pq=True, n=512)
    try:
        shard = idx.single_local_shard()
        assert shard.vector_index.compressed
        queries = _tie_free_queries(vecs, 3)
        for q in queries:
            app.traverser.get_class(GetParams(
                class_name="Ql", near_vector={"vector": q.tolist()},
                limit=K))
            assert app.quality_auditor.drain(20)
        flt = {"path": ["tag"], "operator": "Equal", "valueText": "even"}
        for q in queries:
            app.traverser.get_class(GetParams(
                class_name="Ql", near_vector={"vector": q.tolist()},
                limit=K, filters=LocalFilter.from_dict(flt)))
            assert app.quality_auditor.drain(20)
        s = app.quality_auditor.summary()
        pq_tier = s["tiers"][costmodel.TIER_PQ_RESCORE]
        assert pq_tier["audits"] == 3 and pq_tier["recall_mean"] == 1.0
        g_tier = s["tiers"][costmodel.TIER_GATHER]
        assert g_tier["audits"] == 3 and g_tier["recall_mean"] == 1.0
    finally:
        app.shutdown()


def test_auditor_works_through_coalesced_lanes(tmp_path):
    """The capture point sits at the shard, so coalesced dispatches audit
    like direct ones (the lane's merged batch is one sample)."""
    app, idx, vecs = _mk_app(tmp_path, coalesce=True)
    try:
        q = _tie_free_queries(vecs, 1)[0]
        app.traverser.get_class(GetParams(
            class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
        assert app.quality_auditor.drain(20)
        s = app.quality_auditor.summary()
        assert s["audits"]["ok"] >= 1
        assert s["online_recall"] == 1.0
    finally:
        app.shutdown()


# -- snapshot pinning ---------------------------------------------------------


def test_audit_compares_against_the_pinned_generation(tmp_path):
    """Deletes published BETWEEN capture and audit must not skew the
    comparison: the audit runs against the snapshot the live dispatch
    read and scores 1.0, while the same answer scored against the
    CURRENT state would lose the deleted winners."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        aud = app.quality_auditor
        shard = idx.single_local_shard()
        vidx = shard.vector_index
        tasks = []
        orig_submit = quality.QualityAuditor.submit
        aud.submit = lambda task: (tasks.append(task), True)[1]
        q = _tie_free_queries(vecs, 1)[0]
        res = app.traverser.get_class(GetParams(
            class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
        assert len(tasks) == 1
        task = tasks[0]
        pinned_gen = task.snap.gen
        # delete every live winner, publish a new generation
        for r in res:
            shard.delete_object(r.obj.uuid)
        vidx.flush()
        assert vidx.snapshot_gen > pinned_gen
        # the pinned comparison is clean...
        aud.submit = orig_submit.__get__(aud)
        assert aud.submit(task)
        assert aud.drain(20)
        s = aud.summary()
        assert s["tiers"][costmodel.TIER_EXACT]["recall_mean"] == 1.0
        # ...while the CURRENT host plane no longer contains the winners
        cur_ids, _ = vidx.search_by_vectors_host(task.q, K)
        live_set = set(int(x) for x in np.asarray(task.live_ids)[0])
        assert not live_set & set(int(x) for x in cur_ids[0])
    finally:
        app.shutdown()


# -- subordination ------------------------------------------------------------


def test_drop_not_queue_sheds_beyond_the_budget():
    aud = quality.QualityAuditor(sample_rate=1.0, concurrency=1,
                                 start_workers=False)
    t = object()  # never executed: admission only
    assert aud.submit(t) is True      # queue capacity == concurrency
    assert aud.submit(t) is False     # full -> shed, not queued
    assert aud.submit(t) is False
    s = aud.window.summary()
    assert s["audits"]["shed"] == 2
    aud.shutdown()


def test_deadline_bounds_the_host_scan(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, audit_deadline_ms=1e-9)
    try:
        aud = app.quality_auditor
        tasks = []
        aud.submit = lambda task: (tasks.append(task), True)[1]
        q = _tie_free_queries(vecs, 1)[0]
        app.traverser.get_class(GetParams(
            class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
        assert len(tasks) == 1
        with pytest.raises(quality.AuditDeadlineExceeded):
            aud._run_audit(tasks[0])
    finally:
        app.shutdown()


def test_row_budget_subsamples_wide_batches(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, audit_max_rows=4)
    try:
        aud = app.quality_auditor
        tasks = []
        aud.submit = lambda task: (tasks.append(task), True)[1]
        shard = idx.single_local_shard()
        q = np.stack(_tie_free_queries(vecs, 8))
        shard.object_vector_search(q, K)
        assert len(tasks) == 1
        assert tasks[0].q.shape[0] == 4  # 8 rows budgeted down to 4
        assert tasks[0].live_ids.shape[0] == 4
    finally:
        app.shutdown()


# -- disabled = zero audit work (spy-pinned) ----------------------------------


def test_disabled_serving_path_constructs_no_audit_objects(tmp_path,
                                                           monkeypatch):
    app, idx, vecs = _mk_app(tmp_path, sample_rate=0.0)
    calls = []

    def spy(name):
        def boom(*a, **kw):
            calls.append(name)
            raise AssertionError(f"quality.{name} touched while disabled")
        return boom

    monkeypatch.setattr(quality, "_AuditTask", spy("_AuditTask"))
    monkeypatch.setattr(quality.QualityAuditor, "maybe_capture",
                        spy("maybe_capture"))
    try:
        assert app.quality_auditor is None
        assert quality.get_auditor() is None
        res = app.traverser.get_class(GetParams(
            class_name="Ql",
            near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
        assert len(res) == K
        # the index pinned nothing either (the handle's gate)
        vidx = idx.single_local_shard().vector_index
        assert vidx.search_by_vectors_async(vecs[:1] + 0.5, K).snapshot is None
        assert calls == []
    finally:
        app.shutdown()


def test_default_config_disables_auditing():
    assert load_config({}).quality.audit_sample_rate == 0.0


# -- degradation alerts -------------------------------------------------------


def test_degradation_alert_fires_once_per_transition(tmp_path, caplog):
    app, idx, vecs = _mk_app(tmp_path, alert_threshold=0.9,
                             alert_min_samples=3)
    try:
        aud = app.quality_auditor
        with caplog.at_level(logging.WARNING,
                             logger="weaviate_tpu.monitoring.quality"):
            for _ in range(6):
                aud._observe("exact_scan", 0.5, 0.5, 0.1, 1, 1.0)
        lines = [r for r in caplog.records
                 if "online recall degraded" in r.getMessage()]
        assert len(lines) == 1  # rate-limited: one line per interval
        text = app.metrics.expose().decode()
        assert ('weaviate_quality_degraded_total{tier="exact_scan"} 1.0'
                in text)
        assert aud.summary()["tiers"]["exact_scan"]["degraded"] is True
        # recovery flips the state (counter does not re-fire on healthy)
        for _ in range(30):
            aud._observe("exact_scan", 1.0, 1.0, 0.0, 1, 1.0)
        assert aud.summary()["tiers"]["exact_scan"]["degraded"] is False
    finally:
        app.shutdown()


def test_no_alert_before_min_samples(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, alert_threshold=0.9,
                             alert_min_samples=50)
    try:
        aud = app.quality_auditor
        for _ in range(10):
            aud._observe("exact_scan", 0.0, 0.0, 0.0, 1, 1.0)
        assert aud.summary()["tiers"]["exact_scan"]["degraded"] is False
        text = app.metrics.expose().decode()
        assert 'weaviate_quality_degraded_total{tier="exact_scan"}' \
            not in text
    finally:
        app.shutdown()


# -- exposition: /debug/quality, /debug/index, /debug -------------------------


def test_debug_quality_and_index_endpoints(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        shard = idx.single_local_shard()
        q = _tie_free_queries(vecs, 1)[0]
        app.traverser.get_class(GetParams(
            class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
        assert app.quality_auditor.drain(20)
        for uid in (2, 4, 6):
            shard.delete_object(str(uuidlib.UUID(int=uid)))
        shard.vector_index.flush()

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/quality",
                timeout=30) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["online_recall"] == 1.0
        assert body["audits"]["ok"] >= 1

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/index", timeout=30) as r:
            body = json.loads(r.read())
        h = body["indexes"]["Ql"][shard.name]
        vh = h["vector_index"]
        assert vh["type"] == "hnsw_tpu"
        assert vh["live"] == N - 3
        assert vh["tombstones"] == 3
        assert vh["tombstone_fraction"] == pytest.approx(3 / N, abs=1e-4)
        assert vh["snapshot_gen"] >= 1
        assert vh["staged_lag"] == 0
        assert vh["compressed"] is False and vh["pq"] is None
        assert vh["host_fallback_cache"]["resident"] is False
        assert h["allow_cache"]["capacity"] == 16

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug", timeout=30) as r:
            body = json.loads(r.read())
        eps = body["endpoints"]
        for path in ("/debug/traces", "/debug/perf", "/debug/quality",
                     "/debug/index", "/debug/pprof/"):
            assert path in eps and eps[path]
    finally:
        srv.stop()
        app.shutdown()


def test_debug_index_reports_pq_state(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, sample_rate=0.0, pq=True, n=512)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        shard = idx.single_local_shard()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/index", timeout=30) as r:
            body = json.loads(r.read())
        vh = body["indexes"]["Ql"][shard.name]["vector_index"]
        assert vh["compressed"] is True
        assert vh["pq"]["segments"] == 4
        assert vh["pq"]["centroids"] == 16
        assert vh["pq"]["rescore"] is True
    finally:
        srv.stop()
        app.shutdown()


def test_debug_quality_disabled_reports_disabled(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, sample_rate=0.0)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/quality",
                timeout=30) as r:
            body = json.loads(r.read())
        assert body == {"enabled": False}
    finally:
        srv.stop()
        app.shutdown()


# -- always-on health gauges --------------------------------------------------


def test_health_gauges_stamped_on_write_path_without_any_plane(tmp_path):
    """Tracing off, auditing off: the write path still stamps live count
    and tombstone fraction (the cheap always-on satellite)."""
    app, idx, vecs = _mk_app(tmp_path, sample_rate=0.0)
    try:
        shard = idx.single_local_shard()
        for uid in (1, 2, 3, 4):
            shard.delete_object(str(uuidlib.UUID(int=uid)))
        shard.vector_index.flush()  # deletes apply + gauges stamp
        text = app.metrics.expose().decode()
        assert f'weaviate_vector_index_live_count{{class_name="Ql",'\
            f'shard_name="{shard.name}"}} {float(N - 4)}' in text
        assert 'weaviate_index_tombstone_fraction' in text
    finally:
        app.shutdown()


# -- lifecycle ----------------------------------------------------------------


def test_unconfigure_stashes_final_summary(tmp_path):
    app, idx, vecs = _mk_app(tmp_path)
    q = _tie_free_queries(vecs, 1)[0]
    app.traverser.get_class(GetParams(
        class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
    assert app.quality_auditor.drain(20)
    app.shutdown()
    assert quality.get_auditor() is None
    recents = quality.recent_summaries()
    assert any(s.get("audits", {}).get("ok") for s in recents)


def test_audit_worker_survives_a_poison_task(tmp_path):
    """The exception-guarded run loop (graftlint JGL011's runtime twin):
    a task that blows up is counted as an error and the NEXT audit still
    completes on the same worker."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        aud = app.quality_auditor

        class Boom:
            snap = None
            t_captured = 0.0

        assert aud.submit(Boom())  # poison: _run_audit raises on it
        assert aud.drain(20)       # poison consumed (counted as error)
        q = _tie_free_queries(vecs, 1)[0]
        app.traverser.get_class(GetParams(
            class_name="Ql", near_vector={"vector": q.tolist()}, limit=K))
        assert aud.drain(20)
        s = aud.summary()
        assert s["audits"]["error"] == 1
        assert s["audits"]["ok"] >= 1  # the worker lived on
    finally:
        app.shutdown()
