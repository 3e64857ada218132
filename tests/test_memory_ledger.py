"""Memory & capacity observability (monitoring/memory.py): the
device/host/disk byte ledger, write-path lifecycle instrumentation, and
/debug/memory with exhaustion forecasting.

The acceptance-critical invariants pinned here:

  1. BIT-EXACT ACCOUNTING — the ledger's device bytes for a published
     snapshot equal the sum of its buffers' ``nbytes`` exactly, per
     tier (exact, PQ rescore, PQ codes-only, mesh per-device), and
     publish/compress/compact transitions leave no stale components.
  2. ZERO HOT-PATH WORK — a search dispatch touches the ledger not at
     all (spy-pinned) and performs the same number of host transfers
     with the ledger configured as without (no added device syncs).
  3. FORECAST ALERTS — a synthetic fill drives headroom monotonically
     down and fires the exhaustion alert exactly once per transition,
     with recovery re-arming it.
  4. BOUNDED LABELS — foreign component names fold into "other"; the
     gauge label set is the fixed taxonomy.
  5. ONE TRUTH — /debug/index cache byte sizes come from the same
     sizing helpers the ledger's host providers use.
"""

import json
import urllib.request
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config, ConfigError, load_config
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import memory
from weaviate_tpu.monitoring.metrics import noop_metrics
from weaviate_tpu.storage.bitmap import Bitmap

N, DIM, K = 600, 16, 5


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    memory.configure(None)


def _mk_ledger(**kw):
    kw.setdefault("metrics", noop_metrics())
    return memory.configure(memory.MemoryLedger(**kw))


def _mk_index(tmp_path, pq=None, n=N, name="s"):
    d = {"distance": "l2-squared"}
    if pq:
        d["pq"] = pq
    cfg = parse_and_validate_config("hnsw_tpu", d)
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    rng = np.random.default_rng(7)
    vecs = rng.standard_normal((n, DIM)).astype(np.float32)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs


# -- bit-exact device accounting ----------------------------------------------


def test_exact_tier_components_equal_snapshot_nbytes(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(tmp_path)
    snap = idx._snap
    comps = led.device_components()
    assert comps == {
        "store": snap.store.nbytes,
        "sq_norms": snap.sq_norms.nbytes,
        "tombs": snap.tombs.nbytes,
        "slot_to_doc": snap.slot_to_doc_dev.nbytes,
    }
    assert led.device_bytes_total() == (
        snap.store.nbytes + snap.sq_norms.nbytes + snap.tombs.nbytes
        + snap.slot_to_doc_dev.nbytes)


def test_pq_rescore_tier_components_and_no_stale_store(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(
        tmp_path, pq={"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16},
        n=512)
    assert idx.compressed
    snap = idx._snap
    comps = led.device_components()
    # the float store was dropped at compression: no stale component
    assert comps == {
        "tombs": snap.tombs.nbytes,
        "slot_to_doc": snap.slot_to_doc_dev.nbytes,
        "pq_codes": snap.codes.nbytes,
        "recon_norms": snap.recon_norms.nbytes,
        "rescore_store": snap.rescore_dev.nbytes,
        "rescore_sq_norms": snap.rescore_sq_norms.nbytes,
    }


def test_pq_codes_only_tier_has_no_rescore_components(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(
        tmp_path,
        pq={"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16,
            "rescore": False},
        n=512)
    assert idx.compressed and idx._rescore_dev is None
    snap = idx._snap
    comps = led.device_components()
    assert comps == {
        "tombs": snap.tombs.nbytes,
        "slot_to_doc": snap.slot_to_doc_dev.nbytes,
        "pq_codes": snap.codes.nbytes,
        "recon_norms": snap.recon_norms.nbytes,
    }


def test_mesh_components_and_per_device_split(tmp_path):
    import jax

    from weaviate_tpu.index.mesh import MeshVectorIndex

    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    led = _mk_ledger()
    cfg = parse_and_validate_config("hnsw_tpu_mesh",
                                    {"distance": "l2-squared"})
    idx = MeshVectorIndex(cfg, str(tmp_path / "m"), persist=False,
                          initial_capacity_per_shard=64)
    rng = np.random.default_rng(3)
    vecs = rng.standard_normal((300, DIM)).astype(np.float32)
    idx.add_batch(np.arange(300), vecs)
    idx.flush()
    comps = led.device_components()
    assert comps["store"] == idx._store.nbytes
    assert comps["sq_norms"] == idx._sq_norms.nbytes
    assert comps["tombs"] == idx._tombs.nbytes
    assert comps["allow_words"] == idx._zero_words.nbytes
    total = sum(comps.values())
    doc = led.summary()
    assert doc["device"]["total_bytes"] == total
    # mesh slabs spread evenly: per-chip bytes are total / n_dev
    assert doc["device"]["per_device_bytes"] == total // idx.n_dev


def test_compact_transition_tracks_new_snapshot(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(tmp_path)
    idx.delete(*range(0, N, 2))
    idx.flush()
    idx.compact()
    snap = idx._snap
    comps = led.device_components()
    assert comps == {
        "store": snap.store.nbytes,
        "sq_norms": snap.sq_norms.nbytes,
        "tombs": snap.tombs.nbytes,
        "slot_to_doc": snap.slot_to_doc_dev.nbytes,
    }
    phases = led.summary()["write"]["phases"]
    assert phases["compact"]["samples"] >= 1


def test_drop_zeroes_device_components(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(tmp_path)
    assert led.device_bytes_total() > 0
    idx.drop()
    assert led.device_components() == {}


# -- write-path lifecycle -----------------------------------------------------


def test_write_lifecycle_phases_cow_and_publish_lag(tmp_path):
    led = _mk_ledger()
    idx, vecs = _mk_index(tmp_path)
    # staged single-row adds + deletes, then a flush: the COW copy of the
    # pinned slot/tombstone mirrors and the transient device peak land
    idx.add(N + 1, vecs[0])
    idx.delete(3, 5)
    idx.flush()
    doc = led.summary()["write"]
    assert doc["phases"]["device_write"]["rows"] == N
    assert doc["phases"]["device_write"]["bytes"] == N * DIM * 4
    assert doc["phases"]["flush"]["rows"] == 1
    # the staged row took the slot of one of the two staged tombstones (an
    # overwrite in place, no tombstone bit set); the other one was applied
    assert doc["phases"]["apply_tombstones"]["rows"] == 1
    assert idx.health()["writes"]["slots_reused"] == 1
    assert idx.health()["free_slots"] == 1
    assert doc["cow_copy_bytes_total"] > 0
    # the non-donating write's transient peak covers the replaced store
    assert doc["cow_transient_peak_bytes"] >= \
        memory.array_bytes(idx._store)
    assert doc["staged_publish_lag_ms"]["p50"] >= 0.0
    assert doc["publishes_total"] >= 2


def test_jit_first_seen_write_shapes(tmp_path):
    led = _mk_ledger()
    idx, _ = _mk_index(tmp_path)
    with idx._lock:
        idx._ensure_capacity(idx.capacity + 1)  # force a geometric double
    shapes = [tuple(e["shape"]) for e in led.summary()["jit_first_seen"]]
    # 600 rows are one `_write_slots` program (a 1,024-row bucket), not a
    # zero-padded `_CHUNK` through `_write_rows`
    assert any(s[0] == "write_slots" for s in shapes)
    assert any(s[0] == "grow" for s in shapes)


# -- forecast + fire-once alerts ----------------------------------------------


class _Owner:
    pass


def test_synthetic_fill_headroom_monotone_and_alert_fires_once():
    led = _mk_ledger(device_budget_bytes=1_000_000,
                     headroom_alert_pct=20.0)
    owner = _Owner()
    headrooms = []
    for used in range(100_000, 1_000_001, 100_000):
        led.stamp_device(owner, {"store": used})
        fc = led.forecast_scope("device", used, 1_000_000)
        headrooms.append(fc["headroom_pct"])
    assert headrooms == sorted(headrooms, reverse=True)  # monotone down
    fc = led.summary()["forecast"]["device"]
    assert fc["alert"] is True
    assert fc["alerts_fired"] == 1  # fired exactly once across the fill
    text = led.metrics.expose().decode()
    assert ('weaviate_memory_exhaustion_alerts_total'
            '{scope="device"} 1.0') in text
    # the fill ended at used == budget: the gauge reads zero headroom
    assert 'weaviate_memory_headroom_pct{scope="device"} 0.0' in text
    # ingest EWMA saw growth -> a time-to-exhaustion estimate existed
    assert fc["ingest_bps"] is not None


def test_alert_recovery_rearms_for_next_transition():
    led = _mk_ledger(device_budget_bytes=1_000_000,
                     headroom_alert_pct=20.0)
    owner = _Owner()
    led.stamp_device(owner, {"store": 950_000})
    assert led.summary()["forecast"]["device"]["alerts_fired"] == 1
    led.stamp_device(owner, {"store": 990_000})  # still degraded: no refire
    assert led.summary()["forecast"]["device"]["alerts_fired"] == 1
    led.stamp_device(owner, {"store": 100_000})  # recovery
    assert led.summary()["forecast"]["device"]["alert"] is False
    led.stamp_device(owner, {"store": 960_000})  # second transition
    fc = led.summary()["forecast"]["device"]
    assert fc["alert"] is True and fc["alerts_fired"] == 2


def test_tte_estimate_positive_under_growth():
    led = _mk_ledger(device_budget_bytes=10_000_000)
    owner = _Owner()
    import time as _time

    for used in (1_000_000, 2_000_000, 3_000_000):
        led.stamp_device(owner, {"store": used})
        _time.sleep(0.01)
    fc = led.forecast_scope("device", 3_000_000, 10_000_000)
    assert fc["ingest_bps"] > 0
    assert fc["tte_s"] > 0


# -- bounded labels -----------------------------------------------------------


def test_foreign_component_names_fold_into_other():
    led = _mk_ledger()
    owner = _Owner()
    led.stamp_device(owner, {f"weird_{i}": 10 for i in range(50)})
    comps = led.device_components()
    assert set(comps) == {"other"}
    assert comps["other"] == 500
    text = led.metrics.expose().decode()
    assert 'weaviate_device_bytes{component="other"} 500.0' in text
    assert "weird_" not in text


# -- zero hot-path work -------------------------------------------------------


def test_search_touches_no_ledger_entry_points(tmp_path, monkeypatch):
    _mk_ledger()
    idx, vecs = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:4], K)  # warm + publish settled
    calls = []
    for name in ("stamp_device", "note_write", "note_cow", "note_publish",
                 "note_write_shape", "refresh_host"):
        monkeypatch.setattr(
            memory.MemoryLedger, name,
            lambda self, *a, _n=name, **k: calls.append(_n))
    for _ in range(3):
        idx.search_by_vectors(vecs[:4], K)
    assert calls == []


def test_search_host_transfer_count_unchanged_by_ledger(tmp_path,
                                                        monkeypatch):
    led = _mk_ledger()
    idx, vecs = _mk_index(tmp_path)
    idx.search_by_vectors(vecs[:4], K)  # warm compile caches

    counts = {"n": 0}
    real = np.asarray

    def counting(*a, **k):
        counts["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(np, "asarray", counting)
    assert memory.get_ledger() is led
    idx.search_by_vectors(vecs[:4], K)
    with_ledger = counts["n"]
    memory.configure(None)
    counts["n"] = 0
    idx.search_by_vectors(vecs[:4], K)
    assert with_ledger == counts["n"]  # zero added transfers/syncs


# -- host providers + the one-truth helpers -----------------------------------


def test_host_components_cover_mirrors_and_breaker_cache(tmp_path):
    led = _mk_ledger()
    idx, vecs = _mk_index(tmp_path)
    # this index's provider reports its mirrors exactly...
    comps = memory.index_host_components(idx)
    assert comps["slot_to_doc"] == idx._slot_to_doc.nbytes
    assert comps["host_tombs"] == idx._host_tombs.nbytes
    assert "breaker_rows" not in comps
    # ...and the ledger's aggregate covers it (other tests' still-live
    # indexes may also be registered, so the aggregate is a lower bound)
    totals = led.host_totals()
    assert totals["slot_to_doc"] >= idx._slot_to_doc.nbytes
    # the breaker's host-fallback plane materializes its cache...
    before = totals.get("breaker_rows", 0)
    idx.search_by_vectors_host(vecs[:2], K)
    expected = memory.host_rows_cache_bytes(idx)
    assert expected > 0
    assert led.host_totals().get("breaker_rows", 0) - before == expected
    # ...and releasing it (breaker recovery) drops the component
    idx.release_host_fallback_cache()
    assert led.host_totals().get("breaker_rows", 0) == before


def test_allow_words_device_bytes_counted_via_device_provider(tmp_path):
    """The packed device filter words a hot bitmap caches are DEVICE
    bytes outside snapshot stamping — the device-provider pull accounts
    them (an unaccounted HBM buffer would read as headroom that isn't
    there)."""
    led = _mk_ledger()
    idx, vecs = _mk_index(tmp_path)
    idx.config.flat_search_cutoff = 1  # force the masked-scan path
    bm = Bitmap(np.arange(100, dtype=np.uint64))
    idx.search_by_vectors(vecs[:4], K, allow_list=bm)
    assert getattr(bm, "_words_cache", None) is not None
    words_bytes = memory.array_bytes(bm._words_cache[1])
    assert words_bytes > 0

    class FakeShard:
        pass

    sh = FakeShard()
    sh._allow_cache = {"k": (0, bm, "t")}
    assert memory.allow_words_device_bytes(sh) == words_bytes
    memory.register_device_provider(sh, memory.shard_device_components)
    # other live shards may contribute too: a lower bound on the aggregate
    assert led.device_components().get("allow_words", 0) >= words_bytes


def test_allow_cache_and_auditor_sizing_helpers():
    class FakeShard:
        pass

    sh = FakeShard()
    bm = Bitmap(np.array([1, 2, 3], dtype=np.uint64))
    sh._allow_cache = {"k": (0, bm, "tenant")}
    assert memory.allow_cache_bytes(sh) == bm._ids.nbytes
    assert memory.shard_host_components(sh) == {
        "allow_cache": bm._ids.nbytes}

    class FakeAuditor:
        pass

    class FakeIdx:
        pass

    aud = FakeAuditor()
    vidx = FakeIdx()
    rows = np.zeros((10, 4), np.float32)
    sq = np.zeros(10, np.float32)
    aud._rows_cache = {id(vidx): (object(), rows, sq)}
    assert memory.auditor_rows_bytes(aud) == rows.nbytes + sq.nbytes
    assert memory.auditor_rows_bytes(aud, vidx) == rows.nbytes + sq.nbytes
    assert memory.auditor_rows_bytes(aud, FakeIdx()) == 0
    assert memory.auditor_rows_bytes(None) == 0


# -- end-to-end: App + /debug/memory + /debug/index ---------------------------


def _mk_app(tmp_path, **memory_kw):
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    cfg = Config()
    for k, v in memory_kw.items():
        setattr(cfg.memory, k, v)
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Mem", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tag", "dataType": ["text"]}]})
    rng = np.random.default_rng(5)
    vecs = rng.standard_normal((128, DIM)).astype(np.float32)
    idx = app.db.get_index("Mem")
    idx.put_batch([
        StorObj(class_name="Mem", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "t"}, vector=vecs[i])
        for i in range(128)])
    return app, idx, vecs


def test_debug_memory_endpoint_metrics_and_debug_root(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        assert app.memory_ledger is not None
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/memory",
                timeout=30) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["device"]["components"]["store"] > 0
        assert body["host"]["components"]["slot_to_doc"] > 0
        assert body["disk"]["components"]["used"] > 0
        assert set(body["forecast"]) == {"device", "host", "disk"}
        assert body["write"]["phases"]["device_write"]["rows"] == 128
        # the host scope always has a detectable budget on linux
        assert body["forecast"]["host"]["budget_bytes"] > 0

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug", timeout=30) as r:
            eps = json.loads(r.read())["endpoints"]
        assert "/debug/memory" in eps

        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert 'weaviate_device_bytes{component="store"}' in text
        assert 'weaviate_host_bytes{component="slot_to_doc"}' in text
        assert 'weaviate_disk_bytes{component="used"}' in text
        assert 'weaviate_memory_headroom_pct{scope="host"}' in text
        assert "weaviate_write_flush_ms" in text
        assert "weaviate_cow_copy_bytes_total" in text
    finally:
        srv.stop()
        app.shutdown()


def test_debug_index_bytes_sourced_from_ledger_helpers(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        shard = idx.single_local_shard()
        bm = Bitmap(np.array([1, 2, 3, 4], dtype=np.uint64))
        shard._allow_cache["fake"] = (shard._locked_gen(), bm, "t")
        vidx = shard.vector_index
        vidx.search_by_vectors_host(vecs[:1], K)  # residize breaker cache
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/index", timeout=30) as r:
            h = json.loads(r.read())["indexes"]["Mem"][shard.name]
        assert h["allow_cache"]["bytes"] == memory.allow_cache_bytes(shard)
        assert h["allow_cache"]["bytes"] == bm._ids.nbytes
        assert h["host_fallback_cache_bytes"] == \
            memory.host_rows_cache_bytes(vidx)
        assert h["host_fallback_cache_bytes"] > 0
        assert h["auditor_rows_bytes"] == 0  # no auditor configured
        vh = h["vector_index"]
        assert vh["host_fallback_cache"]["bytes"] == \
            h["host_fallback_cache_bytes"]
        assert vh["memory"]["device_components"]["store"] > 0
    finally:
        srv.stop()
        app.shutdown()


def test_ledger_disabled_app_and_endpoint(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, ledger_enabled=False)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        assert app.memory_ledger is None
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/memory",
                timeout=30) as r:
            assert json.loads(r.read()) == {"enabled": False}
    finally:
        srv.stop()
        app.shutdown()


def test_final_summary_stash_for_ci_artifact(tmp_path):
    led = _mk_ledger()
    owner = _Owner()  # kept alive: the ledger holds owners by weakref
    led.stamp_device(owner, {"store": 1024})
    memory.unconfigure(led)
    docs = memory.recent_summaries()
    assert docs and docs[-1]["device"]["total_bytes"] == 1024
    assert memory.get_ledger() is None


# -- config -------------------------------------------------------------------


def test_config_parsing_and_validation():
    cfg = load_config({
        "MEMORY_LEDGER_ENABLED": "false",
        "MEMORY_LEDGER_WINDOW_S": "120",
        "MEMORY_HEADROOM_ALERT_PCT": "25",
        "MEMORY_DEVICE_BUDGET_BYTES": "123456",
        "MEMORY_HOST_BUDGET_BYTES": "654321",
    })
    assert cfg.memory.ledger_enabled is False
    assert cfg.memory.window_s == 120.0
    assert cfg.memory.headroom_alert_pct == 25.0
    assert cfg.memory.device_budget_bytes == 123456
    assert cfg.memory.host_budget_bytes == 654321
    assert load_config({}).memory.ledger_enabled is True
    with pytest.raises(ConfigError):
        load_config({"MEMORY_LEDGER_WINDOW_S": "0"})
    with pytest.raises(ConfigError):
        load_config({"MEMORY_HEADROOM_ALERT_PCT": "101"})
    with pytest.raises(ConfigError):
        load_config({"MEMORY_DEVICE_BUDGET_BYTES": "-1"})
