"""Continuous device-performance attribution (monitoring/costmodel.py +
monitoring/perf.py) and its wiring.

The acceptance-critical invariants pinned here:

  1. ONE TIMELINE — while profiling.device_trace has a capture open the
     window keeps every closed host phase as an interval on
     perf_counter_ns, bounded, nested as the code nests, and within 1 ms
     of the same `wv/*` annotations read back from the capture's own
     /host:CPU plane.
  2. DUTY-CYCLE MATH — the busy integrator computes the interval UNION
     (overlaps merged, window trimmed) on synthetic interval sets.
  3. DISABLED = ZERO PERF WORK — with TRACING_ENABLED unset, the serving
     path constructs no DispatchShape and never touches the PerfWindow
     (spy-asserted the same way as the tracing spy).
  4. EXPOSITION — /debug/perf serves the window summary and the last
     capture end to end and /metrics carries the duty gauge; the host-wall
     roofline fields and gauges are gone.

Plus: cost-model tier formulas, the shared-costmodel BM25 batch shape,
the front-door gate sheds surfaced in coalescer stats, and the
signal/atexit device-trace teardown.
"""

import json
import threading
import time
import urllib.request
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config, load_config
from weaviate_tpu.monitoring import costmodel, perf, tracing
from weaviate_tpu.serving import robustness
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 400, 16, 5


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)


def _mk_app(tmp_path, tracing_on=True, coalesce=True, window_ms=200.0,
            n=N, pq=False):
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = coalesce
    cfg.coalescer.window_ms = window_ms
    cfg.tracing.enabled = tracing_on
    cfg.tracing.sample_rate = 1.0
    cfg.tracing.slow_query_threshold_ms = 0.0
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    cls = {"class": "Pf", "vectorIndexType": "hnsw_tpu",
           "vectorIndexConfig": {"distance": "l2-squared"},
           "properties": [{"name": "tag", "dataType": ["text"]}]}
    if pq:
        cls["vectorIndexConfig"]["pq"] = {
            "enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16}
    app.schema.add_class(cls)
    rng = np.random.default_rng(11)
    vecs = rng.integers(-8, 8, (n, DIM)).astype(np.float32)
    idx = app.db.get_index("Pf")
    idx.put_batch([
        StorObj(class_name="Pf", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "even" if i % 2 == 0 else "odd"},
                vector=vecs[i])
        for i in range(n)])
    return app, idx, vecs


def _walk(span):
    yield span
    for c in span.get("children", []):
        yield from _walk(c)


def _dispatch_spans(trace_dicts):
    return [s for tr in trace_dicts for s in _walk(tr["root"])
            if s["name"] == "dispatch"]


# -- cost model ---------------------------------------------------------------

def test_capture_log_bounded_and_anchored(monkeypatch):
    """Intervals are kept only while a capture is open, relative to the
    stamp taken before start_trace, at most CAPTURE_LOG_MAX of them (the
    rest counted), with the ledger restricted to the capture beside them."""
    monkeypatch.setattr(perf, "CAPTURE_LOG_MAX", 4)
    w = perf.PerfWindow(window_s=60.0)
    assert w.last_capture() is None
    w.note_interval("hydrate", 10, 20)          # no capture open: dropped
    w.capture_begin()
    for i in range(6):
        w.note_interval("hydrate", 1_000 + i * 1_000_000,
                        2_001_000 + i * 1_000_000, tid=7)
    w.capture_end(t0_ns=1_000, t1_ns=9_000_001_000,
                  options={"python_tracer_level": 0})
    w.note_interval("hydrate", 30, 40)          # closed again: dropped
    cap = w.last_capture()
    assert cap["id"] == 1 and cap["t0_ns"] == 0
    assert set(cap) == {"id", "seconds", "t0_ns", "t1_ns", "options",
                        "dropped", "intervals", "phases"}
    assert cap["t1_ns"] == 9_000_000_000
    assert cap["seconds"] == 9.0
    assert cap["options"] == {"python_tracer_level": 0}
    assert cap["dropped"] == 2
    assert cap["intervals"] == [["hydrate", 7, i * 1_000_000, 2_000_000]
                                for i in range(4)]
    assert cap["phases"] == {"hydrate": {"samples": 4, "p50_ms": 2.0,
                                         "p99_ms": 2.0}}
    # the capture is beside the summary, not in it: incident bundles and
    # the CI artifact carry the summary verbatim
    assert "capture" not in w.summary()
    w.capture_begin()
    w.capture_end(0, 1, {})
    assert w.last_capture()["id"] == 2 and w.last_capture()["intervals"] == []


def test_dispatch_shape_tier_formulas():
    # exact f32 scan: flops 2·B·N·D, bytes N·4D
    s = costmodel.DispatchShape(costmodel.TIER_EXACT, n=1000, dim=64,
                                batch=8, bytes_per_row=64 * 4, k=10)
    assert s.flops() == 2 * 8 * 1000 * 64
    assert s.bytes() == 1000 * 64 * 4
    # pq codes: same useful flops, M bytes per row
    s = costmodel.DispatchShape(costmodel.TIER_PQ_CODES, n=1000, dim=64,
                                batch=8, bytes_per_row=32, k=10)
    assert s.bytes() == 1000 * 32
    # bm25 matmul: n=n_pad, dim=U, batch=Q, bytes U·n_pad·4
    s = costmodel.DispatchShape(costmodel.TIER_BM25_MATMUL, n=4096,
                                dim=16, batch=64, bytes_per_row=16 * 4)
    assert s.flops() == 2 * 64 * 4096 * 16
    assert s.bytes() == 4096 * 16 * 4


def test_shape_ledger_and_hop():
    s = costmodel.DispatchShape(costmodel.TIER_EXACT, n=10, dim=4,
                                batch=1, bytes_per_row=16)
    assert s.ledger() == {}          # nothing measured yet
    assert s.hop_ms() == -1.0
    s.enqueue_ms = 1.0
    s.device_ms = 3.0
    s.finalize_ms = 5.0
    s.hydrate_ms = 2.0
    assert s.hop_ms() == pytest.approx(2.0)
    led = s.ledger()
    assert led == {"enqueue": 1.0, "device": 3.0,
                   "gather_hop": pytest.approx(2.0), "hydrate": 2.0}


def test_roofline_time_and_qps_forms_agree():
    # 1 batch/s of (B=256, N=1e5, D=128, f32): the QPS form at qps=256
    # equals the time form over 1 second of the same work
    f = 2.0 * 256 * 100_000 * 128
    b = 100_000 * 512
    a = costmodel.roofline(f, b, 1.0, costmodel.TPU_V5E)
    q = costmodel.roofline_from_qps(256.0, 100_000, 128, 256, 512, costmodel.TPU_V5E)
    assert a == q


def test_roofline_math_tpu_row():
    # 10k QPS over n=1M, d=128, batch=16384, f32 store:
    # flops/batch = 2*16384*1e6*128 = 4.194e12; batches/s = 10000/16384
    r = costmodel.roofline_from_qps(10_000.0, 1_000_000, 128, 16_384, 128 * 4,
                                    costmodel.TPU_V5E)
    assert r["tflops"] == pytest.approx(2 * 16384 * 1e6 * 128 * (10000 / 16384) / 1e12, rel=1e-3)
    assert r["hbm_gbs"] == pytest.approx(1e6 * 512 * (10000 / 16384) / 1e9, abs=0.01)
    assert r["mfu_pct"] == pytest.approx(100 * r["tflops"] / 197.0, abs=0.01)
    assert r["bw_pct"] == pytest.approx(100 * r["hbm_gbs"] / 819.0, abs=0.01)
    # AI = 2*B/bytes_per_elem = 2*16384/4 = 8192 >> ridge (~240): compute-bound
    assert r["arith_intensity_flops_per_byte"] == pytest.approx(8192, rel=1e-3)
    assert r["regime"] == "compute-bound"


def test_roofline_small_batch_is_bandwidth_bound():
    # batch=256 f32: AI = 128 flops/byte < v5e ridge ~240
    r = costmodel.roofline_from_qps(1_000.0, 100_000, 128, 256, 128 * 4,
                                    costmodel.TPU_V5E)
    assert r["regime"] == "hbm-bandwidth-bound"


# -- duty cycle ---------------------------------------------------------------

def test_duty_cycle_union_math():
    d = perf.DutyCycle(window_s=100.0)
    # disjoint: [0,1] + [2,3] = 2 busy over observed 10s
    d.record(0.0, 1.0)
    d.record(2.0, 3.0)
    assert d.value(now=10.0) == pytest.approx(0.2)
    # overlap merged: [2.5, 4] adds only 1s (2.5-3 already covered)
    d.record(2.5, 4.0)
    assert d.value(now=10.0) == pytest.approx(0.3)
    # containment adds nothing
    d.record(2.6, 3.9)
    assert d.value(now=10.0) == pytest.approx(0.3)


def test_duty_cycle_window_trim_and_saturation():
    d = perf.DutyCycle(window_s=5.0)
    d.record(0.0, 4.0)
    # at t=4 observed span is 4s, busy 4s -> 1.0
    assert d.value(now=4.0) == pytest.approx(1.0)
    # at t=20 the interval (attributed at its end, t=4) left the window
    assert d.value(now=20.0) == 0.0


def test_duty_cycle_empty():
    assert perf.DutyCycle(10.0).value(now=5.0) == 0.0


# -- the perf window (unit) ---------------------------------------------------

def _stamped_shape(device_ms=4.0, wall_ms=10.0, **kw):
    s = costmodel.DispatchShape(
        kw.pop("tier", costmodel.TIER_EXACT), n=kw.pop("n", 50_000),
        dim=kw.pop("dim", 64), batch=kw.pop("batch", 16),
        bytes_per_row=kw.pop("bytes_per_row", 256), k=10)
    s.enqueue_ms = 1.0
    s.device_ms = device_ms
    s.finalize_ms = device_ms + 1.5
    s.hydrate_ms = 2.0
    import time

    t = time.perf_counter()
    s.t_start = t - wall_ms / 1000.0
    s.t_fetch = t - 0.001
    s.t_end = t
    s.t_fetch_mono = time.monotonic()
    return s


def test_perf_window_summary_and_clear():
    w = perf.PerfWindow(window_s=60.0)
    for _ in range(4):
        w.record_dispatch(_stamped_shape(), rows=16)
    w.note_phase("queue_wait", 1.2)
    w.note_phase("scatter", 0.3)
    assert "point_get" not in w.summary()  # no native point-get call yet
    w.note_point_get(2560, 2560, 2560, 1)
    w.note_point_get(2560, 19000, 2570, 0)
    s = w.summary()
    assert s["point_get"] == {"keys": 5120, "segment_probes": 21560,
                              "key_compares": 5130, "arena_grows": 1,
                              "mem_layer_calls": 0, "mem_keys": 0,
                              "mirror_builds": 0, "overlay_fallbacks": 0}
    # a written bucket's memtable layer: a call that asked it, a mirror
    # built, a packed get left to the general path
    w.note_point_get(2560, 18000, 2500, 0, True, 31)
    w.note_point_get(mirror_builds=1)
    w.note_point_get(overlay_fallbacks=1)
    assert w.summary()["point_get"] == {
        "keys": 7680, "segment_probes": 39560, "key_compares": 7630,
        "arena_grows": 1, "mem_layer_calls": 1, "mem_keys": 31,
        "mirror_builds": 1, "overlay_fallbacks": 1}
    assert s["dispatches"] == 4
    assert s["rows"] == 64
    assert 0.0 < s["duty_cycle"] <= 1.0
    assert s["tiers"] == {costmodel.TIER_EXACT: 4}
    assert set(s["phases"]) >= {"enqueue", "device", "gather_hop",
                                "hydrate", "queue_wait", "scatter"}
    shares = [v["share_of_wall"] for v in s["phases"].values()]
    assert all(sh is not None for sh in shares)
    assert sum(shares) == pytest.approx(1.0, abs=0.01)
    # no roofline from analytic work over a host wall: a share of the
    # chip's peaks is read from a capture (benchmarks/readers/xplane_ops)
    assert not {"roofline", "roofline_device_busy", "regimes", "backend",
                "device_busy_s", "device_fetch_s"} & set(s)
    w.clear()
    s2 = w.summary()
    assert s2["dispatches"] == 0 and s2["duty_cycle"] == 0.0
    assert "point_get" not in s2
    assert s2["dispatches_lifetime"] == 4  # lifetime survives clear


def test_perf_window_gauges(tmp_path):
    from weaviate_tpu.monitoring import noop_metrics

    m = noop_metrics()
    w = perf.PerfWindow(window_s=60.0, metrics=m)
    w.record_dispatch(_stamped_shape(), rows=16)
    text = m.expose().decode()
    assert "weaviate_device_duty_cycle" in text
    assert "weaviate_perf_phase_share" in text
    assert "weaviate_device_mfu_pct" not in text
    assert "weaviate_device_hbm_bw_pct" not in text


def test_duty_interval_anchored_at_fetch_not_record_time():
    """Two concurrent dispatches whose in-flight windows fully overlap
    must not double-count duty just because their HYDRATE times differ:
    the interval is anchored at the monotonic fetch stamp, not at the
    (hydration-delayed) record call."""
    import time

    w = perf.PerfWindow(window_s=60.0)
    fetch_mono = time.monotonic() - 0.05  # both fetched 50ms ago
    for _ in range(2):
        s = costmodel.DispatchShape(costmodel.TIER_EXACT, n=1000, dim=16,
                                    batch=4, bytes_per_row=64)
        t = time.perf_counter()
        s.t_start, s.t_fetch, s.t_end = t - 0.010, t, t + 0.001
        s.device_ms = 10.0
        s.t_fetch_mono = fetch_mono
        w.record_dispatch(s)  # second record is "after a slow hydrate"
    s = w.summary()
    busy = s["duty_cycle"] * s["observed_s"]
    assert busy == pytest.approx(0.010, abs=0.004)  # union, not 0.020


def test_gather_empty_shard_records_zero_cost(tmp_path):
    """An allowList whose docs are absent from this shard runs no device
    work — the perf shape must credit neither phantom flops/bytes nor a
    phantom duty-cycle interval (a multi-shard filtered workload must not
    read near-1.0 duty while the device is idle)."""
    from weaviate_tpu.storage.bitmap import Bitmap

    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    try:
        vidx = idx.single_local_shard().vector_index
        absent = Bitmap(np.array([10**9], dtype=np.uint64))
        handle = vidx.search_by_vectors_async(vecs[:1], K, absent)
        ids, dists = handle()
        assert ids.shape[1] == 0
        shape = handle.shape
        assert shape is not None and shape.tier == costmodel.TIER_GATHER
        assert shape.n == 0 and shape.flops() == 0 and shape.bytes() == 0
        assert shape.t_fetch == 0.0  # no device call ran
        w = perf.PerfWindow(window_s=60.0)
        w.record_dispatch(shape, rows=1)
        s = w.summary()
        assert s["duty_cycle"] == 0.0
    finally:
        app.shutdown()


def test_sigterm_teardown_honors_sig_ign(monkeypatch):
    """A process that deliberately ignored SIGTERM must not be killed by
    the teardown chain: stop the capture, swallow the signal."""
    import signal

    from weaviate_tpu.monitoring import profiling

    killed = []
    monkeypatch.setattr(profiling.os, "kill",
                        lambda *a: killed.append(a))
    monkeypatch.setitem(profiling._teardown_state, "prev_sigterm",
                        signal.SIG_IGN)
    profiling._sigterm_teardown(signal.SIGTERM, None)
    assert killed == []


def test_teardown_signal_half_retries_after_thread_failure(monkeypatch):
    """A first install off the main thread must not latch the signal half
    closed — a later main-thread call still arms the SIGTERM handler."""
    import signal

    from weaviate_tpu.monitoring import profiling

    monkeypatch.setitem(profiling._teardown_state, "signal_installed", False)
    prev = signal.getsignal(signal.SIGTERM)
    try:
        got = []
        t = threading.Thread(
            target=lambda: got.append(profiling.install_trace_teardown()))
        t.start(); t.join()
        assert got == [False]  # signal.signal refuses off the main thread
        assert profiling._teardown_state["signal_installed"] is False
        if threading.current_thread() is threading.main_thread():
            assert profiling.install_trace_teardown() is True
            assert profiling._teardown_state["signal_installed"] is True
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_dispatch_span_has_no_host_wall_roofline():
    """The per-dispatch roofline (analytic FLOPs over a host wall, seen
    live at 418% MFU before it was re-based) and the rider split of
    flops/bytes beside it are gone from the dispatch span: the tier, the
    scanned rows and the ledger stay."""
    tracing.configure(tracing.Tracer())
    try:
        tr = tracing.Tracer().start_request("test", "q")
        shape = costmodel.DispatchShape(
            costmodel.TIER_EXACT, n=2000, dim=32, batch=14,
            bytes_per_row=128, k=5)
        shape.enqueue_ms, shape.device_ms, shape.finalize_ms = 800.0, 0.002, 0.2
        rec = tracing.DispatchRecord([(tr.root, 14, 0.0)], owned=True,
                                     actual_rows=14)
        rec.phase("device_search", 0.2)
        rec.attach_shape(shape)
        rec.finish()
        d = [s for s in tr.root.children if s.name == "dispatch"][0]
        assert d.attrs["tier"] == costmodel.TIER_EXACT
        assert d.attrs["n_live"] == 2000 and d.attrs["dim"] == 32
        assert d.attrs["ledger_ms"] == {
            "enqueue": 800.0, "device": 0.002, "gather_hop": 0.198}
        assert not {"mfu_pct", "hbm_bw_pct", "arith_intensity", "regime",
                    "flops", "bytes", "dispatch_flops", "dispatch_bytes",
                    "dispatch_wall_ms"} & set(d.attrs)
        # an attribution span is a share of a dispatch, not an interval
        # that ran: no start
        assert "start_ms" not in d.to_dict(tr.root.start_ns)
    finally:
        tracing.configure(None)


# -- serving-path integration -------------------------------------------------

def _nested_in(inner, outer):
    return outer[2] <= inner[2] and inner[2] + inner[3] <= outer[2] + outer[3]


def test_coalesced_capture_has_queue_wait_on_the_waiters_threads(tmp_path):
    """Coalesced dispatch under an open capture: every rider's admission
    wait is an interval on ITS OWN thread, nested in that thread's
    traverser span; the lane's dispatch phases and scatter are there once
    per dispatch, on the thread that did the work."""
    app, idx, vecs = _mk_app(tmp_path)
    try:
        n_req = 10
        barrier = threading.Barrier(n_req)
        tids = []

        def run(i):
            tids.append(threading.get_native_id())
            with tracing.request("test", f"q{i}"):
                barrier.wait()
                app.traverser.get_class(GetParams(
                    class_name="Pf",
                    near_vector={"vector": (vecs[i] + 0.5).tolist()},
                    limit=K))

        # the first request of a depth brings its lane's wider programs on
        # its own thread (PR 44): sent here, outside any trace and before
        # the capture, so that both hold the riders alone
        app.traverser.get_class(GetParams(
            class_name="Pf", near_vector={"vector": vecs[0].tolist()},
            limit=K))
        w = perf.get_window()
        w.clear()
        w.capture_begin()
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        w.capture_end(0, 1, {})
        iv = w.last_capture()["intervals"]
        by_name: dict = {}
        for x in iv:
            by_name.setdefault(x[0], []).append(x)
        waits = by_name["queue_wait"]
        assert sorted(x[1] for x in waits) == sorted(tids)
        for q in waits:
            span = [x for x in by_name["traverser.get_class"] if x[1] == q[1]]
            assert len(span) == 1 and _nested_in(q, span[0])
        dispatches = {d["attrs"]["dispatch_id"]
                      for d in _dispatch_spans(app.tracer.snapshot())}
        assert len(dispatches) < n_req, "requests never shared a dispatch"
        for name in ("enqueue", "device_wait", "gather_hop", "hydrate",
                     "scatter"):
            assert len(by_name[name]) == len(dispatches), name
            # the flusher / dispatch pool did this work, not a waiter
            assert not {x[1] for x in by_name[name]} & set(tids), name
        assert len(by_name["request"]) == n_req
        # the same waits are the ledger's queue_wait stage
        assert w.summary()["phases"]["queue_wait"]["samples"] == n_req
    finally:
        app.shutdown()


def test_dispatch_span_carries_tier_and_ledger(tmp_path):
    app, idx, vecs = _mk_app(tmp_path)
    try:
        with tracing.request("test", "q"):
            app.traverser.get_class(GetParams(
                class_name="Pf",
                near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
        d = _dispatch_spans(app.tracer.snapshot())
        assert len(d) == 1
        a = d[0]["attrs"]
        assert a["tier"] == costmodel.TIER_EXACT
        assert a["n_live"] == N and a["dim"] == DIM
        assert not {"mfu_pct", "hbm_bw_pct", "regime", "flops",
                    "bytes"} & set(a)
        led = a["ledger_ms"]
        assert {"enqueue", "device", "gather_hop", "hydrate"} <= set(led)
        assert all(v >= 0.0 for v in led.values())
        # the window saw the dispatch too (full coverage)
        s = perf.get_window().summary()
        assert s["dispatches"] >= 1
        assert s["duty_cycle"] > 0.0
    finally:
        app.shutdown()


def test_a_failed_dispatch_or_finalize_leaves_no_phase_open(tmp_path,
                                                            monkeypatch):
    """A dispatch that raises while it is being built closes its `enqueue`;
    a finalize whose host half raises after the fetch closes its
    `gather_hop`; a second fetch closes the first one's hop. Every
    annotation that was entered is left, and the capture log has the
    intervals."""
    from weaviate_tpu.index import tpu as tpu_mod

    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    try:
        vidx = idx.single_local_shard().vector_index
        q = vecs[:2] + 0.5
        vidx.search_by_vectors(q, K)
        open_now = []

        class Ann:
            def __init__(self, name, **stats):
                self.name = name

            def __enter__(self):
                open_now.append(self.name)

            def __exit__(self, *exc):
                open_now.remove(self.name)

            def set_metadata(self, **stats):
                pass

        monkeypatch.setattr(tracing, "_TraceMe", Ann)

        def boom(*a, **kw):
            raise RuntimeError("boom")

        w = perf.get_window()
        w.capture_begin()
        with monkeypatch.context() as m:
            m.setattr(vidx, "_dispatch_scan", boom)
            with pytest.raises(RuntimeError):
                vidx.search_by_vectors(q, K)
        assert open_now == []
        with monkeypatch.context() as m:
            m.setattr(tpu_mod, "unpack_fused", boom)
            with pytest.raises(RuntimeError):
                vidx.search_by_vectors(q, K)
        assert open_now == []
        shape = costmodel.DispatchShape(costmodel.TIER_EXACT, n=N, dim=DIM,
                                        batch=2, batch_padded=2,
                                        bytes_per_row=DIM * 4, k=K)
        tpu_mod._fetch_packed(np.zeros(4), shape)
        tpu_mod._fetch_packed(np.zeros(4), shape)
        assert open_now == ["wv/gather_hop"] and shape.fetches == 2
        shape.end_hop()
        assert open_now == []
        w.capture_end(0, 1, {})
        names = [x[0] for x in w.last_capture()["intervals"]]
        assert names == ["enqueue",
                         "enqueue", "device_wait", "gather_hop",
                         "device_wait", "gather_hop",
                         "device_wait", "gather_hop"]
    finally:
        app.shutdown()


def test_pq_tiers_report_their_bytes(tmp_path):
    """The PQ-rescore tier's cost model reads the bf16 copy (2·D per
    row), pinned through a real compressed dispatch."""
    app, idx, vecs = _mk_app(tmp_path, pq=True, n=512)
    try:
        vidx = idx.single_local_shard().vector_index
        assert vidx.compressed
        with tracing.request("test", "q"):
            app.traverser.get_class(GetParams(
                class_name="Pf",
                near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
        a = _dispatch_spans(app.tracer.snapshot())[0]["attrs"]
        assert a["tier"] == costmodel.TIER_PQ_RESCORE
        handle = vidx.search_by_vectors_async(vecs[:1] + 0.5, K)
        handle()
        shape = handle.shape
        assert shape.tier == costmodel.TIER_PQ_RESCORE
        assert shape.bytes() == shape.n * 2 * DIM == a["n_live"] * 2 * DIM
    finally:
        app.shutdown()


def test_disabled_serving_path_constructs_no_perf_objects(tmp_path,
                                                          monkeypatch):
    """TRACING_ENABLED unset: no DispatchShape is built, the PerfWindow is
    never touched — direct AND coalesced paths (the zero-cost contract,
    same spy style as the tracing test)."""
    app, idx, vecs = _mk_app(tmp_path, tracing_on=False)
    calls = []

    def spy(name):
        def boom(*a, **kw):
            calls.append(name)
            raise AssertionError(f"perf.{name} touched while disabled")
        return boom

    monkeypatch.setattr(costmodel, "DispatchShape", spy("DispatchShape"))
    monkeypatch.setattr(perf.PerfWindow, "record_dispatch",
                        spy("PerfWindow.record_dispatch"))
    monkeypatch.setattr(perf.PerfWindow, "note_phase",
                        spy("PerfWindow.note_phase"))
    # the capture log and the profiler's annotations ride the same switch
    monkeypatch.setattr(perf.PerfWindow, "note_interval",
                        spy("PerfWindow.note_interval"))
    monkeypatch.setattr(perf.PerfWindow, "note_point_get",
                        spy("PerfWindow.note_point_get"))
    monkeypatch.setattr(perf.PerfWindow, "note_posting",
                        spy("PerfWindow.note_posting"))
    monkeypatch.setattr(tracing, "Phase", spy("Phase"))
    monkeypatch.setattr(tracing, "_TraceMe", spy("TraceAnnotation"))
    try:
        assert app.perf_window is None
        assert perf.get_window() is None
        # coalesced lane
        res = app.traverser.get_class(GetParams(
            class_name="Pf",
            near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
        assert len(res) == K
        # direct path (oversize batched group bypasses the coalescer)
        out = app.traverser.get_class_batched([
            GetParams(class_name="Pf",
                      near_vector={"vector": (vecs[i] + 0.5).tolist()},
                      limit=K)
            for i in range(20)])
        assert not any(isinstance(r, Exception) for r in out)
        assert calls == []
    finally:
        app.shutdown()


def test_counting_scan_programs_constructs_nothing_while_disabled(
        tmp_path, monkeypatch):
    """The index counts its full-store dispatches by program as plain
    integers, tracer up or down; naming the program on the `enqueue`
    interval and on the shape lives inside the tracer's gate."""
    app, idx, vecs = _mk_app(tmp_path, tracing_on=False, coalesce=False)
    calls = []

    def spy(name):
        def boom(*a, **kw):
            calls.append(name)
            raise AssertionError(f"{name} touched while disabled")
        return boom

    monkeypatch.setattr(costmodel, "DispatchShape", spy("DispatchShape"))
    monkeypatch.setattr(tracing, "Phase", spy("Phase"))
    monkeypatch.setattr(tracing, "_TraceMe", spy("TraceAnnotation"))
    try:
        vidx = idx.shards[next(iter(idx.shards))].vector_index
        before = vidx.scan_programs.as_dict()
        out = app.traverser.get_class_batched([
            GetParams(class_name="Pf",
                      near_vector={"vector": (vecs[i] + 0.5).tolist()},
                      limit=K)
            for i in range(20)])
        assert not any(isinstance(r, Exception) for r in out)
        after = vidx.scan_programs.as_dict()
        assert after["gmin"] + after["scan"] > before["gmin"] + before["scan"]
        assert after["declined_slower"] == 0
        assert vidx.search_by_vectors_async(vecs[:2] + 0.5, K).shape is None
        assert calls == []
    finally:
        app.shutdown()


def test_enqueue_interval_and_shape_name_the_program(tmp_path, monkeypatch):
    """`program` joins `rows` and `tier` on the `enqueue` interval of a
    full-store scan (the kernel for a batch of 8 or more, the lax.scan
    program under it); a gather dispatch runs neither and says nothing."""
    from weaviate_tpu.storage.bitmap import Bitmap

    seen = []

    class Ann:
        def __init__(self, name, **stats):
            self.name, self.stats = name, dict(stats)
            seen.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **stats):
            self.stats.update(stats)

    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    monkeypatch.setattr(tracing, "_TraceMe", Ann)
    try:
        vidx = idx.shards[next(iter(idx.shards))].vector_index
        for rows, allow in ((16, None), (3, None), (16, Bitmap([1, 2, 3]))):
            vidx.search_by_vectors(vecs[:rows] + 0.5, K, allow)
        stats = [a.stats for a in seen if a.name == "wv/enqueue"]
        assert stats == [
            {"rows": 16, "tier": costmodel.TIER_EXACT, "program": "gmin"},
            {"rows": 3, "tier": costmodel.TIER_EXACT, "program": "scan"},
            {"rows": 16, "tier": costmodel.TIER_GATHER}]
        handle = vidx.search_by_vectors_async(vecs[:16] + 0.5, K)
        handle()
        shape = handle.shape
        assert shape.extra["program"] == "gmin"
        assert shape.describe()["program"] == "gmin"
    finally:
        app.shutdown()


# -- exposition ---------------------------------------------------------------

def test_debug_perf_and_health_count_dispatches_by_program(tmp_path):
    """`/debug/perf` `programs` sums what every shard's index counted;
    `/debug/index` `kernels.gmin.dispatches` has one shard's."""
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, coalesce=False)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        shard = idx.shards[next(iter(idx.shards))]
        shard.vector_index.search_by_vectors(vecs[:16] + 0.5, K)   # kernel
        shard.vector_index.search_by_vectors(vecs[:2] + 0.5, K)    # b < 8
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/debug/perf", timeout=30) as r:
            body = json.loads(r.read())
        # the full-store programs' three counts, and beside them the
        # partition-pruned tier's two (no layout here: both 0)
        assert body["programs"] == {"gmin": 1, "scan": 1,
                                    "declined_slower": 0,
                                    "ivf_declined": 0, "ivf_trainings": 0}
        assert list(body)[-3:] == ["programs", "startup", "compiles"]
        with urllib.request.urlopen(base + "/debug/index", timeout=30) as r:
            page = json.loads(r.read())
        (health,) = page["indexes"]["Pf"].values()
        gmin = health["vector_index"]["kernels"]["gmin"]
        assert gmin["dispatches"] == {
            k: body["programs"][k] for k in ("gmin", "scan",
                                             "declined_slower")}
        assert gmin["validated"] == 1 and gmin["rejected"] == 0
        # a decline is the index's own count too
        shard.vector_index.scan_programs.declined()
        with urllib.request.urlopen(base + "/debug/perf", timeout=30) as r:
            assert json.loads(r.read())["programs"]["declined_slower"] == 1
    finally:
        srv.stop()
        app.shutdown()


def test_debug_perf_endpoint_and_metrics(tmp_path):
    from weaviate_tpu.server import App, RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        with tracing.request("test", "q"):
            app.traverser.get_class(GetParams(
                class_name="Pf",
                near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/perf", timeout=30) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert body["dispatches"] >= 1
        assert 0.0 <= body["duty_cycle"] <= 1.0
        assert "phases" in body and "device" in body["phases"]
        assert body["phases"]["device"]["p99_ms"] >= 0.0
        assert body["tiers"].get(costmodel.TIER_EXACT, 0) >= 1
        assert body["capture"] is None  # no /debug/pprof/trace yet
        assert "roofline" not in body and "regimes" not in body
        # the duty gauge rides the same scrape as everything else
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "weaviate_device_duty_cycle" in text
        assert "weaviate_device_mfu_pct" not in text
        assert "weaviate_device_hbm_bw_pct" not in text
    finally:
        srv.stop()
        app.shutdown()


def test_debug_perf_serves_the_restart_timeline_beside_the_window(tmp_path):
    """`startup` and `compiles` are on the page the traced run collects,
    after the window's own keys, and the first readiness probe closes the
    timeline."""
    from weaviate_tpu.server import RestServer

    perf.timeline_reset()
    tl = perf.startup_begin()
    try:
        with tracing.stage("app"):
            app, idx, vecs = _mk_app(tmp_path)
        srv = RestServer(app, port=0)
        with tracing.stage("listen"):
            srv.start()
        tl.ready(app.metrics)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            with urllib.request.urlopen(base + "/v1/.well-known/ready",
                                        timeout=30) as r:
                assert r.status == 200
            for _ in range(50):   # the stage lands after the probe's reply
                if tl.sealed and "first_ready" in tl.summary()["stages"]:
                    break
                time.sleep(0.05)
            with urllib.request.urlopen(base + "/debug/perf",
                                        timeout=30) as r:
                body = json.loads(r.read())
            assert body["enabled"] is True and "phases" in body
            assert list(body)[-2:] == ["startup", "compiles"]
            st = body["startup"]
            assert {"app", "listen", "first_ready"} <= set(st["stages"])
            assert st["seconds"]["ready"] is not None
            assert st["stages"]["first_ready"]["seconds"] >= 0
            assert body["compiles"]["count"] >= st["compiles"]["count"]
            text = app.metrics.expose().decode()
            assert ('weaviate_startup_durations_ms_count{operation='
                    '"first_ready"} 1.0') in text
        finally:
            srv.stop()
            app.shutdown()
    finally:
        perf.timeline_reset()


def test_debug_perf_disabled_reports_disabled(tmp_path):
    from weaviate_tpu.server import App, RestServer

    app, idx, vecs = _mk_app(tmp_path, tracing_on=False)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/debug/perf", timeout=30) as r:
            body = json.loads(r.read())
        # the window is down; the restart's timeline and the compile tally
        # are the process's own and are served all the same
        assert body["enabled"] is False
        assert set(body) == {"enabled", "startup", "compiles"}
        assert body["startup"] is None      # nobody started this process
        assert set(body["compiles"]) == {"count", "seconds", "cache_hits",
                                         "cache_misses", "last"}
    finally:
        srv.stop()
        app.shutdown()


def test_final_summary_stashed_for_ci_artifact(tmp_path):
    app, idx, vecs = _mk_app(tmp_path)
    with tracing.request("test", "q"):
        app.traverser.get_class(GetParams(
            class_name="Pf",
            near_vector={"vector": (vecs[0] + 0.5).tolist()}, limit=K))
    app.shutdown()
    assert any(s.get("dispatches_lifetime", 0) >= 1
               for s in perf.recent_summaries())


# -- satellites ---------------------------------------------------------------

def test_gate_sheds_surface_in_coalescer_stats(tmp_path):
    """ROADMAP item-4 follow-up: the front-door concurrency gate's
    refusals show up in coalescer.stats() and on the gate-level metric."""
    from weaviate_tpu.monitoring import noop_metrics

    m = noop_metrics()
    gate = robustness.configure_tenant_gate(
        robustness.TenantConcurrencyGate(1, metrics=m))
    app = None
    try:
        assert gate.enter("tA")
        assert not gate.enter("tA")   # over budget -> shed, counted
        assert not gate.enter("tA")
        gate.leave("tA")
        st = gate.stats()
        assert st["shed_total"] == 2 and st["shed"] == {"tA": 2}
        assert st["in_flight_total"] == 0
        # the coalescer's operator view includes the gate section
        app, idx, vecs = _mk_app(tmp_path, tracing_on=False)
        co_stats = app.coalescer.stats()
        assert co_stats["tenant_gate"]["shed_total"] == 2
        assert "weaviate_tenant_gate_shed_total 2.0" in m.expose().decode()
    finally:
        robustness.unconfigure_tenant_gate(gate)
        if app is not None:
            app.shutdown()


def test_gate_shed_tenant_keys_bounded():
    gate = robustness.TenantConcurrencyGate(1)
    gate._SHED_KEYS_MAX = 4  # type: ignore[misc]
    for i in range(10):
        assert gate.enter(f"t{i}")
        assert not gate.enter(f"t{i}")  # over ITS budget -> shed
        gate.leave(f"t{i}")
    st = gate.stats()
    assert len(st["shed"]) <= 5  # 4 tenant keys + "other"
    assert st["shed_total"] == 10
    assert st["shed"].get("other", 0) >= 6


def test_bm25_batch_shape_uses_costmodel():
    from weaviate_tpu.inverted.bm25_device import DeviceBM25

    eng = DeviceBM25.__new__(DeviceBM25)
    eng.last_batch_shape = costmodel.DispatchShape(
        costmodel.TIER_BM25_MATMUL, n=4096, dim=10.0, batch=96,
        bytes_per_row=40, k=10,
        extra={"q": 96, "u": 10, "n_pad": 4096, "slices": 1, "qu": 960})
    st = eng.last_batch_stats
    assert st["q"] == 96 and st["n_pad"] == 4096 and st["u"] == 10
    assert st["tier"] == costmodel.TIER_BM25_MATMUL
    r = eng.last_batch_shape.roofline_at_qps(960.0, "cpu")
    assert r == costmodel.roofline_from_qps(960.0, 4096, 10.0, 96, 40, "cpu")


def test_device_trace_teardown_stops_capture(monkeypatch):
    """The r05 wedge fix: an active capture is stopped by the emergency
    teardown exactly once, from any of atexit / SIGTERM / finally."""
    from weaviate_tpu.monitoring import profiling

    stopped = []
    import jax

    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stopped.append(1))
    with profiling._teardown_lock:
        profiling._teardown_state["active"] = True
    assert profiling.stop_active_trace() is True
    assert profiling.stop_active_trace() is False  # idempotent
    assert stopped == [1]


def test_capture_intervals_match_the_profilers_own_annotations(tmp_path):
    """A capture through profiling.device_trace while two threads run fake
    phases: the intervals of /debug/perf's `capture` are nested as the code
    nests them, bounded, on two threads, and each `wv/*` event of the
    capture's own /host:CPU plane has its interval within 1 ms -- the stamp
    taken before start_trace is the xplane's zero."""
    import glob
    import statistics
    import time

    from jax.profiler import ProfileData

    from weaviate_tpu.monitoring import profiling

    import jax

    jax.devices()  # a server's backend is up long before its first capture
    tracing.configure(tracing.Tracer())
    w = perf.configure(perf.PerfWindow())
    stop = threading.Event()
    tids = []

    def worker():
        tids.append(threading.get_native_id())
        while not stop.is_set():
            with tracing.request("test", "fake"):
                with tracing.span("traverser.get_class"):
                    enq = tracing.Phase("enqueue")
                    time.sleep(0.001)
                    enq.end(rows=3, tier="exact_scan")
                    with tracing.Stopwatch("hydrate", rows=3):
                        time.sleep(0.002)
            time.sleep(0.001)

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        text = profiling.device_trace(str(tmp_path), seconds=0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert "python_tracer_level=0 host_tracer_level=1" in text
    cap = w.last_capture()
    assert cap["options"] == profiling.TRACE_OPTIONS
    assert cap["dropped"] == 0
    assert cap["t0_ns"] == 0 < cap["t1_ns"] and cap["seconds"] >= 0.5
    iv = cap["intervals"]
    assert 40 <= len(iv) <= perf.CAPTURE_LOG_MAX
    assert {x[1] for x in iv} == set(tids) and len(set(tids)) == 2
    # nesting, per thread: hydrate and enqueue in a traverser span in a
    # request (an interval is logged when it closes, so the outer ones of
    # the last few may be missing, never the other way round; one that was
    # open when the capture began is kept, from before zero, without the
    # inner ones that had closed by then)
    assert all(x[2] + x[3] >= 0 for x in iv)
    for tid in tids:
        mine = [x for x in iv if x[1] == tid]
        for outer_name, inner_names in (
                ("request", ("traverser.get_class",)),
                ("traverser.get_class", ("enqueue", "hydrate"))):
            outers = [x for x in mine if x[0] == outer_name and x[2] >= 0]
            assert outers
            for o in outers:
                inner = [x for x in mine if x[0] in inner_names
                         and _nested_in(x, o)]
                assert len(inner) == len(inner_names)
    assert set(cap["phases"]) == {"request", "traverser.get_class",
                                  "enqueue", "hydrate"}
    assert cap["phases"]["hydrate"]["p50_ms"] >= 2.0

    # the same phases as the profiler saw them
    (path,) = glob.glob(str(tmp_path / "traces" / "**" / "*.xplane.pb"),
                        recursive=True)
    events = [(ev.name[3:], ev.start_ns, ev.duration_ns, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for ev in line.events
              if ev.name.startswith("wv/")]
    assert len(events) >= 40
    assert {e[0] for e in events} == set(cap["phases"])
    errs = []
    for name, start, dur, stats in events:
        near = min((x for x in iv if x[0] == name),
                   key=lambda x: abs(x[2] - start))
        errs.append(max(abs(near[2] - start), abs(near[3] - dur)))
        if name == "hydrate":
            assert stats == {"rows": 3}
        if name == "enqueue":   # what end() added
            assert stats == {"rows": 3, "tier": "exact_scan"}
    assert statistics.median(errs) < 0.25e6
    # one thread preempted between its two clock reads may miss; not many
    assert sum(e < 1e6 for e in errs) >= 0.95 * len(errs)


def test_trace_teardown_install_registers_sigterm_chain():
    import signal

    from weaviate_tpu.monitoring import profiling

    prev = signal.getsignal(signal.SIGTERM)
    try:
        # idempotent; in the main test thread installation succeeds
        assert profiling.install_trace_teardown() in (True, False)
        profiling.install_trace_teardown()
        if threading.current_thread() is threading.main_thread():
            assert signal.getsignal(signal.SIGTERM) is \
                profiling._sigterm_teardown
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_perf_window_s_config_parses():
    cfg = load_config({"TRACING_ENABLED": "true", "PERF_WINDOW_S": "12.5"})
    assert cfg.tracing.perf_window_s == 12.5
    with pytest.raises(Exception):
        load_config({"TRACING_ENABLED": "true", "PERF_WINDOW_S": "0"})
