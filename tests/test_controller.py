"""Self-tuning degradation control plane (serving/controller.py).

Covers the four controllers' decide logic against synthetic sensor
feeds (brownout ladder staging + square-wave hysteresis, recall-floor
backoff + bucket-snapped cuts, lane window/depth steering, token-bucket
rate math), the clamped actuate helper, the fail-static guarantees
(tick-thread death reverts + journals; a stalled thread's leases lapse
at the readers; unconfigure restores every knob), the serving-path
integration (tenant_rate sheds with time-to-next-token, brownout
margin/cap/Retry-After knobs at coalescer admission, drain-rate-derived
gate hints, the rescore_r cap in the index), the disabled-mode
zero-construction spy, /debug/controllers + weaviate_controller_*
exposure, config parsing/validation, and the end-to-end brownout storm
journey under the PR-5 seeded device-error storm.
"""

import http.client
import json
import threading
import time
import uuid as uuidlib

import numpy as np
import pytest

from weaviate_tpu.config import Config
from weaviate_tpu.config.config import ConfigError, load_config
from weaviate_tpu.entities.storobj import StorObj
from weaviate_tpu.monitoring import incidents
from weaviate_tpu.serving import controller, robustness
from weaviate_tpu.serving.controller import (
    KNOB_CAP_SCALE,
    KNOB_MARGIN,
    KNOB_RATE_SCALE,
    KNOB_RESCORE_CAP,
    KNOB_RETRY_SCALE,
    KNOB_WINDOW_S,
    R_BUCKETS,
    ControlPlane,
)
from weaviate_tpu.testing import faults
from weaviate_tpu.usecases.traverser import GetParams

N, DIM, K = 200, 16, 5


@pytest.fixture(autouse=True)
def _clean_controller_globals():
    """Isolate the module global: a plane another test forgot must not
    leak into the disabled-default assertions here (and ours must not
    leak out into other files' serving paths)."""
    saved = controller._plane
    controller._plane = None
    yield
    controller._plane = saved


@pytest.fixture(autouse=True)
def _clean_incident_globals():
    saved = (incidents._journal, incidents._engine, incidents._recorder)
    incidents._journal = incidents._engine = incidents._recorder = None
    yield
    incidents._journal, incidents._engine, incidents._recorder = saved


def _plane(**overrides) -> ControlPlane:
    """Unstarted plane for deterministic tick() driving."""
    return ControlPlane(start=False, **overrides)


def _mk_app(tmp_path, **cfg_edits):
    from weaviate_tpu.server import App

    cfg = Config()
    cfg.coalescer.enabled = True
    cfg.coalescer.window_ms = 200.0
    for k, v in cfg_edits.items():
        obj = cfg
        parts = k.split("__")
        for p in parts[:-1]:
            obj = getattr(obj, p)
        setattr(obj, parts[-1], v)
    app = App(config=cfg, data_path=str(tmp_path / "data"))
    app.schema.add_class({
        "class": "Ctl", "vectorIndexType": "hnsw_tpu",
        "vectorIndexConfig": {"distance": "l2-squared"},
        "properties": [{"name": "tag", "dataType": ["text"]}],
    })
    rng = np.random.default_rng(13)
    vecs = rng.integers(-8, 8, (N, DIM)).astype(np.float32)
    idx = app.db.get_index("Ctl")
    idx.put_batch([
        StorObj(class_name="Ctl", uuid=str(uuidlib.UUID(int=i + 1)),
                properties={"tag": "t"}, vector=vecs[i])
        for i in range(N)])
    return app, idx, vecs


# -- the clamped actuate helper + leased store --------------------------------


def test_set_knob_clamps_every_knob():
    p = _plane()
    assert p._set_knob(KNOB_MARGIN, 99.0, "t") == 4.0
    assert p._set_knob(KNOB_MARGIN, 0.1, "t") == 1.0
    assert p._set_knob(KNOB_CAP_SCALE, 0.01, "t") == 0.25
    assert p._set_knob(KNOB_CAP_SCALE, 3.0, "t") == 1.0
    assert p._set_knob(KNOB_RETRY_SCALE, 0.5, "t") == 1.0
    assert p._set_knob(KNOB_RETRY_SCALE, 100.0, "t") == 8.0
    assert p._set_knob(KNOB_RATE_SCALE, 0.0, "t") == 0.25
    # the window clamp band comes from config (and always includes the
    # coalescer's configured default)
    lo, hi = p._clamps[KNOB_WINDOW_S]
    assert p._set_knob(KNOB_WINDOW_S, 0.0, "t") == lo
    assert p._set_knob(KNOB_WINDOW_S, 60.0, "t") == hi


def test_rescore_cap_is_bucket_snapped():
    p = _plane()
    assert p._set_knob(KNOB_RESCORE_CAP, 500, "t") == 128
    assert p._set_knob(KNOB_RESCORE_CAP, 97, "t") == 96
    assert p._set_knob(KNOB_RESCORE_CAP, 63, "t") == 48
    assert p._set_knob(KNOB_RESCORE_CAP, 1, "t") == 32
    for v in R_BUCKETS:
        assert p._set_knob(KNOB_RESCORE_CAP, v, "t") == v


def test_readers_default_when_disabled_and_read_actuated_when_configured():
    # disabled: every reader is the configured default
    assert controller.coalescer_window_s(0.0015) == 0.0015
    assert controller.admission_margin() == 1.0
    assert controller.tenant_cap_scale() == 1.0
    assert controller.retry_after_scale() == 1.0
    assert controller.rescore_r_cap(128) == 128
    assert controller.take_rate_token("t") is None
    p = controller.configure(_plane())
    p._set_knob(KNOB_MARGIN, 2.0, "t")
    p._set_knob(KNOB_RESCORE_CAP, 64, "t")
    assert controller.admission_margin() == 2.0
    assert controller.rescore_r_cap(128) == 64
    # the cap can never RAISE the index's own maximum
    assert controller.rescore_r_cap(48) == 48


def test_stale_lease_reverts_reader_to_default():
    """A stalled tick thread (no lease refresh) fail-statics at the
    reader in bounded time — no watchdog thread needed."""
    p = controller.configure(_plane())
    p._set_knob(KNOB_MARGIN, 2.0, "t")
    assert controller.admission_margin() == 2.0
    p.lease_s = 0.05
    time.sleep(0.12)
    assert controller.admission_margin() == 1.0
    # ...and a tick's refresh re-arms the lease
    p._refresh_leases()
    assert controller.admission_margin() == 2.0


# -- controller 1: burn-rate brownout -----------------------------------------


def test_brownout_ladder_escalates_and_recovers_with_hysteresis():
    p = _plane(hold_ticks=3)
    burn = {"fast": 100.0}
    p._sense_burn = lambda: (burn["fast"], None)
    p.tick()
    assert p.brownout_stage == 1
    assert p._read(KNOB_MARGIN, 1.0) == p.cfg.brownout_margin
    p.tick()
    assert p.brownout_stage == 2
    assert p._read(KNOB_CAP_SCALE, 1.0) == p.cfg.brownout_cap_scale
    assert p._read(KNOB_RETRY_SCALE, 1.0) == p.cfg.brownout_retry_scale
    assert p._read(KNOB_RATE_SCALE, 1.0) == p.cfg.brownout_rate_scale
    p.tick()
    assert p.brownout_stage == 3
    p.tick()
    assert p.brownout_stage == 3  # the ladder tops out
    # recovery: one stage down per hold_ticks CONSECUTIVE clean ticks
    burn["fast"] = 0.0
    for expected in (3, 3, 2, 2, 2, 1, 1, 1, 0):
        p.tick()
        assert p.brownout_stage == expected
    assert p._read(KNOB_MARGIN, 1.0) == 1.0
    assert p._read(KNOB_CAP_SCALE, 1.0) == 1.0


def test_brownout_square_wave_does_not_oscillate():
    """A burn flapping around the threshold faster than hold_ticks must
    not flap the ladder: the clean-tick counter resets on every burning
    tick, so the stage ratchets up and NEVER steps down mid-wave."""
    p = _plane(hold_ticks=3)
    seq = [100.0, 0.0] * 10  # square wave, period 2 < hold_ticks
    stages = []
    for fast in seq:
        p._sense_burn = lambda fast=fast: (fast, None)
        p.tick()
        stages.append(p.brownout_stage)
    # monotone non-decreasing through the whole wave — zero oscillation
    assert all(b >= a for a, b in zip(stages, stages[1:]))
    assert stages[-1] == 3


def test_brownout_slow_burn_holds_stage_one():
    p = _plane(hold_ticks=2)
    p._sense_burn = lambda: (None, 5.0)  # smolder, no cliff
    for _ in range(5):
        p.tick()
    assert p.brownout_stage == 1  # lights stage 1 and HOLDS — never escalates


def test_brownout_slow_burn_decays_aggressive_stages_to_one():
    """A short fast-burn storm ratchets to stage 3; once the 5 m cliff
    clears, residue in the 1 h window must not PIN stage 3 for the rest
    of the hour — the smolder decays the aggressive stages back to 1 on
    the hysteresis clock and holds there until the slow window clears."""
    p = _plane(hold_ticks=2)
    burn = {"fast": 100.0, "slow": 100.0}
    p._sense_burn = lambda: (burn["fast"], burn["slow"])
    for _ in range(3):
        p.tick()
    assert p.brownout_stage == 3
    burn["fast"] = 0.0
    burn["slow"] = 5.0  # the hour window still tallies the storm
    for expected in (3, 2, 2, 1, 1, 1, 1):  # one stage per hold_ticks, floor 1
        p.tick()
        assert p.brownout_stage == expected
    burn["slow"] = 0.0  # hour window finally clear: normal serving
    p.tick(), p.tick()
    assert p.brownout_stage == 0


def test_straggler_tick_after_shutdown_revert_is_reverted():
    """shutdown() with a stalled tick thread: its join times out and
    shutdown reverts — but the straggling tick completes later and
    re-actuates. The actuation re-arms the (idempotent) revert, so the
    straggler's own exit path restores the defaults it disturbed."""
    p = _plane(hold_ticks=1)
    p._sense_burn = lambda: (100.0, None)
    p.tick()
    assert p._read(KNOB_MARGIN, 1.0) == p.cfg.brownout_margin
    # shutdown's revert (no thread was started, join is a no-op)
    p.shutdown()
    assert p._reverted and p._read(KNOB_MARGIN, 1.0) == 1.0
    # a straggling tick that was already in flight completes now
    p.tick()
    assert not p._reverted  # the actuation re-armed the revert
    assert p._read(KNOB_MARGIN, 1.0) == p.cfg.brownout_margin
    # ...and the run loop's finally (stop is set) reverts it again
    p.revert_all("control plane shutdown")
    assert p._reverted and p._read(KNOB_MARGIN, 1.0) == 1.0
    # idempotent: with nothing re-actuated a repeat call is a no-op
    emitted = []
    p.metrics = None
    orig = incidents.emit
    incidents.emit = lambda kind, **kw: emitted.append(kind)
    try:
        p.revert_all("again")
    finally:
        incidents.emit = orig
    assert emitted == []


def test_brownout_stage3_pauses_and_restores_sampling():
    from weaviate_tpu.monitoring import quality, tracing

    tracer = tracing.configure(tracing.Tracer(sample_rate=0.7))
    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.3, start_workers=False))
    try:
        p = _plane(hold_ticks=1)
        burn = {"fast": 100.0}
        p._sense_burn = lambda: (burn["fast"], None)
        for _ in range(3):
            p.tick()
        assert p.brownout_stage == 3
        assert tracer.sample_rate == 0.0
        assert auditor.sample_rate == 0.0
        burn["fast"] = 0.0
        p.tick()  # 3 -> 2 restores optional work
        assert p.brownout_stage == 2
        assert tracer.sample_rate == 0.7
        assert auditor.sample_rate == 0.3
    finally:
        tracing.unconfigure(tracer)
        quality.unconfigure(auditor)


# -- controller 2: recall-guarded candidate budget ----------------------------


def test_budget_cuts_on_slack_holds_in_dead_band_and_backs_off():
    p = _plane(hold_ticks=2, recall_floor=0.98, recall_slack=0.015,
               recall_backoff_margin=0.005)
    sense = {"ewma": 1.0}
    p._sense_recall = lambda: sense["ewma"]
    # slack (1.0 >= 0.995): cut one bucket per hold_ticks
    p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 128  # held, not yet
    p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 96
    p.tick(), p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 64
    # dead band (floor+margin <= ewma < floor+slack): hold position
    sense["ewma"] = 0.99
    for _ in range(4):
        p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 64
    # near the floor: back off IMMEDIATELY (no hysteresis on restores)
    sense["ewma"] = 0.982
    p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 96
    p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 128


def test_budget_reverts_without_recall_signal():
    """No auditor (or a cold one) => the budget may not stay cut: the
    meter that vouched for the cut is gone."""
    p = _plane(hold_ticks=1)
    p._sense_recall = lambda: 1.0
    p.tick(), p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) < 128
    p._sense_recall = lambda: None
    p.tick()
    assert p._read(KNOB_RESCORE_CAP, 128) == 128


def test_budget_holds_cap_while_brownout_pauses_sampling():
    """When the ladder ITSELF silenced the meter (stage 3), the budget
    holds the last vouched-for cap: restoring to 128 would 4x per-query
    work exactly while the SLO burns, and cutting further would act on
    a frozen EWMA."""
    from weaviate_tpu.monitoring import quality

    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.5, start_workers=False))
    try:
        p = _plane(hold_ticks=1, recall_min_samples=2)
        for _ in range(4):
            auditor.window.record("exact_scan", 1.0, 1.0, 0.0, 1, 0.0)
        p.tick(), p.tick()
        held = p._read(KNOB_RESCORE_CAP, 128)
        assert held < 128  # fresh signal: cut
        p._pause_sampling()  # what _enter_stage(3) does
        for _ in range(3):
            p.tick()
        assert p._read(KNOB_RESCORE_CAP, 128) == held  # held, not moved
        p._resume_sampling()  # recovery: fresh signal, steering resumes
        assert p._sense_recall() is not None
        p.tick()  # slack still holds, so the cut can deepen again
        assert p._read(KNOB_RESCORE_CAP, 128) <= held
    finally:
        quality.unconfigure(auditor)


def test_budget_reads_paused_auditor_as_no_signal():
    """Brownout stage 3 zeroes the auditor's sample gate; the
    QualityWindow never decays, so its EWMA is then FROZEN, not fresh —
    the budget must treat a paused gate as no signal (revert, never cut
    on pre-pause numbers while actual recall is unmeasured)."""
    from weaviate_tpu.monitoring import quality

    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.5, start_workers=False))
    try:
        p = _plane(hold_ticks=1, recall_min_samples=2)
        for _ in range(4):
            auditor.window.record("exact_scan", 1.0, 1.0, 0.0, 1, 0.0)
        p.tick(), p.tick()
        assert p._read(KNOB_RESCORE_CAP, 128) < 128  # fresh signal: cut
        auditor.set_sample_rate(0.0)                 # stage-3 pause
        assert p._sense_recall() is None
        p.tick()
        assert p._read(KNOB_RESCORE_CAP, 128) == 128  # reverted, held
        auditor.set_sample_rate(0.5)                 # gate back open
        assert p._sense_recall() is not None
    finally:
        quality.unconfigure(auditor)


def test_budget_min_samples_via_real_auditor_window():
    from weaviate_tpu.monitoring import quality

    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.5, start_workers=False))
    try:
        p = _plane(recall_min_samples=4)
        assert p._sense_recall() is None  # cold window: no signal
        for _ in range(4):
            auditor.window.record("exact_scan", 0.97, 1.0, 0.0, 1, 0.0)
        ew = p._sense_recall()
        assert ew is not None and 0.96 < ew <= 0.98
    finally:
        quality.unconfigure(auditor)


def test_rescore_r_cap_steers_index_budget(tmp_path):
    """index/tpu.py _rescore_r honors the controller cap — but a cap too
    small for a query's 2k slack threshold is IGNORED for that query
    (zeroing r would force the full-precision exact scan, strictly MORE
    device work; the budget controller may only cut)."""
    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.index.tpu import TpuVectorIndex

    cfg = vi.HnswUserConfig.from_dict(
        {"distance": vi.DISTANCE_L2}, "hnsw_tpu")
    idx = TpuVectorIndex(cfg, str(tmp_path), persist=False)
    assert idx._rescore_r(10, 100_000) == 40           # static: 4k
    p = controller.configure(_plane())
    p._set_knob(KNOB_RESCORE_CAP, 32, "budget")
    assert idx._rescore_r(10, 100_000) == 32           # capped
    # k=20 needs r >= 40 > cap: the cap lapses to the static 128 for this
    # query — identical to controller-off (r=4k=80), NOT the exact scan
    assert idx._rescore_r(20, 100_000) == 80
    # deep k where even the static max leaves no slack: exact scan either way
    assert idx._rescore_r(80, 100_000) == 0
    controller.unconfigure(p)
    assert idx._rescore_r(10, 100_000) == 40           # reverted


def test_rescore_r_cap_steers_the_mesh_scan_step_too(tmp_path):
    """The one rule (index/tpu.py rescore_depth) serves both indexes: the
    depth every chip of the mesh's exact tier scans at follows the
    controller's cap, read off the dispatch's shape."""
    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.index.mesh import MeshVectorIndex
    from weaviate_tpu.monitoring import tracing
    from weaviate_tpu.parallel.mesh_search import make_mesh

    cfg = vi.HnswUserConfig.from_dict(
        {"distance": vi.DISTANCE_L2}, "hnsw_tpu_mesh")
    idx = MeshVectorIndex(cfg, str(tmp_path), persist=False,
                          mesh=make_mesh(4), initial_capacity_per_shard=64)
    vecs = np.random.default_rng(5).standard_normal((200, 8)).astype(
        np.float32)
    idx.add_batch(np.arange(200), vecs)

    def depth():
        handle = idx.search_by_vectors_async(vecs[:2], 10)
        handle()
        return handle.shape.extra["rescore_r"]

    prev = tracing.get_tracer()
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    try:
        assert depth() == 40                            # static: 4k
        p = controller.configure(_plane())
        p._set_knob(KNOB_RESCORE_CAP, 32, "budget")
        try:
            assert depth() == 32                        # capped
        finally:
            controller.unconfigure(p)
        assert depth() == 40                            # reverted
    finally:
        tracing.configure(prev)
        idx.shutdown()


# -- controller 3: coalescer window / pipeline depth --------------------------


def test_lanes_widen_window_when_queue_dominated_and_walk_back():
    from weaviate_tpu.serving.coalescer import QueryCoalescer

    co = QueryCoalescer(window_s=0.002, max_batch=64)
    try:
        p = _plane(hold_ticks=2, coalescer=co, depth_max=2)
        sig = {"duty_cycle": 0.95, "queue_wait_mean_ms": 30.0,
               "dispatches": 50}
        p._sense_lanes = lambda: dict(sig)
        default = co.window_s
        p.tick(), p.tick()
        widened = p._read(KNOB_WINDOW_S, default)
        assert widened == pytest.approx(default * 1.5)
        # starved device, short waits: walk back toward the default
        sig.update(duty_cycle=0.1, queue_wait_mean_ms=0.0)
        p.tick(), p.tick()
        assert p._read(KNOB_WINDOW_S, default) == pytest.approx(default)
        # too little traffic: hold (no actuation from 4 dispatches)
        sig.update(dispatches=2, duty_cycle=0.95, queue_wait_mean_ms=30.0)
        p.tick(), p.tick()
        assert p._read(KNOB_WINDOW_S, default) == pytest.approx(default)
    finally:
        co.shutdown()


def test_lanes_hysteresis_counts_one_direction_only():
    """A load flapping between queue-dominated and device-starved every
    tick must never actuate the window: the hold counter tracks
    CONSECUTIVE qualifying ticks in ONE direction, so mixed evidence
    (one widen tick + one narrow tick) is not hold_ticks=2 of anything."""
    from weaviate_tpu.serving.coalescer import QueryCoalescer

    co = QueryCoalescer(window_s=0.002, max_batch=64)
    try:
        p = _plane(hold_ticks=2, coalescer=co, depth_max=2)
        widen = {"duty_cycle": 0.95, "queue_wait_mean_ms": 30.0,
                 "dispatches": 50}
        narrow = {"duty_cycle": 0.1, "queue_wait_mean_ms": 0.0,
                  "dispatches": 50}
        square = [widen, narrow]
        i = {"n": 0}

        def sense():
            i["n"] += 1
            return dict(square[i["n"] % 2])

        p._sense_lanes = sense
        default = co.window_s
        for _ in range(8):
            p.tick()
        assert p._read(KNOB_WINDOW_S, default) == pytest.approx(default)
        assert p._depth == p._depth_default
    finally:
        co.shutdown()


def test_lanes_window_clamped_at_configured_max():
    from weaviate_tpu.serving.coalescer import QueryCoalescer

    co = QueryCoalescer(window_s=0.002, max_batch=64)
    try:
        p = _plane(hold_ticks=1, coalescer=co, window_max_ms=4.0)
        p._sense_lanes = lambda: {"duty_cycle": 0.95,
                                  "queue_wait_mean_ms": 100.0,
                                  "dispatches": 50}
        for _ in range(10):
            p.tick()
        assert p._read(KNOB_WINDOW_S, co.window_s) == pytest.approx(0.004)
    finally:
        co.shutdown()


def test_pipeline_depth_deficit_mechanics():
    """Depth up releases permits immediately; depth down queues a
    deficit that completing lanes absorb — an in-flight dispatch is
    never forcibly reclaimed."""
    from weaviate_tpu.serving.coalescer import QueryCoalescer, _Lane

    co = QueryCoalescer(window_s=60.0, max_batch=64, pipeline_depth=1)
    try:
        assert co.set_pipeline_depth(3) == 3
        # 3 permits live: all three acquires succeed without blocking
        for _ in range(3):
            assert co._inflight.acquire(blocking=False)
        co.set_pipeline_depth(1)
        assert co._depth_deficit == 2
        # two lane completions pay down the deficit instead of releasing
        for _ in range(2):
            lane = _Lane(("k",), None, None, K, False, 0.0, 256)
            co._release_lane(lane)
        assert co._depth_deficit == 0
        assert not co._inflight.acquire(blocking=False)
        # the third completion frees the single configured slot again
        co._release_lane(_Lane(("k2",), None, None, K, False, 0.0, 256))
        assert co._inflight.acquire(blocking=False)
        co._inflight.release()
    finally:
        co.shutdown()


def test_lanes_deepen_pipeline_on_bubble_and_restore():
    from weaviate_tpu.serving.coalescer import QueryCoalescer

    co = QueryCoalescer(window_s=0.002, max_batch=64, pipeline_depth=1)
    try:
        p = _plane(hold_ticks=1, coalescer=co, depth_max=2)
        # pipeline bubble: device idle while work queues
        p._sense_lanes = lambda: {"duty_cycle": 0.1,
                                  "queue_wait_mean_ms": 50.0,
                                  "dispatches": 50}
        p.tick()
        assert co._depth == 2
        # device saturated: extra depth walks back to the default
        p._sense_lanes = lambda: {"duty_cycle": 0.95,
                                  "queue_wait_mean_ms": 0.5,
                                  "dispatches": 50}
        p.tick()
        assert co._depth == 1
    finally:
        co.shutdown()


# -- controller 4: tenant token-bucket rate quotas ----------------------------


def test_token_bucket_rate_weight_and_retry_hint():
    b = controller._TokenBuckets(rate_qps=10.0, burst_s=0.01,
                                 weights={"heavy": 2.0})
    # burst = max(rate*burst_s, 1) = 1 token: the second take sheds
    assert b.take("light") is None
    ra = b.take("light")
    assert ra is not None and 0.0 < ra <= 0.1
    # time-to-next-token scales with the tenant's rate: the weight-2
    # tenant refills twice as fast
    assert b.take("heavy") is None
    ra2 = b.take("heavy")
    assert ra2 is not None and ra2 < ra
    # brownout rate_scale shrinks the refill => a LONGER hint (pin the
    # bucket to empty so wall-clock refill can't race the comparison)
    b2 = controller._TokenBuckets(rate_qps=10.0, burst_s=0.1)
    assert b2.take("t") is None
    with b2._lock:
        b2._buckets["t"][0] = 0.0
        b2._buckets["t"][1] = time.monotonic()
    assert b2.take("t", scale=0.5) == pytest.approx(1.0 / 5.0, rel=0.2)


def test_token_bucket_refills_and_prunes():
    b = controller._TokenBuckets(rate_qps=50.0, burst_s=0.02)
    assert b.take("t") is None
    assert b.take("t") is not None
    time.sleep(0.05)  # > 1/50 s: a token accrued
    assert b.take("t") is None
    b.prune(idle_s=0.0)
    assert b.stats()["tenants"] == 0


def test_rate_quota_sheds_tenant_rate_at_admission(tmp_path):
    app, idx, vecs = _mk_app(tmp_path)
    p = controller.configure(_plane(tenant_rate_qps=0.5,
                                    tenant_rate_burst_s=1.0))
    try:
        shard = idx.single_local_shard()
        co = app.coalescer
        w = co.submit(shard, vecs[0], K, tenant="rated")
        assert w is not None
        with pytest.raises(robustness.OverloadedError) as ei:
            co.submit(shard, vecs[1], K, tenant="rated")
        assert "tenant_rate" not in str(ei.value)  # message names the quota
        assert "rate quota" in str(ei.value)
        # Retry-After = time-to-next-token (2 s at 0.5 qps, one spent)
        assert 0.5 < ei.value.retry_after_s <= 2.5
        assert co.stats()["shed"].get("tenant_rate") == 1
        assert co.stats()["tenants"]["rated"]["shed"]["tenant_rate"] == 1
        # a different tenant has its own bucket
        assert co.submit(shard, vecs[2], K, tenant="other-t") is not None
    finally:
        controller.unconfigure(p)
        app.shutdown()


# -- brownout knobs at coalescer admission ------------------------------------


def test_admission_margin_sheds_deadline_unreachable_earlier(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, coalescer__window_ms=60_000.0)
    p = controller.configure(_plane())
    try:
        shard = idx.single_local_shard()
        co = app.coalescer
        # backlog + a warmed drain EWMA: est_wait = 1 row / 10 rows/s
        assert co.submit(shard, vecs[0], K, tenant="m") is not None
        co._tenants["m"].ewma_rows_per_s = 10.0
        with robustness.deadline_scope(250.0):
            # est 0.1 s < 0.25 s remaining: admitted at margin 1.0
            assert co.submit(shard, vecs[1], K, tenant="m") is not None
        p._set_knob(KNOB_MARGIN, 4.0, "brownout")
        with robustness.deadline_scope(250.0), \
                pytest.raises(robustness.OverloadedError) as ei:
            co.submit(shard, vecs[2], K, tenant="m")
        assert "deadline_unreachable" in str(ei.value)
        assert co.stats()["shed"].get("deadline_unreachable") == 1
        assert ei.value.retry_after_s > 0
    finally:
        controller.unconfigure(p)
        app.shutdown()


def test_tenant_cap_scale_shrinks_budget_and_retry_scale_applies(tmp_path):
    app, idx, vecs = _mk_app(
        tmp_path, coalescer__window_ms=60_000.0,
        coalescer__max_queued_rows=40, coalescer__max_request_rows=4,
        tenancy__max_queued_rows_fraction=0.5)
    p = controller.configure(_plane())
    try:
        shard = idx.single_local_shard()
        co = app.coalescer
        assert co._tenant_row_cap == 20
        # another tenant has work (the budget only fires then)
        assert co.submit(shard, vecs[0], K, tenant="light") is not None
        for i in range(4):  # tenant "big": 16 rows in system
            assert co.submit(shard, vecs[4 * i: 4 * i + 4], K,
                             tenant="big") is not None
        # 16+4 <= 20: admitted at scale 1.0... but at scale 0.5 (cap 10)
        # the SAME submit sheds, with the Retry-After hint scaled 2x
        p._set_knob(KNOB_CAP_SCALE, 0.5, "brownout")
        p._set_knob(KNOB_RETRY_SCALE, 2.0, "brownout")
        with pytest.raises(robustness.OverloadedError) as ei:
            co.submit(shard, vecs[16:20], K, tenant="big")
        assert "tenant_budget" in str(ei.value)
        assert "tenant cap 10" in str(ei.value)
        base = max(co.window_s * 4.0, 0.05)  # cold-start drain hint
        assert ei.value.retry_after_s == pytest.approx(2.0 * base)
        # back at scale 1.0 the request fits the configured cap again
        p._set_knob(KNOB_CAP_SCALE, 1.0, "brownout")
        assert co.submit(shard, vecs[16:20], K, tenant="big") is not None
    finally:
        controller.unconfigure(p)
        app.shutdown()


def test_gate_retry_after_uses_drain_ewma(tmp_path):
    """The front-door concurrency gate's Retry-After derives from the
    coalescer's per-tenant drain EWMA (the PR-11 satellite) instead of
    the old fixed 1 s — and falls back to 1 s only while cold."""
    app, idx, vecs = _mk_app(tmp_path,
                             tenancy__max_concurrent_requests=1)
    try:
        gate = app.tenant_gate
        assert gate.enter("g")  # occupy the single slot
        with pytest.raises(robustness.OverloadedError) as cold:
            with robustness.tenant_concurrency("g"):
                pass
        assert cold.value.retry_after_s == 1.0  # no EWMA yet
        app.coalescer._ewma_rows_per_s = 40.0  # warmed drain estimate
        with pytest.raises(robustness.OverloadedError) as warm:
            with robustness.tenant_concurrency("g"):
                pass
        # max(1 row, ...) / (40 rows/s * depth 1) = 0.025 s — but the
        # gate floors at 0.25 s: its slots free on a request-duration
        # cadence, and a tenant whose slots are held by DIRECT-path
        # requests puts no rows in the coalescer at all, so a tiny
        # idle-queue drain hint would invite refusal churn
        assert warm.value.retry_after_s == pytest.approx(0.25)
        # a congested SHARED queue is the honest drain clock for a
        # gate-capped tenant (it holds almost no rows of its own): the
        # hint scales with the global backlog, so a storm's conformant
        # abuser backs off proportionally to real queue drain
        app.coalescer._queued_rows = 80  # 80 rows / (40 rows/s) = 2 s
        with pytest.raises(robustness.OverloadedError) as congested:
            with robustness.tenant_concurrency("g"):
                pass
        assert congested.value.retry_after_s == pytest.approx(2.0)
        app.coalescer._queued_rows = 0
        gate.leave("g")
    finally:
        app.shutdown()


# -- fail-static: death, stall, unconfigure -----------------------------------


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_tick_die_reverts_knobs_and_journals(tmp_path):
    """The serving.controller.tick fault point's `die` action kills the
    tick thread; its finally must revert every actuated knob to the
    configured default, journal a controller_revert, and leave serving
    on static defaults."""
    journal = incidents.OpsJournal(size=64)
    incidents.configure(journal=journal)
    inj = faults.configure(faults.FaultInjector(seed=3))
    p = controller.configure(ControlPlane(start=False, tick_s=0.01,
                                          hold_ticks=1))
    p._sense_burn = lambda: (100.0, None)
    p.tick()  # actuate: stage 1 engages the margin knob
    assert controller.admission_margin() > 1.0
    try:
        inj.plan("serving.controller.tick", "die", times=1)
        t = threading.Thread(target=p._run, daemon=True)
        p._thread = t
        t.start()
        t.join(timeout=5.0)
        assert not t.is_alive(), "die did not kill the tick thread"
        # fail-static: every knob back at its configured default
        assert controller.admission_margin() == 1.0
        assert controller.rescore_r_cap(128) == 128
        assert p.brownout_stage == 0 and p._reverted
        kinds = {e["kind"] for e in journal.tail()}
        assert "controller_revert" in kinds
        assert "fault_injected" in kinds
    finally:
        faults.unconfigure(inj)
        controller.unconfigure(p)


def test_unconfigure_restores_every_knob_and_object_state():
    from weaviate_tpu.monitoring import quality, tracing
    from weaviate_tpu.serving.coalescer import QueryCoalescer

    tracer = tracing.configure(tracing.Tracer(sample_rate=1.0))
    auditor = quality.configure(quality.QualityAuditor(
        sample_rate=0.4, start_workers=False))
    co = QueryCoalescer(window_s=0.002, pipeline_depth=1)
    try:
        p = controller.configure(_plane(coalescer=co, hold_ticks=1))
        burn = {"fast": 100.0}
        p._sense_burn = lambda: (burn["fast"], None)
        for _ in range(3):
            p.tick()                       # ladder to stage 3
        p._actuate_depth(2, "test")
        p._set_knob(KNOB_RESCORE_CAP, 48, "budget")
        assert p.brownout_stage == 3 and co._depth == 2
        assert tracer.sample_rate == 0.0 and auditor.sample_rate == 0.0
        controller.unconfigure(p)
        assert controller.get_plane() is None
        assert controller.admission_margin() == 1.0
        assert controller.tenant_cap_scale() == 1.0
        assert controller.retry_after_scale() == 1.0
        assert controller.rescore_r_cap(128) == 128
        assert co._depth == 1
        assert tracer.sample_rate == 1.0 and auditor.sample_rate == 0.4
        assert p.brownout_stage == 0
        # the final summary was stashed for the CI artifact
        assert any(s.get("reverted") for s in controller.recent_summaries())
    finally:
        tracing.unconfigure(tracer)
        quality.unconfigure(auditor)
        co.shutdown()


def test_actuations_are_journaled_with_burst_coalescing():
    journal = incidents.OpsJournal(size=64)
    incidents.configure(journal=journal)
    p = _plane()
    p._set_knob(KNOB_MARGIN, 2.0, "brownout", reason="stage 1")
    p._set_knob(KNOB_MARGIN, 3.0, "brownout", reason="stage 1")
    tail = journal.tail()
    acts = [e for e in tail if e["kind"] == "controller_actuation"]
    # burst kind: two actuations of ONE knob coalesce into one counted
    # ring entry per (kind, scope) within the burst window
    assert len(acts) == 1 and acts[0]["count"] == 2
    assert acts[0]["scope"] == KNOB_MARGIN
    assert p._actuations["brownout"] == 2
    assert len(p._recent) == 2


# -- disabled mode / lifecycle ------------------------------------------------


def test_disabled_serving_path_constructs_nothing(tmp_path, monkeypatch):
    built = []
    for name in ("ControlPlane", "_TokenBuckets"):
        orig = getattr(controller, name)

        def make(orig=orig, name=name):
            class Spy(orig):
                def __init__(self, *a, **kw):
                    built.append(name)
                    super().__init__(*a, **kw)
            return Spy
        monkeypatch.setattr(controller, name, make())
    app, idx, vecs = _mk_app(tmp_path)  # CONTROL_PLANE_ENABLED off
    try:
        assert app.control_plane is None
        assert controller.get_plane() is None
        r = app.traverser.get_class(GetParams(
            class_name="Ctl", near_vector={"vector": vecs[0].tolist()},
            limit=K))
        assert len(r) == K
        assert built == []
    finally:
        app.shutdown()


def test_enabled_app_wires_configures_and_reverts_on_shutdown(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, controller__enabled=True,
                             controller__tick_s=30.0)
    try:
        p = controller.get_plane()
        assert p is app.control_plane and p is not None
        assert p.coalescer is app.coalescer
        assert p._thread is not None and p._thread.is_alive()
        p._set_knob(KNOB_MARGIN, 2.0, "brownout")
    finally:
        app.shutdown()
    assert controller.get_plane() is None
    assert controller.admission_margin() == 1.0


def test_debug_controllers_endpoint_and_metrics(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path, controller__enabled=True,
                             controller__tick_s=30.0)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        app.control_plane._set_knob(KNOB_RESCORE_CAP, 96, "budget")
        app.control_plane._publish_gauges()
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request("GET", "/debug/controllers")
        doc = json.loads(conn.getresponse().read())
        conn.close()
        assert doc["enabled"] is True
        assert doc["controllers"]["brownout"]["stage"] == 0
        assert doc["knobs"][KNOB_RESCORE_CAP] == {
            "value": 96, "default": 128.0, "actuated": True}
        assert doc["knobs"]["pipeline_depth"]["actuated"] is False
        assert doc["thread_alive"] is True
        text = app.metrics.expose().decode()
        assert "weaviate_controller_brownout_stage" in text
        assert 'weaviate_controller_knob{knob="rescore_r_cap"} 96.0' in text
        assert "weaviate_controller_actuations_total" in text
    finally:
        srv.stop()
        app.shutdown()


def test_debug_controllers_disabled_reports_disabled(tmp_path):
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(tmp_path)
    srv = RestServer(app, port=0)
    srv.start()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
        conn.request("GET", "/debug/controllers")
        doc = json.loads(conn.getresponse().read())
        conn.close()
        assert doc == {"enabled": False}
    finally:
        srv.stop()
        app.shutdown()


def test_flight_recorder_bundle_carries_controller_section(tmp_path):
    app, idx, vecs = _mk_app(tmp_path, controller__enabled=True,
                             controller__tick_s=30.0,
                             incidents__dir=str(tmp_path / "inc"))
    try:
        app.control_plane._set_knob(KNOB_MARGIN, 2.0, "brownout")
        bundle = app.flight_recorder.capture("manual", reason="test")
        assert "controllers" in bundle
        assert bundle["controllers"]["knobs"][KNOB_MARGIN]["actuated"]
    finally:
        app.shutdown()


# -- config -------------------------------------------------------------------


def test_config_env_parsing():
    cfg = load_config({
        "CONTROL_PLANE_ENABLED": "true",
        "CONTROLLER_TICK_S": "0.5",
        "CONTROLLER_HOLD_TICKS": "5",
        "CONTROLLER_BROWNOUT_ENABLED": "false",
        "CONTROLLER_RECALL_FLOOR": "0.95",
        "CONTROLLER_WINDOW_MAX_MS": "10",
        "CONTROLLER_DEPTH_MAX": "3",
        "TENANT_RATE_QPS": "25",
        "TENANT_RATE_BURST_S": "1.5",
    })
    c = cfg.controller
    assert c.enabled and c.tick_s == 0.5 and c.hold_ticks == 5
    assert not c.brownout_enabled and c.budget_enabled
    assert c.recall_floor == 0.95 and c.window_max_ms == 10.0
    assert c.depth_max == 3
    assert c.tenant_rate_qps == 25.0 and c.tenant_rate_burst_s == 1.5


@pytest.mark.parametrize("env", [
    {"CONTROLLER_TICK_S": "0"},
    {"CONTROLLER_HOLD_TICKS": "0"},
    {"CONTROLLER_BROWNOUT_MARGIN": "0.5"},
    {"CONTROLLER_BROWNOUT_CAP_SCALE": "0"},
    {"CONTROLLER_BROWNOUT_RETRY_SCALE": "0.9"},
    {"CONTROLLER_RECALL_FLOOR": "1.5"},
    {"CONTROLLER_RECALL_SLACK": "0"},
    {"CONTROLLER_RECALL_MIN_SAMPLES": "0"},
    {"CONTROLLER_WINDOW_MIN_MS": "0"},
    {"CONTROLLER_WINDOW_MIN_MS": "8", "CONTROLLER_WINDOW_MAX_MS": "6"},
    {"CONTROLLER_DEPTH_MAX": "0"},
    {"CONTROLLER_DUTY_LO": "0.9", "CONTROLLER_DUTY_HI": "0.8"},
    {"TENANT_RATE_QPS": "-1"},
    {"TENANT_RATE_BURST_S": "0"},
])
def test_config_validation_rejects(env):
    with pytest.raises(ConfigError):
        load_config(env)


# -- the storm journey --------------------------------------------------------


def test_brownout_storm_journey(tmp_path):
    """End to end under the PR-5 seeded device-error storm: concurrent
    REST clients under tight deadlines against an undersized queue push
    the SLO engine into fast burn -> the brownout ladder engages
    (journaled stage transitions + actuations), every shed reply
    carries a Retry-After, nothing hangs, and App shutdown reverts
    every knob."""
    from weaviate_tpu.server import RestServer

    app, idx, vecs = _mk_app(
        tmp_path,
        coalescer__window_ms=2.0,
        coalescer__max_queued_rows=8,
        coalescer__max_request_rows=4,
        controller__enabled=True,
        controller__tick_s=0.05,
        controller__hold_ticks=2,
        robustness__breaker_reset_ms=100.0,
        robustness__fault_injection=(
            "index.tpu.dispatch:device_error:times=inf:p=0.4"),
        robustness__fault_injection_seed=11,
        incidents__slo_min_events=5,
        incidents__dir=str(tmp_path / "inc"))
    srv = RestServer(app, port=0)
    srv.start()
    gql = ('{ Get { Ctl(limit: %d, nearVector: {vector: %s}) '
           '{ _additional { distance } } } }')
    stop = threading.Event()
    outcomes = {"ok": 0, "shed": 0, "deadline": 0, "error": 0}
    retry_after_seen = []
    lock = threading.Lock()

    def client(tid):
        lrng = np.random.default_rng(300 + tid)
        while not stop.is_set():
            q = vecs[int(lrng.integers(0, N))]
            body = json.dumps({"query": gql % (
                K, json.dumps([float(x) for x in q]))})
            conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                              timeout=25)
            try:
                conn.request("POST", "/v1/graphql", body=body, headers={
                    "Content-Type": "application/json",
                    "X-Request-Timeout-Ms": "60"})
                resp = conn.getresponse()
                resp.read()
                with lock:
                    if resp.status == 200:
                        outcomes["ok"] += 1
                    elif resp.status == 429:
                        outcomes["shed"] += 1
                        ra = resp.getheader("Retry-After")
                        if ra is not None:
                            retry_after_seen.append(int(ra))
                    elif resp.status == 504:
                        outcomes["deadline"] += 1
                    else:
                        outcomes["error"] += 1
            except Exception:  # noqa: BLE001 — outcome accounting
                with lock:
                    outcomes["error"] += 1
            finally:
                conn.close()

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(8)]
    try:
        for t in threads:
            t.start()
        deadline = time.monotonic() + 12.0
        while time.monotonic() < deadline \
                and app.control_plane.brownout_stage < 1:
            time.sleep(0.05)
        stop.set()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "client hung"
        p = app.control_plane
        assert p.brownout_stage >= 1, (
            f"brownout never engaged: outcomes={outcomes}")
        # the ladder's moves are journaled under the new kinds
        counts = app.ops_journal.counts()
        assert counts.get("controller_brownout", 0) >= 1
        assert counts.get("controller_actuation", 0) >= 1
        # the engaged ladder is visible on the serving path
        assert controller.admission_margin() > 1.0
        # every shed reply carried a retry hint
        assert all(ra >= 1 for ra in retry_after_seen)
        summary = p.summary()
        assert summary["controllers"]["brownout"]["stage"] == p.brownout_stage
        assert summary["actuations"].get("brownout", 0) >= 1
    finally:
        stop.set()
        srv.stop()
        app.shutdown()
    # shutdown reverted the world to static defaults
    assert controller.get_plane() is None
    assert controller.admission_margin() == 1.0
    assert controller.rescore_r_cap(128) == 128
