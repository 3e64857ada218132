"""The host side of a dispatch, measured continuously: the rolling perf window.

What the device does is read from a profiler capture and nowhere else
(benchmarks/lib/xplane.py); this module keeps what the HOST did around it:

- the **ledger**: per-phase durations over the last ``window_s`` seconds
  (``decode`` / ``queue_wait`` / ``filter`` / ``enqueue`` / ``device`` /
  ``gather_hop`` / ``rescore`` / ``hydrate`` / ``scatter`` / ``encode``),
  fed by
  db/shard.py for EVERY dispatch while the tracer is up (full coverage,
  independent of trace sampling), by the coalescer per admitted request
  and by the gRPC entry per sampled request;
- the **device duty cycle**: the fraction of wall-clock with an in-flight
  device dispatch, integrated from [enqueue-start, fetch-end] intervals.
  It is the lane controller's sensor (``control_signals``), not a device
  measurement: the chip's own busy share comes from a capture;
- the **point-get counters**: what the native LSM point-get plane did
  (storage/lsm_native.py; `hydrate` is two such calls a batch): keys asked,
  segment tables probed, key bytes compared, arenas grown;
- the **rescore counters**: what the float32 rescoring of a compressed
  index's candidates read from the host's rows (index/tpu.py
  ``_rescore_f32``): dispatches, candidate rows scored, bytes read,
  winners the float32 distances moved from their bf16 rank, and the
  dispatches by what scored them (``by``: the native pass, or numpy and why);
- the **capture log**: while ``profiling.device_trace`` has a profiler
  session open, every closed host phase is kept as ``(name, thread id,
  start_ns, end_ns)`` on ``time.perf_counter_ns``, anchored at the stamp
  taken immediately before ``start_trace`` -- the profiler's own zero. The
  last finished capture is ``/debug/perf``'s ``capture``: the host's
  timeline on the same clock as the xplane's device lines, and the ledger
  restricted to the very seconds the xplane covers
  (benchmarks/readers/host_gaps.py gives each device gap to the phase
  that was open in it).

Beside the window, and whether or not the tracer is up, the process keeps
two small records of its own (no request's path touches either):

- the **timeline** of a restart (``Timeline``): every stage from process
  start to the first answered readiness probe as ``(name, thread, start_ns,
  seconds)`` on ``time.perf_counter_ns`` (stamped by ``tracing.stage`` and
  a restore's ``tracing.StageSums``), the fullest device's allocator
  reading at every stage end, every ``grow`` and at most once a second
  inside a replay, and the compiles that fell inside it. ``/debug/perf``
  serves it as ``startup``; the way down is a second timeline that
  ``python -m weaviate_tpu`` prints before it exits;
- the **compile tally** (``CompileTally``): listeners on ``jax.monitoring``
  count every backend compile (or load from the persistent cache) where it
  happens: ``/debug/perf`` ``compiles``.

Exposure: ``GET /debug/perf`` (server/rest.py, same authorizer as pprof),
the gauge ``weaviate_device_duty_cycle``, the per-dispatch phase-share
histogram ``weaviate_perf_phase_share`` and, from the timeline,
``weaviate_startup_durations_ms{operation=<stage>}``.

Lifecycle mirrors the tracer (monitoring/tracing.py): a process-wide
module global installed by App when TRACING_ENABLED is set, None
otherwise -- every serving-path entry point is then a one-comparison
no-op and constructs nothing (spy-pinned in tests/test_perf.py).

``summary()`` is captured verbatim into every incident bundle
(monitoring/incidents.py) and ``recent_summaries()`` keeps the last
windows reachable after the owning App is torn down; neither carries the
capture log.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import Counter, deque
from typing import Optional

from weaviate_tpu.monitoring import costmodel

# ledger stages in display order (the /debug/perf breakdown; decode and
# encode are fed by the gRPC entry per sampled request, queue_wait and
# scatter by the coalescer, the rest by the shard per dispatch)
PHASES = ("decode", "queue_wait", "filter", "enqueue", "device",
          "gather_hop", "rescore", "hydrate", "scatter", "encode")

# the write path's own stages, in order (`/debug/perf` `writes.phases`; the
# ten above and their meaning are the read path's and stay so): `decode`
# (server/rest.py: JSON to objects), `lsm` (db/shard.py put_batch: objects,
# doc-id lookup, inverted index), then index/tpu.py replace_batch:
# `index_lock_wait`, `index` (everything under the index lock but the next
# two: collision check, log append, slots), `device_write` (the write
# programs enqueued: the device runs them after) and `publish`. Each is a
# capture interval too, as `write.<stage>`.
WRITE_PHASES = ("decode", "lsm", "index_lock_wait", "index", "device_write",
                "publish")
# two spans over those stages, a sample a batch each: the whole request
# (`batch_ms`) and the hold of the index lock, which is what a search that
# meets the write waits for (`index_held_ms`: index + device_write + publish)
WRITE_SPANS = ("batch", "index_held")
# what a write did, summed over the window (`writes`): `slab_bytes_copied`
# is the bytes of whole-array generations the write programs made (0 for a
# donated or in-place write), `upload_bytes` what the host handed them;
# `writes_in_place` / `writes_copied` count the write programs by whether
# they were given their arrays to overwrite or made new generations of them,
# and `reader_wait_ms` is what writers waited for searches to finish their
# enqueue on a generation before it was overwritten (index/tpu.py
# `_retire_snapshot`)
WRITE_COUNTERS = ("rows", "batches", "slots_reused", "slots_appended",
                  "tombstones_applied", "slab_bytes_copied", "upload_bytes",
                  "snapshots_published", "grows", "writes_in_place",
                  "writes_copied", "reader_wait_ms")
# what a restore's landing did to the slab, summed over the restart's shards
# (`startup`): bytes of whole-array generations its writes and grows made,
# the grows, and the bytes of the slab(s) at its end to hold them against
RESTORE_COUNTERS = ("slab_bytes_copied", "grows", "slab_bytes")

# intervals one capture keeps; beyond it they are counted as `dropped`
# (a 5 s capture of the busiest cell closes about 3,000)
CAPTURE_LOG_MAX = 65536

# per-phase sample cap (deque maxlen): queue_wait gets one sample per
# ADMITTED REQUEST, so a 60 s window at r05-scale QPS (~13.7k/s) would
# otherwise retain ~800k tuples and every summary() would copy+sort them
# under the window lock. Percentiles are over the most recent samples
# within the window — plenty for p50/p99 at any realistic horizon.
_PHASE_SAMPLES_MAX = 16384

# which walk served a posting read (`note_posting`): the one-pass native
# walk, none (a bucket with no segment: the memtable alone), or a reason
# the Python walk had to (storage/lsm.py Bucket.roaring_get names them)
POSTING_NATIVE = "native"
POSTING_MEMTABLE = "memtable_only"

# what scored a compressed dispatch's candidates (`note_rescore`): the
# one-pass native call, or `numpy:<reason>` (index/rescore_native.py names
# the reasons)
RESCORE_NATIVE = "native"


class DutyCycle:
    """Busy-time integrator over [start, end) intervals within a rolling
    window. Incremental: each recorded interval contributes only the part
    not already covered by earlier intervals (``busy_until`` carries the
    merge frontier), so overlapping concurrent dispatches never double
    count. Exact for intervals arriving in nondecreasing START order; a
    deep pipeline that completes out of order can under-count the overlap
    by at most the reorder window (documented in docs/performance.md)."""

    __slots__ = ("window_s", "_deltas", "_busy_until", "_busy_total",
                 "_first_t")

    def __init__(self, window_s: float):
        self.window_s = max(float(window_s), 1e-3)
        # (t_end, busy_delta): busy time attributed at interval end, plus
        # a running total — value() must be O(evictions), not O(window),
        # because record_dispatch calls it per dispatch under the window
        # lock on the serving path
        self._deltas: deque = deque()
        self._busy_total = 0.0
        self._busy_until = 0.0
        self._first_t: Optional[float] = None

    def record(self, start: float, end: float) -> None:
        if end <= start:
            return
        if self._first_t is None:
            self._first_t = start
        covered_from = max(start, self._busy_until)
        delta = max(end - covered_from, 0.0)
        self._busy_until = max(self._busy_until, end)
        if delta > 0.0:
            self._deltas.append((end, delta))
            self._busy_total += delta

    def _trim(self, now: float) -> None:
        horizon = now - self.window_s
        while self._deltas and self._deltas[0][0] < horizon:
            _, d = self._deltas.popleft()
            self._busy_total -= d
        if not self._deltas:
            self._busy_total = 0.0  # no float-drift residue on empty

    def busy_s(self, now: Optional[float] = None) -> float:
        """Merged busy seconds within the trailing window. The PerfWindow
        divides this by ITS observed span."""
        now = time.monotonic() if now is None else now
        self._trim(now)
        return max(self._busy_total, 0.0)

    def value(self, now: Optional[float] = None) -> float:
        """Busy fraction of the trailing window (0..1). The denominator is
        the OBSERVED span — min(window_s, now - first interval) — so a
        window that just started reports its live fraction instead of
        diluting against unobserved time."""
        now = time.monotonic() if now is None else now
        busy = self.busy_s(now)
        if self._first_t is None:
            return 0.0
        span = min(self.window_s, max(now - self._first_t, 1e-9))
        return min(busy / span, 1.0)


class PerfWindow:
    """Rolling-window aggregate of the host-overhead ledger, the duty
    cycle, and the capture log.

    ``record_dispatch`` is the per-dispatch hot-path entry: one lock, O(1)
    amortized (eviction pops), gauge sets guarded so a broken metrics
    stack can never take down serving. ``summary()`` is the on-demand
    /debug/perf body (with ``last_capture()`` beside it)."""

    def __init__(self, window_s: float = 60.0, metrics=None,
                 sample_hint: float = 1.0):
        self.window_s = max(float(window_s), 1e-3)
        self.metrics = metrics
        # trace sample rate, surfaced in the summary: dispatch coverage
        # here is FULL (shard feeds every dispatch while the tracer is
        # up), but readers correlating with /debug/traces need the rate
        self.sample_hint = float(sample_hint)
        self._lock = threading.Lock()
        # (t_end_mono, tier, rows, one-fetch invariant violated, store
        # rows the dispatch read: DispatchShape.n, the depth its scan step
        # ran at where the shape carries one: extra["rescore_r"], the
        # shape's extra where the dispatch was a probed one, else None)
        self._entries: deque = deque()
        # phase name -> deque[(t_mono, ms)], count-capped (see
        # _PHASE_SAMPLES_MAX) on top of the time-horizon eviction
        self._phase: dict[str, deque] = {
            p: deque(maxlen=_PHASE_SAMPLES_MAX) for p in PHASES}
        # (t_mono, keys, segment_probes, key_compares, arena_grows,
        # mem_layer, mem_keys, mirror_builds, overlay_fallbacks) per native
        # point-get call or memtable-layer event, count-capped like the
        # phases
        self._point_get: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        # (t_mono, rows, bytes, promoted) per float32 rescoring of a
        # compressed dispatch's candidates, count-capped likewise
        self._rescore: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        # [second, keys, segment_probes, ids, {walk: calls}] per second of
        # posting reads: a filtered group reads hundreds of postings, so
        # the calls of one second share an entry
        self._postings: deque = deque()
        # (t_mono, lists, ids, why numpy built them or None, host ms, pool
        # hits, pool grows) per filtered group's device operands
        self._group_inputs: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        # the write path: stage -> deque[(t_mono, ms)], and (t_mono,
        # {counter: amount}) a call of `note_write`, count-capped likewise
        self._write_phase: dict[str, deque] = {
            p: deque(maxlen=_PHASE_SAMPLES_MAX)
            for p in WRITE_PHASES + WRITE_SPANS}
        self._write_counts: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        # (t_mono, (event, reason, riders, rows)) of the admission queue
        # (serving/coalescer.py): a lane dispatched, a bypass, a shed
        self._coalescer: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        # (t_mono, ms) a search that met staged writes and took the index
        # lock (index/tpu.py `_read_snapshot`'s slow path)
        self._read_lock_waits: deque = deque(maxlen=_PHASE_SAMPLES_MAX)
        self._duty = DutyCycle(self.window_s)
        self._rows = 0  # running sum over the live window
        self._first_entry: Optional[float] = None
        self._total_dispatches = 0  # lifetime, never evicted
        # the capture log: a list of (name, tid, start_ns, end_ns) while
        # profiling.device_trace has a session open, None otherwise --
        # note_interval's one comparison outside a capture
        self._capture_log: Optional[list] = None
        self._capture_dropped = 0
        self._captures = 0
        self._last_capture: Optional[dict] = None

    # -- hot path ------------------------------------------------------------

    def record_dispatch(self, shape, rows: int = 0) -> None:
        """Fold one finished device dispatch (a costmodel.DispatchShape
        with its ledger stamped) into the window. Called by db/shard.py
        for every dispatch while the perf plane is up."""
        now = time.monotonic()
        ledger = shape.ledger()
        # the shape's wall endpoints are perf_counter stamps; the window
        # runs on time.monotonic. Only DURATIONS are trusted
        # (clock-agnostic deltas); the in-flight interval -- enqueue start
        # to FETCH end -- is anchored at the monotonic fetch stamp
        # `_fetch_packed` took (NOT at this record call: hydration runs in
        # between, and re-anchoring here would shift concurrent
        # dispatches' intervals by their differing hydrate times and
        # corrupt the overlap merge)
        wall_s = max(shape.t_end - shape.t_start, 0.0)
        # no fetch stamp = no device call ran (an empty gather-tier early
        # return): it must contribute NO duty interval -- counting its
        # host-only wall as "device in flight" would read near-1.0 duty on
        # a workload whose device is idle, inverting the signal
        inflight_s = (max(shape.t_fetch - shape.t_start, 0.0)
                      if shape.t_fetch > 0.0 else 0.0)
        fetch_end = (shape.t_fetch_mono
                     if 0.0 < shape.t_fetch_mono <= now else now)
        # the dispatch invariant (one blocking fetch): violations are
        # counted per window -- a dispatch quietly re-growing a second
        # host round trip must be dashboard-visible, not just test-pinned
        viol = not costmodel.fused_invariant_ok(shape)
        nrows = int(rows) or shape.batch
        with self._lock:
            self._evict(now)
            extra = shape.extra or {}
            self._entries.append((now, shape.tier, nrows, viol,
                                  int(shape.n), extra.get("rescore_r"),
                                  extra if extra.get("ivf") else None))
            self._rows += nrows
            self._total_dispatches += 1
            if self._first_entry is None:
                # anchor the observed span at this dispatch's START, so
                # the first entry's duty divides by its own wall
                self._first_entry = now - wall_s
            for name, ms in ledger.items():
                self._phase[name].append((now, ms))
            if inflight_s > 0.0:
                self._duty.record(fetch_end - inflight_s, fetch_end)
            duty = self._duty_locked(now)
        m = self.metrics
        if m is not None:
            try:
                m.device_duty_cycle.set(duty)
                total = sum(ledger.values())
                if total > 0.0:
                    for name, ms in ledger.items():
                        m.perf_phase_share.labels(name).observe(ms / total)
            except Exception:  # noqa: BLE001 -- metrics must not break serving
                pass

    def note_phase(self, name: str, ms: float) -> None:
        """Record one sample of a ledger stage measured outside the shard
        dispatch (coalescer queue_wait per request, scatter per lane, the
        gRPC entry's decode and encode per sampled request)."""
        now = time.monotonic()
        with self._lock:
            d = self._phase.get(name)
            if d is None:
                d = self._phase[name] = deque(maxlen=_PHASE_SAMPLES_MAX)
            d.append((now, float(ms)))
            # bound growth between dispatch-driven evictions (the maxlen
            # cap bounds the worst case regardless)
            horizon = now - self.window_s
            while d and d[0][0] < horizon:
                d.popleft()

    def note_point_get(self, keys: int = 0, segment_probes: int = 0,
                       key_compares: int = 0, arena_grows: int = 0,
                       mem_layer: bool = False, mem_keys: int = 0,
                       mirror_builds: int = 0,
                       overlay_fallbacks: int = 0) -> None:
        """One call of the native point-get plane, as counted in C
        (`mem_layer`: it was handed a memtable's mirror, which answered
        `mem_keys` of the keys), or one event of a written bucket's
        memtable layer (`storage/lsm.py Bucket.multi_get_packed`): a mirror
        built, or a packed get that found none could be made and left the
        request to the general path."""
        now = time.monotonic()
        with self._lock:
            d = self._point_get
            d.append((now, keys, segment_probes, key_compares, arena_grows,
                      int(mem_layer), mem_keys, mirror_builds,
                      overlay_fallbacks))
            horizon = now - self.window_s
            while d[0][0] < horizon:
                d.popleft()

    def note_rescore(self, rows: int, nbytes: int, promoted: int,
                     by: str) -> None:
        """One compressed dispatch's float32 rescoring on the host, and
        what served it (`RESCORE_NATIVE`, or `numpy:<reason>`)."""
        now = time.monotonic()
        with self._lock:
            d = self._rescore
            d.append((now, rows, nbytes, promoted, by))
            horizon = now - self.window_s
            while d[0][0] < horizon:
                d.popleft()

    def note_posting(self, segment_probes: int, ids: int, walk: str) -> None:
        """One `Bucket.roaring_get`: the segments it asked, the ids it read,
        and the walk that served it (`POSTING_NATIVE`, `POSTING_MEMTABLE`,
        or the reason the Python walk had to)."""
        now = time.monotonic()
        sec = int(now)
        with self._lock:
            d = self._postings
            if not d or d[-1][0] != sec:
                d.append([sec, 0, 0, 0, {}])
                horizon = now - self.window_s
                while d[0][0] < horizon - 1.0:
                    d.popleft()
            e = d[-1]
            e[1] += 1
            e[2] += segment_probes
            e[3] += ids
            e[4][walk] = e[4].get(walk, 0) + 1

    def note_group_inputs(self, lists: int, ids: int, reason: Optional[str],
                          host_ms: float, pool_hits: int,
                          pool_grows: int) -> None:
        """One filtered group's device operands (index/group_inputs.py):
        the distinct allowLists resolved, the ids walked, `reason` why
        numpy built them (None: the native pass), the host's wall time for
        the resolution and the fills, and how many operands the pool had
        parked or had to make."""
        now = time.monotonic()
        with self._lock:
            d = self._group_inputs
            d.append((now, lists, ids, reason, host_ms, pool_hits,
                      pool_grows))
            horizon = now - self.window_s
            while d[0][0] < horizon:
                d.popleft()

    def _keep(self, d: deque, item) -> None:
        """Append `(now, item)` to a window deque and drop what left the
        window."""
        now = time.monotonic()
        with self._lock:
            d.append((now, item))
            while d[0][0] < now - self.window_s:
                d.popleft()

    def note_write_phase(self, name: str, ms: float) -> None:
        """One sample of a stage of the write path (`WRITE_PHASES`) or of
        a span over them (`WRITE_SPANS`)."""
        self._keep(self._write_phase[name], float(ms))

    def note_write(self, counts: dict) -> None:
        """Amounts to add to the window's write counters
        (`WRITE_COUNTERS`)."""
        self._keep(self._write_counts, counts)

    def note_read_lock_wait(self, ms: float) -> None:
        """One search that took the index lock to see staged writes."""
        self._keep(self._read_lock_waits, float(ms))

    def note_coalescer(self, event: str, reason: str = "", riders: int = 0,
                       rows: int = 0) -> None:
        """One event of the admission queue: a `lane` dispatched with its
        riders (requests) and rows, a `bypass` or a `shed` with its
        reason."""
        self._keep(self._coalescer, (event, reason, int(riders), int(rows)))

    def note_interval(self, name: str, start_ns: int, end_ns: int,
                      tid: Optional[int] = None) -> None:
        """One closed host phase on ``time.perf_counter_ns``; `tid` is the
        OS id of the thread it ran on (the id the profiler's host lines
        carry), the calling thread's by default. Kept only while a
        capture is open."""
        if self._capture_log is None:
            return
        if tid is None:
            tid = threading.get_native_id()
        with self._lock:
            log = self._capture_log
            if log is None:
                return
            if len(log) < CAPTURE_LOG_MAX:
                log.append((name, tid, int(start_ns), int(end_ns)))
            else:
                self._capture_dropped += 1

    # -- the capture (monitoring/profiling.py device_trace) ------------------

    def capture_begin(self) -> None:
        """A profiler session is about to start: open the capture log."""
        with self._lock:
            self._capture_log = []
            self._capture_dropped = 0

    def capture_end(self, t0_ns: int, t1_ns: int, options: dict) -> None:
        """The session is over: close the log and keep the capture as
        ``last_capture()``. `t0_ns` was stamped immediately before
        ``start_trace`` (the xplane's zero, to within the anchor error
        PERF.md records), `t1_ns` when the traced span ended, immediately
        before ``stop_trace``; every published time is relative to
        `t0_ns`."""
        with self._lock:
            log, self._capture_log = self._capture_log or [], None
            dropped = self._capture_dropped
            self._captures += 1
            cid = self._captures
        by_name: dict[str, list] = {}
        for name, _, s, e in log:
            by_name.setdefault(name, []).append((e - s) / 1e6)
        phases = {}
        for name, vals in sorted(by_name.items()):
            vals.sort()
            phases[name] = {"samples": len(vals),
                            "p50_ms": round(_pct(vals, 50.0), 3),
                            "p99_ms": round(_pct(vals, 99.0), 3)}
        doc = {
            "id": cid,
            "seconds": round((t1_ns - t0_ns) / 1e9, 6),
            "t0_ns": 0,
            "t1_ns": int(t1_ns - t0_ns),
            "options": dict(options),
            "dropped": dropped,
            "intervals": [[name, tid, s - t0_ns, e - s]
                          for name, tid, s, e in log],
            "phases": phases,
        }
        with self._lock:
            self._last_capture = doc

    def last_capture(self) -> Optional[dict]:
        """The last finished capture (/debug/perf `capture`), None before
        the first."""
        with self._lock:
            return self._last_capture

    def _evict(self, now: float) -> None:
        horizon = now - self.window_s
        while self._entries and self._entries[0][0] < horizon:
            self._rows -= self._entries.popleft()[2]
        for d in (*self._phase.values(), self._point_get, self._rescore,
                  self._group_inputs, *self._write_phase.values(),
                  self._write_counts, self._read_lock_waits,
                  self._coalescer):
            while d and d[0][0] < horizon:
                d.popleft()
        while self._postings and self._postings[0][0] < horizon - 1.0:
            self._postings.popleft()

    def _observed_span(self, now: float) -> float:
        if self._first_entry is None:
            return 0.0
        return min(self.window_s, max(now - self._first_entry, 1e-9))

    def _duty_locked(self, now: float) -> float:
        """Duty over the window's OWN observed span (a fetch-anchored
        interval may predate the first record; clamping keeps duty and
        `observed_s` mutually consistent)."""
        span = self._observed_span(now)
        if span <= 0.0:
            return 0.0
        return min(self._duty.busy_s(now) / span, 1.0)

    # -- introspection -------------------------------------------------------

    def control_signals(self) -> dict:
        """The cheap per-tick sensor read for the control plane's lane
        controller (serving/controller.py): duty cycle, mean queue wait,
        and the dispatch count over the window -- means only, no
        percentile sorts, so a 1 Hz tick costs O(window samples) adds
        under the lock and nothing else."""
        now = time.monotonic()
        with self._lock:
            self._evict(now)
            qw = self._phase.get("queue_wait")
            qw_mean = (sum(ms for _, ms in qw) / len(qw)) if qw else 0.0
            return {
                "duty_cycle": round(self._duty_locked(now), 4),
                "queue_wait_mean_ms": round(qw_mean, 3),
                "dispatches": len(self._entries),
            }

    def clear(self) -> None:
        """Reset the window (bench measurement slices)."""
        with self._lock:
            self._entries.clear()
            for d in self._phase.values():
                d.clear()
            self._point_get.clear()
            self._rescore.clear()
            self._postings.clear()
            self._group_inputs.clear()
            for d in self._write_phase.values():
                d.clear()
            self._write_counts.clear()
            self._read_lock_waits.clear()
            self._coalescer.clear()
            self._duty = DutyCycle(self.window_s)
            self._rows = 0
            self._first_entry = None

    def summary(self) -> dict:
        """The /debug/perf body less `capture`: duty cycle, per-phase
        p50/p99 + share of the accounted dispatch wall, tier tally."""
        now = time.monotonic()
        with self._lock:
            self._evict(now)
            span = self._observed_span(now)
            duty = self._duty_locked(now)
            n = len(self._entries)
            rows = self._rows
            phase_ms = {p: [ms for _, ms in d]
                        for p, d in self._phase.items() if d}
            tiers: dict[str, int] = {}
            tier_rows: dict[str, int] = {}
            violations = 0
            depths: dict[str, int] = {}
            probed: list = []
            for _, tier, _, viol, read, depth, ivf in self._entries:
                tiers[tier] = tiers.get(tier, 0) + 1
                tier_rows[tier] = tier_rows.get(tier, 0) + read
                violations += viol
                if depth is not None:
                    depths[str(depth)] = depths.get(str(depth), 0) + 1
                if ivf is not None:
                    probed.append(ivf)
            total_dispatches = self._total_dispatches
            point_get = [sum(c) for c in list(zip(*self._point_get))[1:]]
            rescore = [sum(c) for c in list(zip(*self._rescore))[1:4]]
            rescores = len(self._rescore)
            rescored_by: dict[str, int] = {}
            for e in self._rescore:
                rescored_by[e[4]] = rescored_by.get(e[4], 0) + 1
            postings = [sum(c) for c in list(zip(*self._postings))[1:4]]
            walks: dict[str, int] = {}
            for e in self._postings:
                for walk, calls in e[4].items():
                    walks[walk] = walks.get(walk, 0) + calls
            groups = list(self._group_inputs)
            write_ms = {p: sorted(ms for _, ms in d)
                        for p, d in self._write_phase.items() if d}
            write_counts = [c for _, c in self._write_counts]
            lock_waits = [ms for _, ms in self._read_lock_waits]
            queue_events = [e for _, e in self._coalescer]
        out: dict = {
            "window_s": self.window_s,
            "observed_s": round(span, 3),
            "trace_sample_rate": self.sample_hint,
            "dispatches": n,
            "dispatches_lifetime": total_dispatches,
            "rows": rows,
            "duty_cycle": round(duty, 4),
        }
        phases: dict = {}
        total_accounted = sum(sum(v) for v in phase_ms.values())
        for p in PHASES:
            vals = phase_ms.get(p)
            if not vals:
                continue
            svals = sorted(vals)
            phases[p] = {
                "samples": len(svals),
                "p50_ms": round(_pct(svals, 50.0), 3),
                "p99_ms": round(_pct(svals, 99.0), 3),
                "mean_ms": round(sum(svals) / len(svals), 3),
                "share_of_wall": round(sum(svals) / total_accounted, 4)
                if total_accounted > 0.0 else None,
            }
        out["phases"] = phases
        if point_get:
            # the native LSM point-get plane over the window: probes a key
            # is how many segments a lookup had to ask (what a bloom filter
            # or a fence would cut), compares a probe is about 1 for the
            # probe that hits and 0 for one that meets an empty slot, and
            # arena_grows is 0 once every serving thread has seen its
            # largest batch. A bucket that is being written hands the call
            # its memtable as one more layer: mem_layer_calls the calls
            # that asked one (two a BatchSearch beside a writer, 0 where
            # nothing is written), mem_keys the keys it answered
            # (tombstones too), mirror_builds the native mirrors made (one
            # a memtable generation a packed reader met), overlay_fallbacks
            # the packed gets that found no mirror could be made (no
            # memory) and left their request to the general path
            out["point_get"] = dict(zip(
                ("keys", "segment_probes", "key_compares", "arena_grows",
                 "mem_layer_calls", "mem_keys", "mirror_builds",
                 "overlay_fallbacks"), point_get))
        if rescore:
            # the float32 rescoring of compressed dispatches over the
            # window: `rows / dispatches` is the candidates a dispatch
            # scored from the host's rows (queries x R), `promoted` the
            # winners whose rank the float32 distances changed: near 0
            # says R is deeper than the bf16 rounding needs; `by` the
            # dispatches by what scored them: `native` the one-pass call
            # (index/rescore_native.py), `numpy:<reason>` the gather and
            # contraction that serve where it cannot
            out["rescore"] = {"dispatches": rescores, **dict(zip(
                ("rows", "bytes", "promoted"), rescore)),
                "by": dict(sorted(rescored_by.items()))}
        if postings:
            # the roaring-set posting reads over the window (every filter
            # leaf, BM25 and hybrid allowLists, `keys()`): `native` calls
            # the one-pass C walk served, `fallback` calls the Python walk
            # had to, by reason; a bucket with no segment is neither
            reasons = {w: c for w, c in sorted(walks.items())
                       if w not in (POSTING_NATIVE, POSTING_MEMTABLE)}
            out["postings"] = {
                **dict(zip(("keys", "segment_probes", "ids"), postings)),
                "native": walks.get(POSTING_NATIVE, 0),
                "fallback": sum(reasons.values()),
                "fallback_reasons": reasons,
            }
        if groups:
            # the device operands of the filtered groups over the window
            # (index/group_inputs.py): `native` groups the one C pass
            # served, `fallback` those numpy had to, by reason; `host_ms`
            # their wall time all told (resolution and fills, no upload);
            # `pool_grows` is 0 once the pool holds the pipeline's depth
            reasons: dict[str, int] = {}
            for g in groups:
                if g[3] is not None:
                    reasons[g[3]] = reasons.get(g[3], 0) + 1
            fallback = sum(reasons.values())
            out["group_inputs"] = {
                "groups": len(groups),
                "lists": sum(g[1] for g in groups),
                "ids": sum(g[2] for g in groups),
                "native": len(groups) - fallback,
                "fallback": fallback,
                "fallback_reasons": dict(sorted(reasons.items())),
                "host_ms": round(sum(g[4] for g in groups), 3),
                "pool_hits": sum(g[5] for g in groups),
                "pool_grows": sum(g[6] for g in groups),
            }
        if write_ms or write_counts:
            # the write path over the window: its stages a batch, and what
            # the batches did to the slots, the slab and the log
            counts = dict.fromkeys(WRITE_COUNTERS, 0)
            for c in write_counts:
                for name, amount in c.items():
                    counts[name] += amount
            counts["reader_wait_ms"] = round(counts["reader_wait_ms"], 3)
            stat = (lambda v: {
                "samples": len(v), "p50_ms": round(_pct(v, 50.0), 3),
                "p99_ms": round(_pct(v, 99.0), 3),
                "mean_ms": round(sum(v) / len(v), 3)})
            out["writes"] = {
                **counts,
                "phases": {p: stat(write_ms[p]) for p in WRITE_PHASES
                           if p in write_ms},
                **{p + "_ms": stat(write_ms[p]) for p in WRITE_SPANS
                   if p in write_ms}}
        if queue_events:
            # the admission queue over the window (serving/coalescer.py):
            # the lanes it dispatched, the requests (`riders`) and rows they
            # carried (`riders_per_lane` is the cause beside `rows /
            # dispatches`), and the requests that did not ride, by reason
            lanes = [e for e in queue_events if e[0] == "lane"]
            by_reason = (lambda kind: dict(sorted(Counter(
                e[1] for e in queue_events if e[0] == kind).items())))
            riders = sum(e[2] for e in lanes)
            out["coalescer"] = {
                "lanes": len(lanes), "riders": riders,
                "rows": sum(e[3] for e in lanes),
                "riders_per_lane": round(riders / len(lanes), 3)
                if lanes else 0.0,
                "bypass": by_reason("bypass"), "shed": by_reason("shed")}
        # searches that found staged writes and took the index lock to see
        # them (the first read after a write), and what they waited there
        out["read_lock_waits"] = len(lock_waits)
        out["read_lock_wait_ms_sum"] = round(sum(lock_waits), 3)
        out["tiers"] = dict(sorted(tiers.items(), key=lambda kv: -kv[1]))
        # the store rows each tier's dispatches read over the window: all
        # live rows a scan, the probed rows an IVF dispatch, and for a
        # per-slot gather the sum over its slots of the rows gathered. What
        # a roofline may charge a program that reads a part of the rows.
        out["tier_rows"] = {t: tier_rows[t] for t in out["tiers"]}
        if depths:
            # dispatches by the depth R their scan step ran at, where the
            # shape says (the mesh's exact tier: "0" is the HIGHEST scan)
            out["rescore_r"] = depths
        if probed:
            # the partition-pruned dispatches of the window (index/plan.py):
            # `probed_rows` the store rows their programs read (every
            # query's top_p tiles, padding included, and the centroids),
            # `base_rows` the live rows a flat scan of the same dispatches
            # would have had to cover; the layout as the newest of them
            # saw it, `padding_share` the part of its slots that hold no row
            last = probed[-1]
            out["ivf"] = {
                "dispatches": len(probed),
                "probed_rows": sum(e["ivf_rows_read"] for e in probed),
                "base_rows": sum(e["ivf_base_rows"] for e in probed),
                "top_p": last["ivf_top_p"], "nlist": last["ivf_nlist"],
                "cap_p": last["ivf_cap_p"],
                "padding_share": last["ivf_padding_share"]}
        # invariant violations over the window
        # (costmodel.fused_invariant_ok): every dispatch translates on the
        # device, so `dispatches` is all of them; violations > 0 means a
        # second blocking fetch crept back into a dispatch. The block
        # keeps its name: dashboards and incident bundles read it
        out["fused"] = {"dispatches": n, "violations": violations}
        return out


def _pct(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile over an already-sorted list."""
    if not sorted_vals:
        return 0.0
    i = min(int(len(sorted_vals) * q / 100.0), len(sorted_vals) - 1)
    return float(sorted_vals[i])


# -- the restart timeline and the compile tally -------------------------------

# `startup.seconds`: the flat partition of process start -> listeners up that
# the benchmark's metric files read, and the stages each part sums. `other`
# is what is left of `app`, `post_startup` and `listen`; `unaccounted` what
# is left of `ready` (the gaps between the stages `python -m weaviate_tpu`
# opens one after another)
STARTUP_PARTS = {
    "boot": ("process", "backend"),
    "lsm": ("lsm.open", "inverted.open"),
    "log": ("log.check", "log.read", "log.parse"),
    "land": ("stage", "grow", "land", "flush"),
    "drain": ("drain",),
    # the partition-pruned tier's half of a restore (index/tpu.py): reading
    # the persisted layout, finding each replayed doc's slot in it,
    # assigning the docs it does not know and, where no layout covers the
    # rows, training one
    "ivf": ("ivf",),
}

# the jax.monitoring keys the tally listens to (jax 0.9: _src/dispatch.py
# BACKEND_COMPILE_EVENT wraps compiler.compile_or_get_cached, so a load from
# the persistent cache is an event too, with the load's seconds; the two
# cache events fire inside it, on the compiling thread, before it ends)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def process_start_ns() -> Optional[int]:
    """When the OS started this process, on the ``perf_counter_ns`` clock:
    field 22 of ``/proc/self/stat`` (clock ticks after boot) against
    ``CLOCK_BOOTTIME``. None where the OS gives none."""
    try:
        with open("/proc/self/stat", "rb") as f:
            stat = f.read()
        # the command (field 2) may hold blanks: count from its last ')'
        ticks = int(stat[stat.rindex(b")") + 2:].split()[19])
        age_ns = (time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                  - ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter_ns() - age_ns if age_ns >= 0 else None


class Timeline:
    """Stages on ``time.perf_counter_ns`` from an anchor on: the restart
    (process start -> listeners up -> first readiness probe) and, as a
    second instance, the way down. A stage is ``(name, thread, start_ns,
    length_ns, stats)``; one whose stats carry ``pieces`` is a SUM of that
    many pieces from `start_ns` on (a restore's inner stages: exclusive
    seconds, so siblings add up to at most their parent). Bounded, and off
    every request's path."""

    MEMORY_ROWS_MAX = 512
    INTERVALS_MAX = 1024
    MEMORY_EVERY_NS = 1_000_000_000

    def __init__(self, anchor_ns: int, anchor: str, memory: bool = True,
                 prefix: str = "startup."):
        self.anchor_ns = int(anchor_ns)
        self.anchor = anchor
        # of the stages' `wv/<prefix><name>` annotations
        self.prefix = prefix
        self._lock = threading.Lock()
        self._intervals: list[tuple] = []
        self._dropped = 0
        self._sample_memory = memory
        self._memory: list[list] = []
        self._memory_dropped = 0
        self._memory_last_ns = 0
        # names of the stages open on the calling thread, innermost last:
        # the stage a compile is charged to
        self._open = threading.local()
        # the process's compile tally when this timeline began and when it
        # was sealed: the compiles inside it are the difference
        self._compiles_from = compiles.counts()
        self._compiles_to: Optional[tuple] = None
        self.peak_at_restore_end: Optional[int] = None
        self._counts = dict.fromkeys(RESTORE_COUNTERS, 0)
        # stamped when the listeners are up: from then on the timeline
        # takes `first_ready` and nothing else (a class made at run time
        # opens its shard outside any restart)
        self.ready_ns: Optional[int] = None
        self.sealed = False

    # -- recording -----------------------------------------------------------

    def recording(self) -> bool:
        return self.ready_ns is None

    def push(self, name: str) -> None:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        stack.append(name)

    def pop(self) -> None:
        stack = getattr(self._open, "stack", None)
        if stack:
            stack.pop()

    def current(self) -> Optional[str]:
        stack = getattr(self._open, "stack", None)
        return stack[-1] if stack else None

    def note(self, name: str, start_ns: int, length_ns: int,
             stats: Optional[dict] = None, capacity: Optional[int] = None,
             sample: bool = True) -> None:
        """One closed stage, and the device's memory at its end."""
        tid = threading.get_native_id()
        with self._lock:
            if len(self._intervals) < self.INTERVALS_MAX:
                self._intervals.append((name, tid, int(start_ns),
                                        int(length_ns), dict(stats or {})))
            else:
                self._dropped += 1
        if sample:
            row = self.memory(name, capacity, force=True)
            if name == "vector.restore" and row is not None:
                self.peak_at_restore_end = row[4]

    def count(self, **counts) -> None:
        """Add to the restore's counters (`RESTORE_COUNTERS`)."""
        with self._lock:
            for name, amount in counts.items():
                self._counts[name] += amount

    def memory(self, event: str, capacity: Optional[int] = None,
               force: bool = False) -> Optional[list]:
        """``[t_ms, event, capacity, bytes_in_use, peak_bytes_in_use]`` of
        the fullest local device; unforced at most once a second. The two
        readings are None where the backend keeps none (cpu) or is not up
        yet."""
        if not self._sample_memory:
            return None
        now = time.perf_counter_ns()
        if not force and now - self._memory_last_ns < self.MEMORY_EVERY_NS:
            return None
        from weaviate_tpu.monitoring import memory as memledger

        in_use, peak = memledger.fullest_allocator() or (None, None)
        row = [round((now - self.anchor_ns) / 1e6, 3), event, capacity,
               in_use, peak]
        with self._lock:
            self._memory_last_ns = now
            if len(self._memory) < self.MEMORY_ROWS_MAX:
                self._memory.append(row)
            else:
                self._memory_dropped += 1
        return row

    def ready(self, metrics=None) -> dict:
        """The listeners are up: close the timeline to everything but
        `first_ready`, feed ``weaviate_startup_durations_ms`` one sample a
        stage and -> `seconds`, the `startup` log line's body."""
        now = time.perf_counter_ns()
        doc = self.summary()
        for name, st in doc["stages"].items():
            _observe_stage(metrics, name, st["seconds"])
        # stamped LAST: `first_ready` is taken from here on, so it is in
        # no summary made above and is sampled once
        self.ready_ns = now
        return doc["seconds"]

    def first_ready(self, metrics=None) -> None:
        """The first readiness probe answered after the listeners were up
        (`ready`): the last stage. The REST server answers probes from the
        moment it listens, while `listen` is still open (the gRPC server
        starts after it): a probe that early is not the stage's, or the
        stage would end before `listen` does and be sampled twice (by
        `ready`, then here)."""
        with self._lock:
            if self.sealed or self.ready_ns is None:
                return
            self.sealed = True
            self._compiles_to = compiles.counts()
        now = time.perf_counter_ns()
        # the sample before the page's stage: who reads the page and then
        # the metrics finds the stage in both
        _observe_stage(metrics, "first_ready", (now - self.ready_ns) / 1e9)
        self.note("first_ready", self.ready_ns, now - self.ready_ns)

    # -- the page ------------------------------------------------------------

    def summary(self) -> dict:
        with self._lock:
            intervals = list(self._intervals)
            memory = [list(r) for r in self._memory]
            dropped, memory_dropped = self._dropped, self._memory_dropped
            counts = dict(self._counts)
            inside = [b - a for a, b in zip(
                self._compiles_from, self._compiles_to or compiles.counts())]
        a = self.anchor_ns
        stages: dict = {}
        tot: dict[str, int] = {}
        for name, _, start, length, stats in intervals:
            tot[name] = tot.get(name, 0) + length
            st = stages.get(name)
            if st is None:
                stages[name] = {"start_ms": round((start - a) / 1e6, 3),
                                "seconds": 0.0, "stats": dict(stats)}
                continue
            # a stage several shards opened: the first start, the summed
            # seconds, numbers added up, `count` of them
            merged = st["stats"]
            merged["count"] = merged.get("count", 1) + 1
            for k, v in stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool) \
                        and isinstance(merged.get(k), (int, float)):
                    merged[k] += v
                else:
                    merged[k] = v
        for name, st in stages.items():
            st["seconds"] = round(tot[name] / 1e9, 6)
        # shards that opened on two threads at once: the page says so, and
        # the parts inside them are cut to the wall clock the opens took
        # together, in proportion
        opens = [(s, s + n) for name, _, s, n, _ in intervals
                 if name == "shard.open"]
        tids = {t for name, t, _, _, _ in intervals if name == "shard.open"}
        union = _union_ns(opens)
        summed = sum(e - s for s, e in opens)
        parallel = len(tids) > 1 and union < summed
        cut = union / summed if parallel else 1.0
        # `ivf` is a part only of a restart that ran the stage: a process
        # without the partition-pruned tier keeps the page it always had
        parts = {k: int(sum(tot.get(n, 0) for n in names)
                        * (1.0 if k == "boot" else cut))
                 for k, names in STARTUP_PARTS.items()
                 if k != "ivf" or "ivf" in tot}
        in_app = sum(v for k, v in parts.items() if k != "boot")
        parts["other"] = sum(tot.get(n, 0) for n in
                             ("app", "post_startup", "listen")) - in_app
        listen = [s + n for name, _, s, n, _ in intervals if name == "listen"]
        if listen:
            parts["ready"] = max(listen) - a
            parts["unaccounted"] = parts["ready"] - (
                parts["boot"] + in_app + parts["other"])
        seconds = {k: round(v / 1e9, 6) for k, v in parts.items()}
        seconds.setdefault("ready", None)
        seconds.setdefault("unaccounted", None)
        return {
            "anchor": self.anchor,
            "stages": stages,
            "seconds": seconds,
            "parallel": parallel,
            "intervals": [[name, tid, round((s - a) / 1e6, 3),
                           round(n / 1e9, 6), stats]
                          for name, tid, s, n, stats in intervals],
            "dropped": dropped,
            "memory": memory,
            "memory_dropped": memory_dropped,
            "compiles": dict(zip(
                ("count", "seconds", "cache_hits", "cache_misses"),
                (inside[0], round(inside[1], 6), *inside[2:]))),
            "peak_at_restore_end_bytes": self.peak_at_restore_end,
            **counts,
        }

    def line(self) -> str:
        """The stages as one JSON line (the way down: no page outlives the
        process, the server's log does)."""
        doc = self.summary()
        return json.dumps({
            "anchor": doc["anchor"],
            "seconds": round((time.perf_counter_ns() - self.anchor_ns) / 1e9,
                             6),
            "stages": doc["stages"]}, default=str)


def _observe_stage(metrics, name: str, seconds: float) -> None:
    """One sample of ``weaviate_startup_durations_ms{operation=<name>}``."""
    if metrics is None:
        return
    try:
        metrics.startup_durations.labels(name).observe(seconds * 1e3)
    except Exception:  # noqa: BLE001 -- metrics must not break start-up
        pass


def _union_ns(spans: list) -> int:
    """Length of the union of `(start, end)` spans."""
    total, reach = 0, None
    for s, e in sorted(spans):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


class CompileTally:
    """Every backend compile of the process, counted where it happens: the
    listeners `install()` hangs on ``jax.monitoring``. `count` and
    `seconds` are the ``backend_compile_duration`` events (a program
    compiled, or loaded from the persistent cache: the seconds are what the
    caller waited either way); `cache_hits` / `cache_misses` the
    compilation cache's own events (a miss is only an event where the
    cache would keep the program: with ``JAX_COMPILATION_CACHE_DIR`` set
    and jax's one-second floor, a short compile is neither)."""

    KEPT = 32

    def __init__(self):
        self._lock = threading.Lock()
        self._t0_ns = time.perf_counter_ns()
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._last: deque = deque(maxlen=self.KEPT)
        # what the cache said about the compile this thread is inside
        self._outcome = threading.local()
        self._installed = False

    def install(self) -> None:
        """Hang the listeners on ``jax.monitoring`` (imports jax; once a
        process: jax has no public way to take one listener off again)."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event: str, **kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._outcome.hit = True
        elif event == CACHE_MISS_EVENT:
            self._outcome.hit = False

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT:
            return
        hit = getattr(self._outcome, "hit", None)
        self._outcome.hit = None
        now = time.perf_counter_ns()
        tl = _startup
        stage = tl.current() if tl is not None and not tl.sealed else None
        t0 = tl.anchor_ns if tl is not None else self._t0_ns
        with self._lock:
            self.count += 1
            self.seconds += float(duration)
            self.cache_hits += hit is True
            self.cache_misses += hit is False
            self._last.append([round((now - t0) / 1e6, 3),
                               round(float(duration), 6), hit, stage,
                               kw.get("fun_name")])

    def counts(self) -> tuple:
        """(count, seconds, cache_hits, cache_misses) so far."""
        with self._lock:
            return (self.count, self.seconds, self.cache_hits,
                    self.cache_misses)

    def summary(self) -> dict:
        with self._lock:
            return {"count": self.count,
                    "seconds": round(self.seconds, 6),
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses,
                    "last": [list(r) for r in self._last]}


# -- module state + zero-hop accessors ----------------------------------------

_window: Optional[PerfWindow] = None

# the restart's timeline (kept for the page after it closed), the one that
# is recording now (the restart's until the listeners are up, the way
# down's from the signal on; None between the two), and the compile tally
_startup: Optional[Timeline] = None
_recording: Optional[Timeline] = None
compiles = CompileTally()

# final summaries of recently-unconfigured windows (CI failure artifact:
# tests/conftest.py dumps these so a red run's bundle carries the perf
# picture of the Apps the suite ran — bounded, newest last). Guarded by
# its own lock: concurrent App teardowns (test suites) share it.
_final_summaries: deque = deque(maxlen=8)
_summaries_lock = threading.Lock()


def configure(window: Optional[PerfWindow]) -> Optional[PerfWindow]:
    """Install (or clear, with None) the process-wide perf window."""
    global _window
    _window = window
    return window


def unconfigure(window: PerfWindow) -> None:
    """Clear the global only if it is still `window` (App shutdown must
    not tear down a newer App's window); stash its final summary for the
    CI artifact dump when it saw any dispatches."""
    global _window
    try:
        if window._total_dispatches > 0:
            doc = window.summary()
            with _summaries_lock:
                _final_summaries.append(doc)
    except Exception:  # noqa: BLE001 — teardown must never fail shutdown
        pass
    if _window is window:
        _window = None


def get_window() -> Optional[PerfWindow]:
    return _window


def startup_begin(main_ns: Optional[int] = None) -> Timeline:
    """Open the restart's timeline: anchored at the OS's start time of the
    process where it gives one (then `process` is the stage from there to
    `main_ns`, the first line of ``main()``), else at `main_ns`."""
    global _startup, _recording
    main_ns = time.perf_counter_ns() if main_ns is None else main_ns
    os_ns = process_start_ns()
    if os_ns is not None and os_ns <= main_ns:
        tl = Timeline(os_ns, "os")
        tl.note("process", os_ns, main_ns - os_ns)
    else:
        tl = Timeline(main_ns, "main")
    _startup = _recording = tl
    return tl


def shutdown_begin() -> Timeline:
    """Open the way down's timeline, anchored now (the signal)."""
    global _recording
    _recording = Timeline(time.perf_counter_ns(), "signal", memory=False,
                          prefix="shutdown.")
    return _recording


def timeline() -> Optional[Timeline]:
    """The timeline that takes stages now, None outside a restart and a
    shutdown: `tracing.stage` and a restore's sums are stamps alone then."""
    tl = _recording
    if tl is not None and tl.recording():
        return tl
    return None


def startup() -> Optional[Timeline]:
    """The restart's timeline (``/debug/perf`` `startup`), recording or
    closed; None in a process nobody started as a server."""
    return _startup


def timeline_reset() -> None:
    """Forget both timelines (tests)."""
    global _startup, _recording
    _startup = _recording = None


def note_phase(name: str, ms: float) -> None:
    """`PerfWindow.note_phase` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_phase(name, ms)


def note_write_phase(name: str, ms: float) -> None:
    """One sample of a write-path stage; no-op when the perf plane is
    disabled."""
    w = _window
    if w is not None:
        w.note_write_phase(name, ms)


def note_write(**counts) -> None:
    """Add to the window's write counters; no-op when disabled."""
    w = _window
    if w is not None:
        w.note_write(counts)


def note_read_lock_wait(ms: float) -> None:
    w = _window
    if w is not None:
        w.note_read_lock_wait(ms)


def note_coalescer(event: str, reason: str = "", riders: int = 0,
                   rows: int = 0) -> None:
    """`PerfWindow.note_coalescer` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_coalescer(event, reason, riders, rows)


def note_point_get(*counts, **events) -> None:
    """`PerfWindow.note_point_get` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_point_get(*counts, **events)


def note_rescore(rows: int, nbytes: int, promoted: int, by: str) -> None:
    """`PerfWindow.note_rescore` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_rescore(rows, nbytes, promoted, by)


def note_group_inputs(lists: int, ids: int, reason: Optional[str],
                      host_ms: float, pool_hits: int,
                      pool_grows: int) -> None:
    """`PerfWindow.note_group_inputs` on the installed window; one
    comparison while the plane is down."""
    w = _window
    if w is not None:
        w.note_group_inputs(lists, ids, reason, host_ms, pool_hits,
                            pool_grows)


def note_posting(segment_probes: int, ids: int, walk: str) -> None:
    """`PerfWindow.note_posting` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_posting(segment_probes, ids, walk)


def note_interval(name: str, start_ns: int, end_ns: int,
                  tid: Optional[int] = None) -> None:
    """`PerfWindow.note_interval` on the installed window; one comparison
    while the plane is down."""
    w = _window
    if w is not None:
        w.note_interval(name, start_ns, end_ns, tid)


def recent_summaries() -> list:
    """Final summaries of windows torn down this process (newest last),
    plus the live window's current summary when one is installed."""
    with _summaries_lock:
        out = list(_final_summaries)
    w = _window
    if w is not None:
        try:
            out.append(w.summary())
        except Exception:  # noqa: BLE001
            pass
    return out
