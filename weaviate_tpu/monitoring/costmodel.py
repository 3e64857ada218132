"""Analytic device cost model: FLOPs/bytes per dispatch vs platform peaks.

The shape of a dispatch (``DispatchShape``: tier, rows scanned, bytes per
row) with the host-overhead ledger the index stamps on it while it runs,
read by the serving path (monitoring/tracing.py DispatchRecord facts, the
rolling window behind ``/debug/perf`` in monitoring/perf.py) and by the
BM25 device engine's batch-shape recording (inverted/bm25_device.py); and
the roofline arithmetic of a scan at a measured rate. The served path computes
no roofline of its own: a share of the chip's peaks is read from a
profiler capture (benchmarks/readers/xplane_ops.py), not from analytic
work over a host wall.

Conventions (kept deliberately):

- FLOPs are the *useful* distance math — ``2 · B · N · D`` per scan batch
  (the matmul at the heart of every tier) — not implementation FLOPs, so
  MFU is comparable across tiers (PQ's reconstruction-as-matmul does more
  hardware FLOPs to serve the same distance work).
- Bytes are the store bytes actually read from HBM per batch (queries,
  LUTs, and top-k buffers are noise at these shapes): ``N · bytes_per_row``
  with bytes_per_row = 4·D for the f32 store, 2·D for the bf16 rescore
  copy, M (segments) for codes-only PQ.
- Arithmetic intensity is therefore ``2·B / bytes_per_elem``: the batch
  width decides the regime, which is why batch-first serving (the
  coalescer) is the design lever.

Peaks live in ONE table keyed by the ``device_kind`` JAX reports, each with
its source. A TPU kind that is not in the table, a platform that is neither
``tpu`` nor ``cpu``, and a failed detection are errors, never a default: a
roofline share against the wrong peaks is worse than none. The ``cpu``
entry is a *nominal* single-socket estimate for the test tier, so cpu rows
carry the same fields — cpu mfu_pct is a proxy, not a claim. The module
imports only the stdlib (platform detection imports jax lazily and caches),
so index/db/serving layers can import it without cycles or backend init.
"""

from __future__ import annotations

import os
import time
from typing import Optional

# -- platform peaks -----------------------------------------------------------

TPU_V5E = "TPU v5 lite"  # jax.devices()[0].device_kind on a v5e chip

PEAKS = {
    TPU_V5E: {"tflops": 197.0, "hbm_gbs": 819.0,
              "note": "v5e peaks: 197 bf16 TFLOP/s MXU, 819 GB/s HBM",
              "source": "Google Cloud documentation, 'TPU v5e': 197 "
                        "TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip"},
    "cpu": {"tflops": 0.096 * (os.cpu_count() or 1), "hbm_gbs": 25.0,
            "note": (f"nominal CPU peaks ({os.cpu_count() or 1} core(s) x "
                     "96 GFLOP/s AVX2+FMA, 25 GB/s DRAM) — proxy only"),
            "source": "nominal estimate for the CPU test tier, not a "
                      "datasheet figure"},
}

_detected_backend: Optional[str] = None


def peaks_key(platform: str, device_kind: str) -> str:
    """(jax platform, device_kind) -> PEAKS key. Raises for a TPU kind the
    table does not hold and for any platform other than tpu / cpu."""
    if platform == "cpu":
        return "cpu"
    if platform == "tpu" and device_kind in PEAKS:
        return device_kind
    raise KeyError(
        f"no peaks for platform {platform!r} device_kind {device_kind!r}: "
        f"add the chip to costmodel.PEAKS with its source "
        f"(known: {sorted(PEAKS)})")


def detect_backend() -> str:
    """PEAKS key for the live jax backend, cached after the first call.
    Raises when no backend comes up or the device is not in the table."""
    global _detected_backend
    if _detected_backend is None:
        from weaviate_tpu import device  # noqa: PLC0415 — lazy: jax import

        ident = device.identity()
        _detected_backend = peaks_key(ident["platform"],
                                      ident["device_kind"])
    return _detected_backend


# -- dispatch tiers -----------------------------------------------------------

# the serving read tiers of index/tpu.py _dispatch_search, plus the BM25
# device engine's batched matmul — the `tier` fact on dispatch traces and
# the top-tier tally in /debug/perf
TIER_EXACT = "exact_scan"            # full f32 (or bf16-store) scan
TIER_PQ_RESCORE = "pq_rescore_bf16"  # PQ with rescore: scans the bf16 copy
TIER_PQ_CODES = "pq_codes"           # codes-only ADC (gmin / recon / LUT)
TIER_PQ_ADC4 = "pq_adc4"             # 4-bit funnel: nibble scan + re-rank
TIER_GATHER = "gather"               # small-allowList gathered row scoring
TIER_BM25_MATMUL = "bm25_matmul"     # dense-row keyword batch matmul


class DispatchShape:
    """The analytic shape of ONE device dispatch, plus the host-overhead
    ledger timings the index stamps while executing it.

    Built on the serving path ONLY while the tracer is up (index/tpu.py
    gates construction on ``tracing.get_tracer()``), so the disabled
    serving path constructs zero of these — the same contract as spans.

    Analytic fields (set at construction):
      tier           one of the TIER_* constants
      n              rows the dispatch scans (live rows; the allowList size
                     on the gather tier; n_pad on the BM25 matmul; on an
                     IVF partition-pruned dispatch the PROBED rows —
                     top_p x bucket capacity a padded query, plus the
                     nlist centroid rows — so flops()/bytes() are
                     probed-aware and the roofline never reports the
                     phantom work of the rows the probe skipped;
                     ``extra`` then carries
                     {"ivf": True, "probed_fraction": probed/N})
      dim            vector dims (effective units for BM25)
      batch          ACTUAL query rows (useful work — padding is reported
                     separately, never smeared; the PR-3 convention)
      batch_padded   device dispatch width after bucket padding
      bytes_per_row  HBM bytes read per scanned row
      k              selection depth

    Ledger fields (stamped by the index/shard while the dispatch runs;
    ms, -1 = not measured):
      enqueue_ms     host time building + enqueueing the device work
                     (query prep, allowList pack, host gather)
      device_ms      the ONE blocking device->host fetch (finalize)
      finalize_ms    whole finalize() wall — device_ms + the host hop
      rescore_ms     float32 rescoring of a compressed dispatch's
                     candidates from the host's rows (inside finalize)
      filter_ms      allowList build (shard, filtered dispatches)
      hydrate_ms     LSM result hydration (shard)
    and the monotonic interval [t_start, t_end] from enqueue start to
    fetch end — the in-flight-device interval the duty cycle integrates.
    """

    __slots__ = ("tier", "n", "dim", "batch", "batch_padded",
                 "bytes_per_row", "k", "extra", "ndev",
                 "enqueue_ms", "device_ms", "finalize_ms", "rescore_ms",
                 "filter_ms", "hydrate_ms", "t_start", "t_end",
                 "t_fetch", "t_fetch_mono", "fetches", "hop")

    def __init__(self, tier: str, n: int, dim: float, batch: int,
                 bytes_per_row: float, k: int = 0,
                 batch_padded: int = 0, extra: Optional[dict] = None,
                 ndev: int = 1):
        self.tier = tier
        self.n = int(n)
        self.dim = dim
        self.batch = int(batch)
        self.batch_padded = int(batch_padded) or int(batch)
        self.bytes_per_row = bytes_per_row
        self.k = int(k)
        self.extra = extra
        # devices the SPMD program spans (mesh dispatches): `n` stays the
        # GLOBAL row count so flops()/bytes() keep reporting whole-dispatch
        # work; a per-chip reading divides by ndev
        self.ndev = max(int(ndev), 1)
        self.enqueue_ms = -1.0
        self.device_ms = -1.0
        self.finalize_ms = -1.0
        self.rescore_ms = -1.0
        self.filter_ms = -1.0
        self.hydrate_ms = -1.0
        self.t_start = 0.0
        self.t_end = 0.0
        # fetch-end stamps (index _fetch_packed): perf_counter for the
        # in-flight duration, monotonic for the duty-cycle anchor (the
        # perf window runs on time.monotonic — hydration happens between
        # fetch end and the window's record call, so the record time is
        # NOT a usable anchor)
        self.t_fetch = 0.0
        self.t_fetch_mono = 0.0
        # blocking device->host fetches (_fetch_packed). Every program
        # emits final doc ids, so a dispatch owes exactly ONE
        # (fused_invariant_ok; violations counted by the perf window).
        self.fetches = 0
        # the open `gather_hop` interval (a tracing.Phase): _fetch_packed
        # opens it at fetch end, the dispatch's finalize closes it
        self.hop = None

    # -- analytic totals -----------------------------------------------------

    def flops(self) -> int:
        """Useful distance FLOPs for the whole dispatch (actual rows). A
        partition-pruned dispatch's `n` is the rows ALL its padded queries
        read, each its own: a query is scored against its own share."""
        n = self.n
        if self.extra and self.extra.get("ivf"):
            n = (self.ndev * self.extra["ivf_top_p"] * self.extra["ivf_cap_p"]
                 + self.extra["ivf_nlist"])
        return int(round(2.0 * self.batch * n * self.dim))

    def bytes(self) -> int:
        """Store bytes read from HBM for the whole dispatch. On the
        pq_adc4 tier `bytes_per_row` covers only the stage-1 nibble scan
        (M/2 per scanned row); the re-rank stages gather per QUERY, not
        per row, so their traffic rides ``extra`` — funnel_c x the 8-bit
        code row for stage 2, funnel_rescore x the bf16 row for stage 3
        — and is added here per batch row."""
        total = self.n * self.bytes_per_row
        if self.extra and self.tier == TIER_PQ_ADC4:
            total += self.batch * (
                self.extra.get("funnel_c", 0)
                * self.extra.get("funnel_stage2_bytes_per_row", 0)
                + self.extra.get("funnel_rescore", 0)
                * self.extra.get("funnel_stage3_bytes_per_row", 0))
        return int(round(total))

    def hop_ms(self) -> float:
        """The host hop between the device fetch and hydration — finalize
        wall minus the blocking fetch (and minus a compressed dispatch's
        float32 rescoring, a phase of its own). The slot->doc translation
        runs ON DEVICE inside the search program, so the hop is the
        unpack: dtype views + two word copies (docs/performance.md
        "anatomy of a dispatch"). -1 when the split was not measured."""
        if self.finalize_ms < 0.0 or self.device_ms < 0.0:
            return -1.0
        return max(self.finalize_ms - self.device_ms
                   - max(self.rescore_ms, 0.0), 0.0)

    def end_hop(self) -> float:
        """Close the open `gather_hop` interval (finalize, on the thread
        that fetched) -> the perf_counter seconds at its end."""
        hop, self.hop = self.hop, None
        return hop.end() / 1e9 if hop is not None else time.perf_counter()

    def ledger(self) -> dict:
        """{phase: ms} of every measured host-overhead ledger stage."""
        out = {}
        if self.filter_ms >= 0.0:
            out["filter"] = self.filter_ms
        if self.enqueue_ms >= 0.0:
            out["enqueue"] = self.enqueue_ms
        if self.device_ms >= 0.0:
            out["device"] = self.device_ms
        hop = self.hop_ms()
        if hop >= 0.0:
            out["gather_hop"] = hop
        if self.rescore_ms >= 0.0:
            out["rescore"] = self.rescore_ms
        if self.hydrate_ms >= 0.0:
            out["hydrate"] = self.hydrate_ms
        return out

    def describe(self) -> dict:
        """Flat dict of the analytic shape (bench rows, trace facts)."""
        d = {"tier": self.tier, "n": self.n, "dim": round(self.dim, 2),
             "batch": self.batch, "batch_padded": self.batch_padded,
             "k": self.k, "flops": self.flops(), "bytes": self.bytes()}
        if self.ndev != 1:
            d["ndev"] = self.ndev
        if self.extra:
            d.update(self.extra)
        return d

    def roofline_at_qps(self, qps: float,
                        backend: Optional[str] = None) -> dict:
        """Offline-style roofline for this shape at a measured QPS (bench
        rows: QPS is per query row, batches/s = qps/batch)."""
        return roofline_from_qps(qps, self.n, self.dim, self.batch,
                                 self.bytes_per_row, backend)


def fused_invariant_ok(shape: "DispatchShape") -> bool:
    """The dispatch ledger invariant: the one packed fetch carries final
    doc ids, so a dispatch makes exactly ONE blocking fetch. The perf
    window counts violations per window (monitoring/perf.py), and
    tests/test_fused_dispatch.py pins the contract per tier."""
    if shape.n <= 0:
        # empty-gather early return: no device work ran, no fetch owed
        return shape.fetches <= 1
    return shape.fetches == 1


# -- roofline math ------------------------------------------------------------

def ridge(backend: Optional[str] = None) -> float:
    """The roofline ridge point (flops/byte) of a backend's peaks — the
    ONE place the compute-vs-bandwidth-bound threshold is computed."""
    peak = PEAKS[backend or detect_backend()]
    return peak["tflops"] * 1e12 / (peak["hbm_gbs"] * 1e9)


def regime(flops: float, bytes_: float,
           backend: Optional[str] = None) -> str:
    """Which peak the work's arithmetic intensity pins."""
    ai = flops / max(bytes_, 1.0)
    return "compute-bound" if ai >= ridge(backend) else "hbm-bandwidth-bound"


def roofline(flops: float, bytes_: float, seconds: float,
             backend: Optional[str] = None) -> dict:
    """Achieved-vs-peak roofline for `flops`/`bytes_` of work done in
    `seconds`: the per-dispatch / per-window form (roofline_from_qps wraps
    this). backend=None detects the live platform."""
    backend = backend or detect_backend()
    peak = PEAKS[backend]
    secs = max(float(seconds), 1e-9)
    tflops = flops / secs / 1e12
    gbs = bytes_ / secs / 1e9
    ai = flops / max(bytes_, 1.0)
    return {
        "tflops": round(tflops, 3),
        "hbm_gbs": round(gbs, 2),
        "mfu_pct": round(100.0 * tflops / peak["tflops"], 2),
        "bw_pct": round(100.0 * gbs / peak["hbm_gbs"], 2),
        "arith_intensity_flops_per_byte": round(ai, 1),
        "ridge_flops_per_byte": round(ridge(backend), 1),
        "regime": regime(flops, bytes_, backend),
        "peaks": peak["note"],
    }


def roofline_from_qps(qps, n, dim, batch, bytes_per_row,
                      backend: Optional[str] = None) -> dict:
    """Achieved-vs-peak roofline fields for one flat-scan row at a
    measured QPS (tests/test_perf.py pins the peaks, the ridge and the
    arithmetic).

    FLOPs are the *useful* distance math (2·B·N·D per batch), bytes the
    store bytes read per batch; arithmetic intensity 2·B/bytes_per_elem —
    batch size decides the regime (the lever batch-first serving
    exploits)."""
    flops_per_batch = 2.0 * batch * n * dim
    bytes_per_batch = float(n) * bytes_per_row
    batches_per_s = qps / batch
    return roofline(flops_per_batch * batches_per_s,
                    bytes_per_batch * batches_per_s, 1.0, backend)


# -- a group of filtered slots: which slots gather, which share one scan ------
# Modelled seconds of the two tiers that serve a filtered slot
# (index/tpu.py search_by_vectors_multi_async). A gathered row is read at a
# fraction of the streamed rate; a scanned slot pays the host for its
# [capacity / 32] words (packed and uploaded) and shares ONE pass over the
# slab with every other scanned slot; every program pays a launch. The
# constants are orders of magnitude, set from the traced runs in PERF.md
# (section 6, PR 30), not fitted: what matters is that the hand-over is a
# comparison of whole dispatches, so a slot a row under and a row over it
# cost about the same.
# a gathered row against a streamed one: 31-42 ns a 768 B row in the
# 2,048- and 8,192-row programs on a v5e (my chip run, PR 30), 0.94 streamed
GATHER_ROW_SLOWDOWN = 32.0
SCAN_EFFICIENCY = 0.7           # share of the HBM peak a masked scan reaches
# packing and uploading words or row indices: about 1 ms for the 256 KB of
# words of one slot at capacity 2^21 (profiled on the sandbox's CPU, PR 30)
HOST_BYTES_PER_S = 2.5e8
LAUNCH_S = 150e-6               # one more program: enqueue, fetch, unpack


def plan_filtered_group(sizes, buckets, scan_rows: int, capacity: int,
                        bytes_per_row: float, max_gather_rows: int,
                        backend: Optional[str] = None
                        ) -> tuple[list[bool], float]:
    """-> (for each slot whether the masked scan serves it, else the gather;
    the modelled seconds of the group served so).

    `sizes[i]` rows slot i's filter allows (> 0), `buckets[i]` the rows its
    gather would read (its row bucket). The cheapest partition sends the
    slots above some size to the scan (a scanned slot's cost does not
    depend on its size, a gathered slot's grows with it), so every cut of
    the slots in order of size is priced and the cheapest kept. A slot
    whose bucket passes `max_gather_rows` has no gather program."""
    hbm = PEAKS[backend or detect_backend()]["hbm_gbs"] * 1e9
    scan_once = scan_rows * bytes_per_row / (hbm * SCAN_EFFICIENCY) + LAUNCH_S
    scan_slot = (capacity / 8.0) / HOST_BYTES_PER_S
    gather_row = bytes_per_row * GATHER_ROW_SLOWDOWN / hbm \
        + 4.0 / HOST_BYTES_PER_S
    order = sorted(range(len(sizes)), key=lambda i: sizes[i])
    # cost of gathering the `cut` smallest slots and scanning the rest
    best_cut, best = 0, float("inf")
    gathered, launched = 0.0, set()
    for cut in range(len(order) + 1):
        if cut:
            i = order[cut - 1]
            if buckets[i] > max_gather_rows:
                break
            gathered += buckets[i] * gather_row
            launched.add(buckets[i])
        scanned = len(order) - cut
        cost = gathered + LAUNCH_S * len(launched) \
            + (scan_once + scan_slot * scanned if scanned else 0.0)
        if cost < best:
            best_cut, best = cut, cost
    to_scan = [True] * len(sizes)
    for i in order[:best_cut]:
        to_scan[i] = False
    return to_scan, best
