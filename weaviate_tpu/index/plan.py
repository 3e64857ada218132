"""What a search dispatch runs, decided once: the one place that knows
which tier (`costmodel.TIER_*`) and which program serve an index state, and
the handle that carries what the dispatch learned to its caller.

`plan_search` is a plain function of a `PlanView`: what one snapshot shows,
the index's config and metric, and the answers that are already functions
(`gmin_scan.kernel_serves` through `ProgramCounts`, `rescore_depth`, the
index's IVF probe planner). Both indexes dispatch from its `SearchPlan`
(`TpuVectorIndex._enqueue_search`, `MeshVectorIndex._dispatch_search`),
`dispatch_tier` is its `tier`, and `SearchPlan.shape` is the one place a
`costmodel.DispatchShape` of a planned dispatch is built. `DispatchHandle` is
what such a dispatch returns: call it for `(ids, dists)`, read `plan`,
`shape`, `snapshot` and `lock_wait_ms` off it on whatever thread holds it.

`_plan_group` / `costmodel.plan_filtered_group` price a GROUP of filters
and stay in `index/tpu.py`: a single filter as a group of one would choose
another program than `flat_search_cutoff` does (ROADMAP Queue 3 item 4).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

from weaviate_tpu.config.config import (PQ4_FUNNEL_C_BUCKETS,
                                        PQ4_FUNNEL_RESCORE_BUCKETS,
                                        RESCORE_R_BUCKETS)
from weaviate_tpu.monitoring import costmodel, incidents
from weaviate_tpu.monitoring.metrics import record_device_fallback
# metrics with a matmul form: the fast scans, the kernels, the funnel
from weaviate_tpu.ops.ivf import MATMUL_METRICS
from weaviate_tpu.serving import controller
from weaviate_tpu.testing import faults

# the smallest slab the group-min kernel tiles (one chip's rows)
MIN_KERNEL_SLAB = 16384

# kernels a dispatch may find refused inside `guarded_kernel_call`
KERNEL_GMIN, KERNEL_FUNNEL = "gmin", "funnel"

# Store rows a full-store program streams in the time a probed program
# reads ONE row of a probed partition. A probed program reads every query's
# partitions for that query alone, the full-store programs read the store
# once for the whole batch: `probed_reads_less` is the one place the two
# are weighed, and a layout pays by how it is read. On a v5e, 768-d
# float32, 4,096 partitions (`PERF.md` section 6, PR 43):
PROBED_ROW_COST = 2.8
# ... the tiled layout, whole tiles read in place (`ops/ivf.py
# ivf_tiles_topk`), timed alone over 352-slot tiles: 4.22 ms for 16 queries
# of 64 tiles (11.7 ns a row) where the flat program takes 6.15 ms for its
# 1.44M slots (4.3 ns a row): 2.75. One query wins 25 to 1, 16 win 1.5 to
# 1, and 64 lose 4 to 1 (26.4 ms: a batch's tiles are a gather).
GATHERED_ROW_COST = 7.0
# ... a bucket table, whose probe gathers its rows by slot (the compressed
# tiers, the PCA prefilter, the mesh): the parent's served program, 0.757 ms
# a query of 64 buckets of 384 slots (30.8 ns a row) against the flat
# scan's 4.45 ms for 1M rows. (One XLA gather of whole 352-row tiles takes
# 13.7 ms: that is not how a bucket table reads.)


def probed_reads_less(b_padded: int, ndev: int, top_p: int, cap_p: int,
                      nlist: int, n: int, gathered: bool = False) -> bool:
    """Is the partition-pruned program the shorter one against a full-store
    scan of `n` rows, for a dispatch of `b_padded` queries that each probe
    `top_p` partitions of `cap_p` slots a chip? Rows stand for bytes (both
    programs read the same operand), weighed by how the layout is read."""
    cost = GATHERED_ROW_COST if gathered else PROBED_ROW_COST
    return (cost * b_padded * ndev * top_p * cap_p + nlist) < n


def same_program_width(view: "PlanView", k: int, widths) -> Optional[int]:
    """Where a lane of single queries has to close so that its dispatch runs
    the program each rider's own would have: the widest of `widths` (padded
    dispatch widths, ascending) for which `probed_reads_less` still holds,
    over a partition layout whose probe serves ONE query at depth `k` on the
    state `view` shows (a wider lane would be declined to the flat program:
    other bytes, and the flat step's answers). None where every width runs
    one program: no layout serves, or it is declined at the first width
    already, and a full-store program reads the store once whatever the
    width."""
    probe = view.ivf_probe(k) if view.ivf_probe is not None else None
    if probe is None:
        return None
    nlist, cap_p = view.ivf_meta
    probed = [w for w in widths if probed_reads_less(
        w, view.ndev, probe[0], cap_p, nlist, view.n, view.ivf_gathered)]
    return probed[-1] if probed and probed[0] == widths[0] else None


def rescore_depth(config, metric: str, k: int, n: int) -> int:
    """Fast-scan candidate depth R of a scan over a slab of n rows (the
    one rule of both indexes: the mesh plans it against one chip's slab):
    0 disables (exactTopK config or non-matmul metrics); otherwise 4k
    clamped to [32, r_max] — selection errors of the single-pass scan sit
    well within 4k candidates. r_max is 128 statically; the control
    plane's recall-guarded budget controller (serving/controller.py) may
    lower it bucket-by-bucket while the shadow auditor's recall EWMA
    holds measured slack over the configured floor — the cap is
    clamped, jit-bucket-snapped, and lapses back to 128 when the
    controller stalls or dies."""
    if getattr(config, "exact_topk", False):
        return 0
    if metric not in MATMUL_METRICS:
        return 0
    # R_BUCKETS single source of truth (config.RESCORE_R_BUCKETS,
    # aliased by serving/controller.py): cap values are buckets and
    # the static choices are {max(4k, floor)} ∪ buckets, so a
    # controller cut can never mint a jit shape the static path
    # wouldn't also compile
    r_top = RESCORE_R_BUCKETS[-1]
    r_max = controller.rescore_r_cap(r_top)
    if r_max < 2 * k:
        # a cap below this query's slack threshold would zero r and
        # force the full-precision exact scan — strictly MORE device
        # work than the static path; the budget controller may only
        # cut, so queries too deep for the cap keep the static max
        r_max = r_top
    r = int(min(max(4 * k, RESCORE_R_BUCKETS[0]), r_max, max(n, 1)))
    # no candidate slack over k => the fast pass would pick the FINAL set
    # at reduced precision; fall back to the HIGHEST-precision scan
    return r if r >= 2 * k else 0


def funnel_budgets(k: int, n: int) -> tuple[int, int]:
    """(rg4 stage-1 groups, rc stage-2 survivors) for a funnel whose
    scan plane holds n rows — one chip's SLAB on the full-store tier
    (capacity, or the mesh's n_loc: each chip funnels its own rows; dead
    slots mask to inf; the group-column count plan_funnel clamps against
    is slab-derived), the probed candidate count on the IVF tier. The two
    caps are the controller's recall-guarded budgets
    (serving/controller.py), single-sourced from the
    config.PQ4_FUNNEL_*_BUCKETS ladders exactly like rescore_r_cap —
    bucket values in, so the jit shapes plan_funnel emits stay bounded.
    The same no-starvation floor as rescore_depth: a cap too shallow for
    this query's k lapses to the static max (the controller may only cut
    work, never break coverage)."""
    from weaviate_tpu.ops import pq4 as pq4_ops

    c_top = PQ4_FUNNEL_C_BUCKETS[-1]
    rc_top = PQ4_FUNNEL_RESCORE_BUCKETS[-1]
    c_cap = controller.funnel_c_cap(c_top)
    rc_cap = controller.funnel_rescore_cap(rc_top)
    if c_cap < 4 * k:
        c_cap = c_top
    if rc_cap < 2 * k:
        rc_cap = rc_top
    return pq4_ops.plan_funnel(k, n, c_cap, rc_cap)


@dataclasses.dataclass(frozen=True, slots=True)
class PlanView:
    """What `plan_search` reads of one index state: an index makes one a
    dispatch from the snapshot it pinned (`_plan_view`), outside any lock."""

    config: object            # flat_search_cutoff, exact_topk
    metric: str
    programs: object          # gmin_scan.ProgramCounts: kernel_serves, counted
    kernels: object           # carries `_gmin_broken`, the gmin failure domain
    component: str            # the fallback counter's label
    n: int                    # rows the slabs hold: n, or the mesh's n_total
    live: int
    dim: int
    ndev: int
    slab: int                 # rows of ONE chip's slab: capacity, or n_loc
    fill: int                 # the fullest slab's high-water mark
    itemsize: int             # bytes a component of the store
    compressed: bool
    pq_segments: int = 0
    # M of the 4-bit plane where this state can run the funnel, else 0
    pq4_segments: int = 0
    # pq.rescore and the rows it reads exist; their bytes a row (on the mesh
    # the funnel's last stage reads them whatever pq.rescore says)
    rescore: bool = False
    rescore_bytes_per_row: int = 0
    # bytes a component where the rescore tier is a full-store scan of a copy
    # of the rows, a choice of program like the exact tier's (one chip); 0
    # where the step scans the codes and rescores inside (mesh)
    rescore_scan_itemsize: int = 0
    # rows `rescore_depth` is planned against where the depth is a static of
    # the scan step the index passes it (mesh: n_loc); 0 where the scan
    # program derives its own from the rows it is given (one chip)
    depth_rows: int = 0
    # (nlist, cap_p) and the index's probe planner, k -> (top_p, prefilter_c)
    # or None, where the partition-pruned plane can serve this state
    ivf_meta: Optional[tuple] = None
    ivf_probe: Optional[Callable] = None
    # the layout is a bucket table whose probe gathers rows by slot, not
    # tiles of the store read in place
    ivf_gathered: bool = False


@dataclasses.dataclass(frozen=True, slots=True)
class SearchPlan:
    """What one dispatch will run. The depths are the statics the
    dispatchers pass their programs; `rows`, `bytes_per_row` and `extra` are
    what the dispatch's `costmodel.DispatchShape` says of it."""

    tier: str
    k_eff: int
    rows: int                 # rows read: n, the probed rows, the allowed
    dim: int
    batch: int
    batch_padded: int
    bytes_per_row: int
    ndev: int = 1
    program: Optional[str] = None     # gmin | scan where a full-store scan
    gmin: Optional[tuple] = None      # (rg, active_g) of the kernel
    rescore_r: Optional[int] = None   # the scan step's depth, where planned
    ivf: Optional[tuple] = None       # (top_p, prefilter_c)
    # the state has a partition layout and a full-store program was taken
    # because it reads less (`probed_reads_less`); the dispatcher counts it
    ivf_declined: bool = False
    funnel: Optional[tuple] = None    # (rg4, rc)
    extra: Optional[dict] = None
    refused: frozenset = frozenset()

    def stats(self) -> dict:
        """What the `enqueue` interval says of the program besides `rows`
        and `tier`."""
        out = {}
        if self.program is not None:
            out["program"] = self.program
        if self.rescore_r is not None:
            out["rescore_r"] = self.rescore_r
        return out

    def shape(self, t_start: float):
        """The dispatch's perf-attribution shape (monitoring/costmodel.py):
        built ONLY while the tracer is up, by the dispatch's owner — the
        disabled serving path constructs nothing (spy-pinned in
        tests/test_perf.py)."""
        extra = {**(self.extra or {}), **self.stats()}
        shape = costmodel.DispatchShape(
            self.tier, n=self.rows, dim=self.dim, batch=self.batch,
            batch_padded=self.batch_padded,
            bytes_per_row=self.bytes_per_row, k=int(self.k_eff),
            ndev=self.ndev, extra=extra or None)
        shape.t_start = t_start
        return shape


def _scan_program(view: PlanView, b_padded: int, kk: int,
                  itemsize: int) -> tuple[str, Optional[tuple]]:
    """Which of the two full-store programs scans a slab of `itemsize`-byte
    components -> (program, the kernel's (rg, active_g) or None). The fused
    group-min kernel where it is eligible, compiles and is the faster at
    this width (`gmin_scan.kernel_serves`, asked through the index's
    `ProgramCounts`), the lax.scan program otherwise. A no is a choice, not
    a degradation: nothing of the kernel's is built, nothing compiled or
    validated, no fallback counted; `declined_slower` counts the dispatches
    the kernel would have fitted."""
    from weaviate_tpu.ops import gmin_scan

    scan = (gmin_scan.PROGRAM_SCAN, None)
    if getattr(view.config, "exact_topk", False):
        return scan  # config opt-out, not degradation
    if view.kernels._gmin_broken:
        record_device_fallback(view.component, "degraded", log=False)
        incidents.emit("device_fallback", scope=view.component)
        return scan
    if view.metric not in MATMUL_METRICS:
        return scan
    # pallas tiling wants >= 8 query sublanes; tiny batches stay on the
    # lax.scan program (they're dispatch-latency-bound anyway)
    if view.slab < MIN_KERNEL_SLAB or b_padded < 8:
        return scan
    ncols = view.slab // gmin_scan.G
    # groups kept: >= k guarantees exact selection under exact arithmetic
    # (at most k groups hold the true top-k); 2k..128 adds slack for bf16
    # fast-scan ranking error
    rg = min(max(32, 2 * kk), 128, ncols)
    if rg < kk:
        return scan
    active_g = max(1, -(-view.fill // ncols))  # live store slices only
    # never hand Mosaic a kernel over its VMEM budget (it can wedge the
    # chip), nor the chip the slower of its two programs
    if not view.programs.kernel_serves(b_padded, view.dim, ncols, active_g,
                                       itemsize):
        return scan
    return gmin_scan.PROGRAM_GMIN, (rg, active_g)


def plan_search(view: PlanView, b: int, b_padded: int, k: int,
                allow_len: Optional[int] = None,
                refused: frozenset = frozenset()) -> SearchPlan:
    """The plan of one dispatch of `b` queries (`b_padded` after bucket
    padding) at depth `k` (the index's effective k: no deeper than its live
    rows) on the state `view` shows, under an allowList of `allow_len` ids
    (None: no filter, or an index without the gather tier: the mesh). The
    funnel's budgets and the kernel's broken flag are asked BEFORE the tier
    is named, so what is planned is what runs. `refused` names the kernels
    this dispatch found refused inside `guarded_kernel_call` (a Mosaic
    rejection at a first compile, a shape already marked broken):
    `DispatchHandle.refuse` plans again without them, and `dispatch_tier`
    names the gmin kernel to ask the tier alone, nothing counted."""
    kk = max(k, 1)
    common = dict(k_eff=k, dim=view.dim, batch=b, batch_padded=b_padded,
                  ndev=view.ndev, refused=refused)
    if allow_len is not None and allow_len < view.config.flat_search_cutoff:
        # flatSearch over the allowList: float32 rows gathered by slot
        return SearchPlan(costmodel.TIER_GATHER,
                          rows=min(allow_len, view.live),
                          bytes_per_row=view.dim * 4, **common)
    rows, extra, probe = view.n, None, None
    # the funnel's scan plane: one chip's slab, or the probed candidates
    funnel_k, funnel_rows = min(kk, view.live), view.slab
    if view.ivf_probe is not None:
        probe = view.ivf_probe(k)
    if probe is not None and not probed_reads_less(
            b_padded, view.ndev, probe[0], view.ivf_meta[1],
            view.ivf_meta[0], view.n, view.ivf_gathered):
        # a wide batch: the store read once for all of it is fewer bytes
        # than every query's own partitions
        probe = None
        common["ivf_declined"] = True
    if probe is not None:
        # partition-pruned (ROADMAP item 3): `rows` is what the device
        # actually reads: every query of the padded dispatch reads its OWN
        # top_p x cap_p candidates a chip, padding included, and the nlist
        # centroid rows are read once. So bytes, and every roofline derived
        # from them, never credit the rows the probe skipped, and a lane of
        # 16 riders is not charged as one query (`tier_rows` is fed from
        # this; `costmodel.DispatchShape.flops` counts a query's rows once)
        nlist, cap_p = view.ivf_meta
        funnel_rows = probe[0] * cap_p
        funnel_k = min(kk, funnel_rows)
        rows = b_padded * view.ndev * funnel_rows + nlist
        extra = {"ivf": True, "ivf_top_p": probe[0], "ivf_nlist": nlist,
                 "ivf_cap_p": cap_p,
                 # the same rows, against the live rows a flat scan of the
                 # dispatch would have to cover
                 "ivf_rows_read": rows,
                 "ivf_base_rows": view.live,
                 "ivf_padding_share": round(
                     1.0 - view.live / max(view.ndev * nlist * cap_p, 1), 4),
                 # ONE query's share of the rows
                 "probed_fraction": round(min(
                     (view.ndev * funnel_rows + nlist) / max(view.n, 1),
                     1.0), 4)}
        common["ivf"] = probe
    common.update(rows=rows, extra=extra)
    if not view.compressed:
        if probe is None:
            common.update(_program(view, b_padded, kk, view.itemsize,
                                   refused))
        return SearchPlan(costmodel.TIER_EXACT,
                          bytes_per_row=view.dim * view.itemsize, **common)
    if view.pq4_segments and KERNEL_FUNNEL not in refused:
        # the 4-bit funnel (pq.bits=4): stage 1 reads M/2 packed bytes a
        # scanned row; the re-ranking stages are attributed in extra (C
        # rows at M bytes, c rows at the rescore rows' bytes, a query).
        # Budgets that cannot cover k leave the dispatch to the 8-bit tiers
        rg4, rc = funnel_budgets(funnel_k, funnel_rows)
        if rc >= funnel_k and (probe is None
                               or min(rg4 * 16, funnel_rows) >= rc):
            if probe is None:
                common["extra"] = {
                    # a chip's budgets x ndev: whole-dispatch survivors
                    "funnel_c": rg4 * 16 * view.ndev,
                    "funnel_rescore": rc * view.ndev,
                    "funnel_stage2_bytes_per_row": view.pq_segments,
                    "funnel_stage3_bytes_per_row":
                        view.rescore_bytes_per_row}
            return SearchPlan(costmodel.TIER_PQ_ADC4, funnel=(rg4, rc),
                              bytes_per_row=view.pq4_segments // 2, **common)
    if not view.rescore:
        # codes-only: the uint8 codes (M = segments bytes a row); the fused
        # codes kernel or the reconstruction scan, the dispatcher's own gate
        # (`pq_gmin.eligible_rg`)
        return SearchPlan(costmodel.TIER_PQ_CODES,
                          bytes_per_row=view.pq_segments, **common)
    if probe is None and view.rescore_scan_itemsize:
        common.update(_program(view, b_padded, kk,
                               view.rescore_scan_itemsize, refused))
    return SearchPlan(costmodel.TIER_PQ_RESCORE,
                      bytes_per_row=view.rescore_bytes_per_row, **common)


def _program(view: PlanView, b_padded: int, kk: int, itemsize: int,
             refused: frozenset) -> dict:
    """A full-store scan's fields of its plan: the program that runs it
    and, where the index passes the scan step its depth, that depth (0: the
    HIGHEST-precision scan)."""
    from weaviate_tpu.ops.gmin_scan import PROGRAM_SCAN

    program, gmin = (PROGRAM_SCAN, None) if KERNEL_GMIN in refused \
        else _scan_program(view, b_padded, kk, itemsize)
    depth = None
    if program == PROGRAM_SCAN and view.depth_rows:
        depth = rescore_depth(view.config, view.metric, kk, view.depth_rows)
    return {"program": program, "gmin": gmin, "rescore_r": depth}


def fetch_stamped(fin, shape):
    """Run `fin`, one dispatch's blocking fetch and host half, and stamp
    what it took on `shape` (None while the tracer is down)."""
    if shape is None:
        return fin()
    if shape.fetches:
        # a RETRIED finalize (permitted — see DispatchHandle) re-runs the
        # fetch; the ledger invariant is per attempt, and the recorded
        # shape must describe the attempt whose results the caller
        # actually got — a leftover count would read as a spurious
        # double-fetch violation in /debug/perf
        shape.fetches = 0
    t0 = time.perf_counter()
    try:
        return fin()
    finally:  # also when the host half of finalize raised
        shape.t_end = shape.end_hop()
        shape.finalize_ms = (shape.t_end - t0) * 1000.0


class DispatchHandle:
    """What a dispatch returns: `handle()` is its finalize -> (ids, dists),
    the ONE blocking device->host fetch, outside any lock and on any
    thread. It carries what the dispatch learned: `plan`, `shape` (the
    costmodel.DispatchShape; None while the tracer is down; a group's
    dispatches are in `shapes`), `snapshot` (the one the dispatch read,
    set only while a quality auditor is configured, so that a sampled
    audit re-executes against the same index state) and `lock_wait_ms`
    (what the snapshot read waited on the write lock; 0.0 on the
    lock-free path). The shape is shared with the fetch, so a reader
    sees the device timings once the handle was called.

    It owns the finalize wrapper: the index's in-flight count, the fault
    point, the stamps (`fetch_stamped`) and the return of the staging
    buffer. Calling it again is permitted: after a failure it is a retried
    finalize (the count and the buffer are settled once), after a
    completed fetch it is the same answer."""

    __slots__ = ("plan", "shape", "shapes", "snapshot", "lock_wait_ms",
                 "_index", "_fault", "_fin", "_out", "_stage", "_done")

    def __init__(self, index=None, fault: Optional[str] = None,
                 plan: Optional[SearchPlan] = None, shape=None):
        self.plan, self.shape, self.shapes = plan, shape, ()
        self.snapshot, self.lock_wait_ms = None, 0.0
        self._index, self._fault = index, fault
        self._fin = self._out = self._stage = None
        self._done = True   # nothing in flight until `launched`

    @classmethod
    def ready(cls, result) -> "DispatchHandle":
        """A handle of no device work: `result` is the answer."""
        handle = cls()
        handle._out = result
        return handle

    def refuse(self, view: PlanView, kernel: str) -> SearchPlan:
        """The one correction a plan gets: `kernel` was refused inside
        `guarded_kernel_call`, which no plan can foresee, so the dispatch
        is planned again without it and its shape is made from that."""
        p = self.plan
        if p.ivf is None and view.ivf_probe is not None:
            # the probe planner said no to this dispatch: not asked again
            view = dataclasses.replace(view, ivf_probe=None)
        self.plan = plan_search(view, p.batch, p.batch_padded, p.k_eff,
                                refused=p.refused | {kernel})
        if self.shape is not None:
            self.shape = self.plan.shape(self.shape.t_start)
        return self.plan

    def launched(self, fin, stage=None) -> "DispatchHandle":
        """The device work is enqueued: `fin` fetches it. `stage` is the
        staging buffer the index takes back after a completed fetch."""
        self._fin, self._stage, self._done = fin, stage, False
        self._index._track_inflight(1)
        return self

    def __call__(self):
        if self._fin is None:
            return self._out
        fetched = False
        try:
            if self._fault is not None:
                faults.fire(self._fault)
            self._out = fetch_stamped(self._fin, self.shape)
            fetched = True
            # fetched: let go of the program's output NOW, as a finalize
            # closure that died with its call did. A caller holds the
            # handle for its facts through hydration, and a device buffer
            # freed that late is freed beside another thread's enqueue
            self._fin = None
            return self._out
        finally:
            if not self._done:  # idempotent: finalize may be retried
                self._done = True
                self._index._track_inflight(-1)
                if fetched and self._stage is not None:
                    # the staging buffer goes back to the pool ONLY
                    # after a completed fetch: by then the program has
                    # consumed its inputs (cpu-backend device_put may
                    # alias host memory). A pre-fetch failure strands
                    # the buffer for the GC instead — a recycled
                    # buffer could be overwritten under a still-
                    # enqueued program and corrupt a permitted retry
                    self._index._release_stage(self._stage)
