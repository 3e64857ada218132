"""The mesh-sharded TPU vector index ("hnsw_tpu_mesh").

The multi-chip twin of index/tpu.py: one logical shard's vectors are spread
over every chip of a jax.sharding.Mesh as per-chip HBM slabs, and every
operation is a whole-mesh SPMD program (kernels in
weaviate_tpu/parallel/mesh_search.py):

- insert: staged host-side, flushed as ONE sharded [n_dev, C, D] write —
  each chip lands its own chunk at its own offset (no per-shard dispatch
  loop);
- search: on every chip the one-chip scan step itself over its own slab
  (ops/scan.py scan_topk: one bf16 MXU pass, R candidates a query rescored
  in f32 from the chip's own rows; the HIGHEST-precision scan under
  exactTopK) + local top-k, cross-chip merge over
  ICI (all_gather + reselect) inside the same jit, then on-device slot→doc
  translation against the sharded pair table — the program returns
  the packed [B, 3k] buffer, so finalize is ONE fetch and dtype views
  (the single-chip one-fetch/zero-translation invariant, now across chips);
- delete: tombstone scatter where each chip claims the global rows in its
  slab;
- filters: the allowList becomes a packed uint32 bitmap sharded over the
  mesh, ANDed into the validity mask on device (helpers/allow_list.go
  semantics; no host-side row gathering);
- growth: geometric slab doubling fully on device (maintainance.go:31).

Reads are SNAPSHOT-ISOLATED with the same lock-free discipline as the
single-chip index (docs/concurrency.md, docs/mesh_serving.md): writers
publish an immutable MeshSnapshot with one atomic reference swap; readers
grab it without the index lock and run the whole two-phase dispatch
(enqueue on the snapshot, fetch outside any lock). Because the mesh write
kernels are NON-donating, a published snapshot pins the exact device slabs
it was built from — deletes, growth, compression, and compaction can never
tear an in-flight dispatch.

Durability reuses the single-chip index's VectorLog (add/delete records,
torn-tail-tolerant replay) — the log format is placement-independent, so a
shard can restart onto a different mesh size and the replay re-balances.

This replaces the reference's scatter-gather over goroutines+HTTP
(adapters/repos/db/index.go:967-1046) for the intra-node multi-chip case:
the collective rides ICI instead of the network.

PQ (compress.go parity, mesh-shaped): codes and ||recon||^2 shard like the
store; each chip runs the reconstruction-matmul scan over its own code
slab, rescores its local candidates against its local row slab at exact
f32, and the k best per chip merge over ICI. Compression downcasts an f32
store to bf16 (the memory move the single-chip index makes by dropping its
float cache); post-compress appends encode on write.

IVF (the partition-pruned tier, mesh-shaped): one k-means codebook is
trained over ALL chips' rows, then each chip gets its own KScaNN-style
balanced bucket table over its local slab (ops/ivf.py balanced_assign per
device, one shared capacity so the [n_dev, nlist, cap_p] table shards
cleanly). The probe runs per chip against replicated centroids; training
happens off-lock from a pinned snapshot with a write backlog, exactly like
the single-chip staged-clustering plane.
"""

from __future__ import annotations

import functools
import math
import os
import threading
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu import device
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index.interface import AllowList, VectorIndex
# the tier and the program of a dispatch are chosen in index/plan.py, once,
# for both indexes
from weaviate_tpu.index.plan import (KERNEL_GMIN, DispatchHandle, PlanView,
                                     plan_search)
from weaviate_tpu.index.tpu import (
    VectorLog,
    restore_record,
    _S2D_FILL,
    _bucket_b,
    _bucket_rows,
    _fetch_packed,
    ivf_probe,
    ivf_settings,
)
# the `enqueue` interval of a dispatch (and its shape, index/plan.py) exist
# ONLY while the tracer is up (tracing.get_tracer() gate — the
# zero-cost-when-disabled contract)
from weaviate_tpu.monitoring import tracing
# memory ledger (monitoring/memory.py): per-device slab components are
# stamped analytically at every buffer mutation; unconfigured => one
# comparison, nothing constructed
from weaviate_tpu.monitoring import memory
# shadow recall auditing (monitoring/quality.py): the dispatch's handle
# carries its snapshot ONLY while an auditor is configured, so the audit
# compares against the exact mesh state the live answer saw
from weaviate_tpu.monitoring import quality
from weaviate_tpu.monitoring.costmodel import (
    TIER_PQ_ADC4,
    TIER_PQ_CODES,
    TIER_PQ_RESCORE,
)
from weaviate_tpu.ops import ivf as ivf_ops
from weaviate_tpu.ops.scan import SCAN_CHUNK
from weaviate_tpu.ops.topk import unpack_fused
from weaviate_tpu.testing import faults, sanitizers
from weaviate_tpu.parallel.mesh_search import (
    make_mesh,
    mesh_delete_step,
    mesh_grow_1d,
    mesh_grow_2d,
    mesh_grow_pairs,
    mesh_insert_step,
    mesh_search_ivf_step,
    mesh_search_pq4_step,
    mesh_search_pq_step,
    mesh_search_step,
    mesh_write_pairs_step,
    mesh_write_rows_step,
    replicated,
    shard_spec,
)
from weaviate_tpu.compress.pq import pack_codes4 as pq_pack_codes4

_MIN_LOC = 1024       # minimum slab rows per chip (power of two, mult of 32)
_FLUSH_CHUNK = 8192   # staged rows that trigger a flush
_MAX_WRITE_C = 8192   # max rows per chip per insert step


def _pow2_at_least(n: int, floor: int) -> int:
    c = floor
    while c < n:
        c *= 2
    return c


@jax.jit
def _downcast_bf16(store):
    """One cached compilation for the compress-time store downcast; the
    output keeps the input's mesh sharding."""
    return store.astype(jnp.bfloat16)


class MeshSnapshot:
    """An immutable view of the mesh index state, published atomically.

    Same contract as the single-chip IndexSnapshot (index/tpu.py): the
    constructor copies REFERENCES under the write lock; correctness rests
    on every referenced buffer being effectively immutable once published —
    the mesh write kernels are non-donating (every flush/delete/grow binds
    NEW device arrays to the index fields, the snapshot keeps the old
    ones), ``host_tombs`` is copy-on-write (_mark_dead), ``slot_to_doc``
    is append-only within a device generation (rows past a snapshot's
    per-device counts are never read by it), and ``counts`` is copied
    outright because the index mutates it in place."""

    __slots__ = (
        "gen", "dim", "n_dev", "n_loc", "counts", "counts_dev", "n_total",
        "live", "store", "sq_norms", "tombs", "zero_words", "slot_to_doc",
        "slot_to_doc_dev", "host_tombs", "allow_token", "compressed", "pq",
        "codes", "recon_norms", "pq4", "codes4", "recon_norms4", "opq_rot",
        "host_vecs", "ivf_centroids", "ivf_buckets", "ivf_meta",
    )

    def __init__(self, gen: int, idx: "MeshVectorIndex"):
        self.gen = gen
        self.dim = idx.dim
        self.n_dev = idx.n_dev
        self.n_loc = idx.n_loc
        self.counts = idx._counts.copy()
        # replicated i32 per-shard high-water marks for the kernels (the
        # P() in_spec broadcasts a plain committed array)
        self.counts_dev = (
            jnp.asarray(self.counts.astype(np.int32))
            if idx.dim is not None else None
        )
        self.n_total = int(self.counts.sum())
        self.live = idx.live
        self.store = idx._store
        self.sq_norms = idx._sq_norms
        self.tombs = idx._tombs
        self.zero_words = idx._zero_words
        self.slot_to_doc = idx._slot_to_doc
        self.slot_to_doc_dev = idx._s2d_dev
        self.host_tombs = idx._host_tombs
        self.allow_token = idx._allow_token
        self.compressed = idx.compressed
        self.pq = idx._pq
        self.codes = idx._codes
        self.recon_norms = idx._recon_norms
        # the 4-bit ladder rung (COW like every other slab: writes bind
        # NEW sharded arrays, this snapshot keeps the ones it was born with)
        self.pq4 = idx._pq4
        self.codes4 = idx._codes4
        self.recon_norms4 = idx._recon_norms4
        self.opq_rot = idx._opq_rot_dev
        self.host_vecs = idx._host_vecs
        self.ivf_centroids = idx._ivf_centroids
        self.ivf_buckets = idx._ivf_buckets
        self.ivf_meta = idx._ivf_meta


class MeshVectorIndex(VectorIndex):
    # serving layers key off this: filtered lanes ride the coalesced
    # two-phase dispatch instead of falling back to the sync pool
    async_supports_filters = True

    _HOST_SCAN_CHUNK = 65536  # rows per host-fallback scan block

    def __init__(
        self,
        config: vi.HnswUserConfig,
        shard_path: str,
        shard_name: str = "",
        metrics=None,
        mesh=None,
        persist: bool = True,
        initial_capacity_per_shard: Optional[int] = None,
        dim_hint: Optional[int] = None,
        class_name: str = "",
    ):
        self.config = config
        self.metric = config.distance
        self.shard_path = shard_path
        self.shard_name = shard_name
        self.class_name = class_name
        self.metrics = metrics
        self.mesh = mesh if mesh is not None else make_mesh(
            getattr(config, "mesh_devices", 0) or None
        )
        self.n_dev = self.mesh.devices.size
        self.dtype = (
            jnp.bfloat16
            if getattr(config, "store_dtype", "float32") == "bfloat16"
            else jnp.float32
        )
        self._lock = sanitizers.register_lock(
            threading.RLock(), "index.mesh")
        self._init_loc = _pow2_at_least(
            initial_capacity_per_shard or _MIN_LOC, 32
        )
        self.dim: Optional[int] = None
        self.n_loc = 0               # slab rows per chip
        self.live = 0
        self._store = None           # sharded [n_dev * n_loc, D]
        self._sq_norms = None        # sharded [n_dev * n_loc] f32
        self._tombs = None           # sharded [n_dev * n_loc] bool
        self._zero_words = None      # sharded [n_dev * n_loc / 32] u32 (no-filter)
        self._counts = np.zeros(self.n_dev, dtype=np.int64)
        self._slot_to_doc = np.zeros(0, dtype=np.int64)  # global row -> doc
        self._s2d_dev = None         # sharded [cap, 2] u32 (id_lo, id_hi)
        self._host_tombs = np.zeros(0, dtype=bool)  # COW: snapshots pin copies
        self._doc_to_row: dict[int, int] = {}
        self._pending: dict[int, np.ndarray] = {}
        self._pending_tombs: list[int] = []
        # snapshot plane (docs/mesh_serving.md): readers are lock-free on
        # the published MeshSnapshot; staged/published generations drive
        # the republish-on-read slow path
        self._snap: Optional[MeshSnapshot] = None
        self._snap_gen = 0
        self._staged_gen = 0
        self._published_gen = -1  # != staged: the first read publishes
        self._staged_t0: Optional[float] = None
        self._inflight = 0
        self._inflight_lock = sanitizers.register_lock(
            threading.Lock(), "index.mesh.inflight")
        self._inflight_gauge = None
        self._host_rows_cache = None  # (gen, rows, sq) breaker-path cache
        # device generation: compact/drop re-create the slabs; an off-lock
        # IVF trainer must abandon results targeted at a dead epoch
        self._device_epoch = 0
        # IVF plane (mesh twin of the single-chip staged clustering):
        # stats lock is leaf-level, ordered after index.mesh
        self._ivf_lock = sanitizers.register_lock(
            threading.Lock(), "index.mesh.ivf")
        self._ivf_stats = {"dispatches": 0, "probed_rows": 0, "base_rows": 0}
        self._ivf_centroids_host = None   # np [nlist, D] f32
        self._ivf_centroids = None        # replicated device copy
        self._ivf_buckets = None          # sharded [n_dev, nlist, cap_p] i32
        self._ivf_assign = np.zeros(0, dtype=np.int32)  # per-row partition
        self._ivf_fills = None            # np [n_dev, nlist] bucket fills
        self._ivf_cap_p = 0
        self._ivf_meta = None             # (nlist, cap_p, gen)
        self._ivf_dirty = False
        self._ivf_trained_n = 0
        self._ivf_gen = 0
        self._ivf_backlog = None          # rows written during off-lock training
        # PQ state (mesh twin of index/tpu.py compression): codes and
        # ||recon||^2 are sharded like the store; the (possibly bf16)
        # store itself stays resident as the per-chip rescore source
        self.compressed = False
        self._pq = None
        self._codes = None          # sharded [n_dev * n_loc, M]
        self._recon_norms = None    # sharded [n_dev * n_loc] f32
        self._pq4 = None            # the 4-bit rung's quantizer (16 cents)
        self._codes4 = None         # sharded [n_dev * n_loc, M/2] uint8
        self._recon_norms4 = None   # sharded [n_dev * n_loc] f32
        self._opq_rot_dev = None    # replicated [D, D] f32 (shared OPQ)
        self._host_vecs = None      # np [cap, D] f32 (compressed mode only)
        self._pq_path = os.path.join(shard_path, "pq.npz") if shard_path else ""
        self._pq4_path = (os.path.join(shard_path, "pq4.npz")
                          if shard_path else "")
        self._restoring = False
        # the running restore's stage sums and what the last one did: the
        # single-chip index's two fields (tpu.py _restore)
        self._restore_sums: Optional[tracing.StageSums] = None
        self.last_restore: Optional[dict] = None
        self._gmin_broken = False  # fused mesh kernel failed: use the scan
        # identity token for the per-allowList packed-words cache
        self._allow_token = object()
        # separate failure domain + codebook cache for the PQ codes kernel
        from weaviate_tpu.ops.gmin_scan import KernelState, ProgramCounts

        self._pqg_state = KernelState()
        self._pqg_cb = None
        self._gmin_validated: set = set()     # shapes that served correctly
        self._gmin_shape_broken: set = set()  # shapes Mosaic rejected
        # the exact tier's full-store dispatches by the program that ran
        # them (tpu.py's `scan_programs`)
        self.scan_programs = ProgramCounts()
        # host-memory provider (monitoring/memory.py): slot map, PQ host
        # rows, and staged rows become /debug/memory host components
        memory.register_host_provider(self, memory.index_host_components)
        self._log = (
            VectorLog(os.path.join(shard_path, "vector.log")) if persist else None
        )
        if dim_hint is not None:
            self._init_device(int(dim_hint))
        if self._log is not None:
            self._restore()

    # -- lifecycle -----------------------------------------------------------

    def _restore(self) -> None:
        """Replay the vector log (startup.go:56 analog). Placement is
        recomputed at replay time, so the same log restores onto any mesh."""
        self._restoring = True
        replay_stats: dict = {}
        with tracing.stage("vector.restore", shard=self.shard_name) as st:
            sums = self._restore_sums = tracing.StageSums()
            try:
                sums.enter("stage")
                for op, ids, vecs in sums.timed(VectorLog.replay_batches(
                        self._log.path, stats=replay_stats, sums=sums),
                        "log.parse"):
                    if op == "add":
                        self._bulk_stage_add(ids, vecs)
                    else:
                        self._stage_delete(int(ids), log=False)
                sums.leave(self._capacity())
                VectorLog.report_replay_stats(self._log.path, replay_stats)
                if self._pq_path and os.path.exists(self._pq_path):
                    from weaviate_tpu.compress.pq import ProductQuantizer

                    with tracing.piece_of(sums, "flush", self._capacity()):
                        self._flush_pending()
                        if self.live > 0:
                            self._enable_pq(
                                ProductQuantizer.load(self._pq_path),
                                np.asarray(self._store, dtype=np.float32),
                                save=False,
                            )
                with tracing.piece_of(sums, "drain", self._capacity()):
                    jax.block_until_ready([a for a in (  # graftlint: disable=JGL001 a restore runs in the constructor, before the index serves: the wait is the `drain` stage (tpu.py _restore)
                        self._store, self._sq_norms, self._tombs,
                        self._s2d_dev, self._codes, self._recon_norms,
                        self._codes4, self._recon_norms4) if a is not None])
            finally:
                self._restoring = False
                self._restore_sums = None
            sums.publish()
            st.note(rows=self.live, capacity=self._capacity())
        self.last_restore = restore_record(
            "compressed" if self.compressed else "uncompressed",
            int(self._counts.sum()), st, sums, replay_stats)

    def _capacity(self) -> int:
        """Slots over all chips (0 before the first row sizes the slabs)."""
        return self.n_dev * self.n_loc if self.dim is not None else 0

    def post_startup(self) -> None:
        self.flush()

    # -- memory ledger stamping (monitoring/memory.py) -----------------------

    def _memory_components(self) -> dict:
        """Analytic byte sizes of the mesh slab buffers (global totals of
        the sharded arrays; the ledger divides by ``ndev`` for per-chip
        headroom). Zero syncs; equals the arrays' ``nbytes`` exactly."""
        comps: dict = {}
        for name, arr in (("store", self._store),
                          ("sq_norms", self._sq_norms),
                          ("tombs", self._tombs),
                          ("slot_to_doc", self._s2d_dev),
                          ("pq_codes", self._codes),
                          ("recon_norms", self._recon_norms),
                          ("pq4_codes", self._codes4),
                          ("pq4_norms", self._recon_norms4),
                          ("opq_rot", self._opq_rot_dev),
                          ("ivf_centroids", self._ivf_centroids),
                          ("ivf_buckets", self._ivf_buckets),
                          ("allow_words", self._zero_words)):
            b = memory.array_bytes(arr)
            if b:
                comps[name] = b
        return comps

    def _stamp_memory(self) -> None:
        """The JGL012-registered stamping hook: every method that binds a
        device buffer to a slab field flows through here."""
        led = memory.get_ledger()
        if led is not None:
            led.stamp_device(self, self._memory_components(),
                             ndev=self.n_dev)

    # -- device plumbing -----------------------------------------------------

    def _init_device(self, dim: int) -> None:
        self.dim = dim
        self.n_loc = self._init_loc
        cap = self.n_dev * self.n_loc
        sh2 = shard_spec(self.mesh, None)
        sh1 = shard_spec(self.mesh)
        self._store = jax.device_put(jnp.zeros((cap, dim), self.dtype), sh2)
        self._sq_norms = jax.device_put(jnp.zeros((cap,), jnp.float32), sh1)
        self._tombs = jax.device_put(jnp.zeros((cap,), jnp.bool_), sh1)
        self._zero_words = jax.device_put(jnp.zeros((cap // 32,), jnp.uint32), sh1)
        self._s2d_dev = jax.device_put(
            jnp.full((cap, 2), _S2D_FILL, jnp.uint32), sh2)
        self._slot_to_doc = np.full(cap, -1, dtype=np.int64)
        self._host_tombs = np.zeros(cap, dtype=bool)
        self._ivf_assign = np.full(cap, -1, dtype=np.int32)
        self._device_epoch += 1
        if self._ivf_centroids_host is not None:
            self._ivf_dirty = True
        if self.compressed and self._pq is not None:
            # a device reset in compressed mode (compact) re-creates the
            # code slabs too; _write_balanced re-encodes rows as they land
            self._codes = jax.device_put(
                jnp.zeros((cap, self._pq.segments), self._pq.code_dtype), sh2)
            self._recon_norms = jax.device_put(jnp.zeros((cap,), jnp.float32), sh1)
            if self._pq4 is not None:
                self._codes4 = jax.device_put(
                    jnp.zeros((cap, self._pq4.segments // 2), jnp.uint8), sh2)
                self._recon_norms4 = jax.device_put(
                    jnp.zeros((cap,), jnp.float32), sh1)
            self._host_vecs = np.zeros((cap, dim), np.float32)
        self._stamp_memory()

    def _grow(self, needed_per_shard: int) -> None:
        new_loc = self.n_loc
        while new_loc < needed_per_shard:
            new_loc *= 2
        if new_loc == self.n_loc:
            return
        sums = self._restore_sums
        if sums is not None:
            sums.enter("grow", capacity=self.n_dev * new_loc)
        old_loc = self.n_loc
        self._store = mesh_grow_2d(self._store, new_loc, self.mesh)
        self._sq_norms = mesh_grow_1d(self._sq_norms, new_loc, self.mesh)
        self._tombs = mesh_grow_1d(self._tombs, new_loc, self.mesh)
        self._s2d_dev = mesh_grow_pairs(
            self._s2d_dev, new_loc, _S2D_FILL, self.mesh)
        if self.compressed:
            self._codes = mesh_grow_2d(self._codes, new_loc, self.mesh)
            self._recon_norms = mesh_grow_1d(self._recon_norms, new_loc, self.mesh)
            if self._codes4 is not None:
                self._codes4 = mesh_grow_2d(self._codes4, new_loc, self.mesh)
                self._recon_norms4 = mesh_grow_1d(
                    self._recon_norms4, new_loc, self.mesh)
            hv = np.zeros((self.n_dev * new_loc, self.dim), np.float32)
            for s in range(self.n_dev):
                hv[s * new_loc : s * new_loc + old_loc] = self._host_vecs[
                    s * old_loc : (s + 1) * old_loc
                ]
            self._host_vecs = hv
        cap = self.n_dev * new_loc
        self._zero_words = jax.device_put(
            jnp.zeros((cap // 32,), jnp.uint32), shard_spec(self.mesh)
        )
        # remap global rows: slab-local offsets are preserved. Fresh host
        # arrays every grow — published snapshots keep the old ones.
        s2d = np.full(cap, -1, dtype=np.int64)
        ht = np.zeros(cap, dtype=bool)
        ia = np.full(cap, -1, dtype=np.int32)
        for s in range(self.n_dev):
            c = int(self._counts[s])
            s2d[s * new_loc : s * new_loc + c] = self._slot_to_doc[
                s * old_loc : s * old_loc + c
            ]
            ht[s * new_loc : s * new_loc + old_loc] = self._host_tombs[
                s * old_loc : (s + 1) * old_loc
            ]
            ia[s * new_loc : s * new_loc + old_loc] = self._ivf_assign[
                s * old_loc : (s + 1) * old_loc
            ]
        self._slot_to_doc = s2d
        self._host_tombs = ht
        self._ivf_assign = ia
        occ = np.nonzero((s2d >= 0) & ~ht)[0]
        self._doc_to_row = dict(zip(s2d[occ].tolist(), occ.tolist()))
        # staged-but-unflushed tombstone rows move with their slab
        self._pending_tombs = [
            (r // old_loc) * new_loc + (r % old_loc) for r in self._pending_tombs
        ]
        if self._ivf_backlog is not None:
            self._ivf_backlog = [
                ((g // old_loc) * new_loc + (g % old_loc), r)
                for g, r in self._ivf_backlog
            ]
        self.n_loc = new_loc
        led = memory.get_ledger()
        if led is not None:
            led.note_write_shape(
                ("mesh_grow", self.n_dev, new_loc, self.dim or 0,
                 self.compressed))
        self._stamp_memory()
        if sums is not None:
            sums.leave(self.n_dev * new_loc)

    # -- staging -------------------------------------------------------------

    def _mark_dead(self, row: int) -> None:
        """Tombstone `row` in the host mask, copy-on-write: a published
        snapshot referencing the current mask keeps its version — torn
        reads of a half-updated liveness mask are impossible."""
        snap = self._snap
        if snap is not None and snap.host_tombs is self._host_tombs:
            self._host_tombs = self._host_tombs.copy()
        self._host_tombs[row] = True

    def _stage_add(self, doc_id: int, vector: np.ndarray, log: bool = True) -> None:
        vector = np.asarray(vector, dtype=np.float32)
        if self.metric == vi.DISTANCE_COSINE:
            nrm = float(np.linalg.norm(vector))
            if nrm > 0:
                vector = vector / nrm
        if self.dim is None:
            self._init_device(int(vector.shape[0]))
        elif vector.shape[0] != self.dim:
            raise ValueError(f"dim mismatch: index has {self.dim}, got {vector.shape[0]}")
        old = self._doc_to_row.pop(doc_id, None)
        if old is not None:
            self._pending_tombs.append(old)
            self._mark_dead(old)  # dead row must not resurrect via _grow
            self.live -= 1
        if doc_id in self._pending:
            self.live -= 1
        self._pending[doc_id] = vector
        self.live += 1
        self._staged_gen += 1
        self._mark_staged()
        if log and self._log is not None:
            self._log.append_add(doc_id, vector)
        if len(self._pending) >= _FLUSH_CHUNK:
            with tracing.piece_of(self._restore_sums, "flush",
                                  self._capacity()):
                self._flush_pending()

    def _bulk_stage_add(self, ids: np.ndarray, vecs: np.ndarray) -> None:
        """Restore-path bulk staging (single-chip twin in tpu.py): a run of
        add records feeds the staging buffer in one dict update with
        _stage_add's exact semantics; small/fragmented runs and docs the
        index already knows take the per-record path."""
        if len(ids) < 256:
            for d, v in zip(ids.tolist(), vecs):
                self._stage_add(int(d), v, log=False)
            return
        if self.dim is None:
            self._init_device(int(np.asarray(vecs).shape[1]))
        elif np.asarray(vecs).shape[1] != self.dim:
            raise ValueError(
                f"dim mismatch: index has {self.dim}, got {np.asarray(vecs).shape[1]}")
        from weaviate_tpu.index.tpu import _prep_bulk_run

        d2r = self._doc_to_row
        ids64, vecs, known = _prep_bulk_run(
            ids, vecs, self.metric,
            lambda d: d in d2r or d in self._pending)
        if known:
            for i in known:
                self._stage_add(int(ids64[i]), vecs[i], log=False)
            keep = np.ones(len(ids64), bool)
            keep[known] = False
            ids64, vecs = ids64[keep], vecs[keep]
            if len(ids64) == 0:
                return
        self._staged_gen += 1
        self._mark_staged()
        self.live += len(ids64)
        if len(ids64) < _FLUSH_CHUNK:
            self._pending.update(zip(ids64.tolist(), vecs))
            if len(self._pending) >= _FLUSH_CHUNK:
                with tracing.piece_of(self._restore_sums, "flush",
                                      self._capacity()):
                    self._flush_pending()
            return
        # a long run (a whole import's log is one) lands as it is: the
        # slabs are sized once from its row count and the rows go down in
        # insert steps, never through a dict entry a row and one stacked
        # copy of the log (single-chip twin: tpu.py _write_block)
        with tracing.piece_of(self._restore_sums, "flush", self._capacity()):
            self._flush_pending()  # earlier staged singles keep their slots
        with tracing.piece_of(self._restore_sums, "land", self._capacity(),
                              rows=len(ids64)):
            self._write_balanced(ids64, vecs)

    def _stage_delete(self, doc_id: int, log: bool = True) -> None:
        row = self._doc_to_row.pop(doc_id, None)
        if row is None:
            if doc_id in self._pending:
                del self._pending[doc_id]
                self.live -= 1
                self._staged_gen += 1
                self._mark_staged()
                if log and self._log is not None:
                    self._log.append_delete(doc_id)
            return
        self._pending_tombs.append(row)
        self._mark_dead(row)  # dead row must not resurrect via _grow
        self.live -= 1
        self._staged_gen += 1
        self._mark_staged()
        if log and self._log is not None:
            self._log.append_delete(doc_id)

    def _assign_balanced(self, count: int) -> list[np.ndarray]:
        """Split `count` new rows over shards so slab fills equalize
        (the chip-level analog of the virtual-shard ring's even spread,
        usecases/sharding/state.go:261)."""
        counts = self._counts.copy()
        takes = np.zeros(self.n_dev, dtype=np.int64)
        remaining = count
        # level-fill: repeatedly top up the emptiest shards
        while remaining > 0:
            order = np.argsort(counts + takes)
            lo = order[0]
            if self.n_dev > 1:
                second = counts[order[1]] + takes[order[1]]
                gap = int(second - (counts[lo] + takes[lo]))
                step = max(1, min(remaining, gap if gap > 0 else remaining // self.n_dev + 1))
            else:
                step = remaining
            takes[lo] += step
            remaining -= step
        out, off = [], 0
        for s in range(self.n_dev):
            out.append(np.arange(off, off + int(takes[s])))
            off += int(takes[s])
        return out

    def _flush_pending(self) -> None:
        """Land staged adds/tombstones on device. PURE staging drain — no
        compression, no IVF training — so the read path's republish
        (_read_snapshot slow path) can call it without ever reaching a
        stop-the-world maintenance fetch."""
        led = memory.get_ledger()
        if self._pending:
            t0 = time.perf_counter()
            rows = np.stack(list(self._pending.values()))
            docs = np.array(list(self._pending.keys()), dtype=np.int64)
            with tracing.piece_of(self._restore_sums, "land",
                                  self._capacity(), rows=len(docs)):
                self._write_balanced(docs, rows)
            self._pending.clear()
            if led is not None:
                led.note_write(
                    "add", "flush", (time.perf_counter() - t0) * 1000.0,
                    rows=rows.shape[0],
                    bytes_moved=rows.shape[0] * (self.dim or 0) * 4)
        if self._pending_tombs:
            t0 = time.perf_counter()
            idx = np.array(self._pending_tombs, dtype=np.int32)
            pad = _bucket_rows(len(idx))
            padded = np.full(pad, -1, dtype=np.int32)
            padded[: len(idx)] = idx
            self._tombs = mesh_delete_step(self._tombs, jnp.asarray(padded), self.mesh)
            if led is not None:
                led.note_write(
                    "delete", "apply_tombstones",
                    (time.perf_counter() - t0) * 1000.0,
                    rows=len(self._pending_tombs))
            self._pending_tombs.clear()
            self._stamp_memory()

    def _maybe_autocompress(self) -> None:
        """Declarative pq.enabled compresses once enough data exists to fit
        codebooks (same trigger as the single-chip index). Reached only
        from flush()/compress()/update_user_config — never from the
        staging threshold sites."""
        if not (
            self.config.pq.enabled
            and not self.compressed
            and not self._restoring
            and self.live >= max(256, self.config.pq.centroids)
        ):
            return
        try:
            self._compress_locked()
        except vi.ConfigValidationError as e:
            # a pq config that only turns out invalid once dims are
            # known (declared before the first import) must not turn
            # every later add/search into an error: auto-disable with a
            # warning and keep serving uncompressed
            import logging

            self.config.pq.enabled = False
            logging.getLogger(__name__).warning(
                "declared pq config is invalid (%s); auto-disabling "
                "compression for this index", e)

    def _write_balanced(self, docs: np.ndarray, rows: np.ndarray) -> None:
        """Land [count, D] rows across slabs in whole-mesh insert steps.
        Shard s takes the contiguous run `assign[s]` of `rows`, a step's
        worth at a time."""
        assign = self._assign_balanced(rows.shape[0])
        needed = max(
            int(self._counts[s]) + len(assign[s]) for s in range(self.n_dev)
        )
        self._grow(needed)
        first = [int(a[0]) if len(a) else 0 for a in assign]
        left = [len(a) for a in assign]
        sums = self._restore_sums
        while any(left):
            if sums is not None:
                sums.tick(self.n_dev * self.n_loc)
            max_off = max(
                int(self._counts[s]) for s in range(self.n_dev) if left[s]
            )
            c = min(_bucket_rows(max(left)), _MAX_WRITE_C, self.n_loc - max_off)
            c = max(c, 1)
            chunks = np.zeros((self.n_dev, c, self.dim), np.float32)
            pairs = np.zeros((self.n_dev, c, 2), np.uint32)
            offsets = self._counts.astype(np.int32)
            takes = np.zeros(self.n_dev, dtype=np.int32)
            taken: list[slice] = []
            for s in range(self.n_dev):
                take = min(c, left[s])
                sel = slice(first[s], first[s] + take)
                first[s] += take
                left[s] -= take
                if take:
                    chunks[s, :take] = rows[sel]
                    du = docs[sel].view(np.uint64)
                    pairs[s, :take, 0] = (du & np.uint64(0xFFFFFFFF)).astype(
                        np.uint32)
                    pairs[s, :take, 1] = (du >> np.uint64(32)).astype(np.uint32)
                takes[s] = take
                taken.append(sel)
            chunks_dev = jax.device_put(
                chunks, shard_spec(self.mesh, None, None)
            )
            self._store, self._sq_norms = mesh_insert_step(
                self._store,
                self._sq_norms,
                chunks_dev,
                jnp.asarray(offsets),
                jnp.asarray(takes),
                self.metric == vi.DISTANCE_L2,
                self.mesh,
            )
            # the device translation table lands the same rows, so the fused
            # dispatch's on-device slot->doc stays in lockstep with the host map
            self._s2d_dev = mesh_write_pairs_step(
                self._s2d_dev,
                jax.device_put(pairs, shard_spec(self.mesh, None, None)),
                jnp.asarray(offsets),
                jnp.asarray(takes),
                self.mesh,
            )
            if self.compressed:
                # post-compress appends also land codes + recon norms (the
                # single-chip index's encode-on-write parity)
                code_chunks = self._pq.encode(
                    chunks.reshape(-1, self.dim)
                ).reshape(self.n_dev, c, self._pq.segments)
                norm_chunks = self._pq.recon_sq_norms(
                    code_chunks.reshape(-1, self._pq.segments)
                ).reshape(self.n_dev, c).astype(np.float32)
                self._codes, self._recon_norms = mesh_write_rows_step(
                    self._codes,
                    self._recon_norms,
                    jax.device_put(jnp.asarray(code_chunks),
                                   shard_spec(self.mesh, None, None)),
                    jax.device_put(jnp.asarray(norm_chunks),
                                   shard_spec(self.mesh, None)),
                    jnp.asarray(offsets),
                    jnp.asarray(takes),
                    self.mesh,
                )
                if self._pq4 is not None:
                    # encode-on-write parity for the 4-bit rung: the same
                    # rows land packed two-codes-per-byte
                    c4 = self._pq4.encode(chunks.reshape(-1, self.dim))
                    p4 = pq_pack_codes4(c4).reshape(
                        self.n_dev, c, self._pq4.segments // 2)
                    n4 = self._pq4.recon_sq_norms(c4).reshape(
                        self.n_dev, c).astype(np.float32)
                    self._codes4, self._recon_norms4 = mesh_write_rows_step(
                        self._codes4,
                        self._recon_norms4,
                        jax.device_put(jnp.asarray(p4),
                                       shard_spec(self.mesh, None, None)),
                        jax.device_put(jnp.asarray(n4),
                                       shard_spec(self.mesh, None)),
                        jnp.asarray(offsets),
                        jnp.asarray(takes),
                        self.mesh,
                    )
            for s in range(self.n_dev):
                take = int(takes[s])
                if not take:
                    continue
                base = s * self.n_loc + int(self._counts[s])
                grows = np.arange(base, base + take)
                d = docs[taken[s]]
                self._slot_to_doc[grows] = d
                self._doc_to_row.update(zip(d.tolist(), grows.tolist()))
                if self.compressed:
                    self._host_vecs[grows] = rows[taken[s]]
                if self._ivf_backlog is not None:
                    # an off-lock k-means fit is in flight: queue the rows,
                    # the trainer (or its finally block) assigns them
                    self._ivf_backlog.append((grows, rows[taken[s]]))
                elif self._ivf_centroids_host is not None:
                    self._ivf_assign[grows] = ivf_ops.assign_partitions(
                        rows[taken[s]], self._ivf_centroids_host)
                    self._ivf_dirty = True
                self._counts[s] += take
        self._stamp_memory()

    # -- product quantization (mesh twin of index/tpu.py compression) --------

    def compress(self) -> None:
        with self._lock:
            self._flush_pending()
            self._compress_locked()

    def _compress_locked(self) -> None:
        from weaviate_tpu.compress.pq import ProductQuantizer

        if self.compressed:
            return
        if self.metric not in (vi.DISTANCE_L2, vi.DISTANCE_DOT, vi.DISTANCE_COSINE):
            # the mesh PQ kernel is the reconstruction matmul; the LUT path
            # the single-chip index keeps for manhattan/hamming has no mesh
            # twin, and silently-wrong distances are worse than an error
            raise vi.ConfigValidationError(
                f"pq on hnsw_tpu_mesh supports l2-squared/dot/cosine, "
                f"not {self.metric}")
        if self.live == 0:
            raise RuntimeError("compress requires imported vectors to fit on")
        host = np.asarray(self._store, dtype=np.float32)  # [cap, D] gather
        occupied = (self._slot_to_doc >= 0) & ~self._host_tombs
        pq = ProductQuantizer(
            dim=self.dim,
            segments=self.config.pq.segments,
            centroids=self.config.pq.centroids,
            metric=self.metric,
            encoder=self.config.pq.encoder.type,
            distribution=self.config.pq.encoder.distribution,
            rotation=self.config.pq.rotation,
        )
        pq.fit(host[occupied])
        self._enable_pq(pq, host, save=True)

    def _obtain_pq4(self, pq, vecs_n: np.ndarray):
        """The 4-bit rung's quantizer: prefer the persisted pq4.npz during
        restore (deterministic across restarts, skips the kmeans fit); any
        rejected/unreadable file only costs a refit with the pinned
        rotation, never the shard (the pq.npz rejection idiom)."""
        from weaviate_tpu.compress.pq import ProductQuantizer

        if self._restoring and self._pq4_path and os.path.exists(self._pq4_path):
            try:
                pq4 = ProductQuantizer.load(self._pq4_path)
                if pq4.segments == pq.segments and pq4.centroids == 16:
                    return pq4
                import logging

                logging.getLogger(__name__).warning(
                    "persisted pq4.npz does not match the pq config "
                    "(segments %d vs %d, centroids %d); refitting",
                    pq4.segments, pq.segments, pq4.centroids)
            except Exception as e:  # noqa: BLE001 — refit beats a dead shard
                import logging

                logging.getLogger(__name__).warning(
                    "could not load persisted pq4.npz (%s); refitting", e)
        pq4 = ProductQuantizer(
            dim=self.dim,
            segments=pq.segments,
            centroids=16,
            metric=self.metric,
            encoder=vi.PQ_ENCODER_KMEANS,
            distribution=self.config.pq.encoder.distribution,
            rotation=vi.PQ_ROTATION_NONE,
        )
        pq4.fit(vecs_n, rotation_matrix=pq.rotation_matrix)
        return pq4

    def _enable_pq(self, pq, host: np.ndarray, save: bool) -> None:
        """Shard codes + ||recon||^2 over the mesh. Dead/padding rows encode
        garbage but are masked by tombs/high-water in the kernel. The store
        itself stays resident as the per-chip rescore source, downcast to
        bf16 when it was f32 (the single-chip index's drop-the-float-cache
        memory move, mesh-shaped); the full-precision rows move to host RAM
        so compact()'s log rewrite never re-persists bf16-rounded data
        (tpu.py _host_vecs parity)."""
        t0 = time.perf_counter()
        codes = pq.encode(host)                       # [cap, M]
        norms = pq.recon_sq_norms(codes).astype(np.float32)
        self._pq = pq
        self._codes = jax.device_put(jnp.asarray(codes), shard_spec(self.mesh, None))
        self._recon_norms = jax.device_put(jnp.asarray(norms), shard_spec(self.mesh))
        if int(getattr(self.config.pq, "bits", 8)) == 4:
            # the 4-bit rung: a second 16-centroid quantizer fit in the
            # SAME rotated space (the 8-bit fit's OPQ matrix is pinned, so
            # Procrustes runs once and both ladders rank identically under
            # rotation) — per-chip funnel scans its packed slab at M/2
            # bytes/row, stage 2 re-ranks against these very 8-bit codes
            occupied = (self._slot_to_doc >= 0) & ~self._host_tombs
            pq4 = self._obtain_pq4(pq, host[occupied])
            codes4 = pq4.encode(host)
            packed4 = pq_pack_codes4(codes4)
            norms4 = pq4.recon_sq_norms(codes4).astype(np.float32)
            self._pq4 = pq4
            self._codes4 = jax.device_put(
                jnp.asarray(packed4), shard_spec(self.mesh, None))
            self._recon_norms4 = jax.device_put(
                jnp.asarray(norms4), shard_spec(self.mesh))
            self._opq_rot_dev = (
                jax.device_put(jnp.asarray(pq4.rotation_matrix, jnp.float32),
                               replicated(self.mesh))
                if pq4.rotation_matrix is not None else None)
        else:
            self._pq4 = None
            self._codes4 = None
            self._recon_norms4 = None
            self._opq_rot_dev = None
        self._host_vecs = np.array(host, dtype=np.float32)
        if self.dtype == jnp.float32:
            self.dtype = jnp.bfloat16
            # module-level jitted downcast (sharding propagates from the
            # input); re-jitting a lambda here would compile per call
            self._store = jax.device_put(
                _downcast_bf16(self._store), shard_spec(self.mesh, None))
        self.compressed = True
        # compressed mode has no IVF tier (parity with the PQ tiers owning
        # the scan); drop any clustering so snapshots don't carry it
        self._ivf_reset()
        self._staged_gen += 1
        self._mark_staged()
        if save and self._pq_path:
            pq.save(self._pq_path)
        if save and self._pq4_path and self._pq4 is not None:
            self._pq4.save(self._pq4_path)
        led = memory.get_ledger()
        if led is not None:
            led.note_write(
                "compress", "compress", (time.perf_counter() - t0) * 1000.0,
                rows=self.live, bytes_moved=memory.array_bytes(self._codes))
        self._stamp_memory()

    # -- VectorIndex ---------------------------------------------------------

    def add(self, doc_id: int, vector: np.ndarray) -> None:
        with self._lock:
            self._stage_add(int(doc_id), vector)

    def add_batch(self, doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        """Bulk import: fresh unique doc_ids take the fully-vectorized
        balanced-write path; collisions fall back to per-row staging."""
        doc_arr = np.asarray(doc_ids, dtype=np.int64)
        vectors = np.asarray(vectors, dtype=np.float32)
        with self._lock:
            collides = any(int(d) in self._doc_to_row for d in doc_arr) or bool(
                self._pending
            )
            fresh = (
                not collides
                and vectors.ndim == 2
                and np.unique(doc_arr).size == doc_arr.size
            )
            if not fresh:
                for d, v in zip(doc_arr, vectors):
                    self._stage_add(int(d), v)
                return
            if self.metric == vi.DISTANCE_COSINE:
                norms = np.linalg.norm(vectors, axis=1, keepdims=True)
                norms[norms == 0] = 1.0
                vectors = vectors / norms
            if self.dim is None:
                self._init_device(int(vectors.shape[1]))
            elif vectors.shape[1] != self.dim:
                raise ValueError(
                    f"dim mismatch: index has {self.dim}, got {vectors.shape[1]}"
                )
            if self._log is not None and not self._restoring:
                self._log.append_add_batch(doc_arr, vectors)
            self._write_balanced(doc_arr, vectors)
            self.live += doc_arr.size
            self._staged_gen += 1
            self._mark_staged()

    def delete(self, *doc_ids: int) -> None:
        with self._lock:
            for d in doc_ids:
                self._stage_delete(int(d))

    def contains(self, doc_id: int) -> bool:
        with self._lock:
            return doc_id in self._doc_to_row or doc_id in self._pending

    def __len__(self) -> int:
        return self.live

    def distancer_name(self) -> str:
        return self.metric

    def _prep_queries(self, vectors: np.ndarray) -> tuple[np.ndarray, int]:
        q = np.asarray(vectors, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        b = q.shape[0]
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(q, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            q = q / norms
        bb = _bucket_b(b)
        if bb != b:
            q = np.concatenate([q, np.zeros((bb - b, q.shape[1]), np.float32)])
        return q, b

    def padded_width(self, b: int) -> int:
        """The query-batch bucket `b` pads to — the coalescer packs lanes
        up to this width for free (same contract as the single-chip twin)."""
        return _bucket_b(max(int(b), 1))

    def _allow_words(self, snap: MeshSnapshot, allow_list: AllowList) -> jax.Array:
        """Sharded packed filter words for `snap`, cached ON the (immutable)
        allowList per index state — same contract as the single-chip twin
        (index/tpu.py _allow_words). Keyed on (allow_token, n_total, cap):
        deletions alone don't rotate the key, but a stale mask only
        re-admits tombstoned rows the device tomb mask kills anyway."""
        from weaviate_tpu.storage.bitmap import (
            Bitmap, allowed_mask, pack_allow_words)

        cap = snap.n_dev * snap.n_loc
        key = (snap.allow_token, snap.n_total, cap)
        cached = getattr(allow_list, "_words_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        mask = np.zeros(cap, dtype=bool)
        occupied = (snap.slot_to_doc >= 0) & ~snap.host_tombs
        if occupied.any():
            docs = snap.slot_to_doc[occupied]
            if isinstance(allow_list, Bitmap):
                mask[occupied] = allowed_mask(allow_list, docs)
            else:
                mask[occupied] = allow_list.contains_array(docs.astype(np.uint64))
        out = jax.device_put(
            jnp.asarray(pack_allow_words(mask, cap)), shard_spec(self.mesh))
        try:
            allow_list._words_cache = (key, out)
        except AttributeError:
            pass
        return out

    # -- snapshot plane (docs/mesh_serving.md) -------------------------------

    def _mark_staged(self) -> None:
        """Stamp the first staging moment of the current unpublished batch
        (ledger publish-lag attribution; no-op when the ledger is down)."""
        if self._staged_t0 is None and memory.get_ledger() is not None:
            self._staged_t0 = time.perf_counter()

    def _publish_snapshot(self) -> None:
        """Build and atomically publish a MeshSnapshot. Caller holds _lock."""
        if self._ivf_dirty:
            self._ivf_rebuild_buckets()
        self._snap_gen += 1
        self._snap = MeshSnapshot(self._snap_gen, self)
        self._published_gen = self._staged_gen
        m = self.metrics
        if m is not None:
            m.index_snapshot_gen.labels(*self._metric_labels()).set(
                self._snap_gen)
        self._stamp_memory()
        led = memory.get_ledger()
        if led is not None and self._staged_t0 is not None:
            led.note_publish(
                (time.perf_counter() - self._staged_t0) * 1000.0)
        self._staged_t0 = None

    def _read_snapshot(self) -> tuple[MeshSnapshot, float]:
        """-> (the current MeshSnapshot, the ms this read waited on the
        write lock). Lock-free when nothing is staged: one reference load +
        one generation compare, and the wait is 0.0. Staged writes take the
        slow path — drain staging under the lock, republish, serve."""
        snap = self._snap
        if snap is not None and self._published_gen == self._staged_gen:
            return snap, 0.0
        t0 = time.perf_counter()
        with self._lock:
            wait_ms = (time.perf_counter() - t0) * 1000.0
            self._flush_pending()
            if self._snap is None or self._published_gen != self._staged_gen:
                self._publish_snapshot()
            snap = self._snap
        m = self.metrics
        if m is not None:
            m.index_lock_wait.labels(*self._metric_labels()).observe(wait_ms)
        return snap, wait_ms

    @property
    def snapshot_gen(self) -> int:
        snap = self._snap
        return snap.gen if snap is not None else 0

    def _track_inflight(self, delta: int) -> None:
        with self._inflight_lock:
            self._inflight += delta
            n = self._inflight
        m = self.metrics
        if m is None:
            return
        g = self._inflight_gauge
        if g is None:
            g = m.index_inflight_dispatches.labels(*self._metric_labels())
            self._inflight_gauge = g
        g.set(n)

    # -- IVF plane (per-device KScaNN buckets, shared codebook) --------------

    def _ivf_nlist(self, s, n: int) -> int:
        if s.nlist > 0:
            return max(1, min(s.nlist, max(n // 8, 1)))
        target = 2 ** int(math.ceil(math.log2(max(n / 256.0, 16.0))))
        return int(max(16, min(target, 4096, max(n // 32, 16))))

    def _ivf_maybe_train(self) -> None:
        """Train/retrain the shared k-means codebook when warranted. Called
        from flush() AFTER the lock is released — the training fetch and
        fit run against a pinned snapshot, never under the index lock."""
        s = ivf_settings()
        if (
            s is None
            or self._restoring
            or self.compressed
            or self.dim is None
            or self.metric not in ivf_ops.MATMUL_METRICS
            or self.live < max(s.min_n, 256)
        ):
            return
        if (self._ivf_centroids_host is not None
                and self.live < self._ivf_trained_n * (1.0 + s.retrain_growth)):
            return
        self._ivf_train(s)

    def _ivf_train(self, s) -> None:
        """Off-lock (re)clustering: pin a snapshot, fetch + fit outside the
        lock while concurrent writes queue into _ivf_backlog, then install
        under the lock iff the device epoch is unchanged."""
        snap, _ = self._read_snapshot()
        if snap.dim is None or snap.n_total == 0:
            return
        epoch = self._device_epoch
        with self._lock:
            if self._ivf_backlog is not None:
                return  # another trainer is in flight
            self._ivf_backlog = []
        t0 = time.perf_counter()
        try:
            # maintenance fetch, off-lock, against the pinned snapshot
            src = np.asarray(snap.store, dtype=np.float32)
            slots = []
            for dev in range(snap.n_dev):
                base = dev * snap.n_loc
                sl = np.arange(base, base + int(snap.counts[dev]))
                slots.append(sl[~snap.host_tombs[sl]])
            rows = src[np.concatenate(slots)] if slots else src[:0]
            n = rows.shape[0]
            if n < 2:
                return
            nlist = self._ivf_nlist(s, n)
            cent = ivf_ops.kmeans_fit(
                rows, nlist, iters=s.train_iters, seed=self._ivf_gen,
                sample=min(len(rows), max(s.train_sample, nlist * 16)))
            if self.metric == vi.DISTANCE_COSINE:
                nrm = np.linalg.norm(cent, axis=1, keepdims=True)
                nrm[nrm == 0] = 1.0
                cent = cent / nrm
            # one shared spill capacity across devices so the per-device
            # balanced assignments stack into one sharded bucket table
            max_per = max((int(sl.size) for sl in slots), default=0)
            cap_t = int(ivf_ops.bucket_capacity(
                np.array([int(1.25 * max_per / nlist) + 1])))
            a_snap = np.full(snap.n_dev * snap.n_loc, -1, dtype=np.int32)
            off = 0
            for sl in slots:
                if sl.size:
                    a_snap[sl] = ivf_ops.balanced_assign(
                        rows[off:off + sl.size], cent, cap_t)
                off += sl.size
            with self._lock:
                if (self._device_epoch != epoch or self.dim != snap.dim
                        or self.n_loc < snap.n_loc):
                    return  # slabs were re-created under us: abandon
                assign = np.full(self.n_dev * self.n_loc, -1, dtype=np.int32)
                for dev in range(snap.n_dev):
                    assign[dev * self.n_loc:
                           dev * self.n_loc + snap.n_loc] = a_snap[
                        dev * snap.n_loc:(dev + 1) * snap.n_loc]
                for g, r in self._ivf_backlog:
                    assign[g] = ivf_ops.assign_partitions(
                        np.asarray(r, np.float32), cent)
                self._ivf_backlog = None
                self._ivf_assign = assign
                self._ivf_centroids_host = cent
                self._ivf_centroids = jax.device_put(
                    jnp.asarray(cent), shard_spec(self.mesh))
                self._ivf_cap_p = cap_t
                self._ivf_trained_n = n
                self._ivf_gen += 1
                self._ivf_dirty = True
                self._staged_gen += 1
                self._mark_staged()
                self._stamp_memory()
            led = memory.get_ledger()
            if led is not None:
                led.note_write(
                    "ivf", "recluster",
                    (time.perf_counter() - t0) * 1000.0, rows=n)
        finally:
            with self._lock:
                bl, self._ivf_backlog = self._ivf_backlog, None
                if bl and self._ivf_centroids_host is not None:
                    # install aborted after writes queued: classify the
                    # leftovers against whatever codebook is current
                    for g, r in bl:
                        self._ivf_assign[g] = ivf_ops.assign_partitions(
                            np.asarray(r, np.float32),
                            self._ivf_centroids_host)
                    self._ivf_dirty = True

    def _ivf_rebuild_buckets(self) -> None:
        """Rebuild the sharded [n_dev, nlist, cap_p] bucket table from the
        per-row assignments. Caller holds _lock (publish path)."""
        cent = self._ivf_centroids_host
        if cent is None or self.dim is None:
            self._ivf_dirty = False
            return
        nlist = cent.shape[0]
        per_dev = []
        for dev in range(self.n_dev):
            a = self._ivf_assign[dev * self.n_loc:(dev + 1) * self.n_loc].copy()
            a[self._host_tombs[dev * self.n_loc:(dev + 1) * self.n_loc]] = -1
            per_dev.append(a)
        fills = np.stack([
            np.bincount(a[a >= 0], minlength=nlist) for a in per_dev])
        # shared capacity: never below what any device needs, never below
        # the training-time spill cap (keeps the table shape monotonic)
        cap_shared = max(int(ivf_ops.bucket_capacity(fills.reshape(-1))),
                         int(self._ivf_cap_p or 0))
        bkt = np.stack([
            ivf_ops.build_buckets(a, nlist, cap_shared)[0] for a in per_dev])
        self._ivf_buckets = jax.device_put(
            jnp.asarray(bkt), shard_spec(self.mesh, None, None))
        self._ivf_fills = fills
        self._ivf_cap_p = cap_shared
        self._ivf_meta = (nlist, cap_shared, self._ivf_gen)
        self._ivf_dirty = False
        self._stamp_memory()

    def _ivf_reset(self) -> None:
        """Drop the clustering (compact/compress/drop paths)."""
        self._ivf_centroids_host = None
        self._ivf_centroids = None
        self._ivf_buckets = None
        self._ivf_assign = np.zeros(0, dtype=np.int32)
        self._ivf_fills = None
        self._ivf_cap_p = 0
        self._ivf_meta = None
        self._ivf_dirty = False
        self._ivf_trained_n = 0

    def ivf_stats(self) -> dict:
        with self._ivf_lock:
            st = dict(self._ivf_stats)
        st["probed_fraction"] = (
            round(st["probed_rows"] / st["base_rows"], 4)
            if st["base_rows"] else None
        )
        return st

    # -- search dispatch (two-phase: enqueue on the snapshot, fetch later) ---

    def _plan_view(self, snap: MeshSnapshot) -> PlanView:
        """What index/plan.py reads of `snap` across the mesh: one chip's
        slab is `n_loc` rows, the fullest holds `counts.max()`."""
        pq = snap.compressed
        ivf = (not pq and snap.ivf_buckets is not None
               and snap.ivf_meta is not None)
        row_bytes = snap.dim * snap.store.dtype.itemsize
        return PlanView(
            config=self.config, metric=self.metric,
            programs=self.scan_programs, kernels=self,
            component="index.mesh.gmin", n=snap.n_total, live=snap.live,
            dim=snap.dim, ndev=snap.n_dev, slab=snap.n_loc,
            fill=int(snap.counts.max()), itemsize=snap.store.dtype.itemsize,
            compressed=pq, pq_segments=snap.pq.segments if pq else 0,
            pq4_segments=(snap.pq4.segments if pq and snap.codes4 is not None
                          and snap.pq4 is not None else 0),
            # the pq steps rescore against the chip's own store slab
            rescore=bool(pq and self.config.pq.rescore),
            rescore_bytes_per_row=row_bytes if pq else 0,
            # the scan step's depth a chip: the one-chip rule, planned
            # against one slab like the funnel's budgets
            depth_rows=snap.n_loc,
            ivf_meta=snap.ivf_meta[:2] if ivf else None,
            ivf_probe=functools.partial(ivf_probe, self.metric, snap) if ivf
            else None, ivf_gathered=ivf)

    def dispatch_tier(self, snap: MeshSnapshot,
                      allow_list: Optional[AllowList] = None,
                      b: int = 1, k: int = 1) -> str:
        """The tier a dispatch of `b` queries at depth `k` against `snap`
        takes (quality auditor attribution): its plan's (index/plan.py).
        The mesh has no gather tier — small filtered reads still run the
        full sharded scan — so the plan is given no allowList; the program
        is not asked, so nothing is counted."""
        return plan_search(
            self._plan_view(snap), b, _bucket_b(b), self._k_eff(snap, k),
            refused=frozenset((KERNEL_GMIN,))).tier

    @staticmethod
    def _k_eff(snap: MeshSnapshot, k: int) -> int:
        """The depth every chip selects at: no deeper than the live rows or
        one scan chunk."""
        return max(1, min(k, snap.live, snap.n_loc, SCAN_CHUNK))

    def _dispatch_search(self, snap: MeshSnapshot, vectors: np.ndarray,
                         k: int, allow_list: Optional[AllowList] = None):
        """Enqueue ONE whole-mesh program against `snap`, the one its plan
        names (index/plan.py), and return its `DispatchHandle`. The program
        runs per-shard scan -> local top-k -> all-gather -> final select ->
        on-device slot->doc translation, so finalize is one packed fetch +
        dtype views (the JGL015 one-fetch / zero-translation invariant,
        across chips). No locks anywhere."""
        if snap.dim is None or snap.live == 0 or snap.n_total == 0:
            b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
            return DispatchHandle.ready((np.zeros((b, 0), dtype=np.uint64),
                                         np.zeros((b, 0), dtype=np.float32)))
        faults.fire("index.mesh.dispatch")
        # the `enqueue` interval and the shape exist ONLY while the tracer
        # is up (the zero-cost-when-disabled contract). The interval's
        # stats name the exact tier's program and, where the scan step
        # ran, its depth ({"program", "rescore_r"}: `SearchPlan.stats`)
        enqueue = (tracing.Phase("enqueue")
                   if tracing.get_tracer() is not None else None)
        try:
            q, b = self._prep_queries(vectors)
            kk = self._k_eff(snap, k)
            use_allow = allow_list is not None
            words = self._allow_words(snap, allow_list) if use_allow else snap.zero_words
            exact = getattr(self.config, "exact_topk", False)
            view = self._plan_view(snap)
            plan = plan_search(view, b, q.shape[0], kk)
            handle = DispatchHandle(
                self, "index.mesh.finalize", plan,
                None if enqueue is None
                else plan.shape(enqueue.start_ns / 1e9))
            if plan.ivf_declined:
                self.scan_programs.declined_probe()
            packed_dev = None
            if plan.tier == TIER_PQ_ADC4:
                # the 4-bit rung: per-chip three-stage funnel (nibble scan
                # -> 8-bit ADC re-rank -> exact rescore against the chip's
                # own store slab), budgets recall-guarded per shard
                # (planned against n_loc: the whole-mesh candidate pool is
                # n_dev x rg4*16)
                from weaviate_tpu.ops import pq_gmin

                rg4, rc = plan.funnel
                _, flat_cb8 = pq_gmin.cached_cb_constants(self)
                packed_dev = mesh_search_pq4_step(
                    snap.codes4,
                    snap.codes,
                    snap.recon_norms4,
                    snap.recon_norms,
                    snap.tombs,
                    snap.counts_dev,
                    words,
                    snap.pq4._dev_codebook(),
                    flat_cb8,
                    snap.store,
                    jnp.asarray(q),
                    snap.pq4.rotation_dev(),
                    snap.slot_to_doc_dev,
                    kk,
                    self.metric,
                    use_allow,
                    rg4,
                    rc,
                    exact,
                    self.mesh,
                )
            elif snap.compressed:
                if plan.tier == TIER_PQ_CODES:
                    # codes-only tier: try the fused per-shard ADC kernel
                    # (mesh twin of the single-chip pq_gmin dispatch)
                    packed_dev = self._pq_gmin_step_or_none(
                        snap, q, kk, words, use_allow)
                if packed_dev is None:
                    chunk = min(snap.n_loc, SCAN_CHUNK)
                    nchunks_eff = max(1, snap.n_loc // chunk)
                    pool_target = self.config.pq.rescore_limit or 1024
                    r_chunk = min(
                        max(2 * kk, -(-pool_target // nchunks_eff), 64), 256, chunk)
                    # the concatenated per-chip pool must cover k (tpu.py:1080)
                    r_chunk = max(r_chunk, min(-(-kk // nchunks_eff), chunk))
                    packed_dev = mesh_search_pq_step(
                        snap.codes,
                        snap.recon_norms,
                        snap.tombs,
                        snap.counts_dev,
                        words,
                        snap.pq._dev_codebook(),
                        snap.store,
                        jnp.asarray(q),
                        snap.pq.rotation_dev(),
                        snap.slot_to_doc_dev,
                        kk,
                        r_chunk,
                        self.metric,
                        use_allow,
                        exact,
                        plan.tier == TIER_PQ_RESCORE,
                        self.mesh,
                    )
            elif plan.ivf is not None:
                top_p = plan.ivf[0]
                _nlist, cap_p, _gen = snap.ivf_meta
                gp = ivf_ops.group_steps(q.shape[0], cap_p, snap.dim, top_p)
                packed_dev = mesh_search_ivf_step(
                    snap.store,
                    snap.tombs,
                    snap.counts_dev,
                    words,
                    snap.ivf_centroids,
                    snap.ivf_buckets,
                    jnp.asarray(q),
                    snap.slot_to_doc_dev,
                    kk,
                    self.metric,
                    use_allow,
                    top_p,
                    exact,
                    gp,
                    self.mesh,
                )
                with self._ivf_lock:
                    st = self._ivf_stats
                    st["dispatches"] += 1
                    st["probed_rows"] += snap.n_dev * top_p * cap_p
                    st["base_rows"] += int(snap.n_total)
            else:
                if plan.gmin is not None:
                    packed_dev = self._gmin_step_or_none(
                        snap, q, kk, words, use_allow, plan.gmin)
                    if packed_dev is None:
                        # Mosaic refused this shape (the one place that
                        # falls back): planned again, the scan step serves
                        plan = handle.refuse(view, KERNEL_GMIN)
                if packed_dev is None:
                    packed_dev = mesh_search_step(
                        snap.store,
                        snap.sq_norms,
                        snap.tombs,
                        snap.counts_dev,
                        words,
                        jnp.asarray(q),
                        snap.slot_to_doc_dev,
                        kk,
                        self.metric,
                        use_allow,
                        self.metric == vi.DISTANCE_L2,
                        exact,
                        True,  # fused: the only epilogue there is
                        self.mesh,
                        plan.rescore_r,
                    )
                self.scan_programs.count(plan.program)
        except BaseException:
            if enqueue is not None:  # a dispatch that failed being built
                enqueue.end()
            raise

        shape = handle.shape
        if enqueue is not None:
            now_ns = enqueue.end(rows=b, tier=plan.tier, ndev=plan.ndev,
                                 **plan.stats())
            shape.enqueue_ms = (now_ns - enqueue.start_ns) / 1e6
        # the shadow audit must re-read the SAME snapshot the live dispatch
        # answered from: the handle carries it, only while an auditor is up
        if quality.get_auditor() is not None:
            handle.snapshot = snap

        def finish():
            packed = _fetch_packed(packed_dev, shape)
            ids, dists = unpack_fused(packed)
            return ids[:b], dists[:b]

        return handle.launched(finish)

    def search_by_vectors(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        snap, _ = self._read_snapshot()
        return self._dispatch_search(snap, vectors, k, allow_list)()

    def search_by_vectors_async(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ):
        """Two-phase dispatch for the serving coalescer: enqueue the whole
        sharded program now (lock-free, on the current snapshot), return
        the finalize closure. The coalescer overlaps the next lane's
        enqueue with this lane's device time (pipeline depth 2); filtered
        lanes ride the same path (async_supports_filters)."""
        snap, wait_ms = self._read_snapshot()
        handle = self._dispatch_search(snap, vectors, k, allow_list)
        handle.lock_wait_ms = wait_ms
        return handle

    # -- fused group-min kernels (guarded; separate failure domains) ---------

    def _pq_gmin_step_or_none(self, snap: MeshSnapshot, q: np.ndarray,
                              kk: int, words, use_allow: bool):
        """Enqueue the fused per-shard PQ codes kernel, or None for the
        legacy reconstruction scan — separate failure domain
        (self._pqg_state); gating and codebook constants are the shared
        helpers in ops/pq_gmin.py. Returns the guarded result RAW (host
        array on the first validation run, device array after), so the
        async finalize defers the fetch."""
        from weaviate_tpu.parallel.mesh_search import mesh_search_pq_gmin_step

        from weaviate_tpu.ops import gmin_scan, pq_gmin

        ncols_l = snap.n_loc // gmin_scan.G
        active_g = max(1, -(-int(snap.counts.max()) // ncols_l)) if ncols_l else 1
        rg = pq_gmin.eligible_rg(
            self._pqg_state, getattr(self.config, "exact_topk", False),
            self.metric, snap.pq, q.shape[0], ncols_l, kk, snap.dim, active_g,
            component="index.mesh.pq_gmin")
        if rg is None:
            return None
        m, c = snap.pq.segments, snap.pq.centroids
        interpret = device.pallas_interpret()
        cb_chunks, flat_cb = pq_gmin.cached_cb_constants(self)
        key = ("pq", q.shape[0], kk, rg, active_g, snap.n_loc, m, c,
               use_allow)
        return gmin_scan.guarded_kernel_call(
            self._pqg_state, key,
            lambda: mesh_search_pq_gmin_step(
                snap.codes,
                snap.recon_norms,
                snap.tombs,
                snap.counts_dev,
                words,
                cb_chunks,
                flat_cb,
                jnp.asarray(q),
                snap.pq.rotation_dev(),
                snap.slot_to_doc_dev,
                kk,
                self.metric,
                use_allow,
                rg,
                active_g,
                interpret,
                self.mesh,
            ),
            "mesh pq codes kernel", component="index.mesh.pq_gmin")

    def _gmin_step_or_none(self, snap: MeshSnapshot, q: np.ndarray, kk: int,
                           words, use_allow: bool, gmin: tuple[int, int]):
        """Enqueue the fused group-min mesh kernel at the plan's `gmin`
        (groups kept, live store slices), or None where Mosaic refuses it:
        the scan step then serves. Validation mirrors tpu.py's
        _gmin_packed_or_none: per compiled shape — a Mosaic rejection on a
        NEW shape falls back for that shape only, a failure on a shape that
        already served propagates, and only repeated distinct-shape
        failures with zero successes disable the path. Returns the guarded
        result RAW so the async finalize defers the fetch."""
        from weaviate_tpu.parallel.mesh_search import mesh_search_gmin_step

        from weaviate_tpu.ops import gmin_scan

        rg, active_g = gmin
        key = (q.shape[0], kk, rg, active_g, snap.n_loc, use_allow)
        interpret = device.pallas_interpret()
        return gmin_scan.guarded_kernel_call(
            self, key,
            lambda: mesh_search_gmin_step(
                snap.store,
                snap.sq_norms,
                snap.tombs,
                snap.counts_dev,
                words,
                jnp.asarray(q),
                snap.slot_to_doc_dev,
                kk,
                self.metric,
                use_allow,
                self.metric == vi.DISTANCE_L2,
                rg,
                active_g,
                interpret,
                self.mesh,
            ),
            "mesh gmin kernel", component="index.mesh.gmin")

    # -- host fallback plane (breaker-degraded serving + shadow audits) ------

    def _snap_prefix_slots(self, snap: MeshSnapshot) -> np.ndarray:
        """Global row ids of every written slot in `snap`, slab order —
        the per-device counts prefixes concatenated. Includes tombstoned
        rows (masked by the caller), matching the single-chip convention
        that host_rows covers the full high-water prefix."""
        if snap.dim is None or snap.n_total == 0:
            return np.zeros(0, dtype=np.int64)
        return np.concatenate([
            np.arange(dev * snap.n_loc,
                      dev * snap.n_loc + int(snap.counts[dev]))
            for dev in range(snap.n_dev)
        ])

    def host_rows(self, snap: MeshSnapshot) -> tuple[np.ndarray, np.ndarray]:
        """(rows f32 [n, D], sq_norms f32 [n]) for `snap`'s written slots —
        the quality auditor's ground-truth source. Compressed mode serves
        the full-precision host copy (the device store is bf16 by then)."""
        slots = self._snap_prefix_slots(snap)
        if snap.compressed and snap.host_vecs is not None:
            rows = snap.host_vecs[slots]
        else:
            rows = np.asarray(snap.store, dtype=np.float32)[slots]
        sq = np.einsum("ij,ij->i", rows, rows, dtype=np.float32)
        return rows, sq

    def _host_fallback_rows(self, snap: MeshSnapshot):
        """Generation-keyed single-entry cache of host_rows for the breaker
        path — one fetch per snapshot generation while degraded."""
        cached = self._host_rows_cache
        if cached is not None and cached[0] == snap.gen:
            return cached[1], cached[2]
        rows, sq = self.host_rows(snap)
        self._host_rows_cache = (snap.gen, rows, sq)  # graftflow: disable=JGL018 generation-keyed single-entry cache with an explicit release (release_host_fallback_cache on breaker recovery); outliving the snapshot is the point
        return rows, sq

    def release_host_fallback_cache(self) -> None:
        """Drop the breaker-path row cache (called on breaker recovery)."""
        self._host_rows_cache = None

    def search_by_vectors_host(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Pure-host scan over the current snapshot (the breaker's degraded
        serving path; bit-compatible contract with the device scan)."""
        snap, _ = self._read_snapshot()
        if snap.dim is None or snap.n_total == 0 or snap.live == 0:
            b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
            return (np.zeros((b, 0), dtype=np.uint64),
                    np.zeros((b, 0), dtype=np.float32))
        rows, sq = self._host_fallback_rows(snap)
        return self._host_search_snap(snap, vectors, k, allow_list, rows, sq)

    def search_by_vectors_host_pinned(
        self, snap: MeshSnapshot, vectors: np.ndarray, k: int,
        allow_list: Optional[AllowList] = None, rows=None, sq_norms=None,
        deadline: Optional[float] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host scan against a PINNED snapshot (quality auditor: the shadow
        re-execution must read the exact state the live dispatch saw)."""
        if snap.dim is None or snap.n_total == 0 or snap.live == 0:
            b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
            return (np.zeros((b, 0), dtype=np.uint64),
                    np.zeros((b, 0), dtype=np.float32))
        if rows is None or sq_norms is None:
            rows, sq_norms = self.host_rows(snap)
        return self._host_search_snap(
            snap, vectors, k, allow_list, rows, sq_norms, deadline)

    def _host_search_snap(self, snap: MeshSnapshot, vectors, k, allow_list,
                          rows, row_sq, deadline: Optional[float] = None):
        from weaviate_tpu.storage.bitmap import Bitmap, allowed_mask

        q = np.asarray(vectors, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(q, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            q = q / norms
        slots = self._snap_prefix_slots(snap)
        live = ~snap.host_tombs[slots]
        docs = snap.slot_to_doc[slots]
        if allow_list is not None:
            if isinstance(allow_list, Bitmap):
                live = live & allowed_mask(allow_list, docs)
            else:
                live = live & allow_list.contains_array(docs.astype(np.uint64))
        n = slots.size
        n_live = int(live.sum())
        if n_live == 0:
            return (np.zeros((q.shape[0], 0), dtype=np.uint64),
                    np.zeros((q.shape[0], 0), dtype=np.float32))
        q_sq = (q ** 2).sum(1)[:, None] if self.metric == vi.DISTANCE_L2 else None
        chunk = (4096 if self.metric in (vi.DISTANCE_MANHATTAN,
                                         vi.DISTANCE_HAMMING)
                 else self._HOST_SCAN_CHUNK)
        d = np.empty((q.shape[0], n), dtype=np.float32)
        for s in range(0, n, chunk):
            if deadline is not None and time.perf_counter() > deadline:
                raise quality.AuditDeadlineExceeded(
                    f"host scan over audit budget at row {s}/{n}")
            e = min(s + chunk, n)
            blk = rows[s:e]
            if self.metric == vi.DISTANCE_L2:
                qx = q @ blk.T
                d[:, s:e] = np.maximum(
                    q_sq - 2.0 * qx + row_sq[s:e][None, :], 0.0)
            elif self.metric == vi.DISTANCE_DOT:
                d[:, s:e] = -(q @ blk.T)
            elif self.metric == vi.DISTANCE_COSINE:
                d[:, s:e] = 1.0 - q @ blk.T
            elif self.metric == vi.DISTANCE_MANHATTAN:
                d[:, s:e] = np.abs(q[:, None, :] - blk[None, :, :]).sum(-1)
            else:
                d[:, s:e] = (q[:, None, :] != blk[None, :, :]).sum(-1)
        d[:, ~live] = np.inf
        kk = min(max(int(k), 1), n_live)
        idx = np.argpartition(d, kk - 1, axis=1)[:, :kk]
        top = np.take_along_axis(d, idx, axis=1)
        order = np.argsort(top, axis=1, kind="stable")
        top = np.take_along_axis(top, order, axis=1)
        idx = np.take_along_axis(idx, order, axis=1)
        ids = np.where(np.isinf(top), -1, docs[idx])
        return ids.astype(np.uint64), top.astype(np.float32)

    # -- health (GET /debug/index parity with TpuVectorIndex) ----------------

    def _ivf_health(self) -> dict:
        s = ivf_settings()
        out: dict = {
            "enabled": s is not None,
            "trained": self._ivf_centroids_host is not None,
        }
        if self._ivf_centroids_host is not None:
            nlist, cap_p, gen = self._ivf_meta or (
                self._ivf_centroids_host.shape[0], self._ivf_cap_p or 0,
                self._ivf_gen)
            out.update({"nlist": int(nlist), "cap_p": int(cap_p),
                        "gen": int(gen), "trained_n": self._ivf_trained_n,
                        "pca_dim": 0})
            fills = self._ivf_fills
            if fills is not None:
                flat = fills.reshape(-1)
                mean = float(flat.mean()) if flat.size else 0.0
                total = int(flat.sum())
                out["buckets"] = {
                    "fill_min": int(flat.min()) if flat.size else 0,
                    "fill_mean": round(mean, 1),
                    "fill_max": int(flat.max()) if flat.size else 0,
                    "empty": int((flat == 0).sum()),
                    "padding_waste": round(
                        1.0 - total / max(flat.size * cap_p, 1), 4),
                    "imbalance": (round(float(flat.max()) / mean, 2)
                                  if mean > 0 else None),
                    "fill_histogram": np.histogram(
                        flat, bins=8, range=(0, max(cap_p, 1)))[0].tolist(),
                    "per_device_rows": fills.sum(axis=1).tolist(),
                }
        out["probes"] = self.ivf_stats()
        return out

    def health(self) -> dict:
        """Mesh diagnostics for GET /debug/index — same keys as the
        single-chip index plus the per-device breakdown."""
        from weaviate_tpu.ops import gmin_scan

        with self._lock:
            counts = self._counts.copy()
            slots = int(counts.sum())
            tombs = int(self._host_tombs.sum())
            comps = self._memory_components()
            slab_bytes_total = sum(comps.values())
            per_device = []
            for dev, jdev in enumerate(self.mesh.devices.flat):
                sl = slice(dev * self.n_loc, dev * self.n_loc + self.n_loc)
                # the allocator's own count beside the analytic share
                # (None where the backend reports no memory_stats — cpu)
                stats = jdev.memory_stats() or {}
                per_device.append({
                    "device": dev,
                    "rows": int(counts[dev]),
                    "tombstones": int(self._host_tombs[sl].sum())
                    if self._host_tombs.size else 0,
                    "slab_bytes": slab_bytes_total // self.n_dev,
                    "allocator_bytes_in_use": stats.get("bytes_in_use"),
                })
            out = {
                "type": "hnsw_tpu_mesh",
                "metric": self.metric,
                "dim": self.dim,
                "devices": self.n_dev,
                "rows_per_device": self.n_loc,
                "capacity": self.n_dev * self.n_loc,
                "slots": slots,
                "live": self.live,
                "tombstones": tombs,
                "tombstone_fraction": round(tombs / max(slots, 1), 4),
                "pending_adds": len(self._pending),
                "pending_tombstones": len(self._pending_tombs),
                "snapshot_gen": self.snapshot_gen,
                "staged_gen": self._staged_gen,
                "published_gen": self._published_gen,
                "staged_lag": self._staged_gen - max(self._published_gen, 0),
                "per_device": per_device,
                # what the restore did (None for an index that began
                # empty): the single-chip index's block
                "restore": self.last_restore,
                "compressed": self.compressed,
                # rescore=false is a footgun: raw ADC
                # distances at recall ~0.24 — surfaced, not just documented
                "pq": None if self._pq is None else {
                    "segments": self._pq.segments,
                    "centroids": self._pq.centroids,
                    "rotation": bool(self.config.pq.rotation),
                    "rescore": bool(self.config.pq.rescore),
                    "code_dtype": str(np.dtype(self._pq.code_dtype)),
                },
                "ivf": self._ivf_health(),
                "host_fallback_cache": {
                    "resident": self._host_rows_cache is not None,
                    "gen": (self._host_rows_cache[0]
                            if self._host_rows_cache is not None else None),
                    "bytes": memory.host_rows_cache_bytes(self),
                },
                "memory": {
                    "device_components": comps,
                    "host_components": memory.index_host_components(self),
                },
                # compiled shapes that served / that Mosaic rejected, per
                # failure domain (the mesh funnel's stage 1 is the
                # traceable scan, so there is no pq4 kernel entry)
                "kernels": {
                    "gmin": gmin_scan.kernel_health(self),
                    "pq_gmin": gmin_scan.kernel_health(self._pqg_state),
                },
            }
        return out

    # -- single-vector entry points ------------------------------------------

    def search_by_vector(
        self, vector: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        ids, dists = self.search_by_vectors(np.asarray(vector)[None, :], k, allow_list)
        keep = dists[0] != np.inf
        return ids[0][keep], dists[0][keep]

    def search_by_vector_distance(
        self,
        vector: np.ndarray,
        target_distance: float,
        max_limit: int,
        allow_list: Optional[AllowList] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Doubling-limit loop (search.go:90-157 semantics)."""
        limit = 64
        while True:
            ids, dists = self.search_by_vector(vector, min(limit, max_limit), allow_list)
            if len(ids) == 0:
                return ids, dists
            beyond = dists > target_distance
            if beyond.any() or len(ids) >= min(max_limit, self.live):
                keep = dists <= target_distance
                return ids[keep][:max_limit], dists[keep][:max_limit]
            if limit >= max_limit:
                return ids[:max_limit], dists[:max_limit]
            limit *= 2

    # -- config / maintenance ------------------------------------------------

    def update_user_config(self, updated: vi.HnswUserConfig) -> None:
        with self._lock:
            vi.validate_config_update(self.config, updated)
            was_enabled = self.config.pq.enabled
            if updated.pq.enabled and not was_enabled:
                # reject what is knowable NOW instead of deferring the
                # failure into the compression trigger
                if self.metric not in (vi.DISTANCE_L2, vi.DISTANCE_DOT,
                                       vi.DISTANCE_COSINE):
                    raise vi.ConfigValidationError(
                        f"pq on hnsw_tpu_mesh supports l2-squared/dot/"
                        f"cosine, not {self.metric}")
                if (self.dim is not None and updated.pq.segments > 0
                        and self.dim % updated.pq.segments != 0):
                    raise vi.ConfigValidationError(
                        f"pq.segments ({updated.pq.segments}) must divide "
                        f"vector dims ({self.dim})")
            prev = self.config
            self.config = updated
            # pq.enabled flipped on triggers compression (compress.go)
            if updated.pq.enabled and not was_enabled and not self.compressed:
                try:
                    self._flush_pending()
                    if self.live > 0:
                        self._compress_locked()
                except Exception:
                    # a failed pq-enable must not stick — config or runtime
                    # (an OOM'd kmeans fit): a committed-but-uncompressed
                    # config would re-run the full fit from the flush-path
                    # declarative trigger on every later flush
                    self.config = prev
                    raise

    def flush(self) -> None:
        with self._lock:
            self._flush_pending()
            self._maybe_autocompress()
            if self._log is not None:
                self._log.flush()
        # IVF (re)training fetches + fits OFF the lock, from a pinned
        # snapshot; concurrent writes queue into the backlog
        self._ivf_maybe_train()

    def compact(self) -> None:
        """Condense: drop tombstoned slots, rewrite the log, rebuild balanced
        (condensor.go analog). In-flight dispatches keep their pinned
        snapshots — the rebuild swaps whole slabs, never mutates them."""
        with self._lock:
            self._flush_pending()
            if self.dim is None or not self._doc_to_row:
                return
            total = int(self._counts.sum())
            if len(self._doc_to_row) == total:
                return
            t_compact0 = time.perf_counter()
            rows = np.array(sorted(self._doc_to_row.values()), dtype=np.int64)
            docs = self._slot_to_doc[rows]
            # compressed mode rewrites the log from the f32 host copy — the
            # device store is bf16 by then and must not degrade durable data
            src = self._host_vecs if self.compressed else np.asarray(
                self._store, dtype=np.float32)
            store_host = np.asarray(src, dtype=np.float32)[rows]
            if self._log is not None:
                self._log.rewrite(zip(docs.tolist(), store_host))
            # mapping rebuild invalidates any packed-words cache keyed on it
            self._allow_token = object()
            self._ivf_reset()
            dim = self.dim
            self.dim = None
            self.n_loc = 0
            self.live = 0
            self._counts = np.zeros(self.n_dev, dtype=np.int64)
            self._doc_to_row.clear()
            self._slot_to_doc = np.zeros(0, dtype=np.int64)
            self._store = self._sq_norms = self._tombs = None
            self._s2d_dev = None
            self._host_tombs = np.zeros(0, dtype=bool)
            self._init_device(dim)
            self._restoring = True
            try:
                self.add_batch(docs, store_host)
            finally:
                self._restoring = False
            self._staged_gen += 1
            self._mark_staged()
            led = memory.get_ledger()
            if led is not None:
                led.note_write(
                    "compact", "compact",
                    (time.perf_counter() - t_compact0) * 1000.0,
                    rows=self.live)

    def drop(self) -> None:
        with self._lock:
            if self._log is not None:
                self._log.close()
                try:
                    os.remove(self._log.path)
                except FileNotFoundError:
                    pass
                self._log = None
            self._store = self._sq_norms = self._tombs = None
            self._zero_words = None  # sharded device words must free too
            self._s2d_dev = None
            self._codes = self._recon_norms = None
            self._host_vecs = None
            self._pq = None
            self.compressed = False
            if self._pq_path:
                try:
                    os.remove(self._pq_path)
                except FileNotFoundError:
                    pass
            self.dim = None
            self.n_loc = 0
            self.live = 0
            self._counts = np.zeros(self.n_dev, dtype=np.int64)
            self._slot_to_doc = np.zeros(0, dtype=np.int64)
            self._host_tombs = np.zeros(0, dtype=bool)
            self._doc_to_row.clear()
            self._pending.clear()
            self._pending_tombs.clear()
            self._snap = None
            self._host_rows_cache = None
            self._ivf_reset()
            self._device_epoch += 1
            self._staged_gen += 1
            self._stamp_memory()  # zero this index's device components

    def shutdown(self) -> None:
        with self._lock:
            self._flush_pending()
            if self._log is not None:
                self._log.flush()
                self._log.close()

    def list_files(self) -> list[str]:
        out = [self._log.path] if self._log is not None else []
        if self._pq_path and os.path.exists(self._pq_path):
            out.append(self._pq_path)  # backups must carry the codebook
        return out
