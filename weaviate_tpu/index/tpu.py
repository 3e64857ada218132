"""The TPU-native vector index ("hnsw_tpu" / "flat").

Design (replaces the reference's HNSW hot path, SURVEY.md §2.4):

The reference walks a graph one edge at a time — pop candidate, fetch ~64
neighbor vectors from a RAM cache, run one AVX2 distance per edge, push heaps
(vector/hnsw/search.go:160 searchLayerByVector). That shape is hostile to a
systolic array. The TPU-first restructuring keeps the *interface contract*
(vector_index.go:23-40: (vector, k, allowList) -> (ids, dists)) but makes the
device do what it is good at:

- the shard's vectors live in HBM as one padded [capacity, D] device array
  (the analog of the sharded-lock vector cache, vector_cache.go:47 — except
  the "cache" IS the store and never misses);
- a query batch is ONE [B, N] distance matmul on the MXU + a masked
  k-selection (ops/distances.py, ops/topk.py). Per-chunk selection defaults
  to lax.approx_min_k at recall_target=0.95 (the TPU PartialReduce /ScaNN
  primitive; recall 0.998-0.999 in the benchmark's cells, and never below the
  target — comparable to HNSW's >=0.99 fixture bar, recall_test.go:137);
  config exactTopK=true forces lax.top_k for guaranteed recall 1.0;
- tombstones (delete.go semantics) are a device bool mask, filters
  (helpers/allow_list.go) become packed bitmaps expanded on device; an
  allowList's docs are LOOKED UP in the snapshot's slot table (a cost that
  follows the posting), never matched against every live row;
- one filtered search below flat_search_cutoff takes a gather path: only
  the allowed rows are gathered and scored (flat_search.go:19 semantics,
  vectorized); a GROUP of slots, each under its own filter, is a bounded
  number of dispatches (search_by_vectors_multi_async: one per-slot gather
  program a row bucket, one scan whose queries carry their own masks), the
  hand-over priced for the whole group (docs/filters.md);
- mutation is staged host-side and flushed to the device in fixed-size
  chunks via dynamic_update_slice (no reallocation until capacity
  doubles — maintainance.go:31 geometric growth parity);
- reads are SNAPSHOT-ISOLATED (docs/concurrency.md): writers publish an
  immutable IndexSnapshot with one atomic reference swap, readers grab it
  lock-free and run the whole two-phase dispatch (enqueue on the snapshot,
  fetch outside any lock) — concurrent searches never convoy on the index
  mutex, and deletes/compression/compaction can't tear an in-flight
  dispatch because the snapshot pins its arrays.

Durability: an append-only binary vector log per shard (add/delete records),
replayed at startup — the analog of the HNSW commit log
(commit_logger.go:279-292) with only the records a flat store needs; a
snapshot+truncate cycle plays the role of condensing (condensor.go:32).
"""

from __future__ import annotations

import functools
import os
import struct
import threading
import time
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu import device
from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index import group_inputs, rescore_native
from weaviate_tpu.index.interface import (AllowList, SnapshotRetired,
                                          VectorIndex)
# the tier and the program of a dispatch are chosen in index/plan.py, once
from weaviate_tpu.index.plan import (KERNEL_FUNNEL, KERNEL_GMIN,
                                     DispatchHandle, PlanView, fetch_stamped,
                                     funnel_budgets, plan_search,
                                     rescore_depth, same_program_width)
# dispatch-shape recording for the perf-attribution plane: a
# costmodel.DispatchShape is built per dispatch ONLY while the tracer is
# up (tracing.get_tracer() gate — the zero-cost-when-disabled contract)
from weaviate_tpu.monitoring import costmodel, perf, tracing
# memory ledger (monitoring/memory.py): device components are stamped
# analytically (shapes x dtypes, zero syncs) at snapshot publish and at
# every buffer-mutating method; unconfigured => one comparison, nothing
# constructed. Search dispatches never touch it.
from weaviate_tpu.monitoring import memory
# ops-event journal (monitoring/incidents.py): write-path compress/compact
# phases and degraded-kernel fallbacks are journaled so an incident bundle
# shows what the index was doing around a symptom; unconfigured => one
# comparison, nothing constructed, emit() is exception-guarded internally
from weaviate_tpu.monitoring import incidents
# shadow recall auditing (monitoring/quality.py): the dispatch's handle
# carries its snapshot ONLY while an auditor is configured (one comparison,
# nothing constructed — the tracer's zero-cost contract), so the audit
# compares against the exact index state the live answer saw
from weaviate_tpu.monitoring import quality
from weaviate_tpu.monitoring.metrics import record_device_fallback
from weaviate_tpu.ops.distances import DISTANCE_FNS
# self-tuning control plane (serving/controller.py): the recall-guarded
# budget controller caps the fast-scan candidate depth (rescore_depth)
# against the shadow auditor's live recall EWMA — cap values come only
# from jit buckets so shapes stay cached; unconfigured => one
# comparison, the static default. controller imports nothing from the
# index layer, so no cycle.
from weaviate_tpu.serving import controller
# named fault-injection points (testing/faults.py): index.tpu.dispatch /
# index.tpu.finalize / index.tpu.alloc — one-comparison no-ops unless a
# harness is configured
from weaviate_tpu.testing import faults, sanitizers
# the one rescore-candidate bucket table (shared with the control plane's
# recall-guarded cap — serving/controller.py R_BUCKETS aliases it)
from weaviate_tpu.config.config import (IVF_LAYOUT_FILE, IVF_TOP_P_BUCKETS,
                                        IvfConfig,
                                        PQ4_FUNNEL_C_BUCKETS,
                                        PQ4_FUNNEL_RESCORE_BUCKETS,
                                        RESCORE_R_BUCKETS, ivf_from_env)
# the partition-pruned scan plane (ROADMAP item 3): k-means/PCA training
# helpers on the write path, probed-bucket search kernels on the read
# path (ops/ivf.py); every hook below is a one-comparison no-op while
# IVF_ENABLED is off
from weaviate_tpu.ops import ivf as ivf_ops
# the scan step both indexes run, and the two-program form that carries its
# TPU compiler option (ops/scan.py)
from weaviate_tpu.ops.scan import (SCAN_CHUNK as _SCAN_CHUNK,
                                   TPU_SCAN_OPTIONS, ScanProgram, scan_topk)
from weaviate_tpu.ops.topk import (bitmap_to_mask, merge_top_k,
                                   rescore_distances, retranslate_packed,
                                   translate_pack, translate_pack_slots,
                                   translate_pack_split, unpack_fused,
                                   unpack_fused_slots)

_CHUNK = 8192          # rows staged per device write (fixed => no recompiles)
_MIN_CAPACITY = 16384
_LOG_ADD = 1
_LOG_DELETE = 2
_LOG_MAGIC = b"WTVL"
_LOG_VERSION = 2  # v2 = per-record checksums + skip-ahead corrupt-region replay
# add records a run holds at most when a COMPRESSED shard replays its log: a
# run is copied out of the log (and again where cosine normalises it), and the
# rows' last home is the host too (`host_vecs`), so a log of one long run
# would hold the corpus four times over on its way in. An uncompressed replay
# lands its one long run as it always did.
_REPLAY_RUN_MAX = 8 * _CHUNK
# a restart rewrites the vector log from its live records when the dead ones
# (superseded adds, delete records) hold more than this share of the live
# records' bytes: the reference's commit-log condensor, run where the log is
# read anyway. Log bytes, and with them the time to restart, then follow the
# live rows: under 1.25 of them after every restart.
_LOG_CONDENSE_DEAD = 0.25

# query-batch padding buckets (limit distinct compiled shapes)
_B_BUCKETS = (1, 4, 16, 64, 256, 1024)

# -- IVF scan-plane toggle ----------------------------------------------------
# A process-wide override with an environment fallback: App applies
# Config.ivf here at init (token-scoped so a torn-down App reverts only
# its own setting); bare-library indexes read
# the IVF_* environment through config's own parser, so one knob can
# never read differently with vs without an App. Disabled (the default)
# => ivf_settings() is None and every IVF hook — write-path training,
# dispatch planning, health — is a one-comparison no-op.
_ivf_override: Optional[IvfConfig] = None
_ivf_env: Optional[IvfConfig] = None
_ivf_token: Optional[object] = None


def set_ivf_config(cfg: Optional[IvfConfig]) -> Optional[object]:
    """Install a process-wide IvfConfig override (App wiring; bench/tests
    flip it for A/B runs). None reverts to the IVF_* environment default,
    re-read fresh. Returns a token for unset_ivf_config — the
    still-ours unconfigure discipline."""
    global _ivf_override, _ivf_token, _ivf_env
    _ivf_override = cfg
    _ivf_token = object() if cfg is not None else None
    if cfg is None:
        _ivf_env = None
    return _ivf_token


def unset_ivf_config(token: Optional[object]) -> None:
    """Revert set_ivf_config's override iff `token` is still current."""
    global _ivf_override, _ivf_token, _ivf_env
    if token is not None and token is _ivf_token:
        _ivf_override = None
        _ivf_token = None
        _ivf_env = None


def ivf_settings() -> Optional[IvfConfig]:
    """The active IVF settings, or None when the plane is disabled (the
    dispatch/write-path gate: one reference read + one bool)."""
    global _ivf_env
    s = _ivf_override
    if s is not None:
        return s if s.enabled else None
    if _ivf_env is None:
        _ivf_env = ivf_from_env()
    return _ivf_env if _ivf_env.enabled else None


def _snap_top_p(v: int) -> int:
    """Largest IVF_TOP_P_BUCKETS entry <= v (floor snap, min bucket) —
    the same bounded-jit-shape discipline as the rescore cap. Beyond the
    ladder's top (large-nlist layouts legitimately probe hundreds of
    partitions) the snap continues on pow2 steps: still one static
    value per octave, so the jit cache stays bounded and a big layout's
    probe width is never silently collapsed to 128."""
    top = IVF_TOP_P_BUCKETS[-1]
    if v > top:
        p = top
        while p * 2 <= v:
            p *= 2
        return int(p)
    best = IVF_TOP_P_BUCKETS[0]
    for b in IVF_TOP_P_BUCKETS:
        if b <= v:
            best = b
    return int(best)


def _bucket_b(b: int) -> int:
    for s in _B_BUCKETS:
        if b <= s:
            return s
    return ((b + 1023) // 1024) * 1024


def _fit_capacity(needed: int) -> int:
    """The least capacity a slab may have that holds `needed` slots: a
    power of two up to one scan chunk, whole scan chunks past it (what
    `scan_topk` divides by). The tiled layout's capacity: its slots are
    nlist * cap_p whatever the growth ladder's next rung would be."""
    cap = _MIN_CAPACITY
    while cap < needed and cap < _SCAN_CHUNK:
        cap *= 2
    if cap < needed:
        cap = -(-needed // _SCAN_CHUNK) * _SCAN_CHUNK
    return cap


def _bucket_rows(n: int) -> int:
    """Pad gather row counts to pow2-ish buckets (min 128 for lane alignment)."""
    b = 128
    while b < n:
        b *= 2
    return b


# Every write kernel exists twice: as written here, a functional update that
# leaves the arrays it was given valid, and DONATING (`_IN_PLACE`, below),
# which overwrites them where they lie. Which one a write runs is
# `TpuVectorIndex._in_place`'s to say:
#
# - arrays no published IndexSnapshot holds (a restore's and a compaction's
#   rebuild, every chunk of a write after its first) are donated: nobody can
#   still be handed them, and a slab that is landed 8,192 rows a program
#   stays ONE generation instead of as many as the launch queue holds;
# - arrays the published snapshot holds are copied (copy-on-write: lock-free
#   readers may still dispatch on them) as long as the memory ledger says a
#   second generation fits the device beside the first;
# - where it does not fit (a slab over about 45% of the chip), the writer
#   retires the snapshot first: searches that are inside their enqueue on it
#   finish it (the writer waits for them: microseconds to a few ms of host
#   work, `/debug/perf` `writes.reader_wait_ms`), searches that come after
#   take the index lock and wait for the write's snapshot, and the write
#   goes in place. A search that was ENQUEUED before the write still gets
#   the rows it was dispatched on: the device runs its programs in order,
#   and a donated buffer is overwritten only after every program that was
#   enqueued on it has read it. What a reader is never handed is a deleted
#   array: it dispatches on a snapshot only between `_pin` and `_unpin`.
#
# What is retired and pinned is not one snapshot but the `ArrayLease` every
# snapshot published since the last donation shares: a delete that copies
# the tombstone bits publishes a snapshot whose slab is still its
# predecessor's, and a reader that kept the predecessor must be waited for
# and turned away exactly like one that holds the newest.
@jax.jit
def _write_rows(store, chunk, offset):
    return jax.lax.dynamic_update_slice(store, chunk, (offset, 0))


@jax.jit
def _write_norms(norms, chunk, offset):
    return jax.lax.dynamic_update_slice(norms, chunk, (offset,))


@jax.jit
def _set_tombstones(tombs, idx):
    # idx padded with an out-of-range sentinel; mode="drop" ignores those
    return tombs.at[idx].set(True, mode="drop")


@jax.jit
def _write_doc_pairs(s2d, idx, pairs):
    """Scatter doc-id word pairs into the device slot->doc table. idx is
    padded (to a _bucket_rows width, bounding jit shapes) with an
    out-of-range sentinel; mode="drop" ignores the padding rows."""
    return s2d.at[idx].set(pairs, mode="drop")


@jax.jit
def _write_slots(store, sq_norms, s2d, tombs, slots, rows, norms, pairs,
                 dead):
    """One small write as ONE program: `rows` into store slots `slots`
    (free slots handed out again and slots past the high-water mark
    alike), their squared norms (l2; None otherwise), their doc-id words
    into the slot->doc table, the slots' tombstone bits cleared and the
    bits of `dead` set. Every index array is padded to a `_bucket_rows`
    width with an out-of-range sentinel that mode="drop" ignores, so a
    batch of 100 uploads 128 rows, not a `_CHUNK`."""
    store = store.at[slots].set(rows.astype(store.dtype), mode="drop")
    if sq_norms is not None:
        sq_norms = sq_norms.at[slots].set(norms, mode="drop")
    s2d = s2d.at[slots].set(pairs, mode="drop")
    tombs = tombs.at[dead].set(True, mode="drop")
    tombs = tombs.at[slots].set(False, mode="drop")
    return store, sq_norms, s2d, tombs


# the donating twins: same function, same program name, the written arrays
# given away (docs/concurrency.md "When a write copies and when it goes in
# place")
_IN_PLACE = {
    _write_rows: jax.jit(_write_rows.__wrapped__, donate_argnums=0),
    _write_norms: jax.jit(_write_norms.__wrapped__, donate_argnums=0),
    _set_tombstones: jax.jit(_set_tombstones.__wrapped__, donate_argnums=0),
    _write_doc_pairs: jax.jit(_write_doc_pairs.__wrapped__,
                              donate_argnums=0),
    _write_slots: jax.jit(_write_slots.__wrapped__,
                          donate_argnums=(0, 1, 2, 3)),
}


@jax.jit
def _scatter_rows(arr, idx, rows):
    """Scatter padded row runs into a [capacity, d] device table (the
    IVF plane's low-dim PCA rows); idx padded with an out-of-range
    sentinel, mode="drop" ignores the padding. Non-donating like every
    write kernel — snapshots may pin the previous generation."""
    return arr.at[idx].set(rows, mode="drop")


@functools.partial(jax.jit, static_argnames=("fill",))
def _relayout(arr, src, fill=0):
    """A per-slot device array in a NEW slot order: slot i of the result is
    slot `src[i]` of `arr`, `fill` where `src[i]` lies past it (an empty
    slot of the tiled layout). One gather of whole rows into an array of
    `len(src)` slots; `arr` stays valid (snapshots may pin it)."""
    return jnp.take(arr, src, axis=0, mode="fill", fill_value=fill)


@jax.jit
def _scatter_bucket(buckets, parts, cols, slots):
    """Scatter freshly-assigned slots into their partitions' free bucket
    columns — the O(batch) incremental bucket update (parts padded with
    an out-of-range id, mode="drop"). Non-donating: snapshots pinning
    the previous bucket generation can never tear."""
    return buckets.at[parts, cols].set(slots, mode="drop")


# unwritten-slot sentinel: both 32-bit words set, so a (bugged) gather of
# an unwritten slot reassembles to 2**64-1 — the same "missing" id the
# kernels' idx -1 sentinel produces, never a plausible doc id
_S2D_FILL = 0xFFFFFFFF


# rows a host fetch of the slab asks for at a time (`_read_rows`)
_HOST_PIECE = 65536


@jax.jit
def _read_rows(store, offset):
    """`_HOST_PIECE` rows of the slab from `offset` (all of a slab that
    holds fewer): what a host fetch asks for a piece at a time
    (`TpuVectorIndex._fetch_rows`)."""
    return jax.lax.dynamic_slice_in_dim(
        store, offset, min(store.shape[0], _HOST_PIECE), 0)


@functools.partial(jax.jit, static_argnames=("new_cap",))
def _grow_pairs(arr, new_cap):
    out = jnp.full((new_cap, arr.shape[1]), _S2D_FILL, arr.dtype)
    return jax.lax.dynamic_update_slice(out, arr, (0, 0))


@functools.partial(jax.jit, static_argnames=("new_cap",))
def _grow_store(store, new_cap):
    out = jnp.zeros((new_cap, store.shape[1]), store.dtype)
    return jax.lax.dynamic_update_slice(out, store, (0, 0))


@functools.partial(jax.jit, static_argnames=("new_cap",))
def _grow_1d(arr, new_cap, fill):
    out = jnp.full((new_cap,), fill, arr.dtype)
    return jax.lax.dynamic_update_slice(out, arr, (0,))


def _doc_pairs(docs: np.ndarray, pad: int) -> np.ndarray:
    """[pad, 2] uint32: the lo/hi words of each int64 doc id, as the device
    slot->doc table holds them; rows past `len(docs)` are padding."""
    pairs = np.zeros((pad, 2), dtype=np.uint32)
    pairs[: len(docs)] = np.ascontiguousarray(
        docs.astype("<i8")).view("<u4").reshape(len(docs), 2)
    return pairs


def _pack(top: jax.Array, idx: jax.Array) -> jax.Array:
    """Pack (dists f32, idx i32) [B,k] each into one [B, 2k] i32 array so the
    host needs a single device->host fetch (the PCIe round trip costs far
    more than the bytes)."""
    return jnp.concatenate([jax.lax.bitcast_convert_type(top, jnp.int32), idx], axis=1)


def _fetch_packed(packed_dev, shape=None) -> np.ndarray:
    """The ONE blocking device->host fetch of a dispatch's finalize. With a
    perf shape attached (tracer up), the blocked time is the `device_wait`
    interval and the ledger's `device` stage — what finalize spends blocked
    on the device — and the `gather_hop` interval opens at its end
    (finalize closes it); without one (disabled path) this is exactly
    np.asarray."""
    if shape is None:
        return np.asarray(packed_dev)
    shape.end_hop()  # a second fetch of one finalize ends the first one's hop
    # a mesh dispatch says how many chips its one program spans
    spans = {"ndev": shape.ndev} if shape.ndev != 1 else {}
    wait = tracing.Phase("device_wait", rows=shape.batch, tier=shape.tier,
                         **spans)
    try:
        out = np.asarray(packed_dev)
    finally:
        end_ns = wait.end()
    shape.hop = tracing.Phase("gather_hop", rows=shape.batch)
    shape.fetches += 1  # the fused-dispatch invariant counts these
    shape.t_fetch = end_ns / 1e9
    shape.device_ms = (end_ns - wait.start_ns) / 1e6
    # duty-cycle anchor: the in-flight interval ends HERE, not at the
    # perf window's record call (hydration runs in between)
    shape.t_fetch_mono = time.monotonic()
    return out


_SCAN_STATICS = ("k", "metric", "use_allow", "exact", "active_chunks",
                 "rescore_r", "candidates")


def _scan_full(
    store, sq_norms, tombs, n, q, allow_words, k, metric, use_allow, exact=False,
    active_chunks=None, rescore_r=0, candidates=False,
):
    """The scan step (ops/scan.py scan_topk) over the whole store of one
    chip, its (dists, slots) packed for one fetch."""
    return _pack(*scan_topk(store, sq_norms, tombs, n, q, allow_words, k,
                            metric, use_allow, exact, active_chunks,
                            rescore_r, candidates))


def _search_full_fused(
    store, sq_norms, tombs, n, q, allow_words, s2d, k, metric, use_allow,
    exact=False, active_chunks=None, rescore_r=0, candidates=False,
):
    """_scan_full as the top-level program: the slot->doc translation
    runs in the SAME XLA program, so the one packed fetch carries final doc
    ids (ops/topk FUSED layout; with `candidates` the layout that keeps
    the slots beside them, translate_pack_slots)."""
    packed = _scan_full(store, sq_norms, tombs, n, q, allow_words, k,
                        metric, use_allow, exact, active_chunks, rescore_r,
                        candidates)
    if not candidates:
        return retranslate_packed(packed, s2d)
    kc = packed.shape[1] // 2
    return translate_pack_slots(
        jax.lax.bitcast_convert_type(packed[:, :kc], jnp.float32),
        packed[:, kc:], s2d)


_search_full_fused = ScanProgram(
    jax.jit(_search_full_fused, static_argnames=_SCAN_STATICS),
    jax.jit(_search_full_fused, static_argnames=_SCAN_STATICS,
            compiler_options=TPU_SCAN_OPTIONS))


# rows of the uint8 code matrix scored per PQ scan step ([B, chunk] f32
# accumulator + one [B, C] VMEM table per segment; codes stream from HBM)
_PQ_SCAN_CHUNK = 32768


def _pq_recon_topk(codes, recon_norms, tombs, n, codebook, rescore_store, q,
                   allow_words, k, r_chunk, metric, use_allow, exact=False,
                   active_chunks=None, do_rescore=True, rot=None):
    """PQ scan the MXU way -> ([B, k] dists, [B, k] slots, -1 = missing):
    asymmetric ADC distance equals the distance to
    the RECONSTRUCTED row (segments are disjoint dims), so each chunk's
    codes gather their centroids into a [chunk, D] block that feeds one
    bf16 matmul — identical math to the LUT scan
    (product_quantization.go:56-75 LookUp) at systolic-array throughput
    instead of per-element gather rates. ||recon||^2 is precomputed at
    encode time. Matmul metrics only (manhattan/hamming keep the LUT path).

    Candidate handling is collect-then-rescore: each chunk emits its top
    r_chunk (k-selection stays SMALL — large-k PartialReduce/top_k are the
    dominant cost on TPU), the per-chunk winners concatenate into one
    [B, nchunks*r_chunk] pool, and the pool is exact-rescored against the
    on-device bf16 rescore copy in the SAME program before the final
    top-k. No cross-chunk merge sorts, no host round trip."""
    cap, m = codes.shape
    _, c, ds = codebook.shape
    chunk = min(cap, _SCAN_CHUNK)
    nchunks = cap // chunk
    if active_chunks is not None:
        nchunks = max(1, min(nchunks, active_chunks))
    b = q.shape[0]
    flat_cb = codebook.reshape(m * c, ds).astype(jnp.bfloat16)
    seg_off = (jnp.arange(m, dtype=jnp.int32) * c)[None, :]

    ext = nchunks * chunk
    codes_c = codes[:ext].reshape(nchunks, chunk, m)
    norms_c = recon_norms[:ext].reshape(nchunks, chunk)
    tombs_c = tombs[:ext].reshape(nchunks, chunk)
    allow_c = allow_words[: ext // 32].reshape(nchunks, chunk // 32) if use_allow else None

    # OPQ: the ADC scan compares against ROTATED-space reconstructions, so
    # the query rotates too; the float rescore below stays in the original
    # space (the rescore store holds unrotated rows)
    qr = q if rot is None else jnp.matmul(
        q.astype(jnp.float32), rot, preferred_element_type=jnp.float32)
    qd = qr.astype(jnp.bfloat16)
    q_sq = jnp.sum(qr.astype(jnp.float32) ** 2, axis=-1, keepdims=True)

    def step(_, xs):
        ci, codes_l, norms_l, tombs_l = xs[0], xs[1], xs[2], xs[3]
        base = ci * chunk
        idx = codes_l.astype(jnp.int32) + seg_off          # [chunk, M]
        recon = jnp.take(flat_cb, idx, axis=0)             # [chunk, M, ds]
        recon = recon.reshape(chunk, m * ds)
        qx = jnp.matmul(qd, recon.T, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.DEFAULT)
        if metric == vi.DISTANCE_L2:
            d = jnp.maximum(q_sq - 2.0 * qx + norms_l[None, :], 0.0)
        elif metric == vi.DISTANCE_DOT:
            d = -qx
        else:  # cosine: queries normalized; recon approximates unit rows
            d = 1.0 - qx
        valid = jnp.logical_and(jnp.arange(chunk) + base < n, jnp.logical_not(tombs_l))
        if use_allow:
            valid = jnp.logical_and(valid, bitmap_to_mask(xs[4], chunk))
        d = jnp.where(valid[None, :], d, jnp.inf)
        if exact:
            neg, li = jax.lax.top_k(-d, r_chunk)
            td = -neg
        else:
            td, li = jax.lax.approx_min_k(d, r_chunk, recall_target=0.95)
        return None, (td, li + base)

    xs = [jnp.arange(nchunks), codes_c, norms_c, tombs_c]
    if use_allow:
        xs.append(allow_c)
    _, (tds, lis) = jax.lax.scan(step, None, tuple(xs))  # [nchunks, B, r_chunk]
    pool = nchunks * r_chunk
    cand_d = jnp.moveaxis(tds, 0, 1).reshape(b, pool)
    cand_i = jnp.moveaxis(lis, 0, 1).reshape(b, pool)
    if do_rescore:
        safe = jnp.clip(cand_i, 0, cap - 1)
        cand = jnp.take(rescore_store, safe, axis=0).astype(jnp.float32)
        qf = q.astype(jnp.float32)[:, None, :]
        if metric == vi.DISTANCE_L2:
            ed = jnp.sum((cand - qf) ** 2, axis=-1)
        elif metric == vi.DISTANCE_DOT:
            ed = -jnp.sum(cand * qf, axis=-1)
        else:
            ed = 1.0 - jnp.sum(cand * qf, axis=-1)
        cand_d = jnp.where(jnp.isinf(cand_d), jnp.inf, ed)
    neg, pos = jax.lax.top_k(-cand_d, k)
    top = -neg
    final = jnp.take_along_axis(cand_i, pos, axis=1)
    final = jnp.where(jnp.isinf(top), -1, final).astype(jnp.int32)
    return top, final


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "r_chunk", "metric", "use_allow", "exact", "active_chunks",
        "do_rescore",
    ),
)
def _search_pq_recon_fused(codes, recon_norms, tombs, n, codebook,
                           rescore_store, q, allow_words, s2d, k, r_chunk,
                           metric, use_allow, exact=False, active_chunks=None,
                           do_rescore=True, rot=None):
    """_pq_recon_topk as a top-level program, its winners translated to doc
    ids in the same program."""
    top, idx = _pq_recon_topk(codes, recon_norms, tombs, n, codebook,
                              rescore_store, q, allow_words, k, r_chunk,
                              metric, use_allow, exact, active_chunks,
                              do_rescore, rot)
    return translate_pack(top, idx, s2d)


def _pq_lut_topk(codes, tombs, n, lut, allow_words, r, use_allow, exact=False,
                 active_chunks=None):
    """PQ twin of _scan_full: scan the [cap, M] code matrix in HBM chunks,
    score each chunk via the additive LUT gather (compress/pq.py
    lut_scan_block — product_quantization.go:56-75 LookUp, vectorized),
    exact cross-chunk merge of the top-r candidate slots
    -> ([B, r] dists, [B, r] slots, -1 = missing)."""
    from weaviate_tpu.compress.pq import lut_scan_block

    cap, m = codes.shape
    chunk = min(cap, _PQ_SCAN_CHUNK)
    nchunks = cap // chunk
    if active_chunks is not None:
        nchunks = max(1, min(nchunks, active_chunks))
    b = lut.shape[0]

    ext = nchunks * chunk
    codes_c = codes[:ext].reshape(nchunks, chunk, m)
    tombs_c = tombs[:ext].reshape(nchunks, chunk)
    allow_c = allow_words[: ext // 32].reshape(nchunks, chunk // 32) if use_allow else None

    def step(carry, xs):
        best_d, best_i = carry
        ci, codes_l, tombs_l = xs[0], xs[1], xs[2]
        base = ci * chunk
        valid = jnp.logical_and(jnp.arange(chunk) + base < n, jnp.logical_not(tombs_l))
        if use_allow:
            valid = jnp.logical_and(valid, bitmap_to_mask(xs[3], chunk))
        d = lut_scan_block(codes_l.astype(jnp.int32), lut)
        d = jnp.where(valid[None, :], d, jnp.inf)
        if exact:
            neg, li = jax.lax.top_k(-d, r)
            td = -neg
        else:
            td, li = jax.lax.approx_min_k(d, r, recall_target=0.95)
        merged = merge_top_k(best_d, best_i, td, li + base, r)
        return merged, None

    init = (jnp.full((b, r), jnp.inf, jnp.float32), jnp.full((b, r), -1, jnp.int32))
    xs = [jnp.arange(nchunks), codes_c, tombs_c]
    if use_allow:
        xs.append(allow_c)
    (top, idx), _ = jax.lax.scan(step, init, tuple(xs))
    idx = jnp.where(jnp.isinf(top), -1, idx).astype(jnp.int32)
    return top, idx


@functools.partial(
    jax.jit, static_argnames=("r", "use_allow", "exact", "active_chunks")
)
def _search_pq_fused(codes, tombs, n, lut, allow_words, s2d, r, use_allow,
                     exact=False, active_chunks=None):
    """_pq_lut_topk as a top-level program, its winners translated to doc
    ids in the same program."""
    top, idx = _pq_lut_topk(codes, tombs, n, lut, allow_words, r, use_allow,
                            exact, active_chunks)
    return translate_pack(top, idx, s2d)


def _gather_live(rows, row_valid, tombs):
    """Row validity for the gather tier, tombstone-masked ON DEVICE with
    the dispatching snapshot's own `tombs`: the host-side allow-slot
    resolution is cached per (allow_token, n, capacity) — a key deletes
    do NOT change — so a cached slot list may include slots tombstoned
    since it was computed; the snapshot's device mask keeps every
    dispatch exact for the state it pinned (and an old snapshot's
    dispatch keeps returning its own pre-delete world)."""
    safe = jnp.clip(rows, 0, tombs.shape[0] - 1)
    return jnp.logical_and(row_valid,
                           jnp.logical_not(jnp.take(tombs, safe)))


def _gather_topk(dists, rows, row_valid, tombs, k):
    """Gather-tier selection: mask the [B, R] block, take the top k, and map
    the winners' POSITIONS in the uploaded `rows` block back to store slots
    on device -> ([B, k] dists, [B, k] slots, -1 = missing)."""
    masked = jnp.where(_gather_live(rows, row_valid, tombs)[None, :],
                       dists, jnp.inf)
    neg, idx = jax.lax.top_k(-masked, k)
    top = -neg
    safe = jnp.clip(idx, 0, rows.shape[0] - 1)
    slots = jnp.where(jnp.isinf(top), -1, jnp.take(rows, safe))
    return top, slots.astype(jnp.int32)


def _score_rows_topk(sub, q, rows, row_valid, tombs, k, metric):
    """Score an uploaded [R, D] row block against [B, D] queries (the gather
    path when the float store lives host-side under PQ). rows [R] carries
    each block position's store slot for the device tombstone mask."""
    dists = DISTANCE_FNS[metric](q.astype(sub.dtype), sub, None)
    return _gather_topk(dists, rows, row_valid, tombs, k)


def _gathered_topk(store, q, rows, row_valid, tombs, k, metric):
    """Gather path for small allowLists (flat_search.go:19 analog): score only
    the gathered rows. rows [R] int32 (padded), row_valid [R] bool; the
    snapshot's tombs mask rides the same program (see _gather_live)."""
    sub = jnp.take(store, rows, axis=0, mode="fill", fill_value=0)
    dists = DISTANCE_FNS[metric](q.astype(store.dtype), sub, None)
    return _gather_topk(dists, rows, row_valid, tombs, min(k, sub.shape[0]))


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _score_rows_fused(sub, q, rows, row_valid, tombs, s2d, k, metric):
    """_score_rows_topk as a top-level program, its winners translated to
    doc ids in the same program."""
    top, slots = _score_rows_topk(sub, q, rows, row_valid, tombs, k, metric)
    return translate_pack(top, slots, s2d)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _search_gathered_fused(store, q, rows, row_valid, tombs, s2d, k, metric):
    """_gathered_topk as a top-level program, its winners translated to doc
    ids in the same program."""
    top, slots = _gathered_topk(store, q, rows, row_valid, tombs, k, metric)
    return translate_pack(top, slots, s2d)


# -- per-slot gather: a group of filtered slots in one program ---------------
# A row-count bucket is four times the one below it (128, 512, ... ): one
# program a bucket, so a group of any mix of selectivities is at most a
# handful of gather dispatches, and the compiled shapes stay few.
_GATHER_MIN_ROWS = 128
# bytes of the [slots, rows, dim] block one loop step gathers (rows are
# lane-padded in HBM: 192 components occupy 256 lanes). The program loops
# over its slots in steps of this size, so a bucket's temporary is bounded
# whatever the group's width; a bucket whose ONE slot passes it is not
# served by the gather at all (the masked scan takes those slots).
_GATHER_BLOCK_BYTES = 64 << 20
# row indices one gather dispatch uploads (int32: 2 MB)
_GATHER_INDEX_ROWS = 1 << 19


def _scan_group_bucket(n: int, bb: int) -> int:
    """Queries of the group's one masked scan after padding: half the
    group's width bucket `bb`, or all of it. Two shapes a width, so that
    which one a request compiles does not follow the draw of its filters
    (how many of a group's slots the plan sends to the scan varies by tens
    from one request to the next); a padded query costs its zero words'
    upload."""
    half = max(bb // 2, _B_BUCKETS[0])
    return half if n <= half else bb


def _gather_row_bucket(n: int) -> int:
    r = _GATHER_MIN_ROWS
    while r < n:
        r *= 4
    return r


def _gather_step_slots(r: int, dim: int) -> int:
    """Slots one loop step of the bucket-`r` gather program scores (a power
    of two; 0: one slot's block alone passes _GATHER_BLOCK_BYTES)."""
    row_bytes = -(-dim // 128) * 128 * 4
    g = _GATHER_BLOCK_BYTES // (r * row_bytes)
    return 1 << (g.bit_length() - 1) if g else 0


def _gather_slots(bb: int, r: int) -> int:
    """Slots of a group of width bucket `bb` that one bucket-`r` gather
    dispatch takes: its slot dimension (the program loops over the real
    ones alone)."""
    return min(bb, max(_GATHER_INDEX_ROWS // r, 1))


def gather_max_rows(dim: int, capacity: int) -> int:
    """The largest row bucket the per-slot gather serves at this width."""
    r = _GATHER_MIN_ROWS
    while r * 4 <= capacity and _gather_step_slots(r * 4, dim):
        r *= 4
    return r


@jax.jit
def _pad_rows(store):
    """The store with its rows padded to whole lanes (zero columns)."""
    return jnp.pad(store, ((0, 0), (0, -store.shape[1] % 128)))


@jax.jit
def _split_doc_pairs(s2d):
    """[capacity, 2] doc-id words -> (lo [capacity], hi [capacity]): the
    layout a per-winner lookup reads without re-laying the table out."""
    return s2d[:, 0], s2d[:, 1]


@functools.partial(jax.jit, static_argnames=("k", "metric", "step"))
def _search_gathered_multi(store, q, rows, counts, n_slots, tombs, s2d_lo,
                           s2d_hi, k, metric, step):
    """The gather tier for a GROUP of filtered slots: slot s scores only its
    own rows. q [S, D], rows [S, R] int32 (slot s's store slots, padded),
    counts [S] (how many of them are real), n_slots (how many of the S are
    real: the loop runs over those alone, `step` slots at a time, so a
    padded slot costs nothing and the gathered block is bounded). Distances
    are elementwise float32 (exact), the snapshot's tombs mask rides the
    program (_gather_live's contract), winners leave as doc ids."""
    s, r = rows.shape
    pos = jnp.arange(r, dtype=jnp.int32)

    def body(c, carry):
        top, idx = carry
        at = c * step
        rows_c = jax.lax.dynamic_slice_in_dim(rows, at, step, 0)
        q_c = jax.lax.dynamic_slice_in_dim(q, at, step, 0)
        cnt_c = jax.lax.dynamic_slice_in_dim(counts, at, step, 0)
        sub = jnp.take(store, rows_c, axis=0)           # [step, R, D]
        d = rescore_distances(sub, q_c, metric)         # [step, R] exact f32
        live = jnp.logical_and(pos[None, :] < cnt_c[:, None],
                               jnp.logical_not(jnp.take(tombs, rows_c)))
        neg, p = jax.lax.top_k(-jnp.where(live, d, jnp.inf), k)
        slots = jnp.where(jnp.isinf(neg), -1,
                          jnp.take_along_axis(rows_c, p, axis=1))
        return (jax.lax.dynamic_update_slice_in_dim(top, -neg, at, 0),
                jax.lax.dynamic_update_slice_in_dim(idx, slots, at, 0))

    init = (jnp.full((s, k), jnp.inf, jnp.float32),
            jnp.full((s, k), -1, jnp.int32))
    top, idx = jax.lax.fori_loop(0, (n_slots + step - 1) // step, body, init)
    return translate_pack_split(top, idx, s2d_lo, s2d_hi)


def _slot_words(slots: np.ndarray, capacity: int,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """Ascending store slots -> the packed uint32 filter words over
    [capacity] slots every masked-scan kernel consumes (bit s % 32 of word
    s // 32), set in `out` (all zero) when given. The bits of one word are
    OR-ed in one reduceat over the slots, so the work follows the slots,
    and the words themselves are the only thing capacity-sized."""
    words = np.zeros(capacity // 32, np.uint32) if out is None else out
    if slots.size * 32 > capacity:
        # more than a slot a word: a bool mask packed whole is the cheaper
        mask = np.zeros(capacity, bool)
        mask[slots] = True
        words[:] = np.packbits(mask.reshape(-1, 32), axis=1,
                               bitorder="little").view(np.uint32).ravel()
    elif slots.size:
        s = slots.astype(np.uint32, copy=False)
        word = s >> np.uint32(5)
        starts = np.flatnonzero(np.r_[True, word[1:] != word[:-1]])
        words[word[starts]] = np.bitwise_or.reduceat(
            np.uint32(1) << (s & np.uint32(31)), starts)
    return words


def _host_distances(cand: np.ndarray, q: np.ndarray, metric: str) -> np.ndarray:
    """Float32 distances of gathered float32 rows on the host: cand
    [B, R, D] (scratch: overwritten), q [B, D] -> [B, R]. The arithmetic of
    ops/topk.rescore_distances (cosine: rows and queries arrive
    normalized), in numpy calls that let go of the GIL."""
    if metric in (vi.DISTANCE_L2, vi.DISTANCE_MANHATTAN):
        np.subtract(cand, q[:, None, :], out=cand)
        if metric == vi.DISTANCE_L2:
            return np.einsum("brd,brd->br", cand, cand)
        return np.abs(cand, out=cand).sum(axis=-1)
    dots = np.matmul(cand, q[:, :, None])[..., 0]
    return -dots if metric == vi.DISTANCE_DOT else np.float32(1.0) - dots


def _prep_bulk_run(ids: np.ndarray, vecs: np.ndarray, metric: str, known_fn):
    """Shared restore-run preparation for the single-chip and mesh indexes:
    f32 cast, cosine normalization, keep-last dedup of in-run duplicate
    docs, and the indices of docs the index already knows (those must take
    the per-record path so their old slots tombstone).
    -> (ids int64 [n], vecs f32 [n, d], known_indices list)."""
    vecs = np.asarray(vecs, np.float32)
    if metric == vi.DISTANCE_COSINE:
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        if vecs.flags.writeable:
            # the replay's own copy of the run (`_replay_v2`): divided where
            # it lies, not into a second array the size of the corpus
            vecs /= nrm
        else:
            vecs = vecs / nrm
    ids64 = ids.astype(np.int64)
    if len(np.unique(ids64)) != len(ids64):
        # keep-last within the run (later records overwrite earlier)
        _, last_rev = np.unique(ids64[::-1], return_index=True)
        order = np.sort(len(ids64) - 1 - last_rev)
        ids64, vecs = ids64[order], vecs[order]
    known = [i for i, d in enumerate(ids64.tolist()) if known_fn(d)]
    return ids64, vecs, known


def restore_record(mode: str, rows: int, st, sums, replay_stats: dict,
                   **extra) -> dict:
    """What one restore did, for `/debug/index` `restore`, the
    `write_phase` incident of scope `restore` and nobody else: `seconds` is
    the `vector.restore` stage `st` itself (`tracing.stage`), `stages` the
    flat parts of the restart timeline that lie inside it (`sums`, the
    restore's `tracing.StageSums`; monitoring/perf.py STARTUP_PARTS),
    `replay` what the log's replay skipped. One shape for every index
    type."""
    return {
        "mode": mode,
        "rows": rows,
        "seconds": round(st.seconds, 3),
        "stages": {part: sums.seconds(*perf.STARTUP_PARTS[part])
                   for part in ("log", "land", "drain")
                   + (("ivf",) if "ivf" in sums.sums else ())},
        "replay": dict(replay_stats),
        **extra,
    }


class VectorLog:
    """Append-only durability log for the device store (commit-log analog).

    v2 record layout (header magic WTVL, version 2):
      ADD:    op(1)=1 | doc_id(<Q) | dim(<I) | ck(<I) | dim x <f4 payload
      DELETE: op(1)=2 | doc_id(<Q) | ck(<I)
    where ck is the 32-bit additive byte checksum of every record byte
    EXCEPT the ck field itself. An additive sum (not crc32) is deliberate:
    it detects any single flipped byte, and the vectorized replay can
    verify a million records with two numpy row-sums instead of a Python
    crc loop. The checksum is what makes mid-log corruption DETECTABLE,
    which in turn makes skip-ahead replay safe: on a bad record, replay
    scans forward for the next offset where a whole record parses AND
    checksums (false resync ~2^-32 per candidate) and continues from
    there, counting the skipped bytes — the flat-store analog of the
    reference's corrupt-region repair (corrupt_commit_logs_fixer.go:1),
    which replays around damage rather than abandoning everything after
    it. v1 logs (no checksum) still replay with the old
    stop-at-first-bad-record behavior.
    """

    def __init__(self, path: str):
        self.path = path
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fresh = True
        if os.path.exists(path):
            # a crash can leave a torn/corrupt tail; anything appended after
            # an unreadable region would be durably written yet unreachable —
            # silent data loss on the next restart. For v2 logs the cut point
            # is the end of the LAST valid record (mid-file damage stays in
            # place for skip-ahead replay to route around); for v1 logs it is
            # the first bad record, as before. A stage of a restart's
            # timeline: the walk is a seek and a read a record.
            size = os.path.getsize(path)
            with tracing.stage("log.check", bytes=size):
                valid = self._valid_prefix_len(path)
                if valid < size:
                    cut = valid
                    if self._version(path) >= 2:
                        cut = max(valid, self._last_valid_end(path))
                    with open(path, "r+b") as f:
                        f.truncate(cut)
                    fresh = cut == 0
                else:
                    fresh = valid == 0
                if not fresh and self._version(path) < 2:
                    # one-time in-place upgrade: appends always write v2
                    # checksummed records, and mixing formats within one
                    # file would make v1 replay mis-parse every appended
                    # vector (checksum bytes read as payload) — rewrite the
                    # whole log as v2 before reusing it.
                    self._upgrade_v1(path)
        self._f = open(path, "ab")
        if fresh:
            self._f.write(_LOG_MAGIC + struct.pack("<H", _LOG_VERSION))
            self._f.flush()
        # the file's bytes, and the records in it (a restore that replays
        # the file says how many it found; appends count themselves)
        self.bytes = os.path.getsize(path)
        self.records = 0

    @staticmethod
    def report_replay_stats(path: str, stats: dict) -> None:
        """One shared skip-report so the single-chip and mesh restores (and
        any future caller) cannot drift in what they tell the operator."""
        if stats.get("skipped_bytes"):
            import logging

            logging.getLogger(__name__).warning(
                "vector log %s: skipped %d corrupt byte(s) across %d "
                "region(s) during replay; records inside the damage are "
                "lost, everything outside it was recovered",
                path, stats["skipped_bytes"], stats.get("skipped_regions", 0))

    @staticmethod
    def _upgrade_v1(path: str) -> None:
        tmp = path + ".upgrade"
        with open(tmp, "wb") as f:
            f.write(_LOG_MAGIC + struct.pack("<H", _LOG_VERSION))
            for op, doc_id, vec in VectorLog.replay(path):
                if op == "add":
                    f.write(VectorLog._enc_add(doc_id, vec))
                else:
                    head = struct.pack("<BQ", _LOG_DELETE, doc_id)
                    f.write(head + struct.pack("<I", VectorLog._sum32(head)))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # -- format helpers ------------------------------------------------------

    @staticmethod
    def _version(path: str) -> int:
        with open(path, "rb") as f:
            head = f.read(6)
        if len(head) < 6 or head[:4] != _LOG_MAGIC:
            return 0
        return struct.unpack_from("<H", head, 4)[0]

    @staticmethod
    def _sum32(*parts) -> int:
        s = 0
        for p in parts:
            s += int(np.frombuffer(p, np.uint8).sum(dtype=np.uint64))
        return s & 0xFFFFFFFF

    @staticmethod
    def _enc_add(doc_id: int, v: np.ndarray) -> bytes:
        head = struct.pack("<BQI", _LOG_ADD, doc_id, v.shape[0])
        payload = v.tobytes()
        return head + struct.pack("<I", VectorLog._sum32(head, payload)) + payload

    @staticmethod
    def _validate_v2(data, off: int, n: int):
        """If a valid v2 record starts at off, return (op, end); else None."""
        op = data[off]
        if op == _LOG_ADD:
            if off + 17 > n:
                return None
            dim, ck = struct.unpack_from("<II", data, off + 9)
            if not 0 < dim <= 65536:
                return None
            end = off + 17 + 4 * dim
            if end > n:
                return None
            if VectorLog._sum32(data[off : off + 13], data[off + 17 : end]) != ck:
                return None
            return (_LOG_ADD, end)
        if op == _LOG_DELETE:
            if off + 13 > n:
                return None
            (ck,) = struct.unpack_from("<I", data, off + 9)
            if VectorLog._sum32(data[off : off + 9]) != ck:
                return None
            return (_LOG_DELETE, off + 13)
        return None

    @staticmethod
    def _resync_v2(data, buf: np.ndarray, off: int, n: int):
        """Smallest off' >= off where a whole v2 record parses and checksums,
        or None. Candidate positions (op byte is 1 or 2) are found with one
        vectorized pass per 1 MiB window; each candidate pays one record-sized
        checksum, so the scan cost is bounded by the damaged span, not the
        log size."""
        pos = off
        while pos < n:
            win = min(pos + (1 << 20), n)
            cands = np.flatnonzero((buf[pos:win] == _LOG_ADD) | (buf[pos:win] == _LOG_DELETE))
            for idx in cands.tolist():
                p = pos + idx
                if VectorLog._validate_v2(data, p, n) is not None:
                    return p
            pos = win
        return None

    @staticmethod
    def _valid_prefix_len(path: str) -> int:
        """Byte length of the longest parseable record prefix. 0 means the
        header itself is unusable (the file must be re-initialized). Scans
        record HEADERS only (seek past payloads), so a multi-GB log costs one
        sequential header walk, not a whole-file read. Does NOT verify
        checksums — it bounds where the cheap walk stops, not data integrity
        (replay re-verifies every record)."""
        with open(path, "rb") as f:
            size = os.fstat(f.fileno()).st_size
            head = f.read(6)
            if len(head) < 6 or head[:4] != _LOG_MAGIC:
                return 0
            v2 = struct.unpack_from("<H", head, 4)[0] >= 2
            add_hdr = 17 if v2 else 13
            del_len = 13 if v2 else 9
            off = 6
            while off < size:
                f.seek(off)
                hdr = f.read(add_hdr)
                if not hdr:
                    return off
                op = hdr[0]
                if op == _LOG_ADD:
                    if len(hdr) < add_hdr:
                        return off
                    (dim,) = struct.unpack_from("<I", hdr, 9)
                    if v2 and not 0 < dim <= 65536:
                        return off
                    end = off + add_hdr + 4 * dim
                    if end > size:
                        return off
                    off = end
                elif op == _LOG_DELETE:
                    if len(hdr) < del_len:
                        return off
                    off += del_len
                else:
                    return off
            return off

    @staticmethod
    def _last_valid_end(path: str) -> int:
        """End offset of the last valid v2 record anywhere in the file (the
        truncation point that preserves recoverable data past mid-file
        damage). Walks record offsets only; vectors are never materialized."""
        with open(path, "rb") as f:
            data = f.read()
        n = len(data)
        if n < 6 or data[:4] != _LOG_MAGIC:
            return 0
        buf = np.frombuffer(data, np.uint8)
        off, last = 6, 6
        while off < n:
            v = VectorLog._validate_v2(data, off, n)
            if v is None:
                nxt = VectorLog._resync_v2(data, buf, off + 1, n)
                if nxt is None:
                    return last
                off = nxt
                continue
            off = last = v[1]
        return last

    # -- appends -------------------------------------------------------------

    def _write(self, data: bytes, records: int) -> None:
        self._f.write(data)
        self.bytes += len(data)
        self.records += records

    def append_add(self, doc_id: int, vector: np.ndarray) -> None:
        v = np.ascontiguousarray(vector, dtype=np.float32)
        self._write(self._enc_add(doc_id, v), 1)

    @staticmethod
    def _enc_add_batch(doc_ids: np.ndarray, vectors: np.ndarray) -> bytes:
        """A run of add records, the per-record checksums computed as two
        numpy row-sums."""
        n, dim = vectors.shape
        rec_len = 17 + 4 * dim
        buf = np.zeros((n, rec_len), np.uint8)
        buf[:, 0] = _LOG_ADD
        buf[:, 1:9] = doc_ids.astype("<u8").view(np.uint8).reshape(n, 8)
        buf[:, 9:13] = np.frombuffer(struct.pack("<I", dim), np.uint8)
        buf[:, 17:] = np.ascontiguousarray(vectors, dtype="<f4").view(np.uint8).reshape(n, 4 * dim)
        sums = buf[:, :13].sum(axis=1, dtype=np.uint64) + buf[:, 17:].sum(axis=1, dtype=np.uint64)
        buf[:, 13:17] = (sums & 0xFFFFFFFF).astype("<u4").view(np.uint8).reshape(n, 4)
        return buf.tobytes()

    def append_add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        """Vectorized bulk append: one write() for the whole batch."""
        self._write(self._enc_add_batch(doc_ids, vectors), len(doc_ids))

    def append_delete(self, doc_id: int) -> None:
        head = struct.pack("<BQ", _LOG_DELETE, doc_id)
        self._write(head + struct.pack("<I", self._sum32(head)), 1)

    def flush(self) -> None:
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        try:
            self._f.flush()
        finally:
            self._f.close()

    @staticmethod
    def _read(path: str, sums=None) -> bytes:
        """The whole log as one `bytes`: a restore's `log.read` stage."""
        with tracing.piece_of(sums, "log.read"), open(path, "rb") as f:
            return f.read()

    @staticmethod
    def replay(path: str, stats: Optional[dict] = None, sums=None):
        """Yield ('add', doc_id, vec) / ('delete', doc_id, None). v2 logs
        verify per-record checksums and SKIP corrupt regions (resuming at the
        next valid record, with the loss counted in `stats`); v1 logs keep
        the old stop-at-first-bad-record behavior. A torn tail is tolerated
        either way (corrupt_commit_logs_fixer.go behavior). `sums`: the
        restore's stage sums (`tracing.StageSums`), to which the file's
        read is `log.read`."""
        if not os.path.exists(path):
            return
        data = VectorLog._read(path, sums)
        if data[:4] != _LOG_MAGIC or len(data) < 6:
            return
        if struct.unpack_from("<H", data, 4)[0] >= 2:
            yield from VectorLog._replay_v2(data, stats, batched=False)
            return
        off = 6
        n = len(data)
        while off < n:
            try:
                op = data[off]
                if op == _LOG_ADD:
                    doc_id, dim = struct.unpack_from("<QI", data, off + 1)
                    start = off + 13
                    end = start + dim * 4
                    if end > n:
                        return  # torn write
                    vec = np.frombuffer(data, "<f4", count=dim, offset=start).copy()
                    yield ("add", doc_id, vec)
                    off = end
                elif op == _LOG_DELETE:
                    (doc_id,) = struct.unpack_from("<Q", data, off + 1)
                    yield ("delete", doc_id, None)
                    off += 9
                else:
                    return  # corrupt record type: stop replay
            except struct.error:
                return

    @staticmethod
    def replay_batches(path: str, stats: Optional[dict] = None,
                       run_max: Optional[int] = None, sums=None):
        """Vectorized replay: maximal runs of same-dim add records parse as
        ONE numpy view — ('add', ids [n] u64, vecs [n, dim] f32) — with
        ('delete', doc_id, None) singles in order. Same corruption tolerance
        as replay(); restores parse the log ~10x faster this way. `run_max`
        cuts a v2 log's runs to that many records; `sums` as in replay()."""
        if not os.path.exists(path):
            return
        data = VectorLog._read(path, sums)
        if data[:4] != _LOG_MAGIC or len(data) < 6:
            return
        if struct.unpack_from("<H", data, 4)[0] >= 2:
            yield from VectorLog._replay_v2(data, stats, batched=True,
                                            run_max=run_max)
            return
        buf = np.frombuffer(data, np.uint8)
        off = 6
        n = len(data)
        while off < n:
            try:
                op = data[off]
                if op == _LOG_ADD:
                    if off + 13 > n:
                        return  # torn header
                    doc_id, dim = struct.unpack_from("<QI", data, off + 1)
                    rec = 13 + 4 * dim
                    max_run = (n - off) // rec
                    if max_run == 0:
                        return  # torn vector payload
                    view = buf[off : off + max_run * rec].reshape(max_run, rec)
                    ok = view[:, 0] == _LOG_ADD
                    dim_b = np.frombuffer(struct.pack("<I", dim), np.uint8)
                    ok &= (view[:, 9:13] == dim_b).all(axis=1)
                    run = max_run if bool(ok.all()) else max(1, int(np.argmin(ok)))
                    sel = view[:run]
                    ids = np.ascontiguousarray(sel[:, 1:9]).view("<u8").ravel()
                    vecs = np.ascontiguousarray(sel[:, 13:]).view("<f4").reshape(run, dim)
                    yield ("add", ids, vecs)
                    off += run * rec
                elif op == _LOG_DELETE:
                    if off + 9 > n:
                        return
                    (doc_id,) = struct.unpack_from("<Q", data, off + 1)
                    yield ("delete", doc_id, None)
                    off += 9
                else:
                    return  # corrupt record type: stop replay
            except struct.error:
                return

    @staticmethod
    def _replay_v2(data: bytes, stats: Optional[dict], batched: bool,
                   run_max: Optional[int] = None):
        """Shared v2 walk. Valid add-runs still parse as one numpy view (the
        checksum column verifies vectorized, two row-sums per run); any
        record that fails validation starts a skip-ahead scan, and the
        skipped span is accumulated into `stats` so callers can REPORT the
        loss instead of silently shrinking the store."""
        buf = np.frombuffer(data, np.uint8)
        off = 6
        n = len(data)

        def _skip(start: int):
            nxt = VectorLog._resync_v2(data, buf, start + 1, n)
            end = n if nxt is None else nxt
            if stats is not None:
                stats["skipped_bytes"] = stats.get("skipped_bytes", 0) + (end - start)
                stats["skipped_regions"] = stats.get("skipped_regions", 0) + 1
            return nxt

        while off < n:
            op = data[off]
            if op == _LOG_ADD and off + 17 <= n:
                dim, ck0 = struct.unpack_from("<II", data, off + 9)
                rec = 17 + 4 * dim
                max_run = (n - off) // rec if 0 < dim <= 65536 else 0
                if run_max:
                    max_run = min(max_run, run_max)
                if max_run == 0:
                    off = _skip(off)
                    if off is None:
                        return
                    continue
                # how far the run of same-width add records goes, by their
                # op and dim bytes alone and in growing probes: a log of
                # re-puts is thousands of short runs between deletes, and a
                # checksum of everything that FOLLOWS each of them made its
                # replay quadratic in the writes
                dim_b = np.frombuffer(struct.pack("<I", dim), np.uint8)
                probe = min(max_run, 4096)
                while True:
                    view = buf[off : off + probe * rec].reshape(probe, rec)
                    ok = view[:, 0] == _LOG_ADD
                    ok &= (view[:, 9:13] == dim_b).all(axis=1)
                    if probe == max_run or not bool(ok.all()):
                        break
                    probe = min(max_run, probe * 8)
                if not bool(ok.all()):
                    view = view[: int(np.argmin(ok))]
                sums = view[:, :13].sum(axis=1, dtype=np.uint64) + view[:, 17:].sum(
                    axis=1, dtype=np.uint64
                )
                stored = np.ascontiguousarray(view[:, 13:17]).view("<u4").ravel()
                ok = (sums & 0xFFFFFFFF) == stored
                run = len(view) if bool(ok.all()) else int(np.argmin(ok))
                if run == 0:  # first record is corrupt — resync
                    off = _skip(off)
                    if off is None:
                        return
                    continue
                sel = view[:run]
                ids = np.ascontiguousarray(sel[:, 1:9]).view("<u8").ravel()
                vecs = np.ascontiguousarray(sel[:, 17:]).view("<f4").reshape(run, dim)
                if batched:
                    yield ("add", ids, vecs)
                else:
                    for i in range(run):
                        yield ("add", int(ids[i]), vecs[i].copy())
                off += run * rec
            elif op == _LOG_DELETE and off + 13 <= n:
                if VectorLog._validate_v2(data, off, n) is None:
                    off = _skip(off)
                    if off is None:
                        return
                    continue
                (doc_id,) = struct.unpack_from("<Q", data, off + 1)
                yield ("delete", doc_id, None)
                off += 13
            else:
                off = _skip(off)
                if off is None:
                    return

    def rewrite(self, entries) -> None:
        """Condense: atomically rewrite the log with only live entries."""
        self._rewrite(
            (self._enc_add(d, np.ascontiguousarray(v, np.float32)), 1)
            for d, v in entries)

    def rewrite_runs(self, runs) -> None:
        """`rewrite` from runs of (doc ids [n], vectors [n, dim]), encoded a
        `_REPLAY_RUN_MAX` of records at a time."""
        self._rewrite(
            (self._enc_add_batch(ids[off : off + _REPLAY_RUN_MAX],
                                 vecs[off : off + _REPLAY_RUN_MAX]),
             min(_REPLAY_RUN_MAX, len(ids) - off))
            for ids, vecs in runs
            for off in range(0, len(ids), _REPLAY_RUN_MAX))

    def _rewrite(self, encoded) -> None:
        """`encoded`: (bytes of whole records, how many) pairs."""
        tmp = self.path + ".tmp"
        size, records = 6, 0
        with open(tmp, "wb") as f:
            f.write(_LOG_MAGIC + struct.pack("<H", _LOG_VERSION))
            for data, count in encoded:
                f.write(data)
                size += len(data)
                records += count
            f.flush()
            os.fsync(f.fileno())
        self._f.close()
        os.replace(tmp, self.path)
        self._f = open(self.path, "ab")
        self.bytes, self.records = size, records


def _live_runs(events, stats: dict):
    """A replay (`VectorLog.replay_batches`) -> its add runs less every
    record that is dead at the log's end: an add whose doc a later record
    deletes or adds again. No delete is yielded: what it deleted is not.
    A restore then lands the live rows and nothing else, however many
    writes the log has seen. `stats` gets `records` and `dead_records`.
    The replay is read to its end first (the runs are views and copies of
    a file that is in memory whole either way)."""
    events = list(events)
    docs, adds, pos = [], [], 0
    for op, ids, _ in events:
        if op == "add":
            docs.append(np.asarray(ids).astype(np.int64))
            adds.append(np.ones(len(ids), bool))
        else:
            docs.append(np.array([ids], np.uint64).astype(np.int64))
            adds.append(np.zeros(1, bool))
    stats["records"] = stats["dead_records"] = 0
    if not docs:
        return []
    doc, is_add = np.concatenate(docs), np.concatenate(adds)
    # the last record of every doc, by a stable sort on the doc id
    order = np.argsort(doc, kind="stable")
    last = order[np.r_[doc[order][1:] != doc[order][:-1], True]]
    live = np.zeros(len(doc), bool)
    live[last[is_add[last]]] = True
    stats["records"] = int(len(doc))
    stats["dead_records"] = int(len(doc) - live.sum())
    out = []
    for op, ids, vecs in events:
        width = len(ids) if op == "add" else 1
        keep, pos = live[pos : pos + width], pos + width
        if op != "add" or not keep.any():
            continue
        out.append((ids, vecs) if keep.all() else (ids[keep], vecs[keep]))
    return out


class PersistedLayout(NamedTuple):
    """`ivf.npz` as a restore wants it (`TpuVectorIndex._ivf_load`): the
    docs sorted, `slots` the slot the layout recorded for each."""

    centroids: np.ndarray     # [nlist, D] f32
    cap_p: int
    gen: int
    trained_n: int
    sized_n: int              # rows the tiles were sized for
    docs: np.ndarray          # [rows] int64, ascending
    slots: np.ndarray         # [rows] int64


class ArrayLease:
    """What the snapshots published between two donations have in common:
    device arrays that may be the same objects from one snapshot to the
    next (a delete copies the tombstone bits and publishes the slab it
    found). So the searches inside their enqueue are counted here, on all of
    those snapshots together (`pins`), and a writer that takes the arrays
    back takes them from all of them at once (`retired`). Both under the
    index's `_inflight_lock`: `_pin`, `_unpin`, `_retire_snapshot`."""

    __slots__ = ("pins", "retired")

    def __init__(self):
        self.pins = 0
        self.retired = False


def ivf_probe(metric: str, snap, k: int) -> Optional[tuple[int, int]]:
    """(top_p, prefilter_c) for an IVF dispatch on `snap` (either
    index's: the mesh trains no prefilter and gets 0), or None to
    take the flat path. None whenever the plane is disabled, the
    snapshot carries no trained layout, or the metric has no
    matmul/rescore form — the first two checks are one comparison
    each (the zero-hop contract). The effective probe count is the
    configured value capped by the controller's recall-guarded
    budget (serving/controller.py ivf_top_p_cap) and snapped to the
    bounded IVF_TOP_P_BUCKETS ladder (or to nlist exactly when the
    request covers every partition), so top_p — a jit static — can
    only take bounded values."""
    if snap.ivf_meta is None:
        return None
    s = ivf_settings()
    if s is None:
        return None
    if metric not in ivf_ops.MATMUL_METRICS:
        return None
    nlist, cap_p, _gen = snap.ivf_meta
    req = s.top_p if s.top_p > 0 else max(1, nlist // 16)
    req = min(req, nlist)
    eff = max(1, min(req, controller.ivf_top_p_cap(req)))
    if eff < nlist:
        eff = min(_snap_top_p(eff), nlist)
    # deep-k coverage: a probe set under ~4k candidates starves the
    # final selection (the flat fast-scan's slack rationale) — widen
    # up the ladder before dispatching; neither the config nor the
    # controller cap may shrink a query below its own k
    while eff < nlist and eff * cap_p < 4 * k:
        nxt = _snap_top_p(min(eff * 2, nlist))
        eff = nlist if nxt <= eff else nxt
    pre_c = 0
    if getattr(snap, "ivf_pca_proj", None) is not None:
        r = eff * cap_p
        # auto: 8k floor for selection quality, r/8 cut, capped at
        # 2048 — past that the full-dim pass stops being the
        # bottleneck the prefilter exists to shrink
        pc = s.prefilter_c if s.prefilter_c > 0 \
            else max(8 * k, min(2048, r // 8))
        pc = _bucket_rows(min(pc, r))  # pow2: bounded jit shapes
        if pc < r:
            pre_c = pc
    return (eff, pre_c)


class IndexSnapshot:
    """One immutable published generation of the device state a search
    dispatch reads.

    Writers stage under the index lock and publish a NEW snapshot with one
    atomic reference swap (`TpuVectorIndex._publish_snapshot`); readers grab
    the current reference lock-free and dispatch on it. The snapshot's
    references pin its arrays: a concurrent delete/compress/compact swaps
    the index's attributes to new arrays but can never tear an in-flight
    dispatch, because

      - a write program is given the arrays of the PUBLISHED snapshot to
        overwrite only after its `lease` was retired (`ArrayLease`: no
        search can pin a snapshot of that lease any more, and none is
        still between `_pin` and `_unpin` on one); until then every update
        REPLACES the array object and the old buffer stays valid until the
        last snapshot holding it drops (module comment above
        `_write_rows`), and
      - the host-side `host_tombs` mirror is copy-on-written by any
        writer that would mutate an array a published snapshot still
        references; `slot_to_doc` needs NO copy — writers only assign
        slots at indices >= this snapshot's `n` (slot assignment is
        append-only between compactions, and compact/grow replace the
        array object wholesale), so the `[:n]` prefix a reader consults
        is immutable by construction.

    `slot_to_doc_dev` is the DEVICE twin of `slot_to_doc`: a
    [capacity, 2] uint32 table of each slot's 64-bit doc-id words,
    maintained by the same staged-generation handshake (rows land via
    `_stage_doc_ids` before `_publish_snapshot` swaps the reference), so
    a fused dispatch's in-program slot->doc translation reads exactly the
    mapping this snapshot's host arrays describe. Everything here is
    frozen at publish.
    """

    __slots__ = ("gen", "dim", "capacity", "n", "live", "store", "sq_norms",
                 "tombs", "slot_to_doc", "slot_to_doc_dev", "host_tombs",
                 "allow_token", "compressed", "pq", "codes", "recon_norms",
                 "rescore_dev", "rescore_sq_norms", "host_vecs",
                 "pq4", "codes4", "recon_norms4", "opq_rot",
                 "ivf_centroids", "ivf_buckets", "ivf_pca_proj",
                 "ivf_pca_rows", "ivf_meta", "ivf_tiled", "docs_ascending",
                 "doc_order", "s2d_cols", "lease")

    def __init__(self, gen: int, idx: "TpuVectorIndex"):
        self.gen = gen
        # shared with every snapshot published since the last donation:
        # their device arrays may be the same objects as this one's
        self.lease = idx._lease
        self.dim = idx.dim
        self.capacity = idx.capacity
        self.n = idx.n
        self.live = idx.live
        self.store = idx._store
        self.sq_norms = idx._sq_norms
        self.tombs = idx._tombs
        self.slot_to_doc = idx._slot_to_doc
        self.slot_to_doc_dev = idx._s2d_dev
        # doc -> slot resolution of a filter (`_allow_slots`): whether the
        # `[:n]` prefix of slot_to_doc ascends strictly (then it is its own
        # index: a binary search a doc), else its sorted order, made on
        # first use; and the device table's two columns as 1-D arrays for
        # the per-slot gather program, split on first use. Both derive from
        # what this snapshot pins and are the same whoever computes them.
        self.docs_ascending = idx._docs_ascending
        self.doc_order = None
        self.s2d_cols = None
        self.host_tombs = idx._host_tombs
        self.allow_token = idx._allow_token
        self.compressed = idx.compressed
        self.pq = idx._pq
        self.codes = idx._codes
        self.recon_norms = idx._recon_norms
        self.rescore_dev = idx._rescore_dev
        self.rescore_sq_norms = idx._rescore_sq_norms
        self.host_vecs = idx._host_vecs
        # 4-bit funnel ladder: nibble-packed codes + their recon norms +
        # the shared OPQ rotation, pinned exactly like the 8-bit slabs —
        # a re-compress mid-dispatch serves this snapshot's ladder
        self.pq4 = idx._pq4
        self.codes4 = idx._codes4
        self.recon_norms4 = idx._recon_norms4
        self.opq_rot = idx._opq_rot_dev
        # the IVF scan plane's device slabs ride the snapshot exactly
        # like the store: a recluster/compact replaces the arrays
        # wholesale (non-donating), so an in-flight dispatch pinning
        # this snapshot keeps answering from ITS partition layout
        self.ivf_centroids = idx._ivf_centroids
        self.ivf_buckets = idx._ivf_buckets
        self.ivf_pca_proj = idx._ivf_pca_proj
        self.ivf_pca_rows = idx._ivf_pca_rows
        # (nlist, cap_p, recluster_gen) — host ints, frozen at publish
        self.ivf_meta = idx._ivf_meta
        # the tiled layout: `store` itself is in partition order and there
        # is no bucket table (partition p's tile starts at slot p * cap_p)
        self.ivf_tiled = idx._ivf_tiled


class TpuVectorIndex(VectorIndex):
    # the async dispatch path handles filtered searches, the PQ codes-only
    # tier, and the small-allowList gather (everything rides the snapshot
    # two-phase enqueue/finalize pipeline) — serving layers key off this
    async_supports_filters = True

    def __init__(
        self,
        config: vi.HnswUserConfig,
        shard_path: str,
        shard_name: str = "",
        metrics=None,
        device=None,
        persist: bool = True,
        class_name: str = "",
    ):
        self.config = config
        self.metric = config.distance
        self.shard_path = shard_path
        self.shard_name = shard_name
        # set before _restore: replay-time metrics must carry the right label
        self.class_name = class_name
        self.metrics = metrics
        self.device = device
        self.dtype = jnp.bfloat16 if getattr(config, "store_dtype", "float32") == "bfloat16" else jnp.float32
        self._lock = sanitizers.register_lock(
            threading.RLock(), "index.tpu")

        self.dim: Optional[int] = None
        self.capacity = 0
        self.n = 0  # high-water slot count (includes tombstoned slots)
        self.live = 0
        self._store = None       # device [capacity, D]
        self._sq_norms = None    # device [capacity] float32 (l2 only)
        self._tombs = None       # device [capacity] bool
        self._slot_to_doc = np.zeros(0, dtype=np.int64)
        # device slot->doc translation table [capacity, 2] uint32 (lo/hi
        # words of the int64 doc id per slot): what lets a fused dispatch
        # emit FINAL doc ids from the one packed fetch (ops/topk
        # translate_pack) with zero host translation
        self._s2d_dev = None
        # reusable pre-pinned host staging buffers for query upload, one
        # small free-list per (padded batch, dim) jit bucket — the
        # per-dispatch numpy concat/zeros allocations the fused-dispatch
        # tentpole collapses. Returned to the pool by finalize, AFTER the
        # one blocking fetch: by then the program has consumed its inputs,
        # so reuse is safe even where device_put aliases host memory
        # (the cpu backend).
        # A compressed dispatch's [queries, candidates, dim] gather of
        # float32 rows (`_rescore_f32`) checks out of the same pool: the
        # key is the buffer's shape.
        self._stage_free: dict[tuple, list[np.ndarray]] = {}
        self._stage_lock = sanitizers.register_lock(
            threading.Lock(), "index.tpu.stage_pool")
        # a filtered group's device operands (a gather bucket's rows and
        # counts, the masked scan's 32 MB of words), pooled by the same
        # rule: back in finalize, after the fetch (index/group_inputs.py)
        self._group_pool = group_inputs.OperandPool(self._STAGE_POOL_CAP,
                                                    self._stage_lock)
        # host mirror of the device tombstone mask: snapshots derive the
        # live doc->slot map from it without a device fetch
        self._host_tombs = np.zeros(0, dtype=bool)
        self._doc_to_slot: dict[int, int] = {}
        # does slot_to_doc[:n] ascend strictly? The shard hands out doc ids
        # from a counter, so on the served path it always does; a library
        # caller that re-adds or adds out of order clears it until the
        # next compaction (`_note_docs_appended`)
        self._docs_ascending = True
        # snapshot-isolated read plane: readers dispatch on the published
        # IndexSnapshot lock-free; writers republish under self._lock.
        # _staged_gen/_published_gen is the read-your-writes handshake: any
        # staging bumps _staged_gen (under the lock), publication copies it
        # — a reader that sees them equal may use the snapshot as-is.
        self._snap: Optional[IndexSnapshot] = None
        self._snap_gen = 0
        self._staged_gen = 0
        self._published_gen = -1
        # monotonic stamp of the OLDEST staged-but-unpublished mutation
        # (ledger staged-publish lag; None = nothing staged / ledger off)
        self._staged_t0: Optional[float] = None
        self._inflight = 0                    # dispatches between enqueue
        self._inflight_lock = sanitizers.register_lock(
            threading.Lock(), "index.tpu.inflight")  # ...and finalize
        self._inflight_gauge = None  # resolved lazily (None) / broken (False)
        # a writer that overwrites the published generation waits here for
        # the searches enqueueing on it (`_retire_snapshot`)
        self._pin_cv = threading.Condition(self._inflight_lock)
        # the lease the next published snapshot takes; replaced at every
        # retirement (under the index lock)
        self._lease = ArrayLease()
        # staging buffer keyed by doc_id: a re-add of a staged doc replaces it
        self._pending: dict[int, np.ndarray] = {}
        self._pending_tombs: list[int] = []
        # dead slots whose tombstone bit is set on the device, handed to
        # the next rows to land before `n` grows (`_place_rows`): a flat
        # slab has no graph edges to repair, so the reference's tombstone
        # clean-up reduces to giving the slot away. Slots, capacity and the
        # scan's length then follow the live rows, not the writes ever made.
        self._free_slots: list[int] = []
        # what the write path did, lifetime (health(); the perf window
        # keeps the same counts a window: monitoring/perf.py `writes`)
        self._wstats = {"slots_reused": 0, "slots_appended": 0,
                        "tombstones_applied": 0, "grows": 0,
                        "writes_in_place": 0, "writes_copied": 0}
        # PQ state (compress.go analog): when compressed, the device holds
        # [cap, M] uint8/16 codes instead of floats; full-precision rows move
        # to host RAM for the rescoring pass
        self.compressed = False
        self._pq = None                     # ProductQuantizer
        self._codes = None                  # device [capacity, M]
        self._rescore_dev = None            # device bf16 [capacity, D]
        self._rescore_sq_norms = None       # device f32 [capacity] (l2 bias)
        self._recon_norms = None            # device f32 [capacity] ||recon||^2
        self._host_vecs: Optional[np.ndarray] = None  # np [capacity, D] f32
        self._pq_path = os.path.join(shard_path, "pq.npz")
        # 4-bit funnel ladder (pq.bits=4): a SECOND quantizer with 16
        # centroids per segment sharing the 8-bit quantizer's OPQ rotation,
        # its nibble-packed codes [cap, M/2] uint8, recon norms, and the
        # rotation as its own device slab (applied to queries at dispatch)
        self._pq4 = None                    # ProductQuantizer (centroids=16)
        self._codes4 = None                 # device [capacity, M/2] uint8
        self._recon_norms4 = None           # device f32 [capacity]
        self._opq_rot_dev = None            # device f32 [D, D] (or None)
        self._pq4_path = os.path.join(shard_path, "pq4.npz")
        # the capacity the shard last grew to, for the restart to come back
        # to whatever the device's budget reads then (`_record_capacity`);
        # and that number while a restore runs, 0 at every other time
        self._capacity_path = os.path.join(shard_path, "capacity")
        self._recorded = 0
        self._restoring = False
        # the stage sums of the restore that is running (tracing.StageSums),
        # None at every other time: the write path's pieces gate on it
        self._restore_sums: Optional[tracing.StageSums] = None
        # (pq, pq4) the next `_init_device` enters the compressed form
        # with: set by a restore that found a codebook and by the
        # compaction of a compressed index, consumed by the first row
        self._pending_pq: Optional[tuple] = None
        # what the last restore did (health(); the `restore` incident)
        self.last_restore: Optional[dict] = None
        # chunks that went through the codebook on their way in, lifetime
        self._chunks_encoded = 0
        # flips true on a Mosaic compile failure of the fused gmin kernel;
        # searches then stay on the lax.scan kernel permanently
        self._gmin_broken = False
        # identity token for the per-allowList packed-words cache: the cache
        # tuple holds a strong ref, so the identity can never be recycled
        self._allow_token = object()
        # separate failure domain + codebook-constant cache for the PQ
        # codes-only fused kernel (ops/pq_gmin.py)
        from weaviate_tpu.ops.gmin_scan import KernelState, ProgramCounts

        self._pqg_state = KernelState()
        self._pqg_cb = None  # (pq identity, cb_chunks dev, flat_cb dev)
        # separate failure domain + codebook-constant cache for the 4-bit
        # funnel kernel family (ops/pq4.py): a Mosaic failure of the 4-bit
        # scan must not poison the 8-bit paths, and vice versa
        self._pq4_state = KernelState()
        self._pq4_cb = None  # (pq4 identity, cb4 chunks dev, dense cb4 dev)
        # per-stage funnel survivor accounting for health()["pq"], updated
        # per funnel dispatch under a leaf lock (lock_hierarchy level 45 —
        # nothing ever nests inside it)
        self._pq4_lock = sanitizers.register_lock(
            threading.Lock(), "index.tpu.pq4")
        self._pq4_stats = {"dispatches": 0, "stage1_pallas": 0,
                           "stage1_rows": 0, "stage2_survivors": 0,
                           "stage3_survivors": 0}
        # per-store-generation [ncols, G*D] rescore-block layouts (see
        # gmin_scan.build_rescore_blocks): keyed by the exact device array
        # object — every write replaces the store array with a fresh copy
        # (copy-on-write, nothing donated: snapshots may still pin the old
        # generation), so object identity IS the write generation. Strong
        # refs keep ids stable.
        self._blk_cache: dict = {}
        # (store array, its lane-padded twin): see _row_store
        self._row_store_cache: Optional[tuple] = None
        self._row_store_lock = sanitizers.register_lock(
            threading.Lock(), "index.tpu.row_store")
        # -- IVF scan plane (ROADMAP item 3; ops/ivf.py) ----------------
        # device slabs (None until the write path trains a layout):
        # centroids [nlist, D] f32, padded partition buckets
        # [nlist, cap_p] i32 (-1 padding), optional PCA projection
        # [D, dp] + per-slot low-dim rows [capacity, dp] — all
        # JGL012-stamped, all replaced wholesale (never donated) so
        # published snapshots can pin them
        self._ivf_centroids = None
        self._ivf_buckets = None
        self._ivf_pca_proj = None
        self._ivf_pca_rows = None
        # host twins: centroid matrix + PCA basis for write-path
        # assignment, per-slot partition assignment (-1 = unassigned),
        # per-partition fills for health, layout metadata
        self._ivf_centroids_host: Optional[np.ndarray] = None
        self._ivf_pca_host: Optional[np.ndarray] = None
        self._ivf_assign = np.zeros(0, dtype=np.int32)
        self._ivf_fills: Optional[np.ndarray] = None
        self._ivf_meta: Optional[tuple[int, int, int]] = None
        self._ivf_cap_p: Optional[int] = None
        # freshly-written (slots, partitions) runs awaiting the O(batch)
        # incremental bucket fold at the next snapshot publish
        self._ivf_pending_slots: list[tuple[np.ndarray, np.ndarray]] = []
        self._ivf_trained_n = 0
        self._ivf_gen = 0            # recluster generation (health)
        self._ivf_dirty = False      # buckets stale vs assignments
        # THE TILED LAYOUT (ops/ivf.py "the tiled layout"; docs/ivf.md):
        # an uncompressed index without the PCA prefilter keeps its one
        # copy of the rows in partition order, partition p in the slots
        # [p * cap_p, (p + 1) * cap_p). No bucket table, no assignment
        # mirror: a slot's partition is `slot // cap_p`, a free slot is a
        # tombstoned one (`_host_tombs`), `n` is nlist * cap_p, and
        # `_ivf_free_n` [nlist] counts each tile's free slots. The bucket
        # table above stays the layout of a compressed index and of one
        # with the prefilter, whose programs gather by slot.
        self._ivf_tiled = False
        self._ivf_free_n: Optional[np.ndarray] = None
        # the layout beside the vector log (`_ivf_persist`): what a restart
        # reads instead of training, and whether memory has moved past it
        self._ivf_path = os.path.join(shard_path, IVF_LAYOUT_FILE)
        self._ivf_unsaved = False
        # a restore's persisted layout until its first row arrives
        self._pending_ivf: Optional[PersistedLayout] = None
        # doc -> slot of the persisted layout while a restore replays
        self._ivf_restore_map: Optional[tuple] = None
        self._ivf_restore_stats = {"placed": 0, "assigned": 0}
        # the settings the layout was trained under: what a write that
        # finds it full lays it out anew with while the plane is off
        self._ivf_settings: Optional[IvfConfig] = None
        self._ivf_trains = 0         # layouts this process made
        self._no_filter_words = None  # `_dispatch_ivf`'s zero filter words
        self._ivf_sized_n = 0        # rows the tiles were sized for
        # probe-accounting counters (health / bench probed_fraction),
        # updated per IVF dispatch under a leaf lock (lock_hierarchy
        # level 45 — nothing ever nests inside it)
        self._ivf_lock = sanitizers.register_lock(
            threading.Lock(), "index.tpu.ivf")
        self._ivf_stats = {"dispatches": 0, "probed_rows": 0,
                           "base_rows": 0}
        # host f32 copy of the store (+ its row sq-norms) for the breaker's
        # fallback plane (search_by_vectors_host), built once per snapshot
        # generation — (gen, rows, sq_norms)
        self._host_rows_cache: Optional[
            tuple[int, np.ndarray, np.ndarray]] = None
        # compiled-shape keys (b, k, rg, active_g, use_allow) that completed a
        # materialized search — each key is its own Mosaic compilation, so one
        # small-shape success must not vouch for a larger VMEM footprint
        self._gmin_validated: set = set()
        self._gmin_shape_broken: set = set()  # keys Mosaic rejected
        # full-store dispatches by the program that ran them (health()
        # kernels.gmin.dispatches, /debug/perf `programs`)
        self.scan_programs = ProgramCounts()
        # host-memory provider (monitoring/memory.py): the slot/tombstone
        # mirrors, PQ host rows, staged rows, and the breaker's fallback
        # cache become /debug/memory host components. Weakref-held — the
        # registry never outlives the index.
        memory.register_host_provider(self, memory.index_host_components)
        self._log = VectorLog(os.path.join(shard_path, "vector.log")) if persist else None
        if self._log is not None:
            self._restore()

    # -- lifecycle -----------------------------------------------------------

    def _restore(self) -> None:
        """Replay the vector log (startup.go:56 restoreFromDisk analog). A
        shard that was compressed (a persisted PQ codebook: the analog of
        commit-log AddPQ replay, deserializer.go) is replayed STRAIGHT into
        the compressed form: the codebook is loaded before the log, and
        every run lands through `_write_block`'s compressed branch — codes,
        bf16 rows and norms on the device, the float32 rows in
        `host_vecs`, chunk by chunk. The float32 slab is never allocated,
        nothing is fetched back, and the codes are re-derived on the
        device, which beats persisting them."""
        self._restoring = True
        replay_stats: dict = {}
        # the log's records, those of them dead at its end, and the bytes
        # the condensor took out (`_live_events`)
        log_stats: dict = {}
        with tracing.stage("vector.restore", shard=self.shard_name) as st:
            sums = self._restore_sums = tracing.StageSums()
            try:
                self._replay_log(sums, replay_stats, log_stats)
                if self.compressed:
                    with tracing.piece_of(sums, "flush", self.capacity):
                        self._publish_snapshot()
                # what the device still owed when the host was done: the
                # write programs only queue, and a server that says ready
                # before they ran answers its first request after them
                with tracing.piece_of(sums, "drain", self.capacity):
                    jax.block_until_ready(self._restored_arrays())  # graftlint: disable=JGL001 a restore runs in the constructor, before the index serves: the wait is the `drain` stage, what the device still owed when the host was done
            finally:
                self._restore_sums = None
            sums.publish()
            st.note(rows=self.n, capacity=self.capacity)
            tl = perf.timeline()
            if tl is not None:
                # what `slab_bytes_copied` is held against
                tl.count(slab_bytes=self._slab_bytes())
        self.last_restore = restore_record(
            "compressed" if self.compressed else "uncompressed", self.n, st,
            sums, replay_stats,
            # restore runs in the constructor: the lifetime count is its own
            chunks_encoded=self._chunks_encoded, log=log_stats)
        if self.n:
            incidents.emit("write_phase", scope="restore",
                           **self.last_restore)

    def _replay_log(self, sums, replay_stats: dict, log_stats: dict) -> None:
        """The replay half of `_restore`: the codebook, the log's runs, the
        last flush. `_restoring` holds from the caller until this
        returns."""
        try:
            self._pending_pq = self._load_persisted_pq()
            self._recorded = self._recorded_capacity()
            if ivf_settings() is not None:
                with tracing.piece_of(sums, "ivf", self.capacity):
                    self._pending_ivf = self._ivf_load()
            sums.enter("stage")
            events = VectorLog.replay_batches(
                self._log.path, stats=replay_stats,
                run_max=_REPLAY_RUN_MAX if self._pending_pq else None,
                sums=sums)
            if self._pending_pq is None:
                # the log's live records alone, and a log that holds much
                # else rewritten from them before they land
                events = self._live_events(events, log_stats)
            records = 0
            for op, ids, vecs in sums.timed(events, "log.parse"):
                if op == "add":
                    records += len(ids)
                    self._bulk_stage_add(ids, vecs)
                else:
                    records += 1
                    self._stage_delete(int(ids), log=False)
            if "records" not in log_stats:
                self._log.records = log_stats["records"] = records
            if ivf_settings() is not None and not self.compressed \
                    and self.dim is not None:
                # the layout's half of the restore: the rows still staged
                # land, and a shard without a layout that covers them
                # trains one here, on the device (`_ivf_end_restore`)
                with tracing.piece_of(sums, "ivf", self.capacity):
                    self._flush_pending()
                    self._ivf_end_restore()
            sums.leave(self.capacity)
            VectorLog.report_replay_stats(self._log.path, replay_stats)
            if os.path.exists(self._pq_path):
                with tracing.piece_of(sums, "flush", self.capacity):
                    self._flush_pending()
                    if self.compressed and self.config.pq.bits == 4 \
                            and self._pq4 is None and self.n:
                        # no usable pq4.npz: refit the funnel's ladder from
                        # the rows just replayed (never costs the shard)
                        vecs_n = self._host_vecs[: self.n]
                        self._set_pq4(self._fit_pq4(self._pq, vecs_n))
                        self._encode_pq4(vecs_n)
        finally:
            self._restoring = False
            self._pending_pq = None
            self._pending_ivf = None
            self._ivf_restore_map = None
            self._recorded = 0

    def _live_events(self, events, stats: dict):
        """The replay's live add runs (`_live_runs`), after the condensor:
        a log whose dead records hold more than `_LOG_CONDENSE_DEAD` of
        its live records' bytes is rewritten from the live runs first."""
        runs = _live_runs(events, stats)
        log = self._log
        log.records = stats["records"]
        live_bytes = sum(len(ids) * (17 + 4 * vecs.shape[1])
                         for ids, vecs in runs)
        dead_bytes = log.bytes - 6 - live_bytes
        if dead_bytes > _LOG_CONDENSE_DEAD * live_bytes:
            log.rewrite_runs(runs)
            stats["condensed_bytes"] = int(dead_bytes)
        for ids, vecs in runs:
            yield "add", ids, vecs

    def _restored_arrays(self) -> list:
        """The device arrays a restore's write programs produce."""
        return [a for a in (
            self._store, self._sq_norms, self._tombs, self._s2d_dev,
            self._codes, self._recon_norms, self._rescore_dev,
            self._rescore_sq_norms, self._codes4, self._recon_norms4)
            if a is not None]

    def _load_persisted_pq(self):
        """(pq, pq4 or None) of a shard that was compressed when it shut
        down, None of one that was not. A pq.npz this build cannot use —
        rejected config (hamming), corrupt zip, missing key, a codebook
        that is not its own shape — must not make the shard unloadable:
        it serves uncompressed with a warning AND a fallback count (a
        fleet of shards quietly serving uncompressed is a capacity
        incident, not a log line)."""
        if not os.path.exists(self._pq_path):
            return None
        from weaviate_tpu.compress.pq import ProductQuantizer

        try:
            pq = ProductQuantizer.load(self._pq_path)
            if pq.codebook.shape != (pq.segments, pq.centroids, pq.ds):
                raise ValueError(
                    f"codebook {pq.codebook.shape} is not "
                    f"{(pq.segments, pq.centroids, pq.ds)}")
        except Exception as e:  # noqa: BLE001 — see above
            record_device_fallback(
                "index.tpu.restore", "pq_codebook_rejected", e, log=False)
            self._serve_uncompressed(e)
            return None
        pq4 = None
        if self.config.pq.bits == 4 and os.path.exists(self._pq4_path):
            try:
                pq4 = ProductQuantizer.load(self._pq4_path)
                if pq4.segments != pq.segments or pq4.centroids != 16:
                    pq4 = None  # stale: refit after the replay
            except Exception as e:  # noqa: BLE001 — refit is always safe
                import logging

                logging.getLogger(__name__).warning(
                    "persisted pq4 codebook rejected (%s: %s); refitting",
                    type(e).__name__, e)
        return pq, pq4

    def _serve_uncompressed(self, e: Exception) -> None:
        """The rest of a rejected codebook (its fallback is counted where
        it was rejected)."""
        import logging

        self.config.pq.enabled = False
        logging.getLogger(__name__).warning(
            "persisted pq codebook rejected (%s: %s); "
            "serving uncompressed", type(e).__name__, e)

    def post_startup(self) -> None:
        self._flush_pending()

    # -- device plumbing -----------------------------------------------------

    def _init_device(self, dim: int) -> None:
        self.dim = dim
        self.capacity = _MIN_CAPACITY
        dev = self.device
        layout, self._pending_ivf = self._pending_ivf, None
        if layout is not None and layout.centroids.shape[1] != dim:
            layout = None   # another width's layout: the rows train anew
        if layout is not None:
            self.capacity = _fit_capacity(
                layout.centroids.shape[0] * layout.cap_p)
        held, self._pending_pq = self._pending_pq, None
        if held is not None and held[0].dim != dim:
            e = ValueError(f"codebook of {held[0].dim} dims, rows of {dim}")
            record_device_fallback(
                "index.tpu.restore", "pq_codebook_rejected", e, log=False)
            self._serve_uncompressed(e)
            held = None
        if held is not None:
            # a restore or a compaction of a compressed shard: the rows
            # about to land go straight into the compressed form
            self._alloc_compressed(*held)
        else:
            self._store = jax.device_put(jnp.zeros((self.capacity, dim), self.dtype), dev)
            self._sq_norms = jax.device_put(jnp.zeros((self.capacity,), jnp.float32), dev)
        self._tombs = jax.device_put(jnp.zeros((self.capacity,), jnp.bool_), dev)
        self._slot_to_doc = np.full(self.capacity, -1, dtype=np.int64)
        self._s2d_dev = jax.device_put(
            jnp.full((self.capacity, 2), _S2D_FILL, jnp.uint32), dev)
        self._host_tombs = np.zeros(self.capacity, dtype=bool)
        if layout is not None:
            self._ivf_adopt(layout)
        self._stamp_memory()

    def _ensure_capacity(self, needed: int) -> None:
        if self._store is None and self._codes is None:
            raise RuntimeError("store not initialised")
        cap = self._ladder_capacity(needed)
        if cap != self.capacity:
            faults.fire("index.tpu.alloc")
            sums = self._restore_sums
            if sums is not None:
                sums.enter("grow", capacity=cap)
            if self.compressed:
                self._codes = _grow_store(self._codes, cap)
                hv = np.zeros((cap, self.dim), np.float32)
                hv[: self.capacity] = self._host_vecs
                self._host_vecs = hv
                if self._rescore_dev is not None:
                    self._rescore_dev = _grow_store(self._rescore_dev, cap)
                    if self._rescore_sq_norms is not None:
                        self._rescore_sq_norms = _grow_1d(
                            self._rescore_sq_norms, cap, jnp.float32(0))
                self._recon_norms = _grow_1d(self._recon_norms, cap, jnp.float32(0))
                if self._codes4 is not None:
                    self._codes4 = _grow_store(self._codes4, cap)
                    self._recon_norms4 = _grow_1d(
                        self._recon_norms4, cap, jnp.float32(0))
            else:
                self._grow_slab(cap)
                self._sq_norms = _grow_1d(self._sq_norms, cap, jnp.float32(0))
            self._tombs = _grow_1d(self._tombs, cap, False)
            if self._s2d_dev is not None:
                self._s2d_dev = _grow_pairs(self._s2d_dev, cap)
            if self._ivf_pca_rows is not None:
                self._ivf_pca_rows = _grow_store(self._ivf_pca_rows, cap)
            if self._ivf_assign.size:
                ia = np.full(cap, -1, np.int32)
                ia[: self.capacity] = self._ivf_assign[: self.capacity]
                self._ivf_assign = ia
            s2d = np.full(cap, -1, dtype=np.int64)
            s2d[: self.capacity] = self._slot_to_doc
            self._slot_to_doc = s2d
            ht = np.zeros(cap, dtype=bool)
            ht[: self.capacity] = self._host_tombs
            self._host_tombs = ht
            self.capacity = cap
            self._record_capacity()
            self._wstats["grows"] += 1
            self._note_write(grows=1, slab_bytes_copied=self._slab_bytes())
            led = memory.get_ledger()
            if led is not None:
                led.note_write_shape(
                    ("grow", cap, self.dim or 0, self.compressed))
            self._stamp_memory()
            if sums is not None:
                sums.leave(cap)

    def _ladder_capacity(self, needed: int) -> int:
        """The capacity that holds `needed` slots: the least rung of a
        ladder that depends on the rows' width and the device's budget
        alone, so an import that climbs it rung by rung and the restart
        that asks for all its rows at once end on the same one. The rungs
        double (maintainance.go:31) while the doubled slab still fits the
        device BESIDE the one it grows from; past that a doubling would
        ask for memory no chip of this kind has (from 2^21 rows of 768-d
        float32, 6.4 GB, to 12.9 GB beside them), and the rungs go up a
        quarter at a time, in whole scan chunks (docs/memory.md "Growth").
        The budget is the memory ledger's; where it is unknown (cpu, no
        ledger) every rung doubles, as it always did. A compressed index
        keeps its doubling (`ROADMAP.md` Queue 2 A1).

        The budget may read otherwise after a restart (another
        `MEMORY_DEVICE_BUDGET_BYTES`, another alert reserve), and the ladder
        with it: a restore comes back to the capacity the shard recorded
        when it last grew (`_recorded`), where that holds the rows and
        today's budget holds it."""
        cap = self.capacity
        usable = None
        if not self.compressed:
            led = memory.get_ledger()
            usable = led.device_usable_bytes() if led is not None else None
        row = self._row_bytes()
        rec = 0 if self.compressed else self._recorded
        if rec >= max(needed, cap) and (
                usable is None or rec * row <= usable):
            return rec
        while cap < needed:
            if usable is None or 3 * cap * row <= usable:
                cap *= 2
            else:
                cap = -(-(cap + cap // 4) // _SCAN_CHUNK) * _SCAN_CHUNK
        return cap

    def _record_capacity(self) -> None:
        """Write the capacity beside the vector log, whole or not at all: a
        restart reads it before it replays (`_recorded_capacity`)."""
        if self._log is None:
            return
        tmp = self._capacity_path + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{self.capacity}\n")
        os.replace(tmp, self._capacity_path)

    def _recorded_capacity(self) -> int:
        """The capacity the shard recorded last, 0 where it recorded none
        (it never grew, or an earlier build wrote its state) or what is
        there cannot be read: the ladder alone decides then."""
        try:
            with open(self._capacity_path) as f:
                return max(int(f.read()), 0)
        except (OSError, ValueError):
            return 0

    def _row_bytes(self) -> int:
        """Device bytes a slot of the uncompressed form holds: its row, its
        squared norm (l2), its doc-id words, its tombstone bit."""
        return ((self.dim or 0) * jnp.dtype(self.dtype).itemsize
                + (4 if self.metric == vi.DISTANCE_L2 else 0) + 8 + 1)

    def _grow_slab(self, cap: int) -> None:
        """The float slab at capacity `cap`, the rows it holds kept. Beside
        the old slab where the new one fits whichever way the old one lies
        in the device's address space (twice its bytes are free under the
        ledger's line: a free half on either side is enough); else THROUGH
        THE HOST: the rows are fetched, the old slab is given back, the new
        one is made in the memory that frees and the rows are landed again.
        The allocator does not move what lives, so after a few doublings
        the free memory is two holes around the slab and a larger slab can
        fit neither, however much is free in all. Minutes apart in a
        shard's life and seconds long; searches wait for it at the index
        lock like for any write."""
        new_bytes = cap * (self.dim or 0) * self._store.dtype.itemsize
        if self._copy_fits(2 * new_bytes):
            self._store = _grow_store(self._store, cap)
            self._stamp_memory()
            return
        # nobody may be handed the old slab from here on
        self._retire_snapshot()
        rows = -(-self.n // _CHUNK) * _CHUNK
        old = self._store
        # the whole array, not a slice of it (a slice is a second array on
        # the device); the fetch returns when the slab's last writer has run
        host = np.asarray(old) if rows else None  # graftlint: disable=JGL001 a slab that cannot grow beside itself moves through the host, under the lock like compact's rebuild: nothing may read or write it meanwhile
        self._store = None
        self._row_store_cache = None
        self._blk_cache.clear()
        old.delete()
        try:
            self._store = self._land_slab(cap, host, rows, old.dtype)
        except Exception:
            # the larger slab could not be made or filled: the rows go back
            # into one of the size they came from, which the device held a
            # moment ago, and the write that asked for more fails alone
            self._store = self._land_slab(old.shape[0], host, rows, old.dtype)
            raise
        finally:
            self._stamp_memory()

    def _land_slab(self, cap: int, host: Optional[np.ndarray], rows: int,
                   dtype):
        """A new slab of `cap` rows that nobody else holds yet, with the
        first `rows` of `host` landed in it."""
        store = jax.device_put(jnp.zeros((cap, self.dim), dtype), self.device)
        write = _IN_PLACE[_write_rows]
        for off in range(0, rows, _CHUNK):
            store = write(store, jnp.asarray(host[off : off + _CHUNK]), off)
        return store

    def _copy_fits(self, nbytes: int) -> bool:
        """Does the memory ledger leave room for `nbytes` more on the
        device (`MemoryLedger.device_room`: the budget less its alert
        reserve less every stamped component)? Yes where nobody knows the
        budget (cpu, no ledger): the allocator is then the only judge, as
        it always was."""
        led = memory.get_ledger()
        if led is None:
            return True
        room = led.device_room()
        return room is None or nbytes <= room

    def _in_place(self, *arrays) -> bool:
        """May the next write program be GIVEN `arrays` (donated, to
        overwrite where they lie), or must it leave them valid and make new
        ones? The module comment above `_write_rows` has the rule. The
        caller holds the lock; where this returns True for arrays the
        published snapshot held, that snapshot has been retired and the
        write must end in `_publish_snapshot`, as every write does."""
        if self.compressed or (self._ivf_centroids_host is not None
                               and not self._ivf_tiled):
            return False
        snap = self._snap
        if snap is None or snap.lease.retired:
            return True
        held = (snap.store, snap.sq_norms, snap.tombs, snap.slot_to_doc_dev)
        if not any(a is h for a in arrays if a is not None for h in held):
            return True
        # the functional kernel makes every array it writes anew
        if self._copy_fits(sum(memory.array_bytes(a) for a in arrays)):
            return False
        self._retire_snapshot()
        return True

    def _run_write(self, kernel, *args, written: int = 1):
        """Run a write kernel over its first `written` arguments, the arrays
        it writes: its donating twin where `_in_place` gives them away,
        else the functional one; counted either way."""
        arrays = args[:written]
        if self._in_place(*arrays):
            self._wstats["writes_in_place"] += 1
            self._note_write(writes_in_place=1)
            return _IN_PLACE[kernel](*args)
        copied = sum(memory.array_bytes(a) for a in arrays)
        self._wstats["writes_copied"] += 1
        self._note_write(writes_copied=1, slab_bytes_copied=copied)
        led = memory.get_ledger()
        if led is not None:
            # old and new generation are both alive while the copy runs
            led.note_cow(0, transient_peak=copied)
        return kernel(*args)

    def _retire_snapshot(self) -> None:
        """Take the published device arrays back (the caller holds the lock
        and will publish): from here no search can pin the published
        snapshot NOR any earlier one of its lease, which may hold the same
        arrays (`_pin` sends them to `_read_snapshot`'s slow path, where
        they wait at the lock for this write's snapshot), and the searches
        that are inside their enqueue on any of them are waited for. They
        hold no lock there, so the wait is their host work: microseconds to
        a few ms. What is published next takes a lease of its own."""
        snap = self._snap
        if snap is None or snap.lease.retired:
            return
        lease = snap.lease
        self._lease = ArrayLease()
        t0 = time.perf_counter()
        with self._pin_cv:
            lease.retired = True
            while lease.pins:
                self._pin_cv.wait()
        self._note_write(
            reader_wait_ms=(time.perf_counter() - t0) * 1000.0)

    def _pin(self, snap: IndexSnapshot,
             follow: bool = True) -> Optional[IndexSnapshot]:
        """-> the snapshot to enqueue on: `snap`, pinned, or where a writer
        has retired its lease the next published one, pinned (`follow`
        False: None). Until `_unpin` no write program is given its device
        arrays. A retired lease that is still pinned may be pinned again (a
        group's dispatches share one snapshot): its writer is still
        waiting, and proceeds only once it has seen no pin under the
        lock."""
        while True:
            lease = snap.lease
            with self._pin_cv:
                if not lease.retired or lease.pins:
                    lease.pins += 1
                    return snap
            if not follow:
                return None
            snap, _ = self._read_snapshot()

    def _unpin(self, snap: IndexSnapshot) -> None:
        lease = snap.lease
        with self._pin_cv:
            lease.pins -= 1
            if lease.retired and not lease.pins:
                self._pin_cv.notify_all()

    def _write_block(self, rows: np.ndarray, start: int) -> None:
        """Land [count, D] float32 rows at slots [start, start+count): every
        write path's one way in (flush, bulk import, restore, compact)."""
        with tracing.piece_of(self._restore_sums, "land", self.capacity,
                              rows=rows.shape[0]):
            self._land_rows(rows, start)
            self._ivf_on_rows_written(rows, start)
        chunks = -(-rows.shape[0] // _CHUNK)
        # every chunk is a whole `_CHUNK` uploaded and, compressed, a new
        # generation of each array it lands in (the float slab's chunks are
        # counted where they run: `_run_write`)
        self._note_write(
            upload_bytes=chunks * _CHUNK * self.dim * 4,
            slab_bytes_copied=(chunks * self._slab_bytes()
                               if self.compressed else 0))
        led = memory.get_ledger()
        if led is not None:
            led.note_write_shape(
                ("write_rows", self.capacity, self.dim, self.compressed))

    def _land_rows(self, rows: np.ndarray, start: int) -> None:
        """The device half of `_write_block`, in fixed-size chunks (one
        compiled shape). In compressed mode a chunk is PQ-encoded on the
        device and the codes, its bf16 copy and the norms hit HBM; the
        float rows go to the host-side rescoring store."""
        count = rows.shape[0]
        sums = self._restore_sums
        off = 0
        while off < count:
            take = min(_CHUNK, count - off)
            chunk = np.zeros((_CHUNK, self.dim), dtype=np.float32)
            chunk[:take] = rows[off : off + take]
            self._ensure_capacity(start + off + _CHUNK)
            if sums is not None:
                sums.tick(self.capacity)
            if self.compressed:
                codes = self._pq.encode(chunk)  # [_CHUNK, M]
                self._codes = _write_rows(self._codes, jnp.asarray(codes), start + off)
                self._recon_norms = _write_norms(
                    self._recon_norms,
                    jnp.asarray(self._pq.recon_sq_norms(codes)),
                    start + off,
                )
                if self._pq4 is not None:
                    self._land_chunk4(chunk, start + off)
                if self._rescore_dev is not None:
                    self._rescore_dev = _write_rows(
                        self._rescore_dev, jnp.asarray(chunk, jnp.bfloat16), start + off
                    )
                    if self._rescore_sq_norms is not None:
                        self._rescore_sq_norms = _write_norms(
                            self._rescore_sq_norms,
                            jnp.asarray(np.einsum("ij,ij->i", chunk, chunk,
                                                  dtype=np.float64)
                                        .astype(np.float32)),
                            start + off,
                        )
            else:
                self._store = self._run_write(
                    _write_rows, self._store, jnp.asarray(chunk, self.dtype),
                    start + off)
                if self.metric == vi.DISTANCE_L2:
                    nchunk = jnp.asarray((chunk.astype(np.float64) ** 2).sum(1).astype(np.float32))
                    self._sq_norms = self._run_write(
                        _write_norms, self._sq_norms, nchunk, start + off)
            off += take
        if self.compressed:
            self._host_vecs[start : start + count] = rows
            self._chunks_encoded += -(-count // _CHUNK)
        self._stamp_memory()

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
        # gen bump AFTER validation: a rejected add must not dirty the
        # published snapshot and push the next reader onto the locked path
        self._staged_gen += 1
        self._mark_staged()
        old = self._doc_to_slot.pop(doc_id, None)
        if old is not None:
            self._pending_tombs.append(old)
            self.live -= 1
        if doc_id in self._pending:
            self.live -= 1
        self._pending[doc_id] = vector
        self.live += 1
        if log and self._log is not None:
            self._log.append_add(doc_id, vector)
        if len(self._pending) >= _CHUNK:
            with tracing.piece_of(self._restore_sums, "flush", self.capacity):
                self._flush_pending()

    def _bulk_stage_add(self, ids: np.ndarray, vecs: np.ndarray) -> None:
        """Restore-path bulk staging with _stage_add's exact semantics
        (keep-last for duplicate docs in the run, per-record path for docs
        the index already knows so their old slots tombstone correctly).
        Tiny runs stay per-record; mid-size runs feed the staging buffer in
        one dict update; only runs of at least a full device chunk
        direct-write — a padded _CHUNK write per fragmented run would make
        churned logs restore SLOWER than the per-record path."""
        if len(ids) < 256:
            for d, v in zip(ids.tolist(), vecs):
                self._stage_add(int(d), v, log=False)
            return
        if self.dim is None:
            self._init_device(int(np.asarray(vecs).shape[1]))
        elif np.asarray(vecs).shape[1] != self.dim:
            raise ValueError(
                f"dim mismatch: index has {self.dim}, got {np.asarray(vecs).shape[1]}")
        d2s = self._doc_to_slot
        ids64, vecs, known = _prep_bulk_run(
            ids, vecs, self.metric,
            lambda d: d in d2s or d in self._pending)
        if known:
            for i in known:
                self._stage_add(int(ids64[i]), vecs[i], log=False)
            keep = np.ones(len(ids64), bool)
            keep[known] = False
            ids64, vecs = ids64[keep], vecs[keep]
            if len(ids64) == 0:
                return
        if len(ids64) < _CHUNK:
            self._pending.update(zip(ids64.tolist(), vecs))
            self.live += len(ids64)
            if len(self._pending) >= _CHUNK:
                with tracing.piece_of(self._restore_sums, "flush",
                                      self.capacity):
                    self._flush_pending()
            return
        with tracing.piece_of(self._restore_sums, "flush", self.capacity):
            self._flush_pending()  # earlier staged singles keep their slots
        count = len(ids64)
        self._staged_gen += 1
        self._mark_staged()
        if self._ivf_tiled:
            # a restore into a persisted layout: every row to the slot the
            # layout recorded for its doc
            self._place_rows_tiled(ids64, np.ascontiguousarray(vecs))
            self.live += count
            return
        self._ensure_capacity(self.n + count)
        self._cow_host_state()
        self._write_block(np.ascontiguousarray(vecs), self.n)
        self._note_docs_appended(ids64)
        self._slot_to_doc[self.n : self.n + count] = ids64
        self._stage_doc_ids(ids64, self.n)
        d2s.update(zip(ids64.tolist(), range(self.n, self.n + count)))
        self.n += count
        self.live += count

    def _stage_delete(self, doc_id: int, log: bool = True) -> None:
        slot = self._doc_to_slot.pop(doc_id, None)
        if slot is None:
            # may still be in the staging buffer; an unknown doc changes
            # nothing and must not dirty the published snapshot
            if doc_id in self._pending:
                del self._pending[doc_id]
                self.live -= 1
                self._staged_gen += 1
                self._mark_staged()
                if log and self._log is not None:
                    self._log.append_delete(doc_id)
            return
        self._pending_tombs.append(slot)
        self.live -= 1
        self._staged_gen += 1
        self._mark_staged()
        if log and self._log is not None:
            self._log.append_delete(doc_id)

    def _note_docs_appended(self, docs: np.ndarray) -> None:
        """Keep `_docs_ascending` true to the run about to land at slot
        `self.n` (called under the write lock, before the assignment)."""
        if not self._docs_ascending or len(docs) == 0:
            return
        docs = np.asarray(docs)
        if (self.n and docs[0] <= self._slot_to_doc[self.n - 1]) \
                or not bool(np.all(docs[1:] > docs[:-1])):
            self._docs_ascending = False

    def _stage_doc_ids(self, docs: np.ndarray, start: int) -> None:
        """Mirror a run of newly-assigned slot->doc entries onto the
        DEVICE translation table (the fused dispatch's in-program
        slot->doc source). Row counts pad to _bucket_rows so the scatter's
        jit shapes stay bounded; padding rows carry an out-of-range slot
        index that mode="drop" ignores. Runs under the write lock, before
        _publish_snapshot — the staged-generation handshake that makes the
        device table and the host mirror describe the same mapping."""
        if self._s2d_dev is None:
            return
        count = len(docs)
        pad = _bucket_rows(count)
        idx = np.full(pad, self.capacity + 1, dtype=np.int32)
        idx[:count] = np.arange(start, start + count, dtype=np.int32)
        self._s2d_dev = self._run_write(
            _write_doc_pairs, self._s2d_dev, jnp.asarray(idx),
            jnp.asarray(_doc_pairs(docs, pad)))
        led = memory.get_ledger()
        if led is not None:
            led.note_write_shape(("write_docs", self.capacity, pad))
        self._stamp_memory()

    def _cow_host_state(self, rewrites_slots: bool = False) -> None:
        """Copy-on-write the host mirrors a published snapshot still pins,
        so in-place writer mutation can never tear a lock-free reader.
        `host_tombs` always (deletes flip bits at arbitrary live slots).
        `slot_to_doc` only where this write hands out a slot again
        (`rewrites_slots`): an append assigns at indices >= every published
        snapshot's `n`, so the `[:n]` prefix a snapshot reads is immutable
        in place and the per-flush O(capacity) copy would be pure overhead;
        a reused slot lies INSIDE that prefix, and the snapshot that still
        holds the old row there must keep reading the old doc id."""
        snap = self._snap
        if snap is None:
            return
        copied = 0
        if snap.host_tombs is self._host_tombs:
            self._host_tombs = self._host_tombs.copy()
            copied += int(self._host_tombs.nbytes)
        if rewrites_slots and snap.slot_to_doc is self._slot_to_doc:
            self._slot_to_doc = self._slot_to_doc.copy()
            copied += int(self._slot_to_doc.nbytes)
        if copied:
            led = memory.get_ledger()
            if led is not None:
                led.note_cow(copied)

    # -- landing rows: reused slots first, then the high-water mark ----------

    def _note_write(self, **counts) -> None:
        """Add to `/debug/perf` `writes` (monitoring/perf.py
        WRITE_COUNTERS): what SERVING writes did. A restore lands the
        corpus through the same code and is the restart timeline's: its
        whole-array copies and its grows are `startup`'s counters
        (perf.RESTORE_COUNTERS)."""
        if not self._restoring:
            perf.note_write(**counts)
            return
        tl = perf.timeline()
        if tl is not None:
            kept = {k: v for k, v in counts.items()
                    if k in perf.RESTORE_COUNTERS}
            if kept:
                tl.count(**kept)

    def _reuse_refused(self) -> Optional[str]:
        """Why this index never hands a dead slot to the next row (None: it
        does). The compressed branch lands rows through the codebook in
        whole chunks at an offset, and an IVF bucket table names the
        partition of the slot's OLD row: both keep appending, tombstones
        staying until `compact()`, and say so in `health()`. (The tiled
        layout hands a row a free slot of ITS partition's tile:
        `_place_rows_tiled`.)"""
        if self.compressed:
            return "compressed"
        if self._ivf_centroids_host is not None and not self._ivf_tiled:
            return "ivf_layout"
        return None

    def _slab_bytes(self) -> int:
        """Bytes of the arrays a non-donating row write makes anew."""
        if self.compressed:
            return (memory.array_bytes(self._codes)
                    + memory.array_bytes(self._recon_norms)
                    + memory.array_bytes(self._codes4)
                    + memory.array_bytes(self._recon_norms4)
                    + memory.array_bytes(self._rescore_dev)
                    + memory.array_bytes(self._rescore_sq_norms))
        return memory.array_bytes(self._store) + (
            memory.array_bytes(self._sq_norms)
            if self.metric == vi.DISTANCE_L2 else 0)

    def _place_rows(self, docs: np.ndarray, rows: np.ndarray) -> None:
        """Give [count, D] rows (normalised, logged, none of them known to
        the index) their slots and land them: the one way in for a flush
        and for a fresh batch; the caller holds the lock. Dead slots go
        first: those of the tombstones staged WITH this write (an upsert's
        old version: the new row overwrites it in place and the tombstone
        bit is never set), then the free list; the rest is appended at `n`.
        Rows for handed-out slots, and an appended run shorter than a
        `_CHUNK`, are ONE `_write_slots` program a `_CHUNK` of rows, with
        whatever tombstones are still staged; a longer appended run takes
        the chunked `_write_block` (the build's and the restore's programs,
        unchanged). What a published snapshot pins is never written: the
        device arrays are functional updates and the host mirrors are
        copied first where the snapshot shares them."""
        if self._ivf_tiled:
            self._place_rows_tiled(docs, rows)
            return
        count = len(docs)
        dead, free = self._pending_tombs, self._free_slots
        take_dead = take_free = 0
        if self._reuse_refused() is None:
            take_dead = min(count, len(dead))
            take_free = min(count - take_dead, len(free))
        reused = take_dead + take_free
        slots = np.empty(reused, np.int64)
        if take_dead:
            slots[:take_dead] = dead[len(dead) - take_dead:]
            del dead[len(dead) - take_dead:]
        if take_free:
            slots[take_dead:] = free[len(free) - take_free:]
            del free[len(free) - take_free:]
        rest = count - reused
        small = rest if (not self.compressed and rest < _CHUNK) else 0
        self._cow_host_state(rewrites_slots=reused > 0)
        if reused:
            # the slot layout under a cached filter changed without `n`
            # moving: no packed-words or slot-list cache keyed on the old
            # layout may be served again
            self._allow_token = object()
            self._docs_ascending = False
            self._wstats["slots_reused"] += reused
        if reused + small:
            self._ensure_capacity(self.n + small)
            head = reused + small
            at = np.concatenate(
                [slots, np.arange(self.n, self.n + small, dtype=np.int64)])
            if small:
                if not reused:
                    self._note_docs_appended(docs[:head])
                self._ivf_on_rows_written(rows[reused:head], self.n)
            self._write_small(docs[:head], rows[:head], at,
                              cleared=slots[take_dead:])
            self.n += small
        if rest - small:
            tail_docs, tail = docs[reused + small:], rows[reused + small:]
            # to the end of the last padded chunk: what `_land_rows` needs
            # and what a restart that lands the same rows asks for
            self._ensure_capacity(
                self.n + -(-len(tail_docs) // _CHUNK) * _CHUNK)
            self._write_block(np.ascontiguousarray(tail), self.n)
            self._note_docs_appended(tail_docs)
            self._slot_to_doc[self.n : self.n + len(tail_docs)] = tail_docs
            self._stage_doc_ids(tail_docs, self.n)
            self._doc_to_slot.update(zip(
                tail_docs.tolist(),
                range(self.n, self.n + len(tail_docs))))
            self.n += len(tail_docs)
        self._wstats["slots_appended"] += rest
        self._note_write(slots_reused=reused, slots_appended=rest)

    def _write_small(self, docs: np.ndarray, rows: np.ndarray,
                     slots: np.ndarray, cleared: np.ndarray) -> None:
        """`_write_slots` over rows for arbitrary slots, a `_CHUNK` at a
        time, padded to the `_bucket_rows` widths; the tombstones still
        staged ride the first program. `cleared`: the slots among them
        that come from the free list (their tombstone bit is set)."""
        t0 = time.perf_counter()
        l2 = self.metric == vi.DISTANCE_L2
        sentinel = self.capacity + 1     # out of range: mode="drop"
        dead = np.asarray(self._pending_tombs, np.int64)
        self._pending_tombs.clear()
        for off in range(0, len(slots), _CHUNK):
            sl = slots[off : off + _CHUNK]
            count, pad = len(sl), _bucket_rows(len(sl))
            idx = np.full(pad, sentinel, np.int32)
            idx[:count] = sl
            buf = np.zeros((pad, self.dim), np.float32)
            buf[:count] = rows[off : off + count]
            pairs = _doc_pairs(docs[off : off + count], pad)
            norms = None
            if l2:
                norms = np.zeros(pad, np.float32)
                norms[:count] = np.einsum(
                    "ij,ij->i", buf[:count], buf[:count], dtype=np.float64)  # graftlint: disable=JGL006 host-side numpy norms: f64 accumulation without a full f64 temp, cast to f32 before the upload (the einsum idiom of `_land_rows`)
            d = dead if off == 0 else dead[:0]
            didx = np.full(_bucket_rows(len(d)), sentinel, np.int32)
            didx[: len(d)] = d
            store, sq_norms, self._s2d_dev, self._tombs = self._run_write(
                _write_slots,
                self._store, self._sq_norms if l2 else None, self._s2d_dev,
                self._tombs, jnp.asarray(idx), jnp.asarray(buf),
                None if norms is None else jnp.asarray(norms),
                jnp.asarray(pairs), jnp.asarray(didx), written=4)
            self._store = store
            if l2:
                self._sq_norms = sq_norms
            self._note_write(
                upload_bytes=buf.nbytes + pairs.nbytes + idx.nbytes
                + didx.nbytes + (norms.nbytes if l2 else 0))
            led = memory.get_ledger()
            if led is not None:
                led.note_write_shape(
                    ("write_slots", self.capacity, self.dim, pad, len(didx)))
        self._slot_to_doc[slots] = docs
        self._host_tombs[cleared] = False
        self._doc_to_slot.update(zip(docs.tolist(), slots.tolist()))
        self._tombstones_applied(dead, t0)
        self._stamp_memory()

    def _tombstones_applied(self, dead: np.ndarray, t0: float) -> None:
        """Tombstones whose device bit was just set by a program enqueued
        since `t0`: the host mirror, the free list, the counts."""
        if len(dead) == 0:
            return
        self._host_tombs[dead] = True
        if self._ivf_tiled:
            # a tile's free slots are its tombstoned ones: counted, not listed
            self._ivf_free_n += np.bincount(
                dead // self._ivf_cap_p, minlength=len(self._ivf_free_n))
            self._ivf_unsaved = True
        else:
            self._free_slots.extend(dead.tolist())
        self._wstats["tombstones_applied"] += len(dead)
        self._note_write(tombstones_applied=len(dead))
        self._obs_index("delete", "apply_tombstones", t0, ops=len(dead))
        led = memory.get_ledger()
        if led is not None:
            led.note_write(
                "delete", "apply_tombstones",
                (time.perf_counter() - t0) * 1000.0, rows=len(dead))

    def _flush_pending(self) -> None:
        flushed = bool(self._pending or self._pending_tombs)
        led = memory.get_ledger()
        if self._pending:
            t0 = time.perf_counter()
            rows = np.stack(list(self._pending.values()))
            docs = np.array(list(self._pending.keys()), dtype=np.int64)
            count = rows.shape[0]
            self._pending.clear()
            self._place_rows(docs, rows)
            self._obs_index("add", "flush", t0, ops=count)
            if led is not None:
                led.note_write(
                    "add", "flush", (time.perf_counter() - t0) * 1000.0,
                    rows=count, bytes_moved=count * (self.dim or 0) * 4)
        self._apply_pending_tombs()
        if flushed:
            # gauges refresh only when state changed: _flush_pending runs at
            # the top of every search and must stay free on the hot path
            self._update_index_gauges()
        self._maybe_declared_compress()
        self._maybe_ivf_train()
        if (flushed or self._published_gen != self._staged_gen) \
                and not self._restoring:
            # publication is the LAST step: readers grabbing the new
            # reference must see every staged mutation already applied.
            # Not inside a restore or a compaction's rebuild: nothing reads
            # then, and a snapshot published between two replayed runs pins
            # a third generation of the slab beside the two a write holds
            # (its end publishes)
            self._publish_snapshot()

    def _apply_pending_tombs(self) -> None:
        """Set the device bit of the tombstones no row of this write took
        the slot of (a delete without a re-put, an index that refuses
        reuse, a row run that went the chunked way)."""
        if not self._pending_tombs:
            return
        t0 = time.perf_counter()
        self._cow_host_state()
        idx = np.array(self._pending_tombs, dtype=np.int32)
        self._pending_tombs.clear()
        pad = _bucket_rows(len(idx))
        padded = np.full(pad, self.capacity + 1, dtype=np.int32)
        padded[: len(idx)] = idx
        self._tombs = self._run_write(
            _set_tombstones, self._tombs, jnp.asarray(padded))
        self._tombstones_applied(idx.astype(np.int64), t0)
        led = memory.get_ledger()
        if led is not None:
            led.note_write_shape(("set_tombstones", self.capacity, pad))
        self._stamp_memory()

    def _maybe_declared_compress(self) -> None:
        # pq.enabled set at class creation: compress once the rows the
        # documented procedure imports before it enables pq are there, the
        # class's `trainingLimit` (the reference requires an explicit
        # post-import config update; we also honor the declarative form,
        # and fit on those rows as it does). Evaluated on every flush AND
        # every direct batch write — the snapshot read path no longer
        # flushes on each search, so writes must carry the trigger
        if (
            self.config.pq.enabled
            and not self.compressed
            and not self._restoring
            and self.n >= self.config.pq.training_limit
        ):
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

    # -- IVF scan plane: write-path training / layout maintenance ------------
    # (ROADMAP item 3.) The clustered layout is WRITE-PATH state like the
    # PQ codebook: k-means trains under the index lock once enough rows
    # exist, every later row run is assigned to its nearest centroid as
    # it lands (host matmul over the rows the write already holds — no
    # device fetch), and the padded partition buckets are rebuilt before
    # the next snapshot publish so readers always see a layout that
    # matches the slot space they dispatch on. All of it is a
    # one-comparison no-op while IVF_ENABLED is off.

    def _ivf_on_rows_written(self, rows: np.ndarray, start: int) -> None:
        """Assign a freshly-written row run to the trained layout (and
        mirror its PCA projection onto the device low-dim table). Rides
        _write_block, so every write path — flush, bulk import, restore,
        compact rebuild — maintains the layout through one hook."""
        cent = self._ivf_centroids_host
        if cent is None:
            return
        count = rows.shape[0]
        assign = ivf_ops.assign_partitions(rows, cent)
        if self._ivf_assign.shape[0] < self.capacity:
            ia = np.full(self.capacity, -1, np.int32)
            ia[: self._ivf_assign.shape[0]] = self._ivf_assign
            self._ivf_assign = ia
        self._ivf_assign[start: start + count] = assign
        if self._ivf_pca_host is not None:
            self._write_ivf_pca(rows @ self._ivf_pca_host, start)
        # queue the run for the O(batch) incremental bucket fold at the
        # next publish (_ivf_apply_pending)
        self._ivf_pending_slots.append(
            (np.arange(start, start + count, dtype=np.int32), assign))
        self._ivf_dirty = True

    def _write_ivf_pca(self, block: np.ndarray, start: int) -> None:
        """Scatter a [count, dp] PCA row run into the device table,
        padded to the shared pow2 row buckets (bounded jit shapes)."""
        if self._ivf_pca_rows is None:
            return
        count = block.shape[0]
        pad = _bucket_rows(count)
        idx = np.full(pad, self.capacity + 1, dtype=np.int32)
        idx[:count] = np.arange(start, start + count, dtype=np.int32)
        rows = np.zeros((pad, block.shape[1]), np.float32)
        rows[:count] = block
        self._ivf_pca_rows = _scatter_rows(
            self._ivf_pca_rows, jnp.asarray(idx), jnp.asarray(rows))
        self._stamp_memory()

    def _ivf_nlist(self, s: IvfConfig, n: int) -> int:
        """Partition count for an n-row layout: the configured value, or
        auto targeting ~256 rows per partition snapped to a pow2 —
        measured on the CPU A/B, fill-targeted sizing beats the sqrt(n)
        rule by 2-4x in both probe recall and probed_fraction (finer
        partitions localize better AND shrink the padded bucket the
        probe pays for); bounded so no layout averages fewer than ~32
        rows per partition."""
        if s.nlist > 0:
            return max(1, min(s.nlist, max(n // 8, 1)))
        import math

        # ceil, not round: rounding DOWN doubles the mean fill (and with
        # it the padded bucket every probe reads). The 4096 ceiling is
        # the HOST k-means budget: training is a write-lock pause, and
        # past ~4096 partitions the fit/assignment cost stops being one
        # (device-side training is the 10M-scale follow-up, ROADMAP
        # item 3) — beyond it the layout goes coarser, not slower
        target = 2 ** int(math.ceil(math.log2(max(n / 256.0, 16.0))))
        return int(max(16, min(target, 4096, max(n // 32, 16))))

    def _ivf_rows_for_training(self) -> np.ndarray:
        """The occupied store rows, host-side, for k-means/PCA fitting.
        Under PQ the f32 rows already live host-side (host_vecs); the
        uncompressed store pays ONE bulk fetch under the write lock —
        the same stop-the-world cold-path trade as compact/compress
        (the graftsan baseline carries the mirrored runtime waiver)."""
        if self.compressed and self._host_vecs is not None:
            return self._host_vecs[: self.n]
        return np.asarray(self._store[: self.n]).astype(np.float32, copy=False)  # graftlint: disable=JGL001 recluster is a write-path cold pass like compress: the k-means fit runs host-side, so the store must materialize once under the lock that covers the layout swap

    def _maybe_ivf_train(self) -> None:
        """Declarative training/recluster trigger (the write-path twin of
        _maybe_declared_compress): train once min_n rows exist, retrain
        once n outgrows the trained layout by retrain_growth. One
        comparison while IVF is disabled."""
        s = ivf_settings()
        if s is None or self._restoring or self.dim is None:
            return
        if self.metric not in ivf_ops.MATMUL_METRICS:
            return
        # the tiled layout's slots are nlist * cap_p whatever it holds: its
        # size is its live rows
        rows = self.live if self._ivf_tiled or self._tiles_next(s) else self.n
        if rows < max(s.min_n, 256):
            return
        if self._ivf_centroids is not None and \
                rows < self._ivf_trained_n * (1.0 + s.retrain_growth):
            # the centroids stand; a tiled layout whose live rows passed
            # the rows its tiles were sized for gets larger tiles (same
            # centroids, every row assigned and laid out again: seconds)
            if self._ivf_tiled and \
                    rows >= self._ivf_sized_n * ivf_ops.TILE_HEADROOM:
                self._ivf_train_tiles(s, refit=False)
            return
        self._ivf_train_locked(s)

    def _tiles_next(self, s: IvfConfig) -> bool:
        """Does the next training lay the store out in tiles? An
        uncompressed index without the PCA prefilter does; a compressed one
        (its rows are codes, a bf16 copy and a host array, landed through
        the codebook in whole chunks) and the prefilter (a second per-slot
        table) keep the bucket table over slots in order of arrival."""
        return not self.compressed and int(s.pca_dim) <= 0

    def _ivf_train_locked(self, s: IvfConfig) -> None:
        """Train (or re-train) the clustered layout, then a fresh snapshot
        publishes it. Runs under the index write lock (callers hold it); a
        recluster replaces every array it touches wholesale, so snapshots
        pinned by in-flight dispatches keep their old layout (the COW
        discipline). The tiled layout trains on the device
        (`_ivf_train_tiles`), the bucket table on the host."""
        if self._tiles_next(s):
            self._ivf_train_tiles(s)
        else:
            self._ivf_train_buckets(s)

    def _ivf_train_buckets(self, s: IvfConfig) -> None:
        """The bucket table's training: k-means centroids, full partition
        assignment, optional PCA basis + low-dim rows, padded buckets, on
        the host from the rows it keeps (`host_vecs`) or fetches."""
        t0 = time.perf_counter()
        self._ivf_trains += 1
        n = self.n
        rows = self._ivf_rows_for_training()
        nlist = self._ivf_nlist(s, n)
        # sample floors at 16 rows per centroid: capping at train_sample
        # alone would degenerate a large-nlist fit to ~one row per
        # cluster (the layout would be the sample, not a clustering)
        cent = ivf_ops.kmeans_fit(
            rows, nlist, iters=s.train_iters, seed=self._ivf_gen,
            sample=min(len(rows), max(s.train_sample, nlist * 16)))
        if self.metric == vi.DISTANCE_COSINE:
            nrm = np.linalg.norm(cent, axis=1, keepdims=True)
            nrm[nrm == 0] = 1.0
            cent = cent / nrm
        # capacity-bounded buckets (ops/ivf.balanced_assign): the padded
        # width is pinned by the MEAN fill with 25% slack — pow2-snapped
        # — instead of by the worst cluster, so skewed data cannot make
        # every probe pay a worst-case-sized bucket read; overfull
        # partitions spill their farthest rows to the nearest centroid
        # with space
        cap_t = ivf_ops.bucket_capacity(
            np.array([int(1.25 * n / nlist) + 1]))
        assign = np.full(self.capacity, -1, np.int32)
        assign[:n] = ivf_ops.balanced_assign(rows, cent, cap_t)
        self._ivf_cap_p = cap_t
        self._ivf_centroids_host = cent
        self._ivf_assign = assign
        self._ivf_centroids = jax.device_put(jnp.asarray(cent), self.device)
        dp = int(s.pca_dim)
        if 0 < dp < self.dim:
            # a RANDOM sample, like the k-means fit — a prefix slice
            # would bias the basis to insertion-ordered data (early
            # tenants/domains) and silently misrank later rows
            psamp = min(len(rows), max(s.train_sample, 4096))
            if psamp < len(rows):
                pick = np.random.default_rng(self._ivf_gen).choice(
                    len(rows), size=psamp, replace=False)
                proj = ivf_ops.pca_fit(rows[pick], dp)
            else:
                proj = ivf_ops.pca_fit(rows, dp)
            self._ivf_pca_host = proj
            self._ivf_pca_proj = jax.device_put(
                jnp.asarray(proj), self.device)
            pr = np.zeros((self.capacity, dp), np.float32)
            pr[:n] = rows @ proj
            self._ivf_pca_rows = jax.device_put(jnp.asarray(pr), self.device)
        else:
            self._ivf_pca_host = None
            self._ivf_pca_proj = None
            self._ivf_pca_rows = None
        self._ivf_trained_n = n
        self._ivf_gen += 1
        self._ivf_rebuild_buckets()  # keeps the balanced cap_t padding
        self._staged_gen += 1
        self._mark_staged()
        self._stamp_memory()
        ms = (time.perf_counter() - t0) * 1000.0
        led = memory.get_ledger()
        if led is not None:
            led.note_write("ivf", "recluster", ms, rows=n)
        incidents.emit("write_phase", scope="ivf_recluster", rows=n,
                       nlist=nlist, ms=round(ms, 1))

    def _ivf_apply_pending(self) -> None:
        """Fold freshly-written slots into the padded buckets: an
        O(batch) device scatter into each bucket's free columns (fills
        tracked host-side), so a small write's flush cost stays O(batch)
        like the flat write path — the full O(n log n) rebuild + whole-
        table upload runs only when a bucket overflows its padding
        (which widens it) or after a retrain."""
        pend, self._ivf_pending_slots = self._ivf_pending_slots, []
        if self._ivf_buckets is None or self._ivf_fills is None or not pend:
            self._ivf_rebuild_buckets()
            return
        slots = np.concatenate([s for s, _ in pend])
        parts = np.concatenate([p for _, p in pend])
        nlist = self._ivf_fills.shape[0]
        counts = np.bincount(parts, minlength=nlist)
        if bool((self._ivf_fills + counts > self._ivf_cap_p).any()):
            self._ivf_rebuild_buckets()
            return
        order = np.argsort(parts, kind="stable")
        sp, ss = parts[order], slots[order]
        starts = np.zeros(nlist + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        cols = (np.arange(sp.size, dtype=np.int64) - starts[sp]
                + self._ivf_fills[sp]).astype(np.int32)
        pad = _bucket_rows(sp.size)
        pi = np.full(pad, nlist + 1, np.int32)  # out of range: dropped
        ci = np.zeros(pad, np.int32)
        si = np.full(pad, -1, np.int32)
        pi[: sp.size] = sp
        ci[: sp.size] = cols
        si[: sp.size] = ss
        self._ivf_buckets = _scatter_bucket(
            self._ivf_buckets, jnp.asarray(pi), jnp.asarray(ci),
            jnp.asarray(si))
        self._ivf_fills = self._ivf_fills + counts
        self._ivf_dirty = False
        self._stamp_memory()

    def _ivf_rebuild_buckets(self) -> None:
        """Rebuild the padded partition buckets from the host assignment
        (one vectorized bucket sort + one device upload). The padding
        width cap_p is KEPT while every bucket still fits — the
        jit-shape stability contract: a handful of inserts re-uploads
        the [nlist, cap_p] table but never re-compiles the search — and
        pow2-widens only on overflow."""
        cent = self._ivf_centroids_host
        if cent is None:
            return
        nlist = cent.shape[0]
        buckets, fills = ivf_ops.build_buckets(
            self._ivf_assign, nlist, self._ivf_cap_p)
        self._ivf_cap_p = int(buckets.shape[1])
        self._ivf_fills = fills
        self._ivf_buckets = jax.device_put(jnp.asarray(buckets), self.device)
        self._ivf_meta = (nlist, self._ivf_cap_p, self._ivf_gen)
        self._ivf_pending_slots = []  # the rebuild covered them
        self._ivf_dirty = False
        self._stamp_memory()

    def _ivf_reset(self) -> None:
        """Drop the whole IVF layout (compact's rebuild and drop() call
        this before wiping the slot space the assignments index)."""
        self._ivf_centroids = None
        self._ivf_buckets = None
        self._ivf_pca_proj = None
        self._ivf_pca_rows = None
        self._ivf_centroids_host = None
        self._ivf_pca_host = None
        self._ivf_assign = np.zeros(0, dtype=np.int32)
        self._ivf_fills = None
        self._ivf_meta = None
        self._ivf_cap_p = None
        self._ivf_pending_slots = []
        self._ivf_trained_n = 0
        self._ivf_dirty = False
        self._ivf_tiled = False
        self._ivf_free_n = None
        self._ivf_restore_map = None

    # -- the tiled layout (ops/ivf.py; docs/ivf.md "The layout") -------------

    def _ivf_train_tiles(self, s: IvfConfig, reserve: int = 0,
                         refit: bool = True) -> bool:
        """Train the layout and lay the store out in its order: the fit and
        the assignment run on the device over the store in place (a sample
        is gathered, the slab never copied to the host), the host balances
        the partitions and gives every live row its slot, and ONE gather on
        the device makes the store, its norms, tombstones and doc table in
        the new order (`_relayout`). The arrays the published snapshot holds
        stay valid until it goes, so for the length of this call two
        generations of the slab are alive; where the device has no room for
        the second, nothing is trained and the index serves flat (-> False).
        `reserve`: rows about to land that the tiles must have room for.
        `refit` False keeps the centroids a tiled layout has and only makes
        its tiles anew (a regrow: `tile_capacity`'s headroom is used up).
        The span `ivf.train` (`/debug/traces`, the capture log) and the
        `write_phase` incident carry its pieces in ms."""
        sw = tracing.Stopwatch("ivf.train", rows=self.live)
        t0 = t = time.perf_counter()
        ms: dict = {}

        def lap(name):
            nonlocal t
            now = time.perf_counter()
            ms[name] = round((now - t) * 1000.0, 1)
            t = now

        n_old, dim = self.n, self.dim
        live_slots = np.flatnonzero(~self._host_tombs[:n_old])
        rows = int(live_slots.size)
        refit = refit or not self._ivf_tiled
        nlist = (self._ivf_nlist(s, rows) if refit
                 else self._ivf_centroids_host.shape[0])
        cap_p = ivf_ops.tile_capacity(rows + reserve, nlist)
        cap_new = _fit_capacity(nlist * cap_p)
        if not self._copy_fits(cap_new * self._row_bytes()):
            incidents.emit("write_phase", scope="ivf_recluster_skipped",
                           rows=rows, nlist=nlist,
                           needs_bytes=cap_new * self._row_bytes())
            sw.stop()
            return False
        self._ivf_trains += 1
        if refit:
            rng = np.random.default_rng(self._ivf_gen)
            sample = min(rows, max(s.train_sample, nlist * 16))
            if sample > ivf_ops._DEVICE_BLOCK:   # whole blocks of the fit
                sample -= sample % ivf_ops._DEVICE_BLOCK
            picked = live_slots[np.sort(
                rng.choice(rows, sample, replace=False))]
            cent_dev = ivf_ops.kmeans_fit_device(
                ivf_ops.gather_rows(self._store,
                                    jnp.asarray(picked, jnp.int32)),
                jnp.asarray(rng.choice(sample, nlist, replace=False),
                            jnp.int32),
                iters=int(s.train_iters),
                normalize=self.metric == vi.DISTANCE_COSINE)
            cent = np.asarray(cent_dev)
            trained_n = rows
        else:
            cent_dev, cent = self._ivf_centroids, self._ivf_centroids_host
            trained_n = self._ivf_trained_n
        lap("fit")
        prefs_dev, d0_dev = ivf_ops.nearest_partitions_device(  # graftflow: disable=JGL019 `prefs` is min(PREFS, nlist): eight values at the most, one a layout
            self._store, cent_dev, prefs=min(ivf_ops.PREFS, nlist))
        prefs = np.asarray(prefs_dev)[live_slots]
        d0 = np.asarray(d0_dev)[live_slots]
        lap("assign")
        part = ivf_ops.balance_partitions(
            prefs, d0, np.full(nlist, cap_p, np.int64))
        new_slots = ivf_ops.tile_slots(part, d0, cap_p)
        src = np.full(cap_new, self.capacity, np.int32)   # past the end: fill
        src[new_slots] = live_slots
        docs = np.full(cap_new, -1, np.int64)
        docs[new_slots] = self._slot_to_doc[live_slots]
        free = np.ones(cap_new, dtype=bool)
        free[new_slots] = False
        lap("layout")
        src_dev = jnp.asarray(src)
        store = _relayout(self._store, src_dev)
        sq_norms = None if self._sq_norms is None else _relayout(
            self._sq_norms, src_dev)
        led = memory.get_ledger()
        if led is not None:
            led.note_cow(0, transient_peak=memory.array_bytes(store))
        self._store, self._sq_norms = store, sq_norms
        self._tombs = jax.device_put(jnp.asarray(free), self.device)
        self._s2d_dev = jax.device_put(
            jnp.asarray(_doc_pairs(docs, cap_new)), self.device)
        self._row_store_cache = None
        self._blk_cache.clear()
        # the host mirrors, new objects: a snapshot keeps the old ones
        self._slot_to_doc, self._host_tombs = docs, free
        self._doc_to_slot = dict(zip(docs[new_slots].tolist(),
                                     new_slots.tolist()))
        self._free_slots = []
        self._allow_token = object()
        self._docs_ascending = False
        self.n, self.capacity = nlist * cap_p, cap_new
        self._record_capacity()
        self._ivf_reset()   # whatever layout there was, of either kind
        self._ivf_centroids, self._ivf_centroids_host = cent_dev, cent
        self._ivf_cap_p = cap_p
        self._ivf_free_n = cap_p - np.bincount(part, minlength=nlist)
        self._ivf_tiled = True
        self._ivf_trained_n, self._ivf_sized_n = trained_n, rows + reserve
        self._ivf_gen += 1
        self._ivf_meta = (nlist, cap_p, self._ivf_gen)
        self._ivf_settings = s
        lap("upload")
        self._ivf_persist()
        lap("persist")
        self._staged_gen += 1
        self._mark_staged()
        self._stamp_memory()
        total = (time.perf_counter() - t0) * 1000.0
        sw.note(nlist=nlist, cap_p=cap_p, **{k + "_ms": v
                                             for k, v in ms.items()})
        sw.stop()
        span = tracing.current_span()
        if span is not None:   # a sampled write: the training and its pieces
            done = span.child_done("ivf.train", total, {
                "rows": rows, "nlist": nlist, "cap_p": cap_p})
            for name, piece_ms in ms.items():
                done.child_done("ivf.train." + name, piece_ms)
        if led is not None:
            led.note_write("ivf", "recluster", total, rows=rows)
        incidents.emit("write_phase", scope="ivf_recluster", rows=rows,
                       nlist=nlist, cap_p=cap_p, ms=round(total, 1), **ms)
        return True

    def _place_rows_tiled(self, docs: np.ndarray, rows: np.ndarray) -> None:
        """`_place_rows` under the tiled layout: every row to a free slot of
        its nearest partition's tile, assigned on the host from the rows
        the write holds (`ivf_ops.nearest_partitions`). A row whose
        partition is full takes the next of its `PREFS` nearest with room,
        then the emptiest; a write the tiles have no room for lays the
        store out anew first, with room for it. A restore that read a
        persisted layout gives a doc the slot recorded for it and assigns
        only the docs the layout does not know. The slots of the versions
        this write replaces are freed first, so an upsert's row may take
        its old slot."""
        self._apply_pending_tombs()
        count = len(docs)
        cap_p = self._ivf_cap_p
        nlist = self._ivf_centroids_host.shape[0]
        docs = np.asarray(docs, np.int64)
        slots = np.full(count, -1, np.int64)
        free = self._host_tombs
        known = self._ivf_restore_map
        if known is not None:
            with tracing.piece_of(self._restore_sums, "ivf", self.capacity):
                keys, vals, reserved = known
                if len(keys):
                    at = np.minimum(np.searchsorted(keys, docs),
                                    len(keys) - 1)
                    found = np.flatnonzero(keys[at] == docs)
                    want = vals[at[found]]
                    ok = free[want]     # taken meanwhile: assigned anew
                    slots[found[ok]] = want[ok]
                    reserved[want[ok]] = False
                    self._ivf_restore_stats["placed"] += int(ok.sum())
                # free for the docs the layout does not know: neither
                # reserved for a doc still to come nor just handed out
                free = np.logical_and(free[: self.n], ~reserved[: self.n])
                free[slots[slots >= 0]] = False
        todo = np.flatnonzero(slots < 0)
        if todo.size:
            with tracing.piece_of(self._restore_sums, "ivf", self.capacity):
                room = (free[: self.n].reshape(nlist, cap_p).sum(axis=1)
                        if known is not None else self._ivf_free_n)
                if int(room.sum()) < todo.size:
                    # no tile has the room: a new layout that has it
                    if known is not None:
                        raise RuntimeError(
                            "the persisted IVF layout has no room for the "
                            "rows the log holds")
                    if not self._ivf_train_tiles(
                            ivf_settings() or self._ivf_settings,
                            reserve=count, refit=False):
                        raise RuntimeError(
                            "the IVF layout is full and the device has no "
                            "room for a larger one")
                    self._place_rows_tiled(docs, rows)
                    return
                prefs, d0 = self._ivf_nearest(rows[todo])
                part = ivf_ops.balance_partitions(prefs, d0, room)
                slots[todo] = ivf_ops.place_in_tiles(part, free, cap_p)
                if known is not None:
                    self._ivf_restore_stats["assigned"] += int(todo.size)
        self._ivf_free_n -= np.bincount(slots // cap_p, minlength=nlist)
        self._cow_host_state(rewrites_slots=True)
        # the slot layout under a cached filter changed without `n` moving
        self._allow_token = object()
        self._ivf_unsaved = True
        self._wstats["slots_reused"] += count
        self._note_write(slots_reused=count)
        with tracing.piece_of(self._restore_sums, "land", self.capacity,
                              rows=count):
            self._write_small(docs, rows, slots, cleared=slots)

    # rows of one write from which its assignment runs on the device: a
    # [rows, nlist] product of 10,000 x 4,096 x 768 is seconds of host
    # sgemm a put batch (minutes an import) and milliseconds there, while a
    # batch of a hundred is 10 ms on the host and would wait on the device
    # behind every queued search, under the write lock
    _IVF_ASSIGN_ON_DEVICE = 2048

    def _ivf_nearest(self, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """The `PREFS` nearest partitions of rows about to land -> ([n,
        prefs] ids nearest first, [n] distance to the nearest)."""
        count = rows.shape[0]
        if count < self._IVF_ASSIGN_ON_DEVICE:
            return ivf_ops.nearest_partitions(rows, self._ivf_centroids_host)
        prefs = min(ivf_ops.PREFS, self._ivf_centroids_host.shape[0])
        out = np.empty((count, prefs), np.int32)
        d0 = np.empty(count, np.float32)
        for off in range(0, count, _CHUNK):
            take = min(_CHUNK, count - off)
            buf = np.zeros((_bucket_rows(take), rows.shape[1]), np.float32)
            buf[:take] = rows[off: off + take]
            ids, dist = ivf_ops.nearest_partitions_of_rows(  # graftflow: disable=JGL019 `prefs` is min(PREFS, nlist): eight values at the most, one a layout
                jnp.asarray(buf), self._ivf_centroids, prefs=prefs)
            out[off: off + take] = np.asarray(ids)[:take]
            d0[off: off + take] = np.asarray(dist)[:take]
        return out, d0

    def _ivf_persist(self) -> None:
        """The layout beside the vector log, whole or not at all: the
        centroids, `cap_p`, the generation, the rows it was trained on and
        the doc of every slot (the store's partition order). A restart
        reads it before it replays (`_ivf_load`) and lands every row in the
        slot it had: no training, no assignment."""
        if self._log is None or not self._ivf_tiled:
            return
        nlist, cap_p, gen = self._ivf_meta
        tmp = self._ivf_path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, centroids=self._ivf_centroids_host,
                     meta=np.array([cap_p, gen, self._ivf_trained_n,
                                    nlist * cap_p, self._ivf_sized_n],
                                   np.int64),
                     slot_to_doc=self._slot_to_doc[: nlist * cap_p])
        os.replace(tmp, self._ivf_path)
        self._ivf_unsaved = False

    def _ivf_load(self) -> Optional[PersistedLayout]:
        """The persisted layout of a shard that had one, as
        `_pending_ivf` wants it; None where there is none, the plane is
        off, the index is compressed, or the file cannot be used (the rows
        then train anew at the end of the restore, never inside a search)."""
        s = ivf_settings()
        if s is None or not os.path.exists(self._ivf_path) \
                or self._pending_pq is not None or not self._tiles_next(s):
            return None
        try:
            with np.load(self._ivf_path) as z:
                cent = np.ascontiguousarray(z["centroids"], np.float32)
                cap_p, gen, trained_n, n_slots, sized_n = (
                    int(v) for v in z["meta"])
                docs = np.asarray(z["slot_to_doc"], np.int64)
            if cent.ndim != 2 or docs.shape != (n_slots,) \
                    or n_slots != cent.shape[0] * cap_p or cap_p % 32:
                raise ValueError("layout of another shape than it states")
        except Exception as e:  # noqa: BLE001 — an unusable layout is retrained
            import logging

            logging.getLogger(__name__).warning(
                "persisted IVF layout rejected (%s: %s); training anew",
                type(e).__name__, e)
            return None
        slots = np.flatnonzero(docs >= 0)
        order = np.argsort(docs[slots], kind="stable")
        return PersistedLayout(cent, cap_p, gen, trained_n, sized_n,
                               docs[slots][order], slots[order])

    def _ivf_adopt(self, layout: PersistedLayout) -> None:
        """Enter a persisted layout with no row in it (`_init_device` made
        the arrays at its capacity): every slot of every tile free, the
        docs' recorded slots reserved for them."""
        cent, cap_p, gen, trained_n, sized_n, keys, vals = layout
        nlist = cent.shape[0]
        self.n = nlist * cap_p
        self._host_tombs[:] = True
        self._tombs = jax.device_put(
            jnp.ones((self.capacity,), jnp.bool_), self.device)
        self._ivf_centroids_host = cent
        self._ivf_centroids = jax.device_put(jnp.asarray(cent), self.device)
        self._ivf_cap_p = cap_p
        self._ivf_free_n = np.full(nlist, cap_p, np.int64)
        self._ivf_tiled = True
        self._ivf_gen = gen
        self._ivf_trained_n, self._ivf_sized_n = trained_n, sized_n
        self._ivf_meta = (nlist, cap_p, gen)
        self._ivf_settings = ivf_settings()
        self._docs_ascending = False
        reserved = np.zeros(self.capacity, dtype=bool)
        reserved[vals] = True
        self._ivf_restore_map = (keys, vals, reserved)
        self._stamp_memory()

    def _ivf_end_restore(self) -> None:
        """The layout's half of a restore's end: the persisted map goes,
        and a shard that is large enough and has no layout (none persisted,
        one rejected) or has outgrown the one it read is trained now, on
        the device, so that no search ever is."""
        self._ivf_restore_map = None
        s = ivf_settings()
        if s is None or self.dim is None or self.compressed:
            return
        self._restoring = False
        try:
            self._maybe_ivf_train()
        finally:
            self._restoring = True

    def ivf_stats(self) -> dict:
        """Cumulative probe accounting (bench probed_fraction rows and
        the health() block): dispatches served by the IVF plane, rows
        the probes actually scanned (top_p x cap_p, padding included —
        the honest device-work count), and the flat-scan rows each
        dispatch WOULD have scanned."""
        with self._ivf_lock:
            st = dict(self._ivf_stats)
        st["probed_fraction"] = round(
            st["probed_rows"] / st["base_rows"], 4) if st["base_rows"] \
            else None
        return st

    # -- memory ledger stamping (monitoring/memory.py) -----------------------

    def _memory_components(self) -> dict:
        """Analytic byte sizes of every device buffer this index holds —
        shapes x dtypes only (zero syncs); each value equals the buffer's
        ``nbytes`` exactly. The bounded component names are the
        memory.DEVICE_COMPONENTS taxonomy."""
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
                          ("rescore_store", self._rescore_dev),
                          ("rescore_sq_norms", self._rescore_sq_norms),
                          ("ivf_centroids", self._ivf_centroids),
                          ("ivf_buckets", self._ivf_buckets),
                          ("ivf_pca_proj", self._ivf_pca_proj),
                          ("ivf_pca_rows", self._ivf_pca_rows)):
            b = memory.array_bytes(arr)
            if b:
                comps[name] = b
        return comps

    def _stamp_memory(self) -> None:
        """Stamp the ledger with this index's current device components
        (the JGL012-registered snapshot-builder hook: every method that
        binds a device buffer to a snapshot field flows through here or
        through _publish_snapshot). One comparison when unconfigured."""
        led = memory.get_ledger()
        if led is not None:
            led.stamp_device(self, self._memory_components())

    def _mark_staged(self) -> None:
        """Record the first staged-but-unpublished mutation's time so
        publication can report the staged-generation lag."""
        if self._staged_t0 is None and memory.get_ledger() is not None:
            self._staged_t0 = time.perf_counter()

    # -- snapshot publication / lock-free reads ------------------------------

    def _publish_snapshot(self) -> None:
        """Publish the current device state as a new immutable snapshot
        (one reference swap — callers hold self._lock). Always the LAST
        step of a mutation: a reader that grabs the new reference sees a
        fully applied write."""
        if self._ivf_dirty:
            # partition assignments changed since the last bucket build:
            # the buckets a snapshot carries must describe exactly the
            # slot space its other arrays hold (the staged-generation
            # handshake, extended to the partition table)
            self._ivf_apply_pending()
        self._snap_gen += 1
        self._snap = IndexSnapshot(self._snap_gen, self)
        self._published_gen = self._staged_gen
        self._note_write(snapshots_published=1)
        m = self.metrics
        if m is not None:
            cls, shard = self._metric_labels()
            m.index_snapshot_gen.labels(cls, shard).set(self._snap_gen)
        self._stamp_memory()
        led = memory.get_ledger()
        if led is not None and self._staged_t0 is not None:
            led.note_publish(
                (time.perf_counter() - self._staged_t0) * 1000.0)
        self._staged_t0 = None

    def _read_snapshot(self) -> tuple[IndexSnapshot, float]:
        """-> (the snapshot a search dispatches on, the ms this read waited
        on the write lock). Fast path: one reference read and one
        generation compare, NO lock — concurrent writers cannot block it,
        and the wait is 0.0. Slow path (staged writes not yet published, or
        never published): take the write lock once, flush + publish, and
        observe the wait — this is the read-your-writes pre-read check,
        paid only by the first read after a write."""
        snap = self._snap
        if snap is not None and not snap.lease.retired \
                and self._published_gen == self._staged_gen:
            return snap, 0.0
        t0 = time.perf_counter()
        with self._lock:
            wait_ms = (time.perf_counter() - t0) * 1000.0
            self._flush_pending()
            # retired and never replaced: a write that overwrote the
            # published arrays failed before its publish
            if self._snap is None or self._snap.lease.retired \
                    or self._published_gen != self._staged_gen:
                self._publish_snapshot()
            snap = self._snap
        perf.note_read_lock_wait(wait_ms)
        m = self.metrics
        if m is not None:
            cls, shard = self._metric_labels()
            m.index_lock_wait.labels(cls, shard).observe(wait_ms)
        return snap, wait_ms

    @property
    def snapshot_gen(self) -> int:
        """Published snapshot generation (0 = never published)."""
        snap = self._snap
        return snap.gen if snap is not None else 0

    def _track_inflight(self, delta: int) -> None:
        """Enqueued-but-not-finalized dispatch count (the read pipeline's
        depth). The labeled gauge child resolves ONCE — per-dispatch cost
        is one small lock and one gauge set."""
        with self._inflight_lock:
            self._inflight += delta
            val = self._inflight
        g = self._inflight_gauge
        if g is None:
            if self.metrics is None:
                return
            cls, shard = self._metric_labels()
            g = self.metrics.index_inflight_dispatches.labels(cls, shard)
            self._inflight_gauge = g
        g.set(val)

    # -- product quantization (compress.go analog) ---------------------------

    def compress(self) -> None:
        """Fit PQ on the current store, encode all rows, swap the device
        float store for codes (compress.go:39: fit on cached vectors, encode,
        persist codebook, drop float cache, flip compressed)."""
        with self._lock:
            self._pending_flush_for_compress()
            self._compress_locked()

    def _pending_flush_for_compress(self) -> None:
        if self._pending or self._pending_tombs:
            self._flush_pending()

    def _compress_locked(self) -> None:
        from weaviate_tpu.compress.pq import ProductQuantizer

        if self.compressed:
            return
        if self.n == 0:
            raise RuntimeError("compress requires imported vectors to fit on")
        pq = ProductQuantizer(
            dim=self.dim,
            segments=self.config.pq.segments,
            centroids=self.config.pq.centroids,
            metric=self.metric,
            encoder=self.config.pq.encoder.type,
            distribution=self.config.pq.encoder.distribution,
            rotation=self.config.pq.rotation,
        )
        vecs = np.asarray(self._store[: self.n], dtype=np.float32)
        if self._ivf_tiled:
            # the tiles' empty slots hold zero rows: fit on the live ones,
            # and keep the layout as a bucket table over the same slots (a
            # compressed index's rows are gathered by slot: `_tiles_next`)
            live = ~self._host_tombs[: self.n]
            pq.fit(vecs[live], sample_max=self.config.pq.training_limit)
            self._ivf_assign = np.where(
                live, np.arange(self.n) // self._ivf_cap_p, -1
            ).astype(np.int32)
            self._ivf_tiled = False
            self._ivf_free_n = None
            # a bucket table's size is its slots (`_maybe_ivf_train`)
            self._ivf_trained_n = self.n
            self._ivf_rebuild_buckets()
            try:
                os.remove(self._ivf_path)
            except OSError:
                pass
        else:
            pq.fit(vecs, sample_max=self.config.pq.training_limit)
        self._enable_pq(pq, vecs, save=True)

    def _fit_pq4(self, pq, vecs_n: np.ndarray):
        """Fit the funnel's 4-bit sub-quantizer: same segment count as the
        8-bit quantizer, 16 centroids per segment, ranked in the SAME
        rotated space (the 8-bit quantizer's OPQ rotation is pinned, not
        re-learned — both ladders of the funnel then agree on geometry and
        queries rotate once per dispatch)."""
        from weaviate_tpu.compress.pq import ProductQuantizer

        pq4 = ProductQuantizer(
            dim=self.dim,
            segments=pq.segments,
            centroids=16,
            metric=self.metric,
            encoder=vi.PQ_ENCODER_KMEANS,
            distribution=self.config.pq.encoder.distribution,
            rotation=vi.PQ_ROTATION_NONE,
        )
        pq4.fit(vecs_n, rotation_matrix=pq.rotation_matrix,
                sample_max=self.config.pq.training_limit)
        return pq4

    def _alloc_compressed(self, pq, pq4) -> None:
        """Enter the compressed form at the current capacity with no row
        in it: zeroed codes, reconstruction norms, the bf16 copy the fast
        scan reads (unless pq.rescore is off: the memory-tightest tier)
        and the host's float32 rows; the float32 slab, if there is one, is
        let go. Rows then land through `_land_rows`."""
        dev, cap = self.device, self.capacity
        self._pq = pq
        self._codes = jax.device_put(
            jnp.zeros((cap, pq.segments), pq.code_dtype), dev)
        self._recon_norms = jax.device_put(
            jnp.zeros((cap,), jnp.float32), dev)
        self._host_vecs = np.zeros((cap, self.dim), np.float32)
        # the bf16 copy stays in HBM: half the f32 footprint the codes
        # replace, and the scan that selects the candidates reads it
        rescore = self.config.pq.rescore
        if rescore:
            # the library that scores its candidates (`_rescore_f32`) is
            # built, if this checkout has not yet, and loaded HERE: a
            # restore or a compression, never a request
            rescore_native.load()
        self._rescore_dev = (jax.device_put(
            jnp.zeros((cap, self.dim), jnp.bfloat16), dev)
            if rescore else None)
        # only l2 reads the norms
        self._rescore_sq_norms = (jax.device_put(
            jnp.zeros((cap,), jnp.float32), dev)
            if rescore and self.metric == vi.DISTANCE_L2 else None)
        self._set_pq4(pq4)
        self._store = None
        self._sq_norms = None
        self.compressed = True
        if not self.config.pq.enabled:
            self.config.pq.enabled = True
        self._stamp_memory()

    def _set_pq4(self, pq4) -> None:
        """The 4-bit funnel ladder (pq.bits=4), empty: a SECOND
        16-centroid quantizer fit in the 8-bit quantizer's rotated space
        (its OPQ rotation is PINNED via fit(rotation_matrix=...), so the
        Procrustes alternation runs once per compress, not once per bit
        depth) — nibble-packed codes halve the code bytes again and serve
        as the funnel's stage-1 scan plane, with the 8-bit codes as stage
        2. None: no ladder."""
        dev, cap = self.device, self.capacity
        self._pq4 = pq4
        self._pq4_cb = None
        self._codes4 = self._recon_norms4 = self._opq_rot_dev = None
        if pq4 is not None:
            self._codes4 = jax.device_put(
                jnp.zeros((cap, pq4.segments // 2), jnp.uint8), dev)
            self._recon_norms4 = jax.device_put(
                jnp.zeros((cap,), jnp.float32), dev)
            if pq4.rotation_matrix is not None:
                self._opq_rot_dev = jax.device_put(
                    jnp.asarray(pq4.rotation_matrix, jnp.float32), dev)
        self._stamp_memory()

    def _land_chunk4(self, chunk: np.ndarray, at: int) -> None:
        """One [_CHUNK, D] chunk into the funnel's ladder at slot `at`:
        its nibble-packed 4-bit codes and their reconstruction norms."""
        from weaviate_tpu.compress import pq as pq_mod

        codes4 = self._pq4.encode(chunk)  # [_CHUNK, M] 0..15
        self._codes4 = _write_rows(
            self._codes4, jnp.asarray(pq_mod.pack_codes4(codes4)), at)
        self._recon_norms4 = _write_norms(
            self._recon_norms4,
            jnp.asarray(self._pq4.recon_sq_norms(codes4)), at)
        self._stamp_memory()

    def _encode_pq4(self, vecs_n: np.ndarray) -> None:
        """Fill the funnel's ladder alone for rows [0, n) (a restore whose
        pq4.npz was missing or stale, after the replay)."""
        for off in range(0, len(vecs_n), _CHUNK):
            chunk = np.zeros((_CHUNK, self.dim), np.float32)
            chunk[: len(vecs_n) - off] = vecs_n[off : off + _CHUNK]
            self._land_chunk4(chunk, off)

    def _enable_pq(self, pq, vecs_n: np.ndarray, save: bool) -> None:
        """Compress an index that holds its rows uncompressed (an explicit
        enable, the declared form at its `trainingLimit`): `vecs_n` [n, D]
        are those rows."""
        t0 = time.perf_counter()
        pq4 = self._fit_pq4(pq, vecs_n) if self.config.pq.bits == 4 else None
        self._alloc_compressed(pq, pq4)
        self._land_rows(vecs_n, 0)
        if save and self._log is not None:
            pq.save(self._pq_path)
            if self._pq4 is not None:
                self._pq4.save(self._pq4_path)
        self._staged_gen += 1
        self._mark_staged()
        led = memory.get_ledger()
        if led is not None:
            led.note_write(
                "compress", "compress", (time.perf_counter() - t0) * 1000.0,
                rows=self.n, bytes_moved=memory.array_bytes(self._codes))
        incidents.emit("write_phase", scope="compress", rows=self.n,
                       ms=round((time.perf_counter() - t0) * 1000.0, 1))
        self._publish_snapshot()

    # -- VectorIndex ---------------------------------------------------------

    def add(self, doc_id: int, vector: np.ndarray) -> None:
        with self._lock:
            self._stage_add(int(doc_id), vector)

    def add_batch(self, doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        """Bulk import. Fresh doc_ids take a fully-vectorized path (the common
        batch-import case, shard_write_batch_objects.go); doc_ids that collide
        with existing/staged entries fall back to per-row staging."""
        self.replace_batch((), doc_ids, vectors)

    def replace_batch(self, old_doc_ids: Sequence[int],
                      doc_ids: Sequence[int], vectors: np.ndarray) -> None:
        """Delete `old_doc_ids` and add the batch under ONE hold of the
        index lock, published as ONE snapshot: a reader sees every row of
        an upsert in its old version or in its new one, never neither and
        never both (`db/shard.py put_batch` gives a re-put a fresh doc id
        and hands the old ones over here). The old slots are the first the
        new rows take (`_place_rows`). The write's phases are those of
        `/debug/perf` `writes`: `index_lock_wait`, `index` (collision
        check, log append, slots), `device_write` (the write programs
        enqueued), `publish`."""
        doc_arr = np.asarray(doc_ids, dtype=np.int64)
        vectors = np.asarray(vectors, dtype=np.float32)
        wait = tracing.Stopwatch("write.index_lock_wait")
        self._lock.acquire()
        wait.stop()
        try:
            with tracing.Stopwatch("write.index") as held:
                dev, pub = self._replace_locked(old_doc_ids, doc_arr, vectors)
        finally:
            self._lock.release()
        self._note_write(rows=len(doc_arr), batches=1)
        perf.note_write_phase("index_held", held.ms)
        tracing.write_stage("index_lock_wait", wait.ms)
        tracing.write_stage("index", max(held.ms - dev - pub, 0.0))
        tracing.write_stage("device_write", dev)
        tracing.write_stage("publish", pub)

    def _replace_locked(self, old_doc_ids, doc_arr: np.ndarray,
                        vectors: np.ndarray) -> tuple[float, float]:
        """-> (ms in `device_write`, ms in `publish`)."""
        for d in old_doc_ids:
            self._stage_delete(int(d))
        if len(doc_arr) == 0:
            return 0.0, 0.0
        ids = doc_arr.tolist()
        d2s = self._doc_to_slot
        # O(batch): dict membership, never a pass over the live docs
        fresh = (not self._pending and len(set(ids)) == len(ids)
                 and not any(d in d2s for d in ids))
        if not fresh or vectors.ndim != 2:
            for d, v in zip(doc_arr, vectors):
                self._stage_add(int(d), v)
            return 0.0, 0.0
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(vectors, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            vectors = vectors / norms
        if self.dim is None:
            self._init_device(int(vectors.shape[1]))
        elif vectors.shape[1] != self.dim:
            raise ValueError(f"dim mismatch: index has {self.dim}, got {vectors.shape[1]}")
        if self._log is not None:
            self._log.append_add_batch(doc_arr, vectors)
        t0 = time.perf_counter()
        count = vectors.shape[0]
        self._staged_gen += 1
        self._mark_staged()
        with tracing.Stopwatch("write.device_write", rows=count) as dev:
            self._place_rows(doc_arr, vectors)
            self._apply_pending_tombs()
        self.live += count
        self._obs_index("add", "device_write", t0, ops=count)
        led = memory.get_ledger()
        if led is not None:
            led.note_write(
                "add", "device_write",
                (time.perf_counter() - t0) * 1000.0,
                rows=count, bytes_moved=count * self.dim * 4)
        self._update_index_gauges()
        self._maybe_declared_compress()
        self._maybe_ivf_train()
        with tracing.Stopwatch("write.publish") as pub:
            self._publish_snapshot()
        return dev.ms, pub.ms

    def delete(self, *doc_ids: int) -> None:
        with self._lock:
            for d in doc_ids:
                self._stage_delete(int(d))

    def contains(self, doc_id: int) -> bool:
        with self._lock:
            return doc_id in self._doc_to_slot or doc_id in self._pending

    def __len__(self) -> int:
        return self.live

    def distancer_name(self) -> str:
        return self.metric

    # -- index metrics (hnsw metrics.go / insert_metrics.go parity;
    # _obs_index/_metric_labels inherited from VectorIndex) ------------------

    def _update_index_gauges(self) -> None:
        m = self.metrics
        if m is None:
            return
        cls, shard = self._metric_labels()
        m.vector_index_tombstones.labels(cls, shard).set(self.n - self.live)
        m.vector_index_size.labels(cls, shard).set(self.capacity)
        # cheap always-on health gauges (the /debug/index satellites):
        # stamped here on the write path, so quality reporting needs
        # neither tracing nor auditing enabled
        m.vector_index_live.labels(cls, shard).set(self.live)
        m.index_tombstone_fraction.labels(cls, shard).set(
            (self.n - self.live) / self.n if self.n > 0 else 0.0)
        if self.dim:
            m.vector_dimensions.labels(cls, shard).set(self.live * self.dim)
            if self.compressed and self._pq is not None:
                m.vector_segments.labels(cls, shard).set(self.live * self._pq.segments)

    # -- fused group-min fast scan (ops/gmin_scan.py) ------------------------

    def _gen_blocks(self, arr, build_fn):
        """Generation-cached block layout for `arr` (the store, the bf16
        rescore store, or the PQ codes): rebuilt only when the underlying
        array object changes (copy-on-write updates replace it). On every
        miss, entries whose source array is no longer a live index member
        are dropped FIRST — a replaced store generation plus its block
        layout (~1 GB HBM at 1M x 128 f32) must free before the new one
        builds, and still-valid entries for the other arrays stay cached.
        Concurrent snapshot readers may race here: dict get/set/pop are
        atomic under the GIL and a lost race only recomputes a layout."""
        hit = self._blk_cache.get(id(arr))
        if hit is not None and hit[0] is arr:
            return hit[1]
        live = {id(x) for x in (self._store, self._rescore_dev, self._codes)
                if x is not None}
        for k in [k for k in list(self._blk_cache) if k not in live]:
            self._blk_cache.pop(k, None)
        blk = build_fn(arr)
        self._blk_cache[id(arr)] = (arr, blk)
        return blk

    def _search_full_gmin(self, snap: IndexSnapshot, q: np.ndarray, kk: int,
                          allow_words, gmin: tuple[int, int], store=None,
                          sq_norms=None):
        """`gmin`: the plan's (groups kept, live store slices). `store`
        given: a compressed index's bf16 rows, and the program returns its
        `_candidate_depth` best by them, slots kept (the groups kept are
        still sized by kk)."""
        from weaviate_tpu.ops import gmin_scan

        interpret = device.pallas_interpret()
        s = snap.store if store is None else store
        args = (
            s,
            snap.sq_norms if sq_norms is None else sq_norms,
            snap.tombs,
            snap.n,
            jnp.asarray(q),
            allow_words if allow_words is not None
            else jnp.zeros((snap.capacity // 32,), jnp.uint32),
        )
        statics = (
            allow_words is not None,
            kk if store is None else self._candidate_depth(kk, snap.n),
            self.metric,
            *gmin,
            interpret,
            self._gen_blocks(s, gmin_scan.build_rescore_blocks),
        )
        return gmin_scan.search_gmin_fused(*args, snap.slot_to_doc_dev,
                                           *statics,
                                           with_slots=store is not None)

    def _gmin_packed_or_none(self, snap: IndexSnapshot, q: np.ndarray,
                             kk: int, allow_words, gmin: tuple[int, int],
                             store=None, sq_norms=None):
        """Run the fused scan the plan chose (`index/plan.py`: eligible, and
        by `gmin_scan.kernel_serves` the faster program at this width), or
        None where Mosaic refuses it: the lax.scan program then runs.
        Validation is per compiled shape: each distinct (b, k, rg,
        active_g, use_allow) is a separate Mosaic compilation with its own
        VMEM footprint (active_g grows as the slab fills), so a failure on a
        NEW shape falls back for that shape only, while a failure on a shape
        that already completed a materialized search is a real runtime
        fault and propagates instead of silently halving throughput."""
        from weaviate_tpu.ops import gmin_scan

        # capacity is part of the key: the compilation is parameterized by
        # the [capacity, D] store, so growth invalidates prior validation
        key = (q.shape[0], kk, *gmin, snap.capacity, allow_words is not None,
               store is not None)
        return gmin_scan.guarded_kernel_call(
            self, key,
            lambda: self._search_full_gmin(snap, q, kk, allow_words, gmin,
                                           store, sq_norms),
            "fused gmin kernel", component="index.tpu.gmin")

    def _pq_gmin_packed_or_none(self, snap: IndexSnapshot, q: np.ndarray,
                                b: int, k: int, allow_list):
        """Run the fused PQ codes kernel, or None for the legacy recon
        scan. Same per-shape validation contract as the dense kernel, on a
        SEPARATE failure domain (self._pqg_state); gating and codebook
        constants are the shared helpers in ops/pq_gmin.py."""
        from weaviate_tpu.ops import gmin_scan, pq_gmin

        ncols = snap.capacity // gmin_scan.G
        kk = min(k, snap.live)
        active_g = max(1, -(-snap.n // ncols))
        rg = pq_gmin.eligible_rg(
            self._pqg_state, getattr(self.config, "exact_topk", False),
            self.metric, snap.pq, q.shape[0], ncols, kk, snap.dim, active_g,
            component="index.tpu.pq_gmin")
        if rg is None:
            return None
        m, c = snap.pq.segments, snap.pq.centroids
        interpret = device.pallas_interpret()
        use_allow = allow_list is not None
        words = (self._allow_words(snap, allow_list) if use_allow
                 else jnp.zeros((snap.capacity // 32,), jnp.uint32))
        cb_chunks, flat_cb = pq_gmin.cached_cb_constants(self, snap.pq)
        key = (q.shape[0], kk, rg, active_g, snap.capacity, m, c, use_allow)

        def thunk():
            args = (snap.codes, snap.recon_norms, snap.tombs, snap.n,
                    jnp.asarray(q), cb_chunks, flat_cb, words)
            statics = (use_allow, kk, self.metric, rg, active_g, interpret,
                       snap.pq.rotation_dev(),
                       self._gen_blocks(snap.codes,
                                        pq_gmin.build_codes_blocks))
            return pq_gmin.search_pq_gmin_fused(
                *args, snap.slot_to_doc_dev, *statics)

        return gmin_scan.guarded_kernel_call(
            self._pqg_state, key, thunk,
            "fused pq codes kernel", component="index.tpu.pq_gmin")

    def _pq4_funnel_packed_or_none(self, snap: IndexSnapshot, q: np.ndarray,
                                   b: int, k: int, allow_list,
                                   budgets: tuple[int, int]):
        """Run the three-stage 4-bit funnel (ops/pq4.py) at the plan's
        `budgets` (rg4, rc: planned against the SLAB, capacity, not live n —
        the scan plane's group-columns are capacity-derived, and on a
        sparse slab the live rows spread across up to min(n, ncols)
        columns, so a live-n clamp would keep far fewer columns than
        actually carry data; dead slots already score inf, so capacity
        never over-scans), or None where the kernel is refused: the 8-bit
        paths then serve. Its own failure domain (self._pq4_state) and
        per-shape validation, like the other fused kernels — but unlike
        eligible_rg, Pallas ineligibility here only downgrades STAGE 1 to
        the traceable byte-LUT scan; the funnel itself still serves."""
        from weaviate_tpu.ops import gmin_scan, pq_gmin
        from weaviate_tpu.ops import pq4 as pq4_ops

        kk = min(max(k, 1), snap.live)
        ncols = snap.capacity // gmin_scan.G
        active_g = max(1, -(-snap.n // ncols))
        mb = snap.pq4.segments // 2
        rg4, rc = budgets
        bq = q.shape[0]
        use_pallas = pq4_ops.pallas_eligible(
            self._pq4_state, self.metric, bq, ncols, snap.dim, mb, active_g,
            component="index.tpu.pq4")
        interpret = device.pallas_interpret()
        exact = bool(getattr(self.config, "exact_topk", False))
        use_allow = allow_list is not None
        words = (self._allow_words(snap, allow_list) if use_allow
                 else jnp.zeros((snap.capacity // 32,), jnp.uint32))
        cb4_chunks, cb4_dense = pq4_ops.cached_cb4_constants(self, snap.pq4)
        _cb8_chunks, flat_cb8 = pq_gmin.cached_cb_constants(self, snap.pq)
        codes8_blk = self._gen_blocks(snap.codes, pq_gmin.build_codes_blocks)
        key = (bq, kk, rg4, rc, active_g, snap.capacity, mb, use_allow,
               use_pallas)

        def thunk():
            args = (snap.codes4, snap.codes, snap.recon_norms4,
                    snap.recon_norms, snap.tombs, snap.n, jnp.asarray(q),
                    cb4_chunks, cb4_dense, flat_cb8, snap.rescore_dev, words)
            statics = dict(use_allow=use_allow, k=kk, metric=self.metric,
                           rg4=rg4, rc=rc, active_g=active_g,
                           use_pallas=use_pallas, interpret=interpret,
                           exact=exact, rot=snap.opq_rot,
                           codes8_blk=codes8_blk)
            return pq4_ops.search_pq4_funnel_fused(
                *args, snap.slot_to_doc_dev, **statics)

        packed = gmin_scan.guarded_kernel_call(
            self._pq4_state, key, thunk,
            "pq4 funnel kernel", component="index.tpu.pq4")
        if packed is not None:
            # per-stage survivor accounting (health()["pq"]["funnel"]):
            # a leaf lock, four integer adds — nothing nests inside it
            with self._pq4_lock:
                st = self._pq4_stats
                st["dispatches"] += 1
                st["stage1_pallas"] += int(use_pallas)
                # survivor counts are LIVE rows, so the funnel reads
                # monotone even on a sparse slab where the slot budgets
                # (rg4*G, rc) exceed the data they can keep
                st["stage1_rows"] += int(snap.n)
                st["stage2_survivors"] += min(rg4 * gmin_scan.G,
                                              int(snap.n))
                st["stage3_survivors"] += min(rc, int(snap.n))
        return packed

    def _rescore_r(self, k: int, n: int) -> int:
        """`rescore_depth` for this index's configuration and metric."""
        return rescore_depth(self.config, self.metric, k, n)

    def _candidate_depth(self, k: int, n: int) -> int:
        """Candidates a query that a compressed index's scan of its bf16
        rows hands the host for float32 scoring: the fast scan's own depth
        (`_rescore_r`), or k where it does not apply."""
        return max(self._rescore_r(k, n), k)

    # bound per-bucket free-list length: buffers parked beyond the live
    # pipeline depth are dead weight (a burst of concurrent dispatches can
    # momentarily check out more; the extras just get collected)
    _STAGE_POOL_CAP = 4

    def _prep_queries_staged(
            self, vectors: np.ndarray) -> tuple[np.ndarray, int]:
        """Query prep (f32 cast, cosine normalization, bucket padding)
        into a REUSABLE pre-staged host buffer from the per-jit-bucket
        pool: the per-dispatch concatenate/zeros allocations of enqueue
        collapse to one copy into a warm buffer.
        -> (padded [bb, D] f32 buffer, actual rows). The buffer must go
        back via _release_stage AFTER the dispatch's blocking fetch (the
        finalize wrapper does) — by then the program has consumed its
        inputs, so the next checkout may overwrite the memory even where
        device_put aliases it (cpu backend)."""
        q = np.asarray(vectors, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        b = q.shape[0]
        bb = _bucket_b(b)
        buf = self._checkout_stage((bb, q.shape[1]))
        np.copyto(buf[:b], q)
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(buf[:b], axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            buf[:b] /= norms
        if bb != b:
            buf[b:] = 0.0
        return buf, b

    def _checkout_stage(self, shape: tuple) -> np.ndarray:
        """A float32 buffer of `shape` from the pool (contents undefined),
        a fresh one where none is parked."""
        with self._stage_lock:
            lst = self._stage_free.get(shape)
            buf = lst.pop() if lst else None
        return np.empty(shape, np.float32) if buf is None else buf

    def _release_stage(self, buf: Optional[np.ndarray]) -> None:
        if buf is None:
            return
        key = buf.shape
        with self._stage_lock:
            # dim is None once drop() ran (or mid-compact teardown): an
            # in-flight dispatch finalizing after drop must NOT re-park
            # its buffer into the cleared pool — "stage_buffers reads 0
            # after drop" would break, and a re-created index with
            # another dim could never check the stale-keyed buffer out
            # again. Checked UNDER the lock: drop() sets dim before its
            # locked clear, so a racing finalize either sees dim None
            # here or appends before the clear wipes it — never after
            if self.dim is None:
                return
            lst = self._stage_free.setdefault(key, [])
            if len(lst) < self._STAGE_POOL_CAP:
                lst.append(buf)

    def _allow_words(self, snap: IndexSnapshot, allow_list: AllowList) -> jax.Array:
        """Packed device filter words for a snapshot's slot layout, cached
        ON the (immutable) allowList: repeated queries with the same filter
        skip the host-side pack entirely. The cache key holds a strong ref
        to the allow token object, so identity can never be recycled; the
        (token, n, capacity) triple still uniquely identifies the layout
        under snapshots because slot assignment is append-only between
        token refreshes (compact issues a fresh token). The words are set
        from the filter's slots (`_allow_slots`: a lookup a posting entry),
        not from a membership pass over the live rows."""
        key = (snap.allow_token, snap.n, snap.capacity)
        cached = getattr(allow_list, "_words_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        words = jnp.asarray(_slot_words(self._allow_slots(snap, allow_list),
                                        snap.capacity))
        try:
            allow_list._words_cache = (key, words)
        except AttributeError:
            pass  # foreign AllowList impls without the cache slot
        return words

    def padded_width(self, b: int) -> int:
        """Query rows after bucket padding (`_bucket_b`) — the dispatch
        width the jit cache is keyed on. Serving traces use it to report
        per-request padding waste (monitoring/tracing.py dispatch facts)."""
        return _bucket_b(max(int(b), 1))

    def lane_width(self, k: int, cap: int) -> int:
        """The widest dispatch, at most `cap` rows, that the plan serves
        with the program ONE query at depth `k` gets (`index/plan.py
        same_program_width`): where the coalescer closes a lane of narrow
        requests. Over a tiled layout that is the widest width still
        probed; `cap` where no layout serves. Answered from the published
        snapshot with no lock (`cap` while nothing is published)."""
        snap = self._snap
        if snap is None or not snap.live or snap.ivf_meta is None:
            return cap
        return same_program_width(
            self._plan_view(snap), min(k, snap.live),
            [w for w in _B_BUCKETS if w <= cap]) or cap

    def search_by_vectors(
        self, vectors: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched kNN on the current published snapshot: grab the
        reference (lock-free unless writes are pending), dispatch, fetch.
        Concurrent writers republish new snapshots but can never tear or
        block this dispatch — the snapshot pins its arrays."""
        snap, _ = self._read_snapshot()
        return self._dispatch_search(snap, vectors, k, allow_list)()

    def _dispatch_search(self, snap: IndexSnapshot, vectors: np.ndarray,
                         k: int, allow_list: Optional[AllowList] = None):
        """Two-phase search on `snap`: enqueue the device work NOW (query
        upload + kernels — nothing blocks), return its `DispatchHandle`:
        handle() -> (ids, dists), whose ONE blocking device->host fetch runs
        outside any lock. Every read-path case — full scan, both PQ tiers, filtered
        scans, the small-allowList gather — dispatches through here, so
        sync and async searches run the same kernels with the same
        arguments (the bit-identical contract). The snapshot is pinned for
        the enqueue (`_pin`: a snapshot a writer has taken back meanwhile
        is replaced by the one that writer published); finalize fetches
        the program's own output and needs no pin."""
        snap = self._pin(snap)
        try:
            return self._enqueue_search(snap, vectors, k, allow_list)
        finally:
            self._unpin(snap)

    def _enqueue_search(self, snap: IndexSnapshot, vectors: np.ndarray,
                        k: int, allow_list: Optional[AllowList]):
        """`_dispatch_search` on a snapshot its caller has pinned: plan the
        dispatch (index/plan.py), run the dispatcher of its tier, hand back
        the `DispatchHandle`."""
        if snap.n == 0 or snap.live == 0:
            b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
            return DispatchHandle.ready((np.zeros((b, 0), dtype=np.uint64),
                                         np.zeros((b, 0), dtype=np.float32)))
        faults.fire("index.tpu.dispatch")
        # the `enqueue` interval and the perf-attribution shape
        # (monitoring/costmodel.py) exist ONLY while the tracer is up — the
        # disabled serving path constructs nothing here (one comparison;
        # spy-pinned in tests/test_perf.py). The shape is stamped with the
        # host-overhead ledger as the dispatch executes
        enqueue = (tracing.Phase("enqueue")
                   if tracing.get_tracer() is not None else None)
        try:
            q, b = self._prep_queries_staged(vectors)
            plan = plan_search(
                self._plan_view(snap), b, q.shape[0], min(k, snap.live),
                None if allow_list is None else len(allow_list))
            handle = DispatchHandle(
                self, "index.tpu.finalize", plan,
                None if enqueue is None
                else plan.shape(enqueue.start_ns / 1e9))
            if plan.ivf_declined:
                self.scan_programs.declined_probe()
            if plan.tier == costmodel.TIER_GATHER:
                fin = self._dispatch_small_allow(
                    snap, q, b, plan.k_eff, allow_list, handle.shape)
            elif plan.ivf is not None:
                # partition-pruned path (ROADMAP item 3): scan only the
                # probed buckets; large allowLists compose via the same
                # packed words, small ones took the gather tier above
                fin = self._dispatch_ivf(snap, q, b, allow_list, plan,
                                         handle.shape)
            elif snap.compressed:
                fin = self._dispatch_full_pq(snap, q, b, allow_list, handle)
            else:
                allow_words = (self._allow_words(snap, allow_list)
                               if allow_list is not None else None)
                fin = self._dispatch_scan(snap, q, b, allow_words, handle)
        except BaseException:
            if enqueue is not None:  # a dispatch that failed being built
                enqueue.end()
            raise
        if enqueue is not None:
            # a full-store scan names the program that ran it
            now_ns = enqueue.end(rows=b, tier=handle.plan.tier,
                                 **handle.plan.stats())
            handle.shape.enqueue_ms = (now_ns - enqueue.start_ns) / 1e6
        # shadow-audit snapshot pin (monitoring/quality.py): record which
        # snapshot THIS dispatch read so a sampled audit re-executes
        # against the same index state — writers publishing between
        # enqueue and finalize must not skew the comparison. Gated so the
        # disabled path stores nothing (one comparison, the tracer
        # contract).
        if quality.get_auditor() is not None:
            handle.snapshot = snap
        # `q` is the staging buffer: the handle returns it to the pool
        return handle.launched(fin, q)

    def _plan_view(self, snap: IndexSnapshot) -> PlanView:
        """What index/plan.py reads of `snap` on one chip."""
        pq = snap.compressed
        rescore = bool(pq and self.config.pq.rescore
                       and snap.rescore_dev is not None)
        funnel = (pq and snap.codes4 is not None and snap.pq4 is not None
                  and self.metric in ivf_ops.MATMUL_METRICS)
        ivf = snap.ivf_meta is not None
        return PlanView(
            config=self.config, metric=self.metric,
            programs=self.scan_programs, kernels=self,
            component="index.tpu.gmin", n=snap.n, live=snap.live,
            dim=snap.dim, ndev=1, slab=snap.capacity, fill=snap.n,
            itemsize=0 if pq else snap.store.dtype.itemsize, compressed=pq,
            pq_segments=snap.pq.segments if pq else 0,
            pq4_segments=snap.pq4.segments if funnel else 0,
            rescore=rescore,
            # the rescore tier scans the bf16 copy of the rows (2·D)
            rescore_bytes_per_row=2 * snap.dim if rescore else 0,
            rescore_scan_itemsize=(snap.rescore_dev.dtype.itemsize
                                   if rescore else 0),
            ivf_meta=snap.ivf_meta[:2] if ivf else None,
            ivf_probe=functools.partial(self._ivf_plan, snap) if ivf
            else None, ivf_gathered=ivf and not snap.ivf_tiled)

    def dispatch_tier(self, snap: IndexSnapshot, allow_list=None,
                      b: int = 1, k: int = 1) -> str:
        """The costmodel TIER_* a dispatch of `b` queries at depth `k` on
        `snap` with `allow_list` takes: its plan's (index/plan.py). For the
        quality auditor, which labels its bounded-cardinality gauges
        without a tracer-built shape; the program is not asked, so nothing
        is counted."""
        return plan_search(
            self._plan_view(snap), b, _bucket_b(b), min(k, snap.live),
            None if allow_list is None else len(allow_list),
            refused=frozenset((KERNEL_GMIN,))).tier

    # -- IVF scan plane: dispatch half ---------------------------------------

    def _ivf_plan(self, snap: IndexSnapshot,
                  k: int) -> Optional[tuple[int, int]]:
        return ivf_probe(self.metric, snap, k)

    def _dispatch_ivf(self, snap: IndexSnapshot, q: np.ndarray, b: int,
                      allow_list, plan, shape):
        """Partition-pruned search: probe the centroids, score only the
        probed buckets (ops/ivf.py), finish through the SAME translate
        epilogue as every flat tier. Covers the exact,
        PQ-rescore, and PQ-codes tiers; tombstones and allowLists mask
        with identical semantics to the flat kernels (the snapshot's own
        device tombs, the same packed filter words)."""
        top_p, pre_c = plan.ivf
        nlist, cap_p, _gen = snap.ivf_meta
        allow_words = (self._allow_words(snap, allow_list)
                       if allow_list is not None else None)
        use_allow = allow_words is not None
        if use_allow:
            words = allow_words
        else:
            # the unread filter operand, made once a capacity: at a
            # thousand single queries a second a fresh device array a
            # dispatch is a launch of its own under the callers' one GIL
            words = self._no_filter_words
            if words is None or words.shape[0] != snap.capacity // 32:
                words = self._no_filter_words = jnp.zeros(
                    (snap.capacity // 32,), jnp.uint32)
        exact = getattr(self.config, "exact_topk", False)
        kk = min(max(plan.k_eff, 1), top_p * cap_p)
        gp = ivf_ops.group_steps(q.shape[0], cap_p, snap.dim, top_p)
        # second-stage chunking (prefilter survivors): pow2 steps so the
        # full-dim gather stays within the same element budget
        steps2 = 1
        if pre_c:
            while steps2 < pre_c and \
                    (q.shape[0] * (pre_c // steps2) * snap.dim) > (1 << 21):
                steps2 *= 2
        if plan.tier == costmodel.TIER_PQ_ADC4:
            # probed three-stage funnel (ops/pq4.search_ivf_pq4): grouped
            # 4-bit byte-LUT cut -> exact 8-bit ADC of the survivors ->
            # bf16 rescore — the funnel budgets bound stages 1/2 over the
            # probed candidate set exactly as over the full store (budgets
            # that cannot cover this k over the probed set were planned as
            # the 8-bit IVF tier: index/plan.py)
            from weaviate_tpu.ops import pq4 as pq4_ops

            r_cand = top_p * cap_p
            rg4, rc = plan.funnel
            c1 = min(rg4 * 16, r_cand)
            # stage-2 chunking over the c1 survivors: pow2 steps under the
            # shared element budget, stopped early if a further halving
            # would stop dividing c1 (the _regroup contract)
            steps2_4 = 1
            while (steps2_4 * 2 <= c1 and c1 % (steps2_4 * 2) == 0
                   and (q.shape[0] * (c1 // steps2_4) * snap.dim)
                   > (1 << 21)):
                steps2_4 *= 2
            statics4 = (kk, self.metric, use_allow, top_p, c1, rc,
                        exact, gp, steps2_4)
            args4 = (snap.codes4, snap.codes, snap.recon_norms4,
                     snap.recon_norms, snap.tombs, snap.n,
                     jnp.asarray(q), words, snap.pq4._dev_codebook(),
                     snap.pq._dev_codebook(), snap.ivf_centroids,
                     snap.ivf_buckets, snap.opq_rot, snap.rescore_dev)
            packed_dev = pq4_ops.search_ivf_pq4_fused(
                *args4, snap.slot_to_doc_dev, *statics4)
            self._note_probe(snap, top_p * cap_p)
            with self._pq4_lock:
                st = self._pq4_stats
                st["dispatches"] += 1
                st["stage1_rows"] += r_cand
                st["stage2_survivors"] += min(c1, r_cand)
                st["stage3_survivors"] += min(rc, r_cand)
            return self._finalize_fused(packed_dev, shape, b)
        if snap.ivf_tiled:
            # the store is in partition order: whole tiles, read in place
            packed_dev = ivf_ops.search_ivf_tiles_fused(
                snap.store, snap.tombs, jnp.asarray(q), words,
                snap.ivf_centroids, snap.slot_to_doc_dev, kk, self.metric,
                use_allow, top_p, cap_p)
            self._note_probe(snap, top_p * cap_p)
            return self._finalize_fused(packed_dev, shape, b)
        statics = (kk, self.metric, use_allow, top_p, pre_c, exact, gp,
                   steps2)
        if plan.tier != costmodel.TIER_PQ_CODES:
            store = snap.store if not snap.compressed else snap.rescore_dev
            args = (store, snap.tombs, snap.n, jnp.asarray(q), words,
                    snap.ivf_centroids, snap.ivf_buckets,
                    snap.ivf_pca_proj, snap.ivf_pca_rows)
            packed_dev = ivf_ops.search_ivf_dense_fused(
                *args, snap.slot_to_doc_dev, *statics)
        else:
            args = (snap.codes, snap.recon_norms, snap.tombs, snap.n,
                    jnp.asarray(q), words, snap.pq._dev_codebook(),
                    snap.ivf_centroids, snap.ivf_buckets,
                    snap.ivf_pca_proj, snap.ivf_pca_rows,
                    snap.pq.rotation_dev())
            packed_dev = ivf_ops.search_ivf_codes_fused(
                *args, snap.slot_to_doc_dev, *statics)
        self._note_probe(snap, top_p * cap_p)
        return self._finalize_fused(packed_dev, shape, b)

    def _note_probe(self, snap: IndexSnapshot, probed: int) -> None:
        """Probe accounting (health / bench probed_fraction): a leaf lock,
        three integer adds — nothing nests inside it."""
        with self._ivf_lock:
            st = self._ivf_stats
            st["dispatches"] += 1
            st["probed_rows"] += probed
            st["base_rows"] += int(snap.n)

    def _dispatch_scan(self, snap: IndexSnapshot, q: np.ndarray, b: int,
                       allow_words, handle: DispatchHandle, store=None,
                       sq_norms=None):
        """Full-store scan over `store` — the f32 store uncompressed, or the
        bf16 rescore copy under PQ-with-rescore (scanning codes first would
        read MORE HBM than the copy the rescore pass consults anyway) — by
        the program the plan names: the fused gmin kernel where it is
        eligible, compiles and is the faster at this width (index/plan.py,
        `gmin_scan.kernel_serves`), the lax.scan program otherwise, and
        where Mosaic refuses the kernel this shape (the one place that
        falls back: the dispatch is planned again). Which one ran is
        counted (`scan_programs`) and, while the tracer is up, named on the
        shape (`extra["program"]`) and in the `enqueue` interval's stats.
        The slot->doc translation runs in the same program, against the
        snapshot's device table, and finalize is a reshape; over the bf16
        copy the program's columns are candidates and finalize scores them
        from the float32 rows the host keeps (`_rescore_f32`)."""
        plan = handle.plan
        kk = min(max(plan.k_eff, 1), snap.n)
        packed_dev = None
        if plan.gmin is not None:
            packed_dev = self._gmin_packed_or_none(
                snap, q, kk, allow_words, plan.gmin, store, sq_norms)
            if packed_dev is None:
                plan = handle.refuse(self._plan_view(snap), KERNEL_GMIN)
        if packed_dev is None:
            packed_dev = self._scan_program(
                snap, snap.store if store is None else store,
                snap.sq_norms if sq_norms is None else sq_norms, q,
                allow_words, kk, candidates=store is not None)
        self.scan_programs.count(plan.program)
        shape = handle.shape
        if store is None:
            return self._finalize_fused(packed_dev, shape, b)

        def finalize():
            return self._rescore_f32(
                snap, q, _fetch_packed(packed_dev, shape), b, kk, shape)

        return finalize

    def _scan_program(self, snap: IndexSnapshot, store, sq_norms, q,
                      allow_words, kk: int, candidates: bool = False):
        """Enqueue the lax.scan program over `store`: `allow_words` None, one
        [capacity / 32] mask for every query, or a [queries, capacity / 32]
        block, a mask a query."""
        return _search_full_fused(
            store, sq_norms if self.metric == vi.DISTANCE_L2 else None,
            snap.tombs, snap.n, jnp.asarray(q),
            allow_words if allow_words is not None
            else jnp.zeros((snap.capacity // 32,), jnp.uint32),
            snap.slot_to_doc_dev, kk, self.metric, allow_words is not None,
            getattr(self.config, "exact_topk", False),
            -(-snap.n // _SCAN_CHUNK), self._rescore_r(kk, snap.n),
            candidates)

    def _rescore_f32(self, snap: IndexSnapshot, q: np.ndarray,
                     packed: np.ndarray, b: int, k: int, shape):
        """The last step of a compressed dispatch under pq.rescore: the
        program selected R candidates a query by the bf16 copy of the rows
        (`packed`: the translate_pack_slots layout, in the scan's order);
        their distances are computed here in float32 from the rows the
        host keeps (`host_vecs`), the top k is taken from THOSE, and the
        reply carries those. Compression may cost recall, never a
        distance. One native call (index/rescore_native.py) reads every
        candidate's row once and scores it in registers, off the GIL and,
        for a wide dispatch, on a few threads of its own; where it cannot
        serve, numpy gathers the rows and contracts them (`_gather_score`).
        `/debug/perf` `rescore.by` counts which."""
        ids, _, slots = unpack_fused_slots(packed[:b])
        r = slots.shape[1]
        phase = tracing.Phase("rescore") if shape is not None else None
        d, why = rescore_native.distances(
            snap.host_vecs, slots, q[:b], self.metric)
        if d is None:
            d = self._gather_score(snap, q[:b], slots)
        # stable: candidates that tie keep the scan's order
        order = np.argsort(d, axis=1, kind="stable")[:, :k]
        dists = np.take_along_axis(d, order, axis=1).astype(
            np.float32, copy=False)
        ids = np.take_along_axis(ids, order, axis=1)
        # bytes: what the phase read from `host_vecs`, either way
        rows, nbytes = b * r, b * r * snap.dim * 4
        # winners the float32 distances moved from the rank the bf16
        # rows gave them: 0 says R is deeper than the rounding needs
        promoted = int(np.count_nonzero(
            (order != np.arange(order.shape[1])) & np.isfinite(dists)))
        perf.note_rescore(rows, nbytes, promoted,
                          perf.RESCORE_NATIVE if why is None
                          else f"numpy:{why}")
        if phase is not None:
            end_ns = phase.end(rows=rows, bytes=nbytes)
            shape.rescore_ms = (end_ns - phase.start_ns) / 1e6
        return ids, dists

    def _gather_score(self, snap: IndexSnapshot, q: np.ndarray,
                      slots: np.ndarray) -> np.ndarray:
        """`_rescore_f32`'s distances without the native library, and the
        plain reference its tests compare against: one gather of the
        candidates' rows into a pooled buffer and one contraction, both
        numpy calls that let go of the GIL. -> [B, R] f32, +inf at -1."""
        b, r = slots.shape
        # pooled: fresh pages a dispatch cost more than the copy (PERF.md,
        # PR 28)
        buf = self._checkout_stage((b, r, snap.dim))
        try:
            np.take(snap.host_vecs, np.maximum(slots, 0).ravel(), axis=0,
                    out=buf.reshape(b * r, snap.dim), mode="clip")
            d = _host_distances(buf, q, self.metric)
        finally:
            self._release_stage(buf)
        d[slots < 0] = np.inf
        return d

    def _finalize_fused(self, packed_dev, shape, b: int,
                        k: Optional[int] = None):
        """Every tier's finalize: the one blocking fetch already carries
        final doc ids, so the host half is dtype views plus two
        vectorized word copies (ops/topk.unpack_fused) — no slot->doc
        table read, no per-row work (the JGL015 contract, and the reason
        the perf ledger's gather_hop share collapses)."""
        def finalize():
            packed = _fetch_packed(packed_dev, shape)
            ids, dists = unpack_fused(packed)
            if k is not None:
                ids, dists = ids[:, :k], dists[:, :k]
            return ids[:b], dists[:b]

        return finalize

    def _dispatch_full_pq(self, snap: IndexSnapshot, q: np.ndarray, b: int,
                          allow_list, handle: DispatchHandle):
        """Compressed full-store search, at the tier the plan names.

        With rescore enabled a full bf16 copy of the rows already lives in
        HBM for the rescoring pass — so the fast scan reads THAT copy
        directly (`_dispatch_scan`: the fused gmin kernel where
        `gmin_scan.kernel_serves` says it is the faster program at 2 B a
        component, the lax.scan program otherwise, as at 768-d), which is
        strictly less HBM traffic and strictly more accurate than scanning
        the codes first; the codes then only serve writes and restarts. The
        reference has no such copy, hence its LUT scan
        (product_quantization.go:56-75).

        With rescore disabled (memory-tightest tier) the scan really runs
        over the codes: reconstruction-matmul ADC for matmul metrics, LUT
        gathers for manhattan. (hamming never compresses — ProductQuantizer
        rejects it at fit/load.)"""
        from weaviate_tpu.compress.pq import build_lut

        pqc = self.config.pq
        plan = handle.plan
        k = plan.k_eff
        if plan.tier == costmodel.TIER_PQ_ADC4:
            # 4-bit funnel tier (pq.bits=4): the stage-1 scan reads M/2
            # bytes per row — less HBM than the bf16 copy (2D) or even the
            # 8-bit codes (M) — and the two re-ranking stages restore
            # recall. Shallow budgets were planned as the 8-bit tiers; a
            # kernel refused here is planned again as them (the codes and
            # rescore slabs both still exist), so /debug/perf carries no
            # phantom 4-bit traffic
            packed4 = self._pq4_funnel_packed_or_none(
                snap, q, b, k, allow_list, plan.funnel)
            if packed4 is not None:
                return self._finalize_fused(packed4, handle.shape, b, k)
            plan = handle.refuse(self._plan_view(snap), KERNEL_FUNNEL)
        if plan.tier == costmodel.TIER_PQ_RESCORE:
            allow_words = (self._allow_words(snap, allow_list)
                           if allow_list is not None else None)
            return self._dispatch_scan(
                snap, q, b, allow_words, handle,
                store=snap.rescore_dev, sq_norms=snap.rescore_sq_norms)
        # codes-only tier from here: raw ADC distances, no rescoring pass.
        # Fast path: the fused PQ-ADC group-min kernel (ops/pq_gmin.py) —
        # reconstruction-as-matmul in VMEM, codes never expand in HBM
        packed_dev = self._pq_gmin_packed_or_none(snap, q, b, k, allow_list)
        if packed_dev is None:
            # legacy reconstruction-scan path:
            # per-chunk candidate depth: selection cost on TPU grows sharply
            # with k, so each chunk contributes a SMALL top-r and the
            # candidate pool is nchunks * r_chunk deep. Sized so the pool
            # stays >= 512 regardless of chunk count (64/chunk over a 1M
            # store; deeper per chunk when the store fits fewer chunks).
            nchunks_eff = max(1, -(-snap.n // _SCAN_CHUNK))
            pool_target = pqc.rescore_limit or 1024
            r_top = RESCORE_R_BUCKETS[-1]
            r_cap = controller.rescore_r_cap(r_top)
            if r_cap < r_top:
                # the budget controller's cap scales the codes-tier
                # candidate pool too (the ISSUE's per-chunk budget): cap
                # values are bucketed, so the derived r_chunk set stays
                # bounded and jit shapes stay cached; the floor keeps
                # the pool's own recall guarantee without ever RAISING
                # a configured rescore_limit below 512 (the controller
                # may only cut work)
                pool_target = max(int(pool_target * r_cap / r_top),
                                  min(512, pool_target))
            r_chunk = min(
                max(2 * k, -(-pool_target // nchunks_eff), 64), 256, snap.n
            )
            # the concatenated pool must cover k (final top_k rejects k > pool)
            r_chunk = max(r_chunk, min(-(-k // nchunks_eff), snap.n))
            allow_words = (self._allow_words(snap, allow_list)
                           if allow_list is not None else None)
            words = (allow_words if allow_words is not None
                     else jnp.zeros((snap.capacity // 32,), jnp.uint32))
            if self.metric in (vi.DISTANCE_L2, vi.DISTANCE_DOT,
                               vi.DISTANCE_COSINE):
                args = (
                    snap.codes,
                    snap.recon_norms,
                    snap.tombs,
                    snap.n,
                    snap.pq._dev_codebook(),
                    jnp.zeros((1, snap.dim), jnp.bfloat16),
                    jnp.asarray(q),
                    words,
                )
                statics = (
                    min(k, snap.live),
                    r_chunk,
                    self.metric,
                    allow_words is not None,
                    getattr(self.config, "exact_topk", False),
                    -(-snap.n // _SCAN_CHUNK),
                    False,
                    snap.pq.rotation_dev(),
                )
                packed_dev = _search_pq_recon_fused(
                    *args, snap.slot_to_doc_dev, *statics)
            else:
                lut = build_lut(jnp.asarray(q), snap.pq._dev_codebook(),
                                self.metric)
                args = (snap.codes, snap.tombs, snap.n, lut, words)
                statics = (
                    min(k, snap.n, _PQ_SCAN_CHUNK),
                    allow_words is not None,
                    getattr(self.config, "exact_topk", False),
                    -(-snap.n // _PQ_SCAN_CHUNK),
                )
                packed_dev = _search_pq_fused(
                    *args, snap.slot_to_doc_dev, *statics)
        return self._finalize_fused(packed_dev, handle.shape, b, k)

    def _allow_slots(self, snap: IndexSnapshot,
                     allow_list: AllowList) -> np.ndarray:
        """Store slots of `allow_list`'s docs in this snapshot, ascending:
        the input-side resolution of both filtered tiers (the gather's row
        list, the masked scan's words). Its cost follows the POSTING, not
        the corpus: each allowed doc is looked up in the snapshot's own
        `slot_to_doc[:n]` prefix by binary search, which is its own index
        while doc ids ascend with the slots (they do on the served path:
        the shard hands them out from a counter and slots are assigned in
        order); a library caller that re-added a doc or added out of order
        gets the prefix's sorted order instead, made once a snapshot
        (`_doc_order`). No pass over the live rows a filter. Cached on the
        (immutable) allowList per slot layout like `_allow_words`: the
        shard's allowList cache (16 filters) reuses the object a filter.

        Staleness contract: the (allow_token, n, capacity) key changes on
        adds, re-adds, and compaction, but NOT on deletes — so the
        cached slot list is computed WITHOUT tombstone knowledge (every
        matching slot, tombstoned or not) and is therefore identical no
        matter which same-key snapshot computed it. Tombstones are
        masked ON DEVICE with the dispatching snapshot's own `tombs`
        (_gather_live): each dispatch is exact for the state it pinned,
        in BOTH staleness directions — a new snapshot's dispatch hitting
        an old cache masks fresh deletes, and an old pinned snapshot's
        dispatch hitting a cache computed after a delete still gathers
        (and keeps) the doc its own world holds live. Excluding
        host_tombs here would break that second direction."""
        key = (snap.allow_token, snap.n, snap.capacity)
        cached = getattr(allow_list, "_slots_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        ids = np.asarray(allow_list.to_array()).astype(np.int64, copy=False)
        docs = snap.slot_to_doc[: snap.n]
        if snap.n == 0:
            slots = ids[:0]
        elif snap.docs_ascending and docs[-1] - docs[0] == snap.n - 1:
            # consecutive doc ids (rows put once, from the shard's counter):
            # the slot is the doc id less the first one
            lo, hi = np.searchsorted(ids, (docs[0], docs[-1] + 1))
            slots = ids[lo:hi] - docs[0]
        elif snap.docs_ascending:
            at = np.searchsorted(docs, ids)
            slots = at[docs[np.minimum(at, snap.n - 1)] == ids]
        else:
            order, sorted_docs = self._doc_order(snap)
            lo = np.searchsorted(sorted_docs, ids, side="left")
            hi = np.searchsorted(sorted_docs, ids, side="right")
            runs = hi - lo   # a re-added doc holds two slots, one dead
            first = np.repeat(lo, runs)
            within = np.arange(first.size) - np.repeat(
                np.cumsum(runs) - runs, runs)
            slots = np.sort(order[first + within])
        slots = slots.astype(np.int32)
        try:
            allow_list._slots_cache = (key, slots)
        except AttributeError:
            pass  # foreign AllowList impls without the cache slot
        return slots

    @staticmethod
    def _doc_order(snap: IndexSnapshot) -> tuple[np.ndarray, np.ndarray]:
        """(slots in order of their doc id, those doc ids) of a snapshot
        whose docs do not ascend with its slots: one sort a snapshot, on
        the first filter that needs it."""
        got = snap.doc_order
        if got is None:
            docs = snap.slot_to_doc[: snap.n]
            order = np.argsort(docs, kind="stable")
            got = snap.doc_order = (order, docs[order])
        return got

    def _dispatch_small_allow(self, snap: IndexSnapshot, q: np.ndarray,
                              b: int, k: int, allow_list: AllowList, shape):
        """Gather path (flatSearch over allowList, flat_search.go:19): the
        host-side doc->slot resolution is one cached vectorized membership
        pass (`_allow_slots`); the row scoring is one enqueued device
        call, and the result-side slot->doc translation rides the same
        program."""
        empty = (np.zeros((b, 0), np.uint64), np.zeros((b, 0), np.float32))
        slots = self._allow_slots(snap, allow_list)
        # short-circuit when NOTHING can match in THIS snapshot: the
        # cached slot list is tombstone-blind, so consult the dispatching
        # snapshot's own host mirror (O(A), per dispatch — never cached):
        # a fully-deleted filter must cost zero device work, not a
        # dispatch that gathers dead rows into all-sentinel columns
        if slots.size == 0 or not np.any(~snap.host_tombs[slots]):
            if shape is not None:
                shape.n = 0  # no device work ran: zero the analytic cost
            return lambda: empty
        if shape is not None:
            # the gather scores only the rows PRESENT in this shard — an
            # allowList spanning other shards must not credit this
            # dispatch their flops/bytes
            shape.n = int(slots.size)
        r = _bucket_rows(slots.size)
        rows = np.full(r, 0, dtype=np.int32)
        rows[: slots.size] = slots
        row_valid = np.zeros(r, dtype=bool)
        row_valid[: slots.size] = True
        kk = min(k, slots.size)
        rows_dev = jnp.asarray(rows)
        valid_dev = jnp.asarray(row_valid)
        if snap.compressed:
            # float rows live host-side under PQ: upload the gathered block
            sub = np.zeros((r, snap.dim), np.float32)
            sub[: slots.size] = snap.host_vecs[slots]
            packed_dev = _score_rows_fused(
                jnp.asarray(sub), jnp.asarray(q), rows_dev, valid_dev,
                snap.tombs, snap.slot_to_doc_dev, kk, self.metric)
        else:
            packed_dev = _search_gathered_fused(
                snap.store, jnp.asarray(q), rows_dev, valid_dev,
                snap.tombs, snap.slot_to_doc_dev, kk, self.metric)
        return self._finalize_fused(packed_dev, shape, b)

    # -- host fallback plane (serving/robustness.py circuit breaker) ---------

    def host_rows(
            self, snap: IndexSnapshot) -> tuple[np.ndarray, np.ndarray]:
        """Host f32 ([n, D] rows, [n] row sq-norms) of `snap`'s occupied
        region — one bulk device->host transfer + one norms pass, no
        caching (callers own their policy: the breaker caches per live
        generation in _host_fallback_rows, the quality auditor keeps its
        own snapshot-pinned cache). Under PQ the full-precision rows
        already live host-side (host_vecs); only the norms are derived."""
        if snap.compressed and snap.host_vecs is not None:
            rows = snap.host_vecs[: snap.n]  # a view — no extra memory
        elif self._pin(snap, follow=False) is None:
            # a writer overwrote this generation in place: its rows are
            # no longer anybody's to read
            raise SnapshotRetired(f"snapshot {snap.gen} was retired")
        else:
            try:
                rows = self._fetch_rows(snap.store, snap.n)
            finally:
                self._unpin(snap)
        # einsum: the norms pass must not transiently duplicate the rows
        sq = np.einsum("ij,ij->i", rows, rows, dtype=np.float32)
        return rows, sq

    def _fetch_rows(self, store, n: int) -> np.ndarray:
        """Host float32 copy of `store`'s first `n` rows, a piece at a time:
        `store[:n]` is a second slab on the device for as long as the fetch
        takes, and a slab that fills half the chip has no room for one. A
        piece is `_HOST_PIECE` rows (a capacity is a power of two from
        16,384 up or whole scan chunks: whole pieces either way), 200 MB at
        768-d."""
        piece = min(store.shape[0], _HOST_PIECE)
        out = np.empty((n, store.shape[1]), np.float32)
        for lo in range(0, n, piece):
            hi = min(lo + piece, n)
            out[lo:hi] = np.asarray(_read_rows(store, lo))[: hi - lo]  # graftlint: disable=JGL001 the host plane's one bulk fetch (breaker open, an audit, compact), a piece at a time because a slice of the whole is a second slab on the device
        return out

    def _host_fallback_rows(
            self, snap: IndexSnapshot) -> tuple[np.ndarray, np.ndarray]:
        """host_rows built ONCE per snapshot generation and cached: the
        breaker's fallback pays one bulk transfer + one norms pass when it
        first opens, not per degraded query — this path exists precisely
        for sustained load on the slowest plane. (A device too far gone
        even to read HBM makes the fetch raise; the caller then surfaces
        the original dispatch error.)"""
        cached = self._host_rows_cache
        if cached is not None and cached[0] == snap.gen:
            return cached[1], cached[2]
        rows, sq = self.host_rows(snap)
        self._host_rows_cache = (snap.gen, rows, sq)
        return rows, sq

    def release_host_fallback_cache(self) -> None:
        """Drop the host fallback copy — a full f32 store materialization
        at serving scale — once the breaker has recovered and the device
        serves THIS index again (db/shard.py calls this on the first
        healthy dispatch after a degraded window, per shard); it rebuilds
        on the next breaker-open episode."""
        self._host_rows_cache = None

    def search_by_vectors_host(
        self, vectors: np.ndarray, k: int,
        allow_list: Optional[AllowList] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Batched kNN entirely on the HOST (numpy brute force) over the
        published snapshot — the read path db/shard.py routes to while the
        device circuit breaker is open (and for the breaker's own recovery
        probes' riders). Same contract as search_by_vectors ([B, k] ids +
        dists, inf-padded absent slots); selection is exact, so recall can
        only go UP while degraded — latency and throughput pay instead."""
        while True:
            snap, _ = self._read_snapshot()
            if snap.n == 0 or snap.live == 0:
                b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
                return (np.zeros((b, 0), np.uint64),
                        np.zeros((b, 0), np.float32))
            try:
                rows, row_sq = self._host_fallback_rows(snap)
            except SnapshotRetired:
                continue    # a writer took it back: the one it published
            return self._host_search_snap(snap, vectors, k, allow_list,
                                          rows, row_sq)

    def search_by_vectors_host_pinned(
        self, snap: IndexSnapshot, vectors: np.ndarray, k: int,
        allow_list: Optional[AllowList] = None,
        rows: Optional[np.ndarray] = None,
        sq_norms: Optional[np.ndarray] = None,
        deadline: Optional[float] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """The quality auditor's host-plane entry (monitoring/quality.py):
        exact brute-force kNN over a CALLER-PINNED snapshot — the exact
        index state the audited live dispatch read, so deletes or
        compression published in between cannot skew the comparison.
        Bypasses _read_snapshot (no flush, no lock, no read-your-writes)
        and the breaker's fallback cache (callers pass their own `rows`).
        `deadline` (time.monotonic seconds) bounds the scan: row chunks
        are checked against it and quality.AuditDeadlineExceeded aborts
        an over-budget audit — audits are subordinate to everything."""
        if snap.n == 0 or snap.live == 0:
            b = 1 if np.asarray(vectors).ndim == 1 else len(vectors)
            return (np.zeros((b, 0), np.uint64),
                    np.zeros((b, 0), np.float32))
        if rows is None:
            rows, sq_norms = self.host_rows(snap)
        return self._host_search_snap(snap, vectors, k, allow_list,
                                      rows, sq_norms, deadline)

    # rows per host-scan chunk: bounds the work between deadline checks
    # (and the [B, chunk, D] broadcast of the non-matmul metrics)
    _HOST_SCAN_CHUNK = 65536

    def _host_search_snap(
        self, snap: IndexSnapshot, vectors: np.ndarray, k: int,
        allow_list: Optional[AllowList], rows: np.ndarray,
        row_sq: np.ndarray, deadline: Optional[float] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Shared exact host scan over a snapshot's materialized rows.
        Distances stream in row chunks (output-column splits — bit-
        identical to the one-shot matmul, since the reduction runs over
        the full dim either way) with a deadline check per chunk."""
        q = np.asarray(vectors, dtype=np.float32)
        if q.ndim == 1:
            q = q[None, :]
        b = q.shape[0]
        empty = (np.zeros((b, 0), np.uint64), np.zeros((b, 0), np.float32))
        if snap.n == 0 or snap.live == 0:
            return empty
        if self.metric == vi.DISTANCE_COSINE:
            norms = np.linalg.norm(q, axis=1, keepdims=True)
            norms[norms == 0] = 1.0
            q = q / norms
        live = ~snap.host_tombs[: snap.n]
        if allow_list is not None:
            from weaviate_tpu.storage.bitmap import Bitmap, allowed_mask

            docs = snap.slot_to_doc[: snap.n]
            if isinstance(allow_list, Bitmap):
                amask = allowed_mask(allow_list, docs)
            else:
                amask = allow_list.contains_array(docs.astype(np.uint64))
            live = live & amask
        n_live = int(live.sum())
        if n_live == 0:
            return empty
        q_sq = (q ** 2).sum(1)[:, None] if self.metric == vi.DISTANCE_L2 \
            else None
        d = np.empty((b, snap.n), np.float32)
        chunk = 4096 if self.metric in (vi.DISTANCE_MANHATTAN,
                                        vi.DISTANCE_HAMMING) \
            else self._HOST_SCAN_CHUNK
        for s in range(0, snap.n, chunk):
            if deadline is not None and time.monotonic() > deadline:
                raise quality.AuditDeadlineExceeded(
                    f"host scan over audit budget at row {s}/{snap.n}")
            blk = rows[s: s + chunk]
            e = s + blk.shape[0]
            if self.metric == vi.DISTANCE_L2:
                qx = q @ blk.T
                d[:, s:e] = np.maximum(
                    q_sq - 2.0 * qx + row_sq[s:e][None, :], 0.0)
            elif self.metric == vi.DISTANCE_DOT:
                d[:, s:e] = -(q @ blk.T)
            elif self.metric == vi.DISTANCE_COSINE:
                d[:, s:e] = 1.0 - q @ blk.T  # rows are insert-normalized
            elif self.metric == vi.DISTANCE_MANHATTAN:
                d[:, s:e] = np.abs(q[:, None, :] - blk[None, :, :]).sum(-1)
            else:  # hamming
                d[:, s:e] = (q[:, None, :] != blk[None, :, :]).sum(-1)
        d[:, ~live] = np.inf
        kk = min(max(int(k), 1), n_live)
        part = np.argpartition(d, kk - 1, axis=1)[:, :kk]
        pd = np.take_along_axis(d, part, axis=1)
        order = np.argsort(pd, axis=1, kind="stable")
        idx = np.take_along_axis(part, order, axis=1)
        top = np.take_along_axis(pd, order, axis=1)
        ids = np.where(np.isinf(top), -1, snap.slot_to_doc[idx])
        return ids.astype(np.uint64), top.astype(np.float32)

    def _ivf_health(self) -> dict:
        """The health() block for the IVF scan plane: partition count,
        bucket fill / padding-waste histogram, imbalance factor, last
        recluster generation, probe accounting. Lock-free racy reads
        like the rest of health()."""
        s = ivf_settings()
        cent = self._ivf_centroids_host
        out = {"enabled": s is not None, "trained": cent is not None}
        if cent is None:
            return out
        meta = self._ivf_meta or (cent.shape[0], self._ivf_cap_p or 0,
                                  self._ivf_gen)
        nlist, cap_p, gen = meta
        out.update({
            "nlist": int(nlist),
            "bucket_capacity": int(cap_p),
            "trained_n": int(self._ivf_trained_n),
            "last_recluster_gen": int(gen),
            "pca_dim": (int(self._ivf_pca_host.shape[1])
                        if self._ivf_pca_host is not None else 0),
        })
        fills = self._ivf_fills
        free_n = self._ivf_free_n
        if self._ivf_tiled and free_n is not None:
            # a tile's fill is what its free slots leave of it; what a
            # restore found recorded for its docs, and what it assigned
            fills = cap_p - free_n
            out["layout"] = "tiles"
            out["restore"] = dict(self._ivf_restore_stats)
            out["trainings"] = self._ivf_trains
        if fills is not None and fills.size and cap_p:
            total = int(fills.sum())
            mean = total / max(int(nlist), 1)
            out["buckets"] = {
                "fill_min": int(fills.min()),
                "fill_mean": round(mean, 1),
                "fill_max": int(fills.max()),
                "empty": int((fills == 0).sum()),
                # fraction of the padded [nlist, cap_p] table holding
                # sentinel rows the probes still read — the price of
                # jit-stable shapes, and the first thing to check when
                # probed_fraction looks too high for the recall it buys
                "padding_waste": round(1.0 - total / (nlist * cap_p), 4),
                "imbalance": (round(float(fills.max()) / mean, 2)
                              if mean > 0 else None),
                # 8 equal-width fill bins over [0, cap_p] — the skew
                # shape at a glance
                "fill_histogram": np.histogram(
                    fills, bins=8, range=(0, cap_p))[0].tolist(),
            }
        out["probes"] = self.ivf_stats()
        return out

    def _kernels_health(self) -> dict:
        from weaviate_tpu.ops.gmin_scan import kernel_health

        with self._pq4_lock:
            dispatches = self._pq4_stats["dispatches"]
            pallas = self._pq4_stats["stage1_pallas"]
        return {
            "gmin": kernel_health(self),
            "pq_gmin": kernel_health(self._pqg_state),
            "pq4": {**kernel_health(self._pq4_state),
                    "stage1_pallas_dispatches": pallas,
                    "stage1_byte_lut_dispatches": dispatches - pallas},
        }

    def health(self) -> dict:
        """Per-index introspection for ``GET /debug/index`` (server/
        rest.py): live/tombstone accounting, snapshot + staged generation
        lag, PQ family state, host-fallback-cache residency. Lock-free by
        design — fields are read racily and may be mutually one mutation
        apart (introspection, not an invariant); nothing here touches the
        device."""
        snap = self._snap
        n, live = self.n, self.live
        tombs = max(n - live, 0)
        cache = self._host_rows_cache
        out = {
            "type": "hnsw_tpu",
            "metric": self.metric,
            "dim": self.dim,
            "capacity": self.capacity,
            "slots": n,
            "live": live,
            "tombstones": tombs,
            # free slots count as tombstones: their bit is set and the
            # scan still walks them, until the next rows take them
            "tombstone_fraction": round(tombs / n, 4) if n > 0 else 0.0,
            # tombstoned slots the next rows to land take before `slots`
            # grows, and why this index hands none out (None: it does)
            "free_slots": (int(self._ivf_free_n.sum())
                           if self._ivf_tiled and self._ivf_free_n is not None
                           else len(self._free_slots)),
            "slot_reuse_refused": self._reuse_refused(),
            "writes": dict(self._wstats),
            "log": self._log_health(),
            "pending_adds": len(self._pending),
            "pending_tombstones": len(self._pending_tombs),
            "snapshot_gen": snap.gen if snap is not None else 0,
            "staged_gen": self._staged_gen,
            "published_gen": self._published_gen,
            # staged writes not yet visible to lock-free readers (the
            # read-your-writes flush debt the next read pays)
            "staged_lag": max(self._staged_gen - self._published_gen, 0),
            "compressed": self.compressed,
            # what the last restart replayed into: `mode` compressed (a
            # persisted codebook: rows went straight into codes, bf16 rows
            # and host_vecs) or uncompressed, rows, seconds, chunks that
            # went through the codebook; None for an index never restored
            "restore": self.last_restore,
            "pq": None,
            # the IVF partition layout's health: a skewed or
            # padding-wasteful layout is visible HERE before it costs
            # recall or HBM (the /debug/index satellite)
            "ivf": self._ivf_health(),
            # a resident copy is a full f32 store materialization held for
            # the breaker's fallback plane (or a recent degraded window);
            # bytes come from the ledger's shared sizing helper so this
            # surface and /debug/memory can never disagree
            "host_fallback_cache": {
                "resident": cache is not None,
                "gen": cache[0] if cache is not None else None,
                "bytes": memory.host_rows_cache_bytes(self),
            },
            # the device/host byte picture of THIS index, from the same
            # analytic accounting the ledger stamps (monitoring/memory.py)
            "memory": {
                "device_components": self._memory_components(),
                "host_components": memory.index_host_components(self),
            },
            # per failure domain: compiled shapes that served / that Mosaic
            # rejected (ops/gmin_scan.kernel_health), and for the funnel
            # which stage-1 scan its dispatches ran
            "kernels": self._kernels_health(),
        }
        pq = self._pq
        if self.compressed and pq is not None:
            out["pq"] = {
                "segments": getattr(pq, "segments", None),
                "centroids": getattr(pq, "centroids", None),
                "rotation": bool(getattr(pq, "rotation", False)),
                "rescore": bool(self.config.pq.rescore
                                and self._rescore_dev is not None),
                "code_dtype": str(getattr(pq, "code_dtype", "")),
                # rows the codebook was fitted on (a declared class: its
                # `trainingLimit`); None for a codebook persisted before
                # the count was kept
                "trained_rows": getattr(pq, "trained_rows", None),
                # quantization-ladder state (the /debug/index satellite):
                # which bit depth serves, whether an OPQ rotation is
                # pinned, the controller-capped funnel budgets, and the
                # per-stage survivor accounting (racy leaf-lock counters,
                # same contract as the IVF probe stats)
                "bits": 4 if self._codes4 is not None else 8,
                "opq": self._opq_rot_dev is not None,
            }
            if self._codes4 is not None and self._pq4 is not None:
                k_ref = 10  # reference depth for the budget readout
                rg4, rc = funnel_budgets(k_ref, max(self.capacity, 1))
                with self._pq4_lock:
                    st = dict(self._pq4_stats)
                d = max(st["dispatches"], 1)
                out["pq"]["funnel"] = {
                    "stage1_c": rg4 * 16,
                    "stage2_rescore": rc,
                    "c_cap": controller.funnel_c_cap(
                        PQ4_FUNNEL_C_BUCKETS[-1]),
                    "rescore_cap": controller.funnel_rescore_cap(
                        PQ4_FUNNEL_RESCORE_BUCKETS[-1]),
                    "dispatches": st["dispatches"],
                    "mean_stage1_rows": round(st["stage1_rows"] / d, 1),
                    "mean_stage2_survivors": round(
                        st["stage2_survivors"] / d, 1),
                    "mean_stage3_survivors": round(
                        st["stage3_survivors"] / d, 1),
                }
        return out

    def _log_health(self) -> Optional[dict]:
        """The vector log on disk: its bytes, and the records in it that a
        replay would no longer land (superseded adds, their deletes): what
        the condensor at restart reads (`_maybe_condense_log`)."""
        log = self._log
        if log is None:
            return None
        return {"bytes": log.bytes, "records": log.records,
                "dead_records": max(
                    log.records - len(self._doc_to_slot) - len(self._pending),
                    0)}

    def search_by_vector(
        self, vector: np.ndarray, k: int, allow_list: Optional[AllowList] = None
    ) -> tuple[np.ndarray, np.ndarray]:
        ids, dists = self.search_by_vectors(np.asarray(vector)[None, :], k, allow_list)
        keep = dists[0] != np.inf
        return ids[0][keep], dists[0][keep]

    def search_by_vectors_async(self, vectors: np.ndarray, k: int,
                                allow_list: Optional[AllowList] = None):
        """Dispatch a batched kNN without blocking on the result.

        Returns finalize() -> (ids, dists). Covers EVERY read-path case —
        filtered searches, both PQ tiers, and the small-allowList gather —
        because dispatch runs on an immutable snapshot: there is no
        fully-locked sync fallback left. Dispatch (query upload + compute)
        overlaps with other in-flight batches — the serving loop and bench
        use a depth-2 pipeline so the PCIe upload of batch i+1 hides
        behind the compute of batch i, and the coalescer's finalize runs on
        its dispatch pool without contending with the next enqueue.
        """
        snap, wait_ms = self._read_snapshot()
        handle = self._dispatch_search(snap, vectors, k, allow_list)
        handle.lock_wait_ms = wait_ms
        return handle

    # -- a group of slots, each with its own filter ---------------------------

    def search_by_vectors_multi_async(self, vectors: np.ndarray, k: int,
                                      allow_lists: Sequence[Optional[AllowList]]):
        """kNN of a GROUP of slots, slot i under `allow_lists[i]` (None: no
        filter), in a bounded number of device dispatches whatever the mix
        of selectivities: at most one per-slot gather program a row bucket
        (`_search_gathered_multi`: 128, 512, ... rows a slot), ONE masked
        scan whose every query carries its own mask, and one plain scan for
        the slots without a filter. Which filtered slots gather and which
        share the scan is priced for the whole group
        (costmodel.plan_filtered_group), not cut at a constant a slot.
        Equal allowList OBJECTS are resolved once.

        -> a `DispatchHandle`: handle() -> (ids [S, k'] uint64, dists
        [S, k'] float32, inf where a slot has fewer than k' answers), with
        `handle.shapes`, the shapes of the dispatches made (empty while the
        tracer is down); or None where this index state has no per-slot
        program (compressed, or the IVF plane on): the caller then searches
        slot by slot. Every slot's answer is exact over the rows its own
        filter allows in the snapshot read here; tombstones are masked on
        the device by that snapshot."""
        snap, wait_ms = self._read_snapshot()
        snap = self._pin(snap)
        try:
            handle = self._enqueue_group(snap, vectors, k, allow_lists)
        finally:
            self._unpin(snap)
        if handle is not None:
            handle.lock_wait_ms = wait_ms
        return handle

    def _enqueue_group(self, snap: IndexSnapshot, vectors: np.ndarray,
                       k: int, allow_lists):
        """`search_by_vectors_multi_async` on the snapshot it pinned."""
        if snap.compressed or (snap.n and self._ivf_plan(snap, 1) is not None):
            return None
        q = np.array(vectors, dtype=np.float32, ndmin=2)
        s = q.shape[0]
        if len(allow_lists) != s:
            raise ValueError(f"{len(allow_lists)} allowLists for {s} queries")
        k_eff = min(k, snap.live)
        if snap.n == 0 or k_eff <= 0:
            return DispatchHandle.ready((np.zeros((s, 0), np.uint64),
                                         np.zeros((s, 0), np.float32)))
        faults.fire("index.tpu.dispatch")
        traced = tracing.get_tracer() is not None
        # one `enqueue` interval a dispatch; the first also holds the
        # resolution of the filters' slots and the plan
        enqueue = tracing.Phase("enqueue") if traced else None
        shapes: list = []
        # (slot positions, finalize -> (ids, dists), the shape to stamp)
        parts: list = []
        try:
            if self.metric == vi.DISTANCE_COSINE:
                norms = np.linalg.norm(q, axis=1, keepdims=True)
                norms[norms == 0] = 1.0
                q /= norms
            bb = _bucket_b(s)
            inputs, gathered, scanned, plain = self._plan_group(
                snap, allow_lists, bb)
            jobs = [(costmodel.TIER_GATHER, r, gathered[r])
                    for r in sorted(gathered)]
            if scanned:
                jobs.append((costmodel.TIER_EXACT, snap.n, scanned))
            # every dispatch's operands in one pass over the lists, before
            # the first upload
            operands = inputs.fill(self._group_pool, [
                (True, sel, _gather_slots(bb, rows), rows)
                if tier == costmodel.TIER_GATHER else
                (False, sel, _scan_group_bucket(len(sel), bb),
                 snap.capacity // 32) for tier, rows, sel in jobs])
            # what the group's first interval resolved
            resolved = {"lists": inputs.lists, "ids": inputs.ids}
            for (tier, rows, sel), operand in zip(jobs, operands):
                gather = tier == costmodel.TIER_GATHER
                shape = None
                if traced:
                    enqueue = enqueue or tracing.Phase("enqueue")
                    shape = costmodel.DispatchShape(
                        tier, dim=snap.dim, batch=len(sel), k=int(k_eff),
                        n=(int(operand.counts.sum(dtype=np.int64)) if gather
                           else snap.n),
                        batch_padded=operand.arr.shape[0],
                        bytes_per_row=snap.dim * snap.store.dtype.itemsize,
                        extra={"row_bucket": rows})
                parts.append((sel, (
                    self._dispatch_gather_group(snap, q[sel], operand, rows,
                                                bb, k_eff, shape) if gather
                    else self._dispatch_scan_group(snap, q[sel], operand,
                                                   k_eff, shape)),
                    shape, operand))
                if shape is not None:
                    end_ns = enqueue.end(rows=len(sel), tier=tier,
                                         row_bucket=rows, **resolved)
                    resolved = {}
                    shape.t_start = enqueue.start_ns / 1e9
                    shape.enqueue_ms = (end_ns - enqueue.start_ns) / 1e6
                    shapes.append(shape)
                    enqueue = None
            if plain:
                if enqueue is not None:
                    enqueue.end()   # _dispatch_search opens its own
                    enqueue = None
                # the unfiltered dispatch as it always was: its own
                # handle stamps its shape (and carries the audit pin, which
                # belongs to single dispatches: a group's has none)
                handle = self._dispatch_search(snap, q[plain], k_eff, None)
                parts.append((plain, handle, None, None))
                if handle.shape is not None:
                    shapes.append(handle.shape)
        finally:
            if enqueue is not None:   # nothing dispatched, or a failure
                enqueue.end()

        def finalize():
            ids = np.zeros((s, k_eff), np.uint64)
            dists = np.full((s, k_eff), np.inf, np.float32)
            for sel, fin, shape, operand in parts:  # graftlint: disable=JGL015 a loop over the group's DISPATCHES (a handful: one a row bucket, one scan), each consumed by unpack_fused; no per-row work
                pi, pd = fetch_stamped(fin, shape)
                # fetched: the program has consumed its operands, the
                # next group may write them (a fetch that failed
                # strands its buffer, as the staging pool's does)
                if operand is not None:
                    # `_release_stage`'s rule: never into the pool
                    # drop() cleared
                    self._group_pool.give(
                        operand, lambda: self.dim is not None)
                ids[sel, : pi.shape[1]] = pi
                dists[sel, : pd.shape[1]] = pd
            return ids, dists

        group = DispatchHandle(self, "index.tpu.finalize")
        group.shapes = shapes
        return group.launched(finalize)

    def _row_store(self, snap: IndexSnapshot):
        """The store as the per-slot programs read it: rows in whole lanes.
        A width that is a multiple of 128 is the store itself. Any other
        (192 is one and a half lanes) the TPU keeps column-major, so that
        nothing is padded, and every program that gathers rows from it
        first copies the WHOLE slab into the row-major tiled layout (read
        from the chip's compiler, tests/perfbench/test_perfbench_compile.py).
        So the twin is made once a store generation (a write replaces the
        store object, and with it the twin) and zero columns change no
        distance; the queries are padded to match."""
        store = snap.store
        dim = store.shape[1]
        if dim % 128 == 0:
            return store
        with self._row_store_lock:
            hit = self._row_store_cache
            if hit is None or hit[0] is not store:
                hit = self._row_store_cache = (store, _pad_rows(store))  # graftflow: disable=JGL018 generation-keyed single-entry cache (the store object is the key: a write replaces it) with an explicit release in compact and drop
            return hit[1]

    def _plan_group(self, snap: IndexSnapshot, allow_lists, bb: int):
        """Which program serves each slot of a group -> (the group's lists
        resolved in this snapshot: group_inputs.GroupInputs; {row bucket:
        the slots that gather in it}; the slots that share the scan; the
        slots without a filter). A slot whose filter allows no row rides no
        dispatch. Equal allowList OBJECTS are resolved once."""
        inputs = group_inputs.GroupInputs(snap, allow_lists,
                                          self._allow_slots, _slot_words)
        size_of = inputs.sizes.tolist()
        plain = [i for i, at in enumerate(inputs.list_of) if at < 0]
        filtered = [i for i, at in enumerate(inputs.list_of)
                    if at >= 0 and size_of[at]]
        sizes = [size_of[inputs.list_of[i]] for i in filtered]
        buckets = [_gather_row_bucket(m) for m in sizes]
        to_scan, _ = costmodel.plan_filtered_group(
            sizes, buckets, snap.n, snap.capacity,
            snap.dim * snap.store.dtype.itemsize,
            gather_max_rows(snap.dim, snap.capacity))
        gathered: dict[int, list[int]] = {}
        scanned: list[int] = []
        for i, r, scan in zip(filtered, buckets, to_scan):
            (scanned if scan else gathered.setdefault(r, [])).append(i)
        for r, sel in gathered.items():
            # a bucket takes as many slots as its index upload allows; the
            # rest share the scan
            room = _gather_slots(bb, r)
            scanned.extend(sel[room:])
            del sel[room:]
        return inputs, gathered, sorted(scanned), plain

    def _dispatch_gather_group(self, snap: IndexSnapshot, q: np.ndarray,
                               operand: group_inputs.Operand, r: int,
                               bb: int, k: int, shape):
        """One per-slot gather program: bucket `r`, the group's slots of
        that bucket, their store slots in `operand` (rows [s_pad, r],
        counts). The shapes compiled are one a (group-width bucket, row
        bucket, k): the slot dimension is padded to what the bucket admits
        and the program loops over the real slots alone."""
        nsel = q.shape[0]
        rows, counts = operand.arr, operand.counts
        s_pad = _gather_slots(bb, r)
        step = min(_gather_step_slots(r, snap.dim), s_pad)
        store = self._row_store(snap)
        qp = np.zeros((s_pad, store.shape[1]), np.float32)
        qp[:nsel, : snap.dim] = q
        cols = snap.s2d_cols
        if cols is None:
            cols = snap.s2d_cols = _split_doc_pairs(snap.slot_to_doc_dev)
        packed_dev = _search_gathered_multi(
            store, jnp.asarray(qp), jnp.asarray(rows),
            jnp.asarray(counts), np.int32(nsel), snap.tombs, cols[0], cols[1],
            min(k, r), self.metric, step)
        return self._finalize_fused(packed_dev, shape, nsel)

    def _dispatch_scan_group(self, snap: IndexSnapshot, q: np.ndarray,
                             operand: group_inputs.Operand, k: int, shape):
        """ONE masked scan for the group's widest filters: query i is
        masked by its own row of `operand`'s [b_pad, capacity / 32] words.
        The lax.scan program serves (the Pallas kernel takes one mask a
        dispatch)."""
        nsel = q.shape[0]
        words = operand.arr
        b_pad = words.shape[0]
        store = self._row_store(snap)
        qp = np.zeros((b_pad, store.shape[1]), np.float32)
        qp[:nsel, : snap.dim] = q
        kk = min(max(k, 1), snap.n)
        return self._finalize_fused(
            self._scan_program(snap, store, snap.sq_norms, qp,
                               jnp.asarray(words), kk), shape, nsel)

    def search_by_vector_distance(
        self,
        vector: np.ndarray,
        target_distance: float,
        max_limit: int,
        allow_list: Optional[AllowList] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """Iteratively double the limit until past the target distance
        (search.go:90-157), except each round is one batched device call."""
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

    def update_user_config(self, updated: vi.HnswUserConfig) -> None:
        with self._lock:
            vi.validate_config_update(self.config, updated)
            was_enabled = self.config.pq.enabled
            if updated.pq.enabled and not was_enabled and self.dim is not None \
                    and updated.pq.segments > 0 \
                    and self.dim % updated.pq.segments != 0:
                # dims are known: reject synchronously instead of deferring
                # the failure into the compression trigger
                raise vi.ConfigValidationError(
                    f"pq.segments ({updated.pq.segments}) must divide vector "
                    f"dims ({self.dim})")
            prev = self.config
            self.config = updated
            # pq.enabled flipped on by a config update triggers compression
            # (compress.go: "triggered by config update pq.enabled")
            if updated.pq.enabled and not was_enabled and not self.compressed:
                try:
                    self._flush_pending()
                    if self.n > 0:
                        self._compress_locked()
                except Exception:
                    # a failed pq-enable must not stick — config or runtime
                    # (an OOM'd kmeans fit): a committed-but-uncompressed
                    # config would re-run the full fit from _flush_pending's
                    # declarative trigger on every later add/search
                    self.config = prev
                    raise

    def flush(self) -> None:
        with self._lock:
            self._flush_pending()
            if self._log is not None:
                self._log.flush()
                if self._ivf_unsaved:
                    self._ivf_persist()

    def compact(self) -> None:
        """Condense: drop tombstoned slots, rewrite log (condensor.go analog).
        Under PQ the rebuild re-encodes against the existing codebook."""
        with self._lock:
            self._flush_pending()
            if self.n == 0:
                return
            live_slots = np.array(sorted(self._doc_to_slot.values()), dtype=np.int64)
            if live_slots.size == self.n:
                return
            t_compact0 = time.perf_counter()
            if self.compressed:
                store_host = self._host_vecs[: self.n]
            else:
                store_host = self._fetch_rows(self._store, self.n)  # graftlint: disable=JGL008 compact is a stop-the-world rebuild: the lock must cover it and the materialized store IS the rebuild's input  # graftflow: disable=JGL016 the same stop-the-world fetch, one call deep
            docs = self._slot_to_doc[live_slots]
            vecs = store_host[live_slots]
            if self._log is not None:
                self._log.rewrite(zip(docs.tolist(), vecs))
            # the slot->doc mapping is about to be rebuilt wholesale: any
            # packed-words cache keyed on the old mapping (same n/capacity
            # possible after re-adds) must never be served again
            self._allow_token = object()
            # rebuild device state; a compressed index is rebuilt straight
            # into its compressed form (`_init_device` takes the codebooks
            # from `_pending_pq`), the pq4 quantizer riding along with the
            # 8-bit one so the re-encode preserves BOTH ladders' codebooks
            pq, pq4, was_compressed = self._pq, self._pq4, self.compressed
            self.compressed = False
            self._pq = None
            self._codes = None
            self._rescore_dev = None
            self._rescore_sq_norms = None
            self._recon_norms = None
            self._pq4 = None
            self._codes4 = None
            self._recon_norms4 = None
            self._opq_rot_dev = None
            self._pq4_cb = None
            self._host_vecs = None
            self.dim = None
            self.capacity = 0
            self.n = 0
            self.live = 0
            self._doc_to_slot.clear()
            self._free_slots.clear()
            self._store = self._sq_norms = self._tombs = None
            self._s2d_dev = None
            self._row_store_cache = None
            # the partition layout indexes the OLD slot space — drop it
            # wholesale; the post-rebuild retrain below is the
            # "recluster on compact" half of the IVF lifecycle
            self._ivf_reset()
            self._slot_to_doc = np.zeros(0, dtype=np.int64)
            self._docs_ascending = True
            self._host_tombs = np.zeros(0, dtype=bool)
            # suppress the declarative compress trigger for the rebuild:
            # config.pq.enabled is true for ANY compressed index (compress
            # sets it), so _flush_pending would otherwise re-FIT a fresh
            # codebook mid-rebuild — changing the codes the re-encode
            # below is contracted to preserve, and leaving _store None
            # for it (the auditor's ground-truth parity test caught this)
            prev_restoring = self._restoring
            self._restoring = True
            self._pending_pq = (pq, pq4) if was_compressed else None
            try:
                for d, v in zip(docs.tolist(), vecs):
                    self._stage_add(int(d), v, log=False)
                self._flush_pending()
            finally:
                self._restoring = prev_restoring
                self._pending_pq = None
            # what a restart comes back to is what the rebuild ended on
            self._record_capacity()
            # recluster on the compacted slot space (fresh k-means — the
            # densified layout is a different distribution than the
            # tombstone-riddled one); publish so readers see it
            self._maybe_ivf_train()
            if self._published_gen != self._staged_gen:
                self._publish_snapshot()
            led = memory.get_ledger()
            if led is not None:
                led.note_write(
                    "compact", "compact",
                    (time.perf_counter() - t_compact0) * 1000.0,
                    rows=self.live)
            incidents.emit(
                "write_phase", scope="compact", rows=self.live,
                ms=round((time.perf_counter() - t_compact0) * 1000.0, 1))

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
            self._s2d_dev = None
            self._row_store_cache = None
            self._ivf_reset()
            self.dim = None
            self.capacity = 0
            self.n = 0
            self.live = 0
            self._slot_to_doc = np.zeros(0, dtype=np.int64)
            self._docs_ascending = True
            self._host_tombs = np.zeros(0, dtype=bool)
            with self._stage_lock:
                # parked staging buffers die with the data (a re-created
                # class may use a different dim; the ledger's
                # stage_buffers component must read 0 after drop)
                self._stage_free.clear()
            self._group_pool.clear()
            self._doc_to_slot.clear()
            self._pending.clear()
            self._pending_tombs.clear()
            self._free_slots.clear()
            self.compressed = False
            self._pq = None
            self._codes = None
            self._rescore_dev = None
            self._rescore_sq_norms = None
            self._recon_norms = None
            self._pq4 = None
            self._codes4 = None
            self._recon_norms4 = None
            self._opq_rot_dev = None
            self._pq4_cb = None
            self._host_vecs = None
            self._staged_gen += 1
            self._publish_snapshot()
            for path in (self._pq_path, self._pq4_path, self._capacity_path,
                         self._ivf_path):
                try:
                    os.remove(path)
                except FileNotFoundError:
                    pass

    def shutdown(self) -> None:
        with self._lock:
            self._flush_pending()
            if self._log is not None:
                self._log.flush()
                if self._ivf_unsaved:
                    # the slots the rows written since the last training
                    # took: a restart lands them there again
                    self._ivf_persist()
                self._log.close()

    def list_files(self) -> list[str]:
        files = [self._log.path] if self._log is not None else []
        for path in (self._pq_path, self._pq4_path, self._capacity_path,
                     self._ivf_path):
            if os.path.exists(path):
                files.append(path)
        return files
