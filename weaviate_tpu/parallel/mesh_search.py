"""Sharded vector-store kernels over a device mesh (shard_map + ICI collectives).

The device twin of Index.objectVectorSearch's errgroup fan-out + merge-sort
(adapters/repos/db/index.go:967-1046): instead of goroutines + HTTP, the
"fan-out" is SPMD execution of the same program on every chip over its local
HBM slab, and the "merge by distance" is an all_gather of [B, k] candidate
sets over ICI followed by a k-selection — all inside one jit.

Every kernel here is a whole-mesh step:

- mesh_search_step:  chunked masked kNN per slab (tombstones + allowList
  bitmap): the single-chip scan step itself (ops/scan.py scan_topk, fast
  scan and f32 rescore included), called on each chip's slab, with the
  cross-chip merge riding ICI. Every search kernel translates its LOCAL
  winners through its slab of the sharded slot->doc word table BEFORE the
  collective, so the gathered candidates already carry final doc ids and
  the merged output is the packed [B, 3k] layout of ops/topk
  translate_pack — one fetch, zero host translation, across chips.
- mesh_insert_step:  ALL shards land their staged rows in ONE program — the
  host ships a [n_dev, C, D] block sharded over the mesh, each chip writes its
  own chunk at its own offset (and derives l2 norms on device). No per-shard
  dispatch loop.
- mesh_delete_step:  tombstone scatter; each chip claims the global rows that
  fall inside its slab.
- mesh_grow_2d/1d:   geometric slab growth fully on device.

The serving-path index built on these kernels is
weaviate_tpu/index/mesh.py (vectorIndexType "hnsw_tpu_mesh").
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from weaviate_tpu.ops.scan import (
    SCAN_CHUNK, TPU_SCAN_OPTIONS, ScanProgram, scan_topk,
)
from weaviate_tpu.ops.topk import (
    bitmap_to_mask, rescore_distances, translate_pack,
)

SHARD_AXIS = "shard"


def _shard_map(f, *, mesh, in_specs, out_specs):
    """jax.shard_map with the replication check off: the kernels' outputs
    are made replicated by an explicit all_gather + reselect, which the
    checker cannot see through pallas_call."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _merge_across_shards_fused(d_top, i_loc, s2d_l, k):
    """The shared per-shard epilogue of every mesh search kernel
    (i_loc [B, k] = LOCAL slab rows, -1 for missing): the cross-chip merge
    with the slot->doc translation BEFORE the collective, so the merge
    semantics cannot diverge between kernels. Each chip gathers its k winners' doc-id words from its
    LOCAL slab of the sharded [cap, 2] uint32 table (a k-row gather — the
    table itself never crosses ICI), packs (dist | id_lo | id_hi) into the
    PR-14 fused [B, 3k] layout, all_gathers the per-chip packed blocks,
    and reselects the final k by distance. The winning id words ride the
    selection, so the replicated output is ALREADY the fused layout:
    finalize stays one fetch / zero host translation across chips
    (the JGL015 invariant, mesh-shaped). Missing slots (i_loc < 0) carry
    the 0xFFFFFFFF sentinel words from translate_pack and +inf distance,
    so they lose every selection and unpack to the same 2**64-1 id the
    single-chip fused path emits."""
    packed_l = translate_pack(d_top, i_loc, s2d_l)          # [B, 3k] i32
    all_p = jax.lax.all_gather(packed_l, SHARD_AXIS, axis=1, tiled=True)
    b = d_top.shape[0]
    w = all_p.reshape(b, -1, 3, k)                          # [B, n_dev, 3, k]
    d_all = jax.lax.bitcast_convert_type(
        w[:, :, 0, :], jnp.float32).reshape(b, -1)
    lo_all = w[:, :, 1, :].reshape(b, -1)
    hi_all = w[:, :, 2, :].reshape(b, -1)
    neg, pos = jax.lax.top_k(-d_all, k)
    d_fin = -neg
    lo = jnp.take_along_axis(lo_all, pos, axis=1)
    hi = jnp.take_along_axis(hi_all, pos, axis=1)
    return jnp.concatenate(
        [jax.lax.bitcast_convert_type(d_fin, jnp.int32), lo, hi], axis=1)


def make_mesh(n_devices: Optional[int] = None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if n_devices:
            if n_devices > len(devices):
                # a pinned meshDevices is the deployment's: never served
                # from fewer chips than it names
                raise ValueError(
                    f"mesh of {n_devices} devices asked for, this process "
                    f"has {len(devices)}")
            devices = devices[:n_devices]
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shard_spec(mesh: Mesh, *trailing_dims: None) -> NamedSharding:
    """NamedSharding splitting dim 0 over the mesh shard axis."""
    return NamedSharding(mesh, P(SHARD_AXIS, *trailing_dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def mesh_search_step(
    store, sq_norms, tombs, n_per_shard, allow_words, queries, s2d,
    k, metric, use_allow, use_norms, exact, fused, mesh, rescore_r=0,
):
    """Fully-sharded masked kNN: every chip runs the one-chip scan step
    (ops/scan.py scan_topk) over its own slab, then the cross-chip merge.

    store:       [n_dev * n_loc, D] sharded P('shard', None) — HBM slabs
    sq_norms:    [n_dev * n_loc] f32 sharded (l2 only; pass zeros otherwise)
    tombs:       [n_dev * n_loc] bool sharded — tombstone mask
    n_per_shard: [n_dev] int32 replicated — live high-water mark per slab
    allow_words: [n_dev * n_loc / 32] uint32 sharded — packed filter bitmap
    queries:     [B, D] replicated
    s2d:         [n_dev * n_loc, 2] uint32 sharded — per-slab slot->doc id
                 words
    fused:       must be True: the program translates on the device and
                 nothing else. The argument stays because the benchmark's
                 compile tests pass it (ROADMAP.md Queue 3); anything else
                 is refused.
    rescore_r:   the fast scan's depth R a chip (index/tpu.py
                 rescore_depth, planned against one slab). R > 0: each chip
                 scans its slab in ONE bf16 MXU pass (DEFAULT precision),
                 keeps R candidates a query, gathers those R rows from its
                 own slab and scores them elementwise in f32, so what
                 crosses ICI is still k (f32 distance, doc id) a chip and
                 every returned distance is the f32 distance of the row
                 returned. 0 (exactTopK, a non-matmul metric, k too deep
                 for R): six passes at HIGHEST precision select k directly,
                 with lax.top_k a chunk if `exact`, else approx_min_k.
    -> FUSED packed [B, 3k] i32 (translate_pack layout, doc ids already
       resolved on device), replicated.

    The cross-chunk and cross-chip merges are exact. The single-pass
    matmul needs TPU_SCAN_OPTIONS or XLA narrows every chip's whole f32
    slab to bf16 ahead of the loop, and the option binds to a top-level
    jit only: hence a ScanProgram (the platform of the store's devices
    picks the program), not a plain jit.
    """
    if not isinstance(fused, bool) or not fused:
        raise ValueError(
            "mesh_search_step translates on the device and nothing else: "
            f"fused must be True, got {fused!r}")

    def shard_fn(store_l, norms_l, tombs_l, n_all, allow_l, q, s2d_l):
        n_mine = n_all[jax.lax.axis_index(SHARD_AXIS)]
        d_top, i_top = scan_topk(
            store_l, norms_l if use_norms else None, tombs_l, n_mine, q,
            allow_l, k, metric, use_allow, exact, rescore_r=rescore_r)
        return _merge_across_shards_fused(d_top, i_top, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS), P(),
            P(SHARD_AXIS), P(), P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(store, sq_norms, tombs, n_per_shard, allow_words, queries, s2d)


_STEP_STATICS = ("k", "metric", "use_allow", "use_norms", "exact", "fused",
                 "mesh", "rescore_r")
mesh_search_step = ScanProgram(
    jax.jit(mesh_search_step, static_argnames=_STEP_STATICS),
    jax.jit(mesh_search_step, static_argnames=_STEP_STATICS,
            compiler_options=TPU_SCAN_OPTIONS))


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "use_norms", "rg",
                     "active_g", "interpret", "mesh"),
)
def mesh_search_gmin_step(
    store, sq_norms, tombs, n_per_shard, allow_words, queries, s2d,
    k, metric, use_allow, use_norms, rg, active_g, interpret, mesh,
):
    """Fused group-min kNN, mesh-sharded: each chip runs the SAME Pallas
    fast-scan + exact-rescore the single-chip index uses
    (ops/gmin_scan.gmin_topk) over its own HBM slab — distances never
    round-trip through HBM — and the cross-chip merge all_gathers k
    (dist, doc-id) candidates over ICI and reselects, exactly like
    mesh_search_step. Same argument layout as mesh_search_step plus the
    gmin parameters (rg kept groups, active_g live slices per slab)."""
    from weaviate_tpu.ops import gmin_scan

    def shard_fn(store_l, norms_l, tombs_l, n_all, allow_l, q, s2d_l):
        my = jax.lax.axis_index(SHARD_AXIS)
        n_mine = n_all[my]
        norms = norms_l if use_norms else jnp.zeros_like(norms_l)
        # per-shard block layout computed in-graph: the mesh path has no
        # host-side generation cache, and the transpose is ~ms at slab scale
        blk_l = gmin_scan.build_rescore_blocks(store_l)
        d_top, i_top = gmin_scan.gmin_topk(
            store_l, norms, tombs_l, n_mine, q, allow_l, use_allow,
            k, metric, rg, active_g, interpret, blk_l)
        return _merge_across_shards_fused(d_top, i_top, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS), P(),
            P(SHARD_AXIS), P(), P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(store, sq_norms, tombs, n_per_shard, allow_words, queries, s2d)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "rg", "active_g",
                     "interpret", "mesh"),
)
def mesh_search_pq_gmin_step(
    codes, recon_norms, tombs, n_per_shard, allow_words, cb_chunks, flat_cb,
    queries, rot, s2d, k, metric, use_allow, rg, active_g, interpret, mesh,
):
    """Codes-only fused ADC kNN, mesh-sharded: each chip runs the SAME
    reconstruction-as-matmul Pallas scan the single-chip index uses
    (ops/pq_gmin.pq_gmin_topk) over its own uint8 code slab — codes never
    expand in HBM — and the cross-chip merge all_gathers k (ADC dist,
    doc-id) candidates over ICI and reselects, exactly like the dense
    mesh_search_gmin_step. ADC distances are deterministic per slab, so the
    merge is exact w.r.t. the quantizer."""
    from weaviate_tpu.ops import pq_gmin

    def shard_fn(codes_l, norms_l, tombs_l, n_all, allow_l, cb_c, fcb, q, r,
                 s2d_l):
        my = jax.lax.axis_index(SHARD_AXIS)
        n_mine = n_all[my]
        d_top, i_top = pq_gmin.pq_gmin_topk(
            codes_l, norms_l, tombs_l, n_mine, q, cb_c, fcb, allow_l,
            use_allow, k, metric, rg, active_g, interpret, r,
            pq_gmin.build_codes_blocks(codes_l))
        return _merge_across_shards_fused(d_top, i_top, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS), P(),
            P(SHARD_AXIS), P(), P(), P(), P(), P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(codes, recon_norms, tombs, n_per_shard, allow_words, cb_chunks,
      flat_cb, queries, rot, s2d)


@functools.partial(
    jax.jit,
    static_argnames=("k", "r_chunk", "metric", "use_allow", "exact",
                     "do_rescore", "mesh"),
)
def mesh_search_pq_step(
    codes, recon_norms, tombs, n_per_shard, allow_words, codebook,
    rescore_store, queries, rot, s2d, k, r_chunk, metric, use_allow, exact,
    do_rescore, mesh,
):
    """Mesh twin of the single-chip PQ reconstruction scan
    (index/tpu.py _pq_recon_topk): each chip scans its OWN code slab —
    gather centroids per chunk into a [chunk, D] block, one bf16 matmul,
    collect per-chunk top-r — then exact-rescores its local candidate pool
    against its local rescore slab and keeps a local top-k; the cross-chip
    merge all_gathers k (dist, doc-id) candidates per chip over ICI and
    reselects. Rescored distances are exact f32, so the final merge is
    exact.

    codes:        [n_dev * n_loc, M] sharded P('shard', None)
    recon_norms:  [n_dev * n_loc] f32 sharded (||reconstruction||^2)
    tombs:        [n_dev * n_loc] bool sharded
    n_per_shard:  [n_dev] int32 replicated
    allow_words:  [n_dev * n_loc / 32] uint32 sharded
    codebook:     [M, C, ds] f32 replicated
    rescore_store:[n_dev * n_loc, D] sharded (bf16/f32 row copy)
    -> FUSED packed [B, 3k] i32 (translate_pack layout), replicated.
    """
    n_dev = mesh.devices.size
    n_loc = codes.shape[0] // n_dev
    m = codes.shape[1]
    _, c, ds = codebook.shape
    chunk = min(n_loc, SCAN_CHUNK)
    nchunks = n_loc // chunk

    def shard_fn(codes_l, norms_l, tombs_l, n_all, allow_l, cb, rs_l, q, r,
                 s2d_l):
        my = jax.lax.axis_index(SHARD_AXIS)
        n_mine = n_all[my]
        b = q.shape[0]
        flat_cb = cb.reshape(m * c, ds).astype(jnp.bfloat16)
        seg_off = (jnp.arange(m, dtype=jnp.int32) * c)[None, :]
        codes_c = codes_l.reshape(nchunks, chunk, m)
        norms_c = norms_l.reshape(nchunks, chunk)
        tombs_c = tombs_l.reshape(nchunks, chunk)
        allow_c = allow_l.reshape(nchunks, chunk // 32) if use_allow else None
        # OPQ: the ADC scan runs in the quantizer's rotated space; the
        # float rescore below uses the RAW query (the rescore slab holds
        # unrotated rows)
        qr = jnp.matmul(q.astype(jnp.float32), r,
                        preferred_element_type=jnp.float32)
        qd = qr.astype(jnp.bfloat16)
        q_sq = jnp.sum(qr ** 2, axis=-1, keepdims=True)

        def step(_, xs):
            ci, cl, nl, tl = xs[0], xs[1], xs[2], xs[3]
            base = ci * chunk
            idx = cl.astype(jnp.int32) + seg_off
            recon = jnp.take(flat_cb, idx, axis=0).reshape(chunk, m * ds)
            qx = jnp.matmul(qd, recon.T, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.DEFAULT)
            if metric == "l2-squared":
                d = jnp.maximum(q_sq - 2.0 * qx + nl[None, :], 0.0)
            elif metric == "dot":
                d = -qx
            else:
                d = 1.0 - qx
            valid = jnp.logical_and(jnp.arange(chunk) + base < n_mine,
                                    jnp.logical_not(tl))
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
        _, (tds, lis) = jax.lax.scan(step, None, tuple(xs))
        pool = nchunks * r_chunk
        cand_d = jnp.moveaxis(tds, 0, 1).reshape(b, pool)
        cand_i = jnp.moveaxis(lis, 0, 1).reshape(b, pool)
        if do_rescore:
            safe = jnp.clip(cand_i, 0, n_loc - 1)
            cand = jnp.take(rs_l, safe, axis=0)
            ed = rescore_distances(cand, q, metric)
            cand_d = jnp.where(jnp.isinf(cand_d), jnp.inf, ed)
        neg, pos = jax.lax.top_k(-cand_d, k)
        d_top = -neg
        i_top = jnp.take_along_axis(cand_i, pos, axis=1)
        i_loc = jnp.where(jnp.isinf(d_top), -1, i_top)
        return _merge_across_shards_fused(d_top, i_loc, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS), P(),
            P(SHARD_AXIS), P(), P(SHARD_AXIS, None), P(), P(),
            P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(codes, recon_norms, tombs, n_per_shard, allow_words, codebook,
      rescore_store, queries, rot, s2d)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "top_p", "exact", "gp",
                     "mesh"),
)
def mesh_search_ivf_step(
    store, tombs, n_per_shard, allow_words, centroids, buckets, queries,
    s2d, k, metric, use_allow, top_p, exact, gp, mesh,
):
    """Partition-pruned kNN over the sharded dense store: the mesh twin of
    ops/ivf.ivf_dense_topk. Centroids are replicated (every chip probes
    the SAME nlist partitions — the KScaNN-style balanced assignment is
    done at build time per device), but buckets are per-device: buckets
    [n_dev, nlist, cap_p] int32 sharded over dim 0 holds LOCAL slab slot
    ids (-1 padding), so each chip gathers only the probed candidates that
    physically live in its own HBM slab. Per-shard candidate scoring and
    local top-k mirror the single-chip grouped scan exactly (shared
    _probe/_candidate_slots/_slot_valid/_grouped_topk helpers); the
    cross-chip merge is the same epilogue as every other mesh search
    kernel. No PCA prefilter tier here: the probed per-device pool
    is already 1/n_dev of the single-chip pool, below where the prefilter
    pays for its extra gather."""
    from weaviate_tpu.ops import ivf as ivf_ops

    n_dev = mesh.devices.size
    n_loc = store.shape[0] // n_dev

    def shard_fn(store_l, tombs_l, n_all, allow_l, cent, bkt_l, q, s2d_l):
        my = jax.lax.axis_index(SHARD_AXIS)
        n_mine = n_all[my]
        qf = q.astype(jnp.float32)
        parts = ivf_ops._probe(qf, cent, top_p, metric)
        slots_g = ivf_ops._candidate_slots(parts, bkt_l[0], gp)
        valid_g = ivf_ops._slot_valid(slots_g, n_mine, tombs_l,
                                      allow_l if use_allow else None)

        def score_full(sl):
            rows = jnp.take(store_l, jnp.clip(sl, 0, n_loc - 1), axis=0)
            return rescore_distances(rows, qf, metric)

        d_top, i_top = ivf_ops._grouped_topk(slots_g, valid_g, score_full,
                                             k, exact)
        i_loc = jnp.where(jnp.isinf(d_top), -1, i_top)
        return _merge_across_shards_fused(d_top, i_loc, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(), P(SHARD_AXIS), P(),
            P(SHARD_AXIS, None, None), P(), P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(store, tombs, n_per_shard, allow_words, centroids, buckets, queries,
      s2d)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "rg4", "rc", "exact",
                     "mesh"),
)
def mesh_search_pq4_step(
    codes4, codes8, recon_norms4, recon_norms8, tombs, n_per_shard,
    allow_words, codebook4, flat_cb8, rescore_store, queries, rot, s2d,
    k, metric, use_allow, rg4, rc, exact, mesh,
):
    """The 4-bit Quick-ADC funnel, mesh-sharded: each chip runs the SAME
    three-stage funnel the single-chip index uses (ops/pq4.pq4_funnel_topk
    — byte-LUT nibble scan -> exact 8-bit ADC of the top rg4*G survivors
    -> exact rescore of the top rc against the chip's own store slab, the
    per-chip stage-3 source) over its own packed uint8 slab, and the
    cross-chip merge all_gathers k (exact dist, doc-id) candidates over ICI
    and reselects, exactly like the other mesh search kernels. Stage-3
    distances are exact f32, so the merge is exact.

    codes4:       [n_dev * n_loc, M/2] uint8 sharded — packed nibble pairs
    codes8:       [n_dev * n_loc, M] uint8 sharded — the 8-bit ladder rung
    recon_norms4/8: [n_dev * n_loc] f32 sharded (per-quantizer ||recon||^2)
    codebook4:    [M, 16, ds] f32 replicated
    flat_cb8:     [M * C, ds] bf16 replicated (pq_gmin.cached_cb_constants)
    rescore_store:[n_dev * n_loc, D] sharded — the resident bf16 store
    rot:          [D, D] f32 replicated OPQ rotation (or None)
    rg4/rc are PER-SHARD budgets (each chip funnels its own slab).
    The in-graph traceable stage 1 is used on every chip — the Pallas
    nibble kernel has no shard_map story yet, and the byte LUT is already
    one gather per packed byte."""
    from weaviate_tpu.ops import pq4 as pq4_ops

    def shard_fn(c4_l, c8_l, n4_l, n8_l, tombs_l, n_all, allow_l, cb4, fcb8,
                 rs_l, q, r, s2d_l):
        my = jax.lax.axis_index(SHARD_AXIS)
        n_mine = n_all[my]
        d_top, i_top = pq4_ops.pq4_funnel_topk(
            c4_l, c8_l, n4_l, n8_l, tombs_l, n_mine, q, None, cb4, fcb8,
            rs_l, allow_l, use_allow, k, metric, rg4, rc,
            use_pallas=False, interpret=False, exact=exact, rot=r)
        return _merge_across_shards_fused(d_top, i_top, s2d_l, k)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS, None), P(SHARD_AXIS),
            P(SHARD_AXIS), P(SHARD_AXIS), P(), P(SHARD_AXIS), P(), P(),
            P(SHARD_AXIS, None), P(), P(), P(SHARD_AXIS, None),
        ),
        out_specs=P(),
    )(codes4, codes8, recon_norms4, recon_norms8, tombs, n_per_shard,
      allow_words, codebook4, flat_cb8, rescore_store, queries, rot, s2d)


# NOTE on donation: the write kernels below deliberately do NOT donate
# their input slabs. Published MeshSnapshot objects pin the previous
# arrays for in-flight lock-free readers (docs/concurrency.md, snapshot
# plane); donating would hand XLA permission to overwrite buffers a
# concurrent dispatch is still scanning. The copy cost is the price of
# the snapshot contract — identical to the single-chip index's
# non-donating _write_rows/_set_tombstones kernels.
@functools.partial(jax.jit, static_argnames=("mesh",))
def mesh_write_rows_step(arr2d, arr1d, chunks2d, vals1d, offsets, takes, mesh):
    """Generic whole-mesh append for an arbitrary-dtype sharded matrix plus
    a per-row f32 vector (codes + recon_norms on the PQ path): each chip
    with takes[my] > 0 lands its chunk at its own offset."""

    def shard_fn(a2_l, a1_l, ch_l, v1_l, offs, tks):
        my = jax.lax.axis_index(SHARD_AXIS)
        off = offs[my]
        active = tks[my] > 0
        written2 = jax.lax.dynamic_update_slice(
            a2_l, ch_l[0].astype(a2_l.dtype), (off, 0))
        written1 = jax.lax.dynamic_update_slice(a1_l, v1_l[0], (off,))
        return (jnp.where(active, written2, a2_l),
                jnp.where(active, written1, a1_l))

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS, None, None),
            P(SHARD_AXIS, None), P(), P(),
        ),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
    )(arr2d, arr1d, chunks2d, vals1d, offsets, takes)


@functools.partial(jax.jit, static_argnames=("use_norms", "mesh"))
def mesh_insert_step(store, sq_norms, chunks, offsets, takes, use_norms, mesh):
    """One whole-mesh append: chunks [n_dev, C, D] sharded over dim 0 (each
    chip receives only its own [C, D] block), offsets/takes [n_dev]
    replicated. Every chip with work (takes[my] > 0) writes its chunk into
    its slab at its own offset and derives the l2 square-norms on device — a
    full import lands in one SPMD program regardless of shard count.

    Chips with takes[my] == 0 keep their slab bit-identical: the masked
    select below matters because a full slab's offset would clamp inside
    dynamic_update_slice and silently zero live rows."""

    def shard_fn(store_l, norms_l, chunk_l, offs, tks):
        my = jax.lax.axis_index(SHARD_AXIS)
        off = offs[my]
        active = tks[my] > 0
        ch = chunk_l[0]  # [C, D]
        written = jax.lax.dynamic_update_slice(
            store_l, ch.astype(store_l.dtype), (off, 0)
        )
        new_store = jnp.where(active, written, store_l)
        if use_norms:
            nch = jnp.sum(ch.astype(jnp.float32) ** 2, axis=1)
            new_norms = jnp.where(
                active, jax.lax.dynamic_update_slice(norms_l, nch, (off,)), norms_l
            )
        else:
            new_norms = norms_l
        return new_store, new_norms

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS), P(SHARD_AXIS, None, None), P(), P(),
        ),
        out_specs=(P(SHARD_AXIS, None), P(SHARD_AXIS)),
    )(store, sq_norms, chunks, offsets, takes)


@functools.partial(jax.jit, static_argnames=("mesh",))
def mesh_delete_step(tombs, rows, mesh):
    """Tombstone scatter: rows [P] int32 global rows, padded with -1. Each
    chip claims the rows inside its slab; out-of-slab rows map to the
    out-of-range sentinel and are dropped by the scatter."""
    n_loc = tombs.shape[0] // mesh.devices.size

    def shard_fn(tombs_l, rows_r):
        my = jax.lax.axis_index(SHARD_AXIS)
        lo = my * n_loc
        mine = jnp.logical_and(rows_r >= lo, rows_r < lo + n_loc)
        local = jnp.where(mine, rows_r - lo, n_loc)
        return tombs_l.at[local].set(True, mode="drop")

    return _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(SHARD_AXIS), P()),
        out_specs=P(SHARD_AXIS),
    )(tombs, rows)


@functools.partial(jax.jit, static_argnames=("mesh",))
def mesh_write_pairs_step(s2d, pairs, offsets, takes, mesh):
    """Whole-mesh append for the sharded slot->doc word table: pairs
    [n_dev, C, 2] uint32 sharded over dim 0 (each chip lands only its own
    [C, 2] block of (id_lo, id_hi) words), offsets/takes [n_dev]
    replicated. Same masked-select discipline as mesh_insert_step, and
    same non-donation contract — published snapshots pin the old table."""

    def shard_fn(s2d_l, pairs_l, offs, tks):
        my = jax.lax.axis_index(SHARD_AXIS)
        off = offs[my]
        active = tks[my] > 0
        written = jax.lax.dynamic_update_slice(s2d_l, pairs_l[0], (off, 0))
        return jnp.where(active, written, s2d_l)

    return _shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(
            P(SHARD_AXIS, None), P(SHARD_AXIS, None, None), P(), P(),
        ),
        out_specs=P(SHARD_AXIS, None),
    )(s2d, pairs, offsets, takes)


@functools.partial(jax.jit, static_argnames=("new_loc", "fill", "mesh"))
def mesh_grow_pairs(arr, new_loc, fill, mesh):
    """mesh_grow_2d for the slot->doc word table: the growth padding is
    the unwritten-slot sentinel (index/tpu.py _S2D_FILL), not zero — a
    zero pad would read as doc id 0. Slab-local offsets are preserved, so
    each chip's prefix stays valid after the grow."""

    def shard_fn(arr_l):
        out = jnp.full((new_loc, arr_l.shape[1]), fill, arr_l.dtype)
        return jax.lax.dynamic_update_slice(out, arr_l, (0, 0))

    return _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(SHARD_AXIS, None),),
        out_specs=P(SHARD_AXIS, None),
    )(arr)


@functools.partial(jax.jit, static_argnames=("new_loc", "mesh"))
def mesh_grow_2d(store, new_loc, mesh):
    """Geometric slab growth (maintainance.go:31 parity) without leaving the
    device: every chip pads its own slab to [new_loc, D]."""

    def shard_fn(store_l):
        out = jnp.zeros((new_loc, store_l.shape[1]), store_l.dtype)
        return jax.lax.dynamic_update_slice(out, store_l, (0, 0))

    return _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(SHARD_AXIS, None),),
        out_specs=P(SHARD_AXIS, None),
    )(store)


@functools.partial(jax.jit, static_argnames=("new_loc", "mesh"))
def mesh_grow_1d(arr, new_loc, mesh):
    def shard_fn(arr_l):
        out = jnp.zeros((new_loc,), arr_l.dtype)
        return jax.lax.dynamic_update_slice(out, arr_l, (0,))

    return _shard_map(
        shard_fn, mesh=mesh, in_specs=(P(SHARD_AXIS),),
        out_specs=P(SHARD_AXIS),
    )(arr)


class MeshSearchPlan:
    """Thin compatibility facade over the mesh index (weaviate_tpu/index/mesh.py)
    for standalone use (the driver dry run, notebooks): round-robin placement,
    no durability."""

    def __init__(self, mesh: Mesh, dim: int, capacity_per_shard: int = 16384,
                 metric: str = "l2-squared", dtype=jnp.float32):
        from weaviate_tpu.entities import vectorindex as vi
        from weaviate_tpu.index.mesh import MeshVectorIndex

        cfg = vi.HnswUserConfig(index_type="hnsw_tpu_mesh", distance=metric)
        if dtype == jnp.bfloat16:
            cfg.store_dtype = "bfloat16"
        self.index = MeshVectorIndex(
            cfg, shard_path="", persist=False, mesh=mesh,
            initial_capacity_per_shard=capacity_per_shard, dim_hint=dim,
        )
        self.mesh = mesh
        self.dim = dim

    def add_batch(self, doc_ids: np.ndarray, vectors: np.ndarray) -> None:
        self.index.add_batch(np.asarray(doc_ids), np.asarray(vectors, np.float32))

    def search(self, queries: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
        ids, d = self.index.search_by_vectors(np.asarray(queries, np.float32), k)
        # uint64 sentinel (max) -> -1 for the standalone API
        return ids.view(np.int64), d
