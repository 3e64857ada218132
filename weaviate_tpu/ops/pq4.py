"""4-bit Quick-ADC scan plane + the three-stage re-ranking funnel.

Why it exists: the 8-bit codes tier (ops/pq_gmin.py) bottoms out at M
bytes per scanned row, and ROADMAP's 100M-vectors-per-chip target needs
another 2x. Quick ADC's observation (Andre et al., PAPERS.md) is that a
4-bit sub-quantizer's 16-entry LUT fits in vector registers, so two codes
pack per byte and the scan reads M/2 bytes per row. The recall a coarser
code gives up comes back through a funnel (AQR-HNSW, PAPERS.md): the
4-bit ADC scan only has to KEEP the true neighbors inside its top-C, the
8-bit reconstruction rescore only inside its top-c, and the final
bf16/exact pass reports real distances.

The three stages, one jitted program:
  1. 4-bit ADC scan over the whole candidate set -> group-min scores
     [B, ncols] over the same G=16 column groups as the dense/8-bit fast
     scans -> approx top C/G groups (C = controller-guarded budget).
     Pallas where eligible — reconstruction-as-matmul with a 16-wide
     one-hot, the pq_gmin kernel's shape with nibble unpacking fused in —
     and a traceable byte-LUT scan otherwise (two 4-bit LUTs folded into
     one 256-entry LUT per byte: HALF the gathers of an 8-bit LUT scan).
  2. exact 8-bit ADC rescore of the C survivors (block gathers over the
     uint8 codes slab — rg4 contiguous G*M-byte slices per query, the
     pq_gmin rescore idiom) -> top c (the second budget).
  3. bf16/exact rescore of the c survivors against the rescore slab ->
     final top-k. Reported distances are the rescore tier's.

Both packings share ONE rotated space: the 4-bit quantizer is fit with
the 8-bit quantizer's OPQ rotation pinned (compress/pq.py fit), so a
candidate's rank only ever moves by quantization error, never by basis.

Codes pack with segment j in the LOW nibble and segment M/2 + j in the
HIGH nibble of byte j (compress/pq.pack_codes4), so unpacking is a
lane-wise concat — no per-element interleave on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from weaviate_tpu.monitoring.metrics import record_device_fallback
from weaviate_tpu.ops.gmin_scan import (G, _VMEM_BUDGET, compiler_params,
                                        mosaic_g)
from weaviate_tpu.ops.pq_gmin import adc_rescore_groups, build_cb_chunks

C4 = 16       # centroids per 4-bit sub-quantizer (one nibble)
_MSEG = 8     # segments per one-hot chunk (rows = _MSEG * C4 = 128)
_QB = 256     # query rows per grid step (upper bound)
_SCG = 256    # group-columns per grid step (upper bound)

_MATMUL_METRICS = ("l2-squared", "dot", "cosine")


def plan_tiles_pq4(b: int, d: int, ncols: int, ag: int, mb: int,
                   ) -> tuple[int, int, int, int]:
    """-> (qb, scg, mseg, footprint_bytes) for the 4-bit scan kernel.
    mb = packed bytes per row (M/2). Same hard-gate contract as
    pq_gmin.plan_tiles_pq: callers must refuse the kernel when even the
    smallest tiling exceeds the VMEM budget."""
    ag = mosaic_g(ag)
    m = 2 * mb
    mseg = min(_MSEG, m)
    qb = min(_QB, b)
    scg = min(_SCG, ncols)

    def footprint(qb_, scg_):
        inputs = (qb_ * d * 4                 # query tile
                  + ag * scg_ * mb            # packed codes tile (uint8)
                  + ag * scg_ * 4)            # bias tile
        cb = (m // mseg + (1 if m % mseg else 0)) * mseg * C4 * d * 2
        scratch = ag * scg_ * d * 4           # recon accumulator (f32)
        unpack = scg_ * m * 4                 # int32 unpacked codes block
        onehot = scg_ * mseg * C4 * 2         # bf16 one-hot chunk
        outputs = qb_ * scg_ * 4
        compute = qb_ * d * 2 + qb_ * scg_ * 4
        return 2 * inputs + cb + scratch + unpack + onehot + 2 * outputs + compute

    while scg > 64 and footprint(qb, scg) > _VMEM_BUDGET:
        scg //= 2
    while qb > 64 and footprint(qb, scg) > _VMEM_BUDGET:
        qb //= 2
    return qb, scg, mseg, footprint(qb, scg)


def fits_vmem_pq4(b: int, d: int, ncols: int, ag: int, mb: int) -> bool:
    return plan_tiles_pq4(b, d, ncols, ag, mb)[3] <= _VMEM_BUDGET


def pallas_eligible(state, metric: str, b: int, ncols: int, dim: int,
                    mb: int, active_g: int,
                    component: str = "ops.pq4") -> bool:
    """Whether stage 1 may run the Pallas kernel for this shape. Unlike
    pq_gmin's eligible_rg this gates ONLY the kernel choice — the funnel
    itself always serves (the traceable byte-LUT scan is the stage-1
    fallback, same scores to quantizer precision)."""
    if state._gmin_broken:
        record_device_fallback(component, "degraded", log=False)
        return False
    if metric not in _MATMUL_METRICS:
        return False
    if b < 8 or ncols < 64:
        return False
    return fits_vmem_pq4(b, dim, ncols, active_g, mb)


def plan_funnel(k: int, n: int, c_cap: int, rc_cap: int) -> tuple[int, int]:
    """Snap the two funnel budgets to kernel-shaped values:
    -> (rg4 kept stage-1 groups, rc stage-2 survivors). C = rg4*G rides
    whole column groups; both stages must cover k and each other
    (k <= rc <= rg4*G). n is the SCAN PLANE's row count — the slab
    capacity on the full-store tier (its column space is capacity/G;
    live rows spread across up to min(live, n/G) columns, so clamping
    against live rows would starve a sparse slab's stage 1), the probed
    candidate capacity on the IVF tier. Inputs are already bucket values
    (config.PQ4_FUNNEL_*_BUCKETS via the controller caps), so the jit
    shapes stay bounded; the clamps here only shrink toward small-index
    floors."""
    ncols = max(1, n // G)
    rg4 = max(1, min(c_cap // G, ncols))
    rc = max(k, min(rc_cap, rg4 * G))
    if rg4 * G < k:
        rc = rg4 * G
    return rg4, rc


def cached_cb4_constants(index, pq4=None):
    """Device codebook constants for the 4-bit plane, cached on the index
    per quantizer instance (`_pq4_cb`): bf16 block-diagonal chunks for the
    Pallas kernel and the dense [M, 16, ds] f32 codebook for the byte-LUT
    builder. Snapshot-isolated readers pass their snapshot's pq4."""
    if pq4 is None:
        pq4 = index._pq4
    cached = index._pq4_cb
    if cached is None or cached[0] is not pq4:
        cb = pq4.codebook  # [M, 16, ds] f32
        m = cb.shape[0]
        chunks = jnp.asarray(build_cb_chunks(cb, min(_MSEG, m)),
                             dtype=jnp.bfloat16)
        dense = jnp.asarray(cb)
        cached = (pq4, chunks, dense)
        index._pq4_cb = cached
    return cached[1], cached[2]


# -- stage 1, Pallas: nibble-unpacking reconstruction-as-matmul ---------------


def _pq4_kernel(q_ref, codes_ref, bias_ref, cb_ref, o_ref, recon_ref, *,
                alpha: float, g: int, mb: int, mseg: int):
    """One (store-tile i, query-tile j) step — pq_gmin._pq_gmin_kernel with
    the nibble unpack fused into the reconstruction pass. recon_ref is
    VMEM scratch [g, scg, D] persisting across the inner (query) grid
    dimension."""
    scg = codes_ref.shape[1]
    m = 2 * mb
    nchunks = -(-m // mseg)

    @pl.when(pl.program_id(1) == 0)
    def _reconstruct():
        def body(gi, _):
            packed = codes_ref[gi].astype(jnp.int32)      # [scg, mb]
            # pack layout: byte j = seg j | seg (mb+j) << 4 — unpack is a
            # lane concat, segments stay in order [0..m)
            codes_blk = jnp.concatenate([packed & 15, packed >> 4], axis=1)
            if m % mseg:
                codes_blk = jnp.pad(
                    codes_blk, ((0, 0), (0, nchunks * mseg - m)))
            acc = jnp.zeros((scg, recon_ref.shape[2]), jnp.float32)
            for t in range(nchunks):
                lo = t * mseg
                blk = jax.lax.slice_in_dim(codes_blk, lo, lo + mseg, axis=1)
                lanes = jax.lax.broadcasted_iota(
                    jnp.int32, (scg, mseg, C4), 2)
                oh = (lanes == blk[:, :, None]).astype(jnp.bfloat16)
                acc = acc + jnp.dot(
                    oh.reshape(scg, mseg * C4), cb_ref[t].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
            recon_ref[gi] = acc
            return 0

        jax.lax.fori_loop(0, g, body, 0)

    qd = q_ref[...].astype(jnp.bfloat16)

    def score(gi, acc):
        qx = jnp.dot(qd, recon_ref[gi].astype(jnp.bfloat16).T,
                     preferred_element_type=jnp.float32)
        return jnp.minimum(acc, bias_ref[gi] + alpha * qx)

    acc0 = jnp.full(o_ref.shape, jnp.inf, jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, g, score, acc0)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def pq4_group_min_scores(q, codes3p, bias2, cb_chunks, alpha: float, *,
                         active_g: int = G, interpret: bool = False):
    """[B, D] rotated queries x [G, ncols, mb] PACKED codes view ->
    [B, ncols] group-min ADC scores (the pq_gmin fast scan at half the
    bytes per row)."""
    b, d = q.shape
    g, ncols, mb = codes3p.shape
    nchunks, mc, _ = cb_chunks.shape
    mseg = mc // C4
    ag = mosaic_g(max(1, min(int(active_g), g)), g)
    qb, scg, _, _ = plan_tiles_pq4(b, d, ncols, ag, mb)
    grid = (ncols // scg, b // qb)  # queries innermost: recon runs once/tile
    return pl.pallas_call(
        functools.partial(_pq4_kernel, alpha=alpha, g=ag, mb=mb, mseg=mseg),
        out_shape=jax.ShapeDtypeStruct((b, ncols), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, d), lambda i, j: (j, 0)),
            pl.BlockSpec((ag, scg, mb), lambda i, j: (0, i, 0)),
            pl.BlockSpec((ag, scg), lambda i, j: (0, i)),
            pl.BlockSpec((nchunks, mc, d), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((qb, scg), lambda i, j: (j, i)),
        scratch_shapes=[_vmem((ag, scg, d), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, codes3p, bias2, cb_chunks)


# -- stage 1, traceable: the byte-LUT scan ------------------------------------


def byte_lut(qr, codebook4):
    """[B, D] ROTATED queries x [M, 16, ds] codebook -> [B, mb*256] f32
    byte LUT: entry j*256 + byte carries q.recon contributions of BOTH
    nibbles of packed byte j (Quick ADC's two-codes-per-lookup, host
    formulation). Flat layout so the scan gathers once per byte."""
    b, d = qr.shape
    m, c, ds = codebook4.shape
    mb = m // 2
    qs = qr.reshape(b, m, ds).astype(jnp.float32)
    lut4 = jnp.einsum("bmd,mcd->bmc", qs, codebook4.astype(jnp.float32))
    # byte value v = lo | hi << 4 -> v = hi*16 + lo: index [hi, lo]
    lut2 = lut4[:, mb:, :, None] + lut4[:, :mb, None, :]  # [B, mb, 16, 16]
    return lut2.reshape(b, mb * 256)


def pq4_scores_traceable(qr, codes3p, bias2, codebook4, alpha: float):
    """Traceable twin of pq4_group_min_scores: [B, ncols] group-min ADC
    scores via the byte LUT — M/2 gathers per row, no reconstruction."""
    b = qr.shape[0]
    g, ncols, mb = codes3p.shape
    lut2 = byte_lut(qr, codebook4)
    joff = (jnp.arange(mb, dtype=jnp.int32) * 256)[None, :]

    def body(gi, acc):
        idx = codes3p[gi].astype(jnp.int32) + joff            # [ncols, mb]
        s = jnp.take(lut2, idx, axis=1).sum(-1)               # [B, ncols]
        return jnp.minimum(acc, bias2[gi][None, :] + alpha * s)

    acc0 = jnp.full((b, ncols), jnp.inf, jnp.float32)
    return jax.lax.fori_loop(0, g, body, acc0)


# -- the funnel ---------------------------------------------------------------


def pq4_funnel_topk(codes4p, codes8, norms4, norms8, tombs, n, q, cb4_chunks,
                    codebook4, flat_cb8, rescore_rows, allow_words, use_allow,
                    k, metric, rg4, rc, active_g=G, use_pallas=False,
                    interpret=False, exact=False, rot=None, codes8_blk=None):
    """The full three-stage funnel -> ([B, k] dists, [B, k] slots, -1
    missing). Stage-1 candidates ride whole column groups (C = rg4*G);
    stage 2 is the exact 8-bit ADC of pq_gmin's rescore; stage 3 gathers
    the rc survivors' bf16 rows and reports exact distances
    (rescore_rows=None degrades to a two-stage funnel reporting 8-bit ADC
    distances — the codes-only memory floor)."""
    from weaviate_tpu.ops.topk import bitmap_to_mask, rescore_distances

    qf = q.astype(jnp.float32)
    qr = qf if rot is None else jnp.matmul(
        qf, rot, preferred_element_type=jnp.float32)
    cap, mb = codes4p.shape
    ncols = cap // G

    slot = jnp.arange(cap)
    dead = jnp.logical_or(tombs, slot >= n)
    if use_allow:
        dead = jnp.logical_or(
            dead, jnp.logical_not(bitmap_to_mask(allow_words, cap)))
    if metric == "l2-squared":
        base4 = norms4
        alpha = -2.0
    else:  # dot / cosine (rows pre-normalized at insert for cosine)
        base4 = jnp.zeros((cap,), jnp.float32)
        alpha = -1.0
    bias4 = jnp.where(dead, jnp.inf, base4)
    bias2 = bias4.reshape(G, ncols)

    # stage 1: 4-bit group-min scan -> top rg4 groups (C = rg4*G rows)
    codes3p = codes4p.reshape(G, ncols, mb)
    if use_pallas:
        gmin = pq4_group_min_scores(qr, codes3p, bias2, cb4_chunks, alpha,
                                    active_g=active_g, interpret=interpret)
    else:
        gmin = pq4_scores_traceable(qr, codes3p, bias2, codebook4, alpha)
    if exact or rg4 >= ncols:
        neg, gidx = jax.lax.top_k(-gmin, rg4)
    else:
        _, gidx = jax.lax.approx_min_k(gmin, rg4, recall_target=0.99)

    # stage 2: exact 8-bit ADC of the C survivors (pq_gmin's rescore)
    ed8, slots = adc_rescore_groups(qr, gidx, codes8, codes8_blk, flat_cb8,
                                    bias2, norms8, metric)
    neg, pos = jax.lax.top_k(-ed8, rc)
    d2 = -neg
    slots2 = jnp.take_along_axis(slots, pos, axis=1)

    # stage 3: bf16/exact rescore of the rc survivors (RAW query — the
    # rescore slab holds unrotated rows; ranks are rotation-invariant)
    if rescore_rows is not None:
        rows = jnp.take(rescore_rows, jnp.clip(slots2, 0, cap - 1), axis=0)
        ed3 = rescore_distances(rows, qf, metric)
        ed3 = jnp.where(jnp.isinf(d2), jnp.inf, ed3)
        neg, pos3 = jax.lax.top_k(-ed3, k)
        top = -neg
        idx = jnp.take_along_axis(slots2, pos3, axis=1)
    else:
        top = d2[:, :k]
        idx = slots2[:, :k]
    idx = jnp.where(jnp.isinf(top), -1, idx).astype(jnp.int32)
    return top, idx


_FUNNEL_STATICS = ("use_allow", "k", "metric", "rg4", "rc", "active_g",
                   "use_pallas", "interpret", "exact")


@functools.partial(jax.jit, static_argnames=_FUNNEL_STATICS)
def search_pq4_funnel_fused(codes4p, codes8, norms4, norms8, tombs, n, q,
                            cb4_chunks, codebook4, flat_cb8, rescore_rows,
                            allow_words, s2d, use_allow, k, metric, rg4, rc,
                            active_g=G, use_pallas=False, interpret=False,
                            exact=False, rot=None, codes8_blk=None):
    """pq4_funnel_topk as a top-level program with the slot->doc
    translation in the same program (ops/topk.translate_pack FUSED [B, 3k]
    layout): one packed fetch carries final doc ids — the funnel twin of
    pq_gmin.search_pq_gmin_fused."""
    from weaviate_tpu.ops.topk import translate_pack

    top, idx = pq4_funnel_topk(
        codes4p, codes8, norms4, norms8, tombs, n, q, cb4_chunks, codebook4,
        flat_cb8, rescore_rows, allow_words, use_allow, k, metric, rg4, rc,
        active_g, use_pallas, interpret, exact, rot, codes8_blk)
    return translate_pack(top, idx, s2d)


# -- IVF composition ----------------------------------------------------------


_IVF_STATICS = ("k", "metric", "use_allow", "top_p", "c1", "rc", "exact",
                "gp", "steps2")


def ivf_pq4_topk(codes4p, codes8, norms4, norms8, tombs, n, q, allow_words,
                 codebook4, codebook8, centroids, buckets, rot,
                 rescore_rows, k, metric, use_allow, top_p, c1, rc, exact,
                 gp, steps2):
    """IVF-probed three-stage funnel: probe -> grouped 4-bit byte-LUT ADC
    over the probed buckets (keep c1) -> grouped exact 8-bit ADC of the
    survivors (keep rc) -> bf16/exact rescore -> ([B, k] dists, [B, k]
    slots, -1 missing). The probe,
    candidate grouping, masking, and collect-then-merge discipline are
    ops/ivf.py's own (shared helpers), so the funnel composes with
    partitions, filters, and tombstones as a tier, not a fork."""
    from weaviate_tpu.entities import vectorindex as vi
    from weaviate_tpu.ops.ivf import (
        _candidate_slots,
        _grouped_topk,
        _probe,
        _regroup,
        _slot_valid,
    )
    from weaviate_tpu.ops.topk import rescore_distances

    qf = q.astype(jnp.float32)
    parts = _probe(qf, centroids, top_p, metric)
    slots_g = _candidate_slots(parts, buckets, gp)
    valid_g = _slot_valid(slots_g, n, tombs,
                          allow_words if use_allow else None)
    cap, mb = codes4p.shape
    m8 = codes8.shape[1]
    _, c8, ds8 = codebook8.shape
    qr = qf if rot is None else jnp.matmul(
        qf, rot, preferred_element_type=jnp.float32)
    q_sq = jnp.sum(qr ** 2, axis=-1, keepdims=True)

    # stage 1: byte-LUT 4-bit ADC (per-query LUT, batched gathers)
    lut2 = byte_lut(qr, codebook4)                       # [B, mb*256]
    joff = (jnp.arange(mb, dtype=jnp.int32) * 256)[None, None, :]

    def score_adc4(sl):
        bq, g = sl.shape
        safe = jnp.clip(sl, 0, cap - 1)
        pk = jnp.take(codes4p, safe, axis=0).astype(jnp.int32)  # [B, g, mb]
        idx = (pk + joff).reshape(bq, g * mb)
        s = jnp.take_along_axis(lut2, idx, axis=1).reshape(bq, g, mb).sum(-1)
        if metric == vi.DISTANCE_L2:
            nrm = jnp.take(norms4, safe)
            return jnp.maximum(q_sq - 2.0 * s + nrm, 0.0)
        if metric == vi.DISTANCE_DOT:
            return -s
        return 1.0 - s

    # stage 2: exact 8-bit ADC (ivf_codes_topk's scoring, per survivor)
    flat_cb8 = codebook8.reshape(m8 * c8, ds8).astype(jnp.bfloat16)
    seg_off = (jnp.arange(m8, dtype=jnp.int32) * c8)[None, None, :]
    qd = qr.astype(jnp.bfloat16)

    def score_adc8(sl):
        safe = jnp.clip(sl, 0, cap - 1)
        cd = jnp.take(codes8, safe, axis=0).astype(jnp.int32)
        recon = jnp.take(flat_cb8, cd + seg_off, axis=0)
        recon = recon.reshape(cd.shape[0], cd.shape[1], m8 * ds8)
        qx = jnp.einsum("bd,bgd->bg", qd, recon,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.DEFAULT)
        if metric == vi.DISTANCE_L2:
            nrm = jnp.take(norms8, safe)
            return jnp.maximum(q_sq - 2.0 * qx + nrm, 0.0)
        if metric == vi.DISTANCE_DOT:
            return -qx
        return 1.0 - qx

    # c1 is already a wide cut over rc (the pre_c discipline): slack=False
    _, pslots = _grouped_topk(slots_g, valid_g, score_adc4, c1, False,
                              slack=False)
    slots2, valid2 = _regroup(pslots, pslots >= 0, steps2)
    top2, idx2 = _grouped_topk(slots2, valid2, score_adc8, rc, exact)

    # stage 3: bf16/exact rescore of the rc survivors (RAW query)
    if rescore_rows is not None:
        rows = jnp.take(rescore_rows, jnp.clip(idx2, 0, cap - 1), axis=0)
        ed3 = rescore_distances(rows, qf, metric)
        ed3 = jnp.where(jnp.isinf(top2), jnp.inf, ed3)
        neg, pos = jax.lax.top_k(-ed3, k)
        top = -neg
        idx = jnp.take_along_axis(idx2, pos, axis=1)
    else:
        top, idx = top2[:, :k], idx2[:, :k]
    return top, jnp.where(jnp.isinf(top), -1, idx)


@functools.partial(jax.jit, static_argnames=_IVF_STATICS)
def search_ivf_pq4_fused(codes4p, codes8, norms4, norms8, tombs, n, q,
                         allow_words, codebook4, codebook8, centroids,
                         buckets, rot, rescore_rows, s2d, k, metric,
                         use_allow, top_p, c1, rc, exact, gp, steps2):
    """ivf_pq4_topk as a top-level program, its winners translated to doc
    ids in the same program."""
    from weaviate_tpu.ops.topk import translate_pack

    top, idx = ivf_pq4_topk(
        codes4p, codes8, norms4, norms8, tombs, n, q, allow_words, codebook4,
        codebook8, centroids, buckets, rot, rescore_rows, k, metric,
        use_allow, top_p, c1, rc, exact, gp, steps2)
    return translate_pack(top, idx, s2d)
