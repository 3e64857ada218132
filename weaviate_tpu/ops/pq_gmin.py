"""Fused PQ-ADC + group-min Pallas kernel: the codes-only serving fast path.

Why it exists: the memory-tightest PQ tier (rescore disabled, or restarts
before the rescore store rebuilds) must scan uint8 codes. The previous path
(index/tpu.py _search_pq_recon) reconstructs every chunk into a [chunk, D]
float block in HBM via an XLA gather each batch — the gather is
VPU-hostile on TPU and the reconstruction round-trips HBM. The reference's
answer is a per-element LUT scan (ssdhelpers/product_quantization.go:56-75),
which is exactly the gather-bound pattern the MXU cannot help with.

The TPU-native formulation: reconstruction IS a matmul. With one-hot row
encodings, recon = onehot([scg, M*C]) @ cb_diag([M*C, D]) where cb_diag is
the block-diagonal expanded codebook (row m*C + c carries codebook[m, c]
in columns m*ds..(m+1)*ds). The kernel builds the one-hot in VMEM (a
broadcasted-iota compare — VPU-cheap), reconstructs each store tile ONCE
per grid row into VMEM scratch, and fuses the distance matmul + group-min
exactly like the dense kernel (ops/gmin_scan.py). Codes never expand in
HBM: HBM traffic is the uint8 codes (M bytes/row vs 2D bytes for the bf16
dense scan — 8x less at M=32, D=128), at the cost of extra MXU work that
amortizes over the query tiles of a serving batch.

Scoring unifies as  score = bias[slot] + alpha * (q . recon[slot]) with
bias carrying ||recon||^2 (+inf dead) for l2 — identical rank semantics to
the dense gmin scan, with ADC error bounded by the quantizer, not the
kernel. Selection + exact-ADC rescore of the kept groups mirrors
gmin_topk; distances returned are ADC-exact (the same values
_search_pq_recon's do_rescore=False tier reports).
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

_MSEG = 8     # segments reconstructed per one-hot matmul chunk
_QB = 256     # query rows per grid step (upper bound)
_SCG = 256    # group-columns per grid step (upper bound)


def plan_tiles_pq(b: int, d: int, ncols: int, ag: int, m: int, c: int,
                  ) -> tuple[int, int, int, int]:
    """-> (qb, scg, mseg, footprint_bytes). Same hard-gate contract as
    gmin_scan.plan_tiles: callers must refuse the kernel when even the
    smallest tiling exceeds the VMEM budget."""
    ag = mosaic_g(ag)  # footprint must price the padded slices the kernel loads
    mseg = min(_MSEG, m)
    qb = min(_QB, b)
    scg = min(_SCG, ncols)

    def footprint(qb_, scg_):
        inputs = (qb_ * d * 4                 # query tile
                  + ag * scg_ * m             # codes tile (uint8)
                  + ag * scg_ * 4)            # bias tile
        cb = (m // mseg + (1 if m % mseg else 0)) * mseg * c * d * 2
        scratch = ag * scg_ * d * 4           # recon accumulator (f32)
        onehot = scg_ * mseg * c * 2          # bf16 one-hot chunk
        outputs = qb_ * scg_ * 4
        compute = qb_ * d * 2 + qb_ * scg_ * 4
        return 2 * inputs + cb + scratch + onehot + 2 * outputs + compute

    while scg > 64 and footprint(qb, scg) > _VMEM_BUDGET:
        scg //= 2
    while qb > 64 and footprint(qb, scg) > _VMEM_BUDGET:
        qb //= 2
    return qb, scg, mseg, footprint(qb, scg)


def fits_vmem_pq(b: int, d: int, ncols: int, ag: int, m: int, c: int) -> bool:
    return plan_tiles_pq(b, d, ncols, ag, m, c)[3] <= _VMEM_BUDGET


_MATMUL_METRICS = ("l2-squared", "dot", "cosine")


def eligible_rg(state, exact_topk: bool, metric: str, pq, b: int, ncols: int,
                kk: int, dim: int, active_g: int,
                component: str = "ops.pq_gmin"):
    """Shared eligibility gate for the fused codes kernel -> rg (kept
    groups) when this shape may serve, else None. ONE copy for the
    single-chip and mesh dispatches so their gating cannot diverge (the
    same contract KernelState enforces for fallback state)."""
    if exact_topk:
        return None  # config opt-out, not degradation
    if state._gmin_broken:
        record_device_fallback(component, "degraded", log=False)
        return None
    if metric not in _MATMUL_METRICS:
        return None
    if pq is None or pq.centroids > 256 or b < 8:
        return None
    if ncols < 64:
        return None
    rg = min(max(32, 2 * kk), 128, ncols)
    if rg < kk:
        return None
    if not fits_vmem_pq(b, dim, ncols, active_g, pq.segments, pq.centroids):
        return None
    return rg


def cached_cb_constants(index, pq=None):
    """Device codebook constants for the fused codes kernel, cached on the
    index per ProductQuantizer instance (index carries `_pqg_cb`): (bf16
    block-diagonal chunks — what the kernel holds in VMEM, counted at 2
    bytes by the planner — and the f32 flat codebook for the exact-ADC
    candidate rescore). `pq` defaults to the index's live quantizer;
    snapshot-isolated readers pass their snapshot's pq so constants always
    match the codes they dispatch against."""
    if pq is None:
        pq = index._pq
    cached = index._pqg_cb
    if cached is None or cached[0] is not pq:
        cb = pq.codebook  # [M, C, ds] f32
        m = cb.shape[0]
        chunks = jnp.asarray(build_cb_chunks(cb, min(_MSEG, m)),
                             dtype=jnp.bfloat16)
        flat = jnp.asarray(cb.reshape(-1, cb.shape[2]))
        cached = (pq, chunks, flat)
        index._pqg_cb = cached
    return cached[1], cached[2]


def build_cb_chunks(codebook: np.ndarray, mseg: int) -> np.ndarray:
    """[M, C, ds] codebook -> [n_chunks, mseg*C, D] bf16 block-diagonal
    chunks: chunk t row (s*C + c) carries codebook[t*mseg + s, c] in columns
    (t*mseg + s)*ds .. +ds, zeros elsewhere — so
    recon = sum_t onehot_t @ cb_chunks[t]."""
    m, c, ds = codebook.shape
    d = m * ds
    nchunks = -(-m // mseg)
    out = np.zeros((nchunks, mseg * c, d), dtype=np.float32)
    for seg in range(m):
        t, s = divmod(seg, mseg)
        rows = slice(s * c, (s + 1) * c)
        cols = slice(seg * ds, (seg + 1) * ds)
        out[t, rows, cols] = codebook[seg]
    return out


def _pq_gmin_kernel(q_ref, codes_ref, bias_ref, cb_ref, o_ref, recon_ref, *,
                    alpha: float, g: int, m: int, c: int, mseg: int):
    """One (store-tile i, query-tile j) step; recon_ref is VMEM scratch
    [g, scg, D] f32 persisting across the inner (query) grid dimension —
    reconstruction runs once per store tile and amortizes over every query
    tile."""
    scg = codes_ref.shape[1]
    nchunks = -(-m // mseg)

    @pl.when(pl.program_id(1) == 0)
    def _reconstruct():
        def body(gi, _):
            codes_blk = codes_ref[gi].astype(jnp.int32)   # [scg, M]
            if m % mseg:
                # pad ragged tail segments with code 0: the padded rows of
                # cb_chunks are zeros, so they contribute nothing
                codes_blk = jnp.pad(
                    codes_blk, ((0, 0), (0, nchunks * mseg - m)))
            acc = jnp.zeros((scg, recon_ref.shape[2]), jnp.float32)
            for t in range(nchunks):
                lo = t * mseg
                blk = jax.lax.slice_in_dim(codes_blk, lo, lo + mseg, axis=1)
                lanes = jax.lax.broadcasted_iota(
                    jnp.int32, (scg, mseg, c), 2)
                oh = (lanes == blk[:, :, None]).astype(jnp.bfloat16)
                acc = acc + jnp.dot(
                    oh.reshape(scg, mseg * c), cb_ref[t].astype(jnp.bfloat16),
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


def pq_group_min_scores(q, codes3, bias2, cb_chunks, alpha: float, *,
                        active_g: int = G, interpret: bool = False):
    """[B, D] queries x [G, ncols, M] codes view -> [B, ncols] group-min ADC
    scores. B % QB == 0 and ncols % SCG == 0 (callers pad; capacities are
    powers of two)."""
    b, d = q.shape
    g, ncols, m = codes3.shape
    nchunks, mc, _ = cb_chunks.shape
    c = mc // min(_MSEG, m)
    ag = mosaic_g(max(1, min(int(active_g), g)), g)
    qb, scg, mseg, _ = plan_tiles_pq(b, d, ncols, ag, m, c)
    grid = (ncols // scg, b // qb)  # queries innermost: recon runs once/tile
    return pl.pallas_call(
        functools.partial(_pq_gmin_kernel, alpha=alpha, g=ag, m=m, c=c,
                          mseg=mseg),
        out_shape=jax.ShapeDtypeStruct((b, ncols), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((qb, d), lambda i, j: (j, 0)),
            pl.BlockSpec((ag, scg, m), lambda i, j: (0, i, 0)),
            pl.BlockSpec((ag, scg), lambda i, j: (0, i)),
            pl.BlockSpec((nchunks, mc, d), lambda i, j: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((qb, scg), lambda i, j: (j, i)),
        scratch_shapes=[_vmem((ag, scg, d), jnp.float32)],
        compiler_params=compiler_params(),
        interpret=interpret,
    )(q, codes3, bias2, cb_chunks)


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


@jax.jit
def build_codes_blocks(codes):
    """[cap, M] codes -> [ncols, G*M] group-block layout (the codes twin of
    gmin_scan.build_rescore_blocks): the ADC rescore's candidate gather
    drops from rg*G scattered M-byte rows per query to rg contiguous
    G*M-byte slices. Cached by the index per codes generation."""
    cap, m = codes.shape
    ncols = cap // G
    return codes.reshape(G, ncols, m).transpose(1, 0, 2).reshape(ncols, G * m)


def adc_rescore_groups(q, gidx, codes, codes_blk, flat_cb, bias2, norms,
                       metric):
    """Exact ADC distances of the members of the kept groups ->
    (ed [B, R*G], slots [B, R*G]); dead members score +inf. q is already
    in the quantizer's (rotated) space. Shared by the codes-only rescore
    and the 4-bit funnel's stage 2.

    q . recon is summed from a per-query LUT (lut[b, m*C + c] =
    q_m . codebook[m, c], the reference's ADC formulation) instead of
    from gathered reconstructions: a [.., M, ds] gather has ds (4 at
    D=128, M=32) as its minor dimension, which the TPU layout pads to 128
    lanes — 2 GB of HBM temp at B=256, rg=32 and 16 GB at the funnel's
    C=4096, which does not compile on a 16 GB chip. The LUT gather's
    output is the unpadded [B, R*G*M]. Candidate codes, bias validity and
    recon norms all ride [ncols, G] block gathers (R descriptors/query),
    never per-slot takes."""
    cap, m = codes.shape
    ncols = cap // G
    b, r = gidx.shape
    c = flat_cb.shape[0] // m
    ds = flat_cb.shape[1]
    offs = (jnp.arange(G) * ncols)[None, None, :]
    slots = (gidx[:, :, None] + offs).reshape(b, r * G)
    if codes_blk is not None:
        cand_codes = jnp.take(codes_blk, gidx, axis=0).reshape(
            b, r * G, m).astype(jnp.int32)
    else:
        cand_codes = jnp.take(codes, slots, axis=0).astype(jnp.int32)
    qf = q.astype(jnp.float32)
    lut = jnp.einsum("bmd,mcd->bmc", qf.reshape(b, m, ds),
                     flat_cb.reshape(m, c, ds),
                     precision=jax.lax.Precision.HIGHEST).reshape(b, m * c)
    seg_off = (jnp.arange(m, dtype=jnp.int32) * c)[None, None, :]
    qx = jnp.take_along_axis(
        lut, (cand_codes + seg_off).reshape(b, r * G * m), axis=1,
    ).reshape(b, r * G, m).sum(-1)
    if metric == "l2-squared":
        q_sq = jnp.sum(qf ** 2, axis=-1, keepdims=True)
        nrm = jnp.take(norms.reshape(G, ncols).T, gidx, axis=0).reshape(
            b, r * G)
        ed = jnp.maximum(q_sq - 2.0 * qx + nrm, 0.0)
    elif metric == "dot":
        ed = -qx
    else:  # cosine: rows pre-normalized at insert
        ed = 1.0 - qx
    cand_bias = jnp.take(bias2.T, gidx, axis=0).reshape(b, r * G)
    return jnp.where(jnp.isinf(cand_bias), jnp.inf, ed), slots


def pq_gmin_topk(codes, recon_norms, tombs, n, q, cb_chunks, flat_cb,
                 allow_words, use_allow, k, metric, rg, active_g=G,
                 interpret=False, rot=None, codes_blk=None):
    """Full codes-only fused search -> ([B, k] ADC dists, [B, k] slots, -1
    missing). Mirrors gmin_scan.gmin_topk: fast scan -> top-RG groups ->
    exact-ADC rescore of RG*G members -> top-k. flat_cb is [M*C, ds] f32
    (row-major codebook) for the candidate reconstruction gather — tiny
    (rg*G rows per query), XLA-side. rot ([D, D], identity when no OPQ)
    maps queries into the quantizer's rotated space — distances are
    rotation-invariant for the matmul metrics, so results rank the
    original space. codes_blk: optional build_codes_blocks(codes) output
    for the block-gather rescore path (adc_rescore_groups)."""
    from weaviate_tpu.ops.topk import bitmap_to_mask

    if rot is not None:
        q = jnp.matmul(q.astype(jnp.float32), rot,
                       preferred_element_type=jnp.float32)
    cap, m = codes.shape
    ncols = cap // G

    slot = jnp.arange(cap)
    dead = jnp.logical_or(tombs, slot >= n)
    if use_allow:
        dead = jnp.logical_or(dead, jnp.logical_not(bitmap_to_mask(allow_words, cap)))
    if metric == "l2-squared":
        base = recon_norms
        alpha = -2.0
    else:  # dot / cosine (rows pre-normalized at insert for cosine)
        base = jnp.zeros((cap,), jnp.float32)
        alpha = -1.0
    bias = jnp.where(dead, jnp.inf, base)

    codes3 = codes.reshape(G, ncols, m)
    bias2 = bias.reshape(G, ncols)
    gmin = pq_group_min_scores(q, codes3, bias2, cb_chunks, alpha,
                               active_g=active_g, interpret=interpret)
    _, gidx = jax.lax.approx_min_k(gmin, rg, recall_target=0.99)

    ed, slots = adc_rescore_groups(q, gidx, codes, codes_blk, flat_cb, bias2,
                                   recon_norms, metric)
    neg, pos = jax.lax.top_k(-ed, k)
    top = -neg
    idx = jnp.take_along_axis(slots, pos, axis=1)
    idx = jnp.where(jnp.isinf(top), -1, idx).astype(jnp.int32)
    return top, idx


@functools.partial(
    jax.jit,
    static_argnames=("use_allow", "k", "metric", "rg", "active_g", "interpret"),
)
def search_pq_gmin_fused(codes, recon_norms, tombs, n, q, cb_chunks, flat_cb,
                         allow_words, s2d, use_allow, k, metric, rg,
                         active_g=G, interpret=False, rot=None,
                         codes_blk=None):
    """pq_gmin_topk as a top-level program with the slot->doc translation
    in the same program (ops/topk.translate_pack, the FUSED [B, 3k]
    layout): the one packed fetch carries final doc ids —
    gmin_scan.search_gmin_fused's codes-only twin."""
    from weaviate_tpu.ops.topk import translate_pack

    top, idx = pq_gmin_topk(codes, recon_norms, tombs, n, q, cb_chunks,
                            flat_cb, allow_words, use_allow, k, metric, rg,
                            active_g, interpret, rot, codes_blk)
    return translate_pack(top, idx, s2d)
