"""Masked top-k over distance blocks.

Replaces the reference's per-query binary heaps
(vector/hnsw/priorityqueue/, flat_search.go:19 max-heap) with a single
device-side lax.top_k over a [B, N] distance block, after masking out:
- unused capacity slots (store is padded),
- tombstoned docIDs (delete.go tombstone semantics),
- docIDs outside the filter allowList (search.go:283-291 applies the
  allowList in the hot loop; here it is a vectorized mask).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

# plain python float: must NOT materialize a device array at import time
# (importing the package would force backend init before config is settled)
INF = float("inf")


@functools.partial(jax.jit, static_argnames=("k",))
def masked_top_k(
    dists: Array,
    valid_mask: Array,
    k: int,
    allow_mask: Array | None = None,
) -> tuple[Array, Array]:
    """dists [B, N] + valid_mask [N] bool (+ optional allow_mask [N] or [B, N])
    -> (top_dists [B, k], top_idx [B, k] int32). Masked-out slots surface as
    +inf distance with index -1."""
    mask = valid_mask[None, :]
    if allow_mask is not None:
        allow = allow_mask if allow_mask.ndim == 2 else allow_mask[None, :]
        mask = jnp.logical_and(mask, allow)
    masked = jnp.where(mask, dists, INF)
    # lax.top_k returns the k largest; negate for smallest
    neg_top, idx = jax.lax.top_k(-masked, k)
    top = -neg_top
    idx = jnp.where(jnp.isinf(top), -1, idx)
    return top, idx.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def merge_top_k(dists_a: Array, idx_a: Array, dists_b: Array, idx_b: Array, k: int):
    """Merge two [B, k'] top-k candidate sets into one [B, k] (scatter-gather
    merge by distance, reference index.go:1040-1046, vectorized).

    One stable sort of the concatenated [B, 2k'] block with the distances as
    its key and the slots as its payload, then the first k columns of both:
    the slots move WITH their distances. Ties keep their order in the block
    (all of `a` stands left of `b`, lower column first), which is the order
    `lax.top_k(-d, k)` + `take_along_axis(i, pos)` gave; (+inf, -1) fill sorts
    last untouched. Not that form, because on a TPU its `take_along_axis` is
    a gather of B * k single elements, and the scan step pays it once a
    chunk: 82 us of a v5e at [256, 40], an eighth of a 768-d scan program
    (ledger, PR 40).

    The key is the distances' image in `_ordered_bits`, not the floats:
    `lax.top_k` orders by the total order (-0.0 ahead of +0.0), `lax.sort`
    of floats calls the two zeros equal, and this answers bit for bit what
    top_k answered."""
    key = _ordered_bits(jnp.concatenate([dists_a, dists_b], axis=1))
    i = jnp.concatenate([idx_a, idx_b], axis=1)
    key, i = jax.lax.sort((key, i), dimension=1, is_stable=True, num_keys=1)
    top = jax.lax.bitcast_convert_type(_ordered_bits(key[:, :k]), jnp.float32)
    return top, i[:, :k]


def _ordered_bits(x: Array) -> Array:
    """float32 -> the int32 whose signed order is the floats' total order
    (-nan < -inf < ... < -0.0 < +0.0 < ... < +inf < +nan), and back: flipping
    the magnitude bits of the negatives is its own inverse."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def pack_topk(top: Array, idx: Array) -> Array:
    """Pack (dists f32, idx i32) [B,k] each into one [B, 2k] i32 array so the
    host needs a single device->host fetch (the PCIe round trip costs far
    more than the bytes)."""
    return jnp.concatenate([jax.lax.bitcast_convert_type(top, jnp.int32), idx], axis=1)


def unpack_topk(packed) -> tuple:
    """Host-side inverse of pack_topk: np [B, 2k] i32 -> (dists f32, idx i32)."""
    k = packed.shape[1] // 2
    return packed[:, :k].view("<f4"), packed[:, k:]


# sentinel word for a missing result slot: both words 0xFFFFFFFF make the
# reassembled uint64 doc id 2**64-1 (np.int64(-1) viewed as uint64), the
# "missing" id the API has always carried for idx -1
_MISS_WORD = 0xFFFFFFFF


def translate_pack(top: Array, idx: Array, s2d: Array) -> Array:
    """Fuse the slot->doc translation into the SAME device program as the
    final top-k: gather each winner's doc id from the device-resident
    translation table and pack everything into one fetchable buffer.

    top [B, k] f32 distances, idx [B, k] i32 slot indices (-1 = missing),
    s2d [capacity, 2] uint32 — the (lo, hi) 32-bit words of each slot's
    int64 doc id (two words because doc ids are 64-bit and jax may run
    with x64 disabled) -> the FUSED packed layout

        [B, 3k] int32 = [ dists (f32 bitcast) | id_lo | id_hi ]

    so `finalize()` on the host is dtype views plus two vectorized word
    copies (ops/topk.unpack_fused) — zero per-row Python work and zero
    host-side slot->doc table reads (the JGL015 contract)."""
    safe = jnp.clip(idx, 0, s2d.shape[0] - 1)
    pair = jnp.take(s2d, safe, axis=0)  # [B, k, 2] u32
    return _pack_fused(top, idx < 0, pair[..., 0], pair[..., 1])


def translate_pack_split(top: Array, idx: Array, s2d_lo: Array,
                         s2d_hi: Array) -> Array:
    """translate_pack over the table's two columns as 1-D arrays
    ([capacity] each): the per-slot gather program reads them, because
    there XLA re-lays the [capacity, 2] table out before every lookup."""
    safe = jnp.clip(idx, 0, s2d_lo.shape[0] - 1)
    return _pack_fused(top, idx < 0, jnp.take(s2d_lo, safe),
                       jnp.take(s2d_hi, safe))


def _pack_fused(top: Array, miss: Array, lo: Array, hi: Array) -> Array:
    sent = jnp.uint32(_MISS_WORD)
    return jnp.concatenate([
        jax.lax.bitcast_convert_type(top, jnp.int32),
        jax.lax.bitcast_convert_type(jnp.where(miss, sent, lo), jnp.int32),
        jax.lax.bitcast_convert_type(jnp.where(miss, sent, hi), jnp.int32),
    ], axis=1)


def retranslate_packed(packed: Array, s2d: Array) -> Array:
    """pack_topk layout -> FUSED layout, traced in the same program: lets
    an existing packed kernel gain device-side translation by wrapping its
    output (XLA folds the bitcast/concat/slice churn away)."""
    kc = packed.shape[1] // 2
    top = jax.lax.bitcast_convert_type(packed[:, :kc], jnp.float32)
    return translate_pack(top, packed[:, kc:], s2d)


def unpack_fused(packed) -> tuple:
    """Host-side inverse of translate_pack: np [B, 3k] i32 ->
    (ids u64 [B, k], dists f32 [B, k]). Dists are a dtype VIEW into the
    fetched buffer; ids reassemble with two vectorized word copies into a
    fresh little-endian u64 array — nothing here is per-row, which is what
    makes the fused finalize "a reshape, not a translation loop"."""
    k = packed.shape[1] // 3
    dists = packed[:, :k].view("<f4")
    ids = np.empty((packed.shape[0], k), "<u8")
    w = ids.view("<u4").reshape(packed.shape[0], k, 2)
    w[..., 0] = packed[:, k: 2 * k].view("<u4")
    w[..., 1] = packed[:, 2 * k:].view("<u4")
    return ids, dists


def translate_pack_slots(top: Array, idx: Array, s2d: Array) -> Array:
    """translate_pack with the slots kept beside the doc ids:

        [B, 4k] int32 = [ dists (f32 bitcast) | id_lo | id_hi | slots ]

    What a compressed index's program returns: its k columns are
    CANDIDATES, which the host scores again from the float32 rows it
    keeps (read by slot) and answers with their doc ids."""
    return jnp.concatenate(
        [translate_pack(top, idx, s2d), idx.astype(jnp.int32)], axis=1)


def unpack_fused_slots(packed) -> tuple:
    """Host-side inverse of translate_pack_slots: np [B, 4k] i32 ->
    (ids u64 [B, k], dists f32 [B, k], slots i32 [B, k], -1 = missing)."""
    k = packed.shape[1] // 4
    ids, dists = unpack_fused(packed[:, : 3 * k])
    return ids, dists, packed[:, 3 * k:]


def rescore_distances(cand: Array, q: Array, metric: str) -> Array:
    """Exact f32 distances of gathered candidates: cand [B, R, D] vs
    q [B, D] -> [B, R]. The shared rescore core of the fast-scan kernels
    (index/tpu.py _search_full and ops/gmin_scan.py)."""
    from weaviate_tpu.entities import vectorindex as vi

    qf = q.astype(jnp.float32)[:, None, :]
    c = cand.astype(jnp.float32)
    if metric == vi.DISTANCE_L2:
        return jnp.sum((c - qf) ** 2, axis=-1)
    if metric == vi.DISTANCE_DOT:
        return -jnp.sum(c * qf, axis=-1)
    return 1.0 - jnp.sum(c * qf, axis=-1)  # cosine: rows pre-normalized


def bitmap_to_mask(bitmap_words: Array, n: int) -> Array:
    """Expand a packed uint32 bitmap [ceil(N/32)] into a bool mask [N].

    This is the device twin of helpers.AllowList (sroar bitmap,
    helpers/allow_list.go:19-29): the host serializes the filter result as a
    dense bitset over docID slots; the device unpacks it with vector ops.
    """
    w = bitmap_words.astype(jnp.uint32)
    bits = jnp.arange(32, dtype=jnp.uint32)
    expanded = (w[:, None] >> bits[None, :]) & jnp.uint32(1)
    return expanded.reshape(-1)[:n].astype(jnp.bool_)
