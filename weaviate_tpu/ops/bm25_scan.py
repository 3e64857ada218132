"""Device-side BM25 scoring over dense per-term impact rows.

Reference: adapters/repos/db/inverted/bm25_searcher.go:99 walks WAND
doc-at-a-time iterators on the CPU — pointer-chasing that cannot map to a
TPU. The host engine (inverted/bm25.py) keeps WAND's pruning math in
vectorized numpy; this module is the device half of the story: hybrid
search's keyword leg rides the same chip as its vector leg.

Design (TPU-first, not a WAND translation):

- At cache-build time each scoring unit (one property x term) is
  materialized as a DENSE f32 impact row over padded doc-id space: row[d]
  is the unit's complete BM25 contribution for doc d (idf, weight, tf
  saturation and length norm all folded in — they are per-generation
  constants), zero where the doc has no posting. The scatter that builds
  the row runs once per write generation, on device.
- At query time the T cached rows are summed ([T, n] -> [n], a pure
  HBM-bandwidth pass the VPU eats at memory speed — no gather, no sort,
  no branch), masked, and fed to one lax.top_k. Exhaustive-over-postings
  is the RIGHT call on device: the whole point of WAND's pruning is to
  skip random memory walks, and a dense row-sum has none to skip.
- Shapes are bucketed (doc capacity to _N_BUCKET, k to pow2) so steady
  state replays two cached executables regardless of corpus growth.

Scores are f32 on device (host engine is f64); rankings agree to f32
resolution — tests/test_bm25_device.py holds the two engines to rtol 1e-5
score agreement on matched ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.ops import topk as topk_ops

Array = jax.Array

# doc-capacity bucket: dense rows are padded to a multiple of this so the
# scatter/sum/top_k executables are reused while the corpus grows
_N_BUCKET = 16384


def n_bucket(max_doc_id: int) -> int:
    """Padded dense-row length for a corpus whose largest doc id is
    max_doc_id (-1 for empty)."""
    need = max(int(max_doc_id) + 1, 1)
    return ((need + _N_BUCKET - 1) // _N_BUCKET) * _N_BUCKET


def k_bucket(k: int) -> int:
    """Round k up to a power of two so limit/offset changes hit the same
    top_k executable."""
    b = 1
    while b < k:
        b <<= 1
    return b


def pad_postings(ids, scores, n_pad: int):
    """Pad (ids, scores) to the next power-of-two length with drop-slot
    sentinels so build_dense_row compiles once per LENGTH BUCKET, not once
    per distinct document frequency (a query sweep over a fresh corpus
    would otherwise trigger a compile per term)."""
    want = k_bucket(max(int(ids.size), 1))
    if want == ids.size:
        return ids, scores
    pad = want - ids.size
    ids = np.concatenate([ids, np.full(pad, n_pad, dtype=ids.dtype)])
    scores = np.concatenate([scores, np.zeros(pad, dtype=scores.dtype)])
    return ids, scores


@jax.jit
def build_dense_row(ids: Array, scores: Array, zeros: Array) -> Array:
    """Scatter one unit's fully-scaled posting scores into a dense row.

    ids [L] int32 (pad slots point at index n, one past the row), scores
    [L] f32 (pad slots 0.0), zeros [n+1] f32 -> dense [n] f32. Runs once
    per (unit, write generation); duplicate ids accumulate, matching the
    host engine's per-unit bincount fold.
    """
    return zeros.at[ids].add(scores, mode="drop")[:-1]


@jax.jit
def add_rows(acc: Array, row: Array) -> Array:
    """Pairwise row accumulation: summing T rows as T-1 dispatches of ONE
    cached [n]+[n] executable keeps compile count independent of how many
    terms a query has (a stacked [T, n] sum would compile per T)."""
    return acc + row


@functools.partial(jax.jit, static_argnames=("k",))
def dense_topk(total: Array, k: int, allow_mask: Array | None = None
               ) -> Array:
    """total [n] f32 summed scores (+ optional allow_mask [n] bool) ->
    packed [2k] int32: bitcast f32 scores in [:k], doc ids in [k:], both
    score-descending; empty slots surface as score 0 / id -1 (BM25 scores
    are strictly positive, so 0 is a safe floor). Packed like
    ops/topk.pack_topk: one blocking device->host fetch instead of two."""
    if allow_mask is not None:
        total = jnp.where(allow_mask, total, 0.0)
    scores, ids = jax.lax.top_k(total, k)
    ids = jnp.where(scores > 0.0, ids, -1).astype(jnp.int32)
    return topk_ops.pack_topk(scores[None, :], ids[None, :])[0]


def unpack_topk(packed, k: int):
    """Host-side twin of dense_topk's packing -> (scores f32 [k], ids
    int32 [k]). Same [*, 2k] convention as ops/topk.unpack_topk (one
    packing layout, one place to change it)."""
    scores, ids = topk_ops.unpack_topk(np.asarray(packed)[None, :])
    return scores[0], ids[0]


_QCHUNK = 32  # query rows per lax.map step: bounds the [Q, n] totals block


@functools.partial(jax.jit, static_argnames=("k",))
def batch_topk(rows: Array, sel: Array, k: int) -> Array:
    """Batched keyword scoring as ONE MXU matmul: rows [U, n] stacked
    dense impact rows, sel [Q, U] f32 query-term selection (1.0 where unit
    u scores query q) -> packed [Q, 2k] int32 (dense_topk packing per
    row).

    totals = sel @ rows gives every query's summed scores in one dispatch
    — Q x (adds + top_k + fetch) become one dispatch + one fetch, and Q
    vector adds become systolic-array work. Q is processed in _QCHUNK-row map steps so
    the transient totals block is [_QCHUNK, n], not [Q, n] (256 queries x
    1M docs would be a 1 GB materialization). Q must be a _QCHUNK
    multiple (caller pads; padded rows are all-zero -> all ids -1)."""
    q, u = sel.shape

    def chunk(s_blk):
        totals = jnp.dot(s_blk, rows, preferred_element_type=jnp.float32)
        scores, ids = jax.lax.top_k(totals, k)
        ids = jnp.where(scores > 0.0, ids, -1).astype(jnp.int32)
        return topk_ops.pack_topk(scores, ids)

    packed = jax.lax.map(chunk, sel.reshape(q // _QCHUNK, _QCHUNK, u))
    return packed.reshape(q, 2 * k)
