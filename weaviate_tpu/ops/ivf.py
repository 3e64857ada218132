"""Partition-pruned (IVF) scan plane: clustered layout + probed search.

ROADMAP item 3 (the KScaNN/KBest recipe, PAPERS.md): a flat scan is O(N)
per dispatch no matter how few host hops the program makes — at
production corpus sizes the headroom burns on rows the query never
needed. This module holds the IVF plane's two halves:

HOST (write path, under the index write lock):
  - ``kmeans_fit``: Lloyd's k-means over a bounded training sample ->
    [nlist, D] f32 centroids (cosine metrics get row-normalized
    centroids so the probe ranks by angle);
  - ``assign_partitions``: nearest-centroid assignment of every row,
    chunked so the [chunk, nlist] distance block stays bounded;
  - ``pca_fit``: top-``dp`` eigenvectors of the sample covariance — the
    pHNSW-style low-dimensional prefilter projection;
  - ``build_buckets``: partition assignments -> PADDED partition buckets
    [nlist, cap_p] int32 (cap_p snapped to the shared pow2 row buckets,
    padding = -1), so jit shapes stay CACHED across inserts until a
    bucket overflows its padding.

DEVICE (read path, one program per dispatch — traced together with the
shared epilogue, so IVF leaves the device the way every tier does):
  - ``probe``: one [B, nlist] centroid distance block + exact top_p
    selection -> the probed partitions per query;
  - ``ivf_dense_topk`` / ``ivf_codes_topk``: gather the probed
    buckets' slots, mask validity exactly like the flat kernels
    (capacity padding, tombstones via the snapshot's own device mask,
    allowList via the SAME packed words the flat kernels consume), an
    optional PCA low-dim prefilter pass, then full-fidelity scoring of
    the survivors through the shared rescore core
    (ops/topk.rescore_distances) and the shared top-k/slot->doc
    epilogue (merge_top_k / translate_pack): the jitted
    ``search_ivf_*_fused`` programs emit the packed layout with final doc
    ids, exactly like every other tier's.

Candidate memory is bounded: probed buckets are scored in groups of
``gp`` probes per lax.scan step (the caller sizes gp so one step's
[B, gp*cap_p, D] gather stays VMEM/host-cache friendly), with the
running top-k merged exactly across steps — the same
collect-then-merge discipline as the flat chunked scans.

Every kernel here is shape-static in (top_p, cap_p, pre_c, gp, k): the
probe count comes from the bounded IVF_TOP_P_BUCKETS ladder (config —
the controller's second recall-guarded budget steps down the same
ladder), cap_p from the pow2 bucket padding, so the jit cache stays as
bounded as the flat path's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.ops.topk import (merge_top_k, rescore_distances,
                                   translate_pack)

Array = jax.Array

INF = float("inf")

# metrics the IVF plane serves: the probe and the candidate rescore are
# both built on the matmul/elementwise distance forms — manhattan and
# hamming keep the flat streamed scan (they are also the metrics the PQ
# plane already excludes)
MATMUL_METRICS = (vi.DISTANCE_L2, vi.DISTANCE_DOT, vi.DISTANCE_COSINE)

# rows per assignment chunk: bounds the [chunk, nlist] host distance block
_ASSIGN_CHUNK = 65536


# -- host half: training / assignment / layout --------------------------------


def _kpp_init(rows: np.ndarray, nlist: int, rng) -> np.ndarray:
    """k-means++ seeding (D^2 sampling): spreads the initial centroids
    over the data's density, which keeps partition fills far more even
    than uniform seeding — and even fills are what bound the padded
    bucket width the probe pays for."""
    n = rows.shape[0]
    cent = np.empty((nlist, rows.shape[1]), np.float32)
    cent[0] = rows[int(rng.integers(n))]
    d2 = ((rows - cent[0]) ** 2).sum(1)
    for i in range(1, nlist):
        total = float(d2.sum())
        if total <= 0:
            cent[i:] = rows[rng.choice(n, size=nlist - i)]
            break
        cent[i] = rows[int(rng.choice(n, p=d2 / total))]
        d2 = np.minimum(d2, ((rows - cent[i]) ** 2).sum(1))
    return cent


def kmeans_fit(rows: np.ndarray, nlist: int, iters: int = 6,
               seed: int = 0, sample: int = 0) -> np.ndarray:
    """Lloyd's k-means on (a sample of) ``rows`` -> [nlist, D] f32
    centroids, k-means++ seeded. Deterministic for a given seed; empty
    clusters are re-seeded from the rows farthest from their centroid so
    a skewed init cannot strand partitions at zero fill. Cosine callers
    should pass normalized rows (the index stores them normalized) — the
    centroids are re-normalized by the caller for the angular probe."""
    rows = np.asarray(rows, np.float32)
    n = rows.shape[0]
    nlist = max(1, min(int(nlist), n))
    rng = np.random.default_rng(seed)
    if sample and n > sample:
        rows = rows[rng.choice(n, size=sample, replace=False)]
        n = rows.shape[0]
    if nlist <= 1024:
        cent = _kpp_init(rows, nlist, rng)
    else:
        # k-means++ is one vectorized pass PER centroid — past ~1024
        # centroids that is minutes of write-lock stall for a seeding
        # refinement Lloyd largely recovers anyway; big layouts seed
        # from distinct random rows (one vectorized draw)
        cent = rows[rng.choice(n, size=nlist, replace=False)].copy()
    for _ in range(max(1, int(iters))):
        assign = assign_partitions(rows, cent)
        counts = np.bincount(assign, minlength=nlist)
        sums = np.zeros_like(cent, dtype=np.float64)  # graftlint: disable=JGL006 host-side numpy accumulation at fit time: f64 partial sums avoid centroid drift over big clusters and never touch the device (the pq.py fit discipline)
        np.add.at(sums, assign, rows)
        nonzero = counts > 0
        cent[nonzero] = (sums[nonzero]
                         / counts[nonzero, None]).astype(np.float32)
        empty = np.flatnonzero(~nonzero)
        if empty.size:
            # re-seed each empty cluster from the globally worst-fit rows
            d = rows - cent[assign]
            far = np.argsort(-np.einsum("ij,ij->i", d, d))[: empty.size]
            cent[empty] = rows[far]
    return cent


def assign_partitions(rows: np.ndarray, centroids: np.ndarray,
                      chunk: int = 0) -> np.ndarray:
    """Nearest-centroid (L2) partition of every row -> int32 [n]. L2
    assignment is the standard IVF layout for every matmul metric
    (cosine rows are insert-normalized, so L2 argmin == angular argmax;
    dot follows the FAISS convention of an L2-built coarse layout).
    chunk=0 sizes the [chunk, nlist] distance block to ~64 MB — scaled
    DOWN with nlist, so a 4096-partition recluster never holds a
    multi-GB transient under the index write lock."""
    rows = np.asarray(rows, np.float32)
    if chunk <= 0:
        chunk = min(_ASSIGN_CHUNK,
                    max(1024, (1 << 24) // max(centroids.shape[0], 1)))
    cn = np.einsum("ij,ij->i", centroids, centroids, dtype=np.float64  # graftlint: disable=JGL006 host-side numpy norms at assignment time: f64 accumulation without a full f64 temp, cast before any device use (the index/tpu.py einsum idiom)
                   ).astype(np.float32)
    out = np.empty(rows.shape[0], np.int32)
    for s in range(0, rows.shape[0], chunk):
        blk = rows[s: s + chunk]
        d = cn[None, :] - 2.0 * (blk @ centroids.T)
        out[s: s + blk.shape[0]] = np.argmin(d, axis=1)
    return out


def balanced_assign(rows: np.ndarray, centroids: np.ndarray,
                    cap: int) -> np.ndarray:
    """Capacity-bounded partition assignment (the KScaNN balanced-bucket
    recipe): nearest-centroid first, then every partition over ``cap``
    keeps its ``cap`` CLOSEST rows and spills the rest to the nearest
    centroid with space (walked in that row's own distance order). The
    padded bucket width is then pinned by ``cap`` instead of by the
    worst cluster's fill — on skewed data that is the difference between
    probing 2x the corpus and probing a tenth of it. Requires
    nlist * cap > n (callers size cap from the mean fill with slack)."""
    rows = np.asarray(rows, np.float32)
    assign = assign_partitions(rows, centroids)
    nlist = centroids.shape[0]
    if nlist * cap <= rows.shape[0]:
        return assign  # cannot balance into this cap: serve unbalanced
    fills = np.bincount(assign, minlength=nlist)
    over = np.flatnonzero(fills > cap)
    if not over.size:
        return assign
    spilled = []
    for p in over:
        members = np.flatnonzero(assign == p)
        d = ((rows[members] - centroids[p]) ** 2).sum(1)
        spill = members[np.argsort(d, kind="stable")[cap:]]
        spilled.append(spill)
        assign[spill] = -1
        fills[p] = cap
    spilled = np.concatenate(spilled)
    cn = np.einsum("ij,ij->i", centroids, centroids).astype(np.float32)
    # chunked [S, nlist] distance blocks; each spilled row walks its own
    # centroid preference order into the first partition with space. The
    # walk is bounded at 32 preferences (near-full layouts could
    # otherwise cost O(spilled x nlist) interpreter time under the index
    # write lock); the rare row whose 32 nearest partitions are all full
    # falls back to the globally emptiest one — placement quality for
    # that row is already marginal, liveness is not
    walk = min(32, nlist)
    for s in range(0, spilled.size, _ASSIGN_CHUNK // 8):
        blk = spilled[s: s + _ASSIGN_CHUNK // 8]
        d = cn[None, :] - 2.0 * (rows[blk] @ centroids.T)
        order = np.argpartition(d, walk - 1, axis=1)[:, :walk]
        order = np.take_along_axis(
            order, np.argsort(np.take_along_axis(d, order, axis=1),
                              axis=1, kind="stable"), axis=1)
        for i, r in enumerate(blk):
            for p in order[i]:
                if fills[p] < cap:
                    assign[r] = p
                    fills[p] += 1
                    break
            else:
                p = int(np.argmin(fills))
                assign[r] = p
                fills[p] += 1
    return assign


def pca_fit(rows: np.ndarray, dp: int) -> np.ndarray:
    """Top-``dp`` principal directions of (a sample of) ``rows`` ->
    [D, dp] f32 projection — the low-dim prefilter basis. Eigh on the
    [D, D] covariance: D is vector dims, never corpus-sized."""
    rows = np.asarray(rows, np.float32)
    mean = rows.mean(axis=0)
    x = rows - mean
    cov = (x.T @ x) / max(x.shape[0] - 1, 1)
    _, vecs = np.linalg.eigh(cov.astype(np.float64))  # graftlint: disable=JGL006 host-side eigendecomposition at fit time: f64 keeps the small [D, D] eigh numerically clean; the projection is cast to f32 before upload
    dp = max(1, min(int(dp), rows.shape[1]))
    return np.ascontiguousarray(vecs[:, ::-1][:, :dp]).astype(np.float32)


def bucket_capacity(fills: np.ndarray) -> int:
    """Padded bucket width for the given per-partition fills: snapped UP
    to a 128-row multiple (the lane-alignment granule), min 128 — coarse
    enough that the [nlist, cap_p] jit shape survives inserts and the
    distinct compiled widths stay bounded, fine enough that padding
    waste stays ~tens of percent instead of the up-to-2x a pow2 snap
    costs (every probe reads cap_p rows, padding included)."""
    top = int(fills.max()) if fills.size else 0
    return max(128, -(-top // 128) * 128)


def build_buckets(assign: np.ndarray, nlist: int,
                  cap_p: Optional[int] = None
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Partition assignment [n] int32 (-1 = unassigned/dead) -> (padded
    buckets [nlist, cap_p] int32 with -1 padding, fills [nlist] int64).
    One vectorized bucket sort — no per-row Python. ``cap_p`` pins the
    padding width (callers keep the previous width while it still fits,
    the jit-stability contract); None re-derives it from the fills."""
    assign = np.asarray(assign, np.int32)
    valid = assign >= 0
    slots = np.flatnonzero(valid).astype(np.int32)
    parts = assign[slots]
    fills = np.bincount(parts, minlength=nlist).astype(np.int64)
    if cap_p is None or (fills.size and int(fills.max()) > cap_p):
        cap_p = bucket_capacity(fills)
    order = np.argsort(parts, kind="stable")
    slots = slots[order]
    parts = parts[order]
    buckets = np.full((nlist, cap_p), -1, np.int32)
    starts = np.zeros(nlist + 1, np.int64)
    np.cumsum(fills, out=starts[1:])
    col = np.arange(slots.size, dtype=np.int64) - starts[parts]
    buckets[parts, col] = slots
    return buckets, fills


# -- the tiled layout: partition-major slots -----------------------------------
#
# An uncompressed one-chip index keeps its ONE copy of the rows in partition
# order: partition p owns the slots [p * cap_p, (p + 1) * cap_p) of the store
# (its tile), filled from the front, the rest of the tile empty (tombstoned,
# zero rows). The table from a partition to its tile is that product, so no
# bucket array rides the snapshot, a probe reads whole [cap_p, D] tiles that
# lie contiguous in HBM, and the flat program scans the same store with the
# same masks. Training runs on the device over the store in place
# (`kmeans_fit_device`, `nearest_partitions_device`); the host only balances
# (`balance_partitions`) and hands out slots (`place_in_tiles`).

# nearest partitions kept a row: a row that finds its nearest partition full
# walks this many preferences before it takes the emptiest partition
PREFS = 8

# rows a block of the device's assignment pass scores against every centroid
_DEVICE_BLOCK = 8192


def _l2_to_centroids(rows, centroids, cn):
    """[R, D] x [L, D] -> [R, L] squared L2 up to the row's own norm (a
    constant a row: the ranking is the L2 ranking)."""
    return cn[None, :] - 2.0 * jnp.matmul(
        rows, centroids.T, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


@jax.jit
def gather_rows(store, slots):
    """`slots` [S] rows of the store as float32 (a training's sample: S x D,
    never the slab)."""
    return jnp.take(store, slots, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("iters", "normalize"))
def kmeans_fit_device(sample, seeds, iters, normalize):
    """Lloyd's k-means on the device over `sample` [S, D] (rows gathered
    from the store: `gather_rows`), seeded from its rows `seeds` [L]
    (distinct) -> [L, D] f32 centroids. Its shapes are the sample's and the
    partition count's, so an import compiles it a few times, not once a
    capacity. An empty cluster is re-seeded from the sample rows farthest
    from their centroid (`kmeans_fit`'s rule). `normalize`: unit centroids
    for the angular probe (cosine)."""
    cent = jnp.take(sample, seeds, axis=0)
    nlist = cent.shape[0]
    s = sample.shape[0]
    blk = min(s, _DEVICE_BLOCK)
    nblk = s // blk  # the caller rounds S down to whole blocks

    def assign(cent):
        cn = jnp.sum(cent * cent, axis=-1)

        def one(rows):
            d = _l2_to_centroids(rows, cent, cn)
            a = jnp.argmin(d, axis=1).astype(jnp.int32)
            return a, jnp.min(d, axis=1) + jnp.sum(rows * rows, axis=-1)

        a, dmin = jax.lax.map(one, sample[: nblk * blk].reshape(nblk, blk, -1))
        return a.reshape(-1), dmin.reshape(-1)

    def step(cent, _):
        a, dmin = assign(cent)
        used = sample[: nblk * blk]
        sums = jax.ops.segment_sum(used, a, num_segments=nlist)
        counts = jax.ops.segment_sum(jnp.ones_like(dmin), a,
                                     num_segments=nlist)
        mean = sums / jnp.maximum(counts, 1.0)[:, None]
        empty = counts == 0
        # the e-th empty cluster takes the e-th farthest sample row
        _, far = jax.lax.top_k(dmin, min(nlist, dmin.shape[0]))
        rank = jnp.clip(jnp.cumsum(empty) - 1, 0, far.shape[0] - 1)
        reseed = jnp.take(used, jnp.take(far, rank), axis=0)
        return jnp.where(empty[:, None], reseed, mean), None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    if normalize:
        nrm = jnp.sqrt(jnp.sum(cent * cent, axis=-1, keepdims=True))
        cent = cent / jnp.where(nrm == 0, 1.0, nrm)
    return cent


@functools.partial(jax.jit, static_argnames=("prefs",))
def nearest_partitions_device(store, centroids, prefs):
    """The `prefs` nearest partitions (L2, the layout's metric for every
    matmul metric: `assign_partitions`) of EVERY slot of the store, scored
    in blocks over the store in place -> ([capacity, prefs] i32, nearest
    first; [capacity] f32, the distance to the nearest up to the row's own
    norm). Slots that hold no row are scored too and ignored by the host."""
    cap, dim = store.shape
    blk = min(cap, _DEVICE_BLOCK)
    cent = centroids.astype(jnp.float32)
    cn = jnp.sum(cent * cent, axis=-1)

    def one(i):
        rows = jax.lax.dynamic_slice(
            store, (i * blk, 0), (blk, dim)).astype(jnp.float32)
        neg, idx = jax.lax.top_k(-_l2_to_centroids(rows, cent, cn), prefs)
        return idx.astype(jnp.int32), -neg[:, 0]

    idx, d0 = jax.lax.map(one, jnp.arange(cap // blk, dtype=jnp.int32))
    return idx.reshape(cap, prefs), d0.reshape(cap)


@functools.partial(jax.jit, static_argnames=("prefs",))
def nearest_partitions_of_rows(rows, centroids, prefs):
    """`nearest_partitions_device` for rows that are not in the store yet
    (a bulk write's, padded to a bucketed count): [R, D] -> ([R, prefs]
    i32, [R] f32)."""
    rows = rows.astype(jnp.float32)
    cent = centroids.astype(jnp.float32)
    neg, idx = jax.lax.top_k(
        -_l2_to_centroids(rows, cent, jnp.sum(cent * cent, axis=-1)), prefs)
    return idx.astype(jnp.int32), -neg[:, 0]


def nearest_partitions(rows: np.ndarray, centroids: np.ndarray,
                       prefs: int = PREFS) -> tuple[np.ndarray, np.ndarray]:
    """`nearest_partitions_device` on the host, for the rows a write holds:
    ([n, prefs] i32 nearest first, [n] f32 distance to the nearest up to the
    row's norm), chunked like `assign_partitions`."""
    rows = np.asarray(rows, np.float32)
    nlist = centroids.shape[0]
    prefs = min(prefs, nlist)
    chunk = min(_ASSIGN_CHUNK, max(1024, (1 << 24) // max(nlist, 1)))
    cn = np.einsum("ij,ij->i", centroids, centroids).astype(np.float32)
    out = np.empty((rows.shape[0], prefs), np.int32)
    d0 = np.empty(rows.shape[0], np.float32)
    for s in range(0, rows.shape[0], chunk):
        d = cn[None, :] - 2.0 * (rows[s: s + chunk] @ centroids.T)
        if prefs < nlist:
            top = np.argpartition(d, prefs - 1, axis=1)[:, :prefs]
        else:
            top = np.broadcast_to(np.arange(nlist), d.shape).copy()
        dt = np.take_along_axis(d, top, axis=1)
        order = np.argsort(dt, axis=1, kind="stable")
        out[s: s + d.shape[0]] = np.take_along_axis(top, order, axis=1)
        d0[s: s + d.shape[0]] = np.take_along_axis(dt, order[:, :1],
                                                   axis=1)[:, 0]
    return out, d0


def balance_partitions(prefs: np.ndarray, d0: np.ndarray,
                       room: np.ndarray) -> np.ndarray:
    """Capacity-bounded assignment (`balanced_assign`'s rule, vectorized):
    every row asks for its nearest partition; a partition with less `room`
    [nlist] than askers keeps the closest of them and the rest ask their
    next preference, a round a preference; a row none of whose preferences
    has room takes the emptiest partition (placement quality for that row is
    already marginal, liveness is not). -> [n] i32 partitions; raises
    ValueError where the rows outnumber the room. `room` is not modified."""
    n, w = prefs.shape
    nlist = room.shape[0]
    room = room.astype(np.int64).copy()
    if n > int(room.sum()):
        raise ValueError(f"{n} rows for {int(room.sum())} free slots")
    part = np.full(n, -1, np.int32)
    todo = np.arange(n)
    for r in range(w):
        if not todo.size:
            break
        want = prefs[todo, r]
        order = np.lexsort((d0[todo], want))   # by partition, closest first
        t, wn = todo[order], want[order]
        first = np.searchsorted(wn, np.arange(nlist))
        ok = np.arange(t.size) - first[wn] < room[wn]
        part[t[ok]] = wn[ok]
        room -= np.bincount(wn[ok], minlength=nlist)
        todo = t[~ok]
    if todo.size:
        emptiest = np.argsort(-room, kind="stable")
        part[todo] = np.repeat(emptiest, room[emptiest])[: todo.size]
    return part


# A tile is sized for the rows a partition holds on average, with room for
# the clusters' own spread (rows past it spill to the next-nearest partition:
# `balance_partitions`) and for the growth before the tiles are made anew
# (`TpuVectorIndex._maybe_ivf_train` regrows a layout whose live rows passed
# the rows it was sized for by TILE_HEADROOM; a regrow is seconds on the
# device). Together with the ladder's rounding, at most an eighth, the
# slots stay under 1.125 x 1.25 x 1.125 = 1.58 times the live rows: the
# padding is what the layout costs in HBM and what the flat program, which
# serves the same store's wide batches, scans for nothing.
TILE_SLACK = 1.125
TILE_HEADROOM = 1.25


def tile_capacity(rows: int, nlist: int) -> int:
    """Slots of a partition's tile for a layout sized for `rows` rows, on
    a ladder of eight rungs an octave in multiples of 32 (a filter's words
    tile with it), at least 128: the jit-shape ladder of the probed
    program."""
    want = max(128.0, TILE_SLACK * TILE_HEADROOM * rows / max(nlist, 1))
    step = max(32, (1 << (int(want).bit_length() - 1)) // 8)
    return int(-(-want // step) * step)


def tile_slots(part: np.ndarray, d0: np.ndarray, cap_p: int) -> np.ndarray:
    """Partitions [n] -> the slot of each row in the tiled layout: tile
    `part * cap_p`, rows of a partition in order of their distance to its
    centroid from the tile's front."""
    order = np.lexsort((d0, part))
    sorted_part = part[order]
    first = np.searchsorted(sorted_part, np.arange(int(part.max()) + 1
                                                   if part.size else 0))
    rank = np.arange(part.size) - first[sorted_part]
    slots = np.empty(part.size, np.int64)
    slots[order] = sorted_part.astype(np.int64) * cap_p + rank
    return slots


def place_in_tiles(part: np.ndarray, free: np.ndarray,
                   cap_p: int) -> np.ndarray:
    """Free slots for rows assigned to partitions `part` [n]: the first
    free slots of each partition's tile, in the rows' order. `free` [slots]
    bool says which slots hold no row (the index's host tombstone mirror);
    the caller made sure every partition has the room."""
    slots = np.empty(part.size, np.int64)
    order = np.argsort(part, kind="stable")
    sp = part[order]
    bounds = np.flatnonzero(np.diff(sp, prepend=-1, append=-1))
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        p = int(sp[lo])
        base = p * cap_p
        got = np.flatnonzero(free[base: base + cap_p])[: hi - lo]
        slots[order[lo:hi]] = got + base
    return slots


# -- device half: probe + candidate scoring ------------------------------------


def _probe(q: Array, centroids: Array, top_p: int,
           metric: str) -> Array:
    """[B, D] queries x [L, D] centroids -> the top_p probed partition
    ids per query [B, top_p] (exact selection — L is nlist-sized, the
    whole point is that this scan is cheap). Centroid norms are computed
    in-program: L·D flops per dispatch beats carrying another slab."""
    qf = q.astype(jnp.float32)
    qx = jnp.matmul(qf, centroids.T, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
    if metric == vi.DISTANCE_L2:
        q_sq = jnp.sum(qf ** 2, axis=-1, keepdims=True)
        cnorms = jnp.sum(centroids.astype(jnp.float32) ** 2, axis=-1)
        d = jnp.maximum(q_sq - 2.0 * qx + cnorms[None, :], 0.0)
    elif metric == vi.DISTANCE_DOT:
        d = -qx
    else:  # cosine: centroids are train-time normalized
        d = 1.0 - qx
    _, parts = jax.lax.top_k(-d, top_p)
    return parts.astype(jnp.int32)


def _candidate_slots(parts: Array, buckets: Array, gp: int) -> Array:
    """Probed partitions [B, top_p] -> grouped candidate slots
    [steps, B, gp*cap_p] (int32, -1 = padding), where each lax.scan step
    covers ``gp`` probes. top_p pads up to a gp multiple with an
    out-of-range partition id that gathers -1 rows (mode=fill)."""
    b, top_p = parts.shape
    steps = -(-top_p // gp)
    pad = steps * gp - top_p
    if pad:
        parts = jnp.concatenate(
            [parts, jnp.full((b, pad), buckets.shape[0], jnp.int32)], axis=1)
    sl = jnp.take(buckets, parts, axis=0, mode="fill",
                  fill_value=-1)                       # [B, steps*gp, cap_p]
    cap_p = buckets.shape[1]
    return jnp.moveaxis(sl.reshape(b, steps, gp * cap_p), 1, 0)


def _slot_valid(slots: Array, n, tombs: Array, allow_words: Optional[Array]
                ) -> Array:
    """The flat kernels' masking semantics, per candidate slot: capacity
    padding (slots >= n), the dispatching snapshot's OWN device
    tombstones (the _gather_live discipline), and the packed allowList
    words the filtered scan kernels already consume."""
    safe = jnp.clip(slots, 0, tombs.shape[0] - 1)
    ok = jnp.logical_and(slots >= 0, slots < n)
    ok = jnp.logical_and(ok, jnp.logical_not(jnp.take(tombs, safe)))
    if allow_words is not None:
        w = jnp.take(allow_words, (safe >> 5).astype(jnp.int32))
        bit = (w >> (safe & 31).astype(jnp.uint32)) & jnp.uint32(1)
        ok = jnp.logical_and(ok, bit.astype(jnp.bool_))
    return ok


def _select(d: Array, slots: Array, kk: int, exact: bool):
    """Per-group smallest-kk selection (the flat scans' exact/approx
    split), returning (dists, slot ids) with -1 for masked winners."""
    td, pos = _select_pos(d, kk, exact)
    ts = jnp.take_along_axis(slots, pos, axis=1)
    return td, jnp.where(jnp.isinf(td), -1, ts)


def _select_pos(d: Array, kk: int, exact: bool):
    """The smallest kk of each row of d -> (dists, positions)."""
    kk = min(kk, d.shape[1])
    if exact or kk >= d.shape[1]:
        neg, pos = jax.lax.top_k(-d, kk)
        return -neg, pos
    return jax.lax.approx_min_k(d, kk, recall_target=0.95)


def _grouped_topk(slots_g: Array, valid_g: Array, score_fn, keep: int,
                  exact: bool, slack: bool = True):
    """Scan the [steps, B, g] candidate groups, scoring each through
    ``score_fn(slots [B, g]) -> [B, g] f32`` and exactly merging the
    running best across steps — the flat scans' collect-then-merge,
    over probed buckets instead of HBM chunks.

    Selection discipline mirrors the flat fast scan: each group's
    approx_min_k keeps 4x``keep`` SLACK candidates (selection errors of
    the approximate pass sit well within 4k — index/tpu.py rescore_depth's
    rationale), the cross-step merge is an exact top-k over the widened
    set, and the final [:, :keep] slice of the sorted merge is the exact
    best of everything any group surfaced. The PCA prefilter stage
    passes slack=False: its `keep` is already a wide cut over the final
    k, and quadrupling it again only inflates the per-step merge sort."""
    steps, b, g = slots_g.shape
    w = min(max(4 * keep, 32), max(steps * g, keep)) if slack else keep
    w = max(w, keep)
    kk = min(w, g)
    init = (jnp.full((b, w), INF, jnp.float32),
            jnp.full((b, w), -1, jnp.int32))

    def step(carry, xs):
        sl, va = xs
        d = jnp.where(va, score_fn(sl), INF)
        td, ts = _select(d, sl, kk, exact)
        return merge_top_k(carry[0], carry[1], td, ts, w), None

    (top, out), _ = jax.lax.scan(step, init, (slots_g, valid_g))
    # merge_top_k sorts by distance: the first `keep` columns are the
    # exact top-keep of the union
    return top[:, :keep], out[:, :keep]


def _regroup(slots: Array, valid: Array, steps: int):
    """[B, C] survivors -> [steps, B, C/steps] groups for the second
    scoring stage (C is a pow2 by construction, steps divides it)."""
    b, c = slots.shape
    g = c // steps
    return (jnp.moveaxis(slots.reshape(b, steps, g), 1, 0),
            jnp.moveaxis(valid.reshape(b, steps, g), 1, 0))


def group_steps(b: int, cap_p: int, dim: int, top_p: int,
                budget_elems: int = 1 << 21) -> int:
    """Probes per scan step so one step's [B, gp*cap_p, D] gather stays
    under ``budget_elems`` elements (~8 MB f32 at the default)."""
    per_probe = max(b * cap_p * dim, 1)
    return max(1, min(top_p, budget_elems // per_probe))


def ivf_dense_topk(store, tombs, n, q, allow_words, centroids,
                   buckets, pca_proj, pca_rows, k, metric, use_allow,
                   top_p, pre_c, exact, gp, steps2):
    """IVF search over a dense row store (the exact tier's f32/bf16
    store, or the PQ-rescore tier's bf16 copy): probe -> gather the
    probed buckets -> optional PCA prefilter -> full-dim scoring of the
    survivors through the shared rescore core -> ([B, k] dists, [B, k]
    slots, -1 missing).

    pre_c > 0 enables the low-dim prefilter: candidates are first ranked
    in the pca_proj subspace (dp dims instead of D) and only the best
    pre_c per query reach the full-dim pass — the pHNSW recipe. pre_c=0
    scores every probed candidate at full dim (and is the setting the
    ``top_p=all`` bit-identity contract pins)."""
    qf = q.astype(jnp.float32)
    parts = _probe(qf, centroids, top_p, metric)
    slots_g = _candidate_slots(parts, buckets, gp)
    valid_g = _slot_valid(slots_g, n, tombs,
                          allow_words if use_allow else None)
    cap = store.shape[0]

    def score_full(sl):
        rows = jnp.take(store, jnp.clip(sl, 0, cap - 1), axis=0)
        return rescore_distances(rows, qf, metric)

    if pre_c:
        qp = jnp.matmul(qf, pca_proj, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)

        def score_pca(sl):
            rows = jnp.take(pca_rows, jnp.clip(sl, 0, cap - 1), axis=0)
            # the prefilter ranks, it never reports: L2 in the subspace
            # orders candidates for every matmul metric (cosine/dot rows
            # are normalized/compared in the same basis)
            return jnp.sum((rows - qp[:, None, :]) ** 2, axis=-1)

        ptop, pslots = _grouped_topk(slots_g, valid_g, score_pca, pre_c,
                                     False, slack=False)
        slots2, valid2 = _regroup(pslots, pslots >= 0, steps2)
        top, idx = _grouped_topk(slots2, valid2, score_full, k, exact)
    else:
        top, idx = _grouped_topk(slots_g, valid_g, score_full, k, exact)
    return top, jnp.where(jnp.isinf(top), -1, idx)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "top_p", "pre_c", "exact",
                     "gp", "steps2"),
)
def search_ivf_dense_fused(store, tombs, n, q, allow_words, centroids,
                           buckets, pca_proj, pca_rows, s2d, k,
                           metric, use_allow, top_p, pre_c, exact, gp,
                           steps2):
    """ivf_dense_topk as a top-level program with the slot->doc
    translation in the SAME program (ops/topk FUSED layout): the IVF plane
    leaves the device through the one fetch every tier makes."""
    top, idx = ivf_dense_topk(store, tombs, n, q, allow_words, centroids,
                              buckets, pca_proj, pca_rows, k,
                              metric, use_allow, top_p, pre_c, exact, gp,
                              steps2)
    return translate_pack(top, idx, s2d)


def ivf_codes_topk(codes, recon_norms, tombs, n, q, allow_words,
                   codebook, centroids, buckets, pca_proj,
                   pca_rows, rot, k, metric, use_allow, top_p, pre_c,
                   exact, gp, steps2):
    """IVF search over the codes-only PQ tier: probed candidates are
    scored by the SAME asymmetric-ADC math as the flat reconstruction
    scan (gather codes -> reconstruct from the bf16 codebook -> one
    f32-accumulated product against the (rotated) query, plus the
    precomputed ||recon||^2 for L2) — per candidate instead of per HBM
    chunk. No rescore pass, exactly like the flat codes tier.
    -> ([B, k] dists, [B, k] slots, -1 missing)."""
    qf = q.astype(jnp.float32)
    parts = _probe(qf, centroids, top_p, metric)
    slots_g = _candidate_slots(parts, buckets, gp)
    valid_g = _slot_valid(slots_g, n, tombs,
                          allow_words if use_allow else None)
    cap, m = codes.shape
    _, c, ds = codebook.shape
    flat_cb = codebook.reshape(m * c, ds).astype(jnp.bfloat16)
    seg_off = (jnp.arange(m, dtype=jnp.int32) * c)[None, None, :]
    qr = qf if rot is None else jnp.matmul(
        qf, rot, preferred_element_type=jnp.float32)
    qd = qr.astype(jnp.bfloat16)
    q_sq = jnp.sum(qr.astype(jnp.float32) ** 2, axis=-1, keepdims=True)

    def score_adc(sl):
        safe = jnp.clip(sl, 0, cap - 1)
        cd = jnp.take(codes, safe, axis=0).astype(jnp.int32)   # [B, g, M]
        recon = jnp.take(flat_cb, cd + seg_off, axis=0)        # [B,g,M,ds]
        recon = recon.reshape(cd.shape[0], cd.shape[1], m * ds)
        qx = jnp.einsum("bd,bgd->bg", qd, recon,
                        preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.DEFAULT)
        if metric == vi.DISTANCE_L2:
            nrm = jnp.take(recon_norms, safe)
            return jnp.maximum(q_sq - 2.0 * qx + nrm, 0.0)
        if metric == vi.DISTANCE_DOT:
            return -qx
        return 1.0 - qx

    if pre_c:
        qp = jnp.matmul(qf, pca_proj, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)

        def score_pca(sl):
            rows = jnp.take(pca_rows, jnp.clip(sl, 0, cap - 1), axis=0)
            return jnp.sum((rows - qp[:, None, :]) ** 2, axis=-1)

        ptop, pslots = _grouped_topk(slots_g, valid_g, score_pca, pre_c,
                                     False, slack=False)
        slots2, valid2 = _regroup(pslots, pslots >= 0, steps2)
        top, idx = _grouped_topk(slots2, valid2, score_adc, k, exact)
    else:
        top, idx = _grouped_topk(slots_g, valid_g, score_adc, k, exact)
    return top, jnp.where(jnp.isinf(top), -1, idx)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "top_p", "pre_c", "exact",
                     "gp", "steps2"),
)
def search_ivf_codes_fused(codes, recon_norms, tombs, n, q, allow_words,
                           codebook, centroids, buckets, pca_proj,
                           pca_rows, rot, s2d, k, metric, use_allow, top_p,
                           pre_c, exact, gp, steps2):
    """ivf_codes_topk as a top-level program, its winners translated to
    doc ids in the same program."""
    top, idx = ivf_codes_topk(codes, recon_norms, tombs, n, q, allow_words,
                              codebook, centroids, buckets,
                              pca_proj, pca_rows, rot, k, metric,
                              use_allow, top_p, pre_c, exact, gp, steps2)
    return translate_pack(top, idx, s2d)


# -- the tiled layout: the probed read -----------------------------------------

# tiles a step of the probed program's loop reads (`ivf_tiles_topk`)
TILE_UNROLL = 8


def ivf_tiles_topk(store, tombs, q, allow_words, centroids, k, metric,
                   use_allow, top_p, cap_p):
    """IVF search over a store kept in partition order (the tiled layout
    above): probe -> read the top_p probed partitions' tiles, each ONE
    contiguous [cap_p, D] slice of the store found by `partition * cap_p`,
    and score every row of them in float32 (`rescore_distances`: the
    distance a reply carries is the float32 distance of the row returned)
    -> ([B, k] dists, [B, k] slots, -1 missing).

    A loop over the probes, and nothing in it but the reduce: a probe's
    tile is a dynamic slice of the whole store that the compiler reads in
    place into the product's sum, so a probe costs the tile's bytes once
    and nothing is gathered by row. What a metric does to the sum (cosine's
    `1 - x`, dot's sign) waits for the whole block after the loop, and the
    loop is unrolled by eight: a step is then an index, ONE fused reduce of
    its eight tiles and the writes of their rows, 1.75 device ops a tile
    where the plain loop has five (0.3157 -> 0.2448 ms a query alone on
    the chip, and a third of the events in a profile of
    a cell that probes 64 tiles a query). The masks are the flat program's,
    read after the loop in one gather of whole tiles each (the tombstone
    bits and the filter words of the nlist x cap_p slots, as [nlist, ...]
    tables): the snapshot's own tombstones (an empty slot of a tile is a
    tombstoned one) and the packed allowList words; every slot of a tile
    lies under `n`. The selection is EXACT, in two levels (the k best of
    every tile, then the k best of those): a query's neighbours lie in a
    few tiles of ONE block, where `approx_min_k` lost them (it read recall
    0.97 where the probe covered everything: PERF.md section 6, PR 43)."""
    qf = q.astype(jnp.float32)
    parts = _probe(qf, centroids, top_p, metric)            # [B, top_p]
    b = qf.shape[0]
    nlist = centroids.shape[0]
    dim = store.shape[1]
    starts = parts * cap_p                                   # [B, top_p]
    l2 = metric == vi.DISTANCE_L2

    def step(_, start):                                      # start [B]
        if b == 1:   # one query: a plain dynamic slice, never a gather
            rows = jax.lax.dynamic_slice(
                store, (start[0], 0), (cap_p, dim))[None]
        else:
            rows = jax.vmap(lambda s: jax.lax.dynamic_slice(
                store, (s, 0), (cap_p, dim)))(start)         # [B, cap_p, D]
        rows = rows.astype(jnp.float32)
        if l2:
            return None, jnp.sum((rows - qf[:, None, :]) ** 2, axis=-1)
        return None, jnp.sum(rows * qf[:, None, :], axis=-1)

    _, d = jax.lax.scan(step, None, starts.T,
                        unroll=min(TILE_UNROLL, top_p))      # [top_p, B, cap_p]
    d = jnp.moveaxis(d, 0, 1)                                # [B, top_p, cap_p]
    if not l2:   # `rescore_distances`' arithmetic, the last op after the loop
        d = -d if metric == vi.DISTANCE_DOT else 1.0 - d
    slots = nlist * cap_p
    ok = jnp.logical_not(jnp.take(
        tombs[:slots].reshape(nlist, cap_p), parts, axis=0))
    if use_allow:
        words = jnp.take(allow_words[: slots // 32].reshape(
            nlist, cap_p // 32), parts, axis=0)              # [B, top_p, W]
        allowed = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) \
            & jnp.uint32(1)
        ok = jnp.logical_and(ok, allowed.reshape(b, top_p, cap_p) != 0)
    d = jnp.where(ok, d, INF)
    kt = min(k, cap_p)
    neg, pos = jax.lax.top_k(-d, kt)                         # [B, top_p, kt]
    slot_of = (starts[:, :, None] + pos).reshape(b, top_p * kt)
    neg, best = jax.lax.top_k(neg.reshape(b, top_p * kt), k)
    top = -neg
    idx = jnp.take_along_axis(slot_of, best, axis=1).astype(jnp.int32)
    return top, jnp.where(jnp.isinf(top), -1, idx)


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "use_allow", "top_p", "cap_p"),
)
def search_ivf_tiles_fused(store, tombs, q, allow_words, centroids, s2d,
                           k, metric, use_allow, top_p, cap_p):
    """ivf_tiles_topk as a top-level program with the slot->doc translation
    in the SAME program, like every tier's."""
    top, idx = ivf_tiles_topk(store, tombs, q, allow_words, centroids, k,
                              metric, use_allow, top_p, cap_p)
    return translate_pack(top, idx, s2d)
