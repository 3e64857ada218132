"""ops/topk.py merge_top_k selects distances and slots together (one stable
sort, the slots its payload) and answers what the form it replaced answered:
`lax.top_k` of the negated distances and a gather of the slots by position,
written out here as the plain reference. Distances and slots are compared bit
for bit, on blocks where the order among equal distances is all that could
differ: ties inside one side and across the two, signed zeros, the first
step's empty running set, (+inf, -1) tails, a new block with few live rows.
One case goes through ops/scan.py scan_topk itself, over a slab whose rows
repeat from chunk to chunk, against numpy with ties broken by the lower slot.
(`blocks` is also what the builder's chip probe feeds both forms on a TPU.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KINDS = ("ties_within", "ties_across", "signed_zeros", "first_step",
         "inf_tails", "short_new")


def plain_merge(dists_a, idx_a, dists_b, idx_b, k):
    """The form merge_top_k had before it carried the slots through the
    selection: top-k of the distances, then the slots read by position."""
    d = jnp.concatenate([dists_a, dists_b], axis=1)
    i = jnp.concatenate([idx_a, idx_b], axis=1)
    neg_top, pos = jax.lax.top_k(-d, k)
    return -neg_top, jnp.take_along_axis(i, pos, axis=1)


def _tails(d, i, live):
    """Columns from `live[row]` on become the (+inf, -1) fill."""
    dead = np.arange(d.shape[1])[None, :] >= live[:, None]
    return (np.where(dead, np.inf, d).astype(np.float32),
            np.where(dead, -1, i).astype(np.int32))


def blocks(kind: str, b: int, k: int, seed: int = 0):
    """(dists_a, slots_a, dists_b, slots_b), each [b, k]: the running
    candidates and a chunk's winners, as numpy float32 / int32. Slots are
    distinct along a row so that a wrong pairing shows."""
    rng = np.random.default_rng([seed, b, k, KINDS.index(kind)])
    slots = np.argsort(rng.random((b, 2 * k)), axis=1).astype(np.int32)
    ia, ib = slots[:, :k], slots[:, k:] + 1000
    few = max(2, k // 4)          # distinct values: every row full of ties
    if kind == "ties_within":
        da = rng.integers(0, few, (b, k)).astype(np.float32)
        db = rng.integers(few, 2 * few, (b, k)).astype(np.float32)
        db[:, ::3] = da[:, ::3]   # and some across, out of order
    elif kind == "ties_across":
        da = np.sort(rng.random((b, k)).astype(np.float32), axis=1)
        db = da.copy()            # every distance stands on both sides
    elif kind == "signed_zeros":
        pool = np.array([-0.0, 0.0, 0.0, -0.0, 0.25, 1.0], np.float32)
        da = pool[rng.integers(0, pool.size, (b, k))]
        db = pool[rng.integers(0, pool.size, (b, k))]
    elif kind == "first_step":
        da = np.full((b, k), np.inf, np.float32)
        ia = np.full((b, k), -1, np.int32)
        db = np.sort(rng.integers(0, few, (b, k)).astype(np.float32), axis=1)
    elif kind == "inf_tails":
        da = np.sort(rng.integers(0, few, (b, k)).astype(np.float32), axis=1)
        db = np.sort(rng.integers(0, few, (b, k)).astype(np.float32), axis=1)
        # rows with fewer than k live entries on both sides together too
        da, ia = _tails(da, ia, rng.integers(0, k + 1, b))
        db, ib = _tails(db, ib, rng.integers(0, k // 2 + 1, b))
    else:
        assert kind == "short_new"
        da = np.sort(rng.integers(0, few, (b, k)).astype(np.float32), axis=1)
        db = np.sort(rng.integers(0, few, (b, k)).astype(np.float32), axis=1)
        db, ib = _tails(db, ib, np.full(b, 3))
    return da, ia, db, ib


def same_bits(got, want):
    """(dists, slots) pairs equal bit for bit: -0.0 is not +0.0 here."""
    np.testing.assert_array_equal(np.asarray(got[0]).view(np.int32),
                                  np.asarray(want[0]).view(np.int32))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("k", [10, 40, 128])
@pytest.mark.parametrize("b", [1, 8, 256])
def test_merge_answers_what_top_k_and_a_gather_answered(b, k, kind):
    from weaviate_tpu.ops.topk import merge_top_k

    args = blocks(kind, b, k)
    got = merge_top_k(*args, k)
    assert got[0].shape == (b, k) and got[0].dtype == jnp.float32
    assert got[1].shape == (b, k) and got[1].dtype == jnp.int32
    same_bits(got, plain_merge(*args, k))


def test_merge_of_blocks_of_two_widths_keeps_the_wider():
    """ops/ivf.py's probe loop: a running [B, w] and a group's [B, g] with
    g < w, merged to w."""
    from weaviate_tpu.ops.topk import merge_top_k

    da, ia, db, ib = blocks("ties_within", 8, 40)
    got = merge_top_k(da, ia, db[:, :16], ib[:, :16], 40)
    same_bits(got, plain_merge(da, ia, db[:, :16], ib[:, :16], 40))


def test_scan_topk_breaks_ties_across_chunks_by_the_lower_slot(monkeypatch):
    """A slab of 4 chunks whose rows repeat from chunk to chunk, so every
    distance stands in every chunk: exact per-chunk selection (`lax.top_k`,
    lower column first; what `approx_min_k` does with a tie is the
    backend's) and the merge as the loop runs it. Small whole numbers, so
    every distance is exact in float32 whatever the order of the sums."""
    from weaviate_tpu.ops import scan

    chunk, nchunks, dim, k = 256, 4, 8, 6
    monkeypatch.setattr(scan, "SCAN_CHUNK", chunk)
    rng = np.random.default_rng(41)
    base = rng.integers(-3, 4, (chunk // 4, dim)).astype(np.float32)
    rows = np.tile(base, (4 * nchunks, 1))           # [1024, 8], much repeated
    cap = rows.shape[0]
    n = cap - 100                                     # the last chunk part full
    tombs = np.zeros(cap, bool)
    tombs[[0, 5, 300, 777]] = True
    q = rng.integers(-3, 4, (5, dim)).astype(np.float32)

    top, idx = jax.jit(
        lambda s, nr, t, qq: scan.scan_topk(
            s, nr, t, n, qq, None, k, "l2-squared", False, exact=True))(
        jnp.asarray(rows), jnp.asarray((rows ** 2).sum(1)),
        jnp.asarray(tombs), jnp.asarray(q))

    d = ((q[:, None, :] - rows[None, :, :]) ** 2).sum(-1)
    d[:, tombs] = np.inf
    d[:, n:] = np.inf
    want_i = np.argsort(d, axis=1, kind="stable")[:, :k].astype(np.int32)
    want_d = np.take_along_axis(d, want_i, axis=1).astype(np.float32)
    same_bits((top, idx), (want_d, want_i))
