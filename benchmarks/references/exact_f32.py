"""The plain reference: exact float32 brute-force nearest neighbours.

Independent of the program under test: numpy only, the textbook distance of
every (query, row) pair that can matter. `pair_distances` is the distance
the comparison holds a reply to; `TopK` is the ground truth, fed one chunk of
rows at a time so the corpus is never held twice, each chunk with the mask
of the rows every query's filter allows.

Metrics as the program names them: `l2-squared` = |r - q|^2, `cosine` =
1 - r.q / (|r||q|), `dot` = -r.q.
"""

from __future__ import annotations

import numpy as np

METRICS = ("l2-squared", "cosine", "dot")


def pair_distances(metric: str, rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Exact distance of each rows[i] to q[i] (or to the one q): float32
    inputs, the textbook formula in float64, rounded to f32 once."""
    rows = np.asarray(rows, np.float32).astype(np.float64)
    q = np.asarray(q, np.float32).astype(np.float64)
    if metric == "l2-squared":
        return ((rows - q) ** 2).sum(-1).astype(np.float32)
    dot = (rows * q).sum(-1)
    if metric == "dot":
        return (-dot).astype(np.float32)
    if metric == "cosine":
        return (1.0 - dot / np.sqrt((rows ** 2).sum(-1) * (q ** 2).sum(-1))
                ).astype(np.float32)
    raise ValueError(f"metric {metric!r} (known: {METRICS})")


def _coarse(metric: str, rows: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """[Q, R] distances through one BLAS matmul: right to ~1e-4 relative,
    used only to choose which pairs get the exact formula."""
    dots = queries @ rows.T
    if metric == "dot":
        return -dots
    rn = (rows ** 2).sum(1)
    qn = (queries ** 2).sum(1)
    if metric == "l2-squared":
        return qn[:, None] - 2.0 * dots + rn[None, :]
    return 1.0 - dots / np.sqrt(qn[:, None] * rn[None, :])


class TopK:
    """Exact top-k of every query over a corpus seen chunk by chunk."""

    def __init__(self, metric: str, queries: np.ndarray, k: int):
        if metric not in METRICS:
            raise ValueError(f"metric {metric!r} (known: {METRICS})")
        self.metric, self.k = metric, k
        self.q = np.ascontiguousarray(queries, np.float32)
        nq = len(self.q)
        self.ids = np.full((nq, k), -1, np.int64)
        self.dists = np.full((nq, k), np.inf, np.float32)
        self.allowed = np.zeros(nq, np.int64)   # rows each query could take

    def update(self, first_id: int, rows: np.ndarray,
               allowed: np.ndarray | None = None) -> None:
        """One chunk of the corpus. `allowed` ([Q, rows] bool, or None for
        no filter): the rows each query's filter lets through; a row that
        is not allowed is never a neighbour of that query."""
        rows = np.ascontiguousarray(rows, np.float32)
        coarse = _coarse(self.metric, rows, self.q)
        if allowed is None:
            self.allowed += rows.shape[0]
        else:
            self.allowed += allowed.sum(1)
            coarse = np.where(allowed, coarse, np.inf)
        kth = self.dists[:, -1]
        open_q = np.flatnonzero(np.isinf(kth))
        # every pair that could beat the current k-th best, with slack for
        # the coarse distance's rounding; a query with no k-th best yet
        # matches nothing here and is served below
        with np.errstate(invalid="ignore"):
            bar = np.where(np.isinf(kth), -np.inf,
                           kth + 1e-3 * np.abs(kth) + 1e-3)
        qi, ri = np.nonzero(coarse <= bar[:, None])
        if open_q.size:
            # nothing to prune with yet: the k best of this chunk by the
            # coarse distance, with room for its rounding
            take = min(4 * self.k, rows.shape[0])
            part = np.argpartition(coarse[open_q], take - 1, axis=1)[:, :take]
            oq, orow = np.repeat(open_q, take), part.ravel()
            keep = np.isfinite(coarse[oq, orow])
            qi = np.concatenate([qi, oq[keep]])
            ri = np.concatenate([ri, orow[keep]])
        if qi.size == 0:
            return
        exact = pair_distances(self.metric, rows[ri], self.q[qi])
        order = np.lexsort((exact, qi))
        qi, ri, exact = qi[order], ri[order], exact[order]
        starts = np.flatnonzero(np.r_[True, qi[1:] != qi[:-1]])
        ends = np.r_[starts[1:], qi.size]
        for s, e in zip(starts, ends):
            e = min(e, s + self.k)
            q = qi[s]
            d = np.concatenate([self.dists[q], exact[s:e]])
            i = np.concatenate([self.ids[q], first_id + ri[s:e]])
            keep = np.lexsort((i, d))[:self.k]
            self.dists[q], self.ids[q] = d[keep], i[keep]

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        """([Q, k] row ids, [Q, k] exact distances), nearest first; where a
        query was allowed fewer than k rows, padded with -1 and inf
        (`self.allowed` has how many it was allowed)."""
        return self.ids, self.dists
