"""The comparison that decides `correct`, as far as the replies go.

chip_smoke.py's `check_answers`, vectorised over a whole window: recall@k of
every reply against the stored exact ground truth, every returned distance
against the reference's distance of the row that was returned, every reply
with k results. The rows come from the state directory's own copy of the
corpus (written by the build from the seed), never from the server.

Under a filter the ground truth is the exact top k of the rows the query's
filter allows (-1 pads it where fewer than k are allowed): recall counts the
entries that exist, a reply is short only with fewer than min(k, allowed)
results, and a returned row that the filter does not allow, by the
dataset's own reading, is counted on its own: a filter is a guarantee, not
an approximation. Without filters every number is what it was.
"""

from __future__ import annotations

import numpy as np

RECALL_BAR = 0.95    # BASELINE.json's bar
# chip_smoke.py's tolerance: the last stage of every tier rescores in f32, so
# 1e-3 relative is rounding with room; a distance taken from a bf16 matmul
# pass would miss it by 10x
DIST_RTOL = 1e-3
DIST_FLOOR = 1e-3
# cosine is 1 - x with x near 1: float32 cannot carry it finer than a few
# units of 1e-7 times the width's rounding, whatever the true distance; a
# bf16 pass is off by 1e-3 and still caught
DIST_ATOL = {"cosine": 1e-5}


def recall_at_k(got_ids: np.ndarray, want_ids: np.ndarray) -> float:
    """|got ∩ want| over the ground-truth entries that exist, all replies
    together: the mean over replies of |got ∩ want| / k where every query
    has k. got_ids, want_ids: [R, k] (-1 pads a short reply or a ground
    truth of fewer than k allowed rows, and never matches)."""
    wanted = int((want_ids >= 0).sum())
    if len(got_ids) == 0 or wanted == 0:
        return 0.0
    hits = (got_ids[:, :, None] == want_ids[:, None, :]) & \
        (got_ids[:, :, None] >= 0)
    return float(hits.any(2).sum() / wanted)


def check_window(reference, metric: str, k: int, rows, pool: np.ndarray,
                 gt_ids: np.ndarray, qidx: np.ndarray, got_ids: np.ndarray,
                 got_dists: np.ndarray, allowed_pairs=None) -> dict:
    """qidx [R]: which pool query each reply answers; got_ids/got_dists
    [R, k], padded with -1 / nan where a reply was short. `rows` is the
    corpus (an array or memmap [N, dim]). `allowed_pairs(queries, rows)`
    -> bool per (pool query, row id) pair, the dataset's reading of each
    query's filter; None where no query has one. -> recall and what
    failed."""
    n = len(qidx)
    out = {"replies": int(n), "recall": 0.0, "short_replies": 0,
           "bad_distances": 0, "unknown_rows": 0, "disallowed_rows": 0,
           "first_bad": None, "first_disallowed": None}
    if n == 0:
        return out
    want_ids = gt_ids[qidx]
    short = (got_ids >= 0).sum(1) < (want_ids >= 0).sum(1)
    out["short_replies"] = int(short.sum())
    out["recall"] = recall_at_k(got_ids, want_ids)
    # each distinct (query, row) pair is held to the reference once
    valid = (got_ids >= 0) & (got_ids < rows.shape[0])
    out["unknown_rows"] = int(((got_ids >= rows.shape[0])).sum())
    qq = np.broadcast_to(qidx[:, None], got_ids.shape)[valid]
    rr = got_ids[valid]
    dd = got_dists[valid].astype(np.float32)
    key = qq.astype(np.int64) * (int(rows.shape[0]) + 1) + rr
    _, first, inverse = np.unique(key, return_index=True,
                                  return_inverse=True)
    uq, ur = qq[first], rr[first]
    if allowed_pairs is not None and len(first):
        off = ~np.asarray(allowed_pairs(uq, ur), bool)[inverse]
        out["disallowed_rows"] = int(off.sum())
        if off.any():
            j = int(np.argmax(off))
            out["first_disallowed"] = {"query": int(qq[j]), "row": int(rr[j])}
    order = np.argsort(ur, kind="stable")          # read the corpus in order
    true_u = np.empty(len(first), np.float32)
    step = 65_536
    for s in range(0, len(first), step):
        sl = order[s:s + step]
        true_u[sl] = reference.pair_distances(
            metric, np.asarray(rows[ur[sl]]), pool[uq[sl]])
    true = true_u[inverse]
    tol = DIST_RTOL * np.maximum(np.abs(true), DIST_FLOOR) \
        + DIST_ATOL.get(metric, 0.0)
    bad = ~np.isfinite(dd) | (np.abs(dd - true) > tol)
    out["bad_distances"] = int(bad.sum())
    if bad.any():
        j = int(np.argmax(bad))
        out["first_bad"] = {"query": int(qq[j]), "row": int(rr[j]),
                            "got": float(dd[j]), "want": float(true[j]),
                            "tolerance": float(tol[j])}
    return out
