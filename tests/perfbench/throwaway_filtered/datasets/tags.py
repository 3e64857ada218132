"""A bag of tags a row, one or two tags a query that must all match: the
shape of the filtered track of big-ann-benchmarks (NeurIPS'23, YFCC 10M), at
a throw-away size. Test only: no configuration of the benchmark names it.

Rows: 1 to `tags_per_row_max` tags each, drawn with Zipf frequencies
(p(tag t) ~ 1 / (t + 1)^`tags_zipf_s`) from a vocabulary of `tags_vocab`,
a chunk at a time from (data seed, chunk index), like the vectors. Queries:
pool query i was made from stored row `pool_picks[i]` and asks for one or
two of that row's tags, so every query is allowed at least one row and the
selectivity runs from one row in all to the share of the commonest tag.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmarks.lib import data as gen
from benchmarks.lib import where

PROPERTY = "tags"


@functools.lru_cache(maxsize=4)
def _chunk_tags(seed: int, chunk: int, n: int, vocab: int, most: int,
                s: float) -> np.ndarray:
    """[n, most] tags of one chunk of rows, padded with -1."""
    rng = np.random.default_rng([seed, 0x7A, chunk])
    p = 1.0 / np.arange(1, vocab + 1) ** s
    tags = np.searchsorted(np.cumsum(p / p.sum()), rng.random((n, most)))
    tags = np.minimum(tags, vocab - 1)
    tags[np.arange(most)[None, :] >= rng.integers(1, most + 1, n)[:, None]] = -1
    return tags


def _columns(cfg: dict, rows: np.ndarray) -> dict:
    rows = np.asarray(rows, np.int64)
    out = np.empty((len(rows), int(cfg["tags_per_row_max"])), np.int64)
    chunks = rows // gen.CHUNK_ROWS
    for chunk in np.unique(chunks):
        lo = int(chunk) * gen.CHUNK_ROWS
        tags = _chunk_tags(
            int(cfg["data_seed"]), int(chunk),
            min(gen.CHUNK_ROWS, int(cfg["rows"]) - lo), int(cfg["tags_vocab"]),
            int(cfg["tags_per_row_max"]), float(cfg["tags_zipf_s"]))
        here = chunks == chunk
        out[here] = tags[rows[here] - lo]
    return {PROPERTY: out}


def properties(cfg: dict, rows: np.ndarray) -> list[dict]:
    return where.properties(_columns(cfg, rows), len(rows))


def _equal(tag: int) -> dict:
    return {"path": [PROPERTY], "operator": "Equal", "valueInt": int(tag)}


def filter_plan(cfg: dict, plan: str | None) -> list:
    if plan is not None:
        raise ValueError(f"dataset tags has no filter plan {plan!r}")
    seed, pool = int(cfg["data_seed"]), int(cfg["pool"])
    picks = gen.pool_picks(seed, int(cfg["rows"]), pool)
    urows, back = np.unique(picks, return_inverse=True)
    bags = _columns(cfg, urows)[PROPERTY][back]
    rng = np.random.default_rng([seed, 0x7B])
    out = []
    for bag in bags:
        own = np.unique(bag[bag >= 0])
        asks = rng.choice(own, size=min(int(rng.integers(1, 3)), len(own)),
                          replace=False)
        out.append(_equal(asks[0]) if len(asks) == 1 else
                   {"operator": "And", "operands": [_equal(t) for t in asks]})
    return out


def allowed(cfg: dict, wheres: list, rows: np.ndarray) -> np.ndarray:
    return where.allowed(wheres, _columns(cfg, rows), len(rows))
