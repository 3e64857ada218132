"""A bag of tags a row from a large vocabulary, one or two tags a query that
must all match: the filtered track of big-ann-benchmarks (NeurIPS'23), dataset
yfcc-10M, whose vectors carry bags from a vocabulary of 200,386 and whose
queries ask one or two tags. The tags themselves cannot be fetched
(docs/dataset_download_attempts.md), so they are drawn here, after the
throw-away `tests/perfbench/throwaway_filtered/datasets/tags.py`:

Rows: 1 to `tags_per_row_max` tags each with Zipf frequencies (p(tag t) ~
1 / (t + 1)^`tags_zipf_s`) from `tags_vocab` tags, a chunk at a time from
(data seed, chunk index), like the vectors. Queries: pool query i was made
from stored row `pool_picks[i]` and asks for one or two of that row's own
tags (`And` of `Equal`), so every query is allowed at least one row. Two
tags drawn independently almost never meet in a second row (the source's
co-occur: a camera, a year, a country), so a query asks two only where the
pair is EXPECTED, by the Zipf law alone and not by counting, to allow
`and_min_rows` rows (the source's rarest filters: about 1e-5 of the rows),
and one otherwise. The selectivity then runs from the rarest tag's rows to
the share of the commonest.

`allowed` reads a chunk's bags once, sorted by tag, so that the ground truth
of 1,024 filters over millions of rows is a lookup a filter and a chunk;
`tests/perfbench/test_perfbench_yfcc_rehearsal.py` holds it to a row-by-row
reading in plain Python and to `benchmarks/lib/where.py`.
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
    """A row's bag as the write path takes it: its distinct tags."""
    bags = _columns(cfg, rows)[PROPERTY]
    return [{PROPERTY: [int(t) for t in np.unique(bag[bag >= 0])]}
            for bag in bags]


def _equal(tag: int) -> dict:
    return {"path": [PROPERTY], "operator": "Equal", "valueInt": int(tag)}


def filter_plan(cfg: dict, plan: str | None) -> list:
    if plan is not None:
        raise ValueError(f"dataset yfcc_tags has no filter plan {plan!r}")
    seed, pool = int(cfg["data_seed"]), int(cfg["pool"])
    picks = gen.pool_picks(seed, int(cfg["rows"]), pool)
    urows, back = np.unique(picks, return_inverse=True)
    bags = _columns(cfg, urows)[PROPERTY][back]
    rng = np.random.default_rng([seed, 0x7B])
    expected = _expected_rows(cfg)
    floor = float(cfg.get("and_min_rows", 0)) * int(cfg["rows"])
    out = []
    for bag in bags:
        own = np.unique(bag[bag >= 0])
        asks = [own[rng.integers(len(own))]]
        if rng.integers(1, 3) == 2:
            # the pairs of this row's tags expected to allow enough rows
            e = expected[own]
            a, b = np.nonzero(np.triu(e[:, None] * e[None, :] >= floor, 1))
            if len(a):
                pick = int(rng.integers(len(a)))
                asks = [own[a[pick]], own[b[pick]]]
        out.append(_equal(asks[0]) if len(asks) == 1 else
                   {"operator": "And", "operands": [_equal(t) for t in asks]})
    return out


def _expected_rows(cfg: dict) -> np.ndarray:
    """Rows expected to carry each tag: a row makes (1 + most) / 2 draws."""
    vocab, most = int(cfg["tags_vocab"]), int(cfg["tags_per_row_max"])
    p = 1.0 / np.arange(1, vocab + 1) ** float(cfg["tags_zipf_s"])
    return int(cfg["rows"]) * (1.0 - (1.0 - p / p.sum()) ** ((1 + most) / 2))


def _asked(w: dict) -> list[int] | None:
    """The tags of a filter of this dataset's own shape (`Equal`, or `And`
    of `Equal`s, on the bag), else None."""
    clauses = w.get("operands") if w.get("operator") == "And" else [w]
    if not clauses or any(c.get("operator") != "Equal"
                          or c.get("path") != [PROPERTY]
                          or "valueInt" not in c for c in clauses):
        return None
    return [int(c["valueInt"]) for c in clauses]


def allowed(cfg: dict, wheres: list, rows: np.ndarray) -> np.ndarray:
    columns = _columns(cfg, rows)
    bags = columns[PROPERTY]
    at, _ = np.nonzero(bags >= 0)
    tags = bags[bags >= 0]
    order = np.argsort(tags, kind="stable")
    tags, at = tags[order], at[order]       # the rows of each tag, together
    out = np.ones((len(wheres), len(bags)), bool)
    for i, w in enumerate(wheres):
        if w is None:
            continue
        asks = _asked(w)
        if asks is None:                    # any other filter: the plain reading
            out[i] = where.evaluate(w, columns)
            continue
        for tag in asks:
            lo, hi = np.searchsorted(tags, (tag, tag + 1))
            has = np.zeros(len(bags), bool)
            has[at[lo:hi]] = True
            out[i] &= has
    return out
