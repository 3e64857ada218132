"""The rows every configuration had before a dataset was a file: one int
property, `bucket` = row % `filter_buckets`, and no filter a query. A
configuration without a `dataset` key gets this one (spec.DEFAULT_DATASET).

What a dataset is: three functions of the configuration and row ids alone,
never of the server. `rows` is an ascending array of row ids.

    properties(cfg, rows)       -> [{property: value}] as the build and the
                                   write requests put them
    filter_plan(cfg, plan)      -> the `where` (GraphQL grammar, or None) of
                                   each of the `pool` queries; `plan` None is
                                   what the queries carry by themselves, a
                                   name is a plan a traffic file asks for
    allowed(cfg, wheres, rows)  -> bool [len(wheres), len(rows)]: which rows
                                   each filter allows, read in numpy

Plans here: `bucket_each`, query i asks for `bucket == i % filter_buckets`
(BASELINE.json's config 3 as traffic: a 1/filter_buckets filter, each query
its own, on a configuration that is there).
"""

from __future__ import annotations

import numpy as np

from benchmarks.lib import where


def _columns(cfg: dict, rows: np.ndarray) -> dict:
    return {"bucket": np.asarray(rows, np.int64) % int(cfg["filter_buckets"])}


def properties(cfg: dict, rows: np.ndarray) -> list[dict]:
    return where.properties(_columns(cfg, rows), len(rows))


def filter_plan(cfg: dict, plan: str | None) -> list:
    pool, buckets = int(cfg["pool"]), int(cfg["filter_buckets"])
    if plan is None:
        return [None] * pool
    if plan == "bucket_each":
        return [{"path": ["bucket"], "operator": "Equal",
                 "valueInt": i % buckets} for i in range(pool)]
    raise ValueError(f"dataset buckets has no filter plan {plan!r}")


def allowed(cfg: dict, wheres: list, rows: np.ndarray) -> np.ndarray:
    return where.allowed(wheres, _columns(cfg, rows), len(rows))
