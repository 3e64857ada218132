"""The rows and queries of `buckets`, for a deployment whose guarantees
include a trained layout that is part of the shard's durable state: "the
layout a search reads after a clean restart covers every live row, and the
restart reads it from beside the vector log: no training runs before or
inside the first search" (configs/cohere-768-cos-ivf.json `guarantees`).

A program that keeps no layout durable can still start such a configuration:
it reads `IVF_*` from the environment, trains on the host under the index's
write lock, and trains again inside every restart. It cannot give the
guarantee, and at the source's size it cannot give a run either: 1,004 s of
build and 186 s of training before a restarted server listens (the parent of
PR 43 on `cohere-768-cos-ivf.single-c20`, my chip run, PR 43), a first set-up
of 1,243 s that the benchmark check cut short with the server still
training. So this dataset refuses such a program when it is loaded, which is
before any state is built, the way `benchmarks/run.py` refuses a checkout
without the program: exit code 1, no result line, in a second.

The question is asked of `weaviate_tpu.config.config`, which imports no JAX:
the program names the layout's file there (`IVF_LAYOUT_FILE`) if it writes
one.
"""

from __future__ import annotations

from benchmarks.datasets.buckets import allowed, filter_plan, properties

__all__ = ["allowed", "filter_plan", "properties", "LayoutNotDurable"]


class LayoutNotDurable(RuntimeError):
    """The program under test keeps no trained layout beside its vector log."""


def _require_durable_layout() -> str:
    from weaviate_tpu.config import config

    name = getattr(config, "IVF_LAYOUT_FILE", None)
    if not name:
        raise LayoutNotDurable(
            "NO RESULT: this checkout's program keeps no trained layout "
            "durable (weaviate_tpu.config.config has no IVF_LAYOUT_FILE): it "
            "would train inside every restart, and the configuration's "
            "guarantees say it does not")
    return name


LAYOUT_FILE = _require_durable_layout()
