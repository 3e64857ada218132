"""REST batch bodies, encoded ahead of a window by worker processes.

One body of 100 rows x 768 floats is 1.6 MB of JSON and 55 ms of
`json.dumps` under the GIL; a window's hundred of them, built one after
another, held the window back six seconds behind the server's readiness
(and into its 30 s compaction tick). A worker gets row ids and what else
the objects carry, reads the rows from the state directory's own copy and
returns the encoded body; it imports numpy and nothing of the program.
"""

from __future__ import annotations

import json
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmarks.lib import data as gen

INLINE_BELOW = 16     # fewer bodies than this: no pool is worth its start


def encode(job) -> bytes:
    """(rows file, its shape, class, row ids, their properties) -> body."""
    path, shape, cls, ids, props = job
    rows = np.memmap(path, np.float32, "r", shape=shape)
    return json.dumps({"objects": [
        {"class": cls, "id": gen.uuid_of(int(i)), "properties": p,
         "vector": np.asarray(rows[int(i)]).tolist()}
        for i, p in zip(ids, props)]}).encode()


def encode_all(jobs: list) -> list[bytes]:
    if len(jobs) < INLINE_BELOW:
        return [encode(j) for j in jobs]
    workers = min(8, os.cpu_count() or 1, len(jobs))
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        return list(ex.map(encode, jobs, chunksize=4))
