"""Rows, queries and ids, all from seeds; numpy only.

Rows: bench.py's clustered generator (`make_data`: 1,024 gaussian centres at
scale 2.0, rows = centre + 0.35 x N(0, I); copied, the original is listed in
PERF.md for deletion), made in chunks that depend only on (data seed, chunk
index), so that neither the build nor the reference holds the corpus twice.
Queries: a stored row plus 0.05 x N(0, I), bench.py's query model.
"""

from __future__ import annotations

import uuid

import numpy as np

N_CLUSTERS = 1024
CHUNK_ROWS = 65_536
QUERY_NOISE = 0.05


def _centers(data_seed: int, dim: int) -> np.ndarray:
    rng = np.random.default_rng([data_seed, 0xC0])
    return rng.standard_normal((N_CLUSTERS, dim), dtype=np.float32) * 2.0


def iter_chunks(data_seed: int, rows: int, dim: int):
    """(first row id, [<=CHUNK_ROWS, dim] f32 rows) for every chunk of the
    corpus; a chunk depends only on (data seed, chunk index)."""
    centers = _centers(data_seed, dim)
    for chunk, lo in enumerate(range(0, rows, CHUNK_ROWS)):
        n = min(CHUNK_ROWS, rows - lo)
        rng = np.random.default_rng([data_seed, 1, chunk])
        assign = rng.integers(0, N_CLUSTERS, n)
        yield lo, centers[assign] + 0.35 * rng.standard_normal(
            (n, dim), dtype=np.float32)


def pool_picks(data_seed: int, rows: int, pool: int) -> np.ndarray:
    """The stored rows the query pool is made from."""
    return np.random.default_rng([data_seed, 2]).integers(0, rows, pool)


def pool_noise(data_seed: int, pool: int, dim: int) -> np.ndarray:
    return QUERY_NOISE * np.random.default_rng([data_seed, 3]).standard_normal(
        (pool, dim), dtype=np.float32)


def uuid_of(row: int) -> str:
    """chip_smoke.py's `_uuid`: the object id of a row."""
    return str(uuid.UUID(int=row + 1))


def row_of(u: str) -> int:
    """chip_smoke.py's `_row`, without building a UUID object."""
    return int(u.replace("-", ""), 16) - 1
