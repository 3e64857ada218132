"""A search is planned once (index/plan.py): the tier `plan_search` names,
the tier `dispatch_tier` tells the quality auditor, the tier on the
dispatch's shape and the tier in the `enqueue` interval's stats are one
value, and the program the plan names is the one `scan_programs` counted.

The refused-funnel state is the test's warrant: before the plan existed,
`dispatch_tier` answered `pq_adc4` for a dispatch whose funnel budgets could
not cover its k and that the 8-bit tier served (the shape was re-labelled
after the fact, the auditor's label was not).
"""

import os

import numpy as np
import pytest

from weaviate_tpu.config.config import IvfConfig
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import plan as plan_mod
from weaviate_tpu.index import tpu
from weaviate_tpu.index.mesh import MeshVectorIndex
from weaviate_tpu.index.plan import plan_search
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import costmodel, perf, tracing
from weaviate_tpu.storage.bitmap import Bitmap

DIM = 16
PQ = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 32}
PQ4 = {**PQ, "bits": 4, "rescore": True, "rotation": "opq"}
IVF = IvfConfig(enabled=True, nlist=8, min_n=256, top_p=2,
                train_sample=4096, train_iters=4)


@pytest.fixture(autouse=True)
def _reset_globals(monkeypatch):
    # 16 queries' partitions hold more rows than these tiny stores: the
    # choice by bytes (tests/test_ivf_tiles.py) is set aside, so a state
    # with a layout plans its probed program
    monkeypatch.setattr(plan_mod, "PROBED_ROW_COST", 0.0)
    monkeypatch.setattr(plan_mod, "GATHERED_ROW_COST", 0.0)
    yield
    tpu.set_ivf_config(None)
    tracing.configure(None)
    perf.configure(None)


def _rows(n, seed=3):
    return np.random.default_rng(seed).integers(
        -50, 50, (n, DIM)).astype(np.float32)


def _one_chip(path, n=600, **cfg):
    idx = TpuVectorIndex(
        parse_and_validate_config(
            "hnsw_tpu", {"distance": "l2-squared", **cfg}),
        str(path), persist=False)
    vecs = _rows(n)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs


def _mesh(path, n=400, pq=None):
    """The mesh's states as tests/test_mesh_index.py makes them: four of
    the CPU's virtual devices, compressed by a config update."""
    os.makedirs(path, exist_ok=True)  # the codebook's save target
    cfg = {"distance": "l2-squared", "meshDevices": 4}
    idx = MeshVectorIndex(parse_and_validate_config("hnsw_tpu_mesh", cfg),
                          str(path), persist=False,
                          initial_capacity_per_shard=64)
    vecs = _rows(n)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    if pq is not None:
        idx.update_user_config(parse_and_validate_config(
            "hnsw_tpu_mesh", {**cfg, "pq": pq}))
        assert idx.compressed
    return idx, vecs


def _ivf_one_chip(path):
    tpu.set_ivf_config(IVF)
    idx, vecs = _one_chip(path, n=2000)
    # an uncompressed layout is the tiled one: no bucket table rides it
    assert idx._read_snapshot()[0].ivf_tiled
    return idx, vecs


def _ivf_mesh(path):
    tpu.set_ivf_config(IVF)
    idx, vecs = _mesh(path, n=1200)
    assert idx._read_snapshot()[0].ivf_buckets is not None
    return idx, vecs


# state -> (make(path) -> (index, rows), k, allowList or None, tier)
STATES = {
    "exact": (_one_chip, 10, None, costmodel.TIER_EXACT),
    "exact, allowList under flat_search_cutoff": (
        lambda p: _one_chip(p, flatSearchCutoff=50), 10,
        Bitmap(np.arange(5, dtype=np.uint64)), costmodel.TIER_GATHER),
    "exact, allowList over flat_search_cutoff": (
        lambda p: _one_chip(p, flatSearchCutoff=50), 10,
        Bitmap(np.arange(200, dtype=np.uint64)), costmodel.TIER_EXACT),
    "ivf trained": (_ivf_one_chip, 10, None, costmodel.TIER_EXACT),
    "pq with rescore": (
        lambda p: _one_chip(p, pq={**PQ, "rescore": True}), 10, None,
        costmodel.TIER_PQ_RESCORE),
    "pq codes-only": (
        lambda p: _one_chip(p, pq={**PQ, "rescore": False}), 10, None,
        costmodel.TIER_PQ_CODES),
    "pq.bits=4": (
        lambda p: _one_chip(p, pq=PQ4), 10, None, costmodel.TIER_PQ_ADC4),
    # the funnel's stage 1 keeps at most 4,096 rows (the top of
    # PQ4_FUNNEL_C_BUCKETS): a deeper k is the 8-bit tier's
    "pq.bits=4, a k the funnel refuses": (
        lambda p: _one_chip(p, n=4400, pq=PQ4), 4200, None,
        costmodel.TIER_PQ_RESCORE),
    "mesh exact": (_mesh, 10, None, costmodel.TIER_EXACT),
    "mesh exact, a small allowList (no gather tier)": (
        _mesh, 10, Bitmap(np.arange(5, dtype=np.uint64)),
        costmodel.TIER_EXACT),
    "mesh pq": (lambda p: _mesh(p, pq={"enabled": True, "segments": 4}),
                10, None, costmodel.TIER_PQ_RESCORE),
    "mesh pq.bits=4": (lambda p: _mesh(p, pq=PQ4), 10, None,
                       costmodel.TIER_PQ_ADC4),
    "mesh ivf trained": (_ivf_mesh, 10, None, costmodel.TIER_EXACT),
}


@pytest.mark.parametrize("state", STATES)
def test_the_plan_the_auditor_the_shape_and_the_interval_name_one_tier(
        state, tmp_path, monkeypatch):
    make, k, allow, tier = STATES[state]
    idx, vecs = make(tmp_path / "s")
    q = vecs[:16] + 0.25
    snap = idx._read_snapshot()[0]
    mesh = isinstance(idx, MeshVectorIndex)
    planned = plan_search(
        idx._plan_view(snap), 16, idx.padded_width(16),
        idx._k_eff(snap, k) if mesh else min(k, snap.live),
        None if mesh or allow is None else len(allow))

    enqueued = []

    class Ann:  # the profiler's annotation, as tests/test_perf.py spies it
        def __init__(self, name, **stats):
            self.name, self.stats = name, dict(stats)
            if name == "wv/enqueue":
                enqueued.append(self.stats)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **stats):
            self.stats.update(stats)

    monkeypatch.setattr(tracing, "_TraceMe", Ann)
    tracing.configure(tracing.Tracer(sample_rate=1.0))
    before = idx.scan_programs.as_dict()
    handle = idx.search_by_vectors_async(q, k, allow)
    ids, _ = handle()
    after = idx.scan_programs.as_dict()

    assert ids.shape[0] == 16 and ids.shape[1] > 0
    # fetched: the handle keeps the answer and lets go of the program's output
    assert handle()[0] is ids and handle._fin is None
    assert handle.plan == planned
    assert len(enqueued) == 1
    assert (planned.tier, idx.dispatch_tier(snap, allow, b=16, k=k),
            handle.shape.tier, enqueued[0]["tier"]) == (tier,) * 4
    assert ("ivf" in state) == (planned.ivf is not None)
    # a full-store scan names its program, and that program was counted
    counted = {p: after[p] - before[p] for p in ("gmin", "scan")
               if after[p] != before[p]}
    assert counted == ({planned.program: 1} if planned.program else {})
    assert enqueued[0].get("program") == planned.program
    assert (handle.shape.extra or {}).get("program") == planned.program
