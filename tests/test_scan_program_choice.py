"""Which of the two full-store scan programs a dispatch runs: the Pallas
group-min kernel (ops/gmin_scan.py) or the lax.scan program (ops/scan.py).

`gmin_scan.kernel_serves` answers for both indexes: the kernel where it
compiles (`fits_vmem`, whose answers are the ones it always gave) AND is the
faster program at that width (fitted on a v5e: PERF.md section 6, PR 40). A no
is a choice, not a degradation: nothing of the kernel's is built, compiled,
validated or counted as a fallback; what ran is counted by program.
"""

import dataclasses

import numpy as np
import pytest

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.index import tpu
from weaviate_tpu.index.plan import plan_search
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import incidents, memory, perf, tracing
from weaviate_tpu.ops import gmin_scan


@pytest.fixture(autouse=True)
def _reset_globals():
    yield
    tracing.configure(None)
    perf.configure(None)
    memory.configure(None)


# the benchmark's cells at b = 256: (dim, capacity, rows, bytes a component)
# -> (the kernel compiles, the kernel serves)
CELLS = {
    "sift-128-l2": ((128, 1 << 22, 4_000_000, 4), (True, True)),
    "cohere-768-cos-pq": ((768, 1 << 21, 2_000_000, 2), (True, False)),
    "cohere-768-cos": ((768, 1 << 20, 1_000_000, 4), (False, False)),
    "cohere-768-cos-10m-share": ((768, 2_621_440, 2_500_000, 4),
                                 (False, False)),
    "cohere-768-cos-mesh4, a chip": ((768, 1 << 19, 500_000, 4),
                                     (False, False)),
}


def _shape(dim, capacity, rows, store_bytes, b=256):
    ncols = capacity // gmin_scan.G
    return b, dim, ncols, -(-rows // ncols), store_bytes


@pytest.mark.parametrize("cell", CELLS)
def test_the_choice_at_the_cells_shapes(cell):
    dims, (fits, serves) = CELLS[cell]
    assert gmin_scan.fits_vmem(*_shape(*dims)) is fits
    assert gmin_scan.kernel_serves(*_shape(*dims)) is serves


# the table the rule was fitted on (PERF.md section 6, PR 40: both programs
# alone at b = 256 on a v5e), one row a case: the faster program serves
MEASURED = {
    "256 B a row: 128-d bf16, 4M rows": ((128, 1 << 22, 4_000_000, 2), True),
    "512 B: 128-d f32, 4M rows": ((128, 1 << 22, 4_000_000, 4), True),
    "512 B: 256-d bf16, 2M rows": ((256, 1 << 21, 2_000_000, 2), False),
    "768 B: 192-d f32, 2M rows": ((192, 1 << 21, 2_000_000, 4), True),
    "768 B: 384-d bf16, 2M rows": ((384, 1 << 21, 2_000_000, 2), False),
    "1,024 B: 256-d f32, 2M rows": ((256, 1 << 21, 2_000_000, 4), False),
    "1,024 B: 512-d bf16, 2M rows": ((512, 1 << 21, 2_000_000, 2), False),
    "1,536 B: 384-d f32, 2M rows": ((384, 1 << 21, 2_000_000, 4), False),
    "1,536 B: 768-d bf16, 2M rows": ((768, 1 << 21, 2_000_000, 2), False),
}


@pytest.mark.parametrize("row", MEASURED)
def test_the_rule_agrees_with_every_measured_row(row):
    dims, kernel_won = MEASURED[row]
    assert gmin_scan.fits_vmem(*_shape(*dims))      # all nine compile
    assert gmin_scan.kernel_serves(*_shape(*dims)) is kernel_won


def test_the_kernel_never_serves_where_it_does_not_compile():
    for d in (16, 64, 128, 192, 256, 384, 768, 1536, 65536):
        for sb in (2, 4):
            for ag in (1, 8, 16):
                for b in (8, 256, 4096):
                    shape = (b, d, 4096, ag, sb)
                    assert (not gmin_scan.kernel_serves(*shape)
                            or gmin_scan.fits_vmem(*shape)), shape


def _mk_index(path, n=600, d=32, pq=None, metric=vi.DISTANCE_L2, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n, d)).astype(np.float32)
    cfg = {"distance": metric}
    if pq is not None:
        cfg["pq"] = pq
    idx = TpuVectorIndex(vi.HnswUserConfig.from_dict(cfg, "hnsw_tpu"),
                         str(path), persist=False)
    idx.add_batch(np.arange(n), vecs)
    idx.flush()
    return idx, vecs


def _spy_choice(monkeypatch):
    asked = []
    real = gmin_scan.kernel_serves

    def spy(*shape):
        asked.append(shape)
        return real(*shape)

    monkeypatch.setattr(gmin_scan, "kernel_serves", spy)
    return asked


def test_the_one_chip_index_asks_the_shared_function(tmp_path, monkeypatch):
    idx, vecs = _mk_index(tmp_path / "a")
    asked = _spy_choice(monkeypatch)
    idx.search_by_vectors(vecs[:16], 5)
    snap = idx._read_snapshot()[0]
    ncols = snap.capacity // gmin_scan.G
    assert asked == [(16, 32, ncols, -(-snap.n // ncols), 4)]
    assert idx.scan_programs.as_dict() == {
        "gmin": 1, "scan": 0, "declined_slower": 0}


@pytest.mark.parametrize("dim,store_bytes,serves", [
    (128, 4, True), (768, 2, False), (768, 4, False)])
def test_the_mesh_gate_answers_as_the_shared_function(
        tmp_path, monkeypatch, dim, store_bytes, serves):
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config
    from weaviate_tpu.index.mesh import MeshVectorIndex

    idx = MeshVectorIndex(
        parse_and_validate_config("hnsw_tpu_mesh", {"distance": "cosine"}),
        str(tmp_path / "m"), persist=False, initial_capacity_per_shard=64)
    idx.add_batch(np.arange(8), np.eye(8, dtype=np.float32))
    asked = _spy_choice(monkeypatch)
    # a chip's slab of the mesh cell's size; the plan's gate reads these four
    n_loc = 1 << 19
    view = dataclasses.replace(
        idx._plan_view(idx._read_snapshot()[0]), slab=n_loc, fill=500_000,
        dim=dim, itemsize=store_bytes)
    plan = plan_search(view, 256, 256, 10)
    ncols = n_loc // gmin_scan.G
    assert asked == [(256, dim, ncols, 16, store_bytes)]
    assert (plan.program == "gmin") is serves
    assert plan.gmin == ((32, 16) if serves else None)
    # declined where it would have compiled: counted, as on one chip
    fits = gmin_scan.fits_vmem(256, dim, ncols, 16, store_bytes)
    assert idx.scan_programs.declined_slower == int(fits and not serves)
    assert idx.health()["kernels"]["gmin"]["dispatches"] == {
        "gmin": 0, "scan": 0, "declined_slower": int(fits and not serves)}


_PQ = {"enabled": True, "trainingLimit": 256, "segments": 8, "centroids": 16}


def test_a_declined_compressed_store_runs_the_scan_and_builds_nothing(
        tmp_path, monkeypatch):
    """768-d bf16 rows: the kernel compiles there and is the slower program,
    so the compressed tier runs the lax.scan program with candidates, holds
    no block copy of its rows, validates no kernel shape and counts a
    decline, not a fallback."""
    led = memory.configure(memory.MemoryLedger())
    idx, vecs = _mk_index(tmp_path / "pq", n=700, d=768, pq=_PQ)
    assert idx.compressed and idx._rescore_dev is not None
    snap = idx._read_snapshot()[0]
    ncols = snap.capacity // gmin_scan.G
    shape = (16, 768, ncols, -(-snap.n // ncols), 2)
    assert gmin_scan.fits_vmem(*shape) and not gmin_scan.kernel_serves(*shape)

    programs, fallbacks = [], []
    real = tpu._search_full_fused

    class Spy:  # the ScanProgram the index calls
        def __call__(self, *args):
            programs.append(args)
            return real(*args)

    monkeypatch.setattr(tpu, "_search_full_fused", Spy())
    monkeypatch.setattr(idx, "_search_full_gmin",
                        lambda *a, **k: pytest.fail("the kernel ran"))
    for mod in (tpu, gmin_scan):
        monkeypatch.setattr(mod, "record_device_fallback",
                            lambda *a, **k: fallbacks.append(a))
    monkeypatch.setattr(incidents, "emit",
                        lambda *a, **k: fallbacks.append(a))

    q = vecs[:16] + 0.001
    ids, dists = idx.search_by_vectors(q, 5)
    np.testing.assert_array_equal(ids[:, 0], np.arange(16, dtype=np.uint64))
    assert len(programs) == 1
    assert programs[0][0] is snap.rescore_dev      # the bf16 rows
    assert programs[0][-1] is True                  # candidates
    assert idx._blk_cache == {}
    assert idx._gmin_validated == set() and idx._gmin_shape_broken == set()
    assert fallbacks == []
    assert idx.health()["kernels"]["gmin"]["dispatches"] == {
        "gmin": 0, "scan": 1, "declined_slower": 1}
    assert idx.health()["kernels"]["gmin"]["validated"] == 0
    # what the device holds is the snapshot's arrays and nothing beside them
    assert led.device_components() == {
        "tombs": snap.tombs.nbytes,
        "slot_to_doc": snap.slot_to_doc_dev.nbytes,
        "pq_codes": snap.codes.nbytes,
        "recon_norms": snap.recon_norms.nbytes,
        "rescore_store": snap.rescore_dev.nbytes,
        "rescore_sq_norms": snap.rescore_sq_norms.nbytes,
    }


def test_a_narrow_compressed_store_keeps_the_kernel(tmp_path):
    """Under the cut the compressed tier is served as before: the kernel,
    over a block copy of the bf16 rows."""
    idx, vecs = _mk_index(tmp_path / "pq", n=700, d=32,
                          pq={**_PQ, "segments": 4})
    assert idx.compressed
    ids, _ = idx.search_by_vectors(vecs[:16] + 0.001, 5)
    np.testing.assert_array_equal(ids[:, 0], np.arange(16, dtype=np.uint64))
    assert len(idx._blk_cache) == 1 and len(idx._gmin_validated) == 1
    assert idx.scan_programs.as_dict() == {
        "gmin": 1, "scan": 0, "declined_slower": 0}


def test_a_scan_nobody_declined_is_not_a_decline(tmp_path):
    """A batch under 8 rows and `exactTopK` never reach the choice: `scan`
    counts them, `declined_slower` does not."""
    idx, vecs = _mk_index(tmp_path / "a")
    idx.search_by_vectors(vecs[:3], 5)
    assert idx.scan_programs.as_dict() == {
        "gmin": 0, "scan": 1, "declined_slower": 0}
    rng = np.random.default_rng(1)
    exact = TpuVectorIndex(vi.HnswUserConfig.from_dict(
        {"distance": "l2-squared", "exactTopK": True}, "hnsw_tpu"),
        str(tmp_path / "e"), persist=False)
    exact.add_batch(np.arange(600),
                    rng.standard_normal((600, 32)).astype(np.float32))
    exact.flush()
    exact.search_by_vectors(vecs[:16], 5)
    assert exact.scan_programs.as_dict() == {
        "gmin": 0, "scan": 1, "declined_slower": 0}


def test_a_mosaic_rejection_is_still_a_fallback_and_counts_as_scan(
        tmp_path, monkeypatch):
    idx, vecs = _mk_index(tmp_path / "a")
    fallbacks = []
    monkeypatch.setattr(gmin_scan, "record_device_fallback",
                        lambda *a, **k: fallbacks.append(a))
    monkeypatch.setattr(
        idx, "_search_full_gmin",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("Mosaic says no")))
    ids, _ = idx.search_by_vectors(vecs[:16], 5)
    assert ids.shape == (16, 5)
    assert [f[:2] for f in fallbacks] == [("index.tpu.gmin", "mosaic_reject")]
    assert idx.scan_programs.as_dict() == {
        "gmin": 0, "scan": 1, "declined_slower": 0}


@pytest.mark.parametrize("metric", [vi.DISTANCE_L2, vi.DISTANCE_COSINE])
def test_a_store_the_kernel_serves_answers_bit_for_bit_as_the_kernel_alone(
        tmp_path, metric):
    """What the index serves where the kernel still serves is the kernel's
    program with the arguments it always had: called directly with them, it
    gives the same packed bytes."""
    from weaviate_tpu import device
    from weaviate_tpu.ops.topk import unpack_fused

    idx, vecs = _mk_index(tmp_path / "a", metric=metric)
    q = vecs[:16] + 0.01
    got_ids, got_d = idx.search_by_vectors(q, 5)
    assert idx.scan_programs.gmin == 1
    snap = idx._read_snapshot()[0]
    qp, b = idx._prep_queries_staged(q)
    packed = gmin_scan.search_gmin_fused(
        snap.store, snap.sq_norms, snap.tombs, snap.n, np.array(qp),
        np.zeros((snap.capacity // 32,), np.uint32), snap.slot_to_doc_dev,
        False, 5, metric, *plan_search(idx._plan_view(snap), 16, 16, 5).gmin,
        device.pallas_interpret(),
        gmin_scan.build_rescore_blocks(snap.store))
    want_ids, want_d = unpack_fused(np.asarray(packed))
    np.testing.assert_array_equal(got_ids, want_ids[:b])
    np.testing.assert_array_equal(got_d, want_d[:b])


def test_counts_lose_no_update_under_many_threads():
    import os
    import sys
    import threading

    counts = gmin_scan.ProgramCounts()
    workers, each = 4 * (os.cpu_count() or 4), 2000

    def work(i):
        for _ in range(each):
            counts.count("gmin" if i % 2 else "scan")
            if not i % 2:
                counts.declined()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    half = workers // 2 * each
    assert counts.as_dict() == {"gmin": half, "scan": half,
                                "declined_slower": half}
