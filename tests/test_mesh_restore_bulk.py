"""What the four-chip deployment forced in `hnsw_tpu_mesh` (PR 25): a log
replay lands a long run of adds straight from the log's rows, in whole-mesh
insert steps on slabs sized once; a pinned `meshDevices` is never served
from fewer chips; and the mesh dispatch says how many chips it spans."""

import numpy as np
import pytest

from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import mesh as mesh_mod
from weaviate_tpu.index.mesh import MeshVectorIndex
from weaviate_tpu.monitoring import perf, tracing
from weaviate_tpu.parallel.mesh_search import make_mesh

DIM = 32


def _cfg(metric="cosine", **kw):
    return parse_and_validate_config("hnsw_tpu_mesh",
                                     {"distance": metric, **kw})


def _rows(n, seed=3):
    return np.random.default_rng(seed).standard_normal(
        (n, DIM)).astype(np.float32)


@pytest.mark.parametrize("metric", ["cosine", "l2-squared"])
@pytest.mark.parametrize("n_dev", [4, 8])
def test_a_long_run_of_the_log_lands_without_staging(tmp_path, monkeypatch,
                                                     metric, n_dev):
    n = 2 * mesh_mod._FLUSH_CHUNK + 1234      # one run, longer than a flush
    vecs = _rows(n)
    idx = MeshVectorIndex(_cfg(metric, meshDevices=n_dev), str(tmp_path))
    for s in range(0, n, 5000):
        idx.add_batch(np.arange(s, min(s + 5000, n)), vecs[s:s + 5000])
    idx.flush()
    want_ids, want_d = idx.search_by_vectors(vecs[:64] + 0.01, 5)
    idx.shutdown()

    staged = []
    real = MeshVectorIndex._flush_pending

    def spy(self):
        staged.append(len(self._pending))
        return real(self)

    monkeypatch.setattr(MeshVectorIndex, "_flush_pending", spy)
    steps = []
    real_step = mesh_mod.mesh_insert_step

    def step_spy(store, *a):
        steps.append(store.shape[0] // n_dev)
        return real_step(store, *a)

    monkeypatch.setattr(mesh_mod, "mesh_insert_step", step_spy)
    back = MeshVectorIndex(_cfg(metric, meshDevices=n_dev), str(tmp_path))
    # every row is on a device before anything asks for a flush, none of
    # them went through the staging dict, and the slabs were sized once
    assert back.live == n and int(back._counts.sum()) == n
    assert not back._pending and max(staged, default=0) == 0
    assert back._counts.max() - back._counts.min() <= n_dev
    assert len(set(steps)) == 1 and steps[0] == back.n_loc
    assert len(steps) == -(-int(back._counts.max()) // mesh_mod._MAX_WRITE_C)
    got_ids, got_d = back.search_by_vectors(vecs[:64] + 0.01, 5)
    np.testing.assert_array_equal(got_ids[:, 0], want_ids[:, 0])
    np.testing.assert_allclose(got_d, want_d, atol=1e-5)
    back.shutdown()


def test_a_long_run_after_deletes_and_rewrites_restores_the_same_state(
        tmp_path):
    n = mesh_mod._FLUSH_CHUNK + 900
    vecs = _rows(n, seed=5)
    idx = MeshVectorIndex(_cfg("l2-squared", meshDevices=4), str(tmp_path))
    idx.add_batch(np.arange(300), vecs[:300])          # a short run: staged
    idx.delete(*range(0, 100, 3))
    idx.add_batch(np.arange(200, n), vecs[200:])       # rewrites 200..299
    idx.flush()
    live = idx.live
    want_ids, want_d = idx.search_by_vectors(vecs[150:250], 3)
    idx.shutdown()
    back = MeshVectorIndex(_cfg("l2-squared", meshDevices=4), str(tmp_path))
    assert back.live == live == n - len(range(0, 100, 3))
    got_ids, got_d = back.search_by_vectors(vecs[150:250], 3)
    np.testing.assert_array_equal(got_ids[:, 0], want_ids[:, 0])
    np.testing.assert_allclose(got_d, want_d, atol=1e-4)
    assert not back.contains(3) and back.contains(4)
    back.shutdown()


def test_a_pinned_mesh_is_never_served_from_fewer_devices(tmp_path):
    import jax

    have = len(jax.devices())
    assert make_mesh(have).devices.size == have
    assert make_mesh(None).devices.size == have
    with pytest.raises(ValueError, match=f"has {have}"):
        make_mesh(have + 1)
    with pytest.raises(ValueError):
        MeshVectorIndex(_cfg(meshDevices=have + 8), str(tmp_path),
                        persist=False)


@pytest.fixture
def annotations(monkeypatch):
    """Every `wv/*` annotation opened while the tracer is up, with the stats
    it ended with."""
    seen = []

    class Ann:
        def __init__(self, name, **stats):
            self.name, self.stats = name, dict(stats)
            seen.append(self)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **stats):
            self.stats.update(stats)

    monkeypatch.setattr(tracing, "_TraceMe", Ann)
    tracing.configure(tracing.Tracer())
    perf.configure(perf.PerfWindow())
    yield seen
    tracing.configure(None)
    perf.configure(None)


def test_the_mesh_dispatch_says_how_many_chips_it_spans(tmp_path,
                                                        annotations):
    vecs = _rows(600)
    idx = MeshVectorIndex(_cfg(meshDevices=4), str(tmp_path), persist=False)
    idx.add_batch(np.arange(600), vecs)
    idx.flush()
    handle = idx.search_by_vectors_async(vecs[:8], 3)
    handle()
    by_name = {a.name: a.stats for a in annotations}
    # since PR 36 also the depth the scan step ran at on every chip, since
    # PR 40 which of the two full-store programs ran (8 rows a chip's slab
    # of 64: under the kernel's smallest slab)
    assert by_name["wv/enqueue"] == {"rows": 8, "tier": "exact_scan",
                                     "ndev": 4, "rescore_r": 32,
                                     "program": "scan"}
    assert by_name["wv/device_wait"] == {"rows": 8, "tier": "exact_scan",
                                         "ndev": 4}
    assert by_name["wv/gather_hop"] == {"rows": 8}
    assert handle.shape.ndev == 4


def test_a_one_chip_dispatch_keeps_its_annotations_as_they_were(
        tmp_path, annotations):
    from weaviate_tpu.index.tpu import TpuVectorIndex

    vecs = _rows(600)
    idx = TpuVectorIndex(parse_and_validate_config(
        "hnsw_tpu", {"distance": "cosine"}), str(tmp_path), persist=False)
    idx.add_batch(np.arange(600), vecs)
    idx.flush()
    idx.search_by_vectors(vecs[:8], 3)
    waits = [a.stats for a in annotations if a.name == "wv/device_wait"]
    assert waits and all("ndev" not in s for s in waits)


def test_with_tracing_off_the_mesh_dispatch_opens_nothing(tmp_path,
                                                          monkeypatch):
    opened = []
    monkeypatch.setattr(tracing, "_TraceMe",
                        lambda name, **kw: opened.append(name))
    assert tracing.get_tracer() is None
    vecs = _rows(600)
    idx = MeshVectorIndex(_cfg(meshDevices=4), str(tmp_path), persist=False)
    idx.add_batch(np.arange(600), vecs)
    handle = idx.search_by_vectors_async(vecs[:8], 3)
    handle()
    assert opened == [] and handle.shape is None
