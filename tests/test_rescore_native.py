"""The float32 rescoring of a compressed dispatch in one native pass
(native/rescore.cpp through index/rescore_native.py; index/tpu.py
`_rescore_f32`), held to the numpy path it replaced: `_host_distances` over
gathered rows and a stable argsort, which stays as the fallback."""

import threading

import numpy as np
import pytest

from weaviate_tpu.entities import vectorindex as vi
from weaviate_tpu.entities.vectorindex import parse_and_validate_config
from weaviate_tpu.index import rescore_native, tpu
from weaviate_tpu.index.tpu import TpuVectorIndex
from weaviate_tpu.monitoring import perf

METRICS = (vi.DISTANCE_COSINE, vi.DISTANCE_DOT, vi.DISTANCE_L2,
           vi.DISTANCE_MANHATTAN)
N = 3000
# float32 sums of up to 768 terms of order 1 in two orders: a few ulp of
# the largest partial sum
ATOL, RTOL = 2e-5, 2e-5


@pytest.fixture(scope="module", autouse=True)
def _library():
    if not rescore_native.load():
        pytest.skip("native/rescore.cpp does not build here")


def _rows(dim: int, metric: str, seed: int = 0) -> np.ndarray:
    v = np.random.default_rng(seed).standard_normal((N, dim)).astype(
        np.float32)
    if metric == vi.DISTANCE_COSINE:
        v /= np.linalg.norm(v, axis=1, keepdims=True)
    return v


def _inputs(b: int, r: int, dim: int, metric: str, seed: int = 1):
    g = np.random.default_rng(seed)
    q = g.standard_normal((b, dim)).astype(np.float32)
    if metric == vi.DISTANCE_COSINE:
        q /= np.linalg.norm(q, axis=1, keepdims=True)
    slots = g.integers(0, N, (b, r)).astype(np.int32)
    slots[g.random((b, r)) < 0.05] = -1
    slots[0, :] = -1          # a query with no candidate at all
    return q, slots


def _reference(vecs, slots, q, metric) -> np.ndarray:
    """The parent's `_rescore_f32`: gather, `_host_distances`, +inf at -1."""
    cand = vecs[np.maximum(slots, 0)]
    d = tpu._host_distances(cand, q, metric)
    d[slots < 0] = np.inf
    return d


@pytest.mark.parametrize("dim", [768, 100, 1])
@pytest.mark.parametrize("metric", METRICS)
def test_native_distances_are_the_numpy_paths(metric, dim):
    vecs = _rows(dim, metric)
    q, slots = _inputs(16, 40, dim, metric)
    got, why = rescore_native.distances(vecs, slots, q, metric)
    assert why is None and got.dtype == np.float32 and got.shape == (16, 40)
    want = _reference(vecs, slots, q, metric)
    assert np.array_equal(np.isinf(got), slots < 0)
    fin = slots >= 0
    np.testing.assert_allclose(got[fin], want[fin], rtol=RTOL, atol=ATOL)
    # the order the float32 distances give is the reference's, wherever the
    # reference tells two candidates apart by more than the rounding
    order = np.argsort(got, axis=1, kind="stable")
    along = np.take_along_axis(want, order, axis=1)
    gaps = np.diff(np.where(np.isinf(along), np.float32(3e38), along), axis=1)
    assert (gaps > -2 * ATOL).all()


@pytest.mark.parametrize("b", [1, 256, 7])
def test_same_bits_at_one_and_at_four_threads(b):
    """b = 7 x r = 9 pairs do not divide by four: the last range is short."""
    metric, r = vi.DISTANCE_COSINE, (40 if b != 7 else 9)
    vecs = _rows(768, metric)
    q, slots = _inputs(b, r, 768, metric, seed=b)
    one, _ = rescore_native.distances(vecs, slots, q, metric, threads=1)
    four, _ = rescore_native.distances(vecs, slots, q, metric, threads=4)
    again, _ = rescore_native.distances(vecs, slots, q, metric, threads=4)
    own, _ = rescore_native.distances(vecs, slots, q, metric)
    for other in (four, again, own):
        assert one.tobytes() == other.tobytes()
    want = _reference(vecs, slots, q, metric)
    fin = slots >= 0
    np.testing.assert_allclose(one[fin], want[fin], rtol=RTOL, atol=ATOL)


def test_the_thread_rule_follows_the_rows_of_the_call():
    """One thread for a narrow call whatever the machine; never more than
    four, nor than a quarter of the cores the process may use."""
    import os
    rule = rescore_native._lib.rescore_threads
    most = max(1, min(4, len(os.sched_getaffinity(0)) // 4))
    assert rule(40, 768) == 1
    assert rule(256 * 40, 768) == min(most, 256 * 40 * 768 * 4 >> 22)
    assert rule(1 << 24, 768) == most


def test_four_python_threads_at_once_equal_the_serial_answers():
    metric = vi.DISTANCE_L2
    vecs = _rows(768, metric)
    ins = [_inputs(64, 40, 768, metric, seed=10 + i) for i in range(4)]
    serial = [rescore_native.distances(vecs, s, q, metric)[0]
              for q, s in ins]
    got: list = [None] * 4
    start = threading.Barrier(4)

    def call(i):
        q, s = ins[i]
        start.wait(timeout=30)
        for _ in range(5):
            got[i] = rescore_native.distances(vecs, s, q, metric,
                                              threads=2)[0]

    ts = [threading.Thread(target=call, args=(i,)) for i in range(4)]
    [t.start() for t in ts]
    [t.join(timeout=60) for t in ts]
    assert not any(t.is_alive() for t in ts)
    for want, have in zip(serial, got):
        assert want.tobytes() == have.tobytes()


def test_what_the_call_cannot_serve_it_says():
    vecs = _rows(8, vi.DISTANCE_L2)
    q, slots = _inputs(4, 5, 8, vi.DISTANCE_L2)
    assert rescore_native.distances(vecs, slots, q, "hamming") == (
        None, "metric")
    assert rescore_native.distances(vecs[:, ::2], slots, q[:, ::2],
                                    vi.DISTANCE_L2) == (None, "layout")
    assert rescore_native.distances(vecs.astype(np.float64), slots, q,
                                    vi.DISTANCE_L2) == (None, "layout")
    assert rescore_native.distances(vecs, slots, q[:3],
                                    vi.DISTANCE_L2) == (None, "layout")
    # a slot no row has scores +inf, as a missing one: nothing is read
    slots[1, 0] = N + 5
    got, why = rescore_native.distances(vecs, slots, q, vi.DISTANCE_L2)
    assert why is None and np.isinf(got[1, 0])
    # a strided block of slots (the program's packed output) is served
    wide = np.full((4, 20), -7, np.int32)
    wide[:, 15:] = slots
    again, _ = rescore_native.distances(vecs, wide[:, 15:], q,
                                        vi.DISTANCE_L2)
    assert got.tobytes() == again.tobytes()


# -- through the index -------------------------------------------------------

_PQ = {"enabled": True, "trainingLimit": 256, "segments": 4, "centroids": 16,
       "rescore": True}


def _index(tmp_path, metric: str, vecs: np.ndarray, name="idx"):
    cfg = parse_and_validate_config("hnsw_tpu",
                                    {"distance": metric, "pq": _PQ})
    idx = TpuVectorIndex(cfg, str(tmp_path / name), persist=False)
    idx.add_batch(np.arange(len(vecs), dtype=np.int64), vecs)
    idx.flush()
    assert idx.compressed and idx._rescore_dev is not None
    return idx


@pytest.fixture
def window():
    w = perf.PerfWindow(window_s=60.0)
    perf.configure(w)
    try:
        yield w
    finally:
        perf.configure(None)


def _no_library(monkeypatch):
    """The loader returns nothing, as after a build that failed."""
    monkeypatch.setattr(rescore_native, "_lib", None)
    monkeypatch.setattr(rescore_native, "_lib_failed", True)


@pytest.mark.parametrize("metric", METRICS)
def test_a_dispatch_answers_as_the_numpy_path_does(tmp_path, monkeypatch,
                                                   window, metric):
    vecs = np.random.default_rng(3).standard_normal((600, 32)).astype(
        np.float32)
    idx = _index(tmp_path, metric, vecs)
    q = vecs[:9] + np.float32(0.01)
    ids, dists = idx.search_by_vectors(q, 10)
    assert window.summary()["rescore"]["by"] == {"native": 1}
    _no_library(monkeypatch)
    ids_np, dists_np = idx.search_by_vectors(q, 10)
    assert window.summary()["rescore"]["by"] == {
        "native": 1, "numpy:no_library": 1}
    np.testing.assert_allclose(dists, dists_np, rtol=RTOL, atol=ATOL)
    # the same winners, but where two of them lie within the rounding
    differ = ids != ids_np
    assert np.abs(dists - dists_np)[differ].max(initial=0.0) <= ATOL
    assert differ.mean() < 0.05


def test_candidates_that_tie_keep_the_scans_order(tmp_path, monkeypatch):
    """Every row the same vector: every candidate of every query ties, and
    the reply is the scan's first k in the scan's order on both paths."""
    vecs = np.tile(np.random.default_rng(5).standard_normal(
        (1, 32)).astype(np.float32), (400, 1))
    idx = _index(tmp_path, vi.DISTANCE_L2, vecs)
    q = np.random.default_rng(6).standard_normal((5, 32)).astype(np.float32)
    ids, dists = idx.search_by_vectors(q, 10)
    assert (dists == dists[:, :1]).all()
    _no_library(monkeypatch)
    ids_np, dists_np = idx.search_by_vectors(q, 10)
    np.testing.assert_array_equal(ids, ids_np)
    np.testing.assert_allclose(dists, dists_np, rtol=RTOL, atol=ATOL)


def test_the_native_path_checks_no_gather_buffer_out(tmp_path, monkeypatch):
    vecs = np.random.default_rng(7).standard_normal((600, 32)).astype(
        np.float32)
    idx = _index(tmp_path, vi.DISTANCE_COSINE, vecs)
    shapes = []
    checkout = idx._checkout_stage

    def spy(shape):
        shapes.append(shape)
        return checkout(shape)

    monkeypatch.setattr(idx, "_checkout_stage", spy)
    idx.search_by_vectors(vecs[:9], 10)
    assert shapes and all(len(s) == 2 for s in shapes)     # queries only
    assert all(len(key) == 2 for key in idx._stage_free)
    _no_library(monkeypatch)
    idx.search_by_vectors(vecs[:9], 10)
    assert [s for s in shapes if len(s) == 3] == [
        (9, idx._rescore_r(10, idx.n), 32)]


def test_entering_the_compressed_form_loads_the_library(tmp_path,
                                                        monkeypatch):
    """Built and loaded by the index's set-up: a request finds it there or
    serves through numpy, and never compiles."""
    from weaviate_tpu import _native
    calls = []
    real = _native.ensure_built
    monkeypatch.setattr(_native, "ensure_built",
                        lambda name: (calls.append(name), real(name))[1])
    monkeypatch.setattr(rescore_native, "_lib", None)
    monkeypatch.setattr(rescore_native, "_lib_failed", False)
    vecs = np.random.default_rng(8).standard_normal((600, 32)).astype(
        np.float32)
    idx = _index(tmp_path, vi.DISTANCE_DOT, vecs)
    assert calls == ["rescore"]
    idx.search_by_vectors(vecs[:3], 10)
    assert calls == ["rescore"]
    assert _native.STATUS["rescore"] in ("loaded", "built")
