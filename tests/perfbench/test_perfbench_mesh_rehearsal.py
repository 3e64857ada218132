"""The mesh deployment's whole command rehearsed on the CPU at 20,000 x 768
over four virtual devices (`XLA_FLAGS=--xla_force_host_platform_device_count=4`
in the children's environment), through the test-only entry that expects
`cpu`: build through `hnsw_tpu_mesh`, clean stop, recovery that re-balances
the log over the mesh with live == rows, `correct` against `exact_f32`, and
the traced path with the three metrics the mesh cell brings. This is the CPU
comparison of system and reference through the normal path. The tiny
configuration is no cell of the benchmark."""

import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

THROWAWAY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "throwaway_mesh")
CELL = "tiny-768-cos-mesh4.batch256"
ROWS = 20_000
MESH_METRICS = ("collective_ms", "chip_busy_spread_pct",
                "hbm_peak_fullest_pct")


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(THROWAWAY, "BENCHMARK.json"), THROWAWAY)
    s.validate()
    return s


@pytest.fixture(scope="module")
def four_devices():
    """The children (build, server) start with four CPU devices; this
    process's own backend is up already and does not change."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        yield


@pytest.fixture(scope="module")
def state_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("mesh_states"))


def _run(spec, state_root, trace, monkeypatch):
    """-> (result, every /debug/index page of the class the harness read)."""
    pages = []
    real = bench_run.index_health

    def spy(server, cls):
        pages.append(real(server, cls))
        return pages[-1]

    monkeypatch.setattr(bench_run, "index_health", spy)
    res = bench_run.run(CELL, seed=2 ** 31 + 5, seconds=2.0, trace=trace,
                        expect_platform="cpu", spec=spec,
                        state_root=state_root, t0=time.monotonic())
    return res, pages


def test_mesh_cell_builds_recovers_on_four_devices_and_is_correct(
        spec, four_devices, state_root, monkeypatch):
    res, pages = _run(spec, state_root, False, monkeypatch)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3
    m = res["metrics"]
    assert set(m) == {"qps", "p50_ms", "recall", "setup_s"}
    assert m["recall"]["value"] >= 0.95 and m["qps"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 4
    # after recovery, and again after the window: one shard, one mesh index
    # over four devices, every one of them holding rows, none lost
    assert len(pages) == 2
    for page in pages:
        assert page["type"] == "hnsw_tpu_mesh" and page["devices"] == 4
        assert page["live"] == ROWS
        rows = [d["rows"] for d in page["per_device"]]
        assert len(rows) == 4 and min(rows) > 0 and sum(rows) == ROWS
        assert max(rows) - min(rows) <= 4       # level-filled
        assert page["capacity"] == 4 * page["rows_per_device"]


def test_traced_mesh_cell_returns_the_mesh_metrics_or_leaves_them_out(
        spec, four_devices, state_root, monkeypatch):
    """Runs second: finds the state directory the first test built."""
    manifest = os.path.join(state_root, "tiny-768-cos-mesh4", "manifest.json")
    before = os.path.getmtime(manifest)
    res, _ = _run(spec, state_root, True, monkeypatch)
    assert os.path.getmtime(manifest) == before               # no rebuild
    assert res["correct"] is True and res["failed"] == 0
    m = res["metrics"]
    for name in ("batch_p95_ms", "slow_share", "hydrate_ms", "fetch_wait_ms",
                 "compiles_in_window"):
        assert name in m, (name, sorted(m))
    assert m["compiles_in_window"]["value"] == 0
    # a CPU trace has no device plane and the CPU allocator reports nothing:
    # the three are left out, not filled from estimates, and nothing raised
    for name in MESH_METRICS + ("device_idle_pct", "scan_device_ms",
                                "scan_roofline", "hbm_used_pct"):
        assert name not in m, name
    for name in MESH_METRICS:
        f = spec.layer_metric(name)
        read = spec.reader(f["reader"]).read
        for sources in ({}, {"xplane": {}}, {"debug_memory": {"device": {}}}):
            assert read(dict(sources, cell={"device_kind": "TPU v5 lite"}),
                        **f["params"]) is None
