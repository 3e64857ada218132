"""The whole command rehearsed on the CPU at 20,000 rows, through the
test-only entry that expects `cpu`: build, clean stop, recovery with
live == rows, warm-up, a 2 s window, the traced path reading /debug/perf and
/debug/traces. The tiny configuration is no cell of the benchmark."""

import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

THROWAWAY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "throwaway")
FILTERED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "throwaway_filtered")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(THROWAWAY, "BENCHMARK.json"), THROWAWAY)
    s.validate()
    return s


@pytest.fixture(scope="module")
def state_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("states"))


def _run(spec, state_root, cell, trace):
    return bench_run.run(cell, seed=1, seconds=2.0, trace=trace,
                         expect_platform="cpu", spec=spec,
                         state_root=state_root, t0=time.monotonic())


def test_closed_loop_cell_builds_recovers_and_measures(spec, state_root):
    res = _run(spec, state_root, "tiny-128-l2.batch256", trace=False)
    assert set(res) == LINE_KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 3
    assert res["compared"]["recall"] == {
        "value": res["metrics"]["recall"]["value"], "limit": ">= 0.95"}
    assert res["compared"]["disallowed_rows"] == {"value": 0, "limit": "== 0"}
    m = res["metrics"]
    assert set(m) == {"qps", "p50_ms", "recall", "setup_s"}
    assert m["recall"]["value"] >= 0.95 and m["qps"]["unit"] == "queries/s"
    assert m["qps"]["value"] > 0 and m["p50_ms"]["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["memory_peak_bytes"] > 20_000 * 128 * 4
    manifest = os.path.join(state_root, "tiny-128-l2", "manifest.json")
    assert os.path.isfile(manifest)


def test_traced_open_loop_cell_reads_the_programs_pages(spec, state_root):
    """Runs second: finds the state directory the first test built."""
    before = os.path.getmtime(os.path.join(state_root, "tiny-128-l2",
                                           "manifest.json"))
    res = _run(spec, state_root, "tiny-128-l2.single", trace=True)
    assert os.path.getmtime(os.path.join(
        state_root, "tiny-128-l2", "manifest.json")) == before   # no rebuild
    assert set(res) - {"breakdown"} == LINE_KEYS
    rate = spec.traffic(spec.workload("tiny-128-l2.single")["traffic"])[
        "rate_per_s"]
    assert res["failed"] == 0 and res["attempted"] > rate
    # 2 s at that rate cannot carry a p99: the run says so and is not correct
    assert res["correct"] is False
    m = res["metrics"]
    for name in ("gen_late_ms", "offered_qps", "p99_ms", "slow_share",
                 "entry_self_ms",
                 "queries_per_dispatch", "fetch_wait_ms",
                 "compiles_in_window"):
        assert name in m, (name, sorted(m))
    assert m["queries_per_dispatch"]["value"] == 1.0
    assert m["compiles_in_window"]["value"] == 0
    assert 0.6 * rate < m["offered_qps"]["value"] < 1.5 * rate
    # no device plane in a CPU trace: the device metrics are left out, not
    # filled from the program's estimates
    for name in ("device_idle_pct", "scan_device_ms", "scan_roofline"):
        assert name not in m
    assert "busy_s" not in res["device"]


def test_a_filter_plan_is_traffic_on_a_state_directory_that_is_there(
        state_root):
    """Runs third: the state directory the first test built serves a mix
    that gives every query its own 10% filter. Nothing is rebuilt; the
    plan's ground truth is computed once and kept beside the unfiltered
    one."""
    spec = Spec(os.path.join(FILTERED, "BENCHMARK.json"), FILTERED)
    state = os.path.join(state_root, "tiny-128-l2")
    before = os.path.getmtime(os.path.join(state, "manifest.json"))
    res = _run(spec, state_root, "tiny-128-l2.bucket-each", trace=False)
    assert os.path.getmtime(os.path.join(state, "manifest.json")) == before
    plans = [f for f in os.listdir(state) if f.startswith("plan-")]
    assert sorted(f[-4:] for f in plans) == [".npz", "json"]
    assert os.path.isfile(os.path.join(state, "gt_ids.npy"))
    assert res["correct"] is True, res["compared"]
    assert res["metrics"]["recall"]["value"] >= 0.95
    assert res["compared"]["disallowed_rows"]["value"] == 0
    assert res["compared"]["short_replies"]["value"] == 0
