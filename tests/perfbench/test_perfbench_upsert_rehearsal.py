"""cohere-768-cos-upsert rehearsed on the CPU: the configuration's own class,
width, generator (`closed_with_writer`) and traffic at 20,000 rows and a rate
a CPU sustains, through the harness end to end, TWICE on one state directory:
the second run restarts on the first window's writes (durability: `live ==
rows` after a replay of deletes and re-adds) and finds the slots where the
first left them. Then the configuration and the cell as `BENCHMARK.json`
declares them, and `write_roofline`'s reader on a cut trace."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import costs
from benchmarks.lib.spec import Spec
from benchmarks.readers import xplane_write

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "throwaway_upsert")
CELL = "tiny-768-cos-upsert.batch256-w100"
REAL = "cohere-768-cos-upsert.batch256-w500"
NEW = ("write_ms", "write_index_ms", "read_lock_wait_ms", "write_device_ms",
       "write_roofline", "idle_write_pct", "slots_over_live")


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(ROOT, "BENCHMARK.json"), ROOT)
    s.validate()
    return s


def _observations(seed: int) -> dict:
    with open(os.path.join(bench_run.OUT_DIR,
                           f"{CELL}-seed{seed}-trace1.json")) as f:
        return json.load(f)["observations"]


def test_two_runs_on_one_state_are_correct_and_the_slots_stay(
        spec, tmp_path_factory):
    state_root = str(tmp_path_factory.mktemp("states"))
    runs = []
    for seed in (2 ** 31 + 37, 2 ** 31 + 38):
        res = bench_run.run(CELL, seed=seed, seconds=3.0, trace=True,
                            expect_platform="cpu", spec=spec,
                            state_root=state_root, t0=time.monotonic())
        assert res["correct"] is True, res["compared"]
        assert res["failed"] == 0 and res["attempted"] >= 4
        compared = res["compared"]
        assert compared["live_rows"] == {"value": 20000,
                                         "limit": "== 20000"}
        assert compared["recall"]["value"] >= 0.99
        for name in ("short_replies", "bad_distances", "unknown_rows",
                     "fallback_answers", "failed_requests"):
            assert compared[name] == {"value": 0, "limit": "== 0"}, name
        metrics = res["metrics"]
        # what the host's clock and the program's counters give on a CPU;
        # the three device_trace metrics need a chip's xplane (the reader of
        # the new one is held to a cut trace below)
        for name in ("write_ms", "write_index_ms", "read_lock_wait_ms",
                     "slots_over_live", "hydrate_ms"):
            assert metrics[name]["value"] >= 0, name
        assert metrics["write_ms"]["value"] > \
            metrics["write_index_ms"]["value"] > 0
        assert metrics["compiles_in_window"]["value"] == 0
        obs = _observations(seed)
        writer = obs["client"]["sender"]["writer"]
        assert writer["due"] == 3 and 2 <= writer["sent"] <= 3
        assert writer["acknowledged_in_window"] >= 2
        assert not writer["alive_at_return"]
        writes = obs["perf"]["writes"]
        assert writes["rows"] == 100 * writes["batches"] > 0
        # an upsert's new row takes its old row's slot: nothing appended,
        # no tombstone set, no slot left over
        assert writes["slots_reused"] == writes["rows"]
        assert writes["grows"] == 0
        # (the second run's restart lands the first window's re-puts, runs
        # of 100 live records, with its first flush after the restore)
        first_window = 100 * _observations(
            2 ** 31 + 37)["perf"]["writes"]["batches"]
        assert writes["slots_appended"] == 0 if not runs else \
            0.9 * first_window <= writes["slots_appended"] <= first_window
        assert set(writes["phases"]) == {
            "decode", "lsm", "index_lock_wait", "index", "device_write",
            "publish"}
        names = {i[0] for i in obs["perf"]["capture"]["intervals"]}
        assert {"write.batch", "write.decode", "write.lsm", "write.index",
                "write.device_write", "write.publish"} <= names
        runs.append(res)
    first, second = (r["metrics"]["slots_over_live"]["value"] for r in runs)
    assert second <= first == 1.0


def test_the_configuration_is_cohere_768_cos_under_a_stream():
    spec = Spec()
    spec.validate()
    cfg = spec.config("cohere-768-cos-upsert")
    pair = spec.config("cohere-768-cos")
    entry = spec.configs["cohere-768-cos-upsert"]
    # the pair cell differs in the writer alone
    for key in ("dim", "distance", "rows", "k", "pool", "data_seed",
                "reference", "chips", "filter_buckets"):
        assert cfg[key] == pair[key], key
    assert (cfg["dim"], cfg["distance"], cfg["rows"], cfg["chips"]) == \
        (768, "cosine", 1_000_000, 1)
    assert cfg["class"]["vectorIndexType"] == "hnsw_tpu"
    assert cfg["class"]["properties"] == pair["class"]["properties"]
    assert cfg["class"]["vectorIndexConfig"] == {
        "distance": "cosine", "cleanupIntervalSeconds": 300}
    assert cfg["architecture"] is None
    assert entry["reduced"] == ["k", "inserts"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert {"durability", "answers", "read_your_writes",
            "upsert_visibility"} <= set(cfg["guarantees"])
    cell = spec.workload(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cohere-768-cos-upsert", "batch256-w500", 1)
    traffic, reads = spec.traffic("batch256-w500"), spec.traffic("batch256")
    for key in ("callers", "request", "width", "limit", "distinct_requests",
                "timeout_s", "tail_percentile", "write_share"):
        assert traffic[key] == reads[key], key
    assert (traffic["generator"], traffic["write_batch"],
            traffic["write_rows_per_s"], traffic["distinct_writes"]) == \
        ("closed_with_writer", 100, 500, 128)
    reported = {m["name"] for which in ("end_to_end", "per_layer")
                for m in spec.metrics_for(REAL, which)}
    assert {"qps", "p50_ms", "recall", "setup_s", "batch_p95_ms",
            "hydrate_ms", "idle_hydrate_pct", "scan_roofline",
            *NEW} <= reported
    for name in NEW:
        m = next(m for m in spec.doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == [REAL] and m["moves"] == "qps"


def _sources(modules, writes):
    return {"xplane": {"/device:TPU:0": {
        "XLA Ops": [], "XLA Modules": modules}},
        "perf": {"writes": writes},
        "cell": {"device_kind": "TPU v5 lite", "dim": 768, "rows": 1_000_000,
                 "chips": 1}}


def test_write_roofline_reads_the_write_program_and_the_servers_rows():
    """A trace cut to the programs of one traced second of the cell on the
    chip (names and durations as my chip run of PR 37 read them)."""
    ms = 1_000_000
    modules = [("jit__write_slots(7318265912437715907)", 10 * ms, 8_150_000),
               ("jit__write_slots(7318265912437715907)", 210 * ms, 8_250_000),
               ("jit__write_slots(7318265912437715907)", 410 * ms, 8_200_000),
               ("jit__search_full_fused(123)", 20 * ms, 5_400_000),
               ("jit__set_tombstones(9)", 30 * ms, 20_000)]
    module = "write_slots|write_rows|write_norms|write_doc_pairs|set_tombstones"
    src = _sources(modules, {"rows": 500, "batches": 5})
    share = xplane_write.read(src, module)
    # 100 rows x 768 x 4 B over 819 GB/s, over the median execution's 8.2 ms
    least = 100 * 768 * 4 / costs.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert share == pytest.approx(100.0 * least / 8.2e-3, rel=1e-9)
    assert 0 < share < 100
    notes = src["notes"]
    assert notes["write_roofline_program"].startswith("jit__write_slots")
    assert notes["write_roofline_rows"] == 100
    assert notes["write_roofline_bound"] == "hbm"
    # never a constant: no account of the rows, no trace, no write program
    assert xplane_write.read(_sources(modules, {}), module) is None
    assert xplane_write.read(
        _sources(modules, {"rows": 0, "batches": 0}), module) is None
    assert xplane_write.read(
        _sources(modules[3:4], {"rows": 500, "batches": 5}), module) is None
    assert xplane_write.read({"perf": {"writes": {"rows": 1, "batches": 1}}},
                             module) is None


def test_bodies_encoded_by_the_pool_are_the_inline_ones(tmp_path):
    import numpy as np

    from benchmarks.lib import bodies

    path = str(tmp_path / "rows.f32")
    rows = np.memmap(path, np.float32, "w+", shape=(400, 24))
    rows[:] = np.random.default_rng(1).standard_normal((400, 24))
    rows.flush()
    jobs = [(path, (400, 24), "Bench", list(range(i, i + 10)),
             [{"bucket": j % 10} for j in range(10)])
            for i in range(0, 20 * 10, 10)]
    assert len(jobs) >= bodies.INLINE_BELOW
    pooled = bodies.encode_all(jobs)
    assert pooled == [bodies.encode(j) for j in jobs]
    first = json.loads(pooled[0])["objects"][0]
    assert first["id"] == "00000000-0000-0000-0000-000000000001"
    assert np.array_equal(np.array(first["vector"], np.float32), rows[0])
