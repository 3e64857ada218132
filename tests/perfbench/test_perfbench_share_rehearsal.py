"""cohere-768-cos-10m-share rehearsed on the CPU: the configuration's own
class, width and traffic at 20,000 rows, through the harness end to end,
under a device budget that holds the test's slab once and not twice (the CPU
reports no memory limit, so the throw-away configuration states one through
the server's own `MEMORY_DEVICE_BUDGET_BYTES`; on the chip the cell sets
nothing and the ledger reads the allocator's limit). The build then writes in
place once the slab has outgrown the room for a copy, the restart lands every
chunk in place, and the two metrics this configuration brought say so. Then
the configuration and the cell as `BENCHMARK.json` declares them."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "throwaway_share")
CELL = "tiny-768-cos-share.batch256"
REAL = "cohere-768-cos-10m-share.batch256"
NEW = ("slab_fill_pct", "restore_copied_over_slab")
PLAIN_768 = [REAL, "cohere-768-cos.batch256",
             "cohere-768-cos-upsert.batch256-w500"]


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(ROOT, "BENCHMARK.json"), ROOT)
    s.validate()
    return s


def test_the_throw_away_runs_correct_and_reads_the_new_metrics(
        spec, tmp_path_factory):
    state_root = str(tmp_path_factory.mktemp("states"))
    seed = 2 ** 31 + 39
    res = bench_run.run(CELL, seed=seed, seconds=3.0, trace=True,
                        expect_platform="cpu", spec=spec,
                        state_root=state_root, t0=time.monotonic())
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    compared = res["compared"]
    assert compared["live_rows"] == {"value": 20000, "limit": "== 20000"}
    assert compared["recall"]["value"] >= 0.99
    for name in ("short_replies", "bad_distances", "unknown_rows",
                 "fallback_answers", "failed_requests"):
        assert compared[name] == {"value": 0, "limit": "== 0"}, name
    metrics = res["metrics"]
    # 20,000 rows in the 32,768 slots the ladder's one doubling gives, after
    # the import and after the restart alike
    assert metrics["slab_fill_pct"]["value"] == pytest.approx(
        100.0 * 20000 / 32768)
    # the restart's one grow is the only whole array it made
    assert 0 < metrics["restore_copied_over_slab"]["value"] <= 1.0
    with open(os.path.join(bench_run.OUT_DIR,
                           f"{CELL}-seed{seed}-trace1.json")) as f:
        obs = json.load(f)["observations"]
    startup = obs["perf"]["startup"]
    assert startup["grows"] == 1
    assert startup["slab_bytes"] == 32768 * 768 * 4
    assert startup["slab_bytes_copied"] == startup["slab_bytes"]
    assert obs["capacity"] == 32768


def test_the_configuration_is_one_chips_share_of_the_10m_corpus():
    spec = Spec()
    spec.validate()
    cfg = spec.config("cohere-768-cos-10m-share")
    pair = spec.config("cohere-768-cos")
    entry = spec.configs["cohere-768-cos-10m-share"]
    # cohere-768-cos's class, property, pool, k and reference
    for key in ("dim", "distance", "k", "pool", "reference", "chips",
                "filter_buckets", "class"):
        assert cfg[key] == pair[key], key
    assert (cfg["dim"], cfg["distance"], cfg["rows"], cfg["chips"]) == \
        (768, "cosine", 2_500_000, 1)
    assert cfg["data_seed"] not in {
        spec.config(c)["data_seed"] for c in spec.configs
        if c != "cohere-768-cos-10m-share"}
    assert cfg["env"] == {} and cfg["architecture"] is None
    assert entry["reduced"] == ["rows", "k"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "four shards" in cfg["reduced"]["rows"]
    assert {"durability", "answers", "consistency",
            "writes_at_fill"} <= set(cfg["guarantees"])
    assert {"rows_generator", "storage", "queries", "traffic"} \
        <= set(cfg["assumed"])
    # 20 scan chunks, filled to 95.4%: half the chip
    capacity = 20 * 131072
    assert cfg["rows"] / capacity == pytest.approx(0.954, abs=1e-3)
    assert capacity * 768 * 4 / (15.75 * 2 ** 30) == pytest.approx(
        0.476, abs=1e-3)
    cell = spec.workload(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cohere-768-cos-10m-share", "batch256", 1)
    reported = {m["name"] for which in ("end_to_end", "per_layer")
                for m in spec.metrics_for(REAL, which)}
    assert {"qps", "p50_ms", "recall", "setup_s", "batch_p95_ms",
            "hydrate_ms", "idle_hydrate_pct", "scan_roofline",
            "hbm_used_pct", "hbm_peak_pct", "hbm_peak_restore_pct",
            *NEW} <= reported
    for name in NEW:
        m = next(m for m in spec.doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == PLAIN_768 and m["moves"] == "setup_s"
        assert m["source"] == "program_counter"
        assert spec.layer_metric(name)["reader"] == "debug_json"
    # the cell is the last entry of every list it joined
    for table in (spec.doc["end_to_end"], spec.doc["per_layer"]):
        for m in table:
            if REAL in m.get("workloads", ()) and m["name"] not in NEW:
                assert m["workloads"][-1] == REAL, m["name"]
