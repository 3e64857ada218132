"""cohere-768-cos-pq rehearsed on the CPU: the configuration's own class
(`pq.enabled`, segments 96, centroids 256), width and traffic at 20,000 rows
through the harness end to end: built through `put_batch` (the declared class
compresses at its `trainingLimit` under the puts), shut down, RECOVERED
straight into the compressed form, searched by `BatchSearch` of 256, every
reply held to exact float32 brute force over the uncompressed rows. Then the
configuration as `BENCHMARK.json` declares it."""

import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "throwaway_pq")
CELL = "tiny-768-cos-pq.batch256"


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(ROOT, "BENCHMARK.json"), ROOT)
    s.validate()
    return s


def test_the_cell_builds_restarts_compressed_and_is_correct(
        spec, tmp_path_factory):
    state_root = str(tmp_path_factory.mktemp("states"))
    res = bench_run.run(CELL, seed=2 ** 31 + 32, seconds=2.0, trace=True,
                        expect_platform="cpu", spec=spec,
                        state_root=state_root, t0=time.monotonic())
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    compared = res["compared"]
    assert compared["recall"]["value"] >= 0.99
    for name in ("short_replies", "bad_distances", "unknown_rows",
                 "fallback_answers", "rejected_kernel_shapes"):
        assert compared[name] == {"value": 0, "limit": "== 0"}, name
    metrics = res["metrics"]
    # 256 queries a dispatch, 40 candidates each scored from the host's rows
    assert metrics["rescore_rows"]["value"] == 256 * 40
    assert metrics["rescore_ms"]["value"] > 0
    assert metrics["hydrate_ms"]["value"] > 0


def test_the_configuration_is_the_sources_deployment():
    spec = Spec()
    spec.validate()
    cfg = spec.config("cohere-768-cos-pq")
    entry = spec.configs["cohere-768-cos-pq"]
    mesh = spec.config("cohere-768-cos-mesh4")
    # the same corpus on one chip and on four: compression and chips differ
    for key in ("dim", "distance", "rows", "k", "pool", "data_seed",
                "reference", "filter_buckets"):
        assert cfg[key] == mesh[key], key
    assert (cfg["dim"], cfg["distance"], cfg["rows"], cfg["chips"]) == \
        (768, "cosine", 2_000_000, 1)
    vic = cfg["class"]["vectorIndexConfig"]
    assert cfg["class"]["vectorIndexType"] == "hnsw_tpu"
    assert vic["pq"] == {"enabled": True, "segments": 96, "centroids": 256}
    assert entry["reduced"] == ["rows", "k"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "float32 distance of the uncompressed row" in \
        cfg["guarantees"]["answers"]
    cell = spec.workload("cohere-768-cos-pq.batch256")
    assert (cell["traffic"], cell["chips"]) == ("batch256", 1)
    reported = {m["name"] for which in ("end_to_end", "per_layer")
                for m in spec.metrics_for(cell["name"], which)}
    assert {"qps", "p50_ms", "recall", "setup_s", "batch_p95_ms",
            "hydrate_ms", "idle_hydrate_pct", "scan_roofline", "rescore_ms",
            "rescore_rows", "idle_rescore_pct", "hbm_peak_pct"} <= reported
    for name in ("rescore_ms", "rescore_rows", "idle_rescore_pct"):
        m = next(m for m in spec.doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == ["cohere-768-cos-pq.batch256"]
        assert m["moves"] == "qps"
