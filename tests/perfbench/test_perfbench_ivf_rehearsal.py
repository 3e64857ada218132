"""cohere-768-cos-ivf rehearsed on the CPU: the configuration's own class,
width and traffic (`single-c20`: 20 closed-loop clients, one Search each) at
20,000 rows in 64 partitions, through the harness end to end. The build
trains the tiled layout under the puts, on the device, and writes it beside
the vector log; the server of the window restores it, trains nothing, and
answers every request from the probed program; every metric the cell brought
reads a number. Then the configuration and the cell as `BENCHMARK.json`
declares them."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "throwaway_ivf")
CELL = "tiny-768-cos-ivf.single-c20"
REAL = "cohere-768-cos-ivf.single-c20"
NEW = ("ivf_probed_fraction", "ivf_roofline", "ivf_declined", "startup_ivf_s")
# `qps`, `hydrate_ms` and `idle_hydrate_pct` wait for a `benchmark` PR: the
# share's rehearsal (a file of the benchmark) holds its cell to be the last
# of every list it joined (PERF.md section 7)
JOINED = ("queries_per_dispatch", "p99_ms")


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(ROOT, "BENCHMARK.json"), ROOT)
    s.validate()
    return s


def test_the_throw_away_serves_single_c20_from_a_restored_layout(
        spec, tmp_path_factory):
    state_root = str(tmp_path_factory.mktemp("states"))
    seed = 2 ** 31 + 43
    res = bench_run.run(CELL, seed=seed, seconds=1.5, trace=True,
                        expect_platform="cpu", spec=spec,
                        state_root=state_root, t0=time.monotonic())
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 20
    compared = res["compared"]
    assert compared["live_rows"] == {"value": 20000, "limit": "== 20000"}
    assert compared["recall"]["value"] >= 0.95
    for name in ("short_replies", "bad_distances", "unknown_rows",
                 "fallback_answers", "failed_requests"):
        assert compared[name] == {"value": 0, "limit": "== 0"}, name
    # the layout is beside the vector log, written by the build
    shards = os.path.join(state_root, "tiny-768-cos-ivf", "data")
    found = [os.path.join(d, f) for d, _, fs in os.walk(shards) for f in fs
             if f == "ivf.npz"]
    assert len(found) == 1 and os.path.exists(
        os.path.join(os.path.dirname(found[0]), "vector.log"))
    with open(os.path.join(bench_run.OUT_DIR,
                           f"{CELL}-seed{seed}-trace1.json")) as f:
        obs = json.load(f)["observations"]
    perf = obs["perf"]
    # the restart read the layout and trained nothing, before or inside the
    # first search; every dispatch of the window was a probed one
    assert perf["programs"]["ivf_trainings"] == 0
    assert perf["programs"]["ivf_declined"] == 0
    assert perf["startup"]["seconds"]["ivf"] > 0
    assert "ivf" in perf["startup"]["stages"]
    block = perf["ivf"]
    assert block["dispatches"] == perf["dispatches"] > 0
    assert (block["nlist"], block["top_p"]) == (64, 8)
    assert block["probed_rows"] == block["dispatches"] * (
        8 * block["cap_p"] + 64)
    assert block["base_rows"] == block["dispatches"] * 20000
    assert perf["tiers"] == {"exact_scan": perf["dispatches"]}
    metrics = res["metrics"]
    assert metrics["queries_per_dispatch"]["value"] == 1.0
    assert metrics["ivf_probed_fraction"]["value"] == pytest.approx(
        (8 * block["cap_p"] + 64) / 20000)
    assert metrics["ivf_declined"]["value"] == 0
    assert metrics["startup_ivf_s"]["value"] == \
        perf["startup"]["seconds"]["ivf"]
    assert metrics["compiles_in_window"]["value"] == 0
    # a CPU trace has no TPU plane to time the program on: the reader says
    # so and reports nothing (the chip's reading: PERF.md section 5)
    assert "ivf_roofline" not in metrics


def test_the_configuration_is_the_source_case_as_it_is_run():
    spec = Spec()
    spec.validate()
    cfg = spec.config("cohere-768-cos-ivf")
    pair = spec.config("cohere-768-cos")
    entry = spec.configs["cohere-768-cos-ivf"]
    # cohere-768-cos's corpus, seed, class, pool, k and reference: the same
    # rows under the two tiers
    for key in ("dim", "distance", "rows", "k", "pool", "reference", "chips",
                "filter_buckets", "class", "data_seed"):
        assert cfg[key] == pair[key], key
    assert cfg["env"] == {"IVF_ENABLED": "true", "IVF_NLIST": "4096",
                          "IVF_TOP_P": "64"}
    # buckets' rows and queries, behind the question that a program without
    # a durable layout fails (the test below)
    assert cfg["dataset"] == "buckets_durable_layout" and "dataset" not in pair
    ours, theirs = spec.dataset(cfg), spec.dataset(pair)
    assert ours.filter_plan(cfg, None) == theirs.filter_plan(pair, None)
    assert ours.properties(cfg, np.arange(25)) == \
        theirs.properties(pair, np.arange(25))
    assert entry["reduced"] == ["k"] == list(cfg["reduced"])
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert "as it is run" in cfg["source"]
    assert "approximate by declaration" in cfg["guarantees"]["answers"]
    assert "covers every live row" in cfg["guarantees"]["durability"]
    assert {"rows_generator", "operating_point", "device_memory", "traffic",
            "coalescer"} <= set(cfg["assumed"])
    cell = spec.workload(REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cohere-768-cos-ivf", "single-c20", 1)
    assert len(cell["why"]) <= 200
    traffic = spec.traffic("single-c20")
    assert (traffic["generator"], traffic["callers"], traffic["request"],
            traffic["width"], traffic["limit"], traffic["where"],
            traffic["distinct_requests"], traffic["timeout_s"],
            traffic["tail_percentile"]) == \
        ("closed", 20, "Search", 1, 10, None, 32, 30.0, 99)
    reported = {m["name"] for which in ("end_to_end", "per_layer")
                for m in spec.metrics_for(REAL, which)}
    assert {"p50_ms", "recall", "setup_s", "scan_device_ms",
            "device_idle_pct", *JOINED, *NEW} <= reported
    for name in NEW:
        m = next(m for m in spec.doc["per_layer"] if m["name"] == name)
        assert m["workloads"] == [REAL]
        assert m["moves"] == ("setup_s" if name == "startup_ivf_s"
                              else "p50_ms")
    assert spec.layer_metric("ivf_roofline")["reader"] == "xplane_ops"
    for name in set(NEW) - {"ivf_roofline"}:
        assert spec.layer_metric(name)["reader"] == "debug_json"
    # the cell is the last entry of every list it joined, and of the cells
    for table in (spec.doc["end_to_end"], spec.doc["per_layer"]):
        for m in table:
            if REAL in m.get("workloads", ()):
                assert m["workloads"][-1] == REAL, m["name"]
    assert spec.doc["workloads"][-1]["name"] == REAL
    assert spec.doc["configs"][-1]["name"] == "cohere-768-cos-ivf"


def test_a_program_without_a_durable_layout_is_refused_before_any_build(
        monkeypatch, capsys):
    """The parent of PR 43 starts this configuration and trains on the host:
    1,004 s of build, 186 s inside every restart (PERF.md section 6). The
    configuration's dataset asks the program for the name of its layout file
    when it is loaded, before `ensure_state`, and a program that has none
    ends the run at once with exit code 1 and no result line."""
    from weaviate_tpu.config import config as program
    from weaviate_tpu.index import tpu

    spec = Spec()
    cfg = spec.config("cohere-768-cos-ivf")
    assert spec.dataset(cfg).LAYOUT_FILE == program.IVF_LAYOUT_FILE \
        == tpu.IVF_LAYOUT_FILE == "ivf.npz"
    built = []
    monkeypatch.setattr(bench_run, "ensure_state",
                        lambda *a, **k: built.append(a))
    monkeypatch.delattr(program, "IVF_LAYOUT_FILE")
    with pytest.raises(RuntimeError, match="keeps no trained layout durable"):
        bench_run.main(["--workload", REAL, "--seed", "1", "--seconds", "1"])
    assert not built
    assert '"correct"' not in capsys.readouterr().out
    # the accepted configurations ask nothing of the program
    assert spec.dataset(spec.config("cohere-768-cos")).__name__.endswith(
        "datasets_buckets")
