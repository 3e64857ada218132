"""The nine per-layer metrics of a restart (PR 35), added by data files alone
over the accepted reader `debug_json`: each file loads, names a layer of
BENCHMARK.json letter for letter and reads a number from a page as the
program serves it; a page of the parent (no `startup`, no `compiles`) gives
nothing and does not raise. Then the whole command rehearsed on the CPU,
traced, through a throw-away set of cells that lists the nine."""

import json
import os
import time

import pytest

from benchmarks import run as bench_run
from benchmarks.lib.spec import ROOT, Spec

STARTUP = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "throwaway_startup")

# metric -> (layer, source, the value the page below gives)
NINE = {
    "startup_ready_s": ("Entry (grpc_server, reply_native)",
                        "program_span", 44.25),
    "startup_boot_s": ("Device", "program_span", 8.5),
    "startup_lsm_s": ("Shard (db/shard, storage/lsm)", "program_span", 3.25),
    "startup_log_s": ("Index, tier choice (index/tpu _dispatch_search)",
                      "program_span", 11.0),
    "startup_land_s": ("Index, tier choice (index/tpu _dispatch_search)",
                       "program_span", 17.5),
    "startup_drain_s": ("Device", "program_span", 0.0),
    "startup_unaccounted_s": ("Entry (grpc_server, reply_native)",
                              "program_span", 0.125),
    "startup_compile_s": ("Index, tier choice (index/tpu _dispatch_search)",
                          "program_counter", 5.75),
    "hbm_peak_restore_pct": ("Device", "program_counter", 93.75),
}

# `/debug/perf` as the program serves it, cut to what the files read
PAGE = {
    "enabled": True,
    "startup": {
        "anchor": "os",
        "seconds": {"boot": 8.5, "lsm": 3.25, "log": 11.0, "land": 17.5,
                    "drain": 0.0, "other": 3.875, "ready": 44.25,
                    "unaccounted": 0.125},
        "peak_at_restore_end_bytes": int(0.9375 * 16 * 2 ** 30),
    },
    "compiles": {"count": 41, "seconds": 5.75, "cache_hits": 39,
                 "cache_misses": 2, "last": []},
}


def _sources(page):
    return {"perf": page, "cell": {"device_kind": "TPU v5 lite"}}


@pytest.fixture(scope="module")
def spec():
    s = Spec()
    s.validate()
    return s


@pytest.mark.parametrize("name", sorted(NINE))
def test_the_file_loads_names_its_layer_and_reads_a_number(spec, name):
    layer, source, want = NINE[name]
    f = spec.layer_metric(name)
    (entry,) = [m for m in spec.doc["per_layer"] if m["name"] == name]
    layers = {m["layer"] for m in spec.doc["per_layer"][:34]}
    assert f["layer"] == entry["layer"] == layer and layer in layers
    assert f["source"] == entry["source"] == source
    assert f["moves"] == entry["moves"] == "setup_s"
    assert f["better"] == entry["better"] == "lower"
    assert "workloads" not in entry          # every cell reports setup_s
    assert f["reader"] == "debug_json" and f["params"]["page"] == "perf"
    reader = spec.reader(f["reader"])
    assert reader.read(_sources(PAGE), **f["params"]) == pytest.approx(want)
    # the parent's page has neither block; a page with tracing's window
    # only, or a timeline that never reached its listeners, reads nothing
    for page in ({"enabled": True, "phases": {}}, {"startup": None,
                 "compiles": None}, None):
        assert reader.read(_sources(page), **f["params"]) is None
    cpu = json.loads(json.dumps(PAGE))
    cpu["startup"]["peak_at_restore_end_bytes"] = None
    cpu["startup"]["seconds"].update(ready=None, unaccounted=None)
    got = reader.read({"perf": cpu, "cell": {"device_kind": "cpu"}},
                      **f["params"])
    if name in ("hbm_peak_restore_pct", "startup_ready_s",
                "startup_unaccounted_s"):
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_the_benchmark_gained_nine_metrics_and_nine_files(spec):
    names = [m["name"] for m in spec.doc["per_layer"]]
    assert names[-9:] == [
        "startup_ready_s", "startup_boot_s", "startup_lsm_s",
        "startup_log_s", "startup_land_s", "startup_drain_s",
        "startup_unaccounted_s", "startup_compile_s", "hbm_peak_restore_pct"]
    assert len(names) == 43 and len(set(names)) == 43
    files = os.listdir(os.path.join(ROOT, "benchmarks", "layer_metrics"))
    assert len(files) == 43
    for w in spec.doc["workloads"]:
        reported = {m["name"] for m in spec.metrics_for(w["name"],
                                                        "per_layer")}
        assert set(NINE) <= reported, w["name"]


def test_the_traced_rehearsal_reports_the_restart(tmp_path_factory):
    """Build, clean stop, recovery, warm-up, a 2 s window, traced, on the
    CPU: eight of the nine are numbers; the ninth is a share of the chip's
    memory and is left out where the backend keeps no allocator statistics
    (as `hbm_peak_pct` is), not filled from an estimate."""
    spec = Spec(os.path.join(STARTUP, "BENCHMARK.json"), STARTUP)
    spec.validate()
    res = bench_run.run("tiny-128-l2.batch256", seed=35, seconds=2.0,
                        trace=True, expect_platform="cpu", spec=spec,
                        state_root=str(tmp_path_factory.mktemp("states")),
                        t0=time.monotonic())
    assert res["correct"] is True, res["compared"]
    m = res["metrics"]
    for name in sorted(set(NINE) - {"hbm_peak_restore_pct"}):
        assert name in m and m[name]["value"] is not None, (name, sorted(m))
        assert m[name]["value"] >= 0 and m[name]["unit"] == "s"
    assert "hbm_peak_restore_pct" not in m and "hbm_used_pct" not in m
    ready = m["startup_ready_s"]["value"]
    parts = sum(m[n]["value"] for n in (
        "startup_boot_s", "startup_lsm_s", "startup_log_s",
        "startup_land_s", "startup_drain_s", "startup_unaccounted_s"))
    assert 0 < parts <= ready + 1e-3           # `other` is the rest
    assert m["startup_unaccounted_s"]["value"] < 0.05 * ready + 0.05
    assert m["startup_compile_s"]["value"] > 0
    with open(os.path.join(bench_run.OUT_DIR,
                           "tiny-128-l2.batch256-seed35-trace1.json")) as f:
        obs = json.load(f)["observations"]
    # under what the benchmark's own poll saw, and within 2 s of it
    assert ready < obs["ready_s"] < ready + 2.0
    startup = obs["perf"]["startup"]
    assert startup["stages"]["vector.restore"]["stats"]["rows"] == 20000
    assert "first_ready" in startup["stages"]
