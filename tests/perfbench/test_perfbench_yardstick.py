"""The yardstick itself, on the CPU in seconds: generators, percentiles, the
comparison that decides `correct`, the contract's limits on BENCHMARK.json,
and that a cell, a configuration, a mix and a metric with a new reader are
added by files alone."""

import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from benchmarks.lib import check, stats
from benchmarks.lib.requests import Request, RequestBuilder
from benchmarks.lib.spec import HERE, ROOT, NAME_RE, Spec, SpecError, check_unit
from benchmarks.references import exact_f32

THROWAWAY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "throwaway")


def _tiny_cfg():
    with open(os.path.join(THROWAWAY, "configs", "tiny-128-l2.json")) as f:
        return json.load(f)


# -- generators ---------------------------------------------------------------


def test_open_loop_schedule_is_a_function_of_the_seed():
    gen = Spec().generator("open_poisson")
    a, b = gen.schedule(3, 100.0, 5.0), gen.schedule(3, 100.0, 5.0)
    c = gen.schedule(4, 100.0, 5.0)
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert a[-1] < 5.0 and np.all(np.diff(a) > 0)
    assert 350 < len(a) < 650          # Poisson, 100/s for 5 s


def test_requests_are_a_function_of_the_seed():
    pool = np.random.default_rng(0).standard_normal((64, 128),
                                                    dtype=np.float32)
    b = RequestBuilder(_tiny_cfg(), {"request": "BatchSearch", "width": 8,
                                     "where": {"path": ["bucket"],
                                               "operator": "Equal",
                                               "valueInt": 3}}, pool)
    one = [b.draw(np.random.default_rng(5)).qidx for _ in range(2)]
    other = b.draw(np.random.default_rng(6)).qidx
    assert np.array_equal(one[0], one[1]) and not np.array_equal(one[0], other)
    req = b.draw(np.random.default_rng(5))
    assert req.kind == "batch" and len(req.msg.requests) == 8
    assert json.loads(req.msg.requests[0].where_json)["valueInt"] == 3
    assert req.msg.requests[0].class_name == "Bench"
    assert list(req.msg.requests[2].near_vector.vector) == \
        pool[req.qidx[2]].tolist()


class _OneWorkerServer:
    """A fake server with one worker: requests are served in order, the
    first takes `stall_s`, the others `service_s`."""

    def __init__(self, stall_s, service_s):
        self.stall_s, self.service_s = stall_s, service_s
        self.queue, self.cv = [], threading.Condition()
        self.served = 0
        threading.Thread(target=self._work, daemon=True).start()

    def _work(self):
        while True:
            with self.cv:
                while not self.queue:
                    self.cv.wait()
                done = self.queue.pop(0)
            wait = self.stall_s if self.served == 0 else self.service_s
            if wait:
                time.sleep(wait)
            self.served += 1
            done("reply", time.monotonic())

    def submit(self, req, done):
        with self.cv:
            self.queue.append(done)
            self.cv.notify()

    def close(self):
        pass


class _Ctx:
    def __init__(self, fake, traffic, seed, seconds):
        self.fake, self.traffic, self.seed, self.seconds = (
            fake, traffic, seed, seconds)
        self.rate_override = None
        self.builder = self

    def draw(self, rng):
        return Request("search", None, rng.integers(0, 10, 1))

    def caller(self):
        return self.fake

    def window_started(self, t):
        self.t_start = t


def test_open_loop_latency_counts_from_the_due_time():
    """A stalled server lengthens the latency of the requests behind the
    stall, though each of them is served at once."""
    gen = Spec().generator("open_poisson")
    fake = _OneWorkerServer(stall_s=0.3, service_s=0.0)
    ctx = _Ctx(fake, {"rate_per_s": 200.0, "channels": 1, "drain_s": 5.0},
               seed=1, seconds=0.6)
    w = gen.run(ctx)
    recs = w["records"]
    assert w["unfinished"] == 0 and len(recs) > 60
    lat = np.array([done - due for due, _, done, _, _ in recs])
    due = np.array([r[0] for r in recs]) - w["t_start"]
    behind = lat[(due > 0.02) & (due < 0.2)]
    assert len(behind) > 10 and np.median(behind) > 0.1   # waited for the stall
    assert np.median(lat[due > 0.5]) < 0.05               # caught up again
    late = np.array([sent - d for d, sent, _, _, _ in recs])
    assert np.all(late >= 0) and np.median(late) < 0.005
    assert w["offered_per_s"] == len(recs) / 0.6


def test_open_loop_counts_what_never_completed_as_failed():
    gen = Spec().generator("open_poisson")
    fake = _OneWorkerServer(stall_s=30.0, service_s=30.0)
    ctx = _Ctx(fake, {"rate_per_s": 50.0, "channels": 1, "drain_s": 0.2},
               seed=2, seconds=0.3)
    w = gen.run(ctx)
    assert w["unfinished"] == len(w["records"]) > 0
    assert all(isinstance(r[4], TimeoutError) for r in w["records"])


# -- percentiles --------------------------------------------------------------


def test_a_tail_needs_ten_samples_beyond_it():
    assert stats.min_samples(99) == 1000 and stats.min_samples(95) == 200
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(range(999), 99)
    assert stats.percentile(range(1000), 99) == 989
    assert stats.percentile(range(999), 99, strict=False) == 989
    with pytest.raises(stats.TooFewSamples):
        stats.percentile(range(199), 95)
    assert stats.percentile([5, 1, 3], 50) == 3 == stats.median([5, 1, 3])
    assert stats.median([1, 2, 3, 4]) == 2.5


def test_an_end_to_end_tail_is_found_by_its_name():
    """`p<q>_ms` in `end_to_end` is that percentile of the window; with too
    few requests for it the metric is left out, and another name is not a
    percentile."""
    assert stats.named_percentile("p95_ms", range(200)) == 189
    assert stats.named_percentile("p99.5_ms", range(10000)) == 9949
    assert stats.named_percentile("p99_ms", range(999)) is None
    assert stats.named_percentile("qps", range(1000)) is None


# -- the comparison -----------------------------------------------------------


def _fixed_case():
    rng = np.random.default_rng(11)
    vecs = rng.standard_normal((2000, 128), dtype=np.float32)
    picks = rng.integers(0, 2000, 16)
    queries = vecs[picks] + 0.05 * rng.standard_normal((16, 128),
                                                       dtype=np.float32)
    topk = exact_f32.TopK("l2-squared", queries, 10)
    for lo in range(0, 2000, 512):          # chunk by chunk, as the build does
        topk.update(lo, vecs[lo:lo + 512])
    return vecs, queries, topk.result()


def test_ground_truth_and_recall_agree_with_chip_smoke():
    import chip_smoke

    vecs, queries, (want_ids, want_d) = _fixed_case()
    assert np.array_equal(want_ids, chip_smoke.exact_topk(vecs, queries, 10))
    got = want_ids.copy()
    got[:4, -1] = (want_ids[:4, -1] + 1) % 2000   # four queries miss one row
    dists = exact_f32.pair_distances("l2-squared", vecs[got],
                                     queries[:, None, :])
    theirs = chip_smoke.check_answers("case", vecs, queries, got.tolist(),
                                      dists.tolist(), want_ids)
    ours = check.check_window(exact_f32, "l2-squared", 10, vecs, queries,
                              want_ids, np.arange(16), got, dists)
    assert ours["recall"] == pytest.approx(theirs) == pytest.approx(156 / 160)
    assert ours["bad_distances"] == 0 and ours["short_replies"] == 0


def test_a_distance_off_the_reference_is_caught_as_chip_smoke_catches_it():
    import chip_smoke

    vecs, queries, (want_ids, want_d) = _fixed_case()
    dists = want_d.copy()
    dists[3, 4] *= 1.01                            # a bf16-sized error
    with pytest.raises(chip_smoke.PhaseFailed):
        chip_smoke.check_answers("case", vecs, queries, want_ids.tolist(),
                                 dists.tolist(), want_ids)
    ours = check.check_window(exact_f32, "l2-squared", 10, vecs, queries,
                              want_ids, np.arange(16), want_ids, dists)
    assert ours["bad_distances"] == 1 and ours["recall"] == 1.0
    assert ours["first_bad"]["query"] == 3
    short = want_ids.copy()
    short[5, 7:] = -1
    ours = check.check_window(exact_f32, "l2-squared", 10, vecs, queries,
                              want_ids, np.arange(16), short, want_d)
    assert ours["short_replies"] == 1 and ours["recall"] == 157 / 160


@pytest.mark.parametrize("metric", ["cosine", "dot"])
def test_reference_top_k_is_exact_for_the_other_metrics(metric):
    rng = np.random.default_rng(2)
    vecs = rng.standard_normal((3000, 64), dtype=np.float32)
    queries = rng.standard_normal((8, 64), dtype=np.float32)
    topk = exact_f32.TopK(metric, queries, 10)
    for lo in range(0, 3000, 1000):
        topk.update(lo, vecs[lo:lo + 1000])
    ids, dists = topk.result()
    v, q = vecs.astype(np.float64), queries.astype(np.float64)
    full = -(q @ v.T)
    if metric == "cosine":
        full = 1.0 + full / np.sqrt((q ** 2).sum(1)[:, None]
                                    * (v ** 2).sum(1)[None, :])
    assert np.array_equal(ids, np.argsort(full, axis=1, kind="stable")[:, :10])
    assert np.allclose(dists, np.sort(full, axis=1)[:, :10], atol=1e-5)


# -- the contract -------------------------------------------------------------


def test_every_file_loads_and_every_name_passes_the_rule():
    spec = Spec()
    spec.validate()
    doc = spec.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(spec.path) <= 64 * 1024
    assert 2 <= len(doc["workloads"]) <= 24 and 1 <= doc["run_seconds"] <= 51
    assert sum(w["chips"] == 4 for w in doc["workloads"]) <= \
        max(1, len(doc["workloads"]) // 2)
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert c["file"].startswith(tuple(p + "/" for p in doc["paths"]))
        cfg = spec.config(c["name"])
        assert cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["k"] == 10 and cfg["guarantees"]["durability"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200
    assert spec.end_to_end["setup_s"]["bound"] == 0.25
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    assert len(names) == len(set(names))
    for word in doc["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in doc["paths"]:                 # files named from a name's letters
        for d, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel
    with pytest.raises(SpecError):
        check_unit("tokens per second")
    assert not NAME_RE.match("a name") and not NAME_RE.match("a/b")


def test_the_harness_holds_no_cells_name():
    with open(os.path.join(HERE, "run.py")) as f:
        src = f.read()
    doc = Spec().doc
    names = [e["name"] for e in
             doc["workloads"] + doc["configs"] + doc["per_layer"]]
    for name in names + [w["traffic"] for w in doc["workloads"]]:
        assert name not in src, name


def test_a_cell_a_config_a_mix_and_a_metric_are_added_by_files(tmp_path):
    """A throw-away set in a temporary directory: new configuration, new
    traffic mix, new per-layer metric with a reader of its own, new cell;
    nothing under benchmarks/ is touched."""
    root = tmp_path / "extra"
    for d in ("configs", "traffic", "layer_metrics", "readers"):
        (root / d).mkdir(parents=True)
    cfg = _tiny_cfg()
    cfg["name"], cfg["rows"] = "toy-128-l2", 1000
    (root / "configs" / "toy-128-l2.json").write_text(json.dumps(cfg))
    (root / "traffic" / "filtered10.json").write_text(json.dumps({
        "generator": "closed", "callers": 2, "request": "BatchSearch",
        "width": 64, "where": {"path": ["bucket"], "operator": "Equal",
                               "valueInt": 3}}))
    (root / "readers" / "answer.py").write_text(
        "def read(sources, plus=0):\n"
        "    return sources['client']['requests'] + plus\n")
    (root / "layer_metrics" / "requests_seen.json").write_text(json.dumps({
        "name": "requests_seen", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "Client (benchmark)",
        "moves": "p50_ms", "reader": "answer", "params": {"plus": 1}}))
    doc = json.loads(json.dumps(Spec().doc))
    for c in doc["configs"]:    # `file` is relative to BENCHMARK.json
        c["file"] = os.path.join(ROOT, c["file"])
    doc["configs"].append({"name": "toy-128-l2", "source": "test",
                           "file": "extra/configs/toy-128-l2.json",
                           "reduced": [], "why": "throw-away"})
    doc["workloads"].append({"name": "toy-128-l2.filtered10",
                             "config": "toy-128-l2", "traffic": "filtered10",
                             "chips": 1, "why": "throw-away"})
    doc["per_layer"].append({"name": "requests_seen", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "Client (benchmark)",
                             "moves": "p50_ms",
                             "workloads": ["toy-128-l2.filtered10"]})
    # the new cell needs its share of the end-to-end metrics
    for m in doc["end_to_end"]:
        if "workloads" in m and m["name"] == "qps":
            m["workloads"].append("toy-128-l2.filtered10")
    bj = tmp_path / "BENCHMARK.json"
    bj.write_text(json.dumps(doc))
    spec = Spec(str(bj), str(root))
    spec.validate()
    assert spec.config("toy-128-l2")["rows"] == 1000
    assert spec.traffic("filtered10")["where"]["valueInt"] == 3
    per_layer = [m["name"] for m in
                 spec.metrics_for("toy-128-l2.filtered10", "per_layer")]
    assert "requests_seen" in per_layer and "gen_late_ms" not in per_layer
    f = spec.layer_metric("requests_seen")
    assert spec.reader(f["reader"]).read({"client": {"requests": 41}},
                                         **f["params"]) == 42
    # and the shipped ones are still found beside them
    assert hasattr(spec.reader("perf_phase"), "read")
    assert spec.generator("closed").run


# -- the command refuses where it cannot stand for the cell --------------------


def _run_cli(cwd, args, limit=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = cwd
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, "-m", "benchmarks.run"] + args,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=limit)
    return proc, time.monotonic() - t0


def _a_cell():
    return Spec().doc["workloads"][0]["name"]


def test_without_an_accelerator_the_command_fails_in_seconds():
    proc, secs = _run_cli(ROOT, ["--workload", _a_cell(), "--seed", "0",
                                 "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0 and secs < 60
    assert "no accelerator" in proc.stderr
    assert "rows acknowledged" not in proc.stdout          # nothing was built
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_alone_in_a_directory_the_command_fails(tmp_path):
    doc = Spec().doc
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in doc["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc, _ = _run_cli(str(tmp_path), ["--workload", _a_cell(), "--seed",
                                       "0", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
