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

from benchmarks import build as builder
from benchmarks.lib import check, stats, where
from benchmarks.lib.requests import Request, RequestBuilder
from benchmarks.lib.spec import HERE, ROOT, NAME_RE, Spec, SpecError, check_unit
from benchmarks.references import exact_f32

THROWAWAY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "throwaway")
FILTERED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "throwaway_filtered")


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
    cfg = _tiny_cfg()
    traffic = {"request": "BatchSearch", "width": 8,
               "where": {"path": ["bucket"], "operator": "Equal",
                         "valueInt": 3}}
    cfg["pool"] = len(pool)
    b = RequestBuilder(cfg, traffic, pool, builder.plan_filters(
        cfg, traffic, Spec().dataset(cfg)))
    one = [b.draw(np.random.default_rng(5)).qidx for _ in range(2)]
    other = b.draw(np.random.default_rng(6)).qidx
    assert np.array_equal(one[0], one[1]) and not np.array_equal(one[0], other)
    req = b.draw(np.random.default_rng(5))
    assert req.kind == "batch" and len(req.msg.requests) == 8
    assert json.loads(req.msg.requests[0].where_json)["valueInt"] == 3
    assert req.msg.requests[0].class_name == "Bench"
    assert list(req.msg.requests[2].near_vector.vector) == \
        pool[req.qidx[2]].tolist()


def test_every_pool_query_goes_out_with_its_own_filter():
    """In a Search and in every slot of a BatchSearch; a query without a
    filter carries none; the draw is still a function of the seed alone."""
    spec = Spec(os.path.join(FILTERED, "BENCHMARK.json"), FILTERED)
    cfg = spec.config("tiny-128-l2-tags")
    filters = builder.plan_filters(cfg, {}, spec.dataset(cfg))
    assert len(filters) == 1024 == cfg["pool"]
    assert filters == builder.plan_filters(cfg, None, spec.dataset(cfg))
    filters[5] = None
    pool = np.zeros((1024, 128), np.float32)
    for traffic in ({"request": "BatchSearch", "width": 64},
                    {"request": "Search"}):
        b = RequestBuilder(cfg, traffic, pool, filters)
        req = b.draw(np.random.default_rng(9))
        again = b.draw(np.random.default_rng(9))
        assert np.array_equal(req.qidx, again.qidx)
        assert req.msg.SerializeToString() == again.msg.SerializeToString()
        slots = req.msg.requests if req.kind == "batch" else [req.msg]
        for q, slot in zip(req.qidx, slots):
            assert slot.where_json == (json.dumps(filters[q])
                                       if filters[q] else "")
    assert b._search(5).where_json == ""
    with pytest.raises(ValueError):
        RequestBuilder(cfg, {}, pool, filters[:10])


def test_a_traffic_mix_names_its_filter_plan():
    """A constant `where`, a plan of the dataset's, no filter at all, or
    what the dataset's queries carry by themselves; the state directory
    keeps each plan's ground truth under the hash of its filters."""
    spec = Spec(os.path.join(FILTERED, "BENCHMARK.json"), FILTERED)
    tiny, tags = spec.config("tiny-128-l2"), spec.config("tiny-128-l2-tags")
    buckets = spec.dataset(tiny)
    assert spec.dataset(tiny).__name__.endswith("datasets_buckets")
    none = builder.plan_filters(tiny, spec.traffic("batch256"), buckets)
    assert none == [None] * 1024
    assert builder.plan_files("/s", none) == ("/s/gt_ids.npy",
                                              "/s/gt_dists.npy")
    each = builder.plan_filters(
        tiny, spec.traffic("batch256-bucket-each"), buckets)
    assert [w["valueInt"] for w in each[:12]] == [0, 1, 2, 3, 4, 5, 6, 7, 8,
                                                  9, 0, 1]
    const = builder.plan_filters(tiny, {"where": each[3]}, buckets)
    assert const == [each[3]] * 1024
    files = {builder.plan_files("/s", p) for p in (each, const)}
    assert len(files) == 2
    for npz, text in files:
        assert re.match(r"^/s/plan-[0-9a-f]{16}\.npz$", npz)
        assert text == npz[:-4] + ".json"
    assert builder.plan_filters(tags, spec.traffic("batch256-nofilter"),
                                spec.dataset(tags)) == none
    with pytest.raises(ValueError):
        builder.plan_filters(tiny, {"filter_plan": "no_such"}, buckets)
    rows = np.array([3, 13, 14, 20003])
    assert buckets.properties(tiny, rows) == [
        {"bucket": 3}, {"bucket": 3}, {"bucket": 4}, {"bucket": 3}]
    assert buckets.allowed(tiny, [each[3], None, each[4]], rows).tolist() == [
        [True, True, False, True], [True] * 4, [False, False, True, False]]


WHERE_CASES = {
    "Equal on a scalar": (
        {"path": ["n"], "operator": "Equal", "valueInt": 2}, [0, 0, 1, 0]),
    "Equal on a bag is any entry": (
        {"path": ["bag"], "operator": "Equal", "valueInt": 7}, [1, 0, 1, 0]),
    "the padding is no entry": (
        {"path": ["bag"], "operator": "Equal", "valueInt": -1}, [0, 0, 0, 0]),
    "And": ({"operator": "And", "operands": [
        {"path": ["bag"], "operator": "Equal", "valueInt": 7},
        {"path": ["bag"], "operator": "Equal", "valueInt": 9}]}, [0, 0, 1, 0]),
    "Or and Not": ({"operator": "Or", "operands": [
        {"path": ["n"], "operator": "LessThan", "valueInt": 1},
        {"operator": "Not", "operands": [
            {"path": ["n"], "operator": "LessThanEqual", "valueInt": 2}]}]},
        [1, 0, 0, 1]),
    "ContainsAll": ({"path": ["bag"], "operator": "ContainsAll",
                     "valueInt": [7, 9]}, [0, 0, 1, 0]),
    "ContainsAny": ({"path": ["bag"], "operator": "ContainsAny",
                     "valueInt": [4, 9]}, [0, 1, 1, 0]),
}


@pytest.mark.parametrize("case", sorted(WHERE_CASES))
def test_a_where_is_read_in_numpy(case):
    clause, want = WHERE_CASES[case]
    columns = {"n": np.array([0, 1, 2, 3]),
               "bag": np.array([[7, -1, -1], [4, 5, -1], [9, 7, 3],
                                [-1, -1, -1]])}
    assert where.evaluate(clause, columns).tolist() == [bool(x) for x in want]
    assert where.properties(columns, 4)[2] == {"n": 2, "bag": [9, 7, 3]}


@pytest.mark.parametrize("clause", [
    {"path": ["other"], "operator": "Equal", "valueInt": 1},
    {"path": ["n"], "operator": "Like", "valueInt": 1},
    {"path": ["n"], "operator": "Equal", "valueText": "a"},
    {"path": ["bag"], "operator": "LessThan", "valueInt": 1},
    {"operator": "And", "operands": []}])
def test_a_where_the_reference_cannot_read_is_an_error(clause):
    with pytest.raises(where.WhereError):
        where.evaluate(clause, {"n": np.arange(3),
                                "bag": np.zeros((3, 2), int)})


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


def _filtered_case():
    """The fixed case under a filter a query: queries 0-3 may take one row
    in five, queries 4-7 seven rows in all, query 8 none, the rest all."""
    vecs, queries, _ = _fixed_case()
    allowed = np.ones((16, 2000), bool)
    allowed[:4] = (np.arange(2000) % 5 == 2)[None, :]
    allowed[4:8] = False
    allowed[4:8, [3, 77, 512, 513, 1400, 1999, 1024]] = True
    allowed[8] = False
    topk = exact_f32.TopK("l2-squared", queries, 10)
    for lo in range(0, 2000, 512):
        topk.update(lo, vecs[lo:lo + 512], allowed[:, lo:lo + 512])
    return vecs, queries, allowed, topk


def test_filtered_ground_truth_agrees_with_chip_smoke_under_the_same_mask():
    import chip_smoke

    vecs, queries, allowed, topk = _filtered_case()
    ids, dists = topk.result()
    assert topk.allowed.tolist() == [400] * 4 + [7] * 4 + [0] + [2000] * 7
    for q in range(16):
        allow = np.flatnonzero(allowed[q])
        n = min(10, len(allow))
        assert (ids[q] >= 0).sum() == n and np.all(ids[q, n:] == -1)
        assert np.all(np.isinf(dists[q, n:]))
        if n == 0:
            continue
        # chip_smoke's brute force wants more rows than k: pad the short
        # allow lists with far-away copies it ranks last
        if len(allow) <= 10:
            far = np.vstack([vecs, np.full((11, 128), 1e3, np.float32)])
            theirs = chip_smoke.exact_topk(
                far, queries[q:q + 1], 10,
                np.concatenate([allow, 2000 + np.arange(11)]))[0][:n]
        else:
            theirs = chip_smoke.exact_topk(vecs, queries[q:q + 1], 10,
                                           allow)[0]
        assert np.array_equal(ids[q, :n], theirs)
        assert allowed[q, ids[q, :n]].all()


def _check_filtered(got_ids, got_dists=None):
    vecs, queries, allowed, topk = _filtered_case()
    want_ids, _ = topk.result()
    if got_ids is None:
        got_ids = want_ids.copy()
    else:
        got_ids = got_ids(want_ids.copy(), allowed)
    safe = np.where(got_ids >= 0, got_ids, 0)
    dists = exact_f32.pair_distances("l2-squared", vecs[safe],
                                     queries[:, None, :])
    dists[got_ids < 0] = np.nan
    return check.check_window(
        exact_f32, "l2-squared", 10, vecs, queries, want_ids, np.arange(16),
        got_ids, dists, allowed_pairs=lambda qq, rr: allowed[qq, rr])


def _one_row_outside(ids, allowed):
    ids[0, 9] = np.flatnonzero(~allowed[0])[0]
    return ids


def _drop_one_of_seven(ids, allowed):
    ids[5, 6] = -1
    return ids


def _a_wrong_neighbour(ids, allowed):
    ids[1, 9] = next(r for r in np.flatnonzero(allowed[1])
                     if r not in ids[1])
    return ids


def _answer_the_empty_filter(ids, allowed):
    ids[8, 0] = 17
    return ids


FILTERED_CHECKS = {
    # what the replies did -> (recall, short, disallowed)
    "the exact answers: short ground truth counts for what it has":
        (None, 1.0, 0, 0),
    "a row outside its query's filter, whatever the recall":
        (_one_row_outside, 137 / 138, 0, 1),
    "six results where the filter allows seven":
        (_drop_one_of_seven, 137 / 138, 1, 0),
    "an allowed row that is not a neighbour":
        (_a_wrong_neighbour, 137 / 138, 0, 0),
    "an answer where the filter allows nothing":
        (_answer_the_empty_filter, 1.0, 0, 1),
}


@pytest.mark.parametrize("case", sorted(FILTERED_CHECKS))
def test_under_a_filter_the_check_holds_each_clause(case):
    """138 ground-truth entries exist: 4 x 10 + 4 x 7 + 0 + 7 x 10."""
    change, recall, short, disallowed = FILTERED_CHECKS[case]
    out = _check_filtered(change)
    assert out["recall"] == pytest.approx(recall)
    assert out["short_replies"] == short
    assert out["disallowed_rows"] == disallowed
    assert out["bad_distances"] == 0 and out["unknown_rows"] == 0
    assert (out["first_disallowed"] is None) == (disallowed == 0)


def test_without_filters_the_check_reads_what_it_read():
    vecs, queries, (want_ids, want_d) = _fixed_case()
    a = check.check_window(exact_f32, "l2-squared", 10, vecs, queries,
                           want_ids, np.arange(16), want_ids, want_d)
    b = check.check_window(exact_f32, "l2-squared", 10, vecs, queries,
                           want_ids, np.arange(16), want_ids, want_d,
                           allowed_pairs=lambda qq, rr: np.ones(len(qq), bool))
    assert a == b and a["recall"] == 1.0 and a["disallowed_rows"] == 0
    topk = exact_f32.TopK("l2-squared", queries, 10)
    for lo in range(0, 2000, 512):
        topk.update(lo, vecs[lo:lo + 512], np.ones((16, len(vecs[lo:lo + 512])),
                                                   bool))
    assert np.array_equal(topk.result()[0], want_ids)
    assert topk.allowed.tolist() == [2000] * 16


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
    for d in ("configs", "traffic", "layer_metrics", "readers", "datasets"):
        (root / d).mkdir(parents=True)
    cfg = _tiny_cfg()
    cfg["name"], cfg["rows"] = "toy-128-l2", 1000
    (root / "configs" / "toy-128-l2.json").write_text(json.dumps(cfg))
    # a dataset of its own (rows carry a tenant, every query asks for its
    # own), a configuration that names it, and a traffic mix that puts a
    # filter plan of the buckets dataset on a configuration that is there
    (root / "datasets" / "tenants.py").write_text(
        "import numpy as np\n"
        "from benchmarks.lib import where\n"
        "def _cols(cfg, rows):\n"
        "    return {'tenant': np.asarray(rows) % cfg['tenants']}\n"
        "def properties(cfg, rows):\n"
        "    return where.properties(_cols(cfg, rows), len(rows))\n"
        "def filter_plan(cfg, plan):\n"
        "    return [{'path': ['tenant'], 'operator': 'Equal',\n"
        "             'valueInt': i % cfg['tenants']}\n"
        "            for i in range(cfg['pool'])]\n"
        "def allowed(cfg, wheres, rows):\n"
        "    return where.allowed(wheres, _cols(cfg, rows), len(rows))\n")
    tenants = dict(cfg, name="toy-128-l2-tenants", dataset="tenants",
                   tenants=7)
    tenants["class"] = dict(cfg["class"], properties=[
        {"name": "tenant", "dataType": ["int"]}])
    (root / "configs" / "toy-128-l2-tenants.json").write_text(
        json.dumps(tenants))
    (root / "traffic" / "bucket-each.json").write_text(json.dumps({
        "generator": "closed", "callers": 2, "request": "BatchSearch",
        "width": 64, "filter_plan": "bucket_each"}))
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
    doc["configs"].append({"name": "toy-128-l2-tenants", "source": "test",
                           "file": "extra/configs/toy-128-l2-tenants.json",
                           "reduced": [], "why": "throw-away"})
    doc["workloads"].append({"name": "toy-128-l2-tenants.batch256",
                             "config": "toy-128-l2-tenants",
                             "traffic": "batch256", "chips": 1,
                             "why": "throw-away"})
    a_config = doc["configs"][0]["name"]      # one that is there
    doc["workloads"].append({"name": a_config + ".bucket-each",
                             "config": a_config, "traffic": "bucket-each",
                             "chips": doc["workloads"][0]["chips"],
                             "why": "throw-away"})
    doc["per_layer"].append({"name": "requests_seen", "unit": "count",
                             "better": "higher", "source": "host_clock",
                             "layer": "Client (benchmark)",
                             "moves": "p50_ms",
                             "workloads": ["toy-128-l2.filtered10"]})
    # the new cell needs its share of the end-to-end metrics
    for m in doc["end_to_end"]:
        if "workloads" in m and m["name"] == "qps":
            m["workloads"] += ["toy-128-l2.filtered10",
                               "toy-128-l2-tenants.batch256",
                               a_config + ".bucket-each"]
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
    # the configuration with a dataset of its own: rows, filters, reading
    tcfg = spec.config("toy-128-l2-tenants")
    dataset = spec.dataset(tcfg)
    assert dataset.properties(tcfg, np.array([8])) == [{"tenant": 1}]
    filters = builder.plan_filters(tcfg, spec.traffic("batch256"), dataset)
    assert filters[9]["valueInt"] == 2 and len(filters) == tcfg["pool"]
    assert dataset.allowed(tcfg, filters[:2], np.arange(14)).sum(1).tolist() \
        == [2, 2]
    # the filter plan as traffic on a configuration that is there: its own
    # file, byte for byte, and the dataset it always had
    there = spec.config(a_config)
    with open(os.path.join(ROOT, Spec().configs[a_config]["file"]), "rb") as f:
        assert there["_sha256"] == __import__("hashlib").sha256(
            f.read()).hexdigest()
    plan = builder.plan_filters(there, spec.traffic("bucket-each"),
                                spec.dataset(there))
    assert plan[13] == {"path": ["bucket"], "operator": "Equal",
                        "valueInt": 13 % there["filter_buckets"]}
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
