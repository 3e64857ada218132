"""A filtered throw-away cell rehearsed on the CPU, end to end: a
configuration whose 20,000 rows carry a bag of tags (a dataset of the
throw-away root's own, found by name), whose pool queries each carry their
own one or two tags, built, recovered and searched by `BatchSearch` of 256
slots with 256 filters; every reply held to exact brute force over the rows
its own filter allows. Then the rest of a run with the timed path broken
underneath: an answer altered where it is produced must make the run not
`correct`. None of this is a cell of the benchmark."""

import os
import time

import numpy as np
import pytest

from benchmarks import build as builder
from benchmarks import run as bench_run
from benchmarks.lib.spec import Spec

FILTERED = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "throwaway_filtered")
CELL = "tiny-128-l2-tags.batch256"


@pytest.fixture(scope="module")
def spec():
    s = Spec(os.path.join(FILTERED, "BENCHMARK.json"), FILTERED)
    s.validate()
    return s


@pytest.fixture(scope="module")
def state_root(tmp_path_factory):
    return str(tmp_path_factory.mktemp("states"))


def _run(spec, state_root, trace):
    return bench_run.run(CELL, seed=2, seconds=2.0, trace=trace,
                         expect_platform="cpu", spec=spec,
                         state_root=state_root, t0=time.monotonic())


def test_a_filtered_cell_builds_recovers_and_is_held_to_its_filters(
        spec, state_root):
    res = _run(spec, state_root, trace=True)
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    compared = res["compared"]
    assert compared["recall"]["value"] >= 0.95
    for name in ("disallowed_rows", "short_replies", "bad_distances",
                 "unknown_rows"):
        assert compared[name] == {"value": 0, "limit": "== 0"}
    # 256 slots with 256 filters are served one query a dispatch
    assert res["metrics"]["queries_per_dispatch"]["value"] == 1.0
    # the state directory keeps the queries' filters and their ground truth
    cfg = spec.config("tiny-128-l2-tags")
    state = os.path.join(state_root, cfg["name"])
    filters = builder.plan_filters(cfg, spec.traffic("batch256"),
                                   spec.dataset(cfg))
    gt_ids, allowed = builder.load_truth(state, filters)
    assert gt_ids.shape == (1024, 10) and allowed.min() >= 1
    short = allowed < 10                  # filters that allow fewer than k
    assert short.any() and np.all((gt_ids[short] >= 0).sum(1)
                                  == allowed[short])
    assert not os.path.exists(os.path.join(state, "gt_ids.npy"))


def test_an_answer_altered_where_it_is_produced_is_not_correct(
        spec, state_root, monkeypatch):
    """Runs second, on the state the first test built: every reply's first
    result is swapped for the next row, which its query's filter all but
    never allows."""
    real = bench_run.parse_reply

    def altered(req, reply, k):
        ids, dists, err = real(req, reply, k)
        ids[:, 0] = np.where(ids[:, 0] >= 0, (ids[:, 0] + 1) % 20_000, -1)
        return ids, dists, err

    monkeypatch.setattr(bench_run, "parse_reply", altered)
    res = _run(spec, state_root, trace=False)
    assert res["correct"] is False
    assert res["compared"]["disallowed_rows"]["value"] > 0
    assert res["compared"]["bad_distances"]["value"] > 0
    assert res["metrics"]["recall"]["value"] < 0.95
