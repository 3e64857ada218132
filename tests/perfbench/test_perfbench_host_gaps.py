"""The `host_gaps` reader on a trace small enough to work out by hand, and the
layer metrics this reader and the program's capture log brought: their files
pass the yardstick's own validation and read nothing, without raising, from a
program that has no capture log."""

import pytest

from benchmarks.lib import xplane
from benchmarks.lib.spec import Spec

DEV = "/device:TPU:0"

# three ops, two gaps: [100, 300) and [400, 700) of a window 0..1000
TRACE = {DEV: {xplane.OPS_LINE: [("scan", 0, 100), ("scan", 300, 100),
                                 ("scan", 700, 300)],
               xplane.MODULES_LINE: []}}

# thread 1: a request with a hydrate nested in it; thread 2: an enqueue that
# overlaps both, and a phase no metric names
INTERVALS = [["request", 1, 50, 600], ["hydrate", 1, 150, 100],
             ["enqueue", 2, 200, 300], ["scatter", 2, 650, 40]]

# gap [100, 300):  [100,150) request alone              request 50
#                  [150,200) hydrate alone (innermost)  hydrate 50
#                  [200,250) hydrate | enqueue          25 each
#                  [250,300) request | enqueue          25 each
# gap [400, 700):  [400,500) request | enqueue          50 each
#                  [500,650) request alone              request 150
#                  [650,690) scatter alone              scatter 40
#                  [690,700) nothing open               none 10
BY_HAND = {"request": 275.0, "hydrate": 75.0, "enqueue": 100.0,
           "scatter": 40.0, None: 10.0}
NAMED = ["entry.decode", "entry.encode", "request", "hydrate", "filter",
         "enqueue", "device_wait", "gather_hop"]
NEW = ("idle_entry_pct", "idle_hydrate_pct", "idle_dispatch_pct",
       "idle_no_request_pct", "idle_other_pct", "entry_decode_ms",
       "entry_encode_ms", "hbm_peak_pct")


def _sources(intervals=INTERVALS):
    return {"xplane": TRACE, "perf": {"capture": {"intervals": intervals}}}


@pytest.fixture(scope="module")
def reader():
    return Spec().reader("host_gaps")


def test_every_idle_nanosecond_goes_to_the_innermost_open_phase(reader):
    w0, w1, gaps = reader.idle_gaps(TRACE)
    assert (w0, w1, gaps) == (0, 1000, [(100, 300), (400, 700)])
    assert reader.attribute(gaps, INTERVALS) == BY_HAND
    assert reader.flatten([(50, 650, "request"), (150, 250, "hydrate")]) == [
        (50, 150, "request"), (150, 250, "hydrate"), (250, 650, "request")]


def test_the_shares_sum_to_the_idle_share(reader):
    got = {
        "entry": reader.read(_sources(), "phases", [
            "entry.decode", "entry.encode", "request"]),
        "hydrate": reader.read(_sources(), "phases", ["hydrate", "filter"]),
        "dispatch": reader.read(_sources(), "phases", [
            "enqueue", "device_wait", "gather_hop"]),
        "none": reader.read(_sources(), "none"),
        "other": reader.read(_sources(), "other", NAMED),
    }
    assert got == pytest.approx({"entry": 27.5, "hydrate": 7.5,
                                 "dispatch": 10.0, "none": 1.0,
                                 "other": 4.0})
    idle = xplane.device_summary(TRACE)["devices"][DEV]["idle_pct"]
    assert idle == pytest.approx(50.0)
    assert sum(got.values()) == pytest.approx(idle)
    # one attribution serves the five metrics of a run, and its record
    s = _sources()
    reader.read(s, "none")
    assert s["notes"]["idle_pct_by_phase"] == pytest.approx({
        "request": 27.5, "enqueue": 10.0, "hydrate": 7.5, "scatter": 4.0,
        "None": 1.0})


def test_an_unknown_what_is_refused_and_an_empty_interval_opens_nothing(reader):
    with pytest.raises(ValueError):
        reader.read(_sources(), "some")
    # a phase that closed in the instant it opened holds no idle time, and a
    # capture with no interval at all leaves every gap to `none`
    empty = INTERVALS + [["filter", 3, 695, 0]]
    assert reader.read(_sources(empty), "none") == pytest.approx(1.0)
    assert reader.read(_sources([]), "none") == pytest.approx(50.0)
    assert reader.read(_sources([]), "other", NAMED) == 0.0


def test_no_capture_record_reads_nothing(reader):
    # a parent commit's /debug/perf, a CPU run, an untraced run
    assert reader.read({"xplane": TRACE, "perf": {"phases": {}}},
                       "none") is None
    assert reader.read({"xplane": TRACE, "perf": {"capture": None}},
                       "none") is None
    assert reader.read({"perf": {"capture": {"intervals": INTERVALS}}},
                       "none") is None
    assert reader.read({}, "none") is None


def test_the_new_metrics_validate_and_read_nothing_from_the_parent():
    spec = Spec()
    spec.validate()
    parent = {"xplane": TRACE, "perf": {"phases": {"hydrate": {
        "samples": 3, "p50_ms": 1.0}}}, "debug_memory": {"device": {
            "allocator": {"allocator_bytes_in_use": 1}}},
        "cell": {"device_kind": "TPU v5 lite"}}
    for name in NEW:
        assert name in spec.per_layer
        f = spec.layer_metric(name)
        assert spec.reader(f["reader"]).read(parent, **f["params"]) is None
    batch = {m["name"] for m in spec.metrics_for(
        "cohere-768-cos.batch256", "per_layer")}
    single = {m["name"] for m in spec.metrics_for(
        "cohere-768-cos.single", "per_layer")}
    assert set(NEW) <= batch
    assert set(NEW) - single == {"idle_hydrate_pct"}   # it moves qps
    # the five idle shares partition the phases: every name one of them
    # reads is outside `other`, and nothing is read twice
    lists = [spec.layer_metric(n)["params"].get("phases", [])
             for n in NEW[:3]]
    flat = [p for ps in lists for p in ps]
    assert len(flat) == len(set(flat))
    assert sorted(flat) == sorted(
        spec.layer_metric("idle_other_pct")["params"]["phases"])


def test_the_breakdowns_idle_gaps_are_named_by_the_host_interval():
    """`breakdown.idle_gaps` of a traced run: the idle seconds by the
    interval that was open in them, then the longest gaps, each by the
    interval that held most of it; without a capture log, unattributed."""
    from benchmarks import run as bench_run

    devs = xplane.device_summary(TRACE)["devices"][DEV]
    named = bench_run.name_idle_gaps(
        TRACE, {"intervals": INTERVALS}, 500e-9, devs["gaps_s"])
    assert named[:5] == [
        ["request", pytest.approx(275e-9)], ["enqueue", pytest.approx(100e-9)],
        ["hydrate", pytest.approx(75e-9)], ["scatter", pytest.approx(40e-9)],
        ["no interval open", pytest.approx(10e-9)]]
    # gap [400, 700): request 200, enqueue 50; gap [100, 300): request 75
    assert named[5:] == [
        ["gap at +0.000s, mostly request", pytest.approx(300e-9)],
        ["gap at +0.000s, mostly request", pytest.approx(200e-9)]]
    assert len(named) <= 10
    bare = bench_run.name_idle_gaps(TRACE, None, 500e-9, devs["gaps_s"])
    assert [n.split(":")[0] for n, _ in bare] == [
        "unattributed (no capture log)", "unattributed", "unattributed"]
    assert bare[0][1] == 500e-9
