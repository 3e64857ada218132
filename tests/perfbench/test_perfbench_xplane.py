"""benchmarks/lib/xplane.py against traces whose numbers are known: two made
by hand, and one recorded on a TPU v5e in PR 22 (`cohere-768-cos.batch256`,
cut to three executions of the search program: 7 module and 719 op events,
op names cut to 72 characters). The recorded trace's expected numbers were
computed apart from the module, by counting cover over the elementary
intervals between all event edges (`_busy_by_cover` below does it again)."""

import json
import os

import numpy as np
import pytest

from benchmarks.lib import xplane
from benchmarks.lib.spec import Spec

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures_xplane_v5e_batch256.json")
DEV = "/device:TPU:0"


def _trace(ops, modules=(), plane=DEV):
    return {plane: {xplane.OPS_LINE: list(ops),
                    xplane.MODULES_LINE: list(modules)}}


def test_busy_is_the_union_of_op_intervals_not_their_sum():
    # a while op [0, 100) with its body [10, 40) and [50, 90) inside it, an
    # idle gap, then two ops that touch
    t = _trace([("while", 0, 100), ("body.a", 10, 30), ("body.b", 50, 40),
                ("copy", 300, 50), ("fusion", 350, 50), ("empty", 500, 0)])
    s = xplane.device_summary(t)
    d = s["devices"][DEV]
    assert s["window_s"] == pytest.approx(400e-9)        # 0 .. 400
    assert d["busy_s"] == pytest.approx(200e-9)          # 100 + 100, not 270
    assert d["idle_pct"] == pytest.approx(50.0)
    assert d["op_seconds"]["while"] == pytest.approx(100e-9)
    assert d["gaps_s"][0] == (pytest.approx(200e-9), pytest.approx(100e-9))
    assert xplane.merge_intervals(t[DEV][xplane.OPS_LINE]) == \
        [(0, 100), (300, 400)]


def test_the_window_spans_every_device_and_the_median_device_is_picked():
    t = {}
    for n, busy in enumerate((100, 300, 200, 400)):
        t.update(_trace([("op", 1000, busy)], plane=f"/device:TPU:{n}"))
    t["/device:TPU:3"][xplane.OPS_LINE].append(("late", 1900, 100))
    s = xplane.device_summary(t)
    assert s["window_s"] == pytest.approx(1000e-9)       # 1000 .. 2000
    idle = {p: d["idle_pct"] for p, d in s["devices"].items()}
    assert idle["/device:TPU:0"] == pytest.approx(90.0)
    assert idle["/device:TPU:3"] == pytest.approx(50.0)
    # of four devices the lower middle one: 70% idle (device 1)
    assert xplane.median_device(idle) == ("/device:TPU:1",
                                          pytest.approx(70.0))
    assert xplane.median_device({}) is None


def test_a_programs_time_is_its_module_events():
    mods = [("jit_search(1)", 0, 1000), ("jit_other(2)", 1000, 50),
            ("jit_search(1)", 2000, 1200)]
    ops = [("fusion", 300, 600), ("fusion", 2100, 100)]
    t = _trace(ops, mods)
    times = xplane.module_times(t, "search")[DEV]
    assert times == {"jit_search(1)": [1000e-9, 1200e-9]}


def _busy_by_cover(ops):
    ev = np.array([(s, s + d) for _, s, d in ops if d > 0])
    pts = np.unique(ev.ravel())
    cover = np.zeros(len(pts) - 1, int)
    for s, e in ev:
        cover[np.searchsorted(pts, s):np.searchsorted(pts, e)] += 1
    widths = pts[1:] - pts[:-1]
    return int(widths[cover > 0].sum()), int(widths[cover == 0].max())


def test_recorded_v5e_trace_reduces_to_the_numbers_computed_by_hand():
    with open(FIXTURE) as f:
        t = xplane.from_json(f.read())
    ops = t[DEV][xplane.OPS_LINE]
    assert len(ops) == 719 and len(t[DEV][xplane.MODULES_LINE]) == 7
    s = xplane.device_summary(t)
    d = s["devices"][DEV]
    assert xplane.window_ns(t) == (1007, 52018680)
    assert s["window_s"] == pytest.approx(0.052017673, rel=1e-9)
    assert d["busy_s"] == pytest.approx(0.032308244, rel=1e-9)
    assert d["idle_pct"] == pytest.approx(37.88987062, rel=1e-8)
    assert _busy_by_cover(ops) == (32308244, 13696214)
    # ops nest (a while's time includes its body's): the sum of all op
    # durations is more than the busy time
    assert sum(x[2] for x in ops) == 41907050
    top = list(d["op_seconds"].items())[:3]
    assert [n[:10] for n, _ in top] == ["%convert.3", "%while.4 =",
                                        "%fusion.36"]
    assert [round(v * 1e9) for _, v in top] == [22102108, 9609046, 6927005]
    assert d["gaps_s"][0] == (pytest.approx(0.013696214),
                              pytest.approx(0.010762179))
    # the named search program: three executions
    times = xplane.module_times(t, "search")[DEV]
    assert list(times) == ["jit__search_full_fused(10375372082987777793)"]
    assert [round(x * 1e9) for x in next(iter(times.values()))] == \
        [10762188, 10774456, 10771253]


MESH_FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "fixtures_xplane_v5e_mesh4.json")


def _pages(rows, dim, chips=1, batch=256, tier="exact_scan",
           operand="store", bytes_a_component=4, queries=None,
           dispatches=100, **perf):
    """The server's pages as a run collects them, for a slab of
    `capacity` = the next power of two of `rows`, held as `operand`."""
    capacity = 1 << (rows - 1).bit_length()
    queries = batch if queries is None else queries
    return {
        "cell": {"device_kind": "TPU v5 lite", "chips": chips, "rows": rows,
                 "dim": dim, "batch": batch, "pq_segments": dim // 8},
        "perf": dict({"rows": queries * dispatches, "dispatches": dispatches,
                      "tiers": {tier: dispatches}}, **perf),
        "index": {"live": rows, "capacity": capacity},
        "debug_memory": {"device": {"components": {
            operand: capacity * dim * bytes_a_component,
            "tombs": capacity}}},
    }


def _value(sources, name):
    spec = Spec()
    m = spec.layer_metric(name)
    return spec.reader(m["reader"]).read(sources, **m["params"])


def test_the_readers_turn_the_recorded_trace_into_the_cells_metrics():
    spec = Spec()
    with open(FIXTURE) as f:
        sources = dict(_pages(1_000_000, 768),
                       xplane=xplane.from_json(f.read()))
    assert _value(sources, "scan_device_ms") == pytest.approx(10.771253)
    assert _value(sources, "device_idle_pct") == pytest.approx(37.88987062)
    # 1M x 768 f32 is 3.072 GB: 3.7509 ms at 819 GB/s, more than the 2.0 ms
    # its 393 GFLOP take at 197 TFLOP/s, so HBM bounds it
    assert _value(sources, "scan_roofline") == pytest.approx(
        100 * (3.072e9 / 819e9) / 10.771253e-3)
    assert sources["notes"] == {
        "roofline_program": "jit__search_full_fused(10375372082987777793)",
        "roofline_tier": "exact_scan", "roofline_operand": "store",
        "roofline_bytes_a_row": 3072.0, "roofline_rows": 1_000_000.0,
        "roofline_queries": 256.0, "roofline_bound": "hbm"}
    assert spec.reader("xplane_ops").read({}, what="idle_pct") is None
    with pytest.raises(KeyError):     # a chip that is not in the table
        sources["cell"]["device_kind"] = "TPU v9"
        _value(sources, "scan_roofline")


def test_the_four_chip_trace_is_charged_one_chips_share_of_the_slab():
    """3M x 768 f32 over four chips: 750,000 rows, 2.304 GB a chip; the
    components and the capacity /debug/memory and /debug/index report are
    the whole mesh's."""
    with open(MESH_FIXTURE) as f:
        sources = dict(_pages(3_000_000, 768, chips=4),
                       xplane=xplane.from_json(f.read()))
    assert _value(sources, "scan_roofline") == pytest.approx(
        100 * (750_000 * 3072 / 819e9) / 13.918968e-3)
    assert sources["notes"]["roofline_rows"] == 750_000
    assert sources["notes"]["roofline_program"].startswith("jit_mesh_search")


# what one execution of the recorded 10.771253 ms program would have been
# given under other accounts of the server's: (pages, least ms or None, what
# the notes must say)
_F32_MS = 1e3 * 1_000_000 * 3072 / 819e9
YARDSTICK = {
    "a 2 B operand (pq with rescore scans the bf16 copy)": (
        _pages(1_000_000, 768, tier="pq_rescore_bf16",
               operand="rescore_store", bytes_a_component=2, queries=16),
        _F32_MS / 2, {"roofline_operand": "rescore_store",
                      "roofline_bytes_a_row": 1536.0,
                      "roofline_bound": "hbm"}),
    "at 2 B and 256 wide the multiply-adds bound it, not the bytes": (
        _pages(2_000_000, 768, tier="pq_rescore_bf16",
               operand="rescore_store", bytes_a_component=2),
        1e3 * 2 * 256 * 2_000_000 * 768 / 197e12,
        {"roofline_bound": "flops"}),
    "one query a dispatch under a 256-wide traffic": (
        _pages(2_000_000, 768, queries=1), 2 * _F32_MS,
        {"roofline_queries": 1.0, "roofline_bound": "hbm"}),
    "a part scan: the tier's own account says fewer rows": (
        _pages(1_000_000, 768, tier="gather", tier_rows={"gather": 100 * 5000}),
        _F32_MS / 200, {"roofline_rows": 5000.0, "roofline_tier": "gather"}),
    "codes: segments bytes a row": (
        _pages(1_000_000, 768, tier="pq_codes", operand="pq_codes",
               bytes_a_component=0.125),
        1e3 * 1_000_000 * 96 / 819e9, {"roofline_bytes_a_row": 96.0}),
    "a part scan without the account": (
        _pages(1_000_000, 768, tier="gather"), None,
        {"roofline_null": "tier gather scans a part of the rows and "
                          "/debug/perf has no tier_rows to say how many"}),
    "the operand named is not resident": (
        _pages(1_000_000, 768, tier="pq_rescore_bf16"), None,
        {"roofline_null": "tier pq_rescore_bf16 scans rescore_store, which "
                          "/debug/memory does not hold on the device "
                          "(['store', 'tombs'])"}),
    "bytes a component that are no type's": (
        _pages(1_000_000, 768, bytes_a_component=3), None, {}),
    "an account of more rows than the configuration has": (
        _pages(1_000_000, 768, tier_rows={"exact_scan": 100 * 2_000_000}),
        None, {}),
    "live is not the configuration's rows": (
        dict(_pages(1_000_000, 768), index={"live": 999_999,
                                            "capacity": 1 << 20}), None, {}),
    "no /debug/perf": (dict(_pages(1_000_000, 768), perf=None), None, {}),
    "a tier the benchmark does not know": (
        _pages(1_000_000, 768, tier="bm25_matmul"), None, {}),
    "two tiers, and the program's name gives it to one": (
        dict(_pages(1_000_000, 768), perf={
            "rows": 300, "dispatches": 300,
            "tiers": {"gather": 200, "exact_scan": 100}}),
        _F32_MS, {"roofline_tier": "exact_scan", "roofline_queries": 1.0}),
}


@pytest.mark.parametrize("case", sorted(YARDSTICK))
def test_the_yardstick_counts_what_one_execution_was_given(case):
    pages, least_ms, notes = YARDSTICK[case]
    with open(FIXTURE) as f:
        sources = dict(pages, xplane=xplane.from_json(f.read()))
    value = _value(sources, "scan_roofline")
    if least_ms is None:      # no reading, and the note says which account
        assert value is None
        assert sources["notes"]["roofline_null"]
        assert "roofline_bound" not in sources["notes"]
    else:
        assert value == pytest.approx(100 * least_ms / 10.771253)
    for key, want in notes.items():
        assert sources["notes"][key] == want, sources["notes"]


def test_to_json_round_trips():
    t = _trace([("a", 1, 2)], [("m", 0, 5)])
    assert xplane.from_json(xplane.to_json(t)) == t
    assert json.loads(xplane.to_json(t))[DEV][xplane.OPS_LINE] == [["a", 1, 2]]
