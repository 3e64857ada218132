"""benchmarks/readers/xplane_mesh.py (`collective_ms`, `chip_busy_spread_pct`)
against traces whose numbers are known: made by hand, and one recorded on a
four-chip TPU v5e in PR 25 (`cohere-768-cos-mesh4.batch256` at 3,000,000
rows, `--keep-trace`, cut to three executions of the search program on each
of the four device planes: 12 module and 2,976 op events, op names cut to 72
characters). The recorded trace's expected numbers were read off the events
apart from the reader (`_busy_by_cover` counts cover over the elementary
intervals between all event edges; the all-gathers are listed by hand)."""

import os

import numpy as np
import pytest

from benchmarks.lib import xplane
from benchmarks.lib.spec import Spec
from benchmarks.readers import xplane_mesh

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures_xplane_v5e_mesh4.json")
AG = "%all-gather.1 = s32[256,120]{1,0:T(8,128)} all-gather(s32[256,30]{1,0"


def _plane(n, ops, modules):
    return {f"/device:TPU:{n}": {xplane.OPS_LINE: list(ops),
                                 xplane.MODULES_LINE: list(modules)}}


def _read(trace, what, **params):
    sources = {"xplane": trace}
    return xplane_mesh.read(sources, what, **params), sources.get("notes")


def test_collective_ms_is_the_union_inside_one_execution_median_of_both():
    """Two executions a device. Device 0 waits 4 and 6 us in its all-gather
    (median 5), device 1 waits 1 and 3 (median 2), devices 2 and 3 wait 2
    and 2: the median device (the lower middle of 2, 2, 2, 5) reads 2 us. A
    collective of another program, and the scan, are not counted; the two
    halves of an asynchronous collective count as their union."""
    mods = [("jit_mesh_search_step(7)", 0, 100_000),
            ("jit_mesh_insert_step(9)", 150_000, 50_000),
            ("jit_mesh_search_step(7)", 300_000, 100_000)]
    t = {}
    for n, (a, b) in enumerate(((4000, 6000), (1000, 3000), (2000, 2000),
                                (1500, 2500))):
        half = b // 2
        t.update(_plane(n, [
            ("%while.4 = (s32[], f32[256,10]) while(...)", 0, 90_000),
            (AG, 90_000, a),
            ("%all-reduce.2 = f32[8] all-reduce(f32[8] %x)", 160_000, 9_000),
            ("%while.4 = (s32[], f32[256,10]) while(...)", 300_000, 90_000),
            ("%all-gather-start.3 = (s32[256,30], s32[256,120]) "
             "all-gather-start(...)", 390_000, half),
            ("%all-gather-done.3 = s32[256,120] all-gather-done(...)",
             390_000 + half - 10, b - half + 10),
        ], mods))
    value, notes = _read(t, "collective_ms", module="search")
    assert value == pytest.approx(2.0e-3)
    by_dev = notes["collective_ms"]["by_device"]
    assert by_dev["/device:TPU:0"] == {"executions": 2,
                                       "ms": pytest.approx(5.0e-3)}
    assert by_dev["/device:TPU:1"]["ms"] == pytest.approx(2.0e-3)
    assert "slowest chip" in notes["collective_ms"]["reads"]


def test_one_plane_without_a_collective_reads_null_and_spread_zero():
    t = _plane(0, [("%while.4 = while(...)", 0, 900), ("%fusion.3", 950, 50)],
               [("jit__search_full_fused(1)", 0, 1000)])
    assert _read(t, "collective_ms", module="search") == (None, {})
    value, notes = _read(t, "busy_spread_pct")
    assert value == 0.0
    assert notes["busy_pct_by_device"] == {"/device:TPU:0":
                                           pytest.approx(95.0)}


def test_busy_spread_is_highest_less_lowest_share_of_one_window():
    t = {}
    for n, busy in enumerate((900, 700, 800, 850)):
        t.update(_plane(n, [("%while.4", 1000, busy)], []))
    t["/device:TPU:1"][xplane.OPS_LINE].append(("%late", 1990, 10))
    # the window is 1000 .. 2000 for every device: 90, 71, 80 and 85 points
    value, notes = _read(t, "busy_spread_pct")
    assert value == pytest.approx(19.0)
    assert notes["busy_pct_by_device"]["/device:TPU:1"] == pytest.approx(71.0)


def test_no_trace_or_no_device_plane_reads_null_and_a_wrong_param_raises():
    for sources in ({}, {"xplane": {}}, {"xplane": None}):
        for what in ("collective_ms", "busy_spread_pct"):
            assert xplane_mesh.read(dict(sources), what, module="x") is None
    with pytest.raises(ValueError):
        xplane_mesh.read({"xplane": _plane(0, [("a", 0, 1)], [])}, "ops_ms")


@pytest.mark.parametrize("name", ["collective_ms", "chip_busy_spread_pct"])
def test_the_metric_files_name_this_reader(name):
    spec = Spec()
    f = spec.layer_metric(name)
    assert f["reader"] == "xplane_mesh"
    assert f["layer"] == "Mesh collectives (parallel/mesh_search)"
    assert spec.per_layer[name]["workloads"] == \
        ["cohere-768-cos-mesh4.batch256"]
    assert spec.reader(f["reader"]).read({}, **f["params"]) is None


def _busy_by_cover(ops):
    ev = np.array([(s, s + d) for _, s, d in ops if d > 0])
    pts = np.unique(ev.ravel())
    cover = np.zeros(len(pts) - 1, int)
    for s, e in ev:
        cover[np.searchsorted(pts, s):np.searchsorted(pts, e)] += 1
    return int((pts[1:] - pts[:-1])[cover > 0].sum())


def test_recorded_four_chip_trace_reduces_to_the_numbers_read_by_hand():
    with open(FIXTURE) as f:
        t = xplane.from_json(f.read())
    assert sorted(t) == [f"/device:TPU:{n}" for n in range(4)]
    assert all(len(t[p][xplane.MODULES_LINE]) == 3
               and len(t[p][xplane.OPS_LINE]) == 744 for p in t)
    # one all-gather of s32[4,30,256] an execution; nanoseconds, by device
    gathers = {0: (5797, 6345, 5985), 1: (5587, 4904, 5576),
               2: (5623, 4796, 5726), 3: (3997, 4547, 4023)}
    for n, want in gathers.items():
        got = [d for name, _, d in t[f"/device:TPU:{n}"][xplane.OPS_LINE]
               if name.startswith("%all-gather")]
        assert tuple(got) == want
    value, notes = _read(t, "collective_ms", module="search")
    # medians 5985, 5576, 5623, 4023: the median device is the 5576 one
    assert value == pytest.approx(5576e-6)
    assert {p: d["ms"] for p, d in
            notes["collective_ms"]["by_device"].items()} == {
        "/device:TPU:0": pytest.approx(5985e-6),
        "/device:TPU:1": pytest.approx(5576e-6),
        "/device:TPU:2": pytest.approx(5623e-6),
        "/device:TPU:3": pytest.approx(4023e-6)}
    # the chips run in step: the slice is 41.757 ms of three back-to-back
    # executions, and every chip is busy for all but 8 to 13 us of it
    w0, w1 = xplane.window_ns(t)
    assert w1 - w0 == 41_757_136
    busy = {p: _busy_by_cover(t[p][xplane.OPS_LINE]) for p in t}
    assert busy == {"/device:TPU:0": 41_749_147, "/device:TPU:1": 41_746_737,
                    "/device:TPU:2": 41_746_740, "/device:TPU:3": 41_743_731}
    value, notes = _read(t, "busy_spread_pct")
    assert value == pytest.approx(
        100.0 * (41_749_147 - 41_743_731) / 41_757_136)
    assert value == pytest.approx(0.01297, abs=1e-5)
    assert notes["busy_pct_by_device"]["/device:TPU:3"] == pytest.approx(
        100.0 * 41_743_731 / 41_757_136)
    # the reader the benchmark had picks the same program: 13.919 ms
    from benchmarks.readers import xplane_ops
    assert xplane_ops.read({"xplane": t}, "module_ms", module="search") == \
        pytest.approx(13.918968)
