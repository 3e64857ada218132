"""bench.py roofline fields + perf regression gate (VERDICT r4 item 2).

The reference gates perf in CI (test/benchmark/run_performance_tracker.sh,
benchmark_sift.go:35-53); our analog lives in bench.py's matrix merge. These
tests pin the arithmetic (so a wrong constant can't silently misreport MFU)
and the gate's compare/skip semantics.
"""

import json

import pytest

import bench


def test_roofline_math_tpu_row():
    # 10k QPS over n=1M, d=128, batch=16384, f32 store:
    # flops/batch = 2*16384*1e6*128 = 4.194e12; batches/s = 10000/16384
    r = bench._roofline(10_000.0, 1_000_000, 128, 16_384, 128 * 4, bench.costmodel.TPU_V5E)
    assert r["tflops"] == pytest.approx(2 * 16384 * 1e6 * 128 * (10000 / 16384) / 1e12, rel=1e-3)
    assert r["hbm_gbs"] == pytest.approx(1e6 * 512 * (10000 / 16384) / 1e9, abs=0.01)
    assert r["mfu_pct"] == pytest.approx(100 * r["tflops"] / 197.0, abs=0.01)
    assert r["bw_pct"] == pytest.approx(100 * r["hbm_gbs"] / 819.0, abs=0.01)
    # AI = 2*B/bytes_per_elem = 2*16384/4 = 8192 >> ridge (~240): compute-bound
    assert r["arith_intensity_flops_per_byte"] == pytest.approx(8192, rel=1e-3)
    assert r["regime"] == "compute-bound"


def test_roofline_small_batch_is_bandwidth_bound():
    # batch=256 f32: AI = 128 flops/byte < v5e ridge ~240
    r = bench._roofline(1_000.0, 100_000, 128, 256, 128 * 4, bench.costmodel.TPU_V5E)
    assert r["regime"] == "hbm-bandwidth-bound"


def test_qps_fields_walks_nested_rows():
    row = {
        "qps": 100.0, "qps_e2e": 50.0, "p50_ms": 3.0,
        "qps_8term": 25.0, "qps_8term_zipf": 30.0,  # bm25_cpu shape
        "uncompressed": {"qps": 10.0, "recall@10": 1.0},
        "selectivities": {"1pct": {"qps": 5.0}, "10pct": {"qps": 7.0}},
    }
    got = dict(bench._qps_fields(row))
    assert got == {"qps": 100.0, "qps_e2e": 50.0,
                   "qps_8term": 25.0, "qps_8term_zipf": 30.0,
                   "uncompressed.qps": 10.0,
                   "selectivities.1pct.qps": 5.0,
                   "selectivities.10pct.qps": 7.0}


@pytest.fixture()
def clean_gate():
    bench._REGRESSIONS.clear()
    yield
    bench._REGRESSIONS.clear()


def test_gate_flags_regression_same_backend_only(clean_gate):
    old = {
        "rowA": {"backend": "cpu", "qps": 100.0},
        "rowB": {"backend": "tpu-v5e", "qps": 100.0},        # backend differs
        "rowC": {"backend": "cpu", "qps": 100.0, "stale": "old"},  # stale: skip
        "rowD": {"backend": "cpu", "qps": 100.0},
    }
    new = {
        "rowA": {"backend": "cpu", "qps": 80.0},    # -20%: flag
        "rowB": {"backend": "cpu", "qps": 10.0},    # backend changed: skip
        "rowC": {"backend": "cpu", "qps": 10.0},    # old was stale: skip
        "rowD": {"backend": "cpu", "qps": 95.0},    # -5% inside gate: ok
    }
    bench._gate_check(old, new)
    assert [r["row"] for r in bench._REGRESSIONS] == ["rowA"]
    assert bench._REGRESSIONS[0]["drop_pct"] == 20.0
    with pytest.raises(SystemExit) as exc:
        bench._gate_exit()
    assert exc.value.code == 4


def test_gate_skips_mismatched_workload_shape(clean_gate):
    # a smoke run at a smaller n must not race the full-size artifact row
    bench._gate_check(
        {"r": {"backend": "cpu", "n": 200_000, "qps": 100.0}},
        {"r": {"backend": "cpu", "n": 20_000, "qps": 10.0}})
    assert not bench._REGRESSIONS


def test_gate_clean_run_exits_quietly(clean_gate):
    bench._gate_check({"r": {"backend": "cpu", "qps": 100.0}},
                      {"r": {"backend": "cpu", "qps": 101.0}})
    assert not bench._REGRESSIONS
    bench._gate_exit()  # no raise


def test_gate_env_off(clean_gate, monkeypatch):
    monkeypatch.setenv("BENCH_GATE", "0")
    bench._gate_check({"r": {"backend": "cpu", "qps": 100.0}},
                      {"r": {"backend": "cpu", "qps": 1.0}})
    assert not bench._REGRESSIONS


def test_merge_matrix_runs_gate(clean_gate, tmp_path, monkeypatch):
    mfile = tmp_path / "m.json"
    monkeypatch.setattr(bench, "MATRIX_FILE", str(mfile))
    bench._merge_matrix({"row": {"backend": "cpu", "qps": 100.0, "round": 5}})
    assert not bench._REGRESSIONS
    bench._merge_matrix({"row": {"backend": "cpu", "qps": 50.0, "round": 5}})
    assert bench._REGRESSIONS and bench._REGRESSIONS[0]["row"] == "row"
    data = json.loads(mfile.read_text())
    assert data["row"]["qps"] == 50.0  # artifacts still written
