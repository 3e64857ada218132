"""chip_smoke.py itself: its phases pass against a CPU server child at a
small scale when told to expect `cpu`; as the driver runs it (no arguments,
no accelerator here) it fails within seconds, before any data is loaded."""

import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_phases_pass_against_a_cpu_server_child():
    import chip_smoke

    report = chip_smoke.run(seed=0, rows=20_000, pq_rows=20_000,
                            expect_platform="cpu")
    assert report["ok"], report.get("error")
    assert report["device"]["platform"] == "cpu"
    assert report["rows"] == 20_000 and report["dim"] == 128
    for name in ("search_b1", "batch256", "filtered", "pq4_funnel"):
        assert report["recall"][name] >= 0.95, (name, report["recall"])
    assert report["recall"]["pq8_self_rank1"] == 1.0
    for key in ("Smoke.gmin", "SmokePq8.pq_gmin", "SmokePq4.pq4"):
        k = report["kernels"][key]
        assert k["validated"] >= 1 and k["rejected"] == 0, (key, k)
    assert report["pq4_stage1"] == "pallas"
    assert report["fallback_samples"] == {} and report["breaker_state"] == 0
    assert not any(v.startswith("build_failed")
                   for v in report["native"].values())
    for name in ("import", "batch256.first_query", "batch256.steady_query",
                 "pq8_fit", "pq4_fit", "shutdown"):
        assert name in report["phase_seconds"], name
    assert report["compile_cache"]["dir"]
    # the driver's contract for the last line: exactly these keys
    last = chip_smoke.result_line(report)
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["kind"], str)
    assert type(last["device"]["count"]) is int and last["device"]["count"] >= 1


def test_cli_success_ends_with_the_result_line(monkeypatch, capsys):
    import json

    import chip_smoke

    report = {"ok": True, "rows": 5, "device": {
        "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1}}
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **kw: report)
    assert chip_smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines[-2].startswith("observations: ")
    assert json.loads(lines[-2].split(": ", 1)[1])["rows"] == 5
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def _run_cli(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)
    return proc, time.monotonic() - t0


def test_cli_without_an_accelerator_fails_before_loading_data():
    proc, secs = _run_cli(REPO, os.path.join(REPO, "chip_smoke.py"))
    assert proc.returncode != 0
    assert secs < 60
    assert "platform cpu" in proc.stderr
    assert "[import]" not in proc.stdout  # no data was loaded
    last = proc.stdout.strip().splitlines()[-1]
    assert not last.startswith("{")  # no result line


def test_cli_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc, _ = _run_cli(str(tmp_path), str(tmp_path / "chip_smoke.py"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
