"""What the process runs on (weaviate_tpu/device.py), the peaks table keyed
by device_kind (monitoring/costmodel.py), the native build's staleness rule
(weaviate_tpu/_native) and the health()["kernels"] block — each an error or
a visible fact where the code used to pick a default."""

import os
import subprocess
import time

import numpy as np
import pytest

from weaviate_tpu import _native, device
from weaviate_tpu.monitoring import costmodel


# -- peaks: keyed by device_kind, no default ----------------------------------


def test_peaks_key_maps_known_kinds():
    assert costmodel.peaks_key("tpu", "TPU v5 lite") == costmodel.TPU_V5E
    assert costmodel.peaks_key("cpu", "cpu") == "cpu"
    assert "source" in costmodel.PEAKS[costmodel.TPU_V5E]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="TPU v9"):
        costmodel.peaks_key("tpu", "TPU v9")
    with pytest.raises(KeyError, match="gpu"):
        costmodel.peaks_key("gpu", "NVIDIA H100")
    with pytest.raises(KeyError):
        costmodel.roofline(1.0, 1.0, 1.0, "tpu-v9")


def test_failed_detection_raises_and_is_not_cached(monkeypatch):
    monkeypatch.setattr(costmodel, "_detected_backend", None)

    def boom():
        raise RuntimeError("no backend came up")

    with monkeypatch.context() as m:
        m.setattr(device, "identity", boom)
        with pytest.raises(RuntimeError, match="no backend"):
            costmodel.detect_backend()
    assert costmodel.detect_backend() == "cpu"  # the test tier


# -- one kernel-mode function -------------------------------------------------


def test_pallas_interpret_by_backend(monkeypatch):
    import jax

    assert device.pallas_interpret() is True  # the suite runs on cpu
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert device.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        device.pallas_interpret()


def test_identity_reports_the_live_backend():
    import jax

    ident = device.identity()
    assert ident == {"platform": "cpu", "device_kind": "cpu",
                     "count": len(jax.devices())}


# -- compile cache placed from outside ----------------------------------------


def test_compile_cache_leaves_jax_alone_when_env_set(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert device.enable_compile_cache() == "/somewhere/else"
    assert calls == []


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert device.enable_compile_cache() == want
    assert calls["jax_compilation_cache_dir"] == want
    # a fixed path: the same call names the same directory
    assert device.enable_compile_cache() == want


# -- native libraries: rebuilt when older than their source -------------------


@pytest.fixture
def native_sandbox(tmp_path, monkeypatch):
    lib_dir, src_dir = tmp_path / "_native", tmp_path / "native"
    lib_dir.mkdir()
    src_dir.mkdir()
    monkeypatch.setattr(_native, "_DIR", str(lib_dir))
    monkeypatch.setattr(_native, "_SRC_DIR", str(src_dir))
    monkeypatch.setattr(_native, "_LIBS", {"toy": ("toy.cpp", ())})
    monkeypatch.setattr(_native, "STATUS", {})
    (src_dir / "toy.cpp").write_text(
        'extern "C" int toy_answer() { return 42; }\n')
    return lib_dir, src_dir


def test_native_library_older_than_source_is_rebuilt(native_sandbox):
    import ctypes

    lib_dir, src_dir = native_sandbox
    so = lib_dir / "libtoy.so"
    so.write_bytes(b"left over from an older source")
    old = time.time() - 3600
    os.utime(so, (old, old))
    path = _native.ensure_built("toy")
    assert _native.STATUS["toy"] == "built"
    assert ctypes.CDLL(path).toy_answer() == 42
    # now newer than its source: loaded as is, no compile
    _native.STATUS.clear()
    before = os.path.getmtime(path)
    assert _native.ensure_built("toy") == path
    assert _native.STATUS["toy"] == "loaded"
    assert os.path.getmtime(path) == before


def test_native_build_failure_is_recorded_not_silent(native_sandbox):
    lib_dir, src_dir = native_sandbox
    (src_dir / "toy.cpp").write_text("this is not C++\n")
    with pytest.raises(subprocess.CalledProcessError):
        _native.ensure_built("toy")
    assert _native.STATUS["toy"].startswith("build_failed")
    assert not list(lib_dir.iterdir())  # no half-written library left


# -- health()["kernels"]: positive proof a kernel ran compiled ----------------


def test_health_kernels_counts_validated_and_rejected_shapes(tmp_path):
    from weaviate_tpu.entities.vectorindex import parse_and_validate_config
    from weaviate_tpu.index.tpu import TpuVectorIndex
    from weaviate_tpu.ops import gmin_scan

    cfg = parse_and_validate_config("hnsw_tpu", {"distance": "l2-squared"})
    idx = TpuVectorIndex(cfg, str(tmp_path), persist=False)
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((16384, 32)).astype(np.float32)
    idx.add_batch(np.arange(len(vecs)), vecs)
    k0 = idx.health()["kernels"]
    assert k0["gmin"]["validated"] == 0 and k0["gmin"]["rejected"] == 0

    ids, _ = idx.search_by_vectors(vecs[:16], 5)  # b >= 8: the gmin kernel
    assert [int(r[0]) for r in ids] == list(range(16))
    k1 = idx.health()["kernels"]["gmin"]
    assert k1["validated"] == 1 and k1["rejected"] == 0
    assert k1["validated_shapes"][0][0] == 16  # the padded batch

    def mosaic_says_no():
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    assert gmin_scan.guarded_kernel_call(
        idx._pqg_state, (16, 5, "some-shape"), mosaic_says_no,
        "fused pq codes kernel", component="index.tpu.pq_gmin") is None
    k2 = idx.health()["kernels"]
    assert k2["pq_gmin"]["rejected"] == 1
    assert k2["pq_gmin"]["rejected_shapes"] == [[16, 5, "some-shape"]]
    assert k2["gmin"]["validated"] == 1  # separate failure domains
    assert k2["pq4"]["stage1_pallas_dispatches"] == 0
