"""Peaks of the chips and the work a scan needs, kept with the benchmark.

Copied from weaviate_tpu/monitoring/costmodel.py (`PEAKS`, `DispatchShape`'s
arithmetic) so that no PR which claims a gain can move the yardstick; the
original is listed in PERF.md. A device that is not in the table is an error,
never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197.0e12,      # bf16 MXU
        "hbm_bytes_per_s": 819.0e9,
        "hbm_bytes": 16 * 2 ** 30,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add the "
                       f"chip to benchmarks/lib/costs.PEAKS with its source "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def scan_flops(batch: int, rows: int, dim: int) -> float:
    """The useful distance math of one exhaustive scan: one multiply-add per
    (query, row, component). Implementation FLOPs (extra passes, padding)
    do not count."""
    return 2.0 * batch * rows * dim


def scan_bytes(rows: int, dim: int, bytes_per_component: int = 4) -> float:
    """The store bytes one scan has to read from HBM: every row once.
    Queries and top-k buffers are noise at these shapes."""
    return float(rows) * dim * bytes_per_component


def roofline_share(flops: float, bytes_: float, seconds: float,
                   device_kind: str) -> tuple[float, str]:
    """(least time the chip could take / time taken, in %; which bound)."""
    p = peaks(device_kind)
    t_flops = flops / p["flops_per_s"]
    t_bytes = bytes_ / p["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "hbm"
    return 100.0 * max(t_flops, t_bytes) / max(seconds, 1e-12), bound
