"""The chip requirement and the table of peaks (the benchmark's own copy)."""

from __future__ import annotations

from typing import Any, Dict

#: Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
#: Source: Google Cloud documentation, "TPU v5e" system architecture page:
#: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2**30},
}


class NoChip(SystemExit):
    """Raised (exit code 3) when the cell's chips are not there."""


def peaks_for(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise NoChip(f"benchmark: device kind {kind!r} is not in the table of peaks (benchmarks/harness/device.py)") from None


def require_chips(chips: int) -> Dict[str, Any]:
    """A TPU with exactly ``chips`` chips, or exit non-zero with no result line."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as err:  # no backend at all
        raise NoChip(f"benchmark: JAX found no device: {err}") from None
    report = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices)}
    if report["platform"] != "tpu":
        raise NoChip(f"benchmark: needs a TPU, JAX found {report}")
    if report["count"] != chips:
        raise NoChip(f"benchmark: the cell needs {chips} chip(s), JAX sees {report['count']}")
    peaks_for(report["kind"])
    return report


def memory_peak_bytes() -> int:
    """``peak_bytes_in_use`` on the fullest device."""
    import jax

    peaks = []
    for device in jax.devices():
        stats = device.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
