"""The device: what JAX reports, the table of peaks, peak memory."""

from __future__ import annotations

import sys

#: Published peaks of one chip, keyed by ``device_kind``. Source: Google
#: Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM.
#: A device that is not listed is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; add it to "
            f"perfbench/device.py with its source"
        )
    return PEAKS[kind]


def require_chips(chips: int, allow_cpu: bool = False) -> dict:
    """The device as JAX reports it. Exits non-zero, printing no result,
    unless it is an accelerator with at least ``chips`` chips.
    ``allow_cpu`` is the rehearsal path of ``perfbench/tests`` only."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] == "cpu" and not allow_cpu:
        sys.exit(f"perfbench: needs an accelerator, JAX found {info}")
    if info["count"] < chips:
        sys.exit(f"perfbench: the cell needs {chips} chip(s), JAX found {info}")
    info["count"] = chips
    return info


def memory_peak_bytes(chips: int) -> dict:
    """Peak device memory on the fullest of the first ``chips`` chips.

    ``peak_bytes_in_use`` counts live buffers (weights, caches, state);
    a running program's temporaries sit in ``peak_bytes_reserved`` when
    the runtime reports it apart (PERF.md, PR 21). The peak reported is
    the larger of the two readings."""
    import jax

    in_use, reserved = 0, 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        in_use = max(in_use, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
    return {
        "memory_peak_bytes": max(in_use, reserved),
        "peak_bytes_in_use": in_use,
        "peak_bytes_reserved": reserved,
    }
