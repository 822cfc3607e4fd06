"""Inference latency benchmark harness.

Fixes the measurement-design flaws of the reference's harness
(reference notebooks/cv/onnx_experiments.py:90-104,130-139 — cold calls
timed, host transfer inside the latency window, OpenVINO "mean" over a
single sample, `latency` mutated as a closure global):
- warmup iterations excluded;
- host->device transfer timed separately from compute;
- percentiles, not just the mean;
- every timing window closed by a scalar host readback, so the window
  holds the device work and not only its enqueue.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np


@dataclasses.dataclass(frozen=True)
class LatencyStats:
    """Percentile summary of one timing series (milliseconds) — the ONE
    definition of "p50/p95/p99/max" (``latency_benchmark`` below)
    instead of each caller hand-rolling its own np.percentile calls.
    Only post-warmup samples should ever enter:
    serving SLOs are quoted at tail percentiles, and a mean/min pair
    hides exactly the outliers that matter."""

    count: int
    mean_ms: float
    p50_ms: float
    p95_ms: float
    p99_ms: float
    min_ms: float
    max_ms: float

    @classmethod
    def from_ms(cls, samples_ms: Sequence[float]) -> "LatencyStats":
        xs = np.asarray(samples_ms, dtype=np.float64)
        if xs.size == 0:
            raise ValueError(
                "LatencyStats needs at least one sample (callers decide "
                "how to render an empty series)"
            )
        return cls(
            count=int(xs.size),
            mean_ms=float(xs.mean()),
            p50_ms=float(np.percentile(xs, 50)),
            p95_ms=float(np.percentile(xs, 95)),
            p99_ms=float(np.percentile(xs, 99)),
            min_ms=float(xs.min()),
            max_ms=float(xs.max()),
        )

    @classmethod
    def from_seconds(cls, samples_s: Sequence[float]) -> "LatencyStats":
        return cls.from_ms(np.asarray(samples_s, dtype=np.float64) * 1e3)

    def as_dict(self) -> dict:
        """The legacy ``latency_benchmark`` stats schema (mean/p50/p95/
        p99/min/max, no count — existing consumers key on exactly
        these)."""
        return {
            "mean_ms": self.mean_ms,
            "p50_ms": self.p50_ms,
            "p95_ms": self.p95_ms,
            "p99_ms": self.p99_ms,
            "min_ms": self.min_ms,
            "max_ms": self.max_ms,
        }

    def percentiles(self, digits: int = 3) -> dict:
        """The serving tail summary ({p50,p95,p99}_ms, rounded)."""
        return {
            "p50_ms": round(self.p50_ms, digits),
            "p95_ms": round(self.p95_ms, digits),
            "p99_ms": round(self.p99_ms, digits),
        }


def _sync(out) -> float:
    """Force completion of `out` via scalar readbacks — one element per
    leaf, so every transfer/computation in the tree is fenced while only
    single elements cross to the host (never a full device-to-host copy).
    """
    total = 0.0
    for leaf in jax.tree.leaves(out):
        if isinstance(leaf, jax.Array):
            total += float(leaf.ravel()[0])
        else:
            total += float(np.asarray(leaf).ravel()[0])
    return total


def latency_benchmark(
    fn: Callable,
    host_args: Sequence[Any],
    device: Optional[jax.Device] = None,
    warmup: int = 5,
    iters: int = 30,
) -> dict:
    """Benchmark `fn` with transfer and compute measured separately."""
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    if device is None:
        device = jax.devices()[0]
    jitted = jax.jit(fn)

    # --- transfer: host -> device, timed per iteration; windows closed by
    # scalar readback (module docstring doctrine) ---
    transfer_ms = []
    for _ in range(warmup):
        placed = jax.tree.map(lambda a: jax.device_put(a, device), tuple(host_args))
        _sync(placed)
    for _ in range(iters):
        t0 = time.perf_counter()
        placed = jax.tree.map(lambda a: jax.device_put(a, device), tuple(host_args))
        _sync(placed)
        transfer_ms.append((time.perf_counter() - t0) * 1e3)

    # --- compute: device-resident args, synced by scalar readback ---
    # warmup=0 means the first timed iteration includes compilation.
    out = None
    for _ in range(warmup):
        out = jitted(*placed)
    if out is not None:
        _sync(out)
    compute_ms = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = jitted(*placed)
        _sync(out)
        compute_ms.append((time.perf_counter() - t0) * 1e3)

    # Only post-warmup iterations ever enter the series (the warmup
    # loops above run outside the timed windows), so these are
    # steady-state statistics.
    return {
        "device": str(device),
        "iters": iters,
        "warmup": warmup,
        "transfer": LatencyStats.from_ms(transfer_ms).as_dict(),
        "compute": LatencyStats.from_ms(compute_ms).as_dict(),
    }
