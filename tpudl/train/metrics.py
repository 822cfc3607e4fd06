"""Throughput and MFU accounting.

The reference reports wall-clock latency means from Python lists
(reference: notebooks/cv/onnx_experiments.py:90-104,130-140). Here the two
BASELINE.json `metric` quantities — images/sec/chip and samples/sec — plus
MFU are first-class (SURVEY.md §5.5). FLOPs come from the compiled
executable's cost analysis with an analytic fallback.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np

#: Peak dense bf16 FLOP/s per chip. Sources: public TPU spec sheets.
PEAK_FLOPS = {
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "cpu": 1e12,  # nominal, for the CPU tests; not a device metric
}


def device_peak_flops(device: Optional[jax.Device] = None) -> float:
    """Peak FLOP/s of ``device`` (default: the first) from PEAK_FLOPS.
    A ``device_kind`` the table does not know is an error, never a
    default: a utilization over the wrong peak looks like a result."""
    if device is None:
        device = jax.devices()[0]
    kind = str(device.device_kind)
    for name, peak in PEAK_FLOPS.items():
        if name.lower() in kind.lower():
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r}: add it to "
        f"tpudl.train.metrics.PEAK_FLOPS with its source "
        f"(known: {sorted(PEAK_FLOPS)})"
    )


def compiled_flops(lowered_or_compiled) -> Optional[float]:
    """FLOPs per invocation from XLA cost analysis, if the backend reports it."""
    try:
        compiled = (
            lowered_or_compiled.compile()
            if hasattr(lowered_or_compiled, "compile")
            else lowered_or_compiled
        )
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        flops = cost.get("flops")
        return float(flops) if flops else None
    except Exception:
        return None


def transformer_train_flops(num_params: int, tokens_per_step: int) -> float:
    """Analytic fallback: 6*N*D for a transformer fwd+bwd step."""
    return 6.0 * num_params * tokens_per_step


def mfu(
    flops_per_step: float,
    step_seconds: float,
    num_chips: int = 1,
    peak_per_chip: Optional[float] = None,
) -> float:
    if peak_per_chip is None:
        peak_per_chip = device_peak_flops()
    return flops_per_step / (step_seconds * num_chips * peak_per_chip)


class Throughput:
    """Steady-state throughput meter: skips warmup/compile steps, blocks on
    device results only at boundaries (the reference times cold calls and
    includes host transfer in the window — SURVEY.md §5.1)."""

    def __init__(self, items_per_step: int, warmup: int = 2):
        self.items_per_step = items_per_step
        self.warmup = warmup
        self._count = 0
        # warmup=0 means "count every step": the window opens at construction.
        self._start = time.perf_counter() if warmup == 0 else None
        self._measured_steps = 0

    def step(self, sync_value=None):
        self._count += 1
        if self._count == self.warmup:
            if sync_value is not None:
                jax.block_until_ready(sync_value)
            self._start = time.perf_counter()
        elif self._count > self.warmup:
            self._measured_steps += 1

    def result(self, sync_value=None) -> dict:
        if sync_value is not None:
            jax.block_until_ready(sync_value)
        if self._measured_steps == 0 or self._start is None:
            return {
                "steps_measured": 0,
                "seconds": 0.0,
                "items_per_sec": 0.0,
                "step_ms": 0.0,
            }
        elapsed = time.perf_counter() - self._start
        steps = self._measured_steps
        per_sec = self.items_per_step * steps / elapsed if elapsed > 0 else 0.0
        return {
            "steps_measured": steps,
            "seconds": elapsed,
            "items_per_sec": per_sec,
            "step_ms": 1000.0 * elapsed / steps if elapsed > 0 else 0.0,
        }


class MetricFetcher:
    """Asynchronous device->host metrics drain for the train loop.

    Under JAX async dispatch, ``float(metrics["loss"])`` on the main
    thread stalls the dispatch pipeline until the step that produced the
    metric finishes — the per-logged-step readback the round-5 bench
    showed idling the device between dispatches. This fetcher moves the
    readback off-thread: ``fit()`` submits each dispatch's DEVICE
    metrics (a dict of scalars, or [K]-stacked leaves from a fused
    K-step dispatch) and keeps dispatching; a single worker thread
    converts them to host floats (blocking on the device in the
    background) and queues per-step host dicts that the loop drains —
    without blocking — on subsequent iterations.

    ``window`` bounds how many dispatches' metrics may be in flight:
    holding a metrics tree pins its device buffers live, so the window
    is device memory, and a consumer that outruns readback indefinitely
    would otherwise grow the queue without bound. ``submit`` past the
    window blocks and reports the blocked seconds, which the train loop
    records as a ``metric_wait`` span — the one place steady-state
    metric backpressure is visible.

    The tradeoff is STALENESS, not loss: every logger callback still
    fires, in step order, from the consumer's thread — just up to
    ``window`` dispatches after the step ran. ``flush()`` at epoch /
    checkpoint / end-of-fit boundaries forces the queue dry.

    Worker errors surface on the consumer's next ``submit``/``ready``/
    ``flush`` call.
    """

    def __init__(
        self,
        window: int = 8,
        clock: Callable[[], float] = time.perf_counter,
    ):
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self._window = int(window)
        self._clock = clock
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._done = threading.Condition(self._lock)
        self._pending: collections.deque = collections.deque()
        self._ready: collections.deque = collections.deque()
        self._outstanding = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="tpudl-metric-fetcher", daemon=True
        )
        self._thread.start()
        # Live health: the sticky worker error is exactly the failure
        # mode an operator cannot see from outside (the loop keeps
        # dispatching until its next submit raises) — surface it on
        # /healthz the moment the worker dies. Latest fetcher wins the
        # name; its error stays visible even after close().
        from tpudl.obs import exporter as obs_exporter

        obs_exporter.register_health_source("metric_fetcher", self.health)

    def health(self) -> dict:
        with self._lock:
            err = self._error
            return {
                "healthy": err is None,
                "error": f"{type(err).__name__}: {err}"
                if err is not None
                else None,
                "outstanding": self._outstanding,
                "closed": self._closed,
            }

    # -- consumer side (the train loop's thread) -----------------------

    def submit(self, first_step: int, metrics: dict, count: int = 1) -> float:
        """Queue one dispatch's device metrics covering steps
        ``first_step .. first_step + count - 1`` (``count`` > 1 means
        each leaf is [count]-stacked). Returns seconds blocked on the
        window (0.0 in the steady state)."""
        waited = 0.0
        with self._work:
            self._raise_pending()
            if self._closed:
                raise RuntimeError("MetricFetcher is closed")
            if self._outstanding >= self._window:
                t0 = self._clock()
                while (
                    self._outstanding >= self._window
                    and not self._closed
                    and self._error is None
                ):
                    self._done.wait()
                waited = self._clock() - t0
                self._raise_pending()
                if self._closed:
                    raise RuntimeError("MetricFetcher is closed")
            self._pending.append((int(first_step), int(count), metrics))
            self._outstanding += 1
            self._work.notify()
        return waited

    def ready(self) -> List[Tuple[int, dict]]:
        """Drain completed (step, host_metrics) pairs, non-blocking."""
        with self._lock:
            self._raise_pending()
            out = list(self._ready)
            self._ready.clear()
            return out

    def flush(self) -> List[Tuple[int, dict]]:
        """Block until every submitted dispatch is converted; drain.
        Raises the worker's error instead if readback failed (pending
        conversions behind the failure are abandoned — the worker is
        gone and their device metrics may be poisoned the same way)."""
        with self._done:
            while (
                self._outstanding > 0
                and self._error is None
                and not self._closed
            ):
                self._done.wait()
            self._raise_pending()
            out = list(self._ready)
            self._ready.clear()
            return out

    def close(self) -> None:
        """Stop the worker (idempotent). Pending conversions are
        abandoned; call ``flush()`` first to keep them."""
        with self._work:
            if self._closed:
                return
            self._closed = True
            self._work.notify_all()
            self._done.notify_all()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=5.0)

    def __enter__(self) -> "MetricFetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _raise_pending(self) -> None:
        # Sticky on purpose: every later submit/ready/flush keeps
        # raising — clearing it once let fit()'s finally-block flush
        # wait forever on work a dead worker would never finish.
        if self._error is not None:
            raise self._error

    # -- worker side ---------------------------------------------------

    def _run(self) -> None:
        while True:
            with self._work:
                while not self._pending and not self._closed:
                    self._work.wait()
                if not self._pending:
                    return  # closed and drained
                first_step, count, metrics = self._pending.popleft()
            try:
                # np.asarray blocks on the device HERE, in the worker —
                # the whole point: the train loop's thread never does.
                host = {k: np.asarray(v) for k, v in metrics.items()}
                rows = []
                for j in range(count):
                    rows.append((
                        first_step + j,
                        {
                            k: float(a[j]) if count > 1 else float(a)
                            for k, a in host.items()
                        },
                    ))
            except BaseException as e:
                with self._done:
                    # The worker dies here: abandon everything still
                    # pending (nothing will ever convert it) so no
                    # consumer waits on outstanding work that cannot
                    # complete.
                    self._error = e
                    self._outstanding -= 1 + len(self._pending)
                    self._pending.clear()
                    self._done.notify_all()
                    self._work.notify_all()
                return
            with self._done:
                self._ready.extend(rows)
                self._outstanding -= 1
                self._done.notify_all()


def measure_step_time(
    fn: Callable, *args, warmup: int = 3, iters: int = 10
) -> float:
    """Mean seconds per call with warmup excluded and device sync at the
    boundaries (fixes the reference's cold-call timing at
    notebooks/cv/onnx_experiments.py:92-95)."""
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters
