"""Dispatch-hygiene audits: recompiles, implicit host transfers, and
(via tpudl.analysis.donation) lost buffer donation.

The paper's behavioral signature is "export -> backends -> measured
latency" (PAPER.md §0); every PR 8-11 review round hand-found the same
silent regressions in the hot loops: a shape that quietly recompiles
per step, an eager readback that serializes the dispatch pipeline, a
donated buffer that silently copies. These context managers make those
audits reusable — in tests, in the benchmark (perfbench wraps its timed
window in a RecompileWatcher), and ad hoc around any suspect loop:

    with assert_no_recompiles():
        for _ in range(50):
            engine.step()

    with assert_no_host_transfers(allow=("h2d",)):
        run_decode_steady_state()

**Recompiles** are counted via the ``jax.monitoring`` backend-compile
event — the same channel the persistent compile cache's hit counters
ride (tpudl.runtime.compile_cache). One module-level listener feeds a
process-global counter; watchers snapshot it, so nesting and
concurrent use are safe and no listener is ever unregistered (jax only
offers clear-all).

**Host transfers** use ``jax.transfer_guard`` in ``disallow`` mode,
which blocks IMPLICIT transfers only: explicit ``jax.device_put`` /
``jax.device_get`` pass. That is the audit contract — every intended
transfer in a hot loop must be explicit, so anything implicit after
warmup is a regression. ``allow=("h2d",)`` exempts a direction (the
serving decode loop feeds small per-step control arrays from host by
design). Platform caveat: the CPU backend's device-to-host path is
zero-copy and never guarded, so d2h regressions only trip on real
accelerators — tier-1 fixtures therefore seed h2d violations.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Iterable, Optional

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_TRANSFER_KINDS = ("h2d", "d2h", "d2d")

_compiles = 0
_compile_seconds = 0.0
_compiles_mu = threading.Lock()
_listener_installed = False
_install_mu = threading.Lock()


class DispatchHygieneError(AssertionError):
    """A hot loop recompiled or implicitly transferred after warmup."""


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    global _compiles, _compile_seconds
    if event == _COMPILE_EVENT:
        with _compiles_mu:
            _compiles += 1
            _compile_seconds += duration


def _ensure_listener() -> None:
    global _listener_installed
    if _listener_installed:
        return
    with _install_mu:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event
        )
        _listener_installed = True


def compile_count() -> int:
    """Backend compiles observed process-wide since the listener
    installed (monotonic; diff two reads to bracket a region)."""
    _ensure_listener()
    with _compiles_mu:
        return _compiles


def compile_seconds() -> float:
    """Seconds spent in backend compiles since the listener installed
    (monotonic, like :func:`compile_count`)."""
    _ensure_listener()
    with _compiles_mu:
        return _compile_seconds


class RecompileWatcher:
    """Counts backend compiles inside a ``with`` region without
    asserting — the benchmark form (perfbench reports the count)."""

    def __init__(self, label: str = ""):
        self.label = label
        self._start: Optional[int] = None
        self._count = 0

    @property
    def count(self) -> int:
        if self._start is None:
            return self._count
        return compile_count() - self._start

    def __enter__(self) -> "RecompileWatcher":
        _ensure_listener()
        self._start = compile_count()
        return self

    def __exit__(self, *exc) -> bool:
        self._count = compile_count() - self._start
        self._start = None
        return False


@contextlib.contextmanager
def assert_no_recompiles(allow: int = 0, label: str = ""):
    """Fail if more than ``allow`` backend compiles happen inside the
    region. Wrap the STEADY STATE (after warmup has compiled every
    program the loop legitimately uses); a recompile inside means a
    shape/dtype/static-arg is quietly varying per step."""
    with RecompileWatcher(label=label) as watcher:
        yield watcher
    if watcher.count > allow:
        where = f" in {label}" if label else ""
        raise DispatchHygieneError(
            f"{watcher.count} backend compile(s){where} after warmup "
            f"(allowed {allow}) — some dispatch in the steady state is "
            f"recompiling; look for a python-varying shape, dtype, or "
            f"static argument"
        )


@contextlib.contextmanager
def assert_no_host_transfers(
    allow: Iterable[str] = (), label: str = ""
):
    """Disallow IMPLICIT transfers inside the region; ``allow`` names
    directions to exempt ("h2d", "d2h", "d2d"). Explicit
    ``device_put``/``device_get`` always pass — intent made visible is
    the contract. The offending transfer raises AT ITS SITE (jax's
    guard error names the aval); this wrapper re-raises it as
    :class:`DispatchHygieneError` with the audit context attached.

    Thread-local, like every jax config context: guards apply to the
    auditing thread only (a MetricFetcher readback on its own thread
    is untouched)."""
    import jax

    allow = set(allow)
    unknown = allow - set(_TRANSFER_KINDS)
    if unknown:
        raise ValueError(
            f"unknown transfer kinds {sorted(unknown)}; expected a "
            f"subset of {_TRANSFER_KINDS}"
        )
    guards = {
        "h2d": jax.transfer_guard_host_to_device,
        "d2h": jax.transfer_guard_device_to_host,
        "d2d": jax.transfer_guard_device_to_device,
    }
    with contextlib.ExitStack() as stack:
        for kind, guard in guards.items():
            stack.enter_context(
                guard("allow" if kind in allow else "disallow")
            )
        try:
            yield
        except Exception as e:
            if "transfer" in str(e).lower() and "Disallowed" in str(e):
                where = f" in {label}" if label else ""
                raise DispatchHygieneError(
                    f"implicit host transfer{where} after warmup: {e} "
                    f"— make the intended transfer explicit "
                    f"(jax.device_put/device_get) or pass "
                    f"allow=(...) if this direction is by design"
                ) from e
            raise
