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
event. ONE registration (``install_listeners``, made at ``tpudl.runtime``
import and again, idempotently, by every reader below) keeps what JAX
says of each program it builds: process-global totals that watchers
snapshot (so nesting and concurrent use are safe and no listener is
ever unregistered; jax only offers clear-all), the persistent compile
cache's hit and miss counters, and one span record a program and stage
(``program.trace`` / ``program.lower`` / ``program.compile``), written
to the active span recorder or else to the start-up recorder
(``tpudl.obs.spans.startup_recorder``), so that a set-up nobody was
watching can still be read by program afterwards.

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

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRANSFER_KINDS = ("h2d", "d2h", "d2d")

_compiles = 0
_compile_seconds = 0.0
_STAGE = {
    _TRACE_EVENT: "program.trace",
    _LOWER_EVENT: "program.lower",
    _COMPILE_EVENT: "program.compile",
}
_compiles_mu = threading.Lock()
_listener_installed = False
_install_mu = threading.Lock()


class _Building(threading.local):
    """What the calling thread is in the middle of: the stages JAX has
    begun and not ended, innermost last (a span, or None for a stage
    that is part of the one around it), and what the compile cache has
    said since the last ``program.compile``."""

    def __init__(self):
        self.stages = []
        self.cache = {}


_building = _Building()


class DispatchHygieneError(AssertionError):
    """A hot loop recompiled or implicitly transferred after warmup."""


def _program(fun_name: str) -> str:
    """JAX names a trace by the function (``tpudl_prefill``) and the
    lowering and the compile by the module (``jit(tpudl_prefill)``):
    one name for the three."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _on_stage_begin(event: str, value, fun_name: str = "", **kwargs) -> None:
    # JAX announces a stage as it begins (a scalar, the start time) and
    # times it as it ends (the duration, below), in a ``with`` of its
    # own: the two come in pairs, innermost first, whatever is raised
    # in between. The span is OPEN in between, so that what the stage
    # holds (a ``kernel.trace``) is its child and not its sibling.
    if event not in _STAGE:
        return
    stages = _building.stages
    if event != _COMPILE_EVENT and any(stages):
        # A jitted function traced inside another's trace (every
        # ``jnp`` function is one) or inside a lowering: its seconds
        # are the outer program's, whose span holds them.
        stages.append(None)
        return
    from tpudl.obs import spans as obs_spans

    stages.append(obs_spans.startup_recorder().begin(
        _STAGE[event], obs_spans.CAT_COMPILE, program=_program(fun_name)
    ))


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    global _compiles, _compile_seconds
    if event == _CACHE_READ_EVENT:
        _building.cache["cache_read_s"] = duration
        return
    if event not in _STAGE:
        return
    attrs = {}
    if event == _COMPILE_EVENT:
        with _compiles_mu:
            _compiles += 1
            _compile_seconds += duration
        attrs, _building.cache = _building.cache, {}
    stages = _building.stages
    span = stages.pop() if stages else None
    if span is not None:
        span.end_lasting(duration, **attrs)  # JAX's own seconds


def _on_cache_event(event: str, **kwargs) -> None:
    if event not in (_HIT_EVENT, _MISS_EVENT):
        return
    from tpudl.obs import counters as obs_counters
    from tpudl.obs import spans as obs_spans

    hit = event == _HIT_EVENT
    _building.cache["cache_hit"] = int(hit)
    name = "compile_cache_hits" if hit else "compile_cache_misses"
    obs_counters.registry().counter(name).inc()
    rec = obs_spans.active_recorder()
    if rec is not None:
        rec.event(name[:-1], "compile")


def install_listeners() -> None:
    """The one hook-up to ``jax.monitoring``. Idempotent."""
    global _listener_installed
    if _listener_installed:
        return
    with _install_mu:
        if _listener_installed:
            return
        import jax.monitoring

        jax.monitoring.register_scalar_listener(_on_stage_begin)
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event
        )
        jax.monitoring.register_event_listener(_on_cache_event)
        _listener_installed = True


def compile_count() -> int:
    """Backend compiles observed process-wide since the listener
    installed (monotonic; diff two reads to bracket a region)."""
    install_listeners()
    with _compiles_mu:
        return _compiles


def compile_seconds() -> float:
    """Seconds spent in backend compiles since the listener installed
    (monotonic, like :func:`compile_count`)."""
    install_listeners()
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
