"""SLO-driven autoscaler: the control loop that consumes the hint.

ROADMAP item 2 named this module outright — "the autoscaler that
consumes the hint". The router publishes the scale-out signal
(``serve_router_autoscale_hint`` = burning + unready replicas), the SLO
monitors publish burn state, the scraped health carries queue depth;
this module closes the loop: measured fleet state in, ``add_replica``
/ ``remove_replica`` out (the paper's behavioral signature — measure,
then act on the measurement, never guess).

Hysteresis, because every input flickers at a burn edge:

- **scale-up** requires the pressure signal (SLO burn, a nonzero
  autoscale hint, or aggregate queue fill over ``up_queue_frac``) to
  persist for ``up_sustain_s`` — one slow request cannot buy a
  replica;
- **scale-down** requires sustained idleness (no pressure AND fleet
  busy fraction under ``idle_busy_frac``) for ``down_sustain_s`` —
  longer than the up window on purpose: adding too late sheds traffic,
  removing too late wastes a replica, so the asymmetry leans safe;
- every action opens a ``cooldown_s`` window in which no further
  action fires, and resets both sustain timers — a burn edge that
  flaps faster than the cooldown produces ONE action, not a seesaw;
- scale-down is **drain-then-remove**: ``Router.remove_replica``
  releases the victim's sticky pins, stops new placements, and waits
  out its in-flight work — a drain never drops a request.

``evaluate()`` is one control-loop tick (call it from the serving
driver's loop, the test idiom — deterministic with an injected clock);
``start()`` runs the same tick on a background thread for operators.
The replica factory (``spawn``) is the deployment seam: in-process it
builds a Replica over shared compiled programs
(tests/test_autoscale.py), on a real pod it would boot a mesh.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

from tpudl.obs import registry
from tpudl.obs.spans import active_recorder
from tpudl.serve.queue import CAT_SERVE_REQUEST
from tpudl.serve.router import Replica


@dataclasses.dataclass(frozen=True)
class AutoscaleConfig:
    """Hysteresis knobs. Defaults suit the in-process test fleets;
    a real deployment stretches the windows to its scrape cadence."""

    min_replicas: int = 1
    max_replicas: int = 8
    #: Pressure must persist this long before a scale-up.
    up_sustain_s: float = 0.5
    #: Idleness must persist this long before a scale-down (longer than
    #: up_sustain_s by design — see module docstring).
    down_sustain_s: float = 3.0
    #: No action fires within this window after any action.
    cooldown_s: float = 1.0
    #: A router autoscale hint at or above this is pressure.
    up_hint: int = 1
    #: Aggregate admission-queue fill at or above this is pressure
    #: (catches overload before the SLO windows confirm the burn).
    up_queue_frac: float = 0.5
    #: Fleet busy fraction at or below this is idle.
    idle_busy_frac: float = 0.05
    #: Drain budget per scale-down (None = wait forever).
    drain_timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {self.min_replicas}"
            )
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                f"max_replicas ({self.max_replicas}) must be >= "
                f"min_replicas ({self.min_replicas})"
            )


class Autoscaler:
    """Consume the router's aggregated signals; add/remove replicas.

    ``router`` needs the PR-10 surface: ``load_report()``,
    ``add_replica(replica)``, ``remove_replica(name, drain=...,
    timeout_s=...)``. ``spawn(name) -> Replica`` builds a scale-up
    replica (NOT started — ``add_replica`` starts it). ``fleet``
    (optional ``tpudl.obs.fleet.FleetMonitor``) adds the cross-process
    burn signal: a burning member counts as pressure even when this
    router's own monitors are quiet."""

    def __init__(
        self,
        router,
        spawn: Callable[[str], Replica],
        config: Optional[AutoscaleConfig] = None,
        clock: Callable[[], float] = time.monotonic,
        fleet=None,
        name_prefix: str = "auto",
    ):
        self.router = router
        self.spawn = spawn
        self.config = config or AutoscaleConfig()
        self.clock = clock
        self.fleet = fleet
        self.name_prefix = name_prefix
        self.history: List[dict] = []
        self.num_scale_ups = 0
        self.num_scale_downs = 0
        self._counter = 0
        self._pressure_since: Optional[float] = None
        self._idle_since: Optional[float] = None
        self._cooldown_until = float("-inf")
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._register_health_source()

    def _register_health_source(self) -> None:
        import weakref

        from tpudl.obs import exporter as obs_exporter

        self_ref = weakref.ref(self)

        def _health() -> dict:
            scaler = self_ref()
            if scaler is None:
                return {"healthy": True, "autoscaler": "collected"}
            # Deliberately LOCK-FREE: evaluate() holds the control
            # lock across a scale-down drain (unbounded), and a
            # /healthz probe must never block behind routine scaling —
            # these are GIL-atomic int reads and a list tail peek.
            history = scaler.history
            return {
                "healthy": True,
                "scale_ups": scaler.num_scale_ups,
                "scale_downs": scaler.num_scale_downs,
                "last_action": history[-1] if history else None,
            }

        obs_exporter.register_health_source("serve_autoscaler", _health)

    # -- signal aggregation --------------------------------------------

    def signals(self) -> dict:
        """One sample of the pressure/idle classification over the
        router's load report (+ the fleet monitor's burn view)."""
        report = self.router.load_report()
        burning = bool(report.get("burning"))
        fleet_burning: List[str] = []
        if self.fleet is not None:
            try:
                fleet_burning = list(self.fleet.burning_sources())
            except Exception:
                # A broken fleet scrape must not stall the control
                # loop; the router's own signals still drive it.
                fleet_burning = []
        hint = int(report.get("autoscale_hint", 0))
        queue_frac = float(report.get("queue_frac", 0.0))
        busy_frac = float(report.get("busy_frac", 0.0))
        pressure = (
            burning
            or bool(fleet_burning)
            or hint >= self.config.up_hint
            or queue_frac >= self.config.up_queue_frac
        )
        idle = (
            not pressure
            and hint == 0
            and busy_frac <= self.config.idle_busy_frac
        )
        reasons = []
        if burning:
            reasons.append("slo_burn")
        if fleet_burning:
            reasons.append(f"fleet_burn:{','.join(fleet_burning)}")
        if hint >= self.config.up_hint:
            reasons.append(f"hint:{hint}")
        if queue_frac >= self.config.up_queue_frac:
            reasons.append(f"queue_frac:{queue_frac:.2f}")
        return {
            "pressure": pressure,
            "idle": idle,
            "reasons": reasons,
            "hint": hint,
            "busy_frac": busy_frac,
            "queue_frac": queue_frac,
            "report": report,
        }

    # -- the control tick ----------------------------------------------

    def evaluate(self) -> Optional[dict]:
        """One hysteresis tick: classify, update the sustain timers,
        and fire at most one scaling action. Returns the action record
        (also appended to ``history``) or None."""
        with self._lock:
            now = self.clock()
            sig = self.signals()
            if sig["pressure"]:
                if self._pressure_since is None:
                    self._pressure_since = now
                self._idle_since = None
            elif sig["idle"]:
                if self._idle_since is None:
                    self._idle_since = now
                self._pressure_since = None
            else:
                self._pressure_since = None
                self._idle_since = None
            reg = registry()
            reg.gauge("serve_autoscaler_pressure").set(
                int(sig["pressure"])
            )
            if now < self._cooldown_until:
                return None
            active = int(sig["report"].get("active_replicas", 0))
            action = None
            if (
                self._pressure_since is not None
                and now - self._pressure_since >= self.config.up_sustain_s
            ):
                if active < self.config.max_replicas:
                    action = self._scale_up(sig, now)
                # At max: pressure is real but unactionable — keep the
                # timer running so the gauge shows a saturated fleet.
            elif (
                self._idle_since is not None
                and now - self._idle_since >= self.config.down_sustain_s
                and active > self.config.min_replicas
            ):
                action = self._scale_down(sig, now)
            if action is not None:
                self._cooldown_until = now + self.config.cooldown_s
                self._pressure_since = None
                self._idle_since = None
                self.history.append(action)
                reg.gauge("serve_autoscaler_replicas").set(
                    self.router.load_report().get("active_replicas", 0)
                )
                rec = active_recorder()
                if rec is not None:
                    rec.event(
                        "autoscale", CAT_SERVE_REQUEST, **{
                            k: v for k, v in action.items()
                            if k != "at"
                        },
                    )
            return action

    def _scale_up(self, sig: dict, now: float) -> dict:
        self._counter += 1
        name = f"{self.name_prefix}{self._counter}"
        replica = self.spawn(name)
        self.router.add_replica(replica)
        self.num_scale_ups += 1
        registry().counter("serve_autoscaler_scale_ups").inc()
        return {
            "action": "scale_up",
            "replica": replica.name,
            "reason": "+".join(sig["reasons"]) or "pressure",
            "at": now,
        }

    def _scale_down(self, sig: dict, now: float) -> dict:
        per_replica = sig["report"].get("per_replica", {})
        if not per_replica:
            return None
        # Victim: the least-loaded active replica (fewest in-flight
        # tokens, then least scraped busyness) — the cheapest drain.
        victim = min(
            per_replica,
            key=lambda n: (
                per_replica[n].get("inflight_tokens", 0),
                per_replica[n].get("busy", 0),
            ),
        )
        t0 = self.clock()
        self.router.remove_replica(
            victim, drain=True, timeout_s=self.config.drain_timeout_s
        )
        drain_ms = 1e3 * (self.clock() - t0)
        self.num_scale_downs += 1
        registry().counter("serve_autoscaler_scale_downs").inc()
        return {
            "action": "scale_down",
            "replica": victim,
            "reason": "idle",
            # Migration-based drains make this ~transfer time, not
            # O(longest in-flight generation) — the number that lets an
            # operator read whether scale-downs are actually instant.
            "drain_ms": round(drain_ms, 3),
            "at": now,
        }

    # -- optional background loop --------------------------------------

    def start(self, interval_s: float = 0.25) -> "Autoscaler":
        """Run ``evaluate()`` on a daemon thread every ``interval_s``
        (a drain blocks the loop for its duration — scale decisions
        are serialized by design)."""
        if self._thread is not None:
            return self
        self._stop.clear()

        def _loop():
            while not self._stop.wait(interval_s):
                try:
                    self.evaluate()
                except Exception:
                    # The control loop must outlive one bad tick (a
                    # replica factory hiccup, a drain timeout); the
                    # error surfaces through counters/history staying
                    # flat, and the next tick retries.
                    registry().counter(
                        "serve_autoscaler_tick_errors"
                    ).inc()

        self._thread = threading.Thread(
            target=_loop, name="tpudl-autoscaler", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None

    def __enter__(self) -> "Autoscaler":
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
