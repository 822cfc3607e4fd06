"""SLO-driven autoscaler (ISSUE 10 tentpole, tpudl.serve.autoscale) +
the router's live fleet-membership APIs.

Hysteresis units run against a fake router with an injected clock —
deterministic edge-by-edge checks that a flickering burn cannot flap
the fleet, sustain windows gate both directions, cooldown separates
actions, and min/max bounds hold. The drain contract runs against a
REAL two-replica router: removing a replica that owns in-flight work
must deliver every Result (generate()-parity intact) before the
replica disappears. The end-to-end acceptance (overload -> fleet
burn -> scale-up -> recovery with zero shed_slo -> idle drain) runs
last, at test-sized load, with sessions from tests/serve_helpers.py."""

import time
import types
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.obs as obs
from tests.serve_helpers import (
    build_programs,
    make_requests,
    session_from_programs,
    warmup_session,
)
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs.fleet import FleetMonitor
from tpudl.obs.slo import Objective, SloMonitor
from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.serve import (
    AutoscaleConfig,
    Autoscaler,
    Replica,
    Request,
    Router,
    ServeSession,
)

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter._reset_health_for_tests()
    yield
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter._reset_health_for_tests()


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _session(model, params, **kw):
    kw.setdefault("prompt_len", PROMPT_LEN)
    kw.setdefault("num_slots", 2)
    return ServeSession.from_model(model, params, **kw)


# ---------------------------------------------------------------------------
# Hysteresis units (fake router, fake clock — fully deterministic)
# ---------------------------------------------------------------------------


class FakeRouter:
    def __init__(self, replicas=2):
        self.n = replicas
        self.hint = 0
        self.burning = False
        self.busy_frac = 0.0
        self.queue_frac = 0.0
        self.added = []
        self.removed = []

    def load_report(self):
        return {
            "replicas": self.n,
            "active_replicas": self.n,
            "ready_replicas": self.n,
            "draining": [],
            "busy_frac": self.busy_frac,
            "queue_frac": self.queue_frac,
            "outstanding": 0,
            "burning": self.burning,
            "autoscale_hint": self.hint,
            "per_replica": {
                f"r{i}": {
                    "ready": True, "busy": i, "inflight_tokens": i * 10,
                }
                for i in range(self.n)
            },
        }

    def add_replica(self, replica):
        self.n += 1
        self.added.append(replica.name)

    def remove_replica(self, name, drain=True, timeout_s=None):
        assert drain, "the autoscaler must always drain on scale-down"
        self.n -= 1
        self.removed.append(name)


def _scaler(router, t, **cfg_kw):
    cfg = AutoscaleConfig(**{
        "min_replicas": 2, "max_replicas": 4, "up_sustain_s": 0.5,
        "down_sustain_s": 3.0, "cooldown_s": 1.0, **cfg_kw,
    })
    spawned = []

    def spawn(name):
        spawned.append(name)
        return types.SimpleNamespace(name=name)

    scaler = Autoscaler(
        router, spawn, cfg, clock=lambda: t[0]
    )
    scaler._spawned = spawned
    return scaler


def test_scale_up_requires_sustained_pressure():
    router, t = FakeRouter(2), [0.0]
    scaler = _scaler(router, t)
    router.hint = 1
    assert scaler.evaluate() is None  # pressure just started
    t[0] = 0.3
    assert scaler.evaluate() is None  # not sustained yet
    t[0] = 0.6
    action = scaler.evaluate()
    assert action is not None and action["action"] == "scale_up"
    assert router.added == ["auto1"] and router.n == 3
    assert "hint" in action["reason"]


def test_flickering_burn_edge_never_flaps():
    """Pressure that flickers on/off faster than the sustain window
    produces NO action in either direction — the no-flapping bar."""
    router, t = FakeRouter(2), [0.0]
    scaler = _scaler(router, t)
    for i in range(20):
        t[0] = 0.2 * i
        router.burning = i % 2 == 0  # flips every 0.2s < 0.5s sustain
        # Off-phases are NOT idle either (busy fleet): timers reset.
        router.busy_frac = 0.5
        assert scaler.evaluate() is None, (i, scaler.history)
    assert router.added == [] and router.removed == []


def test_cooldown_separates_actions_and_max_bounds():
    router, t = FakeRouter(2), [0.0]
    scaler = _scaler(router, t, max_replicas=4)
    router.burning = True
    assert scaler.evaluate() is None  # starts the sustain timer
    t[0] = 0.6
    assert scaler.evaluate()["action"] == "scale_up"  # n -> 3
    # Still burning: cooldown (1.0s) blocks any second action, even
    # though the sustain window rebuilds underneath it.
    t[0] = 0.8
    assert scaler.evaluate() is None  # in cooldown (timer restarts)
    t[0] = 1.2
    assert scaler.evaluate() is None  # still in cooldown
    t[0] = 1.7
    assert scaler.evaluate()["action"] == "scale_up"  # n -> 4
    assert scaler.history[1]["at"] - scaler.history[0]["at"] >= 1.0
    # At max_replicas: sustained pressure is unactionable, no action.
    t[0] = 5.0
    assert scaler.evaluate() is None
    assert router.n == 4


def test_sustained_idle_drains_to_min_and_picks_least_loaded():
    router, t = FakeRouter(4), [0.0]
    scaler = _scaler(router, t, down_sustain_s=2.0, cooldown_s=0.5)
    router.busy_frac = 0.0
    assert scaler.evaluate() is None
    t[0] = 2.5
    action = scaler.evaluate()
    assert action is not None and action["action"] == "scale_down"
    # Victim: fewest in-flight tokens (r0 in the fake's report).
    assert router.removed == ["r0"]
    # Cooldown, then the next sustained idle window drains one more.
    t[0] = 3.2
    assert scaler.evaluate() is None  # restarts the idle timer
    t[0] = 5.5
    assert scaler.evaluate()["action"] == "scale_down"
    assert router.n == 2
    # Never below min_replicas, however long the idle lasts.
    t[0] = 60.0
    assert scaler.evaluate() is None
    assert router.n == 2


def test_busy_but_not_burning_is_neutral():
    """Mid load (no pressure, not idle): both timers stay unset and
    nothing ever fires."""
    router, t = FakeRouter(2), [0.0]
    scaler = _scaler(router, t)
    router.busy_frac = 0.6
    for i in range(10):
        t[0] = float(i)
        assert scaler.evaluate() is None
    assert scaler._pressure_since is None and scaler._idle_since is None


def test_queue_pressure_and_fleet_burn_count_as_pressure():
    router, t = FakeRouter(2), [0.0]

    class FakeFleet:
        burning = []

        def burning_sources(self):
            return self.burning

    fleet = FakeFleet()
    scaler = _scaler(router, t)
    scaler.fleet = fleet
    router.queue_frac = 0.9  # queue depth alone is pressure
    sig = scaler.signals()
    assert sig["pressure"] and any(
        r.startswith("queue_frac") for r in sig["reasons"]
    )
    router.queue_frac = 0.0
    fleet.burning = ["replica7"]  # cross-process burn alone is pressure
    sig = scaler.signals()
    assert sig["pressure"] and any(
        "fleet_burn" in r for r in sig["reasons"]
    )


def test_config_validation():
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscaleConfig(min_replicas=0)
    with pytest.raises(ValueError, match="max_replicas"):
        AutoscaleConfig(min_replicas=4, max_replicas=2)


# ---------------------------------------------------------------------------
# Router live-membership APIs (real replicas)
# ---------------------------------------------------------------------------


def _greedy_requests(n, seed=0, max_new_lo=6, max_new_hi=16, **kw):
    rng = np.random.default_rng(seed)
    return [
        Request(
            request_id=f"r{i}",
            input_ids=rng.integers(
                1, CFG.vocab_size, size=int(rng.integers(2, PROMPT_LEN + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(max_new_lo, max_new_hi)),
            **kw,
        )
        for i in range(n)
    ]


def _assert_generate_parity(model, params, requests, results):
    for req in requests:
        want = np.asarray(
            generate(
                model, params, jnp.asarray(req.input_ids)[None, :],
                max_new_tokens=req.max_new_tokens,
            )
        )[0]
        got = np.asarray(results[req.request_id].tokens)
        np.testing.assert_array_equal(
            got, want[: got.shape[0]],
            err_msg=f"request {req.request_id} diverged",
        )


def test_add_replica_live_and_validation(model_and_params):
    model, params = model_and_params
    with Router([Replica("r0", _session(model, params))]) as router:
        router.add_replica(Replica("r1", _session(model, params)))
        assert router.load_report()["active_replicas"] == 2
        requests = _greedy_requests(6, seed=3)
        results = router.serve(requests, timeout_s=300.0)
        _assert_generate_parity(model, params, requests, results)
        assert all(
            r.session.engine.num_prefills > 0 for r in router.replicas
        ), "the added replica took no work"
        # Duplicate names and mismatched compiled shapes are rejected.
        with pytest.raises(ValueError, match="duplicate replica name"):
            router.add_replica(Replica("r1", _session(model, params)))
        with pytest.raises(ValueError, match="compiled shapes"):
            router.add_replica(Replica(
                "r2", _session(model, params, prompt_len=4)
            ))


def test_remove_replica_drains_without_dropping(model_and_params):
    """The acceptance drain contract: removing a replica that owns
    in-flight work delivers EVERY Result with generate()-parity before
    the replica disappears, and releases its sticky pins."""
    model, params = model_and_params
    sessions = [_session(model, params) for _ in range(2)]
    for s in sessions:  # slow decodes so work is in flight at removal
        orig = s.engine.decode_call

        def slow(*args, _orig=orig):
            time.sleep(0.02)
            return _orig(*args)

        s.engine.decode_call = slow
    replicas = [Replica(f"r{i}", s) for i, s in enumerate(sessions)]
    requests = _greedy_requests(8, seed=5, max_new_lo=8, max_new_hi=20)
    # Pin one stream to r0 so its sticky release is observable.
    requests[0] = Request(
        "r0-pinned", [3, 5, 7], max_new_tokens=12, session_key="user-1"
    )
    with Router(replicas) as router:
        for req in requests:
            router.submit(req)
        victim = "r0" if any(
            owner == "r0" for owner, _ in router._assigned.values()
        ) else "r1"
        removed = router.remove_replica(victim, drain=True, timeout_s=120.0)
        assert removed.name == victim
        assert all(r.name != victim for r in router.replicas)
        assert victim not in router._ready
        # Nothing the victim owned was dropped, and no request was
        # restarted on a survivor (a drain is not a failover).
        assert router.num_failovers == 0
        results = router.collect(timeout_s=300.0)
        assert set(results) == {r.request_id for r in requests}
        assert all(res.ok for res in results.values()), {
            rid: res.finish_reason for rid, res in results.items()
        }
        _assert_generate_parity(
            model, params,
            [r for r in requests if r.request_id != "r0-pinned"],
            results,
        )
        assert "user-1" not in router._sticky or (
            router._sticky["user-1"] != victim
        )
        # The survivor still serves new work.
        more = _greedy_requests(2, seed=6)
        more = [
            Request(f"post-{r.request_id}", r.input_ids,
                    max_new_tokens=r.max_new_tokens)
            for r in more
        ]
        post = router.serve(more, timeout_s=300.0)
        assert all(res.ok for res in post.values())


def test_remove_replica_timeout_restores_service(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    orig = session.engine.decode_call

    def slow(*args):
        time.sleep(0.05)
        return orig(*args)

    session.engine.decode_call = slow
    replicas = [
        Replica("r0", session), Replica("r1", _session(model, params)),
    ]
    with Router(replicas) as router:
        # Park long work on r0 (least-loaded placement from cold books).
        for req in _greedy_requests(4, seed=7, max_new_lo=20,
                                    max_new_hi=32):
            router.submit(req)
        victim = next(
            owner for owner, _ in router._assigned.values()
            if owner is not None
        )
        with pytest.raises(TimeoutError, match="still in flight"):
            router.remove_replica(victim, drain=True, timeout_s=0.0)
        # Back in service: not draining, still in the fleet, and the
        # run completes.
        assert victim not in router._draining
        assert any(r.name == victim for r in router.replicas)
        results = router.collect(timeout_s=300.0)
        assert all(res.ok for res in results.values())
        with pytest.raises(ValueError, match="no replica named"):
            router.remove_replica("nope")


# ---------------------------------------------------------------------------
# End-to-end acceptance: overload -> burn -> scale-up -> recovery ->
# idle drain
# ---------------------------------------------------------------------------


def _slowed(call, seconds):
    """The scenario's load shaper: a compiled call that then sleeps.
    A CPU engine over a tiny model cannot be overloaded by a paced
    arrival stream otherwise, and the sleep releases the GIL, so the
    replica threads overlap as separate meshes would. It shapes load:
    no time it causes is reported or compared with anything."""

    def wrapped(*args):
        out = call(*args)
        jax.block_until_ready(out)
        time.sleep(seconds)
        return out

    return wrapped


def _submit_paced(router, requests, rate, rng, tick=lambda: None):
    """Open-loop arrivals at ``rate`` requests/s (exponential gaps)."""
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=len(requests)))
    t0 = time.perf_counter()
    for request, due in zip(requests, arrivals):
        lag = due - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        router.submit(request)
        tick()


def test_autoscale_acceptance_end_to_end(tmp_path):
    """2x-capacity open-loop overload on a 2-replica fleet with
    per-replica TTFT monitors and a FleetMonitor over the process's
    live telemetry -> the burn sustains -> the Autoscaler adds a
    replica (spawned over the SAME compiled programs: scale-up costs
    no compilation) -> the burn clears, and traffic after it sees ZERO
    shed_slo -> sustained idle drains the fleet back to 2 with every
    Result delivered and generate() parity intact."""
    obs.enable(str(tmp_path / "obs"))  # the fleet trace rides along
    n_overload, n_after = 90, 20
    programs = build_programs(num_slots=4)
    warmup_session(session_from_programs(programs))
    monitors = []

    def make_replica(name):
        # Alert on 0.6 x a 300 ms objective (the tighter internal bar:
        # burn detection needs violations to fire).
        monitor = SloMonitor([Objective(
            name=f"ttft_{name}", metric="serve_ttft_ms", threshold=180.0,
            quantile=0.95, window_s=4.0, fast_window_s=0.5, min_count=3,
        )])
        monitors.append(monitor)
        # Deep queues: capacity sheds must not be the relief valve.
        session = session_from_programs(
            programs, slo=monitor, queue_capacity=4 * n_overload
        )
        engine = session.engine
        engine.prefill_call = _slowed(engine.prefill_call, 0.004)
        engine.decode_call = _slowed(engine.decode_call, 0.004)
        return Replica(name, session)

    exporter = obs_exporter.ObsExporter(port=0).start()
    fleet = FleetMonitor({"serving": exporter.snapshot}, scrape_interval_s=0.1)
    rng = np.random.default_rng(0)
    results = {}
    try:
        with Router([make_replica(f"r{i}") for i in range(2)]) as router:
            scaler = Autoscaler(
                router, make_replica,
                AutoscaleConfig(
                    min_replicas=2, max_replicas=3, up_sustain_s=0.2,
                    down_sustain_s=0.5, cooldown_s=1.0, idle_busy_frac=0.05,
                ),
                fleet=fleet,
            )
            # -- phase 1: overload. The control loop ticks THROUGHOUT:
            # per arrival while submitting, then per poll while the
            # backlog drains — the burn peaks during the drain, which
            # is exactly when the scale-up must fire.
            fleet_burned = set()

            def tick():
                scaler.evaluate()
                if not fleet_burned:
                    # The fleet plane's own confirmation of the burn.
                    fleet_burned.update(fleet.burning_sources())

            _submit_paced(
                router,
                make_requests(n_overload, seed=0, best_effort_every=3),
                250.0, rng, tick,
            )
            deadline = time.perf_counter() + 600.0
            while len(results) < n_overload:
                assert time.perf_counter() < deadline
                results.update(router.poll())
                tick()
                time.sleep(0.002)
            assert scaler.num_scale_ups == 1, (
                f"overload never triggered a scale-up ({scaler.history})"
            )
            assert fleet_burned
            # -- the burn clears after the scale-up.
            deadline = time.perf_counter() + 30.0
            while any(m.burning_names() for m in monitors):
                assert time.perf_counter() < deadline, (
                    "the SLO burn never cleared after scale-up"
                )
                time.sleep(0.02)
            # -- phase 2: traffic after the scale-up is not shed (a rate
            # the three replicas hold even where five other pytest
            # workers share the machine's cores).
            _submit_paced(
                router,
                make_requests(
                    n_after, seed=1, best_effort_every=3, tag="p2-"
                ),
                20.0, rng,
            )
            after = router.collect(timeout_s=600.0)
            results.update(after)
            reasons = Counter(r.finish_reason for r in after.values())
            assert reasons["shed_slo"] == 0, (
                f"post-scale-up traffic still shed on SLO burn "
                f"({dict(reasons)}): the added replica did not relieve "
                f"the overload"
            )
            # -- phase 3: sustained idle -> drain-then-remove.
            deadline = time.perf_counter() + 60.0
            while scaler.num_scale_downs < scaler.num_scale_ups:
                assert time.perf_counter() < deadline, (
                    "sustained idle never drained the scaled-up replica"
                )
                scaler.evaluate()
                time.sleep(0.05)
            assert (scaler.num_scale_ups, scaler.num_scale_downs) == (1, 1)
            assert router.load_report()["active_replicas"] == 2
            assert len(results) == n_overload + n_after, (
                "a drain lost in-flight work"
            )
            # -- the shrunk fleet still serves generate()'s tokens.
            parity = make_requests(4, seed=2, tag="parity-")
            _assert_generate_parity(
                programs["model"], programs["params"], parity,
                router.serve(parity, timeout_s=600.0),
            )
    finally:
        exporter.close()
    # The recorded stream stitches into a fleet report that shows the
    # membership churn.
    from tpudl.obs import report as obs_report
    from tpudl.obs.spans import active_recorder

    records = active_recorder().records
    fleet_report = obs_report.build_fleet_report(records)
    actions = {
        a["action"] for a in fleet_report["autoscale_actions"]
    }
    assert actions == {"scale_up", "scale_down"}
    membership = {
        (m["what"], m["replica"]) for m in fleet_report["membership"]
    }
    assert ("replica_added", "auto1") in membership
    assert any(w == "replica_removed" for w, _ in membership)
