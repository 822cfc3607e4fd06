"""Multi-replica serving router (tpudl.serve.router).

The correctness bar stays test_serve's: whatever the router does —
least-loaded placement, sticky sessions, mid-stream failover when a
replica's /healthz goes 503, prefill/decode disaggregation — every
greedy request's final tokens must match ``generate()`` run on it
alone. On top of that: SLO burn sheds best-effort work at the door
(not queue overflow), an unready fleet sheds instead of hanging, and
the per-replica obs gauges publish what the router scraped.
"""

import time
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs.slo import Objective, SloMonitor
from tpudl.serve import (
    PrefillWorker,
    Replica,
    Request,
    Router,
    ServeSession,
)

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8


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
            err_msg=f"request {req.request_id} diverged through the router",
        )


def test_router_round_trip_parity_and_balance(model_and_params):
    """Six greedy requests over two replicas: every result matches solo
    generate(), BOTH replicas did work (the token-weighted least-loaded
    books spread a burst submitted faster than health publishes), and
    the per-replica gauges carry the scraped view."""
    from tpudl.obs import registry

    model, params = model_and_params
    replicas = [
        Replica(f"r{i}", _session(model, params)) for i in range(2)
    ]
    requests = _greedy_requests(6, seed=1)
    with Router(replicas) as router:
        results = router.serve(requests, timeout_s=300.0)
    assert set(results) == {r.request_id for r in requests}
    _assert_generate_parity(model, params, requests, results)
    assert all(r.session.engine.num_prefills > 0 for r in replicas), (
        "placement starved one replica on a 6-request burst"
    )
    reg = registry()
    assert reg.gauge("serve_router_ready_replicas").value == 2
    assert reg.gauge("serve_replica_r0_ready").value == 1
    assert reg.gauge("serve_replica_r1_ready").value == 1


def test_router_sticky_sessions(model_and_params):
    """Requests sharing a session_key pin to one replica (KV/prefix
    affinity); keyless requests spread by load."""
    model, params = model_and_params
    replicas = [
        Replica(f"r{i}", _session(model, params)) for i in range(2)
    ]
    requests = [
        Request(f"s{i}", [3, 5, 7], max_new_tokens=4, session_key="user-1")
        for i in range(4)
    ]
    with Router(replicas) as router:
        owners = set()
        for req in requests:
            router.submit(req)
            owners.add(router._assigned[req.request_id][0])
        results = router.collect(timeout_s=300.0)
    assert len(owners) == 1, f"sticky key split across replicas: {owners}"
    assert router._sticky["user-1"] in {"r0", "r1"}
    assert all(r.finish_reason == "length" for r in results.values())


def test_router_failover_on_503_mid_stream(model_and_params):
    """One replica's /healthz goes 503 while its requests are mid-
    stream: the router requeues its outstanding work onto the survivor
    and every request still completes with solo-generate() tokens.
    Late results from the failed replica are dropped (the restarted
    copy is authoritative)."""
    model, params = model_and_params
    sessions = [_session(model, params) for _ in range(2)]
    # Slow every decode dispatch so work is still in flight at the flip
    # (the CPU tiny model would otherwise drain in milliseconds).
    for s in sessions:
        orig = s.engine.decode_call

        def slow(*args, _orig=orig):
            time.sleep(0.02)
            return _orig(*args)

        s.engine.decode_call = slow
    health = {"ok": True}
    r0 = Replica(
        "r0", sessions[0],
        health_fn=lambda: {
            **sessions[0].engine.health(), "healthy": health["ok"]
        },
    )
    r1 = Replica("r1", sessions[1])
    requests = _greedy_requests(4, seed=3, max_new_lo=12, max_new_hi=18)
    # migrate=False: the from-scratch path (seated work on the pool
    # would migrate; tests/test_serve_chaos.py has that path).
    with Router([r0, r1], scrape_interval_s=0.0, migrate=False) as router:
        for req in requests:
            router.submit(req)
        assert any(
            owner == "r0" for owner, _ in router._assigned.values()
        ), "no request landed on r0 — the failover path is untested"
        time.sleep(0.1)  # let both replicas get into their streams
        health["ok"] = False  # /healthz -> 503 mid-stream
        results = router.collect(timeout_s=300.0)
    assert router.num_failovers >= 1
    assert not router._ready["r0"]
    assert set(results) == {r.request_id for r in requests}
    assert all(r.finish_reason == "length" for r in results.values())
    _assert_generate_parity(model, params, requests, results)


def test_router_unready_fleet_sheds_capacity(model_and_params):
    """No ready replica at all: submits shed as shed_capacity Results
    (outage is data, not an exception) and the router's own health
    source reports unhealthy."""
    model, params = model_and_params
    r0 = Replica(
        "r0", _session(model, params),
        health_fn=lambda: {"healthy": False, "error": "HTTP 503"},
    )
    with Router([r0], scrape_interval_s=0.0) as router:
        router.submit(Request("x", [1, 2], max_new_tokens=2))
        results = router.poll()
        assert results["x"].finish_reason == "shed_capacity"
        from tpudl.obs.exporter import _health_sources

        health = _health_sources["serve_router"]()
        assert health["healthy"] is False
        assert health["ready_replicas"] == 0


def test_router_slo_burn_sheds_best_effort_only(model_and_params):
    """While any replica's SLO burns, best-effort requests (priority >
    shed_priority_above) shed AT THE ROUTER as shed_slo; latency-class
    work keeps flowing. The autoscale hint gauge counts the burning
    replica."""
    from tpudl.obs import registry

    model, params = model_and_params
    r0 = Replica("r0", _session(model, params))
    with Router([r0], scrape_interval_s=0.0) as router:
        router._burning["r0"] = frozenset({"ttft_p95"})
        assert router.burning
        router.submit(
            Request("be", [1, 2], max_new_tokens=2, priority=1)
        )
        router.submit(
            Request("lat", [1, 2, 3], max_new_tokens=2, priority=0)
        )
        results = router.collect(timeout_s=300.0)
        assert results["be"].finish_reason == "shed_slo"
        assert results["be"].tokens == []
        assert results["lat"].finish_reason == "length"
        assert router._autoscale_hint() == 1
        assert registry().gauge("serve_router_autoscale_hint").value == 1
        router._burning["r0"] = frozenset()
        assert router._autoscale_hint() == 0


def test_router_overload_sheds_by_slo_burn_not_capacity(model_and_params):
    """Overload through the router against per-replica TTFT monitors,
    on an injected clock that moves 10 virtual ms every time anything
    reads it (so a request's wait is a count of the fleet's work, not
    of this machine's speed): 40 requests land at once on 2 x 2 slots
    with queues deep enough to hold them all, the waiting ones blow
    the 100 ms objective, the engines hand their queues back as
    shed_slo and the router sheds best-effort work at the door while
    it burns. Queue overflow is never the relief valve."""
    model, params = model_and_params
    t = [0.0]

    def clock():
        t[0] += 0.01
        return t[0]

    replicas = [
        Replica(f"r{i}", _session(
            model, params, clock=clock, queue_capacity=160,
            slo=SloMonitor([Objective(
                name=f"ttft_r{i}", metric="serve_ttft_ms", threshold=100.0,
                quantile=0.95, window_s=1e6, fast_window_s=1e6, min_count=3,
            )], clock=clock),
        ))
        for i in range(2)
    ]
    burst = _greedy_requests(40, seed=11)
    with Router(replicas) as router:
        for request in burst:
            router.submit(request)
        first = router.collect(timeout_s=300.0)
        # The engines' burn reached the router: best-effort work now
        # sheds at the door, before it is placed.
        assert router.burning
        router.submit(Request("be", [1, 2], max_new_tokens=2, priority=1))
        door = router.poll()["be"]
    reasons = Counter(r.finish_reason for r in first.values())
    assert sum(reasons.values()) == len(burst)
    assert reasons["length"] >= 4  # every slot served its seat
    assert reasons["shed_slo"] > 0
    assert set(reasons) == {"length", "shed_slo"}  # no shed_capacity
    assert (door.finish_reason, door.tokens) == ("shed_slo", [])


def test_router_disaggregated_prefill_parity(model_and_params):
    """Prefill/decode disaggregation over paged decode replicas: a
    dedicated PrefillWorker runs every batch-1 prefill and hands (row
    cache, first token) to decode replicas, which never pay a prefill
    dispatch — and the outputs still match solo generate()."""
    model, params = model_and_params
    replicas = [
        Replica(f"r{i}", _session(model, params))
        for i in range(2)
    ]
    worker = PrefillWorker.from_model("p0", model, params, PROMPT_LEN)
    requests = _greedy_requests(6, seed=5)
    with Router(replicas, prefill=[worker]) as router:
        results = router.serve(requests, timeout_s=300.0)
    assert worker.num_prefills == 6
    for replica in replicas:
        # The decode engines never ran a local prefill dispatch — that
        # is the disaggregation contract (TPOT never pays a prefill).
        assert replica.session.engine.num_prefills == 0
    assert set(results) == {r.request_id for r in requests}
    _assert_generate_parity(model, params, requests, results)


def _slow_prefill_worker(model, params, sleep_s):
    """A PrefillWorker whose prefill dispatch takes ``sleep_s`` — the
    deterministic way to have work waiting in the prefill inbox while
    the fleet's state changes underneath it."""
    worker = PrefillWorker.from_model("p0", model, params, PROMPT_LEN)
    orig_call = worker.prefill_call

    def slow_call(*args):
        time.sleep(sleep_s)
        return orig_call(*args)

    worker.prefill_call = slow_call
    return worker


def test_router_disaggregated_deadline_and_sticky(model_and_params):
    """The disaggregated path keeps two AdmissionQueue contracts: a
    request whose deadline passes while queued behind a busy prefill
    tier is never started (shed_timeout with its real queue wait), and
    session_key stickiness binds at PLACEMENT — every request of a key
    decodes on the same replica even though the decode target is chosen
    at prefill completion."""
    model, params = model_and_params
    replicas = [
        Replica(f"r{i}", _session(model, params)) for i in range(2)
    ]
    seated = {name: [] for name in ("r0", "r1")}
    for replica in replicas:
        orig = replica.seat_prefilled

        def record(item, _name=replica.name, _orig=orig):
            seated[_name].append(item.entry.request.request_id)
            _orig(item)

        replica.seat_prefilled = record
    worker = _slow_prefill_worker(model, params, sleep_s=0.4)
    sticky = [
        Request(f"s{i}", [3, 5, 7], max_new_tokens=3, session_key="u1")
        for i in range(3)
    ]
    late = Request("late", [2, 4], max_new_tokens=3, deadline_s=0.05)
    with Router(replicas, prefill=[worker]) as router:
        for req in sticky:
            router.submit(req)
        router.submit(late)  # expires behind the 0.4s prefills ahead
        results = router.collect(timeout_s=300.0)
    assert results["late"].finish_reason == "shed_timeout"
    assert results["late"].queue_wait_s > 0.05
    assert all(results[r.request_id].finish_reason == "length"
               for r in sticky)
    owners = {
        name for name, rids in seated.items()
        if any(r.request_id in rids for r in sticky)
    }
    assert len(owners) == 1, (
        f"sticky key split across replicas at placement: {seated}"
    )
    assert "late" not in seated["r0"] + seated["r1"]  # never started


def test_router_disaggregated_unready_fleet_sheds_not_strands(
    model_and_params,
):
    """Every replica goes unready while a request sits in the prefill
    tier: placement sheds it as shed_capacity instead of parking it on
    a dead replica (failover only fires on a ready->unready edge, so a
    request placed on an already-unready replica would strand and
    collect() would spin forever)."""
    model, params = model_and_params
    health = {"ok": True}
    r0 = Replica(
        "r0", _session(model, params),
        health_fn=lambda: {"healthy": health["ok"]},
    )
    worker = _slow_prefill_worker(model, params, sleep_s=0.4)
    with Router([r0], prefill=[worker], scrape_interval_s=0.0) as router:
        router.submit(Request("x", [1, 2], max_new_tokens=2))
        health["ok"] = False  # fleet dies while x is still prefilling
        results = router.collect(timeout_s=300.0)
    assert results["x"].finish_reason == "shed_capacity"
    assert results["x"].tokens == []


def test_replica_scrape_over_real_http_healthz(model_and_params):
    """The scraped-placement contract end to end over HTTP: a Replica
    with ``health_url`` reads a live PR-6 ``/healthz`` endpoint (200 →
    ready, serves; raising source → 503 with the health JSON in the
    body → unready, sheds) — the same payload shape a real exporter
    publishes per replica process."""
    from tpudl.obs import exporter as obs_exporter

    model, params = model_and_params
    obs_exporter._reset_health_for_tests()
    session = _session(model, params)
    wedged = {"now": False}

    def engine_source():
        if wedged["now"]:
            raise RuntimeError("engine wedged")
        return {"healthy": True, **session.engine.health()}

    obs_exporter.register_health_source("serve_engine", engine_source)
    try:
        with obs_exporter.ObsExporter(port=0) as ex:
            url = f"http://127.0.0.1:{ex.port}/healthz"
            # (The contract is the payload, not its latency: beside five
            # other workers the default 1 s scrape has timed out.)
            replica = Replica(
                "r0", session, health_url=url, scrape_timeout_s=30.0
            )
            with Router([replica], scrape_interval_s=0.0) as router:
                requests = _greedy_requests(2, seed=7)
                results = router.serve(requests, timeout_s=300.0)
                assert all(
                    r.finish_reason == "length" for r in results.values()
                )
                scraped = replica.scrape()
                assert scraped["healthy"] is True
                assert scraped["num_slots"] == 2  # engine state rode along
                wedged["now"] = True  # /healthz now answers 503
                assert replica.scrape()["healthy"] is False
                router.submit(Request("x", [1, 2], max_new_tokens=2))
                assert router.poll()["x"].finish_reason == "shed_capacity"
    finally:
        obs_exporter.unregister_health_source("serve_engine")


def test_router_duplicate_and_empty_validation(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="at least one replica"):
        Router([])
    r0 = Replica("r0", _session(model, params))
    with Router([r0]) as router:
        router.submit(Request("dup", [1, 2], max_new_tokens=2))
        with pytest.raises(ValueError, match="duplicate"):
            router.submit(Request("dup", [1, 2], max_new_tokens=2))
        router.collect(timeout_s=300.0)
    sessions = [_session(model, params) for _ in range(2)]
    with pytest.raises(ValueError, match="unique"):
        Router([Replica("same", sessions[0]), Replica("same", sessions[1])])


def test_router_validates_at_the_door(model_and_params):
    """Router.submit admission-validates against the fleet's compiled
    shapes: an unservable request is a caller-visible ValueError — on
    the DISAGGREGATED path too, where it previously reached the prefill
    worker thread (negative pad -> crash) instead of the caller."""
    model, params = model_and_params
    too_long = Request(
        "long", list(range(1, PROMPT_LEN + 2)), max_new_tokens=2
    )
    r0 = Replica("r0", _session(model, params))
    with Router([r0]) as router:
        with pytest.raises(ValueError, match="prompt window"):
            router.submit(too_long)
        with pytest.raises(ValueError, match="max_new_tokens"):
            router.submit(Request("zero", [1, 2], max_new_tokens=0))
        assert not router._assigned and not router.results
    r1 = Replica("r1", _session(model, params))
    worker = PrefillWorker.from_model("p0", model, params, PROMPT_LEN)
    with Router([r1], prefill=[worker]) as router:
        with pytest.raises(ValueError, match="prompt window"):
            router.submit(too_long)
        assert len(worker) == 0 and not router._assigned


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_replica_crash_publishes_unhealthy_and_fails_over(model_and_params):
    """An exception escaping the replica loop (engine.step() raising)
    publishes unhealthy on the way out — the finally path — so the
    router fails its outstanding work over to survivors. Previously the
    crash left the last HEALTHY snapshot published forever: readiness
    never flipped, failover never fired, and collect() hung to
    timeout."""
    model, params = model_and_params
    r0 = Replica("r0", _session(model, params))
    r1 = Replica("r1", _session(model, params))
    armed = {"on": False}
    orig_step = r0.session.engine.step

    def exploding_step():
        if armed["on"]:
            raise RuntimeError("chip fell off")
        return orig_step()

    r0.session.engine.step = exploding_step
    requests = _greedy_requests(6, seed=23)
    with Router([r0, r1], scrape_interval_s=0.0) as router:
        for req in requests:
            router.submit(req)
        armed["on"] = True
        results = router.collect(timeout_s=300.0)
        assert router._ready["r0"] is False
    h = r0.scrape()
    assert h["healthy"] is False
    assert "crashed" in h.get("error", "")
    assert set(results) == {r.request_id for r in requests}
    assert all(res.finish_reason in ("eos", "length")
               for res in results.values())
    _assert_generate_parity(model, params, requests, results)


def test_replica_inbox_wait_counts_against_deadline(model_and_params):
    """A request's deadline budget spans the router hop: time queued in
    the REPLICA's inbox counts, so a deadline that expires there sheds
    (shed_timeout) instead of being served late — previously the
    replica restarted the full deadline_s from its own clock at
    session.submit time."""
    model, params = model_and_params
    r0 = Replica("r0", _session(model, params))
    orig_step = r0.session.engine.step

    def slow_step():
        time.sleep(0.3)
        return orig_step()

    r0.session.engine.step = slow_step
    with Router([r0]) as router:
        time.sleep(0.05)  # replica thread is inside a slow step
        router.submit(
            Request("late", [1, 2], max_new_tokens=2, deadline_s=0.1)
        )
        results = router.collect(timeout_s=300.0)
    assert results["late"].finish_reason == "shed_timeout"
    assert results["late"].queue_wait_s >= 0.1
    assert not router._deadline_at  # stamp cleaned up with the Result


def test_prefill_worker_failure_surfaces_not_kills(model_and_params):
    """One poisoned request blowing up mid-prefill surfaces as a
    ``failed:`` Result (assignment released — collect() doesn't hang)
    while the worker THREAD survives to prefill everything behind it
    in the inbox."""
    model, params = model_and_params
    r0 = Replica("r0", _session(model, params))
    worker = PrefillWorker.from_model("p0", model, params, PROMPT_LEN)
    orig_call = worker.prefill_call
    poison = {"armed": True}

    def flaky_call(p, ids, mask):
        if poison["armed"]:
            poison["armed"] = False
            raise RuntimeError("boom")
        return orig_call(p, ids, mask)

    worker.prefill_call = flaky_call
    good = _greedy_requests(3, seed=7)
    with Router([r0], prefill=[worker]) as router:
        router.submit(Request("bad", [1, 2, 3], max_new_tokens=4))
        for req in good:
            router.submit(req)
        results = router.collect(timeout_s=300.0)
    assert results["bad"].finish_reason.startswith("failed: RuntimeError")
    assert results["bad"].tokens == []
    assert worker.num_prefills == 3, "worker thread died on the poison"
    _assert_generate_parity(model, params, good, results)


def test_router_lock_order_monitor_clean_under_traffic(
    model_and_params, monkeypatch
):
    """TPUDL_DEBUG_LOCK_ORDER: real traffic over wrapped router +
    replica locks builds the live cross-object held-before graph with
    ZERO inversions, checked against the ranks the STATIC pass derives
    from the serve/obs sources (tpudl.analysis.concurrency) — the
    runtime half of the ISSUE-12 concurrency tier, on the exact
    subsystem whose _deadline_at/_books races motivated it."""
    import os

    import tpudl
    from tpudl.analysis import concurrency as conc

    tpudl_dir = os.path.dirname(tpudl.__file__)
    ranks = conc.derive_lock_ranks(
        [os.path.join(tpudl_dir, "serve"), os.path.join(tpudl_dir, "obs")]
    )
    monitor = conc.LockOrderMonitor(ranks=ranks)
    monkeypatch.setattr(conc, "_default_monitor", monitor)
    monkeypatch.setenv("TPUDL_DEBUG_LOCK_ORDER", "1")

    model, params = model_and_params
    replicas = [
        Replica(f"lo{i}", _session(model, params)) for i in range(2)
    ]
    # The flag was live at construction: the books and both replicas'
    # result locks must be wrapped.
    requests = _greedy_requests(4, seed=11)
    with Router(replicas) as router:
        assert isinstance(router._books, conc.OrderedLock)
        assert all(
            isinstance(r._results_lock, conc.OrderedLock)
            for r in replicas
        )
        results = router.serve(requests, timeout_s=300.0)
    assert set(results) == {r.request_id for r in requests}
    _assert_generate_parity(model, params, requests, results)
    assert monitor.violations == [], monitor.violations
    # The wrapper was live: the monitor saw the router's book
    # acquisitions. (The edge set is empty BY DESIGN — the router
    # never holds two locks at once, e.g. _harvest_one drains
    # replica.take() before entering the books; the monitor existing
    # is what keeps that property from silently regressing.)
    assert monitor.acquisitions > 0
