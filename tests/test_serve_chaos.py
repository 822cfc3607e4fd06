"""Page-granular KV migration + the serving chaos harness (ISSUE 13).

The correctness bar is byte-exactness: a request migrated mid-stream
must produce EXACTLY the tokens an uninterrupted run produces — f32
against solo ``generate()``, int8 against an uninterrupted engine run
(the pools' stored bytes ship verbatim) — with ZERO prefill dispatches
on the target. On top of that, the chaos contract: a killed replica
falls back to capped resubmission, a corrupt payload sheds as
``failed`` (never resumes), a frozen replica goes stale-unready and
recovers, and drains return in a fraction of the longest in-flight
generation with nothing dropped.
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.obs as obs
from tpudl.models.generate import generate, paged_decode_fn, prefill_fn
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import spans as obs_spans
from tpudl.serve import (
    MigrationCompatError,
    MigrationCorruptError,
    Replica,
    Request,
    Router,
    ServeSession,
    chaos,
)
from tpudl.serve.cache import PagedKVCache, parse_migration

pytestmark = pytest.mark.chaos

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8
PAGE = 8


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
def programs():
    """Shared compiled programs (one jit wrapper = one compile for the
    whole module) plus a warm migration round trip, so every timed or
    failover-sensitive test below runs compiled code — a cold XLA
    compile inside a migration window reads as a dead replica."""
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    pf = jax.jit(prefill_fn(model))
    dec = jax.jit(paged_decode_fn(model, PAGE, False))
    ids = jax.ShapeDtypeStruct((2, PROMPT_LEN), jnp.int32)
    _, template = jax.eval_shape(prefill_fn(model), params, ids, ids)
    out = {
        "model": model, "params": params, "prefill": pf,
        "decode": dec, "template": template,
    }
    src = _session(out)
    src.submit(Request("warm", [1, 2, 3], max_new_tokens=4))
    for _ in range(2):
        src.engine.step()
    payload = src.engine.export_request("warm")
    dst = _session(out)
    dst.engine.install_migrated(payload)
    while dst.engine.step():
        pass
    return out


def _session(programs, slow_s: float = 0.0, **kw):
    cache = PagedKVCache(programs["template"], page_size=PAGE)
    session = ServeSession(
        programs["prefill"], programs["decode"], programs["params"],
        cache, PROMPT_LEN, **kw,
    )
    if slow_s:
        orig = session.engine.decode_call

        def slow(*args):
            time.sleep(slow_s)
            return orig(*args)

        session.engine.decode_call = slow
    return session


def _want(programs, req):
    return np.asarray(
        generate(
            programs["model"], programs["params"],
            jnp.asarray(req.input_ids, jnp.int32)[None, :],
            max_new_tokens=req.max_new_tokens,
        )
    )[0]


def _assert_parity(programs, requests, results):
    for req in requests:
        res = results[req.request_id]
        assert res.ok, (req.request_id, res.finish_reason)
        got = np.asarray(res.tokens)
        np.testing.assert_array_equal(
            got, _want(programs, req)[: got.shape[0]],
            err_msg=f"{req.request_id} diverged across migration",
        )


# ---------------------------------------------------------------------------
# engine-level migration contract
# ---------------------------------------------------------------------------


def test_migration_roundtrip_byte_exact_zero_prefill(programs):
    """Export mid-stream, install on a fresh engine: the continuation
    is token-for-token ``generate()``, the target pays ZERO prefill
    dispatches, and the source slot/pages are fully released."""
    src = _session(programs)
    dst = _session(programs)
    req = Request("r0", [3, 5, 7, 11, 2], max_new_tokens=20)
    src.submit(req)
    for _ in range(5):
        src.engine.step()
    free_before = src.engine.cache.free_pages
    payload = src.engine.export_request("r0")
    assert payload is not None and isinstance(payload, bytes)
    # Export frees the source seat (commit-or-invisible: payload first).
    assert all(s is None for s in src.engine._slots)
    assert src.engine.cache.free_pages > free_before
    rid = dst.engine.install_migrated(payload)
    assert rid == "r0"
    while dst.engine.step():
        pass
    res = dst.engine.results["r0"]
    assert res.finish_reason == "length"
    np.testing.assert_array_equal(
        np.asarray(res.tokens), _want(programs, req)
    )
    assert dst.engine.num_prefills == 0, (
        "migration must not re-pay prefill on the target"
    )


def test_migration_int8_pages_ship_as_int8(programs):
    """Quantized pools migrate as stored bytes: the payload's page
    arrays are int8 (+ f32 scale rows), and the migrated continuation
    is byte-exact against an UNINTERRUPTED int8 engine run (the
    quantized contract is engine-vs-engine, not engine-vs-f32)."""
    model, params = programs["model"], programs["params"]
    dec8 = jax.jit(paged_decode_fn(model, PAGE, True))

    def mk8():
        cache = PagedKVCache(
            programs["template"], page_size=PAGE, kv_dtype="int8"
        )
        return ServeSession(
            programs["prefill"], dec8, params,
            cache, PROMPT_LEN,
        )

    req = Request("r0", [3, 5, 7, 11, 2], max_new_tokens=16)
    control = mk8()
    control.submit(req)
    want = control.collect()["r0"]
    src, dst = mk8(), mk8()
    src.submit(req)
    for _ in range(4):
        src.engine.step()
    payload = src.engine.export_request("r0")
    meta = parse_migration(payload)
    assert meta["quantized"] is True
    kinds = {
        path.rsplit("'", 2)[-2]: arr.dtype
        for path, arr in meta["_arrays"].items()
    }
    assert kinds["pages_k"] == np.int8 and kinds["pages_v"] == np.int8
    assert kinds["scale_k"] == np.float32
    dst.engine.install_migrated(payload)
    while dst.engine.step():
        pass
    assert dst.engine.results["r0"].tokens == want.tokens
    assert dst.engine.num_prefills == 0


def test_migration_crc_guard(programs):
    """Any bit flip or truncation in transfer raises
    MigrationCorruptError at the door; through the migrate inbox the
    same payload becomes a ``failed`` Result — never a resumed
    stream."""
    src = _session(programs)
    req = Request("r0", [3, 5, 7], max_new_tokens=12)
    src.submit(req)
    for _ in range(3):
        src.engine.step()
    payload = src.engine.export_request("r0")
    flipped = chaos.corrupt_payload(payload)
    assert len(flipped) == len(payload)
    assert sum(
        bin(a ^ b).count("1") for a, b in zip(payload, flipped)
    ) == 1, "corrupt_payload must flip exactly one bit"
    dst = _session(programs)
    with pytest.raises(MigrationCorruptError):
        dst.engine.install_migrated(flipped)
    with pytest.raises(MigrationCorruptError):
        parse_migration(payload[: len(payload) // 2])
    # Through the inbox (the router's hand-off path): failed Result.
    from tpudl.serve.engine import _Migrated

    dst2 = _session(programs)
    dst2.engine.migrate_inbox.append(_Migrated("r0", flipped))
    dst2.engine.step()
    res = dst2.engine.results["r0"]
    assert res.finish_reason.startswith("failed")
    assert res.tokens == []
    assert all(s is None for s in dst2.engine._slots), (
        "a corrupt payload must never seat"
    )


def test_failed_migration_bills_payload_tenant(programs):
    """The terminal record of a migration that cannot resume carries
    the payload's tenant, prompt length, and CUMULATIVE hop count —
    failed migrated requests must not be metered under ``_base`` (the
    failure class multi-tenant billing most needs to see)."""
    from tpudl.obs import metering

    # The hop count rides the payload (export stamps hops survived).
    src = _session(programs)
    src.submit(Request("rm", [3, 5, 7], max_new_tokens=12))
    src.engine.step()
    assert parse_migration(src.engine.export_request("rm"))[
        "migrations"
    ] == 0

    dst = _session(programs)
    meter = metering.meter()
    meter.reset()
    try:
        dst.engine._fail_migrated(
            "rx", RuntimeError("boom"),
            meta={
                "request": {
                    "tenant": "acme", "input_ids": [1, 2, 3, 4],
                },
                "migrations": 2,
            },
        )
        snap = meter.tenants()
        assert metering.BASE_TENANT not in snap
        a = snap["acme"]
        assert a["requests_total"] == 1
        assert a["tokens_in"] == 4
        assert a["migrations"] == 3  # 2 survived hops + this failure
        assert a["sheds"] == {"failed": 1}
        # A corrupt transfer has no parsed meta: the fallback still
        # lands the record (under _base) instead of crashing.
        dst.engine._fail_migrated("ry", RuntimeError("crc"), meta=None)
        assert meter.tenants()[metering.BASE_TENANT][
            "migrations"
        ] == 1
    finally:
        meter.reset()


def test_migration_deadline_rides_payload(programs):
    """The absolute deadline stamp rides the payload: a target inside
    the budget seats and honors the remainder; a transfer that
    exhausted it sheds as shed_timeout, never resumes."""
    src = _session(programs)
    req = Request("r0", [3, 5, 7], max_new_tokens=12, deadline_s=0.4)
    src.submit(req)
    src.engine.step()
    slot = next(
        i for i, s in enumerate(src.engine._slots) if s is not None
    )
    stamp = src.engine._slots[slot].entry.deadline
    assert stamp is not None
    payload = src.engine.export_request("r0")
    assert parse_migration(payload)["deadline_at"] == stamp
    # Transfer "takes" longer than the remaining budget:
    time.sleep(0.5)
    dst = _session(programs)
    dst.engine.install_migrated(payload)
    res = dst.engine.results["r0"]
    assert res.finish_reason == "shed_timeout"
    assert all(s is None for s in dst.engine._slots)
    # Within budget: seats and completes.
    src2 = _session(programs)
    req2 = Request("r1", [3, 5, 7], max_new_tokens=12, deadline_s=60.0)
    src2.submit(req2)
    src2.engine.step()
    dst2 = _session(programs)
    dst2.engine.install_migrated(src2.engine.export_request("r1"))
    while dst2.engine.step():
        pass
    assert dst2.engine.results["r1"].ok


def test_migration_prefix_reference_first(programs):
    """Prefix-share fleets ship a target-cached prefix as token-block
    REFERENCES (pre-leased), shrinking the payload; a cold target gets
    the full page payload; a reference-only payload against a tree
    that lost the prefix is REFUSED (MigrationCompatError), not
    resumed with holes."""
    model, params = programs["model"], programs["params"]

    def mk_share():
        return ServeSession.from_model(
            model, params, prompt_len=3 * PAGE, num_slots=2,
            page_size=PAGE, prefix_share=True,
        )

    shared = list(range(2, 2 + PAGE))  # one full page
    prompt = shared + [31, 37, 41]
    req = Request("r0", prompt, max_new_tokens=12)
    dst = mk_share()
    dst.submit(Request("warm", shared + [51, 52], max_new_tokens=3))
    dst.collect()

    def export_from_fresh_source(skip):
        src = mk_share()
        src.submit(Request("r0", prompt, max_new_tokens=12))
        for _ in range(3):
            src.engine.step()
        return src.engine.export_request("r0", skip_prefix_tokens=skip)

    skip = dst.engine.cache.prefix_match_len(prompt)
    assert skip == PAGE
    lease = dst.engine.cache.match_and_lease(prompt)
    full_payload = export_from_fresh_source(0)
    ref_payload = export_from_fresh_source(skip)
    assert len(ref_payload) < len(full_payload)
    dst.engine.install_migrated(ref_payload, lease=lease)
    while dst.engine.step():
        pass
    res = dst.engine.results["r0"]
    got = np.asarray(res.tokens)
    want = np.asarray(
        generate(
            model, params, jnp.asarray(prompt)[None, :],
            max_new_tokens=12,
        )
    )[0]
    np.testing.assert_array_equal(got, want[: got.shape[0]])
    # Cold target: tree miss -> reference-only payload refused.
    cold = mk_share()
    with pytest.raises(MigrationCompatError, match="reference"):
        cold.engine.install_migrated(export_from_fresh_source(skip))
    # ... while the full payload seats fine and seeds the cold tree.
    cold.engine.install_migrated(export_from_fresh_source(0))
    while cold.engine.step():
        pass
    assert cold.engine.results["r0"].ok
    assert cold.engine.cache.prefix_match_len(prompt) >= PAGE, (
        "a migrated-in prompt's full pages should enter the radix tree"
    )


# ---------------------------------------------------------------------------
# router-level: failover, crash fallback, cap, drain
# ---------------------------------------------------------------------------


def test_failover_migrates_zero_reprefill_span_audited(programs, tmp_path):
    """The acceptance scenario: kill (preempt) one replica of three
    mid-decode under load — every in-flight request completes on
    survivors with byte-exact generate() parity, migrated requests
    issue ZERO prefill dispatches on the target (span-audited: one
    prefill event per request fleet-wide), and the failover token-gap
    histogram observes the stall."""
    obs.enable(str(tmp_path / "obs"))
    sessions = [_session(programs, slow_s=0.02) for _ in range(3)]
    replicas = [Replica(f"r{i}", s) for i, s in enumerate(sessions)]
    # Chaos preemption notice on r1's engine: mid-decode it turns lame
    # duck (unready, thread answering) — the migration path.
    sessions[1].engine.chaos_hooks.append(chaos.step_preempter(6))
    rng = np.random.default_rng(3)
    requests = [
        Request(
            f"q{i}",
            rng.integers(1, CFG.vocab_size, size=5).tolist(),
            max_new_tokens=int(rng.integers(14, 20)),
        )
        for i in range(6)
    ]
    with Router(replicas, scrape_interval_s=0.0) as router:
        for req in requests:
            router.submit(req)
        assert any(
            owner == "r1" for owner, _ in router._assigned.values()
        ), "nothing landed on the doomed replica — scenario is vacuous"
        results = router.collect(timeout_s=300.0)
    assert replicas[1].lame, "the chaos preemption never fired"
    assert router.num_migrations >= 1
    assert set(results) == {r.request_id for r in requests}
    _assert_parity(programs, requests, results)
    # Fleet-wide prefill accounting: exactly one per request — a
    # resubmission would re-pay one.
    assert sum(s.engine.num_prefills for s in sessions) == len(requests)
    records = obs_spans.active_recorder().records
    migrated = {
        r["request_id"]
        for r in records
        if r.get("name") == "request_migrated"
    }
    assert migrated, "no request_migrated event recorded"
    for rid in migrated:
        prefills = [
            r for r in records
            if r.get("name") == "prefill" and r.get("request_id") == rid
        ]
        assert len(prefills) == 1, (
            f"{rid}: expected exactly its original prefill span, got "
            f"{len(prefills)} — the target re-prefilled"
        )
        installs = [
            r for r in records
            if r.get("name") == "migration_install"
            and r.get("request_id") == rid
        ]
        assert len(installs) == 1
    snap = obs_counters.registry().snapshot()
    assert snap["histograms"]["serve_failover_token_gap_ms"]["count"] >= 1
    assert snap["counters"]["serve_migrations_total"] >= 1


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_crashed_replica_falls_back_to_resubmit(programs):
    """A chaos KILL (thread dies) leaves no payloads: the router falls
    back to today's resubmission path — parity via re-generation, and
    the fleet pays the prefill again (that is the fallback's cost)."""
    sessions = [_session(programs, slow_s=0.02) for _ in range(2)]
    replicas = [Replica(f"r{i}", s) for i, s in enumerate(sessions)]
    sessions[0].engine.chaos_hooks.append(chaos.step_killer(4))
    requests = [
        Request(f"q{i}", [3 + i, 5, 7], max_new_tokens=14)
        for i in range(4)
    ]
    with Router(
        replicas, scrape_interval_s=0.0, migrate_timeout_s=0.3
    ) as router:
        for req in requests:
            router.submit(req)
        results = router.collect(timeout_s=300.0)
    assert router.num_failovers >= 1
    assert router.num_migrations == 0
    assert replicas[0]._published["healthy"] is False
    _assert_parity(programs, requests, results)


def test_failover_resubmissions_capped(programs):
    """The ping-pong guard: with the cap at 0, the first from-scratch
    resubmission sheds the request as ``failover_exhausted`` instead
    of restarting it — a request bouncing across successively dying
    replicas terminates."""
    sessions = [_session(programs, slow_s=0.05) for _ in range(2)]
    replicas = [Replica(f"r{i}", s) for i, s in enumerate(sessions)]
    requests = [
        Request(f"q{i}", [3 + i, 5, 7], max_new_tokens=30)
        for i in range(4)
    ]
    with Router(
        replicas, scrape_interval_s=0.0, migrate=False, max_failovers=0
    ) as router:
        for req in requests:
            router.submit(req)
        doomed = {
            rid for rid, (owner, _) in router._assigned.items()
            if owner == "r0"
        }
        assert doomed
        time.sleep(0.1)
        replicas[0].lame = True  # unready; migrate=False -> resubmit
        results = router.collect(timeout_s=300.0)
    for rid in doomed:
        assert results[rid].finish_reason == "failover_exhausted", (
            rid, results[rid].finish_reason
        )
        assert results[rid].tokens == []
    survivors = set(results) - doomed
    assert all(results[rid].ok for rid in survivors)
    snap = obs_counters.registry().snapshot()
    assert snap["counters"]["serve_requests_failover_exhausted"] == len(
        doomed
    )


def test_drain_is_instant_and_drops_nothing(programs):
    """The acceptance drain bar: removing a loaded replica returns in
    < 10% of the time its longest in-flight generation still needed,
    every Result is delivered with parity, and zero requests restart
    (migrations, not failovers)."""
    step_s = 0.05
    max_new = 40
    sessions = [_session(programs, slow_s=step_s) for _ in range(2)]
    for session in sessions:
        # Each session compiles its own seat program at its first seat
        # (test_frozen_replica_goes_stale_then_recovers): served once
        # here, nothing of d1's compiles inside the timed drain while
        # d0's requests are seated on it.
        session.serve([Request("warm", [1, 2, 3], max_new_tokens=2)])
    replicas = [Replica(f"d{i}", s) for i, s in enumerate(sessions)]
    requests = [
        Request(f"w{i}", [3, 5, 7 + i], max_new_tokens=max_new)
        for i in range(4)
    ]
    with Router(replicas, scrape_interval_s=0.0) as router:
        for req in requests:
            router.submit(req)
        time.sleep(8 * step_s)  # everyone mid-stream, far from done
        t0 = time.perf_counter()
        router.remove_replica("d0", drain=True, timeout_s=60.0)
        drain_s = time.perf_counter() - t0
        results = router.collect(timeout_s=300.0)
    longest_remaining_s = max_new * step_s  # conservative lower bound
    assert drain_s < 0.1 * longest_remaining_s, (
        f"drain took {drain_s:.3f}s — not < 10% of the "
        f"{longest_remaining_s:.1f}s the longest generation needed"
    )
    assert router.num_failovers == 0
    assert set(results) == {r.request_id for r in requests}
    _assert_parity(programs, requests, results)
    snap = obs_counters.registry().snapshot()
    assert snap["histograms"]["serve_drain_ms"]["count"] >= 1


def test_frozen_replica_goes_stale_then_recovers(programs):
    """A freeze mid-step: the stale-heartbeat bound flips the replica
    unready (work fails over; the frozen thread cannot answer the
    migration pull, so resubmission covers it), and when the freeze
    ends the replica publishes again and scrapes ready."""
    sessions = [_session(programs, slow_s=0.01) for _ in range(2)]
    for session in sessions:
        # Each session compiles its own seat program at its first seat;
        # behind the router that compile alone can outlast the 0.15 s
        # staleness bound, r0's work fails over before it reaches the
        # step that freezes, and nothing is frozen at all.
        session.serve([Request("warm", [1, 2, 3], max_new_tokens=2)])
    replicas = [
        Replica("r0", sessions[0], stale_after_s=0.15),
        Replica("r1", sessions[1]),
    ]
    # The freeze has to outlast the collect below (0.48-0.63 s here, on
    # this tree and on its parent alike): at 0.6 s the replica had
    # sometimes recovered before the assertion looked.
    sessions[0].engine.chaos_hooks.append(chaos.step_freezer(3, 2.0))
    requests = [
        Request(f"q{i}", [3 + i, 5, 7], max_new_tokens=16)
        for i in range(4)
    ]
    with Router(
        replicas, scrape_interval_s=0.0, migrate_timeout_s=0.1
    ) as router:
        for req in requests:
            router.submit(req)
        results = router.collect(timeout_s=300.0)
        assert not router._ready["r0"], (
            "the freeze never flipped r0 unready via staleness"
        )
        _assert_parity(programs, requests, results)
        # The freeze ends; the loop publishes again and r0 rejoins.
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and not router._ready["r0"]:
            router.poll()
            time.sleep(0.02)
        assert router._ready["r0"], "r0 never recovered after the freeze"


# ---------------------------------------------------------------------------
# chaos injector units
# ---------------------------------------------------------------------------


def test_once_marker_claims_exactly_once(tmp_path):
    assert chaos.claim_once(str(tmp_path), "kill")
    assert not chaos.claim_once(str(tmp_path), "kill")
    assert chaos.claim_once(str(tmp_path), "freeze")
    assert chaos.claim_once(None, "kill")  # no dir: always claims


def test_step_killer_fires_once_at_step(tmp_path):
    hook = chaos.step_killer(5, once_dir=str(tmp_path))
    for step in range(5):
        hook(step)  # below the threshold: nothing
    with pytest.raises(chaos.ChaosKill):
        hook(5)
    hook(6)  # latched: never re-fires
    # A second engine's hook sharing the once-dir never fires at all.
    other = chaos.step_killer(5, once_dir=str(tmp_path))
    other(7)


def test_step_freezer_sleeps_injected(tmp_path):
    slept = []
    hook = chaos.step_freezer(2, 1.5, sleep=slept.append)
    hook(1)
    assert slept == []
    hook(2)
    assert slept == [1.5]
    hook(3)
    assert slept == [1.5]


def test_env_hooks_and_scrape_chaos(monkeypatch, tmp_path):
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_KILL_STEP", "3")
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_FREEZE_STEP", "4")
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_ONCE_DIR", str(tmp_path))
    hooks = chaos.engine_step_hooks()
    assert len(hooks) == 2
    monkeypatch.delenv("TPUDL_SERVE_CHAOS_KILL_STEP")
    monkeypatch.delenv("TPUDL_SERVE_CHAOS_FREEZE_STEP")
    assert chaos.engine_step_hooks() == []

    class FakeMonitor:
        scrape_fault = None

    mon = FakeMonitor()
    assert not chaos.install_scrape_chaos(mon)
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_SCRAPE_FAIL_N", "2")
    assert chaos.install_scrape_chaos(mon)
    with pytest.raises(chaos.ChaosScrapeBlackhole):
        mon.scrape_fault("m0")
    with pytest.raises(chaos.ChaosScrapeBlackhole):
        mon.scrape_fault("m0")
    mon.scrape_fault("m0")  # budget spent: clean


def test_maybe_corrupt_migration_env_gated(monkeypatch):
    payload = b"tpudl-payload-bytes"
    assert chaos.maybe_corrupt_migration(payload) == payload
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_FLIP_MIGRATION", "1")
    flipped = chaos.maybe_corrupt_migration(payload)
    assert flipped != payload and len(flipped) == len(payload)


def test_corrupted_transfer_sheds_failed_never_resumes(
    programs, monkeypatch
):
    """End-to-end chaos corruption: with the env flip on, a failover
    migration's payload is corrupted in transfer — the target's crc
    sheds the request as ``failed``; it is never resumed."""
    monkeypatch.setenv("TPUDL_SERVE_CHAOS_FLIP_MIGRATION", "1")
    sessions = [_session(programs, slow_s=0.02) for _ in range(2)]
    replicas = [Replica(f"r{i}", s) for i, s in enumerate(sessions)]
    requests = [
        Request(f"q{i}", [3 + i, 5, 7], max_new_tokens=16)
        for i in range(4)
    ]
    with Router(replicas, scrape_interval_s=0.0) as router:
        for req in requests:
            router.submit(req)
        doomed = {
            rid for rid, (owner, _) in router._assigned.items()
            if owner == "r0"
        }
        assert doomed
        time.sleep(0.1)
        replicas[0].lame = True
        results = router.collect(timeout_s=300.0)
    assert router.num_migrations >= 1
    migrated_failed = [
        rid for rid in doomed
        if results[rid].finish_reason.startswith("failed")
    ]
    assert migrated_failed, (
        "corrupted migration payloads must shed as failed, got "
        f"{ {rid: results[rid].finish_reason for rid in doomed} }"
    )
    for rid in migrated_failed:
        assert results[rid].tokens == []
    snap = obs_counters.registry().snapshot()
    assert snap["counters"]["serve_migrations_failed"] >= 1
    assert "TPUDL_SERVE_CHAOS_FLIP_MIGRATION" in os.environ  # guard on


# ---------------------------------------------------------------------------
# review-round regressions
# ---------------------------------------------------------------------------


def test_pad_aligned_payload_ignores_prepinned_lease(programs):
    """A pad-aligned (non-prefix-share) source exports rows that do NOT
    follow the radix tree's canonical token->position mapping: a
    pre-pinned lease handed to import must be DROPPED (pages imported
    fully private), not spliced in over wrong KV — the continuation
    stays byte-exact and the pin is released."""
    model, params = programs["model"], programs["params"]
    share = ServeSession.from_model(
        model, params, prompt_len=2 * PAGE, num_slots=2,
        page_size=PAGE, prefix_share=True,
    )
    prompt = list(range(2, 2 + PAGE)) + [31, 37]
    # Warm the share target's tree with the same leading page.
    share.submit(Request("warm", prompt[:PAGE] + [51], max_new_tokens=3))
    share.collect()
    # Pad-aligned source: plain paged session (seat() path, start > 0).
    src = ServeSession.from_model(
        model, params, prompt_len=2 * PAGE, num_slots=2,
        page_size=PAGE,
    )
    req = Request("r0", prompt, max_new_tokens=10)
    src.submit(req)
    for _ in range(3):
        src.engine.step()
    assert int(src.engine.cache.start[0]) > 0  # genuinely pad-aligned
    payload = src.engine.export_request("r0")
    assert parse_migration(payload)["left_aligned"] is False
    evictable_before = share.engine.cache.radix.evictable_pages
    lease = share.engine.cache.match_and_lease(prompt)
    share.engine.install_migrated(payload, lease=lease)
    assert share.engine.cache.radix.evictable_pages == evictable_before, (
        "the dropped lease must be released (refcount back to 0)"
    )
    while share.engine.step():
        pass
    res = share.engine.results["r0"]
    got = np.asarray(res.tokens)
    want = np.asarray(
        generate(
            model, params, jnp.asarray(prompt)[None, :],
            max_new_tokens=10,
        )
    )[0]
    np.testing.assert_array_equal(got, want[: got.shape[0]])


def test_export_declines_json_unstable_request_ids(programs):
    """request_id/session_key ride the payload as JSON: an id that
    does not round-trip (tuple -> list) must DECLINE export — the
    resubmit fallback preserves the original object — instead of
    resuming under a mutated (here: unhashable) identity."""
    src = _session(programs)
    req = Request(("user7", 42), [3, 5, 7], max_new_tokens=8)
    src.submit(req)
    src.engine.step()
    assert src.engine.export_request(("user7", 42)) is None
    # The request is untouched and still completes locally.
    while src.engine.step():
        pass
    assert src.engine.results[("user7", 42)].ok


def test_migrate_out_returns_reference_payload_as_request(programs):
    """A queued migrate-inbox payload that was reference-skipped is
    whole only against the tree it was probed on: a second relocation
    must hand the REQUEST back for resubmission, never forward the
    holey payload to a target that would refuse it."""
    src = _session(programs)
    req = Request("r0", [3, 5, 7, 11, 2, 9, 4, 6], max_new_tokens=8)
    src.submit(req)
    for _ in range(2):
        src.engine.step()
    full = src.engine.export_request("r0")
    meta = parse_migration(full)
    meta["skip_tokens"] = PAGE  # simulate a reference-skipped transfer
    from tpudl.serve.cache import pack_migration
    from tpudl.serve.engine import _Migrated

    holey = pack_migration(
        {k: v for k, v in meta.items() if k not in ("_arrays", "arrays")},
        [],
    )
    holder = _session(programs)
    replica = Replica("hold", holder)
    replica.session.engine.migrate_inbox.append(_Migrated("r0", holey))
    replica.session.engine.migrate_inbox.append(_Migrated("r1", full))
    box = {
        "done": __import__("threading").Event(),
        "lock": __import__("threading").Lock(),
        "claimed": False, "abandoned": False,
        "skip": {}, "payloads": {}, "requests": {},
    }
    replica._migrate_out(box)
    assert "r0" in box["requests"], "skip>0 payload must come back as a Request"
    assert box["requests"]["r0"].request_id == "r0"
    assert "r1" in box["payloads"], "skip==0 payload forwards as-is"
