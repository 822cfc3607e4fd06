"""A full engine dispatches the next decode step before it reads the
last one's tokens (tpudl.serve.engine ``_decode_step``).

The step ahead takes its input tokens from the device, so the same
programs see the same inputs in the same order: every request's tokens
are what ``generate()`` gives it alone, and what an engine that never
runs ahead (one with a slot to spare) gives it. On top of that: the
rule engages from the engine's own slots and from nothing else, a slot
that ends while a step is ahead is handled (by length it rides as an
idle row, by ``eos_id`` its wasted row reaches nobody), whoever touches
a slot from outside ``step`` lands the step in flight first, and the
spans stay the ones a step always had, one ``decode_step`` a landing
(opened before the call's admission where the step it lands was in
flight: the seats queue behind that step, tests/test_seat_async.py).
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl import obs
from tpudl.analysis.dispatch import RecompileWatcher
from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

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


class FakeClock:
    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _session(model, params, num_slots=2, **kw):
    kw.setdefault("page_size", 4)
    return ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=num_slots, **kw
    )


def _requests(n, draw=0, new=(4, 12), **kw):
    rng = np.random.default_rng(draw)
    return [
        Request(
            f"r{i}",
            rng.integers(1, CFG.vocab_size,
                         size=int(rng.integers(2, PROMPT_LEN + 1))).tolist(),
            max_new_tokens=int(rng.integers(*new)), **kw,
        )
        for i in range(n)
    ]


def _alone(model, params, req):
    """What ``generate()`` gives the request by itself, as long as the
    request's ``max_new_tokens``."""
    return np.asarray(generate(
        model, params, jnp.asarray(req.input_ids)[None, :],
        max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
    ))[0]


def _ahead():
    return obs_counters.registry().counter("serve_decode_steps_ahead").value


def _spans(records, name=None):
    """The spans called ``name``; with no name, the hot path's (the
    records a process makes of how it began, ISSUE 49, left out)."""
    return [r for r in records if r.get("kind") == "span"
            and (r["name"] == name if name is not None else
                 not r["name"].startswith(("startup.", "program.", "kernel.")))]


# ---------------------------------------------------------------------------
# (a) the same tokens, whether or not the engine runs ahead
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("continuous", [True, False],
                         ids=["continuous", "static"])
@pytest.mark.parametrize("sampling", [
    {}, {"temperature": 0.8, "seed": 11},
], ids=["greedy", "sampled"])
def test_a_full_engine_serves_what_one_with_a_slot_to_spare_serves(
    model_and_params, sampling, continuous
):
    model, params = model_and_params
    requests = _requests(4, draw=1, **sampling)
    full = _session(model, params, num_slots=2, continuous=continuous)
    got = full.serve(requests)
    ran_ahead = _ahead()
    assert ran_ahead > 0
    spare = _session(model, params, num_slots=5, continuous=continuous)
    want = spare.serve(requests)
    assert _ahead() == ran_ahead  # five slots never fill with four
    for req in requests:
        rid = req.request_id
        assert got[rid].tokens == want[rid].tokens, rid
        assert got[rid].finish_reason == want[rid].finish_reason == "length"
        if not sampling:
            np.testing.assert_array_equal(
                got[rid].tokens, _alone(model, params, req), err_msg=rid
            )


# ---------------------------------------------------------------------------
# (b) an eos met while a step is ahead
# ---------------------------------------------------------------------------


def test_an_eos_under_a_step_ahead_wastes_a_row_that_reaches_nobody(
    model_and_params
):
    model, params = model_and_params
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, CFG.vocab_size, size=5).tolist()
               for _ in range(3)]
    probe = np.asarray(generate(
        model, params, jnp.asarray(prompts[0])[None, :], max_new_tokens=20
    ))[0]
    eos = int(probe[4])
    assert eos not in probe[:4].tolist()
    requests = [
        Request("A", prompts[0], max_new_tokens=20, eos_id=eos),
        Request("B", prompts[1], max_new_tokens=24),
        Request("C", prompts[2], max_new_tokens=8),  # A's slot, after it
    ]
    session = _session(model, params)
    engine = session.engine
    streamed = {r.request_id: [] for r in requests}
    engine.on_token = lambda rid, tok: streamed[rid].append(tok)
    for req in requests:
        session.submit(req)
    wasted = None
    while engine.step():
        if wasted is None and "A" in engine.results:
            # The step that landed A's eos had its successor queued
            # already, A's row among its rows.
            step = engine._in_flight
            assert step is not None and step.ahead
            (wasted,) = [s for s in step.rows
                         if s is not None and s.request.request_id == "A"]
    assert wasted is not None
    results = engine.results
    assert results["A"].finish_reason == "eos"
    assert results["A"].tokens == probe[:5].tolist()
    # The row the step ahead computed for A went to nobody: not to A,
    # whose stream ends at its eos, and not to C, who takes A's slot
    # once that step has landed.
    assert wasted.tokens == results["A"].tokens
    for req in requests:
        rid = req.request_id
        assert streamed[rid] == results[rid].tokens, rid
        want = _alone(model, params, req)
        np.testing.assert_array_equal(
            results[rid].tokens, want[: len(results[rid].tokens)],
            err_msg=rid,
        )
    assert len(results["C"].tokens) == 8
    cache = engine.cache
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)


# ---------------------------------------------------------------------------
# (c) a finish by length rides the step ahead as an idle row
# ---------------------------------------------------------------------------


def test_a_length_finish_rides_the_step_ahead_as_an_idle_row(
    model_and_params, tmp_path
):
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    engine, cache = session.engine, session.engine.cache
    short = Request("short", [5, 6, 7], max_new_tokens=4)
    long = Request("long", [8, 9, 10, 11], max_new_tokens=10)
    session.submit(short)
    session.submit(long)
    advanced = []
    advance = cache.advance

    def spy(slots, steps=1):
        advanced.append(list(slots))
        advance(slots, steps)

    cache.advance = spy
    most = {}
    rows_of = {}
    while engine.step():
        for i, s in enumerate(engine._slots):
            if s is not None:
                rid = s.request.request_id
                rows_of[rid] = s.kv_base
                most[rid] = max(most.get(rid, 0), int(cache.lens[i]))
    records = rec.records
    obs.disable()
    # A request of n tokens writes n - 1 rows past its prompt's: no
    # dispatch, ahead or not, advanced a slot beyond that.
    for req in (short, long):
        rid = req.request_id
        assert most[rid] <= rows_of[rid] + req.max_new_tokens - 1
        np.testing.assert_array_equal(
            engine.results[rid].tokens, _alone(model, params, req)
        )
    # ``short`` has 4 tokens: one from its prefill, three decode rows.
    # The fourth dispatch (ahead of the step that selects its last
    # token) computes ``long`` alone.
    assert advanced[:4] == [[0, 1], [0, 1], [0, 1], [1]]
    steps = _spans(records, "decode_step")
    assert [s["rids"] for s in steps[:4]] == [
        ["short", "long"], ["short", "long"], ["short", "long"], ["long"]
    ]
    assert [s["ahead"] for s in steps[:4]] == [0, 1, 1, 1]
    # With a slot free nothing runs ahead any more.
    assert all(s["ahead"] == 0 for s in steps[4:])
    assert len(steps) == engine.num_decode_steps == 9


# ---------------------------------------------------------------------------
# (d) the rule, from the engine's own slots
# ---------------------------------------------------------------------------


def test_with_a_free_slot_nothing_runs_ahead(model_and_params, tmp_path):
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params, num_slots=5)
    session.serve(_requests(4, draw=2))
    records = rec.records
    obs.disable()
    steps = _spans(records, "decode_step")
    assert steps and all(s["ahead"] == 0 for s in steps)
    assert _ahead() == 0
    assert session.engine._in_flight is None


def test_a_full_engine_runs_ahead_over_the_seats_it_makes(
    model_and_params, tmp_path
):
    """Driven a call at a time: the step a call lands was in flight
    (``ahead`` 1) or is the call's own (0); a call that finds a step in
    flight seats behind it, and the step it dispatches next takes the
    seat's first token from the device like its neighbours' tokens; a
    call leaves a step in flight iff every slot was seated at its
    dispatches."""
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    engine = session.engine
    for req in _requests(6, draw=4):
        session.submit(req)
    calls = []
    while True:
        was_in_flight = engine._in_flight is not None
        prefills = engine.num_prefills
        if not engine.step():
            break
        calls.append((was_in_flight, engine.num_prefills - prefills,
                      engine._in_flight is not None))
        # Between two calls the device holds one decode step at most,
        # and no first token.
        assert len(engine._unread) <= 1
        assert all(s is None or s.first is None for s in engine._slots)
    records = rec.records
    obs.disable()
    steps = _spans(records, "decode_step")
    emits = _spans(records, "emit")
    engine_steps = [s for s in _spans(records, "engine_step")
                    if any(d["parent"] == s["id"] for d in steps)]
    assert len(steps) == len(emits) == len(engine_steps) == len(calls)
    seen = {0: 0, 1: 0}
    behind = 0
    for (was_in_flight, seated, left_in_flight), step, emit, outer in zip(
        calls, steps, emits, engine_steps
    ):
        assert step["ahead"] == int(was_in_flight)
        behind += seated if was_in_flight else 0
        seen[step["ahead"]] += 1
        # Slots seated when the call dispatched: those still seated at
        # its end and those its landing finished.
        full = outer["busy"] + emit["finished"] == engine.num_slots
        # A slot whose last token the landed step selects has no row in
        # the step ahead; with both slots ending there is no such step.
        rows_left = emit["finished"] < engine.num_slots or any(
            res.finish_reason == "eos" for res in engine.results.values()
        )
        assert left_in_flight == (full and rows_left)
    assert seen[1] == _ahead() > 0
    # Six requests on two slots: four seats follow the first two, each
    # behind the step that was ahead when its slot came free, and the
    # step dispatched after it is ahead too.
    waits = _spans(records, "prefill")
    assert behind == sum(w["behind"] for w in waits) == 4
    assert obs_counters.registry().counter(
        "serve_prefills_behind_step"
    ).value == behind
    # Only the first step, and those after the queue ran dry and left a
    # slot free, took their tokens from the host.
    flags = [s["ahead"] for s in steps]
    assert flags[0] == 0 and flags[1:] == sorted(flags[1:], reverse=True)
    assert obs_counters.registry().counter("serve_decode_steps").value == (
        len(steps)
    )


# ---------------------------------------------------------------------------
# (e) whoever touches a slot from outside lands the step in flight first
# ---------------------------------------------------------------------------


def test_export_lands_the_step_in_flight_and_loses_no_token(
    model_and_params
):
    model, params = model_and_params
    src = _session(model, params)
    dst = _session(model, params)
    moved, stays = _requests(2, draw=5, new=(10, 14))
    src.submit(moved)
    src.submit(stays)
    src.engine.step()
    src.engine.step()
    in_flight = src.engine._in_flight
    assert in_flight is not None
    (slot,) = [s for s in in_flight.rows
               if s.request.request_id == moved.request_id]
    assert len(slot.tokens) == 3  # prefill's, and two landed steps'
    payload = src.engine.export_request(moved.request_id)
    assert src.engine._in_flight is None
    # The third step's token was on the device alone: it is in the
    # payload, and the neighbour has its own.
    assert len(slot.tokens) == 4
    (kept,) = [s for s in src.engine._slots if s is not None]
    assert len(kept.tokens) == 4
    dst.engine.install_migrated(payload)
    got = {**src.engine.run_until_drained(), **dst.engine.run_until_drained()}
    for req in (moved, stays):
        np.testing.assert_array_equal(
            got[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )
    for engine in (src.engine, dst.engine):
        assert engine._in_flight is None
        assert (engine.cache.pages_reserved, engine.cache.tokens_live) == (
            0, 0
        )


def test_install_lands_the_step_in_flight_before_it_seats(model_and_params):
    model, params = model_and_params
    src = _session(model, params)
    dst = _session(model, params)
    moved, a, b = _requests(3, draw=6, new=(10, 14))
    a.max_new_tokens = 3
    src.submit(moved)
    src.engine.step()
    payload = src.engine.export_request(moved.request_id)
    dst.submit(a)
    dst.submit(b)
    dst.engine.step()
    # ``a``'s third and last token is the step in flight's to land: the
    # install frees its slot by landing it, and takes that slot.
    assert dst.engine._in_flight is not None
    assert a.request_id not in dst.engine.results
    dst.engine.install_migrated(payload)
    assert dst.engine._in_flight is None
    assert dst.engine.results[a.request_id].finish_reason == "length"
    got = dst.engine.run_until_drained()
    for req in (moved, a, b):
        np.testing.assert_array_equal(
            got[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )


def test_a_replicas_migration_pull_lands_the_step_in_flight_first(
    model_and_params
):
    """The drain path: what leaves as a payload holds every token the
    device has computed, and a request the step in flight finishes
    leaves as its Result, not as a payload."""
    import threading

    from tpudl.serve.cache import parse_migration
    from tpudl.serve.router import Replica

    model, params = model_and_params
    session = _session(model, params)
    replica = Replica("leaving", session)
    ends, goes_on = _requests(2, draw=12, new=(10, 14))
    ends.max_new_tokens = 3
    session.submit(ends)
    session.submit(goes_on)
    session.engine.step()
    assert session.engine._in_flight is not None
    box = {
        "done": threading.Event(), "lock": threading.Lock(),
        "claimed": False, "abandoned": False,
        "skip": {}, "payloads": {}, "requests": {},
    }
    replica._migrate_out(box)
    assert session.engine._in_flight is None
    assert list(box["payloads"]) == [goes_on.request_id]
    assert not box["requests"]
    meta = parse_migration(box["payloads"][goes_on.request_id])
    want = _alone(model, params, goes_on)
    assert meta["tokens"] == want[:3].tolist()  # prefill's, and two steps'
    done = session.engine.results[ends.request_id]
    assert done.finish_reason == "length"
    np.testing.assert_array_equal(done.tokens, _alone(model, params, ends))


def test_a_drained_engine_holds_no_step_and_an_abandoned_one_lets_go(
    model_and_params
):
    model, params = model_and_params
    session = _session(model, params)
    requests = _requests(2, draw=7, new=(6, 9))
    results = session.serve(requests)
    assert session.engine._in_flight is None
    assert session.engine.step() is False
    assert sum(len(r.tokens) for r in results.values()) == sum(
        r.max_new_tokens for r in requests
    )
    # The closed-loop window's end: a step in flight, and nobody comes
    # back for it.
    for req in _requests(2, draw=8, new=(6, 9)):
        req.request_id = "late-" + req.request_id
        session.submit(req)
    session.engine.step()
    assert session.engine._in_flight is not None
    pool = jax.tree.leaves(session.engine.cache.cache)[0]
    del session
    gc.collect()
    pool.block_until_ready()  # the step ahead ran to its end


# ---------------------------------------------------------------------------
# (f) the spans a step always had, one decode_step a landing
# ---------------------------------------------------------------------------


def test_on_the_fake_clock_a_landing_is_one_decode_step_with_its_family(
    model_and_params, tmp_path
):
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    session.engine.clock = FakeClock()
    session.serve(_requests(4, draw=9))
    records = rec.records
    obs.disable()
    spans = _spans(records)
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    for group in kids.values():
        group.sort(key=lambda s: s["ts"])
    steps = _spans(records, "decode_step")
    assert len(steps) == session.engine.num_decode_steps
    assert {s["ahead"] for s in steps} == {0, 1}
    own = {s["id"]: sec for s, sec in obs_spans.self_seconds(spans)}
    landed = 0
    dispatches = 0
    for outer in _spans(records, "engine_step"):
        names = [k["name"] for k in kids.get(outer["id"], [])]
        if "decode_step" not in names:
            continue
        landed += 1
        decode = kids[outer["id"]][names.index("decode_step")]
        emit = kids[outer["id"]][names.index("emit")]
        inner = kids[decode["id"]]
        inner_names = [k["name"] for k in inner]
        if decode["ahead"]:
            # The step was in flight when the call began: its span
            # opens before the admission, whose dispatches queue
            # behind it; the first tokens of those seats are waited
            # for once the step has landed and its emit is done.
            assert names[:2] == ["decode_step", "emit"]
            assert set(names[2:]) <= {"prefill"}
            assert inner_names == ["admit", "decode_prepare",
                                   "decode.dispatch", "decode.readback"]
            prepare = inner[1]
            assert own[outer["id"]] == pytest.approx(2.0)
            assert own[decode["id"]] == pytest.approx(3.0)
        else:
            # Nothing was in flight: the first tokens of the call's
            # seats are waited for inside the step it dispatches.
            assert names == ["admit", "decode_prepare", "decode_step", "emit"]
            assert inner_names[0] == "decode.dispatch"
            assert inner_names[-1] == "decode.readback"
            assert set(inner_names[1:-1]) <= {"prefill"}
            prepare = kids[outer["id"]][1]
            assert prepare["ts"] + prepare["dur"] == pytest.approx(
                decode["ts"]
            )
            # engine_step keeps its three boundary ticks, decode_step
            # its last one: no phase of a call goes unnamed.
            assert own[outer["id"]] == pytest.approx(3.0)
            assert own[decode["id"]] == pytest.approx(1.0)
        assert decode["ts"] + decode["dur"] == pytest.approx(emit["ts"])
        at = inner_names.index("decode.dispatch")
        dispatch, readback = inner[at], inner[-1]
        assert prepare["ts"] + prepare["dur"] == pytest.approx(dispatch["ts"])
        for a, b in zip(inner[at:], inner[at + 1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
        # decode.dispatch holds the call's dispatches, one
        # decode.address each: the landed step's own if none was in
        # flight, and the step ahead's.
        made = [k["name"] for k in kids.get(dispatch["id"], [])]
        assert set(made) <= {"decode.address"}
        assert len(made) >= 1 - decode["ahead"]
        dispatches += len(made)
    assert landed == len(steps)
    assert dispatches == len(steps)  # every step was dispatched once


# ---------------------------------------------------------------------------
# One program whichever way the tokens come
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("device", [None, 1], ids=["uncommitted", "put"])
def test_tokens_from_the_device_compile_nothing(model_and_params, device):
    """Parameters put on a device of their own commit the selection's
    output there, and a committed argument is another program to jit:
    the host's tokens are put the same way, so the first step ahead
    meets the program the warm-up compiled."""
    model, params = model_and_params
    if device is not None:
        params = jax.device_put(params, jax.devices()[device])
    session = _session(model, params)
    # A warm-up that never fills the engine. Twice: the pool comes back
    # from its first program committed where the parameters are, and
    # the seat is compiled again for that (as it was before this rule).
    session.serve(_requests(1, draw=10))
    session.serve(_requests(1, draw=10))
    assert _ahead() == 0
    with RecompileWatcher("full engine") as watch:
        session.serve(_requests(4, draw=11))
    assert _ahead() > 0
    assert watch.count == 0
