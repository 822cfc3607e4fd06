"""A seat reads nothing back (tpudl.serve.engine ``_seat``): the prefill,
the selection of the first token and the scatter are dispatched, the
token stays on the device for the slot's first decode step, and the host
reads it when it lands what the device holds, in dispatch order. So a
seat may be made behind a decode step that is still running.

The mathematics do not move: every request's tokens are what an engine
that reads every first token back at once gives it (``_eager``: the old
order), in every mode the engine has. On top of that: how a request ends
on its first token, the order and the transfers of the landings, a seat
behind a step call by call, who lands from outside ``step``, what a
failing prefill leaves behind, the one small program that hands the
token over, and the spans around it all on a fake clock.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl import obs
from tpudl.analysis.dispatch import RecompileWatcher
from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaConfig, LlamaForCausalLM
from tpudl.models.lora import extract_adapters
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession
from tpudl.serve import engine as engine_mod

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 16


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
    kw.setdefault("prompt_len", PROMPT_LEN)
    return ServeSession.from_model(model, params, num_slots=num_slots, **kw)


def _eager(session):
    """The reference: an engine whose seats find nothing in flight and
    have their first token on the host before anything else happens, as
    every seat had before the token stayed on the device."""
    engine = session.engine
    seat = engine._seat

    def seat_and_land(entry, slot):
        engine.land()
        seat(entry, slot)
        engine.land()

    engine._seat = seat_and_land
    return session


def _requests(n, draw=0, new=(3, 10), shared=0, vocab=CFG.vocab_size, **kw):
    rng = np.random.default_rng(draw)
    prefix = rng.integers(1, vocab, size=shared).tolist()
    return [
        Request(
            f"r{i}",
            prefix + rng.integers(
                1, vocab, size=int(rng.integers(2, PROMPT_LEN - shared + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(*new)), **kw,
        )
        for i in range(n)
    ]


def _alone(model, params, req):
    return np.asarray(generate(
        model, params, jnp.asarray(req.input_ids)[None, :],
        max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
    ))[0]


def _count(name):
    return obs_counters.registry().counter(name).value


def _spans(records, name=None):
    return [r for r in records if r.get("kind") == "span"
            and (name is None or r["name"] == name)]


def _inside(child, parent):
    return (child["ts"] >= parent["ts"]
            and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"])


# ---------------------------------------------------------------------------
# (a) the same tokens as an engine that reads every first token at once
# ---------------------------------------------------------------------------


#: Small on purpose: the adapter tests compile lora programs of their own.
LORA_CFG = dict(vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
                num_kv_heads=1, intermediate_size=64, max_seq_len=64,
                rope_theta=10_000.0, dtype=jnp.float32)


def _lora_model():
    model = LlamaForCausalLM(LlamaConfig(**LORA_CFG))
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _tiny_adapter(seed):
    lp = LlamaForCausalLM(LlamaConfig(**LORA_CFG, lora_rank=2)).init(
        jax.random.key(seed), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    rng = np.random.default_rng(seed)
    return {
        path: {
            "lora_a": np.asarray(f["lora_a"]),
            "lora_b": rng.normal(
                scale=0.05, size=np.shape(f["lora_b"])
            ).astype(np.float32),
        }
        for path, f in extract_adapters(lp).items()
    }


MODES = {
    "greedy": ({}, {}),
    "sampled": ({}, {"temperature": 0.8, "seed": 11}),
    "static": ({"continuous": False}, {}),
    "radix": ({"prefix_share": True}, {"shared": 8}),
    "speculator": ({"spec_k": 2}, {}),
}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_the_tokens_of_an_engine_that_lands_every_first_token_at_once(
    model_and_params, mode
):
    model, params = model_and_params
    session_kw, request_kw = MODES[mode]
    requests = _requests(6, draw=1, **request_kw)
    for req, new in zip(requests, (4, 9, 6, 11, 5, 7)):
        req.max_new_tokens = new  # no two slots end on one step
    got = _session(model, params, **session_kw).serve(requests)
    behind = _count("serve_prefills_behind_step")
    hits = _count("serve_prefix_hit_tokens")
    want = _eager(_session(model, params, **session_kw)).serve(requests)
    # The reference seats behind nothing; the engine itself does
    # wherever it runs ahead (a speculating one never does, a static
    # one refills only when every slot is empty).
    assert _count("serve_prefills_behind_step") == behind
    assert (behind > 0) == (mode in ("greedy", "sampled", "radix"))
    if mode == "radix":
        assert hits > 0  # the suffix ran through ``chunk_prefill_call``
    for req in requests:
        rid = req.request_id
        assert got[rid].tokens == want[rid].tokens, rid
        assert got[rid].finish_reason == want[rid].finish_reason == "length"
        if "temperature" not in request_kw:
            np.testing.assert_array_equal(
                got[rid].tokens, _alone(model, params, req), err_msg=rid
            )


def test_an_adapter_tenants_tokens_are_the_eager_engines():
    model, params = _lora_model()
    adapters = {"t0": _tiny_adapter(1), "t1": _tiny_adapter(2)}
    requests = _requests(6, draw=2, vocab=100)
    for i, req in enumerate(requests):
        req.tenant = (None, "t0", "t1")[i % 3]

    def session():
        return ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=2,
            adapters=adapters,
        )

    served = session()
    got = served.serve(requests)
    assert _count("serve_prefills_behind_step") > 0
    # Every pin went back with its slot.
    assert served.engine.adapter_pool.stats()["leased"] == 0
    want = _eager(session()).serve(requests)
    for req in requests:
        rid = req.request_id
        assert got[rid].tokens == want[rid].tokens, rid


# ---------------------------------------------------------------------------
# (b) a request that ends on its first token, under a dispatched step
# ---------------------------------------------------------------------------


def test_an_eos_on_the_first_token_wastes_a_row_that_reaches_nobody(
    model_and_params
):
    model, params = model_and_params
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, CFG.vocab_size, size=6).tolist()
               for _ in range(4)]
    first = int(_alone(
        model, params, Request("p", prompts[2], max_new_tokens=1)
    )[0])
    requests = [
        Request("A", prompts[0], max_new_tokens=3),
        Request("B", prompts[1], max_new_tokens=12),
        # Takes A's slot behind a step in flight and ends at once.
        Request("C", prompts[2], max_new_tokens=8, eos_id=first),
        Request("D", prompts[3], max_new_tokens=5),
    ]
    session = _session(model, params)
    engine = session.engine
    streamed = {r.request_id: [] for r in requests}
    engine.on_token = lambda rid, tok: streamed[rid].append(tok)
    for req in requests:
        session.submit(req)
    wasted = None
    while engine.step():
        if wasted is None and "C" in engine.results:
            # The call that seated C dispatched the next step with C's
            # row among its rows, from the token on the device, before
            # anybody could know that the token ends the request.
            step = engine._in_flight
            assert step is not None and step.ahead
            (wasted,) = [s for s in step.rows
                         if s is not None and s.request.request_id == "C"]
    assert wasted is not None
    results = engine.results
    assert results["C"].finish_reason == "eos"
    assert results["C"].tokens == wasted.tokens == [first]
    for req in requests:
        rid = req.request_id
        assert streamed[rid] == results[rid].tokens, rid
        want = _alone(model, params, req)
        np.testing.assert_array_equal(
            results[rid].tokens, want[: len(results[rid].tokens)],
            err_msg=rid,
        )
    assert len(results["D"].tokens) == 5  # in C's slot, after it
    cache = engine.cache
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)


def test_one_token_asked_for_gets_no_decode_row(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    engine, cache = session.engine, session.engine.cache
    one = Request("one", [5, 6, 7], max_new_tokens=1)
    session.submit(one)
    advanced = []
    advance = cache.advance

    def spy(slots, steps=1):
        advanced.append(list(slots))
        advance(slots, steps)

    cache.advance = spy
    # Alone: its seat is its whole life, and no decode step is made.
    assert engine.step() is True
    assert engine.results["one"].finish_reason == "length"
    assert engine.results["one"].tokens == _alone(model, params, one).tolist()
    assert advanced == [] and engine.num_decode_steps == 0
    assert engine.step() is False
    # Behind a step in flight: the step ahead leaves its row idle.
    long = [Request(f"long{i}", [8, 9, 10, 11 + i], max_new_tokens=3 + 6 * i)
            for i in range(2)]
    again = Request("again", [5, 6, 7], max_new_tokens=1)
    for req in (*long, again):
        session.submit(req)
    while engine.step():
        pass
    assert engine.results["again"].tokens == engine.results["one"].tokens
    # long0 (slot 0) ends after three tokens, two decode rows; ``again``
    # takes its slot behind the step in flight and no dispatch computes
    # slot 0 any more.
    assert advanced[:2] == [[0, 1], [0, 1]]
    assert all(rows == [1] for rows in advanced[2:])
    assert _count("serve_prefills_behind_step") == 1
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)


# ---------------------------------------------------------------------------
# (c) the landings: in order, each in its own transfer
# ---------------------------------------------------------------------------


def test_three_seats_of_one_call_land_in_order_each_in_its_own_transfer(
    model_and_params, monkeypatch
):
    model, params = model_and_params
    session = _session(model, params, num_slots=3)
    engine = session.engine
    requests = _requests(3, draw=5, new=(4, 6))
    for req in requests:
        session.submit(req)
    events = []
    engine.on_token = lambda rid, tok: events.append(("token", rid))
    device_get = jax.device_get

    def spy(tree):
        leaves = jax.tree.leaves(tree)
        events.append(("get", tuple(leaf.shape for leaf in leaves)))
        return device_get(tree)

    monkeypatch.setattr(engine_mod.jax, "device_get", spy)
    engine.step()
    rids = [r.request_id for r in requests]
    # Three transfers of one token each, every one followed by its
    # token's emission, then the decode step's transfer and its tokens:
    # nobody sees a second token before a first.
    assert events == [
        ("get", ((1,),)), ("token", rids[0]),
        ("get", ((1,),)), ("token", rids[1]),
        ("get", ((1,),)), ("token", rids[2]),
        ("get", ((3,),)),
        ("token", rids[0]), ("token", rids[1]), ("token", rids[2]),
    ]
    assert _count("serve_prefills_behind_step") == 0
    while engine.step():
        pass
    seen = {}
    for kind, rid in events:
        if kind == "token":
            seen[rid] = seen.get(rid, 0) + 1
    assert seen == {r.request_id: r.max_new_tokens for r in requests}


def test_a_seat_behind_a_step_in_flight_call_by_call(model_and_params,
                                                     tmp_path):
    """What each call dispatches and what it lands, around one seat."""
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    engine = session.engine
    ends = Request("ends", [5, 6, 7], max_new_tokens=3)
    stays = Request("stays", [8, 9, 10, 11], max_new_tokens=12)
    comes = Request("comes", [12, 13], max_new_tokens=6)
    for req in (ends, stays, comes):
        session.submit(req)
    tokens = {}
    engine.on_token = lambda rid, tok: tokens.setdefault(rid, []).append(tok)

    def held():
        return ["first" if isinstance(u, engine_mod._First) else
                ("ahead" if u.ahead else "step") for u in engine._unread]

    # Call 1: both seats, their step from the host's zeros and the two
    # tokens on the device, the step ahead of it; the firsts and the
    # first step land.
    engine.step()
    assert held() == ["ahead"]
    assert {rid: len(t) for rid, t in tokens.items()} == {
        "ends": 2, "stays": 2
    }
    # Call 2: lands the step ahead, which ends ``ends`` by length; no
    # row is left for it in the step after, which stays in flight.
    engine.step()
    assert "ends" in engine.results and held() == ["ahead"]
    assert engine._slots[0] is None
    assert [s and s.request.request_id for s in engine._in_flight.rows] == [
        None, "stays"
    ]
    assert _count("serve_prefills_behind_step") == 0
    # Call 3: a step is in flight and a slot is free. ``comes`` is
    # seated BEHIND it, the next step is dispatched ahead with its
    # first token taken from the device, the step in flight lands, and
    # only then is the first token read.
    order = []
    engine.on_token = lambda rid, tok: order.append(rid)
    engine.step()
    assert _count("serve_prefills_behind_step") == 1
    assert order == ["stays", "comes"]
    assert held() == ["ahead"]
    assert [s.request.request_id for s in engine._in_flight.rows] == [
        "comes", "stays"
    ]
    assert len(engine._slots[0].tokens) == 1
    while engine.step():
        pass
    records = rec.records
    obs.disable()
    for req in (ends, stays, comes):
        np.testing.assert_array_equal(
            engine.results[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )
    steps = _spans(records, "decode_step")
    assert [s["ahead"] for s in steps[:4]] == [0, 1, 1, 1]
    assert [s["rids"] for s in steps[:4]] == [
        ["ends", "stays"], ["ends", "stays"], ["stays"], ["comes", "stays"]
    ]
    waits = {w["request_id"]: w for w in _spans(records, "prefill")}
    assert [waits[r]["behind"] for r in ("ends", "stays", "comes")] == [
        0, 0, 1
    ]
    assert _count("serve_decode_steps_ahead") == sum(
        s["ahead"] for s in steps
    )


def test_a_seat_with_its_token_on_the_host_holds_the_step_ahead_back(
    model_and_params
):
    """A migrated request seated behind a step in flight has no entry
    on the device: the next step waits for the landing and takes the
    host's tokens, as it did before seats stayed on the device."""
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
    dst.engine.step()  # lands a's last token; a step stays in flight
    assert dst.engine._in_flight is not None
    assert a.request_id in dst.engine.results
    dst.engine.migrate_inbox.append(
        engine_mod._Migrated(moved.request_id, payload)
    )
    dst.engine.step()  # seats it behind the step, dispatches nothing
    assert dst.engine._in_flight is None
    assert any(s is not None and s.request.request_id == moved.request_id
               for s in dst.engine._slots)
    got = dst.engine.run_until_drained()
    for req in (moved, a, b):
        np.testing.assert_array_equal(
            got[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )


# ---------------------------------------------------------------------------
# (d) whoever touches a slot from outside lands the first tokens too
# ---------------------------------------------------------------------------


def _seated_unread(model, params):
    """A session one call into two long requests, with a third seated
    by hand so that its first token is still on the device."""
    session = _session(model, params, num_slots=3)
    engine = session.engine
    a, b, c = _requests(3, draw=7, new=(10, 14))
    session.submit(a)
    session.submit(b)
    engine.step()
    session.submit(c)
    engine._fill_slots()  # admission alone: the seat, nothing landed
    slot = engine._slots[2]
    assert slot.request.request_id == c.request_id
    assert slot.tokens == [] and slot.first is not None
    assert [type(u).__name__ for u in engine._unread] == ["_First"]
    return session, (a, b, c)


def test_a_pending_first_is_busy_and_health_counts_it(model_and_params):
    model, params = model_and_params
    session, _ = _seated_unread(model, params)
    engine = session.engine
    assert engine._active()
    assert engine.health()["slots_busy"] == 3
    engine.land()
    assert engine._slots[2].first is None
    assert len(engine._slots[2].tokens) == 1
    assert not engine._unread


def test_export_lands_a_pending_first_into_the_payload(model_and_params):
    from tpudl.serve.cache import parse_migration

    model, params = model_and_params
    session, (a, b, c) = _seated_unread(model, params)
    dst = _session(model, params, num_slots=3)
    payload = session.engine.export_request(c.request_id)
    assert not session.engine._unread
    want = _alone(model, params, c)
    assert parse_migration(payload)["tokens"] == want[:1].tolist()
    dst.engine.install_migrated(payload)
    got = {**session.engine.run_until_drained(),
           **dst.engine.run_until_drained()}
    for req in (a, b, c):
        np.testing.assert_array_equal(
            got[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )


def test_install_lands_a_pending_first_before_it_seats(model_and_params):
    model, params = model_and_params
    src = _session(model, params)
    moved = _requests(1, draw=8, new=(10, 14))[0]
    moved.request_id = "moved"
    src.submit(moved)
    src.engine.step()
    payload = src.engine.export_request("moved")
    session, (a, b, c) = _seated_unread(model, params)
    c_slot = session.engine._slots[2]
    with pytest.raises(RuntimeError, match="no free slot"):
        session.engine.install_migrated(payload)
    # The refused install still landed what the device held.
    assert c_slot.first is None and len(c_slot.tokens) == 1
    assert not session.engine._unread


def test_a_replicas_migration_pull_lands_a_pending_first(model_and_params):
    from tpudl.serve.cache import parse_migration
    from tpudl.serve.router import Replica

    model, params = model_and_params
    session, (a, b, c) = _seated_unread(model, params)
    replica = Replica("leaving", session)
    box = {
        "done": threading.Event(), "lock": threading.Lock(),
        "claimed": False, "abandoned": False,
        "skip": {}, "payloads": {}, "requests": {},
    }
    replica._migrate_out(box)
    assert not session.engine._unread
    assert sorted(box["payloads"]) == sorted(
        r.request_id for r in (a, b, c)
    )
    meta = parse_migration(box["payloads"][c.request_id])
    assert meta["tokens"] == _alone(model, params, c)[:1].tolist()


def test_a_drain_lands_every_first_and_leaves_nothing_unread(
    model_and_params
):
    model, params = model_and_params
    session, requests = _seated_unread(model, params)
    got = session.engine.run_until_drained()
    assert not session.engine._unread
    assert session.engine.step() is False
    for req in requests:
        np.testing.assert_array_equal(
            got[req.request_id].tokens, _alone(model, params, req),
            err_msg=req.request_id,
        )


# ---------------------------------------------------------------------------
# (e) a prefill that fails surfaces at its landing
# ---------------------------------------------------------------------------


class _Broken:
    """Stands for a first token whose prefill failed on the device: the
    transfer that reads it raises."""

    shape = (1,)


@pytest.mark.parametrize("mode", ["plain", "radix", "adapter"])
def test_a_failing_prefill_frees_its_slot_lease_and_pin_at_the_landing(
    model_and_params, monkeypatch, mode
):
    if mode == "adapter":
        model, params = _lora_model()
        session = ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=2,
            adapters={"t0": _tiny_adapter(1)},
        )
        request_kw = {"tenant": "t0", "vocab": 100}
    else:
        model, params = model_and_params
        session = _session(model, params, prefix_share=(mode == "radix"))
        request_kw = {}
    engine, cache = session.engine, session.engine.cache
    good, bad = _requests(2, draw=9, new=(4, 6), **request_kw)
    session.submit(good)
    session.submit(bad)
    engine._fill_slots()
    assert [u.state.request.request_id for u in engine._unread] == [
        good.request_id, bad.request_id
    ]
    engine._unread[1].sel = _Broken()
    device_get = jax.device_get

    def failing(tree):
        if any(isinstance(leaf, _Broken) for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, _Broken)
        )):
            raise RuntimeError("the prefill failed on the device")
        return device_get(tree)

    monkeypatch.setattr(engine_mod.jax, "device_get", failing)
    with pytest.raises(RuntimeError, match="failed on the device"):
        engine.land()
    # The good neighbour landed; the failed seat left nothing behind.
    assert len(engine._slots[0].tokens) == 1
    assert engine._slots[1] is None and not engine._unread
    assert cache.pages_of(1) == 0
    assert 1 not in cache._leases
    if mode == "adapter":
        # The good seat's pin alone is left.
        assert engine.adapter_pool._resident["t0"].refcount == 1
    got = engine.run_until_drained()
    assert len(got[good.request_id].tokens) == good.max_new_tokens
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)
    if mode == "adapter":
        assert engine.adapter_pool.stats()["leased"] == 0


# ---------------------------------------------------------------------------
# (f) one small program hands the token over, whichever vector it gets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("placed", ["uncommitted", "put", "mesh"])
def test_the_merge_program_compiles_once_whichever_base_it_gets(
    model_and_params, placed
):
    """The host's tokens and a step's selection are one program to jit,
    committed or not, and the decode program meets one kind of vector:
    a full engine that seats behind its steps compiles nothing after a
    warm-up that never filled it."""
    model, params = model_and_params
    if placed == "mesh":
        from tpudl.fleet import build_mesh_session

        session = build_mesh_session(
            model, params, PROMPT_LEN, devices=jax.devices()[:2], tp=2,
            num_slots=2, page_size=4,
        )
    else:
        if placed == "put":
            params = jax.device_put(params, jax.devices()[1])
        session = _session(model, params)
    engine = session.engine
    merge = engine_mod._set_first
    # A warm-up that never fills the engine, twice (the pool comes back
    # from its first program committed where the parameters are).
    session.serve(_requests(1, draw=10))
    session.serve(_requests(1, draw=10))
    assert _count("serve_prefills_behind_step") == 0
    # Both vectors, dry, as ``compile_prefill_lengths`` runs them.
    first = np.int32([7])
    if engine._token_sharding is not None:
        first = jax.device_put(first, engine._token_sharding)
    else:
        first = jnp.asarray(first)
    before = merge._cache_size()
    tokens = engine._with_firsts(np.zeros(2, np.int32), [(0, first)])
    tokens = engine._with_firsts(tokens, [(1, first)], from_device=True)
    assert np.asarray(tokens).tolist() == [7, 7]
    if engine._token_sharding is not None:
        assert tokens.sharding.is_equivalent_to(engine._token_sharding, 1)
    with RecompileWatcher("seats behind steps") as watch:
        session.serve(_requests(5, draw=11))
    assert _count("serve_prefills_behind_step") > 0
    assert watch.count == 0
    # An argument that is numpy's is another entry of the call cache
    # where nothing is committed, never another compile (the decode
    # program's tokens likewise); committed, both ways are one entry.
    assert merge._cache_size() - before <= 1 + (placed == "uncommitted")
    assert engine.decode_call._cache_size() <= 1 + (placed == "uncommitted")


def test_the_dry_run_beside_the_seat_makes_the_merge_program(
    model_and_params
):
    """``compile_prefill_lengths`` leaves the program compiled over both
    vectors before any request is served."""
    model, params = model_and_params
    params = jax.device_put(params, jax.devices()[2])
    session = _session(model, params, num_slots=5)  # a shape of its own
    engine = session.engine
    engine.compile_prefill_lengths((PROMPT_LEN,))
    first = jax.device_put(np.int32([7]), jax.devices()[2])
    with RecompileWatcher("after the dry run") as watch:
        tokens = engine._with_firsts(np.zeros(5, np.int32), [(3, first)])
        tokens = engine._with_firsts(tokens, [(1, first)], from_device=True)
    assert watch.count == 0
    assert np.asarray(tokens).tolist() == [0, 7, 0, 7, 0]
    assert tokens.sharding == engine._token_sharding


# ---------------------------------------------------------------------------
# (g) the spans on a fake clock
# ---------------------------------------------------------------------------


def test_on_the_fake_clock_a_call_that_lands_a_step_opens_its_span_first(
    model_and_params, tmp_path
):
    """What the benchmark's readers need. A call that lands a step
    already in flight opens ``decode_step`` BEFORE its admission, so
    that what is left of the step on the device lies in its window, and
    reads the first tokens of its seats AFTER the span has closed, so
    that no prefill's device time does. Where nothing was in flight the
    first tokens are read between the dispatch and the read-back."""
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    session.engine.clock = FakeClock()
    session.serve(_requests(6, draw=13))
    records = rec.records
    obs.disable()
    spans = _spans(records)
    by_id = {s["id"]: s for s in spans}
    kids = {}
    for s in spans:
        kids.setdefault(s["parent"], []).append(s)
    for group in kids.values():
        group.sort(key=lambda s: s["ts"])

    def end(s):
        return s["ts"] + s["dur"]

    behind = 0
    for wait in _spans(records, "prefill"):
        (readback,) = kids[wait["id"]]
        assert readback["name"] == "prefill.readback"
        assert readback["ts"] == wait["ts"]
        for attr in ("rows", "tokens", "prefix_hit_tokens", "request_id",
                     "slot", "behind", "queue_wait_s", "since_pop_s"):
            assert attr in wait, attr
        parent = by_id[wait["parent"]]
        (dispatch,) = [
            d for d in _spans(records, "prefill.dispatch")
            if by_id[d["parent"]]["name"] == "admit"
            and any(s["name"] == "seat"
                    and s["request_id"] == wait["request_id"]
                    for s in kids[d["parent"]])
            and d["ts"] < wait["ts"]
        ][-1:]
        assert end(dispatch) <= wait["ts"]
        if wait["behind"]:
            behind += 1
            # Seated behind a step: that step's ``decode_step`` held
            # the admission, and was closed (its emit too) before the
            # wait began.
            assert parent["name"] == "engine_step"
            names = [k["name"] for k in kids[parent["id"]]]
            assert names[:2] == ["decode_step", "emit"]
            decode, emit = kids[parent["id"]][:2]
            assert decode["ahead"] == 1
            admit = kids[decode["id"]][0]
            assert admit["name"] == "admit"
            assert admit["ts"] > decode["ts"]
            assert _inside(dispatch, admit)
            assert end(decode) == emit["ts"] and end(emit) <= wait["ts"]
        else:
            assert parent["name"] == "decode_step"
            inner = [k["name"] for k in kids[parent["id"]]]
            assert inner[0] == "decode.dispatch"
            assert inner[-1] == "decode.readback"
            assert set(inner[1:-1]) == {"prefill"}
    assert 0 < behind == _count("serve_prefills_behind_step")
    assert behind < len(_spans(records, "prefill"))
    # No ``decode_step`` that was opened around a step in flight holds
    # a wait for a first token.
    for decode in _spans(records, "decode_step"):
        if decode["ahead"]:
            assert "prefill" not in [k["name"] for k in kids[decode["id"]]]
    # The category sums count each phase once: a wait is the only
    # ``serve_prefill`` top, an ``admit`` under a ``decode_step`` is
    # still inside its ``engine_step``.
    tops = obs_spans.without_same_category_children(spans)
    assert sorted(s["name"] for s in tops if s["cat"] == "serve_prefill") == (
        ["prefill"] * 6
    )
    assert {s["name"] for s in tops if s["cat"] == "serve_engine"} == {
        "engine_step"
    }

