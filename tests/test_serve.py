"""Continuous-batching serving engine (tpudl.serve).

The correctness bar mirrors test_generate's: every request served
through the slot engine — whatever its neighbors, seat time or
refills — must produce token-for-token what ``generate()``
produces for that request alone, through both the live model and the
deserialized StableHLO artifact pair. On top of that: admission
rejects the unservable, deadlines shed the late, and continuous
batching measurably beats run-to-completion static batching on ragged
workloads (asserted on the DETERMINISTIC decode-step count; a time is
the benchmark's to measure, on the chip).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.serve import (
    AdmissionQueue,
    PagedKVCache,
    Request,
    ServeSession,
    assert_serving_parity,
)

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8
SLOTS = 4


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _session(model, params, **kw):
    kw.setdefault("prompt_len", PROMPT_LEN)
    kw.setdefault("num_slots", SLOTS)
    return ServeSession.from_model(model, params, **kw)


def _ragged_requests(n, seed=0, max_new_lo=4, max_new_hi=20, **kw):
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


# ---------------------------------------------------------------------------
# Tier-1 smoke: the satellite-specified config (tiny Llama, 4 slots,
# 8 requests) through the whole stack.
# ---------------------------------------------------------------------------


def test_smoke_continuous_serving(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    requests = _ragged_requests(8, seed=1)
    assert_serving_parity(session, model, params, requests)
    assert session.engine.num_prefills == 8  # every request was seated
    assert session.engine.num_decode_steps > 0


def test_results_carry_timing_and_reasons(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    results = session.serve(_ragged_requests(6, seed=2))
    assert len(results) == 6
    for res in results.values():
        assert res.finish_reason == "length"  # no eos configured
        assert res.ttft_s is not None and res.ttft_s >= 0
        # Queue wait ends at seating; TTFT adds the prefill on top.
        assert res.queue_wait_s is not None
        assert res.queue_wait_s <= res.ttft_s
        assert len(res.tokens) > 1 and res.tpot_s is not None


# ---------------------------------------------------------------------------
# Edge cases the ISSUE names.
# ---------------------------------------------------------------------------


def test_refill_on_exact_step_neighbor_emits_eos(model_and_params):
    """The moment slot A emits EOS, the waiting request is seated into
    it — while slot B keeps decoding mid-stream. Neither B nor the
    newcomer may be perturbed (bit-exact vs. each alone)."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    prompts = [
        rng.integers(1, CFG.vocab_size, size=5).tolist() for _ in range(3)
    ]
    # Probe greedily to find an eos that request A emits mid-stream.
    probe = generate(
        model, params, jnp.asarray(prompts[0])[None, :], max_new_tokens=20
    )
    eos = int(probe[0, 4])  # A finishes the step it produces token 5
    requests = [
        Request("A", prompts[0], max_new_tokens=20, eos_id=eos),
        Request("B", prompts[1], max_new_tokens=24),
        Request("C", prompts[2], max_new_tokens=8),  # seated on A's eos
    ]
    session = _session(model, params, num_slots=2)
    results = session.serve(requests)
    assert results["A"].finish_reason == "eos"
    assert results["A"].tokens[-1] == eos and len(results["A"].tokens) <= 20
    # C was refilled mid-stream: the engine never drained between A and
    # C (a drain would show as an idle gap; prefills == 3 with decode
    # steps bounded by B's runtime shows overlap).
    assert session.engine.num_prefills == 3
    assert session.engine.num_decode_steps < (20 + 24 + 8 - 3)
    for req in requests:
        want = np.asarray(
            generate(
                model, params, jnp.asarray(req.input_ids)[None, :],
                max_new_tokens=req.max_new_tokens, eos_id=req.eos_id,
            )
        )[0]
        got = np.asarray(results[req.request_id].tokens)
        np.testing.assert_array_equal(
            got, want[: got.shape[0]], err_msg=req.request_id
        )


def test_queue_timeout_shedding(model_and_params):
    """A request whose deadline passes before it is seated is shed with
    finish_reason=shed_timeout; running requests are never aborted."""
    model, params = model_and_params
    t = [0.0]
    session = _session(model, params, num_slots=2, clock=lambda: t[0])
    session.submit(Request("late", [1, 2, 3], max_new_tokens=4,
                           deadline_s=1.0))
    t[0] = 5.0  # deadline passed while queued
    session.submit(Request("ok", [1, 2, 3], max_new_tokens=4))
    results = session.collect()
    assert results["late"].finish_reason == "shed_timeout"
    assert results["late"].tokens == []
    assert results["ok"].finish_reason == "length"


def test_admission_rejects(model_and_params):
    model, params = model_and_params
    session = _session(model, params, num_slots=2)
    with pytest.raises(ValueError, match="prompt window"):
        session.submit(
            Request("long", list(range(1, PROMPT_LEN + 2)), max_new_tokens=2)
        )
    with pytest.raises(ValueError, match="max_seq_len"):
        session.submit(
            Request("huge", [1, 2], max_new_tokens=CFG.max_seq_len)
        )
    with pytest.raises(ValueError, match="at least one token"):
        session.submit(Request("empty", [], max_new_tokens=2))
    with pytest.raises(ValueError, match="max_new_tokens"):
        session.submit(Request("zero", [1], max_new_tokens=0))
    with pytest.raises(ValueError, match="uint32"):
        # Seeds ride as uint32 in the engine; out-of-range must fail at
        # admission, not mid-serving (which would strand the batch).
        session.submit(Request("neg", [1], max_new_tokens=2, seed=-1))
    session.submit(Request("dup", [1, 2], max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate"):
        session.submit(Request("dup", [1, 2], max_new_tokens=2))
    results = session.collect()
    assert results["dup"].ok


def test_queue_capacity_sheds(model_and_params):
    model, params = model_and_params
    session = _session(model, params, num_slots=2, queue_capacity=2)
    for i in range(4):
        session.submit(Request(f"q{i}", [1, 2], max_new_tokens=3))
    results = session.collect()
    reasons = sorted(r.finish_reason for r in results.values())
    assert reasons == ["length", "length", "shed_capacity", "shed_capacity"]


def test_artifact_vs_live_parity(model_and_params, tmp_path):
    """A ServeSession fed the StableHLO artifact pair produces
    token-for-token the same outputs as the live model — and as
    generate() — for the same seeds, through files on disk."""
    from tpudl.export.decode import export_serving_decoder

    model, params = model_and_params
    prefix = str(tmp_path / "serve_tiny")
    export_serving_decoder(
        model, params, num_slots=SLOTS, prompt_len=PROMPT_LEN,
        path_prefix=prefix,
    )
    art = ServeSession.from_artifacts(
        f"{prefix}.prefill.stablehlo", f"{prefix}.decode.stablehlo", params
    )
    assert (art.num_slots, art.prompt_len, art.max_seq_len) == (
        SLOTS, PROMPT_LEN, CFG.max_seq_len,
    )
    # Mixed greedy + sampled workload, same seeds through both backends.
    requests = _ragged_requests(8, seed=4)
    for i, req in enumerate(requests):
        if i % 3 == 0:
            req.temperature = 0.8
            req.seed = 100 + i
    live = _session(model, params)
    r_live = live.serve([Request(**r.__dict__) for r in requests])
    r_art = art.serve([Request(**r.__dict__) for r in requests])
    for rid in r_live:
        assert r_live[rid].tokens == r_art[rid].tokens, rid
    # Greedy requests additionally match live generate() run alone.
    for req in requests:
        if req.temperature:
            continue
        want = np.asarray(
            generate(
                model, params, jnp.asarray(req.input_ids)[None, :],
                max_new_tokens=req.max_new_tokens,
            )
        )[0]
        np.testing.assert_array_equal(
            np.asarray(r_live[req.request_id].tokens),
            want[: len(r_live[req.request_id].tokens)],
        )


def test_default_session_serves_from_the_paged_pool(model_and_params):
    """No flag, no environment: the one serving cache is the page pool
    at its defaults (page size 16, every slot can hold max_seq_len),
    and health() reports the pool's facts."""
    model, params = model_and_params
    session = _session(model, params)
    cache = session.engine.cache
    assert isinstance(cache, PagedKVCache)
    assert cache.page_size == 16 and not cache.quantized
    assert cache.num_pages == SLOTS * -(-CFG.max_seq_len // 16) + 1
    health = session.engine.health()
    assert health["free_pages"] == cache.num_pages - 1
    assert health["page_size"] == 16
    assert "write_index" not in health and "paged" not in health


@pytest.mark.parametrize("paged", [None, True])
def test_from_model_paged_keyword_selects_nothing(model_and_params, paged):
    """``paged`` survives as a keyword the benchmark's configurations
    pass: None and True build the default session, False names the
    cache that was removed."""
    model, params = model_and_params
    default = _session(model, params).engine.cache
    cache = _session(model, params, paged=paged).engine.cache
    assert type(cache) is type(default)
    assert (cache.page_size, cache.num_pages, cache.quantized) == (
        default.page_size, default.num_pages, default.quantized,
    )
    with pytest.raises(ValueError, match="dense slot cache was removed"):
        _session(model, params, paged=False)


def test_sampling_is_batch_composition_independent(model_and_params):
    """Token t of a sampled request draws from fold_in(key(seed), t):
    the same request yields the same tokens served alone or in a full
    ragged batch — reproducibility generate()'s shared rng stream
    cannot offer."""
    model, params = model_and_params
    req = Request("s", [7, 8, 9], max_new_tokens=10, temperature=1.0, seed=42)
    alone = _session(model, params).serve([Request(**req.__dict__)])
    crowd_reqs = [Request(**req.__dict__)] + _ragged_requests(6, seed=6)
    crowd = _session(model, params).serve(crowd_reqs)
    assert alone["s"].tokens == crowd["s"].tokens
    # And a different seed actually changes the stream.
    other = Request("s", [7, 8, 9], max_new_tokens=10, temperature=1.0,
                    seed=43)
    r_other = _session(model, params).serve([other])
    assert r_other["s"].tokens != alone["s"].tokens


def test_continuous_beats_static_on_decode_steps(model_and_params):
    """The acceptance ratio on its deterministic basis: equal slots,
    ragged lengths, the SAME engine with mid-stream refill on vs off —
    continuous must finish the workload in >= 1.3x fewer decode steps."""
    model, params = model_and_params
    lengths = [40, 6, 6, 6, 40, 6, 6, 6]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 500, size=5).tolist() for _ in lengths]

    def reqs():
        return [
            Request(f"r{i}", prompts[i], max_new_tokens=n)
            for i, n in enumerate(lengths)
        ]

    cont = _session(model, params)
    r_cont = cont.serve(reqs())
    stat = _session(model, params, continuous=False)
    r_stat = stat.serve(reqs())
    assert all(r.ok for r in r_cont.values())
    # Identical tokens either way — batching policy is invisible to
    # outputs, it only moves time.
    for rid in r_cont:
        assert r_cont[rid].tokens == r_stat[rid].tokens, rid
    ratio = stat.engine.num_decode_steps / cont.engine.num_decode_steps
    assert ratio >= 1.3, (
        f"continuous batching only {ratio:.2f}x fewer decode steps than "
        f"static (cont={cont.engine.num_decode_steps}, "
        f"stat={stat.engine.num_decode_steps})"
    )


def test_serve_obs_flow(model_and_params):
    """Engine metrics land in the obs registry: busy gauge, TTFT/TPOT
    histograms, completion counters, cache byte accounting."""
    from tpudl.obs import registry

    model, params = model_and_params
    reg = registry()
    completed0 = reg.counter("serve_requests_completed").value
    prefills0 = reg.counter("serve_prefills").value
    ttft0 = reg.histogram("serve_ttft_ms").count
    session = _session(model, params, num_slots=2)
    session.serve(_ragged_requests(4, seed=8))
    assert reg.counter("serve_requests_completed").value == completed0 + 4
    assert reg.counter("serve_prefills").value == prefills0 + 4
    assert reg.histogram("serve_ttft_ms").count == ttft0 + 4
    assert reg.gauge("serve_slots_busy").value == 0  # drained
    assert reg.gauge("serve_cache_bytes").value > 0


# ---------------------------------------------------------------------------
# Queue and cache units (host-only, no model).
# ---------------------------------------------------------------------------


def test_admission_queue_priority_fifo_and_fit():
    t = [0.0]
    q = AdmissionQueue(capacity=8, clock=lambda: t[0])

    class R:
        def __init__(self, name, size=1):
            self.name, self.size = name, size

    assert q.push(R("b0"), priority=1)
    assert q.push(R("a0"), priority=0)
    assert q.push(R("a1"), priority=0)
    assert q.push(R("big", size=99), priority=0)
    # Priority first, FIFO within priority, fit-filter skips without
    # reordering what it skips.
    entry, shed = q.pop(fit=lambda r: r.size < 10)
    assert entry.request.name == "a0" and not shed
    entry, _ = q.pop(fit=lambda r: r.size < 10)
    assert entry.request.name == "a1"
    entry, _ = q.pop(fit=lambda r: r.size < 10)
    assert entry.request.name == "b0"  # "big" skipped, still queued
    assert len(q) == 1
    entry, _ = q.pop()
    assert entry.request.name == "big"


def test_admission_queue_deadlines_and_capacity():
    t = [0.0]
    q = AdmissionQueue(capacity=2, clock=lambda: t[0])
    assert q.push("x", deadline_s=1.0)
    assert q.push("y")
    assert not q.push("overflow")  # bounded
    t[0] = 2.0
    entry, shed = q.pop()
    assert entry.request == "y"  # x expired on the way
    assert [e.request for e in shed] == ["x"]
    q.push("z", deadline_s=0.5)
    t[0] = 9.0
    assert [e.request for e in q.drain_expired()] == ["z"]
    assert len(q) == 0
    with pytest.raises(ValueError, match="capacity"):
        AdmissionQueue(capacity=0)


def test_engine_refuses_a_cache_that_is_not_the_pool(model_and_params):
    """The engine drives the pool through PagedKVCache's methods; a
    bare cache pytree (what the removed dense path held) is a
    TypeError at construction, not an AttributeError mid-step."""
    from tpudl.models.generate import prefill_fn
    from tpudl.serve import Engine

    model, params = model_and_params
    ids = jax.ShapeDtypeStruct((SLOTS, PROMPT_LEN), jnp.int32)
    _, template = jax.eval_shape(prefill_fn(model), params, ids, ids)
    with pytest.raises(TypeError, match="PagedKVCache"):
        Engine(None, None, params, template, AdmissionQueue(4), PROMPT_LEN)
    with pytest.raises(TypeError, match="PagedKVCache"):
        ServeSession(None, None, params, template, PROMPT_LEN)


def test_admission_queue_starvation_promotion():
    """The aged-FIFO guard: a low-priority entry that has waited past
    promote_after_s is served next regardless of the high-priority
    stream still arriving — bounded wait instead of starving forever."""
    t = [0.0]
    q = AdmissionQueue(capacity=8, clock=lambda: t[0], promote_after_s=5.0)
    assert q.push("low", priority=9)
    assert q.push("hi0", priority=0)
    entry, _ = q.pop()
    assert entry.request == "hi0"  # not aged yet: priority order holds
    t[0] = 6.0  # "low" has now waited past the promotion bound
    q.push("hi1", priority=0)
    entry, _ = q.pop()
    assert entry.request == "low"  # aged FIFO promotion
    entry, _ = q.pop()
    assert entry.request == "hi1"
    assert len(q) == 0

    # An aged head that fails the fit filter doesn't block normal pops.
    class R:
        def __init__(self, name, big=False):
            self.name, self.big = name, big

    q.push(R("big-old", big=True), priority=9)
    t[0] += 6.0
    q.push(R("small"), priority=0)
    entry, _ = q.pop(fit=lambda r: not r.big)
    assert entry.request.name == "small"

    # promote_after_s=None disables promotion entirely.
    t2 = [0.0]
    q2 = AdmissionQueue(capacity=8, clock=lambda: t2[0],
                        promote_after_s=None)
    q2.push("low", priority=9)
    t2[0] = 1e9
    q2.push("hi", priority=0)
    entry, _ = q2.pop()
    assert entry.request == "hi"
    with pytest.raises(ValueError, match="promote_after_s"):
        AdmissionQueue(promote_after_s=0)


def test_admission_queue_deadline_heap_and_lazy_deletion():
    """Expiry comes off the dedicated deadline min-heap (O(expired log
    n), not a full scan) with lazy deletion: entries consumed through
    one index never resurface through another."""
    t = [0.0]
    q = AdmissionQueue(capacity=16, clock=lambda: t[0])
    q.push("a", deadline_s=1.0)
    q.push("b", deadline_s=2.0)
    q.push("c", deadline_s=3.0)
    q.push("d")
    entry, shed = q.pop()
    assert entry.request == "a" and not shed  # popped before expiry
    t[0] = 2.5  # a is consumed, b expired: only b sheds
    entry, shed = q.pop()
    assert entry.request == "c"
    assert [e.request for e in shed] == ["b"]
    assert len(q) == 1  # just d
    # drain_all hands back scheduling order and empties EVERY index —
    # no stale entry sheds later from the deadline heap or FIFO.
    q.push("e", priority=1, deadline_s=9.0)
    q.push("f", priority=0)
    assert [e.request for e in q.drain_all()] == ["d", "f", "e"]
    assert len(q) == 0
    t[0] = 1e9
    assert q.drain_expired() == []
    assert q.pop() == (None, [])


# ---------------------------------------------------------------------------
# Paged + quantized KV cache.
# ---------------------------------------------------------------------------


def _paged_template(num_slots=2, seq=32, hkv=2, hd=4, dtype=jnp.float32):
    shape = jax.ShapeDtypeStruct
    return {
        "layer": {
            "k": shape((num_slots, seq, hkv, hd), dtype),
            "v": shape((num_slots, seq, hkv, hd), dtype),
            "valid": shape((num_slots, seq), jnp.bool_),
            "index": shape((), jnp.int32),
        }
    }


def _paged_row(seq=32, hkv=2, hd=4, fill=1.0):
    return {
        "layer": {
            "k": jnp.full((1, seq, hkv, hd), fill, jnp.float32),
            "v": jnp.full((1, seq, hkv, hd), -fill, jnp.float32),
            "valid": jnp.ones((1, seq), jnp.bool_),
            "index": jnp.int32(8),
        }
    }


def test_paged_cache_seating_and_reservation():
    cache = PagedKVCache(_paged_template(), page_size=8)
    assert (cache.num_slots, cache.max_seq_len) == (2, 32)
    assert cache.pages_per_slot == 4
    assert cache.free_pages == 8  # 2 slots x 4 pages; page 0 is trash
    assert cache.fits_tokens(64) and not cache.fits_tokens(65)
    cache.seat(_paged_row(), 0, pad=2, prompt_len=8, reserve_tokens=16)
    assert cache.free_pages == 6  # ceil(16 / 8) = 2 pages reserved
    assert cache.page_table[0, 0] != 0  # mapped off the trash page
    assert (cache.start[0], cache.lens[0]) == (2, 8)
    # The prompt region actually landed in the mapped page.
    page = int(cache.page_table[0, 0])
    assert float(
        jnp.abs(cache.cache["layer"]["pages_k"][page]).sum()
    ) > 0
    with pytest.raises(ValueError, match="already seated"):
        cache.seat(_paged_row(), 0, pad=0, prompt_len=8, reserve_tokens=8)
    with pytest.raises(ValueError, match="exceeds the logical"):
        cache.seat(_paged_row(), 1, pad=0, prompt_len=8, reserve_tokens=33)
    cache.advance([0])
    assert cache.lens[0] == 9
    cache.free(0)
    assert cache.free_pages == 8
    assert (cache.page_table[0] == 0).all()  # back on the trash page
    assert cache.lens[0] == 0
    # Exhaustion raises when admission is bypassed (fits_tokens is the
    # predicate that makes this unreachable in the engine).
    small = PagedKVCache(_paged_template(), page_size=8, num_pages=6)
    small.seat(_paged_row(), 0, pad=0, prompt_len=8, reserve_tokens=32)
    assert small.free_pages == 1
    assert not small.fits_tokens(16)
    with pytest.raises(RuntimeError, match="exhausted"):
        small.seat(_paged_row(), 1, pad=0, prompt_len=8, reserve_tokens=16)
    small.reset()
    assert small.free_pages == 5
    with pytest.raises(ValueError, match="page_size"):
        PagedKVCache(_paged_template(), page_size=0)
    with pytest.raises(ValueError, match="kv_dtype"):
        PagedKVCache(_paged_template(), kv_dtype="int4")
    with pytest.raises(ValueError, match="validity"):
        PagedKVCache({"k": jax.ShapeDtypeStruct((3, 16), jnp.float32)})


def test_cache_bytes_accounting_matches_buffers():
    """The regression the ISSUE names: ``nbytes`` (the serve_cache_bytes
    gauge's source) must equal the ACTUAL buffer bytes — quantized
    pools report int8 + scale bytes, not the dense dtype, and the
    host-side page-table/start/len addressing is counted."""
    template = _paged_template()
    f32 = PagedKVCache(template, page_size=8)
    q8 = PagedKVCache(template, page_size=8, kv_dtype="int8")
    for paged in (f32, q8):
        device = sum(
            leaf.nbytes for leaf in jax.tree.leaves(paged.cache)
        )
        host = (
            paged.page_table.nbytes + paged.start.nbytes
            + paged.lens.nbytes
        )
        assert paged.nbytes == device + host
    # int8 pools really store int8 values (+f32 scales): the dense-
    # dtype assumption would report 4x these bytes.
    assert q8.cache["layer"]["pages_k"].dtype == jnp.int8
    assert q8.cache["layer"]["scale_k"].dtype == jnp.float32
    value_bytes = q8.cache["layer"]["pages_k"].nbytes
    assert value_bytes * 4 == f32.cache["layer"]["pages_k"].nbytes
    assert q8.nbytes < f32.nbytes


def test_paged_rollover_free_long_generation():
    """5 x 20-token requests through 2 slots of a 32-position model:
    cumulative decode writes cross the model's sequence bound several
    times (what a cache with one shared write index would have to
    reset for). The default session serves them with ``generate()``'s
    tokens: slots recycle piecewise, every page comes back."""
    model = LlamaForCausalLM(LLAMA_TINY(dtype=jnp.float32, max_seq_len=32))
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2
    )
    rng = np.random.default_rng(5)
    requests = [
        Request(f"r{i}", rng.integers(1, 500, size=5).tolist(),
                max_new_tokens=20)
        for i in range(5)
    ]
    total_decode_tokens = sum(r.max_new_tokens for r in requests)
    assert total_decode_tokens > 32
    results = session.serve(requests)
    assert session.engine.cache.free_pages == session.engine.cache.num_pages - 1
    for req in requests:
        want = np.asarray(
            generate(model, params, jnp.asarray(req.input_ids)[None, :],
                     max_new_tokens=20)
        )[0]
        np.testing.assert_array_equal(
            np.asarray(results[req.request_id].tokens), want
        )


def test_int8_kv_decode_parity_at_tolerance(model_and_params):
    """int8 paged KV vs the f32 path: greedy decode matches generate()
    except at genuine near-ties (reference top-2 logit margin within
    atol — the quantization contract assert_serving_parity's tolerance
    mode checks); the cache_bytes gauge reports the QUANTIZED bytes."""
    from tpudl.obs import registry

    model, params = model_and_params
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS,
        kv_dtype="int8",
    )
    assert session.engine.cache.quantized
    assert (
        registry().gauge("serve_cache_bytes").value
        == session.engine.cache.nbytes
    )
    assert_serving_parity(
        session, model, params, _ragged_requests(8, seed=1), atol=0.05
    )


def test_streaming_matches_collect(model_and_params):
    """session.stream() delivers every request's tokens incrementally;
    the concatenated chunks AND the final Result are byte-identical to
    a submit/collect run of the same requests (streaming changes
    delivery, not generation)."""
    model, params = model_and_params
    requests = _ragged_requests(6, seed=11)
    ref = _session(model, params).serve(
        [Request(**r.__dict__) for r in requests]
    )
    session = _session(model, params)
    chunks, finals, order = {}, {}, {}
    for chunk in session.stream([Request(**r.__dict__) for r in requests]):
        chunks.setdefault(chunk.request_id, []).extend(chunk.tokens)
        order.setdefault(chunk.request_id, 0)
        order[chunk.request_id] += 1
        if chunk.done:
            finals[chunk.request_id] = chunk.result
    assert set(finals) == set(ref)
    for rid in ref:
        assert chunks[rid] == finals[rid].tokens == ref[rid].tokens, rid
        assert finals[rid].finish_reason == ref[rid].finish_reason
        # Tokens arrived incrementally, not one collect-at-eos blob.
        assert order[rid] >= 2 or len(ref[rid].tokens) <= 1
    assert session.engine.on_token is None  # feed uninstalled
    with pytest.raises(ValueError, match="chunk_tokens"):
        next(session.stream([], chunk_tokens=0))


def test_stream_validates_and_submits_at_call_time(model_and_params):
    """stream() does its validation, its submission, and its claim on
    the engine's token feed AT CALL TIME: misuse raises at the call
    site (not at a far-away first iteration), a second concurrent
    stream is rejected up front, and requests handed to a stream the
    caller never iterates are still admitted — collect() finishes
    them."""
    model, params = model_and_params
    session = _session(model, params)
    with pytest.raises(ValueError, match="chunk_tokens"):
        session.stream([], chunk_tokens=0)  # no next() needed
    req = _ragged_requests(1, seed=13)[0]
    gen = session.stream([req])  # never iterated
    assert session.engine.on_token is not None  # feed claimed eagerly
    with pytest.raises(RuntimeError, match="already active"):
        session.stream([])
    results = session.collect()  # the un-iterated stream's request ran
    assert results[req.request_id].finish_reason == "length"
    assert len(results[req.request_id].tokens) == req.max_new_tokens
    with pytest.raises(StopIteration):
        next(gen)  # nothing pending: exhausts and releases the feed
    assert session.engine.on_token is None
    # A failing submit releases the feed too (no stuck claim).
    with pytest.raises(ValueError, match="duplicate"):
        session.stream([Request(**req.__dict__)] * 2)
    assert session.engine.on_token is None


def test_stream_abandoned_and_stale_feed_reclaim(model_and_params):
    """Two feed-ownership regressions: a stream() whose generator was
    dropped (GC'd) before its first iteration must not wedge the
    session — the next stream() reclaims the token feed and delivers
    the abandoned stream's admitted work too — and a STARTED generator
    that lost the feed (collect() released it, a new stream claimed it)
    stops silently instead of stepping the engine under the new
    owner."""
    import gc

    model, params = model_and_params
    session = _session(model, params)
    session.stream([Request("first", [3, 5, 7], max_new_tokens=4)])
    gc.collect()  # the un-iterated generator is gone; feed still claimed
    finals = {}
    for chunk in session.stream([Request("second", [4, 6], max_new_tokens=3)]):
        if chunk.done:
            finals[chunk.request_id] = chunk.result
    assert set(finals) == {"first", "second"}  # reclaimed, not "active"
    assert len(finals["first"].tokens) == 4

    gen3 = session.stream([Request("third", [2, 4], max_new_tokens=6)])
    assert not next(gen3).done  # started and suspended mid-feed
    session.collect()  # finishes "third", releases gen3's feed
    gen4 = session.stream([Request("fourth", [9, 1], max_new_tokens=2)])
    assert list(gen3) == []  # stale: yields nothing, steps nothing
    assert session.engine.on_token is not None  # gen4 kept its claim
    finals4 = [c.result for c in gen4 if c.done]
    assert [r.request_id for r in finals4] == ["fourth"]
    assert len(finals4[0].tokens) == 2

    # close()d before first iteration: the generator finishes without
    # ever entering its try, so its finally never releases the feed —
    # the next stream() must reclaim it (the alive-but-closed branch,
    # distinct from the GC'd one above).
    gen5 = session.stream([Request("fifth", [1, 2], max_new_tokens=2)])
    gen5.close()
    finals5 = [c.result for c in session.stream([]) if c.done]
    assert [r.request_id for r in finals5] == ["fifth"]


def test_paged_page_size_not_dividing_model_bound(model_and_params):
    """A page_size that does not divide the model's compiled bound:
    the logical per-slot bound clamps to model_seq_len (admission must
    not promise positions the decode program cannot address), and a
    prompt span that rounds past the dense prefill row zero-pads its
    last page instead of raising at trace time — which previously
    struck AFTER pages were reserved, stranding the slot."""
    model, params = model_and_params
    session = _session(model, params, page_size=100)
    engine = session.engine
    assert engine.cache.max_seq_len == CFG.max_seq_len  # clamped, not 100
    assert engine.max_seq_len == CFG.max_seq_len
    reqs = _ragged_requests(3, seed=17)
    results = session.serve(reqs)
    for req in reqs:
        want = np.asarray(
            generate(model, params, jnp.asarray(req.input_ids)[None, :],
                     max_new_tokens=req.max_new_tokens)
        )[0]
        got = np.asarray(results[req.request_id].tokens)
        np.testing.assert_array_equal(
            got, want[: got.shape[0]],
            err_msg=f"{req.request_id} diverged on the padded-page cache",
        )


def test_never_fitting_prefill_inbox_head_sheds(model_and_params):
    """A prefilled item whose worst case exceeds what even an EMPTY
    cache could seat must shed (``shed_capacity``) instead of
    permanently blocking every prefilled request behind it — the
    disaggregation inbox is a plain deque with no deadline or
    fit-filtered-pop path, unlike AdmissionQueue."""
    import time

    from tpudl.serve.engine import _Prefilled, first_token
    from tpudl.serve.queue import _Entry

    model, params = model_and_params
    session = _session(model, params)
    engine = session.engine

    def prefilled(req):
        ids = np.asarray(req.input_ids, np.int32)
        pad = PROMPT_LEN - ids.shape[0]
        padded = np.concatenate([np.zeros(pad, np.int32), ids])[None, :]
        mask = np.concatenate(
            [np.zeros(pad, np.int32), np.ones(ids.shape[0], np.int32)]
        )[None, :]
        logits, row_cache = engine.prefill_call(engine.params, padded, mask)
        t = time.monotonic()
        return _Prefilled(
            _Entry(priority=0, seq=0, request=req, deadline=None,
                   submitted_at=t),
            row_cache, first_token(logits, req), int(ids.shape[0]), t, t,
            PROMPT_LEN,
        )

    huge = Request("huge", [1, 2, 3], max_new_tokens=CFG.max_seq_len)
    assert PROMPT_LEN + huge.max_new_tokens > CFG.max_seq_len
    ok = Request("ok", [4, 5], max_new_tokens=3)
    engine.prefill_inbox.append(prefilled(huge))
    engine.prefill_inbox.append(prefilled(ok))
    engine.run_until_drained()
    assert engine.results["huge"].finish_reason == "shed_capacity"
    assert engine.results["huge"].tokens == []
    assert engine.results["ok"].finish_reason == "length"
    assert len(engine.results["ok"].tokens) == 3
    assert not engine.prefill_inbox


def test_parity_tolerance_fires_on_wide_margin(model_and_params):
    """assert_serving_parity's atol (quantized-contract) mode measures
    the teacher-forced logit margin between the reference's choice and
    the token the engine ACTUALLY produced: a wide-margin divergence is
    a cache bug and must fire, tolerance or no tolerance."""
    import dataclasses

    model, params = model_and_params
    req = Request("t", [3, 5, 7, 11], max_new_tokens=4)
    real = _session(model, params).serve([Request(**req.__dict__)])
    logits = model.apply(
        {"params": params}, jnp.asarray(req.input_ids, jnp.int32)[None, :]
    )
    wrong = int(np.argmin(np.asarray(logits[0, -1])))
    assert wrong != real["t"].tokens[0]
    tampered = {
        "t": dataclasses.replace(
            real["t"], tokens=[wrong] + list(real["t"].tokens[1:])
        )
    }

    class _TamperedSession:
        def serve(self, requests):
            return tampered

    with pytest.raises(AssertionError, match="cache bug"):
        assert_serving_parity(
            _TamperedSession(), model, params, [req], atol=0.05
        )


def test_admission_queue_lazy_indexes_stay_bounded():
    """Lazy deletion must not leak: entries consumed through one index
    are eventually purged from the others — including the FIFO when
    promotion is disabled (it used to grow one dead entry per push for
    the process lifetime) and when a stuck live head blocks the
    head-cleanup path (compaction handles the dead middle)."""
    t = [0.0]
    q = AdmissionQueue(capacity=4, clock=lambda: t[0],
                       promote_after_s=None)
    for i in range(500):
        assert q.push(i, deadline_s=5.0)
        entry, shed = q.pop()
        assert entry.request == i and not shed
    assert len(q) == 0
    assert len(q._fifo) <= 16
    assert len(q._heap) <= 16
    assert len(q._by_deadline) <= 16

    # A live low-priority head parks in the FIFO while 500 higher-
    # priority entries churn through: the dead middle compacts.
    q2 = AdmissionQueue(capacity=4, clock=lambda: t[0],
                        promote_after_s=None)
    assert q2.push("stuck", priority=9)
    for i in range(500):
        assert q2.push(i, priority=0)
        entry, _ = q2.pop()
        assert entry.request == i
    assert len(q2) == 1  # "stuck" still waiting (promotion disabled)
    assert len(q2._fifo) <= 16
    assert len(q2._heap) <= 16
    entry, _ = q2.pop()
    assert entry.request == "stuck"


# ---------------------------------------------------------------------------
# Overload and capacity, on counts (the engine's injected clock, bytes).
# ---------------------------------------------------------------------------


def test_open_loop_overload_sheds_late_and_serves_the_rest(model_and_params):
    """An open loop offered far past capacity with tight deadlines, on
    the engine's injected clock (one ``step()`` call is 10 virtual ms;
    24 arrivals 0.2 virtual ms apart, 20 ms of deadline each, two
    slots): arrivals, steps and expiries interleave, the engine keeps
    serving what it seated and sheds the late — overload is telemetry,
    not a crash, and every offered request ends in exactly one Result."""
    model, params = model_and_params
    t = [0.0]
    session = _session(model, params, num_slots=2, clock=lambda: t[0])
    requests = _ragged_requests(24, seed=1, deadline_s=0.02)
    arrived = 0
    while True:
        while arrived < len(requests) and 0.0002 * arrived <= t[0]:
            session.submit(requests[arrived])
            arrived += 1
        progressed = session.engine.step()
        t[0] += 0.01
        if arrived == len(requests) and not progressed:
            break
    results = session.collect()
    assert set(results) == {r.request_id for r in requests}
    completed = [r for r in results.values() if r.ok]
    shed = [r for r in results.values() if not r.ok]
    assert len(completed) + len(shed) == 24
    assert len(completed) >= 2  # both slots served through the overload
    assert shed and all(
        r.finish_reason == "shed_timeout" and r.tokens == [] for r in shed
    )
    for r in completed:
        want = next(q for q in requests if q.request_id == r.request_id)
        assert len(r.tokens) == want.max_new_tokens


def test_int8_pages_hold_1_8x_the_slots_a_byte_of_bf16_pages():
    """Byte accounting, no run: at a published k/v row (Mistral-7B's 8
    heads of 128) an int8 pool with its per-row scales and the same
    host addressing holds the same resident slots in at most 1/1.8 of
    the bytes of the bf16 pool — what ``kv_dtype="int8"`` is for."""
    template = _paged_template(
        num_slots=4, seq=64, hkv=8, hd=128, dtype=jnp.bfloat16
    )
    bf16 = PagedKVCache(template, page_size=16)
    q8 = PagedKVCache(template, page_size=16, kv_dtype="int8")
    assert (q8.num_slots, q8.num_pages, q8.free_pages) == (
        bf16.num_slots, bf16.num_pages, bf16.free_pages
    )
    assert bf16.nbytes / q8.nbytes >= 1.8, (bf16.nbytes, q8.nbytes)
