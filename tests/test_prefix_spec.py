"""Prefix-sharing radix KV cache + speculative decoding (ISSUE 11).

Two correctness bars on top of test_serve's:

- a request seated against a CACHED prefix produces byte-identical
  tokens to a cold ``generate()`` run (exact-mode parity — sharing is
  an addressing trick, never a numerics change), with refcounts, COW
  splits, LRU eviction, and hash-collision safety asserted at the
  radix-tree level;
- a speculating engine passes teacher-forced margin-mode parity, and a
  slot whose whole proposal window is REJECTED continues decoding with
  state identical to never having speculated (the rollback regression
  — driven hard by a garbage draft that disagrees with the target
  almost everywhere).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.models.generate import generate
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs import registry
from tpudl.serve import (
    PagedKVCache,
    RadixPrefixTree,
    Request,
    ServeSession,
    assert_serving_parity,
)

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 16
PAGE = 4


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
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, **kw)


def _shared_requests(n, shared_tokens=12, seed=0, max_new=8, tag="r"):
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, CFG.vocab_size, size=shared_tokens).tolist()
    return [
        Request(
            f"{tag}{i}",
            shared + rng.integers(
                1, CFG.vocab_size,
                size=int(rng.integers(1, PROMPT_LEN - shared_tokens + 1)),
            ).tolist(),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# Radix tree units
# ---------------------------------------------------------------------------


def test_radix_insert_and_match():
    tree = RadixPrefixTree(PAGE)
    ids = list(range(100, 116))  # 4 full blocks
    assert tree.match_len(ids) == 0
    node = tree.insert_suffix(None, tree.blocks_of(ids), [5, 6, 7, 8])
    assert tree.match_len(ids) == 16
    # Page-granular: a 9-token prefix matches only 2 full blocks.
    assert tree.match_len(ids[:9]) == 8
    assert tree.match_len([1, 2, 3]) == 0  # sub-page prompts never match
    tree.release(node)


def test_radix_cow_split():
    tree = RadixPrefixTree(PAGE)
    ab = list(range(100, 116))
    node = tree.insert_suffix(None, tree.blocks_of(ab), [5, 6, 7, 8])
    tree.release(node)
    # Diverge after 2 blocks: the compressed edge splits; the shared
    # half keeps pages [5, 6], both continuations live below it.
    ac = ab[:8] + [7] * 8
    pages, deepest = tree.match_and_lease(ac)
    assert pages == [5, 6]
    assert tree.stats()["splits"] == 1
    new = tree.insert_suffix(deepest, tree.blocks_of(ac)[2:], [10, 11])
    assert tree.match_len(ab) == 16  # the original path survived the split
    assert tree.match_len(ac) == 16
    tree.release(new)
    assert tree.stats()["nodes"] == 3  # shared half + two tails


def test_radix_split_refcount_accounting():
    """A split inserts an ancestor ABOVE an already-leased node; the
    later release must unpin both halves exactly once (regression for
    the path-walking lease contract)."""
    tree = RadixPrefixTree(PAGE)
    ab = list(range(100, 116))
    lease_ab = tree.insert_suffix(None, tree.blocks_of(ab), [5, 6, 7, 8])
    # Second prompt splits the edge WHILE the first lease is alive.
    pages, lease_ac = tree.match_and_lease(ab[:8] + [9] * 8)
    assert pages == [5, 6]
    tree.release(lease_ac)
    assert tree.evictable_pages == 0  # ab's lease still pins everything
    tree.release(lease_ab)
    assert tree.evictable_pages == 4  # every page reclaimable now


def test_radix_lru_eviction():
    tree = RadixPrefixTree(PAGE)
    a = tree.insert_suffix(None, tree.blocks_of([1] * 8), [2, 3])
    b = tree.insert_suffix(None, tree.blocks_of([2] * 8), [4, 9])
    tree.release(a)
    tree.release(b)
    # Touch a: b becomes the LRU victim.
    _, lease = tree.match_and_lease([1] * 8)
    tree.release(lease)
    assert sorted(tree.evict(2)) == [4, 9]
    assert tree.match_len([2] * 8) == 0
    assert tree.match_len([1] * 8) == 8
    # A leased node is never evictable, whatever the pressure.
    _, lease = tree.match_and_lease([1] * 8)
    assert tree.evict(10) == []
    tree.release(lease)


def test_radix_hash_collision_safety(monkeypatch):
    """Force every block hash to collide: matching must still resolve
    by FULL token-block compare — hash-only matching would hand a
    different prompt another request's KV pages."""
    import tpudl.serve.cache as cache_mod

    monkeypatch.setattr(cache_mod, "block_hash", lambda block: 7)
    tree = RadixPrefixTree(PAGE)
    n1 = tree.insert_suffix(None, tree.blocks_of([1] * 8), [2, 3])
    n2 = tree.insert_suffix(None, tree.blocks_of([9] * 8), [4, 5])
    assert tree.match_len([1] * 8) == 8
    assert tree.match_len([9] * 8) == 8
    assert tree.match_len([3] * 8) == 0
    pages, lease = tree.match_and_lease([9] * 8)
    assert pages == [4, 5]
    tree.release(lease)
    tree.release(n1)
    tree.release(n2)
    # Eviction under collisions detaches the right sibling.
    freed = tree.evict(10)
    assert sorted(freed) == [2, 3, 4, 5]


# ---------------------------------------------------------------------------
# Cache-level sharing
# ---------------------------------------------------------------------------


def _paged_template(num_slots=2, seq=32, hkv=2, hd=4):
    return {"layers_0": {"attn": {
        "k": jax.ShapeDtypeStruct((num_slots, seq, hkv, hd), jnp.float32),
        "v": jax.ShapeDtypeStruct((num_slots, seq, hkv, hd), jnp.float32),
        "valid": jax.ShapeDtypeStruct((num_slots, seq), jnp.bool_),
        "index": jax.ShapeDtypeStruct((), jnp.int32),
    }}}


def _paged_row(seq=32, hkv=2, hd=4, seed=0):
    rng = np.random.default_rng(seed)
    return {"layers_0": {"attn": {
        "k": jnp.asarray(rng.normal(size=(1, seq, hkv, hd)), jnp.float32),
        "v": jnp.asarray(rng.normal(size=(1, seq, hkv, hd)), jnp.float32),
        "valid": jnp.ones((1, seq), jnp.bool_),
        "index": jnp.int32(8),
    }}}


def test_seat_shared_counts_only_new_pages():
    cache = PagedKVCache(_paged_template(), page_size=PAGE,
                         prefix_share=True)
    ids = list(range(100, 110))  # 10 tokens: 2 full blocks + tail
    row = _paged_row()
    free0 = cache.free_pages
    cache.seat_shared(row, 0, ids, reserve_tokens=16,
                      lease=cache.match_and_lease(ids))
    assert free0 - cache.free_pages == 4  # all 4 pages new, cold seat
    # Same prefix, different tail: only the 2 unshared pages allocate.
    ids2 = ids[:8] + [7, 7, 7]
    lease2 = cache.match_and_lease(ids2)
    assert len(lease2[0]) == 2
    free1 = cache.free_pages
    cache.seat_shared(_paged_row(seed=1), 1, ids2, reserve_tokens=15,
                      lease=lease2)
    assert free1 - cache.free_pages == 2
    # COW: both slots map the SAME physical prefix pages.
    assert list(cache.page_table[0][:2]) == list(cache.page_table[1][:2])
    assert (cache.start[1], cache.lens[1]) == (0, 11)  # left-aligned
    # free(): private pages return, tree pages stay cached/evictable.
    cache.free(0)
    cache.free(1)
    assert cache.radix.evictable_pages == 2
    assert cache.available_pages == cache.num_pages - 1


def test_seat_shared_gather_round_trip():
    """Pages -> dense prefix rows reproduces the seated row bytes (the
    input the chunked suffix prefill resumes from)."""
    cache = PagedKVCache(_paged_template(), page_size=PAGE,
                         prefix_share=True)
    ids = list(range(100, 112))
    row = _paged_row(seed=3)
    cache.seat_shared(row, 0, ids, reserve_tokens=16,
                      lease=cache.match_and_lease(ids))
    pages, lease = cache.match_and_lease(ids)
    rows = cache.gather_prefix_rows(pages, 12)
    attn = rows["layers_0"]["attn"]
    np.testing.assert_array_equal(
        np.asarray(attn["k"][0, :12]),
        np.asarray(row["layers_0"]["attn"]["k"][0, :12]),
    )
    assert int(attn["index"]) == 12
    assert np.asarray(attn["valid"]).sum() == 12
    cache.release_lease(lease[1] if isinstance(lease, tuple) else lease)


def test_fits_request_pinned_matched_pages_not_double_counted():
    """Admission must not count a matched prefix's refcount-0 pages
    BOTH as mapped-for-free and as reclaimable: seating pins them
    first, so they cannot also satisfy the remaining allocation
    (regression — the old predicate admitted requests seat_shared then
    crashed on with 'page pool exhausted')."""
    cache = PagedKVCache(_paged_template(seq=32), page_size=PAGE,
                         num_pages=10, prefix_share=True)
    prefix = list(range(100, 108))  # 2 full blocks
    # A seats (2 tree pages + 1 private), B fills most of the pool,
    # then A frees: free pool = 1 page, A's prefix cached evictable.
    cache.seat_shared(_paged_row(), 0, prefix, reserve_tokens=12,
                      lease=cache.match_and_lease(prefix))
    other = [9] * 8
    cache.seat_shared(_paged_row(seed=1), 1, other, reserve_tokens=24,
                      lease=cache.match_and_lease(other))
    cache.free(0)
    assert cache.free_pages == 1 and cache.radix.evictable_pages == 2
    # 12 tokens = 3 pages - 2 matched = 1 new <= 1 free: seatable.
    assert cache.fits_request(prefix, 12)
    # 16 tokens = 4 pages - 2 matched = 2 new, but the only evictable
    # pages ARE the matched ones (pinned at seat): must be denied.
    assert not cache.fits_request(prefix, 16)
    # Sanity: the admitted shape actually seats.
    cache.seat_shared(_paged_row(seed=2), 0, prefix, reserve_tokens=12,
                      lease=cache.match_and_lease(prefix))


def test_prefix_share_rejects_pad_aligned_seat():
    cache = PagedKVCache(_paged_template(), page_size=PAGE,
                         prefix_share=True)
    with pytest.raises(ValueError, match="seat_shared"):
        cache.seat(_paged_row(), 0, pad=2, prompt_len=8, reserve_tokens=8)


# ---------------------------------------------------------------------------
# Engine-level sharing: the exact-parity acceptance bar
# ---------------------------------------------------------------------------


def test_shared_prefix_exact_parity(model_and_params):
    """Requests seated against a cached prefix produce BYTE-IDENTICAL
    tokens to cold generate() runs (exact-mode assert_serving_parity),
    while the radix cache demonstrably served prefix tokens."""
    model, params = model_and_params
    session = _session(model, params, prefix_share=True)
    hits0 = registry().counter("serve_prefix_hit_tokens").value
    requests = _shared_requests(6, seed=2)
    assert_serving_parity(session, model, params, requests)
    assert registry().counter("serve_prefix_hit_tokens").value > hits0
    assert session.engine.cache.radix.stats()["nodes"] > 0


def test_shared_prefix_fully_matched_prompt(model_and_params):
    """A SECOND identical, page-aligned prompt (full-tree hit) still
    yields exact parity — the last prompt token re-runs through the
    chunk program to produce first-token logits."""
    model, params = model_and_params
    session = _session(model, params, prefix_share=True)
    ids = list(np.random.default_rng(5).integers(1, 512, size=12))
    reqs = [
        Request("a", [int(t) for t in ids], max_new_tokens=6),
        Request("b", [int(t) for t in ids], max_new_tokens=6),
    ]
    results = session.serve(reqs)
    want = np.asarray(generate(
        model, params, jnp.asarray(ids, jnp.int32)[None, :],
        max_new_tokens=6,
    ))[0]
    for rid in ("a", "b"):
        np.testing.assert_array_equal(np.asarray(results[rid].tokens), want)


def test_prefix_eviction_under_pool_pressure():
    """A pool too small to cache every prefix evicts LRU refcount-0
    tree pages instead of refusing admission — and every request still
    parity-matches its cold run."""
    # A small compiled bound keeps pages_per_slot (8) under the tiny
    # pool; 4 distinct 3-page prompts against 9 usable pages forces
    # the tree to evict between seats.
    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=32)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(2), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    session = _session(
        model, params, prefix_share=True, num_pages=10, num_slots=1,
    )
    rng = np.random.default_rng(9)
    reqs = []
    for i in range(4):
        prefix = rng.integers(1, 512, size=8).tolist()
        reqs.append(Request(f"e{i}", prefix + rng.integers(
            1, 512, size=4).tolist(), max_new_tokens=4))
    assert_serving_parity(session, model, params, reqs)
    assert session.engine.cache.radix.stats()["evictions"] > 0


def test_prefix_share_needs_no_other_flag(model_and_params):
    """``prefix_share=True`` alone, pool at its defaults (page size 16):
    the session shares pages and holds exact parity."""
    model, params = model_and_params
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        prefix_share=True,
    )
    assert session.engine.prefix_share
    assert session.engine.cache.page_size == 16
    prompt = np.random.default_rng(41).integers(1, 512, size=16).tolist()
    assert_serving_parity(
        session, model, params,
        [Request(f"d{i}", prompt, max_new_tokens=3 + i) for i in range(3)],
    )
    assert session.engine.cache.radix.stats()["cached_pages"] > 0


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------


def test_spec_greedy_margin_parity_and_acceptance(model_and_params):
    """Teacher-forced margin-mode parity for the int8 self-draft, and
    per-stream accepted-tokens/step >= 2 on the greedy config (the
    acceptance bar)."""
    model, params = model_and_params
    session = _session(model, params, spec_k=3)
    reg = registry()
    acc0 = reg.counter("spec_accepted_tokens").value
    slot0 = reg.counter("spec_slot_steps").value
    rng = np.random.default_rng(11)
    reqs = [
        Request(f"s{i}", rng.integers(1, 512, size=int(
            rng.integers(2, PROMPT_LEN + 1))).tolist(), max_new_tokens=12)
        for i in range(5)
    ]
    assert_serving_parity(session, model, params, reqs, atol=0.06)
    accepted = reg.counter("spec_accepted_tokens").value - acc0
    slot_steps = reg.counter("spec_slot_steps").value - slot0
    assert accepted / slot_steps >= 2.0, (accepted, slot_steps)


def test_spec_full_rejection_rollback(model_and_params):
    """THE rollback regression: a draft with unrelated random weights
    disagrees with the target almost everywhere, so windows are
    (nearly always) fully rejected — and the emitted stream must still
    be EXACTLY the non-speculative greedy stream, i.e. state after a
    rejected window is indistinguishable from never having
    speculated."""
    model, params = model_and_params
    garbage = model.init(
        jax.random.key(123), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    session = _session(
        model, params, spec_k=3, draft_model=model, draft_params=garbage,
    )
    rng = np.random.default_rng(13)
    reqs = [
        Request(f"g{i}", rng.integers(1, 512, size=6).tolist(),
                max_new_tokens=10)
        for i in range(4)
    ]
    results = session.serve(list(reqs))
    for req in reqs:
        want = np.asarray(generate(
            model, params,
            jnp.asarray(req.input_ids, jnp.int32)[None, :],
            max_new_tokens=req.max_new_tokens,
        ))[0]
        got = np.asarray(results[req.request_id].tokens)
        np.testing.assert_array_equal(
            got, want[: got.shape[0]],
            err_msg=f"{req.request_id}: rejected-window rollback "
                    f"corrupted the decode state",
        )


def test_spec_eos_mid_window(model_and_params):
    """An eos accepted in the middle of a window truncates the window
    there, exactly like non-speculative serving stops at eos."""
    model, params = model_and_params
    prompt = [3, 1, 4, 1, 5]
    cold = np.asarray(generate(
        model, params, jnp.asarray(prompt, jnp.int32)[None, :],
        max_new_tokens=12,
    ))[0]
    eos = int(cold[4])  # force a finish at token 5 of 12
    session = _session(model, params, spec_k=3)
    res = session.serve([
        Request("e", prompt, max_new_tokens=12, eos_id=eos)
    ])["e"]
    assert res.finish_reason == "eos"
    assert res.tokens[-1] == eos
    np.testing.assert_array_equal(
        np.asarray(res.tokens), cold[: len(res.tokens)]
    )
    assert eos not in res.tokens[:-1]


def test_spec_sampled_determinism(model_and_params):
    """Sampled requests reproduce their tokens across sessions (the
    per-(request, position) Philox streams), independent of batch
    composition."""
    model, params = model_and_params
    req = Request("t", [5, 6, 7, 8], max_new_tokens=10,
                  temperature=0.8, seed=42)
    out1 = _session(model, params, spec_k=3).serve(
        [dataclasses.replace(req)]
    )["t"].tokens
    # Same request next to a neighbor: its stream must not change.
    session = _session(model, params, spec_k=3)
    other = Request("o", [9, 9, 2], max_new_tokens=10)
    res = session.serve([dataclasses.replace(req), other])
    assert res["t"].tokens == out1


def test_spec_companion_draft_different_architecture(model_and_params):
    """A companion draft with DIFFERENT KV geometry (fewer layers)
    gets its own cache template — only the tokenizer must match
    (regression: the draft pool was built from the target's template,
    crashing any non-self draft at first seat)."""
    model, params = model_and_params
    small_cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96,
                           num_layers=1)
    draft = LlamaForCausalLM(small_cfg)
    draft_params = draft.init(
        jax.random.key(7), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    session = _session(
        model, params, spec_k=3, draft_model=draft,
        draft_params=draft_params,
    )
    rng = np.random.default_rng(19)
    reqs = [
        Request(f"cd{i}", rng.integers(1, 512, size=5).tolist(),
                max_new_tokens=8)
        for i in range(3)
    ]
    # Greedy correction keeps the stream exact whatever the draft says.
    results = session.serve(list(reqs))
    for req in reqs:
        want = np.asarray(generate(
            model, params,
            jnp.asarray(req.input_ids, jnp.int32)[None, :],
            max_new_tokens=req.max_new_tokens,
        ))[0]
        np.testing.assert_array_equal(
            np.asarray(results[req.request_id].tokens),
            want[: len(results[req.request_id].tokens)],
        )


def test_spec_needs_no_other_flag(model_and_params):
    """``spec_k`` alone, pool at its defaults: a speculating session
    whose draft pool has the target's page size."""
    model, params = model_and_params
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2, spec_k=3,
    )
    spec = session.engine.speculator
    assert spec is not None and spec.k == 3
    assert spec.cache.page_size == session.engine.cache.page_size == 16


def test_acceptance_rules_unit():
    from tpudl.serve.speculate import (
        greedy_accept,
        sample_accept,
        softmax,
    )

    # Greedy: full acceptance emits the proposals verbatim.
    emitted, accepted = greedy_accept([4, 5, 6], [4, 5, 6])
    assert (emitted, accepted) == ([4, 5, 6], 3)
    # First disagreement: target's choice replaces it, window ends.
    emitted, accepted = greedy_accept([4, 9, 6], [4, 5, 6])
    assert (emitted, accepted) == ([4, 5], 1)
    emitted, accepted = greedy_accept([9, 9, 9], [1, 2, 3])
    assert (emitted, accepted) == ([1], 0)

    # Sampling: q == p accepts every proposal (ratio 1).
    p = softmax(np.asarray([1.0, 2.0, 3.0]), 1.0)
    emitted, accepted = sample_accept(
        [2, 2], [p, p], [p, p], seed=1, token_index=0
    )
    assert accepted == 2 and emitted == [2, 2]
    # A proposal with target mass ZERO is always rejected, and the
    # residual draw can only produce tokens with p > q mass.
    q = np.asarray([0.0, 1.0, 0.0])
    p0 = np.asarray([0.7, 0.0, 0.3])
    for seed in range(8):
        emitted, accepted = sample_accept(
            [1], [q], [p0], seed=seed, token_index=0
        )
        assert accepted == 0
        assert emitted[0] in (0, 2)


def test_spec_with_prefix_share_composed(model_and_params):
    """The two tentpole halves compose: radix-shared seating under a
    speculating engine, margin parity intact."""
    model, params = model_and_params
    session = _session(model, params, prefix_share=True, spec_k=3)
    reqs = _shared_requests(4, seed=21, max_new=6, tag="c")
    assert_serving_parity(session, model, params, reqs, atol=0.06)
    assert session.engine.cache.radix.stats()["nodes"] > 0


# ---------------------------------------------------------------------------
# Exported paged artifacts (ROADMAP item 6 leftover)
# ---------------------------------------------------------------------------


def test_from_artifacts_paged_parity(model_and_params):
    """The paged-KV contract round-trips through StableHLO: geometry
    (page size, pool size, slots, quantization) recovered from avals
    alone, int8 pools included, greedy tokens parity-checked."""
    model, params = model_and_params
    from tpudl.export.decode import export_serving_decoder

    pre, dec = export_serving_decoder(
        model, params, num_slots=2, prompt_len=PROMPT_LEN,
        page_size=PAGE, kv_dtype="int8",
    )
    session = ServeSession.from_artifacts(pre, dec, params)
    cache = session.engine.cache
    assert cache.quantized and cache.page_size == PAGE
    assert session.num_slots == 2
    rng = np.random.default_rng(17)
    reqs = [
        Request(f"x{i}", rng.integers(1, 512, size=6).tolist(),
                max_new_tokens=8)
        for i in range(3)
    ]
    assert_serving_parity(session, model, params, reqs, atol=0.05)


def test_from_artifacts_refuses_the_dense_decode_pair(model_and_params):
    """The pair ``export_decoder`` writes for offline generation has the
    4-argument dense decode: a session over it is a loud error that
    names the contract it wants, not a second serving path."""
    model, params = model_and_params
    from tpudl.export.decode import export_decoder

    pre, dec = export_decoder(model, params, 1, PROMPT_LEN)
    with pytest.raises(ValueError, match="7 of the paged decode contract"):
        ServeSession.from_artifacts(pre, dec, params)


def test_from_artifacts_paged_clamps_model_bound(model_and_params):
    """A page size that does not divide the model's compiled bound
    rounds the page span past the model's position space; the artifact
    session must clamp admission at the TRUE bound (recovered from the
    prefill artifact's dense rows), exactly like the live path."""
    model, params = model_and_params
    from tpudl.export.decode import export_serving_decoder

    pre, dec = export_serving_decoder(
        model, params, num_slots=2, prompt_len=PROMPT_LEN,
        page_size=28,  # 4 * 28 = 112 > the model's 96
    )
    session = ServeSession.from_artifacts(pre, dec, params)
    assert session.max_seq_len == CFG.max_seq_len == 96
    with pytest.raises(ValueError, match="max_seq_len"):
        session.submit(Request("z", [1, 2, 3],
                               max_new_tokens=96 - PROMPT_LEN + 1))


# ---------------------------------------------------------------------------
# Router prefix affinity + trace attribution
# ---------------------------------------------------------------------------


def test_router_prefix_affinity(model_and_params):
    """A request whose prefix lives in one replica's radix tree routes
    there even when another replica is equally idle — prefix affinity
    beats cold least-loaded placement."""
    model, params = model_and_params
    from tpudl.serve import Replica, Router

    replicas = [
        Replica(f"r{i}", _session(model, params, prefix_share=True))
        for i in range(2)
    ]
    reqs = _shared_requests(4, seed=31, max_new=4, tag="af")
    with Router(replicas) as router:
        # Seed: the first request lands somewhere and plants the
        # prefix in that replica's tree.
        router.serve([reqs[0]], timeout_s=120.0)
        seeded = next(
            r for r in replicas
            if r.session.engine.cache.radix.stats()["nodes"] > 0
        )
        other = next(r for r in replicas if r is not seeded)
        results = router.serve(reqs[1:], timeout_s=120.0)
    assert all(r.ok for r in results.values())
    # Every follow-up went to the seeded replica's engine.
    assert other.session.engine.num_prefills == 0
    assert seeded.session.engine.num_prefills == len(reqs)


def test_report_request_prefix_and_spec_attrs(model_and_params, tmp_path):
    """report.py --request surfaces prefix_hit_tokens and per-window
    accepted/proposed — where TTFT and TPOT went."""
    model, params = model_and_params
    from tpudl.obs import report as obs_report
    from tpudl.obs import spans as obs_spans

    obs_spans.enable(str(tmp_path))
    try:
        session = _session(model, params, prefix_share=True, spec_k=3)
        reqs = _shared_requests(3, seed=41, max_new=6, tag="tr")
        session.serve(list(reqs))
        records = obs_spans.active_recorder().records
        timeline = obs_report.build_request_timeline(records, "tr2")
    finally:
        obs_spans.disable()
    assert timeline["prefix_hit_tokens"] and timeline[
        "prefix_hit_tokens"] >= PAGE
    spec = timeline["speculation"]
    assert spec is not None and spec["proposed"] > 0
    chunk = next(
        e for e in timeline["timeline"] if e["what"] == "decode_chunk"
    )
    assert chunk["detail"]["proposed"] > 0
    assert "accepted" in chunk["detail"]
    prefill = next(
        e for e in timeline["timeline"] if e["what"] == "prefill"
    )
    assert prefill["detail"]["prefix_hit_tokens"] >= PAGE
