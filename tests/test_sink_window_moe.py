"""Two kinds of layer that differ in more than numbers (ISSUE 47):
full-context layers beside sliding-window layers with their own KV
heads, rotary base and a learned sink in the softmax, keys 192 wide
beside values 128 wide, under one page manager whose pools hold the
heads merged into the lanes, read in place by the merged kernel.

The program is held to the plain reference of
``perfbench/reference/sink_window_moe.py`` (float32, whole sequences, no
cache, no ring, no kernel) at tiny sizes that KEEP the asymmetries:
hidden 64, 8 query heads on 2 (full) / 4 (sliding) KV heads, keys 192
wide of which 64 rotate, values 128 wide (the published head: the
narrowest at which both kinds' merged rows are whole lanes, so that
the cache holds them merged and the kernel, in interpret mode, serves
both groups), a window of 16 on pages of 8 (a float32 tile's rows), 16
experts top-4 of which 4 are held, 7 layers in the published pattern.

Tolerances. Program and reference are both float32 on the CPU here, so
what separates them is the order of their sums: logits of size ~0.15
agree to about 1e-6; ``ATOL`` = 1e-5. Each mechanism taken out moves
the logits by at least ``FAULT`` = 1e-3 (asserted one by one below).
"""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.models.llama as llama
import tpudl.ops.paged_attention as pa
from perfbench.families import sink_window_moe_serve as family
from perfbench.reference import sink_window_moe as ref
from tpudl.models.generate import paged_decode_fn, prefill_fn
from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.models.paged import PagedView, heads_in_lanes
from tpudl.obs import registry
from tpudl.serve import Request, ServeSession
from tpudl.serve.cache import PagedKVCache

REPO = pathlib.Path(__file__).resolve().parents[1]
ATOL, FAULT = 1e-5, 1e-3
WINDOW, PAGE, SEQ, PROMPT = 16, 8, 96, 24
#: ceil(16 / 8) + 1 pages a ring.
RING = 3
VOCAB = 97


def tiny_config(**over) -> dict:
    """The published configuration file, shrunk: the same keys, the same
    pattern of layers (full, sliding x 4, full, sliding; dense, then
    experts), the published head (192 / 128, 64 rotate)."""
    with open(REPO / "perfbench/configs/mimo-v2-flash-l7-e16.json") as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        num_attention_heads=8, swa_num_attention_heads=8,
        num_key_value_heads=2, swa_num_key_value_heads=4,
        sliding_window=WINDOW, moe_intermediate_size=32,
        n_routed_experts=4, num_experts_per_tok=4, torch_dtype="float32",
    )
    cfg["deployment"] = dict(cfg["deployment"], router_experts=16,
                             first_expert=0)
    cfg.update(over)
    return cfg


def _program(cfg, seq_len=SEQ, seed=11):
    """(model, params) of ``cfg`` with the reference's seeded weights."""
    model = LlamaForCausalLM(family.model_config(cfg, seq_len, jnp.float32))
    s = ref.settings(cfg)
    params = family.to_flax(
        ref.all_weights(ref.seed_key(seed), s, jnp.float32), s)
    return model, params


@pytest.fixture(scope="module")
def served():
    cfg = tiny_config()
    model, params = _program(cfg)
    return cfg, model, params, ref.seed_key(11)


@pytest.fixture(scope="module")
def whole(served):
    """Two sequences of 64 positions and the reference's logits."""
    cfg, _, _, key = served
    ids = np.random.default_rng(0).integers(1, VOCAB, size=(2, 64))
    return ids, _reference_logits(cfg, key, ids)


def _reference_logits(cfg, key, ids):
    return np.asarray(ref.logits(key, cfg, jnp.float32, jnp.asarray(ids)))


def _cache_for(model, params, slots, **kwargs):
    ids = jax.ShapeDtypeStruct((slots, PROMPT), jnp.int32)
    _, template, *_ = jax.eval_shape(prefill_fn(model), params, ids, ids)
    return PagedKVCache(template, page_size=PAGE, **kwargs)


def _drive(model, params, cache, sequences, steps):
    """Prefill each sequence's first tokens (left-padded to the window)
    into a slot of its own, then step all slots together, teacher-forced
    along the sequences; returns {slot: [logits after each position]}."""
    prefill = jax.jit(prefill_fn(model))
    decode = jax.jit(
        paged_decode_fn(model, cache.page_size, cache.quantized),
        donate_argnums=(1,),
    )
    out, at = {}, {}
    for slot, (seq, n) in sequences.items():
        pad = PROMPT - n
        ids = np.concatenate([np.zeros(pad, np.int32), seq[:n]])[None]
        mask = (np.arange(PROMPT) >= pad).astype(np.int32)[None]
        logits, row, *_ = prefill(params, ids, mask)
        cache.seat(row, slot, pad, PROMPT, PROMPT + steps)
        out[slot], at[slot] = [np.asarray(logits[0])], n
    for _ in range(steps):
        token = np.zeros((cache.num_slots,), np.int32)
        position = np.zeros((cache.num_slots,), np.int32)
        for slot, (seq, _) in sequences.items():
            token[slot], position[slot] = seq[at[slot]], at[slot]
        # Read back before the host's lengths move: on the CPU the
        # dispatch may read ``cache.lens`` where numpy holds it.
        logits = np.asarray(cache.decode(decode, params, token, position))
        cache.advance(list(sequences))
        for slot in sequences:
            out[slot].append(logits[slot])
            at[slot] += 1
    return out


def test_the_tree_is_what_init_declares(served):
    """A sliding layer has 4 KV heads, a full one 2; keys 192, values
    128; ``o_proj`` reads heads x 128; a sink [8] float32 on the sliding
    layers alone; no shared expert."""
    _, model, params, _ = served
    ids = jnp.zeros((1, 8), jnp.int32)
    declared = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    assert jax.tree.map(lambda a: (a.shape, a.dtype), declared) == jax.tree.map(
        lambda a: (a.shape, a.dtype), params)
    full = declared["model"]["layer_5"]["attention"]
    swa = declared["model"]["layer_6"]["attention"]
    assert full["k_proj"]["kernel"].shape == (64, 2 * 192)
    assert full["v_proj"]["kernel"].shape == (64, 2 * 128)
    assert swa["k_proj"]["kernel"].shape == (64, 4 * 192)
    assert swa["v_proj"]["kernel"].shape == (64, 4 * 128)
    assert swa["o_proj"]["kernel"].shape == (8 * 128, 64)
    assert swa["sink"].shape == (8,) and swa["sink"].dtype == jnp.float32
    assert "sink" not in full
    assert not any("shared" in k for k in declared["model"]["layer_1"]["moe"])


def test_whole_sequence_forward_matches_the_reference(served, whole):
    _, model, params, _ = served
    ids, want = whole
    got = jax.jit(lambda p, i: model.apply({"params": p}, i))(params, ids)
    assert want.std() > 0.05  # logits that tell tokens apart
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("path", ["gather", "kernel"])
def test_prefill_seat_and_ring_decode_match_the_reference(
    served, path, monkeypatch
):
    """Three slots, one idle; prompts of 24 and 7 tokens in a window of
    24; 40 steps (the window and three pages more) take the context to
    64 and 47 positions: the three-page rings wrap and their oldest
    pages are overwritten. On the gather path and with the merged
    kernel in interpret mode, which then serves all 7 layers of both
    groups."""
    cfg, model, params, key = served
    if path == "kernel":
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    steps = 40
    rng = np.random.default_rng(0)
    sequences = {
        0: (rng.integers(1, VOCAB, size=PROMPT + steps + 1), PROMPT),
        2: (rng.integers(1, VOCAB, size=7 + steps + 1), 7),
    }
    cache = _cache_for(model, params, 3)
    assert cache.window == WINDOW and cache.ring_pages == RING
    copies = registry().counter("serve_kv_pool_copies").value
    got = _drive(model, params, cache, sequences, steps)
    assert registry().counter("serve_kv_pool_copies").value == copies
    assert cache.in_place_layers == (7 if path == "kernel" else 0)
    assert (cache.lens[0] - PROMPT) // PAGE > RING  # wrapped
    for slot, (seq, n) in sequences.items():
        want = _reference_logits(cfg, key, seq[None, : n + steps])[0]
        np.testing.assert_allclose(
            np.stack(got[slot]), want[n - 1:], atol=ATOL)


def test_blocked_prefill_is_the_dense_prefill(served, monkeypatch):
    """A long prompt into an empty cache is attended in blocks of
    queries, the sliding layers over their band with the sink in every
    block's denominator: the same logits and the same rows as one dense
    pass."""
    _, model, params, _ = served
    rng = np.random.default_rng(2)
    pad = 5
    ids = np.concatenate(
        [np.zeros(pad, np.int32), rng.integers(1, VOCAB, size=PROMPT - pad)]
    )[None]
    mask = (np.arange(PROMPT) >= pad).astype(np.int32)[None]
    dense_logits, dense_rows, *_ = jax.jit(prefill_fn(model))(params, ids, mask)
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 0)
    monkeypatch.setattr(llama, "PREFILL_BLOCK", 8)
    logits, rows, *_ = jax.jit(prefill_fn(model))(params, ids, mask)
    np.testing.assert_allclose(logits, dense_logits, atol=ATOL)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(dense_rows)):
        if a.ndim == 4:  # rows [1, T, Hkv, D]: the prompt's, not the pad's
            a, b = a[:, pad:PROMPT], b[:, pad:PROMPT]
        np.testing.assert_allclose(a, b, atol=ATOL)


# -- the kernel against the gather, alone ------------------------------------


@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("widths", [(192, 128), (128, 128)])
@pytest.mark.parametrize("sink", [False, True])
def test_merged_kernel_is_the_gather(sink, widths, chunk):
    """``_merged_kernel`` (interpret mode) against ``paged_attention_ref``
    over the same merged pools: with and without a sink, keys and values
    of unequal and of equal widths, an idle slot on the trash page, a
    slot whose context is shorter than a page, one that starts behind a
    left pad, chunks of one token and of several."""
    d, dv = widths
    rng = np.random.default_rng(7)
    slots, heads, hkv, ps, pages = 4, 8, 2, 8, 5
    n = slots * pages + 1
    pk = jnp.asarray(rng.normal(size=(n, ps, hkv * d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(n, ps, hkv * dv)), jnp.float32)
    table = 1 + rng.permutation(slots * pages).reshape(slots, pages)
    table[0] = 0  # idle: lens 0 on the trash page
    view = PagedView(
        jnp.asarray(table, jnp.int32),
        jnp.asarray([0, 0, 11, 3], jnp.int32),
        jnp.asarray([0, 2, 36, 19], jnp.int32), ps, False,
    )
    q = jnp.asarray(rng.normal(size=(slots, chunk, heads, d)), jnp.float32)
    bias = (
        jnp.asarray(1 + rng.normal(size=(heads,)), jnp.float32)
        if sink else None
    )
    assert pa.in_place_ok(q, pk, view, pv, bias)
    want = pa.paged_attention(q, pk, pv, view, sink=bias, impl="reference")
    got = pa.paged_attention(q, pk, pv, view, sink=bias, impl="fused")
    assert got.shape == (slots, chunk, heads, dv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    if sink:
        # The sink takes its share: without it the context is another.
        plain = pa.paged_attention(q, pk, pv, view, impl="fused")
        assert float(jnp.abs(plain - got)[1:].max()) > 0.05


def test_the_kernel_over_the_ring_is_the_gather():
    """Through the rotated view of a ring, a slot younger than the
    window beside one far past it."""
    rng = np.random.default_rng(4)
    slots, heads, hkv, d, dv, ps = 3, 8, 4, 192, 128, 8
    n = slots * RING + 1
    pk = jnp.asarray(rng.normal(size=(n, ps, hkv * d)), jnp.float32)
    pv = jnp.asarray(rng.normal(size=(n, ps, hkv * dv)), jnp.float32)
    ring = jnp.asarray(
        1 + rng.permutation(slots * RING).reshape(slots, RING), jnp.int32)
    view = PagedView(
        (jnp.zeros((slots, 30), jnp.int32), ring),
        jnp.asarray([0, 50, 7], jnp.int32),
        jnp.asarray([5, 77, 118], jnp.int32), ps, False,
    ).ring_view(WINDOW)
    q = jnp.asarray(rng.normal(size=(slots, 1, heads, d)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(heads,)), jnp.float32)
    want = pa.paged_attention(q, pk, pv, view, sink=bias, impl="reference")
    got = pa.paged_attention(q, pk, pv, view, sink=bias, impl="fused")
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_which_pools_which_kernel():
    """``in_place_ok`` by what it can observe: a pool pair held as
    declared is the first kernel's (and takes no sink), a merged pair
    the second's; int8 and mesh-committed pools gather."""
    view = PagedView(jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
                     jnp.zeros((2,), jnp.int32), 16, False)
    bf = jnp.bfloat16
    q128 = jnp.zeros((2, 1, 8, 128), bf)
    q192 = jnp.zeros((2, 1, 8, 192), bf)
    declared = jnp.zeros((9, 16, 2, 128), bf)
    k, v = jnp.zeros((9, 16, 2 * 192), bf), jnp.zeros((9, 16, 2 * 128), bf)
    sink = jnp.zeros((8,), jnp.float32)
    assert pa.in_place_ok(q128, declared, view)
    assert pa.in_place_ok(q128, declared, view, declared)
    assert not pa.in_place_ok(q128, declared, view, declared, sink)
    assert pa.in_place_ok(q192, k, view, v)
    assert pa.in_place_ok(q192, k, view, v, sink)
    assert not pa.in_place_ok(q192, k, view)  # a merged k needs its v
    # A key row of 384 under 128-wide queries would be 3 KV heads, which
    # neither the 256-wide value row nor the 8 query heads divide by.
    assert not pa.in_place_ok(q128, k, view, v)
    assert not pa.in_place_ok(q192, k, view, jnp.zeros((9, 16, 2 * 96), bf))
    for flag in ("quantized", "sharded"):
        other = dataclasses.replace(view, **{flag: True})
        assert not pa.in_place_ok(q192, k, other, v)
    with pytest.raises(ValueError, match="merged widths"):
        pa.paged_attention(q128, k, v, view, impl="fused")


# -- one part wrong at a time ------------------------------------------------


FAULTS = {
    "the sink left out": dict(add_swa_attention_sink_bias=False),
    "the sink added to the full layers": dict(
        add_full_attention_sink_bias=True),
    "the value scale left out": dict(attention_value_scale=1.0),
    "the KV heads of the two kinds swapped": dict(
        num_key_value_heads=4, swa_num_key_value_heads=2),
    "theta swapped": dict(rope_theta=10000, swa_rope_theta=5000000),
    "a window of 17": dict(sliding_window=WINDOW + 1),
    "the sliding layers rotated over the whole head": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_comparison_sees_one_wrong_part(served, whole, fault):
    """Each part, wrong in the program alone, moves the logits by over a
    hundred times the tolerance."""
    cfg, model, params, _ = served
    ids, want = whole
    if FAULTS[fault] is None:
        wrong = LlamaForCausalLM(dataclasses.replace(
            model.cfg, sliding_partial_rotary_factor=1.0))
    else:
        wrong, params = _program(tiny_config(**FAULTS[fault]))
    got = jax.jit(lambda p, i: wrong.apply({"params": p}, i))(params, ids)
    assert float(jnp.abs(got - want).max()) > FAULT


def test_the_shares_of_a_layer_add_up_to_the_whole():
    """The 4 shares' routed parts, each computed by the PROGRAM's layer
    with ``experts_held = (4 i, 4)``, sum to what the uncut reference
    gives for the whole expert layer (no shared expert to count once)."""
    from tpudl.ops.moe import DroplessMoE

    s = dict(ref.settings(tiny_config()), n_routed_experts=16)
    w = ref.layer_weights(ref.seed_key(5), 1, s, jnp.float32, "s", False)
    w = dict(w, router_bias=5 * w["router_bias"])
    y = jnp.asarray(np.random.default_rng(1).normal(size=(18, 64)),
                    jnp.float32)
    whole_layer = ref.experts(y, w, s)
    total = jnp.zeros_like(whole_layer)
    for first in range(0, 16, 4):
        layer = DroplessMoE(
            num_experts=16, experts_per_token=4, intermediate_size=32,
            experts_held=(first, 4), dtype=jnp.float32,
        )
        params = {
            "router": {"kernel": w["router"]},
            "router_bias": w["router_bias"],
            **{f"{n}_proj": {"kernel": w[f"experts_{n}"][first:first + 4]}
               for n in ("gate", "up", "down")},
        }
        part, _ = layer.apply({"params": params}, y[None],
                              jnp.ones((1, 18), bool), mutable=["moe_stats"])
        total = total + part[0]
    assert float(jnp.abs(whole_layer).max()) > 1e-3
    np.testing.assert_allclose(total, whole_layer, atol=1e-6)


# -- the page manager --------------------------------------------------------


def test_the_pools_hold_the_heads_merged(served):
    """A layer one of whose head widths is not whole lanes holds k and v
    with the heads merged into the lanes; ``nbytes`` is the declared
    rows' bytes, by group; the pools that were there stay as declared."""
    _, model, params, _ = served
    slots = 3
    cache = _cache_for(model, params, slots)
    pools = cache.cache["model"]
    pages = slots * (SEQ // PAGE) + 1
    full, swa = pools["layer_5"]["attention"], pools["layer_6"]["attention"]
    assert full["pages_k"].shape == (pages, PAGE, 2 * 192)
    assert full["pages_v"].shape == (pages, PAGE, 2 * 128)
    assert swa["pages_k"].shape == (slots * RING + 1, PAGE, 4 * 192)
    assert swa["pages_v"].shape == (slots * RING + 1, PAGE, 4 * 128)
    # 4 B a value: 2 x (192 + 128) a position a full layer, twice that a
    # sliding one; two full layers, five sliding.
    assert cache.row_bytes == [2 * 4 * 2 * 320, 5 * 4 * 4 * 320]
    assert cache.nbytes - cache.addressing_nbytes == (
        pages * PAGE * cache.row_bytes[0]
        + (slots * RING + 1) * PAGE * cache.row_bytes[1]
    )
    assert heads_in_lanes([(4, 192), (4, 128)], jnp.bfloat16)
    assert not heads_in_lanes([(8, 128), (8, 128)], jnp.bfloat16)
    assert not heads_in_lanes([(2, 48), (2, 32)], jnp.bfloat16)  # 96 lanes
    assert not heads_in_lanes([(4, 192), (4, 128)], jnp.int8)
    assert not heads_in_lanes([(576,)], jnp.bfloat16)


def test_pages_and_bytes_by_group_after_seat_and_free(served):
    _, model, params, _ = served
    cache = _cache_for(model, params, 3)
    prefill = jax.jit(prefill_fn(model))
    ids = np.arange(1, PROMPT + 1, dtype=np.int32)[None]
    _, row, *_ = prefill(params, ids, np.ones_like(ids))
    free, free_ring = cache.free_pages, len(cache._free_ring)
    assert cache.fits_tokens(SEQ) and not cache.fits_tokens(SEQ * 4)
    cache.seat(row, 1, 3, PROMPT, PROMPT + 40)
    assert cache.free_pages == free - 8  # ceil(64 / 8)
    assert len(cache._free_ring) == free_ring - RING
    assert cache.pages_reserved == 8
    assert cache.pages_reserved_window == RING
    assert cache.tokens_live == PROMPT - 3
    assert cache.tokens_live_window == WINDOW
    assert cache.bytes_live == (
        (PROMPT - 3) * cache.row_bytes[0] + WINDOW * cache.row_bytes[1])
    cache.free(1)
    assert cache.free_pages == free and len(cache._free_ring) == free_ring
    assert cache.bytes_live == 0 and cache.pages_reserved_window == 0


def test_int8_pools_serve_through_the_gather(served):
    """The int8 store (the control) keeps pools as declared, a dequant
    scale a head, and the gather, over table and ring: the served
    tokens' logits stay close to the plain pools'."""
    cfg, model, params, key = served
    steps = 40
    rng = np.random.default_rng(5)
    seq = rng.integers(1, VOCAB, size=PROMPT + steps + 1)
    cache = _cache_for(model, params, 2, kv_dtype="int8")
    swa = cache.cache["model"]["layer_2"]["attention"]
    assert swa["pages_k"].shape == (2 * RING + 1, PAGE, 4, 192)
    assert swa["scale_v"].shape == (2 * RING + 1, PAGE, 4)
    got = np.stack(_drive(model, params, cache, {1: (seq, PROMPT)}, steps)[1])
    assert cache.in_place_layers == 0
    want = _reference_logits(cfg, key, seq[None, : PROMPT + steps])[0]
    assert np.abs(got - want[PROMPT - 1:]).max() < 0.02


def test_session_serves_and_says_what_it_reads(tmp_path):
    """``ServeSession.from_model`` with no switch, a prompt window of
    512 compiled at 256 and 512 rows: a left-padded prompt at each
    length, 40 tokens each (the window and three pages more). Greedy
    tokens are the reference's; every ``decode_step`` span says the
    bytes its attention read over both groups."""
    from tpudl.obs import spans as obs_spans

    cfg = tiny_config()
    model, params = _program(cfg, seq_len=560)
    key = ref.seed_key(11)
    session = ServeSession.from_model(model, params, 512, num_slots=2,
                                      page_size=PAGE)
    assert session.engine.prefill_lengths == (256, 512)
    recorder = obs_spans.enable(str(tmp_path / "spans.jsonl"))
    try:
        rng = np.random.default_rng(6)
        requests = [
            Request(request_id=i,
                    input_ids=rng.integers(1, VOCAB, n).tolist(),
                    max_new_tokens=40)
            for i, n in enumerate([300, 100])
        ]
        results = session.serve(requests)
    finally:
        obs_spans.disable()
    for r in requests:
        tokens = results[r.request_id].tokens
        seq = np.asarray(r.input_ids + tokens[:-1])[None]
        want = _reference_logits(cfg, key, seq)[0][len(r.input_ids) - 1:]
        margin = want.max(-1) - want[np.arange(len(tokens)), tokens]
        assert margin.max() < ATOL
    rows = sorted(s["rows"] for s in recorder.records
                  if s.get("kind") == "span" and s.get("name") == "prefill")
    assert rows == [256, 512]
    steps = [s for s in recorder.records
             if s.get("kind") == "span" and s.get("name") == "decode_step"]
    assert steps
    cache = session.engine.cache
    for s in steps:
        assert s["kv_bytes_live"] == (
            s["tokens_live"] * cache.row_bytes[0]
            + s["tokens_live_window"] * cache.row_bytes[1])
        assert 0 < s["tokens_live_window"] <= 2 * WINDOW
    assert "serve_kv_bytes_live" in registry().snapshot()["gauges"]
    assert cache.bytes_live == 0


# -- what is refused, with a sentence ----------------------------------------


REFUSALS = {
    "prefix_share": dict(prefix_share=True),
    "spec_k": dict(spec_k=2),
    "adapters": dict(adapters={"t": {}}),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_from_model_refuses_with_a_sentence(served, what):
    _, model, params, _ = served
    with pytest.raises(ValueError, match="is not wired to|are not wired to"):
        ServeSession.from_model(model, params, PROMPT, num_slots=2,
                                page_size=PAGE, **REFUSALS[what])


MLA = dict(attention="mla", kv_lora_rank=16, qk_nope_head_dim=16,
           qk_rope_head_dim=8, v_head_dim=16)
NOT_BUILT = {
    "a sink with latent attention": (
        dict(MLA, full_attention_sink=True), "latent attention keeps ONE"),
    "a value scale with latent attention": (
        dict(MLA, attention_value_scale=0.5), "latent attention keeps ONE"),
    "a value width with a stream": (
        dict(value_head_size=16, hyper_streams=2), "hyper_streams is not wired"),
    "a sink in a looped stack": (
        dict(full_attention_sink=True, loop_passes=2, sandwich_norm=True),
        "looped"),
    "sliding KV heads with no sliding layer": (
        dict(sliding_num_kv_heads=2), "layer_types names none"),
    "KV heads that do not divide": (
        dict(layer_types=("full_attention", "sliding_attention"),
             sliding_window=4, sliding_num_kv_heads=3),
        "must divide"),
}


@pytest.mark.parametrize("what", sorted(NOT_BUILT))
def test_the_configuration_refuses_with_a_sentence(what):
    change, sentence = NOT_BUILT[what]
    base = dict(vocab_size=32, hidden_size=32, num_layers=2, num_heads=4,
                num_kv_heads=2, intermediate_size=32, max_seq_len=16)
    with pytest.raises(ValueError, match=sentence):
        LlamaConfig(**{**base, **change})


def test_a_sliding_layer_steps_one_token_at_a_time(served):
    _, model, params, _ = served
    cache = _cache_for(model, params, 2)
    from tpudl.models.generate import paged_chunk_decode_fn

    step = paged_chunk_decode_fn(model, PAGE, False)
    with pytest.raises(ValueError, match="one token at a time"):
        jax.eval_shape(
            step, params, cache.cache, jnp.zeros((2, 3), jnp.int32),
            jnp.zeros((2, 3), jnp.int32), *cache.dispatch_args())
