"""Layers that differ in their attention under one page manager
(ISSUE 30): full-context layers beside sliding-window layers, each kind
with its own head count and rotary positions, a gate a head, routed
experts dispatched two ways.

The program is held to the plain reference of
``perfbench/reference/window_moe.py`` (float32, whole sequences, no
cache, no ring, no kernel) at tiny sizes with seeded weights: a batch-1
prefill of left-padded prompts, then paged decode through ring and
table until the context is over three windows, so that every ring
wraps at least twice. Then each mechanism against its own equation,
the page manager's group invariants, and every path that refuses,
which has to say why.

Tolerances. Program and reference are both float32 on the CPU here, so
what separates them is the order of their sums (blocked against whole
softmax, the experts' sum by sorted group against expert by expert):
logits of size ~0.2 agree to about 1e-6; ``ATOL`` = 2e-5 leaves room
for a longer context. A wrong mask, position, ring page or gate shows
at 1e-2 and more (asserted by breaking each below).
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
from perfbench.families import window_moe_serve as family
from perfbench.reference import window_moe as ref
from tpudl.models.generate import paged_decode_fn, prefill_fn
from tpudl.models.llama import LlamaForCausalLM, RopeScaling, rope
from tpudl.models.paged import PagedView
from tpudl.obs import registry
from tpudl.ops.moe import DroplessMoE
from tpudl.serve import Request, ServeSession
from tpudl.serve.cache import PagedKVCache

REPO = pathlib.Path(__file__).resolve().parents[1]
ATOL = 2e-5
WINDOW, PAGE, SEQ, PROMPT = 16, 4, 96, 24
#: ceil(16 / 4) + 1 pages a ring.
RING = 5


def tiny_config(**over) -> dict:
    """The published configuration file, shrunk: the same keys, the same
    pattern of layers (full, window x 3, full), 6 and 8 query heads on
    2 KV heads, 8 experts of which 2 a token."""
    with open(REPO / "perfbench/configs/laguna-xs2-l5.json") as f:
        cfg = json.load(f)
    cfg.update(
        vocab_size=97, hidden_size=32, intermediate_size=64, head_dim=16,
        num_key_value_heads=2,
        num_attention_heads_per_layer=[6, 8, 8, 8, 6],
        num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, sliding_window=WINDOW,
        torch_dtype="float32",
    )
    cfg["rope_parameters"] = json.loads(json.dumps(cfg["rope_parameters"]))
    # YaRN's blend has to fall inside the 8 rotated dimensions.
    cfg["rope_parameters"]["full_attention"].update(
        original_max_position_embeddings=8, beta_fast=4
    )
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def served():
    """(config, model, params, key) with weights large enough that the
    logits are not all alike."""
    cfg = tiny_config()
    model = LlamaForCausalLM(family.model_config(cfg, SEQ, jnp.float32))
    s = ref.settings(cfg)
    key = ref.seed_key(11)
    params = family.to_flax(ref.all_weights(key, s, jnp.float32), s)
    return cfg, model, params, key


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


def test_prefill_and_ring_decode_match_the_reference(served):
    """Three slots, one idle; prompts of 24 and 7 tokens in a window of
    24; 60 steps take the context to 84 and 67 positions: over three
    windows of 16, and the five-page rings wrap three times."""
    cfg, model, params, key = served
    steps = 60
    rng = np.random.default_rng(0)
    sequences = {
        0: (rng.integers(1, 97, size=PROMPT + steps + 1), PROMPT),
        2: (rng.integers(1, 97, size=7 + steps + 1), 7),
    }
    cache = _cache_for(model, params, 3)
    assert cache.window == WINDOW and cache.ring_pages == RING
    copies = registry().counter("serve_kv_pool_copies").value
    got = _drive(model, params, cache, sequences, steps)
    assert registry().counter("serve_kv_pool_copies").value == copies
    assert (cache.lens[0] - PROMPT) // PAGE >= 2 * RING  # wrapped twice
    for slot, (seq, n) in sequences.items():
        want = _reference_logits(cfg, key, seq[None, : n + steps])[0]
        have = np.stack(got[slot])
        assert want[n - 1:].std() > 0.05  # logits that tell tokens apart
        np.testing.assert_allclose(have, want[n - 1:], atol=ATOL)


@pytest.mark.parametrize("broken", ["window", "gate", "rotary", "ring"])
def test_the_comparison_sees_a_wrong_mechanism(served, broken, monkeypatch):
    """Each mechanism, taken out of the program, moves the logits by far
    more than the tolerance."""
    cfg, model, params, key = served
    steps = 30
    rng = np.random.default_rng(1)
    seq = rng.integers(1, 97, size=PROMPT + steps + 1)
    mcfg = model.cfg
    if broken == "window":
        mcfg = dataclasses.replace(mcfg, sliding_window=2 * WINDOW)
    elif broken == "gate":
        params = jax.tree.map(lambda x: x, params)
        g = params["model"]["layer_1"]["attention"]["g_proj"]
        g["kernel"] = jnp.zeros_like(g["kernel"])
    elif broken == "rotary":
        mcfg = dataclasses.replace(mcfg, partial_rotary_factor=1.0)
    elif broken == "ring":
        # A ring that puts every page one place further round.
        real = PagedView.ring_view

        def shifted(self, window):
            view = real(self, window)
            view.page_table = jnp.roll(view.page_table, 1, axis=1)
            return view

        monkeypatch.setattr(PagedView, "ring_view", shifted)
    wrong = LlamaForCausalLM(mcfg)
    cache = _cache_for(wrong, params, 1)
    got = np.stack(_drive(wrong, params, cache, {0: (seq, PROMPT)}, steps)[0])
    want = _reference_logits(cfg, key, seq[None, : PROMPT + steps])[0]
    # Half a head's rotation is the smallest of the four: 1.3e-3, still
    # sixty times the tolerance; the others move whole hundredths.
    assert np.abs(got - want[PROMPT - 1:]).max() > (
        1e-3 if broken == "rotary" else 1e-2
    )


def test_blocked_prefill_is_the_dense_prefill(served, monkeypatch):
    """A long prompt into an empty cache is attended in blocks of
    queries, window layers over their band, and the head runs on the
    last row alone: the same logits and the same rows as one dense
    pass."""
    _, model, params, _ = served
    rng = np.random.default_rng(2)
    pad = 5
    ids = np.concatenate(
        [np.zeros(pad, np.int32), rng.integers(1, 97, size=PROMPT - pad)]
    )[None]
    mask = (np.arange(PROMPT) >= pad).astype(np.int32)[None]
    dense = jax.jit(prefill_fn(model))(params, ids, mask)
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 0)
    monkeypatch.setattr(llama, "PREFILL_BLOCK", 8)
    blocked = jax.jit(prefill_fn(model))(params, ids, mask)
    np.testing.assert_allclose(blocked[0], dense[0], atol=ATOL)
    for a, b in zip(jax.tree.leaves(blocked[1]), jax.tree.leaves(dense[1])):
        if a.ndim == 4:  # rows [1, T, Hkv, D]: the prompt's, not the pad's
            a, b = a[:, pad:PROMPT], b[:, pad:PROMPT]
        np.testing.assert_allclose(a, b, atol=ATOL)
    np.testing.assert_array_equal(blocked[2], dense[2])


def test_layers_have_their_own_heads(served):
    _, model, params, _ = served
    layers = params["model"]
    for i, heads in enumerate((6, 8, 8, 8, 6)):
        attn = layers[f"layer_{i}"]["attention"]
        assert attn["q_proj"]["kernel"].shape == (32, heads * 16)
        assert attn["o_proj"]["kernel"].shape == (heads * 16, 32)
        assert attn["g_proj"]["kernel"].shape == (32, heads)
        assert attn["k_proj"]["kernel"].shape == (32, 2 * 16)
        spec = model.cfg.layer_spec(i)
        assert spec.num_heads == heads
        assert spec.window == (WINDOW if heads == 8 else 0)
        assert spec.rotary_dim == (16 if heads == 8 else 8)
    assert model.cfg.window_layers == 3
    assert "gate_proj" in layers["layer_0"] and "moe" in layers["layer_1"]


def test_partial_rotary_yarn_is_its_equation():
    """The first ``rotary_dim`` values of a head rotate by YaRN's blended
    frequencies over that many dimensions, cos and sin times the stated
    ``attention_factor``; the rest pass."""
    scaling = RopeScaling(
        factor=64.0, original_max_position=4096, beta_fast=64.0,
        beta_slow=1.0, attention_factor=1.41589,
    )
    assert scaling.cos_sin_scale == 1.41589 and scaling.attention_scale == 1.0
    x = jax.random.normal(jax.random.key(0), (1, 5, 3, 128))
    pos = jnp.asarray([[0, 1, 7, 300, 5000]])
    got = np.asarray(rope(x, pos, 500_000.0, scaling, 64))
    i = np.arange(32)
    plain = 500_000.0 ** (-2 * i / 64)
    def turns(r):
        return 64 * np.log(4096 / (r * 2 * np.pi)) / (2 * np.log(500_000.0))
    low, high = np.floor(turns(64.0)), np.ceil(turns(1.0))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    freq = plain / 64.0 * ramp + plain * (1 - ramp)
    ang = np.asarray(pos)[0][:, None] * freq
    cos, sin = 1.41589 * np.cos(ang)[:, None], 1.41589 * np.sin(ang)[:, None]
    x = np.asarray(x)[0]
    want = np.concatenate([
        x[..., :32] * cos - x[..., 32:64] * sin,
        x[..., 32:64] * cos + x[..., :32] * sin,
        x[..., 64:],
    ], -1)
    # float32 angles: position 5,000 times a frequency near 1 is good
    # to about 3e-4 radians.
    np.testing.assert_allclose(got[0], want, atol=1e-3)
    np.testing.assert_allclose(got[0, :3], want[:3], atol=1e-5)
    # The whole head, plainly, is what every uniform configuration gets.
    whole = np.asarray(rope(jnp.asarray(x)[None], pos, 10_000.0))
    again = np.asarray(rope(jnp.asarray(x)[None], pos, 10_000.0, None, 128))
    np.testing.assert_array_equal(whole, again)


def test_gate_scales_each_head(served):
    """``ctx_h <- sigmoid(x W_gamma)_h ctx_h`` before ``W_o``: a zero
    ``W_gamma`` is a gate of one half on every head, and a gate that
    differs by head is that head's share of ``W_o`` scaled."""
    _, model, params, _ = served
    gated = llama.LlamaAttention(model.cfg, 1)
    ungated = llama.LlamaAttention(
        dataclasses.replace(model.cfg, attention_gate=False), 1
    )
    p = dict(params["model"]["layer_1"]["attention"])
    rest = {k: v for k, v in p.items() if k != "g_proj"}
    x = jax.random.normal(jax.random.key(3), (1, 6, 32))
    pos = jnp.arange(6)[None]
    plain = ungated.apply({"params": rest}, x, pos)
    zero = {"kernel": jnp.zeros_like(p["g_proj"]["kernel"])}
    half = gated.apply({"params": dict(rest, g_proj=zero)}, x, pos)
    np.testing.assert_allclose(half, 0.5 * plain, atol=1e-6)
    # The layer's own gate, head by head, against the equation: the
    # ungated context of head h is what W_o's rows of head h see.
    gate = jax.nn.sigmoid(x @ p["g_proj"]["kernel"])  # [1, 6, 8]
    o = p["o_proj"]["kernel"].reshape(8, 16, 32)
    want = 0.0
    for h in range(8):
        only = rest | {"o_proj": {"kernel": jnp.zeros_like(o).at[h].set(
            o[h]).reshape(128, 32)}}
        want = want + gate[..., h:h + 1] * ungated.apply(
            {"params": only}, x, pos)
    np.testing.assert_allclose(
        gated.apply({"params": p}, x, pos), want, atol=1e-6)


@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all", "share"])
def test_sorted_and_dense_dispatch_agree(held):
    kw = dict(num_experts=8, experts_per_token=2, intermediate_size=32,
              shared_intermediate_size=32, routed_scaling_factor=2.5,
              experts_held=held, dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, 64))
    real = jnp.ones((2, 24), bool).at[0, :5].set(False)
    dense, by_group = (DroplessMoE(dispatch=d, **kw)
                       for d in ("dense", "sorted"))
    params = jax.tree.map(
        lambda a: 20 * a, dense.init(jax.random.key(0), x, real)["params"]
    )
    want, sown = dense.apply({"params": params}, x, real,
                             mutable=["moe_stats"])
    got, sown_sorted = by_group.apply({"params": params}, x, real,
                                      mutable=["moe_stats"])
    assert np.abs(np.asarray(want)).max() > 10
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)
    np.testing.assert_array_equal(
        sown["moe_stats"]["tokens_per_expert"][0],
        sown_sorted["moe_stats"]["tokens_per_expert"][0],
    )


def test_dispatch_is_chosen_by_the_traced_rows(monkeypatch):
    import tpudl.ops.moe as moe

    monkeypatch.setattr(moe, "SORTED_DISPATCH_ROWS", 16)
    layer = DroplessMoE(num_experts=8, experts_per_token=2,
                        intermediate_size=8, dtype=jnp.float32)
    few, many = jnp.zeros((2, 8, 16)), jnp.zeros((2, 9, 16))
    params = layer.init(jax.random.key(0), few, jnp.ones((2, 8), bool))
    reg = registry()
    before = {d: reg.counter(f"serve_moe_dispatch_{d}").value
              for d in ("dense", "sorted")}
    layer.apply(params, few, jnp.ones((2, 8), bool), mutable=["moe_stats"])
    layer.apply(params, many, jnp.ones((2, 9), bool), mutable=["moe_stats"])
    assert reg.counter("serve_moe_dispatch_dense").value == before["dense"] + 1
    assert reg.counter("serve_moe_dispatch_sorted").value == before["sorted"] + 1


def test_ring_view_is_the_rotated_table():
    """Position t on ring page (t // page) mod R; the view starts at the
    page of the window's first position and counts from there."""
    ring = jnp.asarray([[11, 12, 13, 14, 15], [0, 0, 0, 0, 0]], jnp.int32)
    view = PagedView(
        (jnp.zeros((2, 24), jnp.int32), ring),
        jnp.asarray([3, 0], jnp.int32), jnp.asarray([41, 0], jnp.int32),
        PAGE, False,
    )
    rotated = view.ring_view(WINDOW)
    # Window of position 41: [26, 41]; page 6 first, on ring place 1.
    np.testing.assert_array_equal(
        rotated.page_table[0], [12, 13, 14, 15, 11]
    )
    assert int(rotated.start[0]) == 26 - 24 and int(rotated.lens[0]) == 41 - 24
    # An idle slot stays on the trash page at position 0.
    np.testing.assert_array_equal(rotated.page_table[1], [0] * 5)
    assert int(rotated.start[1]) == 0 and int(rotated.lens[1]) == 0
    assert view.ring_view(WINDOW) is rotated  # once a program
    # Early in a sequence the left pad bounds the window.
    early = PagedView(
        (jnp.zeros((1, 24), jnp.int32), ring[:1]),
        jnp.asarray([9], jnp.int32), jnp.asarray([12], jnp.int32),
        PAGE, False,
    ).ring_view(WINDOW)
    np.testing.assert_array_equal(early.page_table[0], [13, 14, 15, 11, 12])
    assert int(early.start[0]) == 1 and int(early.lens[0]) == 4
    with pytest.raises(ValueError, match="ring table"):
        PagedView(jnp.zeros((1, 4), jnp.int32), ring[0, :1], ring[0, :1],
                  PAGE, False).ring_view(WINDOW)


def test_kernel_reads_the_ring_in_place(monkeypatch):
    """The in-place kernel (interpret mode on the CPU) over the rotated
    view equals the gather over it, at 6 and 8 query heads a KV head:
    the window layer's read is PR 27's kernel, unchanged."""
    rng = np.random.default_rng(4)
    slots, hkv, d, ps, window = 3, 2, 128, 4, 16
    ring_pages = window // ps + 1
    pool = [jnp.asarray(rng.normal(size=(slots * ring_pages + 1, ps, hkv, d)),
                        jnp.float32) for _ in "kv"]
    ring = jnp.asarray(
        1 + rng.permutation(slots * ring_pages).reshape(slots, ring_pages),
        jnp.int32,
    )
    view = PagedView(
        (jnp.zeros((slots, 30), jnp.int32), ring),
        jnp.asarray([0, 50, 7], jnp.int32),
        jnp.asarray([5, 77, 118], jnp.int32), ps, False,
    ).ring_view(window)
    for heads in (12, 16):
        q = jnp.asarray(rng.normal(size=(slots, 1, heads, d)), jnp.float32)
        want = pa.paged_attention(q, *pool, view, impl="reference")
        got = pa.paged_attention(q, *pool, view, impl="fused")
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_pages_by_group_after_seat_and_free(served):
    _, model, params, _ = served
    cache = _cache_for(model, params, 4)
    pools = cache.cache["model"]
    full = (4 * (SEQ // PAGE) + 1, PAGE, 2, 16)
    rings = (4 * RING + 1, PAGE, 2, 16)
    for i, shape in enumerate((full, rings, rings, rings, full)):
        assert pools[f"layer_{i}"]["attention"]["pages_k"].shape == shape
    assert cache.nbytes == (
        4 * (2 * 2 * np.prod(full) + 3 * 2 * np.prod(rings))
        + cache.page_table.nbytes + cache.ring_table.nbytes
        + cache.start.nbytes + cache.lens.nbytes
    )
    prefill = jax.jit(prefill_fn(model))
    ids = np.ones((1, PROMPT), np.int32)
    _, row, _ = prefill(params, ids, ids)
    free_full, free_ring = cache.free_pages, len(cache._free_ring)
    assert cache.fits_tokens(PROMPT + 40)
    cache.seat(row, 1, 0, PROMPT, PROMPT + 40)
    _, row, _ = prefill(params, ids, ids)
    cache.seat(row, 3, 4, PROMPT, PROMPT + 8)
    assert cache.pages_reserved == 16 + 8
    assert cache.pages_reserved_window == 2 * RING
    assert cache.free_pages == free_full - 24
    assert len(cache._free_ring) == free_ring - 2 * RING
    assert cache.tokens_live == PROMPT + PROMPT - 4
    assert cache.tokens_live_window == 2 * WINDOW
    # The ring holds the prompt's last pages where their numbers fall:
    # logical page j on place j mod R.
    ring = cache.ring_table[1]
    assert sorted(ring) == sorted(cache._rings[1]) and 0 not in ring
    k_row = np.asarray(row["model"]["layer_1"]["attention"]["k"])[0]
    k_pool = np.asarray(cache.cache["model"]["layer_1"]["attention"]["pages_k"])
    for j in range(PROMPT // PAGE - RING, PROMPT // PAGE):
        np.testing.assert_array_equal(
            k_pool[cache.ring_table[3][j % RING]],
            k_row[j * PAGE:(j + 1) * PAGE],
        )
    table, start, lens = cache.dispatch_args()
    assert table[0].shape == (4, SEQ // PAGE) and table[1].shape == (4, RING)
    cache.free(1)
    cache.free(3)
    assert cache.free_pages == free_full
    assert len(cache._free_ring) == free_ring
    assert cache.pages_reserved == cache.pages_reserved_window == 0
    assert cache.tokens_live_window == 0
    assert not cache.ring_table.any()


def test_a_slot_waits_for_a_ring(served):
    _, model, params, _ = served
    cache = _cache_for(model, params, 2)
    cache._free_ring = cache._free_ring[: RING - 1]
    assert not cache.fits_tokens(8)
    ids = np.ones((1, PROMPT), np.int32)
    _, row, _ = jax.jit(prefill_fn(model))(params, ids, ids)
    with pytest.raises(RuntimeError, match="ring"):
        cache.seat(row, 0, 0, PROMPT, PROMPT + 4)


def test_int8_pools_serve_window_layers(served):
    """The int8 store keeps the gather, over the rotated view like any
    table: the served tokens' logits stay close to the plain pools'."""
    cfg, model, params, key = served
    steps = 40
    rng = np.random.default_rng(5)
    seq = rng.integers(1, 97, size=PROMPT + steps + 1)
    cache = _cache_for(model, params, 2, kv_dtype="int8")
    assert cache.cache["model"]["layer_2"]["attention"]["scale_k"].shape == (
        2 * RING + 1, PAGE, 2
    )
    got = np.stack(_drive(model, params, cache, {1: (seq, PROMPT)}, steps)[1])
    want = _reference_logits(cfg, key, seq[None, : PROMPT + steps])[0]
    assert np.abs(got - want[PROMPT - 1:]).max() < 0.02


def test_session_serves_and_says_what_it_holds(served, tmp_path):
    """``ServeSession.from_model`` with no switch: greedy tokens are the
    reference's, the spans carry both groups' counters."""
    from tpudl.obs import spans as obs_spans

    cfg, model, params, key = served
    session = ServeSession.from_model(model, params, PROMPT, num_slots=2,
                                      page_size=PAGE)
    recorder = obs_spans.enable(str(tmp_path / "spans.jsonl"))
    try:
        rng = np.random.default_rng(6)
        requests = [
            Request(request_id=i, input_ids=rng.integers(1, 97, n).tolist(),
                    max_new_tokens=t)
            for i, (n, t) in enumerate([(PROMPT, 50), (9, 30), (17, 44)])
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
    steps = [s for s in recorder.records
             if s.get("kind") == "span" and s.get("name") == "decode_step"]
    assert steps
    for s in steps:
        assert s["pages_reserved_window"] in (RING, 2 * RING)
        assert 0 < s["tokens_live_window"] <= 2 * WINDOW
        assert s["tokens_live_window"] <= s["tokens_live"]
    gauges = registry().snapshot()["gauges"]
    assert "serve_kv_pages_reserved_window" in gauges
    assert "serve_kv_tokens_live_window" in gauges
    cache = session.engine.cache
    assert cache.pages_reserved == cache.pages_reserved_window == 0


REFUSALS = {
    "prefix_share": dict(prefix_share=True),
    "spec_k": dict(spec_k=2),
    "adapters": dict(adapters={"t": {}}),
}


@pytest.mark.parametrize("what", sorted(REFUSALS))
def test_from_model_refuses_with_a_sentence(served, what):
    _, model, params, _ = served
    with pytest.raises(ValueError, match="not wired to|cannot compose"):
        ServeSession.from_model(model, params, PROMPT, num_slots=2,
                                page_size=PAGE, **REFUSALS[what])


def test_the_cache_refuses_what_walks_one_table(served):
    _, model, params, _ = served
    with pytest.raises(ValueError, match="prefix sharing is not wired"):
        _cache_for(model, params, 2, prefix_share=True)
    cache = _cache_for(model, params, 2)
    for call in (
        lambda: cache.export_request(0, {}),
        lambda: cache.import_request(b"", 0),
        lambda: cache.commit(None),
    ):
        with pytest.raises(ValueError, match="not wired to window layers"):
            call()
    from tpudl.export.decode import export_serving_decoder

    with pytest.raises(ValueError, match="not wired to window layers"):
        export_serving_decoder(model, params, num_slots=2,
                               prompt_len=PROMPT, page_size=PAGE)


def test_a_window_layer_steps_one_token_at_a_time(served):
    from tpudl.models.generate import paged_chunk_decode_fn

    _, model, params, _ = served
    cache = _cache_for(model, params, 2)
    verify = paged_chunk_decode_fn(model, PAGE, False)
    tokens = jnp.zeros((2, 3), jnp.int32)
    with pytest.raises(ValueError, match="one token at a time"):
        jax.eval_shape(verify, params, cache.cache, tokens, tokens,
                       *cache.dispatch_args())


def test_two_window_sizes_are_refused():
    template = {
        "a": {"k": jnp.zeros((1, 8, 1, 4)), "valid": jnp.zeros((1, 8), bool),
              "index": jnp.zeros((), jnp.int32),
              "window": jnp.zeros((4,), jnp.int8)},
        "b": {"k": jnp.zeros((1, 8, 1, 4)), "valid": jnp.zeros((1, 8), bool),
              "index": jnp.zeros((), jnp.int32),
              "window": jnp.zeros((6,), jnp.int8)},
    }
    with pytest.raises(ValueError, match="one ring size"):
        PagedKVCache(template, page_size=2)


def test_config_says_what_is_wrong():
    tiny = llama.LLAMA_TINY
    with pytest.raises(ValueError, match="names 1 layers"):
        tiny(layer_types=("full_attention",))
    with pytest.raises(ValueError, match="sliding_window"):
        tiny(layer_types=("full_attention", "sliding_attention"))
    with pytest.raises(ValueError, match="must be"):
        tiny(layer_types=("full_attention", "chunked"), sliding_window=4)
