"""The shortcut-connected double layer (two latent attentions and two
dense FFNs around one routed-expert branch), the low-rank latent query
with its two scales, and the softmax router with identity experts,
through the normal serving path (ISSUE 33), at a tiny size on the CPU.

The program is held to ``perfbench/reference/shortcut_moe.py`` (float32
at ``highest``, whole sequences, no cache, no absorption, every held
expert applied plainly). Float32 throughout, so a tolerance is float32
rounding over a few hundred operations a logit (2e-4, as
``tests/test_latent_moe.py`` holds its program) unless it says
otherwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families.shortcut_moe_serve import model_config, to_flax
from perfbench.reference import shortcut_moe as ref
from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.ops.moe import DroplessMoE
from tpudl.serve import Request, ServeSession

#: Hidden 64, 4 heads, a latent row of 48 + 16 = 64 (held folded, two
#: positions a row of 128 lanes, so that the latent kernel can read it
#: in interpret mode), query rank 32, 32 routed + 16 identity experts of
#: which experts 8-15 are held, 6 choices a token, 2 double layers: the
#: published model's shape at a size a test can hold.
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 48,
    "q_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
    "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32, "num_layers": 2,
    "n_routed_experts": 8, "zero_expert_num": 16, "moe_topk": 6,
    "routed_scaling_factor": 6, "vocab_size": 256, "rms_norm_eps": 1e-5,
    "rope_theta": 10000,
    "deployment": {"routed_experts": 32, "router_experts": 48,
                   "first_expert": 8},
}
MAX_SEQ, WINDOW, PAGE, SLOTS = 64, 16, 16, 3
SETTINGS = ref.settings(CONFIG)
TOL = 2e-4


@pytest.fixture(scope="module")
def served():
    """(model, params, key): float32, the reference's weights laid over
    the program's tree."""
    key = ref.seed_key(2**31 + 33)
    model = LlamaForCausalLM(model_config(CONFIG, MAX_SEQ, jnp.float32))
    params = to_flax(ref.all_weights(key, SETTINGS, jnp.float32), SETTINGS)
    return model, params, key


def _session(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, WINDOW, **kw)


def _requests(seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(f"r{i}", rng.integers(1, 256, size=n).tolist(),
                max_new_tokens=m)
        for i, (n, m) in enumerate(
            [(5, 9), (WINDOW, 4), (3, 12), (7, 6), (2, 5)])
    ]


def _teacher_forced(reqs, got):
    """(ids [B, S], picks [B, T], chosen [B, T], valid [B, T]): every
    request's prompt and served tokens, right-padded, and where each
    served token was chosen."""
    t_max = max(r.max_new_tokens for r in reqs)
    ids = np.zeros((len(reqs), WINDOW + t_max), np.int32)
    picks = np.zeros((len(reqs), t_max), np.int32)
    chosen = np.zeros((len(reqs), t_max), np.int32)
    valid = np.zeros((len(reqs), t_max), bool)
    for row, r in enumerate(reqs):
        tokens = list(got[r.request_id].tokens)
        seq = list(r.input_ids) + tokens[:-1]
        ids[row, :len(seq)] = seq
        picks[row, :len(tokens)] = len(r.input_ids) - 1 + np.arange(len(tokens))
        chosen[row, :len(tokens)] = tokens
        valid[row, :len(tokens)] = True
    return ids, picks, chosen, valid


# -- (a) the full forward, (b) the served path, (c) absorbed decode ----------


def test_full_forward_agrees_with_the_reference(served):
    """(a) The up-projected (training / scoring) path, whole sequences:
    the double layer's wiring, the low-rank query and both scales, the
    softmax router and the identity term, all at once."""
    model, params, key = served
    ids = jnp.asarray(
        np.random.default_rng(3).integers(1, 256, size=(2, 24)), jnp.int32)
    want = ref.logits(key, CONFIG, jnp.float32, ids)
    got = model.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("path, layers", [("gather", 0), ("in_place", 4)])
def test_served_tokens_are_the_references(
        served, path, layers, monkeypatch, tmp_path):
    """(b) Prefill, then decode through the engine and the paged pools,
    more requests than slots and every length different, against the
    reference's whole-sequence forward: each served token is the
    reference's best to within float32 rounding. (c) The same tokens
    against the program's OWN up-projected forward: the absorbed decode
    is the up-projected attention. Both on the gather path and with the
    latent kernel reading all FOUR pools (two a layer) in place, which
    the cache, the gauge and every ``decode_step`` span say; no pool is
    copied, and a layer's two row leaves are two pools."""
    import tpudl.ops.paged_attention as pa

    model, params, key = served
    if path == "in_place":
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    copies = registry().counter("serve_kv_pool_copies").value
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params)
        reqs = _requests()
        got = sess.serve(reqs)
        steps = [r for r in rec.records
                 if r.get("kind") == "span" and r.get("name") == "decode_step"]
    finally:
        obs_spans.disable()
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert all(len(got[r.request_id].tokens) == r.max_new_tokens for r in reqs)
    ids, picks, chosen, valid = map(jnp.asarray, _teacher_forced(reqs, got))
    gaps = np.asarray(ref.margins(key, CONFIG, jnp.float32, ids, picks, chosen))
    assert gaps[np.asarray(valid)].max() <= TOL
    own = model.apply({"params": params}, ids)
    at_picks = jnp.take_along_axis(own, picks[..., None], axis=1)
    own_gap = at_picks.max(-1) - jnp.take_along_axis(
        at_picks, chosen[..., None], axis=-1)[..., 0]
    assert np.asarray(own_gap)[np.asarray(valid)].max() <= TOL
    cache = sess.engine.cache
    pools = jax.tree_util.tree_flatten_with_path(cache.cache)[0]
    assert [jax.tree_util.keystr(p) for p, _ in pools] == [
        f"['model']['layer_{n}']['attention_{i}']['pages_kv']"
        for n in (0, 1) for i in (0, 1)]
    assert cache.folds == (2,) * 4 and cache.in_place_layers == layers
    assert registry().gauge("serve_paged_attention_in_place").value == layers
    assert registry().gauge("serve_kv_pool_folded_layers").value == 4
    assert registry().counter("serve_kv_pool_copies").value == copies
    assert steps and all(
        s["kv_in_place"] == int(layers > 0) for s in steps)
    # ``tokens_live`` stays positions a slot: ONE pool's, not four.
    assert all(s["tokens_live"] <= SLOTS * MAX_SEQ for s in steps)


def test_the_cache_counts_every_pool(served):
    """The bytes are four pools' of 64 values a position."""
    model, params, _ = served
    cache = _session(model, params).engine.cache
    host = cache.page_table.nbytes + cache.start.nbytes + cache.lens.nbytes
    assert cache.nbytes - host == 4 * cache.num_pages * PAGE * 64 * 4


def test_int8_control_runs_and_parts_from_the_reference(served):
    """The control's path: the quantizer's rules reach both low-rank
    query matrices, the dense FFNs and the experts (router, latent
    down-projection and W_kvb kept), the pools are int8 rows through the
    gather, and it serves further from the reference than float32."""
    from tpudl.quant import quantize_model

    model, params, key = served
    sess = _session(model, params, weight_dtype="int8", kv_dtype="int8")
    layer = sess.engine.params["model"]["layer_1"]
    for site in (layer["attention_0"]["q_a_proj"],
                 layer["attention_1"]["q_b_proj"],
                 layer["attention_1"]["o_proj"], layer["mlp_0"]["gate_proj"],
                 layer["mlp_1"]["down_proj"], layer["moe"]["up_proj"]):
        assert set(site["kernel"]) == {"qvalues", "qscale"}
    assert layer["moe"]["router"]["kernel"].dtype == jnp.float32
    assert layer["attention_0"]["kv_a_proj"]["kernel"].dtype == jnp.float32
    assert layer["attention_0"]["kv_b_proj"].dtype == jnp.float32
    reqs = _requests()
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert sess.engine.cache.in_place_layers == 0
    ids = jnp.asarray(
        np.random.default_rng(4).integers(1, 256, size=(2, 24)), jnp.int32)
    want = np.asarray(ref.logits(key, CONFIG, jnp.float32, ids))
    sound = np.abs(np.asarray(model.apply({"params": params}, ids)) - want)
    qmodel, qparams = quantize_model(model, params, "int8")
    control = np.abs(np.asarray(qmodel.apply({"params": qparams}, ids)) - want)
    assert control.mean() > 10 * sound.mean() and control.mean() > 1e-5


# -- the expert layer: (d) both forms, (e) the share, (f) all or none --------


def _layer(held, dispatch="auto", zero=16):
    return DroplessMoE(
        num_experts=32, experts_per_token=6, intermediate_size=32,
        routed_scaling_factor=6.0, experts_held=held, dtype=jnp.float32,
        scoring="softmax", renormalize=False, zero_experts=zero,
        dispatch=dispatch,
    )


def _layer_weights(seed=5):
    """An uncut layer's expert branch: all 32 routed experts held. The
    bias is made large enough to decide some choices of so flat a
    softmax (scores near 1 / 48)."""
    s = dict(SETTINGS, experts_held=32, first_expert=0)
    w = ref.layer_weights(ref.seed_key(seed), 0, s, jnp.float32)
    return s, dict(w, router_bias=5 * w["router_bias"])


def _params(w, first, count):
    held = slice(first, first + count)
    return {
        "router": {"kernel": w["router"]}, "router_bias": w["router_bias"],
        **{f"{n}_proj": {"kernel": w[f"experts_{n}"][held]}
           for n in ("gate", "up", "down")},
    }


def _apply(layer, params, x, real=None):
    real = jnp.ones(x.shape[:2], bool) if real is None else real
    y, state = layer.apply({"params": params}, x, real,
                           mutable=["moe_stats"])
    stats = state["moe_stats"]
    return (y, np.asarray(stats["tokens_per_expert"][0]),
            np.asarray(stats["real_experts_a_token"][0]))


def _tokens(shape, seed=1):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=shape), jnp.float32)


@pytest.mark.parametrize("held", [None, (8, 8)], ids=["uncut", "share"])
def test_both_dispatch_forms_agree_with_identity_ids_present(held):
    """(d) The dense form has no column for an identity id and the
    sorted form sorts it behind the last group: the same sum (float32
    rounding of sums taken in another order: 1e-5), the same counts."""
    _, w = _layer_weights()
    first, count = held or (0, 32)
    x = _tokens((2, 9, 64))
    dense = _apply(_layer(held, "dense"), _params(w, first, count), x)
    by_groups = _apply(_layer(held, "sorted"), _params(w, first, count), x)
    np.testing.assert_allclose(
        np.asarray(dense[0]), np.asarray(by_groups[0]), atol=1e-5)
    np.testing.assert_array_equal(dense[1], by_groups[1])
    np.testing.assert_array_equal(dense[2], by_groups[2])
    # 18 tokens, and some chose an identity expert.
    assert dense[2].sum() == 18 and dense[2][:6].sum() > 0


@pytest.mark.parametrize("count", [8, 16])
def test_the_shares_and_the_identity_term_once_are_the_uncut_layer(count):
    """(e) At 32 real + 16 identity experts: every share computes its
    own experts' part and, for its own tokens, the identity term. The
    routed parts of all the shares plus the identity term COUNTED ONCE
    are the uncut layer, and the uncut layer is the reference's."""
    s, w = _layer_weights()
    x = _tokens((2, 9, 64))
    flat = x.reshape(-1, 64)
    whole, counts, chose = _apply(_layer(None), _params(w, 0, 32), x)
    want = ref.experts(flat, w, s).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=1e-5)
    identity = (
        ref.route(flat, w, s)[:, 32:].sum(-1, keepdims=True) * flat
    ).reshape(x.shape)
    assert float(jnp.abs(identity).max()) > 0.1
    # Dropless: every choice of a real expert is served by some share.
    assert counts.sum() == (chose * np.arange(7)).sum()
    total, seen = identity, []
    for first in range(0, 32, count):
        part, c, chose_here = _apply(
            _layer((first, count)), _params(w, first, count), x)
        total = total + (part - identity)
        seen.append(c)
        np.testing.assert_array_equal(chose_here, chose)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(seen), counts)


def test_a_token_of_identity_choices_only_and_one_of_none():
    """(f) A router that sends token 0 to identity experts only and
    token 1 to real experts only: the first comes back as itself times
    the sum of its gates (no expert matmul touches it: held experts see
    no token of it), the second has no identity term, and the bias
    never weighs."""
    s, w = _layer_weights()
    router = np.zeros((64, 48), np.float32)
    router[0, 32:] = 4.0   # feature 0 -> identity experts
    router[1, :32] = 4.0   # feature 1 -> real experts
    w = dict(w, router=jnp.asarray(router))
    x = np.zeros((1, 2, 64), np.float32)
    x[0, 0, 0] = x[0, 1, 1] = 1.0
    x[0, :, 2:] = np.random.default_rng(7).normal(size=(2, 62)) * 0.1
    x = jnp.asarray(x)
    y, counts, chose = _apply(_layer(None), _params(w, 0, 32), x)
    gates = np.asarray(ref.route(x[0], w, s))
    assert (gates[0, :32] == 0).all() and (gates[0, 32:] > 0).sum() == 6
    assert (gates[1, 32:] == 0).all() and (gates[1, :32] > 0).sum() == 6
    np.testing.assert_allclose(
        np.asarray(y[0, 0]), gates[0].sum() * np.asarray(x[0, 0]), atol=1e-6)
    # One token chose no real expert, the other six.
    np.testing.assert_array_equal(chose, [1, 0, 0, 0, 0, 0, 1])
    assert counts.sum() == 6
    only, _, _ = _apply(
        _layer(None), _params(w, 0, 32), x, jnp.asarray([[True, False]]))
    want = ref.experts(x[0], w, s)
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(only), np.asarray(y), atol=0)
    # Scores are the softmax's own, times 6, not renormalised: the six
    # chosen of a token do not sum to 6.
    assert 0 < gates[1].sum() < 6 * 0.99


def test_the_sigmoid_router_is_what_it_was_and_a_scoring_is_named():
    x = _tokens((1, 4, 64))
    real = jnp.ones((1, 4), bool)
    layer = DroplessMoE(num_experts=8, experts_per_token=2,
                        intermediate_size=32, dtype=jnp.float32)
    state = layer.init(jax.random.key(0), x, real)
    assert state["params"]["router"]["kernel"].shape == (64, 8)
    _, stats = layer.apply(state, x, real, mutable=["moe_stats"])
    assert set(stats["moe_stats"]) == {"tokens_per_expert"}
    with pytest.raises(ValueError, match="scoring must be one of"):
        dataclasses.replace(layer, scoring="tanh").init(
            jax.random.key(0), x, real)


# -- the configuration --------------------------------------------------------


def test_the_config_names_the_block_and_refuses_what_it_cannot_build():
    cfg = model_config(CONFIG, MAX_SEQ, jnp.float32)
    assert cfg.block == "shortcut" and cfg.expert_layers == 2
    assert (cfg.q_lora_rank, cfg.mla_scale_q) == (32, 2 ** 0.5)
    assert cfg.mla_scale_kv == pytest.approx((64 / 48) ** 0.5)
    assert (cfg.num_experts, cfg.zero_experts) == (32, 16)
    assert cfg.experts_held == (8, 8)
    plain = LlamaConfig()
    assert (plain.block, plain.q_lora_rank, plain.zero_experts) == (
        "llama", 0, 0)
    assert (plain.mla_scale_q, plain.mla_scale_kv) == (1.0, 1.0)
    assert (plain.router_scoring, plain.router_renormalize) == (
        "sigmoid", True)
    for change, sentence in [
        ({"block": "parallel"}, "block must be one of"),
        ({"router_scoring": "tanh"}, "router_scoring must be"),
        ({"attention": "gqa", "kv_lora_rank": 0}, "block='shortcut' is two"),
        ({"first_k_dense": 1}, "block='shortcut' is two"),
        ({"num_experts": 0, "zero_experts": 0}, "block='shortcut' is two"),
        ({"num_experts": 0}, "zero_experts are ids past num_experts"),
    ]:
        with pytest.raises(ValueError, match=sentence):
            dataclasses.replace(cfg, **change)


@pytest.mark.parametrize("options, sentence", [
    ({"adapters": {"t": {}}},
     "adapters are not wired to the shortcut double layer"),
    ({"spec_k": 2}, "spec_k is not wired to the shortcut double layer"),
], ids=["tenant_adapters", "speculation"])
def test_an_option_that_is_not_wired_refuses_with_its_sentence(
        served, options, sentence):
    model, params, _ = served
    with pytest.raises(ValueError, match=sentence):
        _session(model, params, **options)


def test_an_exported_artifact_refuses_routed_experts(served, tmp_path):
    from tpudl.export.decode import export_serving_decoder

    model, params, _ = served
    with pytest.raises(ValueError, match="exported decode artifact"):
        export_serving_decoder(
            model, params, num_slots=SLOTS, prompt_len=WINDOW,
            path_prefix=str(tmp_path / "shortcut"), page_size=PAGE,
        )


# -- choices that cost nothing, on the spans and in the registry -------------


def test_zero_and_real_choices_reach_the_spans_and_the_registry(
        served, tmp_path):
    model, params, _ = served
    reg = registry()
    names = ("serve_moe_assignments", "serve_moe_real_assignments",
             "serve_moe_zero_assignments")
    before = {n: reg.counter(n).value for n in names}
    seen = reg.histogram("serve_moe_real_experts_a_token").count
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params)
        reqs = _requests()
        sess.serve(reqs)
        spans = [r for r in rec.records if r.get("kind") == "span"
                 and r["name"] in ("decode_step", "prefill")]
    finally:
        obs_spans.disable()
    steps = [s for s in spans if s["name"] == "decode_step"]
    assert steps and len(spans) - len(steps) == len(reqs)
    by_rid = {r.request_id: r for r in reqs}
    for s in spans:
        tokens = (s["busy"] if s["name"] == "decode_step"
                  else len(by_rid[s["request_id"]].input_ids))
        # Every real token makes 6 choices in each of 2 layers, each of
        # a real expert (held here or not) or of an identity expert.
        assert s["moe_real_assignments"] + s["moe_zero_assignments"] == (
            6 * 2 * tokens)
        assert s["moe_assignments"] <= s["moe_real_assignments"]
        assert sum(s["moe_real_experts_a_token"]) == 2 * tokens
        assert 0 <= s["moe_experts_touched"] <= 2 * 8
    zero = sum(s["moe_zero_assignments"] for s in spans)
    real = sum(s["moe_real_assignments"] for s in spans)
    # A third of the router's outputs are identity experts.
    assert 0.2 < zero / (zero + real) < 0.5
    after = {n: reg.counter(n).value - before[n] for n in names}
    assert after == {
        names[0]: sum(s["moe_assignments"] for s in spans),
        names[1]: real, names[2]: zero,
    }
    assert reg.histogram("serve_moe_real_experts_a_token").count - seen == (
        2 * len(spans))
