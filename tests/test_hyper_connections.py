"""A residual stream of several vectors a token mixed by
manifold-constrained hyper-connections (``LlamaConfig.hyper_streams``,
``HyperBlock``, tpudl.models.hyper), the latent prefill attended in
blocks, and both through the normal serving path (ISSUE 38), at a tiny
size on the CPU.

The program is held to ``perfbench/reference/hyper_mla_moe.py`` (float32
at ``highest``, whole sequences, no cache, no absorption, every expert
applied plainly). Float32 throughout, so a tolerance is float32 rounding
over a few hundred operations a logit (2e-4, as
``tests/test_latent_moe.py`` holds its program) unless it says
otherwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.models.hyper as hyper
import tpudl.models.llama as llama
from perfbench.families.hyper_mla_moe_serve import model_config, to_flax
from perfbench.reference import hyper_mla_moe as ref
from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

#: Hidden 64 in 4 streams, 4 heads, a latent row of 48 + 16 = 64 (held
#: folded, two positions a row of 128 lanes, so that the latent kernel
#: can read it in interpret mode), query rank 32, YaRN over an original
#: context of 16, one dense layer and two of 8 experts (2 chosen, one
#: shared): the published model's shape at a size a test can hold.
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 48,
    "q_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 16,
    "v_head_dim": 16, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn",
    },
}
MAX_SEQ, WINDOW, PAGE, SLOTS = 64, 16, 16, 3
SETTINGS = ref.settings(CONFIG)
TOL = 2e-4


@pytest.fixture(scope="module")
def served():
    """(model, params, key): float32, the reference's weights laid over
    the program's tree."""
    key = ref.seed_key(2**31 + 38)
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


def _spans(records, name):
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


# -- (a) the routine against the equations ------------------------------------


def _connection(alpha=ref.HYPER_ALPHA, seed=7):
    """(module, params, the reference's weights of the same maps)."""
    w = ref.hyper_weights(ref.seed_key(seed), SETTINGS)
    w = dict(w, alpha=jnp.full((3,), alpha, jnp.float32))
    module = hyper.HyperConnection(
        4, CONFIG["hc_sinkhorn_iters"], CONFIG["hc_eps"], 30.0,
        CONFIG["rms_norm_eps"],
    )
    return module, {"params": dict(w)}, w


def _tokens_leading(maps):
    """``h_post`` [n, B, S] or ``h_res`` [n, n, B, S] as the equations
    write them: [B, S, n] or [B, S, n, n]."""
    lead = maps.ndim - 2
    return jnp.moveaxis(maps, tuple(range(lead)), tuple(range(-lead, 0)))


def _stream(shape=(2, 9), seed=1, scale=1.0):
    return scale * jnp.asarray(
        np.random.default_rng(seed).normal(size=(*shape, 4, 64)), jnp.float32)


@pytest.mark.parametrize("alpha", [ref.HYPER_ALPHA, 40.0],
                         ids=["as_drawn", "clamped"])
def test_the_routine_is_the_references_equations(alpha):
    """One sublayer around ``F = tanh``: the maps, the read and the
    write-back against ``ref.hyper_sublayer``, a sequence at a time.
    ``H_res`` is doubly stochastic to 1e-3 after the 20 iterations.
    With ``alpha`` 40 the logits of ``M`` pass +-30 (``x^ phi`` has
    deviation 0.32 here) and the clamp decides entries: ``exp`` stays
    finite, the maps agree entry by entry, and what the iterations
    leave of the column sums is what the statistic reports."""
    module, variables, w = _connection(alpha)
    x = _stream()
    real = jnp.ones(x.shape[:2], bool).at[1, -2:].set(False)
    (u, post, res), state = module.apply(
        variables, x, real, mutable=["moe_stats"])
    got = hyper.mix_out(x, res, post, jnp.tanh(u))
    h_post, h_res = _tokens_leading(post), _tokens_leading(res)
    for row in range(x.shape[0]):
        h_pre, want_post, want_res = ref.hyper_maps(x[row], w, SETTINGS)
        # (Float32 rounding of ``x^ phi``, times alpha.)
        np.testing.assert_allclose(h_post[row], want_post, atol=1e-5)
        np.testing.assert_allclose(h_res[row], want_res, atol=1e-5)
        np.testing.assert_allclose(
            u[row], jnp.einsum("sn,snd->sd", h_pre, x[row]), atol=1e-5)
    np.testing.assert_allclose(
        got, jnp.einsum("bsij,bsjd->bsid", h_res, x)
        + h_post[..., None] * jnp.tanh(u)[:, :, None], atol=1e-5)
    rows = np.asarray(h_res.sum(-1))
    columns = np.asarray(h_res.sum(-2))
    np.testing.assert_allclose(rows, 1.0, atol=1e-5)
    if alpha == ref.HYPER_ALPHA:
        np.testing.assert_allclose(columns, 1.0, atol=1e-3)
    else:
        logits = alpha * (
            x.reshape(2, 9, -1) * jax.lax.rsqrt(jnp.mean(
                jnp.square(x.reshape(2, 9, -1)), -1, keepdims=True) + 1e-6)
        ) @ w["phi"][:, 8:] + w["b"][8:]
        assert float(jnp.abs(logits).max()) > 30.0
        assert np.isfinite(np.asarray(h_res)).all()
    off, tokens, error = np.asarray(state["moe_stats"]["hyper_res"][0])
    counted = np.asarray(real)
    assert tokens == counted.sum() == 16
    mass = np.asarray(
        (h_res.sum((-2, -1)) - jnp.trace(h_res, axis1=-2, axis2=-1)) / 4)
    np.testing.assert_allclose(off, mass[counted].sum(), rtol=1e-5)
    np.testing.assert_allclose(
        error, np.abs(columns - 1.0).max(-1)[counted].max(), atol=1e-7)
    assert 0.0 < off / tokens < 0.75


def test_a_sublayer_is_the_references():
    """The same sublayer with the layer's norm in its place, ``F`` a
    fixed matrix: ``ref.hyper_sublayer`` whole."""
    module, variables, w = _connection()
    x = _stream(seed=2)
    scale = 1.0 + 0.1 * _stream((1,), seed=3)[0, 0]
    matrix = _stream((1,), seed=4)[0].T @ _stream((1,), seed=5)[0] / 8.0
    u, h_post, h_res = module.apply(
        variables, x, jnp.ones(x.shape[:2], bool), mutable=["moe_stats"])[0]
    assert _tokens_leading(h_res).shape == (2, 9, 4, 4)
    y = ref._rms_norm(u, scale, 1e-6) @ matrix
    got = hyper.mix_out(x, h_res, h_post, y)
    for row in range(x.shape[0]):
        want = ref.hyper_sublayer(
            x[row], w, scale, lambda z: z @ matrix, SETTINGS)
        np.testing.assert_allclose(got[row], want, atol=2e-5)


def test_the_drawn_maps_differ_by_token_and_mix_in_part():
    """What the configuration's ``assumed`` promises of the seeded draw,
    at the PUBLISHED width (14,336 values a token): ``H_res``'s
    off-diagonal mass differs from token to token and sits well inside
    (0, 0.75), so a wrong ``phi`` or a transposed ``mat`` moves the
    logits."""
    s = dict(SETTINGS, hidden_size=3584)
    w = ref.hyper_weights(ref.seed_key(11), s)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(64, 4, 3584)), jnp.float32)
    _, _, h_res = ref.hyper_maps(x, w, s)
    off = 1.0 - np.asarray(jnp.trace(h_res, axis1=-2, axis2=-1)) / 4
    assert 0.1 < off.mean() < 0.6
    assert off.min() > 0.02 and off.max() < 0.73
    assert off.std() > 0.03
    assert not np.allclose(h_res[0], np.swapaxes(h_res[0], -1, -2), atol=1e-2)


# -- (b) the whole model: forward, then prefill and paged decode ---------------


def test_full_forward_agrees_with_the_reference(served):
    """The training / scoring path, whole sequences: the stream's
    wiring (repeat at the bottom, two sublayers a layer with maps of
    their own, the sum at the top), the low-rank query, YaRN and the
    router, all at once."""
    model, params, key = served
    ids = jnp.asarray(
        np.random.default_rng(3).integers(1, 256, size=(2, 24)), jnp.int32)
    want = ref.logits(key, CONFIG, jnp.float32, ids)
    got = model.apply({"params": params}, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("path, layers", [("gather", 0), ("in_place", 3)])
def test_served_tokens_are_the_references(
        served, path, layers, monkeypatch, tmp_path):
    """Prefill, then decode through the engine and the paged pool, more
    requests than slots and every length different, against the
    reference's whole-sequence forward: each served token is the
    reference's best to within TOL = 2e-4 (float32 rounding of a few
    hundred operations a logit; maps made in bfloat16 move the logits
    by 2.7e-3, ``test_bfloat16_maps_would_fail`` below), on the
    gather path and with the latent kernel reading the three pools in
    place. Both spans carry the maps' statistic beside the experts'."""
    import tpudl.ops.paged_attention as pa

    model, params, key = served
    if path == "in_place":
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    seen = registry().histogram("serve_hyper_res_offdiag").count
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params)
        reqs = _requests()
        got = sess.serve(reqs)
        steps = _spans(rec.records, "decode_step")
        prefills = _spans(rec.records, "prefill")
    finally:
        obs_spans.disable()
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert all(len(got[r.request_id].tokens) == r.max_new_tokens for r in reqs)
    ids, picks, chosen, valid = map(jnp.asarray, _teacher_forced(reqs, got))
    gaps = np.asarray(ref.margins(key, CONFIG, jnp.float32, ids, picks, chosen))
    assert gaps[np.asarray(valid)].max() <= TOL
    cache = sess.engine.cache
    assert cache.folds == (2,) * 3 and cache.in_place_layers == layers
    assert registry().gauge("serve_hyper_streams").value == 4
    assert len(prefills) == len(reqs) and steps
    for span in prefills + steps:
        assert 0.0 < span["hyper_res_offdiag"] < 0.75
        assert 0.0 <= span["hyper_res_sum_error"] < 1e-3
        assert span["moe_assignments"] > 0
    assert all(s["kv_in_place"] == int(layers > 0) for s in steps)
    # Tokens differ, so their maps do: no two prefills mix alike.
    assert len({round(s["hyper_res_offdiag"], 6) for s in prefills}) > 1
    assert registry().histogram("serve_hyper_res_offdiag").count == (
        seen + len(prefills) + len(steps))


def test_bfloat16_maps_would_fail(served, monkeypatch):
    """The tolerance is tight enough for the precision the maps are
    stated in: the same float32 program with the Sinkhorn iterations
    run in bfloat16 (maps made in the stream's type) parts from the
    reference by over ten times TOL (2.7e-3), and with ``H_res`` only
    ROUNDED to bfloat16 at the end by over three times (6.7e-4);
    float32 maps agree to 2e-7."""
    model, params, key = served
    ids = jnp.asarray(
        np.random.default_rng(3).integers(1, 256, size=(2, 24)), jnp.int32)
    want = np.asarray(ref.logits(key, CONFIG, jnp.float32, ids))
    plain = hyper.sinkhorn

    def gap():
        return np.abs(
            np.asarray(model.apply({"params": params}, ids)) - want).max()

    assert gap() < TOL / 100
    monkeypatch.setattr(
        hyper, "sinkhorn", lambda m, iters, eps: plain(
            m.astype(jnp.bfloat16), iters, eps).astype(jnp.float32))
    assert gap() > 10 * TOL
    monkeypatch.setattr(
        hyper, "sinkhorn", lambda m, iters, eps: plain(m, iters, eps).astype(
            jnp.bfloat16).astype(jnp.float32))
    assert gap() > 3 * TOL


def test_int8_control_keeps_the_maps(served):
    """The control's path: the quantizer reaches the projections and
    the experts and leaves ``phi`` (as the router) float32, the pool is
    int8 rows through the gather, and it serves."""
    model, params, _ = served
    sess = _session(model, params, weight_dtype="int8", kv_dtype="int8")
    layer = sess.engine.params["model"]["layer_1"]
    for site in (layer["attention"]["q_a_proj"], layer["attention"]["o_proj"],
                 layer["moe"]["up_proj"], layer["moe"]["shared_down_proj"],
                 sess.engine.params["model"]["layer_0"]["mlp"]["gate_proj"]):
        assert set(site["kernel"]) == {"qvalues", "qscale"}
    for name in ("hyper_attention", "hyper_mlp"):
        assert layer[name]["phi"].dtype == jnp.float32
        assert layer[name]["phi"].shape == (256, 24)
    assert layer["moe"]["router"]["kernel"].dtype == jnp.float32
    reqs = _requests()
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert sess.engine.cache.in_place_layers == 0


# -- (c) the latent prefill in blocks -----------------------------------------

#: name -> what the configuration changes of the tiny model above: the
#: three latent configurations of the benchmark, by shape.
LATENT = {
    "xing_shaped": {},
    "sarvam_shaped": dict(hyper_streams=0, q_lora_rank=0),
    "longcat_shaped": dict(
        hyper_streams=0, block="shortcut", first_k_dense=0, rope_scaling=None,
        mla_scale_q=2.0, mla_scale_kv=3.4641, router_scoring="softmax",
        router_renormalize=False, zero_experts=4, num_shared_experts=0),
}


@pytest.mark.parametrize("name", sorted(LATENT))
def test_blocked_latent_prefill_is_the_unblocked_one(name, monkeypatch):
    """A left-padded prompt of 40 rows into an empty cache, attended in
    one pass and in five blocks of 8 queries (the rule's bound and the
    block set low for the test: the rule reads the static shapes of the
    program being traced): the same last logits and the same row cache,
    to float32 rounding of sums taken in another order."""
    from tpudl.models.generate import prefill_fn

    cfg = dataclasses.replace(
        model_config(CONFIG, MAX_SEQ, jnp.float32), **LATENT[name])
    model = LlamaForCausalLM(cfg)
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(1, 256, size=(1, 40)), jnp.int32)
    mask = jnp.asarray(np.arange(40)[None] >= 7, jnp.int32)
    params = model.init(jax.random.key(1), ids)["params"]
    one_pass = prefill_fn(model)(params, ids, mask)
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 1024)
    monkeypatch.setattr(llama, "PREFILL_BLOCK", 8)
    calls = []
    blocked = llama._blocked_attention
    monkeypatch.setattr(
        llama, "_blocked_attention",
        lambda *a: calls.append(a[0].shape) or blocked(*a))
    in_blocks = prefill_fn(model)(params, ids, mask)
    attentions = cfg.num_layers * (2 if cfg.block == "shortcut" else 1)
    assert calls == [(1, 40, 4, 32)] * attentions
    np.testing.assert_allclose(in_blocks[0], one_pass[0], atol=1e-5)
    # (The 7 padded rows' attention is over nothing, and is nothing.)
    for a, b in zip(jax.tree.leaves(in_blocks[1]),
                    jax.tree.leaves(one_pass[1])):
        if a.ndim == 3:
            a, b = a[:, 7:40], b[:, 7:40]
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-5)


def test_a_short_latent_prefill_keeps_its_one_pass(monkeypatch):
    """512 rows at 64 heads are 67 MB of scores, under the bound: the
    latent cells that prefill 256 / 512 rows trace the program they
    had. 2,048 rows at 32 heads (537 MB) are attended in blocks."""
    calls = []
    monkeypatch.setattr(
        llama, "_blocked_attention", lambda *a: calls.append(a) or a[2])
    monkeypatch.setattr(
        llama, "_mla_up_projected", lambda *a: calls.append("one pass"))

    def prefill(heads, rows):
        q = jax.ShapeDtypeStruct((1, rows, heads, 8), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, rows, 24), jnp.float32)
        w = jax.ShapeDtypeStruct((16, heads, 16), jnp.float32)
        valid = jax.ShapeDtypeStruct((1, rows), jnp.bool_)
        jax.eval_shape(
            lambda q, kv, w, valid: llama._mla_prefill(
                q, q, kv, w, 8, None, 0.1, valid, True), q, kv, w, valid)
        return calls.pop()

    assert prefill(64, 512) == "one pass"
    assert prefill(32, 2048) != "one pass"
    # A chunk behind a shared prefix attends the cache it was handed.
    q = jnp.zeros((1, 2048, 32, 8))
    llama._mla_prefill(q, q, None, jnp.zeros((16, 32, 16)), 8, None, 0.1,
                       None, False)
    assert calls.pop() == "one pass"


# -- (d) no stream: today's tree and outputs -----------------------------------


def test_without_streams_the_blocks_are_todays(served):
    """``hyper_streams`` 0 (the default) builds ``LlamaBlock``, a
    residual of one vector a token and the parameter tree every
    configuration had: the dense SwiGLU at the layer's top level, no
    maps. The same attention and expert weights laid into that tree
    give the logits of the sarvam family's reference wiring (``x + F(
    norm(x))``), which the stream's model does not."""
    model, params, _ = served
    cfg = dataclasses.replace(model.cfg, hyper_streams=0)
    assert llama._block_of(cfg) is llama.LlamaBlock
    assert llama._block_of(model.cfg) is llama.HyperBlock
    plain = LlamaForCausalLM(cfg)
    ids = jnp.asarray(
        np.random.default_rng(3).integers(1, 256, size=(2, 12)), jnp.int32)
    tree = plain.init(jax.random.key(0), ids)["params"]["model"]
    assert sorted(tree["layer_0"]) == [
        "attention", "down_proj", "gate_proj", "input_norm",
        "post_attention_norm", "up_proj"]
    assert sorted(tree["layer_1"]) == [
        "attention", "input_norm", "moe", "post_attention_norm"]
    laid = {"lm_head": params["lm_head"], "model": {
        k: v for k, v in params["model"].items() if "layer" not in k}}
    for i in range(3):
        layer = {k: v for k, v in params["model"][f"layer_{i}"].items()
                 if not k.startswith("hyper_")}
        layer.update(layer.pop("mlp", {}))
        laid["model"][f"layer_{i}"] = layer
    assert jax.tree.structure(laid["model"]) == jax.tree.structure(tree)
    got = plain.apply({"params": laid}, ids)

    def layer_by_hand(x, w, dense):
        w = {k: v for k, v in w.items() if not isinstance(v, dict)}
        x = x + ref.attention(
            ref._rms_norm(x, w["input_norm"], 1e-6), w, SETTINGS)
        y = ref._rms_norm(x, w["post_attention_norm"], 1e-6)
        if dense:
            return x + ref._swiglu(
                y, w["gate_proj"], w["up_proj"], w["down_proj"])
        return x + ref.experts(y, w, SETTINGS)

    key = served[2]
    outer = ref.outer_weights(key, SETTINGS, jnp.float32)
    with jax.default_matmul_precision("highest"):
        for row in range(2):
            x = outer["embed_tokens"][ids[row]]
            for i in range(3):
                dense = ref.is_dense(SETTINGS, i)
                x = layer_by_hand(x, ref.layer_weights(
                    key, i, SETTINGS, jnp.float32, dense), dense)
            want = ref.head(x, outer, SETTINGS)
            np.testing.assert_allclose(got[row], want, atol=TOL)
    streamed = model.apply({"params": params}, ids)
    assert np.abs(np.asarray(streamed) - np.asarray(got)).max() > 100 * TOL


# -- (e) what the stream is not wired to says so -------------------------------


@pytest.mark.parametrize("change, sentence", [
    (dict(hyper_streams=1), "hyper_streams must be 0"),
    (dict(hyper_streams=-4), "hyper_streams must be 0"),
    (dict(block="shortcut", first_k_dense=0),
     "hyper_streams is not wired to block='shortcut'"),
    (dict(lora_rank=4), "hyper_streams is not wired to lora_rank"),
    (dict(moe_experts=4, num_experts=0),
     "hyper_streams is not wired to lora_rank, moe_experts"),
])
def test_the_configuration_refuses_with_a_sentence(served, change, sentence):
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(served[0].cfg, **change)


@pytest.mark.parametrize("asked, sentence", [
    (dict(spec_k=2), "spec_k is not wired to a residual stream of 4"),
    (dict(adapters={"t": {}}),
     "per-tenant adapters is not wired to a residual stream of 4"),
    (dict(mesh=object()),
     "a mesh-committed session is not wired to a residual stream of 4"),
])
def test_from_model_refuses_with_a_sentence(served, asked, sentence):
    model, params, _ = served
    if "adapters" in asked:
        asked = dict(adapters={"t": {"lora_a": jnp.zeros((2, 2))}})
    with pytest.raises(ValueError, match=sentence):
        _session(model, params, **asked)


def test_the_block_and_the_artifact_refuse_with_a_sentence(served):
    from tpudl.export.decode import export_serving_decoder

    model, params, _ = served
    ids = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(ValueError, match="adapters are not wired to a stream"):
        model.apply({"params": params}, ids, adapters=_NoAdapters())
    gqa = dataclasses.replace(
        model.cfg, attention="gqa", num_experts=0, first_k_dense=0)
    dense = LlamaForCausalLM(gqa)
    with pytest.raises(ValueError, match="exported decode artifact is not "
                       "wired to a residual stream"):
        export_serving_decoder(
            dense, dense.init(jax.random.key(0), ids)["params"],
            num_slots=2, prompt_len=8)


class _NoAdapters:
    """An adapter view as ``LlamaModel`` hands one down a layer."""

    def for_layer(self, name):
        return self


def test_a_grouped_query_dense_model_takes_the_stream():
    """The stream is the block's, not the latent attention's: a
    grouped-query dense decoder with four streams serves through the
    page pool, and its spans carry the maps' statistic alone."""
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, intermediate_size=128, max_seq_len=MAX_SEQ,
        dtype=jnp.float32, hyper_streams=4)
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((1, 4), jnp.int32)
    params = model.init(jax.random.key(2), ids)["params"]
    sess = _session(model, params)
    got = sess.serve(_requests()[:3])
    assert all(r.finish_reason == "length" for r in got.values())
    full = model.apply({"params": params}, jnp.asarray(
        [_requests()[0].input_ids], jnp.int32))
    assert int(full[0, -1].argmax()) == got["r0"].tokens[0]


# -- (f) the latent kernel at this configuration's sizes -----------------------


def test_latent_kernel_at_32_heads_over_nine_blocks():
    """The published model's decode attention: 32 heads (an absorbed
    query of 2 x 32 rows by 1,152 lanes), a slot of 272 pages (4,352
    positions: nine blocks of 32 pages), the pool held ``[NP, 8,
    1152]``. In interpret mode against the gather path."""
    import tpudl.ops.paged_attention as pa
    from tpudl.models.paged import PagedView

    slots, pages, heads = 2, 272, 32
    rng = np.random.default_rng(38)
    pool = jnp.asarray(
        rng.normal(size=(slots * pages + 1, 8, 1152)), jnp.float32)
    query = jnp.asarray(rng.normal(size=(slots, 1, heads, 576)), jnp.float32)
    table = rng.permutation(np.arange(1, slots * pages + 1)).reshape(
        slots, pages)
    view = lambda: PagedView(  # noqa: E731
        jnp.asarray(table, jnp.int32), jnp.asarray([0, 2048], jnp.int32),
        jnp.asarray([4351, 4100], jnp.int32), 16, False)
    assert pa.latent_in_place_ok(query, pool, view())
    want = pa.paged_latent_attention(
        query, pool, view(), rank=512, scale=0.05, impl="reference")
    got = pa.paged_latent_attention(
        query, pool, view(), rank=512, scale=0.05, impl="fused")
    assert got.shape == (slots, 1, heads, 512)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
