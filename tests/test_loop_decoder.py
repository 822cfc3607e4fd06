"""A stack run several times over the same weights (Ouro-2.6B's
mechanism, ISSUE 40) at a tiny size on the CPU, float32, seeded random
weights: 3 passes over 2 sandwich-normed layers, hidden 64, 4 heads of
16, held to ``perfbench/reference/loop_decoder.py`` (which imports
nothing of ``tpudl``).

(a) the full forward, and batch-1 prefill followed by paged decode
    through ``ServeSession.from_model``, agree with the reference on
    logits and on the exit distribution;
(b) a model whose passes SHARE one cache, a reference whose final norm
    runs once at the end, and one whose second and fourth norms are
    left out, each fail (a);
(c) one pass without the sandwich form is today's configuration, tree,
    cache leaves and logits (the plain decoder's reference);
(d) ``PagedKVCache`` with a pool pair a (pass, layer);
(e) what the loop is not wired to says so in a sentence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.models.llama as llama
from perfbench.families.loop_decoder_serve import model_config, to_flax
from perfbench.reference import loop_decoder as ref
from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

CONFIG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, vocab_size=128, num_hidden_layers=2,
    total_ut_steps=3, rms_norm_eps=1e-6, rope_theta=1e6,
    early_exit_threshold=1,
)
PASSES, LAYERS = 3, 2
WINDOW, MAX_SEQ, PAGE, SLOTS = 16, 48, 4, 3
#: Float32 on one backend: the program and the reference differ by the
#: order of their sums alone. Logits are of order 0.5 here and six
#: layer applications of float32 rounding leave 1e-6. A bfloat16 run of
#: this float32 configuration reads 1e-2 and more (the last test of
#: (a)), a wrong wiring 1e-1.
TOL = 2e-5


@pytest.fixture(scope="module")
def served():
    """(model, params, key) of the tiny looped model, float32."""
    key = ref.seed_key(40)
    model = LlamaForCausalLM(model_config(CONFIG, MAX_SEQ, jnp.float32))
    params = to_flax(ref.all_weights(key, CONFIG, jnp.float32))
    return model, params, key


def _session(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, WINDOW, **kw)


def _requests(seed=0, n=5, new=9):
    rng = np.random.default_rng(seed)
    lengths = [7, 12, 16, 3, 10, 5, 14][:n]
    return [
        Request(f"r{i}", rng.integers(1, 128, size=k).tolist(),
                max_new_tokens=new)
        for i, k in enumerate(lengths)
    ]


def _gaps(key, reqs, got, faults=(), cfg=CONFIG):
    """For every request, ``max(logits) - logits[chosen]`` of the
    reference at each served token, teacher-forced: [tokens] float."""
    out = []
    for r in reqs:
        tokens = list(got[r.request_id].tokens)
        seq = jnp.asarray([list(r.input_ids) + tokens[:-1]], jnp.int32)
        logits, _ = ref.logits(key, cfg, jnp.float32, seq, faults)
        rows = np.asarray(logits[0, len(r.input_ids) - 1:])
        out.append(rows.max(-1) - rows[np.arange(len(tokens)), tokens])
    return np.concatenate(out)


def _spans(records, name):
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


# -- (a) the program is the reference -----------------------------------------


def test_the_tree_is_what_init_declares(served):
    """The reference's weights laid out by the family are the tree
    ``model.init`` declares, leaf for leaf: four norms a layer, the MLP
    under ``mlp``, the float32 gate beside the final norm; ONE set of
    layers whatever the passes."""
    model, params, _ = served
    init = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    assert jax.tree.structure(init) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    tree = params["model"]
    assert sorted(tree) == ["early_exit_gate", "embed_tokens", "final_norm",
                            "layer_0", "layer_1"]
    assert sorted(tree["layer_0"]) == [
        "attention", "input_norm", "input_norm_2", "mlp",
        "post_attention_norm", "post_attention_norm_2"]
    assert tree["early_exit_gate"]["kernel"].dtype == jnp.float32
    assert tree["early_exit_gate"]["kernel"].shape == (64, 1)


def test_full_forward_agrees_with_the_reference(served):
    """Logits of two whole sequences and the exit distribution summed
    over their tokens, the sown statistic ``[passes + 1]``."""
    model, params, key = served
    ids = jnp.asarray(
        np.random.default_rng(1).integers(1, 128, size=(2, 20)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, state = model.apply(
            {"params": params}, ids, mutable=["moe_stats"])
    want, pdf = ref.logits(key, CONFIG, jnp.float32, ids)
    np.testing.assert_allclose(got, want, atol=TOL)
    stat = np.asarray(state["moe_stats"]["model"][llama.LOOP_STAT_NAME][0])
    assert stat.shape == (PASSES + 1,) and stat[-1] == 40
    np.testing.assert_allclose(stat[:-1], pdf.sum((0, 1)), atol=1e-4)
    # A distribution: every token's masses sum to 1, none is trivial.
    np.testing.assert_allclose(pdf.sum(-1), 1.0, atol=1e-6)
    assert 0.05 < float(pdf.min()) and float(pdf.max()) < 0.95


@pytest.mark.parametrize("path, calls", [("gather", 0),
                                         ("in_place", PASSES * LAYERS)])
def test_served_tokens_are_the_references(served, path, calls, monkeypatch,
                                          tmp_path):
    """Batch-1 prefill, the seat, then paged decode through the engine,
    five requests over three slots (so that a slot is freed and seated
    again mid-run): every served token is the reference's best to
    float32 rounding, on the gather path and with the k/v kernel
    (interpret mode) at ONE query head a KV head, which every (pass,
    layer) then takes. Spans carry the passes and the exit
    distribution; no pool is copied."""
    import tpudl.ops.paged_attention as pa

    model, params, key = served
    if path == "in_place":
        # The kernel wants whole lanes: heads of 128.
        cfg = dict(CONFIG, head_dim=128)
        model = LlamaForCausalLM(model_config(cfg, MAX_SEQ, jnp.float32))
        params = to_flax(ref.all_weights(key, cfg, jnp.float32))
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    else:
        cfg = CONFIG
    copies = registry().counter("serve_kv_pool_copies").value
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params, page_size=16 if calls else PAGE)
        reqs = _requests()
        got = sess.serve(reqs)
        records = list(rec.records)
    finally:
        obs_spans.disable()
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert _gaps(key, reqs, got, cfg=cfg).max() <= TOL
    cache = sess.engine.cache
    assert cache.in_place_layers == calls
    assert registry().counter("serve_kv_pool_copies").value == copies
    assert registry().gauge("serve_loop_passes").value == PASSES
    assert registry().gauge("serve_kv_pools").value == 2 * PASSES * LAYERS
    prefills, steps = _spans(records, "prefill"), _spans(records, "decode_step")
    assert len(prefills) == len(reqs) and steps
    for span in prefills + steps:
        assert span["loop_passes"] == PASSES
        assert len(span["loop_exit_pdf"]) == PASSES
        assert sum(span["loop_exit_pdf"]) == pytest.approx(1.0, abs=1e-5)
    # The last prompt's prefill span holds the reference's mean exit
    # distribution over that prompt's tokens.
    last = reqs[-1]
    _, pdf = ref.logits(key, cfg, jnp.float32,
                        jnp.asarray([last.input_ids], jnp.int32))
    by_rows = {s["tokens"]: s for s in prefills}
    np.testing.assert_allclose(
        by_rows[len(last.input_ids)]["loop_exit_pdf"], pdf[0].mean(0),
        atol=1e-5)
    assert registry().histogram(
        "serve_loop_exit_last_pass_mass").snapshot()["count"] > 0


def test_a_bfloat16_run_of_the_float32_configuration_fails(served):
    """The tolerance is tight enough: the same weights served in
    bfloat16 lose to the float32 reference by a thousand times it."""
    _, params, key = served
    model = LlamaForCausalLM(model_config(CONFIG, MAX_SEQ, jnp.bfloat16))
    low = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim == 2 and a.shape[1] > 1
        else a, params)
    ids = jnp.asarray(
        np.random.default_rng(1).integers(1, 128, size=(2, 20)), jnp.int32)
    got = model.apply({"params": low}, ids)
    want, _ = ref.logits(key, CONFIG, jnp.float32, ids)
    assert float(jnp.abs(got - want).max()) > 100 * TOL


# -- (b) wrong in one part ----------------------------------------------------


def test_passes_that_share_one_cache_fail(served, monkeypatch):
    """Every pass writing the SAME cache leaves (what a module called
    three times would do by its path alone): the prefill advances the
    write index once a pass and the decode step's later passes attend
    to the earlier tokens' LAST pass: the served tokens lose by whole
    tenths of a logit."""
    model, params, key = served
    monkeypatch.setattr(
        llama, "_pass_leaves", lambda cfg, t: ((lambda name: name), True))
    reqs = _requests(n=3)
    # Three writes of a 16-row window need 48 rows of the row cache.
    got = _session(model, params).serve(reqs)
    assert _gaps(key, reqs, got).max() > 1000 * TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_reference_broken_in_one_part_disagrees(served, fault):
    """The final norm once at the end; the second and fourth norms left
    out: against either the program's logits are off by tenths, and its
    served tokens are not that reference's best."""
    model, params, key = served
    ids = jnp.asarray(
        np.random.default_rng(1).integers(1, 128, size=(2, 20)), jnp.int32)
    got = model.apply({"params": params}, ids)
    wrong, _ = ref.logits(key, CONFIG, jnp.float32, ids, (fault,))
    assert float(jnp.abs(got - wrong).max()) > 1000 * TOL
    reqs = _requests(n=3)
    served_tokens = _session(model, params).serve(reqs)
    assert _gaps(key, reqs, served_tokens).max() <= TOL
    assert _gaps(key, reqs, served_tokens, (fault,)).max() > 1000 * TOL


# -- (c) one pass, no sandwich: today's ---------------------------------------


@pytest.mark.parametrize("kv_heads", [4, 2])
def test_one_pass_without_the_sandwich_form_is_todays(kv_heads):
    """``loop_passes`` 1 and ``sandwich_norm`` off (the defaults) build
    ``LlamaBlock`` through the stack's one loop: the configuration, the
    parameter tree and the cache leaves every configuration had, and
    the logits of the plain pre-norm decoder's reference
    (``perfbench/reference/decoder.py``: two norms a layer, the final
    norm once), which the looped model's are not. (That the accepted
    configurations' programs are the parent's byte for byte is
    ``scripts/lowered_text.py``'s to show.)"""
    from perfbench.families import decoder_serve
    from perfbench.reference import decoder as plain_ref
    from tpudl.models.generate import prefill_fn

    plain = dict(
        vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=kv_heads, intermediate_size=96, max_seq_len=MAX_SEQ,
        dtype=jnp.float32, rope_theta=1e6, rms_norm_eps=1e-6)
    cfg = LlamaConfig(**plain, loop_passes=1, sandwich_norm=False)
    assert cfg == LlamaConfig(**plain)
    assert llama._block_of(cfg) is llama.LlamaBlock
    names, last = llama._pass_leaves(cfg, 0)
    assert (names("pages_k"), last) == ("pages_k", True)
    model = LlamaForCausalLM(cfg)
    ids = jnp.asarray(
        np.random.default_rng(2).integers(1, 128, size=(2, 11)), jnp.int32)
    init = model.init(jax.random.key(3), ids)["params"]
    assert sorted(init["model"]["layer_0"]) == [
        "attention", "down_proj", "gate_proj", "input_norm",
        "post_attention_norm", "up_proj"]
    assert "early_exit_gate" not in init["model"]
    settings = dict(CONFIG, num_key_value_heads=kv_heads)
    key = plain_ref.seed_key(40)
    weights = plain_ref.all_weights(key, settings, jnp.float32)
    params = decoder_serve.to_flax(weights)
    assert jax.tree.structure(params) == jax.tree.structure(init)
    logits, row = prefill_fn(model)(params, ids, jnp.ones_like(ids))
    assert sorted(row["model"]["layer_0"]["attention"]) == [
        "index", "k", "v", "valid"]
    x = weights["outer"]["embed_tokens"][ids]
    for w in weights["layers"]:
        x = plain_ref.block(x, w, settings)
    want = plain_ref.head(x, weights["outer"], settings)
    with jax.default_matmul_precision("highest"):
        full = model.apply({"params": params}, ids)
    np.testing.assert_allclose(full, want, atol=TOL)
    np.testing.assert_allclose(logits, want[:, -1], atol=TOL)


def test_one_pass_with_the_sandwich_form_has_no_gate(served):
    """The two fields are apart: sandwich-normed layers run once are
    the reference at one pass (no gate, no statistic)."""
    model, params, key = served
    once = dict(CONFIG, total_ut_steps=1)
    cfg = dataclasses.replace(model.cfg, loop_passes=1)
    tree = {"lm_head": params["lm_head"], "model": {
        k: v for k, v in params["model"].items() if k != "early_exit_gate"}}
    ids = jnp.asarray(
        np.random.default_rng(1).integers(1, 128, size=(1, 9)), jnp.int32)
    got, state = LlamaForCausalLM(cfg).apply(
        {"params": tree}, ids, mutable=["moe_stats"])
    want, pdf = ref.logits(key, once, jnp.float32, ids)
    np.testing.assert_allclose(got, want, atol=TOL)
    assert not state and pdf.shape[-1] == 1


# -- (d) the page manager over a pool pair a (pass, layer) ---------------------


def test_the_cache_pools_a_pair_a_pass_and_layer(served):
    """``2 x passes x layers`` pool leaves under ONE table; bytes,
    seat / free / ``fits_tokens``; ``tokens_live`` stays ONE pool's
    positions; the donation rule holds over all of them."""
    model, params, _ = served
    sess = _session(model, params)
    cache, engine = sess.engine.cache, sess.engine
    pools = cache.cache["model"]
    assert sorted(pools) == ["layer_0", "layer_1"]
    names = sorted(pools["layer_0"]["attention"])
    assert names == sorted(
        f"pages_{kind}_pass{t}" for kind in "kv" for t in range(PASSES))
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == len(cache.folds) == 2 * PASSES * LAYERS
    pages = SLOTS * (MAX_SEQ // PAGE) + 1
    assert all(leaf.shape == (pages, PAGE, 4, 16) for leaf in leaves)
    assert cache.nbytes - cache.addressing_nbytes == (
        2 * PASSES * LAYERS * pages * PAGE * 4 * 16 * 4)
    copies = registry().counter("serve_kv_pool_copies").value
    free = cache.free_pages
    ids = np.arange(1, WINDOW + 1, dtype=np.int32)[None]
    # The statistic sits at its fixed place, after three that are None.
    _, row, *none, stat = engine.prefill_call(params, ids, np.ones_like(ids))
    assert none == [None, None, None]
    attn = row["model"]["layer_1"]["attention"]
    assert sorted(attn) == sorted(
        ["index", "valid"]
        + [f"{kind}_pass{t}" for kind in "kv" for t in range(PASSES)])
    # ONE write index, advanced once a chunk whatever the passes.
    assert int(attn["index"]) == WINDOW and stat.shape == (1, PASSES + 1)
    for _ in range(2):
        before = cache.cache
        cache.seat(row, slot=1, pad=0, prompt_len=WINDOW,
                   reserve_tokens=WINDOW + 8)
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
        assert cache.free_pages == free - (WINDOW + 8) // PAGE
        assert cache.tokens_live == WINDOW
        assert cache.fits_tokens(free * PAGE - WINDOW - 8)
        assert not cache.fits_tokens(free * PAGE - WINDOW - 8 + PAGE)
        cache.free(1)
        assert cache.free_pages == free and cache.tokens_live == 0
    assert registry().counter("serve_kv_pool_copies").value == copies
    # Every pass's rows reached its own pool: pass t's first page of
    # layer 1 holds pass t's keys, and the passes differ.
    cache.seat(row, slot=0, pad=0, prompt_len=WINDOW,
               reserve_tokens=WINDOW + 8)
    page = int(cache.page_table[0, 0])
    held = cache.cache["model"]["layer_1"]["attention"]
    for t in range(PASSES):
        np.testing.assert_array_equal(
            held[f"pages_k_pass{t}"][page], attn[f"k_pass{t}"][0, :PAGE])
    assert not np.allclose(attn["k_pass0"], attn["k_pass1"])


def test_int8_pools_serve_close_to_the_reference(served):
    """The int8 control's path: weights and all ``2 x passes x layers``
    pools int8 (the gather path with a scale leaf a pool); the norms
    and the gate are kept. Close: at this size every served token is
    still the reference's best or near it."""
    model, params, key = served
    sess = _session(model, params, weight_dtype="int8", kv_dtype="int8")
    held = sess.engine.params["model"]
    assert set(held["layer_0"]["attention"]["q_proj"]["kernel"]) == {
        "qvalues", "qscale"}
    assert set(held["layer_0"]["mlp"]["down_proj"]["kernel"]) == {
        "qvalues", "qscale"}
    for name in ref.LAYER_NORMS:
        assert held["layer_0"][name]["scale"].dtype == jnp.float32
    assert held["early_exit_gate"]["kernel"].dtype == jnp.float32
    assert held["early_exit_gate"]["kernel"].shape == (64, 1)
    pools = sess.engine.cache.cache["model"]["layer_0"]["attention"]
    assert len(pools) == 4 * PASSES
    assert pools["pages_k_pass2"].dtype == jnp.int8
    reqs = _requests(n=3)
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert _gaps(key, reqs, got).max() < 0.2


def test_prefix_sharing_serves_the_references_tokens(served):
    """Two prompts with a common first 12 tokens through a radix
    session: the second maps the first's pages in EVERY (pass, layer)
    pool and prefills its suffix alone (the chunk attends the gathered
    rows pass by pass), and both are the reference's."""
    model, params, key = served
    sess = _session(model, params, prefix_share=True)
    head = np.random.default_rng(4).integers(1, 128, size=12).tolist()
    reqs = [Request("p0", head + [5, 6], max_new_tokens=6),
            Request("p1", head + [9, 3, 2], max_new_tokens=6)]
    got = {}
    for r in reqs:
        got.update(sess.serve([r]))
    assert _gaps(key, reqs, got).max() <= TOL
    assert registry().counter("serve_prefix_hit_tokens").value >= 12


def test_a_request_migrates_with_every_passes_rows(served):
    """Export mid-stream, install on another engine: the continuation
    is the uninterrupted one and the target pays no prefill."""
    model, params, _ = served
    req = Request("m0", [3, 5, 7, 11, 2], max_new_tokens=14)
    want = _session(model, params).serve([req])["m0"].tokens
    src, dst = _session(model, params), _session(model, params)
    src.submit(req)
    for _ in range(5):
        src.engine.step()
    payload = src.engine.export_request("m0")
    assert dst.engine.install_migrated(payload) == "m0"
    while dst.engine.step():
        pass
    assert list(dst.engine.results["m0"].tokens) == list(want)
    assert dst.engine.num_prefills == 0


# -- (e) what the loop is not wired to says so --------------------------------


@pytest.mark.parametrize("change, sentence", [
    (dict(loop_passes=0), "loop_passes must be >= 1"),
    (dict(loop_exit_threshold=0.9),
     "loop_exit_threshold 0.9 < 1 lets a token leave the loop"),
    (dict(sandwich_norm=False), "loop_passes > 1 needs sandwich_norm"),
    (dict(attention="mla", kv_lora_rank=8, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=8),
     "sandwich_norm is grouped-query attention and a dense SwiGLU"),
    (dict(hyper_streams=4), "sandwich_norm is grouped-query attention"),
    (dict(lora_rank=4), "sandwich_norm is grouped-query attention"),
    (dict(num_experts=8, experts_per_token=2, moe_intermediate_size=16),
     "sandwich_norm is grouped-query attention"),
])
def test_the_configuration_refuses_with_a_sentence(served, change, sentence):
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(served[0].cfg, **change)


@pytest.mark.parametrize("asked, sentence", [
    (dict(spec_k=2), "spec_k is not wired to a stack run 3 times a token"),
    (dict(adapters={"t": {"lora_a": jnp.zeros((2, 2))}}),
     "per-tenant adapters is not wired to a stack run 3 times a token"),
    (dict(mesh=object()),
     "a mesh-committed session is not wired to a stack run 3 times"),
])
def test_from_model_refuses_with_a_sentence(served, asked, sentence):
    model, params, _ = served
    with pytest.raises(ValueError, match=sentence):
        _session(model, params, **asked)


class _NoAdapters:
    """An adapter view as ``LlamaModel`` hands one down a layer."""

    def for_layer(self, name):
        return self


def test_the_stack_and_the_artifact_refuse_with_a_sentence(served):
    from tpudl.export.decode import export_serving_decoder

    model, params, _ = served
    ids = jnp.ones((1, 4), jnp.int32)
    with pytest.raises(ValueError,
                       match="adapters are not wired to a looped stack"):
        model.apply({"params": params}, ids, adapters=_NoAdapters())
    once = LlamaForCausalLM(dataclasses.replace(model.cfg, loop_passes=1))
    with pytest.raises(ValueError, match="adapters are not wired to a "
                       "sandwich-normed layer"):
        once.apply({"params": params}, ids, adapters=_NoAdapters())
    with pytest.raises(ValueError, match="exported decode artifact is not "
                       "wired to a stack run several times"):
        export_serving_decoder(model, params, num_slots=2, prompt_len=8)
