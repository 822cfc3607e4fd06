"""Learned sparse attention over the latent cache (GLM-5.2's mechanism,
ISSUE 44) at a tiny size on the CPU, float32, seeded random weights:
hidden 64, 4 heads, a latent of 16 + 16, an indexer of 2 heads x 16
that keeps 8 positions a query, 5 layers typed ``full, shared, shared,
full, shared`` (one dense, four with 8 routed experts of which 2 are
held), held to ``perfbench/reference/sparse_mla_moe.py`` (which
imports nothing of ``tpudl`` and makes its OWN choice of positions).

(a) the full forward agrees with the reference on logits AND on the
    chosen sets; batch-1 prefill followed by paged decode through
    ``ServeSession.from_model`` (an indexer-key pool on the ``full``
    layers only, under the latent rows' page table) serves the
    reference's tokens, at lengths under and over ``index_topk``;
(b) a stack that hands a ``shared`` layer the WRONG layer's choice
    fails (a); the up-projected and the absorbed form agree under a
    choice;
(c) with ``index_topk`` no smaller than the sequence the model is plain
    ``LatentAttention``, exactly;
(d) int8 pools, prefix sharing and migration with the second leaf;
(e) the parts that 4 shares of the experts give, the shared expert
    counted once, add up to the uncut layer;
(f) what the indexer is not wired to says so in a sentence.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.models.llama as llama
from perfbench.families.sparse_mla_moe_serve import model_config, to_flax
from perfbench.reference import sparse_mla_moe as ref
from tpudl.models.llama import LlamaForCausalLM
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

TOPK = 8
TYPES = ["full", "shared", "shared", "full", "shared"]
CONFIG = dict(
    hidden_size=64, num_attention_heads=4, kv_lora_rank=16, q_lora_rank=24,
    qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
    index_n_heads=2, index_head_dim=16, index_topk=TOPK,
    indexer_types=TYPES,
    mlp_layer_types=["dense"] + ["sparse"] * 4,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=5,
    n_routed_experts=2, n_shared_experts=1, num_experts_per_tok=2,
    routed_scaling_factor=2.5, vocab_size=128, rms_norm_eps=1e-5,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"},
    deployment={"router_experts": 8, "first_expert": 0},
)
FULL = [i for i, t in enumerate(TYPES) if t == "full"]
WINDOW, MAX_SEQ, SLOTS = 16, 48, 3
#: Float32 on one backend: the program and the reference differ by the
#: order of their sums alone (logits of order 0.5, five layers: 1e-6).
#: A wrong hand-on of the choice reads 1e-2 and more (b).
TOL = 2e-5


def _built(config, max_seq=MAX_SEQ, seed=44):
    key = ref.seed_key(seed)
    s = ref.settings(config)
    model = LlamaForCausalLM(model_config(config, max_seq, jnp.float32))
    return model, to_flax(ref.all_weights(key, s, jnp.float32), s), key


@pytest.fixture(scope="module")
def served():
    """(model, params, key) of the tiny model, float32."""
    return _built(CONFIG)


def _session(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", 4)
    return ServeSession.from_model(model, params, WINDOW, **kw)


def _requests(seed=0):
    """Prompts of 3-16 tokens, answers that end at 6-46 positions: under
    ``index_topk``, across it in the prompt, and far over it."""
    rng = np.random.default_rng(seed)
    return [
        Request(f"r{i}", rng.integers(1, 128, size=k).tolist(),
                max_new_tokens=new)
        for i, (k, new) in enumerate(
            [(7, 9), (12, 20), (16, 30), (3, 3), (10, 12)])
    ]


ROWS = 5


def _gaps(key, reqs, got, cfg=CONFIG):
    """For every request, ``max(logits) - logits[chosen]`` of the
    reference at each served token, teacher-forced: [tokens] float.
    Always ``ROWS`` rows of ``MAX_SEQ`` (right-padded: what follows a
    sequence cannot reach it), so that the reference compiles once."""
    ids = np.zeros((ROWS, MAX_SEQ), np.int32)
    for row, r in enumerate(reqs):
        seq = list(r.input_ids) + list(got[r.request_id].tokens)[:-1]
        ids[row, :len(seq)] = seq
    logits = np.asarray(ref.logits(key, cfg, jnp.float32, jnp.asarray(ids)))
    out = []
    for row, r in enumerate(reqs):
        tokens = list(got[r.request_id].tokens)
        at = len(r.input_ids) - 1
        rows = logits[row, at:at + len(tokens)]
        out.append(rows.max(-1) - rows[np.arange(len(tokens)), tokens])
    return np.concatenate(out)


@pytest.fixture(scope="module")
def whole(served):
    """``ROWS`` whole sequences of ``MAX_SEQ`` tokens, the reference's
    logits and its choices (one [ROWS, MAX_SEQ, MAX_SEQ] a ``full``
    layer)."""
    _, _, key = served
    ids = jnp.asarray(np.random.default_rng(1).integers(
        1, 128, size=(ROWS, MAX_SEQ)), jnp.int32)
    x, outer, theirs = ref.forward(key, CONFIG, jnp.float32, ids)
    return ids, ref.head(x, outer, ref.settings(CONFIG)), theirs


def _choices(state) -> list:
    """The ``full`` layers' sown choices, in layer order."""
    found = jax.tree_util.tree_leaves_with_path(state["intermediates"])
    return [leaf for _, leaf in sorted(
        (jax.tree_util.keystr(path), leaf) for path, leaf in found)]


def _spans(records, name):
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


# -- (a) the program is the reference -----------------------------------------


def test_the_tree_is_what_init_declares(served):
    """The reference's weights laid out by the family are the tree
    ``model.init`` declares, leaf for leaf: an ``indexer`` under the
    attention of the ``full`` layers and of no other."""
    model, params, _ = served
    init = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    assert jax.tree.structure(init) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(init), jax.tree.leaves(params)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    for i in range(5):
        attention = params["model"][f"layer_{i}"]["attention"]
        assert ("indexer" in attention) == (i in FULL)
    indexer = params["model"]["layer_3"]["attention"]["indexer"]
    assert sorted(indexer) == ["k_norm", "k_proj", "q_proj", "weights_proj"]
    assert sorted(indexer["k_norm"]) == ["bias", "scale"]


@pytest.mark.parametrize("length", [6, TOPK, MAX_SEQ])
def test_full_forward_agrees_with_the_reference(served, whole, length):
    """Logits of whole sequences, the chosen sets of both ``full``
    layers (EQUAL, position for position) and the sown statistic, under
    ``index_topk`` positions (every position is chosen) and over."""
    model, params, _ = served
    ids, want, theirs = whole
    ids = ids[:, :length]
    with jax.default_matmul_precision("highest"):
        got, state = jax.jit(lambda p, i: model.apply(
            {"params": p}, i, mutable=["moe_stats", "intermediates"])
        )(params, ids)
    np.testing.assert_allclose(got, want[:, :length], atol=TOL)
    mine = _choices(state)
    assert len(mine) == len(theirs) == len(FULL)
    chosen = np.minimum(np.arange(length) + 1, TOPK)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)[:, :length, :length])
        np.testing.assert_array_equal(
            np.asarray(a).sum(-1), np.broadcast_to(chosen, (ROWS, length)))
    for i in FULL:
        stat = np.asarray(state["moe_stats"]["model"][f"layer_{i}"][
            "attention"][llama.SPARSE_STAT_NAME][0])
        assert stat.tolist() == [ROWS * chosen.sum(),
                                 ROWS * length * (length + 1) // 2]
    if length > TOPK:
        # The two layers' indexers do not choose alike.
        assert (np.asarray(mine[0]) != np.asarray(mine[1])).any()


@pytest.mark.parametrize("page", [4, 16])
def test_served_tokens_are_the_references(served, page, tmp_path):
    """Batch-1 prefill, the seat, then paged decode through the engine,
    five requests over three slots (so that a slot is freed and seated
    again mid-run), sequences of 6 to 46 positions: every served token
    is the reference's best to float32 rounding. At a page of 16 both
    pools are held folded (``[NP, 4, 128]``, ``[NP, 2, 128]``). The
    spans carry what was chosen of what was live; no pool is copied."""
    model, params, key = served
    copies = registry().counter("serve_kv_pool_copies").value
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params, page_size=page)
        reqs = _requests()
        got = sess.serve(reqs)
        records = list(rec.records)
    finally:
        obs_spans.disable()
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert _gaps(key, reqs, got).max() <= TOL
    assert registry().counter("serve_kv_pool_copies").value == copies
    assert registry().gauge("serve_index_topk").value == TOPK
    assert registry().gauge("serve_index_pools").value == len(FULL)
    pools = sess.engine.cache.cache["model"]
    for i in range(5):
        names = sorted(pools[f"layer_{i}"]["attention"])
        assert names == (["pages_index_k", "pages_kv"] if i in FULL
                         else ["pages_kv"])
    if page == 16:
        assert pools["layer_0"]["attention"]["pages_kv"].shape[1:] == (4, 128)
        assert pools["layer_0"]["attention"]["pages_index_k"].shape[1:] == (
            2, 128)
    prefills, steps = _spans(records, "prefill"), _spans(records, "decode_step")
    assert len(prefills) == len(reqs) and steps
    for span in prefills + steps:
        assert span["index_layers"] == len(FULL)
        assert 0 < span["sparse_rows_chosen"] <= span["sparse_rows_live"]
    by_tokens = {s["tokens"]: s for s in prefills}
    n = np.arange(1, 17)
    assert by_tokens[16]["sparse_rows_live"] == n.sum()
    assert by_tokens[16]["sparse_rows_chosen"] == np.minimum(n, TOPK).sum()
    # A late step's slots each see more than they attend.
    assert steps[-1]["sparse_rows_chosen"] < steps[-1]["sparse_rows_live"]
    shares = registry().histogram("serve_sparse_chosen_share").snapshot()
    assert shares["count"] > 0


# -- (b) the hand-on, and the two forms ----------------------------------------


def test_a_shared_layer_handed_the_wrong_choice_fails(served, whole,
                                                      monkeypatch):
    """Layer 4 reuses layer 3's choice, not layer 0's: a stack that
    hands every ``shared`` layer the FIRST indexer's choice loses to the
    reference by a thousand times the tolerance."""
    model, params, _ = served
    ids, want, _ = whole

    def first_choice_everywhere(model_, block, x, positions, kv_mask, decode,
                                paged, adapters):
        cfg = model_.cfg
        first = None
        for i in range(cfg.num_layers):
            x, choice = block(cfg, cfg.mlp_kind(i), i, name=f"layer_{i}")(
                x, positions, kv_mask, decode, paged, None, first)
            first = choice if first is None else first
        return llama.RMSNorm(cfg.rms_norm_eps, name="final_norm")(x)

    monkeypatch.setattr(llama, "_sparse_stack", first_choice_everywhere)
    wrong = jax.jit(lambda p, i: model.apply({"params": p}, i))(params, ids)
    assert float(jnp.abs(wrong - want).max()) > 1000 * TOL


@pytest.mark.parametrize("fold", [1, 4])
def test_the_two_forms_agree_under_a_choice(fold):
    """Keys and values up-projected from the rows under ``mask &
    choice`` (prefill), the absorbed query over the same rows (decode's
    dense form), and the absorbed query over the CHOSEN rows gathered
    out of a page pool (``chosen_latent_rows``; the pool held folded
    too) give the same context."""
    from tpudl.models.paged import PagedView
    from tpudl.ops.paged_attention import chosen_latent_rows

    rng = np.random.default_rng(7)
    b, t, h, r, dn, dr, dv, k, ps = 2, 32, 4, 16, 16, 16, 16, 8, 16
    q_nope = jnp.asarray(rng.normal(size=(b, 1, h, dn)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, 1, h, dr)), jnp.float32)
    rows = jnp.asarray(rng.normal(size=(b, t, r + dr)), jnp.float32)
    kv_b = jnp.asarray(rng.normal(size=(r, h, dn + dv)), jnp.float32)
    lens = jnp.asarray([t - 1, 19], jnp.int32)
    seen = jnp.arange(t)[None] <= lens[:, None]
    chosen = jnp.stack([
        jnp.asarray(rng.permutation(int(n) + 1)[:k], jnp.int32) for n in lens
    ])[:, None]
    choice = jnp.zeros((b, 1, t), bool).at[
        jnp.arange(b)[:, None], 0, chosen[:, 0]].set(True)
    mask = seen[:, None, None, :]
    up = llama._mla_prefill(q_nope, q_rope, rows, kv_b, dn, mask, 0.2,
                            None, False, choice)
    absorbed = llama._mla_absorbed(
        q_nope, q_rope, rows, kv_b, dn, (mask & choice[:, None]), 0.2)
    np.testing.assert_allclose(up, absorbed, atol=1e-5)
    # The same rows in a page pool, slot b's pages scattered.
    pages = t // ps
    table = jnp.asarray(1 + rng.permutation(b * pages).reshape(b, pages),
                        jnp.int32)
    pool = jnp.zeros((1 + b * pages, ps, r + dr), jnp.float32).at[table].set(
        rows.reshape(b, pages, ps, r + dr))
    pool = pool.reshape(pool.shape[0], ps // fold, fold * (r + dr))
    view = PagedView(page_table=table, start=jnp.zeros((b,), jnp.int32),
                     lens=lens, page_size=ps, quantized=False)
    query = llama._absorbed_query(q_nope, q_rope, kv_b, dn)
    u = chosen_latent_rows(query, pool, view, r, 0.2, chosen)
    paged = jnp.einsum("bshr,rhd->bshd", u, kv_b[..., dn:])
    np.testing.assert_allclose(paged, absorbed, atol=1e-5)
    assert view.took == [False]


# -- (c) a choice of everything is no choice -----------------------------------


def test_a_topk_of_the_whole_sequence_is_plain_latent_attention(served):
    """``index_topk`` >= every length: each position is chosen, and the
    model IS the same weights without an indexer, bit for bit: full
    forward, and prefill then paged decode (where a table that holds no
    more than ``index_topk`` positions takes the path that was there)."""
    _, params, _ = served
    model = _built(dict(CONFIG, index_topk=MAX_SEQ))[0]
    plain_cfg = dataclasses.replace(
        model.cfg, index_topk=0, index_n_heads=0, index_head_dim=0,
        indexer_types=None)
    plain = LlamaForCausalLM(plain_cfg)
    bare = jax.tree.map(lambda a: a, params)
    for i in FULL:
        del bare["model"][f"layer_{i}"]["attention"]["indexer"]
    ids = jnp.asarray(np.random.default_rng(5).integers(
        1, 128, size=(2, 40)), jnp.int32)
    np.testing.assert_array_equal(
        jax.jit(lambda p: model.apply({"params": p}, ids))(params),
        jax.jit(lambda p: plain.apply({"params": p}, ids))(bare))
    reqs = _requests()[1:3]
    with_indexer = _session(model, params).serve(reqs)
    without = _session(plain, bare).serve(reqs)
    for r in reqs:
        assert (list(with_indexer[r.request_id].tokens)
                == list(without[r.request_id].tokens))


# -- (d) the second leaf through the cache's other paths -----------------------


def test_int8_pools_serve_close_to_the_reference(served):
    """The int8 control's path: weights (the indexer's ``q_proj`` and
    ``k_proj`` with them; ``weights_proj`` and the LayerNorm kept) and
    both kinds of pool int8, a scale leaf a pool."""
    model, params, key = served
    sess = _session(model, params, weight_dtype="int8", kv_dtype="int8")
    indexer = sess.engine.params["model"]["layer_0"]["attention"]["indexer"]
    for name in ("q_proj", "k_proj"):
        assert set(indexer[name]["kernel"]) == {"qvalues", "qscale"}
    assert indexer["weights_proj"]["kernel"].dtype == jnp.float32
    assert indexer["k_norm"]["scale"].dtype == jnp.float32
    pools = sess.engine.cache.cache["model"]["layer_3"]["attention"]
    assert sorted(pools) == ["pages_index_k", "pages_kv", "scale_index_k",
                             "scale_kv"]
    assert pools["pages_index_k"].dtype == jnp.int8
    reqs = _requests()[:3]
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert _gaps(key, reqs, got).max() < 0.1


def test_prefix_sharing_serves_the_references_tokens(served):
    """Two prompts with a common first 12 tokens (over ``index_topk``)
    through a radix session: the second maps the first's pages in BOTH
    kinds of pool and prefills its suffix alone, its queries choosing
    among the gathered indexer keys; both are the reference's."""
    model, params, key = served
    hits = registry().counter("serve_prefix_hit_tokens").value
    sess = _session(model, params, prefix_share=True)
    head = np.random.default_rng(4).integers(1, 128, size=12).tolist()
    reqs = [Request("p0", head + [5, 6], max_new_tokens=12),
            Request("p1", head + [9, 3, 2], max_new_tokens=12)]
    got = {}
    for r in reqs:
        got.update(sess.serve([r]))
    assert _gaps(key, reqs, got).max() <= TOL
    assert registry().counter("serve_prefix_hit_tokens").value >= hits + 12


def test_a_request_migrates_with_its_indexer_keys(served):
    """Export mid-stream (past ``index_topk`` positions), install on
    another engine: the continuation is the uninterrupted one and the
    target pays no prefill."""
    model, params, _ = served
    req = Request("m0", [3, 5, 7, 11, 2, 9, 4], max_new_tokens=20)
    want = _session(model, params).serve([req])["m0"].tokens
    src, dst = _session(model, params), _session(model, params)
    src.submit(req)
    for _ in range(8):
        src.engine.step()
    payload = src.engine.export_request("m0")
    assert dst.engine.install_migrated(payload) == "m0"
    while dst.engine.step():
        pass
    assert list(dst.engine.results["m0"].tokens) == list(want)
    assert dst.engine.num_prefills == 0


# -- (e) the shares of the experts ---------------------------------------------


@pytest.mark.parametrize("count", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_whole(count):
    """Every share computes its own experts' part; the shared expert,
    which every chip computes alike, is counted once. Together they are
    the uncut layer: the reference's with every expert held."""
    s = dict(ref.settings(CONFIG), n_routed_experts=8)
    w = ref.layer_weights(ref.seed_key(5), 1, s, jnp.float32, False, False)
    w = dict(w, router_bias=5 * w["router_bias"])
    y = jnp.asarray(np.random.default_rng(1).normal(size=(18, 64)),
                    jnp.float32)
    whole = ref.experts(y, w, s)
    total = jnp.zeros_like(whole)
    for first in range(0, 8, count):
        part = dict(s, n_routed_experts=count, first_expert=first)
        held = {k: w[k][first:first + count]
                for k in ("experts_gate", "experts_up", "experts_down")}
        total = total + ref.experts(y, dict(w, **held), part,
                                    shared=first == 0)
    np.testing.assert_allclose(total, whole, atol=1e-5)
    # ... and the program's layer, told which experts it holds, gives
    # the reference's part for that share.
    from tpudl.ops.moe import DroplessMoE

    layer = DroplessMoE(
        num_experts=8, experts_per_token=2, intermediate_size=32,
        shared_intermediate_size=32, routed_scaling_factor=2.5,
        experts_held=(count, count), dtype=jnp.float32,
    )
    held = slice(count, 2 * count)
    params = {
        "router": {"kernel": w["router"]}, "router_bias": w["router_bias"],
        **{f"{n}_proj": {"kernel": w[f"experts_{n}"][held]}
           for n in ("gate", "up", "down")},
        **{f"shared_{n}_proj": {"kernel": w[f"shared_{n}"]}
           for n in ("gate", "up", "down")},
    }
    got, _ = layer.apply({"params": params}, y[None],
                         jnp.ones((1, 18), bool), mutable=["moe_stats"])
    part = dict(s, n_routed_experts=count, first_expert=count)
    want = ref.experts(
        y, dict(w, **{k: w[k][held] for k in
                      ("experts_gate", "experts_up", "experts_down")}), part)
    np.testing.assert_allclose(got[0], want, atol=1e-5)


# -- (f) what the indexer is not wired to says so ------------------------------


@pytest.mark.parametrize("change, sentence", [
    (dict(index_topk=-1), "index_topk must be >= 0"),
    (dict(index_topk=0), "describe the indexer of learned sparse attention"),
    (dict(q_lora_rank=0), "needs attention='mla' and q_lora_rank > 0"),
    (dict(attention="gqa"), "needs attention='mla' and q_lora_rank > 0"),
    (dict(index_head_dim=8), "an index_head_dim that holds the 16 roped"),
    (dict(indexer_types=("shared",) + ("full",) * 4),
     "the first one 'full'"),
    (dict(indexer_types=("full",) * 4), "names each of the 5 layers"),
    (dict(block="shortcut", first_k_dense=0),
     "block='shortcut' is not wired to learned sparse attention"),
    (dict(hyper_streams=4),
     "hyper_streams is not wired to learned sparse attention"),
    (dict(lora_rank=4), "lora_rank is not wired to learned sparse attention"),
    (dict(remat=True),
     "moe_experts, fp8_train or remat is not wired to learned sparse"),
    (dict(layer_types=("full_attention",) * 5),
     "layer_types are the grouped-query block's"),
    (dict(sandwich_norm=True, loop_passes=2),
     "sandwich_norm is grouped-query attention"),
])
def test_the_configuration_refuses_with_a_sentence(served, change, sentence):
    with pytest.raises(ValueError, match=sentence):
        dataclasses.replace(served[0].cfg, **change)


@pytest.mark.parametrize("asked, sentence", [
    (dict(mesh=object()),
     "a mesh-committed session is not wired to learned sparse attention"),
    (dict(spec_k=2), "spec_k is not wired to latent attention"),
    (dict(adapters={"t": {"lora_a": jnp.zeros((2, 2))}}),
     "per-tenant adapters are not wired to latent attention"),
])
def test_from_model_refuses_with_a_sentence(served, asked, sentence):
    model, params, _ = served
    with pytest.raises(ValueError, match=sentence):
        _session(model, params, **asked)


class _NoAdapters:
    """An adapter view as ``LlamaModel`` hands one down a layer."""

    def for_layer(self, name):
        return self


def test_the_stack_refuses_adapters_with_a_sentence(served):
    model, params, _ = served
    with pytest.raises(ValueError, match="adapters are not wired to "
                       "learned sparse attention"):
        model.apply({"params": params}, jnp.ones((1, 4), jnp.int32),
                    adapters=_NoAdapters())
