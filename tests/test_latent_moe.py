"""Latent (MLA) attention and dropless routed experts through the normal
serving path (ISSUE 26), at a tiny size on the CPU.

The program is held to ``perfbench/reference/mla_moe.py`` (float32,
whole sequences, no cache, no absorption, every held expert applied
plainly): served logits after a prefill and a decode through the paged
latent pool, the absorbed against the up-projected attention, YaRN's
frequencies and softmax scale, the expert layer with every token sent to
one expert, the shares of a layer adding up to the whole, the one-pool
layer seated, freed and seated again with no pool copy, and each
session option that is not wired refusing with its sentence.

The pool's held shape (ISSUE 29, ``tpudl.models.paged.page_fold``): the
rule as a table, and every path that touches the pool at a second tiny
size whose row the rule FOLDS (64 wide on pages of 4: two positions a
held row of 128 lanes), held to the same reference and to the pool
held as declared.

Reading the held pool in place (ISSUE 31): a session of that second
size on pages of 16 (a held page of 8 rows of 128 lanes) serves the
same tokens through the latent kernel of ``tpudl.ops.paged_attention``
as through the gather, and says which path it took.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.families.mla_moe_serve import model_config, to_flax
from perfbench.reference import mla_moe as ref
from tpudl.models.llama import (
    LlamaForCausalLM,
    RopeScaling,
    _mla_absorbed,
    _mla_up_projected,
)
from tpudl.models.paged import (
    PagedView,
    page_fold,
    paged_attend_mask,
    paged_gather,
    paged_write,
)
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.ops.moe import DroplessMoE
from tpudl.serve import Request, ServeSession

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "deepseek_yarn"}
#: Hidden 64, 4 heads, latent 16 + rope 8, 8 experts of which 4 held
#: (2-5), 3 layers with one leading dense: the published model's shape
#: at a size a test can hold.
CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "vocab_size": 256, "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "rope_scaling": YARN,
    "deployment": {"router_experts": 8, "first_expert": 2},
}
MAX_SEQ, WINDOW, PAGE, SLOTS = 64, 16, 4, 3
LATENT = CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"]
SETTINGS = ref.settings(CONFIG)
#: The same model with a row of 48 + 16 = 64: no whole number of lanes,
#: and two of them are (CONFIG's 24 would need 16 on a page of 4), so
#: the pool is held folded, ``[NP, 2, 128]``.
FOLDED = dict(CONFIG, kv_lora_rank=48, qk_rope_head_dim=16)


def _build(config):
    key = ref.seed_key(2**31 + 26)
    settings = ref.settings(config)
    model = LlamaForCausalLM(model_config(config, MAX_SEQ, jnp.float32))
    params = to_flax(ref.all_weights(key, settings, jnp.float32), settings)
    return model, params, key


@pytest.fixture(scope="module")
def served():
    """(model, params, key): float32, the reference's weights laid over
    the program's tree."""
    return _build(CONFIG)


@pytest.fixture(scope="module")
def folded():
    """``served`` at the size whose pool is held folded."""
    return _build(FOLDED)


@pytest.fixture(params=["declared", "folded"])
def either(request):
    """(model, params, key, config) of both sizes in turn: the pool
    held as its layer declares it, and held folded."""
    name, config = {
        "declared": ("served", CONFIG), "folded": ("folded", FOLDED),
    }[request.param]
    return (*request.getfixturevalue(name), config)


def _session(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, WINDOW, **kw)


def _requests(shared=0, seed=0):
    rng = np.random.default_rng(seed)
    head = rng.integers(1, 256, size=shared).tolist()
    return [
        Request(f"r{i}", head + rng.integers(1, 256, size=n).tolist(),
                max_new_tokens=m)
        for i, (n, m) in enumerate(
            [(5, 9), (WINDOW - shared, 4), (3, 12), (7, 6), (2, 5)])
    ]


def _margins(key, reqs, got, config=CONFIG, dtype=jnp.float32):
    """The reference's margin at every served token, teacher-forced."""
    width = WINDOW + max(r.max_new_tokens for r in reqs)
    t_max = max(r.max_new_tokens for r in reqs)
    ids = np.zeros((len(reqs), width), np.int32)
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
    out = np.asarray(ref.margins(
        key, config, dtype, jnp.asarray(ids), jnp.asarray(picks),
        jnp.asarray(chosen)))
    return out[valid]


# -- the served path against the reference ----------------------------------


@pytest.mark.parametrize("options, limit", [
    ({}, 2e-4),
    ({"prefix_share": True}, 2e-4),
    ({"kv_dtype": "int8"}, 0.05),
    ({"kv_dtype": "int8", "prefix_share": True}, 0.05),
], ids=["plain", "prefix_share", "int8_latents", "int8_prefix_share"])
def test_served_tokens_are_the_references(either, options, limit):
    """Prefill, then decode through the paged latent pool, more
    requests than slots and every length different: each served token
    is the reference's best to within ``limit`` logits (float32
    rounding; a quantisation step of the cached rows for int8)."""
    model, params, key, config = either
    copies = registry().counter("serve_kv_pool_copies").value
    sess = _session(model, params, **options)
    reqs = _requests(shared=8 if options.get("prefix_share") else 0)
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    assert all(len(got[r.request_id].tokens) == r.max_new_tokens for r in reqs)
    gaps = _margins(key, reqs, got, config)
    assert gaps.max() <= limit, gaps.max()
    assert registry().counter("serve_kv_pool_copies").value == copies
    if options.get("prefix_share"):
        assert sess.engine.cache.radix.stats()["cached_pages"] >= 2


def test_int8_weights_part_from_the_reference_more_than_float32(served):
    """The control's path runs (expert kernels, shared expert and the
    projections quantised; router, latent down-projection and W_kv_b
    kept) and is what it is meant to be: further from the reference."""
    from tpudl.quant import quantize_model

    model, params, key = served
    sess = _session(model, params, weight_dtype="int8", kv_dtype="int8")
    moe = sess.engine.params["model"]["layer_1"]["moe"]
    assert set(moe["gate_proj"]["kernel"]) == {"qvalues", "qscale"}
    assert moe["gate_proj"]["kernel"]["qvalues"].shape == (4, 64, 32)
    assert set(moe["shared_up_proj"]["kernel"]) == {"qvalues", "qscale"}
    assert moe["router"]["kernel"].dtype == jnp.float32
    attention = sess.engine.params["model"]["layer_1"]["attention"]
    assert attention["kv_b_proj"].dtype == jnp.float32
    reqs = _requests()
    got = sess.serve(reqs)
    assert all(got[r.request_id].finish_reason == "length" for r in reqs)
    ids = jnp.asarray(
        np.random.default_rng(4).integers(1, 256, size=(2, 24)), jnp.int32)
    x, outer = ref.forward(key, CONFIG, jnp.float32, ids)
    want = np.asarray(ref.head(x, outer, SETTINGS))
    sound = np.abs(np.asarray(model.apply({"params": params}, ids)) - want)
    qmodel, qparams = quantize_model(model, params, "int8")
    control = np.abs(np.asarray(qmodel.apply({"params": qparams}, ids)) - want)
    assert control.mean() > 10 * sound.mean() and control.mean() > 1e-5


def test_the_pool_is_one_leaf_a_layer_with_no_head_axis(either):
    """One leaf a layer, held in the shape the rule gives its row (24
    wide: as declared; 64 wide: two positions a held row), the same
    bytes either way; an int8 pool keeps a row a position, its scales
    beside it."""
    model, params, _, config = either
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    fold = page_fold(PAGE, (width,), jnp.float32)
    assert fold == {24: 1, 64: 2}[width]
    cache = _session(model, params).engine.cache
    layers = cache.cache["model"]
    assert sorted(layers) == ["layer_0", "layer_1", "layer_2"]
    for layer in layers.values():
        assert list(layer["attention"]) == ["pages_kv"]
        assert layer["attention"]["pages_kv"].shape == (
            cache.num_pages, PAGE // fold, fold * width)
    pools = 3 * cache.num_pages * PAGE * width * 4
    host = (cache.page_table.nbytes + cache.start.nbytes + cache.lens.nbytes)
    assert cache.nbytes == pools + host
    quantized = _session(model, params, kv_dtype="int8").engine.cache
    pool = quantized.cache["model"]["layer_1"]["attention"]
    assert sorted(pool) == ["pages_kv", "scale_kv"]
    assert pool["pages_kv"].dtype == jnp.int8
    assert pool["pages_kv"].shape == (quantized.num_pages, PAGE, width)
    assert pool["scale_kv"].shape == (quantized.num_pages, PAGE)


def test_seat_free_and_seat_again_copy_no_pool(either):
    """The donation rule holds for the one-pool layer: a pool tree kept
    from before a seat is dead after it, the counter stays where it
    was, and a freed slot's pages seat the next prompt."""
    model, params, _, _ = either
    sess = _session(model, params)
    cache, engine = sess.engine.cache, sess.engine
    copies = registry().counter("serve_kv_pool_copies").value
    free = cache.free_pages
    ids = np.arange(1, WINDOW + 1, dtype=np.int32)[None]
    _, row, _ = engine.prefill_call(params, ids, np.ones_like(ids))
    for _ in range(2):
        before = cache.cache
        cache.seat(row, slot=1, pad=0, prompt_len=WINDOW,
                   reserve_tokens=WINDOW + 8)
        assert jax.tree.leaves(before)[0].is_deleted()
        assert cache.free_pages == free - (WINDOW + 8) // PAGE
        assert cache.tokens_live == WINDOW
        cache.free(1)
        assert cache.free_pages == free and cache.tokens_live == 0
    assert registry().counter("serve_kv_pool_copies").value == copies


def test_a_request_migrates_with_its_latent_rows(either):
    """Export mid-stream, install on another engine: the continuation is
    the uninterrupted one and the target pays no prefill."""
    model, params, _, _ = either
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


# -- the held pool read in place (ISSUE 31) ----------------------------------


@pytest.mark.parametrize("path, layers", [("gather", 0), ("in_place", 3)])
def test_a_session_serves_the_references_tokens_on_either_path(
        folded, path, layers, monkeypatch, tmp_path):
    """End to end through the engine on pages of 16 (held [NP, 8, 128]):
    the decode program that reads the pool in place (what ``auto`` finds
    on a TPU: only the kernel module's own question is answered so, and
    the kernel runs in interpret mode) and the one that gathers serve
    the reference's tokens, copy no pool, and say which path they took:
    ``in_place_layers``, the gauge, ``kv_in_place`` and ``pages_live``
    on every ``decode_step`` span."""
    import tpudl.ops.paged_attention as pa

    model, params, key = folded
    if path == "in_place":
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    copies = registry().counter("serve_kv_pool_copies").value
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params, page_size=16)
        reqs = _requests()
        got = sess.serve(reqs)
        steps = [r for r in rec.records
                 if r.get("kind") == "span" and r.get("name") == "decode_step"]
    finally:
        obs_spans.disable()
    assert _margins(key, reqs, got, FOLDED).max() <= 2e-4
    assert registry().counter("serve_kv_pool_copies").value == copies
    cache = sess.engine.cache
    assert cache.folds == (2, 2, 2)
    assert registry().gauge("serve_kv_pool_folded_layers").value == 3
    assert cache.in_place_layers == layers
    assert registry().gauge("serve_paged_attention_in_place").value == layers
    assert steps and all(
        s["kv_in_place"] == int(layers > 0) for s in steps)
    # An idle slot costs a page, a busy one the pages its live positions
    # lie on: never its whole table of four.
    assert all(SLOTS <= s["pages_live"] <= 4 * SLOTS for s in steps)
    assert any(s["pages_live"] > SLOTS for s in steps)


def test_both_paths_serve_the_same_tokens(folded, monkeypatch):
    import tpudl.ops.paged_attention as pa

    model, params, _ = folded
    tokens = []
    for on_a_tpu in (False, True):
        monkeypatch.setattr(pa, "is_tpu_backend", lambda: on_a_tpu)
        got = _session(model, params, page_size=16).serve(_requests(seed=3))
        tokens.append({rid: list(r.tokens) for rid, r in got.items()})
    assert tokens[0] == tokens[1]


# -- the pool's held shape (ISSUE 29) ----------------------------------------


@pytest.mark.parametrize("page, tail, dtype, fold", [
    (16, (8, 128), jnp.bfloat16, 1),   # a head axis (Mistral): as today
    (16, (2, 64), jnp.bfloat16, 1),    # a head axis, whatever its width
    (16, (512,), jnp.bfloat16, 1),     # whole lanes already
    (16, (576,), jnp.bfloat16, 2),     # the latent row: [NP, 8, 1152]
    (16, (576,), jnp.int8, 1),         # int8 keeps a row a position
    (4, (24,), jnp.float32, 1),        # 24 x 16 = 384: 16 is no part of 4
    (4, (64,), jnp.float32, 2),
    (16, (64,), jnp.float32, 2),       # 16 / 2 = 8 rows: one whole tile
    (32, (96,), jnp.bfloat16, 4),      # 4, 8, 16, 32 fit; 32 / 4 = 8 rows
    (16, (32,), jnp.float32, 4),       # no fold leaves whole tiles: least
    (16, (100,), jnp.bfloat16, 1),     # needs 32 positions
], ids=str)
def test_the_rule_of_the_held_shape(page, tail, dtype, fold):
    assert page_fold(page, tail, dtype) == fold
    assert page % fold == 0
    if fold > 1:
        assert (fold * tail[0]) % 128 == 0


def _view(table, start, lens):
    return PagedView(jnp.asarray(table, jnp.int32),
                     jnp.asarray(start, jnp.int32),
                     jnp.asarray(lens, jnp.int32), PAGE, False)


@pytest.mark.parametrize("chunk", [1, 3])
def test_folded_write_gather_and_attention_are_the_declared_pools(chunk):
    """The same steps on a pool held folded ``[NP, 2, 128]`` and on one
    held as declared ``[NP, 4, 64]``: after every write the folded pool
    is the declared one's bytes, and the attention over its held rows is
    the attention over the logical rows. Slot 0 writes odd positions
    from an odd left pad, slot 1 even ones across a page boundary,
    slot 2 is idle on the trash page, slot 3 writes up to and past the
    table's capacity (the overshoot lands on the trash page)."""
    rng = np.random.default_rng(29 + chunk)
    heads, r, dn, dr, dv, pages = 4, 48, 16, 16, 16, 3
    width, num_pages = r + dr, 1 + 4 * pages
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    kv_b = f(r, heads, dn + dv) * 0.3
    declared = f(num_pages, PAGE, width)
    held = declared.reshape(num_pages, PAGE // 2, 2 * width)
    table = np.zeros((4, pages), np.int32)
    for slot in (0, 1, 3):
        table[slot] = 1 + slot * pages + np.arange(pages)
    start = np.array([3, 0, 0, 1])
    lens = np.array([5, 2, 0, pages * PAGE - 2 * chunk])
    for _ in range(3):
        view = _view(table, start, lens)
        value = f(4, chunk, width)
        declared, _ = paged_write(declared, None, value, view)
        held, _ = paged_write(held, None, value, view)
        np.testing.assert_array_equal(
            np.asarray(held).reshape(declared.shape)[1:],
            np.asarray(declared)[1:])
        rows = paged_gather(held, None, view, jnp.float32)
        assert rows.shape == (4, pages * PAGE // 2, 2 * width)
        q_nope, q_rope = f(4, chunk, heads, dn), f(4, chunk, heads, dr)
        got = _mla_absorbed(
            q_nope, q_rope, rows, kv_b, dn,
            paged_attend_mask(view, chunk, fold=2), 0.2)
        want = _mla_absorbed(
            q_nope, q_rope, paged_gather(declared, None, view, jnp.float32),
            kv_b, dn, paged_attend_mask(view, chunk), 0.2)
        live = [0, 1, 3]  # an idle slot attends nothing: any row will do
        np.testing.assert_allclose(
            np.asarray(got)[live], np.asarray(want)[live], atol=2e-6)
        lens = lens + chunk * np.array([1, 1, 0, 1])
    # The last chunks of slot 3 ran past its three pages.
    assert lens[3] > pages * PAGE


def test_folded_rows_come_back_bit_for_bit(folded):
    """Logical rows in, logical rows out, whatever shape the pool holds
    them in: a prefill's rows seated left-aligned and gathered back for
    a suffix prefill; a request exported and installed, its rows read
    on the target; and a slot seated, decoded, freed and seated again."""
    from tpudl.serve.cache import _migration_gather, parse_migration

    model, params, _ = folded
    sess = _session(model, params, prefix_share=True)
    cache, engine = sess.engine.cache, sess.engine
    assert cache.cache["model"]["layer_0"]["attention"]["pages_kv"].shape[1:] == (
        PAGE // 2, 2 * 64)
    ids = np.arange(1, WINDOW + 1, dtype=np.int32)[None]
    _, row, _ = engine.prefill_call(params, ids, np.ones_like(ids))
    want = {name: np.asarray(layer["attention"]["kv"][0, :WINDOW])
            for name, layer in row["model"].items()}
    cache.seat_shared(row, slot=0, input_ids=ids[0],
                      reserve_tokens=WINDOW + 8)
    matched, lease = cache.match_and_lease(ids[0][:WINDOW - 3])
    assert len(matched) == (WINDOW - 3) // PAGE
    back = cache.gather_prefix_rows(matched, len(matched) * PAGE)
    cache.release_lease(lease)
    for name, rows in want.items():
        got = np.asarray(back["model"][name]["attention"]["kv"][0])
        n = len(matched) * PAGE
        np.testing.assert_array_equal(got[:n], rows[:n])
    # Migration: the payload carries logical rows [T, 64].
    meta = {"request": {"input_ids": ids[0].tolist()},
            "reserve_tokens": WINDOW + 8}
    parsed = parse_migration(cache.export_request(0, meta))
    for path, arr in parsed["_arrays"].items():
        layer = path.split("'")[3]
        np.testing.assert_array_equal(arr, want[layer])
    other = _session(model, params).engine.cache
    other.import_request(cache.export_request(0, meta), slot=2)
    landed = _migration_gather(
        other.cache, jnp.asarray(other.page_table[2]), PAGE)
    for name, rows in want.items():
        got = np.asarray(landed["model"][name]["attention"]["pages_kv"])
        np.testing.assert_array_equal(got[:WINDOW], rows)
    # Seat, decode, free, seat again: the second tenant of the pages
    # is served as the first was.
    plain = _session(model, params, num_slots=1)
    req = lambda rid: Request(rid, [3, 5, 7, 11, 2], max_new_tokens=7)  # noqa: E731
    first = plain.serve([req("a")])["a"].tokens
    assert plain.engine.cache.free_pages == plain.engine.cache.num_pages - 1
    assert list(plain.serve([req("b")])["b"].tokens) == list(first)


def test_an_exported_folded_pool_is_read_back_at_its_page_size(tmp_path):
    """``from_artifacts`` recovers the page size from shapes alone: a
    folded pool leaf has ``page_size / f`` rows to a page, and the
    prefill artifact's dense row says how wide one position is."""
    from tpudl.export.decode import export_serving_decoder

    # Latent attention over dense layers: an artifact session takes the
    # two-value contracts, which a model with routed experts has not.
    model = LlamaForCausalLM(dataclasses.replace(
        model_config(FOLDED, MAX_SEQ, jnp.float32), num_layers=2,
        num_experts=0, experts_per_token=0, num_shared_experts=0,
        first_k_dense=0, experts_held=None,
    ))
    ids = jnp.ones((1, WINDOW), jnp.int32)
    params = model.init(jax.random.key(29), ids)["params"]
    prefix = str(tmp_path / "latent")
    export_serving_decoder(
        model, params, num_slots=SLOTS, prompt_len=WINDOW,
        path_prefix=prefix, page_size=PAGE,
    )
    art = ServeSession.from_artifacts(
        f"{prefix}.prefill.stablehlo", f"{prefix}.decode.stablehlo", params)
    cache = art.engine.cache
    assert cache.page_size == PAGE and cache.folds == (2, 2)
    assert cache.max_seq_len == MAX_SEQ
    live = _session(model, params).serve(_requests())
    served = art.serve(_requests())
    assert {k: v.tokens for k, v in served.items()} == {
        k: v.tokens for k, v in live.items()}


@pytest.mark.parametrize("options, sentence", [
    ({"paged": False}, "dense slot cache was removed"),
    ({"adapters": {"t": {}}}, "adapters are not wired to latent"),
    ({"spec_k": 2}, "spec_k is not wired to latent"),
], ids=["dense_cache", "tenant_adapters", "speculation"])
def test_an_option_that_is_not_wired_refuses_with_its_sentence(
        served, options, sentence):
    model, params, _ = served
    with pytest.raises(ValueError, match=sentence):
        _session(model, params, **options)


# -- tokens per expert, on the spans and in the registry ---------------------


def test_tokens_per_held_expert_reach_the_spans_and_the_registry(
        served, tmp_path):
    model, params, _ = served
    reg = registry()
    before = reg.counter("serve_moe_assignments").value
    seen = reg.histogram("serve_moe_tokens_per_expert").count
    rec = obs_spans.enable(str(tmp_path))
    try:
        sess = _session(model, params)
        reqs = _requests()
        sess.serve(reqs)
        spans = [r for r in rec.records if r.get("kind") == "span"]
    finally:
        obs_spans.disable()
    steps = [s for s in spans if s["name"] == "decode_step"]
    prefills = [s for s in spans if s["name"] == "prefill"]
    assert steps and len(prefills) == len(reqs)
    for s in steps + prefills:
        assert {"moe_assignments", "moe_experts_touched",
                "moe_load_max_over_mean"} <= set(s)
        # Two expert layers of four held experts.
        assert 0 <= s["moe_experts_touched"] <= 8
    for s in steps:
        # A real token picks 2 of 8 experts in each of 2 layers; idle
        # slots ride along and are not counted.
        assert s["moe_assignments"] <= 2 * 2 * s["busy"]
    by_rid = {r.request_id: r for r in reqs}
    for s in prefills:
        # Padding is not counted either: at most 2 x 2 a prompt token.
        n = len(by_rid[s["request_id"]].input_ids)
        assert 0 < s["moe_assignments"] <= 2 * 2 * n
    total = sum(s["moe_assignments"] for s in steps + prefills)
    assert reg.counter("serve_moe_assignments").value - before == total
    assert reg.histogram("serve_moe_tokens_per_expert").count - seen == (
        8 * len(steps + prefills))


# -- attention: two forms, one result; YaRN ----------------------------------


@pytest.mark.parametrize("chunk", [1, 3])
def test_absorbed_attention_is_the_up_projected(chunk):
    rng = np.random.default_rng(chunk)
    b, t, heads, r, dn, dr, dv = 2, 12, 4, 16, 16, 8, 16
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)  # noqa: E731
    q_nope, q_rope = f(b, chunk, heads, dn), f(b, chunk, heads, dr)
    rows, kv_b = f(b, t, r + dr), f(r, heads, dn + dv) * 0.3
    lens = np.array([5, 9])
    pos = np.arange(t)[None, None, None, :]
    mask = jnp.asarray(
        pos <= (lens[:, None, None, None] + np.arange(chunk)[None, None, :, None])
    )
    a = _mla_absorbed(q_nope, q_rope, rows, kv_b, dn, mask, 0.2)
    u = _mla_up_projected(q_nope, q_rope, rows, kv_b, dn, mask, 0.2)
    np.testing.assert_allclose(np.asarray(a), np.asarray(u), atol=2e-5)


def test_yarn_frequencies_and_softmax_scale_are_the_references():
    scaling = RopeScaling(factor=40.0, original_max_position=4096,
                          mscale=1.0, mscale_all_dim=1.0)
    published = dict(SETTINGS, qk_rope_head_dim=64, qk_nope_head_dim=128,
                     yarn_original=4096)
    got = np.asarray(scaling.inv_freq(64, 10000.0))
    np.testing.assert_allclose(got, np.asarray(ref.yarn_inv_freq(published)),
                               rtol=1e-6)
    plain = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    # Fast frequencies are kept, slow ones stretched forty times.
    np.testing.assert_allclose(got[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 40, rtol=1e-6)
    sigma = 192 ** -0.5 * scaling.attention_scale
    assert sigma == pytest.approx(ref.softmax_scale(published))
    assert sigma == pytest.approx(0.13523, abs=1e-5)
    assert scaling.cos_sin_scale == 1.0
    # No mscale_all_dim: the softmax keeps its plain scale.
    assert RopeScaling(40.0, 4096).attention_scale == 1.0


def test_full_forward_agrees_with_the_reference(served):
    """The up-projected (training / scoring) path, whole sequences."""
    model, params, key = served
    ids = np.random.default_rng(3).integers(1, 256, size=(2, 24))
    x, outer = ref.forward(key, CONFIG, jnp.float32, jnp.asarray(ids))
    want = ref.head(x, outer, SETTINGS)
    got = model.apply({"params": params}, jnp.asarray(ids, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


# -- the expert layer ---------------------------------------------------------


def _layer(held, width=8, k=2, shared=32):
    return DroplessMoE(
        num_experts=width, experts_per_token=k, intermediate_size=32,
        shared_intermediate_size=shared, routed_scaling_factor=2.5,
        experts_held=held, dtype=jnp.float32,
    )


def _layer_weights(seed=5, width=8):
    s = dict(SETTINGS, num_experts=width, router_experts=width,
             first_expert=0)
    w = ref.layer_weights(ref.seed_key(seed), 1, s, jnp.float32)
    # A bias large enough to decide some choices, as the seeded one is
    # meant to.
    return s, dict(w, router_bias=5 * w["router_bias"])


def _params(w, first, count, shared=True):
    held = slice(first, first + count)
    out = {
        "router": {"kernel": w["router"]}, "router_bias": w["router_bias"],
        **{f"{n}_proj": {"kernel": w[f"experts_{n}"][held]}
           for n in ("gate", "up", "down")},
    }
    if shared:
        out.update({f"shared_{n}_proj": {"kernel": w[f"shared_{n}"]}
                    for n in ("gate", "up", "down")})
    return out


def _apply(layer, params, x, real=None):
    real = jnp.ones(x.shape[:2], bool) if real is None else real
    y, state = layer.apply({"params": params}, x, real,
                           mutable=["moe_stats"])
    return y, np.asarray(state["moe_stats"]["tokens_per_expert"][0])


@pytest.mark.parametrize("count", [2, 4])
def test_the_shares_of_a_layer_add_up_to_the_whole(count):
    """Every share computes its own experts' part; the shared expert,
    which every chip computes alike, is counted once. Together they are
    the uncut layer, and the uncut layer is the reference's."""
    s, w = _layer_weights()
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 64)),
                    jnp.float32)
    whole, counts = _apply(_layer(None), _params(w, 0, 8), x)
    want = ref.experts(x.reshape(-1, 64), w, s).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=1e-5)
    assert counts.sum() == 2 * 18  # dropless: every choice is served
    total = jnp.zeros_like(whole)
    seen = []
    for first in range(0, 8, count):
        part, c = _apply(_layer((first, count), shared=32 * (first == 0)),
                         _params(w, first, count, shared=first == 0), x)
        total = total + part
        seen.append(c)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(seen), counts)


def test_every_token_sent_to_one_expert_and_none_dropped():
    """A selection bias that puts expert 3 into every token's top 2:
    with no capacity the expert takes all 40 tokens, and the layer is
    still the reference's."""
    s, w = _layer_weights()
    w = dict(w, router_bias=w["router_bias"].at[3].set(10.0))
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 40, 64)),
                    jnp.float32)
    real = jnp.arange(40)[None] >= 4  # the first four are padding
    y, counts = _apply(_layer((2, 4)), _params(w, 2, 4), x, real)
    assert counts[1] == 36 and counts.sum() <= 2 * 36
    s_held = dict(s, num_experts=4, first_expert=2)
    w_held = dict(w, **{k: w[k][2:6] for k in
                        ("experts_gate", "experts_up", "experts_down")})
    want = ref.experts(x.reshape(-1, 64), w_held, s_held).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)
    # The bias decides the choice and never the weight: the gates are
    # the renormalised scores themselves.
    gates = np.asarray(ref.route(x[0], w, s))
    assert np.allclose(gates.sum(-1), 2.5, atol=1e-5)
    assert (gates[:, 3] > 0).all()


def test_a_layer_is_told_which_experts_it_holds_or_refuses():
    x = jnp.zeros((1, 2, 64))
    with pytest.raises(ValueError, match="outside the router's 8 experts"):
        _layer((6, 4)).init(jax.random.key(0), x, jnp.ones((1, 2), bool))


def test_the_config_names_each_layers_kind():
    cfg = model_config(CONFIG, MAX_SEQ, jnp.float32)
    assert [cfg.mlp_kind(i) for i in range(3)] == ["dense", "moe", "moe"]
    assert cfg.expert_layers == 2 and cfg.experts_held == (2, 4)
    with pytest.raises(ValueError, match="attention='mla' needs"):
        dataclasses.replace(cfg, kv_lora_rank=0)
    with pytest.raises(ValueError, match="two layers: set one"):
        dataclasses.replace(cfg, moe_experts=4)
