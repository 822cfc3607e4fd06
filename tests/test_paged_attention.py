"""Decode attention that reads the live pages where they lie (ISSUE 27).

The kernel of ``tpudl.ops.paged_attention`` (interpret mode here: the
CPU test mode) is held to the path it replaces, ``paged_gather`` +
``_gqa_decode_attention`` under ``paged_attend_mask``, at tiny sizes:
ragged lengths, left padding, an idle slot on the trash page, lengths on
a page boundary and at the table's last position, unmapped pages, one
query a slot and a verify window of three, one and four query heads a
KV head. Then the choice the program makes by what it can observe: a
plain k / v pool takes the kernel on a TPU; an int8 pool, a headless
(latent) pool and a pool committed to a mesh keep the gather, and so
does every CPU run. And one session end to end through the kernel,
which serves the gather's tokens and says so in its spans and gauge.

The latent sibling (ISSUE 31) is held the same way to the path IT
replaces, ``paged_gather`` + ``attend_latent_rows``: ONE headless pool
held folded (two positions of 576 a held row) and held as a row of
whole lanes, an idle slot, a slot full to 1,280, lengths at a page's
and at a block's edge, left padding; and chosen the same way: one token
a slot over an unquantized pool on one device of a TPU whose held row
is whole lanes, the gather for everything else.

What only a chip shows (the compiled kernels, their times) is in
``tests/test_tpu_compile.py`` (a compile for a described v5e) and in
PERF.md (chip runs).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.ops.paged_attention as pa
from tpudl import obs
from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
from tpudl.models.paged import PagedView
from tpudl.obs import registry
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

#: 4 slots x 20 pages of 4 positions: a slot spans up to three blocks
#: of ``PAGES_PER_BLOCK`` = 8 pages, so the double buffer turns over
#: inside a slot and between slots.
B, P, PS, HKV, D = 4, 20, 4, 2, 128
L = P * PS
NP = B * P + 1

#: name -> (start [B], lens [B], table rows unmapped from page j on, or
#: None). ``lens`` is where this step's first token was written.
CASES = {
    "ragged_lens": ([0, 0, 0, 0], [2, 17, 40, 70], None),
    "left_pad": ([3, 16, 37, 5], [9, 50, 38, 75], None),
    "idle_slot_on_trash_page": ([0, 6, 0, 0], [31, 44, 0, 12], {2: 0}),
    "lens_on_page_boundary": ([0, 4, 8, 0], [15, 16, 31, 32], None),
    "lens_at_table_end": ([0, 60, 0, 33], [L - 1, L - 1, L - 3, L - 2], None),
    "unmapped_pages": ([0, 2, 0, 0], [10, 21, 5, 33], {0: 3, 1: 6, 2: 2, 3: 9}),
}


def _pools(rng, dtype, page_size=PS):
    return tuple(
        jnp.asarray(rng.normal(size=(NP, page_size, HKV, D)), dtype)
        for _ in "kv"
    )


def _view(case, page_size=PS, **facts):
    start, lens, unmapped = CASES[case]
    rng = np.random.default_rng(7)
    table = rng.permutation(np.arange(1, NP)).reshape(B, P).astype(np.int32)
    for slot, first in (unmapped or {}).items():
        table[slot, first:] = 0
    return PagedView(
        jnp.asarray(table), jnp.asarray(start, jnp.int32),
        jnp.asarray(lens, jnp.int32), page_size,
        facts.pop("quantized", False), **facts,
    )


@pytest.mark.parametrize("group", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("chunk", [1, 3], ids=["s1", "s3"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_gather_path(case, chunk, group):
    rng = np.random.default_rng(0)
    pages_k, pages_v = _pools(rng, jnp.float32)
    q = jnp.asarray(
        rng.normal(size=(B, chunk, HKV * group, D)), jnp.float32
    )
    want = pa.paged_attention(
        q, pages_k, pages_v, _view(case), impl="reference"
    )
    view = _view(case)
    got = pa.paged_attention(q, pages_k, pages_v, view, impl="fused")
    assert view.took == [True]
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_kernel_matches_gather_path_bf16():
    """The cells' dtype: float32 logits and statistics, ``p . v`` in
    bfloat16 with float32 accumulation, as the gather path has them.
    (A page of 8 positions x 2 heads: one bfloat16 tile of 16 rows.)"""
    rng = np.random.default_rng(1)
    pages_k, pages_v = _pools(rng, jnp.bfloat16, page_size=8)
    q = jnp.asarray(rng.normal(size=(B, 1, 4 * HKV, D)), jnp.bfloat16)
    want = pa.paged_attention(
        q, pages_k, pages_v, _view("left_pad", 8), impl="reference"
    ).astype(jnp.float32)
    got = pa.paged_attention(
        q, pages_k, pages_v, _view("left_pad", 8), impl="fused"
    ).astype(jnp.float32)
    np.testing.assert_allclose(got, want, atol=0.03)


def test_kernel_refuses_what_it_cannot_read():
    rng = np.random.default_rng(0)
    pages_k, pages_v = _pools(rng, jnp.float32)
    q = jnp.zeros((B, 1, HKV, D), jnp.float32)
    for view in (_view("ragged_lens", quantized=True),
                 _view("ragged_lens", sharded=True)):
        with pytest.raises(ValueError, match="unquantized"):
            pa.paged_attention(q, pages_k, pages_v, view, impl="fused")
    narrow = jnp.zeros((NP, PS, HKV, 64), jnp.float32)
    with pytest.raises(ValueError, match="multiple of"):
        pa.paged_attention(
            q[..., :64], narrow, narrow, _view("ragged_lens"), impl="fused"
        )


# ---------------------------------------------------------------------------
# The program chooses by what it can observe.
# ---------------------------------------------------------------------------

WINDOW, SEQ, SLOTS, PAGE = 8, 64, 3, 8
#: head_dim 128 (the kernel reads whole 128-lane rows), float32 (exact
#: parity on one backend), a page of 8 positions x 1 KV head = one
#: float32 tile.
DECODER = LlamaConfig(
    vocab_size=128, hidden_size=256, num_layers=2, num_heads=2,
    num_kv_heads=1, intermediate_size=128, max_seq_len=SEQ,
    rope_theta=10_000.0, dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def decoder():
    model = LlamaForCausalLM(DECODER)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, WINDOW), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture
def on_a_tpu(monkeypatch):
    """What ``impl="auto"`` sees on the chip. Only the kernel module's
    own question is answered so: ``resolve_impl`` still finds the CPU
    and runs the kernel in interpret mode."""
    monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)


def _session(model, params, **kw):
    kw.setdefault("num_slots", SLOTS)
    kw.setdefault("page_size", PAGE)
    return ServeSession.from_model(model, params, WINDOW, **kw)


def _latent_session(rank=16, rope=8, **kw):
    from perfbench.families.mla_moe_serve import model_config, to_flax
    from perfbench.reference import mla_moe as ref

    config = {
        "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": rank,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": rope, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_hidden_layers": 2, "first_k_dense_replace": 1,
        "num_experts": 4, "num_experts_per_tok": 2,
        "num_shared_experts": 1, "routed_scaling_factor": 2.5,
        "vocab_size": 256, "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 16,
            "type": "deepseek_yarn"},
        "deployment": {"router_experts": 8, "first_expert": 2},
    }
    settings = ref.settings(config)
    model = LlamaForCausalLM(model_config(config, SEQ, jnp.float32))
    params = to_flax(
        ref.all_weights(ref.seed_key(27), settings, jnp.float32), settings
    )
    return _session(model, params, **kw)


def _mesh_session(model, params):
    from tpudl.fleet import build_mesh_session

    return build_mesh_session(
        model, params, WINDOW, devices=jax.devices()[:2], tp=2,
        num_slots=SLOTS, page_size=PAGE,
    )


PROGRAMS = {
    # name: (session, attention layers on the kernel on a TPU)
    "kv_pool": (lambda m, p: _session(m, p), 2),
    "int8_view": (lambda m, p: _session(m, p, kv_dtype="int8"), 0),
    # A latent row of 24 finds no fold and is no whole number of lanes.
    "headless_pool": (lambda m, p: _latent_session(), 0),
    # 48 + 16 = 64 wide on pages of 16: held [NP, 8, 128], the latent
    # kernel's; the same pool stored int8 keeps a row a position.
    "folded_latent_pool": (
        lambda m, p: _latent_session(48, 16, page_size=16), 2),
    "int8_latent_pool": (
        lambda m, p: _latent_session(48, 16, page_size=16, kv_dtype="int8"),
        0),
    "mesh_committed_pool": (_mesh_session, 0),
}


def _traced_decode(session):
    """The session's decode program traced (nothing runs): its jaxpr
    and what it noted of itself."""
    engine = session.engine
    cache = engine.cache
    vec = jnp.zeros((cache.num_slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(engine.decode_call)(
        engine.params, cache.cache, vec, vec, *cache.dispatch_args()
    )
    return str(jaxpr), engine.decode_call.__wrapped__.attention_in_place


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_program_takes_the_kernel_only_where_it_can(name, decoder, on_a_tpu):
    build, layers = PROGRAMS[name]
    jaxpr, took = _traced_decode(build(*decoder))
    assert sum(took) == layers and len(took) == 2
    assert ("pallas_call" in jaxpr) == bool(layers)


def test_every_cpu_run_gathers(decoder):
    jaxpr, took = _traced_decode(_session(*decoder))
    assert took == (False, False) and "pallas_call" not in jaxpr


def test_verify_window_runs_the_same_kernel(decoder, on_a_tpu):
    session = _session(*decoder, spec_k=3)
    engine, cache = session.engine, session.engine.cache
    ids = jnp.zeros((SLOTS, 3), jnp.int32)
    jaxpr = jax.make_jaxpr(engine.verify_call)(
        engine.params, cache.cache, ids, ids, *cache.dispatch_args()
    )
    assert engine.verify_call.__wrapped__.attention_in_place == (True, True)
    assert "pallas_call" in str(jaxpr)


def _requests(n=5):
    rng = np.random.default_rng(3)
    return [
        Request(f"r{i}", rng.integers(1, 128, size=int(k)).tolist(),
                max_new_tokens=int(m))
        for i, (k, m) in enumerate(
            zip(rng.integers(1, WINDOW + 1, n), rng.integers(3, 20, n)))
    ]


def test_session_serves_the_same_tokens_in_place(decoder, monkeypatch, tmp_path):
    """End to end through the engine: the session whose decode program
    reads the pool in place serves what the gathering session serves,
    counts no pool copy, and says which path it took."""
    model, params = decoder
    want = {rid: r.tokens for rid, r in
            _session(model, params).serve(_requests()).items()}
    assert registry().gauge("serve_paged_attention_in_place").value == 0

    monkeypatch.setattr(pa, "is_tpu_backend", lambda: True)
    copies = registry().counter("serve_kv_pool_copies")
    before = copies.value
    obs.enable(str(tmp_path / "obs"))
    try:
        session = _session(model, params)
        got = {rid: r.tokens for rid, r in
               session.serve(_requests()).items()}
        records = obs_spans.active_recorder().records
    finally:
        obs.disable()
    assert got == want
    assert copies.value == before
    assert session.engine.cache.in_place_layers == 2
    assert registry().gauge("serve_paged_attention_in_place").value == 2
    steps = [r for r in records
             if r.get("kind") == "span" and r.get("name") == "decode_step"]
    assert steps and all(s["kv_in_place"] == 1 for s in steps)
    # A k / v pool is held as declared: no layer folded.
    assert session.engine.cache.folds == (1, 1, 1, 1)
    assert registry().gauge("serve_kv_pool_folded_layers").value == 0
    # (A constant of the session: the gauge says it, no step's span.)
    assert not any("kv_fold" in s for s in steps)
    # An idle slot costs a page; a busy one the pages its live
    # positions lie on, never its whole table.
    pages_a_slot = SEQ // PAGE
    assert all(
        SLOTS <= s["pages_live"] <= s["busy"] * pages_a_slot
        + (SLOTS - s["busy"]) for s in steps
    )
    assert any(s["pages_live"] > SLOTS for s in steps)


def test_a_latent_session_says_how_its_pool_is_held(tmp_path):
    """The headless pool of a latent layer whose row is no whole number
    of lanes (48 + 16 = 64 wide, pages of 8) is held folded: one leaf
    a layer, two positions a held row, on ``PagedKVCache.folds`` and
    the gauge; a row of 24 finds no fold and says 1."""
    obs.enable(str(tmp_path / "obs"))
    try:
        session = _latent_session(rank=48, rope=16)
        session.serve(_requests())
        records = obs_spans.active_recorder().records
    finally:
        obs.disable()
    cache = session.engine.cache
    assert cache.folds == (2, 2)
    assert jax.tree.leaves(cache.cache)[0].shape == (
        cache.num_pages, PAGE // 2, 2 * 64)
    assert registry().gauge("serve_kv_pool_folded_layers").value == 2
    steps = [r for r in records
             if r.get("kind") == "span" and r.get("name") == "decode_step"]
    assert steps and all(s["kv_in_place"] == 0 for s in steps)
    plain = _latent_session()
    plain.serve(_requests(2))
    assert plain.engine.cache.folds == (1, 1)
    assert registry().gauge("serve_kv_pool_folded_layers").value == 0


def test_pages_live_counts_what_the_kernel_visits():
    from tpudl.models.generate import prefill_fn
    from tpudl.serve import PagedKVCache

    model = LlamaForCausalLM(DECODER)
    ids = jax.ShapeDtypeStruct((SLOTS, WINDOW), jnp.int32)
    params = jax.eval_shape(
        model.init, jax.random.key(0), jnp.zeros((1, WINDOW), jnp.int32)
    )["params"]
    _, template = jax.eval_shape(prefill_fn(model), params, ids, ids)
    cache = PagedKVCache(template, page_size=PAGE)
    assert cache.pages_live() == SLOTS  # every slot idle: a page each
    cache.start[:] = [0, 5, 17]
    cache.lens[:] = [0, 8, SEQ - 2]
    # slot 1: pages 0-1; slot 2: pages 2-7.
    assert cache.pages_live() == 1 + 2 + 6
    # A verify window of 3 reaches one position past the table's end:
    # there is no such page.
    cache.lens[1] = 15
    assert cache.pages_live(3) == 1 + 3 + 6


# ---------------------------------------------------------------------------
# The headless pool of a latent layer (ISSUE 31): the sibling kernel.
# ---------------------------------------------------------------------------

#: 4 slots x 80 pages of 16 positions, the sarvam cell's table a slot
#: (1,280 positions): a slot spans up to three blocks of
#: ``LATENT_PAGES_PER_BLOCK`` = 32 pages (512 positions).
LB, LP, LPS, LH = 4, 80, 16, 4
LNP = LB * LP + 1
#: name -> (row width C, rank, positions a held row).
HELD = {"folded_576": (576, 512, 2), "whole_lanes_128": (128, 96, 1)}
#: name -> (start [B], lens [B], slots idle on the trash page).
LATENT_CASES = {
    "ragged_with_an_idle_slot": ([0, 0, 0, 0], [40, 700, 0, 300], [2]),
    "slot_full_to_1280": ([0, 5, 0, 100], [1279, 1279, 1, 1278], []),
    "lens_at_a_pages_edge": ([0, 0, 0, 3], [15, 16, 17, 31], []),
    "lens_across_a_blocks_edge": ([0, 0, 0, 500], [511, 512, 513, 1023], []),
    "left_pad": ([384, 17, 255, 256], [600, 18, 256, 1000], []),
}


def _latent_inputs(held, case, dtype=jnp.float32, chunk=1, **facts):
    """(query, pool as held, view): a permuted page table, an idle
    slot's row on the trash page."""
    width, _, fold = HELD[held]
    start, lens, idle = LATENT_CASES[case]
    rng = np.random.default_rng(31)
    pool = jnp.asarray(
        rng.normal(size=(LNP, LPS // fold, fold * width)), dtype)
    query = jnp.asarray(rng.normal(size=(LB, chunk, LH, width)), dtype)
    table = rng.permutation(np.arange(1, LNP)).reshape(LB, LP)
    table[idle] = 0
    view = PagedView(
        jnp.asarray(table, jnp.int32), jnp.asarray(start, jnp.int32),
        jnp.asarray(lens, jnp.int32), LPS,
        facts.pop("quantized", False), **facts,
    )
    return query, pool, view


def _latent(held, case, impl, **kw):
    query, pool, view = _latent_inputs(held, case, **kw)
    out = pa.paged_latent_attention(
        query, pool, view, rank=HELD[held][1], scale=0.2, impl=impl)
    return out, view


@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_kernel_matches_gather_path(case, held):
    want, _ = _latent(held, case, "reference")
    got, view = _latent(held, case, "fused")
    assert view.took == [True]
    assert got.shape == (LB, 1, LH, HELD[held][1])
    assert got.dtype == want.dtype
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_latent_kernel_matches_gather_path_bf16():
    """The cell's dtype: float32 logits and statistics, ``p . rows`` in
    bfloat16 with float32 accumulation, one division at the end. (The
    CPU has no bfloat16 matmul into float32: the gather path is asked
    in float32 of the same bfloat16 values.)"""
    query, pool, view = _latent_inputs(
        "folded_576", "left_pad", jnp.bfloat16)
    want = pa.paged_latent_attention(
        query.astype(jnp.float32), pool.astype(jnp.float32), view,
        rank=512, scale=0.2, impl="reference")
    got = pa.paged_latent_attention(
        query, pool, view, rank=512, scale=0.2, impl="fused")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(jnp.float32), want, atol=0.03)


#: name -> (what the program observes, the held pool it observes it
#: on): everything the latent kernel leaves to the gather.
GATHERED = {
    "int8_pool": (dict(quantized=True), "folded_576"),
    "pool_on_a_mesh": (dict(sharded=True), "folded_576"),
    "chunk_of_three": (dict(chunk=3), "whole_lanes_128"),
}


@pytest.mark.parametrize("name", sorted(GATHERED))
def test_latent_kernel_refuses_and_auto_gathers(name, on_a_tpu):
    facts, held = GATHERED[name]
    query, pool, view = _latent_inputs(held, "left_pad", **facts)
    scales = None
    if view.quantized:
        pool = jnp.zeros((LNP, LPS, HELD[held][0]), jnp.int8)
        scales = jnp.ones((LNP, LPS), jnp.float32)
    rank = HELD[held][1]
    assert not pa.latent_in_place_ok(query, pool, view)
    with pytest.raises(ValueError, match="one unquantized headless pool"):
        pa.paged_latent_attention(
            query, pool, view, rank=rank, scale=0.2, impl="fused")
    view.took.clear()
    out = pa.paged_latent_attention(
        query, pool, view, rank=rank, scale=0.2, scales=scales)
    assert view.took == [False] and out.shape == (*query.shape[:3], rank)


@pytest.mark.parametrize("page, width, row, ok", [
    (16, 1152, 576, True),    # the cell's: [NP, 8, 1152]
    (16, 128, 128, True),     # a row of whole lanes, held as declared
    (16, 576, 576, False),    # held as declared: 4.5 lanes
    (8, 128, 64, False),      # [NP, 4, 128]: half a tile a page
    (16, 1152, 512, False),   # not the query's width
], ids=str)
def test_latent_kernel_takes_a_held_row_of_whole_lanes(page, width, row, ok):
    fold = max(width // row, 1)
    pool = jax.ShapeDtypeStruct((9, page // fold, width), jnp.bfloat16)
    query = jax.ShapeDtypeStruct((2, 1, 4, row), jnp.bfloat16)
    view = PagedView(None, None, None, page, False)
    assert pa.latent_in_place_ok(query, pool, view) is ok


def test_latent_auto_on_the_cpu_gathers():
    query, pool, view = _latent_inputs("folded_576", "lens_at_a_pages_edge")
    assert pa.latent_in_place_ok(query, pool, view)
    jaxpr = jax.make_jaxpr(lambda q, p: pa.paged_latent_attention(
        q, p, view, rank=512, scale=0.2))(query, pool)
    assert view.took == [False] and "pallas_call" not in str(jaxpr)


def test_latent_auto_on_a_tpu_takes_the_kernel_under_its_scope(on_a_tpu):
    """The kernel's operations sit under ``mla_core``, the scope of the
    dense absorbed attention: what reads that scope reads the same work
    whatever implements it, and nothing is left under ``kv_gather``."""
    query, pool, view = _latent_inputs("folded_576", "lens_at_a_pages_edge")
    lowered = jax.jit(lambda q, p: pa.paged_latent_attention(
        q, p, view, rank=512, scale=0.2)).lower(query, pool)
    assert view.took == [True]
    text = lowered.as_text(debug_info=True)
    assert "mla_core" in text and "kv_gather" not in text
