"""The long prefill's attention as one Pallas call
(``tpudl.ops.flash_attention.prefill_attention``, PR 46; since PR 48 a
group of query heads a KV head, keys of part lanes and a mask made in
the kernel): the kernel (interpreted on the CPU, tiny shapes) against
the XLA blocks of ``llama._blocked_attention`` in float32, the rule that
chooses between them, who is offered the kernel at all, and the
program's note of its choice."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.models.llama as llama
import tpudl.ops.attention as attention
import tpudl.ops.grouped_matmul as grouped_matmul

# (``tpudl.ops.flash_attention`` the attribute is the function.)
fa = importlib.import_module("tpudl.ops.flash_attention")

#: name -> rows, heads, key width, value width, rows of left padding,
#: whether an indexer chose, (block_q, block_k), the softmax's scale
CASES = {
    "equal_widths_choice_left_padding": (256, 2, 128, 128, 40, True, (128, 128), None),
    "keys_wider_than_values": (128, 2, 256, 128, 0, False, (64, 64), None),
    "rows_not_a_multiple_of_the_tiles": (200, 2, 128, 128, 9, True, (128, 128), None),
    "tiles_wider_than_tall": (128, 2, 128, 128, 20, True, (64, 128), None),
    "one_tile": (128, 3, 128, 256, 5, False, (128, 128), None),
    "key_tiles_of_nothing_but_padding": (256, 2, 128, 128, 150, True, (64, 64), None),
    "scale_given": (128, 2, 128, 128, 17, True, (64, 64), 0.31),
    "scale_a_power_of_two": (128, 2, 128, 128, 3, True, (128, 128), 0.0625),
}


def _inputs(rows, heads, dk, dv, pad, choice, dtype=jnp.float32):
    keys = jax.random.split(jax.random.key(rows + heads), 4)
    q = jax.random.normal(keys[0], (1, rows, heads, dk), dtype)
    k = jax.random.normal(keys[1], (1, rows, heads, dk), dtype)
    v = jax.random.normal(keys[2], (1, rows, heads, dv), dtype)
    valid = (jnp.arange(rows) >= pad)[None]
    chosen = None
    if choice:
        # A third of the keys, and always the query's own position (an
        # indexer's top-k never leaves a real query with nothing).
        chosen = jax.random.uniform(keys[3], (1, rows, rows)) < 0.3
        chosen = chosen | jnp.eye(rows, dtype=bool)[None]
    return q, k, v, valid, chosen


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_is_the_xla_blocks(name):
    rows, heads, dk, dv, pad, choice, (bq, bk), scale = CASES[name]
    q, k, v, valid, chosen = _inputs(rows, heads, dk, dv, pad, choice)
    want = jax.jit(lambda *a: llama._blocked_attention(
        *a[:4], 0, 64, scale, *a[4:]))(q, k, v, valid, chosen)
    got = jax.jit(lambda *a: fa.prefill_attention(
        *a[:4], scale, *a[4:], block_q=bq, block_k=bk, interpret=True
    ))(q, k, v, valid, chosen)
    assert got.shape == want.shape == (1, rows, heads, dv)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got[0, pad:], want[0, pad:], atol=2e-6)


#: name -> rows, query heads, KV heads, key width, value width, rows of
#: left padding (it ends inside a tile), (block_q, block_k) or None (the
#: call's own), whether the validity row has holes too
GROUPED = {
    # MiMo's full layer: 16 query heads a KV head, keys 192 beside 128
    "mimo_full": (384, 64, 4, 192, 128, 77, (128, 128), False),
    # Laguna's: 6 a KV head, one width
    "laguna_full": (384, 48, 8, 128, 128, 130, (128, 128), False),
    "tiles_of_the_call": (640, 8, 2, 192, 128, 141, None, False),
    "rows_not_a_multiple_of_the_tiles": (200, 16, 2, 128, 128, 30, (128, 128), False),
    "tiles_taller_than_wide": (512, 8, 2, 192, 128, 140, (256, 128), False),
    "tiles_wider_than_tall": (512, 8, 2, 192, 128, 140, (128, 256), False),
    "query_tiles_of_nothing_but_padding": (512, 8, 2, 192, 128, 300, (128, 128), False),
    "holes_in_the_validity_row": (384, 8, 2, 192, 128, 77, (128, 128), True),
}


def _grouped(name, batch=1):
    rows, heads, kv_heads, dk, dv, pad, _, holes = GROUPED[name]
    keys = jax.random.split(jax.random.key(rows + heads), 4)
    q = jax.random.normal(keys[0], (batch, rows, heads, dk))
    k = jax.random.normal(keys[1], (batch, rows, kv_heads, dk))
    v = jax.random.normal(keys[2], (batch, rows, kv_heads, dv))
    valid = jnp.broadcast_to(jnp.arange(rows) >= pad, (batch, rows))
    if holes:
        valid = valid & (jax.random.uniform(keys[3], (batch, rows)) > 0.1)
    return q, k, v, valid


def _kernel_and_blocks(name):
    bq, bk = GROUPED[name][6] or (None, None)
    return (
        jax.jit(lambda q, k, v, valid: fa.prefill_attention(
            q, k, v, valid, block_q=bq, block_k=bk, interpret=True)),
        jax.jit(lambda q, k, v, valid: llama._blocked_attention(
            q, k, v, valid, 0, 64)),
    )


@pytest.mark.parametrize("name", sorted(GROUPED))
def test_grouped_kernel_is_the_xla_blocks(name):
    """A group of query heads on each KV head and keys of 192: the
    kernel makes its own mask (no [B, S, S] operand) and gives what the
    blocks give on every real row."""
    q, k, v, valid = _grouped(name)
    kernel, blocks = _kernel_and_blocks(name)
    got, want = kernel(q, k, v, valid), blocks(q, k, v, valid)
    assert got.shape == want.shape == q.shape[:3] + v.shape[-1:]
    assert bool(jnp.isfinite(got).all())
    real = np.asarray(valid[0])
    assert real.sum() > 0
    np.testing.assert_allclose(got[0, real], want[0, real], atol=3e-6)


@pytest.mark.parametrize("name", ["mimo_full", "laguna_full"])
def test_a_row_with_no_valid_key_beside_a_real_one(name):
    """Two rows a call, the second all padding: zeros there, and in the
    first row's query tiles of nothing but padding; its real rows as the
    blocks have them."""
    q, k, v, valid = _grouped(name, batch=2)
    valid = valid.at[1].set(False)
    kernel, blocks = _kernel_and_blocks(name)
    got, want = kernel(q, k, v, valid), blocks(q, k, v, valid)
    assert bool(jnp.isfinite(got).all())
    assert not bool(got[1].any())
    real = np.asarray(valid[0])
    assert not bool(got[0, :real.argmax() // 128 * 128].any())
    np.testing.assert_allclose(got[0, real], want[0, real], atol=3e-6)


def test_no_mask_operand_without_a_choice():
    """Without an indexer's choice the call takes a [B, 1, S] validity
    row and no [B, S, S] operand; with one, the int8 mask as before."""
    q, k, v, valid = _grouped("laguna_full")
    rows = q.shape[1]

    def operands(chosen):
        text = jax.jit(lambda *a: fa.prefill_attention(
            *a, interpret=True)).lower(q, k, v, valid, None, chosen).as_text()
        return f"tensor<1x{rows}x{rows}xi8>" in text

    assert not operands(None)
    assert operands(jnp.ones((1, rows, rows), bool))


def test_the_walk_holds_nothing_above_the_diagonal():
    qi, kj = (np.asarray(a) for a in fa._lower_tiles(1024, 256, 128))
    assert all(j * 128 <= i * 256 + 255 for i, j in zip(qi, kj))
    assert len(qi) == 2 + 4 + 6 + 8
    assert len(fa._lower_tiles(512, 128, 128)[0]) == 4 * 5 // 2


def test_a_query_with_no_key_gets_zeros_and_every_row_is_finite():
    """An empty choice (not what an indexer gives a real query, but
    nothing in the mask forbids it) and a row of nothing but padding:
    zeros where no key is allowed, the other rows as the blocks have
    them."""
    q, k, v, valid, chosen = _inputs(128, 2, 128, 128, 40, True)
    chosen = chosen.at[0, 77].set(False)
    kernel = jax.jit(lambda q, k, v, valid, chosen: fa.prefill_attention(
        q, k, v, valid, None, chosen, block_q=64, block_k=64, interpret=True
    ))
    got = kernel(q, k, v, valid, chosen)
    want = jax.jit(lambda q, k, v, valid, chosen: llama._blocked_attention(
        q, k, v, valid, 0, 64, None, chosen))(q, k, v, valid, chosen)
    assert bool(jnp.isfinite(got).all())
    assert not bool(got[0, 77].any()) and not bool(got[0, :40].any())
    real = np.setdiff1d(np.arange(40, 128), [77])
    np.testing.assert_allclose(got[0, real], want[0, real], atol=2e-6)
    nothing = kernel(q, k, v, jnp.zeros_like(valid), chosen)
    assert not bool(nothing.any())


def _on_one_chip(monkeypatch):
    monkeypatch.setattr(attention, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(grouped_matmul, "one_device", lambda: True)


def _shapes(heads=4, kv_heads=4, dk=256, dv=128, dtype=jnp.bfloat16):
    return (
        jax.ShapeDtypeStruct((1, 512, heads, dk), dtype),
        jax.ShapeDtypeStruct((1, 512, kv_heads, dk), dtype),
        jax.ShapeDtypeStruct((1, 512, kv_heads, dv), dtype),
    )


def test_rule_takes_the_latent_form_on_one_chip(monkeypatch):
    _on_one_chip(monkeypatch)
    assert fa.prefill_kernel_ok(*_shapes(), 0)
    assert fa.prefill_kernel_ok(*_shapes(64, 64, 256, 256), 0)


@pytest.mark.parametrize("fact", [
    "grouped_heads", "key_width_of_part_lanes", "mimo_full", "laguna_full",
])
def test_each_fact_the_rule_now_takes(fact, monkeypatch):
    """What turned the rule off before PR 48 (but a window) and the two
    served full layers: the rule says yes, and a serving prefill's
    ``_blocked_attention`` hands the call on."""
    _on_one_chip(monkeypatch)
    shapes = {
        "grouped_heads": _shapes(8, 2, 128, 128),
        "key_width_of_part_lanes": _shapes(dk=192),
        "mimo_full": _shapes(64, 4, 192, 128),
        "laguna_full": _shapes(48, 8, 128, 128),
    }[fact]
    assert fa.prefill_kernel_ok(*shapes, 0)
    seen = []
    monkeypatch.setattr(
        fa, "prefill_attention",
        lambda q, k, v, valid, scale, chosen: seen.append(q.shape) or jnp.zeros(
            q.shape[:3] + v.shape[-1:], q.dtype),
    )
    valid = jax.ShapeDtypeStruct((1, 512), jnp.bool_)
    jax.eval_shape(
        lambda q, k, v, valid: llama._blocked_attention(
            q, k, v, valid, 0, 256, forward_only=True),
        *shapes, valid,
    )
    assert seen == [shapes[0].shape]


@pytest.mark.parametrize("fact", [
    "cpu", "several_devices", "window", "mimo_window", "laguna_window",
    "a_sink", "float32", "value_width_of_part_lanes",
    "query_heads_not_whole_groups", "key_width_of_part_sublanes",
    "kv_heads_of_keys_and_values_differ",
])
def test_each_fact_that_turns_the_rule_off(fact, monkeypatch):
    """With the fact, the rule says no (a sink: its caller does) and
    ``_blocked_attention`` runs today's blocks: the kernel is never
    reached."""
    _on_one_chip(monkeypatch)
    shapes, window, sink = _shapes(), 0, None
    if fact == "cpu":
        monkeypatch.setattr(attention, "is_tpu_backend", lambda: False)
    elif fact == "several_devices":
        monkeypatch.setattr(grouped_matmul, "one_device", lambda: False)
    elif fact == "window":
        window = 128
    elif fact == "mimo_window":
        shapes, window = _shapes(64, 8, 192, 128), 128
        sink = jax.ShapeDtypeStruct((64,), jnp.float32)
    elif fact == "laguna_window":
        shapes, window = _shapes(64, 8, 128, 128), 512
    elif fact == "a_sink":
        shapes = _shapes(64, 8, 192, 128)
        sink = jax.ShapeDtypeStruct((64,), jnp.float32)
    elif fact == "float32":
        shapes = _shapes(dtype=jnp.float32)
    elif fact == "value_width_of_part_lanes":
        shapes = _shapes(dv=64)
    elif fact == "query_heads_not_whole_groups":
        shapes = _shapes(6, 4, 128, 128)
    elif fact == "key_width_of_part_sublanes":
        shapes = _shapes(dk=72)
    elif fact == "kv_heads_of_keys_and_values_differ":
        shapes = _shapes()[:2] + (_shapes(kv_heads=2)[2],)
    assert fa.prefill_kernel_ok(*shapes, window) == (fact == "a_sink")
    monkeypatch.setattr(
        fa, "prefill_attention",
        lambda *a, **kw: pytest.fail("the kernel was called"),
    )
    valid = jax.ShapeDtypeStruct((1, 512), jnp.bool_)
    if fact in ("query_heads_not_whole_groups",
                "kv_heads_of_keys_and_values_differ"):
        return  # no attention has such heads: the rule alone is asked
    out = jax.eval_shape(
        lambda q, k, v, valid, sink: llama._blocked_attention(
            q, k, v, valid, window, 256, sink=sink, forward_only=True),
        *shapes, valid, sink,
    )
    assert out.shape == (1, 512, shapes[0].shape[2], shapes[2].shape[-1])


def test_blocked_attention_takes_the_kernel_where_the_rule_says_so(monkeypatch):
    q, k, v, valid, chosen = _inputs(128, 2, 128, 128, 40, True)

    def blocked(**kw):
        return jax.jit(lambda *a: llama._blocked_attention(
            *a[:4], 0, 64, 0.2, a[4], **kw))(q, k, v, valid, chosen)

    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: True)
    before = fa.prefill_kernel_calls()
    got = blocked(forward_only=True)
    assert fa.prefill_kernel_calls() == before + 1
    # Whoever may differentiate the call is not offered the kernel,
    # whatever the rule says.
    want = blocked()
    assert fa.prefill_kernel_calls() == before + 1
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: False)
    np.testing.assert_array_equal(blocked(forward_only=True), want)
    assert fa.prefill_kernel_calls() == before + 1
    np.testing.assert_allclose(got[0, 40:], want[0, 40:], atol=2e-6)


def _two_kinds(**over):
    """A full layer and a sliding one that differ as MiMo's do: KV
    heads by kind, a window, a sink in the sliding layer's softmax."""
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(**{**dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        num_kv_heads=1, intermediate_size=64, max_seq_len=64,
        dtype=jnp.float32, head_size=128,
        layer_types=("full_attention", "sliding_attention"),
        sliding_window=8, sliding_num_kv_heads=2,
        sliding_attention_sink=True), **over})
    model = LlamaForCausalLM(cfg)
    ids = jnp.arange(3, 35, dtype=jnp.int32)[None]
    params = model.init(jax.random.key(0), ids)["params"]
    return model, params, ids


def test_scoring_with_a_window_and_a_sink_differentiates(monkeypatch):
    """Training and scoring attend a window or a sink through the same
    routine as a serving prefill, and are not offered the kernel (it has
    no backward pass): with the rule forced to yes the gradient is that
    of the XLA blocks, and ``prefill_attention`` is never called."""
    model, params, ids = _two_kinds(head_size=16)

    def loss(params):
        logits = model.apply({"params": params}, ids)
        return jnp.mean(jax.nn.log_softmax(logits)[0, :-1, :] ** 2)

    want = jax.jit(jax.grad(loss))(params)
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: True)
    monkeypatch.setattr(
        fa, "prefill_attention",
        lambda *a, **kw: pytest.fail("the kernel was called"),
    )
    got = jax.jit(jax.grad(loss))(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_array_equal(a, b)
    sinks = got["model"]["layer_1"]["attention"]["sink"]
    assert bool(jnp.abs(sinks).max() > 0)


def test_two_kinds_prefill_through_the_kernel_is_the_dense_prefill(monkeypatch):
    """A serving prefill of a full and a sliding layer on one chip (the
    kernel interpreted, float32 let through): the full layer's
    attention is the kernel, the sliding layer's (a window, a sink) the
    blocks, and the logits and cache rows are the dense pass's."""
    from tpudl.models.generate import prefill_fn

    model, params, ids = _two_kinds()
    pad = 5
    ids = ids.at[0, :pad].set(0)
    mask = (jnp.arange(ids.shape[1]) >= pad).astype(jnp.int32)[None]
    dense, dense_rows, *_ = jax.jit(prefill_fn(model))(params, ids, mask)
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 0)
    monkeypatch.setattr(
        fa, "prefill_kernel_ok", lambda q, k, v, window: not window)
    seen = []
    kernel = fa.prefill_attention
    monkeypatch.setattr(
        fa, "prefill_attention",
        lambda q, k, *a: seen.append((q.shape[2], k.shape[2]))
        or kernel(q, k, *a),
    )
    program = prefill_fn(model)
    logits, rows, *_ = jax.jit(program)(params, ids, mask)
    assert seen == [(4, 1)]
    assert program.attention_in_kernel == {ids.shape[1]: 1}
    np.testing.assert_allclose(logits, dense, atol=1e-5)
    for a, b in zip(jax.tree.leaves(rows), jax.tree.leaves(dense_rows)):
        if a.ndim == 4:  # rows [1, T, Hkv, D]: the prompt's, not the pad's
            a, b = a[:, pad:ids.shape[1]], b[:, pad:ids.shape[1]]
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.parametrize("widths", ["whole_lanes", "keys_padded_to_lanes"])
def test_mla_prefill_gives_the_same_rows_through_the_kernel(widths, monkeypatch):
    """``_mla_prefill`` with the rule forced on (the kernel interpreted)
    and off: the same rows. Keys of 64 + 32 values are zero-padded to a
    lane by the concatenates that build them, and the scale stays the
    caller's."""
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 0)
    dn, dr = (96, 32) if widths == "whole_lanes" else (64, 32)
    rows, heads, rank, dv = (128 if widths == "whole_lanes" else 136), 2, 48, 128
    keys = jax.random.split(jax.random.key(7), 5)
    q_nope = jax.random.normal(keys[0], (1, rows, heads, dn))
    q_rope = jax.random.normal(keys[1], (1, rows, heads, dr))
    latent = jax.random.normal(keys[2], (1, rows, rank + dr))
    kv_b = jax.random.normal(keys[3], (rank, heads, dn + dv)) * 0.2
    valid = (jnp.arange(rows) >= 13)[None]
    choice = jax.random.uniform(keys[4], (1, rows, rows)) < 0.4
    choice = choice | jnp.eye(rows, dtype=bool)[None]

    def run():
        return jax.jit(lambda *a: llama._mla_prefill(
            *a[:4], dn, None, 0.11, a[4], True, a[5]
        ))(q_nope, q_rope, latent, kv_b, valid, choice)

    seen = []
    kernel = fa.prefill_attention
    monkeypatch.setattr(
        fa, "prefill_attention",
        lambda q, *a, **kw: seen.append(q.shape) or kernel(q, *a, **kw),
    )
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: False)
    want = run()
    assert not seen
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: True)
    got = run()
    assert seen == [(1, rows, heads, 128)]
    assert got.shape == want.shape == (1, rows, heads, dv)
    np.testing.assert_allclose(got[0, 13:], want[0, 13:], atol=5e-6)


def test_prefill_program_notes_its_kernel_layers(monkeypatch, tmp_path):
    """The prefill contract's own note, by traced length, and what the
    engine makes of it: the ``prefill`` span's ``attention_in_kernel``
    and the gauge ``serve_prefill_attention_in_kernel``."""
    from tpudl.models.generate import prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
    from tpudl.obs import registry
    from tpudl.obs import spans as obs_spans
    from tpudl.serve import Request, ServeSession

    cfg = LlamaConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=2, intermediate_size=64, max_seq_len=96,
        dtype=jnp.float32, attention="mla", kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    )
    model = LlamaForCausalLM(cfg)
    ids = jnp.ones((1, 64), jnp.int32)
    params = model.init(jax.random.key(0), ids)["params"]
    monkeypatch.setattr(llama, "PREFILL_SCORE_BYTES", 0)

    program = prefill_fn(model)
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: False)
    jax.eval_shape(program, params, ids, ids)
    assert program.attention_in_kernel == {64: 0}
    monkeypatch.setattr(fa, "prefill_kernel_ok", lambda *a: True)
    jax.eval_shape(program, params, ids[:, :32], ids[:, :32])
    assert program.attention_in_kernel == {64: 0, 32: cfg.num_layers}

    session = ServeSession.from_model(
        model, params, num_slots=2, prompt_len=64,
    )
    rec = obs_spans.enable(str(tmp_path))
    try:
        session.serve([Request("r", [3, 4, 5, 6], max_new_tokens=2)])
        prefills = [r for r in rec.records if r.get("name") == "prefill"]
    finally:
        obs_spans.disable()
    assert prefills and all(s["attention_in_kernel"] == 1 for s in prefills)
    assert registry().gauge("serve_prefill_attention_in_kernel").value == (
        cfg.num_layers
    )
    # A length the program was never traced at, an artifact: no note.
    assert session.engine._kernel_layers(48) == 0
