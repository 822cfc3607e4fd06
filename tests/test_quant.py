"""Low-precision weight tier (tpudl.quant).

Four contracts, mirroring the tiers above it: (1) RULES — the default
rule sets quantize exactly the attention/MLP projections and keep
every precision-load-bearing leaf (norms/embeddings/heads) full, with
quantize->dequantize error bounded per rule class; (2) STRUCTURE —
the quantized tree has the SAME module structure as the full-precision
tree, round-trips through an Orbax checkpoint, and a weight_dtype
model serves a FULL-precision tree bit-identically to the plain
module; (3) PARITY — quantized decode matches f32 ``generate()`` under
``assert_serving_parity``'s teacher-forced logit-margin atol mode,
both live-jitted and through the StableHLO artifact pair, and composed
with the paged int8 KV cache (weights int8 + KV int8 in one session —
the acceptance-criterion cell); (4) the shared ``LatencyStats``
summary every benchmark consumes quotes the same percentiles.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.quant import (
    default_quant_rules,
    dequantize_leaf,
    dequantize_tree,
    is_quantized,
    quant_dot,
    quantize_leaf,
    quantize_model,
    quantize_tree,
    weight_bytes_report,
)
from tpudl.serve import Request, ServeSession, assert_serving_parity

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8
SLOTS = 4

#: Grid tolerances: near-tie argmax flips only; a wide-margin
#: divergence is a cache/matmul bug.
INT8_ATOL = 0.06
KV8_ATOL = 0.10


@pytest.fixture(scope="module")
def llama_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def bert_and_params():
    from tpudl.models.bert import BertConfig, BertForSequenceClassification

    cfg = BertConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
        intermediate_size=128, max_position_embeddings=64,
        num_labels=2, dtype=jnp.float32,
    )
    model = BertForSequenceClassification(cfg)
    ids = jnp.zeros((2, 16), jnp.int32)
    mask = jnp.ones((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), ids, mask)["params"]
    return model, params, ids, mask


def _requests(n, seed=0, max_new=(4, 16)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            request_id=f"q{i}",
            input_ids=rng.integers(
                1, CFG.vocab_size, size=int(rng.integers(2, PROMPT_LEN + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for i in range(n)
    ]


def _leaf_paths(params, pred):
    """Sorted "a/b/kernel" paths of leaves matching ``pred`` (quantized
    dicts walk as ONE leaf)."""
    from tpudl.parallel.sharding import _path_str

    out = []
    jax.tree_util.tree_map_with_path(
        lambda path, leaf: out.append(_path_str(path))
        if pred(leaf)
        else None,
        params,
        is_leaf=is_quantized,
    )
    return sorted(out)


# ---------------------------------------------------------------------------
# 1. Rules: which leaves quantize, and how tightly they reconstruct
# ---------------------------------------------------------------------------


def test_llama_rule_classes(llama_and_params):
    """Default Llama rules quantize exactly the seven per-block
    projections; embeddings/norms/lm_head stay full precision."""
    model, params = llama_and_params
    qtree = quantize_tree(params, default_quant_rules(model.cfg, "int8"))
    quantized = _leaf_paths(qtree, is_quantized)
    expected = sorted(
        [
            f"model/layer_{i}/attention/{name}/kernel"
            for i in range(CFG.num_layers)
            for name in ("q_proj", "k_proj", "v_proj", "o_proj")
        ]
        + [
            f"model/layer_{i}/{name}/kernel"
            for i in range(CFG.num_layers)
            for name in ("gate_proj", "up_proj", "down_proj")
        ]
    )
    assert quantized == expected
    kept = _leaf_paths(qtree, lambda l: not is_quantized(l))
    for path in kept:
        assert "_proj" not in path, f"projection left unquantized: {path}"
    assert any("embed" in p for p in kept)
    assert any("norm" in p for p in kept)
    assert any("lm_head" in p for p in kept)


def test_bert_rule_classes(bert_and_params):
    """Default BERT rules quantize the encoder attention + MLP
    projections; embeddings/pooler/classifier stay full precision."""
    model, params, _, _ = bert_and_params
    qtree = quantize_tree(params, default_quant_rules(model.cfg, "int8"))
    quantized = _leaf_paths(qtree, is_quantized)
    assert len(quantized) == model.cfg.num_layers * 6  # q/k/v/out + 2 MLP
    for path in quantized:
        assert "encoder/" in path
    kept = _leaf_paths(qtree, lambda l: not is_quantized(l))
    assert not any("pooler" in p or "classifier" in p for p in quantized)
    assert any("embed" in p for p in kept)


def test_int8_roundtrip_bound():
    """Per-output-channel int8: |dequantized - w| <= scale/2 elementwise
    (half a quantization step at the channel's own scale)."""
    w = jax.random.normal(jax.random.key(1), (96, 48)) * jnp.linspace(
        0.01, 3.0, 48
    )
    leaf = quantize_leaf(w, "int8")
    assert leaf["qvalues"].dtype == jnp.int8
    assert leaf["qscale"].shape == (48,)
    err = np.abs(np.asarray(dequantize_leaf(leaf)) - np.asarray(w))
    bound = 0.5 * np.asarray(leaf["qscale"])[None, :] + 1e-7
    assert np.all(err <= bound), float((err - bound).max())


def test_fp8_roundtrip_bound():
    """e4m3 storage: relative error bounded by the 3-mantissa-bit grid
    (<= 2^-3 of the element) plus the subnormal floor at the channel
    scale."""
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("no float8_e4m3fn in this jax build")
    w = jax.random.normal(jax.random.key(2), (64, 32)) * jnp.linspace(
        0.05, 2.0, 32
    )
    leaf = quantize_leaf(w, "fp8_e4m3")
    assert leaf["qvalues"].dtype == jnp.float8_e4m3fn
    deq = np.asarray(dequantize_leaf(leaf))
    wf = np.asarray(w)
    bound = np.abs(wf) * 2.0**-3 + np.asarray(leaf["qscale"])[None, :] * 2.0**-8
    assert np.all(np.abs(deq - wf) <= bound)


def test_rules_refuse_uncovered_leaf():
    """A >=2-D leaf no rule covers is a rule-set bug, not a default."""
    params = {"mystery": {"kernel": jnp.ones((4, 4))}}
    with pytest.raises(ValueError, match="no quantization rule"):
        quantize_tree(params, ((r"other/kernel$", "int8"),))


def test_quantize_idempotent_and_dequantize_inverse(llama_and_params):
    """Already-quantized leaves pass through untouched; dequantize
    restores the original tree STRUCTURE (values to quantized
    precision)."""
    model, params = llama_and_params
    rules = default_quant_rules(model.cfg, "int8")
    once = quantize_tree(params, rules)
    twice = quantize_tree(once, rules)
    assert jax.tree_util.tree_structure(
        once, is_leaf=is_quantized
    ) == jax.tree_util.tree_structure(twice, is_leaf=is_quantized)
    chex_like = jax.tree.map(
        lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
        once, twice,
    )
    assert all(jax.tree.leaves(chex_like))
    deq = dequantize_tree(once)
    assert jax.tree_util.tree_structure(deq) == jax.tree_util.tree_structure(
        params
    )


def test_weight_bytes_ratio_bar(llama_and_params):
    """int8 stores >= 3.5x fewer bytes on quantized layers than f32
    (the parity-grid acceptance bar; 4x minus the scale rows)."""
    model, params = llama_and_params
    qtree = quantize_tree(params, default_quant_rules(model.cfg, "int8"))
    report = weight_bytes_report(qtree)
    assert report["num_quantized_leaves"] == CFG.num_layers * 7
    assert report["quant_ratio"] >= 3.5


# ---------------------------------------------------------------------------
# 2. Structure: the seam never changes the tree, checkpoints round-trip
# ---------------------------------------------------------------------------


def test_weight_dtype_model_full_precision_params_bitident(llama_and_params):
    """A weight_dtype model serving an UNQUANTIZED tree runs the exact
    nn.Dense math — bit-identical logits to the plain module (the
    checkpoint-interchange half of the seam contract)."""
    import dataclasses

    model, params = llama_and_params
    qmodel = model.clone(
        cfg=dataclasses.replace(model.cfg, weight_dtype="int8")
    )
    ids = jnp.arange(1, PROMPT_LEN + 1, dtype=jnp.int32)[None, :]
    ref = model.apply({"params": params}, ids)
    got = qmodel.apply({"params": params}, ids)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    # And init declares the same tree: restored checkpoints fit both.
    qinit = qmodel.init(jax.random.key(0), ids)["params"]
    assert jax.tree_util.tree_structure(
        qinit
    ) == jax.tree_util.tree_structure(params)


def test_quant_dot_fused_matches_reference():
    """The contraction-fused form differs from dequantize-then-matmul
    only by scale-multiply association."""
    x = jax.random.normal(jax.random.key(3), (5, 64))
    w = jax.random.normal(jax.random.key(4), (64, 32))
    leaf = quantize_leaf(w, "int8")
    fused = np.asarray(quant_dot(x, leaf, impl="fused"))
    ref = np.asarray(quant_dot(x, leaf, impl="reference"))
    np.testing.assert_allclose(fused, ref, atol=1e-5, rtol=1e-5)
    with pytest.raises(ValueError, match="impl"):
        quant_dot(x, leaf, impl="pallas")


def test_checkpoint_roundtrip_quantized_tree(llama_and_params, tmp_path):
    """A quantized tree is two ordinary arrays per kernel under the
    original key — Orbax round-trips it with no custom handlers, and
    the restored tree serves bit-identical logits."""
    import dataclasses

    from tpudl.export import load_params, save_params

    model, params = llama_and_params
    qmodel, qtree = quantize_model(model, params, "int8")
    path = str(tmp_path / "quant_ckpt")
    save_params(path, qtree)
    restored = load_params(path, like=qtree)
    flat_a = jax.tree.leaves(qtree)
    flat_b = jax.tree.leaves(restored)
    assert len(flat_a) == len(flat_b)
    for a, b in zip(flat_a, flat_b):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ids = jnp.arange(1, PROMPT_LEN + 1, dtype=jnp.int32)[None, :]
    np.testing.assert_array_equal(
        np.asarray(qmodel.apply({"params": qtree}, ids)),
        np.asarray(qmodel.apply({"params": restored}, ids)),
    )
    assert qmodel.cfg == dataclasses.replace(model.cfg, weight_dtype="int8")


def test_bert_quantized_forward_close(bert_and_params):
    """BERT int8 weights: quantized logits track f32 within the
    quantization perturbation (encoder projections only — head is full
    precision, so logits move but stay close)."""
    model, params, ids, mask = bert_and_params
    qmodel, qtree = quantize_model(model, params, "int8")
    ref = np.asarray(model.apply({"params": params}, ids, mask))
    got = np.asarray(qmodel.apply({"params": qtree}, ids, mask))
    np.testing.assert_allclose(got, ref, atol=0.05)


# ---------------------------------------------------------------------------
# 3. Serving parity: live, composed with int8 KV, and exported
# ---------------------------------------------------------------------------


def test_quantized_decode_parity_int8(llama_and_params):
    """ServeSession.from_model(weight_dtype="int8") vs the f32
    reference under the teacher-forced logit-margin atol contract."""
    model, params = llama_and_params
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS,
        weight_dtype="int8",
    )
    assert_serving_parity(
        session, model, params, _requests(6), atol=INT8_ATOL
    )


def test_quantized_weights_compose_with_int8_kv(llama_and_params):
    """The acceptance-criterion cell: weights int8 AND paged int8 KV in
    ONE session, parity vs f32 at atol (tolerance widened — two
    bounded perturbations stack)."""
    model, params = llama_and_params
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS,
        weight_dtype="int8", kv_dtype="int8",
    )
    assert_serving_parity(
        session, model, params, _requests(6, seed=1), atol=KV8_ATOL
    )


def test_exported_quantized_decoder_parity(llama_and_params):
    """The quantized decoder exports through the existing StableHLO
    path (quantized leaves are plain in_tree dicts) and the
    deserialized artifact session holds the same parity contract."""
    from tpudl.export.decode import export_serving_decoder

    model, params = llama_and_params
    qmodel, qtree = quantize_model(model, params, "int8")
    pre, dec = export_serving_decoder(
        qmodel, qtree, num_slots=SLOTS, prompt_len=PROMPT_LEN
    )
    session = ServeSession.from_artifacts(pre, dec, qtree)
    assert_serving_parity(
        session, model, params, _requests(6, seed=2), atol=INT8_ATOL
    )


# ---------------------------------------------------------------------------
# 4. LatencyStats: the one percentile summary every benchmark consumes
# ---------------------------------------------------------------------------


def test_latency_stats_shared_summary():
    from tpudl.export.latency import LatencyStats

    stats = LatencyStats.from_ms(list(range(1, 101)))
    assert stats.count == 100
    assert stats.p50_ms == pytest.approx(50.5)
    assert stats.max_ms == 100.0
    assert set(stats.as_dict()) == {
        "mean_ms", "p50_ms", "p95_ms", "p99_ms", "min_ms", "max_ms"
    }
    assert set(stats.percentiles()) == {"p50_ms", "p95_ms", "p99_ms"}
    sec = LatencyStats.from_seconds([0.001, 0.002])
    assert sec.p50_ms == pytest.approx(1.5)
    with pytest.raises(ValueError):
        LatencyStats.from_ms([])
