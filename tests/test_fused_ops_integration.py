"""Fused-epilogue tier wired through the models and the train loop.

``fused_ops="force"`` runs the actual Pallas kernels (interpret mode on
CPU) inside real models and real compiled train steps; ``fused_ops=True``
("auto") must fall back to the bit-identical composite off-TPU — the
dispatch-seam contract models rely on for the default path staying
unchanged."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl.runtime.mesh import MeshSpec, make_mesh
from tpudl.train.loop import (
    compile_step,
    create_train_state,
    cross_entropy_loss,
    make_classification_eval_step,
    make_classification_train_step,
)


def _bert_state(fused_ops, seed=0, dtype=jnp.float32):
    from tpudl.models.bert import BertConfig, BertForSequenceClassification

    cfg = BertConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, hidden_dropout=0.0, attention_dropout=0.0,
        max_position_embeddings=32, dtype=dtype, fused_ops=fused_ops,
    )
    model = BertForSequenceClassification(cfg)
    return create_train_state(
        jax.random.key(seed), model, jnp.zeros((1, 16), jnp.int32),
        optax.adamw(1e-3),
    )


def _batch(batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "input_ids": rng.integers(0, 128, (batch, seq)).astype(np.int32),
        "attention_mask": np.ones((batch, seq), np.int32),
        "label": rng.integers(0, 2, (batch,)).astype(np.int32),
    }


def _step_fn(loss_impl="reference"):
    return make_classification_train_step(
        input_keys=("input_ids", "attention_mask"), label_key="label",
        loss_impl=loss_impl,
    )


def test_bert_fused_block_loss_and_grads_match_composite():
    """The full fused BERT block (fused LayerNorm+residual, fused
    bias+GeLU, fused cross-entropy) on a real
    make_classification_train_step: loss and updated params match the
    composite step within bf16-level tolerance."""
    mesh = make_mesh(MeshSpec(dp=-1))
    batch = _batch()
    rng = jax.random.key(1)

    results = {}
    for mode, loss_impl in ((False, "reference"), ("force", "fused")):
        state = _bert_state(mode)
        step = compile_step(
            _step_fn(loss_impl), mesh, state, None, donate_state=False
        )
        new_state, metrics = step(state, batch, rng)
        results[mode] = (new_state, metrics)

    (s_ref, m_ref), (s_fused, m_fused) = results[False], results["force"]
    np.testing.assert_allclose(
        float(m_fused["loss"]), float(m_ref["loss"]), rtol=1e-4, atol=1e-5
    )
    flat_ref = jax.tree_util.tree_leaves_with_path(s_ref.params)
    flat_fused = dict(
        (jax.tree_util.keystr(p), l)
        for p, l in jax.tree_util.tree_leaves_with_path(s_fused.params)
    )
    assert set(flat_fused) == set(
        jax.tree_util.keystr(p) for p, _ in flat_ref
    )
    for path, ref_leaf in flat_ref:
        key = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(flat_fused[key]), np.asarray(ref_leaf),
            rtol=2e-3, atol=2e-5, err_msg=f"param {key} diverged",
        )


def test_bert_fused_auto_is_reference_off_tpu():
    """fused_ops=True (auto) off-TPU must be the composite: the forward
    (loss) is BIT-identical, and the updated params agree to float
    reassociation level (autodiff walks a structurally different —
    mathematically identical — graph, the caveat class
    test_fused_dispatch documents for conv/dropout models)."""
    mesh = make_mesh(MeshSpec(dp=-1))
    batch = _batch()
    rng = jax.random.key(1)
    outs = []
    for mode in (False, True):
        state = _bert_state(mode)
        step = compile_step(
            _step_fn(), mesh, state, None, donate_state=False
        )
        new_state, metrics = step(state, batch, rng)
        outs.append((new_state, metrics))
    assert float(outs[0][1]["loss"]) == float(outs[1][1]["loss"])
    for a, b in zip(
        jax.tree.leaves(outs[0][0].params), jax.tree.leaves(outs[1][0].params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
        )


def test_donation_audit_with_fused_kernels():
    """The donation contract survives the fused tier: every old state
    leaf is deleted and >= 80% of buffers are reused in place when the
    step runs the Pallas kernels (test_fused_dispatch's audit, fused)."""
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state("force")
    step = compile_step(_step_fn("fused"), mesh, state, None)
    state = jax.device_put(state, step.state_shardings)
    batch = _batch()
    rng = jax.random.key(1)

    def ptrs(tree):
        out = set()
        for leaf in jax.tree.leaves(tree):
            for shard in leaf.addressable_shards:
                out.add(shard.data.unsafe_buffer_pointer())
        return out

    old_leaves = jax.tree.leaves(state)
    old_ptrs = ptrs(state)
    state2, _ = step(state, batch, rng)
    assert all(leaf.is_deleted() for leaf in old_leaves)
    reused = ptrs(state2) & old_ptrs
    assert len(reused) >= 0.8 * len(old_ptrs), (
        f"only {len(reused)}/{len(old_ptrs)} donated buffers reused with "
        "fused kernels enabled — a kernel boundary is silently copying"
    )


def test_bert_fused_eval_step_and_loss_impl():
    """Eval path: the fused per-example loss feeds the same masked-mean
    metrics as the composite."""
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state(False)
    batch = _batch()
    ref_step = compile_step(
        make_classification_eval_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh, state, None, has_rng=False,
    )
    fused_step = compile_step(
        make_classification_eval_step(
            input_keys=("input_ids", "attention_mask"), label_key="label",
            loss_impl="fused",
        ),
        mesh, state, None, has_rng=False,
    )
    m_ref = ref_step(state, batch)
    m_fused = fused_step(state, batch)
    np.testing.assert_allclose(
        float(m_fused["loss"]), float(m_ref["loss"]), rtol=1e-5, atol=1e-6
    )
    assert float(m_fused["accuracy"]) == float(m_ref["accuracy"])


def test_cross_entropy_loss_impl_seam():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(13, 5)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 5, size=(13,)), jnp.int32)
    ref = cross_entropy_loss(logits, labels, 0.1)
    fused = cross_entropy_loss(logits, labels, 0.1, impl="fused")
    np.testing.assert_allclose(float(fused), float(ref), rtol=1e-5,
                               atol=1e-6)


def test_llama_fused_forward_and_grads():
    """Fused RMSNorm(+residual) and SwiGLU through the tiny Llama stack
    (the serve decode path's per-step ops): logits and grads match the
    composite."""
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    ids = jnp.asarray(
        np.random.default_rng(1).integers(0, 512, (2, 16)), jnp.int32
    )
    ref_model = LlamaForCausalLM(LLAMA_TINY(dtype=jnp.float32))
    fused_model = LlamaForCausalLM(
        LLAMA_TINY(dtype=jnp.float32, fused_ops="force")
    )
    variables = ref_model.init(jax.random.key(0), ids)

    z_ref = ref_model.apply(variables, ids)
    z_fused = fused_model.apply(variables, ids)
    np.testing.assert_allclose(
        np.asarray(z_fused), np.asarray(z_ref), rtol=1e-4, atol=1e-4
    )

    def loss(model):
        def f(params):
            z = model.apply({"params": params}, ids)
            return jnp.mean(z * z)
        return f

    g_ref = jax.grad(loss(ref_model))(variables["params"])
    g_fused = jax.grad(loss(fused_model))(variables["params"])
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_fused),
        jax.tree.leaves(g_ref),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5,
            err_msg=f"grad {jax.tree_util.keystr(path)} diverged",
        )


def test_llama_fused_auto_is_bitwise_reference_off_tpu():
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    ids = jnp.asarray(
        np.random.default_rng(2).integers(0, 512, (2, 12)), jnp.int32
    )
    ref_model = LlamaForCausalLM(LLAMA_TINY(dtype=jnp.float32))
    auto_model = LlamaForCausalLM(
        LLAMA_TINY(dtype=jnp.float32, fused_ops=True)
    )
    variables = ref_model.init(jax.random.key(0), ids)
    z_ref = np.asarray(ref_model.apply(variables, ids))
    z_auto = np.asarray(auto_model.apply(variables, ids))
    assert (z_ref == z_auto).all()


def test_bert_param_tree_identical_across_modes():
    """Checkpoints/HF imports interchange between fused and composite:
    identical param paths, shapes, dtypes."""
    ids = jnp.zeros((1, 16), jnp.int32)
    trees = {}
    for mode in (False, "force"):
        from tpudl.models.bert import (
            BertConfig,
            BertForSequenceClassification,
        )

        cfg = BertConfig(
            vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
            intermediate_size=64, max_position_embeddings=32,
            dtype=jnp.float32, fused_ops=mode,
        )
        variables = BertForSequenceClassification(cfg).init(
            jax.random.key(0), ids
        )
        trees[mode] = {
            jax.tree_util.keystr(p): (l.shape, l.dtype)
            for p, l in jax.tree_util.tree_leaves_with_path(
                variables["params"]
            )
        }
    assert trees[False] == trees["force"]


def test_block_size_overrides_preserve_parity():
    """The block-size seams (norms.BLOCK_ROWS_OVERRIDE /
    cross_entropy.VOCAB_BLOCK_OVERRIDE) change only the kernel grid:
    fused outputs at a non-default block size still match the
    composite references (interpret mode on CPU)."""
    from tpudl.ops import cross_entropy as ce_mod
    from tpudl.ops import norms as norms_mod
    from tpudl.ops.cross_entropy import (
        softmax_cross_entropy,
        softmax_cross_entropy_ref,
    )
    from tpudl.ops.norms import layer_norm, layer_norm_ref

    x = jax.random.normal(jax.random.key(0), (48, 96), jnp.float32)
    scale = jnp.ones((96,))
    bias = jnp.full((96,), 0.1)
    logits = jax.random.normal(jax.random.key(1), (24, 384), jnp.float32)
    labels = jax.random.randint(jax.random.key(2), (24,), 0, 384)
    try:
        norms_mod.BLOCK_ROWS_OVERRIDE = 32
        ce_mod.VOCAB_BLOCK_OVERRIDE = 128
        np.testing.assert_allclose(
            layer_norm(x, scale, bias, impl="fused"),
            layer_norm_ref(x, scale, bias),
            rtol=1e-5, atol=1e-5,
        )
        np.testing.assert_allclose(
            softmax_cross_entropy(logits, labels, impl="fused"),
            softmax_cross_entropy_ref(logits, labels),
            rtol=1e-5, atol=1e-5,
        )
    finally:
        norms_mod.BLOCK_ROWS_OVERRIDE = None
        ce_mod.VOCAB_BLOCK_OVERRIDE = None
    try:
        norms_mod.BLOCK_ROWS_OVERRIDE = 0
        with pytest.raises(ValueError, match="block-rows"):
            layer_norm(x, scale, bias, impl="fused")
    finally:
        norms_mod.BLOCK_ROWS_OVERRIDE = None
