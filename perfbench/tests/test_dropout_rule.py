"""The reference draws the masks the program drops by.

``DropoutRule`` restates how a step's masks follow from its key. Here it
is held against the program's own modules on the CPU: a site's mask is
the one ``tpudl.ops.dropout.Dropout`` draws at that place in the tree.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import bert as ref
from tpudl.ops.dropout import Dropout

RATE = 0.1


class _Leaf(nn.Module):
    @nn.compact
    def __call__(self, x):
        return Dropout(RATE)(x, deterministic=False)


class _Tree(nn.Module):
    """A site two scopes down, and one that draws in its own scope
    before its child does (as the attention module does)."""

    @nn.compact
    def __call__(self, x):
        own = self.make_rng("dropout")
        return _Leaf(name="inner")(x), jax.random.key_data(own)


@pytest.mark.parametrize("step", [0, 2])
def test_a_sites_mask_is_the_programs(step):
    key = ref.DropoutRule.step_key(jax.random.key(1), step)
    x = jnp.ones((4, 8, 16), jnp.float32)
    out, own = _Tree().apply({}, x, rngs={"dropout": key})
    keep = ref.DropoutRule.keep(key, ("inner", "Dropout_0"), x.shape, RATE)
    np.testing.assert_array_equal(np.asarray(out) != 0, np.asarray(keep))
    scale = 1.0 / (1.0 - 26 / 256)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(keep)], scale,
                               rtol=1e-6)
    from flax.core.scope import LazyRng

    np.testing.assert_array_equal(
        np.asarray(own),
        np.asarray(jax.random.key_data(LazyRng.create(key, 1).as_jax_rng())),
    )


def test_the_rate_in_effect_is_a_multiple_of_a_256th():
    assert ref.DropoutRule.threshold(0.1) == 26
    assert ref.DropoutRule.threshold(0.0) == 0
    keep = ref.DropoutRule.keep(jax.random.key(3), ("x",), (512, 512), 0.1)
    assert abs(float(jnp.mean(keep)) - (1 - 26 / 256)) < 0.003


def test_another_key_drops_other_elements_and_moves_the_loss():
    cfg = {"vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 1,
           "num_attention_heads": 2, "intermediate_size": 32,
           "max_position_embeddings": 8, "type_vocab_size": 2,
           "layer_norm_eps": 1e-12, "num_labels": 2,
           "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1}
    w = ref.make_weights(ref.seed_key(5), cfg)
    ids = jnp.arange(32, dtype=jnp.int32).reshape(4, 8)
    ones, labels = jnp.ones_like(ids), jnp.array([0, 1, 0, 1])

    def loss(key):
        masks = ref.DropoutRule.masks(key, cfg, 4, 8) if key is not None else None
        return float(ref.loss_fn(w, cfg, ids, ones, labels, masks))

    a, b, none = loss(jax.random.key(1)), loss(jax.random.key(2)), loss(None)
    assert a != b and a != none
    assert set(ref.DropoutRule.masks(jax.random.key(1), cfg, 4, 8)) == {
        "embeddings", "pooled", "layer_0/probs", "layer_0/attention_out",
        "layer_0/output"}
    assert ref.DropoutRule.masks(
        jax.random.key(1), {**cfg, "hidden_dropout_prob": 0.0,
                            "attention_probs_dropout_prob": 0.0}, 4, 8) == {}
