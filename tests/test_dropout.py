"""Low-width-bits dropout (tpudl.ops.dropout) — the headline-path mask
generator (BASELINE.md: 195 -> 168 ms/step vs bernoulli masks, round 3)."""

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.ops.dropout import Dropout, dropout, dropout_keep_mask


def test_keep_fraction_matches_rate():
    keep = dropout_keep_mask(jax.random.key(0), (512, 512), 0.1)
    frac = float(jnp.mean(keep.astype(jnp.float32)))
    # u8 quantization: exact expectation is 1 - 26/256 = 0.8984
    np.testing.assert_allclose(frac, 1.0 - 26 / 256, atol=3e-3)


def test_exact_path_is_bernoulli():
    k = jax.random.key(1)
    got = dropout_keep_mask(k, (64, 64), 0.25, exact=True)
    want = jax.random.bernoulli(k, 0.75, (64, 64))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_zero_rate_keeps_everything():
    assert bool(jnp.all(dropout_keep_mask(jax.random.key(2), (8, 8), 0.0)))
    x = jnp.ones((4, 4))
    np.testing.assert_array_equal(
        np.asarray(dropout(jax.random.key(3), x, 0.0)), np.asarray(x)
    )


def test_dropout_scales_survivors():
    x = jnp.ones((256, 256), jnp.float32)
    y = dropout(jax.random.key(4), x, 0.5)
    vals = np.unique(np.asarray(y))
    assert set(np.round(vals, 5)) <= {0.0, 2.0}
    # E[y] == 1 under inverted dropout
    np.testing.assert_allclose(float(jnp.mean(y)), 1.0, atol=0.05)


def test_module_respects_deterministic_and_rngs():
    m = Dropout(0.5)
    x = jnp.ones((32, 32))
    out_det = m.apply({}, x, deterministic=True)
    np.testing.assert_array_equal(np.asarray(out_det), np.asarray(x))
    out_a = m.apply({}, x, deterministic=False,
                    rngs={"dropout": jax.random.key(5)})
    out_b = m.apply({}, x, deterministic=False,
                    rngs={"dropout": jax.random.key(5)})
    out_c = m.apply({}, x, deterministic=False,
                    rngs={"dropout": jax.random.key(6)})
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))
    assert not np.array_equal(np.asarray(out_a), np.asarray(out_c))
    assert float(jnp.mean((out_a == 0).astype(jnp.float32))) > 0.3


def test_gradient_masks_match_forward():
    x = jnp.ones((64, 64))
    k = jax.random.key(7)
    y, vjp = jax.vjp(lambda x: dropout(k, x, 0.5), x)
    (dx,) = vjp(jnp.ones_like(y))
    # Dropped positions get zero gradient; kept get the 1/(1-rate) scale.
    np.testing.assert_array_equal(np.asarray(dx != 0), np.asarray(y != 0))


def test_bert_trains_with_lowbits_dropout():
    """End-to-end: the BERT fine-tune (hidden + attention dropout 0.1 on
    the low-bits path) still learns."""
    import optax

    from tpudl.data.synthetic import synthetic_token_batches
    from tpudl.models.bert import BERT_TINY, BertForSequenceClassification
    from tpudl.train import create_train_state, make_classification_train_step

    model = BertForSequenceClassification(
        BERT_TINY(vocab_size=256, num_heads=2, dtype=jnp.float32)
    )
    batches = list(
        synthetic_token_batches(16, seq_len=16, vocab_size=256, num_batches=30)
    )
    state = create_train_state(
        jax.random.key(0),
        model,
        jnp.asarray(batches[0]["input_ids"]),
        optax.adamw(3e-3),
    )
    step = jax.jit(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        )
    )
    rng = jax.random.key(1)
    first = None
    for batch in batches:
        state, metrics = step(state, batch, rng)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
