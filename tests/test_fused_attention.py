"""Fused short-seq attention + fused softmax-dropout kernels.

CPU tier (interpret mode): exact-shape parity for every masking mode at
dropout 0 — the PRNG-backed dropout paths are TPU-only (interpret mode
has no PRNG emulation; asserted here) and get their statistical checks
on the real chip via scripts/tpu_dropout_check.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.ops.attention import attend, causal_mask, padding_mask
from tpudl.ops.fused_attention import fused_attention
from tpudl.ops.softmax_dropout import hybrid_attention, softmax_dropout


def _qkv(seed, b=2, s=96, h=4, d=32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(
        jax.random.normal(k, (b, s, h, d), jnp.float32) for k in ks
    )


def _padding(seed, b, s):
    lengths = jax.random.randint(jax.random.key(seed), (b,), s // 2, s + 1)
    return (jnp.arange(s)[None, :] < lengths[:, None]).astype(jnp.int32)


@pytest.mark.parametrize("impl", ["fused_kernel", "hybrid"])
def test_matches_reference_no_mask(impl):
    q, k, v = _qkv(0)
    fn = fused_attention if impl == "fused_kernel" else hybrid_attention
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(attend(q, k, v)), atol=2e-4
    )


@pytest.mark.parametrize("impl", ["fused_kernel", "hybrid"])
def test_matches_reference_padding_and_causal(impl):
    q, k, v = _qkv(1)
    am = _padding(2, 2, 96)
    expected = attend(
        q, k, v,
        mask=jnp.logical_and(padding_mask(am), causal_mask(96, 96)),
    )
    fn = fused_attention if impl == "fused_kernel" else hybrid_attention
    got = fn(q, k, v, mask=am, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-4)


@pytest.mark.parametrize("impl", ["fused_kernel", "hybrid"])
def test_grads_match_reference(impl):
    q, k, v = _qkv(3)
    am = _padding(4, 2, 96)
    fn = fused_attention if impl == "fused_kernel" else hybrid_attention

    def loss_ref(q, k, v):
        return jnp.sum(attend(q, k, v, mask=padding_mask(am)) ** 2)

    def loss_fused(q, k, v):
        return jnp.sum(fn(q, k, v, mask=am) ** 2)

    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    g_f = jax.grad(loss_fused, (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_f):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-4)


def test_softmax_dropout_matches_jax_softmax():
    logits = jax.random.normal(jax.random.key(5), (2, 4, 64, 96)) * 4
    got = softmax_dropout(logits, out_dtype=jnp.float32)
    want = jax.nn.softmax(logits, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_softmax_dropout_masks_and_pads():
    # Non-128-multiple Skv exercises the padded-columns masking.
    logits = jax.random.normal(jax.random.key(6), (2, 2, 40, 72))
    am = _padding(7, 2, 72)
    got = softmax_dropout(logits, mask=am, out_dtype=jnp.float32)
    masked = jnp.where(
        padding_mask(am), logits.astype(jnp.float32), -jnp.inf
    )
    want = jax.nn.softmax(masked, axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_softmax_dropout_grad_matches():
    logits = jax.random.normal(jax.random.key(8), (2, 2, 64, 64))

    def f_k(x):
        return jnp.sum(softmax_dropout(x, out_dtype=jnp.float32) ** 2)

    def f_r(x):
        return jnp.sum(jax.nn.softmax(x, axis=-1) ** 2)

    np.testing.assert_allclose(
        np.asarray(jax.grad(f_k)(logits)),
        np.asarray(jax.grad(f_r)(logits)),
        atol=1e-6,
    )


def test_attend_dispatches_fused():
    q, k, v = _qkv(9, s=64)
    got = attend(q, k, v, implementation="fused")
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(attend(q, k, v)), atol=2e-4
    )
    # Mid-seq branch routes to the whole-attention kernel.
    q2, k2, v2 = _qkv(10, s=384, h=2)
    got2 = attend(q2, k2, v2, implementation="fused", causal=True)
    want2 = attend(q2, k2, v2, mask=causal_mask(384, 384))
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2), atol=2e-4)
    # Past MAX_SEQ: flash takes over, WITH in-kernel dropout (round-4; on
    # the CPU interpret path that surfaces as the no-hardware-PRNG
    # refusal rather than the round-3 unconditional ValueError).
    q3, k3, v3 = _qkv(11, s=640, h=2)
    got3 = attend(q3, k3, v3, implementation="fused")
    np.testing.assert_allclose(
        np.asarray(got3), np.asarray(attend(q3, k3, v3)), atol=2e-4
    )
    with pytest.raises(NotImplementedError, match="hardware PRNG"):
        attend(q3, k3, v3, implementation="fused", dropout_rate=0.1,
               dropout_rng=jax.random.key(0))


def test_in_kernel_dropout_requires_tpu():
    q, k, v = _qkv(11, s=64)
    with pytest.raises(NotImplementedError, match="TPU"):
        fused_attention(
            q, k, v, dropout_rate=0.1, dropout_rng=jax.random.key(0)
        )
    with pytest.raises(NotImplementedError, match="TPU"):
        softmax_dropout(
            jnp.zeros((1, 1, 64, 64)), dropout_rate=0.1,
            dropout_rng=jax.random.key(0),
        )


def test_validation():
    q, k, v = _qkv(12, s=64)
    with pytest.raises(ValueError, match="dropout_rng"):
        fused_attention(q, k, v, dropout_rate=0.1)
    with pytest.raises(ValueError, match="head_group"):
        fused_attention(q, k, v, head_group=3)
    big = jnp.zeros((1, 2048, 2, 32))
    with pytest.raises(ValueError, match="flash"):
        fused_attention(big, big, big)
    with pytest.raises(ValueError, match="Sq == Skv"):
        fused_attention(q, k[:, :32], v[:, :32])
