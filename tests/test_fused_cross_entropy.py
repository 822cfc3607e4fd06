"""Fused (vocab-streaming) softmax-cross-entropy parity vs the optax
composite, plus the no-[B, V]-softmax materialization guarantee.

Interpreter-mode Pallas on the CPU backend. Shapes deliberately include
non-tile-multiple vocab sizes so the padded columns' exclusion from the
logsumexp / label gather / smoothing sum is under test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.ops.cross_entropy import (
    softmax_cross_entropy,
    softmax_cross_entropy_ref,
)


def _data(rng, b=19, v=300, scale=3.0):
    logits = jnp.asarray(rng.normal(size=(b, v)) * scale, jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, size=(b,)), jnp.int32)
    return logits, labels


@pytest.mark.parametrize("b,v", [(19, 300), (32, 256), (7, 100), (64, 1000)])
def test_forward_parity(rng_np, b, v):
    logits, labels = _data(rng_np, b, v)
    np.testing.assert_allclose(
        np.asarray(softmax_cross_entropy(logits, labels, impl="fused")),
        np.asarray(softmax_cross_entropy_ref(logits, labels)),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("smoothing", [0.1, 0.3])
def test_forward_parity_label_smoothing(rng_np, smoothing):
    logits, labels = _data(rng_np)
    np.testing.assert_allclose(
        np.asarray(
            softmax_cross_entropy(logits, labels, smoothing, impl="fused")
        ),
        np.asarray(softmax_cross_entropy_ref(logits, labels, smoothing)),
        rtol=1e-5, atol=1e-5,
    )


@pytest.mark.parametrize("smoothing", [0.0, 0.1])
def test_gradient_parity(rng_np, smoothing):
    logits, labels = _data(rng_np)
    gf = jax.grad(
        lambda z: softmax_cross_entropy(
            z, labels, smoothing, impl="fused"
        ).mean()
    )(logits)
    gr = jax.grad(
        lambda z: softmax_cross_entropy_ref(z, labels, smoothing).mean()
    )(logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_gradient_parity_per_example_cotangent(rng_np):
    """Non-uniform per-example cotangents (the masked-eval weighting
    path) must scale each row's gradient independently."""
    logits, labels = _data(rng_np, b=11, v=200)
    w = jnp.asarray(rng_np.uniform(0.0, 2.0, size=(11,)), jnp.float32)
    gf = jax.grad(
        lambda z: jnp.sum(
            softmax_cross_entropy(z, labels, impl="fused") * w
        )
    )(logits)
    gr = jax.grad(
        lambda z: jnp.sum(softmax_cross_entropy_ref(z, labels) * w)
    )(logits)
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def test_vocab_padding_masked_out(rng_np):
    """V=100 pads to 128 lanes; the 28 pad columns must not leak into
    the logsumexp even when the real logits are very negative (a pad
    zero would dominate exp(0))."""
    logits = jnp.asarray(
        rng_np.normal(size=(9, 100)) - 50.0, jnp.float32
    )
    labels = jnp.asarray(rng_np.integers(0, 100, size=(9,)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(softmax_cross_entropy(logits, labels, impl="fused")),
        np.asarray(softmax_cross_entropy_ref(logits, labels)),
        rtol=1e-5, atol=1e-4,
    )


def test_tiny_num_classes(rng_np):
    """The classification loss sites run V=2 through the same kernel."""
    logits, labels = _data(rng_np, b=33, v=2, scale=1.0)
    np.testing.assert_allclose(
        np.asarray(softmax_cross_entropy(logits, labels, impl="fused")),
        np.asarray(softmax_cross_entropy_ref(logits, labels)),
        rtol=1e-5, atol=1e-5,
    )


def test_auto_cpu_fallback_and_shape_checks(rng_np):
    logits, labels = _data(rng_np)
    auto = softmax_cross_entropy(logits, labels, impl="auto")
    assert (
        np.asarray(auto)
        == np.asarray(softmax_cross_entropy_ref(logits, labels))
    ).all()
    with pytest.raises(ValueError, match="logits"):
        softmax_cross_entropy(logits[None], labels, impl="fused")


def test_lm_shaped_leading_dims(rng_np):
    """[B, S, V] logits / [B, S] labels (the LM loss shape) are
    rank-generic on BOTH paths — fwd and grads — like the optax
    composite always was."""
    logits = jnp.asarray(rng_np.normal(size=(3, 5, 130)) * 2, jnp.float32)
    labels = jnp.asarray(rng_np.integers(0, 130, size=(3, 5)), jnp.int32)
    ref = softmax_cross_entropy_ref(logits, labels)
    for impl in ("reference", "fused"):
        out = softmax_cross_entropy(logits, labels, impl=impl)
        assert out.shape == (3, 5)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    gf = jax.grad(
        lambda z: softmax_cross_entropy(z, labels, impl="fused").mean()
    )(logits)
    gr = jax.grad(lambda z: softmax_cross_entropy_ref(z, labels).mean())(
        logits
    )
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                               rtol=1e-5, atol=1e-6)


def _sub_jaxprs(params):
    """Sub-jaxprs hiding in an eqn's params (custom_vjp/pjit bodies)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def _bv_eqns(jaxpr, min_size, skip=("pallas_call",)):
    """All equations (recursively, except inside Pallas kernels) whose
    output is a float array of at least ``min_size`` elements."""
    found = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in skip:
                continue
            for sub in _sub_jaxprs(eqn.params):
                walk(sub)
            for var in eqn.outvars:
                aval = var.aval
                if (
                    hasattr(aval, "shape")
                    and np.issubdtype(aval.dtype, np.floating)
                    and int(np.prod(aval.shape or (1,))) >= min_size
                ):
                    found.append((eqn.primitive.name, aval.shape))
    walk(jaxpr)
    return found


def test_fused_never_materializes_bv_softmax(rng_np):
    """Jaxpr audit: with tile-aligned shapes, the fused fwd+bwd contains
    NO [B, V]-sized float intermediate outside the Pallas kernels —
    the probability tensor exists only tile-by-tile in VMEM. The
    composite's jaxpr (sanity leg) contains several."""
    b, v = 64, 256  # tile-aligned: no pad/slice ops in the entry
    logits, labels = _data(rng_np, b, v)

    def fused_loss(z):
        return softmax_cross_entropy(z, labels, impl="fused").mean()

    def ref_loss(z):
        return softmax_cross_entropy_ref(z, labels).mean()

    fwd = jax.make_jaxpr(fused_loss)(logits)
    assert _bv_eqns(fwd.jaxpr, b * v) == [], (
        f"fused forward materializes [B, V] floats: "
        f"{_bv_eqns(fwd.jaxpr, b * v)}"
    )
    # Backward: the gradient itself is [B, V] but must come straight out
    # of the Pallas kernel — nothing else [B, V]-sized around it.
    bwd = jax.make_jaxpr(jax.grad(fused_loss))(logits)
    assert _bv_eqns(bwd.jaxpr, b * v) == [], (
        f"fused backward materializes [B, V] floats beyond the kernel: "
        f"{_bv_eqns(bwd.jaxpr, b * v)}"
    )
    # The audit itself must be able to see a materialization (meta-test).
    assert len(_bv_eqns(jax.make_jaxpr(ref_loss)(logits).jaxpr, b * v)) > 0
