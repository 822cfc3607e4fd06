"""Low-width-bits dropout: the TPU-native mask generation path.

jax.random.bernoulli generates 32 random bits per element and converts
them to floats before comparing — for attention-probability dropout on
the configs[1] headline step that random-bit traffic alone showed as
the rng-bit-generator in the profile, and the whole bernoulli dropout
chain cost about a tenth of the step (early rounds, BASELINE.md; the
BERT cell's mask-generation metrics in PERF.md are today's reading).

A keep/drop decision needs nowhere near 32 bits of entropy: this module
draws uint8 bits from the same (hardware-RBG-backed) generator and
compares against ``round(rate * 256)`` — a quarter of the random-bit
traffic and an integer compare instead of a float convert+compare.
The BERT cell of the benchmark runs this path (PERF.md, "Where the
time goes").

The cost: the effective drop rate quantizes to multiples of 1/256
(rate 0.1 becomes 26/256 ~ 0.1016). Dropout rates are loose
hyperparameters — a 0.16-point shift is far inside run-to-run noise —
but it is a real semantic deviation, so it lives here under its own
name instead of silently replacing bernoulli everywhere; `exact=True`
restores bit-exact bernoulli semantics.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


def quantized_rate(rate: float, exact: bool = False) -> float:
    """The EFFECTIVE drop rate of dropout_keep_mask: on the uint8 path
    the requested rate rounds to threshold/256. Inverted-dropout rescale
    must use this value, not the nominal rate, or E[output] drifts from
    the input by the quantization gap (~0.17% at rate 0.1)."""
    if exact or rate <= 0.0:
        return rate
    if rate >= 1.0:
        return 1.0
    return min(int(round(rate * 256.0)), 255) / 256.0


def dropout_keep_mask(
    rng: jax.Array, shape, rate: float, exact: bool = False
) -> jax.Array:
    """Boolean keep-mask: True with probability 1 - quantized_rate(rate).

    ``exact=False`` (default) uses uint8 random bits — rate quantized to
    round(rate * 256) / 256; ``exact=True`` uses jax.random.bernoulli
    (f32-uniform compare, 4x the bit traffic).
    """
    # The scope names mask generation in a profiler trace (HLO metadata
    # only); ``apply_keep_mask`` puts the application under it too.
    with jax.named_scope("dropout"):
        if exact:
            return jax.random.bernoulli(rng, 1.0 - rate, shape)
        if rate >= 1.0:
            return jnp.zeros(shape, bool)  # flax.nn.Dropout(1.0) semantics
        threshold = int(round(rate * 256.0))
        if threshold <= 0:
            return jnp.ones(shape, bool)
        bits = jax.random.bits(rng, shape, jnp.uint8)
        return bits >= jnp.uint8(min(threshold, 255))


def apply_keep_mask(
    keep: jax.Array, x: jax.Array, rate: float, exact: bool = False
) -> jax.Array:
    """Inverted-dropout application of a keep-mask: kept elements are
    scaled by the EFFECTIVE keep probability, so E[output] == input on
    the quantized path too."""
    eff = quantized_rate(rate, exact)
    with jax.named_scope("dropout"):
        return jnp.where(keep, x / (1.0 - eff), 0.0).astype(x.dtype)


def dropout(
    rng: jax.Array,
    x: jax.Array,
    rate: float,
    exact: bool = False,
) -> jax.Array:
    """Inverted dropout of ``x`` (scale-at-train by the EFFECTIVE keep
    probability, so E[output] == input on the quantized path too)."""
    if rate <= 0.0:
        return x
    keep = dropout_keep_mask(rng, x.shape, rate, exact=exact)
    return apply_keep_mask(keep, x, rate, exact)


class Dropout(nn.Module):
    """Drop-in for flax.linen.Dropout on the low-width-bits path (same
    "dropout" rng collection and `deterministic` contract)."""

    rate: float
    exact: bool = False

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        if deterministic or self.rate <= 0.0:
            return x
        return dropout(self.make_rng("dropout"), x, self.rate, self.exact)


# ---------------------------------------------------------------------------
# Sharded-attention dropout key plumbing (shared by the ring and ulysses
# sequence-parallel paths — ONE fold convention, or the two would
# silently diverge).
# ---------------------------------------------------------------------------


def shard_fold_axes(mesh, axis_name: str, heads_sharded: bool, batch_axes):
    """(name, size) pairs of the mesh axes whose slots hold DISTINCT
    data and therefore need distinct dropout masks: the sharded batch
    axes, the sequence-parallel axis itself, and tp only when heads are
    genuinely tp-sharded — folding an axis the output is REPLICATED over
    would make 'replicated' shards disagree."""
    from tpudl.runtime.mesh import AXIS_TENSOR

    axes = tuple(
        (a, mesh.shape[a]) for a in batch_axes if mesh.shape[a] > 1
    )
    axes += ((axis_name, mesh.shape[axis_name]),)
    if heads_sharded:
        axes += ((AXIS_TENSOR, mesh.shape[AXIS_TENSOR]),)
    return axes


def device_fold_rng(key_data, key_impl, fold_axes):
    """Inside a shard_map body: re-wrap the replicated raw key data and
    fold in this device's mixed-radix position over ``fold_axes``."""
    import jax

    rng = jax.random.wrap_key_data(key_data, impl=key_impl)
    idx = 0
    for name, size in fold_axes:
        idx = idx * size + jax.lax.axis_index(name)
    return jax.random.fold_in(rng, idx)
