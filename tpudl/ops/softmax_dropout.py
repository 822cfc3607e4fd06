"""Pallas TPU fused masked-softmax + attention dropout over logits.

The seq-128 lesson (early rounds, BASELINE.md, 2026-07-30): XLA's
batched [B, H, S, S] attention matmuls are effectively unbeatable at
short sequence — a whole-attention Pallas kernel spends its time filling
and draining the MXU on 128x64x128 dots (tpudl.ops.fused_attention is at
einsum parity standalone but loses in-step). What XLA is NOT good at is
attention-probability dropout: jax.random.bernoulli materializes the
[B, H, S, S] keep mask through HBM, measured at 20 ms/step on the
headline BERT fine-tune (45.7% -> 50.5% MFU with dropout off).

So this kernel splits the work where each side is strongest: XLA keeps
the batched QK^T and PV matmuls; one bandwidth-bound Pallas pass turns
logits into dropped probabilities — row softmax, kv-validity/causal
masking, and dropout drawn from the TPU hardware PRNG in-kernel, so no
mask ever touches HBM. The backward pass is one more pass: it re-reads
the logits (which XLA already has in HBM — zero extra residual memory),
regenerates the identical dropout bits by reseeding, and emits dlogits.

Traffic per layer at the headline shape: fwd reads logits f32 + writes
probs bf16; bwd reads logits + upstream grad + writes dlogits — ~3 HBM
round trips of the score tensor total, versus the reference path's
softmax + bernoulli + two wheres (~6 round trips plus mask generation).

Seeding matches tpudl.ops.fused_attention: the keep mask is a pure
function of (dropout_rng, grid cell), forward and backward bit-identical
by construction. Requires a real TPU when dropout_rate > 0 (interpret
mode has no PRNG emulation).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.attention import MASK_VALUE
from tpudl.ops.pallas_utils import (
    flat_cell_id,
    keep_mask as _keep_mask_impl,
    kv_valid,
    round_up as _round_up,
    seed_cell,
)


def _seed_cell(seed_ref):
    seed_cell(seed_ref, flat_cell_id(3))


def _masked_softmax(s, kvm_ref, *, causal, q_off, block_q, has_kvmask):
    """Row softmax of one [Gh*bq, Skv] merged logits tile (heads are
    rows too — softmax rows are independent, so head-merging is free and
    buys big enough tiles to amortize grid/DMA overhead) with
    kv-validity and causal masking; returns post-softmax pre-dropout
    probabilities."""
    rows, skv = s.shape
    masked = has_kvmask or causal
    if has_kvmask:
        s = jnp.where(kv_valid(kvm_ref, s.shape), s, MASK_VALUE)
    if causal:
        row_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, skv), 0)
        q_ids = q_off + jax.lax.rem(row_ids, block_q)
        kv_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, skv), 1)
        s = jnp.where(kv_ids <= q_ids, s, MASK_VALUE)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if masked:
        p = jnp.where(s <= MASK_VALUE, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p / jnp.where(l > 0.0, l, 1.0)


def _keep_mask(shape, rate):
    return _keep_mask_impl(shape, rate)


def _fwd_kernel(seed_ref, x_ref, kvm_ref, o_ref, *,
                causal, rate, block_q, has_kvmask):
    if rate > 0.0:
        _seed_cell(seed_ref)
    gh, bq, skv = x_ref.shape[1:]
    s = x_ref[0].reshape(gh * bq, skv).astype(jnp.float32)
    p = _masked_softmax(
        s, kvm_ref, causal=causal, q_off=pl.program_id(2) * block_q,
        block_q=block_q, has_kvmask=has_kvmask,
    )
    if rate > 0.0:
        keep = _keep_mask(s.shape, rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
    o_ref[0] = p.reshape(gh, bq, skv).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, x_ref, kvm_ref, g_ref, dx_ref, *,
                causal, rate, block_q, has_kvmask):
    if rate > 0.0:
        _seed_cell(seed_ref)
    gh, bq, skv = x_ref.shape[1:]
    s = x_ref[0].reshape(gh * bq, skv).astype(jnp.float32)
    p = _masked_softmax(
        s, kvm_ref, causal=causal, q_off=pl.program_id(2) * block_q,
        block_q=block_q, has_kvmask=has_kvmask,
    )
    g = g_ref[0].reshape(gh * bq, skv).astype(jnp.float32)
    if rate > 0.0:
        keep = _keep_mask(s.shape, rate)
        g = jnp.where(keep, g * (1.0 / (1.0 - rate)), 0.0)
    # softmax VJP: dlogits = p * (g - <g, p>_row)
    dx = p * (g - jnp.sum(g * p, axis=-1, keepdims=True))
    dx_ref[0] = dx.reshape(gh, bq, skv).astype(dx_ref.dtype)


def _prep(x, kvmask, block_q):
    b, h, sq, skv = x.shape
    sq_p = _round_up(sq, block_q)
    skv_p = _round_up(skv, 128)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, sq_p - sq), (0, skv_p - skv)))
    kvm = jnp.pad(kvmask, ((0, 0), (0, skv_p - skv)))[:, None, :]
    return xp, kvm, sq_p, skv_p


def _head_group(h: int, block_q: int, skv_p: int) -> int:
    """Heads per grid cell: target ~2 MB f32 tiles so DMA/grid overhead
    amortizes (the whole point vs per-head cells)."""
    g = h
    while g > 1 and (h % g != 0 or g * block_q * skv_p * 4 > 2**21):
        g -= 1
    return max(g, 1)


def _specs(b, h, sq_p, skv_p, block_q, group):
    tile = pl.BlockSpec(
        (1, group, block_q, skv_p), lambda bi, hi, qi: (bi, hi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    kvm = pl.BlockSpec((1, 1, skv_p), lambda bi, hi, qi: (bi, 0, 0),
                       memory_space=pltpu.VMEM)
    seed = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid = (b, h // group, sq_p // block_q)
    sem = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel")
    )
    return grid, seed, tile, kvm, sem


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _sd(x, kvmask, seed, causal, rate, block_q, out_dtype, interpret,
        has_mask):
    out, _ = _sd_fwd(
        x, kvmask, seed, causal, rate, block_q, out_dtype, interpret, has_mask
    )
    return out


def _sd_fwd(x, kvmask, seed, causal, rate, block_q, out_dtype, interpret,
            has_mask):
    b, h, sq, skv = x.shape
    xp, kvm, sq_p, skv_p = _prep(x, kvmask, block_q)
    has_kvmask = bool(has_mask) or skv_p != skv
    group = _head_group(h, block_q, skv_p)
    grid, seed_spec, tile, kvm_spec, sem = _specs(
        b, h, sq_p, skv_p, block_q, group
    )
    o = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=causal, rate=rate, block_q=block_q,
            has_kvmask=has_kvmask,
        ),
        grid=grid,
        compiler_params=sem,
        in_specs=[seed_spec, tile, kvm_spec],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, skv_p), out_dtype),
        interpret=interpret,
    )(seed, xp, kvm)
    return o[:, :, :sq, :skv], (x, kvmask, seed)


def _sd_bwd(causal, rate, block_q, out_dtype, interpret, has_mask, res, g):
    x, kvmask, seed = res
    b, h, sq, skv = x.shape
    xp, kvm, sq_p, skv_p = _prep(x, kvmask, block_q)
    gp = jnp.pad(
        g, ((0, 0), (0, 0), (0, sq_p - sq), (0, skv_p - skv))
    )
    has_kvmask = bool(has_mask) or skv_p != skv
    group = _head_group(h, block_q, skv_p)
    grid, seed_spec, tile, kvm_spec, sem = _specs(
        b, h, sq_p, skv_p, block_q, group
    )
    g_tile = pl.BlockSpec(
        (1, group, block_q, skv_p), lambda bi, hi, qi: (bi, hi, qi, 0),
        memory_space=pltpu.VMEM,
    )
    dx = pl.pallas_call(
        functools.partial(
            _bwd_kernel, causal=causal, rate=rate, block_q=block_q,
            has_kvmask=has_kvmask,
        ),
        grid=grid,
        compiler_params=sem,
        in_specs=[seed_spec, tile, kvm_spec, g_tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, skv_p), x.dtype),
        interpret=interpret,
    )(seed, xp, kvm, gp)
    return dx[:, :, :sq, :skv], jnp.zeros_like(kvmask), jnp.zeros_like(seed)


_sd.defvjp(_sd_fwd, _sd_bwd)


def softmax_dropout(
    logits: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    out_dtype=jnp.bfloat16,
    block_q: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Masked row-softmax + attention dropout of [B, H, Sq, Skv] logits
    in one Pallas pass, probabilities returned in ``out_dtype``.

    ``mask``: [B, Skv] kv-validity row or [B, 1, 1, Skv] padding mask
    (dense masks rejected). Bottom-right-aligned causal masking assumes
    Sq == Skv when ``causal`` (asserted). ``dropout_rate`` > 0 needs
    ``dropout_rng`` and a real TPU.
    """
    from tpudl.ops.attention import is_tpu_backend, normalize_kv_mask

    b, h, sq, skv = logits.shape
    if causal and sq != skv:
        raise ValueError(
            f"causal softmax_dropout expects Sq == Skv, got {sq} vs {skv}"
        )
    if interpret is None:
        interpret = not is_tpu_backend()
    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires dropout_rng")
        if interpret:
            raise NotImplementedError(
                "in-kernel dropout uses the TPU hardware PRNG, which "
                "pallas interpret mode does not emulate — run on TPU or "
                "use implementation='reference'"
            )
        seed = jax.random.bits(dropout_rng, (2,), jnp.uint32)
    else:
        seed = jnp.zeros((2,), jnp.uint32)

    has_mask = mask is not None
    kvmask = normalize_kv_mask(
        mask, b, skv, dtype=jnp.float32, impl="softmax_dropout"
    )
    return _sd(
        logits, kvmask, seed, causal, float(dropout_rate),
        min(block_q, _round_up(sq, 8)), out_dtype, interpret, has_mask,
    )


def hybrid_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Short-seq attention on [B, S, H, D]: XLA batched matmuls around the
    fused softmax+dropout kernel — the fastest configuration measured at
    the configs[1] headline shape (each side doing what it's best at)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    # Logits materialize in the input dtype (bf16 on the training path) —
    # the same precision the reference einsum path stores them at (its
    # f32 cast happens AFTER the bf16 dot output); the kernel upcasts to
    # f32 in-register for the softmax. Halves score-tensor HBM traffic.
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * jnp.asarray(
        scale, q.dtype
    )
    probs = softmax_dropout(
        logits, mask=mask, causal=causal, dropout_rate=dropout_rate,
        dropout_rng=dropout_rng, out_dtype=v.dtype,
    )
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
