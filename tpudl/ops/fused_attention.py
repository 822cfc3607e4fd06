"""Pallas TPU fused short-sequence attention with in-kernel dropout.

Why this exists next to tpudl.ops.flash_attention: the flash kernel's
streaming design (kv tiles + online softmax + 3-kernel backward with
saved logsumexp) wins when S is large, but at the configs[1] headline
shape (BERT fine-tune, seq 128) it LOSES to XLA's einsum attention
(measured in the early rounds, BASELINE.md; no ledger row times it).
At short S the whole [S, S] score tile fits in registers, so
the right kernel shape is different:

- one grid cell owns a (batch row, head group): q/k/v arrive as natural
  [B, S, H*D] rows — NO host-side transposes or BSHD->BHSD copies, the
  model's reshape into the kernel is a free bitcast;
- full softmax is computed in-cell (no online merge, no logsumexp
  residual), and the backward pass is ONE kernel that recomputes the
  [S, S] probabilities and emits dq/dk/dv together;
- attention-probability dropout runs IN the kernel from the TPU hardware
  PRNG (pltpu.prng_random_bits): the [B, H, S, S] keep mask never touches
  HBM. Measured on the headline step, materialized-mask dropout costs
  20 ms/step (45.7% -> 50.5% MFU when switched off) — this kernel makes
  that cost disappear instead of making the semantics disappear.

HBM traffic per layer becomes the theoretical floor (read q,k,v + write
o; backward reads those + do and writes dq,dk,dv) — the einsum path's
[B, H, S, S] logits/probs round trips (~800 MB/layer at the headline
shape) are gone.

Scope: self-attention with Sq == Skv == S, S small enough that [S, S]
f32 tiles live in VMEM comfortably (guarded at S <= 1024; use flash
beyond). Masking contract matches flash: [B, S] kv-validity rows or
[B, 1, 1, S] padding masks plus an in-kernel causal triangle; dense
masks are rejected.

Dropout determinism: the keep mask is a pure function of (dropout_rng,
batch row, head group) — forward and backward regenerate identical bits
by reseeding per cell, so no mask is stored anywhere. The PRNG sequence
is the TPU hardware generator's; it does not reproduce
jax.random.bernoulli's threefry stream (the reference implementation's
masks differ — parity tests compare distributions, not bits). Requires a
real TPU: pallas interpret mode has no PRNG emulation, so
dropout_rate > 0 raises under interpret.
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
    keep_mask,
    kv_valid,
    round_up as _round_up,
    seed_cell,
)

#: [S, S] f32 score tiles above this do not fit the in-register design
#: (measured 2026-07-30: S=512 compiles and beats einsum 4.3 vs 5.5 ms
#: fwd+bwd; S=1024 blows VMEM in the one-pass backward — use flash).
MAX_SEQ = 512


def _kernel_body(
    g, seed_ref, q_ref, k_ref, v_ref, kvm_ref, *, scale, causal, rate,
    head_dim, has_kvmask,
):
    """Shared fwd recompute for one head g of the cell's group: returns
    (p, keep) where p is the post-softmax pre-dropout probability tile
    [S, S] f32 and keep the dropout keep-mask (or None)."""
    d = head_dim
    q = q_ref[0, :, g * d:(g + 1) * d]
    k = k_ref[0, :, g * d:(g + 1) * d]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [S, S]

    seq = s.shape[0]
    if has_kvmask:
        s = jnp.where(kv_valid(kvm_ref, s.shape), s, MASK_VALUE)
    if causal:
        q_ids = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 0)
        kv_ids = jax.lax.broadcasted_iota(jnp.int32, (seq, seq), 1)
        s = jnp.where(kv_ids <= q_ids, s, MASK_VALUE)

    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if has_kvmask or causal:
        # exp(MASK - m) can be 1.0 on fully-masked rows (m == MASK);
        # re-zero explicitly so those rows produce 0, not garbage.
        p = jnp.where(s <= MASK_VALUE, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l > 0.0, l, 1.0)

    keep = keep_mask((seq, seq), rate) if rate > 0.0 else None
    return p, keep


def _seed_cell(seed_ref):
    seed_cell(seed_ref, flat_cell_id(2))


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, kvm_ref, o_ref, *,
                scale, causal, rate, head_dim, group, has_kvmask):
    if rate > 0.0:
        _seed_cell(seed_ref)
    d = head_dim
    for g in range(group):
        p, keep = _kernel_body(
            g, seed_ref, q_ref, k_ref, v_ref, kvm_ref,
            scale=scale, causal=causal, rate=rate, head_dim=d,
            has_kvmask=has_kvmask,
        )
        if keep is not None:
            p = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        v = v_ref[0, :, g * d:(g + 1) * d]
        o_ref[0, :, g * d:(g + 1) * d] = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref,
                dq_ref, dk_ref, dv_ref, *,
                scale, causal, rate, head_dim, group, has_kvmask):
    if rate > 0.0:
        # Identical reseed + identical per-g generation order as forward
        # -> bit-identical keep masks with nothing stored.
        _seed_cell(seed_ref)
    d = head_dim
    inv = 1.0 / (1.0 - rate) if rate > 0.0 else 1.0
    for g in range(group):
        p, keep = _kernel_body(
            g, seed_ref, q_ref, k_ref, v_ref, kvm_ref,
            scale=scale, causal=causal, rate=rate, head_dim=d,
            has_kvmask=has_kvmask,
        )
        q = q_ref[0, :, g * d:(g + 1) * d]
        k = k_ref[0, :, g * d:(g + 1) * d]
        v = v_ref[0, :, g * d:(g + 1) * d]
        do = do_ref[0, :, g * d:(g + 1) * d]

        # out = drop(p) @ v, drop(p) = keep * p * inv
        dpd = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [S, S] = d out / d drop(p)
        if keep is not None:
            dp = jnp.where(keep, dpd * inv, 0.0)
            pd = jnp.where(keep, p * inv, 0.0)
        else:
            dp = dpd
            pd = p
        dv_ref[0, :, g * d:(g + 1) * d] = jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)
        # softmax VJP wrt logits: ds = p * (dp - <dp, p>_row), then the
        # scale from s = (q k^T) * scale.
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        ds = (ds * scale).astype(q.dtype)
        dq_ref[0, :, g * d:(g + 1) * d] = jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dq_ref.dtype)
        dk_ref[0, :, g * d:(g + 1) * d] = jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dk_ref.dtype)


def _specs(b, s_p, h, d, group):
    row = pl.BlockSpec(
        (1, s_p, group * d), lambda bi, hg: (bi, 0, hg),
        memory_space=pltpu.VMEM,
    )
    # [B, 1, S] with a (1, 1, S) block: the lane-dim layout TPU block
    # specs require (middle dim 1 == array dim satisfies the tiling rule).
    kvm = pl.BlockSpec((1, 1, s_p), lambda bi, hg: (bi, 0, 0),
                       memory_space=pltpu.VMEM)
    seed = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid = (b, h // group)
    return grid, seed, row, kvm


def _prep(q, k, v, kvmask):
    """[B, S, H, D] -> padded [B, S_p, H*D] rows (free reshape, S padded
    to the f32 tile sublane/lane quantum) + padded kv row."""
    b, s, h, d = q.shape
    s_p = _round_up(s, 128)
    flat = lambda x: jnp.pad(
        x.reshape(b, s, h * d), ((0, 0), (0, s_p - s), (0, 0))
    )
    kvm = jnp.pad(kvmask, ((0, 0), (0, s_p - s)))[:, None, :]
    return flat(q), flat(k), flat(v), kvm, s_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _fused(q, k, v, kvmask, seed, causal, scale, rate, group, interpret,
           has_mask):
    out, _ = _fused_fwd(
        q, k, v, kvmask, seed, causal, scale, rate, group, interpret, has_mask
    )
    return out


def _fused_fwd(q, k, v, kvmask, seed, causal, scale, rate, group, interpret,
               has_mask):
    b, s, h, d = q.shape
    qf, kf, vf, kvm, s_p = _prep(q, k, v, kvmask)
    has_kvmask = bool(has_mask) or s_p != s
    grid, seed_spec, row, kvm_spec = _specs(b, s_p, h, d, group)
    o = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, rate=rate,
            head_dim=d, group=group, has_kvmask=has_kvmask,
        ),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        in_specs=[seed_spec, row, row, row, kvm_spec],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((b, s_p, h * d), q.dtype),
        interpret=interpret,
    )(seed, qf, kf, vf, kvm)
    out = o[:, :s, :].reshape(b, s, h, d)
    return out, (q, k, v, kvmask, seed)


def _fused_bwd(causal, scale, rate, group, interpret, has_mask, res, g_out):
    q, k, v, kvmask, seed = res
    b, s, h, d = q.shape
    qf, kf, vf, kvm, s_p = _prep(q, k, v, kvmask)
    # Padded do rows are zero -> their ds/dq contributions vanish; padded
    # kv columns are masked in the recompute exactly as in forward.
    dof = jnp.pad(
        g_out.astype(q.dtype).reshape(b, s, h * d),
        ((0, 0), (0, s_p - s), (0, 0)),
    )
    has_kvmask = bool(has_mask) or s_p != s
    grid, seed_spec, row, kvm_spec = _specs(b, s_p, h, d, group)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_kernel, scale=scale, causal=causal, rate=rate,
            head_dim=d, group=group, has_kvmask=has_kvmask,
        ),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        in_specs=[seed_spec, row, row, row, kvm_spec, row],
        out_specs=[row, row, row],
        out_shape=[
            jax.ShapeDtypeStruct((b, s_p, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, s_p, h * d), k.dtype),
            jax.ShapeDtypeStruct((b, s_p, h * d), v.dtype),
        ],
        interpret=interpret,
    )(seed, qf, kf, vf, kvm, dof)
    unflat = lambda x: x[:, :s, :].reshape(b, s, h, d)
    return (
        unflat(dq), unflat(dk), unflat(dv),
        jnp.zeros_like(kvmask), jnp.zeros_like(seed),
    )


_fused.defvjp(_fused_fwd, _fused_bwd)


def _pick_group(h: int, s: int) -> int:
    """Largest head group whose [S, group*D] rows stay comfortably inside
    VMEM alongside the [S, S] f32 score tile; at short S, bigger groups
    amortize per-cell grid/DMA overhead."""
    g = h
    # At long S the score tile dominates VMEM; shrink the group.
    while g > 1 and s * g > 4096:
        g = next((x for x in range(g - 1, 0, -1) if h % x == 0), 1)
    return g


def fused_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    head_group: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused short-seq attention on [B, S, H, D] (tpudl.ops.attention
    contract): full-softmax Pallas kernel, one-pass backward, optional
    in-kernel attention-probability dropout from the TPU hardware PRNG.

    ``mask``: [B, S] kv-validity row or [B, 1, 1, S] padding mask (dense
    masks rejected — use implementation='reference'). ``head_group``
    packs that many heads into one grid cell (must divide H; default
    auto). ``dropout_rate`` > 0 needs ``dropout_rng`` and a real TPU.
    """
    from tpudl.ops.attention import is_tpu_backend, normalize_kv_mask

    b, s, h, d = q.shape
    if k.shape[1] != s:
        raise ValueError(
            f"fused_attention is self-attention-shaped (Sq == Skv); got "
            f"Sq={s}, Skv={k.shape[1]} — use flash_attention"
        )
    if s > MAX_SEQ:
        raise ValueError(
            f"fused_attention holds full [S, S] score tiles in VMEM; "
            f"S={s} > {MAX_SEQ} — use implementation='flash'"
        )
    if scale is None:
        scale = d ** -0.5
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

    group = head_group or _pick_group(h, s)
    if h % group != 0:
        raise ValueError(f"head_group {group} does not divide {h} heads")

    has_mask = mask is not None
    kvmask = normalize_kv_mask(
        mask, b, s, dtype=jnp.float32, impl="fused_attention"
    )
    return _fused(
        q, k, v, kvmask, seed, causal, scale, float(dropout_rate), group,
        interpret, has_mask,
    )
