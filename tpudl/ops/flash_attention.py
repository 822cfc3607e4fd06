"""Pallas TPU flash attention: fused, memory-linear attention.

The one first-party custom kernel the framework warrants (SURVEY.md §2.2):
attention is the hot op of the NLP configs (BASELINE.json configs[1,3,4])
and the naive einsum path materializes the [B, H, Sq, Skv] logits in HBM.
This kernel never does — the online-softmax recurrence keeps one
(block_q, block_k) tile in VMEM, both matmuls hit the MXU in the input
dtype with f32 accumulation, and the backward pass recomputes probability
tiles from the saved logsumexp instead of storing them (memory O(S), not
O(S^2)).

Layout: grid (batch, heads, q_blocks, kv_blocks) with the kv axis
innermost, so Pallas double-buffers the K/V tile stream from HBM while the
MXU works; running max / denominator / output accumulators live in VMEM
scratch across kv steps.

Masking: padding masks enter as a [B, Skv] kv-validity row (the BERT
case), causal masks are generated in-kernel from block indices (the
decoder case) — neither ever materializes an S×S array. Arbitrary dense
[B, H, Sq, Skv] masks are not supported here; use the reference
implementation for those.

Dropout (attention-probability, BERT/Llama-style) runs IN-KERNEL from
the TPU hardware PRNG using the same reseed-regenerate contract as
tpudl.ops.fused_attention (tpudl.ops.pallas_utils): each logical
(batch, head, q_tile, kv_tile) cell seeds its own stream keyed by the
LOGICAL tile id — not the grid-order cell id, which differs between the
kv-major dk/dv launch and the q-major forward/dq launches — and the
backward regenerates the identical keep mask instead of storing it, so
long-context dropout costs zero HBM. The online-softmax denominator
accumulates UNDROPPED probabilities (dropout applies after softmax
normalization); only the p@V numerator and the dp/dv backward terms are
masked, and the standard delta = sum(do*o) identity still equals
sum_j w'_j dp'_j under the mask, so the backward recurrences are
unchanged in form. TPU-only (like the fused kernel): interpret mode has
no hardware PRNG, so dropout_rate > 0 raises there; real-TPU
verification lives in scripts/tpu_dropout_check.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.attention import MASK_VALUE
from tpudl.ops.pallas_utils import kernel_trace

#: Default tile sizes; VPU/MXU-aligned (multiples of the f32 (8,128) tile).
#: Swept on TPU v5 lite at seq 4096 (2026-07-30): large kv tiles keep the
#: MXU fed (256x256 -> 49 ms, 512x1024 -> 22 ms fwd+bwd; XLA einsum
#: attention: 26.5 ms).
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _interpret_default() -> bool:
    # Interpret mode off-TPU so the same kernel runs in the hermetic test
    # environment (SURVEY.md §4.2) and compiled on TPU.
    from tpudl.ops.attention import is_tpu_backend

    return not is_tpu_backend()


#: Grid semantics for every pallas_call here: batch/head/q axes carry no
#: cross-step state (parallel); the kv (resp. q) reduction axis streams
#: through the VMEM scratch accumulators (arbitrary).
_DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")


def _fit_block(seq: int, limit: int) -> int:
    """Largest power-of-two block <= limit that divides the 128-aligned
    sequence — avoids pad-to-tile waste on non-power-of-two lengths
    (e.g. skv=1280 takes 256-blocks, not a 2048 pad)."""
    aligned = _round_up(seq, 128)
    b = min(limit, aligned)
    while b > 128 and aligned % b != 0:
        b //= 2
    return max(b, 128)


def _block_sizes(sq: int, skv: int, block_q, block_k):
    bq = block_q or _fit_block(sq, DEFAULT_BLOCK_Q)
    bk = block_k or _fit_block(skv, DEFAULT_BLOCK_K)
    return min(bq, _round_up(sq, 128)), min(bk, _round_up(skv, 128))


def _tile_contributes(qi, kv, causal, block_q, block_k, causal_offset):
    """Whether kv tile `kv` can contribute to q tile `qi` (causal skip).

    Causal masking is bottom-right aligned like
    tpudl.ops.attention.causal_mask: kv_idx <= q_idx + (Skv - Sq)."""
    if not causal:
        return True
    q_end = (qi + 1) * block_q - 1 + causal_offset
    return kv * block_k <= q_end


def _dropout_keep(seed_ref, bi, hi, qi, kv, nh, nq, nkv, shape, rate):
    """Regenerate the dropout keep-mask for logical tile (bi, hi, qi, kv).

    Seeded by the LOGICAL flattened tile id so the q-major forward/dq
    grids and the kv-major dk/dv grid reproduce bit-identical masks for
    the same tile (the pallas_utils reseed contract). One
    prng_random_bits draw per cell, immediately after seeding."""
    from tpudl.ops.pallas_utils import keep_mask, seed_cell

    cell = ((bi * nh + hi) * nq + qi) * nkv + kv
    seed_cell(seed_ref, cell)
    return keep_mask(shape, rate)


def _tile_keep(kvm_row, qi, kv, causal, block_q, block_k, causal_offset,
               has_kvmask):
    """[block_q, block_k] attend-mask for one tile (or None when nothing
    masks): kv validity row plus the (bottom-right-aligned) causal
    triangle, generated from indices — never materialized at [Sq, Skv]."""
    keep = (kvm_row > 0.0)[None, :] if has_kvmask else None
    if causal:
        q_ids = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        kv_ids = kv * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        tri = kv_ids <= q_ids + causal_offset
        keep = tri if keep is None else jnp.logical_and(keep, tri)
    return keep


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, kvm_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, block_q, block_k, causal_offset,
                has_kvmask, rate):
    qi, kv = pl.program_id(2), pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kv == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(_tile_contributes(qi, kv, causal, block_q, block_k, causal_offset))
    def _accumulate():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [block_q, block_k]

        keep = _tile_keep(kvm_ref[0, 0, :], qi, kv, causal,
                          block_q, block_k, causal_offset, has_kvmask)
        if keep is not None:
            s = jnp.where(keep, s, MASK_VALUE)

        m_prev = m_scr[:, :1]  # [block_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        corr = jnp.exp(m_prev - m_new)  # [block_q, 1]
        # Denominator: UNDROPPED p (dropout acts after normalization).
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
        if rate > 0.0:
            keep_d = _dropout_keep(
                seed_ref, pl.program_id(0), pl.program_id(1), qi, kv,
                pl.num_programs(1), pl.num_programs(2), nkv,
                (block_q, block_k), rate,
            )
            p_num = jnp.where(keep_d, p, 0.0)
        else:
            p_num = p
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p_num.astype(v_ref.dtype), v_ref[0, 0, :, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(kv == nkv - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        out = acc_scr[:] / l_safe
        if rate > 0.0:
            out = out * (1.0 / (1.0 - rate))
        o_ref[0, 0, :, :] = out.astype(o_ref.dtype)
        lse_ref[0, 0, 0, :] = m_scr[:, 0] + jnp.log(l_safe[:, 0])


def _fwd(q, k, v, kvmask, seed, causal, scale, block_q, block_k, interpret,
         has_mask=True, rate=0.0):
    b, sq, h, d = q.shape
    skv = k.shape[1]
    bq, bk = _block_sizes(sq, skv, block_q, block_k)
    sq_p, skv_p = _round_up(sq, bq), _round_up(skv, bk)

    # BSHD -> BHSD, padded to tile multiples; padded kv is masked off.
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    # kv-validity mask as [B, 1, Skv]: the lane-dim layout TPU block specs
    # require (last two block dims must be tile-aligned or match the array).
    kvm = jnp.pad(kvmask, ((0, 0), (0, skv_p - skv)))[:, None, :]

    # Padding the kv axis re-introduces masking even without a user mask.
    has_kvmask = bool(has_mask) or skv_p != skv

    grid = (b, h, sq_p // bq, skv_p // bk)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            causal_offset=skv - sq, has_kvmask=has_kvmask, rate=rate,
        ),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_DIM_SEMANTICS
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, sq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(seed, qt, kt, vt, kvm)
    return o, lse, (qt, kt, vt, kvm)


# ---------------------------------------------------------------------------
# Backward: dq over (q_blocks, kv_blocks); dk/dv over (kv_blocks, q_blocks).
# Probability tiles are recomputed from the saved logsumexp.
# ---------------------------------------------------------------------------


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref,
               dlt_ref, dq_ref, dq_scr,
               *, scale, causal, block_q, block_k, causal_offset,
               has_kvmask, rate):
    qi, kv = pl.program_id(2), pl.program_id(3)
    nkv = pl.num_programs(3)

    @pl.when(kv == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_tile_contributes(qi, kv, causal, block_q, block_k, causal_offset))
    def _accumulate():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = dlt_ref[0, 0, 0, :][:, None]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = _tile_keep(kvm_ref[0, 0, :], qi, kv, causal,
                          block_q, block_k, causal_offset, has_kvmask)
        p = jnp.exp(s - lse)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            # grad w.r.t. TRUE softmax p: g = keep_d * dp / (1-rate);
            # delta (= sum(do*o)) already equals sum_j w'_j dp_j.
            keep_d = _dropout_keep(
                seed_ref, pl.program_id(0), pl.program_id(1), qi, kv,
                pl.num_programs(1), pl.num_programs(2), nkv,
                (block_q, block_k), rate,
            )
            dp = jnp.where(keep_d, dp * (1.0 / (1.0 - rate)), 0.0)
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kv == nkv - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, kvm_ref, do_ref, lse_ref,
                dlt_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, block_q, block_k, causal_offset,
                has_kvmask, rate):
    kv, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_tile_contributes(qi, kv, causal, block_q, block_k, causal_offset))
    def _accumulate():
        q = q_ref[0, 0, :, :]
        k = k_ref[0, 0, :, :]
        v = v_ref[0, 0, :, :]
        do = do_ref[0, 0, :, :]
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = dlt_ref[0, 0, 0, :][:, None]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        keep = _tile_keep(kvm_ref[0, 0, :], qi, kv, causal,
                          block_q, block_k, causal_offset, has_kvmask)
        p = jnp.exp(s - lse)
        if keep is not None:
            p = jnp.where(keep, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if rate > 0.0:
            # kv-major grid: note qi/kv pulled from swapped program_ids,
            # nq from axis 3 and nkv from axis 2 — the LOGICAL id matches
            # the forward/dq launches bit-for-bit.
            keep_d = _dropout_keep(
                seed_ref, pl.program_id(0), pl.program_id(1), qi, kv,
                pl.num_programs(1), nq, pl.num_programs(2),
                (block_q, block_k), rate,
            )
            inv = 1.0 / (1.0 - rate)
            p_num = jnp.where(keep_d, p * inv, 0.0)
            dp = jnp.where(keep_d, dp * inv, 0.0)
        else:
            p_num = p
        dv_scr[:] += jax.lax.dot_general(
            p_num.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# custom_vjp wrapper
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
           interpret, has_mask, rate):
    o, _, _ = _fwd(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
                   interpret, has_mask, rate)
    return o[:, :, : q.shape[1], :].transpose(0, 2, 1, 3)


def _flash_fwd(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
               interpret, has_mask, rate):
    o, lse, (qt, kt, vt, kvm) = _fwd(
        q, k, v, kvmask, seed, causal, scale, block_q, block_k, interpret,
        has_mask, rate,
    )
    out = o[:, :, : q.shape[1], :].transpose(0, 2, 1, 3)
    # Padded tensors are the residuals (no re-pad in bwd); the unpadded
    # kvmask rides along so bwd can recover the original Skv statically.
    return out, (qt, kt, vt, kvm, kvmask, seed, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, has_mask, rate,
               res, g):
    return _bwd_core(causal, scale, block_q, block_k, interpret, has_mask,
                     rate, res, g, dlse=None)


def _bwd_core(causal, scale, block_q, block_k, interpret, has_mask, rate,
              res, g, dlse):
    """Shared backward for _flash (dlse=None) and _flash_lse.

    The lse cotangent needs NO kernel change: d(lse)/d(s_ij) = p_ij, and
    both kernels compute ``ds = p * (dp - delta)`` — so folding the lse
    cotangent in is exactly ``delta -= dlse`` on the per-row delta
    operand.
    """
    qt, kt, vt, kvm, kvmask, seed, o, lse = res
    b, h, sq_p, d = qt.shape
    skv_p = kt.shape[2]
    sq, skv = g.shape[1], kvmask.shape[1]
    bq, bk = _block_sizes(sq, skv, block_q, block_k)
    has_kvmask = bool(has_mask) or skv_p != skv
    dim_sem = pltpu.CompilerParams(dimension_semantics=_DIM_SEMANTICS)

    do = jnp.pad(
        g.astype(qt.dtype).transpose(0, 2, 1, 3),
        ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)),
    )
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, :, None, :]
    if dlse is not None:
        delta = delta - jnp.pad(
            dlse.astype(jnp.float32), ((0, 0), (0, 0), (0, sq_p - sq))
        )[:, :, None, :]

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h, j, 0),
                           memory_space=pltpu.VMEM)
    kvm_spec = pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j),
                            memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i),
                            memory_space=pltpu.VMEM)

    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            causal_offset=skv - sq, has_kvmask=has_kvmask, rate=rate,
        ),
        grid=(b, h, sq_p // bq, skv_p // bk),
        compiler_params=dim_sem,
        in_specs=[seed_spec, q_spec, kv_spec, kv_spec, kvm_spec, q_spec,
                  row_spec, row_spec],
        out_specs=[q_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sq_p, d), qt.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(seed, qt, kt, vt, kvm, do, lse, delta)[0]

    # kv-major grid: swap the roles of the last two grid axes in the specs.
    q_spec_t = pl.BlockSpec((1, 1, bq, d), lambda b, h, j, i: (b, h, i, 0),
                            memory_space=pltpu.VMEM)
    kv_spec_t = pl.BlockSpec((1, 1, bk, d), lambda b, h, j, i: (b, h, j, 0),
                             memory_space=pltpu.VMEM)
    kvm_spec_t = pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j),
                              memory_space=pltpu.VMEM)
    row_spec_t = pl.BlockSpec((1, 1, 1, bq), lambda b, h, j, i: (b, h, 0, i),
                              memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
            causal_offset=skv - sq, has_kvmask=has_kvmask, rate=rate,
        ),
        grid=(b, h, skv_p // bk, sq_p // bq),
        compiler_params=dim_sem,
        in_specs=[seed_spec, q_spec_t, kv_spec_t, kv_spec_t, kvm_spec_t,
                  q_spec_t, row_spec_t, row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv_p, d), kt.dtype),
            jax.ShapeDtypeStruct((b, h, skv_p, d), vt.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
    )(seed, qt, kt, vt, kvm, do, lse, delta)

    dq = dq[:, :, :sq, :].transpose(0, 2, 1, 3)
    dk = dk[:, :, :skv, :].transpose(0, 2, 1, 3)
    dv = dv[:, :, :skv, :].transpose(0, 2, 1, 3)
    return dq, dk, dv, jnp.zeros_like(kvmask), jnp.zeros_like(seed)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _prep_call(q, k, mask, scale, dropout_rate, dropout_rng, interpret):
    """Shared entry preamble for flash_attention / flash_attention_with_lse
    (ONE place for the scale/interpret defaults, the dropout contract, the
    seed derivation, and kv-mask normalization — the two public entry
    points must not drift)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = _interpret_default()

    from tpudl.ops.attention import normalize_kv_mask

    if dropout_rate > 0.0:
        if dropout_rng is None:
            raise ValueError("dropout_rate > 0 requires a dropout_rng")
        if interpret:
            raise NotImplementedError(
                "flash_attention dropout draws from the TPU hardware PRNG, "
                "which interpret mode does not implement — run on TPU or "
                "set dropout_rate=0"
            )
        seed = jax.random.bits(dropout_rng, (2,), jnp.uint32)
    else:
        seed = jnp.zeros((2,), jnp.uint32)

    kvmask = normalize_kv_mask(
        mask, b, skv, dtype=jnp.float32, impl="flash_attention"
    )
    return kvmask, seed, scale, interpret, mask is not None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
               interpret, has_mask, rate):
    o, lse, _ = _fwd(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
                     interpret, has_mask, rate)
    return (
        o[:, :, : q.shape[1], :].transpose(0, 2, 1, 3),
        lse[:, :, 0, : q.shape[1]],
    )


def _flash_lse_fwd(q, k, v, kvmask, seed, causal, scale, block_q, block_k,
                   interpret, has_mask, rate):
    o, lse, (qt, kt, vt, kvm) = _fwd(
        q, k, v, kvmask, seed, causal, scale, block_q, block_k, interpret,
        has_mask, rate,
    )
    out = (
        o[:, :, : q.shape[1], :].transpose(0, 2, 1, 3),
        lse[:, :, 0, : q.shape[1]],
    )
    return out, (qt, kt, vt, kvm, kvmask, seed, o, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, has_mask,
                   rate, res, g):
    do, dlse = g
    return _bwd_core(causal, scale, block_q, block_k, interpret, has_mask,
                     rate, res, do, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """flash_attention that ALSO returns the per-query logsumexp
    ([B, H, Sq] f32) — the statistic a distributed-softmax caller needs
    to merge partial attention results across kv blocks, e.g. the
    flash-bodied ring attention (tpudl.ops.ring_attention): combined
    output = sum_t o_t * exp(lse_t - logsumexp_t lse_t). Differentiable
    in BOTH outputs (the lse cotangent folds into the backward's delta
    operand — see _bwd_core). Fully-masked query rows report
    lse = MASK_VALUE (an exact zero weight in any merge).

    Under dropout the returned lse is of the UNDROPPED distribution
    (dropout acts after normalization — the kernel's factorization), so
    merge weights are dropout-independent: exactly the distributed
    semantics tpudl.ops.ring_attention's exact-dropout contract needs.
    """
    kvmask, seed, scale, interpret, has_mask = _prep_call(
        q, k, mask, scale, dropout_rate, dropout_rng, interpret
    )
    return _flash_lse(
        q, k, v, kvmask, seed, causal, scale, block_q, block_k, interpret,
        has_mask, float(dropout_rate),
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention on [B, S, H, D] inputs (same contract as
    tpudl.ops.attention.dot_product_attention).

    ``mask`` may be a [B, Skv] kv-validity mask or a broadcastable
    [B, 1, 1, Skv] padding mask (tpudl.ops.attention.padding_mask output);
    dense [B, H, Sq, Skv] masks are rejected — use the reference
    implementation for those.

    ``dropout_rate`` > 0 (with a ``dropout_rng``) applies in-kernel
    attention-probability dropout from the TPU hardware PRNG (see module
    docstring) — the long-context dropout path the einsum implementation
    cannot afford (its mask alone is O(S^2) HBM). TPU-only: raises under
    interpret mode, which has no hardware PRNG.
    """
    kvmask, seed, scale, interpret, has_mask = _prep_call(
        q, k, mask, scale, dropout_rate, dropout_rng, interpret
    )
    return _flash(
        q, k, v, kvmask, seed, causal, scale, block_q, block_k, interpret,
        has_mask, float(dropout_rate),
    )


# ---------------------------------------------------------------------------
# The long prefill's attention: a fresh chunk over itself, forward only.
# A sibling of ``_fwd`` with its own ``pallas_call``: the serving models
# hold a position's heads side by side ([B, S, H x D]: a head is a column
# block, and what the kernel writes is what ``o_proj`` reads), keys and
# values of different widths, a group of query heads a KV head, and a
# choice of keys a query; ``_fwd`` wants [B, H, S, D] (a transpose either
# side of the call), one width and a key-validity row only.
# ---------------------------------------------------------------------------

#: The call's name: its row of a trace's breakdown.
PREFILL_NAME = "prefill_attention"
#: The tile of scores a grid step holds in VMEM, queries x keys. Swept
#: on the chip at 8,192 rows x 64 heads x 256 / 256 (PERF.md, PR 46) and
#: at the grouped-query shapes (PR 48).
PREFILL_BLOCK_Q = 1024
PREFILL_BLOCK_K = 1024
#: Where the running maximum starts: far above ``MASK_VALUE``, so that a
#: masked score's weight is exp(MASK_VALUE - m) = 0 whatever the row has
#: seen (a row with no key allowed sums to 0 and is written as zeros),
#: and far below every real score.
_MAX_FLOOR = -1e30

_prefill_calls = 0
_prefill_attentions = 0


def prefill_kernel_calls() -> int:
    """``prefill_attention`` calls traced so far in this process."""
    return _prefill_calls


def prefill_attentions() -> int:
    """Attentions over a dense row cache (a prefill's layers, whichever
    form each then took) traced so far in this process."""
    return _prefill_attentions


def count_prefill_attention() -> None:
    """An attention layer says that it is being traced over a dense row
    cache (tpudl.models.llama, both kinds of layer)."""
    global _prefill_attentions
    _prefill_attentions += 1


def note_prefill(program, rows: int, before: int, attentions: int) -> None:
    """A prefill contract's note of itself (tpudl.models.generate
    .prefill_fn), written while it is traced at ``rows``: how many of
    its layers' attentions were the kernel's since the count read
    ``before``, as ``program.attention_in_kernel[rows]``, of how many
    attentions it made since that count read ``attentions``, as
    ``program.attention_layers[rows]``."""
    note = program.__dict__.setdefault("attention_in_kernel", {})
    note[rows] = _prefill_calls - before
    note = program.__dict__.setdefault("attention_layers", {})
    note[rows] = _prefill_attentions - attentions


def prefill_kernel_ok(q, k, v, window) -> bool:
    """Whether ``prefill_attention`` can stand in for the XLA blocks of
    ``tpudl.models.llama._blocked_attention``, from what the program
    can observe while it is traced: one TPU device (no mesh to
    partition a kernel over), bfloat16, query heads a whole number of
    groups over the KV heads (one a head in the up-projected latent
    form, 6 to 16 in the grouped-query models), values of whole
    128-value lanes (a head is a column block of [B, S, H x Dv]), keys
    of whole 16-row sublane tiles (the query arrives positions minor,
    and the keys are zero-padded to whole lanes), and no ``window``: a
    band would be another walk, and the one tried, this body over key
    tiles about as wide as the window, did not lead the blocks at a
    window of 128 or 512 (PERF.md §6, PR 48). Whatever the rule is
    asked, the kernel has no backward pass and takes no sink: only a
    serving prefill of a layer without one offers it."""
    from tpudl.ops.attention import is_tpu_backend
    from tpudl.ops.grouped_matmul import one_device

    return (
        is_tpu_backend()
        and one_device()
        and q.dtype == k.dtype == v.dtype == jnp.bfloat16
        and k.shape[2] == v.shape[2]
        and q.shape[2] % k.shape[2] == 0
        and not window
        and k.shape[-1] == q.shape[-1]
        and q.shape[-1] % 16 == 0
        and v.shape[-1] % 128 == 0
    )


def _lower_tiles(rows: int, bq: int, bk: int):
    """The (query tile, key tile) pairs at or below the diagonal, a
    query tile's keys in order: the kernel's walk. Tiles above it are
    neither computed nor fetched."""
    import numpy as np

    pairs = [
        (i, j)
        for i in range(rows // bq)
        for j in range(((i + 1) * bq - 1) // bk + 1)
    ]
    qi, kj = np.asarray(pairs, np.int32).T
    return jnp.asarray(qi), jnp.asarray(kj)


def _prefill_kernel(
    qi_ref, kj_ref, first_ref,  # scalar prefetch: the walk, the padding
    *refs,
    scale: float, block_q: int, block_k: int, key_width: int, has_keep: bool,
):
    """The query heads of one KV head (``group``: the scratch's leading
    size) against ONE (block_k) tile of its keys and values a step, a
    head's (block_q, block_k) tile of scores at a time under a running
    maximum and denominator; everything between the two matrix products
    is float32 and stays in VMEM. A query tile arrives with its
    positions minor (``[group x key_width, block_q]``) and is turned
    once, at the first of its key tiles (zeros up to the keys' padded
    width). What a query may attend is either the operand ``keep`` (an
    indexer's choice, with the causal rule and the validity in it) or
    made here, from the tiles' positions and the validity row, and only
    in a tile that an edge crosses: the diagonal, or left padding
    (``clean``, a fourth prefetched scalar a row: the first position
    from which every slot is real). A key tile wholly in the row's left
    padding (before tile ``first``), and every step of a query tile
    that ends before it, does nothing."""
    if has_keep:
        qt_ref, k_ref, v_ref, keep_ref, o_ref, *scratch = refs
    else:
        clean_ref, qt_ref, k_ref, v_ref, real_ref, o_ref, *scratch = refs
    q_scr, m_scr, l_scr, acc_scr = scratch

    bq, bk, dk = block_q, block_k, key_width
    group, dv = acc_scr.shape[0], acc_scr.shape[-1]
    row, step = pl.program_id(0), pl.program_id(2)
    qi, kj = qi_ref[step], kj_ref[step]
    q0, k0 = qi * bq, kj * bk
    first = first_ref[row]

    # A power of two scales the query exactly, once a tile; any other
    # scale multiplies the float32 scores.
    exact = math.frexp(scale)[0] == 0.5

    @pl.when(kj == 0)
    def _init():
        for i in range(group):
            q = qt_ref[0, i * dk:(i + 1) * dk, :]
            if q_scr.shape[-1] != dk:
                q = jnp.concatenate(
                    [q, jnp.zeros((q_scr.shape[-1] - dk, bq), q.dtype)], 0
                )
            q = q.T
            q_scr[i] = q * jnp.asarray(scale, q.dtype) if exact else q
        m_scr[...] = jnp.full_like(m_scr, _MAX_FLOOR)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def accumulate(allowed):
        """The step's work; ``allowed`` [block_q, block_k] bool, the
        same for every head, or None: every pair counts."""
        def head(i, carry):
            s = jax.lax.dot_general(
                q_scr[i], k_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            if not exact:
                s = s * scale
            if allowed is not None:
                s = jnp.where(allowed, s, MASK_VALUE)
            m_prev = m_scr[i][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_scr[i][:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[i] = acc_scr[i] * corr + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[i] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[i] = jnp.broadcast_to(l_new, l_scr.shape[1:])
            return carry

        if group == 1:  # the latent form: no loop around its one head
            head(0, 0)
        else:
            jax.lax.fori_loop(0, group, head, 0)

    # Something real on both sides: a key tile at or after the row's
    # first real one, queries that reach it.
    some = (kj >= first) & (q0 + bq > first * bk)
    if has_keep:
        @pl.when(some)
        def _chosen():
            accumulate(keep_ref[0] != 0)
    else:
        edge = (k0 + bk - 1 > q0) | (k0 < clean_ref[row])

        @pl.when(some & edge)
        def _edge():
            behind = (
                jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                - jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
                + (q0 - k0)
            )  # how far a key lies behind its query
            accumulate((behind >= 0) & (real_ref[0] != 0))

        @pl.when(some & jnp.logical_not(edge))
        def _inside():
            accumulate(None)

    @pl.when(kj == ((qi + 1) * bq - 1) // bk)
    def _finalize():
        for i in range(group):
            l = l_scr[i][:, :1]
            o_ref[0, :, i * dv:(i + 1) * dv] = (
                acc_scr[i] / jnp.where(l > 0.0, l, 1.0)
            ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("heads", "scale", "block_q", "block_k", "interpret")
)
def _prefill_call(
    qt, k, v, keep, first, real, clean, *, heads, scale, block_q, block_k,
    interpret,
):
    """The kernel's call, jitted on its own so that the layers of a
    prefill program share one traced and lowered function (as the paged
    kernels do). qt: [B, H x Dk, S]; k: [B, S, Hkv x Dkp] (a head's
    keys zero-padded to whole lanes); v: [B, S, Hkv x Dv]; ``keep``:
    int8 [B, S, S], nonzero where a query attends a key, or None, and
    then ``real`` int32 [B, 1, S], nonzero at a real slot, with
    ``clean`` int32 [B], the first slot from which every slot is real;
    ``first``: int32 [B], the key tiles that hold nothing but a row's
    left padding (never computed, and the one fetched in their place is
    the first that counts); S a whole number of both blocks."""
    b, s, _ = v.shape
    dk = qt.shape[1] // heads
    kv_heads = k.shape[-1] // _round_up(dk, 128)
    group = heads // kv_heads
    dkp, dv = k.shape[-1] // kv_heads, v.shape[-1] // kv_heads
    bq, bk = block_q, block_k
    walk = _lower_tiles(s, bq, bk)
    steps = int(walk[0].shape[0])

    # Index maps over (batch, KV head, step of the walk): a KV head's
    # keys, values and its group's queries and results are column
    # blocks; a key tile before the row's first real one is asked for
    # as that one (asked for again, it is not fetched again).
    def query(b, h, t, qi, kj, first, *_):
        return b, h, qi[t]

    def keys(b, h, t, qi, kj, first, *_):
        return b, jnp.maximum(kj[t], first[b]), h

    def mask(b, h, t, qi, kj, first, *_):
        return b, qi[t], jnp.maximum(kj[t], first[b])

    def slots(b, h, t, qi, kj, first, *_):
        return b, 0, jnp.maximum(kj[t], first[b])

    def result(b, h, t, qi, kj, first, *_):
        return b, qi[t], h

    if keep is not None:
        prefetch, allowed = [*walk, first], keep
        allowed_spec = pl.BlockSpec((1, bq, bk), mask)
    else:
        prefetch, allowed = [*walk, first, clean], real
        allowed_spec = pl.BlockSpec((1, 1, bk), slots)

    item = k.dtype.itemsize
    tiles = item * bk * (dkp + dv) + (bq * bk if keep is not None else 4 * bk)
    resident = (
        2 * (tiles + item * group * bq * (dk + dv))   # double buffers
        + group * bq * (item * dkp + 4 * dv + 1024)   # scratch
        + 4 * 4 * bq * bk                    # scores and weights, float32
    )
    with kernel_trace(PREFILL_NAME):
        return pl.pallas_call(
            functools.partial(
                _prefill_kernel, scale=scale, block_q=bq, block_k=bk,
                key_width=dk, has_keep=keep is not None,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(b, kv_heads, steps),
                in_specs=[
                    pl.BlockSpec((1, group * dk, bq), query),
                    pl.BlockSpec((1, bk, dkp), keys),
                    pl.BlockSpec((1, bk, dv), keys),
                    allowed_spec,
                ],
                out_specs=pl.BlockSpec((1, bq, group * dv), result),
                scratch_shapes=[
                    pltpu.VMEM((group, bq, dkp), qt.dtype),
                    pltpu.VMEM((group, bq, 128), jnp.float32),
                    pltpu.VMEM((group, bq, 128), jnp.float32),
                    pltpu.VMEM((group, bq, dv), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, s, heads * dv), qt.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
                vmem_limit_bytes=resident + (16 << 20),
            ),
            cost_estimate=pl.CostEstimate(
                flops=2 * b * heads * steps * bq * bk * (dkp + dv),
                transcendentals=b * heads * steps * bq * bk,
                bytes_accessed=(
                    item * (qt.size + b * s * heads * dv)
                    + b * kv_heads * steps * tiles
                ),
            ),
            interpret=interpret,
            name=PREFILL_NAME,
        )(*prefetch, qt, k, v, allowed)


def prefill_attention(
    q, k, v, valid, scale=None, chosen=None,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Causal attention of a chunk over itself as ONE kernel call: no
    [H, S, S] and no [H, block, S] tensor exists outside VMEM. q: [B,
    S, H, Dk]; k: [B, S, Hkv, Dk]; v: [B, S, Hkv, Dv] (H a whole number
    of groups over Hkv, a group's heads a grid step against each tile
    of their KV head; the widths may differ: Dv a whole number of
    128-value lanes, Dk of 16-row tiles); valid: [B, S] bool, the real
    slots (a left-padded row's first keys are not); ``scale``: the
    softmax's, Dk ** -0.5 unless given; ``chosen`` [B, S, S] bool: an
    indexer's choice of keys a query. -> [B, S, H, Dv]. Scores are
    float32 from the operands' own dtype, the weights are rounded to
    the values' dtype for the second product; a query with no key
    allowed gets zeros. Tiles above the diagonal or of nothing but left
    padding (keys or queries) are neither computed nor fetched; a
    choice is a mask operand, no tile is skipped for it; without one no
    [B, S, S] mask exists: the kernel makes it, in the tiles that need
    one."""
    global _prefill_calls

    b, s, h, dk = q.shape
    dv = v.shape[-1]
    if interpret is None:
        interpret = _interpret_default()
    bq = block_q or _fit_block(s, PREFILL_BLOCK_Q)
    bk = block_k or _fit_block(s, PREFILL_BLOCK_K)
    rows = _round_up(s, max(bq, bk))
    keep = real = clean = None
    if chosen is not None:
        slot = jnp.arange(s)
        keep = (slot[None, :] <= slot[:, None])[None] & valid[:, None, :]
        keep = (keep & chosen).astype(jnp.int8)
    else:
        # No [B, S, S] mask: the kernel makes it from positions, the
        # validity row and the first slot from which every slot is real
        # (tiles from there on need no look at the row).
        real = valid.astype(jnp.int32)[:, None, :]
        clean = jnp.where(valid, 0, jnp.arange(1, s + 1, dtype=jnp.int32))
        clean = clean.max(axis=1)
    # The query with its POSITIONS minor: how XLA writes a serving
    # model's query on the chip (its roped halves are narrower than a
    # lane), so this transpose is none there, where [B, S, H x Dk] asked
    # for a transposing copy of the whole query a layer (PERF.md, PR 46).
    # The kernel turns a tile once. Keys and values come from matmuls
    # the caller makes ([B, S, H x D], ``llama._kernel_operands``); a
    # key of part lanes is zero-padded to whole ones (the kernel pads
    # its query tile the same way).
    qt = q.transpose(0, 2, 3, 1).reshape(b, h * dk, s)
    if dk % 128:
        k = jnp.pad(k, ((0, 0),) * 3 + ((0, _round_up(dk, 128) - dk),))
    k, v = k.reshape(b, s, -1), v.reshape(b, s, -1)
    if rows != s:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, rows - s)))
        k, v = (jnp.pad(x, ((0, 0), (0, rows - s), (0, 0))) for x in (k, v))
        if keep is not None:
            keep = jnp.pad(keep, ((0, 0), (0, rows - s), (0, rows - s)))
        else:
            real = jnp.pad(real, ((0, 0), (0, 0), (0, rows - s)))
    _prefill_calls += 1
    # Key tiles before a row's first real slot hold nothing to attend.
    first = (jnp.argmax(valid, axis=1) // bk).astype(jnp.int32)
    out = _prefill_call(
        qt, k, v, keep, first, real, clean, heads=h,
        scale=float(scale or dk ** -0.5), block_q=bq, block_k=bk,
        interpret=bool(interpret),
    )
    return out[:, :s].reshape(b, s, h, dv)
