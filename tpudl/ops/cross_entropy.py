"""Fused softmax-cross-entropy over integer labels (online logsumexp).

``optax.softmax_cross_entropy_with_integer_labels`` materializes the
[B, V] log-probability tensor in HBM (and its VJP materializes the
[B, V] softmax); at Llama vocab (128k) that is the dominant memory
stream of the loss step, and even BERT's 30k vocab pays a full extra
round-trip over the logits. This kernel streams the vocab axis through
VMEM exactly once — the online-logsumexp recurrence of the flash-
attention lineage applied to the loss — keeping only per-row statistics
(running max, running sum-exp, the label's logit, and under label
smoothing the row logit-sum):

    loss_b = lse_b - (1 - s) * z_b[t_b] - (s / V) * sum_j z_b[j]

The [B, V] probability tensor is NEVER materialized: the forward saves
only ``lse`` [B], and the backward writes the gradient tile-by-tile as
``g_b * (softmax(z)_bj - q_bj)`` with each exp tile living only in VMEM
(q = the (1-s)-smoothed one-hot). Vocab-padding columns (V not a
lane-tile multiple) are masked out of the logsumexp, the label gather,
and the smoothing sum.

Dispatch: ``impl`` = "auto" | "fused" | "reference" with the
tpudl.ops.norms contract; the reference composite is exactly the optax
path tpudl.train.loop always used, so ``impl="reference"`` (the
default at the loss sites) is behavior-identical to the pre-kernel
code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.attention import MASK_VALUE
from tpudl.ops.norms import resolve_impl
from tpudl.ops.pallas_utils import round_up


#: Override for the vocab-block cap below (None = the 1024 default):
#: the tests' seam for holding the kernel grid against the reference.
#: The divisibility walk still applies, so any override stays legal.
VOCAB_BLOCK_OVERRIDE: Optional[int] = None


def _fit_vocab_block(v_pad: int, limit: int = 1024) -> int:
    override = VOCAB_BLOCK_OVERRIDE
    if override is not None:
        if override < 128:
            raise ValueError(
                f"vocab-block override must be >= 128, got {override}"
            )
        limit = override
    b = min(limit, v_pad)
    while b > 128 and v_pad % b != 0:
        b //= 2
    return max(b, 128)


def _setup(logits, labels):
    b, v = logits.shape
    bb = min(256, round_up(b, 8))
    b_pad = round_up(b, bb)
    v_pad = round_up(v, 128)
    bv = _fit_vocab_block(v_pad)
    if (b_pad, v_pad) != (b, v):
        logits = jnp.pad(logits, ((0, b_pad - b), (0, v_pad - v)))
    lab = labels.astype(jnp.int32)[:, None]
    if b_pad != b:
        lab = jnp.pad(lab, ((0, b_pad - b), (0, 0)))
    return logits, lab, bb, bv, b_pad, v_pad


def _row_stat(a, b_pad):
    """[B] f32 -> [B_pad, 128] broadcast (rows on sublanes)."""
    a = a.astype(jnp.float32)[:, None]
    if b_pad != a.shape[0]:
        a = jnp.pad(a, ((0, b_pad - a.shape[0]), (0, 0)))
    return jnp.broadcast_to(a, (b_pad, 128))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _xent_fwd_kernel(z_ref, lab_ref, loss_ref, lse_ref,
                     m_scr, l_scr, t_scr, s_scr,
                     *, v, bv, smoothing, has_pad):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr, MASK_VALUE)
        l_scr[:, :] = jnp.zeros_like(l_scr)
        t_scr[:, :] = jnp.zeros_like(t_scr)
        if smoothing > 0.0:
            s_scr[:, :] = jnp.zeros_like(s_scr)

    z = z_ref[:, :].astype(jnp.float32)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    if has_pad:
        valid = col < v
        zm = jnp.where(valid, z, MASK_VALUE)
    else:
        valid = None
        zm = z

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(zm, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, :1] * corr + jnp.sum(
        jnp.exp(zm - m_new), axis=-1, keepdims=True
    )
    m_scr[:, :] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:, :] = jnp.broadcast_to(l_new, l_scr.shape)

    hit = col == lab_ref[:, :1]
    t_scr[:, :1] += jnp.sum(
        jnp.where(hit, z, 0.0), axis=-1, keepdims=True
    )
    if smoothing > 0.0:
        zs = jnp.where(valid, z, 0.0) if has_pad else z
        s_scr[:, :1] += jnp.sum(zs, axis=-1, keepdims=True)

    @pl.when(j == nv - 1)
    def _finalize():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])
        loss = lse - (1.0 - smoothing) * t_scr[:, :1]
        if smoothing > 0.0:
            loss = loss - (smoothing / v) * s_scr[:, :1]
        loss_ref[:, :] = jnp.broadcast_to(loss, loss_ref.shape)
        lse_ref[:, :] = jnp.broadcast_to(lse, lse_ref.shape)


def _xent_bwd_kernel(z_ref, lab_ref, lse_ref, g_ref, dz_ref,
                     *, v, bv, smoothing, has_pad):
    j = pl.program_id(1)
    z = z_ref[:, :].astype(jnp.float32)
    p = jnp.exp(z - lse_ref[:, :1])
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    q = jnp.where(col == lab_ref[:, :1], 1.0 - smoothing, 0.0)
    if smoothing > 0.0:
        q = q + smoothing / v
    dz = g_ref[:, :1] * (p - q)
    if has_pad:
        dz = jnp.where(col < v, dz, 0.0)
    dz_ref[:, :] = dz.astype(dz_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing + custom_vjp
# ---------------------------------------------------------------------------


def _xent_fwd_call(logits, labels, smoothing, interpret):
    b, v = logits.shape
    zp, lab, bb, bv, b_pad, v_pad = _setup(logits, labels)
    grid = (b_pad // bb, v_pad // bv)
    stat = pl.BlockSpec((bb, 128), lambda i, j: (i, 0),
                        memory_space=pltpu.VMEM)
    loss, lse = pl.pallas_call(
        functools.partial(
            _xent_fwd_kernel, v=v, bv=bv, smoothing=smoothing,
            has_pad=v_pad != v,
        ),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        in_specs=[
            pl.BlockSpec((bb, bv), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[stat, stat],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, 128), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, 128), jnp.float32),
            pltpu.VMEM((bb, 128), jnp.float32),
            pltpu.VMEM((bb, 128), jnp.float32),
            pltpu.VMEM((bb, 128), jnp.float32),
        ],
        interpret=interpret,
    )(zp, lab)
    return loss[:b, 0], lse[:b, 0]


def _xent_bwd_call(logits, labels, lse, g, smoothing, interpret):
    b, v = logits.shape
    zp, lab, bb, bv, b_pad, v_pad = _setup(logits, labels)
    stat = pl.BlockSpec((bb, 128), lambda i, j: (i, 0),
                        memory_space=pltpu.VMEM)
    dz = pl.pallas_call(
        functools.partial(
            _xent_bwd_kernel, v=v, bv=bv, smoothing=smoothing,
            has_pad=v_pad != v,
        ),
        grid=(b_pad // bb, v_pad // bv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        in_specs=[
            pl.BlockSpec((bb, bv), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            stat,
            stat,
        ],
        out_specs=pl.BlockSpec((bb, bv), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b_pad, v_pad), logits.dtype),
        interpret=interpret,
    )(zp, lab, _row_stat(lse, b_pad), _row_stat(g, b_pad))
    if (b_pad, v_pad) != (b, v):
        dz = dz[:b, :v]
    return dz


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent(logits, labels, smoothing, interpret):
    loss, _ = _xent_fwd_call(logits, labels, smoothing, interpret)
    return loss


def _xent_vjp_fwd(logits, labels, smoothing, interpret):
    loss, lse = _xent_fwd_call(logits, labels, smoothing, interpret)
    return loss, (logits, labels, lse)


def _xent_vjp_bwd(smoothing, interpret, res, g):
    logits, labels, lse = res
    dz = _xent_bwd_call(logits, labels, lse, g, smoothing, interpret)
    return dz, np.zeros(labels.shape, dtype=jax.dtypes.float0)


_xent.defvjp(_xent_vjp_fwd, _xent_vjp_bwd)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------


def softmax_cross_entropy_ref(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """The optax composite tpudl.train.loop always used (per-example,
    [B] f32) — the behavior baseline every fused parity test compares
    against."""
    import optax

    if label_smoothing > 0.0:
        onehot = optax.smooth_labels(
            jax.nn.one_hot(labels, logits.shape[-1]), label_smoothing
        )
        return optax.softmax_cross_entropy(logits, onehot)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)


def softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Per-example softmax cross-entropy over integer labels
    (``logits`` [..., V], ``labels`` [...] int; returns [...] f32 —
    leading dims are rank-generic like the optax composite, so the
    LM-shaped [B, S, V] call works on both paths).

    ``impl="fused"`` streams the vocab axis (online logsumexp) so the
    [B, V] softmax is never materialized in HBM — forward keeps per-row
    statistics only, backward writes the gradient tile-by-tile. See the
    module docstring for the dispatch contract."""
    if logits.ndim < 2 or labels.shape != logits.shape[:-1]:
        raise ValueError(
            f"expected logits [..., V] and labels [...], got "
            f"{logits.shape} and {labels.shape}"
        )
    fused, interpret = resolve_impl(impl, interpret)
    if not fused:
        # The composite broadcasts leading dims natively — no reshape,
        # bit-identical to the pre-seam optax call.
        return softmax_cross_entropy_ref(logits, labels, label_smoothing)
    lead = labels.shape
    if logits.ndim > 2:
        logits = logits.reshape(-1, logits.shape[-1])
        labels = labels.reshape(-1)
    out = _xent(logits, labels, float(label_smoothing), interpret)
    return out.reshape(lead)
