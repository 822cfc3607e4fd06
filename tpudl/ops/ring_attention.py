"""Ring attention: sequence-parallel attention over the `sp` mesh axis.

Long-context support the reference lineage never had (its NLP family is an
empty placeholder — reference notebooks/nlp/README.md; SURVEY.md §5.7
records sequence parallelism as the declared TPU-idiomatic path). Design:
activations arrive sharded [B, S/n, H, D] along `sp`; each device computes
blockwise attention against the K/V shard it currently holds while
`ppermute` rotates K/V (and the kv-validity mask) one hop around the ring.
After n steps every query shard has seen every K/V shard, the partial
softmax statistics having been merged online — the full [S, S] logits
matrix never exists, per-device attention memory is O(S^2 / n^2), and the
K/V transfers ride neighbor-to-neighbor ICI hops that overlap with the
per-block compute.

The loop is a `lax.scan` (reverse-differentiable, unlike while/fori), so
the same code trains: gradients flow through `ppermute`'s transpose
(another ppermute in the reverse direction, also riding ICI).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpudl.ops.attention import MASK_VALUE
from tpudl.runtime.mesh import AXIS_SEQ, BATCH_AXES, AXIS_TENSOR


def _ring_local(q, k, v, kvm, key_data=None, *, axis_name, scale, causal,
                dropout_rate=0.0, key_impl=None, fold_axes=()):
    """Per-device ring loop. q, k, v: [b, s_local, h, d]; kvm: [b, s_local].

    Device i starts holding kv block i; after t rotations it holds block
    (i - t) mod n. The online-softmax merge is the same recurrence as the
    flash kernel's (tpudl.ops.flash_attention), at shard granularity.

    Dropout (round 4) uses the flash kernel's factorization: dropout acts
    AFTER softmax normalization, so the denominator ``l`` accumulates
    undropped probabilities while only the p@V numerator is masked, with
    the 1/(1-rate) rescale applied once at the end. Masks are a pure
    function of (key, q-shard position, kv rotation index), drawn with
    the low-width-bits generator per tile — autodiff through the scan
    replays the identical draw, so forward and backward masks agree by
    construction (no custom-vjp contract needed at this granularity).
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_l, h, d = q.shape

    dropout_on = dropout_rate > 0.0
    if dropout_on:
        from tpudl.ops.dropout import (
            device_fold_rng,
            dropout_keep_mask,
            quantized_rate,
        )

        rng = device_fold_rng(key_data, key_impl, fold_axes)
        eff_rate = quantized_rate(dropout_rate, exact=False)
        inv_keep = 1.0 / (1.0 - eff_rate)

    q32 = q.astype(jnp.float32)
    m0 = jnp.full((b, h, s_l, 1), MASK_VALUE, jnp.float32)
    l0 = jnp.zeros((b, h, s_l, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_l, d), jnp.float32)
    q_ids = idx * s_l + jax.lax.broadcasted_iota(jnp.int32, (s_l, 1), 0)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(carry, t):
        m, l, acc, k, v, kvm = carry
        src = (idx - t) % n  # global block index of the kv shard we hold
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, k.astype(jnp.float32)) * scale
        keep = (kvm > 0)[:, None, None, :]
        if causal:
            kv_ids = src * s_l + jax.lax.broadcasted_iota(
                jnp.int32, (1, s_l), 1
            )
            keep = jnp.logical_and(keep, (kv_ids <= q_ids)[None, None, :, :])
        s = jnp.where(keep, s, MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        # Denominator: UNDROPPED p (dropout acts post-normalization).
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_on:
            # Tile keyed by the GLOBAL kv block this device holds at
            # tick t — every (q-shard, kv-block) pair draws its own mask.
            keep_d = dropout_keep_mask(
                jax.random.fold_in(rng, src), p.shape, dropout_rate,
                exact=False,
            )
            p_num = jnp.where(keep_d, p, 0.0)
        else:
            p_num = p
        acc = acc * corr + jnp.einsum(
            "bhqk,bkhd->bhqd", p_num.astype(v.dtype), v
        ).astype(jnp.float32)
        k, v, kvm = (
            jax.lax.ppermute(x, axis_name, perm) for x in (k, v, kvm)
        )
        return (m_new, l, acc, k, v, kvm), None

    (m, l, acc, _, _, _), _ = jax.lax.scan(
        body, (m0, l0, acc0, k, v, kvm), jnp.arange(n)
    )
    l_safe = jnp.where(l > 0.0, l, 1.0)
    o = acc / l_safe
    if dropout_on:
        o = o * inv_keep
    o = o.transpose(0, 2, 1, 3)  # [b, s_l, h, d]
    return o.astype(q.dtype)


def _ring_local_flash(q, k, v, kvm=None, key_data=None, *, axis_name, scale,
                      causal, dropout_rate=0.0, key_impl=None, fold_axes=()):
    """Flash-bodied ring loop (round-5; r4 VERDICT weak #5): each tick
    runs the Pallas flash kernel on the held kv block and merges the
    per-block (o, lse) pairs — per-device attention memory stays
    O(s_local) instead of the einsum body's [b, h, s_local, s_local]
    f32 logits block, which is the whole point of ring on the longest
    sequences.

    Causality without a traced kernel offset: the diagonal tick (the
    device's own block, t=0) runs the CAUSAL kernel; every later block
    is either wholly prior (src < idx: unmasked) or wholly future
    (src > idx: its per-tick lse is overwritten with MASK_VALUE, an
    EXACTLY-zero merge weight, so the block contributes nothing and
    needs no gradient). Masking via the merge weight rather than a
    zeroed kv row keeps ``kvm=None`` (the unpadded long-context hot
    path) on the kernel's maskless fast codegen for every tick.

    Dropout keeps the einsum body's exact factorization: per-tick lse
    is of the UNDROPPED distribution (flash_attention_with_lse), so
    merge weights are dropout-independent and only the p@V numerators
    are masked, per (q-shard, kv-block) via fold_in(rng, src) — the
    same tile-keying convention as the einsum body, drawn by the
    in-kernel hardware PRNG instead of jax.random bits."""
    from tpudl.ops.flash_attention import flash_attention_with_lse

    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)

    rng = None
    if dropout_rate > 0.0:
        from tpudl.ops.dropout import device_fold_rng

        rng = device_fold_rng(key_data, key_impl, fold_axes)

    def call(k_, v_, kvm_, causal_flag, src):
        tick_rng = None if rng is None else jax.random.fold_in(rng, src)
        o, lse = flash_attention_with_lse(
            q, k_, v_, mask=kvm_, causal=causal_flag, scale=scale,
            dropout_rate=dropout_rate, dropout_rng=tick_rng,
        )
        return o.astype(jnp.float32), lse

    # Tick 0: the diagonal block (the kv shard this device starts with).
    o_acc, lse_acc = call(k, v, kvm, causal, idx)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def rotate(*xs):
        return tuple(
            None if x is None else jax.lax.ppermute(x, axis_name, perm)
            for x in xs
        )

    k, v, kvm = rotate(k, v, kvm)

    def body(carry, t):
        o_acc, lse_acc, k, v, kvm = carry
        src = (idx - t) % n  # global block index of the kv shard we hold
        o_t, lse_t = call(k, v, kvm, False, src)
        if causal:
            # Wholly-future block: exact zero weight in the merge.
            lse_t = jnp.where(src > idx, MASK_VALUE, lse_t)
        new_lse = jnp.logaddexp(lse_acc, lse_t)
        w_acc = jnp.exp(lse_acc - new_lse).transpose(0, 2, 1)[..., None]
        w_t = jnp.exp(lse_t - new_lse).transpose(0, 2, 1)[..., None]
        o_acc = o_acc * w_acc + o_t * w_t
        k, v, kvm = rotate(k, v, kvm)
        return (o_acc, new_lse, k, v, kvm), None

    if kvm is None:
        def body_nokvm(carry, t):
            o_acc, lse_acc, k, v = carry
            (o_acc, new_lse, k, v, _), _ = body(
                (o_acc, lse_acc, k, v, None), t
            )
            return (o_acc, new_lse, k, v), None

        (o_acc, _, _, _), _ = jax.lax.scan(
            body_nokvm, (o_acc, lse_acc, k, v), jnp.arange(1, n)
        )
    else:
        (o_acc, _, _, _, _), _ = jax.lax.scan(
            body, (o_acc, lse_acc, k, v, kvm), jnp.arange(1, n)
        )
    return o_acc.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    mesh: Optional[Mesh] = None,
    axis_name: str = AXIS_SEQ,
    local_impl: Optional[str] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Sequence-parallel attention on [B, S, H, D] (the
    tpudl.ops.attention contract; Sq == Skv required — queries and keys
    shard along the same sequence axis).

    ``mask`` may be a [B, S] kv-validity mask or a [B, 1, 1, S] padding
    mask; dense masks are rejected like tpudl.ops.flash_attention.
    ``mesh`` defaults to the active tpudl mesh
    (tpudl.parallel.sharding.active_mesh); batch shards over (dp, fsdp),
    sequence over `sp`, heads over `tp`.

    ``local_impl`` picks the per-tick body (round 5, mirroring ulysses):
    "flash" (the Pallas kernel per kv block + an (o, lse) merge —
    per-device attention memory O(s_local), the long-context default on
    TPU) or "reference" (the einsum online-softmax body — exact
    tpudl.ops.attention numerics, materializes one [b, h, s_local,
    s_local] f32 block per tick; the default on CPU where the kernel
    would run interpreted). None = by backend.

    ``dropout_rate`` > 0 (round 4): attention-probability dropout with
    exact post-softmax semantics despite the distributed softmax — the
    online merge keeps the denominator undropped while the numerator is
    masked per (q-shard, kv-block) tile (see _ring_local /
    _ring_local_flash). Each mesh slot folds its position into
    ``dropout_rng``; mask BITS therefore depend on the mesh layout and
    the body implementation, like every sharded dropout path. The
    EFFECTIVE rate also differs slightly per body: the reference body
    quantizes to 1/256 (the low-width-bits generator, e.g. 0.1 ->
    25/256 = 0.0977) while the flash body applies the requested rate
    in-kernel — CPU-vs-TPU training trajectories differ by that 2%
    relative drop-probability, not by a bug.
    """
    from tpudl.ops.attention import normalize_kv_mask, unmeshed_attention
    from tpudl.parallel.sharding import current_mesh

    if local_impl is None:
        from tpudl.ops.attention import is_tpu_backend

        local_impl = "flash" if is_tpu_backend() else "reference"
    if local_impl not in ("flash", "reference"):
        raise ValueError(
            f"local_impl must be 'flash' or 'reference', got {local_impl!r}"
        )

    if dropout_rate > 0.0 and dropout_rng is None:
        raise ValueError("dropout_rate > 0 requires a dropout_rng")

    if mesh is None:
        mesh = current_mesh()
    if mesh is None:
        # No mesh (single-device init/eval): ring degenerates to reference
        # attention — numerically identical, so models with
        # attention_impl="ring" init and evaluate unmeshed.
        return unmeshed_attention(
            q, k, v, mask, causal, scale,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        )
    b, s, h, d = q.shape
    if k.shape[1] != s:
        raise ValueError(
            f"ring attention shards q and kv along one sequence axis; "
            f"got Sq={s}, Skv={k.shape[1]}"
        )
    n_sp = mesh.shape[axis_name]
    if s % n_sp != 0:
        raise ValueError(f"seq len {s} not divisible by {axis_name}={n_sp}")
    if scale is None:
        scale = d ** -0.5

    kvm = normalize_kv_mask(mask, b, s, impl="ring_attention")

    batch = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1) or None
    n_tp = mesh.shape[AXIS_TENSOR]
    heads_sharded = h % max(n_tp, 1) == 0 and n_tp > 1
    heads = AXIS_TENSOR if heads_sharded else None
    qkv_spec = P(batch, axis_name, heads, None)
    key_impl = (
        jax.random.key_impl(dropout_rng) if dropout_rate > 0.0 else None
    )
    from tpudl.ops.dropout import shard_fold_axes

    fold_axes = shard_fold_axes(mesh, axis_name, heads_sharded, BATCH_AXES)
    local_body = _ring_local_flash if local_impl == "flash" else _ring_local
    body = partial(
        local_body, axis_name=axis_name, scale=scale, causal=causal,
        dropout_rate=dropout_rate, key_impl=key_impl, fold_axes=fold_axes,
    )
    # The flash body takes no kv-mask operand when the caller passed no
    # mask, keeping every tick on the kernel's maskless fast codegen
    # (causal future-block zeroing happens via the merge weight, not the
    # mask channel). The einsum body always takes the row (its masking
    # is a where() it pays either way).
    skip_kvm = local_impl == "flash" and mask is None
    operands = [q, k, v]
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if not skip_kvm:
        operands.append(kvm)
        in_specs.append(P(batch, axis_name))
    if dropout_rate > 0.0:
        operands.append(jax.random.key_data(dropout_rng))
        in_specs.append(
            P(*([None] * jax.random.key_data(dropout_rng).ndim))
        )
        if skip_kvm:
            # key_data is positional after kvm in the body signature.
            inner = body
            body = lambda q_, k_, v_, kd_: inner(q_, k_, v_, None, kd_)  # noqa: E731
    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        check_vma=False,
    )
    return fn(*operands)
