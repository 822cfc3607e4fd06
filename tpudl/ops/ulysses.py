"""Ulysses-style sequence parallelism: all-to-all head/sequence resharding.

The second of the two canonical long-context strategies (the brief's
"ring attention OR all-to-all sequence parallelism"; the reference tree
has neither — its NLP family is an empty placeholder, reference
notebooks/nlp/README.md, SURVEY.md §5.7). Complements
tpudl.ops.ring_attention:

- **ring**: K/V shards rotate around the `sp` ring (n-1 ppermute hops
  overlapped with blockwise compute); attention math is reimplemented as
  an online-softmax merge. Communication scales with S but overlaps.
- **ulysses** (this module): two `all_to_all` collectives reshard
  activations from sequence-sharded [B, S/n, H, D] to head-sharded
  [B, S, H/n, D]; in between, every device runs full-sequence attention
  on its head slice. With ``local_impl="reference"`` the numerics are
  exactly the reference implementation's by construction; the default on
  TPU is ``local_impl="flash"`` (the Pallas kernel — flash-tolerance
  numerics, but peak memory linear in S instead of the [B, H/n, S, S]
  score tensor). The all-to-all rides ICI's all-to-all bandwidth;
  requires heads % sp == 0.

Which to use: ulysses while heads ≥ sp (cheap, exact, simple); ring when
sequence length pushes past what a full-S slice of heads can hold or
sp exceeds the head count.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tpudl.runtime.mesh import AXIS_SEQ, BATCH_AXES, AXIS_TENSOR


def _ulysses_local(q, k, v, kvm=None, key_data=None, *, axis_name, causal,
                   scale, local_impl, dropout_rate=0.0, key_impl=None,
                   fold_axes=()):
    """Per-device body. q/k/v: [B, S/n, H_local, D] (H_local = H/tp·... the
    heads remaining on this device's tp slice); kvm: [B, S] full-sequence
    kv-validity row (replicated over sp), or None when the caller passed
    no mask — kept None so flash takes its maskless codegen path (no
    per-tile kv-row traffic on the unmasked long-context hot path).

    Dropout: after the all-to-all each device holds FULL sequences for
    its head slice, so attention-probability dropout is exact BERT/Llama
    semantics applied locally (in-kernel hardware PRNG under flash;
    jax.random masks under reference). Ring achieves the same semantics
    differently — numerator-only masking inside its distributed-softmax
    merge (tpudl.ops.ring_attention)."""
    from tpudl.ops.attention import dot_product_attention

    n = jax.lax.psum(1, axis_name)

    # [B, S/n, H, D] -> [B, S, H/n, D]: split heads over the ring, gather
    # the sequence. One ICI all-to-all each way.
    def seq_to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    def heads_to_seq(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    if n > 1:
        q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)

    rng = None
    if dropout_rate > 0.0:
        from tpudl.ops.dropout import device_fold_rng

        rng = device_fold_rng(key_data, key_impl, fold_axes)

    if local_impl == "flash":
        # Pallas flash kernel on the head slice: peak memory stays linear
        # in S instead of materializing the [B, H/n, S, S] score tensor —
        # the whole point of the long-context path ulysses serves.
        from tpudl.ops.flash_attention import flash_attention

        out = flash_attention(
            q, k, v, mask=kvm, causal=causal, scale=scale,
            dropout_rate=dropout_rate, dropout_rng=rng,
        )
    else:
        from tpudl.ops.attention import combine_kv_causal_mask

        out = dot_product_attention(
            q, k, v,
            mask=combine_kv_causal_mask(
                None if kvm is None else kvm > 0,
                q.shape[1], k.shape[1], causal,
            ),
            scale=scale,
            dropout_rate=dropout_rate,
            dropout_rng=rng,
        )
    if n > 1:
        out = heads_to_seq(out)
    return out


def ulysses_attention(
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
    """Sequence-parallel attention on [B, S, H, D] via all-to-all
    (tpudl.ops.attention contract; Sq == Skv — one shared sequence axis).

    ``mask`` may be a [B, S] kv-validity row or a [B, 1, 1, S] padding
    mask (dense masks are rejected, as in ring/flash). ``mesh`` defaults
    to the active tpudl mesh; batch shards over (dp, fsdp), sequence over
    `sp`, heads over `tp` — requires (H / tp) % sp == 0.

    ``local_impl`` picks the per-device attention body: "flash" (Pallas
    kernel — memory linear in S, the long-context default on TPU) or
    "reference" (einsum — exact tpudl.ops.attention numerics, the default
    on CPU where the kernel would run interpreted). None = by backend.

    ``dropout_rate`` > 0 (round 4): attention-probability dropout with
    exact semantics — after the all-to-all every head attends its full
    sequence locally, so this is plain per-head dropout; each mesh slot
    folds its position into ``dropout_rng`` for independent masks. The
    flash body draws in-kernel (TPU hardware PRNG); the reference body
    uses the low-width-bits jax.random path, which also runs on CPU.
    """
    from tpudl.ops.attention import normalize_kv_mask, unmeshed_attention
    from tpudl.parallel.sharding import current_mesh

    # Resolve + validate the local body BEFORE the unmeshed early-return,
    # so an invalid value always errors and an explicitly pinned "flash"
    # (chosen for its O(S) memory) is honored even without a mesh.
    if local_impl is None:
        from tpudl.ops.attention import is_tpu_backend

        # Flash only where the Pallas TPU kernel lowers; cpu/gpu take the
        # exact einsum body.
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
        if local_impl == "flash":
            from tpudl.ops.flash_attention import flash_attention

            return flash_attention(
                q, k, v, mask=mask, causal=causal, scale=scale,
                dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            )
        return unmeshed_attention(
            q, k, v, mask, causal, scale,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        )

    b, s, h, d = q.shape
    if k.shape[1] != s:
        raise ValueError(
            f"ulysses attention shards q and kv along one sequence axis; "
            f"got Sq={s}, Skv={k.shape[1]}"
        )
    n_sp = mesh.shape[axis_name]
    n_tp = mesh.shape[AXIS_TENSOR]
    if s % n_sp != 0:
        raise ValueError(f"seq len {s} not divisible by {axis_name}={n_sp}")
    local_heads = h // n_tp if h % n_tp == 0 else h
    if local_heads % n_sp != 0:
        raise ValueError(
            f"{local_heads} local heads not divisible by {axis_name}={n_sp} "
            f"(ulysses shards heads over sp; use implementation='ring' when "
            f"sp exceeds the per-device head count)"
        )
    if scale is None:
        scale = d ** -0.5

    batch = tuple(a for a in BATCH_AXES if mesh.shape[a] > 1) or None
    heads_sharded = h % max(n_tp, 1) == 0 and n_tp > 1
    heads = AXIS_TENSOR if heads_sharded else None
    qkv_spec = P(batch, axis_name, heads, None)
    key_impl = (
        jax.random.key_impl(dropout_rng) if dropout_rate > 0.0 else None
    )
    from tpudl.ops.dropout import shard_fold_axes

    fold_axes = shard_fold_axes(mesh, axis_name, heads_sharded, BATCH_AXES)
    body = partial(_ulysses_local, axis_name=axis_name, causal=causal,
                   scale=scale, local_impl=local_impl,
                   dropout_rate=dropout_rate, key_impl=key_impl,
                   fold_axes=fold_axes)

    operands = [q, k, v]
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    if mask is not None:
        operands.append(normalize_kv_mask(mask, b, s, impl="ulysses_attention"))
        in_specs.append(P(batch, None))
    if dropout_rate > 0.0:
        # Key data rides as a replicated raw-uint32 operand (key ARRAYS
        # don't thread shard_map specs); each device re-wraps and folds
        # its mesh position in (_device_dropout_rng).
        operands.append(jax.random.key_data(dropout_rng))
        in_specs.append(P(*([None] * jax.random.key_data(dropout_rng).ndim)))
        if mask is None:
            # kvm is positional before key_data in the body signature —
            # wrap the ONE bound partial rather than rebuilding it.
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
