"""Attention ops: the single seam all tpudl transformer models go through.

`dot_product_attention(q, k, v, ...)` is the reference implementation
(einsum + f32 softmax). `attend()` dispatches by implementation name so
models can switch to the Pallas flash kernel (tpudl.ops.flash_attention) or
the ring/sequence-parallel path (tpudl.ops.ring_attention) without touching
model code. The reference repo has no attention anywhere (its NLP family is
an empty placeholder — reference notebooks/nlp/README.md, SURVEY.md §5.7);
this design makes long-context support first-class instead.

Shapes follow the TPU-friendly convention:
  q, k, v: [batch, seq, heads, head_dim]   (BSHD)
  mask:    broadcastable to [batch, heads, q_seq, kv_seq], True = attend
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: Large negative fill for masked logits, safe in bf16.
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def is_tpu_backend() -> bool:
    """Whether the default JAX backend is a TPU — the one place the
    question is asked. Gates Pallas-kernel defaults: on a TPU a kernel
    compiles or the call fails (never interpreted, never swapped for
    the reference); the interpreter is the CPU test mode."""
    return jax.default_backend() == "tpu"


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    dropout_exact: bool = False,
) -> jax.Array:
    """Reference attention: bf16 matmuls on the MXU, softmax in f32.

    q: [B, Sq, H, D]; k, v: [B, Skv, H, D]; returns [B, Sq, H, D].
    ``dropout_rate`` drops attention probabilities (BERT-style) when a
    ``dropout_rng`` is supplied — via low-width hardware bits by default
    (rate quantized to 1/256, tpudl.ops.dropout); ``dropout_exact=True``
    restores bit-exact jax.random.bernoulli masks (4x the bit traffic).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = jnp.where(mask, logits, MASK_VALUE)
    weights = jax.nn.softmax(logits, axis=-1)
    weights = weights.astype(v.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        from tpudl.ops.dropout import apply_keep_mask, dropout_keep_mask

        # Low-width-bits mask (tpudl.ops.dropout): 4x less random-bit
        # traffic than bernoulli — 14.5 ms/step on the headline BERT
        # fine-tune; rate quantizes to 1/256 unless dropout_exact, and
        # the rescale uses the EFFECTIVE (quantized) rate so expectation
        # is preserved exactly.
        keep = dropout_keep_mask(
            dropout_rng, weights.shape, dropout_rate, exact=dropout_exact
        )
        weights = apply_keep_mask(
            keep, weights, dropout_rate, dropout_exact
        )
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def causal_mask(q_len: int, kv_len: int) -> jax.Array:
    """[1, 1, q_len, kv_len] lower-triangular mask (True = attend)."""
    i = jnp.arange(q_len)[:, None] + (kv_len - q_len)
    j = jnp.arange(kv_len)[None, :]
    return (j <= i)[None, None, :, :]


def padding_mask(attention_mask: jax.Array) -> jax.Array:
    """[B, Skv] 1/0 padding mask -> [B, 1, 1, Skv] boolean attend-mask."""
    return attention_mask[:, None, None, :].astype(bool)


def normalize_kv_mask(
    mask: Optional[jax.Array],
    batch: int,
    kv_len: int,
    dtype=jnp.int32,
    impl: str = "attention",
) -> jax.Array:
    """The kv-validity-mask contract shared by the flash/ring/ulysses
    implementations: None -> all-ones; [B, 1, 1, S] padding masks squeeze
    to [B, S]; dense [B, H, Sq, Skv] masks are rejected (only the
    reference implementation supports those)."""
    if mask is None:
        return jnp.ones((batch, kv_len), dtype)
    if mask.ndim == 4:
        if mask.shape[1] != 1 or mask.shape[2] != 1:
            raise NotImplementedError(
                f"{impl} supports [B, S] / [B, 1, 1, S] padding masks and "
                f"causal=True; got dense mask {mask.shape} — use "
                f"implementation='reference'"
            )
        mask = mask[:, 0, 0, :]
    return jnp.broadcast_to(mask, (batch, kv_len)).astype(dtype)


def combine_kv_causal_mask(
    mask: Optional[jax.Array], q_len: int, kv_len: int, causal: bool
) -> Optional[jax.Array]:
    """The one mask-assembly rule every einsum-path implementation shares:
    lift a [B, Skv] kv-validity row to [B, 1, 1, Skv] (4-D masks pass
    through), then AND in the causal triangle when asked — a causal model
    with padded batches must not see future positions just because a
    padding mask is set. Returns None when nothing masks."""
    if mask is not None and mask.ndim == 2:
        mask = padding_mask(mask)
    if causal:
        tri = causal_mask(q_len, kv_len)
        mask = tri if mask is None else jnp.logical_and(mask.astype(bool), tri)
    return mask


def unmeshed_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array],
    causal: bool,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Single-device degenerate path for the sequence-parallel
    implementations: reference attention with the kv-validity mask and the
    causal triangle combined by combine_kv_causal_mask."""
    if mask is not None:
        mask = normalize_kv_mask(mask, q.shape[0], k.shape[1])
    return dot_product_attention(
        q, k, v, combine_kv_causal_mask(mask, q.shape[1], k.shape[1], causal),
        scale=scale, dropout_rate=dropout_rate, dropout_rng=dropout_rng,
    )


def attend(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mask: Optional[jax.Array] = None,
    *,
    implementation: str = "reference",
    causal: bool = False,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    dropout_exact: bool = False,
) -> jax.Array:
    """Dispatch to an attention implementation.

    implementation:
      "reference" — this module's einsum attention (any backend);
      "fused"     — Pallas TPU fused short-seq kernel (full softmax per
                    cell, one-pass backward, IN-KERNEL attention dropout
                    from the hardware PRNG);
      "flash"     — Pallas TPU flash-attention kernel (streaming online
                    softmax; in-kernel dropout at any length);
      "ring"      — sequence-parallel ring attention over the `sp` mesh
                    axis (ppermute K/V rotation, online-softmax merge);
      "ulysses"   — sequence-parallel attention via all-to-all head/seq
                    resharding over `sp` (requires local heads divisible
                    by sp; per-device body is flash on TPU, exact
                    reference numerics on CPU — ulysses_attention's
                    local_impl parameter pins either).

    Attention-probability dropout is supported by EVERY implementation
    (round 4): the Pallas kernels draw in-kernel from the TPU hardware
    PRNG; ulysses applies per-head dropout on its post-all-to-all local
    sequences; ring masks the online-softmax numerator per
    (q-shard, kv-block) tile while denominators stay undropped — exact
    post-softmax semantics even though the softmax itself is
    distributed. Sharded paths fold each mesh slot's position into the
    key, so mask BITS (not statistics) depend on the mesh layout.
    """
    if dropout_rate > 0.0 and dropout_rng is None:
        raise ValueError(
            "dropout_rate > 0 requires a dropout_rng (dropout would "
            "otherwise be silently skipped)"
        )
    if dropout_exact and dropout_rate > 0.0 and implementation != "reference":
        raise ValueError(
            "dropout_exact (bit-exact bernoulli masks) is only available "
            "on implementation='reference'; the fused kernel draws from "
            "the TPU hardware PRNG"
        )
    if implementation == "reference":
        mask = combine_kv_causal_mask(mask, q.shape[1], k.shape[1], causal)
        return dot_product_attention(
            q, k, v, mask, dropout_rate=dropout_rate,
            dropout_rng=dropout_rng, dropout_exact=dropout_exact,
        )
    if implementation == "fused":
        # Three regimes (measured in the early rounds, BASELINE.md):
        # at short S, XLA's batched matmuls are unbeatable
        # and only softmax+dropout is worth fusing (hybrid); at mid S the
        # whole-attention kernel wins (S=256/512: 4.1/4.3 ms vs einsum's
        # 5.0/5.5 fwd+bwd); past MAX_SEQ its one-pass backward blows VMEM
        # and flash's streaming design takes over (with its own
        # in-kernel dropout — see the fallthrough below).
        from tpudl.ops.fused_attention import MAX_SEQ, fused_attention

        if q.shape[1] <= 256:
            from tpudl.ops.softmax_dropout import hybrid_attention

            return hybrid_attention(
                q, k, v, mask=mask, causal=causal,
                dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            )
        if q.shape[1] <= MAX_SEQ:
            return fused_attention(
                q, k, v, mask=mask, causal=causal,
                dropout_rate=dropout_rate, dropout_rng=dropout_rng,
            )
        # Past MAX_SEQ the streaming flash kernel takes over — WITH
        # in-kernel dropout (the round-3 S>512 dropout carve-out is gone;
        # configs[4]'s seq-2048 fine-tune trains with real
        # attention_dropout now). Falls through to the shared branch.
        implementation = "flash"
    if implementation == "flash":
        from tpudl.ops.flash_attention import flash_attention

        return flash_attention(
            q, k, v, mask=mask, causal=causal,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        )
    if implementation == "ulysses":
        # Exact dropout under SP: post-all-to-all every head is fully
        # local, so the per-head masks are plain BERT/Llama semantics.
        from tpudl.ops.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, mask=mask, causal=causal,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        )
    if implementation == "ring":
        # Exact post-softmax dropout despite the distributed softmax:
        # the online merge keeps denominators undropped and masks only
        # the numerator per (q-shard, kv-block) tile.
        from tpudl.ops.ring_attention import ring_attention

        return ring_attention(
            q, k, v, mask=mask, causal=causal,
            dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        )
    raise ValueError(f"unknown attention implementation: {implementation!r}")
