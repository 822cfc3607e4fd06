"""Serving export of the autoregressive decode path.

The reference's core loop is export -> session -> infer (reference
notebooks/cv/onnx_experiments.py:33-42,81: ONNX export, InferenceSession,
session.run). Its decoder-model analog is this module: the prefill and
single-token decode steps of tpudl.models.generate are exported as
StableHLO artifacts with the KV cache as EXPLICIT inputs/outputs (the
functional form a serving runtime needs — no flax mutable-state plumbing
survives serialization), and a deserialized-artifact generation loop
reproduces live ``generate()`` token for token
(tests/test_decode_export.py).

Artifacts (``export_decoder``, the offline generation pair):
- prefill: (params, input_ids, attention_mask) -> (last_logits, cache)
- decode:  (params, cache, token, position) -> (logits, new_cache)

``export_serving_decoder`` writes the pair tpudl.serve runs: a batch-1
prefill and the slot-batched PAGED decode (page pools in and out, page
table / start / lens as inputs).

Both can carry multi-platform lowering (cpu + tpu) like the rest of
tpudl.export — one artifact, either backend, the property the reference
buys with ONNX.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tpudl.export.export import export_stablehlo, load_exported

# The functional prefill/decode contracts live with the live generation
# loop (one definition — the exported artifacts CANNOT diverge from
# generate()); re-exported here for the serving-side API. The padded-mask
# contract is shared the same way.
from tpudl.models.generate import (  # noqa: F401
    decode_fn,
    prefill_fn,
    validate_left_padded,
)


def export_decoder(
    model,
    params,
    batch_size: int,
    prompt_len: int,
    path_prefix: Optional[str] = None,
    platforms: Optional[Sequence[str]] = None,
) -> Tuple[bytes, bytes]:
    """Export (prefill, decode) StableHLO artifacts for fixed
    ``batch_size``/``prompt_len`` shapes (static shapes are the serving
    contract — the KV cache is bounded by model.cfg.max_seq_len).

    With ``path_prefix``, writes ``{prefix}.prefill.stablehlo`` and
    ``{prefix}.decode.stablehlo``.
    """
    ids = jnp.zeros((batch_size, prompt_len), jnp.int32)
    mask = jnp.ones((batch_size, prompt_len), jnp.int32)
    pf = prefill_fn(model)
    # A real (abstractly-traced) cache example for the decode export.
    _, cache = jax.eval_shape(pf, params, ids, mask)
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache)
    token = jnp.zeros((batch_size,), jnp.int32)
    position = jnp.full((batch_size,), prompt_len, jnp.int32)

    prefill_blob = export_stablehlo(
        pf,
        (params, ids, mask),
        path=f"{path_prefix}.prefill.stablehlo" if path_prefix else None,
        platforms=platforms,
    )
    decode_blob = export_stablehlo(
        decode_fn(model),
        (params, cache, token, position),
        path=f"{path_prefix}.decode.stablehlo" if path_prefix else None,
        platforms=platforms,
    )
    return prefill_blob, decode_blob


def export_serving_decoder(
    model,
    params,
    num_slots: int,
    prompt_len: int,
    path_prefix: Optional[str] = None,
    platforms: Optional[Sequence[str]] = None,
    page_size: int = 16,
    kv_dtype: Optional[str] = None,
    num_pages: Optional[int] = None,
) -> Tuple[bytes, bytes]:
    """Export the artifact pair the continuous-batching engine serves
    (tpudl.serve): a BATCH-1 prefill (requests are seated one at a
    time) and a batch-``num_slots`` decode (all slots step together).
    ``ServeSession.from_artifacts`` recovers every shape it needs from
    these blobs — no side-channel metadata.

    The decode is the PAGED contract
    (tpudl.models.generate.paged_decode_fn): the cache input is the
    page-pool pytree and three host-owned addressing arrays (page
    table, start, lens) ride as extra traced inputs — seating/freeing
    against the deserialized program never recompiles, exactly like
    the live path. ``page_size``/``kv_dtype``/``num_pages`` fix the
    exported pool geometry (a PagedKVCache at the same settings);
    ``from_artifacts`` reads it all back from the avals."""
    from tpudl.models.generate import paged_decode_fn
    from tpudl.serve.cache import PagedKVCache

    pf = prefill_fn(model)
    ids = jnp.zeros((1, prompt_len), jnp.int32)
    mask = jnp.ones((1, prompt_len), jnp.int32)
    _, template, *_ = jax.eval_shape(
        pf,
        params,
        jnp.zeros((num_slots, prompt_len), jnp.int32),
        jnp.ones((num_slots, prompt_len), jnp.int32),
    )
    cache = PagedKVCache(
        template, page_size=page_size, num_pages=num_pages,
        kv_dtype=kv_dtype,
    )
    cache._no_rings("the exported decode artifact")
    if getattr(getattr(model, "cfg", None), "num_experts", 0) > 0:
        raise ValueError(
            "the exported decode artifact is not wired to routed "
            "experts: an artifact session takes the two-value prefill "
            "and decode contracts, and a model with routed experts "
            "returns its tokens per held expert beside them"
        )
    if getattr(getattr(model, "cfg", None), "hyper_streams", 0):
        raise ValueError(
            "the exported decode artifact is not wired to a residual "
            "stream of several vectors a token (hyper_streams): an "
            "artifact session takes the two-value prefill and decode "
            "contracts, and such a model returns its maps' statistic "
            "beside them"
        )
    if getattr(getattr(model, "cfg", None), "loop_passes", 1) > 1:
        raise ValueError(
            "the exported decode artifact is not wired to a stack run "
            "several times a token (loop_passes): an artifact session "
            "takes the two-value prefill and decode contracts, and such "
            "a model returns its exit distribution beside them"
        )
    token = jnp.zeros((num_slots,), jnp.int32)
    position = jnp.full((num_slots,), prompt_len, jnp.int32)
    prefill_blob = export_stablehlo(
        pf,
        (params, ids, mask),
        path=f"{path_prefix}.prefill.stablehlo" if path_prefix else None,
        platforms=platforms,
    )
    decode_blob = export_stablehlo(
        paged_decode_fn(model, cache.page_size, cache.quantized),
        (params, cache.cache, token, position, *cache.dispatch_args()),
        path=f"{path_prefix}.decode.stablehlo" if path_prefix else None,
        platforms=platforms,
    )
    return prefill_blob, decode_blob


def generate_with_exported(
    prefill_call: Callable,
    decode_call: Callable,
    params,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array] = None,
    max_new_tokens: int = 32,
    eos_id: Optional[int] = None,
    max_seq_len: Optional[int] = None,
    eos_check_every: int = 8,
) -> jax.Array:
    """Greedy generation driven entirely by deserialized artifacts — the
    session.run loop of the reference, over StableHLO. Ragged prompt
    batches ride LEFT-padded through ``attention_mask`` (0 = pad; same
    contract as tpudl.models.generate — the exported cache carries the
    per-slot validity mask, so padded rows reproduce their unpadded
    tokens). Returns [B, max_new_tokens] token ids, eos-padded like
    generate().

    ``max_seq_len`` is the exporting model's KV-cache bound
    (model.cfg.max_seq_len) — the deserialized callables cannot see it,
    and overflowing it would silently CLAMP cache writes to the last slot
    (corrupted tokens, no error). Always pass it on serving paths.

    ``eos_check_every`` paces the all-rows-done early-exit readback
    (same contract as ``generate()``): the check is a blocking host
    sync, so it runs after the first token (catching the
    finished-at-token-1 batch for free) and then once per
    ``eos_check_every`` tokens — NOT per token, which would serialize
    the otherwise-async decode dispatches.
    """
    b, s = input_ids.shape
    if eos_check_every < 1:
        raise ValueError(
            f"eos_check_every must be >= 1, got {eos_check_every}"
        )
    if max_seq_len is not None and s + max_new_tokens > max_seq_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"exporting model's KV-cache bound max_seq_len={max_seq_len}"
        )
    if attention_mask is None:
        mask = jnp.ones_like(input_ids)
    else:
        mask = attention_mask
        validate_left_padded(mask)
    logits, cache = prefill_call(params, input_ids, mask)
    position = jnp.sum(mask, axis=-1).astype(jnp.int32)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    done = jnp.zeros((b,), bool)
    tokens = []
    for i in range(max_new_tokens):
        if eos_id is not None:
            token = jnp.where(done, eos_id, token)
            done = jnp.logical_or(done, token == eos_id)
        tokens.append(token)
        if i + 1 == max_new_tokens:
            break
        if (
            eos_id is not None
            and (i == 0 or (i + 1) % eos_check_every == 0)
            and bool(done.all())
        ):
            # Every row finished: the remaining positions are eos by
            # contract — emit them without paying a dead decode dispatch
            # per token (a batch that finishes at token 1 used to scan
            # all remaining steps; tests/test_decode_export.py asserts
            # the decode-call count).
            break
        logits, cache = decode_call(params, cache, token, position)
        position = position + 1
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = jnp.stack(tokens, axis=1)
    if out.shape[1] < max_new_tokens:
        pad = jnp.full(
            (b, max_new_tokens - out.shape[1]), eos_id, out.dtype
        )
        out = jnp.concatenate([out, pad], axis=1)
    return out


def load_decoder(
    prefill_blob_or_path, decode_blob_or_path
) -> Tuple[Callable, Callable]:
    """Deserialize the (prefill, decode) artifact pair."""
    return (
        load_exported(prefill_blob_or_path),
        load_exported(decode_blob_or_path),
    )
