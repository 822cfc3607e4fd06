"""Autoregressive decoding with a KV cache (Llama serving path).

The decoder-model analog of the reference repo's inference benchmarking
(reference notebooks/cv/onnx_experiments.py:77-140): a jitted prefill +
a jitted single-token decode step over static-shape KV caches, so the
generation loop runs as two compiled XLA programs whatever the length.

Greedy (temperature=0), temperature, top-k, and top-p (nucleus)
sampling. Ragged prompt batches are served LEFT-padded: the cache marks
padded slots invalid (LlamaAttention's ``valid`` buffer) and masks by
slot write-order, while mask-aware positions keep RoPE phases identical
to the unpadded prompt — so a left-padded row generates token-for-token
what it would alone (tests/test_generate.py).
"""

from __future__ import annotations

import functools
import re
from typing import Optional

import jax
import jax.numpy as jnp

from tpudl.models.hyper import HYPER_STAT_NAME
from tpudl.models.turned import as_declared


def named(fn, name: str):
    """``fn`` under another name: ``jax.jit`` calls the compiled
    program ``jit_<name>``, which is how a profiler trace tells a
    draft model's programs from the target's."""
    fn.__name__ = fn.__qualname__ = name
    return fn


#: The collection a layer sows its serving statistics into
#: (tpudl.ops.moe.DroplessMoE, tpudl.models.hyper.HyperConnection).
MOE_STATS = "moe_stats"


def _apply_cached(model, variables, *args, **kwargs):
    """``model.apply`` with the cache mutable: ``(output, cache, *stats)``:
    one array ``[layers, ...]`` a statistic the layers sow, at fixed places
    (MOE_STAT_NAMES, HYPER_STAT_NAME, a looped stack's LOOP_STAT_NAME ``[1,
    passes + 1]``, then the indexers' SPARSE_STAT_NAME), None where a later one
    is sown and this one is not; none sown adds nothing. Kernels may be turned."""
    from tpudl.models.llama import LOOP_STAT_NAME, SPARSE_STAT_NAME
    out, mutated = model.apply(
        {**variables, "params": as_declared(variables["params"])},
        *args, mutable=["cache", MOE_STATS], **kwargs
    )
    from tpudl.ops.moe import MOE_STAT_NAMES
    stats = jax.tree_util.tree_leaves_with_path(mutated.get(MOE_STATS, {}))

    def layer(path_leaf) -> int:  # -1: the stack's own statistic
        found = re.search(r"layer_(\d+)", jax.tree_util.keystr(path_leaf[0]))
        return int(found.group(1)) if found else -1

    stacks = [[
        leaf for path, leaf in sorted(stats, key=layer)
        if path[-2].key == name
    ] for name in (*MOE_STAT_NAMES, HYPER_STAT_NAME, LOOP_STAT_NAME, SPARSE_STAT_NAME)]
    while stacks and not stacks[-1]:
        stacks.pop()
    stacks = [jnp.stack(leaves) if leaves else None for leaves in stacks]
    return (out, mutated["cache"], *stacks)


# The contract functions below carry names of their own (``tpudl_prefill``,
# ``tpudl_decode``, ...) for the same reason: the trace's ``XLA Modules``
# line then reads ``jit_tpudl_decode``, not ``jit_fn``.


def prefill_fn(model):
    """THE functional prefill contract (cache as explicit pytree I/O):
    (params, input_ids, attention_mask) -> (last_logits, cache). One
    definition serves both the live loop below and the serving export
    (tpudl.export.decode) — they cannot diverge. A model with routed
    experts returns further values (``_apply_cached``), as the contracts
    below do. Once traced at a length, ``attention_in_kernel[rows]`` of its
    ``attention_layers[rows]`` attentions are the prefill kernel's."""

    def tpudl_prefill(params, input_ids, attention_mask):
        positions = jnp.maximum(
            jnp.cumsum(attention_mask, axis=-1) - 1, 0
        ).astype(jnp.int32)
        # Only the last position's logits leave this program: where the
        # window's would pass the bound a long prefill keeps its scores
        # under (4,096 rows x a 100k vocabulary: 1.6 GB), ask for that row.
        from tpudl.models.llama import PREFILL_SCORE_BYTES
        from tpudl.ops.flash_attention import note_prefill, prefill_attentions
        from tpudl.ops.flash_attention import prefill_kernel_calls
        vocab = getattr(getattr(model, "cfg", None), "vocab_size", 0)
        one_row = 4 * input_ids.shape[1] * vocab > PREFILL_SCORE_BYTES
        before, attentions = prefill_kernel_calls(), prefill_attentions()
        logits, *rest = _apply_cached(
            model,
            {"params": params},
            input_ids,
            attention_mask,
            decode=True,
            positions=positions,
            last_only=one_row,
        )
        note_prefill(tpudl_prefill, input_ids.shape[1], before, attentions)
        return (logits[:, -1, :], *rest)
    return tpudl_prefill


def decode_fn(model):
    """``generate()``'s decode step (dense rows; the engine's is paged):
    (params, cache, token, position) -> (logits, new_cache)."""

    def tpudl_decode(params, cache, token, position):
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            token[:, None],
            jnp.ones_like(token)[:, None],
            decode=True,
            positions=position[:, None],
            mutable=["cache"],
        )
        return logits[:, -1, :], mutated["cache"]

    return tpudl_decode


def paged_decode_fn(
    model, page_size: int, quantized: bool, sharded: bool = False
):
    """THE paged single-token decode contract (tpudl.models.paged):
    ``(params, cache, token, position, page_table, start, lens) ->
    (logits, new_cache)`` where ``cache`` holds per-layer page pools
    (``pages_k``/``pages_v`` + ``scale_k``/``scale_v`` when int8) and
    the three small int32 arrays are the HOST-owned addressing state —
    page table [B, P], first attendable logical position [B], and the
    logical write position [B]. ``page_size``/``quantized``/``sharded``
    (the pool was committed to a mesh: ``PagedKVCache.sharded``) are
    static (baked into the compiled program); placement changes never
    recompile. Built for the serve engine
    (tpudl.serve.cache.PagedKVCache owns the pools and addressing).

    Once traced, the program says of itself which of its attention
    layers read the pool in place (tpudl.ops.paged_attention):
    ``attention_in_place``, one bool a layer, None before."""
    from tpudl.models.paged import PagedView

    def tpudl_decode(params, cache, token, position, page_table, start, lens):
        view = PagedView(
            page_table=page_table, start=start, lens=lens,
            page_size=page_size, quantized=quantized, sharded=sharded,
        )
        logits, *rest = _apply_cached(
            model,
            {"params": params, "cache": cache},
            token[:, None],
            jnp.ones_like(token)[:, None],
            decode=True,
            positions=position[:, None],
            paged=view,
        )
        tpudl_decode.attention_in_place = tuple(view.took)
        return (logits[:, -1, :], *rest)

    tpudl_decode.attention_in_place = None
    return tpudl_decode


def lora_prefill_fn(model, impl: str = "auto"):
    """THE multi-tenant prefill contract: ``(params, input_ids,
    attention_mask, adapter_pools, adapter_table [1, r_max],
    adapter_scale [1]) -> (last_logits, cache)``. The batch-1 prefill
    with ONE tenant's adapter applied through the segmented-LoRA seam
    (tpudl.models.lora.AdapterView) — an all-zero table row (every
    entry on the never-written page 0) serves the plain base model, so
    tenantless requests ride the same compiled program. ``impl`` is the
    tpudl.ops dispatch seam for the segmented kernel (static)."""
    from tpudl.models.lora import AdapterView

    def tpudl_prefill(params, input_ids, attention_mask, apools, atable, ascale):
        positions = jnp.maximum(
            jnp.cumsum(attention_mask, axis=-1) - 1, 0
        ).astype(jnp.int32)
        logits, mutated = model.apply(
            {"params": params},
            input_ids,
            attention_mask,
            decode=True,
            positions=positions,
            adapters=AdapterView(
                pools=apools, table=atable, scale=ascale, impl=impl
            ),
            mutable=["cache"],
        )
        return logits[:, -1, :], mutated["cache"]

    return tpudl_prefill


def lora_paged_decode_fn(
    model, page_size: int, quantized: bool, impl: str = "auto",
    sharded: bool = False,
):
    """THE multi-tenant paged decode contract: ``paged_decode_fn``'s
    seven arguments plus ``(adapter_pools, adapter_table [B, r_max],
    adapter_scale [B])`` — every slot applies ITS tenant's adapter
    pages through one segmented-LoRA dispatch per projection site
    (tpudl.ops.segmented_lora). The pools and tables are traced
    inputs, so loading/evicting adapters between steps never
    recompiles; slots with no tenant carry an all-zero table row and
    decode the plain base model."""
    from tpudl.models.lora import AdapterView
    from tpudl.models.paged import PagedView

    def tpudl_decode(
        params, cache, token, position, page_table, start, lens,
        apools, atable, ascale,
    ):
        view = PagedView(
            page_table=page_table, start=start, lens=lens,
            page_size=page_size, quantized=quantized, sharded=sharded,
        )
        logits, mutated = model.apply(
            {"params": params, "cache": cache},
            token[:, None],
            jnp.ones_like(token)[:, None],
            decode=True,
            positions=position[:, None],
            paged=view,
            adapters=AdapterView(
                pools=apools, table=atable, scale=ascale, impl=impl
            ),
            mutable=["cache"],
        )
        tpudl_decode.attention_in_place = tuple(view.took)
        return logits[:, -1, :], mutated["cache"]

    tpudl_decode.attention_in_place = None
    return tpudl_decode


def chunk_prefill_fn(model):
    """THE suffix-prefill contract for prefix-sharing serving
    (tpudl.serve.cache radix mode): ``(params, cache, tokens [B, C],
    positions [B, C]) -> (last_logits, cache)``. The provided ``cache``
    already holds the SHARED prefix KV (gathered out of radix-tree
    pages into dense rows, ``index`` pinned at the prefix length); this
    runs only the C unshared suffix tokens through the dense decode
    branch — which writes the chunk at ``index``..``index+C`` and
    attends slot-order-causally over prefix + chunk — so prefill cost
    is O(suffix), not O(prompt window). Positions are ABSOLUTE (token
    index in the unpadded prompt), keeping RoPE phases identical to a
    cold full prefill."""

    def tpudl_chunk_prefill(params, cache, tokens, positions):
        logits, *rest = _apply_cached(
            model,
            {"params": params, "cache": cache},
            tokens,
            jnp.ones_like(tokens),
            decode=True,
            positions=positions,
        )
        return (logits[:, -1, :], *rest)

    return tpudl_chunk_prefill


def paged_chunk_decode_fn(
    model, page_size: int, quantized: bool, sharded: bool = False
):
    """THE speculative-verify contract: ``(params, cache, tokens
    [B, C], positions [B, C], page_table, start, lens) -> (logits
    [B, C, V], new_cache)``. One slot-batched dispatch writes each
    slot's C-token window into its pages (token j at logical position
    ``lens + j``) and returns the logits for EVERY window position —
    the target model's verdict on all k draft proposals at once
    (tpudl.serve.speculate). Causality within the window rides the
    chunked paged mask; rejected tails roll back on the host by simply
    not advancing ``lens`` past the accepted count (the garbage rows
    are masked and overwritten by the next window)."""
    from tpudl.models.paged import PagedView

    def tpudl_verify(params, cache, tokens, positions, page_table, start, lens):
        view = PagedView(
            page_table=page_table, start=start, lens=lens,
            page_size=page_size, quantized=quantized, sharded=sharded,
        )
        out = _apply_cached(
            model,
            {"params": params, "cache": cache},
            tokens,
            jnp.ones_like(tokens),
            decode=True,
            positions=positions,
            paged=view,
        )
        tpudl_verify.attention_in_place = tuple(view.took)
        return out

    tpudl_verify.attention_in_place = None
    return tpudl_verify


@functools.partial(jax.jit, static_argnums=(0,))
def _prefill(model, params, input_ids, attention_mask):
    return prefill_fn(model)(params, input_ids, attention_mask)


@functools.partial(jax.jit, static_argnums=(2,))
def _eos_update(token, done, eos_id):
    """Finished rows emit eos forever; one fused dispatch per token (the
    eager two-op form costs two dispatches per generated token)."""
    token = jnp.where(done, eos_id, token)
    return token, jnp.logical_or(done, token == eos_id)


@functools.partial(jax.jit, static_argnums=(0,))
def _decode_step(model, params, cache, token, position):
    return decode_fn(model)(params, cache, token, position)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5))
def _decode_chunk(
    model, steps, greedy, top_k, has_top_p, has_eos,
    params, cache, token, position, done, rng,
    temperature, top_p, eos_id,
):
    """``steps`` decode iterations as ONE compiled lax.scan: split rng,
    decode from the previous (eos-masked) token, select, eos-mask, emit.
    The per-token Python loop paid ~5 device dispatches per generated
    token (decode, select, eos ops, position, rng split) — pure host
    dispatch latency; the scan collapses a whole eos-check window into
    one dispatch. Split order matches the
    un-scanned loop exactly, so tokens are bit-identical.

    Only STRUCTURAL switches are static (greedy, the top-k size, top-p
    and eos presence, the chunk length); temperature / top_p / eos_id
    ride as traced scalars, so a serving process varying per-request
    sampling hyperparameters reuses the one compiled model-sized scan
    instead of recompiling it per (temperature, top_p) tuple.
    """

    def body(carry, _):
        cache, token, position, done, rng = carry
        rng, step_rng = jax.random.split(rng)
        logits, cache = decode_fn(model)(params, cache, token, position)
        nxt = _select_impl(
            logits, step_rng,
            0.0 if greedy else temperature,
            top_k,
            top_p if has_top_p else None,
            greedy=greedy,
        )
        if has_eos:
            nxt = jnp.where(done, eos_id, nxt)
            done = jnp.logical_or(done, nxt == eos_id)
        return (cache, nxt, position + 1, done, rng), nxt

    (cache, token, position, done, rng), toks = jax.lax.scan(
        body, (cache, token, position, done, rng), None, length=steps
    )
    # The all-rows-done scalar is computed IN-GRAPH so the chunk loop's
    # early-exit readback costs zero extra dispatches (an eager
    # done.all() per chunk paid one more dispatch and sync just to ask
    # "may I stop").
    return cache, token, position, done, rng, toks, jnp.all(done)


_NEG_INF = -1e30


def validate_sampling(temperature, top_k, top_p) -> None:
    """Reject sampling-parameter combinations that would silently not do
    what was asked: top_k/top_p only apply to the categorical branch, so
    pairing them with greedy (temperature 0) is an error, not a no-op."""
    if temperature == 0.0 and (top_k is not None or top_p is not None):
        raise ValueError(
            "top_k/top_p require temperature > 0 (temperature=0.0 is "
            "greedy argmax and would silently ignore them)"
        )
    if top_k is not None and not 0 < top_k:
        raise ValueError(f"top_k must be positive, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def validate_left_padded(attention_mask) -> None:
    """Shared left-padded-mask contract for the live loop AND the
    exported serving loop (tpudl.export.decode — one definition, the
    paths cannot diverge): every row must be BINARY 0s then 1s with at
    least one real token. Right padding would leave the final slot —
    whose logits seed generation — on a pad; a non-binary mask (e.g. a
    2) would pass the monotonicity check yet corrupt
    ``position = sum(mask)`` and with it cache validity. One host sync
    for all three checks fused."""
    m = attention_mask
    ok = jnp.logical_and(
        jnp.logical_and(
            jnp.all(m[:, 1:] >= m[:, :-1]),
            jnp.all(jnp.sum(m, axis=-1) > 0),
        ),
        jnp.all((m == 0) | (m == 1)),
    )
    if not bool(ok):
        raise ValueError(
            "ragged prompt batches are served LEFT-padded: every "
            "attention_mask row must be binary (0/1) 0s then 1s with at "
            "least one real token (right-padding would leave the final "
            "slot — whose logits seed generation — on a pad; non-binary "
            "values corrupt position = sum(mask))"
        )


def _select_impl(logits, rng, temperature, top_k=None, top_p=None,
                 greedy=None):
    """Next-token selection on [B, V] logits: greedy at temperature 0,
    else categorical over temperature-scaled logits optionally truncated
    to the top-k tokens and/or the top-p (nucleus) probability mass.
    top_p keeps the smallest prefix of probability-sorted tokens whose
    cumulative mass reaches p (the argmax always survives). Parameter
    combinations are checked once by validate_sampling, not per step.
    Traced inside _decode_chunk's scan; _select_first serves the one
    prefill-token selection. ``greedy`` makes the structural
    branch explicit when ``temperature`` is a traced scalar (a tracer
    cannot drive the ``== 0.0`` Python branch); None = derive from the
    concrete temperature. top_k (a shape) must be concrete; top_p may
    be traced, but its None-ness is structural.
    """
    if greedy is None:
        greedy = temperature == 0.0
    # Selection math in f32 regardless of model dtype: a 128k-vocab bf16
    # cumsum has ~3-digit resolution — comparable to 1-p at top_p=0.95 —
    # and the scan path's traced f32 scalars would otherwise promote
    # while the first-token path stayed bf16 (different numerics for
    # token 0 than tokens 1..N).
    logits = logits.astype(jnp.float32)
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, min(top_k, logits.shape[-1]))[0][..., -1:]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None:
        # Cutoff-VALUE formulation: sort values (no index permutation),
        # find the smallest prefix whose exclusive cumulative mass stays
        # < p (so the prefix that first reaches p survives — the argmax
        # always does), then keep by comparing against the last kept
        # value. Avoids the two full-vocab index gathers of the
        # argsort/inverse-permutation form, which dominated decode time
        # at a 128k vocab (~20 ms/token -> ~2). Tokens BIT-EQUAL to the
        # cutoff logit are also kept — a measure-zero superset for
        # continuous logits.
        sorted_desc = -jnp.sort(-logits, axis=-1)
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        keep_sorted = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        num_kept = jnp.sum(keep_sorted.astype(jnp.int32), axis=-1,
                           keepdims=True)  # >= 1
        v_cut = jnp.take_along_axis(sorted_desc, num_kept - 1, axis=-1)
        logits = jnp.where(logits >= v_cut, logits, _NEG_INF)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _select_first(logits, rng, greedy, top_k, has_top_p, temperature, top_p):
    """First-token (prefill-logits) selection with the SAME
    static/traced split as _decode_chunk: only structure is static, so
    per-request temperature/top_p reuse one compiled program instead of
    recompiling the full-vocab sort per float tuple."""
    return _select_impl(
        logits, rng,
        0.0 if greedy else temperature,
        top_k,
        top_p if has_top_p else None,
        greedy=greedy,
    )


def generate(
    model,
    params,
    input_ids: jax.Array,
    attention_mask: Optional[jax.Array] = None,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
    eos_id: Optional[int] = None,
    rng: Optional[jax.Array] = None,
    eos_check_every: int = 8,
) -> jax.Array:
    """Generate continuations for a [B, S] prompt batch.

    ``model`` is a LlamaForCausalLM whose config ``max_seq_len`` bounds
    S + max_new_tokens. Ragged prompts batch via LEFT-padding: pad short
    rows on the left and pass ``attention_mask`` (0 = pad); each row then
    generates exactly what it would unpadded. ``temperature``/``top_k``/
    ``top_p`` select the sampling rule (see ``_select_impl``). Returns
    [B, max_new_tokens] generated ids (after ``eos_id``, positions are
    padded with eos). ``eos_check_every`` paces the all-rows-done
    early-exit readback (1 = check every token).
    """
    b, s = input_ids.shape
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}"
        )
    validate_sampling(temperature, top_k, top_p)
    if attention_mask is None:
        attention_mask = jnp.ones_like(input_ids)
    else:
        validate_left_padded(attention_mask)
    if eos_check_every < 1:
        raise ValueError(
            f"eos_check_every must be >= 1 (1 = check every token), got "
            f"{eos_check_every}"
        )
    if s + max_new_tokens > model.cfg.max_seq_len:
        raise ValueError(
            f"prompt ({s}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_seq_len {model.cfg.max_seq_len} (the KV cache bound)"
        )
    if rng is None:
        rng = jax.random.key(0)

    logits, cache = _prefill(model, params, input_ids, attention_mask)
    # Next absolute position per row (mask-aware: left padding skipped).
    position = jnp.sum(attention_mask, axis=-1).astype(jnp.int32)

    done = jnp.zeros((b,), bool)
    rng, sel_rng = jax.random.split(rng)
    greedy = temperature == 0.0
    t_op = jnp.float32(temperature)
    p_op = jnp.float32(top_p if top_p is not None else 1.0)
    token = _select_first(
        logits, sel_rng, greedy, top_k, top_p is not None, t_op, p_op
    )
    if eos_id is not None:
        token, done = _eos_update(token, done, eos_id)
    # The decode loop runs as compiled lax.scan CHUNKS of
    # ``eos_check_every`` tokens (_decode_chunk): one host dispatch per
    # chunk — and with an eos, one done-all readback per chunk —
    # instead of ~5 dispatches per token: the difference between
    # dispatch-latency-bound and HBM-bandwidth-bound serving. Chunking is
    # unconditional (without an eos the readback is simply skipped), so
    # the jit cache holds the chunk-length scan plus one remainder
    # length per (max_new_tokens - 1) % eos_check_every residue — at
    # most eos_check_every distinct lengths across all requests, not
    # one model-sized executable per requested length.
    out = [token[:, None]]
    remaining = max_new_tokens - 1
    eos_op = jnp.int32(eos_id if eos_id is not None else 0)
    all_done = eos_id is not None and bool(done.all())
    while remaining > 0:
        if all_done:
            # Every row finished: pad the rest with eos, skip dead steps
            # (a batch that finishes at token 1 runs ZERO decode chunks —
            # tests/test_generate.py counts the invocations).
            out.append(jnp.full((b, remaining), eos_id, token.dtype))
            break
        steps = min(eos_check_every, remaining)
        cache, token, position, done, rng, toks, all_done_op = _decode_chunk(
            model, steps, greedy, top_k,
            top_p is not None, eos_id is not None,
            params, cache, token, position, done, rng,
            t_op, p_op, eos_op,
        )
        out.append(toks.T)
        remaining -= steps
        # One readback of the chunk's in-graph all-done scalar — the
        # same sync the chunked design already paid, no extra dispatch.
        all_done = remaining > 0 and eos_id is not None and bool(all_done_op)
    return jnp.concatenate(out, axis=1)
