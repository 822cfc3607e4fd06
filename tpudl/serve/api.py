"""Request-level serving API: ``Request`` in, ``Result`` out.

The synchronous front end over tpudl.serve.engine:

    session = ServeSession.from_model(model, params, prompt_len=64)
    session.submit(Request("r0", prompt_ids, max_new_tokens=32))
    results = session.collect()          # {"r0": Result(tokens=[...])}

``from_artifacts`` builds the SAME session from serialized StableHLO
blobs (tpudl.export.decode.export_serving_decoder) — a served artifact
and the live model are interchangeable: every shape the engine needs
(slot count, prompt length, cache bound) is recovered from the
artifact's input avals, and greedy outputs are token-for-token
identical to live ``generate()`` (tests/test_serve.py asserts it;
``assert_serving_parity`` is the reusable check).

Admission errors (prompt longer than the compiled prompt window, or
prompt window + max_new_tokens overflowing the KV-cache bound) raise at
``submit`` — a request that can NEVER be seated is a caller bug, not
load. The window is the LONGEST prompt a session takes, not what every
prompt costs: ``from_model`` compiles its prefill at the window and at
its half (``prefill_lengths``) and a prompt runs at the shortest length
that holds it. Overload is data, not an exception: a full queue or a missed
deadline produces a ``Result`` with finish_reason ``shed_capacity`` /
``shed_timeout``.

Knobs: ``TPUDL_SERVE_SLOTS`` (default slot count for ``from_model``,
artifact sessions carry theirs in the decode program's batch dim),
``TPUDL_SERVE_QUEUE_DEPTH`` (admission queue capacity),
``TPUDL_SERVE_PAGE_SIZE`` / ``TPUDL_SERVE_KV_DTYPE`` (page size and
optional int8 storage of ``from_model``'s KV pool — see
tpudl.serve.cache.PagedKVCache),
``TPUDL_SERVE_PREFIX_SHARE`` (radix prefix-sharing KV — COW page
sharing + chunked suffix prefill), ``TPUDL_SERVE_SPEC_K``
(speculative decoding window; 0/unset = off — see
tpudl.serve.speculate).

Streaming: ``session.stream(requests)`` yields ``StreamChunk``s as
tokens are selected (the router's per-request streaming feed) instead
of collect-at-eos; a request's concatenated chunk tokens are
byte-identical to the ``Result.tokens`` submit/collect returns.
"""

from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.analysis.registry import env_flag, env_int, env_str
from tpudl.obs import registry
from tpudl.obs import requestlog
from tpudl.obs.spans import (
    CAT_ENCLOSING,
    active_recorder,
    startup_phase,
    startup_recorder,
    startup_span,
)
from tpudl.serve.cache import (
    PagedKVCache,
    _is_attn_cache,
    _is_pool,
    _is_valid_leaf,
)
from tpudl.serve.queue import CAT_SERVE_REQUEST, AdmissionQueue
from tpudl.serve.weights import held


@dataclasses.dataclass
class Request:
    """One generation request. ``seed`` drives the per-request sampling
    stream (token t uses ``fold_in(key(seed), t)``), so a sampled
    request reproduces its tokens regardless of batch composition;
    ``temperature=0`` is greedy argmax, identical to ``generate()``.
    ``deadline_s`` is relative seconds from submit — a request not
    SEATED by then is shed (running requests are never aborted)."""

    request_id: Any
    input_ids: Sequence[int]
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    temperature: float = 0.0
    seed: int = 0
    priority: int = 0
    deadline_s: Optional[float] = None
    #: Sticky-placement key for the multi-replica router: requests
    #: sharing a session_key land on the same replica (prefix/KV
    #: affinity). None = place purely by load.
    session_key: Optional[Any] = None
    #: Multi-tenant adapter serving (tpudl.serve.lora): which tenant's
    #: LoRA adapter decodes this request. None = the plain base model.
    #: Flows through admission, placement (router adapter affinity +
    #: per-tenant quotas/SLO classes), and migration payloads (failover
    #: re-pins the adapter on the target replica).
    tenant: Optional[str] = None


@dataclasses.dataclass
class Result:
    """Outcome of one request. ``tokens`` are the generated ids,
    INCLUDING the eos that ended generation (no padding — compare
    against a ``generate()`` row by prefix). finish_reason:
    ``eos`` | ``length`` | ``shed_timeout`` | ``shed_capacity`` |
    ``shed_slo`` | ``failover_exhausted`` (the router's per-request
    failover-resubmission cap ran out — see
    ``TPUDL_SERVE_MAX_FAILOVERS``) | ``failed: ...`` (a mid-prefill
    exception, or a migration payload that could not be resumed —
    corrupt transfers are shed here, never resumed silently)."""

    request_id: Any
    tokens: List[int]
    finish_reason: str
    ttft_s: Optional[float] = None
    tpot_s: Optional[float] = None
    queue_wait_s: Optional[float] = None

    @property
    def ok(self) -> bool:
        return self.finish_reason in ("eos", "length")


@dataclasses.dataclass
class StreamChunk:
    """One increment of a streamed request: ``tokens`` selected since
    the previous chunk. The last chunk has ``done=True`` and carries
    the final ``Result`` (whose ``tokens`` are the full sequence — the
    authoritative value; concatenated chunk tokens equal it exactly).
    Shed requests stream a single empty ``done`` chunk."""

    request_id: Any
    tokens: List[int]
    done: bool
    result: Optional[Result] = None


def validate_request(request: Request, prompt_len: int, max_seq_len: int) -> None:
    """Admission validation shared by ``ServeSession.submit`` and the
    router: raise ValueError for a request that can never be served at
    the compiled shapes. A bad request must be rejected at the door —
    admitted past it, it would kill a prefill worker thread or block an
    engine's disaggregation inbox forever."""
    n = len(request.input_ids)
    if n < 1:
        raise ValueError("input_ids must hold at least one token")
    if n > prompt_len:
        raise ValueError(
            f"prompt length {n} exceeds the session's compiled "
            f"prompt window {prompt_len} (rejected at admission)"
        )
    if request.max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {request.max_new_tokens}"
        )
    if prompt_len + request.max_new_tokens > max_seq_len:
        raise ValueError(
            f"prompt window ({prompt_len}) + max_new_tokens "
            f"({request.max_new_tokens}) exceeds max_seq_len "
            f"{max_seq_len} (the KV-cache bound) — rejected at "
            f"admission"
        )
    if request.temperature < 0.0:
        raise ValueError(
            f"temperature must be >= 0, got {request.temperature}"
        )
    if not 0 <= request.seed < 2**32:
        # The engine carries seeds as uint32; an out-of-range seed
        # would raise mid-serving (stranding every in-flight request)
        # instead of here at admission.
        raise ValueError(
            f"seed must fit uint32 [0, 2**32), got {request.seed}"
        )


#: Under about this many rows a batch-1 prefill costs one pass over the
#: weights however few rows it has (one v5e chip: 197 TFLOP/s over 819
#: GB/s is 240 operations a byte, and bfloat16 weights give one
#: operation a byte a row): a shorter program would buy nothing and
#: cost a compile.
PREFILL_FLOOR_ROWS = 256


def prefill_lengths(window: int) -> tuple:
    """The lengths ``from_model`` compiles its batch-1 prefill at,
    ascending: the prompt window and, where it is no lower than
    ``PREFILL_FLOOR_ROWS``, its half (512 -> (256, 512); 4096 ->
    (2048, 4096); 64 -> (64,)). One half and no more: every length is
    a program to trace, load and warm at set-up, the first extra
    length takes most of the padding away, and a quarter was measured
    to cost set-up more than it gave (PERF.md, PR 34)."""
    half, odd = divmod(int(window), 2)
    if odd or half < PREFILL_FLOOR_ROWS:
        return (int(window),)
    return (half, int(window))


def left_pad(input_ids, rows: int):
    """A prompt as the batch-1 prefill takes it: ``(ids, mask)``, both
    int32 ``[1, rows]``, the prompt at the right end under a mask of
    ones (positions come from the mask's running sum)."""
    ids = np.asarray(input_ids, np.int32)
    pad = rows - ids.shape[0]
    padded = np.concatenate([np.zeros(pad, np.int32), ids])[None, :]
    mask = np.concatenate(
        [np.zeros(pad, np.int32), np.ones(ids.shape[0], np.int32)]
    )[None, :]
    return padded, mask


def _find_layer(tree, is_layer=_is_pool) -> Optional[dict]:
    """First per-layer cache dict in a cache pytree: a page pool of
    the decode artifact, or (``_is_attn_cache``) the dense rows of the
    prefill artifact's same layer: the artifact-geometry probe
    ``from_artifacts`` reads shapes off."""
    from collections.abc import Mapping

    if isinstance(tree, Mapping):
        if is_layer(tree):
            return dict(tree)
        for value in tree.values():
            found = _find_layer(value, is_layer)
            if found is not None:
                return found
    return None


def _env_int(name: str, default: int) -> int:
    return env_int(name, default, min_value=1)


class ServeSession:
    """Synchronous submit()/collect() serving over the slot engine."""

    def __init__(
        self,
        prefill_call: Callable,
        decode_call: Callable,
        params: Any,
        cache: PagedKVCache,
        prompt_len: int,
        queue_capacity: Optional[int] = None,
        clock: Callable[[], float] = time.monotonic,
        continuous: bool = True,
        slo=None,
        chunk_prefill_call: Optional[Callable] = None,
        speculator=None,
        verify_call: Optional[Callable] = None,
        adapter_pool=None,
    ):
        # Deferred import: engine imports Request/Result from this
        # module.
        from tpudl.obs import exporter as obs_exporter
        from tpudl.serve.engine import Engine

        # Live telemetry: a serving process with TPUDL_OBS_PORT set
        # exposes /metrics, /healthz (engine slots/queue + SLO burn
        # state), and /snapshot while it runs.
        obs_exporter.maybe_start_from_env()
        self.queue = AdmissionQueue(
            capacity=queue_capacity
            if queue_capacity is not None
            else _env_int("TPUDL_SERVE_QUEUE_DEPTH", 256),
            clock=clock,
        )
        self.engine = Engine(
            prefill_call, decode_call, params, cache, self.queue,
            prompt_len, clock=clock, continuous=continuous,
            chunk_prefill_call=chunk_prefill_call,
            speculator=speculator, verify_call=verify_call,
            adapter_pool=adapter_pool,
        )
        if slo is not None:
            # A tpudl.obs.slo.SloMonitor: the engine feeds it
            # TTFT/TPOT/queue-wait and sheds while objectives burn;
            # /healthz flips 503 with the burning objective named.
            self.engine.attach_slo(slo)
            slo.register_as_health_source()
        self._pending_ids: set = set()
        #: Weakref to the live stream() generator — lets stream()
        #: distinguish an ACTIVE stream (raise) from a generator that
        #: was abandoned before its first iteration (a never-started
        #: frame runs no ``finally``, so only this reference can
        #: reclaim the engine's token feed).
        self._stream_gen = None
        #: No call of ``serve`` / ``stream`` has been made yet: the
        #: first one builds the decode program, and is recorded whole
        #: as ``startup.first_requests``.
        self._unserved = True

    # -- constructors --------------------------------------------------

    @classmethod
    @startup_phase("startup.from_model", lambda session: dict(
        slots=session.engine.num_slots,
        prompt_len=session.engine.prompt_len,
        lengths=list(session.engine.prefill_lengths),
    ))
    def from_model(
        cls,
        model,
        params,
        prompt_len: int,
        num_slots: Optional[int] = None,
        paged: Optional[bool] = None,
        page_size: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        num_pages: Optional[int] = None,
        weight_dtype: Optional[str] = None,
        prefix_share: Optional[bool] = None,
        spec_k: Optional[int] = None,
        draft_weight_dtype: str = "int8",
        draft_model=None,
        draft_params=None,
        adapters: Optional[Dict[str, Any]] = None,
        adapter_rank_max: Optional[int] = None,
        adapter_pages: Optional[int] = None,
        adapter_dtype: Optional[str] = None,
        adapter_alpha: float = 16.0,
        adapter_impl: str = "auto",
        mesh=None,
        **kwargs,
    ) -> "ServeSession":
        """Live-model session: jit the prefill/decode contracts (batch 1
        and batch ``num_slots`` respectively) and derive the cache
        template by abstract evaluation. ``prompt_len`` is the prompt
        WINDOW, the longest prompt the session takes: a window of 512
        or more is served by two prefill programs
        (``prefill_lengths``: the window and its half), a prompt
        runs at the shortest that holds it and reserves pages from that
        length on, and every length's prefill and seat are compiled
        (and run once, dry) before this returns. A shorter window has
        one program and compiles nothing until the first request.

        Parameters that lie whole on ONE TPU device are held as the
        serving programs read them (tpudl.serve.weights): the session's
        own tree keeps the head-split attention kernels turned
        (``[out, in]``, tpudl.models.turned), once, so that no program
        turns them over in every call; every other leaf is the
        caller's array, and ``params`` itself is not touched. No knob:
        a CPU run, host arrays, a mesh-committed or quantized tree and
        an adapter session keep the tree as given (gauges
        ``serve_weights_relaid_leaves`` / ``_bytes``).

        ``prefix_share=True`` (or ``TPUDL_SERVE_PREFIX_SHARE=1``) turns
        on the radix prefix cache: seating
        walks a tree of page-granular token-block hashes, maps every
        matched full page into the new slot's table copy-on-write for
        free, and prefills only the unshared suffix through the
        chunked prefill program — a shared system prompt is prefilled
        once per replica, then TTFT is O(unshared suffix) and resident
        capacity multiplies on top of int8 KV.

        ``spec_k=K`` (or ``TPUDL_SERVE_SPEC_K``)
        turns on speculative decoding: a DRAFT path proposes K tokens
        per slot (default: a quantized self-draft built by
        ``tpudl.quant`` at ``draft_weight_dtype``; pass
        ``draft_model``/``draft_params`` for a small companion model)
        and the target verifies the window in one slot-batched chunk
        dispatch — acceptance keeps the output distribution
        (tpudl.serve.speculate), gated by ``assert_serving_parity``'s
        teacher-forced margin mode.

        The KV cache is the paged pool (tpudl.serve.cache.PagedKVCache).
        ``kv_dtype="int8"`` (or ``TPUDL_SERVE_KV_DTYPE=int8``) stores
        pages quantized with per-(page, row, head) dequant scales fused
        into the decode gather — ~4x the resident slots per byte.
        ``page_size`` (``TPUDL_SERVE_PAGE_SIZE``, default 16) and
        ``num_pages`` (default: every slot can hold ``max_seq_len``)
        size the pool. ``mesh`` is the mesh ``params`` were committed
        to, if any (tpudl.fleet.meshrep.build_mesh_session passes it):
        the pools are committed to it before any program is built for
        them. ``paged`` selects nothing: it is accepted because the
        benchmark's configurations still pass ``"paged": true``.

        ``adapters={tenant: lora_tree}`` turns on MULTI-TENANT adapter
        serving (tpudl.serve.lora): the base model stays resident once
        while every tenant's LoRA A/B factors live in fixed-size paged
        pools — loaded lazily, LRU-evicted at refcount 0 under
        pressure, reloaded transparently — and each decode dispatch
        applies every slot's own adapter through ONE segmented-matmul
        dispatch per projection site (tpudl.ops.segmented_lora).
        ``Request.tenant`` picks the adapter (None = plain base).
        Composes with
        ``weight_dtype`` — the old lora/quantization mutual exclusion
        is lifted, since adapters ride OUTSIDE the base projections.
        ``adapter_rank_max`` (``TPUDL_SERVE_LORA_RANK``; default = the
        largest registered rank) bounds per-tenant rank,
        ``adapter_pages`` (``TPUDL_SERVE_LORA_PAGES``) sizes the pool,
        ``adapter_dtype="int8"`` (``TPUDL_SERVE_LORA_DTYPE``) stores
        pages quantized with per-page dequant scales. Parity contract:
        ``tpudl.serve.lora.assert_tenant_parity`` vs the sequential
        merged-adapter reference — exact for f32 pages, teacher-forced
        margin for int8.

        ``weight_dtype="int8"``/``"fp8_e4m3"`` (or
        ``TPUDL_SERVE_WEIGHT_DTYPE``) serves a QUANTIZED weight tree
        (tpudl.quant.quantize_model: attention/MLP projection kernels
        stored low precision with dequant fused into the contraction;
        norms/embeddings/head stay full) — the decode-TPOT lever that
        composes with the int8 KV cache above; already-quantized
        params pass through untouched. Parity contract:
        ``assert_serving_parity(..., atol=...)`` vs the full-precision
        model, same as the quantized-KV tier."""
        from tpudl.models.generate import (
            chunk_prefill_fn,
            lora_paged_decode_fn,
            lora_prefill_fn,
            named,
            paged_chunk_decode_fn,
            paged_decode_fn,
            prefill_fn,
        )

        if paged is not None and not paged:
            raise ValueError(
                "paged=False: the dense slot cache was removed, every "
                "session serves from the paged pool (PagedKVCache)"
            )
        if weight_dtype is None:
            weight_dtype = env_str("TPUDL_SERVE_WEIGHT_DTYPE")
        if weight_dtype is not None:
            from tpudl.quant import quantize_model

            with startup_span("startup.quantize"):
                model, params = quantize_model(model, params, weight_dtype)
        num_slots = (
            num_slots
            if num_slots is not None
            else _env_int("TPUDL_SERVE_SLOTS", 4)
        )
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if prefix_share is None:
            prefix_share = env_flag("TPUDL_SERVE_PREFIX_SHARE")
        if spec_k is None:
            spec_k = env_int("TPUDL_SERVE_SPEC_K")
            if spec_k == 0:
                spec_k = None
        if adapters is not None:
            if not adapters:
                raise ValueError(
                    "adapters={} registers no tenants — pass None to "
                    "serve the plain base model"
                )
            if prefix_share:
                raise ValueError(
                    "prefix_share cannot compose with per-tenant "
                    "adapters: k/v projections are tenant-adapted, so "
                    "identical prompt tokens produce DIFFERENT KV per "
                    "tenant — a shared page would be wrong for one of "
                    "them"
                )
            if spec_k:
                raise ValueError(
                    "spec_k cannot compose with per-tenant adapters "
                    "yet (the draft path has no adapter view)"
                )
        cfg = getattr(model, "cfg", None)
        streams = getattr(cfg, "hyper_streams", 0)
        registry().gauge("serve_hyper_streams").set(streams)
        if streams:
            # A residual of several vectors a token (hyper-connections)
            # is served from the paged pool, with its int8 store and
            # prefix sharing where its attention has them. What was
            # written for one vector a token says so here.
            for what, asked, why in (
                ("per-tenant adapters", adapters is not None,
                 "the adapter pool addresses the projections of "
                 "LlamaBlock, and HyperBlock takes no adapter view"),
                ("spec_k", spec_k,
                 "the verify step does not report its window's maps "
                 "(hyper_res_offdiag, hyper_res_sum_error)"),
                ("a mesh-committed session", mesh is not None,
                 "no sharding rule places the maps' parameters, and the "
                 "stream's constraint has run on no mesh"),
            ):
                if asked:
                    raise ValueError(
                        f"{what} is not wired to a residual stream of "
                        f"{streams} vectors a token (hyper_streams): {why}"
                    )
        passes = getattr(cfg, "loop_passes", 1)
        registry().gauge("serve_loop_passes").set(passes)
        if passes > 1:
            # A stack run several times over the same weights is served
            # from the paged pool, a k/v pool pair a (pass, layer) under
            # the one table, with its int8 store, prefix sharing and
            # migration. What was written for one pass a token says so.
            for what, asked, why in (
                ("per-tenant adapters", adapters is not None,
                 "the adapter pool addresses the projections of "
                 "LlamaBlock, and SandwichBlock takes no adapter view"),
                ("spec_k", spec_k,
                 "the verify step does not report its window's exit "
                 "distribution (loop_exit_pdf), and a draft would have "
                 "to run every pass to propose a token"),
                ("a mesh-committed session", mesh is not None,
                 "the exit gate has no sharding rule, and a looped "
                 "stack has run on no mesh"),
            ):
                if asked:
                    raise ValueError(
                        f"{what} is not wired to a stack run {passes} "
                        f"times a token (loop_passes): {why}"
                    )
        topk = getattr(cfg, "index_topk", 0)
        registry().gauge("serve_index_topk").set(topk)
        registry().gauge("serve_index_pools").set(
            cfg.indexer_types.count("full") if topk else 0
        )
        if topk and mesh is not None:
            # Learned sparse attention is served from the paged pool, a
            # second leaf (the indexer's keys) on the layers that have
            # an indexer, with its int8 store, prefix sharing and
            # migration (adapters and spec_k: latent attention's, below).
            raise ValueError(
                "a mesh-committed session is not wired to learned sparse "
                "attention (index_topk): no sharding rule places the "
                "indexer's headless key pool, and the gather of chosen "
                "rows has run on no mesh"
            )
        if getattr(cfg, "block", "llama") == "shortcut":
            # The shortcut double layer (two latent attentions and two
            # dense FFNs around one expert branch) is served from the
            # paged pool, two leaves a layer, with its int8 store.
            if adapters is not None:
                raise ValueError(
                    "per-tenant adapters are not wired to the shortcut "
                    "double layer: the adapter pool addresses ONE "
                    "attention and one dense MLP a layer, and this "
                    "layer has two of each around an expert branch"
                )
            if spec_k:
                raise ValueError(
                    "spec_k is not wired to the shortcut double layer: "
                    "the verify step does not report its window's "
                    "tokens per expert nor its choices of identity "
                    "experts"
                )
        if getattr(cfg, "attention", "gqa") == "mla" or (
            getattr(cfg, "num_experts", 0) > 0
        ):
            # What a latent (MLA) cache or routed experts are wired to:
            # the paged pool (one leaf a layer), its int8 store, prefix
            # sharing and migration. The rest says so here, in a
            # sentence, instead of failing on a shape further down.
            if adapters is not None:
                raise ValueError(
                    "per-tenant adapters are not wired to latent "
                    "attention or routed experts: the adapter pool "
                    "addresses the q/k/v/o and dense MLP projections of "
                    "the grouped-query block"
                )
            if spec_k:
                raise ValueError(
                    "spec_k is not wired to latent attention or routed "
                    "experts: the verify step does not report the "
                    "tokens per expert of its window"
                )
        if getattr(cfg, "window_layers", 0):
            # Window layers keep rings of pages under a table of their
            # own (PagedKVCache): what walks ONE table over every
            # layer, or steps several tokens a slot, says so here.
            for what, asked in (
                ("prefix_share", prefix_share), ("spec_k", spec_k),
                ("per-tenant adapters", adapters is not None),
                ("a mesh-committed pool", mesh is not None),
            ):
                if asked:
                    raise ValueError(
                        f"{what} is not wired to window layers: a "
                        f"sliding-attention layer's cache is a ring of "
                        f"its last {cfg.sliding_window} positions a "
                        f"slot, stepped one token at a time on one chip"
                    )
        # The weights as the serving programs read them
        # (tpudl.serve.weights): on one chip the session's own tree,
        # ``serving``, holds the head-split attention kernels turned,
        # and the contracts turn them back inside their programs. A
        # quantized tree and the tree under the adapter programs are
        # kept as given, as is whatever lies on no chip or on a mesh.
        serving, leaves, nbytes = params, 0, 0
        if adapters is None and getattr(cfg, "weight_dtype", None) is None:
            serving, leaves, nbytes = held(params)
        registry().gauge("serve_weights_relaid_leaves").set(leaves)
        registry().gauge("serve_weights_relaid_bytes").set(nbytes)
        pf = prefill_fn(model)
        prefill_call = jax.jit(pf)
        ids = jax.ShapeDtypeStruct((num_slots, prompt_len), jnp.int32)
        # The cache template is one prefilled row at every slot. The
        # row is traced through the jit, and at the batch-1 shape, that
        # serves the window's prompts: the jit keeps the trace, so the
        # model is traced once for its window and not twice (0.6 s of
        # set-up for 16 layers). Rows and their validity carry the
        # batch axis; the write index and a window layer's marker
        # (``[window]``) have none.
        row_ids = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
        with startup_span("startup.cache_template"):
            _, row, *_ = jax.eval_shape(
                prefill_call, serving, row_ids, row_ids
            )
        cache_template = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(
                (num_slots, *leaf.shape[1:]), leaf.dtype
            ) if leaf.ndim >= 2 else leaf,
            row,
        )
        speculator = None
        verify = None
        if kv_dtype is None:
            kv_dtype = env_str("TPUDL_SERVE_KV_DTYPE")
        cache = PagedKVCache(
            cache_template,
            page_size=(
                page_size
                if page_size is not None
                else _env_int("TPUDL_SERVE_PAGE_SIZE", 16)
            ),
            num_pages=num_pages,
            kv_dtype=kv_dtype,
            prefix_share=bool(prefix_share),
        )
        if mesh is not None:
            cache.commit(mesh)
        # Every program that takes the pool and returns its successor
        # donates it (PagedKVCache: the ownership rule). What the cache
        # knows of its pool (page size, int8 rows, committed to a mesh)
        # is static in the programs.
        pool_facts = (cache.page_size, cache.quantized)
        decode = jax.jit(
            paged_decode_fn(model, *pool_facts, sharded=cache.sharded),
            donate_argnums=(1,),
        )
        if adapters is not None:
            from tpudl.serve.lora import AdapterPool

            if adapter_rank_max is None:
                adapter_rank_max = env_int("TPUDL_SERVE_LORA_RANK")
            if adapter_pages is None:
                adapter_pages = env_int("TPUDL_SERVE_LORA_PAGES")
            if adapter_dtype is None:
                adapter_dtype = env_str("TPUDL_SERVE_LORA_DTYPE")
            if adapter_rank_max is None:
                # Default rank budget: the largest registered adapter
                # (probed off the trees before the pool exists — ranks
                # validate again at register).
                from tpudl.models.lora import as_flat_adapters

                ranks = [
                    int(jnp.shape(f["lora_a"])[-1])
                    for tree in adapters.values()
                    for f in as_flat_adapters(tree).values()
                ]
                if not ranks:
                    raise ValueError(
                        "no lora_a/lora_b leaves in any adapter tree"
                    )
                adapter_rank_max = max(ranks)
            pool = AdapterPool(
                model.cfg,
                r_max=adapter_rank_max,
                num_slots=num_slots,
                num_pages=adapter_pages,
                dtype=adapter_dtype,
            )
            for tenant, tree in adapters.items():
                pool.register(tenant, tree, alpha=adapter_alpha)
            kwargs["adapter_pool"] = pool
            decode = jax.jit(
                lora_paged_decode_fn(
                    model, *pool_facts, impl=adapter_impl,
                    sharded=cache.sharded,
                ),
                donate_argnums=(1,),
            )
        chunk_prefill = (
            jax.jit(chunk_prefill_fn(model)) if prefix_share else None
        )
        if spec_k:
            from tpudl.quant import quantize_model, weight_bytes_report
            from tpudl.serve.speculate import Speculator

            if draft_model is None:
                # Quantized SELF-draft: same architecture, low-precision
                # weights — agrees with the target on almost every
                # greedy token at a fraction of the bytes/dispatch.
                draft_model, draft_params = quantize_model(
                    model, params, draft_weight_dtype
                )
            elif draft_params is None:
                raise ValueError("draft_model needs draft_params")
            # The draft's OWN cache template: a companion model's KV
            # geometry (layers, kv-heads, head-dim) need not match the
            # target's — only the tokenizer must.
            _, draft_template = jax.eval_shape(
                prefill_fn(draft_model), draft_params, ids, ids
            )
            draft_cache = PagedKVCache(
                draft_template,
                page_size=cache.page_size,
                num_pages=num_pages,
            )
            if mesh is not None:
                draft_cache.commit(mesh)
            speculator = Speculator(
                jax.jit(named(
                    prefill_fn(draft_model), "tpudl_draft_prefill"
                )),
                jax.jit(
                    named(
                        paged_decode_fn(
                            draft_model, draft_cache.page_size, False,
                            sharded=draft_cache.sharded,
                        ),
                        "tpudl_draft_decode",
                    ),
                    donate_argnums=(1,),
                ),
                draft_params,
                draft_cache,
                k=spec_k,
                weight_bytes=weight_bytes_report(
                    draft_params
                )["total_bytes"],
            )
            verify = jax.jit(
                paged_chunk_decode_fn(
                    model, *pool_facts, sharded=cache.sharded
                ),
                donate_argnums=(1,),
            )
        if adapters is not None:
            prefill_call = jax.jit(lora_prefill_fn(model, impl=adapter_impl))
        session = cls(
            prefill_call, decode, serving, cache, prompt_len,
            chunk_prefill_call=chunk_prefill, speculator=speculator,
            verify_call=verify, **kwargs,
        )
        lengths = prefill_lengths(prompt_len)
        shapes_alone = any(
            isinstance(leaf, jax.ShapeDtypeStruct)
            for leaf in jax.tree.leaves(params)
        )
        if len(lengths) > 1 and adapters is None and not shapes_alone:
            # A caller's warm-up reaches only the lengths its prompts
            # pick, so the session makes every length's programs exist
            # itself, here. One length stays where there is nothing
            # more to make or nothing to run it with: a window too
            # short to halve (its one program compiled by the first
            # request, as before), the adapter prefill (three more
            # traced inputs), and parameters that are shapes alone (a
            # compile rehearsal for a described chip: it serves
            # nothing and lowers the programs at the shapes it names).
            session.engine.compile_prefill_lengths(lengths)
        return session

    @classmethod
    def from_artifacts(
        cls,
        prefill_blob_or_path,
        decode_blob_or_path,
        params,
        **kwargs,
    ) -> "ServeSession":
        """Artifact session over the pair ``export_serving_decoder``
        writes: every engine shape is recovered from the deserialized
        programs — slot count from the decode input avals, prompt
        window and sequence bound from the prefill's; page size, pool
        size, per-slot page span and int8 quantization from the
        pool/page-table avals, so the paged-KV contract round-trips
        through StableHLO with no side-channel metadata. A decode
        artifact of another contract (the dense pair ``export_decoder``
        writes for offline generation) raises."""
        from tpudl.export.export import load_exported_obj

        pre = load_exported_obj(prefill_blob_or_path)
        dec = load_exported_obj(decode_blob_or_path)
        (pre_args, _) = jax.tree.unflatten(pre.in_tree, pre.in_avals)
        (dec_args, _) = jax.tree.unflatten(dec.in_tree, dec.in_avals)
        _, ids_aval, _ = pre_args
        if len(dec_args) != 7:
            raise ValueError(
                f"decode artifact takes {len(dec_args)} arguments, not "
                f"the 7 of the paged decode contract (params, pools, "
                f"token, position, page_table, start, lens) — a session "
                f"serves from the paged pool; export with "
                f"tpudl.export.decode.export_serving_decoder"
            )
        if ids_aval.shape[0] != 1:
            raise ValueError(
                f"serving prefill artifact must be batch-1 (one request "
                f"seated at a time), got batch {ids_aval.shape[0]} — "
                f"export with tpudl.export.decode.export_serving_decoder"
            )
        prompt_len = int(ids_aval.shape[1])
        _, pools, token_aval, _, table_aval, _, _ = dec_args
        pool = _find_layer(pools)
        if pool is None:
            raise ValueError(
                "paged decode artifact carries no page-pool cache "
                "(no pages_<name> leaf in its cache avals)"
            )
        # The model's compiled sequence bound lives in the PREFILL
        # artifact's dense row-cache outputs ([1, max_seq_len]
        # validity rows): when page_size does not divide it, the
        # page span rounds past the model's position space and the
        # cache must clamp admission exactly like the live path.
        _, pre_cache = jax.tree.unflatten(pre.out_tree, pre.out_avals)
        model_bound = next(
            (
                int(leaf.shape[1])
                for leaf in jax.tree.leaves(pre_cache)
                if _is_valid_leaf(leaf)
            ),
            None,
        )
        name, pages = next(
            (k, v) for k, v in pool.items() if k.startswith("pages_")
        )
        # A pool held folded (tpudl.models.paged.page_fold) has
        # page_size / f rows of f positions to a page: the prefill's
        # dense row of the same leaf says how wide ONE position is.
        row = _find_layer(pre_cache, _is_attn_cache)[name[len("pages_"):]]
        fold = 1 if pages.ndim != 3 else int(pages.shape[2] // row.shape[2])
        cache = PagedKVCache.from_pool_template(
            pools,
            num_slots=int(token_aval.shape[0]),
            pages_per_slot=int(table_aval.shape[1]),
            page_size=int(pages.shape[1]) * fold,
            quantized=any(k.startswith("scale_") for k in pool),
            num_pages=int(pages.shape[0]),
            model_seq_len=model_bound,
        )
        # An artifact's call donates nothing by itself: the pool rule
        # (PagedKVCache) is applied where the artifact is loaded.
        return cls(
            pre.call, jax.jit(dec.call, donate_argnums=(1,)), params,
            cache, prompt_len, **kwargs,
        )

    # -- introspection -------------------------------------------------

    @property
    def num_slots(self) -> int:
        return self.engine.num_slots

    @property
    def prompt_len(self) -> int:
        return self.engine.prompt_len

    @property
    def max_seq_len(self) -> int:
        return self.engine.max_seq_len

    # -- the request lifecycle -----------------------------------------

    def submit(self, request: Request) -> Any:
        """Admit one request. Raises ValueError for requests that can
        never be served at this session's compiled shapes; records a
        ``shed_capacity`` Result when the queue is full. Returns the
        request_id either way."""
        rid = request.request_id
        if rid in self._pending_ids or rid in self.engine.results:
            raise ValueError(f"duplicate request_id {rid!r}")
        validate_request(request, self.prompt_len, self.max_seq_len)
        if request.tenant is not None:
            pool = self.engine.adapter_pool
            if pool is None:
                raise ValueError(
                    f"request {rid!r} names tenant {request.tenant!r} "
                    f"but this session serves no adapters (build it "
                    f"with ServeSession.from_model(adapters=...))"
                )
            if not pool.knows(request.tenant):
                raise ValueError(
                    f"unknown tenant {request.tenant!r} — register its "
                    f"adapter before submitting (known: "
                    f"{sorted(map(str, pool.tenants))})"
                )
        self._pending_ids.add(rid)
        admitted = self.queue.push(
            request, priority=request.priority, deadline_s=request.deadline_s
        )
        if not admitted:
            self.engine.results[rid] = Result(
                request_id=rid, tokens=[], finish_reason="shed_capacity",
                queue_wait_s=0.0,
            )
            registry().counter("serve_requests_shed_capacity").inc()
            rec = active_recorder()
            if rec is not None:
                # Capacity sheds never reach the queue, so their trace
                # is a single completion event (queue_wait 0).
                rec.event(
                    "request_complete", CAT_SERVE_REQUEST, request_id=rid,
                    finish_reason="shed_capacity", queue_wait_s=0.0,
                    num_tokens=0,
                )
            requestlog.log_result(requestlog.build_record(
                rid, "shed_capacity", site="session",
                tenant=request.tenant,
                tokens_in=len(request.input_ids), queue_wait_s=0.0,
            ))
        return rid

    def collect(self) -> Dict[Any, Result]:
        """Run the engine until every submitted request has a Result,
        then hand them over (and flush a counters snapshot onto the
        active obs stream, if recording)."""
        self.engine.run_until_drained()
        out = {
            rid: self.engine.results.pop(rid) for rid in self._pending_ids
        }
        self._pending_ids.clear()
        # collect() finishes work an abandoned stream() admitted; that
        # generator never ran, so release its token feed here (a live
        # generator releases its own and ignores this — it checks feed
        # ownership before touching the engine).
        self.engine.on_token = None
        rec = active_recorder()
        if rec is not None:
            rec.counters(registry().snapshot())
        return out

    def _first_requests_began(self) -> Optional[float]:
        """The start-up clock's reading where this call of ``serve`` /
        ``stream`` is the session's first, else None (asked once a
        call)."""
        if not self._unserved:
            return None
        self._unserved = False
        return startup_recorder().clock()

    @staticmethod
    def _first_requests_made(began: float) -> None:
        """The session's first requests, whole (the decode program is
        built on the way), as ``startup.first_requests``: recorded after
        the fact, so that a recorder's tree of the hot path is the same
        on a first call as on any other, and as an ENCLOSING span, since
        it lies around the steps and programs of that call on the same
        clock and would count their seconds a second time."""
        rec = startup_recorder()
        rec.record(
            "startup.first_requests", CAT_ENCLOSING, began,
            rec.clock() - began,
        )

    def serve(self, requests: Sequence[Request]) -> Dict[Any, Result]:
        """submit() them all, collect() once — the closed-loop shape."""
        began = self._first_requests_began()
        for request in requests:
            self.submit(request)
        out = self.collect()
        if began is not None:
            self._first_requests_made(began)
        return out

    def stream(
        self,
        requests: Sequence[Request] = (),
        chunk_tokens: int = 1,
    ):
        """Incremental serving: submit ``requests`` (already-submitted
        pending work streams too) and yield ``StreamChunk``s as tokens
        are selected, interleaved across every in-flight request, until
        all pending requests have completed. The final chunk per
        request carries its ``Result``; concatenating a request's chunk
        tokens reproduces ``Result.tokens`` exactly (same engine, same
        selection — streaming changes delivery, not generation).

        ``chunk_tokens`` batches the yield granularity (1 = one chunk
        per token, the TTFT-faithful default). Validation, submission,
        and claiming the engine's token feed all happen HERE at call
        time (misuse — chunk_tokens=0, two concurrent streams — raises
        at the call site, and requests are admitted even if the caller
        abandons the generator un-iterated; collect() finishes them).
        Only token delivery is lazy: breaking out mid-iteration leaves
        undelivered work pending and releases the feed."""
        if chunk_tokens < 1:
            raise ValueError(
                f"chunk_tokens must be >= 1, got {chunk_tokens}"
            )
        began = self._first_requests_began()
        if self.engine.on_token is not None:
            prior = self._stream_gen() if self._stream_gen else None
            if prior is None or prior.gi_frame is None:
                # The feed belongs to a stream() generator that can
                # never release it: GC'd (weakref dead), or finished /
                # close()d before its first iteration — gi_frame is
                # None only once a generator completes, and closing an
                # UNSTARTED generator finishes it without ever entering
                # the try, so its ``finally`` never ran. Reclaim the
                # feed; collect() finishes the work it admitted. (An
                # alive, merely un-iterated generator keeps its claim —
                # it can still be driven — and a second stream() then
                # raises below.)
                self.engine.on_token = None
            else:
                raise RuntimeError(
                    "a stream() is already active on this session"
                )
        buf: Dict[Any, List[int]] = {}

        def sink(rid, token):
            buf.setdefault(rid, []).append(token)

        self.engine.on_token = sink
        try:
            for request in requests:
                self.submit(request)
        except BaseException:
            self.engine.on_token = None
            raise
        gen = self._stream_chunks(buf, chunk_tokens, sink, began)
        self._stream_gen = weakref.ref(gen)
        return gen

    def _stream_chunks(
        self, buf: Dict[Any, List[int]], chunk_tokens: int, sink,
        began: Optional[float] = None,
    ):
        """The lazy half of ``stream()`` (which owns validation and
        submission): step the engine and yield chunks until every
        pending request completes, then release the token feed — but
        only while this generator still OWNS the feed (``sink``); a
        stale generator whose feed was reclaimed stops silently rather
        than stepping the engine under the new owner."""
        try:
            while self._pending_ids:
                if self.engine.on_token is not sink:
                    return
                progressed = self.engine.step()
                finished = [
                    rid for rid in list(self._pending_ids)
                    if rid in self.engine.results
                ]
                for rid in finished:
                    result = self.engine.results.pop(rid)
                    self._pending_ids.discard(rid)
                    yield StreamChunk(
                        rid, buf.pop(rid, []), True, result
                    )
                for rid, toks in list(buf.items()):
                    if len(toks) >= chunk_tokens:
                        buf[rid] = []
                        yield StreamChunk(rid, toks, False, None)
                if not progressed and not finished and self._pending_ids:
                    raise RuntimeError(
                        f"engine drained with requests still pending "
                        f"(no Result for {sorted(map(str, self._pending_ids))})"
                    )
        finally:
            if self.engine.on_token is sink:
                self.engine.on_token = None
            if began is not None:
                # From the call of ``stream`` to the last chunk.
                self._first_requests_made(began)
        rec = active_recorder()
        if rec is not None:
            rec.counters(registry().snapshot())


def assert_serving_parity(
    session: ServeSession,
    model,
    params,
    requests: Sequence[Request],
    atol: Optional[float] = None,
) -> None:
    """Assert every GREEDY request's engine tokens match live
    ``generate()`` run on the request alone — the artifact-vs-live
    interchangeability check (a Result's tokens are the generate row up
    to and including eos; generate pads with eos after).

    ``atol=None`` (exact mode) demands token-for-token equality — the
    f32 contract. ``atol`` set is the QUANTIZED-cache
    contract ("parity at tolerance"): an int8 KV cache perturbs logits
    by a bounded dequantization error, so greedy argmax may flip — but
    ONLY at a genuine near-tie. The check walks the tokens and, at the
    first divergence, teacher-forces the reference sequence through the
    model to measure how far the reference's choice beats the token the
    engine ACTUALLY produced at that step: a margin
    within ``atol`` is a legitimate quantization flip (the
    autoregressive paths legitimately differ after it — comparison
    stops); a wide margin means the cache returned wrong values and the
    assert fires. A real paging/dequant bug diverges immediately at
    wide margins, so the tolerance mode still catches it."""
    results = session.serve(list(requests))
    for req in requests:
        if req.temperature != 0.0:
            continue
        res = results[req.request_id]
        assert res.ok, (req.request_id, res.finish_reason)
        assert_tokens_match_generate(
            model, params, req, np.asarray(res.tokens), atol
        )


def assert_tokens_match_generate(model, params, req, got, atol) -> None:
    """The per-request half of ``assert_serving_parity`` (factored so
    the multi-tenant gate — tpudl.serve.lora.assert_tenant_parity,
    whose REFERENCE params differ per request — reuses the exact same
    rule): compare one greedy request's engine tokens against live
    ``generate()`` on ``params``, exactly (``atol=None``) or under the
    teacher-forced logit-margin contract."""
    from tpudl.models.generate import generate

    want = np.asarray(
        generate(
            model, params,
            jnp.asarray(req.input_ids, jnp.int32)[None, :],
            max_new_tokens=req.max_new_tokens,
            eos_id=req.eos_id,
        )
    )[0]
    got = np.asarray(got)
    if atol is None:
        np.testing.assert_array_equal(
            got, want[: got.shape[0]],
            err_msg=f"request {req.request_id} diverged from "
                    f"generate()",
        )
        if req.eos_id is not None and got.shape[0] < want.shape[0]:
            assert np.all(want[got.shape[0]:] == req.eos_id), (
                f"request {req.request_id}: engine stopped at eos "
                f"but generate() kept producing non-eos tokens"
            )
        return
    n = min(got.shape[0], want.shape[0])
    mismatches = np.nonzero(got[:n] != want[:n])[0]
    if mismatches.size == 0:
        return
    t = int(mismatches[0])
    # Teacher-force the reference path up to the diverging step and
    # measure how contested the reference's choice actually was.
    prompt = np.asarray(req.input_ids, np.int32)
    prefix = np.concatenate([prompt, want[:t].astype(np.int32)])
    logits = model.apply(
        {"params": params}, jnp.asarray(prefix)[None, :]
    )
    last = np.asarray(logits[0, -1], np.float32)
    margin = float(last[int(want[t])] - last[int(got[t])])
    assert margin <= atol, (
        f"request {req.request_id}: diverged from generate() at "
        f"step {t} where the reference prefers token {want[t]} "
        f"over the engine's {got[t]} by logit margin {margin:.4f} "
        f"> atol={atol} — that is a cache bug, not a quantization "
        f"near-tie"
    )
