"""Slot-based continuous batching over the two compiled decode programs.

The whole engine is host orchestration around exactly two XLA
executables — the batch-1 prefill and the slot-batched single-token
paged decode that tpudl.models.generate defines and tpudl.export.decode
serializes (``(params, ids, mask) -> (logits, cache)`` and
``(params, pools, token, position, page_table, start, lens) ->
(logits, pools)``). Requests are multiplexed onto them through a
fixed-slot cache:

    queue ──pop──▶ prefill(batch=1) ──insert──▶ slot i of the cache
                                                    │
                 every step: decode(batch=slots) ───┘  finished slot →
                 emit per-slot token, advance         Result out,
                 per-slot position                    refill from queue

A slot that finishes (eos / max tokens) is refilled IMMEDIATELY —
mid-stream, while its neighbors keep decoding — which is the whole
trick: a ragged batch never waits for its longest row
(``continuous=False`` disables exactly this refill, turning the same
engine into the run-to-completion static-batch baseline the load
benchmark compares against).

Why mid-stream insertion is correct: the new row's pages hold only its
own prompt, and every per-row op is batch-independent, so neighbors are
bit-unaffected (see tpudl.serve.cache).

The cache is a tpudl.serve.cache.PagedKVCache over tpudl.models.paged
pools: each slot carries its own length and decode writes through a
host-owned page table (three small traced inputs of the decode
contract, ``paged_decode_fn``: page table + start + lens), so slots
share no write position and a finished slot's pages recycle piecewise.
Admission is ``fits_tokens``: are enough free pages left to reserve the
request's worst case up front.

Two hooks the multi-replica router (tpudl.serve.router) builds on:
``on_token`` (called per (request_id, token) as the host reads it
back — the streaming feed) and ``prefill_inbox`` (externally prefilled requests:
a dedicated prefill replica runs the batch-1 program and hands the row
cache over; this engine only seats and decodes — prefill/decode
disaggregation over the same mid-stream insertion contract).

Sampling is per-request and batch-composition-independent: token ``t``
of a request is drawn with ``fold_in(key(request.seed), t)``, so the
same request yields the same tokens whatever its neighbors are — a
reproducibility property the batched ``generate()`` rng stream does not
have (greedy requests match ``generate()`` token for token; sampled
ones match themselves across engine runs and artifact/live backends).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs import registry
from tpudl.obs import requestlog
from tpudl.obs.spans import active_recorder, startup_span
from tpudl.serve.api import Request, Result, left_pad
from tpudl.serve.cache import (
    MigrationCompatError,
    MigrationCorruptError,
    PagedKVCache,
)
from tpudl.serve.queue import CAT_SERVE_REQUEST, AdmissionQueue, _Entry

#: Span categories (their own rows in the obs report breakdown table).
#: One ``engine_step`` span encloses a step's ``admit`` (which encloses
#: the ``prefill.dispatch`` and ``seat`` spans it causes),
#: ``decode_prepare``, ``decode_step``, ``emit`` and a ``prefill``
#: (around ``prefill.readback``) for every first token the step reads
#: back: the wait for it, wherever the engine waits. ``decode_step``
#: encloses ``decode.dispatch`` (around ``decode.address``) and
#: ``decode.readback``; in a call that lands a step already in flight
#: it is opened first and encloses ``admit`` and ``decode_prepare``
#: too, so that the landing step's device time lies inside it. A child
#: of its parent's category is counted once in a sum over that
#: category: ``serve_prefill`` is the waits for first tokens
#: (``prefill.dispatch`` is host work of ``admit``, ``serve_engine``).
CAT_SERVE_ENGINE = "serve_engine"
CAT_SERVE_PREFILL = "serve_prefill"
CAT_SERVE_SEAT = "serve_seat"
CAT_SERVE_DECODE = "serve_decode"
CAT_SERVE_EMIT = "serve_emit"


# Selection programs under names of their own (a trace's ``XLA
# Modules`` line reads ``jit_tpudl_select``), their operations under
# the ``select`` scope.


def tpudl_select(logits):
    """Argmax-only selection: the fast path when no active slot samples
    (temperature 0 is the default) — skips the per-slot key derivation
    and the O(slots x vocab) categorical draw `_select_tokens` would
    compute just to discard. Same f32 argmax, bit-identical tokens."""
    with jax.named_scope("select"):
        return jnp.argmax(
            logits.astype(jnp.float32), axis=-1
        ).astype(jnp.int32)


def tpudl_select_sampled(logits, temps, seeds, steps):
    """Per-slot next-token selection on [B, V] logits: greedy argmax
    where ``temps[i] == 0``, else categorical over temperature-scaled
    logits keyed by ``fold_in(key(seeds[i]), steps[i])`` — the stream
    that makes sampling per-request deterministic regardless of which
    slot or neighbors the request has. f32 selection math like
    tpudl.models.generate._select_impl."""
    with jax.named_scope("select"):
        logits = logits.astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        keys = jax.vmap(
            lambda s, t: jax.random.fold_in(jax.random.key(s), t)
        )(seeds, steps)
        scaled = logits / jnp.where(temps > 0, temps, 1.0)[:, None]
        sampled = jax.vmap(jax.random.categorical)(
            keys, scaled
        ).astype(jnp.int32)
        return jnp.where(temps > 0, sampled, greedy)


_select_greedy = jax.jit(tpudl_select)
_select_tokens = jax.jit(tpudl_select_sampled)


def _selection_sharding(params):
    """Where the selection leaves a step's tokens: on every device that
    holds the parameters, whole, if the parameters are committed there
    (a replica put on a device of its own, a mesh); None if they are
    committed nowhere, and neither are then the tokens. Tokens that the
    host hands a decode step are put the same way (``Engine._dispatch``),
    so that a step that takes them from the device is the same program
    to jit and compiles nothing."""
    leaf = next(iter(jax.tree.leaves(params)), None)
    if not getattr(leaf, "committed", False):
        return None
    sharding = leaf.sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(
            sharding.mesh, jax.sharding.PartitionSpec()
        )
    return sharding


def select_first(logits, request):
    """A request's FIRST token selected from its batch-1 prefill logits
    (step 0 of its per-request sampling stream), int32 [1] ON THE
    DEVICE: nothing is read back. Shared by the engine's seat, which
    leaves it there for the next decode step, and ``first_token``."""
    if request.temperature > 0:
        return _select_tokens(
            logits,
            np.float32([request.temperature]),
            np.uint32([request.seed]),
            np.int32([0]),
        )
    return _select_greedy(logits)


def first_token(logits, request):
    """``select_first`` read back at once: the router's dedicated
    prefill workers hand a host token over with the row, so
    disaggregated serving draws the tokens the engine's own seat
    draws. The engine itself never calls this (``Engine._seat``)."""
    return int(jax.device_get(select_first(logits, request))[0])


def tpudl_first_token(tokens, slot, first):
    """A decode step's token vector (int32 [slots]) with ``slot``'s
    entry taken from a prefill's selection (int32 [1]) as it lies on
    the device. ``slot`` is traced: one program whichever slot and
    however many seats a call makes."""
    return jax.lax.dynamic_update_slice(tokens, first, (slot,))


_set_first = jax.jit(tpudl_first_token)


def record_expert_load(
    counts=None, chose=None, hyper=None, loop=None, sparse=None
) -> dict:
    """The statistics a model's layers sowed in one prefill or decode
    step, on the host, in the places the serving contracts return them
    (tpudl.models.generate): counted into the registry and returned as
    the step's span attributes. Each is None for a model without it.

    ``counts``: int [expert layers, experts held], the real tokens each
    held expert got (tpudl.ops.moe.DroplessMoE): ``moe_assignments``
    (their sum),
    ``moe_experts_touched`` (held experts, over the layers, that got a
    token: those whose weights the step had to read) and
    ``moe_load_max_over_mean`` (the busiest held expert's tokens over
    the mean, the worse layer's; 1.0 is even).

    ``chose`` (a model with identity experts): int [expert layers,
    experts_per_token + 1], the real tokens by how many REAL experts
    they chose. From it ``moe_real_assignments`` (choices that name a
    real expert, held here or elsewhere), ``moe_zero_assignments``
    (choices that name an identity expert and cost nothing) and
    ``moe_real_experts_a_token`` (the tokens by real experts chosen,
    the layers summed); counters of the first two names under
    ``serve_``, and each layer's mean real experts a token into the
    histogram ``serve_moe_real_experts_a_token`` (one observation a
    layer, not one a token: this runs between decode steps).

    ``hyper`` (a model whose residual is several streams a token):
    float [2 x layers, 3], a sublayer's maps a row
    (tpudl.models.hyper.HYPER_STAT_NAME). ``hyper_res_offdiag``: the
    mean over the real tokens, sublayers and layers of the mass
    ``H_res`` puts off its diagonal (0: streams kept apart; ``1 -
    1/n``: fully mixed), also observed into the histogram
    ``serve_hyper_res_offdiag``; ``hyper_res_sum_error``: the largest
    ``|column sum - 1|`` the Sinkhorn iterations left.

    ``loop`` (a stack run several times over the same weights): float
    [1, passes + 1], the exit distribution's mass at each pass summed
    over the real tokens, and how many they were
    (tpudl.models.llama.LOOP_STAT_NAME). ``loop_passes``: the passes
    every token ran; ``loop_exit_pdf``: the mean exit distribution over
    the real tokens, a number a pass; its last entry (the mass that no
    earlier pass's gate let go) is observed into the histogram
    ``serve_loop_exit_last_pass_mass``.

    ``sparse`` (learned sparse attention): int [layers with an indexer,
    2], the positions the call's real queries attended and the
    positions they could see (tpudl.models.llama.SPARSE_STAT_NAME),
    alike in every such layer. ``sparse_rows_chosen`` /
    ``sparse_rows_live``: one layer's pair; ``index_layers``: how many
    layers chose; chosen over live is observed into the histogram
    ``serve_sparse_chosen_share`` (1.0: nothing was skipped)."""
    attrs = {}
    if sparse is not None:
        sparse = np.asarray(sparse)
        chosen, live = int(sparse[0, 0]), int(sparse[0, 1])
        if live:
            registry().histogram("serve_sparse_chosen_share").observe(
                chosen / live
            )
        attrs.update(
            sparse_rows_chosen=chosen, sparse_rows_live=live,
            index_layers=len(sparse),
        )
    if loop is not None:
        loop = np.asarray(loop, np.float64)[0]
        pdf = (loop[:-1] / loop[-1] if loop[-1] else loop[:-1]).tolist()
        registry().histogram("serve_loop_exit_last_pass_mass").observe(
            pdf[-1]
        )
        attrs.update(loop_passes=len(pdf), loop_exit_pdf=pdf)
    if hyper is not None:
        hyper = np.asarray(hyper, np.float64)
        tokens = hyper[:, 1].sum()
        off = float(hyper[:, 0].sum() / tokens) if tokens else 0.0
        registry().histogram("serve_hyper_res_offdiag").observe(off)
        attrs.update(
            hyper_res_offdiag=off,
            hyper_res_sum_error=float(hyper[:, 2].max()),
        )
    if counts is None:
        return attrs
    counts = np.asarray(counts)
    total = int(counts.sum())
    reg = registry()
    reg.counter("serve_moe_assignments").inc(total)
    per_expert = reg.histogram("serve_moe_tokens_per_expert")
    for n in counts.ravel().tolist():
        per_expert.observe(n)
    means = counts.mean(axis=1)
    skew = [
        float(row.max() / mean) for row, mean in zip(counts, means) if mean > 0
    ]
    attrs.update(
        moe_assignments=total,
        moe_experts_touched=int((counts > 0).sum()),
        moe_load_max_over_mean=max(skew) if skew else 0.0,
    )
    if chose is not None:
        chose = np.asarray(chose)
        k = chose.shape[1] - 1
        by_layer = chose @ np.arange(k + 1)  # real choices a layer
        tokens = chose.sum(axis=1)
        real = int(by_layer.sum())
        zero = int(tokens.sum()) * k - real
        reg.counter("serve_moe_real_assignments").inc(real)
        reg.counter("serve_moe_zero_assignments").inc(zero)
        a_token = reg.histogram("serve_moe_real_experts_a_token")
        for n, of in zip(by_layer.tolist(), tokens.tolist()):
            if of:
                a_token.observe(n / of)
        attrs.update(
            moe_real_assignments=real, moe_zero_assignments=zero,
            moe_real_experts_a_token=chose.sum(axis=0).tolist(),
        )
    return attrs


class _Prefilled:
    """One externally prefilled request awaiting a decode slot: the
    handoff unit of prefill/decode disaggregation (built by the
    router's PrefillWorker, drained by ``Engine._fill_slots``)."""

    __slots__ = (
        "entry", "row_cache", "first_token", "prompt_ids_len",
        "t_popped", "t_first", "rows",
    )

    def __init__(self, entry: _Entry, row_cache: Any, first_token: int,
                 prompt_ids_len: int, t_popped: float, t_first: float,
                 rows: int):
        self.entry = entry
        self.row_cache = row_cache
        self.first_token = first_token
        self.prompt_ids_len = prompt_ids_len
        self.t_popped = t_popped  # queue wait ended here (prefill start)
        self.t_first = t_first  # first token selected here (TTFT end)
        self.rows = rows  # the length the prompt was padded to


class _Slot:
    """Host-side state of one occupied decode slot. ``tokens`` is empty
    and ``first`` set while the slot's first token is still on the
    device (``_First``): the slot is seated and busy, its times
    ``t_first`` / ``t_last`` are not known yet."""

    __slots__ = (
        "entry", "request", "tokens", "first", "position", "steps",
        "t_seated", "t_first", "t_last", "gap_origin",
        "prefix_hit", "spec_proposed", "spec_accepted",
        "adapter_reloads", "migrations", "kv_base",
    )

    def __init__(self, entry: _Entry, prompt_len: int, seated: float,
                 kv_base: int = 0):
        self.entry = entry
        self.request: Request = entry.request
        self.tokens: List[int] = []
        self.first: Optional["_First"] = None
        self.position = prompt_len  # next absolute RoPE position
        # Tokens drawn so far (the sampling fold_in index): the first
        # is drawn at the seat, landed or not.
        self.steps = 1
        # Cache rows the slot held with its first token alone: with n
        # tokens it holds ``kv_base + n - 1``, whatever the cache's own
        # ``lens`` says while a step is in flight.
        self.kv_base = kv_base
        self.t_seated = seated  # pop time: queue wait ends HERE
        # First token out: TTFT ends here (incl. prefill), where the
        # host has it (``Engine._first_landed``).
        self.t_first: Optional[float] = None
        self.t_last: Optional[float] = None
        # Migrated slots: the SOURCE's last-token time, consumed when
        # the first post-migration token lands (the failover token-gap
        # histogram — how long the client's stream actually stalled).
        self.gap_origin: Optional[float] = None
        # Per-request usage accumulators for the terminal request-log
        # record (tpudl.obs.requestlog): what the span stream scatters
        # over prefill/decode events, gathered where the Result is
        # built.
        self.prefix_hit = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.adapter_reloads = 0
        self.migrations = 0


class _First:
    """A request's first token where its prefill's selection left it:
    on the device (``sel``, int32 [1]), beside what the prefill program
    returned after the logits and the row (``counts``). It is queued
    with the decode steps the device holds unread, in dispatch order
    (``Engine._unread``), feeds the slot's first decode step from the
    device, and is read back by ``Engine._land_first``. ``attrs``: what
    the ``prefill`` span around that read-back will say of the prefill
    (made at its dispatch; None where nothing records)."""

    __slots__ = ("slot", "state", "sel", "counts", "attrs")

    def __init__(self, sel, counts, attrs):
        self.slot: Optional[int] = None  # both set by ``_install``
        self.state: Optional[_Slot] = None
        self.sel = sel
        self.counts = counts
        self.attrs = attrs


class _Plan:
    """One decode step's inputs, made before its dispatch. ``rows[i]``
    is the ``_Slot`` whose row ``i`` the step computes, None for a row
    that rides idle. ``tokens``: the host's array, or with ``ahead``
    the selection of the step before as it lies on the device (int32
    [slots] either way, what ``decode_call`` takes); ``firsts``: the
    ``(slot, selection)`` of every row whose input is a first token
    still on the device, set into ``tokens`` there at the dispatch."""

    __slots__ = ("rows", "ahead", "tokens", "firsts", "positions", "temps",
                 "seeds", "steps", "pages_live")

    def __init__(self, num_slots: int, prev: Optional["_InFlight"]):
        self.rows: List[Optional[_Slot]] = [None] * num_slots
        self.ahead = prev is not None
        self.tokens = (
            prev.sel if self.ahead else np.zeros(num_slots, np.int32)
        )
        self.firsts: List[tuple] = []
        self.positions = np.zeros(num_slots, np.int32)
        self.temps = np.zeros(num_slots, np.float32)
        self.seeds = np.zeros(num_slots, np.uint32)
        self.steps = np.zeros(num_slots, np.int32)
        self.pages_live = 0


class _InFlight:
    """One decode step the device has been handed and the host has not
    read back: the tokens it selects (``sel``, int32 [slots], on the
    device) and what its program returned beside the logits
    (``extras``), the slots whose rows it computes (``rows``, as
    ``_Plan`` has them), whether its input tokens were the selection of
    the step before as it lay on the device (``ahead``), and what its
    ``decode_step`` span will say of it (``attrs``, made at the
    dispatch; None where nothing records)."""

    __slots__ = ("rows", "sel", "extras", "ahead", "attrs")

    def __init__(self, rows, sel, extras, ahead: bool, attrs):
        self.rows = rows
        self.sel = sel
        self.extras = extras
        self.ahead = ahead
        self.attrs = attrs


class _Migrated:
    """One migrated-in request awaiting a free slot: the payload bytes
    as transferred (crc verified lazily, ON the engine thread, so a
    corrupt transfer becomes a ``failed`` Result instead of a router
    crash) plus the radix lease the router pre-pinned on this cache."""

    __slots__ = ("rid", "payload", "lease", "meta")

    def __init__(self, rid: Any, payload, lease=None):
        self.rid = rid
        self.payload = payload
        self.lease = lease
        self.meta: Optional[dict] = None

    def ensure_parsed(self) -> dict:
        if self.meta is None:
            from tpudl.serve.cache import parse_migration

            self.meta = (
                self.payload
                if isinstance(self.payload, dict)
                else parse_migration(self.payload)
            )
        return self.meta


class Engine:
    """The request multiplexer. Pulls from an AdmissionQueue, keeps
    ``num_slots`` generation streams in flight, writes ``Result``s into
    ``self.results`` keyed by request_id. Synchronous: ``step()``
    advances the world by one decode step; ``run_until_drained()`` loops
    it (the ServeSession front end drives either). With every slot
    seated the device is kept one step ahead of what ``step()`` has
    read back (``_decode_step``), and a seat reads nothing back
    (``_seat``): its first token stays on the device for the slot's
    first decode step. What the device holds unread is ``_unread``
    (``_in_flight``: the decode step among it), and ``land()`` reads
    it all back for whoever touches a slot from outside."""

    def __init__(
        self,
        prefill_call: Callable,
        decode_call: Callable,
        params: Any,
        cache: PagedKVCache,
        queue: AdmissionQueue,
        prompt_len: int,
        clock: Callable[[], float] = time.monotonic,
        continuous: bool = True,
        chunk_prefill_call: Optional[Callable] = None,
        speculator=None,
        verify_call: Optional[Callable] = None,
        adapter_pool=None,
    ):
        if not isinstance(cache, PagedKVCache):
            raise TypeError(
                f"Engine serves from a PagedKVCache, got "
                f"{type(cache).__name__}"
            )
        if prompt_len < 1 or prompt_len >= cache.max_seq_len:
            raise ValueError(
                f"prompt_len must be in [1, max_seq_len) = "
                f"[1, {cache.max_seq_len}), got {prompt_len}"
            )
        self.prefill_call = prefill_call
        self.decode_call = decode_call
        self.params = params
        self.cache = cache
        self.queue = queue
        self.prompt_len = prompt_len
        # The lengths the batch-1 prefill runs at, ascending: the
        # window alone until compile_prefill_lengths adds shorter ones.
        self.prefill_lengths = (prompt_len,)
        self.num_slots = cache.num_slots
        self.max_seq_len = cache.max_seq_len
        self.clock = clock
        self.continuous = continuous
        # Prefix sharing (radix mode, tpudl.serve.cache): seat walks
        # the radix tree, maps matched full pages for free, and — with
        # the chunked prefill program — prefills only the unshared
        # suffix (the TTFT lever for shared system prompts). Without
        # the chunk program (artifact sessions) sharing still
        # deduplicates pages; only the compute skip is lost.
        self.prefix_share = bool(cache.prefix_share)
        self.chunk_prefill_call = chunk_prefill_call
        # Multi-tenant LoRA serving (tpudl.serve.lora.AdapterPool):
        # when present, the prefill/decode programs are the lora_*
        # contracts (three extra traced inputs — pools, per-slot page
        # table, per-slot scaling) and each seated request pins its
        # tenant's adapter pages for the slot's lifetime.
        self.adapter_pool = adapter_pool
        if adapter_pool is not None:
            if self.prefix_share:
                raise ValueError(
                    "adapter serving cannot share KV prefixes across "
                    "tenants (k/v projections are tenant-adapted, so "
                    "identical tokens produce DIFFERENT pages per "
                    "tenant) — prefix_share must be off"
                )
            if speculator is not None:
                raise ValueError(
                    "speculative decoding with per-tenant adapters is "
                    "not supported (the draft has no adapter view)"
                )
        # Speculative decoding (tpudl.serve.speculate): draft k cheap
        # tokens, verify them in ONE slot-batched chunk dispatch.
        self.speculator = speculator
        self.verify_call = verify_call
        if speculator is not None:
            if verify_call is None:
                raise ValueError(
                    "speculator needs verify_call (the k-token paged "
                    "chunk decode program)"
                )
        self._slots: List[Optional[_Slot]] = [None] * self.num_slots
        import collections

        # What the device holds and the host has not read back, in
        # dispatch order: decode steps (``_InFlight``) and first tokens
        # (``_First``). Between two ``step``s at most one decode step
        # and nothing else.
        self._unread = collections.deque()
        self._token_sharding = _selection_sharding(params)
        self.results: Dict[Any, Result] = {}
        # The recorder of the step under way (tpudl.obs.spans), looked
        # up once in ``step`` and read by what it calls; None outside a
        # step and whenever recording is off.
        self._rec = None
        self._seats = 0  # prompts seated in the step under way
        # Streaming feed: called with (request_id, token) the moment
        # the host has a token (prefill's first token included, where
        # it lands) — BEFORE the finish check, so a consumer sees eos
        # arrive as a token and then the Result. ServeSession.stream()
        # installs it.
        self.on_token: Optional[Callable[[Any, int], None]] = None
        # Disaggregation inbox: _Prefilled items seated by _fill_slots
        # ahead of local queue pops (deque: appends are thread-safe, the
        # router's prefill workers feed it from their own threads).
        self.prefill_inbox = collections.deque()
        # Migration inbox: (rid, payload, lease) triples appended by the
        # router when a dying/draining replica's decode state is shipped
        # here (_Migrated; drained by _fill_slots AHEAD of everything
        # else — this work already paid its prefill somewhere).
        self.migrate_inbox = collections.deque()
        # Chaos injection (tpudl.serve.chaos, env-gated, default none):
        # hooks called with the decode-step count at the top of step().
        from tpudl.serve import chaos as serve_chaos

        self.chaos_hooks: List[Callable[[int], None]] = (
            serve_chaos.engine_step_hooks()
        )
        # Stat counters (also mirrored into the obs registry): decode
        # steps are the deterministic cost unit the static-vs-continuous
        # comparison uses (wall time rides on them 1:1 at fixed slots).
        self.num_decode_steps = 0
        self.num_prefills = 0
        # SLO hook (attach_slo): while any subscribed objective burns,
        # admission sheds the queue instead of seating doomed work.
        self._slo = None
        self._slo_burning: frozenset = frozenset()
        # Static shapes: the cache's resident bytes never change after
        # construction — publish once, not per step.
        registry().gauge("serve_cache_bytes").set(cache.nbytes)
        # Live health: slots/queue state on /healthz while this engine
        # is the process's serving engine (latest instance wins). The
        # source holds a WEAK reference — a registered bound method
        # would pin the engine and its whole KV pool
        # (potentially GBs) for the process lifetime, and keep serving
        # a dead engine's state as live readiness data.
        import weakref

        from tpudl.obs import exporter as obs_exporter

        self_ref = weakref.ref(self)

        def _engine_health() -> dict:
            eng = self_ref()
            if eng is None:
                return {"healthy": True, "engine": "collected"}
            return eng.health()

        obs_exporter.register_health_source("serve_engine", _engine_health)

    # -- live telemetry ------------------------------------------------

    def health(self) -> dict:
        """/healthz payload: slot occupancy + admission-queue state
        (what the serve router's readiness and autoscale signals read).
        Burning SLO objectives surface via the monitor's own health
        source; here they only annotate the engine's view. A slot whose
        first token is still on the device is busy."""
        out = {
            "healthy": True,
            "slots_busy": sum(s is not None for s in self._slots),
            "num_slots": self.num_slots,
            "queue_depth": (
                len(self.queue)
                + len(self.prefill_inbox)
                + len(self.migrate_inbox)
            ),
            "queue_capacity": self.queue.capacity,
            "results_pending": len(self.results),
            "decode_steps": self.num_decode_steps,
            "prefills": self.num_prefills,
            "max_seq_len": self.max_seq_len,
            "slo_burning": sorted(self._slo_burning),
            "free_pages": self.cache.free_pages,
            "page_size": self.cache.page_size,
            "kv_quantized": self.cache.quantized,
        }
        if self.prefix_share:
            out["prefix_cache"] = self.cache.radix.stats()
        if self.speculator is not None:
            out["spec_k"] = self.speculator.k
        if self.adapter_pool is not None:
            out["adapters"] = self.adapter_pool.stats()
        return out

    def attach_slo(self, monitor) -> None:
        """Subscribe this engine's admission path to a
        ``tpudl.obs.slo.SloMonitor``: the engine feeds the monitor its
        TTFT/queue-wait/TPOT observations, and while any objective
        burns, queued-but-unseated requests are shed
        (``finish_reason="shed_slo"``) instead of being served into a
        blown objective — the ROADMAP-2 shed/autoscale signal.

        The subscription holds a WEAK engine reference: a monitor
        outliving its engine (the router's long-lived monitor across
        engine generations) must not pin each dead engine's KV cache
        through its callback list."""
        import weakref

        self_ref = weakref.ref(self)

        def _on_transition(objective, state):
            eng = self_ref()
            if eng is None:
                return
            if state["burning"]:
                eng._slo_burning = eng._slo_burning | {objective.name}
            else:
                eng._slo_burning = eng._slo_burning - {objective.name}
            registry().gauge("slo_burning").set(len(eng._slo_burning))

        self._slo = monitor
        monitor.subscribe(_on_transition)
        monitor.evaluate()

    def _slo_observe(self, metric: str, value: float) -> None:
        if self._slo is not None:
            self._slo.observe(metric, value)

    # -- prefill lengths -----------------------------------------------

    def prefill_rows(self, tokens: int) -> int:
        """The shortest compiled prefill length that holds a prompt of
        ``tokens`` tokens (the window for one that admission should
        have refused: the pad then fails as it always did)."""
        return next(
            (rows for rows in self.prefill_lengths if rows >= tokens),
            self.prompt_len,
        )

    def compile_prefill_lengths(self, lengths) -> None:
        """Serve prompts at these lengths (``tpudl.serve.api
        .prefill_lengths``; the window stays the longest): every
        length's batch-1 prefill and seat, and a speculator's draft
        pair, are compiled and run once here, dry, so that no request
        meets a program that does not exist yet. The dry seat aims at
        the trash page and no slot is touched. A radix session seats
        through ONE left-aligned program whatever the row's length, so
        only its prefill is made. Beside the seat, the program that
        hands a first token to the next decode step on the device
        (``tpudl_first_token``), over both vectors it is given: the
        host's tokens and a step's selection, which its own result
        stands for here (an array of the device, placed alike).
        Recorded as ``startup.prefill_lengths`` around one
        ``startup.prefill_dry_run`` a length, which nothing of blocks
        on the device: a dry run's device time shows in whatever reads
        back first afterwards."""
        with startup_span("startup.prefill_lengths"):
            for rows in lengths:
                with startup_span(
                    "startup.prefill_dry_run", rows=rows
                ) as dry_run:
                    logits, row_cache, *_ = self.prefill_call(
                        self.params, *left_pad([0], rows)
                    )
                    if not self.prefix_share:
                        self.cache.compile_seat(row_cache, rows)
                    if self.speculator is not None:
                        self.speculator.compile_seat(rows)
                    first = _select_greedy(logits)
                    tokens = self._with_firsts(
                        np.zeros(self.num_slots, np.int32), [(0, first)]
                    )
                    self._with_firsts(tokens, [(0, first)], from_device=True)
                    dry_run.note(kernel_layers=self._kernel_layers(rows))
        self.prefill_lengths = tuple(sorted({*lengths, self.prompt_len}))

    def _kernel_layers(self, rows: int) -> int:
        """Layers of the prefill program at ``rows`` whose attention is
        ONE kernel call (tpudl.ops.flash_attention.prefill_attention):
        what the program noted of itself while it was traced; 0 for a
        length not traced yet, an artifact, the XLA blocks."""
        return self._noted("attention_in_kernel", rows)

    def _noted(self, what: str, rows: int) -> int:
        """The prefill program's own note ``what`` of itself at
        ``rows`` (tpudl.ops.flash_attention.note_prefill)."""
        program = getattr(self.prefill_call, "__wrapped__", self.prefill_call)
        return getattr(program, what, {}).get(rows, 0)

    def _with_firsts(self, tokens, firsts, from_device: bool = False):
        """A decode step's token vector as the device takes it: the
        host's array put where the selection would have left it (where
        the parameters are committed), or with ``from_device`` the
        selection of the step before as it lies there; then every
        ``(slot, selection)`` of ``firsts`` set into it on the device,
        one small program a first token. Its arguments being placed
        alike, its result is too: the decode program meets one kind of
        vector whichever way the tokens come."""
        if not from_device and self._token_sharding is not None:
            tokens = jax.device_put(tokens, self._token_sharding)
        for slot, sel in firsts:
            tokens = _set_first(tokens, np.int32(slot), sel)
        return tokens

    # -- admission / seating -------------------------------------------

    def _record_shed(self, entries: List[_Entry], reason: str) -> None:
        reg = registry()
        rec = active_recorder()
        now = self.clock()
        for entry in entries:
            req = entry.request
            wait = now - entry.submitted_at
            self.results[req.request_id] = Result(
                request_id=req.request_id,
                tokens=[],
                finish_reason=reason,
                queue_wait_s=wait,
            )
            reg.counter(f"serve_requests_{reason}").inc()
            if rec is not None:
                rec.event(
                    "request_complete", CAT_SERVE_REQUEST,
                    request_id=req.request_id, finish_reason=reason,
                    queue_wait_s=wait, num_tokens=0,
                )
            requestlog.log_result(requestlog.build_record(
                req.request_id, reason, site="engine",
                tenant=getattr(req, "tenant", None),
                tokens_in=len(req.input_ids), queue_wait_s=wait,
            ))

    def _seat(self, entry: _Entry, slot: int) -> None:
        """Prefill one request and scatter it into ``slot`` of the live
        cache; select its first token. NOTHING IS READ BACK: the
        prefill, the selection and the scatter are dispatched, the
        token stays on the device for the slot's first decode step
        (``_First``) and the host reads it when it lands what the
        device holds (``_land_first``). So a seat costs the host its
        dispatches and may be made behind a decode step that is still
        running. Radix mode first walks the prefix tree: matched full
        pages seat for free, and the batch-1 program is replaced by the
        CHUNKED suffix prefill — prefill cost drops from O(prompt
        window) to O(unshared suffix)."""
        req = entry.request
        ids = np.asarray(req.input_ids, np.int32)
        n = int(ids.shape[0])
        rec = self._rec
        t0 = self.clock()
        part = None
        if rec is not None:
            # Everything up to the program's call returning; the wait
            # for the first token is ``prefill``'s, at its landing.
            part = rec.begin("prefill.dispatch", CAT_SERVE_ENGINE, t0)
        lease = None
        hit = 0
        tenant_pinned = False
        reloads0 = 0
        # A decode step the device still holds: the prefill queues
        # behind it and the chip goes from one to the other.
        behind = self._in_flight is not None
        # The prompt runs left-padded to the shortest compiled length
        # that holds it; the row, its seat and its pages follow that
        # length, not the window.
        rows = ran = self.prefill_rows(n)
        row_offset = rows - n
        try:
            if self.adapter_pool is not None:
                # Pin the tenant's adapter pages BEFORE the prefill
                # dispatch (loading them on demand — an evicted
                # tenant's next request reloads transparently here);
                # the pin transfers to the slot at bind time.
                reloads0 = self.adapter_pool.num_reloads
                arow, ascale = self.adapter_pool.acquire(req.tenant)
                tenant_pinned = req.tenant is not None
            if self.prefix_share:
                lease = self.cache.match_and_lease(ids)
                # A fully-matched prompt still needs its LAST token's
                # logits to select the first generated token, so the
                # compute skip caps at ids_len - 1.
                hit = min(len(lease[0]) * self.cache.page_size, n - 1)
            if hit > 0 and self.chunk_prefill_call is not None:
                prefix = self.cache.gather_prefix_rows(lease[0], hit)
                suffix = ids[hit:][None, :]
                positions = np.arange(hit, n, dtype=np.int32)[None, :]
                logits, row_cache, *counts = self.chunk_prefill_call(
                    self.params, prefix, suffix, positions
                )
                row_offset = 0  # chunk rows are already left-aligned
                ran = suffix.shape[1]
            else:
                hit = 0  # no chunk program: full prefill, pages dedup only
                padded, mask = left_pad(ids, rows)
                if self.adapter_pool is not None:
                    logits, row_cache = self.prefill_call(
                        self.params, padded, mask,
                        self.adapter_pool.pools,
                        arow[None, :],
                        np.float32([ascale]),
                    )
                    counts = []
                else:
                    # A model with routed experts returns its tokens
                    # per held expert beside the logits and the row.
                    logits, row_cache, *counts = self.prefill_call(
                        self.params, padded, mask
                    )
            sel = select_first(logits, req)
        except BaseException:
            if lease is not None:
                self.cache.release_lease(lease[1])
            if tenant_pinned:
                self.adapter_pool.release(req.tenant)
            raise
        attrs = None
        if part is not None:
            part.end(self.clock())
            # request_id on the prefill span is the trace link between
            # the queued event and this request's decode chunks.
            # prefix_hit_tokens names how much of the prompt the radix
            # cache paid for (report.py --request's TTFT attribution).
            # rows: the length the program ran; tokens: the prompt's
            # among them (what the padding and a shared prefix leave).
            # behind: a decode step was in flight at the dispatch.
            # attention_in_kernel: the program's attention at that
            # length was the prefill kernel's (its own note, traced),
            # in attention_kernel_layers of its attention_layers.
            kernel_layers = self._kernel_layers(ran)
            attrs = dict(
                slot=slot, request_id=req.request_id,
                queue_wait_s=t0 - entry.submitted_at,
                prefix_hit_tokens=hit, rows=ran, tokens=n - hit,
                behind=int(behind),
                attention_in_kernel=int(kernel_layers > 0),
                attention_kernel_layers=kernel_layers,
                attention_layers=self._noted("attention_layers", ran),
            )
        reg = registry()
        if hit:
            reg.counter("serve_prefix_hit_tokens").inc(hit)
        self.num_prefills += 1
        reg.counter("serve_prefills").inc()
        reg.counter("serve_prefill_rows").inc(ran)
        reg.counter("serve_prefill_tokens").inc(n - hit)
        if behind:
            reg.counter("serve_prefills_behind_step").inc()
        self._install(entry, slot, row_cache, _First(sel, counts, attrs),
                      n, t0, None, rows, lease=lease, row_offset=row_offset,
                      tenant_pinned=self.adapter_pool is not None,
                      prefix_hit=hit,
                      adapter_reloads=(
                          self.adapter_pool.num_reloads - reloads0
                          if self.adapter_pool is not None else 0
                      ))

    def _seat_prefilled(self, item: _Prefilled, slot: int) -> None:
        """Seat a request a DEDICATED prefill replica already prefilled
        (tpudl.serve.router disaggregation): same mid-stream insertion,
        no local batch-1 dispatch — this engine only decodes. The
        handoff carries the first token on the host, landed at once."""
        self._install(
            item.entry, slot, item.row_cache, item.first_token,
            item.prompt_ids_len, item.t_popped, item.t_first, item.rows,
        )

    def _install(self, entry: _Entry, slot: int, row_cache: Any,
                 first, ids_len: int, t_popped: float,
                 t_first: Optional[float], rows: int, lease=None,
                 row_offset: Optional[int] = None,
                 tenant_pinned: bool = False, prefix_hit: int = 0,
                 adapter_reloads: int = 0,
                 ) -> None:
        """Shared seat tail: cache insertion (page reservation+scatter,
        or radix-shared left-aligned seat), draft-cache seating,
        adapter binding, slot activation. ``rows`` is the length the
        row was prefilled at. ``first``: the first token where the host
        has it already (an int, selected at ``t_first``; landed here,
        at once), else the ``_First`` that holds it on the device, which
        is queued behind whatever the device holds unread."""
        req = entry.request
        tenant = getattr(req, "tenant", None)
        if self.adapter_pool is not None and not tenant_pinned:
            # Externally prefilled path (no _seat ran): pin here. The
            # router rejects tenant-ful requests on the disaggregated
            # path, so this only ever pins None (a no-op) — kept
            # anyway so the invariant "a bound slot holds a pin" has
            # one owner.
            self.adapter_pool.acquire(tenant)
        span = None
        if self._rec is not None:
            span = self._rec.begin(
                "seat", CAT_SERVE_SEAT, self.clock(),
                request_id=req.request_id, slot=slot,
            )
        try:
            if self.prefix_share:
                ids = np.asarray(req.input_ids, np.int32)
                if lease is None:
                    # Disaggregated handoff: the worker prefilled the
                    # full row; matched pages still dedup (values
                    # identical).
                    lease = self.cache.match_and_lease(ids)
                self.cache.seat_shared(
                    row_cache, slot, ids, ids_len + req.max_new_tokens,
                    lease=lease,
                    row_offset=(
                        rows - ids_len
                        if row_offset is None else row_offset
                    ),
                )
            else:
                self.cache.seat(
                    row_cache, slot, rows - ids_len,
                    rows, rows + req.max_new_tokens,
                )
        except BaseException:
            # A failed seat must not strand the tenant pin: the slot
            # was never bound, so free_slot will never run for it —
            # without this release the pages would be unevictable for
            # the process lifetime.
            if self.adapter_pool is not None:
                self.adapter_pool.release(tenant)
            raise
        if span is not None:
            span.end(self.clock(), pages=self.cache.pages_of(slot))
            self._seats += 1
        if self.adapter_pool is not None:
            # The seat pin transfers to the slot; free_slot drops it.
            self.adapter_pool.bind_slot(slot, tenant)
        if self.speculator is not None:
            self.speculator.seat(
                slot, np.asarray(req.input_ids, np.int32),
                rows, rows + req.max_new_tokens,
            )
        queue_wait_ms = 1e3 * (t_popped - entry.submitted_at)
        registry().histogram("serve_queue_wait_ms").observe(queue_wait_ms)
        self._slo_observe("serve_queue_wait_ms", queue_wait_ms)
        s = _Slot(entry, ids_len, t_popped,
                  kv_base=int(self.cache.lens[slot]))
        s.prefix_hit = prefix_hit
        s.adapter_reloads = adapter_reloads
        self._slots[slot] = s
        if isinstance(first, _First):
            first.slot, first.state = slot, s
            s.first = first
            self._unread.append(first)
        else:
            self._first_landed(slot, s, first, t_first)

    def _first_landed(self, slot: int, s: _Slot, token: int,
                      now: float) -> None:
        """The host has ``s``'s first token, selected at ``now``: TTFT
        ends, the stream starts, and the request may be over."""
        s.tokens.append(token)
        s.t_first = s.t_last = now
        ttft_ms = 1e3 * (now - s.entry.submitted_at)
        registry().histogram("serve_ttft_ms").observe(ttft_ms)
        self._slo_observe("serve_ttft_ms", ttft_ms)
        if self.on_token is not None:
            self.on_token(s.request.request_id, token)
        # A request can finish on its very first token.
        self._maybe_finish(slot, token)

    def _land_first(self, first: _First,
                    t: Optional[float] = None) -> Optional[float]:
        """Read one first token back, in a transfer of its own, with
        what its prefill returned beside it. Under a ``prefill`` span
        (around ``prefill.readback``) that carries the prefill's
        attributes: the wait for that prefill wherever the engine
        waits, so that the program's device time lies inside it
        (behind a decode step the prefill starts on the chip when that
        step ends, which is when this wait begins). ``t``: a clock
        reading just made, where the span begins; the reading at which
        it ended is returned, so that waits tile. A prefill that
        failed surfaces here: the slot is freed, with its pages, lease
        and pin, and the error goes on."""
        slot, s = first.slot, first.state
        rec = self._rec
        span = readback = None
        if rec is not None and first.attrs is not None:
            if t is None:
                t = self.clock()
            span = rec.begin("prefill", CAT_SERVE_PREFILL, t, **first.attrs)
            readback = rec.begin("prefill.readback", CAT_SERVE_PREFILL, t)
        s.first = None
        try:
            sel, counts = jax.device_get((first.sel, first.counts))
        except BaseException:
            self._release(slot)
            raise
        if readback is not None:
            readback.end(self.clock())
        load = record_expert_load(*counts) if counts else {}
        now = self.clock()
        if span is not None:
            # since_pop_s: what the request waited from its pop to this
            # token, the dispatch and what the device held before the
            # prefill included (report.py --request's TTFT attribution:
            # queue wait + this = TTFT).
            span.end(now, since_pop_s=now - s.t_seated, **load)
        self._first_landed(slot, s, int(sel[0]), now)
        return now

    def _land_firsts(self, t: Optional[float] = None) -> Optional[float]:
        """Land the first tokens that precede the oldest decode step
        the device holds, each in its own transfer, in order. ``t`` as
        ``_land_first`` takes and returns it."""
        unread = self._unread
        while unread and isinstance(unread[0], _First):
            t = self._land_first(unread.popleft(), t)
        return t

    @property
    def _in_flight(self) -> Optional[_InFlight]:
        """The newest decode step the device holds and the host has
        not read back; None where it holds none."""
        return next(
            (u for u in reversed(self._unread) if isinstance(u, _InFlight)),
            None,
        )

    def _active(self) -> bool:
        return any(s is not None for s in self._slots)

    def _fill_slots(self) -> None:
        """A step's admission, under the ``admit`` span: parent of the
        ``prefill`` and ``seat`` spans it causes, so that its SELF time
        is what admission costs the host (SLO evaluation and shedding,
        the inboxes, the free-slot scans, the queue's pop, a seat's
        tail, the occupancy gauges). ``popped``: requests it seated,
        ``shed``: entries it shed, ``queue_depth``: entries left
        waiting (queue and inboxes, as ``health`` counts them)."""
        rec = self._rec
        span = None
        if rec is not None:
            span = rec.begin("admit", CAT_SERVE_ENGINE, self.clock())
        popped, shed = self._admit()
        if span is not None:
            span.end(
                self.clock(), popped=popped, shed=shed,
                queue_depth=(
                    len(self.queue) + len(self.prefill_inbox)
                    + len(self.migrate_inbox)
                ),
            )

    def _admit(self) -> tuple:
        """Seat queued work into empty slots. Static mode only refills
        once the WHOLE batch drained (the run-to-completion baseline);
        continuous mode refills the moment a slot frees. Returns the
        requests seated and the entries shed."""
        popped = shed = 0
        if self._slo is not None:
            # Drive burn-state transitions from the engine's own thread
            # (the subscriber flips _slo_burning synchronously), then
            # shed: while an objective burns, queued work would only be
            # served into a blown objective — hand it back now so the
            # client can retry elsewhere (the ROADMAP-2 router's cue).
            self._slo.evaluate()
            if self._slo_burning and len(self.queue):
                burnt = self.queue.drain_all()
                shed += len(burnt)
                self._record_shed(burnt, "shed_slo")
        if not self.continuous and self._active():
            return popped, shed
        # A decode step in flight holds nothing up: a seat reads nothing
        # back, so its prefill, selection and scatter queue behind that
        # step on the device (ordered after it through the pool, like
        # the step ahead) and the chip goes from one to the other while
        # the host dispatches. The call's ``decode_step`` span is open
        # already (``step``), so the landing step's device time lies in
        # it and not under ``admit`` alone.
        # Migrated-in requests seat FIRST: they are mid-stream — their
        # prefill AND some decode are already paid, and every queued
        # token of delay widens the client's visible stall (the
        # failover token gap).
        while self.migrate_inbox:
            slot = next(
                (i for i, s in enumerate(self._slots) if s is None), None
            )
            if slot is None:
                break
            item = self.migrate_inbox[0]
            try:
                meta = item.ensure_parsed()
            except Exception as e:
                # Corrupt transfer: caught by the crc at the door, shed
                # as failed — NEVER resumed silently.
                self.migrate_inbox.popleft()
                self._fail_migrated(item.rid, e, lease=item.lease)
                continue
            if not self._fits_migrated(meta):
                if self._fits_migrated_ever(meta):
                    break  # fits once seated work frees pages
                self.migrate_inbox.popleft()
                self._fail_migrated(
                    item.rid,
                    RuntimeError(
                        "migrated reservation cannot fit this cache "
                        "even empty"
                    ),
                    lease=item.lease, meta=meta,
                )
                continue
            self.migrate_inbox.popleft()
            try:
                self._install_migrated(meta, slot=slot, lease=item.lease)
                popped += 1
            except (MigrationCorruptError, MigrationCompatError,
                    ValueError, RuntimeError) as e:
                # install/import released the lease on their own
                # failure paths — report only.
                self._fail_migrated(item.rid, e, meta=meta)
        # Externally prefilled requests (disaggregation) seat first:
        # their prefill cost is already paid, a queue pop would re-pay
        # it locally.
        while self.prefill_inbox:
            slot = next(
                (i for i, s in enumerate(self._slots) if s is None), None
            )
            if slot is None:
                break
            head = self.prefill_inbox[0]
            if not self._fits(head.entry.request, head.rows):
                if self._fits_ever(head.entry.request, head.rows):
                    break  # fits once seated work frees capacity
                # A never-fitting head (too big for even an EMPTY
                # cache) would otherwise block every prefilled request
                # behind it forever — the inbox is a plain deque with
                # no deadline/skip path, unlike AdmissionQueue's
                # fit-filtered pop. Shed it instead.
                self._record_shed(
                    [self.prefill_inbox.popleft().entry], "shed_capacity"
                )
                shed += 1
                continue
            self._seat_prefilled(self.prefill_inbox.popleft(), slot)
            popped += 1
        while True:
            slot = next(
                (i for i, s in enumerate(self._slots) if s is None), None
            )
            if slot is None:
                break
            entry, expired = self.queue.pop(fit=self._fits)
            shed += len(expired)
            self._record_shed(expired, "shed_timeout")
            if entry is None:
                break
            self._seat(entry, slot)
            popped += 1
        self._publish_occupancy()
        return popped, shed

    def _publish_occupancy(self) -> None:
        """Slots in use, the cache's reserved-against-live counters and
        the path its decode program took, as gauges."""
        reg = registry()
        reg.gauge("serve_slots_busy").set(
            sum(s is not None for s in self._slots)
        )
        reg.gauge("serve_kv_pages_reserved").set(self.cache.pages_reserved)
        reg.gauge("serve_kv_tokens_live").set(self.cache.tokens_live)
        reg.gauge("serve_kv_bytes_live").set(self.cache.bytes_live)
        if self.cache.window:
            # The window layers' rings, beside the full-context group.
            reg.gauge("serve_kv_pages_reserved_window").set(
                self.cache.pages_reserved_window
            )
            reg.gauge("serve_kv_tokens_live_window").set(
                self.cache.tokens_live_window
            )
        # Layers of the decode program whose attention reads the pool
        # in place (0 until its first dispatch traced it).
        reg.gauge("serve_paged_attention_in_place").set(
            self.cache.in_place_layers
        )
        # Layers of the longest prefill program whose attention is the
        # prefill kernel's (0 where it runs the XLA blocks, or dense).
        reg.gauge("serve_prefill_attention_in_kernel").set(
            self._kernel_layers(self.prompt_len)
        )
        # Pool leaves held folded (one a latent layer; a k / v pool
        # never is).
        reg.gauge("serve_kv_pool_folded_layers").set(
            sum(fold > 1 for fold in self.cache.folds)
        )
        # Pool leaves under the one manager: a k and a v a layer, and
        # as many again for every further pass of a looped stack.
        reg.gauge("serve_kv_pools").set(len(self.cache.folds))

    def _paged_attrs(self, pages_live: int) -> dict:
        """What a ``decode_step`` span says of the paged cache: the
        pages the seated slots hold, the positions the step read and
        their bytes over both groups (``PagedKVCache.bytes_live``),
        whether its attention read the pool in place (the program's
        own note, ``PagedKVCache.in_place_layers``) and the pages an
        in-place step visits, counted on the host before dispatch. (How
        the pool is held is a constant of the session: the gauge
        ``serve_kv_pool_folded_layers`` says it, not every step.)"""
        cache = self.cache
        attrs = {"pages_reserved": cache.pages_reserved,
                 "tokens_live": cache.tokens_live,
                 "kv_bytes_live": cache.bytes_live,
                 "kv_in_place": int(cache.in_place_layers > 0),
                 "pages_live": pages_live}
        if cache.window:
            # The same two of the window layers' rings: pages held, and
            # positions ONE such layer reads (at most its window a slot).
            attrs["pages_reserved_window"] = cache.pages_reserved_window
            attrs["tokens_live_window"] = cache.tokens_live_window
        return attrs

    def _fits(self, request, rows: Optional[int] = None) -> bool:
        """Can this request be seated RIGHT NOW? Its worst case fits
        the per-slot logical bound and enough pool pages are free to
        reserve it up front (so it can never strand mid-decode).
        ``rows`` is the length its row was prefilled at, where another
        worker has prefilled it; else the length its prompt will run
        at. Radix mode counts only the UNSHARED pages (matched prefix
        pages seat for free — sharing multiplies admission capacity on
        top of int8's byte multiplier), and left-aligned seating
        reserves from the real prompt length, not the padded row.
        A speculating engine additionally needs draft-cache room; an
        adapter-serving engine needs the tenant's pages securable
        (resident, or loadable by evicting lease-free adapters)."""
        if self.adapter_pool is not None and (
            getattr(request, "tenant", None) is not None
        ):
            if not self.adapter_pool.can_seat(request.tenant):
                return False
        if rows is None:
            rows = self.prefill_rows(len(request.input_ids))
        if self.speculator is not None:
            # Pad-aligned draft seating reserves the whole padded row.
            # submit() already validates prompt_len + max_new
            # against the session bound, so the bound check here is
            # belt-and-suspenders for work pushed straight onto the
            # queue.
            draft_need = rows + request.max_new_tokens
            if draft_need > self.speculator.cache.max_seq_len or not (
                self.speculator.cache.fits_tokens(draft_need)
            ):
                return False
        if self.prefix_share:
            need = len(request.input_ids) + request.max_new_tokens
            return need <= self.max_seq_len and self.cache.fits_request(
                request.input_ids, need
            )
        need = rows + request.max_new_tokens
        return need <= self.max_seq_len and self.cache.fits_tokens(need)

    def _fits_ever(self, request, rows: Optional[int] = None) -> bool:
        """Could this request be seated in an EMPTY cache? False means
        waiting can never help (the worst case exceeds the compiled
        seq-len bound, or the pool is too small outright)."""
        if rows is None:
            rows = self.prefill_rows(len(request.input_ids))
        need = (
            len(request.input_ids) + request.max_new_tokens
            if self.prefix_share
            else rows + request.max_new_tokens
        )
        if need > self.max_seq_len:
            return False
        if self.adapter_pool is not None and (
            getattr(request, "tenant", None) is not None
        ):
            if not self.adapter_pool.can_ever_seat(request.tenant):
                return False
        if self.speculator is not None:
            draft_need = rows + request.max_new_tokens
            if draft_need > self.speculator.cache.max_seq_len or (
                self.speculator.cache.pages_needed(draft_need)
                > self.speculator.cache.num_pages - 1
            ):
                return False
        # Page 0 is the trash page; an empty pool frees the rest
        # (radix mode: refcount-0 cached pages evict on demand, so
        # the whole pool minus the trash page is reachable).
        return self.cache.pages_needed(need) <= self.cache.num_pages - 1

    # -- page-granular migration ---------------------------------------

    def export_request(self, rid: Any, skip_prefix_tokens: int = 0):
        """Ship one SEATED request's full decode state — page-granular
        KV (int8 as int8), generated tokens, per-request sampling
        position (the ``fold_in(key(seed), t)`` index), and absolute
        deadline — as a crc32-guarded payload another engine's
        ``install_migrated`` resumes byte-exact, with zero prefill
        dispatches. A speculating engine additionally ships the
        draft's KV remainder as a nested payload
        (``Speculator.export_slot``), so draft and target cross the
        wire in lens-lockstep and the first post-failover propose
        window runs as if the request never moved. Returns ``None``
        when the request is not seated here — the caller's cue to fall
        back to a from-scratch resubmission.

        ``skip_prefix_tokens`` omits that many leading logical rows
        from the payload (the router probed AND LEASED them in the
        target's radix tree — prefix by reference, not by bytes).
        Commit-or-invisible: the slot is freed only after the payload
        exists in full. What the device holds unread (a decode step in
        flight, a first token) is landed first, so the payload holds
        every token the device has computed; a request that this
        finished is not seated any more (None) and its Result is in
        ``results``."""
        self.land()
        slot = next(
            (
                i
                for i, s in enumerate(self._slots)
                if s is not None and s.request.request_id == rid
            ),
            None,
        )
        if slot is None:
            return None
        s = self._slots[slot]
        req = s.request
        # The payload meta is JSON: an id (or tenant key — it feeds a
        # dict lookup on the target) that does not round-trip
        # (tuple -> list, custom object -> crash) would resume under a
        # MUTATED identity — or an unhashable one that kills the
        # target's loop. Decline instead; resubmission preserves the
        # original object.
        import json as _json

        for value in (req.request_id, req.session_key, req.tenant):
            try:
                if _json.loads(_json.dumps(value)) != value:
                    return None
            except (TypeError, ValueError):
                return None
        skip = int(skip_prefix_tokens)
        if skip and int(self.cache.start[slot]) != 0:
            skip = 0  # pad-aligned rows cannot ship by tree reference
        t0 = self.clock()
        meta = {
            "request": {
                "request_id": req.request_id,
                "input_ids": [int(t) for t in req.input_ids],
                "max_new_tokens": req.max_new_tokens,
                "eos_id": req.eos_id,
                "temperature": req.temperature,
                "seed": req.seed,
                "priority": req.priority,
                "deadline_s": req.deadline_s,
                "session_key": req.session_key,
                # The tenant id rides the payload so failover RE-PINS
                # the adapter on the target engine's pool (reloading it
                # there if needed) before decode resumes.
                "tenant": req.tenant,
            },
            "tokens": [int(t) for t in s.tokens],
            "position": s.position,
            "steps": s.steps,
            "prompt_ids_len": len(req.input_ids),
            "submitted_at": s.entry.submitted_at,
            "deadline_at": s.entry.deadline,
            "t_seated": s.t_seated,
            "t_first": s.t_first,
            "t_last": s.t_last,
            # Hops survived so far: rides the payload so the target's
            # terminal record counts migrations CUMULATIVELY (and a
            # failed install can attribute the full hop count).
            "migrations": s.migrations,
            # What the target must reserve: rows written so far plus
            # one page-write per token still to generate.
            "reserve_tokens": int(self.cache.lens[slot])
            + max(0, req.max_new_tokens - len(s.tokens)),
        }
        extra_leaves = []
        if self.speculator is not None:
            # The draft remainder: a nested payload of the draft
            # cache's rows (its own pack/crc), riding as one uint8
            # leaf. Draft lens equals target lens between windows
            # (lens-lockstep), so the reserve formula is the target's.
            draft_reserve = int(self.speculator.cache.lens[slot]) + max(
                0, req.max_new_tokens - len(s.tokens)
            )
            draft_bytes = self.speculator.export_slot(
                slot, req.input_ids, draft_reserve
            )
            meta["draft"] = {
                "k": self.speculator.k,
                "nbytes": len(draft_bytes),
            }
            import numpy as _np

            extra_leaves.append(
                ("draft:payload", _np.frombuffer(draft_bytes, _np.uint8))
            )
        payload = self.cache.export_request(
            slot, meta, skip_tokens=skip, extra_leaves=extra_leaves
        )
        # Commit point: the payload exists in full — the local copy of
        # this request ends here (no double decode, no late Result).
        self._release(slot)
        reg = registry()
        reg.counter("serve_migrations_exported").inc()
        reg.counter("serve_migration_payload_bytes").inc(len(payload))
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "migration_export", CAT_SERVE_REQUEST,
                request_id=rid, payload_bytes=len(payload),
                skip_tokens=skip, tokens_done=len(s.tokens),
                export_s=self.clock() - t0,
            )
        return payload

    def install_migrated(self, payload, slot: Optional[int] = None,
                         lease=None) -> Any:
        """Seat an ``export_request`` payload into a free slot and
        resume decode at the recorded position: the KV rows scatter
        straight into fresh pages, the sampling stream continues at the
        recorded fold_in index, and NOT ONE prefill dispatch runs here.
        The payload's absolute deadline is honored — a transfer that
        exhausted the client's budget is recorded as ``shed_timeout``,
        never resumed. Raises ``MigrationCorruptError`` on a payload
        that fails the crc (resuming garbage is the one unforgivable
        outcome) and ``MigrationCompatError`` on a cache this engine
        cannot seat it in. Returns the request_id. Lands what the
        device holds unread first, as whoever touches a slot from
        outside ``step`` does (admission, inside one, seats without)."""
        self.land()
        return self._install_migrated(payload, slot, lease)

    def _install_migrated(self, payload, slot: Optional[int] = None,
                          lease=None) -> Any:
        from tpudl.serve.cache import parse_migration

        try:
            meta = (
                payload
                if isinstance(payload, dict) and "_arrays" in payload
                else parse_migration(payload)
            )
            if self.speculator is not None and "draft" not in meta:
                # A speculating engine cannot resume a draft-less
                # payload: the draft cache would start empty while the
                # target cache is mid-stream, breaking lens-lockstep.
                raise MigrationCompatError(
                    "this engine speculates but the payload carries "
                    "no draft remainder"
                )
            req = Request(**meta["request"])
            entry = _Entry(
                priority=req.priority, seq=0, request=req,
                deadline=meta.get("deadline_at"),
                submitted_at=meta["submitted_at"],
            )
        except BaseException:
            self.cache.release_lease(lease[1] if lease else None)
            raise
        if entry.deadline is not None and self.clock() > entry.deadline:
            # The migration transfer ate the remaining budget: shed at
            # the door (AdmissionQueue's never-start-past-deadline
            # guarantee, kept across replica generations).
            self.cache.release_lease(lease[1] if lease else None)
            self._record_shed([entry], "shed_timeout")
            return req.request_id
        if slot is None:
            slot = next(
                (i for i, s in enumerate(self._slots) if s is None), None
            )
        if slot is None:
            self.cache.release_lease(lease[1] if lease else None)
            raise RuntimeError(
                "no free slot for the migrated request (callers check "
                "for one before installing)"
            )
        tenant_pinned = False
        if req.tenant is not None:
            if self.adapter_pool is None or not (
                self.adapter_pool.knows(req.tenant)
            ):
                self.cache.release_lease(lease[1] if lease else None)
                raise MigrationCompatError(
                    f"migrated request is tenant {req.tenant!r} but "
                    f"this engine's adapter pool does not serve it"
                )
            # Re-pin the tenant's adapter HERE (loading it into this
            # pool if needed) before any KV lands: resuming a tenant's
            # decode against the bare base model would silently change
            # its tokens.
            self.adapter_pool.acquire(req.tenant)
            tenant_pinned = True
        try:
            # Consumes the lease: released on every import failure path.
            self.cache.import_request(meta, slot, lease=lease)
            if self.adapter_pool is not None:
                self.adapter_pool.bind_slot(slot, req.tenant)
            if self.speculator is not None:
                # Draft remainder: the rider leaf is the nested draft
                # payload verbatim — seat it so draft/target lockstep
                # resumes without a re-prefill on either cache. A
                # non-speculating engine ignores the rider instead
                # (the target import never reads it).
                try:
                    self.speculator.import_slot(
                        slot, meta["_arrays"]["draft:payload"].tobytes()
                    )
                except BaseException:
                    # Target rows already landed: unwind them so the
                    # failure is invisible (both caches seat or none).
                    self.cache.free(slot)
                    if self.adapter_pool is not None:
                        self.adapter_pool.free_slot(slot)
                    raise
        except BaseException:
            if tenant_pinned:
                self.adapter_pool.release(req.tenant)
            raise
        s = _Slot(
            entry, int(meta["prompt_ids_len"]), float(meta["t_seated"]),
            kv_base=int(self.cache.lens[slot]) - len(meta["tokens"]) + 1,
        )
        s.tokens = [int(t) for t in meta["tokens"]]
        s.t_first = float(meta["t_first"])
        s.position = int(meta["position"])
        s.steps = int(meta["steps"])
        s.t_last = float(meta["t_last"])
        s.gap_origin = float(meta["t_last"])
        # The terminal record counts hops cumulatively: the payload
        # carries the count survived BEFORE this move, and this install
        # is one more (usage before the move was already metered on the
        # source's spans — only the hop count rides).
        s.migrations = int(meta.get("migrations", 0)) + 1
        self._slots[slot] = s
        registry().counter("serve_migrations_installed").inc()
        self._publish_occupancy()
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "migration_install", CAT_SERVE_REQUEST,
                request_id=req.request_id, slot=slot,
                resumed_at_token=len(s.tokens),
            )
        return req.request_id

    def _fail_migrated(self, rid: Any, exc: BaseException,
                       lease=None, meta: Optional[dict] = None) -> None:
        """A migrated payload that cannot be resumed (corrupt transfer,
        incompatible cache, unseatable reservation) surfaces as a
        ``failed`` Result — the generation state is gone and silently
        resuming garbage is forbidden, so honesty is all that's left.
        ``meta`` is the parsed payload when the transfer survived the
        crc: it carries tenant, prompt length, and accumulated hop
        count, so the terminal record bills the RIGHT tenant instead of
        ``_base`` (a corrupt transfer has no meta — those fields fall
        back to unknown)."""
        if lease is not None:
            self.cache.release_lease(lease[1])
        self.results[rid] = Result(
            request_id=rid, tokens=[],
            finish_reason=f"failed: {type(exc).__name__}: {exc}",
        )
        reg = registry()
        reg.counter("serve_requests_failed").inc()
        reg.counter("serve_migrations_failed").inc()
        mreq = (meta or {}).get("request") or {}
        tenant = mreq.get("tenant")
        tokens_in = len(mreq.get("input_ids") or [])
        migrations = int((meta or {}).get("migrations", 0) or 0) + 1
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "request_complete", CAT_SERVE_REQUEST, request_id=rid,
                finish_reason="failed",
                error=f"{type(exc).__name__}: {exc}", num_tokens=0,
                shed_by="migration", tenant=tenant,
            )
        requestlog.log_result(requestlog.build_record(
            rid, f"failed: {type(exc).__name__}: {exc}", site="engine",
            tenant=tenant, tokens_in=tokens_in, migrations=migrations,
        ))

    def _fits_migrated(self, meta: dict) -> bool:
        """Can this payload's reservation seat RIGHT NOW? The radix
        path credits the (pre-leased) matched prefix exactly like
        ``fits_request`` does for fresh prompts; a tenant-ful payload
        additionally needs its adapter securable in this pool."""
        reserve = int(meta["reserve_tokens"])
        if reserve > self.max_seq_len:
            return False
        tenant = meta["request"].get("tenant")
        if tenant is not None:
            if self.adapter_pool is None or not (
                self.adapter_pool.can_seat(tenant)
            ):
                return False
        if self.speculator is not None and "draft" in meta:
            # Lens-lockstep means the draft reservation equals the
            # target's — the draft cache must seat it too, right now.
            if not self.speculator.cache.fits_tokens(reserve):
                return False
        if self.prefix_share and meta.get("left_aligned"):
            return self.cache.fits_request(
                meta["request"]["input_ids"], reserve
            )
        return self.cache.fits_tokens(reserve)

    def _fits_migrated_ever(self, meta: dict) -> bool:
        reserve = int(meta["reserve_tokens"])
        if reserve > self.max_seq_len:
            return False
        tenant = meta["request"].get("tenant")
        if tenant is not None:
            if self.adapter_pool is None or not (
                self.adapter_pool.can_ever_seat(tenant)
            ):
                return False
        if self.speculator is not None and "draft" in meta:
            dc = self.speculator.cache
            if dc.pages_needed(reserve) > dc.num_pages - 1:
                return False
        return self.cache.pages_needed(reserve) <= self.cache.num_pages - 1

    # -- stepping ------------------------------------------------------

    def _maybe_finish(self, slot: int, token: int) -> None:
        s = self._slots[slot]
        req = s.request
        if req.eos_id is not None and token == req.eos_id:
            self._finish(slot, "eos")
        elif len(s.tokens) >= req.max_new_tokens:
            self._finish(slot, "length")

    def _finish(self, slot: int, reason: str) -> None:
        s = self._slots[slot]
        req = s.request
        n = len(s.tokens)
        tpot = (s.t_last - s.t_first) / (n - 1) if n > 1 else None
        ttft = s.t_first - s.entry.submitted_at
        queue_wait = s.t_seated - s.entry.submitted_at
        self.results[req.request_id] = Result(
            request_id=req.request_id,
            tokens=list(s.tokens),
            finish_reason=reason,
            ttft_s=ttft,
            tpot_s=tpot,
            # Queue wait ends at SEATING (pop), not first token — TTFT
            # additionally carries the prefill (and, for the session's
            # first request, compilation); matches serve_queue_wait_ms.
            queue_wait_s=queue_wait,
        )
        reg = registry()
        reg.counter("serve_requests_completed").inc()
        reg.counter("serve_tokens_generated").inc(n)
        if tpot is not None:
            reg.histogram("serve_tpot_ms").observe(1e3 * tpot)
            self._slo_observe("serve_tpot_ms", 1e3 * tpot)
        rec = active_recorder()
        if rec is not None:
            # Completion closes the per-request trace with the measured
            # aggregates report.py --request checks the stitched
            # timeline against.
            rec.event(
                "request_complete", CAT_SERVE_REQUEST,
                request_id=req.request_id, finish_reason=reason,
                ttft_s=ttft, tpot_s=tpot, queue_wait_s=queue_wait,
                generation_s=s.t_last - s.t_first, num_tokens=n,
            )
        # Terminal durable-log record: slot occupancy x KV footprint,
        # from the rows the request's own tokens hold (the cache's
        # ``lens`` may count a step in flight, or nothing any more).
        active_s = max(0.0, s.t_last - s.t_seated)
        pages = -(-(s.kv_base + n - 1) // self.cache.page_size)
        kv_page_s = pages * active_s
        kv_byte_s = kv_page_s * (
            self.cache.nbytes / max(1, self.cache.num_pages)
        )
        # Sample capture (schema v2, opt-in): token ids ride ONLY on
        # completed results from this site — sheds/failures never carry
        # user content into the durable log.
        samples = {}
        if requestlog.samples_enabled():
            samples = {
                "prompt_ids": list(req.input_ids),
                "output_ids": list(s.tokens),
            }
        requestlog.log_result(requestlog.build_record(
            req.request_id, reason, site="engine",
            tenant=getattr(req, "tenant", None),
            tokens_in=len(req.input_ids), tokens_out=n,
            prefix_hit_tokens=s.prefix_hit,
            spec_proposed=s.spec_proposed, spec_accepted=s.spec_accepted,
            kv_page_seconds=kv_page_s, kv_byte_seconds=kv_byte_s,
            adapter_reloads=s.adapter_reloads, migrations=s.migrations,
            queue_wait_s=queue_wait, ttft_s=ttft, tpot_s=tpot,
            active_s=active_s,
            **samples,
        ))
        self._release(slot)

    def _release(self, slot: int) -> None:
        """Vacate ``slot``: its pages and radix lease go back to the
        cache, the draft's rows to the speculator, the tenant pin to
        the adapter pool (the adapter stays CACHED at refcount 0, the
        evictable pool, for the next request)."""
        self.cache.free(slot)
        if self.speculator is not None:
            self.speculator.free(slot)
        if self.adapter_pool is not None:
            self.adapter_pool.free_slot(slot)
        self._slots[slot] = None

    def _decode_on_pool(self, program, tokens, positions, *extra):
        """``cache.decode`` of one program of the paged decode
        contract, the step's addressing made first and under a span of
        its own, ``decode.address`` (child of ``decode.dispatch``): the
        host tables handed to the device every step, ``bytes`` of
        them."""
        rec = self._rec
        span = None
        if rec is not None:
            span = rec.begin(
                "decode.address", CAT_SERVE_DECODE, self.clock()
            )
        addressing = self.cache.dispatch_args()
        if span is not None:
            span.end(self.clock(), bytes=self.cache.addressing_nbytes)
        return self.cache.decode(
            program, self.params, tokens, positions, *extra,
            addressing=addressing,
        )

    def _prepare(self, prev: Optional[_InFlight]) -> Optional[_Plan]:
        """The host arrays of the next decode step. With nothing in
        flight (``prev`` None) every seated slot's row, its input token
        from the host, or from the device where the slot's first token
        is still there (``plan.firsts``). With ``prev`` in flight the
        step AHEAD of it, whose tokens are ``prev``'s selection on the
        device, and only where the engine can see that running ahead
        costs nobody anything: every slot is seated (no arrival could
        be seated before that step anyway), each by the request whose
        row ``prev`` computes or by one seated since whose first token
        is on the device too (its entry is set there; one seated since
        with a token on the host, a migrated or externally prefilled
        request, has neither), and nothing speculates (acceptance needs
        the host). None where that does not hold, or no row would be
        left.

        A slot whose last token is on the device already (by length:
        the host knows beforehand; ``prev``'s to select, or a first
        token that is the only one asked for) has no row. Under a step
        ahead its pages are handed back here, what wrote them having
        been dispatched, so that its table row maps the trash page as
        an idle slot's does (whoever is given the pages next writes
        them in a program ordered after on the device: the pool goes
        from program to program). The slot itself stays seated until
        its token lands. One that ends by ``eos_id`` is known one step
        late: the step dispatched meanwhile computes one row for
        nobody, inside the slot's own reservation, and ``_land`` drops
        its token."""
        ahead = prev is not None
        if ahead and (self.speculator is not None or any(
            s is None or (s is not row and s.first is None)
            for s, row in zip(self._slots, prev.rows)
        )):
            return None
        plan = _Plan(self.num_slots, prev)
        ending = []
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            # Its token in ``prev``, where it has a row there.
            coming = int(ahead and s is prev.rows[i])
            pending = s.first is not None
            if len(s.tokens) + pending + coming >= s.request.max_new_tokens:
                ending.append(i)
                continue
            plan.rows[i] = s
            if pending and not coming:
                plan.firsts.append((i, s.first.sel))
            elif not ahead:
                plan.tokens[i] = s.tokens[-1]
            # ``position`` and ``steps`` move when a decode step's
            # token lands: a row of the step ahead is one past them.
            plan.positions[i] = s.position + coming
            plan.temps[i] = s.request.temperature
            plan.seeds[i] = s.request.seed
            plan.steps[i] = s.steps + coming
        if not any(plan.rows):
            return None
        if ahead:
            for i in ending:
                self.cache.free(i)
        # Counted for the span alone, so outside what is read for time.
        if self._rec is not None:
            plan.pages_live = self.cache.pages_live()
        return plan

    def _dispatch(self, plan: _Plan) -> _InFlight:
        """Hand the device one decode step and its selection; nothing
        is read back. The cache's ``lens`` advance HERE, so that the
        next dispatch's addressing counts the row this one writes; idle
        slots ride along and their output is discarded (idle rows write
        into the trash page)."""
        tokens = self._with_firsts(
            plan.tokens, plan.firsts, from_device=plan.ahead
        )
        # Tenant adapters ride the paged contract as three more
        # traced inputs.
        adapters = (
            self.adapter_pool.dispatch_args()
            if self.adapter_pool is not None else ()
        )
        logits = self._decode_on_pool(
            self.decode_call, tokens, plan.positions, *adapters,
        )
        if plan.temps.any():
            sel = _select_tokens(logits, plan.temps, plan.seeds, plan.steps)
        else:
            sel = _select_greedy(logits)
        # Each ACTIVE slot's logical length advanced by one (idle
        # slots stay pinned on the trash page).
        rows = plan.rows
        self.cache.advance([i for i, s in enumerate(rows) if s is not None])
        attrs = None
        if self._rec is not None:
            # "rids" names every request this decode chunk advances —
            # the per-request trace's decode leg (report.py --request
            # selects the chunks containing its id). The pages the
            # seated slots hold and the positions the step reads (lens
            # already counts the token it writes); whether attention
            # reads the pool in place, and the pages it then visits
            # (``_paged_attrs``). ``ahead``: its input tokens were the
            # selection of the step before, on the device.
            attrs = dict(
                busy=sum(s is not None for s in rows),
                rids=[s.request.request_id for s in rows if s is not None],
                ahead=int(plan.ahead),
                **self._paged_attrs(plan.pages_live),
            )
        # What the program returned beside the logits is this step's
        # until the next dispatch overwrites it: kept with the step.
        step = _InFlight(rows, sel, list(self.cache.program_extras),
                         plan.ahead, attrs)
        self._unread.append(step)
        return step

    def _decode_step(self, span=None) -> None:
        """One LANDING: the oldest decode step the device holds is read
        back and its tokens emitted. With nothing in flight that step
        is dispatched here first, from the host's tokens and the first
        tokens the device still holds; and where ``_prepare`` finds
        that the engine may run ahead, the step after it is dispatched
        BEFORE the read-back and stays in flight until the next call
        lands it, so that the read-back's wait, the emit, whoever
        drives the engine, the next admission (seats and their
        prefills included) and the next step's arrays all pass under a
        busy device.

        What the device holds unread lands in dispatch order, each
        first token in a transfer of its own and never in one that
        waits for a later program: the first tokens that precede the
        step (seated by this call with nothing in flight: between
        ``decode.dispatch`` and ``decode.readback``), the step, and,
        once ``decode_step`` and ``emit`` are closed, the first tokens
        that follow it (seated by this call behind the step). So no
        prefill's device time lies in the ``decode_step`` of a call
        that found a step in flight.

        The spans are the ones a step always had: ``decode_prepare``
        (the host arrays of the call's first dispatch), ``decode_step``
        around ``decode.dispatch`` (every dispatch the call makes: of
        the step it lands, when none was in flight, and of the step
        ahead, whose arrays are then made inside it) and
        ``decode.readback`` (the wait for the step that lands), then
        ``emit``. ``span``: the ``decode_step`` that ``step`` opened
        before admission because a step was in flight, so that what is
        left of that step on the device lies inside it;
        ``decode_prepare`` is then its child. ``decode_step``'s
        attributes describe the step that lands. A call whose seats
        all asked for one token has no step to make: it lands them."""
        rec = self._rec
        prepare = None
        if rec is not None:
            # The step's host arrays: a sibling before ``decode_step``,
            # or its child where that is open already.
            prepare = rec.begin(
                "decode_prepare", CAT_SERVE_ENGINE, self.clock()
            )
        prev = self._in_flight
        plan = self._prepare(prev)
        t0 = self.clock()
        dispatch = readback = None
        if rec is not None:
            prepare.end(t0, slots=self.num_slots)
        if prev is None and plan is None:
            self._land_firsts()
            return
        if rec is not None:
            if span is None:
                span = rec.begin("decode_step", CAT_SERVE_DECODE, t0)
            dispatch = rec.begin("decode.dispatch", CAT_SERVE_DECODE, t0)
        if prev is None:
            plan = self._prepare(self._dispatch(plan))
        if plan is not None:
            self._dispatch(plan)
        t = None
        if dispatch is not None:
            # Every dispatch of the call has returned; what is left of
            # decode_step is the wait for the device and the copy back.
            t = self.clock()
            dispatch.end(t)
        t = self._land_firsts(t)
        if dispatch is not None:
            readback = rec.begin("decode.readback", CAT_SERVE_DECODE, t)
        # The head of the queue is now the oldest decode step.
        t = self._land(self._unread.popleft(), span, readback)
        self._land_firsts(t)

    def land(self) -> None:
        """Read back and emit everything the device holds unread, in
        dispatch order: first tokens and the decode step in flight, if
        there are any. ``step`` lands them by itself; whoever touches a
        slot from outside ``step`` (an export, an install, a drain)
        calls this first, so that no token the device has computed is
        lost and no slot is met without its first token."""
        unread = self._unread
        while unread:
            item = unread.popleft()
            if isinstance(item, _First):
                self._land_first(item)
            else:
                self._land(item)

    def _land(self, step: _InFlight, span=None,
              readback=None) -> Optional[float]:
        """Read ``step``'s tokens back and emit them, in order: each
        goes to the slot whose row computed it if that slot still holds
        the same request (one that ended meanwhile, by ``eos_id`` a
        step or a first token before, gets nothing, nor would whoever
        was seated there since). ``span`` / ``readback``: the open
        ``decode_step`` and ``decode.readback`` spans of a landing
        inside ``step``, which returns the clock reading that ended its
        ``emit``."""
        # Explicit readback (jax.device_get, not an implicit
        # np.asarray): the per-step token sync is the ONE intended
        # d2h in the decode steady state, and the dispatch-hygiene
        # audit (tpudl.analysis.assert_no_host_transfers) disallows
        # implicit transfers — intent made visible is the contract.
        # (A model with routed experts: its tokens per held expert ride
        # the same transfer.)
        sel, counts = jax.device_get((step.sel, step.extras))
        if readback is not None:
            readback.end(self.clock())
        load = record_expert_load(*counts) if counts else {}
        # Read before ``now``, so that they are ``decode_step``'s own
        # tail and not ``emit``'s.
        attrs = {**(step.attrs or {}), **load}
        now = self.clock()
        emit = None
        if span is not None:
            span.end(now, **attrs)
            emit = self._rec.begin("emit", CAT_SERVE_EMIT, now)
        self.num_decode_steps += 1
        reg = registry()
        reg.counter("serve_decode_steps").inc()
        if step.ahead:
            reg.counter("serve_decode_steps_ahead").inc()
        finished = 0
        for i, s in enumerate(step.rows):
            if s is None or self._slots[i] is not s:
                continue
            s.position += 1
            s.steps += 1
            if s.gap_origin is not None:
                # First token after a migration landed: the client's
                # stream stalled from the SOURCE's last token until now
                # — the failover token gap the bench banks.
                reg.histogram(
                    "serve_failover_token_gap_ms"
                ).observe(1e3 * (now - s.gap_origin))
                s.gap_origin = None
            s.t_last = now
            tok = int(sel[i])
            s.tokens.append(tok)
            if self.on_token is not None:
                self.on_token(s.request.request_id, tok)
            self._maybe_finish(i, tok)
            finished += self._slots[i] is not s
        if emit is None:
            return None
        t = self.clock()
        emit.end(t, finished=finished)
        return t

    def _spec_step(self) -> None:
        """One speculative window: k draft dispatches propose, ONE
        slot-batched target chunk dispatch verifies, acceptance emits
        1..k tokens per slot. Rollback of a rejected tail is per-slot
        ``lens`` bookkeeping on both caches (tpudl.serve.speculate's
        lockstep contract: both saw the same window, both advance by
        the emitted count)."""
        rec = self._rec
        prepare = None
        if rec is not None:
            prepare = rec.begin(
                "decode_prepare", CAT_SERVE_ENGINE, self.clock()
            )
        from tpudl.serve.speculate import (
            greedy_accept,
            sample_accept,
            softmax,
        )

        spec = self.speculator
        k = spec.k
        b = self.num_slots
        active = [i for i, s in enumerate(self._slots) if s is not None]
        tokens0 = np.zeros(b, np.int32)
        positions0 = np.zeros(b, np.int32)
        temps = np.zeros(b, np.float32)
        seeds = np.zeros(b, np.uint32)
        token_index = np.zeros(b, np.int32)
        for i in active:
            s = self._slots[i]
            tokens0[i] = s.tokens[-1]
            positions0[i] = s.position
            temps[i] = s.request.temperature
            seeds[i] = s.request.seed
            token_index[i] = s.steps
        rids = [self._slots[i].request.request_id for i in active]
        # The draft never moves the target's ``lens``: counted here,
        # outside what is read for time.
        pages_live = self.cache.pages_live(k) if rec is not None else 0
        t0 = self.clock()
        span = dispatch = None
        if rec is not None:
            prepare.end(t0, slots=len(active))
            span = rec.begin("decode_step", CAT_SERVE_DECODE, t0)
        proposals, q_probs = spec.propose(
            tokens0, positions0, active, temps, seeds, token_index
        )
        # Verify window [t_last, p_1 .. p_{k-1}]: k input rows write k
        # KV positions and yield the target's verdict on p_1 .. p_k.
        chunk = np.concatenate([tokens0[:, None], proposals[:, : k - 1]],
                               axis=1)
        pos_chunk = positions0[:, None] + np.arange(k, dtype=np.int32)[None, :]
        lens_before = {i: int(self.cache.lens[i]) for i in active}
        if span is not None:
            dispatch = rec.begin(
                "decode.dispatch", CAT_SERVE_DECODE, self.clock()
            )
        logits = self._decode_on_pool(self.verify_call, chunk, pos_chunk)
        sampling = any(temps[i] > 0 for i in active)
        verdict = logits if sampling else _select_greedy(logits)
        readback = None
        if dispatch is not None:
            t = self.clock()
            dispatch.end(t)
            readback = rec.begin("decode.readback", CAT_SERVE_DECODE, t)
        verdict = jax.device_get(verdict)
        if readback is not None:
            readback.end(self.clock())
        if sampling:
            host_logits = np.asarray(verdict, np.float32)
            target_choice = host_logits.argmax(axis=-1).astype(np.int32)
        else:
            target_choice = verdict
        now = self.clock()
        total_emitted = 0
        total_accepted = 0
        slot_accepted: List[int] = []  # aligned with rids (= active order)
        slot_emitted: List[int] = []
        for i in active:
            s = self._slots[i]
            req = s.request
            if temps[i] > 0:
                p_list = [
                    softmax(host_logits[i, j], float(temps[i]))
                    for j in range(k)
                ]
                emitted, accepted = sample_accept(
                    proposals[i], q_probs[i], p_list,
                    int(seeds[i]), int(token_index[i]),
                )
            else:
                emitted, accepted = greedy_accept(
                    proposals[i], target_choice[i]
                )
            emitted = emitted[: req.max_new_tokens - len(s.tokens)]
            if req.eos_id is not None:
                for idx, tok in enumerate(emitted):
                    if tok == req.eos_id:
                        emitted = emitted[: idx + 1]
                        break
            n = len(emitted)
            # Rollback + advance in one move: lens lands exactly past
            # the accepted rows; the rejected tail's page writes are
            # masked garbage the next window overwrites.
            self.cache.set_len(i, lens_before[i] + n)
            spec.sync_len(i, n)
            s.position += n
            s.steps += n
            s.t_last = now
            s.spec_proposed += k
            s.spec_accepted += min(accepted, n)
            total_emitted += n
            total_accepted += min(accepted, n)
            slot_accepted.append(min(accepted, n))
            slot_emitted.append(n)
            for tok in emitted:
                s.tokens.append(int(tok))
                if self.on_token is not None:
                    self.on_token(req.request_id, int(tok))
                self._maybe_finish(i, int(tok))
                if self._slots[i] is None:
                    break
        if span is not None:
            # accepted/proposed on every speculative decode chunk: the
            # per-step attribution report.py --request renders (where
            # did TPOT go — draft quality is readable off the ratio).
            # slot_accepted/slot_emitted align with rids so a single
            # request's trace sums ITS OWN numbers, not the batch's.
            # The extent ends at ``now``, before the acceptance loop,
            # as it always has.
            span.end(now, busy=len(active), rids=rids,
                     proposed=k * len(active), proposed_per_slot=k,
                     accepted=total_accepted, emitted=total_emitted,
                     slot_accepted=slot_accepted,
                     slot_emitted=slot_emitted,
                     **self._paged_attrs(pages_live))
        self.num_decode_steps += 1
        reg = registry()
        reg.counter("serve_decode_steps").inc()
        reg.counter("spec_proposed_tokens").inc(k * len(active))
        reg.counter("spec_accepted_tokens").inc(total_accepted)
        reg.counter("spec_emitted_tokens").inc(total_emitted)
        # One slot-step per active slot per window: accepted/slot_steps
        # is the per-STREAM acceptance rate (the bench's
        # accepted-tokens/step), which a batch-summed ratio would
        # overstate by the occupancy factor.
        reg.counter("spec_slot_steps").inc(len(active))

    def step(self) -> bool:
        """Seat what fits, land one decode step (``_decode_step``; a
        speculative window when a speculator is attached) and the first
        tokens around it. False when fully drained (no active slots,
        nothing the device holds unread and nothing seatable queued)."""
        # One look for the recorder a step; what the step calls reads
        # ``self._rec``. With none, nothing below reads a clock or
        # allocates for tracing.
        rec = self._rec = active_recorder()
        span = decode = None
        if rec is not None:
            span = rec.begin("engine_step", CAT_SERVE_ENGINE, self.clock())
            self._seats = 0
        try:
            for hook in self.chaos_hooks:
                # Fault injection (tpudl.serve.chaos): a kill hook
                # raises (crashing the replica driver thread exactly
                # like a real engine fault), a freeze hook sleeps here
                # holding the whole loop (the stale-heartbeat path).
                hook(self.num_decode_steps)
            if rec is not None and self._unread:
                # A decode step is in flight and this call lands it:
                # its ``decode_step`` opens before the admission, whose
                # dispatches queue behind that step on the device.
                decode = rec.begin(
                    "decode_step", CAT_SERVE_DECODE, self.clock()
                )
            self._fill_slots()
            if not self._unread and not self._active():
                # Nothing seated: the queue is empty or held only
                # expired entries (shed during the fill's pop).
                self._record_shed(
                    self.queue.drain_expired(), "shed_timeout"
                )
                return False
            if self.speculator is None:
                self._decode_step(decode)
            else:
                # Acceptance needs every slot's last token on the host.
                self._land_firsts()
                if self._active():
                    self._spec_step()
            return True
        finally:
            if span is not None:
                # Closing it also drops what an exception left open
                # beneath it. Its self time (the chaos hooks and a few
                # lines: admission and the step's host arrays have
                # spans of their own) is its duration less its
                # children's.
                self._rec = None
                span.end(
                    self.clock(), seats=self._seats,
                    busy=sum(s is not None for s in self._slots),
                )

    def run_until_drained(self) -> Dict[Any, Result]:
        while self.step():
            pass
        self._publish_occupancy()
        return self.results
