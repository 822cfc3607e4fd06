"""Multi-tenant LoRA serving: the paged adapter pool (S-LoRA's shape
on tpudl's paged substrate).

One base model stays resident ONCE (full precision or tpudl.quant
int8/fp8 — the composition the old ``lora_rank``/``weight_dtype``
mutual exclusion forbade); every tenant is a LoRA fine-tune whose A/B
factors page in and out of fixed-size pools exactly like KV pages
(PR 8): a **page is one rank unit** — one column of every site's A
factor plus the matching row of its B factor — so a rank-``r`` adapter
owns ``r`` pages across all per-layer site pools simultaneously, and
the host-owned page table rides into each decode dispatch as a small
traced input (``tpudl.models.generate.lora_paged_decode_fn``), so
loading or evicting an adapter never recompiles anything. Physical
page 0 is the never-written all-zero page: empty slots and ranks short
of ``r_max`` map to it and contribute exactly nothing through the
segmented kernel (tpudl.ops.segmented_lora).

Lifecycle contract (the PR-11 radix-tree discipline applied to
adapters):

- ``register`` keeps a HOST-side copy of each tenant's factors (the
  reload source: eviction frees device pages only, so an evicted
  tenant's next request reloads transparently —
  ``serve_adapter_reloads_total`` counts those);
- seating a request ``acquire``s its tenant (loading on demand,
  refcount++), so an in-use adapter can never be evicted mid-decode;
- under page pressure, ``refcount == 0`` residents evict LRU-first;
- ``int8`` pools store one f32 dequant scale per page per site (the
  tpudl.quant symmetric rule at page granularity), applied inside the
  kernel's gather.

Thread model: the engine thread is the only mutator; the router's
adapter-affinity probe (``resident_since``) reads cross-thread, so all
shared state sits under one lock (the RadixPrefixTree pattern).

``assert_tenant_parity`` is the acceptance gate: the heterogeneous
batched engine vs the sequential one-adapter-at-a-time reference
(each tenant's adapter MERGED into the base and run through
``generate()``) — exact tokens for f32 adapter pages, teacher-forced
logit-margin for int8 pages.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.obs import registry

#: Symmetric int8 range (the tpudl.quant / tpudl.models.paged value).
INT8_MAX = 127.0
SCALE_EPS = 1e-12


def _site_shapes(cfg) -> Dict[str, Tuple[int, int]]:
    """(in, out) dims per adaptable projection site for one Llama
    block — every ``_proj`` call site. MoE configs have no dense MLP
    projections, so only the attention sites exist there."""
    h = cfg.hidden_size
    hd = cfg.head_dim
    vd = getattr(cfg, "value_dim", hd)  # values may be another width
    sites = {
        "q_proj": (h, cfg.num_heads * hd),
        "k_proj": (h, cfg.num_kv_heads * hd),
        "v_proj": (h, cfg.num_kv_heads * vd),
        "o_proj": (cfg.num_heads * vd, h),
    }
    if getattr(cfg, "moe_experts", 0) == 0:
        sites.update({
            "gate_proj": (h, cfg.intermediate_size),
            "up_proj": (h, cfg.intermediate_size),
            "down_proj": (cfg.intermediate_size, h),
        })
    return sites


def _site_key(path: str) -> Optional[Tuple[str, str]]:
    """'model/layer_3/attention/q_proj' -> ('layer_3', 'q_proj')."""
    parts = path.split("/")
    layer = next((p for p in parts if p.startswith("layer_")), None)
    if layer is None:
        return None
    return layer, parts[-1]


class _Resident:
    """One tenant's device-side residency: the pages it owns and the
    lease bookkeeping that protects them."""

    __slots__ = ("pages", "rank", "scaling", "refcount", "stamp", "since")

    def __init__(self, pages: List[int], rank: int, scaling: float,
                 stamp: int, since: float):
        self.pages = pages
        self.rank = rank
        self.scaling = scaling
        self.refcount = 0
        self.stamp = stamp  # LRU recency (pool clock at last touch)
        self.since = since  # wall residency start (affinity signal)


class AdapterPool:
    """Paged pool of per-tenant LoRA factors for one serving engine.

    ``cfg`` is the base model's LlamaConfig (site shapes derive from
    it); ``r_max`` is the per-tenant rank budget = logical table width;
    ``num_pages`` sizes the pool (page 0 is the all-zero page, never
    allocated); ``dtype="int8"`` stores pages quantized with per-page
    f32 scales. The pool also owns the per-SLOT addressing the engine
    ships into each dispatch (``slot_table``/``slot_scale`` — the
    paged-KV page-table idiom), so the engine's adapter surface is
    ``acquire``/``bind_slot``/``free_slot``/``dispatch_args``."""

    def __init__(
        self,
        cfg,
        r_max: int,
        num_slots: int,
        num_pages: Optional[int] = None,
        dtype: Optional[str] = None,
        clock=time.monotonic,
    ):
        if r_max < 1:
            raise ValueError(f"r_max must be >= 1, got {r_max}")
        if dtype not in (None, "int8"):
            raise ValueError(
                f"adapter dtype must be None (f32 pages) or 'int8', "
                f"got {dtype!r}"
            )
        if num_pages is None:
            # Default: 64 resident full-rank adapters (the bench's
            # headline geometry) + the zero page.
            num_pages = 64 * r_max + 1
        if num_pages < r_max + 1:
            raise ValueError(
                f"num_pages={num_pages} cannot hold one rank-{r_max} "
                f"adapter (+ the zero page)"
            )
        self.r_max = int(r_max)
        self.num_pages = int(num_pages)
        self.num_slots = int(num_slots)
        self.quantized = dtype == "int8"
        self.clock = clock
        self._sites = _site_shapes(cfg)
        self._layers = [f"layer_{i}" for i in range(cfg.num_layers)]
        store = jnp.int8 if self.quantized else jnp.float32
        pools: Dict[str, dict] = {}
        for layer in self._layers:
            pools[layer] = {}
            for site, (fin, fout) in self._sites.items():
                entry = {
                    "a": jnp.zeros((self.num_pages, fin), store),
                    "b": jnp.zeros((self.num_pages, fout), store),
                }
                if self.quantized:
                    entry["a_scale"] = jnp.zeros(
                        (self.num_pages,), jnp.float32
                    )
                    entry["b_scale"] = jnp.zeros(
                        (self.num_pages,), jnp.float32
                    )
                pools[layer][site] = entry
        #: The traced pool pytree every dispatch carries. Replaced
        #: functionally on load (jnp ``.at`` scatters) — shapes never
        #: change, so placement churn never recompiles.
        self.pools = pools
        self._lock = threading.RLock()
        self._free: List[int] = list(range(1, self.num_pages))
        self._resident: Dict[Any, _Resident] = {}
        self._host: Dict[Any, dict] = {}
        self._was_resident: set = set()
        self._slot_tenant: Dict[int, Any] = {}
        self._clock_ticks = 0
        self._scatter_jit: Dict[int, Any] = {}
        self.slot_table = np.zeros(
            (self.num_slots, self.r_max), np.int32
        )
        self.slot_scale = np.zeros((self.num_slots,), np.float32)
        self.num_loads = 0
        self.num_reloads = 0
        self.num_evictions = 0

    # -- registration ---------------------------------------------------

    def register(self, tenant: Any, adapter: Any,
                 alpha: float = 16.0) -> None:
        """Register one tenant's adapter (a LoRA param tree, or the
        ``tpudl.models.lora.extract_adapters`` flat form). Host-side
        only — device pages load lazily at first acquire. Shapes and
        rank are validated here, at the door."""
        from tpudl.models.lora import as_flat_adapters

        flat = as_flat_adapters(adapter)
        if not flat:
            raise ValueError(
                f"tenant {tenant!r}: adapter tree holds no lora_a/"
                f"lora_b leaves"
            )
        sites: Dict[Tuple[str, str], Tuple[np.ndarray, np.ndarray]] = {}
        rank = None
        for path, factors in flat.items():
            key = _site_key(path)
            if key is None:
                raise ValueError(
                    f"tenant {tenant!r}: adapter site {path!r} names no "
                    f"layer_<i> segment"
                )
            layer, site = key
            if site not in self._sites:
                raise ValueError(
                    f"tenant {tenant!r}: {path!r} is not an adaptable "
                    f"site (known: {sorted(self._sites)})"
                )
            a = np.asarray(factors["lora_a"], np.float32)
            b = np.asarray(factors["lora_b"], np.float32)
            fin, fout = self._sites[site]
            if a.shape[0] != fin or b.shape[1] != fout or (
                a.shape[1] != b.shape[0]
            ):
                raise ValueError(
                    f"tenant {tenant!r}: {path!r} factors "
                    f"{a.shape}x{b.shape} do not fit site ({fin}, {fout})"
                )
            if rank is None:
                rank = int(a.shape[1])
            elif int(a.shape[1]) != rank:
                raise ValueError(
                    f"tenant {tenant!r}: mixed ranks across sites "
                    f"({rank} vs {a.shape[1]}) — one rank per tenant"
                )
            sites[(layer, site)] = (a, b)
        if rank < 1 or rank > self.r_max:
            raise ValueError(
                f"tenant {tenant!r}: rank {rank} outside [1, r_max="
                f"{self.r_max}]"
            )
        with self._lock:
            res = self._resident.get(tenant)
            if res is not None:
                # Re-registration must not leave the OLD factors
                # serving from still-resident pages (the refreshed LRU
                # stamp would even keep them alive): drop the cached
                # residency so the next acquire loads the new version.
                # A leased residency cannot be swapped under a seated
                # request — that is a caller error, not an eviction.
                if res.refcount > 0:
                    raise ValueError(
                        f"tenant {tenant!r} is leased by a seated "
                        f"request — re-register only between requests"
                    )
                self._resident.pop(tenant)
                self._free.extend(res.pages)
            self._host[tenant] = {
                "sites": sites,
                "rank": rank,
                "scaling": float(alpha) / rank,
            }

    def knows(self, tenant: Any) -> bool:
        with self._lock:
            return tenant in self._host

    @property
    def tenants(self) -> List[Any]:
        with self._lock:
            return list(self._host)

    # -- residency ------------------------------------------------------

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def evictable_pages(self) -> int:
        """Pages held by refcount-0 residents — reclaimable without
        touching any seated request."""
        with self._lock:
            return sum(
                r.rank for r in self._resident.values() if r.refcount == 0
            )

    def can_seat(self, tenant: Any) -> bool:
        """Admission predicate: is (or could) this tenant('s adapter)
        be resident right now? The engine's ``_fits`` consults it so a
        request is only seated once its adapter pages are securable."""
        with self._lock:
            host = self._host.get(tenant)
            if host is None:
                return False
            if tenant in self._resident:
                return True
            return host["rank"] <= len(self._free) + sum(
                r.rank
                for r in self._resident.values()
                if r.refcount == 0
            )

    def can_ever_seat(self, tenant: Any) -> bool:
        with self._lock:
            host = self._host.get(tenant)
            return host is not None and (
                host["rank"] <= self.num_pages - 1
            )

    def resident_since(self, tenant: Any) -> Optional[float]:
        """When this tenant's adapter became resident (None = not
        resident) — the router's adapter-affinity probe: the replica
        holding the adapter LONGEST wins placement ties. Read-only and
        lock-guarded, so the router calls it cross-thread."""
        with self._lock:
            res = self._resident.get(tenant)
            return res.since if res is not None else None

    def _ensure_resident(self, tenant: Any) -> _Resident:
        """Callers hold the lock. Loads (evicting LRU refcount-0
        residents under pressure) when not already resident."""
        res = self._resident.get(tenant)
        self._clock_ticks += 1
        if res is not None:
            res.stamp = self._clock_ticks
            return res
        host = self._host.get(tenant)
        if host is None:
            raise KeyError(
                f"tenant {tenant!r} is not registered with this pool"
            )
        rank = host["rank"]
        while rank > len(self._free):
            victim = min(
                (
                    (tid, r)
                    for tid, r in self._resident.items()
                    if r.refcount == 0
                ),
                key=lambda item: item[1].stamp,
                default=None,
            )
            if victim is None:
                raise RuntimeError(
                    f"adapter pool exhausted: tenant {tenant!r} needs "
                    f"{rank} pages, {len(self._free)} free and every "
                    f"resident adapter is leased (admission should "
                    f"have checked can_seat)"
                )
            tid, r = victim
            self._resident.pop(tid)
            self._free.extend(r.pages)
            self.num_evictions += 1
            registry().counter("serve_adapter_evictions_total").inc()
        pages = [self._free.pop() for _ in range(rank)]
        self._scatter(host, pages)
        res = _Resident(
            pages, rank, host["scaling"], self._clock_ticks, self.clock()
        )
        self._resident[tenant] = res
        self.num_loads += 1
        reg = registry()
        reg.counter("serve_adapter_loads_total").inc()
        if tenant in self._was_resident:
            self.num_reloads += 1
            reg.counter("serve_adapter_reloads_total").inc()
        self._was_resident.add(tenant)
        reg.gauge("serve_adapters_resident").set(len(self._resident))
        return res

    def _scatter(self, host: dict, pages: List[int]) -> None:
        """Write one tenant's rank rows into every (layer, site) pool
        at ``pages``. Row layout: page j holds A[:, j] and B[j, :].
        Missing sites scatter zeros (pages are recycled — stale rows
        from an evicted tenant must not leak through). One jitted
        scatter per rank value (the _seat_jit idiom)."""
        rank = len(pages)
        updates: Dict[str, dict] = {}
        for layer in self._layers:
            updates[layer] = {}
            for site, (fin, fout) in self._sites.items():
                factors = host["sites"].get((layer, site))
                if factors is None:
                    a_rows = np.zeros((rank, fin), np.float32)
                    b_rows = np.zeros((rank, fout), np.float32)
                else:
                    a, b = factors
                    a_rows = np.ascontiguousarray(a.T)  # [r, in]
                    b_rows = np.ascontiguousarray(b)  # [r, out]
                entry: dict = {}
                if self.quantized:
                    a_q, a_sc = _quantize_rows(a_rows)
                    b_q, b_sc = _quantize_rows(b_rows)
                    entry = {
                        "a": a_q, "b": b_q,
                        "a_scale": a_sc, "b_scale": b_sc,
                    }
                else:
                    entry = {"a": a_rows, "b": b_rows}
                updates[layer][site] = entry
        fn = self._scatter_jit.get(rank)
        if fn is None:
            fn = jax.jit(
                lambda pools, ups, ids: jax.tree.map(
                    lambda p, u: p.at[ids].set(u.astype(p.dtype)),
                    pools, ups,
                )
            )
            self._scatter_jit[rank] = fn
        self.pools = fn(
            self.pools, updates, jnp.asarray(pages, jnp.int32)
        )

    # -- the engine surface ---------------------------------------------

    def acquire(self, tenant: Optional[Any]):
        """Pin one tenant for a request being seated (loading on
        demand): refcount++ so eviction can never take its pages
        mid-decode. Returns ``(table_row [r_max] int32, scaling)`` —
        the batch-1 prefill's addressing. ``tenant=None`` (a request
        served off the plain base) returns the zero row unpinned."""
        row = np.zeros((self.r_max,), np.int32)
        if tenant is None:
            return row, 0.0
        with self._lock:
            res = self._ensure_resident(tenant)
            res.refcount += 1
            row[: res.rank] = res.pages
            return row, res.scaling

    def release(self, tenant: Optional[Any]) -> None:
        """Drop one ``acquire`` pin (failure paths; ``free_slot`` is
        the normal route). Refcount-0 residents stay CACHED — they are
        the evictable pool, reclaimed only under pressure."""
        if tenant is None:
            return
        with self._lock:
            res = self._resident.get(tenant)
            assert res is not None and res.refcount > 0, (
                f"release of unpinned tenant {tenant!r}"
            )
            res.refcount -= 1

    def bind_slot(self, slot: int, tenant: Optional[Any]) -> None:
        """Point ``slot``'s table row at an ALREADY-ACQUIRED tenant's
        pages (the pin transfers from the seat path to the slot; it is
        dropped by ``free_slot``). ``tenant=None`` zeroes the row."""
        with self._lock:
            if tenant is None:
                self.slot_table[slot, :] = 0
                self.slot_scale[slot] = 0.0
                self._slot_tenant.pop(slot, None)
                return
            res = self._resident.get(tenant)
            assert res is not None, (
                f"bind_slot for non-resident tenant {tenant!r} — "
                f"acquire first"
            )
            self.slot_table[slot, :] = 0
            self.slot_table[slot, : res.rank] = res.pages
            self.slot_scale[slot] = res.scaling
            self._slot_tenant[slot] = tenant

    def free_slot(self, slot: int) -> None:
        """Zero the slot's addressing and drop its tenant pin."""
        with self._lock:
            tenant = self._slot_tenant.pop(slot, None)
            self.slot_table[slot, :] = 0
            self.slot_scale[slot] = 0.0
            if tenant is not None:
                res = self._resident.get(tenant)
                if res is not None and res.refcount > 0:
                    res.refcount -= 1

    def dispatch_args(self):
        """The three extra traced inputs every multi-tenant dispatch
        carries: (pools pytree, slot table [B, r_max], slot scale
        [B]); copies of the host's two, which a slot's binding moves
        while a dispatch may still be queued."""
        with self._lock:
            return (
                self.pools,
                jnp.asarray(self.slot_table.copy()),
                jnp.asarray(self.slot_scale.copy()),
            )

    # -- accounting -----------------------------------------------------

    @property
    def nbytes(self) -> int:
        """Resident bytes: every pool leaf (int8 values AND their f32
        scale rows) plus the host-side slot addressing — the number
        ``serve_adapters_per_gb`` divides into, reconciled against the
        actual buffer nbytes by regression test (the PR-8
        byte-accounting idiom: an estimate that drifts from ``.nbytes``
        silently corrupts the capacity headline)."""
        with self._lock:
            device = int(sum(
                leaf.nbytes for leaf in jax.tree.leaves(self.pools)
            ))
            return device + self.slot_table.nbytes + self.slot_scale.nbytes

    @property
    def bytes_per_page(self) -> int:
        """Stored bytes one page (one rank unit) occupies across every
        (layer, site) pool — ``nbytes`` minus the host tables, over the
        page count. An adapter of rank r costs exactly
        ``r * bytes_per_page`` of pool capacity."""
        device = int(sum(
            leaf.nbytes for leaf in jax.tree.leaves(self.pools)
        ))
        return device // self.num_pages

    def adapters_per_gb(self, rank: Optional[int] = None) -> float:
        """Resident adapters one GB of pool holds at ``rank`` (default
        r_max) — the capacity headline the bench banks."""
        rank = self.r_max if rank is None else rank
        return 1e9 / (self.bytes_per_page * rank)

    def stats(self) -> dict:
        with self._lock:
            return {
                "registered": len(self._host),
                "resident": len(self._resident),
                "leased": sum(
                    1 for r in self._resident.values() if r.refcount > 0
                ),
                "free_pages": len(self._free),
                "num_pages": self.num_pages,
                "r_max": self.r_max,
                "quantized": self.quantized,
                "loads": self.num_loads,
                "reloads": self.num_reloads,
                "evictions": self.num_evictions,
            }


def _quantize_rows(rows: np.ndarray):
    """Symmetric int8 per page row: ``rows`` [r, dim] -> (int8 rows,
    f32 scale [r]) with ``q * scale`` reconstructing to half a step of
    the row max (the tpudl.models.paged.quantize_kv rule at page
    granularity)."""
    scale = np.maximum(
        np.abs(rows).max(axis=-1) / INT8_MAX, SCALE_EPS
    ).astype(np.float32)
    q = np.clip(
        np.round(rows / scale[:, None]), -INT8_MAX, INT8_MAX
    ).astype(np.int8)
    return q, scale


def assert_tenant_parity(
    session,
    base_model,
    base_params,
    adapters: Dict[Any, Any],
    requests: Sequence,
    atol: Optional[float] = None,
    alpha: float = 16.0,
) -> None:
    """Serve the whole multi-tenant batch through ONE heterogeneous
    engine run, then check every greedy request against the sequential
    one-adapter-at-a-time reference: its tenant's adapter MERGED into
    the base tree (``tpudl.models.lora.merge_adapter``) and decoded
    with plain ``generate()``. ``atol=None`` demands exact tokens (the
    f32 adapter-page contract — COW addressing must never change
    tokens); ``atol`` set is the int8-page contract: a flip must be a
    genuine near-tie under the teacher-forced logit margin
    (``assert_serving_parity``'s rule, per-tenant reference)."""
    from tpudl.models.lora import as_flat_adapters, merge_adapter
    from tpudl.serve.api import assert_tokens_match_generate

    results = session.serve(list(requests))
    merged_cache: Dict[Any, Any] = {}
    for req in requests:
        if req.temperature != 0.0:
            continue
        res = results[req.request_id]
        assert res.ok, (req.request_id, res.finish_reason)
        tenant = req.tenant
        if tenant not in merged_cache:
            if tenant is None:
                merged_cache[tenant] = base_params
            else:
                merged_cache[tenant] = merge_adapter(
                    base_params,
                    as_flat_adapters(adapters[tenant]),
                    alpha=alpha,
                )
        assert_tokens_match_generate(
            base_model, merged_cache[tenant], req,
            np.asarray(res.tokens), atol,
        )
