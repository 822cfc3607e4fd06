"""The serving KV cache: per-layer page pools behind the engine.

The engine's decode program is compiled ONCE for fixed-shape pools
(``[num_pages, page_size, ...]`` per layer, or the same bytes held
folded: ``PagedKVCache``). Continuous batching never reshapes them —
requests come and go by mutating WHICH pages a slot's
host-side page table row maps (``PagedKVCache``): a seat scatters a
batch-1 prefill's dense cache rows (the shape
tpudl.models.llama.LlamaAttention builds in decode mode) into the
slot's pages, a free points the row back at the trash page. Every
per-row op in the model is batch-independent, so a refill is
bit-invisible to the other slots (asserted by tests/test_serve.py).
"""

from __future__ import annotations

import functools
import json
import struct
import threading
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from tpudl.obs import registry
from tpudl.obs.spans import startup_span


def _is_valid_leaf(leaf) -> bool:
    """The per-slot validity buffer: [num_slots, max_seq_len] bool."""
    return leaf.ndim == 2 and leaf.dtype == jnp.bool_


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------


#: The leaves of a dense decode cache that are not rows: which
#: positions are real, and where the next chunk is written; and, on a
#: layer that keeps a window of the context only, ``window``, whose
#: LENGTH is the window (a template keeps shapes, not values).
_ROW_BOOKKEEPING = frozenset({"valid", "index"})
_ROW_META = _ROW_BOOKKEEPING | {"window"}


def _is_attn_cache(node) -> bool:
    """A per-layer dense decode cache dict, as an attention layer's
    decode branch declares it: ``valid``, ``index`` and the layer's ROW
    leaves ``[B, max_seq, ...]`` — ``k`` and ``v`` for grouped-query
    attention (``[.., Hkv, D]``), the one ``kv`` for latent attention
    (``[.., C]``, no head axis). The pool follows what is declared: a
    row leaf ``name`` is pooled as ``pages_<name>`` (+ ``scale_<name>``
    for int8 pools)."""
    from collections.abc import Mapping

    return (
        isinstance(node, Mapping) and set(node) > _ROW_BOOKKEEPING
        and all(hasattr(v, "shape") for v in node.values())
    )


def _window_of(attn) -> int:
    """The window a layer's dense cache declares (0: the whole
    context)."""
    return int(attn["window"].shape[0]) if "window" in attn else 0


def _row_names(attn) -> list:
    """The row leaves a layer declares, by name."""
    return sorted(set(attn) - _ROW_META)


def _is_pool(node) -> bool:
    """A per-layer page-pool dict (``pages_<name>`` leaves)."""
    from collections.abc import Mapping

    return isinstance(node, Mapping) and any(
        str(k).startswith("pages_") for k in node
    )


def _map_attn_caches(tree, fn):
    """Rebuild a cache pytree (nested Mappings) with every per-layer
    attention cache dict replaced by ``fn(dict)`` — the surgery that
    turns the dense eval_shape template into page pools, and pairs
    pool/row layers during seating."""
    from collections.abc import Mapping

    if _is_attn_cache(tree):
        return fn(tree)
    if isinstance(tree, Mapping):
        return {k: _map_attn_caches(v, fn) for k, v in tree.items()}
    return tree


def _zip_attn_caches(a, b, fn):
    """Walk two structurally-parallel cache pytrees; replace each
    per-layer pair with ``fn(a_dict, b_dict)`` (used to scatter a dense
    prefill row cache into the matching layer's page pool)."""
    from collections.abc import Mapping

    if _is_pool(a) or _is_attn_cache(a):
        return fn(a, b)
    if isinstance(a, Mapping):
        return {k: _zip_attn_caches(v, b[k], fn) for k, v in a.items()}
    return a


# ---------------------------------------------------------------------------
# Radix prefix tree (copy-on-write page sharing)
# ---------------------------------------------------------------------------


def block_hash(block: Tuple[int, ...]) -> int:
    """Child-index key for one page-sized token block. Module-level so
    tests can monkeypatch it into collisions: the tree NEVER trusts the
    hash alone — every lookup re-compares the full token tuple."""
    return hash(block)


class _RadixNode:
    """One compressed radix-tree edge: a run of page-sized token blocks
    and the physical pages holding their KV, parallel lists. A lease
    (one seated slot mapping through this node) increments ``refcount``
    on the node AND every ancestor, so ``refcount == 0`` implies the
    whole subtree is lease-free — the eviction-safety invariant."""

    __slots__ = (
        "blocks", "pages", "children", "parent", "refcount", "stamp",
    )

    def __init__(self, blocks, pages, parent):
        self.blocks: List[Tuple[int, ...]] = blocks
        self.pages: List[int] = pages
        #: hash(first block) -> [nodes]. A LIST per hash: collisions
        #: resolve by comparing the stored block tuples, never the
        #: hash alone.
        self.children: Dict[int, List["_RadixNode"]] = {}
        self.parent: Optional["_RadixNode"] = parent
        self.refcount = 0
        self.stamp = 0  # LRU recency (tree._clock at last touch)


class RadixPrefixTree:
    """Prefix index over page-granular token blocks -> physical KV
    pages (the vLLM/SGLang RadixAttention idea on tpudl's paged
    substrate). ``match_and_lease`` walks a prompt's full token blocks
    down the tree, SPLITTING a partially-matched compressed edge at the
    divergence point (the COW-split: the shared prefix half keeps the
    shared pages, both continuations hang under it), pins every matched
    node with a refcount lease, and hands back the matched pages —
    which the seat maps into the new slot's page table FOR FREE.
    ``insert_suffix`` registers the freshly-prefilled full blocks so
    later requests hit them. Releasing a lease (slot freed) does NOT
    free the pages: refcount-0 nodes stay cached and become the
    EVICTABLE pool, reclaimed leaf-first in LRU order under page
    pressure (``evict``).

    Thread model: the owning engine thread is the only mutator; the
    router's prefix-affinity probe calls ``match_len`` concurrently,
    so every public method takes the internal lock. Scans are O(tree)
    — prefix trees here index a handful of system prompts, not the
    token universe; keep it simple until a bench says otherwise."""

    def __init__(self, page_size: int):
        self.page_size = int(page_size)
        self.root = _RadixNode([], [], None)
        self._lock = threading.RLock()
        self._clock = 0
        #: Pages in refcount-0 nodes — reclaimable without touching any
        #: live slot (maintained incrementally by lease/release).
        self.evictable_pages = 0
        #: Pages held by the tree in total (leased + evictable).
        self.cached_pages = 0
        self.num_splits = 0
        self.num_evictions = 0

    # -- block helpers --------------------------------------------------

    def blocks_of(self, tokens) -> List[Tuple[int, ...]]:
        """The FULL page-sized token blocks of a prompt (the sharable
        granularity; a trailing partial block is always private)."""
        ps = self.page_size
        n = len(tokens) // ps
        return [tuple(int(t) for t in tokens[i * ps:(i + 1) * ps])
                for i in range(n)]

    def _child(self, node: _RadixNode, block) -> Optional[_RadixNode]:
        for cand in node.children.get(block_hash(block), ()):
            # Full token-block compare: a hash collision must select by
            # VALUE or two different prompts would share wrong KV.
            if cand.blocks[0] == block:
                return cand
        return None

    def _attach(self, parent: _RadixNode, node: _RadixNode) -> None:
        node.parent = parent
        parent.children.setdefault(block_hash(node.blocks[0]), []).append(
            node
        )

    def _detach(self, node: _RadixNode) -> None:
        key = block_hash(node.blocks[0])
        siblings = node.parent.children.get(key, [])
        siblings.remove(node)
        if not siblings:
            del node.parent.children[key]

    # -- queries --------------------------------------------------------

    def match_len(self, tokens) -> int:
        """Longest cached prefix of ``tokens`` in TOKENS (page-granular;
        read-only — the router's prefix-affinity probe)."""
        return self.match_info(tokens)[0]

    def match_info(self, tokens) -> Tuple[int, int]:
        """``(matched_tokens, matched_evictable_pages)`` — the second
        number counts matched pages currently sitting in the EVICTABLE
        pool (refcount 0). Admission needs it: seating pins those
        pages, so they cannot also satisfy the request's remaining
        allocation — counting them both as "mapped for free" and as
        "reclaimable" would admit work the seat cannot place."""
        with self._lock:
            blocks = self.blocks_of(tokens)
            node, i = self.root, 0
            evictable = 0
            while i < len(blocks):
                child = self._child(node, blocks[i])
                if child is None:
                    break
                j = 0
                while (
                    j < len(child.blocks)
                    and i + j < len(blocks)
                    and child.blocks[j] == blocks[i + j]
                ):
                    j += 1
                if j and child.refcount == 0:
                    # A partial match splits at lease time; the matched
                    # half inherits this refcount, so counting its j
                    # pages is exact.
                    evictable += j
                i += j
                if j < len(child.blocks):
                    break
                node = child
            return i * self.page_size, evictable

    # -- lease lifecycle ------------------------------------------------
    #
    # A lease is represented by its DEEPEST node; acquire/release walk
    # the ancestor path. That makes COW-splits lease-transparent: the
    # split copies the node's refcount onto the new upper half (every
    # lease through the node also covers its prefix), and a later
    # release's root-walk decrements both halves exactly once.

    def _acquire_path(self, node: _RadixNode) -> None:
        self._clock += 1
        while node is not None and node is not self.root:
            if node.refcount == 0:
                self.evictable_pages -= len(node.pages)
            node.refcount += 1
            node.stamp = self._clock
            node = node.parent

    def release(self, lease: Optional[_RadixNode]) -> None:
        """Drop one seat's pin (``lease`` = the deepest node
        ``match_and_lease``/``insert_suffix`` handed out). Refcount-0
        nodes stay CACHED — their pages join the evictable pool, freed
        only by LRU eviction under pressure."""
        if lease is None:
            return
        with self._lock:
            node = lease
            while node is not None and node is not self.root:
                node.refcount -= 1
                assert node.refcount >= 0, "radix lease released twice"
                if node.refcount == 0:
                    self.evictable_pages += len(node.pages)
                node = node.parent

    def match_and_lease(self, tokens):
        """Walk ``tokens``'s full blocks, splitting a partially-matched
        edge at the divergence, and LEASE the matched path. Returns
        ``(matched_pages, deepest_node_or_None)``; the caller owns the
        lease and must ``release`` it exactly once
        (``PagedKVCache.free`` does, per seated slot)."""
        with self._lock:
            blocks = self.blocks_of(tokens)
            node, i = self.root, 0
            pages: List[int] = []
            while i < len(blocks):
                child = self._child(node, blocks[i])
                if child is None:
                    break
                j = 0
                while (
                    j < len(child.blocks)
                    and i + j < len(blocks)
                    and child.blocks[j] == blocks[i + j]
                ):
                    j += 1
                if j == 0:
                    break
                if j < len(child.blocks):
                    # Divergence (or prompt end) inside the compressed
                    # edge: split so the matched half is its own node —
                    # leases and eviction then stay whole-node.
                    child = self._split_at(child, j)
                pages.extend(child.pages)
                i += j
                node = child
            if node is self.root:
                return pages, None
            self._acquire_path(node)
            return pages, node

    def _split_at(self, node: _RadixNode, j: int) -> _RadixNode:
        """COW-split a compressed edge at block ``j``: blocks[:j] become
        a new (shared) parent keeping those pages, blocks[j:] stay on
        ``node``, re-hung underneath. Refcount/stamp copy to the new
        parent — every lease through ``node`` also covers its prefix,
        so the path invariant (ancestor refcount >= descendant) holds."""
        upper = _RadixNode(node.blocks[:j], node.pages[:j], None)
        upper.refcount = node.refcount
        upper.stamp = node.stamp
        parent = node.parent
        self._detach(node)
        self._attach(parent, upper)
        node.blocks = node.blocks[j:]
        node.pages = node.pages[j:]
        self._attach(upper, node)
        self.num_splits += 1
        return upper

    def insert_suffix(self, parent, blocks, pages):
        """Register freshly-prefilled full blocks under ``parent`` (the
        deepest matched node, or None for the root): the tree takes
        OWNERSHIP of those pages (they return to the pool only via
        eviction). The new node is born refcount-1 — it extends the
        seating slot's lease, whose ancestors were already pinned by
        ``match_and_lease`` — and becomes the lease's deepest node.
        Returns None when there is nothing to insert (the caller keeps
        the match lease as-is)."""
        if not blocks:
            return None
        assert len(blocks) == len(pages)
        with self._lock:
            node = _RadixNode(list(blocks), list(pages), None)
            self._attach(parent if parent is not None else self.root, node)
            self.cached_pages += len(pages)
            node.refcount = 1  # pinned by the seating slot from birth
            self._clock += 1
            node.stamp = self._clock
            return node

    # -- eviction -------------------------------------------------------

    def _evictable_leaves(self) -> List[_RadixNode]:
        out: List[_RadixNode] = []

        def walk(node: _RadixNode) -> None:
            for cands in node.children.values():
                for child in cands:
                    walk(child)
            if node is not self.root and node.refcount == 0 and (
                not node.children
            ):
                out.append(node)

        walk(self.root)
        return out

    def evict(self, need_pages: int) -> List[int]:
        """Reclaim up to ``need_pages`` pages by evicting refcount-0
        LEAF nodes oldest-stamp-first (leaf-first keeps the tree
        consistent: an interior node only becomes a leaf once its
        subtree is gone, and refcount-0 guarantees no lease is
        anywhere below). Returns the freed page ids."""
        freed: List[int] = []
        with self._lock:
            while len(freed) < need_pages:
                leaves = self._evictable_leaves()
                if not leaves:
                    break
                victim = min(leaves, key=lambda n: n.stamp)
                self._detach(victim)
                freed.extend(victim.pages)
                self.cached_pages -= len(victim.pages)
                self.evictable_pages -= len(victim.pages)
                self.num_evictions += 1
        return freed

    def stats(self) -> dict:
        with self._lock:
            n_nodes = 0
            stack = [self.root]
            while stack:
                node = stack.pop()
                n_nodes += 1
                for cands in node.children.values():
                    stack.extend(cands)
            return {
                "nodes": n_nodes - 1,  # excluding the root
                "cached_pages": self.cached_pages,
                "evictable_pages": self.evictable_pages,
                "splits": self.num_splits,
                "evictions": self.num_evictions,
            }


class PagedKVCache:
    """The serving KV cache: paged, optionally int8-quantized.

    KV lives in per-layer page pools ``[num_pages, page_size, Hkv, D]``
    (int8 with ``[num_pages, page_size, Hkv]`` f32 dequant scales when
    ``kv_dtype="int8"``); a slot owns the pages its HOST-side page
    table row maps. WHICH pools a layer has is the layer's to declare
    (``_is_attn_cache``): a grouped-query layer declares ``k`` and
    ``v``, a latent (MLA) layer ONE headless leaf ``kv`` and so one
    pool ``pages_kv``; seating, the prefix gather, migration and
    ``nbytes`` walk the declared leaves.

    The SHAPE a leaf is held in is the cache's to choose, by one rule
    (tpudl.models.paged.page_fold, from the page size, the row's
    trailing shape and the stored dtype): the shape whose default
    layout on the chip is major-to-minor with a page contiguous, so
    that no program re-lays the pool on the way in or out. A row with
    a head axis, or one that is whole 128-value lanes wide, is held as
    declared (``[num_pages, page_size, Hkv, D]``; a layer one of whose
    head widths is not whole lanes, keys 192 wide beside values 128
    wide, holds both leaves with the heads merged into the lanes,
    ``[num_pages, page_size, Hkv * D]``, so that no head's row is
    padded to 256: tpudl.models.paged.heads_in_lanes); a headless row of
    another width C (the latent 576) is held FOLDED, ``f`` positions
    to a held row: ``[num_pages, page_size / f, f * C]``, position
    ``t`` of a page in row ``t // f``, lanes ``(t % f) * C ...``
    (576 on pages of 16: ``[num_pages, 8, 1152]``, a page one
    contiguous 18 KB run). The bytes, ``nbytes`` and ``page_size`` are
    what was declared; ``folds`` says what was chosen. The decode
    program addresses the held shape (``paged_write``, ``paged_gather``
    and the latent attention); the seats, the prefix gather and
    migration keep LOGICAL rows ``[T, C]`` on the outside and reshape
    a slot's worth of pages, never a pool. Three consequences the
    engine builds on:

    - **No shared write index**: each slot carries its own length, so
      a long generation in one slot never costs another its cache.
    - **Reservation-based admission**: ``seat`` reserves every page a
      request could need (``ceil((prompt_len + max_new_tokens) /
      page_size)``) up front, so a seated request can NEVER strand
      mid-decode on an empty pool; ``fits_tokens`` is the admission
      predicate.
    - **Physical page 0 is the trash page**: freed/idle slots' table
      rows point at it, so their ride-along decode writes land where no
      live slot ever reads.

    ``template`` is the SAME dense cache template ``ServeSession``
    already derives (eval_shape of the prefill contract); the pools are
    built by tree surgery on it, so the paged cache needs no new model
    contract beyond ``paged_decode_fn``. Addressing state (page table,
    per-slot start/len) is host-side numpy, shipped into each decode
    dispatch as small traced inputs — seating and freeing never
    recompile anything.

    **The pool changes hands, it is not copied.** ``self.cache`` is the
    ONE live pool tree. Every compiled program that takes it and
    returns its successor (decode, verify, the seat programs, the
    migration scatter) donates it, so the scatter happens in place and
    no second pool is ever allocated; a tree that went into such a
    program is dead afterwards (``Array has been deleted``). So nothing
    keeps a pool tree: programs that write go through ``decode`` or the
    seat / import methods here, programs that only read (the gathers)
    take ``self.cache`` at the moment they are dispatched. The counter
    ``serve_kv_pool_copies`` counts the dispatches whose pool survived,
    which is XLA (or a program jitted without ``donate_argnums``)
    copying after all; it reads 0.

    **Two groups of layers under one manager.** A layer that declares
    a ``window`` (its dense cache's ``window`` leaf: sliding-window
    attention) needs the last ``window`` positions only, so its pool
    is a pool of RINGS: ``ring_pages = ceil(window / page_size) + 1``
    pages a slot, ``num_slots * ring_pages + 1`` in all, whatever the
    context, against ``pages_per_slot`` a slot for a layer that keeps
    the context. Position ``t`` lies on ring page ``(t // page_size)
    mod ring_pages``. One manager holds both: ``seat`` reserves the
    full-context pages and takes a ring, scatters the whole prompt
    into the first and the prompt's last ``ring_pages`` pages into the
    second; ``free`` returns both; ``start`` / ``lens`` are shared;
    the decode program gets ``(page_table, ring_table)`` and a window
    layer addresses its ring through
    tpudl.models.paged.PagedView.ring_view. ``pages_reserved`` /
    ``tokens_live`` count the full-context group,
    ``pages_reserved_window`` / ``tokens_live_window`` the rings. What
    is not wired to rings says so: prefix sharing, migration and a
    mesh-committed pool raise with a sentence.

    ``prefix_share=True`` adds the RADIX layer (``RadixPrefixTree``):
    seating goes LEFT-ALIGNED through ``seat_shared`` — token ``i`` at
    logical position ``i``, so identical token prefixes are
    page-identical — matched full pages map copy-on-write for free,
    freed prompts stay CACHED (evictable at refcount 0, reclaimed LRU
    leaf-first under pressure), and ``gather_prefix_rows`` turns a
    cached prefix back into dense rows for the chunked suffix prefill.
    """

    def __init__(
        self,
        template: Any,
        page_size: int = 16,
        num_pages: Optional[int] = None,
        kv_dtype: Optional[str] = None,
        max_target_len: Optional[int] = None,
        prefix_share: bool = False,
    ):
        import numpy as np

        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (store dtype) or 'int8', "
                f"got {kv_dtype!r}"
            )
        valid_leaves = [
            leaf
            for leaf in jax.tree.leaves(
                template, is_leaf=lambda x: hasattr(x, "shape")
            )
            if _is_valid_leaf(leaf)
        ]
        if not valid_leaves:
            raise ValueError(
                "cache template has no [num_slots, max_seq_len] bool "
                "validity leaf — not a tpudl decode cache"
            )
        self.num_slots = int(valid_leaves[0].shape[0])
        self.model_seq_len = int(valid_leaves[0].shape[1])
        self.page_size = int(page_size)
        self.quantized = kv_dtype == "int8"
        cap = max_target_len if max_target_len is not None else (
            self.model_seq_len
        )
        if cap > self.model_seq_len:
            raise ValueError(
                f"max_target_len {cap} exceeds the model's compiled "
                f"sequence bound {self.model_seq_len}"
            )
        self.pages_per_slot = -(-cap // self.page_size)
        if num_pages is None:
            # Capacity parity by default: every slot can hold its whole
            # span (+1 trash page); overcommit or shrink via num_pages.
            num_pages = self.num_slots * self.pages_per_slot + 1
        if num_pages < 2 + self.pages_per_slot - 1:
            raise ValueError(
                f"num_pages={num_pages} cannot hold even one slot "
                f"(pages_per_slot={self.pages_per_slot} + trash page)"
            )
        self.num_pages = int(num_pages)
        # The window group: layers whose dense cache declares a window.
        windows = set()
        _map_attn_caches(
            template, lambda attn: windows.add(_window_of(attn)) or attn
        )
        windows.discard(0)
        if len(windows) > 1:
            raise ValueError(
                f"one page manager keeps one ring size: the layers "
                f"declare windows {sorted(windows)}"
            )
        self.window = windows.pop() if windows else 0
        if self.window and prefix_share:
            raise ValueError(
                "prefix sharing is not wired to window layers: a ring "
                "holds a slot's last positions, which no other slot's "
                "prefix can map"
            )
        self.ring_pages = (
            -(-self.window // self.page_size) + 1 if self.window else 0
        )
        self.num_ring_pages = self.num_slots * self.ring_pages + 1
        # Bytes ONE position takes over all the full-context layers'
        # pools, and over all the window layers' (``bytes_live``).
        self.row_bytes = [0, 0]

        def to_pool(attn: dict) -> dict:
            from tpudl.models.paged import heads_in_lanes, page_fold

            pages = self.num_ring_pages if _window_of(attn) else self.num_pages
            page = (pages, self.page_size)
            pool = {}
            names = _row_names(attn)
            # A layer with a head width that is not whole lanes holds
            # its heads merged into the lanes, k and v alike, so that
            # the chip pads no head's row.
            merged = bool(names) and heads_in_lanes(
                [attn[n].shape[2:] for n in names],
                jnp.int8 if self.quantized else attn[names[0]].dtype,
            )
            for name in names:
                row = attn[name]
                tail = tuple(int(d) for d in row.shape[2:])
                if merged:
                    tail = (tail[0] * tail[1],)
                dtype = jnp.int8 if self.quantized else row.dtype
                # Held in the shape the chip lays out with a page
                # contiguous: a headless row that is not whole lanes
                # wide is folded, ``fold`` positions to a held row.
                fold = page_fold(self.page_size, tail, dtype)
                pool[f"pages_{name}"] = jnp.zeros(
                    (pages, self.page_size // fold)
                    + tail[:-1] + (fold * tail[-1],),
                    dtype,
                )
                if self.quantized:
                    # One dequant scale per stored vector (the last
                    # axis): per head for [.., Hkv, D], per row for a
                    # headless [.., C].
                    pool[f"scale_{name}"] = jnp.zeros(
                        page + tail[:-1], jnp.float32
                    )
            self.row_bytes[bool(_window_of(attn))] += sum(
                leaf.nbytes for leaf in pool.values()
            ) // (pages * self.page_size)
            return pool

        with startup_span("startup.pools") as phase:
            self.cache = _map_attn_caches(template, to_pool)
            phase.note(**self._pool_facts())
        # Host-owned addressing: page 0 is the trash page, never
        # allocated; unmapped table entries point at it.
        self._free: list = list(range(1, self.num_pages))
        self._reserved: dict = {}
        self.page_table = np.zeros(
            (self.num_slots, self.pages_per_slot), np.int32
        )
        # The rings' own addressing (page 0 of a ring pool is its trash
        # page): a slot takes ``ring_pages`` pages at seat and returns
        # them at free.
        self._free_ring: list = list(range(1, self.num_ring_pages))
        self._rings: dict = {}
        self.ring_table = np.zeros(
            (self.num_slots, self.ring_pages), np.int32
        )
        self.start = np.zeros((self.num_slots,), np.int32)
        self.lens = np.zeros((self.num_slots,), np.int32)
        self._reset_occupancy()
        self._seat_jit = {}
        self.program_extras: list = []
        self.sharded = False
        self.in_place_layers = 0
        # Prefix sharing (radix mode): seating is LEFT-ALIGNED (token i
        # of every prompt lives at logical position i, start == 0), so
        # identical token prefixes land on identical page-aligned
        # content and the radix tree can map them for free. The dense
        # row template is kept for gather_prefix_rows (pages -> dense
        # prefix rows for the chunked suffix prefill).
        self.prefix_share = bool(prefix_share)
        self.radix: Optional[RadixPrefixTree] = None
        self._leases: dict = {}
        self._row_template = None
        self._seat_shared_fn = None
        self._gather_rows_fn = None
        if self.prefix_share:
            self.radix = RadixPrefixTree(self.page_size)
            self._row_template = jax.tree.map(
                lambda leaf: jax.ShapeDtypeStruct(
                    leaf.shape if getattr(leaf, "ndim", 0) == 0
                    else (1,) + tuple(leaf.shape[1:]),
                    leaf.dtype,
                ),
                template,
                is_leaf=lambda x: hasattr(x, "shape"),
            )

    @classmethod
    def from_pool_template(
        cls,
        pools: Any,
        num_slots: int,
        pages_per_slot: int,
        page_size: int,
        quantized: bool,
        num_pages: int,
        model_seq_len: Optional[int] = None,
    ) -> "PagedKVCache":
        """Build a paged cache straight from a POOL pytree (the decode
        artifact's cache input avals) — the exported-artifact session's
        constructor, where no dense template exists. Every geometry
        fact is recovered from the artifact's own shapes
        (``ServeSession.from_artifacts``). ``model_seq_len`` is the
        exporting model's compiled sequence bound (read off the
        prefill artifact's dense cache rows): when ``page_size`` does
        not divide it, the page span rounds up past positions the
        model's position space actually has, and the ``max_seq_len``
        clamp must keep admission from seating work there — the same
        clamp the live constructor applies. Prefix sharing needs the
        live chunked prefill program, so it stays a from_model-only
        feature."""
        import numpy as np

        obj = cls.__new__(cls)
        obj.num_slots = int(num_slots)
        obj.page_size = int(page_size)
        obj.quantized = bool(quantized)
        obj.pages_per_slot = int(pages_per_slot)
        obj.model_seq_len = int(
            model_seq_len
            if model_seq_len is not None
            else obj.pages_per_slot * obj.page_size
        )
        obj.num_pages = int(num_pages)
        obj.cache = jax.tree.map(
            lambda leaf: jnp.zeros(leaf.shape, leaf.dtype),
            pools,
            is_leaf=lambda x: hasattr(x, "shape"),
        )
        obj._free = list(range(1, obj.num_pages))
        obj._reserved = {}
        obj.page_table = np.zeros(
            (obj.num_slots, obj.pages_per_slot), np.int32
        )
        obj.row_bytes = [sum(
            leaf.nbytes for leaf in jax.tree.leaves(obj.cache)
        ) // (obj.num_pages * obj.page_size), 0]
        # An exported artifact has one table: no window group.
        obj.window = obj.ring_pages = 0
        obj.num_ring_pages = 1
        obj._free_ring, obj._rings = [], {}
        obj.ring_table = np.zeros((obj.num_slots, 0), np.int32)
        obj.start = np.zeros((obj.num_slots,), np.int32)
        obj.lens = np.zeros((obj.num_slots,), np.int32)
        obj._reset_occupancy()
        obj._seat_jit = {}
        obj.program_extras = []
        obj.sharded = False
        obj.in_place_layers = 0
        obj.prefix_share = False
        obj.radix = None
        obj._leases = {}
        obj._row_template = None
        obj._seat_shared_fn = None
        obj._gather_rows_fn = None
        return obj

    # -- the held shape --------------------------------------------------

    @functools.cached_property
    def folds(self) -> tuple:
        """The fold each pool leaf is held in
        (tpudl.models.paged.page_fold), read off the leaves once (a
        pool keeps its shape): 1 where a page is held as its layer
        declares it."""
        from tpudl.models.paged import held_fold

        flat, _ = jax.tree_util.tree_flatten_with_path(self.cache)
        return tuple(
            held_fold(leaf, self.page_size) for path, leaf in flat
            if str(getattr(path[-1], "key", "")).startswith("pages_")
        )

    # -- occupancy counters --------------------------------------------

    def _reset_occupancy(self) -> None:
        """Reserved against in use, kept at O(1) where they change:
        ``pages_reserved`` is the pages the seated slots' table rows
        map (shared prefix pages count once per slot that maps them),
        ``tokens_live`` the sum over seated slots of ``lens - start``,
        the positions a decode step has to read."""
        import numpy as np

        self.pages_reserved = 0
        self.tokens_live = 0
        self.pages_reserved_window = 0
        self._slot_pages = np.zeros((self.num_slots,), np.int64)

    @property
    def tokens_live_window(self) -> int:
        """Positions a decode step reads in ONE window layer: the sum
        over seated slots of ``min(lens - start, window)`` (an idle
        slot's ``lens`` and ``start`` are 0)."""
        import numpy as np

        if not self.window:
            return 0
        return int(np.minimum(self.lens - self.start, self.window).sum())

    @property
    def bytes_live(self) -> int:
        """Bytes of cache rows a decode step's attention reads over
        both groups: ``tokens_live`` positions of every full-context
        layer's rows and ``tokens_live_window`` of every window
        layer's, each at its own pools' row bytes. Layers whose rows
        differ (KV heads or widths by kind) make positions a poor
        count of what a step reads; this is the count."""
        return (
            self.tokens_live * self.row_bytes[0]
            + self.tokens_live_window * self.row_bytes[1]
        )

    def _seated(self, slot: int, pages: int) -> None:
        """``slot`` was just seated on ``pages`` pages, with its
        ``start`` and ``lens`` set."""
        self._slot_pages[slot] = pages
        self.pages_reserved += pages
        self.tokens_live += int(self.lens[slot]) - int(self.start[slot])

    def pages_of(self, slot: int) -> int:
        """Pages ``slot``'s table row maps (0 when it is not seated)."""
        return int(self._slot_pages[slot])

    # -- capacity ------------------------------------------------------

    @property
    def max_seq_len(self) -> int:
        """Logical positions addressable per slot — the admission bound
        (prompt window + max_new_tokens must fit). Clamped to the
        model's compiled bound: a page_size that does not divide it
        rounds the page span up, but positions past ``model_seq_len``
        do not exist in the decode program's position space."""
        return min(self.pages_per_slot * self.page_size, self.model_seq_len)

    def pages_needed(self, tokens: int) -> int:
        return -(-tokens // self.page_size)

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Pages seatable right now: the free pool plus (radix mode)
        refcount-0 tree pages, which eviction reclaims without touching
        any live slot."""
        extra = self.radix.evictable_pages if self.radix is not None else 0
        return len(self._free) + extra

    def fits_tokens(self, tokens: int) -> bool:
        """Admission predicate: can a request that may write ``tokens``
        logical positions be seated right now? Reservation up front
        means yes here == never strands mid-decode. Radix sessions use
        ``fits_request`` instead — it credits the cached prefix."""
        return (
            self.pages_needed(tokens) <= self.available_pages
            and self.ring_pages <= len(self._free_ring)
        )

    def fits_request(self, input_ids, tokens: int) -> bool:
        """Radix-mode admission: matched prefix pages map for free, so
        only the unshared remainder counts against the pool — sharing
        COMPOUNDS with int8 KV's resident-slot multiplier. Matched
        pages that are currently refcount-0 get PINNED by the seat, so
        they are excluded from the reclaimable side (counting them both
        as free-to-map and as evictable would admit a request
        ``seat_shared`` cannot place — the reservation invariant)."""
        if self.radix is None:
            return self.fits_tokens(tokens)
        matched, matched_evictable = self.radix.match_info(input_ids)
        need = self.pages_needed(tokens) - matched // self.page_size
        avail = len(self._free) + (
            self.radix.evictable_pages - matched_evictable
        )
        return need <= avail

    def prefix_match_len(self, input_ids) -> int:
        """Cached-prefix length (tokens) for a prompt — 0 when prefix
        sharing is off. Read-only (the router's affinity probe calls
        this from its own thread)."""
        if self.radix is None:
            return 0
        return self.radix.match_len(input_ids)

    # -- seating / freeing ---------------------------------------------

    def seat(
        self,
        row_cache: Any,
        slot: int,
        pad: int,
        prompt_len: int,
        reserve_tokens: int,
    ) -> None:
        """Reserve pages for ``reserve_tokens`` logical positions and
        scatter a batch-1 dense prefill row cache's prompt region
        (``[0, prompt_len)``, quantizing if int8) into the first pages.
        ``pad`` is the row's left-pad count — logical positions below
        it stay masked, exactly like dense validity."""
        import numpy as np

        if self.prefix_share:
            raise ValueError(
                "prefix-share caches seat left-aligned via seat_shared "
                "(pad-aligned seat would break the radix tree's "
                "canonical token->logical-position mapping)"
            )
        if not 0 <= slot < self.num_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.num_slots})")
        if slot in self._reserved:
            raise ValueError(f"slot {slot} is already seated")
        if reserve_tokens > self.max_seq_len:
            raise ValueError(
                f"reserve_tokens {reserve_tokens} exceeds the logical "
                f"per-slot bound {self.max_seq_len}"
            )
        n = self.pages_needed(reserve_tokens)
        if n > len(self._free) or self.ring_pages > len(self._free_ring):
            raise RuntimeError(
                f"page pool exhausted: need {n} pages and a ring of "
                f"{self.ring_pages}, {len(self._free)} and "
                f"{len(self._free_ring)} free (admission should have "
                f"checked fits_tokens)"
            )
        pages = [self._free.pop() for _ in range(n)]
        self._reserved[slot] = pages
        self.page_table[slot, :] = 0
        self.page_table[slot, : len(pages)] = pages
        self.start[slot] = pad
        self.lens[slot] = prompt_len
        self._seated(slot, len(pages))
        prompt_pages = self.pages_needed(prompt_len)
        # Page ids go to the program as the host holds them: made an
        # int32 array on the device, each new count of them would be a
        # small program of its own, compiled at a length's first seat.
        page_ids = np.asarray(pages[:prompt_pages], np.int32)
        if self.window:
            # The prompt's last pages go where the ring keeps them:
            # logical page j on ring page j mod ring_pages.
            ring = [self._free_ring.pop() for _ in range(self.ring_pages)]
            self._rings[slot] = ring
            self.ring_table[slot] = ring
            self.pages_reserved_window += self.ring_pages
            kept = range(self._first_kept_page(prompt_pages), prompt_pages)
            page_ids = (page_ids, np.asarray(
                [ring[j % self.ring_pages] for j in kept], np.int32
            ))
        self._replace_pool(
            self._seat_program(prompt_pages), row_cache, page_ids
        )

    def compile_seat(self, row_cache: Any, prompt_len: int) -> None:
        """Run the seat program for rows of ``prompt_len`` once, aimed
        at the trash page: it is compiled and loaded, and no slot, page
        list or counter changes."""
        import numpy as np

        prompt_pages = self.pages_needed(prompt_len)
        page_ids = np.zeros((prompt_pages,), np.int32)
        if self.window:
            kept = prompt_pages - self._first_kept_page(prompt_pages)
            page_ids = (page_ids, np.zeros((kept,), np.int32))
        self._replace_pool(
            self._seat_program(prompt_pages), row_cache, page_ids
        )

    def _first_kept_page(self, prompt_pages: int) -> int:
        """The first of a prompt's pages that a ring keeps: its last
        ``ring_pages``, which hold the last ``window`` positions
        wherever the prompt ends in its page."""
        return max(prompt_pages - self.ring_pages, 0)

    def _seat_program(self, prompt_pages: int):
        """The jitted, pool-donating scatter for prompts of
        ``prompt_pages`` pages: one program per distinct count (one
        a prefill length of the session)."""
        fn = self._seat_jit.get(prompt_pages)
        if fn is None:
            fn = self._seat_jit[prompt_pages] = jax.jit(
                self._make_seat_fn(prompt_pages), donate_argnums=(0,)
            )
        return fn

    def _make_seat_fn(self, prompt_pages: int):
        """The scatter itself: dense prefill row -> page pool."""
        from tpudl.models.paged import quantize_kv

        ps, quantized = self.page_size, self.quantized
        span = prompt_pages * ps
        # Where a window layer's kept pages start in the row.
        kept_from = self._first_kept_page(prompt_pages) * ps

        def tpudl_seat(pool_tree, row_tree, page_ids):
            # With a window group: (the table's pages, the ring's).
            table_ids, ring_ids = (
                page_ids if isinstance(page_ids, tuple) else (page_ids, None)
            )

            def one(pool: dict, row: dict) -> dict:
                out = dict(pool)
                ids, first = table_ids, 0
                if _window_of(row):
                    ids, first = ring_ids, kept_from
                for kv in _row_names(row):
                    name, sname = f"pages_{kv}", f"scale_{kv}"
                    rowvals = row[kv]
                    take = min(span, rowvals.shape[1])
                    blocks = rowvals[0, first:take]
                    if take < span:
                        # page_size doesn't divide the model bound: the
                        # last prompt page extends past the dense row.
                        # Zero-fill the tail — those logical positions
                        # sit beyond prompt_len, so lens/validity masks
                        # them until a decode write lands real values.
                        blocks = jnp.pad(
                            blocks,
                            [(0, span - take)] + [(0, 0)] * (blocks.ndim - 1),
                        )
                    if quantized:
                        blocks, s = quantize_kv(blocks)
                        out[sname] = _set_pages(out[sname], ids, s)
                    out[name] = _set_pages(out[name], ids, blocks)
                return out

            with jax.named_scope("kv_scatter"):
                return _zip_attn_caches(pool_tree, row_tree, one)

        return tpudl_seat

    # -- prefix-sharing (radix) seating ---------------------------------

    def match_and_lease(self, input_ids):
        """Radix walk + lease for one prompt (engine seat path): the
        matched pages map into the slot's table for free; the lease
        pins them until ``free``/``release_lease``. See
        ``RadixPrefixTree.match_and_lease``."""
        if self.radix is None:
            raise ValueError("match_and_lease requires prefix_share=True")
        return self.radix.match_and_lease(input_ids)

    def release_lease(self, lease) -> None:
        """Failure-path unpin (a lease whose seat never completed)."""
        if lease is not None:
            self.radix.release(lease)

    def _alloc_pages(self, n: int) -> list:
        """Pop ``n`` pages from the free pool, evicting LRU refcount-0
        radix nodes when the pool alone is short — the under-pressure
        path ``fits_tokens``'s ``available_pages`` promised."""
        if n > len(self._free) and self.radix is not None:
            self._free.extend(self.radix.evict(n - len(self._free)))
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted: need {n} pages, {len(self._free)} "
                f"free (admission should have checked fits_tokens)"
            )
        return [self._free.pop() for _ in range(n)]

    def seat_shared(
        self,
        row_cache: Any,
        slot: int,
        input_ids,
        reserve_tokens: int,
        lease=None,
        row_offset: int = 0,
    ) -> None:
        """LEFT-ALIGNED radix seating: token ``i`` of the prompt lives
        at logical position ``i`` (start 0) so identical prefixes are
        page-identical across requests. ``lease`` is the
        ``match_and_lease`` result whose pages map into the table for
        free; only the UNSHARED remainder allocates (evicting LRU
        cached pages under pressure), and only the unshared suffix of
        ``row_cache`` is scattered — shared pages are never rewritten
        (copy-on-write: decode writes land at ``lens >= ids_len``,
        always in private pages). ``row_offset`` names where the
        prompt's first token sits in the dense row (its left-pad count
        for a full-prefill row; 0 for a chunk-prefill row). The
        prompt's freshly written FULL pages are inserted into the tree
        so later requests hit them."""
        import numpy as np

        ids = np.asarray(input_ids, np.int32)
        ids_len = int(ids.shape[0])
        if not 0 <= slot < self.num_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.num_slots})")
        if slot in self._reserved or slot in self._leases:
            raise ValueError(f"slot {slot} is already seated")
        matched_pages, deepest = lease if lease is not None else ([], None)
        m = len(matched_pages)
        try:
            if reserve_tokens > self.max_seq_len:
                raise ValueError(
                    f"reserve_tokens {reserve_tokens} exceeds the logical "
                    f"per-slot bound {self.max_seq_len}"
                )
            assert m * self.page_size <= ids_len, (
                "lease longer than the prompt — matched against the "
                "wrong request"
            )
            new_pages = self._alloc_pages(self.pages_needed(reserve_tokens) - m)
        except BaseException:
            self.release_lease(deepest)
            raise
        prompt_pages = self.pages_needed(ids_len)
        full = ids_len // self.page_size
        self.page_table[slot, :] = 0
        self.page_table[slot, :m] = matched_pages
        self.page_table[slot, m:m + len(new_pages)] = new_pages
        self.start[slot] = 0
        self.lens[slot] = ids_len
        self._seated(slot, m + len(new_pages))
        # Scatter ONLY the unshared pages [m, prompt_pages); matched
        # pages keep their (identical) bytes untouched and page ids
        # outside that range aim at the trash page.
        page_ids = np.zeros((self.pages_per_slot,), np.int32)
        page_ids[m:prompt_pages] = new_pages[: prompt_pages - m]
        if self._seat_shared_fn is None:
            self._seat_shared_fn = jax.jit(
                self._make_seat_shared_fn(), donate_argnums=(0,)
            )
        self._replace_pool(
            self._seat_shared_fn, row_cache, jnp.asarray(page_ids),
            jnp.int32(row_offset),
        )
        # The prompt's full pages enter the tree (tree-owned: they go
        # back to the pool only via eviction); the partial tail +
        # decode-reserve pages stay private to the slot.
        node = self.radix.insert_suffix(
            deepest,
            self.radix.blocks_of(ids)[m:full],
            new_pages[: full - m],
        )
        final = node if node is not None else deepest
        if final is not None:
            self._leases[slot] = final
        self._reserved[slot] = new_pages[full - m:]

    def _make_seat_shared_fn(self):
        """The one jitted left-aligned scatter (all requests, any match
        length): the dense row is sliced from ``row_offset``, re-laid
        as pages, and written at ``page_ids`` — entries pinned to 0
        land in the trash page, which is how matched-prefix pages and
        the unused tail are skipped without a second program."""
        from tpudl.models.paged import quantize_kv

        ps, quantized = self.page_size, self.quantized
        span = self.pages_per_slot * ps

        def tpudl_seat_shared(pool_tree, row_tree, page_ids, row_offset):
            def one(pool: dict, row: dict) -> dict:
                out = dict(pool)
                for kv in _row_names(row):
                    name, sname = f"pages_{kv}", f"scale_{kv}"
                    rowvals = row[kv][0]
                    padded = jnp.pad(
                        rowvals,
                        [(0, span)] + [(0, 0)] * (rowvals.ndim - 1),
                    )
                    blocks = jax.lax.dynamic_slice_in_dim(
                        padded, row_offset, span, axis=0
                    )
                    if quantized:
                        blocks, s = quantize_kv(blocks)
                        out[sname] = _set_pages(out[sname], page_ids, s)
                    out[name] = _set_pages(out[name], page_ids, blocks)
                return out

            with jax.named_scope("kv_scatter"):
                return _zip_attn_caches(pool_tree, row_tree, one)

        return tpudl_seat_shared

    def gather_prefix_rows(self, matched_pages, matched_tokens: int):
        """Materialize a leased prefix into a batch-1 DENSE row cache
        (k/v rows [0, matched_tokens), validity set, index pinned) —
        the input the chunked suffix prefill resumes from. One jitted
        program for every match length (page ids ride in padded)."""
        import numpy as np

        if self._row_template is None:
            raise ValueError(
                "gather_prefix_rows requires prefix_share=True (needs "
                "the dense row template)"
            )
        if self._gather_rows_fn is None:
            self._gather_rows_fn = jax.jit(self._make_gather_rows_fn())
        page_ids = np.zeros((self.pages_per_slot,), np.int32)
        page_ids[: len(matched_pages)] = matched_pages
        return self._gather_rows_fn(
            self.cache, jnp.asarray(page_ids), jnp.int32(matched_tokens)
        )

    def _make_gather_rows_fn(self):
        ps, quantized = self.page_size, self.quantized
        span = self.pages_per_slot * ps
        row_template = self._row_template

        def tpudl_gather_rows(pool_tree, page_ids, m_tok):
            def one(pool: dict, tmpl: dict) -> dict:
                seq = int(tmpl["valid"].shape[1])
                out = {}
                for kv in _row_names(tmpl):
                    name, sname = f"pages_{kv}", f"scale_{kv}"
                    rows = _slot_rows(pool[name], page_ids, ps)
                    if quantized:
                        rows = rows.astype(jnp.float32) * (
                            _slot_rows(pool[sname], page_ids, ps)[..., None]
                        )
                    if span >= seq:
                        rows = rows[:seq]
                    else:
                        rows = jnp.pad(
                            rows,
                            [(0, seq - span)] + [(0, 0)] * (rows.ndim - 1),
                        )
                    # (A row whose heads the pool holds merged into its
                    # lanes takes its declared form here.)
                    out[kv] = rows.reshape(tmpl[kv].shape).astype(
                        tmpl[kv].dtype
                    )
                out["valid"] = (jnp.arange(seq) < m_tok)[None, :]
                out["index"] = jnp.asarray(m_tok, tmpl["index"].dtype)
                return out

            with jax.named_scope("kv_gather"):
                return _zip_attn_caches(pool_tree, row_template, one)

        return tpudl_gather_rows

    def free(self, slot: int) -> None:
        """Return the slot's PRIVATE pages to the pool, release its
        radix lease (shared pages stay cached in the tree, evictable
        once their refcount drops to 0), and point its table row at the
        trash page (idle ride-along writes land there)."""
        if not 0 <= slot < self.num_slots:
            raise IndexError(f"slot {slot} out of range [0, {self.num_slots})")
        lease = self._leases.pop(slot, None)
        if lease is not None and self.radix is not None:
            self.radix.release(lease)
        pages = self._reserved.pop(slot, None)
        if pages:
            self._free.extend(pages)
        ring = self._rings.pop(slot, None)
        if ring:
            self._free_ring.extend(ring)
            self.pages_reserved_window -= len(ring)
            self.ring_table[slot, :] = 0
        self.pages_reserved -= int(self._slot_pages[slot])
        self._slot_pages[slot] = 0
        self.tokens_live -= int(self.lens[slot]) - int(self.start[slot])
        self.page_table[slot, :] = 0
        self.start[slot] = 0
        self.lens[slot] = 0

    def reset(self) -> None:
        """Free every slot (the pool arrays keep their bytes — masked).
        Radix mode: the prefix cache SURVIVES a reset (cached prefixes
        are the point); ``drop_prefix_cache`` clears it too."""
        for slot in list(set(self._reserved) | set(self._leases)):
            self.free(slot)

    def drop_prefix_cache(self) -> None:
        """Evict every lease-free radix page back to the pool (after
        ``reset``, that is the whole tree)."""
        if self.radix is not None:
            self._free.extend(self.radix.evict(self.radix.evictable_pages))

    # -- page-granular migration ---------------------------------------

    def export_request(self, slot: int, meta: dict, skip_tokens: int = 0,
                       extra_leaves=()) -> bytes:
        """Serialize one seated request's KV state into a single
        crc32-guarded payload: its logical rows ``[skip_tokens, lens)``
        gathered straight out of the page pools in STORED dtype (int8
        pages ship as int8 with their scale rows — import re-scatters
        the exact bytes, so a quantized request resumes bit-identical),
        plus the addressing facts (``lens``/``start``/alignment) the
        target needs to rebuild its page-table row. ``meta`` is the
        engine-owned request/sampling state riding along (tokens so
        far, fold_in position, absolute deadline, reservation).

        ``skip_tokens`` is the reference-first prefix contract: the
        caller probed (and LEASED) that many tokens in the TARGET's
        radix tree, so they ship as token-block references (the prompt
        ids already in ``meta``) instead of page payload; a target
        whose tree no longer holds them refuses the import
        (``MigrationCompatError``) rather than resuming with holes.

        Non-destructive: the caller frees the slot only once the
        payload exists — the commit-or-invisible discipline of
        tpudl.ft.store applied to a transfer."""
        import numpy as np

        self._no_rings("migration")
        if slot not in self._reserved and slot not in self._leases:
            raise ValueError(f"slot {slot} is not seated")
        lens = int(self.lens[slot])
        start = int(self.start[slot])
        left_aligned = start == 0
        skip = int(skip_tokens)
        if not 0 <= skip <= lens:
            raise ValueError(f"skip_tokens {skip} outside [0, {lens}]")
        if skip and not left_aligned:
            raise ValueError(
                "reference-prefix export requires a left-aligned slot "
                "(pad-aligned rows cannot match the radix tree's "
                "canonical token->position mapping)"
            )
        page_ids = jnp.asarray(self.page_table[slot], jnp.int32)
        host = jax.device_get(
            _migration_gather(self.cache, page_ids, self.page_size)
        )
        flat, _ = jax.tree_util.tree_flatten_with_path(host)
        leaves = [
            (jax.tree_util.keystr(path), np.asarray(arr)[skip:lens])
            for path, arr in flat
        ]
        # Rider leaves (e.g. the speculative draft's nested payload)
        # ship alongside the KV rows under caller-chosen paths; import
        # reads only the paths its own pools need, so riders are
        # crc-covered but structurally inert here.
        leaves.extend((name, np.asarray(arr)) for name, arr in extra_leaves)
        payload_meta = dict(meta)
        payload_meta.update(
            kind="tpudl-kv-migration",
            lens=lens,
            start=start,
            skip_tokens=skip,
            left_aligned=left_aligned,
            page_size=self.page_size,
            quantized=self.quantized,
        )
        return pack_migration(payload_meta, leaves)

    def import_request(self, payload, slot: int, lease=None) -> dict:
        """Seat a migrated request's KV into ``slot`` from an
        ``export_request`` payload: verify the crc, allocate the full
        reservation, scatter the shipped rows into fresh pages, and
        rebuild the page-table row — ZERO prefill compute. ``lease``
        is a pre-pinned ``RadixPrefixTree.match_and_lease`` result
        (the router pins the probed prefix BEFORE the transfer so
        eviction cannot invalidate the reference contract mid-flight);
        without one, a prefix-share cache matches here. The lease is
        CONSUMED: released on every failure path, installed into the
        slot's bookkeeping on success.

        Raises ``MigrationCorruptError`` on a payload that fails
        validation (never resume garbage) and ``MigrationCompatError``
        on a structurally valid payload this cache cannot seat
        (quantization/geometry mismatch, reference prefix the tree no
        longer holds) — the caller's cue to fall back to a
        from-scratch resubmission. Returns the payload's meta dict
        (the engine rebuilds its slot state from it)."""
        import numpy as np

        self._no_rings("migration")
        meta = payload if isinstance(payload, dict) else parse_migration(payload)
        matched_pages: list = []
        deepest = None
        if lease is not None:
            matched_pages, deepest = lease
        try:
            if meta.get("kind") != "tpudl-kv-migration":
                raise MigrationCorruptError(
                    "payload is not a tpudl KV migration"
                )
            if bool(meta["quantized"]) != self.quantized:
                raise MigrationCompatError(
                    f"payload kv quantization ({meta['quantized']}) does "
                    f"not match this cache ({self.quantized})"
                )
            if not 0 <= slot < self.num_slots:
                raise IndexError(
                    f"slot {slot} out of range [0, {self.num_slots})"
                )
            if slot in self._reserved or slot in self._leases:
                raise ValueError(f"slot {slot} is already seated")
            if lease is not None and self.radix is None:
                raise ValueError(
                    "import lease given but prefix_share is off"
                )
        except BaseException:
            self.release_lease(deepest)
            raise
        lens = int(meta["lens"])
        start = int(meta["start"])
        skip = int(meta["skip_tokens"])
        reserve = max(int(meta["reserve_tokens"]), lens)
        ids = np.asarray(meta["request"]["input_ids"], np.int32)
        if lease is not None and not meta["left_aligned"]:
            # A pad-aligned payload's rows do not follow the radix
            # tree's canonical token->position mapping: splicing the
            # leased pages in would resume over WRONG KV. Drop the pin
            # and import fully private (skip is 0 for these payloads —
            # export refuses reference mode off a pad-aligned slot).
            self.release_lease(deepest)
            matched_pages, deepest = [], None
        if lease is None and self.prefix_share and meta["left_aligned"]:
            matched_pages, deepest = self.radix.match_and_lease(ids)
        m = len(matched_pages)
        try:
            if reserve > self.max_seq_len:
                raise MigrationCompatError(
                    f"reserve_tokens {reserve} exceeds this cache's "
                    f"per-slot bound {self.max_seq_len}"
                )
            if m * self.page_size < skip:
                raise MigrationCompatError(
                    f"payload ships rows only past token {skip} (prefix "
                    f"by reference) but this cache's radix tree holds "
                    f"{m * self.page_size} — re-export with the full "
                    f"page payload"
                )
            rows = self._migration_rows(meta, lens, skip)
            new_pages = self._alloc_pages(self.pages_needed(reserve) - m)
        except BaseException:
            self.release_lease(deepest)
            raise
        used = self.pages_needed(lens)
        self.page_table[slot, :] = 0
        self.page_table[slot, :m] = matched_pages
        self.page_table[slot, m:m + len(new_pages)] = new_pages
        self.start[slot] = start
        self.lens[slot] = lens
        self._seated(slot, m + len(new_pages))
        # Matched pages (and reserved-but-unwritten ones past ``used``)
        # aim at the trash page in the scatter's page_ids — their bytes
        # are either already identical (matched) or garbage-until-
        # written (reserve), exactly like seat_shared's skip contract.
        page_ids = np.zeros((self.pages_per_slot,), np.int32)
        page_ids[m:used] = self.page_table[slot, m:used]
        self._replace_pool(
            _migration_scatter, rows, jnp.asarray(page_ids)
        )
        tree_pages = 0
        node = None
        if self.radix is not None and meta["left_aligned"]:
            # The prompt's full pages enter the tree so later requests
            # share them — a migrated-in system prompt is as cacheable
            # as a locally prefilled one.
            full = int(ids.shape[0]) // self.page_size
            if full > m:
                node = self.radix.insert_suffix(
                    deepest,
                    self.radix.blocks_of(ids)[m:full],
                    [int(p) for p in self.page_table[slot, m:full]],
                )
                tree_pages = full - m
        final = node if node is not None else deepest
        if final is not None:
            self._leases[slot] = final
        self._reserved[slot] = new_pages[tree_pages:]
        return meta

    def _migration_rows(self, meta: dict, lens: int, skip: int):
        """Rebuild the full-span row pytree the scatter program takes
        from a parsed payload's arrays, validating every leaf against
        THIS cache's pool geometry (tail dims + stored dtype)."""
        import numpy as np

        span = self.pages_per_slot * self.page_size
        arrays = meta["_arrays"]

        def make_rows(pool: dict) -> dict:
            from tpudl.models.paged import row_tail

            return {
                name: np.zeros(
                    (span,) + row_tail(arr, self.page_size), arr.dtype
                )
                for name, arr in pool.items()
            }

        rows = _map_pools(self.cache, make_rows)
        flat, treedef = jax.tree_util.tree_flatten_with_path(rows)
        filled = []
        for path, buf in flat:
            key = jax.tree_util.keystr(path)
            src = arrays.get(key)
            if src is None:
                raise MigrationCompatError(
                    f"payload has no rows for {key} — exported from a "
                    f"different model geometry"
                )
            src = np.asarray(src)
            want = (lens - skip,) + buf.shape[1:]
            if tuple(src.shape) != want or src.dtype != buf.dtype:
                raise MigrationCompatError(
                    f"{key}: payload rows {tuple(src.shape)}/{src.dtype} "
                    f"do not fit this cache's {want}/{buf.dtype}"
                )
            buf[skip:lens] = src
            filled.append(buf)
        return jax.tree_util.tree_unflatten(treedef, filled)

    def _no_rings(self, what: str) -> None:
        """``what`` walks ONE table over every layer's pool."""
        if self.window:
            raise ValueError(
                f"{what} is not wired to window layers: their pools are "
                f"rings of {self.ring_pages} pages a slot under a table "
                f"of their own"
            )

    # -- per-dispatch addressing ---------------------------------------

    def dispatch_args(self):
        """The three small traced inputs each paged decode dispatch
        takes: (page_table [B, P], start [B], lens [B]) as int32; with
        a window group the first is the pair (page_table, ring_table
        [B, ring_pages]) (tpudl.models.paged.PagedView). COPIES of
        the host's tables: the engine moves ``lens`` and frees slots
        while the dispatch that took them is still queued, and a device
        array made of a host buffer may go on reading it (on the CPU it
        is that buffer)."""
        table = jnp.asarray(self.page_table.copy())
        if self.window:
            table = (table, jnp.asarray(self.ring_table.copy()))
        return (table, jnp.asarray(self.start.copy()),
                jnp.asarray(self.lens.copy()))

    @property
    def addressing_nbytes(self) -> int:
        """Host bytes ``dispatch_args`` hands to the device a dispatch
        (the ring table has no columns without a window group)."""
        return (
            self.page_table.nbytes + self.ring_table.nbytes
            + self.start.nbytes + self.lens.nbytes
        )

    def decode(self, program, params, tokens, positions, *extra,
               addressing=None):
        """Dispatch one program of the paged decode contract
        (``paged_decode_fn``, ``paged_chunk_decode_fn``, the LoRA
        decode with its adapter arguments in ``extra``) on the pool,
        keep the pool it returns and hand back the logits. What the
        program returned beside the two (a model with routed experts:
        its tokens per held expert, still on the device) is kept as
        ``program_extras`` until the next dispatch. ``addressing``:
        this dispatch's ``dispatch_args()``, where the caller has made
        them already (the engine does, under a span)."""
        pool = self.cache
        if addressing is None:
            addressing = self.dispatch_args()
        logits, self.cache, *self.program_extras = program(
            params, pool, tokens, positions, *addressing, *extra
        )
        self._handed_over(pool)
        # What the program noted of itself while it was traced: the
        # attention layers that read the pool in place (an exported
        # artifact keeps no such note and counts as the gather).
        took = getattr(
            getattr(program, "__wrapped__", program),
            "attention_in_place", None,
        )
        self.in_place_layers = sum(took or ())
        return logits

    def pages_live(self, chunk: int = 1) -> int:
        """Pages a dispatch of ``chunk`` tokens a slot visits where
        attention reads the pool in place, summed over the slots: those
        that cover logical positions ``[start, lens + chunk - 1]``; an
        idle slot (lens 0 on the trash page) counts one. Counted from
        the host's ``start`` / ``lens`` as the dispatch sees them, so
        before ``advance``."""
        import numpy as np

        last = np.minimum(
            (self.lens + chunk - 1) // self.page_size,
            self.pages_per_slot - 1,
        )
        first = self.start // self.page_size
        return int(np.maximum(last - first, 0).sum()) + self.num_slots

    def commit(self, mesh) -> None:
        """Commit the pool to ``mesh`` before the first program sees
        it, KV heads over ``tp`` where they divide (the split the
        column-parallel k/v projections write in). A pool left on one
        device would come back from its first seat in the compiler's
        own sharding: one whole copy, and every donating program
        compiled twice. Where ``tp`` does not divide the KV heads the
        pool starts replicated and that first seat does re-shard it:
        one counted copy (``serve_kv_pool_copies``), in place from then
        on. The cache then knows its pool lives on a mesh (``sharded``)
        and the decode programs built for it are told
        (``PagedView.sharded``): under GSPMD a kernel that reads the
        pool in place would have it gathered whole to every chip, so
        those programs keep the dense gather."""
        from jax.sharding import NamedSharding, PartitionSpec

        self._no_rings("a pool committed to a mesh")

        def place(leaf):
            # pages_k/v [pages, page, Hkv, D]; scale_k/v [pages, page, Hkv]
            if leaf.shape[2] % mesh.shape["tp"] == 0:
                return NamedSharding(mesh, PartitionSpec(None, None, "tp"))
            return NamedSharding(mesh, PartitionSpec())

        with startup_span("startup.pools", **self._pool_facts()):
            self.cache = jax.device_put(
                self.cache, jax.tree.map(place, self.cache)
            )
        self.sharded = True

    def _pool_facts(self) -> dict:
        """What a ``startup.pools`` span says of the pools it made or
        placed."""
        leaves = jax.tree.leaves(self.cache)
        return {
            "leaves": len(leaves),
            "bytes": sum(leaf.nbytes for leaf in leaves),
            "pages": self.num_pages,
        }

    def _replace_pool(self, program, *args) -> None:
        """``program(pool, *args) -> pool``: a seat or an import."""
        pool = self.cache
        self.cache = program(pool, *args)
        self._handed_over(pool)

    @staticmethod
    def _handed_over(pool) -> None:
        """``pool`` went into a program that returned its successor.
        One leaf tells whether it was donated (no device call): a leaf
        that is still alive was copied."""
        copies = registry().counter("serve_kv_pool_copies")
        if not jax.tree.leaves(pool)[0].is_deleted():
            copies.inc()

    def advance(self, slots, steps: int = 1) -> None:
        """Advance the logical length of each ACTIVE slot after a
        decode dispatch wrote its token(s) (idle slots stay pinned at 0
        on the trash page). ``steps`` > 1 serves the speculative path's
        per-slot window advance."""
        for slot in slots:
            self.lens[slot] += steps
        self.tokens_live += steps * len(slots)

    def set_len(self, slot: int, length: int) -> None:
        """Pin one slot's logical length — the speculative ROLLBACK
        primitive: a rejected proposal tail simply never advances lens,
        so its page writes are masked garbage the next window
        overwrites. Per-slot bookkeeping only."""
        self.tokens_live += int(length) - int(self.lens[slot])
        self.lens[slot] = int(length)

    # -- accounting ----------------------------------------------------

    @property
    def nbytes(self) -> int:
        """Resident bytes: page pools (quantized values AND their scale
        rows) plus the host-side page-table/start/len addressing — the
        accurate number behind the ``serve_cache_bytes`` gauge (the
        dense-dtype assumption would overstate int8 pools 4x and miss
        the tables entirely)."""
        device = int(
            sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache))
        )
        return device + self.addressing_nbytes


# ---------------------------------------------------------------------------
# Page-granular KV migration: the transfer format + pool gather/scatter
# ---------------------------------------------------------------------------

MIGRATION_MAGIC = b"TPUDLMIG"
MIGRATION_VERSION = 1
_MIGRATION_HEADER = struct.Struct("<II")  # (version, meta length)


class MigrationCorruptError(RuntimeError):
    """A migration payload failed validation (bad magic/version, crc32
    mismatch, truncated array region): the bytes cannot be trusted and
    the request must NOT be resumed from them — the transfer analog of
    tpudl.ft.store's commit-or-invisible rule. The router sheds the
    request as ``failed`` instead of decoding garbage."""


class MigrationCompatError(ValueError):
    """A structurally valid payload that cannot seat in THIS cache:
    quantization or model-geometry mismatch, a reservation past the
    per-slot bound, or a reference-only prefix the target's radix tree
    no longer holds. Unlike corruption this is recoverable — the
    router's fallback is the from-scratch resubmission path."""


def pack_migration(meta: dict, leaves) -> bytes:
    """One request's migration payload: ``MAGIC | version | meta-len |
    meta json | raw leaf buffers | crc32``. ``leaves`` is an ordered
    list of ``(path, ndarray)`` — descriptors (path/shape/dtype/offset)
    land in the meta so parse needs no side channel. The trailing crc32
    covers EVERYTHING before it, so any truncation or bit flip anywhere
    in the transfer is caught before a single row is resumed."""
    import numpy as np

    descs = []
    bufs = []
    offset = 0
    for path, arr in leaves:
        arr = np.ascontiguousarray(arr)
        descs.append({
            "path": path,
            "shape": list(arr.shape),
            "dtype": arr.dtype.name,
            "offset": offset,
            "nbytes": int(arr.nbytes),
        })
        bufs.append(arr.tobytes())
        offset += arr.nbytes
    meta = dict(meta)
    meta["arrays"] = descs
    blob = json.dumps(meta).encode()
    body = (
        MIGRATION_MAGIC
        + _MIGRATION_HEADER.pack(MIGRATION_VERSION, len(blob))
        + blob
        + b"".join(bufs)
    )
    return body + struct.pack("<I", zlib.crc32(body))


def parse_migration(payload) -> dict:
    """Decode + VERIFY a migration payload. Raises
    ``MigrationCorruptError`` on anything that fails the magic /
    version / crc32 / array-bounds checks — a corrupt transfer raises
    here, at the door, never as a resumed-garbage token stream.
    Returns the meta dict with ``"_arrays"`` holding the decoded
    ``{path: ndarray}`` leaves."""
    import numpy as np

    head = len(MIGRATION_MAGIC) + _MIGRATION_HEADER.size
    if not isinstance(payload, (bytes, bytearray, memoryview)):
        raise TypeError(
            f"migration payload must be bytes, got {type(payload).__name__}"
        )
    payload = bytes(payload)
    if len(payload) < head + 4 or payload[: len(MIGRATION_MAGIC)] != (
        MIGRATION_MAGIC
    ):
        raise MigrationCorruptError(
            "not a tpudl migration payload (bad magic or truncated)"
        )
    (crc,) = struct.unpack("<I", payload[-4:])
    if zlib.crc32(payload[:-4]) != crc:
        raise MigrationCorruptError(
            "crc32 mismatch — truncated or corrupted migration payload; "
            "refusing to resume from it"
        )
    version, blob_len = _MIGRATION_HEADER.unpack(
        payload[len(MIGRATION_MAGIC):head]
    )
    if version != MIGRATION_VERSION:
        raise MigrationCorruptError(
            f"migration payload version {version} != {MIGRATION_VERSION}"
        )
    try:
        meta = json.loads(payload[head:head + blob_len].decode())
    except Exception as e:
        raise MigrationCorruptError(
            f"unreadable migration meta: {type(e).__name__}: {e}"
        ) from None
    data = payload[head + blob_len:-4]
    arrays = {}
    for desc in meta.get("arrays", []):
        end = desc["offset"] + desc["nbytes"]
        if end > len(data):
            raise MigrationCorruptError(
                f"array region truncated: {desc['path']} ends at byte "
                f"{end}, payload holds {len(data)}"
            )
        dtype = np.dtype(desc["dtype"])
        arrays[desc["path"]] = np.frombuffer(
            data,
            dtype=dtype,
            count=desc["nbytes"] // dtype.itemsize,
            offset=desc["offset"],
        ).reshape(desc["shape"])
    meta["_arrays"] = arrays
    return meta


def _map_pools(tree, fn):
    """Rebuild a PAGED cache pytree with every per-layer page-pool dict
    replaced by ``fn(pool)`` — the migration analog of
    ``_map_attn_caches`` (which matches dense k/v/valid/index dicts)."""
    from collections.abc import Mapping

    if _is_pool(tree):
        return fn(tree)
    if isinstance(tree, Mapping):
        return {k: _map_pools(v, fn) for k, v in tree.items()}
    return tree


def _slot_rows(leaf, page_ids, page_size: int):
    """One slot's logical rows ``[P * page_size, *tail]`` out of a pool
    leaf by its page ids: whole pages are fetched as the leaf holds
    them, and the small result takes its logical row form (a held page
    is its rows' row-major bytes: a reshape, of a slot's worth of
    pages, never of a pool)."""
    from tpudl.models.paged import row_tail

    return leaf[page_ids].reshape(
        page_ids.shape[0] * page_size, *row_tail(leaf, page_size)
    )


def _set_pages(leaf, page_ids, rows):
    """Write logical rows ``[P * page_size, *tail]`` into a pool leaf
    as whole pages at ``page_ids``, in the shape the leaf holds a page
    in (a held page is its rows' row-major bytes: a reshape of the
    small side)."""
    return leaf.at[page_ids].set(
        rows.reshape(page_ids.shape[0], *leaf.shape[1:]).astype(leaf.dtype)
    )


@functools.partial(jax.jit, static_argnums=(2,))
def _migration_gather(cache, page_ids, page_size):
    """Materialize one slot's logical rows from every pool leaf in
    STORED dtype — no dequantization, so int8 pages and their scale
    rows round-trip bit-exact through a migration. Module-level jit on
    purpose: every cache with the same geometry (all replicas of a
    fleet) shares ONE compiled program, so migrating never recompiles
    per replica."""

    def one(pool: dict) -> dict:
        return {
            name: _slot_rows(arr, page_ids, page_size)
            for name, arr in pool.items()
        }

    return _map_pools(cache, one)


@functools.partial(jax.jit, donate_argnums=(0,))
def _migration_scatter(cache, rows, page_ids):
    """Write a full-span row pytree into the pools at ``page_ids``
    (entries pinned to 0 land in the trash page — how matched-prefix
    pages and the unwritten reserve tail are skipped without a second
    program). The scatter twin of ``_migration_gather``, with the same
    shared-compilation property."""

    def one(pool: dict, r: dict) -> dict:
        out = dict(pool)
        for name, vals in r.items():
            out[name] = _set_pages(out[name], page_ids, vals)
        return out

    return _zip_attn_caches(cache, rows, one)
