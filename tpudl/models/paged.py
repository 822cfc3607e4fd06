"""Paged KV-cache primitives: page pools, gather/scatter, int8 quant.

The dense decode cache (tpudl.models.llama.LlamaAttention decode mode)
allocates ``[num_slots, max_seq_len, Hkv, D]`` per layer whether a slot
holds a 14-token short request or a 256-token horizon-filler, and every
slot shares ONE device-side write index, which is right for
``generate()``'s batch in lockstep and wrong for a server whose
requests come and go. The paged layout, the serve engine's, replaces
both:

- KV lives in a pool of fixed-size **pages** ``[num_pages, page_size,
  Hkv, D]`` per layer; a slot owns whichever pages its **page table**
  row ``page_table[slot, j]`` maps (logical page ``j`` -> physical page
  id). Memory scales with what requests actually reserve, not with
  ``num_slots x max_seq_len``.
- Each slot carries its OWN length (``lens[slot]``) — decode writes
  row ``b`` at its own logical position, so no horizon is shared.
- Pages optionally store **int8** with a dequant scale per (page, row,
  kv-head) — ~4x the resident tokens per byte vs f32 pools — applied
  inside the decode gather (one fused multiply on the gathered view).

A pool leaf is HELD in the shape the chip lays out major-to-minor
with a page contiguous (``page_fold`` and ``heads_in_lanes``, the one
place that knows): a row with a head axis, or whose width is a whole
number of 128-value lanes, is held as ``[num_pages, page_size, ...]``
(a layer with a head width that is NOT whole lanes, keys 192 wide,
holds its heads merged into the lanes, ``[num_pages, page_size, Hkv *
D]``, so that no head's row is padded); a headless row of
another width C (a latent layer's 576) is held FOLDED,
``[num_pages, page_size / f, f * C]``: position ``t`` of a page lies
in row ``t // f``, lanes ``(t % f) * C ...``. The bytes are the same
row-major bytes either way; what changes is that the chip's default
layout of the held shape has the page index outermost, so no program
re-lays the pool on the way in or out. Every program addresses the
leaf in its held shape and reads the fold off it (``held_fold``).

A decode step first writes its rows into the pool (``paged_write``, a
scatter into the donated pool), then attends. There are two ways to
read, and the program chooses by what it can observe
(tpudl.ops.paged_attention): an unquantized pool on one device of a TPU
(a k / v pool pair, or a latent layer's one headless pool as it is
held) is read **in place** — a kernel brings only the pages that cover
a slot's live positions from HBM by the page table and keeps a running
softmax over them, so nothing of shape ``[B, P * ps, ...]`` exists;
everything else (int8 pools, a pool committed to a mesh, a latent
layer's chunk of several tokens, any CPU run) **gathers**:
``paged_gather`` makes every slot's whole logical view dense (in the
held row form) and attention runs under ``paged_attend_mask``. Both
mean the same thing.

Masking: slot ``b`` attends logical positions ``[start[b], lens[b]]``
(``start`` = its left-pad count, ``lens`` = where this step's token was
just written). Physical page ids play no role in masking — the page
table is pure address translation, updated on the HOST between steps
(it rides into the decode program as a small traced input, so seating
and freeing slots never recompiles anything).

Physical page 0 is reserved as the **trash page**: freed slots' table
rows point at it, so an idle slot's ride-along decode write lands in a
page no live slot ever maps — the paged analog of the dense cache's
"stale rows are masked" contract.

The serving-side pool manager is tpudl.serve.cache.PagedKVCache; the
decode program contract is tpudl.models.generate.paged_decode_fn.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

#: Symmetric int8 range: quantized values live in [-127, 127].
INT8_MAX = 127.0
#: Floor on quantization scales so an all-zero row dequantizes to zeros
#: instead of dividing by zero.
SCALE_EPS = 1e-12


@dataclasses.dataclass
class PagedView:
    """Per-dispatch paged-cache addressing, threaded through the model.

    ``page_table`` ([B, P] int32) maps slot b's logical page j to a
    physical pool page (0 = the trash page for unmapped entries);
    ``start`` ([B] int32) is slot b's first attendable logical position
    (its left-pad count); ``lens`` ([B] int32) is the logical position
    this step's token is written at. ``page_size``, ``quantized`` and
    ``sharded`` are STATIC (baked into the compiled program); the
    arrays are traced inputs, so the host mutates placement freely
    between dispatches. ``sharded`` says the pool was committed to a
    mesh (``PagedKVCache.commit``): a fact of the cache, not a setting.
    ``took`` is the program's record of itself, written while it is
    traced: one entry a paged attention layer, True where the layer
    reads the pool in place (tpudl.ops.paged_attention) and False
    where it gathers.

    A cache with WINDOW layers (``PagedKVCache.window``) hands
    ``page_table`` over as the pair ``(page_table, ring_table)``: the
    second, [B, R] int32, maps each slot's ring of ``R = window /
    page_size + 1`` pages in the window layers' own, smaller pools. A
    window layer addresses its pool through ``ring_view``.
    """

    page_table: jax.Array
    start: jax.Array
    lens: jax.Array
    page_size: int
    quantized: bool
    sharded: bool = False
    took: list = dataclasses.field(default_factory=list)
    ring_table: Optional[jax.Array] = None

    def __post_init__(self):
        if isinstance(self.page_table, (tuple, list)):
            self.page_table, self.ring_table = self.page_table
        self._rings: dict = {}

    @property
    def logical_len(self) -> int:
        """Positions addressable per slot: pages_per_slot x page_size."""
        return int(self.page_table.shape[1]) * self.page_size

    def ring_view(self, window: int) -> "PagedView":
        """The view a layer that keeps only the last ``window``
        positions addresses its ring by: an ordinary view over a table
        of R pages, so the write, the gather, the mask and the
        in-place kernel serve it unchanged.

        Logical position ``t`` of a slot lies on ring page ``(t //
        page_size) mod R``. A step at position ``lens`` attends
        ``[first, lens]`` with ``first = max(start, lens - window +
        1)``, at most R consecutive logical pages from ``first //
        page_size`` on; the ring table is ROTATED so that this page
        comes first, and ``start`` / ``lens`` are taken relative to it
        (RoPE was applied before the cache, so attention needs no
        absolute position). The page just behind the window is the one
        the next page's first write lands on: its old rows lie past the
        relative ``lens`` until they are overwritten, and are masked
        like any unwritten row. Derived on the device from the three
        small inputs (a [B, R] gather), once a program."""
        view = self._rings.get(window)
        if view is None:
            if self.ring_table is None:
                raise ValueError(
                    "a window layer needs the cache's ring table: build "
                    "the pools with tpudl.serve.cache.PagedKVCache from "
                    "this model's own prefill template"
                )
            ps, ring = self.page_size, int(self.ring_table.shape[1])
            if ring * ps < window + ps - 1:
                raise ValueError(
                    f"a ring of {ring} pages of {ps} cannot hold a "
                    f"window of {window}"
                )
            first = jnp.maximum(self.start, self.lens - (window - 1))
            page0 = first // ps
            turn = (
                page0[:, None]
                + jnp.arange(ring, dtype=page0.dtype)[None, :]
            ) % ring
            view = self._rings[window] = PagedView(
                page_table=jnp.take_along_axis(
                    self.ring_table, turn, axis=1
                ),
                start=first - page0 * ps, lens=self.lens - page0 * ps,
                page_size=ps, quantized=self.quantized,
                sharded=self.sharded, took=self.took,
            )
        return view


def quantize_kv(x: jax.Array):
    """Symmetric int8 quantization over the head_dim axis.

    ``x`` [..., Hkv, D] -> (q int8 [..., Hkv, D], scale f32 [..., Hkv])
    (a headless row [..., C] -> one scale a row);
    ``q * scale`` reconstructs x to ~0.4% of the per-head max — the
    granularity that keeps greedy decode token-stable at tiny scales
    while costing 4/D extra bytes per element (scale rows ride in the
    pool next to their page)."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / INT8_MAX
    scale = jnp.maximum(scale, SCALE_EPS)
    q = jnp.round(xf / scale[..., None])
    q = jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8)
    return q, scale


#: The chip's tile: 128 values along the minor axis (lanes), 8 rows
#: along the one before it (sublanes).
LANES = 128
SUBLANES = 8


def lane_window(first: int, size: int, width: int):
    """Whole lanes around ``[first, first + size)`` of a row ``width``
    wide: what the matrix unit takes as it lies, where a cut at
    ``first`` would be a copy."""
    return first // LANES * LANES, min(
        -(-(first + size) // LANES) * LANES, width
    )


def page_fold(page_size: int, tail, dtype) -> int:
    """How many positions of a page share one row of the HELD pool
    leaf: the ONE rule of the pool's shape, from what the cache can
    observe when it builds a leaf (the page size, the row's trailing
    shape ``tail``, the stored dtype).

    The chip's default layout of a buffer follows its SHAPE: a leaf
    ``[NP, ps, C]`` whose ``C`` is not a whole number of 128-value
    lanes is laid page-index-minor, and every program that takes it
    re-lays it on the way in and out (two pool-sized copies a layer a
    program). So such a row is folded ``f`` positions to a held row,
    ``[NP, ps / f, f * C]``, with the smallest ``f`` that divides the
    page and makes ``f * C`` whole lanes; one that leaves ``ps / f`` a
    whole number of 8-row tiles is taken first (then a slot's gathered
    pages merge into one view without a copy). 1 = held as declared:
    a row with a head axis (``[.., Hkv, D]``), a width that is whole
    lanes already, a width no such ``f`` exists for, and an int8 pool,
    whose dequant scales ride beside it one a position."""
    tail = tuple(int(d) for d in tail)
    if len(tail) != 1 or jnp.dtype(dtype) == jnp.int8:
        return 1
    width = tail[0]
    if width % LANES == 0:
        return 1
    folds = [
        f for f in range(2, page_size + 1)
        if page_size % f == 0 and (f * width) % LANES == 0
    ]
    whole_tiles = [f for f in folds if (page_size // f) % SUBLANES == 0]
    return min(whole_tiles or folds or [1])


def heads_in_lanes(tails, dtype) -> bool:
    """Whether a LAYER's rows with a head axis are held with the heads
    merged into the lane axis, ``[NP, ps, Hkv * D]``: the second rule
    of the pool's shape, from what the cache can observe when it
    builds a layer's leaves (every row leaf's trailing shape, the
    stored dtype).

    The chip pads the minor axis of a buffer to whole 128-value lanes:
    a leaf ``[NP, ps, Hkv, 192]`` would take a third more bytes in HBM
    and in every DMA of a page than its rows have. Where one of a
    layer's head widths is not whole lanes and every ``Hkv * D`` of it
    is, all its leaves are held merged (k and v alike: one kernel
    reads both, tpudl.ops.paged_attention): position ``t`` of a page
    in row ``t``, head ``h`` in lanes ``h * D ...``; the bytes are the
    declared row-major bytes. False = held as declared: every head
    width of whole lanes (the pools that were there, bit for bit), a
    headless row (``page_fold``'s), a merged width that is not whole
    lanes either, and an int8 pool, whose dequant scales are one a
    head."""
    tails = [tuple(int(d) for d in tail) for tail in tails]
    if jnp.dtype(dtype) == jnp.int8 or any(len(t) != 2 for t in tails):
        return False
    return any(t[1] % LANES for t in tails) and not any(
        (t[0] * t[1]) % LANES for t in tails
    )


def held_fold(pages, page_size: int) -> int:
    """The fold a pool leaf is held in, read off its shape."""
    return page_size // int(pages.shape[1])


def row_tail(pages, page_size: int) -> tuple:
    """The trailing shape of ONE logical position of a pool leaf
    ``[NP, *held page]``, whatever shape the page is held in."""
    fold = held_fold(pages, page_size)
    tail = tuple(int(d) for d in pages.shape[2:])
    return tail if fold == 1 else (tail[0] // fold,)


def paged_write(
    pages: jax.Array,
    scales: Optional[jax.Array],
    value: jax.Array,
    view: PagedView,
):
    """Write a token chunk's KV per slot into its current page rows.

    ``pages`` [NP, ps, Hkv, D] (int8 or compute dtype), ``scales``
    [NP, ps, Hkv] f32 (quantized pools only), ``value`` [B, S, Hkv, D]
    (the freshly projected + RoPE'd k or v; [B, Hkv, D] is accepted as
    the S=1 single-token form). A pool with no head axis ([NP, ps, C],
    scales [NP, ps]: a latent cache's one row a position) takes
    ``value`` [B, S, C] the same way, and ``value`` [B, S, Hkv, D]
    where the heads are held merged into its lanes (C = Hkv x D,
    ``heads_in_lanes``); held folded ([NP, ps / f, f * C],
    ``page_fold``) the row lands in held row ``off // f``, lanes
    ``(off % f) * C ...``: the pool is never reshaped.
    Token j of slot b lands at physical
    ``(page_table[b, (lens[b]+j) // ps], (lens[b]+j) % ps)`` — the
    speculative-verify dispatch writes its whole k-token window this
    way; idle slots (lens pinned at 0 on a trash-mapped row) write into
    page 0, which no live slot maps. Positions past the table's logical
    capacity (a verify window overshooting a nearly-full slot) redirect
    to the trash page instead of clamping onto the slot's last page —
    a clamped write would corrupt KEPT rows of the same slot."""
    if value.ndim == pages.ndim - 1:
        value = value[:, None]
    if value.ndim == pages.ndim + 1:
        # Held with the heads merged into the lanes (``heads_in_lanes``):
        # the chunk's rows take that form, the pool is never reshaped.
        value = value.reshape(*value.shape[:2], -1)
    s = value.shape[1]
    ps = view.page_size
    p = view.page_table.shape[1]
    # The scope names these operations in a profiler trace (HLO
    # metadata only; tpudl.obs.spans has the host's half).
    with jax.named_scope("kv_scatter"):
        pos = (
            view.lens[:, None]
            + jnp.arange(s, dtype=view.lens.dtype)[None, :]
        )
        pidx = pos // ps
        page = jnp.take_along_axis(
            view.page_table, jnp.minimum(pidx, p - 1), axis=1
        )
        page = jnp.where(pidx < p, page, 0)
        off = pos % ps
        fold = held_fold(pages, ps)
        if view.quantized:
            q, sc = quantize_kv(value)
            pages = pages.at[page, off].set(q)
            scales = scales.at[page, off].set(sc)
        elif fold == 1:
            pages = pages.at[page, off].set(value.astype(pages.dtype))
        else:
            # Held folded: the row goes into lane block ``off % fold``
            # of held row ``off // fold``. A scatter whose window starts
            # at a lane offset is taken apart into one update at a time
            # by the chip's compiler, so whole held rows are read,
            # merged and written back (both native). One position a
            # slot at a time: two positions of a chunk may share a
            # held row, two slots never do (but on the trash page).
            width = value.shape[-1]
            block = jnp.arange(fold * width) // width
            for j in range(s):
                at = (page[:, j], off[:, j] // fold)
                mine = block[None, :] == (off[:, j] % fold)[:, None]
                new = jnp.tile(value[:, j].astype(pages.dtype), (1, fold))
                pages = pages.at[at].set(jnp.where(mine, new, pages[at]))
    return pages, scales


def paged_gather(
    pages: jax.Array,
    scales: Optional[jax.Array],
    view: PagedView,
    compute_dtype,
) -> jax.Array:
    """Materialize every slot's logical KV view from the pool (the
    gather path; tpudl.ops.paged_attention reads a pool in place where
    it can, and then this is not called).

    Returns [B, L, Hkv, D] ([B, L, C] from a pool with no head axis,
    or whose heads are held merged into its lanes: the caller splits
    them) in ``compute_dtype`` where L = pages_per_slot x page_size; from a
    pool held folded, the HELD rows [B, L / f, f * C] (logical position
    ``t`` in row ``t // f``, lanes ``(t % f) * C ...``), because
    splitting the lanes of a view this size is a copy of it on the
    chip: the attention reads them as they lie
    (``paged_attend_mask(fold=f)``). Dequantization (``q * scale``) is
    fused into this gather for int8 pools. Unmapped logical pages
    resolve to the trash page — finite garbage the attention mask
    excludes."""
    with jax.named_scope("kv_gather"):
        # Whole pages, not rows: a slot's table row names its pages and
        # a page's positions lie together, so one slice a page (16
        # rows) is fetched where a row index would fetch 16. The
        # logical view [B, P * ps, ...] is the same either way (the
        # prefix and migration gathers cut a slot's pages the same
        # way).
        out = pages[view.page_table]  # [B, P, ps, ...]
        if view.quantized:
            out = (
                out.astype(jnp.float32)
                * scales[view.page_table][..., None]
            )
        out = out.reshape(
            out.shape[0], out.shape[1] * out.shape[2], *out.shape[3:]
        )
        return out.astype(compute_dtype)


def paged_attend_mask(
    view: PagedView, chunk: int = 1, fold: int = 1
) -> jax.Array:
    """[B, 1, S, L] bool — query j of the chunk attends logical
    positions in [start, lens + j] inclusive (lens + j = where query
    j's own token was just written), so a multi-token verify chunk is
    causal within itself exactly like sequential single-token steps.
    ``fold`` > 1 gives the same mask in the order a folded pool's held
    rows come in, [B, fold, S, L / fold]: entry ``[b, g, j, i]`` is
    logical position ``fold * i + g``."""
    pos = jnp.arange(view.logical_len).reshape(-1, fold).T
    upper = view.lens[:, None] + jnp.arange(
        chunk, dtype=view.lens.dtype
    )[None, :]
    lower = view.start[:, None, None, None]
    return (pos[None, :, None, :] >= lower) & (
        pos[None, :, None, :] <= upper[:, None, :, None]
    )
