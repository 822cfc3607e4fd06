"""Paged decode attention that reads the live pages where they lie.

The paged decode branches (tpudl.models.llama: LlamaAttention over a
k / v pool pair, LatentAttention over ONE headless pool) used to make a
dense view of every slot's whole table (tpudl.models.paged.paged_gather)
and attend it under a mask, whatever was live: 40 % of a Mistral decode
step's device time (PERF.md, PR 26), 22 % of the latent cell's (PR 30).
The two kernels here visit, for slot ``b``, only the pages that cover
its logical positions ``[start[b], lens[b] + S - 1]``:

- the page table, ``start`` and ``lens`` are scalar-prefetched into
  SMEM; the pools stay in HBM (``memory_space=pl.ANY``) and are read in
  place, one ``make_async_copy`` a live page into a double-buffered VMEM
  block of pages, the next block (of this slot, or the first of the
  next) in flight while this one is attended;
- a running (flash-style) softmax over the blocks: logits and
  statistics in float32, ``p . v`` in the pool's dtype with float32
  accumulation, the division once at the end;
- nothing of shape ``[B, P * ps, ...]`` is made; an idle slot (lens 0
  on the trash page) costs one page.

The k / v kernel (``_kernel``, PR 27) takes a pool ``[NP, ps, Hkv, D]``
as ``[NP, ps * Hkv, D]`` (a bitcast for XLA) and a block as a matrix
whose row ``t * Hkv + h`` is head ``h`` of position ``t``: all ``S * H``
query rows of a slot meet every row of the block in ONE matmul, and a
query row keeps the columns of its own KV head and positions under the
mask (no strided read of a head out of the page). The latent kernel
(``_latent_kernel``, PR 31; below, with its own notes) takes the one
pool as it is HELD, ``[NP, ps / f, f * C]`` (``page_fold``): a row is
key and value at once and all heads share it.

Dispatch seam (tpudl.ops.norms.resolve_impl's rule), the same for both:
``"reference"`` is the gather and the dense attention, left as they
were; ``"fused"`` is the kernel (compiled on the TPU, interpret mode
elsewhere: the CPU test mode); ``"auto"`` is what the model calls, and
chooses by what the program can observe (``in_place_ok``,
``latent_in_place_ok``): the kernel on a TPU for an unquantized pool on
one device whose rows are whole lanes (and, latent, one token a slot);
the gather for everything else (int8 pools, whose dequantisation is
fused into the gather; a pool committed to a mesh, which GSPMD would
gather whole to every chip for a custom call; any CPU run).

There is no knob. Inference only.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.attention import MASK_VALUE, is_tpu_backend
from tpudl.ops.norms import resolve_impl
from tpudl.ops.pallas_utils import kernel_trace, round_up

#: Pages fetched into one VMEM block. A page of Mistral's pool is
#: 32 KB a pool: per-page compute is too small to hide a DMA, so a
#: block is 8 pages (128 positions; 1 MB of VMEM for both pools, double
#: buffered). Measured on the chip against 2, 4, 16 and 32 on the
#: serving cells' patterns (PERF.md, PR 27).
PAGES_PER_BLOCK = 8
#: The scope the kernel's operations sit under (inside the layer's
#: ``attention``). ``kv_gather`` keeps meaning the gather.
SCOPE = "paged_attention"


def in_place_ok(q, pages, view, pages_v=None, sink=None) -> bool:
    """Whether a kernel can serve this layer, from what the program
    can observe at trace time: the view's static facts and the shapes.
    ``pages`` is the k pool. A pool pair held as declared, ``[NP, ps,
    Hkv, D]``, is ``_kernel``'s (v is k's twin, no sink); one held with
    the heads merged into the lanes is ``_merged_kernel``'s
    (``merged_in_place_ok``)."""
    if view.quantized or view.sharded:
        return False
    if pages.ndim == 3:
        return pages_v is not None and merged_in_place_ok(q, pages, pages_v)
    if pages.ndim != 4 or sink is not None or (
        pages_v is not None and pages_v.shape != pages.shape
    ):
        return False
    _, ps, hkv, d = pages.shape
    # A page is a whole number of the dtype's (sublane x 128) tiles.
    sublanes = 8 * (4 // jnp.dtype(pages.dtype).itemsize)
    return (
        d % 128 == 0
        and q.shape[-1] == d
        and q.shape[2] % hkv == 0
        and (ps * hkv) % sublanes == 0
    )


def paged_attention_ref(q, pages_k, pages_v, view, scale_k=None, scale_v=None,
                        sink=None):
    """Today's path: every slot's logical view gathered dense out of
    both pools, then grouped-query attention under the mask."""
    from tpudl.models.llama import _gqa_decode_attention
    from tpudl.models.paged import paged_attend_mask, paged_gather

    kf = paged_gather(pages_k, scale_k, view, q.dtype)
    vf = paged_gather(pages_v, scale_v, view, q.dtype)
    if kf.ndim == 3:
        # Heads held merged into the lanes: a key head is a query's width.
        hkv = kf.shape[-1] // q.shape[-1]
        kf = kf.reshape(*kf.shape[:2], hkv, -1)
        vf = vf.reshape(*vf.shape[:2], hkv, -1)
    return _gqa_decode_attention(
        q, kf, vf, paged_attend_mask(view, chunk=q.shape[1]), sink=sink
    )


def _kernel(
    table_ref, start_ref, lens_ref,  # scalar prefetch
    q_ref, k_hbm, v_hbm,
    o_ref,
    kbuf, vbuf, sem,
    *, page_size: int, heads: int, kv_heads: int, chunk: int, ppb: int,
):
    """All slots in one invocation: an outer loop over slots, an inner
    one over the slot's blocks of pages. ``q_ref`` / ``o_ref``
    ``[B, R, D]`` with row ``r = s * H + h``; ``k_hbm`` / ``v_hbm``
    ``[NP, ps * Hkv, D]``; ``kbuf`` / ``vbuf`` ``[2, ppb * ps * Hkv,
    D]``; ``sem`` one DMA semaphore a buffer and pool."""
    num_slots, rows, d = q_ref.shape
    pages = table_ref.shape[1]
    page_rows = page_size * kv_heads
    cols = ppb * page_rows
    group = heads // kv_heads

    def span(b):
        """First and last logical page slot ``b`` attends."""
        lo = start_ref[b] // page_size
        hi = jnp.minimum((lens_ref[b] + chunk - 1) // page_size, pages - 1)
        return lo, jnp.maximum(hi, lo)

    def each_page(b, j, buf, act):
        """``act`` on the copies of block ``j`` of slot ``b`` into
        buffer ``buf``: one a live page and pool. Start and wait walk
        the same pages. (A loop, not ``ppb`` unrolled copies: the
        kernel is lowered in every process that serves, and set-up
        time is judged.)"""
        lo, hi = span(b)
        first = lo + j * ppb

        def page(i, _):
            phys = table_ref[b, first + i]
            rows_i = pl.ds(pl.multiple_of(i * page_rows, page_rows), page_rows)
            for hbm, vmem, which in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(
                    hbm.at[phys], vmem.at[buf, rows_i], sem.at[buf, which]
                ))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(hi - first + 1, ppb), page, 0)

    # A page that was not fetched holds what the buffer held before:
    # its keys are masked whatever they are, its values meet a weight
    # of exactly 0, which only a finite value survives.
    vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
    # Row r is query s = r // H of head r % H; column c is head c % Hkv
    # of the block's position c // Hkv.
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    own_head = (row % heads) // group == col % kv_heads
    col_pos = col // kv_heads
    row_s = jnp.minimum(row // heads, chunk - 1)
    scale = d ** -0.5

    each_page(0, 0, 0, lambda copy: copy.start())

    def slot_body(b, buf):
        lo, hi = span(b)
        blocks = (hi - lo) // ppb + 1
        q = q_ref[b]
        first = start_ref[b]
        # A verify window may overshoot a nearly full slot: positions
        # past the table's capacity do not exist (paged_write sent
        # their rows to the trash page).
        upper = jnp.minimum(lens_ref[b] + row_s, pages * page_size - 1)

        def block_body(j, carry):
            m, l, acc, buf = carry
            last = j + 1 >= blocks
            nb = jnp.where(last, b + 1, b)
            nj = jnp.where(last, 0, j + 1)

            @pl.when(nb < num_slots)
            def _():
                each_page(nb, nj, 1 - buf, lambda copy: copy.start())

            each_page(b, j, buf, lambda copy: copy.wait())
            k = kbuf[buf]
            v = vbuf[buf]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            pos = (lo + j * ppb) * page_size + col_pos
            s = jnp.where(
                own_head & (pos >= first) & (pos <= upper), s, MASK_VALUE
            )
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32
            )
            return m_new, l, acc, 1 - buf

        m0 = jnp.full((rows, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
        acc0 = jnp.zeros((rows, d), jnp.float32)
        _, l, acc, buf = jax.lax.fori_loop(
            0, blocks, block_body, (m0, l0, acc0, buf)
        )
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return buf

    jax.lax.fori_loop(0, num_slots, slot_body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused(q, pages_k, pages_v, page_table, start, lens, *, interpret: bool):
    """The kernel's call, jitted on its own: every layer of a decode
    program calls it with the same shapes, so it is traced once and
    lowered once (one function the layers share), where sixteen
    ``pallas_call``s inline would each be lowered in every process that
    serves. Its scope is named inside, so the shared function carries
    it whichever layer it was first lowered for."""
    b, s, h, d = q.shape
    n_pages, ps, hkv, _ = pages_k.shape
    rows = round_up(s * h, 16)
    q3 = q.reshape(b, s * h, d).astype(pages_k.dtype)
    if rows != s * h:
        q3 = jnp.pad(q3, ((0, 0), (0, rows - s * h), (0, 0)))
    ppb = min(PAGES_PER_BLOCK, int(page_table.shape[1]))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block = (2, ppb * ps * hkv, d)
    with jax.named_scope(SCOPE), kernel_trace("paged_attention"):
        out = pl.pallas_call(
            functools.partial(
                _kernel, page_size=ps, heads=h, kv_heads=hkv, chunk=s,
                ppb=ppb,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(1,),
                in_specs=[whole, in_hbm, in_hbm],
                out_specs=whole,
                scratch_shapes=[
                    pltpu.VMEM(block, pages_k.dtype),
                    pltpu.VMEM(block, pages_v.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="paged_attention",
        )(
            page_table.astype(jnp.int32),
            start.astype(jnp.int32),
            lens.astype(jnp.int32),
            q3,
            pages_k.reshape(n_pages, ps * hkv, d),
            pages_v.reshape(n_pages, ps * hkv, d),
        )
        return out[:, : s * h].reshape(b, s, h, d)


def paged_attention_fused(q, pages_k, pages_v, view, interpret: bool,
                          sink=None):
    """The Pallas path. ``q`` [B, S, H, D]; ``pages_k`` / ``pages_v``
    [NP, ps, Hkv, D], or [NP, ps, Hkv x D] and [NP, ps, Hkv x Dv] with
    the heads merged into the lanes; returns [B, S, H, Dv] in
    ``q.dtype``."""
    if not in_place_ok(q, pages_k, view, pages_v, sink):
        raise ValueError(
            "the paged-attention kernels read an unquantized k / v pool "
            "pair on one device: [NP, ps, Hkv, D] twice with D a "
            "multiple of 128 and no sink, or [NP, ps, Hkv x D] beside "
            "[NP, ps, Hkv x Dv] with both merged widths and Dv "
            f"multiples of 128; got q {q.shape}, pools {pages_k.shape} "
            f"{pages_k.dtype} and {pages_v.shape}, sink "
            f"{sink is not None}, quantized={view.quantized}, "
            f"sharded={view.sharded}"
        )
    if pages_k.ndim == 3:
        return _fused_merged(
            q, pages_k, pages_v, view.page_table, view.start, view.lens,
            sink, interpret=interpret,
        )
    return _fused(
        q, pages_k, pages_v, view.page_table, view.start, view.lens,
        interpret=interpret,
    )


def paged_attention(
    q, pages_k, pages_v, view, *,
    scale_k=None, scale_v=None, sink=None,
    impl: str = "auto", interpret: Optional[bool] = None,
):
    """Attention of ``q`` [B, S, H, D] over each slot's logical
    positions ``[start, lens + j]`` (query ``j`` of the chunk) of the
    paged pools, ``paged_write`` having put this step's rows there.
    Returns [B, S, H, Dv]. ``sink`` [H] float32: a learned score a
    query head in the softmax, which no value follows. ``view`` records
    which path the layer took (``PagedView.took``). See the module
    docstring for the seam."""
    if impl == "auto":
        impl = (
            "fused"
            if is_tpu_backend()
            and in_place_ok(q, pages_k, view, pages_v, sink)
            else "reference"
        )
    fused, interpret = resolve_impl(impl, interpret)
    view.took.append(fused)
    if fused:
        return paged_attention_fused(
            q, pages_k, pages_v, view, interpret, sink
        )
    return paged_attention_ref(
        q, pages_k, pages_v, view, scale_k, scale_v, sink
    )


# ---------------------------------------------------------------------------
# A k / v pool pair held with the heads MERGED into the lanes
# (tpudl.models.paged.heads_in_lanes: keys 192 wide beside values 128
# wide, KV heads that differ by layer kind), read the same way. A second
# entry with its own ``pallas_call`` and the first one's page walk (``span``,
# ``each_page``: a slot's live pages, double-buffered, the next slot's first
# block in flight): the rows are other rows, so the arithmetic is another,
# and ``_kernel`` above is left as it is, line for line, for the pools that
# reach it today. Not one kernel with branches: there a page is ``ps x Hkv``
# rows of ONE head's width and a query keeps its own head's COLUMNS of the
# scores; here a page is ``ps`` rows of all heads' widths and a query keeps
# its own head's LANES.
# ---------------------------------------------------------------------------

#: Pages fetched into one VMEM block of the merged kernel: a window
#: layer's whole ring (9 pages of 16 for a window of 128) is ONE block,
#: and a page here is all heads of 16 positions (24-48 KB a pool).
MERGED_PAGES_PER_BLOCK = 16


def merged_in_place_ok(q, pages_k, pages_v) -> bool:
    """``in_place_ok`` for a pool pair held ``[NP, ps, Hkv x D]`` /
    ``[NP, ps, Hkv x Dv]``: the key row is a whole number of query
    widths (that number is Hkv), both merged widths and a value head
    are whole lanes, the query heads group over the KV heads, and a
    page is whole tiles of the dtype."""
    from tpudl.models.paged import LANES

    if pages_k.ndim != 3 or pages_v.ndim != 3:
        return False
    (_, ps, wk), (_, ps_v, wv) = pages_k.shape, pages_v.shape
    d = q.shape[-1]
    if wk % d or ps_v != ps or pages_v.dtype != pages_k.dtype:
        return False
    hkv = wk // d
    sublanes = 8 * (4 // jnp.dtype(pages_k.dtype).itemsize)
    return (
        wv % hkv == 0
        and (wv // hkv) % LANES == 0
        and wk % LANES == 0
        and q.shape[2] % hkv == 0
        and ps % sublanes == 0
    )


def _merged_kernel(
    table_ref, start_ref, lens_ref,  # scalar prefetch
    *refs,
    page_size: int, heads: int, kv_heads: int, chunk: int, ppb: int,
    scale: float, has_sink: bool,
):
    """One slot a grid step, an inner loop over the slot's blocks of
    pages; the first block of the next slot is in flight while this
    slot's last is attended (``_latent_kernel``'s frame, ``_kernel``'s
    walk of two pools).

    ``q_ref`` [1, R, Hkv x D], row ``r = s * H + h``: head ``h``'s
    query in the lanes of ITS KV head and zeros elsewhere, so ONE
    matmul against a block's rows ``[ppb * ps, Hkv x D]`` scores every
    query row against every position, [R, ppb * ps], with no column of
    another head to mask. ``sink_ref`` [R, 1] float32 (``has_sink``):
    in a running softmax a sink is only the state a row STARTS from
    (running maximum the sink, running sum 1, accumulator 0, in place
    of -inf, 0, 0): no pass of its own. ``p . v`` against the block's
    value rows [ppb * ps, Hkv x Dv] accumulates every KV head's values
    for every row; a row keeps its own head's lanes once, at the end.
    ``o_ref`` [1, R, Dv]; ``kbuf`` / ``vbuf`` [2, ppb, ps, width]: a
    page is a LEADING index of the buffer; ``sem`` one DMA semaphore a
    buffer and pool; ``turn`` (SMEM) the buffer the next block lands
    in."""
    if has_sink:
        q_ref, sink_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, turn = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sem, turn = refs
    b = pl.program_id(0)
    num_slots = pl.num_programs(0)
    pages = table_ref.shape[1]
    rows = q_ref.shape[1]
    dv = o_ref.shape[2]
    cols = ppb * page_size
    group = heads // kv_heads

    def span(slot):
        """First and last logical page ``slot`` attends."""
        lo = start_ref[slot] // page_size
        hi = jnp.minimum(
            (lens_ref[slot] + chunk - 1) // page_size, pages - 1
        )
        return lo, jnp.maximum(hi, lo)

    def each_page(slot, j, which, act):
        """``act`` on the copies of block ``j`` of ``slot`` into buffer
        ``which``: one a live page and pool. Start and wait walk the
        same pages (a loop, not unrolled copies: set-up time is
        judged)."""
        lo, hi = span(slot)
        first = lo + j * ppb

        def page(i, _):
            phys = table_ref[slot, first + i]
            for hbm, vmem, pool in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(
                    hbm.at[phys], vmem.at[which, i], sem.at[which, pool]
                ))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(hi - first + 1, ppb), page, 0)

    @pl.when(b == 0)
    def _():
        # A page that was not fetched holds what the buffer held
        # before: its keys are masked whatever they are, its values
        # meet a weight of exactly 0, which only a finite value
        # survives.
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        turn[0] = 0
        each_page(0, 0, 0, lambda copy: copy.start())

    lo, hi = span(b)
    blocks = (hi - lo) // ppb + 1
    q = q_ref[0]
    first = start_ref[b]
    # Row r is query s = r // H; column c is position c of the block.
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    row_s = jnp.minimum(row // heads, chunk - 1)
    # A verify window may overshoot a nearly full slot: positions past
    # the table's capacity do not exist.
    upper = jnp.minimum(lens_ref[b] + row_s, pages * page_size - 1)

    def block_body(j, carry):
        m, l, acc, which = carry
        last = j + 1 >= blocks
        nb = jnp.where(last, b + 1, b)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(nb < num_slots)
        def _():
            each_page(nb, nj, 1 - which, lambda copy: copy.start())

        each_page(b, j, which, lambda copy: copy.wait())
        k = kbuf[which].reshape(cols, kbuf.shape[-1])
        v = vbuf[which].reshape(cols, vbuf.shape[-1])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        pos = (lo + j * ppb) * page_size + col
        s = jnp.where((pos >= first) & (pos <= upper), s, MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        return m_new, l, acc, 1 - which

    if has_sink:
        m0 = sink_ref[...]
        l0 = jnp.ones((rows, 1), jnp.float32)
    else:
        m0 = jnp.full((rows, 1), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, kv_heads * dv), jnp.float32)
    _, l, acc, which = jax.lax.fori_loop(
        0, blocks, block_body, (m0, l0, acc0, turn[0])
    )
    turn[0] = which
    # Row r is query head r % H, whose values are KV head (r % H) // g's.
    own = (
        jax.lax.broadcasted_iota(jnp.int32, (rows, dv), 0) % heads
    ) // group
    out = jnp.zeros((rows, dv), jnp.float32)
    for h in range(kv_heads):
        out = jnp.where(own == h, acc[:, h * dv:(h + 1) * dv], out)
    o_ref[0] = (out / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_merged(
    q, pages_k, pages_v, page_table, start, lens, sink, *, interpret: bool,
):
    """The merged kernel's call, jitted on its own like ``_fused``:
    the layers of one kind share one lowering, and the scope is named
    inside so the shared function carries it."""
    b, s, h, d = q.shape
    _, ps, wk = pages_k.shape
    wv = pages_v.shape[-1]
    hkv = wk // d
    dv = wv // hkv
    rows = round_up(s * h, 16)
    ppb = min(MERGED_PAGES_PER_BLOCK, int(page_table.shape[1]))
    with jax.named_scope(SCOPE), kernel_trace("merged_paged_attention"):
        # Head h's query in the lanes of its KV head, zeros elsewhere.
        own = (
            jnp.arange(h)[:, None] // (h // hkv) == jnp.arange(hkv)[None, :]
        )
        q3 = jnp.where(
            own[None, None, :, :, None],
            q.astype(pages_k.dtype)[:, :, :, None, :], 0,
        ).reshape(b, s * h, wk)
        if rows != s * h:
            q3 = jnp.pad(q3, ((0, 0), (0, rows - s * h), (0, 0)))
        operands = [q3]
        in_specs = [pl.BlockSpec((1, rows, wk), lambda i, *_: (i, 0, 0))]
        if sink is not None:
            column = jnp.tile(sink.astype(jnp.float32), s)
            operands.append(
                jnp.pad(column, (0, rows - s * h)).reshape(rows, 1)
            )
            in_specs.append(pl.BlockSpec(memory_space=pltpu.VMEM))
        in_hbm = pl.BlockSpec(memory_space=pl.ANY)
        out = pl.pallas_call(
            functools.partial(
                _merged_kernel, page_size=ps, heads=h, kv_heads=hkv,
                chunk=s, ppb=ppb, scale=d ** -0.5,
                has_sink=sink is not None,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=in_specs + [in_hbm, in_hbm],
                out_specs=pl.BlockSpec(
                    (1, rows, dv), lambda i, *_: (i, 0, 0)
                ),
                scratch_shapes=[
                    pltpu.VMEM((2, ppb, ps, wk), pages_k.dtype),
                    pltpu.VMEM((2, ppb, ps, wv), pages_v.dtype),
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, rows, dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="merged_paged_attention",
        )(
            page_table.astype(jnp.int32),
            start.astype(jnp.int32),
            lens.astype(jnp.int32),
            *operands,
            pages_k,
            pages_v,
        )
        return out[:, : s * h].reshape(b, s, h, dv)


# ---------------------------------------------------------------------------
# The headless pool of a latent (MLA) layer, read the same way. A sibling
# with its own ``pallas_call``: ONE pool serves as keys and as values, it
# has no head axis, and it may be held folded. The k / v kernel above is
# left as it is, line for line.
# ---------------------------------------------------------------------------

#: Pages fetched into one VMEM block of the latent kernel. A latent page
#: is 18 KB and all of a slot's heads share it: a block costs its
#: matmuls' fixed part (a microsecond or two), not its bytes, so most
#: slots of the sarvam cell (~19 live pages) take ONE block. Timed on
#: the chip against 8, 16 and 64 on that pattern (PERF.md, PR 31).
LATENT_PAGES_PER_BLOCK = 32
#: The scope the latent kernel's operations sit under (inside the
#: layer's ``attention``): the one ``_mla_absorbed`` names, so whatever
#: implements the absorbed attention is read as the same work.
LATENT_SCOPE = "mla_core"


def latent_in_place_ok(query, pages, view) -> bool:
    """``in_place_ok`` for a headless pool, from what the program can
    observe at trace time. ``query`` [B, S, H, C] is the absorbed query
    (``[q_nope W_kv_b^K | q_rope]``), ``pages`` the pool as it is HELD,
    [NP, ps / f, f * C] (tpudl.models.paged.page_fold): one query a
    slot, a held row of whole lanes that is ``f`` query widths, and a
    page of whole 8-row tiles, so that no copy lands on part of one."""
    from tpudl.models.paged import LANES, SUBLANES, held_fold

    if view.quantized or view.sharded or pages.ndim != 3:
        return False
    _, held, width = pages.shape
    return (
        query.shape[1] == 1
        and width % LANES == 0
        and held % SUBLANES == 0
        and view.page_size % held == 0
        and width == held_fold(pages, view.page_size) * query.shape[-1]
    )


def paged_latent_attention_ref(query, pages, view, rank, scale, scales=None):
    """The gather path: every slot's whole logical view made dense out
    of the pool (held rows, dequantised where the pool is int8), then
    the absorbed attention under the mask."""
    from tpudl.models.llama import attend_latent_rows
    from tpudl.models.paged import held_fold, paged_attend_mask, paged_gather

    rows = paged_gather(pages, scales, view, query.dtype)
    mask = paged_attend_mask(
        view, chunk=query.shape[1], fold=held_fold(pages, view.page_size)
    )
    with jax.named_scope(LATENT_SCOPE):
        return attend_latent_rows(query, rows, mask, scale, rank)


def _latent_kernel(
    table_ref, start_ref, lens_ref,  # scalar prefetch
    q_ref, kv_hbm,
    o_ref,
    buf, sem, turn,
    *, page_size: int, rank: int, ppb: int, scale: float,
):
    """One slot a grid step (the query and the output are too large to
    sit in VMEM whole; the pipeline brings a slot's), an inner loop over
    the slot's blocks of pages; the first block of the next slot is in
    flight while this slot's last is attended.

    ``kv_hbm`` [NP, ps / f, f * C] as held; ``buf`` [2, ppb, ps / f,
    f * C]: a page is a LEADING index of the buffer, so a copy is one
    whole page onto whole tiles whatever the dtype packs into a tile.
    ``q_ref`` [1, Hp, C padded to whole lanes]: the heads' queries, put
    ``f`` times into one matrix [f * Hp, f * C] once a slot, rows
    ``g * Hp ...`` holding them in lanes ``g * C ...`` and zeros
    elsewhere (a rotation inside a window of whole lanes), so ONE
    matmul against a block's held rows scores lane block ``g`` (logical
    positions ``f * i + g``) in rows ``g * Hp ...``: all ``f`` groups
    under one softmax. The value of a position is the first ``rank``
    values of its row: group ``g`` accumulates ``p_g . rows`` over a
    window of whole lanes around its block, cut once at the end.
    ``o_ref`` [1, Hp, rank]; ``sem`` one DMA semaphore a buffer;
    ``turn`` (SMEM) the buffer the next block lands in."""
    from tpudl.models.paged import lane_window

    b = pl.program_id(0)
    num_slots = pl.num_programs(0)
    pages = table_ref.shape[1]
    _, _, held, width = buf.shape
    fold = page_size // held
    c = width // fold
    hp = o_ref.shape[1]
    cols = ppb * held
    windows = [lane_window(g * c, rank, width) for g in range(fold)]

    def span(slot):
        """First and last logical page ``slot`` attends."""
        lo = start_ref[slot] // page_size
        hi = jnp.minimum(lens_ref[slot] // page_size, pages - 1)
        return lo, jnp.maximum(hi, lo)

    def each_page(slot, j, which, act):
        """``act`` on the copies of block ``j`` of ``slot`` into buffer
        ``which``: one a live page. Start and wait walk the same
        pages (a loop, not unrolled copies: set-up time is judged)."""
        lo, hi = span(slot)
        first = lo + j * ppb

        def page(i, _):
            act(pltpu.make_async_copy(
                kv_hbm.at[table_ref[slot, first + i]], buf.at[which, i],
                sem.at[which],
            ))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(hi - first + 1, ppb), page, 0)

    @pl.when(b == 0)
    def _():
        # A page that was not fetched holds what the buffer held
        # before: masked as a key, and as a value it meets a weight of
        # exactly 0, which only a finite value survives.
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        turn[0] = 0
        each_page(0, 0, 0, lambda copy: copy.start())

    def stacked_query():
        padded = q_ref[0]
        lanes = padded.shape[1]
        zeros = lambda n: jnp.zeros((hp, n), padded.dtype)  # noqa: E731
        groups = []
        for g in range(fold):
            begin, end = lane_window(g * c, c, width)
            part = padded
            if end - begin > lanes:
                part = jnp.concatenate(
                    [part, zeros(end - begin - lanes)], axis=1
                )
            if g * c > begin:
                # The lanes that come round are zeros. (Rotated as
                # 32-bit values: the chip rotates no packed ones.)
                part = pltpu.roll(
                    part.astype(jnp.float32), g * c - begin, 1
                ).astype(padded.dtype)
            groups.append(jnp.concatenate([
                x for x in (zeros(begin), part, zeros(width - end))
                if x.shape[1]
            ], axis=1))
        return jnp.concatenate(groups, axis=0)

    lo, hi = span(b)
    blocks = (hi - lo) // ppb + 1
    q = stacked_query()
    first = start_ref[b]
    upper = jnp.minimum(lens_ref[b], pages * page_size - 1)
    # Row g * Hp + h is head h on lane block g; column i is held row i
    # of the block: logical position f * i + g of it.
    row = jax.lax.broadcasted_iota(jnp.int32, (fold * hp, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (fold * hp, cols), 1)
    offset = fold * col + row // hp

    def block_body(j, carry):
        m, l, accs, which = carry
        last = j + 1 >= blocks
        nb = jnp.where(last, b + 1, b)
        nj = jnp.where(last, 0, j + 1)

        @pl.when(nb < num_slots)
        def _():
            each_page(nb, nj, 1 - which, lambda copy: copy.start())

        each_page(b, j, which, lambda copy: copy.wait())
        rows = buf[which].reshape(cols, width)
        s = jax.lax.dot_general(
            q, rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        pos = (lo + j * ppb) * page_size + offset
        s = jnp.where((pos >= first) & (pos <= upper), s, MASK_VALUE)
        top = jnp.max(s, axis=-1, keepdims=True)
        m_new = m
        for g in range(fold):
            m_new = jnp.maximum(m_new, top[g * hp:(g + 1) * hp])
        p = jnp.exp(s - jnp.concatenate([m_new] * fold, axis=0))
        alpha = jnp.exp(m - m_new)
        total = jnp.sum(p, axis=-1, keepdims=True)
        l = alpha * l + sum(
            total[g * hp:(g + 1) * hp] for g in range(fold)
        )
        accs = tuple(
            alpha * acc + jnp.dot(
                p[g * hp:(g + 1) * hp].astype(rows.dtype),
                rows[:, begin:end], preferred_element_type=jnp.float32,
            )
            for g, (acc, (begin, end)) in enumerate(zip(accs, windows))
        )
        return m_new, l, accs, 1 - which

    m0 = jnp.full((hp, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((hp, 1), jnp.float32)
    accs0 = tuple(
        jnp.zeros((hp, end - begin), jnp.float32) for begin, end in windows
    )
    _, l, accs, which = jax.lax.fori_loop(
        0, blocks, block_body, (m0, l0, accs0, turn[0])
    )
    turn[0] = which
    u = sum(
        acc[:, g * c - begin:g * c - begin + rank]
        for g, (acc, (begin, _)) in enumerate(zip(accs, windows))
    )
    o_ref[0] = (u / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("page_size", "rank", "scale", "interpret")
)
def _fused_latent(
    query, pages, page_table, start, lens,
    *, page_size: int, rank: int, scale: float, interpret: bool,
):
    """The latent kernel's call, jitted on its own like ``_fused``: the
    layers of a decode program share one lowering, and the scope is
    named inside so the shared function carries it."""
    from tpudl.models.paged import LANES

    b, _, h, c = query.shape
    _, held, width = pages.shape
    hp = round_up(h, 16)
    ppb = min(LATENT_PAGES_PER_BLOCK, int(page_table.shape[1]))
    with jax.named_scope(LATENT_SCOPE), kernel_trace("latent_paged_attention"):
        q = jnp.pad(
            query.reshape(b, h, c).astype(pages.dtype),
            ((0, 0), (0, hp - h), (0, round_up(c, LANES) - c)),
        )
        out = pl.pallas_call(
            functools.partial(
                _latent_kernel, page_size=page_size, rank=rank, ppb=ppb,
                scale=scale,
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(b,),
                in_specs=[
                    pl.BlockSpec(
                        (1, hp, q.shape[-1]), lambda i, *_: (i, 0, 0)
                    ),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(
                    (1, hp, rank), lambda i, *_: (i, 0, 0)
                ),
                scratch_shapes=[
                    pltpu.VMEM((2, ppb, held, width), pages.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SMEM((1,), jnp.int32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b, hp, rank), query.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)
            ),
            interpret=interpret,
            name="latent_paged_attention",
        )(
            page_table.astype(jnp.int32),
            start.astype(jnp.int32),
            lens.astype(jnp.int32),
            q,
            pages,
        )
        return out[:, :h].reshape(b, 1, h, rank)


def paged_latent_attention(
    query, pages, view, *, rank: int, scale: float,
    scales=None, chosen=None,
    impl: str = "auto", interpret: Optional[bool] = None,
):
    """Absorbed latent attention of ``query`` [B, S, H, C] over each
    slot's logical positions ``[start, lens + j]`` of the ONE headless
    pool ``pages`` (as held), ``paged_write`` having put this step's
    rows there: a position's score is ``query . row``, its value the
    row's first ``rank``. Returns ``u`` [B, S, H, rank]. ``view``
    records the path taken. ``"auto"``: the kernel on a TPU where
    ``latent_in_place_ok``, else the gather. ``chosen`` [B, S, k]: of
    those positions, the ones an indexer chose (``chosen_latent_rows``)."""
    if chosen is not None:
        return chosen_latent_rows(query, pages, view, rank, scale, chosen, scales)
    if impl == "auto":
        impl = (
            "fused"
            if is_tpu_backend() and latent_in_place_ok(query, pages, view)
            else "reference"
        )
    fused, interpret = resolve_impl(impl, interpret)
    view.took.append(fused)
    if not fused:
        return paged_latent_attention_ref(
            query, pages, view, rank, scale, scales
        )
    if not latent_in_place_ok(query, pages, view):
        raise ValueError(
            "the latent paged-attention kernel reads one unquantized "
            "headless pool [NP, ps / f, f * C] on one device, a held "
            "row of whole lanes and a page of whole 8-row tiles, one "
            f"query a slot; got query {query.shape}, pool {pages.shape} "
            f"{pages.dtype}, page size {view.page_size}, "
            f"quantized={view.quantized}, sharded={view.sharded}"
        )
    return _fused_latent(
        query, pages, view.page_table, view.start, view.lens,
        page_size=view.page_size, rank=rank, scale=float(scale),
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Learned sparse attention (tpudl.models.llama, below its stack): an
# indexer chooses ``k`` of a slot's cached positions, and the latent
# attention reads those ROWS (token-granular, not page-granular). Scopes
# inside a layer's ``attention``: ``dsa_index`` (the scores), ``dsa_select``
# (the top-k) and, inside ``mla_core``, ``dsa_gather`` (the rows' read).
# ---------------------------------------------------------------------------

def paged_index_choice(q, w, pages, view, k: int, scales=None):
    """An indexer's choice at a paged decode step. q [B, S, Hi, Di] and
    w [B, S, Hi] (tpudl.models.llama.Indexer) score every logical
    position of each slot's table in the pool of indexer keys ``pages``
    ([NP, ps, Di], this step's keys written); of the positions a query
    may see, ``[start, lens + j]``, the ``k`` best are chosen, exactly
    (``jax.lax.top_k``: a tie goes to the lower position). ->
    ``(chosen, live)``: int32 [B, S, k] logical positions, in no order,
    padded with positions the query may NOT see where it sees fewer
    than ``k`` (the attention masks by position), and how many it sees,
    int32 [B, S]. ``chosen`` is None where a table holds no more than
    ``k`` positions: every live one is attended, by the path that was
    there."""
    from tpudl.models.llama import index_scores
    from tpudl.models.paged import paged_attend_mask, paged_gather

    mask = paged_attend_mask(view, chunk=q.shape[1])[:, 0]
    live = mask.sum(-1, dtype=jnp.int32)
    if view.logical_len <= k:
        return None, live
    with jax.named_scope("dsa_index"):
        keys = paged_gather(pages, scales, view, q.dtype)
        # A pool of another width than whole lanes is held folded.
        keys = keys.reshape(keys.shape[0], view.logical_len, -1)
        scores = jnp.where(mask, index_scores(q, w, keys), -jnp.inf)
    with jax.named_scope("dsa_select"):
        return jax.lax.top_k(scores, k)[1].astype(jnp.int32), live


def chosen_latent_rows(query, pages, view, rank, scale, chosen, scales=None):
    """``paged_latent_attention`` over the ``chosen`` [B, S, k] logical
    positions of each slot alone: their rows are gathered out of the
    pool through the page table (a held row each; of a folded pool the
    row that holds the position, whose other lane blocks are masked),
    dequantised where the pool is int8, and attended as
    ``attend_latent_rows`` attends a gathered table, every (slot,
    query) a batch row of its own. A chosen position the query may not
    see (``paged_index_choice``'s padding) is masked. Scope:
    ``mla_core`` > ``dsa_gather``."""
    from tpudl.models.llama import attend_latent_rows
    from tpudl.models.paged import held_fold

    view.took.append(False)
    b, s, h, c = query.shape
    k, ps = chosen.shape[-1], view.page_size
    fold = held_fold(pages, ps)
    with jax.named_scope(LATENT_SCOPE):
        with jax.named_scope("dsa_gather"):
            page = jnp.take_along_axis(
                view.page_table, (chosen // ps).reshape(b, s * k), axis=1
            ).reshape(b, s, k)
            off = chosen % ps
            rows = pages[page, off // fold]
            if scales is not None:
                rows = rows.astype(jnp.float32) * scales[page, off][..., None]
            rows = rows.astype(query.dtype).reshape(b * s, k, -1)
        upper = view.lens[:, None] + jnp.arange(s, dtype=view.lens.dtype)
        seen = (chosen >= view.start[:, None, None]) & (
            chosen <= upper[:, :, None]
        )
        block = jnp.arange(fold, dtype=off.dtype)[None, :, None]
        mask = (
            seen.reshape(b * s, 1, k)
            & (off.reshape(b * s, 1, k) % fold == block)
        )[:, :, None]
        u = attend_latent_rows(
            query.reshape(b * s, 1, h, c), rows, mask, scale, rank
        )
        return u.reshape(b, s, h, rank)
