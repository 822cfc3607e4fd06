"""Paged decode attention that reads the live pages where they lie.

The paged decode branch (tpudl.models.llama.LlamaAttention) used to
make a dense logical view ``[B, P * ps, Hkv, D]`` of the k and the v
pool twice a layer (tpudl.models.paged.paged_gather) and attend it
under a mask: every slot's whole table, whatever was live. With 5-13 %
of the gathered positions live that was 40 % of a decode step's device
time (PERF.md, PR 26). This kernel visits, for slot ``b``, only the
pages that cover its logical positions ``[start[b], lens[b] + S - 1]``:

- the page table, ``start`` and ``lens`` are scalar-prefetched into
  SMEM; the two pools stay in HBM (``memory_space=pl.ANY``) and are
  read in place, one ``make_async_copy`` a live page into a
  double-buffered VMEM block of ``PAGES_PER_BLOCK`` pages, the next
  block (of this slot, or the first of the next) in flight while this
  one is attended;
- a running (flash-style) softmax over the blocks: logits and
  statistics in float32, ``p . v`` in the pool's dtype with float32
  accumulation, the division once at the end;
- nothing of shape ``[B, P * ps, ...]`` is made; an idle slot (lens 0
  on the trash page) costs one page.

The pool keeps its shape ``[NP, ps, Hkv, D]``: a page is ``ps * Hkv``
contiguous rows of ``D`` lanes, so the kernel takes the pool as
``[NP, ps * Hkv, D]`` (a bitcast for XLA, no copy) and a block as a
matrix ``[PAGES_PER_BLOCK * ps * Hkv, D]`` whose row ``t * Hkv + h`` is
head ``h`` of position ``t``. All ``S * H`` query rows of a slot meet
every row of the block in ONE matmul, and a query row keeps only the
columns of its own KV head (and of its own positions) under the mask:
that costs the matrix unit nothing it would not pay anyway (the keys
are its stationary operand either way) and needs no strided read of a
head out of the page.

Dispatch seam (tpudl.ops.norms.resolve_impl's rule): ``"reference"`` is
the gather and ``_gqa_decode_attention``, left as they were;
``"fused"`` is this kernel (compiled on the TPU, interpret mode
elsewhere: the CPU test mode); ``"auto"`` is what the model calls, and
chooses by what the program can observe (``in_place_ok``): the kernel
on a TPU for a k / v pool pair with a head axis, not quantized, on one
device, ``head_dim`` a multiple of 128; the gather for everything else
(int8 pools, whose dequantisation is fused into the gather; a pool
committed to a mesh, which GSPMD would gather whole to every chip for
a custom call; any CPU run). There is no knob. Inference only.
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
from tpudl.ops.pallas_utils import round_up

#: Pages fetched into one VMEM block. A page of Mistral's pool is
#: 32 KB a pool: per-page compute is too small to hide a DMA, so a
#: block is 8 pages (128 positions; 1 MB of VMEM for both pools, double
#: buffered). Measured on the chip against 2, 4, 16 and 32 on the
#: serving cells' patterns (PERF.md, PR 27).
PAGES_PER_BLOCK = 8
#: The scope the kernel's operations sit under (inside the layer's
#: ``attention``). ``kv_gather`` keeps meaning the gather.
SCOPE = "paged_attention"


def in_place_ok(q, pages, view) -> bool:
    """Whether the kernel can serve this layer, from what the program
    can observe at trace time: the view's static facts and the shapes.
    ``pages`` is the k pool (v is its twin)."""
    if view.quantized or view.sharded or pages.ndim != 4:
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


def paged_attention_ref(q, pages_k, pages_v, view, scale_k=None, scale_v=None):
    """Today's path: every slot's logical view gathered dense out of
    both pools, then grouped-query attention under the mask."""
    from tpudl.models.llama import _gqa_decode_attention
    from tpudl.models.paged import paged_attend_mask, paged_gather

    kf = paged_gather(pages_k, scale_k, view, q.dtype)
    vf = paged_gather(pages_v, scale_v, view, q.dtype)
    return _gqa_decode_attention(
        q, kf, vf, paged_attend_mask(view, chunk=q.shape[1])
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
    with jax.named_scope(SCOPE):
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


def paged_attention_fused(q, pages_k, pages_v, view, interpret: bool):
    """The Pallas path. ``q`` [B, S, H, D]; ``pages_k`` / ``pages_v``
    [NP, ps, Hkv, D]; returns [B, S, H, D] in ``q.dtype``."""
    if not in_place_ok(q, pages_k, view):
        raise ValueError(
            "the paged-attention kernel reads an unquantized k / v pool "
            "pair [NP, ps, Hkv, D] on one device with D a multiple of "
            f"128; got q {q.shape}, pool {pages_k.shape} "
            f"{pages_k.dtype}, quantized={view.quantized}, "
            f"sharded={view.sharded}"
        )
    return _fused(
        q, pages_k, pages_v, view.page_table, view.start, view.lens,
        interpret=interpret,
    )


def paged_attention(
    q, pages_k, pages_v, view, *,
    scale_k=None, scale_v=None,
    impl: str = "auto", interpret: Optional[bool] = None,
):
    """Attention of ``q`` [B, S, H, D] over each slot's logical
    positions ``[start, lens + j]`` (query ``j`` of the chunk) of the
    paged pools, ``paged_write`` having put this step's rows there.
    Returns [B, S, H, D]. ``view`` records which path the layer took
    (``PagedView.took``). See the module docstring for the seam."""
    if impl == "auto":
        impl = (
            "fused"
            if is_tpu_backend() and in_place_ok(q, pages_k, view)
            else "reference"
        )
    fused, interpret = resolve_impl(impl, interpret)
    view.took.append(fused)
    if fused:
        return paged_attention_fused(q, pages_k, pages_v, view, interpret)
    return paged_attention_ref(q, pages_k, pages_v, view, scale_k, scale_v)
