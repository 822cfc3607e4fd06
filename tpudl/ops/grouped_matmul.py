"""The sorted experts' grouped matmul as one Pallas kernel.

``DroplessMoE``'s ``"sorted"`` form (tpudl.ops.moe) multiplies rows
that lie sorted by expert with that expert's matrix: ``out[r] = lhs[r]
@ rhs[g]`` for the rows ``r`` of group ``g``, the groups contiguous and
in order, rows behind the last group no group's. ``jax.lax.ragged_dot``
does that at a quarter of the chip's roofline at the served shapes
(64 experts of ``[3584, 1024]``, 128-256 rows a group: 2.35 ms a call
for 470 MB of weights; PERF.md, PR 38 and PR 42). The kernel here walks
the row tiles:

- the walk is made outside, from the group sizes alone (``_walk``): one
  VISIT a (row tile, group that has rows in it), in row order, so a tile
  that a group boundary cuts is visited once a group and all visits of
  a group are consecutive. The visit's tile and group, and a group's
  row span, are scalar-prefetched into SMEM;
- a visit multiplies the whole ``[tm, k]`` tile of rows with the group's
  ``[k, tn]`` matrix on the matrix unit, float32 accumulation over the
  whole ``k`` in one product, and stores the rows that are the group's
  (the others keep what an earlier visit of the tile stored);
- the matrices stay in HBM (``memory_space=pl.ANY``) and are streamed
  by the kernel itself into a double-buffered VMEM block, one copy a
  group and not one a visit: at a group's first visit the NEXT group
  that has rows is put in flight, so its matrix arrives while this
  group's tiles are multiplied, however many they are. (A ``BlockSpec``
  would fetch a step ahead only: the 7.3 MB of a matrix against ONE
  tile's product.) An empty group costs nothing, rows behind the last
  group are never visited and their output is never written.

The rows leave sorted order in the kernel too (``rows_to``, PR 45). The
layer sums a token's ``k`` results, so each sorted row's result has to
go back to its assignment's place ``token * k + choice``, and outside
the kernel that was a float32 gather of every row, held or not. Given
the sort's index the down projection's call leaves its result in HBM,
one row a LEADING index (``[m, 1, n]``: a row is contiguous there, as
it is in the ``[2, tm, 1, tn]`` VMEM scratch a visit's product is laid
into; a one-row slice of an ``(8, 128)``-tiled block is not a copy the
compiler takes), and a visit copies each row it owns to
``out[rows_to[row]]``, its column tile's slice: the copies of a visit
start together and are waited for two visits later, so the next
product overlaps them. What moves grows with the rows the groups
cover: a row behind the last group (an assignment of an expert held
elsewhere) is never written, and ``sum_choices``, a second small
kernel, selects its place away while it sums a token's ``k`` rows in
one pass, in the order the layer's sum has always had on the chip. The
layer asks for both wherever it takes the kernel: on the chip it leads
XLA's gather at every served shape, Laguna's 1.4 us visits included
(PERF.md, PR 45).

bfloat16 operands, float32 accumulation, the result rounded once to the
type asked for: ``ragged_dot``'s arithmetic. ``grouped_kernel_ok`` says
where the layer takes the kernel, from what a traced program can
observe; there is no knob. Inference only (no gradient is defined).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.attention import is_tpu_backend
from tpudl.ops.norms import resolve_impl
from tpudl.ops.pallas_utils import kernel_trace, round_up

#: The kernel's name: its row of a device trace's breakdown.
NAME = "moe_grouped_matmul"
#: Rows of a tile. A group boundary inside a tile costs the tile one
#: more visit, so smaller tiles waste less of the matrix unit on rows
#: that are masked away, and larger ones amortise a step's fixed cost;
#: timed on the chip at 64, 128, 256 and 512 (PERF.md, PR 42).
ROW_TILE = 128
#: The most a streamed block of one matrix may take of VMEM (there are
#: two): a wider matrix is walked in column tiles.
MATRIX_BLOCK_BYTES = 8 << 20
LANES = 128
#: Arrays of the walk (``_walk``), scalar-prefetched ahead of all else.
WALK = 8


def one_device() -> bool:
    """Whether the backend holds ONE device. With several, the experts
    may be committed to a mesh, which a traced layer cannot see, and
    GSPMD would gather them whole to every chip for a custom call."""
    return jax.device_count() == 1


def grouped_kernel_ok(lhs, kernels, scales) -> bool:
    """Whether the sorted form's grouped matmuls run as the kernel, from
    what the program can observe at trace time: a TPU of one device,
    unquantized bfloat16 expert kernels (``_ExpertKernel`` gave no
    scales) over bfloat16 rows, every contracted and produced width of
    whole 128-lane tiles. No shape threshold: on the chip the kernel
    leads ``ragged_dot`` at both served shapes (PERF.md, PR 42)."""
    if not (is_tpu_backend() and one_device()):
        return False
    if any(scale is not None for scale in scales):
        return False
    return all(
        a.dtype == jnp.bfloat16 and a.shape[-1] % LANES == 0
        for a in (lhs, *kernels)
    )


def _column_tile(k: int, n: int, itemsize: int) -> int:
    """The widest tile of ``n`` columns, a whole number of lanes and a
    divisor of ``n``, whose ``[k, tile]`` block fits the budget."""
    lanes = n // LANES
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and (
            k * (n // parts) * itemsize <= MATRIX_BLOCK_BYTES
        ):
            return n // parts
    return LANES


def _walk(sizes, row_tiles: int, tm: int):
    """The visits of the kernel, from the group sizes: per visit its
    group and row tile, per group its row span, its first visit, the
    next group that has rows (-1: none) and the buffer its matrix
    takes, and the number of visits. A visit past that number repeats
    the last one's tile and group, so that it moves no block."""
    groups = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    has_rows = sizes > 0
    first_tile = starts // tm
    tiles = jnp.where(has_rows, (ends - 1) // tm - first_tile + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    first_visit = visit_ends - tiles
    total = visit_ends[-1]
    visit = jnp.minimum(
        jnp.arange(row_tiles + groups - 1, dtype=jnp.int32),
        jnp.maximum(total - 1, 0),
    )
    group_of = jnp.minimum(
        jnp.sum(visit_ends[None, :] <= visit[:, None], axis=1), groups - 1
    ).astype(jnp.int32)
    tile_of = first_tile[group_of] + visit - first_visit[group_of]
    ids = jnp.arange(groups, dtype=jnp.int32)
    # The nearest later group that has rows: a running minimum from
    # the right over the ids of those that do.
    later = jax.lax.cummin(jnp.where(has_rows, ids, groups), reverse=True)
    following = jnp.concatenate([later[1:], jnp.full((1,), groups, jnp.int32)])
    following = jnp.where(following < groups, following, -1)
    buffer_of = (jnp.cumsum(has_rows.astype(jnp.int32)) - 1) % 2
    return (
        group_of, tile_of.astype(jnp.int32), starts, ends, first_visit,
        following.astype(jnp.int32), buffer_of.astype(jnp.int32),
        total.reshape(1),
    )


def _product(walk, column, visit, lhs_ref, rhs_hbm, matrix, sem, tn: int):
    """The visit's ``[tm, tn]`` float32 product, the group's matrix
    streamed as the header says. Called inside ``visit < total``."""
    group_of, _, _, _, first_visit, following, buffer_of, _ = walk
    group = group_of[visit]
    buffer = buffer_of[group]

    def stream(g, buf):
        return pltpu.make_async_copy(
            rhs_hbm.at[g, :, pl.ds(pl.multiple_of(column * tn, LANES), tn)],
            matrix.at[buf], sem.at[buf],
        )

    @pl.when(visit == first_visit[group])
    def _():
        # Only the walk's first group was not put in flight by the
        # group before it.
        @pl.when(visit == 0)
        def _():
            stream(group, buffer).start()

        stream(group, buffer).wait()
        nxt = following[group]

        @pl.when(nxt >= 0)
        def _():
            stream(nxt, 1 - buffer).start()

    return jnp.dot(
        lhs_ref[...], matrix[buffer], preferred_element_type=jnp.float32
    )


def _own_rows(walk, visit, tm: int):
    """The sorted rows ``[lo, hi)`` of the visit's tile that are its
    group's."""
    group_of, tile_of, starts, ends = walk[:4]
    group, first = group_of[visit], tile_of[visit] * tm
    return (
        jnp.maximum(starts[group], first),
        jnp.minimum(ends[group], first + tm),
    )


def _kernel(*refs, tm: int, tn: int):
    """One visit: ``lhs_ref`` ``[tm, k]`` the visit's row tile,
    ``rhs_hbm`` ``[groups, k, n]`` in HBM, ``out_ref`` ``[tm, tn]`` the
    tile's output block (resident while consecutive visits name it),
    ``matrix`` ``[2, k, tn]``, ``sem`` one DMA semaphore a buffer."""
    walk, (lhs_ref, rhs_hbm, out_ref, matrix, sem) = refs[:WALK], refs[WALK:]
    _, tile_of, *_, total = walk
    column, visit = pl.program_id(0), pl.program_id(1)

    @pl.when(visit < total[0])
    def _():
        product = _product(
            walk, column, visit, lhs_ref, rhs_hbm, matrix, sem, tn
        ).astype(out_ref.dtype)
        lo, hi = _own_rows(walk, visit, tm)
        row = tile_of[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0
        )
        out_ref[...] = jnp.where(
            (row >= lo) & (row < hi), product, out_ref[...]
        )


def _kernel_by_index(*refs, tm: int, tn: int):
    """One visit whose rows go to the places ``rows_to`` names:
    ``rows_to`` ``[rows]`` int32 in SMEM (scalar-prefetched behind the
    walk), ``out_hbm`` ``[m, 1, n]`` in HBM, ``result`` ``[2, tm, 1,
    tn]`` the products of this visit and the last, a row a leading
    index, ``row_sem`` one DMA semaphore a result block. A row the
    visit owns is copied from its result block to
    ``out_hbm[rows_to[row], 0, column tile]``; the copies
    of a visit start together and are waited for two visits later,
    before their block is written again (and at the walk's end), so the
    next visit's product overlaps them."""
    walk, refs = refs[:WALK], refs[WALK:]
    rows_to, lhs_ref, rhs_hbm, out_hbm, matrix, sem, result, row_sem = refs
    _, tile_of, *_, total = walk
    column, visit = pl.program_id(0), pl.program_id(1)
    total = total[0]
    columns = pl.ds(pl.multiple_of(column * tn, LANES), tn)

    def row_copy(slot, at, to):
        return pltpu.make_async_copy(
            result.at[slot, at], out_hbm.at[to, :, columns], row_sem.at[slot],
        )

    def land(of):
        """Wait for the row copies that visit ``of`` started."""
        lo, hi = _own_rows(walk, of, tm)

        def wait(_, c):
            # Every row copy signals the same count: any descriptor of
            # the block's semaphore waits for one of them.
            row_copy(of % 2, 0, 0).wait()
            return c

        jax.lax.fori_loop(lo, hi, wait, 0)

    @pl.when(visit < total)
    def _():
        slot = visit % 2
        product = _product(
            walk, column, visit, lhs_ref, rhs_hbm, matrix, sem, tn
        )

        @pl.when(visit >= 2)
        def _():
            land(visit - 2)

        result[slot] = product.astype(result.dtype).reshape(tm, 1, tn)
        lo, hi = _own_rows(walk, visit, tm)
        first = tile_of[visit] * tm

        def start(row, c):
            row_copy(slot, row - first, rows_to[row]).start()
            return c

        jax.lax.fori_loop(lo, hi, start, 0)

        @pl.when(visit == total - 1)
        def _():
            @pl.when(visit >= 1)
            def _():
                land(visit - 1)

            land(visit)


@functools.partial(
    jax.jit,
    static_argnames=("preferred_element_type", "row_tile", "interpret"),
)
def grouped_matmul(
    lhs, rhs, group_sizes, preferred_element_type=None, *, rows_to=None,
    row_tile: Optional[int] = None, interpret: Optional[bool] = None,
):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` through the kernel:
    ``lhs`` ``[m, k]`` sorted by group, ``rhs`` ``[groups, k, n]``,
    ``group_sizes`` int ``[groups]`` (their sum at most ``m``). Returns
    ``[m, n]`` in ``preferred_element_type`` (default: ``lhs``'s type);
    the rows behind the last group are NOT written and hold whatever
    the buffer held (``ragged_dot`` leaves zeros there: the caller
    leaves those rows out either way). With ``rows_to`` (int ``[m]``,
    no place named twice) what is returned is ``[m, 1, n]`` and sorted
    row ``r``'s result is its row ``rows_to[r]``; the places of the
    rows behind the last group are the ones not written. Jitted on its own
    so that the layers of a program, and gate and up of a layer, share
    one lowered function (as the paged kernels do); compiled on a TPU,
    interpret mode elsewhere (the CPU test mode)."""
    m, k = lhs.shape
    groups, _, n = rhs.shape
    out_dtype = jnp.dtype(preferred_element_type or lhs.dtype)
    _, interpret = resolve_impl("fused", interpret)
    tm = row_tile or ROW_TILE
    rows = round_up(m, tm)
    if rows != m:
        lhs = jnp.pad(lhs, ((0, rows - m), (0, 0)))
    tn = _column_tile(k, n, rhs.dtype.itemsize)
    walk = _walk(group_sizes, rows // tm, tm)
    by_index = rows_to is not None
    prefetch = walk + ((rows_to.astype(jnp.int32),) if by_index else ())
    resident = (
        2 * k * tn * rhs.dtype.itemsize
        + 2 * tm * k * lhs.dtype.itemsize
        + 2 * tm * tn * out_dtype.itemsize
        + 2 * tm * tn * 4  # the product before it is rounded, the mask
    )
    scratch = [
        pltpu.VMEM((2, k, tn), rhs.dtype), pltpu.SemaphoreType.DMA((2,)),
    ]
    if by_index:
        # The result stays in HBM and takes its rows by copy, from two
        # blocks of products in VMEM.
        out_spec = pl.BlockSpec(memory_space=pl.ANY)
        scratch += [
            pltpu.VMEM((2, tm, 1, tn), out_dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
    else:
        out_spec = pl.BlockSpec((tm, tn), lambda j, v, g, tile, *_: (tile[v], j))
    with jax.named_scope(NAME), kernel_trace(NAME):
        out = pl.pallas_call(
            functools.partial(
                _kernel_by_index if by_index else _kernel, tm=tm, tn=tn
            ),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(n // tn, rows // tm + groups - 1),
                in_specs=[
                    pl.BlockSpec((tm, k), lambda j, v, g, tile, *_: (tile[v], 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=out_spec,
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct(
                (m, 1, n) if by_index else (rows, n), out_dtype
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=resident + (16 << 20),
            ),
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n, transcendentals=0,
                bytes_accessed=(
                    rhs.size * rhs.dtype.itemsize
                    + (n // tn) * lhs.size * lhs.dtype.itemsize
                    + m * n * out_dtype.itemsize
                ),
            ),
            interpret=interpret,
            name=NAME,
        )(*prefetch, lhs, rhs)
    if by_index or rows == m:
        return out
    return out[:m]


#: Tokens of a step of ``sum_choices``: with 8 choices of 6,144 columns
#: a 3 MB block of rows, double-buffered.
SUM_TOKENS = 16
SUM_NAME = "moe_sum_choices"


def _sum_kernel(held, rows_ref, out_ref, *, k: int, tokens: int):
    """One step: ``rows_ref`` ``[tokens * k, 1, n]`` the rows of
    ``tokens`` tokens as the indexed call left them, ``held`` int32 in
    SMEM, one an assignment; ``out_ref`` ``[tokens, 1, n]``."""
    first = pl.program_id(0) * tokens * k
    zero = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    def one_token(t, carry):
        # A select, not a product: a row nobody wrote may hold NaN.
        terms = [
            jax.lax.select(
                held[first + t * k + c] != 0,
                rows_ref[t * k + c], zero,
            )
            for c in range(k)
        ]
        n = k
        while n > 1 and n % 2 == 0:
            n //= 2
            terms = [terms[i] + terms[i + n] for i in range(n)]
        out_ref[t] = functools.reduce(jnp.add, terms)
        return carry

    # A loop, not ``tokens`` copies of its body: a traced program pays
    # for every operation of a kernel's body on the host, at set-up.
    jax.lax.fori_loop(0, tokens, one_token, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sum_choices(rows, held, *, interpret: Optional[bool] = None):
    """A token's ``k`` rows summed in float32: ``rows`` ``[T * k, 1,
    n]`` as ``grouped_matmul(..., rows_to=)`` returns them, ``held``
    bool ``[T, k]`` the assignments that have a row (the others' places
    were never written and are selected away). Returns ``[T, n]``
    float32.

    The order is the one the chip's own reduce takes over the ``k``
    sublanes of a ``[T, k, n]`` tile, which is what summed these rows
    while XLA gathered them (read from the compiler's LLO: ``vrot.slane
    4, vadd, vrot.slane 2, vadd, vrot.slane 1, vadd``): halves folded
    onto each other, ``((c0 + c4) + (c2 + c6)) + ((c1 + c5) + (c3 +
    c7))`` at ``k`` = 8, ``(c0 + c2) + (c1 + c3)`` at 4. XLA's reduce
    over a LEADING axis, which ``k`` is here, runs another order, and
    its slices of one operand do not fuse; a kernel of one pass keeps
    the layer's bits and reads each row once."""
    places, _, n = rows.shape
    tokens, k = held.shape
    _, interpret = resolve_impl("fused", interpret)
    steps = pl.cdiv(tokens, SUM_TOKENS)
    held = jnp.pad(
        held.reshape(-1).astype(jnp.int32),
        (0, steps * SUM_TOKENS * k - places),
    )
    with jax.named_scope(SUM_NAME), kernel_trace(SUM_NAME):
        out = pl.pallas_call(
            functools.partial(_sum_kernel, k=k, tokens=SUM_TOKENS),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(steps,),
                in_specs=[pl.BlockSpec(
                    (SUM_TOKENS * k, 1, n), lambda i, held: (i, 0, 0)
                )],
                out_specs=pl.BlockSpec(
                    (SUM_TOKENS, 1, n), lambda i, held: (i, 0, 0)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct((tokens, 1, n), rows.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=(
                    2 * SUM_TOKENS * (k + 1) * n * rows.dtype.itemsize
                    + (16 << 20)
                ),
            ),
            cost_estimate=pl.CostEstimate(
                flops=places * n, transcendentals=0,
                bytes_accessed=(places + tokens) * n * rows.dtype.itemsize,
            ),
            interpret=interpret,
            name=SUM_NAME,
        )(held, rows)
    return out.reshape(tokens, n)
