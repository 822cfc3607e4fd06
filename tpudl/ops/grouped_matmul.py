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
from tpudl.ops.pallas_utils import round_up

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


def _kernel(
    group_of, tile_of, starts, ends, first_visit, following, buffer_of,
    total,  # scalar prefetch
    lhs_ref, rhs_hbm,
    out_ref,
    matrix, sem,
    *, tm: int, tn: int,
):
    """One visit: ``lhs_ref`` ``[tm, k]`` the visit's row tile,
    ``rhs_hbm`` ``[groups, k, n]`` in HBM, ``out_ref`` ``[tm, tn]`` the
    tile's output block (resident while consecutive visits name it),
    ``matrix`` ``[2, k, tn]``, ``sem`` one DMA semaphore a buffer."""
    column = pl.program_id(0)
    visit = pl.program_id(1)
    group = group_of[visit]
    buffer = buffer_of[group]

    def stream(g, buf):
        return pltpu.make_async_copy(
            rhs_hbm.at[g, :, pl.ds(pl.multiple_of(column * tn, LANES), tn)],
            matrix.at[buf], sem.at[buf],
        )

    @pl.when(visit < total[0])
    def _():
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

        product = jnp.dot(
            lhs_ref[...], matrix[buffer], preferred_element_type=jnp.float32
        ).astype(out_ref.dtype)
        row = tile_of[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0
        )
        own = (row >= starts[group]) & (row < ends[group])
        out_ref[...] = jnp.where(own, product, out_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("preferred_element_type", "row_tile", "interpret"),
)
def grouped_matmul(
    lhs, rhs, group_sizes, preferred_element_type=None, *,
    row_tile: Optional[int] = None, interpret: Optional[bool] = None,
):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` through the kernel:
    ``lhs`` ``[m, k]`` sorted by group, ``rhs`` ``[groups, k, n]``,
    ``group_sizes`` int ``[groups]`` (their sum at most ``m``). Returns
    ``[m, n]`` in ``preferred_element_type`` (default: ``lhs``'s type);
    the rows behind the last group are NOT written and hold whatever
    the buffer held (``ragged_dot`` leaves zeros there: the caller
    leaves those rows out either way). Jitted on its own so that the
    layers of a program, and gate and up of a layer, share one lowered
    function (as the paged kernels do); compiled on a TPU, interpret
    mode elsewhere (the CPU test mode)."""
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
    resident = (
        2 * k * tn * rhs.dtype.itemsize
        + 2 * tm * k * lhs.dtype.itemsize
        + 2 * tm * tn * out_dtype.itemsize
        + 2 * tm * tn * 4  # the product before it is rounded, the mask
    )
    with jax.named_scope(NAME):
        out = pl.pallas_call(
            functools.partial(_kernel, tm=tm, tn=tn),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(walk),
                grid=(n // tn, rows // tm + groups - 1),
                in_specs=[
                    pl.BlockSpec((tm, k), lambda j, v, g, tile, *_: (tile[v], 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec(
                    (tm, tn), lambda j, v, g, tile, *_: (tile[v], j)
                ),
                scratch_shapes=[
                    pltpu.VMEM((2, k, tn), rhs.dtype),
                    pltpu.SemaphoreType.DMA((2,)),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
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
        )(*walk, lhs, rhs)
    return out[:m] if rows != m else out
