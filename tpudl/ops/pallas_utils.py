"""Shared helpers for the tpudl Pallas TPU kernels.

The cell-seeding + threshold recipe here is a forward/backward
bit-exactness CONTRACT: fused_attention and softmax_dropout regenerate
their dropout masks in the backward pass by reseeding with exactly this
scheme — any change must keep both passes (and both modules) in lockstep,
which is why there is one copy.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.obs.spans import startup_span


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def kernel_trace(kernel: str):
    """Around a serving kernel's ``pl.pallas_call(...)(...)``: that
    call traces the kernel's Python body, on the host, in every process
    and whatever the compile cache holds, and the start-up span
    ``kernel.trace`` (attr ``kernel``) says for how long. It runs while
    a program is being traced, never in a compiled step."""
    return startup_span("kernel.trace", kernel=kernel)


def kv_valid(kvm_ref, shape):
    """Boolean ``shape`` = [Sq, Skv] tile from a (1, 1, Skv) f32
    kv-validity block (> 0 = attend). The f32 row is broadcast first
    and compared after: a [1, Skv] PREDICATE broadcast along sublanes
    costs Mosaic minutes of compile and, at Skv = 512, the scoped VMEM
    (the compiler refused the kernel); the f32 sublane broadcast is
    free."""
    return jnp.broadcast_to(kvm_ref[0, 0:1, :], shape) > 0.0


def seed_cell(seed_ref, cell) -> None:
    """Seed the TPU PRNG with a distinct stream per grid cell: prng_seed
    takes at most two 32-bit words, so the flattened cell id folds into
    them arithmetically — distinct cells get distinct (s0, s1) pairs for
    any key."""
    s0 = seed_ref[0] + cell.astype(jnp.uint32)
    s1 = seed_ref[1] ^ (cell.astype(jnp.uint32) * jnp.uint32(2654435761))
    pltpu.prng_seed(s0, s1)


def flat_cell_id(grid_rank: int):
    """Row-major flattened id of the current grid cell."""
    cell = pl.program_id(0)
    for axis in range(1, grid_rank):
        cell = cell * pl.num_programs(axis) + pl.program_id(axis)
    return cell


def keep_mask(shape, rate: float):
    """In-kernel dropout keep-mask from the hardware PRNG (True = keep
    with probability 1 - rate). prng_random_bits yields int32 on TPU —
    reinterpret as uint32 or the threshold compare drops ~55% instead of
    ``rate``."""
    threshold = jnp.uint32(round(rate * (2.0 ** 32)))
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits >= threshold
