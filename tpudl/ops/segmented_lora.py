"""Segmented LoRA: heterogeneous-adapter batched matmul over page pools.

Multi-tenant adapter serving (S-LoRA's scenario family on tpudl's paged
substrate) hits one compute problem the fused-ops tier does not cover:
every decode dispatch carries ``num_slots`` requests whose LoRA factors
are DIFFERENT per slot — a different tenant's fine-tune in every row.
Materializing each slot's ``[in, r] @ [r, out]`` delta as dense weights
would re-create the full-matrix bytes LoRA exists to avoid; batching
the base matmul but looping adapters host-side would pay one dispatch
per TENANT instead of one per step.

This kernel computes the whole ragged batch in ONE dispatch:

    delta[b] = scale[b] * (x[b] @ A_{t(b)}) @ B_{t(b)}

where the A/B factors live in fixed-size PAGE POOLS — one page holds
one rank unit (one column of A and the matching row of B) — and each
slot's ``table[b]`` row maps its logical rank indices to physical pool
pages (tpudl.serve.lora.AdapterPool owns the pools and the tables, the
exact shape of the PR-8 paged-KV addressing contract: the table is a
small traced input, so adapter load/evict never recompiles anything).
The gather happens INSIDE the kernel: unmapped table entries point at
physical page 0, which is never written and stays all-zero, so a
tenant of rank ``r < r_max`` (or a slot with no tenant at all)
contributes exactly zero through its unused pages — rank raggedness
needs no mask. Accumulation is f32 regardless of the pool dtype;
``int8`` pools carry one f32 dequant scale per page applied to the
gathered rows (the tpudl.quant symmetric contract at page granularity).

Dispatch seam (the tpudl.ops ``impl=`` contract, norms.resolve_impl's
rule): ``"reference"`` is the XLA composite — gather the pages with a
take, contract with two f32 einsums — and the parity baseline;
``"fused"`` is the Pallas kernel (compiled on TPU, interpret mode
elsewhere — the CPU test mode); ``"auto"`` picks fused on TPU. The two
differ only in f32 reduction order; tests/test_tenant_lora.py holds
them (and the sequential one-adapter-at-a-time
merged reference) at EXACT token parity for f32 pools and
teacher-forced logit-margin parity for int8 pools. Inference-only: no
custom VJP (adapters train per-tenant offline; serving only reads
them).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.norms import resolve_impl
from tpudl.ops.pallas_utils import round_up

#: Rows of an int8 array in one (32, 128) TPU tile.
_INT8_ROWS = 32


def _as_3d(x):
    """[B, H] -> [B, 1, H]; [B, S, H] passes through."""
    if x.ndim == 2:
        return x[:, None, :], True
    if x.ndim == 3:
        return x, False
    raise ValueError(
        f"segmented_lora takes [B, H] or [B, S, H] activations, got "
        f"shape {x.shape}"
    )


def segmented_lora_ref(x, pools, table, scale):
    """XLA composite reference: gather each slot's pages, contract in
    f32. ``pools`` is one site's pool dict (``{"a": [NP, in],
    "b": [NP, out]}`` + ``a_scale``/``b_scale`` f32 ``[NP]`` rows for
    int8 storage); ``table`` [B, P] int32 maps logical rank units to
    physical pages (0 = the all-zero trash page); ``scale`` [B] f32 is
    each slot's alpha/rank (0 for slots with no adapter)."""
    x3, squeeze = _as_3d(x)
    table = jnp.asarray(table, jnp.int32)
    scale = jnp.asarray(scale, jnp.float32)
    a = pools["a"][table].astype(jnp.float32)  # [B, P, in]
    b = pools["b"][table].astype(jnp.float32)  # [B, P, out]
    if "a_scale" in pools:
        a = a * pools["a_scale"][table][..., None]
        b = b * pools["b_scale"][table][..., None]
    coef = jnp.einsum(
        "bsh,bph->bsp", x3.astype(jnp.float32), a,
        preferred_element_type=jnp.float32,
    )
    delta = jnp.einsum(
        "bsp,bpo->bso", coef, b, preferred_element_type=jnp.float32,
    )
    delta = (delta * scale[:, None, None]).astype(x.dtype)
    return delta[:, 0, :] if squeeze else delta


def _seg_lora_kernel(
    x_ref, a_ref, b_ref, t_ref, sc_ref, *rest, pages: int, quantized: bool
):
    """One slot: gather its pages and accumulate ``pages`` rank-1
    updates in f32. The page loop is a static unroll (r_max is small —
    it is the rank budget, not the batch); page 0 rows are all-zero by
    the pool contract, so short ranks and empty slots fall out free.
    The table and the scales sit whole in SMEM; the slot indexes them."""
    if quantized:
        asc_ref, bsc_ref, out_ref = rest
    else:
        (out_ref,) = rest
    slot = pl.program_id(0)
    x = x_ref[0].astype(jnp.float32)  # [S_pad, H_pad]
    acc = jnp.zeros(out_ref.shape[1:], jnp.float32)  # [S_pad, O_pad]
    for j in range(pages):
        page = t_ref[slot, j]
        a_row = _pool_row(a_ref, page, quantized)  # [1, H_pad]
        b_row = _pool_row(b_ref, page, quantized)  # [1, O_pad]
        if quantized:
            a_row = a_row * asc_ref[page]
            b_row = b_row * bsc_ref[page]
        coef = jnp.sum(x * a_row, axis=-1, keepdims=True)
        acc = acc + coef * b_row
    out_ref[0] = (acc * sc_ref[slot]).astype(out_ref.dtype)


def _pool_row(pool_ref, page, quantized: bool):
    """Row ``page`` of a VMEM-resident pool as f32 ``[1, W]``. A 32-bit
    pool reads the row at its dynamic sublane offset. An int8 pool
    packs 32 rows to a tile and Mosaic takes no dynamic offset inside
    one, so it loads the aligned 32-row tile and selects the row."""
    if not quantized:
        return pool_ref[pl.ds(page, 1), :].astype(jnp.float32)
    base = pl.multiple_of((page // _INT8_ROWS) * _INT8_ROWS, _INT8_ROWS)
    tile = pool_ref[pl.ds(base, _INT8_ROWS), :].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.sum(
        jnp.where(rows == page - base, tile, 0.0), axis=0, keepdims=True
    )


def _pad_rows(arr, rows: int, cols: Optional[int] = None):
    pad = [(0, rows - arr.shape[0])]
    if cols is not None:
        pad.append((0, cols - arr.shape[1]))
    return jnp.pad(arr, pad)


def segmented_lora_fused(x, pools, table, scale, interpret: bool):
    """The Pallas path: grid over slots, table/scales in SMEM, pools
    VMEM-resident (adapter pools are rank-units, orders of magnitude
    smaller than the weights they adapt — they fit on-chip at every
    geometry this repo serves)."""
    x3, squeeze = _as_3d(x)
    b_dim, s, h = x3.shape
    table = jnp.asarray(table, jnp.int32)
    scale = jnp.asarray(scale, jnp.float32)
    pages = int(table.shape[1])
    quantized = "a_scale" in pools
    o = int(pools["b"].shape[1])
    np_rows = int(pools["a"].shape[0])

    h_pad = round_up(h, 128)
    o_pad = round_up(o, 128)
    s_pad = round_up(s, 8)
    # int8 pools tile at (32, 128); f32 at (8, 128).
    np_pad = round_up(np_rows, _INT8_ROWS if quantized else 8)

    xp = jnp.pad(x3, ((0, 0), (0, s_pad - s), (0, h_pad - h)))
    ap = _pad_rows(pools["a"], np_pad, h_pad)
    bp = _pad_rows(pools["b"], np_pad, o_pad)

    x_spec = pl.BlockSpec(
        (1, s_pad, h_pad), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    pool_a_spec = pl.BlockSpec(
        (np_pad, h_pad), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    pool_b_spec = pl.BlockSpec(
        (np_pad, o_pad), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    # Scalars ride whole in SMEM (no block shape): a (1, pages) block of
    # the table is neither (8, 128)-aligned nor the whole array, which
    # the TPU lowering refuses.
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [x_spec, pool_a_spec, pool_b_spec, smem, smem]
    args = [xp, ap, bp, table, scale]
    if quantized:
        in_specs += [smem, smem]
        args += [
            _pad_rows(pools["a_scale"], np_pad),
            _pad_rows(pools["b_scale"], np_pad),
        ]
    out = pl.pallas_call(
        functools.partial(
            _seg_lora_kernel, pages=pages, quantized=quantized
        ),
        grid=(b_dim,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, s_pad, o_pad), lambda i: (i, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b_dim, s_pad, o_pad), x.dtype),
        interpret=interpret,
    )(*args)
    out = out[:, :s, :o]
    return out[:, 0, :] if squeeze else out


def segmented_lora(
    x,
    pools,
    table,
    scale,
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
):
    """``delta[b] = scale[b] * (x[b] @ A_pages(table[b])) @
    B_pages(table[b])`` — the heterogeneous-adapter batched LoRA delta
    for one projection site. Returns ``x.dtype``, shape ``[B, S, out]``
    (or ``[B, out]`` for 2-D ``x``); callers add it onto the base
    projection's output. See the module docstring for the pool/table
    contract and the ``impl`` seam."""
    if set(pools) not in ({"a", "b"}, {"a", "b", "a_scale", "b_scale"}):
        raise ValueError(
            f"pool dict must hold a/b (+ a_scale/b_scale when int8), "
            f"got keys {sorted(pools)}"
        )
    use_fused, interpret = resolve_impl(impl, interpret)
    if use_fused:
        return segmented_lora_fused(x, pools, table, scale, interpret)
    return segmented_lora_ref(x, pools, table, scale)
