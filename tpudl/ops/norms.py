"""Fused LayerNorm / RMSNorm (+residual-add) Pallas TPU kernels.

The BERT-base MFU plateau of the early rounds (BASELINE.md; well under
BERT-large on the identical pipeline) is per-op memory traffic: at
hidden 768 the matmuls are too small to hide the epilogue, and every
``LayerNorm(hidden + out)`` is two extra full HBM round-trips over the
activation (write the sum, read it back, write the normed value) plus
f32 statistics passes. These kernels read the activation ONCE, do the
residual add and the f32 statistics in VMEM, and write the normed value
(plus, for the residual form, the summed value the next residual hop
needs) in the same pass.

Backward is one-pass too: the forward saves the per-row statistics
(mean/rstd for LayerNorm, rstd for RMSNorm) so the backward recomputes
x-hat from the raw inputs without re-deriving the statistics, and
accumulates dscale/dbias across row blocks in VMEM scratch instead of
materializing an x-hat tensor.

Dispatch contract (the ``attend`` seam pattern): every public entry
takes ``impl`` —

- ``"reference"`` — the XLA composite (exactly the numerics the models
  used before this tier existed: native-dtype residual add, f32
  statistics and normalization, cast back to the input dtype);
- ``"fused"``     — the Pallas kernel (compiled on TPU, interpret mode
  elsewhere, like tpudl.ops.flash_attention);
- ``"auto"``      — fused on TPU, reference off-TPU (the safe
  production default for model configs' ``fused_ops=True``).

Residual form: ``layer_norm(x, scale, bias, residual=r)`` returns
``(normed, x + r)`` — the summed output is the value the next residual
connection carries (pre-norm decoders) and is produced in the input
dtype; statistics are computed from the f32 sum (bf16-level divergence
from the composite's bf16 add, inside every parity tolerance).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.pallas_utils import round_up


def resolve_impl(impl: str, interpret: Optional[bool]):
    """The epilogue-kernel dispatch rule shared by norms / mlp_fused /
    cross_entropy: ``impl`` -> (use_fused, interpret)."""
    from tpudl.ops.attention import is_tpu_backend

    if impl == "auto":
        impl = "fused" if is_tpu_backend() else "reference"
    if impl not in ("fused", "reference"):
        raise ValueError(
            f"impl must be 'auto', 'fused' or 'reference', got {impl!r}"
        )
    if interpret is None:
        interpret = not is_tpu_backend()
    return impl == "fused", interpret


def fused_ops_impl(flag) -> str:
    """Model-config ``fused_ops`` flag -> ops ``impl`` name.

    False -> "reference" (default; nothing changes), True -> "auto"
    (fused on TPU, composite off-TPU — what bench flips on), "force" ->
    "fused" everywhere (interpret mode off-TPU — the CPU test mode that
    actually exercises the kernels)."""
    if not flag:
        return "reference"
    if flag == "force":
        return "fused"
    return "auto"


#: Override for the row-block heuristic below (None = heuristic): the
#: tests' seam for holding the kernel grid against the references.
#: Shared by the MLP epilogues too (they grid through ``_grid_setup``).
BLOCK_ROWS_OVERRIDE: Optional[int] = None


#: What a row-blocked call may hold in VMEM: the compiler's default
#: scoped limit on v5e is 16 MiB; the remainder is headroom for the
#: parameter rows, statistics columns and reduction scratch.
_VMEM_BUDGET = 14 << 20


def _block_rows(n: int, h_pad: int, itemsize: int, row_blocks: int,
                f32_temps: int) -> int:
    """Row-block height: sublane-aligned (16 covers bf16's min tile),
    capped so one (rows, h_pad) block stays ~1 MB AND so everything the
    call holds fits ``_VMEM_BUDGET`` — ``row_blocks`` row-shaped
    inputs/outputs, each double-buffered by the pipeline, plus
    ``f32_temps`` full-block f32 values live in the kernel body. The
    second cap is what binds at wide rows (the SwiGLU backward at 8,192
    columns holds five streams: the 1 MB rule alone asks for 17 MB)."""
    override = BLOCK_ROWS_OVERRIDE
    if override is not None:
        if override < 1:
            raise ValueError(
                f"block-rows override must be >= 1, got {override}"
            )
        return min(round_up(override, 16), round_up(n, 16))
    cap = max(16, ((1 << 20) // max(h_pad * itemsize, 1)) // 16 * 16)
    per_row = h_pad * (2 * row_blocks * itemsize + 4 * f32_temps)
    fit = max(16, (_VMEM_BUDGET // per_row) // 16 * 16)
    return min(256, cap, fit, round_up(n, 16))


def _grid_setup(x2, others, *, outputs: int, f32_temps: int):
    """Pad [N, H] operands to (N_pad, H_pad) tile multiples; returns the
    padded arrays plus (bn, n_pad, h_pad). ``outputs`` counts the call's
    row-shaped outputs and ``f32_temps`` its live f32 temporaries — with
    the inputs, what ``_block_rows`` sizes the block against."""
    n, h = x2.shape
    h_pad = round_up(h, 128)
    bn = _block_rows(n, h_pad, x2.dtype.itemsize,
                     1 + len(others) + outputs, f32_temps)
    n_pad = round_up(n, bn)
    def pad(a):
        return jnp.pad(a, ((0, n_pad - a.shape[0]), (0, h_pad - a.shape[1])))
    return pad(x2), [pad(o) for o in others], bn, n_pad, h_pad


def _row_param(p, h_pad):
    """[H] param -> [1, H_pad] f32 row (broadcast over the row block)."""
    return jnp.pad(p.astype(jnp.float32), (0, h_pad - p.shape[0]))[None, :]


# ---------------------------------------------------------------------------
# forward kernels
# ---------------------------------------------------------------------------


def _norm_fwd_kernel(*refs, kind, has_res, emit_sum, eps, h):
    """One row-block: residual add (f32), statistics, normalize, write.

    Ref order: x, [res], scale, [bias], y, [s], [mean], rstd — bias/mean
    only for kind='layer', s only when the caller wants the summed value
    back (pre-norm residual carries; post-norm callers skip the write).
    Padded columns hold zeros, so sum(s)/H and sum(s*s)/H are exact
    without a column mask."""
    it = iter(refs)
    x_ref = next(it)
    r_ref = next(it) if has_res else None
    scale_ref = next(it)
    bias_ref = next(it) if kind == "layer" else None
    y_ref = next(it)
    s_ref = next(it) if (has_res and emit_sum) else None
    mean_ref = next(it) if kind == "layer" else None
    rstd_ref = next(it)

    s = x_ref[:, :].astype(jnp.float32)
    if has_res:
        s = s + r_ref[:, :].astype(jnp.float32)
        if emit_sum:
            s_ref[:, :] = s.astype(s_ref.dtype)
    if kind == "layer":
        mean = jnp.sum(s, axis=-1, keepdims=True) / h
        var = jnp.maximum(
            jnp.sum(s * s, axis=-1, keepdims=True) / h - mean * mean, 0.0
        )
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (s - mean) * rstd
        y = xhat * scale_ref[:, :] + bias_ref[:, :]
        mean_ref[:, :] = jnp.broadcast_to(mean, mean_ref.shape)
    else:
        rstd = jax.lax.rsqrt(
            jnp.sum(s * s, axis=-1, keepdims=True) / h + eps
        )
        y = (s * rstd) * scale_ref[:, :]
    y_ref[:, :] = y.astype(y_ref.dtype)
    rstd_ref[:, :] = jnp.broadcast_to(rstd, rstd_ref.shape)


def _norm_fwd(x2, scale, bias, res2, *, kind, eps, interpret,
              emit_sum=True):
    n, h = x2.shape
    has_res = res2 is not None
    emit_sum = has_res and emit_sum
    xp, extras, bn, n_pad, h_pad = _grid_setup(
        x2, [res2] if has_res else [], outputs=1 + int(emit_sum),
        f32_temps=2,
    )
    grid = (n_pad // bn,)
    row = pl.BlockSpec((bn, h_pad), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    par = pl.BlockSpec((1, h_pad), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((bn, 128), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)

    in_specs = [row] + ([row] if has_res else []) + [par]
    args = [xp] + extras + [_row_param(scale, h_pad)]
    if kind == "layer":
        in_specs.append(par)
        args.append(_row_param(bias, h_pad))
    out_specs = [row] + ([row] if emit_sum else [])
    out_shape = [jax.ShapeDtypeStruct((n_pad, h_pad), x2.dtype)] * (
        1 + int(emit_sum)
    )
    if kind == "layer":
        out_specs.append(stat)
        out_shape.append(jax.ShapeDtypeStruct((n_pad, 128), jnp.float32))
    out_specs.append(stat)
    out_shape.append(jax.ShapeDtypeStruct((n_pad, 128), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_norm_fwd_kernel, kind=kind, has_res=has_res,
                          emit_sum=emit_sum, eps=eps, h=float(h)),
        grid=grid,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    it = iter(outs)
    y = next(it)[:n, :h]
    s = next(it)[:n, :h] if emit_sum else None
    mean = next(it)[:n, :1] if kind == "layer" else None
    rstd = next(it)[:n, :1]
    return y, s, mean, rstd


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _norm_bwd_kernel(*refs, kind, has_res, has_gs, h):
    """One-pass backward over row blocks: recompute x-hat from the raw
    inputs + saved statistics, emit dx (= dresidual), and accumulate the
    cross-row dscale/dbias partials in VMEM scratch (grid axis is
    sequential — 'arbitrary')."""
    it = iter(refs)
    x_ref = next(it)
    r_ref = next(it) if has_res else None
    scale_ref = next(it)
    g_ref = next(it)
    gs_ref = next(it) if has_gs else None
    mean_ref = next(it) if kind == "layer" else None
    rstd_ref = next(it)
    dx_ref = next(it)
    dscale_ref = next(it)
    dbias_ref = next(it) if kind == "layer" else None
    dsc_scr = next(it)
    dbi_scr = next(it) if kind == "layer" else None

    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dsc_scr[:, :] = jnp.zeros_like(dsc_scr)
        if kind == "layer":
            dbi_scr[:, :] = jnp.zeros_like(dbi_scr)

    s = x_ref[:, :].astype(jnp.float32)
    if has_res:
        s = s + r_ref[:, :].astype(jnp.float32)
    g = g_ref[:, :].astype(jnp.float32)
    rstd = rstd_ref[:, :1]
    scale = scale_ref[:, :]
    if kind == "layer":
        xhat = (s - mean_ref[:, :1]) * rstd
    else:
        xhat = s * rstd
    dxhat = g * scale
    m2 = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / h
    if kind == "layer":
        m1 = jnp.sum(dxhat, axis=-1, keepdims=True) / h
        ds = rstd * (dxhat - m1 - xhat * m2)
    else:
        ds = rstd * (dxhat - xhat * m2)
    if has_gs:
        ds = ds + gs_ref[:, :].astype(jnp.float32)
    dx_ref[:, :] = ds.astype(dx_ref.dtype)

    dsc_scr[0:1, :] += jnp.sum(g * xhat, axis=0, keepdims=True)
    if kind == "layer":
        dbi_scr[0:1, :] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finalize():
        dscale_ref[:, :] = jnp.broadcast_to(
            dsc_scr[0:1, :], dscale_ref.shape
        )
        if kind == "layer":
            dbias_ref[:, :] = jnp.broadcast_to(
                dbi_scr[0:1, :], dbias_ref.shape
            )


def _norm_bwd(x2, scale, res2, mean, rstd, g2, gs2, *, kind, interpret):
    n, h = x2.shape
    has_res = res2 is not None
    has_gs = gs2 is not None
    extras = ([res2] if has_res else []) + [g2] + ([gs2] if has_gs else [])
    xp, extras, bn, n_pad, h_pad = _grid_setup(
        x2, extras, outputs=1, f32_temps=2
    )
    it = iter(extras)
    rp = next(it) if has_res else None
    gp = next(it)
    gsp = next(it) if has_gs else None
    # Per-row stats ride as (N_pad, 128) broadcasts (the flash-kernel
    # lse layout trick, rotated: rows on sublanes).
    def stat_arr(a):
        return jnp.broadcast_to(
            jnp.pad(a, ((0, n_pad - a.shape[0]), (0, 0))), (n_pad, 128)
        )

    row = pl.BlockSpec((bn, h_pad), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    par = pl.BlockSpec((1, h_pad), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    stat = pl.BlockSpec((bn, 128), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    red = pl.BlockSpec((8, h_pad), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)

    in_specs = [row] + ([row] if has_res else []) + [par, row]
    args = [xp] + ([rp] if has_res else []) + [_row_param(scale, h_pad), gp]
    if has_gs:
        in_specs.append(row)
        args.append(gsp)
    if kind == "layer":
        in_specs.append(stat)
        args.append(stat_arr(mean))
    in_specs.append(stat)
    args.append(stat_arr(rstd))

    out_specs = [row, red]
    out_shape = [
        jax.ShapeDtypeStruct((n_pad, h_pad), x2.dtype),
        jax.ShapeDtypeStruct((8, h_pad), jnp.float32),
    ]
    scratch = [pltpu.VMEM((8, h_pad), jnp.float32)]
    if kind == "layer":
        out_specs.append(red)
        out_shape.append(jax.ShapeDtypeStruct((8, h_pad), jnp.float32))
        scratch.append(pltpu.VMEM((8, h_pad), jnp.float32))

    outs = pl.pallas_call(
        functools.partial(_norm_bwd_kernel, kind=kind, has_res=has_res,
                          has_gs=has_gs, h=float(h)),
        grid=(n_pad // bn,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    dx = outs[0][:n, :h]
    dscale = outs[1][0, :h].astype(scale.dtype)
    dbias = outs[2][0, :h].astype(scale.dtype) if kind == "layer" else None
    return dx, dscale, dbias


# ---------------------------------------------------------------------------
# custom_vjp wrappers (x flattened to [N, H])
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x2, scale, bias, eps, interpret):
    y, _, _, _ = _norm_fwd(x2, scale, bias, None, kind="layer", eps=eps,
                           interpret=interpret)
    return y


def _ln_fwd(x2, scale, bias, eps, interpret):
    y, _, mean, rstd = _norm_fwd(x2, scale, bias, None, kind="layer",
                                 eps=eps, interpret=interpret)
    return y, (x2, scale, mean, rstd)


def _ln_bwd(eps, interpret, res, g):
    x2, scale, mean, rstd = res
    dx, dscale, dbias = _norm_bwd(x2, scale, None, mean, rstd, g, None,
                                  kind="layer", interpret=interpret)
    return dx, dscale, dbias


_ln.defvjp(_ln_fwd, _ln_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _ln_res(x2, scale, bias, r2, eps, interpret, emit_sum):
    y, s, _, _ = _norm_fwd(x2, scale, bias, r2, kind="layer", eps=eps,
                           interpret=interpret, emit_sum=emit_sum)
    return (y, s) if emit_sum else y


def _ln_res_fwd(x2, scale, bias, r2, eps, interpret, emit_sum):
    y, s, mean, rstd = _norm_fwd(x2, scale, bias, r2, kind="layer",
                                 eps=eps, interpret=interpret,
                                 emit_sum=emit_sum)
    out = (y, s) if emit_sum else y
    return out, (x2, scale, r2, mean, rstd)


def _ln_res_bwd(eps, interpret, emit_sum, res, g):
    x2, scale, r2, mean, rstd = res
    gy, gs = g if emit_sum else (g, None)
    dx, dscale, dbias = _norm_bwd(x2, scale, r2, mean, rstd, gy, gs,
                                  kind="layer", interpret=interpret)
    return dx, dscale, dbias, dx


_ln_res.defvjp(_ln_res_fwd, _ln_res_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _rms(x2, scale, eps, interpret):
    y, _, _, _ = _norm_fwd(x2, scale, None, None, kind="rms", eps=eps,
                           interpret=interpret)
    return y


def _rms_fwd(x2, scale, eps, interpret):
    y, _, _, rstd = _norm_fwd(x2, scale, None, None, kind="rms", eps=eps,
                              interpret=interpret)
    return y, (x2, scale, rstd)


def _rms_bwd(eps, interpret, res, g):
    x2, scale, rstd = res
    dx, dscale, _ = _norm_bwd(x2, scale, None, None, rstd, g, None,
                              kind="rms", interpret=interpret)
    return dx, dscale


_rms.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rms_res(x2, scale, r2, eps, interpret, emit_sum):
    y, s, _, _ = _norm_fwd(x2, scale, None, r2, kind="rms", eps=eps,
                           interpret=interpret, emit_sum=emit_sum)
    return (y, s) if emit_sum else y


def _rms_res_fwd(x2, scale, r2, eps, interpret, emit_sum):
    y, s, _, rstd = _norm_fwd(x2, scale, None, r2, kind="rms", eps=eps,
                              interpret=interpret, emit_sum=emit_sum)
    out = (y, s) if emit_sum else y
    return out, (x2, scale, r2, rstd)


def _rms_res_bwd(eps, interpret, emit_sum, res, g):
    x2, scale, r2, rstd = res
    gy, gs = g if emit_sum else (g, None)
    dx, dscale, _ = _norm_bwd(x2, scale, r2, None, rstd, gy, gs,
                              kind="rms", interpret=interpret)
    return dx, dscale, dx


_rms_res.defvjp(_rms_res_fwd, _rms_res_bwd)


# ---------------------------------------------------------------------------
# reference composites (exactly the pre-existing model numerics)
# ---------------------------------------------------------------------------


def layer_norm_ref(x, scale, bias, residual=None, *, eps=1e-12):
    """XLA composite LayerNorm(+residual): native-dtype residual add
    (what ``hidden + out`` in the models always did), f32 statistics and
    scale/bias (flax ``nn.LayerNorm(dtype=jnp.float32)`` semantics),
    output cast back to the input dtype."""
    s = x if residual is None else x + residual
    x32 = s.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) - mean * mean, 0.0
    )
    # Association matches flax nn.LayerNorm bitwise: scale folds into
    # the rsqrt factor BEFORE the (x - mean) multiply.
    mul = jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    y = ((x32 - mean) * mul + bias.astype(jnp.float32)).astype(x.dtype)
    return y if residual is None else (y, s)


def rms_norm_ref(x, scale, residual=None, *, eps=1e-5):
    """XLA composite RMSNorm(+residual) — the tpudl.models.llama.RMSNorm
    math verbatim: f32 mean-square, ``(norm * scale)`` in f32, cast back."""
    s = x if residual is None else x + residual
    x32 = s.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt(
        jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps
    )
    y = (norm * scale).astype(x.dtype)
    return y if residual is None else (y, s)


# ---------------------------------------------------------------------------
# public entries
# ---------------------------------------------------------------------------


def _flatten(a):
    return a.reshape(-1, a.shape[-1])


def layer_norm(
    x: jax.Array,
    scale: jax.Array,
    bias: jax.Array,
    residual: Optional[jax.Array] = None,
    *,
    eps: float = 1e-12,
    return_sum: bool = True,
    impl: str = "auto",
    interpret: Optional[bool] = None,
):
    """Fused LayerNorm(+residual-add) over the last axis of ``x``.

    Returns the normed array (input dtype), or ``(normed, x + residual)``
    when ``residual`` is given — one activation read, both writes, f32
    statistics saved for the one-pass backward. ``return_sum=False``
    skips the summed output entirely (post-norm architectures consume
    only the normed value — one fewer full HBM write). ``impl``: see
    module docstring."""
    fused, interpret = resolve_impl(impl, interpret)
    if not fused:
        out = layer_norm_ref(x, scale, bias, residual, eps=eps)
        if residual is not None and not return_sum:
            return out[0]
        return out
    shape = x.shape
    if residual is None:
        y = _ln(_flatten(x), scale, bias, float(eps), interpret)
        return y.reshape(shape)
    out = _ln_res(_flatten(x), scale, bias, _flatten(residual),
                  float(eps), interpret, return_sum)
    if not return_sum:
        return out.reshape(shape)
    y, s = out
    return y.reshape(shape), s.reshape(shape)


def rms_norm(
    x: jax.Array,
    scale: jax.Array,
    residual: Optional[jax.Array] = None,
    *,
    eps: float = 1e-5,
    return_sum: bool = True,
    impl: str = "auto",
    interpret: Optional[bool] = None,
):
    """Fused RMSNorm(+residual-add) over the last axis of ``x`` — the
    decode-path norm (runs every serve decode step). Same contract as
    :func:`layer_norm` minus the bias/mean."""
    fused, interpret = resolve_impl(impl, interpret)
    if not fused:
        out = rms_norm_ref(x, scale, residual, eps=eps)
        if residual is not None and not return_sum:
            return out[0]
        return out
    shape = x.shape
    if residual is None:
        y = _rms(_flatten(x), scale, float(eps), interpret)
        return y.reshape(shape)
    out = _rms_res(_flatten(x), scale, _flatten(residual), float(eps),
                   interpret, return_sum)
    if not return_sum:
        return out.reshape(shape)
    y, s = out
    return y.reshape(shape), s.reshape(shape)
