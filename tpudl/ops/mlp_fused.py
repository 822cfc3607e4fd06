"""Fused MLP epilogues: bias+GeLU (exact) and SwiGLU Pallas TPU kernels.

The transformer MLP's elementwise epilogues run over the 4x intermediate
width — at BERT-base that is the single largest activation stream in the
block, and the composite path pays it several times: the bias add and
the exact (erf) GeLU read/write [N, 4H] separately, and XLA's autodiff
saves the pre-activation AND recomputes erf pieces in the backward.
These kernels do the epilogue in one VMEM pass each way:

- ``bias_gelu(x, bias)``   — y = gelu_exact(x + b); matches
  ``nn.gelu(dense(x), approximate=False)`` given ``dense``'s pre-bias
  output (the BERT intermediate epilogue);
- ``swiglu(gate, up)``     — y = silu(gate) * up (the Llama MLP gate,
  which also runs per serve decode step).

Backward needs NO forward recompute: both derivatives are closed-form
in the saved inputs (u = x + b resp. gate/up), so the backward is a
single elementwise pass that also folds the cross-row dbias reduction
into VMEM scratch instead of a separate [N, F] -> [F] XLA reduce.

Dispatch: ``impl`` = "auto" | "fused" | "reference" with the same
contract as tpudl.ops.norms (auto = fused on TPU, composite off-TPU;
fused runs interpret mode off-TPU for the hermetic parity tests).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpudl.ops.norms import resolve_impl, _grid_setup

_INV_SQRT2 = 1.0 / math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


#: Rational approximation of erf on [-4, 4] (numerator odd in x,
#: denominator even; highest power first) — the f32 form XLA itself
#: expands ``erf`` to, so the kernel agrees with ``jax.lax.erf`` to
#: ~5e-7. Spelled out because Pallas TPU has no lowering for the erf
#: primitive; multiply, add and divide all lower.
_ERF_ALPHA = (
    -2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
    -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
    -1.60960333262415e-02,
)
_ERF_BETA = (
    -1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
    -7.37332916720468e-03, -1.42647390514189e-02,
)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf(x):
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    return x * _horner(x2, _ERF_ALPHA) / _horner(x2, _ERF_BETA)


def _gelu_exact(u):
    """Exact (erf) GeLU in f32 — matches jax.nn.gelu(approximate=False)."""
    return u * 0.5 * (1.0 + _erf(u * _INV_SQRT2))


def _gelu_grad(u):
    """d/du gelu_exact(u) = Phi(u) + u * phi(u)."""
    phi = jnp.exp(-0.5 * u * u) * _INV_SQRT_2PI
    return 0.5 * (1.0 + _erf(u * _INV_SQRT2)) + u * phi


# ---------------------------------------------------------------------------
# bias + GeLU
# ---------------------------------------------------------------------------


def _bg_fwd_kernel(x_ref, b_ref, y_ref):
    u = x_ref[:, :].astype(jnp.float32) + b_ref[:, :]
    y_ref[:, :] = _gelu_exact(u).astype(y_ref.dtype)


def _bg_bwd_kernel(x_ref, b_ref, g_ref, dx_ref, db_ref, db_scr):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        db_scr[:, :] = jnp.zeros_like(db_scr)

    u = x_ref[:, :].astype(jnp.float32) + b_ref[:, :]
    du = g_ref[:, :].astype(jnp.float32) * _gelu_grad(u)
    dx_ref[:, :] = du.astype(dx_ref.dtype)
    db_scr[0:1, :] += jnp.sum(du, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _finalize():
        db_ref[:, :] = jnp.broadcast_to(db_scr[0:1, :], db_ref.shape)


def _bg_call(x2, bias, g2, interpret):
    """Shared pallas_call builder: forward when g2 is None, else backward."""
    n, f = x2.shape
    # The backward evaluates both erf polynomials and the gaussian on
    # the block at once: about twice the forward's live f32 values.
    xp, extras, bn, n_pad, f_pad = _grid_setup(
        x2, [g2] if g2 is not None else [], outputs=1,
        f32_temps=3 if g2 is None else 6,
    )
    bp = jnp.pad(bias.astype(jnp.float32), (0, f_pad - f))[None, :]
    row = pl.BlockSpec((bn, f_pad), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    par = pl.BlockSpec((1, f_pad), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    if g2 is None:
        y = pl.pallas_call(
            _bg_fwd_kernel,
            grid=(n_pad // bn,),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            in_specs=[row, par],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), x2.dtype),
            interpret=interpret,
        )(xp, bp)
        return y[:n, :f]
    red = pl.BlockSpec((8, f_pad), lambda i: (0, 0),
                       memory_space=pltpu.VMEM)
    dx, db = pl.pallas_call(
        _bg_bwd_kernel,
        grid=(n_pad // bn,),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        in_specs=[row, par, row],
        out_specs=[row, red],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, f_pad), x2.dtype),
            jax.ShapeDtypeStruct((8, f_pad), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((8, f_pad), jnp.float32)],
        interpret=interpret,
    )(xp, bp, extras[0])
    return dx[:n, :f], db[0, :f].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _bg(x2, bias, interpret):
    return _bg_call(x2, bias, None, interpret)


def _bg_fwd(x2, bias, interpret):
    return _bg_call(x2, bias, None, interpret), (x2, bias)


def _bg_bwd(interpret, res, g):
    x2, bias = res
    return _bg_call(x2, bias, g, interpret)


_bg.defvjp(_bg_fwd, _bg_bwd)


def bias_gelu_ref(x: jax.Array, bias: jax.Array) -> jax.Array:
    """XLA composite: exactly what the models did — native-dtype bias
    add (nn.Dense's epilogue) followed by exact-erf GeLU."""
    return jax.nn.gelu(x + bias.astype(x.dtype), approximate=False)


def bias_gelu(
    x: jax.Array,
    bias: jax.Array,
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused ``gelu_exact(x + bias)`` over the last axis of ``x``
    ([..., F] with bias [F]) — the BERT intermediate epilogue, one VMEM
    pass forward, one (recompute-free) pass backward with the dbias
    reduction folded in."""
    fused, interpret = resolve_impl(impl, interpret)
    if not fused:
        return bias_gelu_ref(x, bias)
    shape = x.shape
    return _bg(x.reshape(-1, shape[-1]), bias, interpret).reshape(shape)


# ---------------------------------------------------------------------------
# SwiGLU
# ---------------------------------------------------------------------------


def _sw_fwd_kernel(g_ref, u_ref, y_ref):
    g = g_ref[:, :].astype(jnp.float32)
    y = g * jax.nn.sigmoid(g) * u_ref[:, :].astype(jnp.float32)
    y_ref[:, :] = y.astype(y_ref.dtype)


def _sw_bwd_kernel(g_ref, u_ref, go_ref, dg_ref, du_ref):
    g = g_ref[:, :].astype(jnp.float32)
    u = u_ref[:, :].astype(jnp.float32)
    go = go_ref[:, :].astype(jnp.float32)
    sg = jax.nn.sigmoid(g)
    silu = g * sg
    dg_ref[:, :] = (go * u * (sg + silu * (1.0 - sg))).astype(dg_ref.dtype)
    du_ref[:, :] = (go * silu).astype(du_ref.dtype)


def _sw_call(g2, u2, go2, interpret):
    n, f = g2.shape
    gp, extras, bn, n_pad, f_pad = _grid_setup(
        g2, [u2] + ([go2] if go2 is not None else []),
        outputs=1 if go2 is None else 2, f32_temps=3,
    )
    row = pl.BlockSpec((bn, f_pad), lambda i: (i, 0),
                       memory_space=pltpu.VMEM)
    sem = pltpu.CompilerParams(dimension_semantics=("parallel",))
    if go2 is None:
        y = pl.pallas_call(
            _sw_fwd_kernel,
            grid=(n_pad // bn,),
            compiler_params=sem,
            in_specs=[row, row],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), g2.dtype),
            interpret=interpret,
        )(gp, extras[0])
        return y[:n, :f]
    dg, du = pl.pallas_call(
        _sw_bwd_kernel,
        grid=(n_pad // bn,),
        compiler_params=sem,
        in_specs=[row, row, row],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, f_pad), g2.dtype),
            jax.ShapeDtypeStruct((n_pad, f_pad), u2.dtype),
        ],
        interpret=interpret,
    )(gp, extras[0], extras[1])
    return dg[:n, :f], du[:n, :f]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sw(g2, u2, interpret):
    return _sw_call(g2, u2, None, interpret)


def _sw_fwd(g2, u2, interpret):
    return _sw_call(g2, u2, None, interpret), (g2, u2)


def _sw_bwd(interpret, res, g):
    g2, u2 = res
    return _sw_call(g2, u2, g, interpret)


_sw.defvjp(_sw_fwd, _sw_bwd)


def swiglu_ref(gate: jax.Array, up: jax.Array) -> jax.Array:
    """XLA composite: ``silu(gate) * up`` — tpudl.models.llama verbatim."""
    return jax.nn.silu(gate) * up


def swiglu(
    gate: jax.Array,
    up: jax.Array,
    *,
    impl: str = "auto",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused ``silu(gate) * up`` (the Llama MLP gate): one elementwise
    VMEM pass each way, closed-form backward from the saved inputs."""
    fused, interpret = resolve_impl(impl, interpret)
    if not fused:
        return swiglu_ref(gate, up)
    shape = gate.shape
    f = shape[-1]
    return _sw(gate.reshape(-1, f), up.reshape(-1, f), interpret).reshape(
        shape
    )
