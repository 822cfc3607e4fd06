"""Manifold-constrained hyper-connections (arXiv:2512.24880): a residual
stream of ``n`` vectors a token, ``X`` in ``R^{n x d}``, in place of
one. Around a sublayer ``F`` (an attention, an FFN, an expert layer),
with the sublayer's own ``phi`` in ``R^{nd x (2n + n^2)}``, ``b`` in
``R^{2n + n^2}`` and three scalars ``alpha``:

    x^ = RMSNorm(vec(X))                  all n d values, no learned scale
    [h~_pre | h~_post | h~_res] = x^ phi, each part times its alpha, + b
    h_pre  = sigmoid(h~_pre)              in R^n: what F reads
    h_post = 2 sigmoid(h~_post)           in R^n: where F's output lands
    M = exp(clamp(mat(h~_res), -c, c))    in R^{n x n}
    ``iters`` times: M's columns over (their sums + eps), then its rows
                     over (theirs + eps)  -> H_res, doubly stochastic
    u  = h_pre^T X                        in R^d
    y  = F(norm(u))                       the layer's own norm, outside
    X' = H_res X + h_post y^T             stream i gains h_post[i] y

The maps are float32 whatever the stream is stored in: a mixing matrix
rounded to bfloat16 moves every value of the stream by a part in 256,
every sublayer. ``HyperConnection`` makes the maps and reads ``u`` (scopes
``hyper`` > ``hyper_maps``, ``hyper_mix_in``); ``mix_out`` writes
``X'`` (``hyper`` > ``hyper_mix_out``). Both are XLA's fusions; what
they cost a 4,096-row prefill and a decode step is in PERF.md
section 5.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

#: The statistic a ``HyperConnection`` sows into the serving
#: statistics' collection (tpudl.models.generate), float32 [3] over the
#: call's real tokens: the mass their ``H_res`` put off its diagonal,
#: summed (0 a token: streams kept apart; ``1 - 1/n``: fully mixed);
#: how many they were; the largest ``|column sum - 1|`` among them
#: (rows are normalised last, so the columns carry what the iterations
#: left).
HYPER_STAT_NAME = "hyper_res"


def _identity_bias(n: int):
    """``b`` that starts a connection as the plain residual: the
    sublayer reads the streams' mean, writes to each with weight 1, and
    ``H_res`` is the identity to ``exp(-8)``."""
    def init(key, shape, dtype=jnp.float32):
        del key
        pre = jnp.full((n,), -jnp.log(n - 1.0))
        res = 8.0 * (jnp.eye(n) - 1.0)
        return jnp.concatenate(
            [pre, jnp.zeros((n,)), res.reshape(-1)]
        ).astype(dtype).reshape(shape)

    return init


def sinkhorn(m, iters: int, eps: float):
    """``iters`` times: columns over (their sums + ``eps``), then rows
    over (theirs + ``eps``). ``m``: [n, n, ...], ``m[i, j]`` what
    stream ``i`` takes of stream ``j``, positive."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


class HyperConnection(nn.Module):
    """One sublayer's maps and its read of the stream. ``stream``
    [B, S, n, d]; ``real`` [B, S] bool, the tokens the statistic counts.
    Returns ``(u [B, S, d] in the stream's dtype, h_post [n, B, S],
    h_res [n, n, B, S])``, the maps float32 with their ENTRIES LEADING
    and the tokens last, as ``mix_out`` takes them: twenty-four numbers
    a token make no minor axis the chip lays out well (a ``[B, S, n,
    n]`` array is tiles of mostly padding, through forty reductions)."""

    streams: int
    sinkhorn_iters: int = 20
    eps: float = 1e-6
    clamp: float = 30.0
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, stream, real):
        n = self.streams
        b, s, _, d = stream.shape
        phi = self.param(
            "phi", nn.initializers.normal(0.02), (n * d, 2 * n + n * n),
            jnp.float32,
        )
        bias = self.param(
            "b", _identity_bias(n), (2 * n + n * n,), jnp.float32
        )
        alpha = self.param(
            "alpha", nn.initializers.constant(0.01), (3,), jnp.float32
        )
        with jax.named_scope("hyper"):
            with jax.named_scope("hyper_maps"):
                flat = stream.reshape(b, s, n * d).astype(jnp.float32)
                # x^ phi = rsqrt(mean(x^2) + eps) (x phi): the normed
                # copy of the stream is never made.
                scale = jax.lax.rsqrt(
                    jnp.mean(jnp.square(flat), -1) + self.norm_eps
                )
                raw = scale[..., None] * jnp.einsum(
                    "bsk,km->bsm", flat, phi.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                )
                # [h~_pre | h~_post | mat(h~_res)], each part times its
                # alpha, plus b; then the entries lead.
                by_entry = jnp.concatenate([
                    jnp.full((width,), alpha[part])
                    for part, width in enumerate((n, n, n * n))
                ])
                logit = jnp.moveaxis(by_entry * raw + bias, -1, 0)
                h_pre = jax.nn.sigmoid(logit[:n])
                h_post = 2.0 * jax.nn.sigmoid(logit[n:2 * n])
                h_res = sinkhorn(
                    jnp.exp(jnp.clip(
                        logit[2 * n:].reshape(n, n, b, s),
                        -self.clamp, self.clamp,
                    )),
                    self.sinkhorn_iters, self.eps,
                )
                counted = real.astype(jnp.float32)
                off = (
                    jnp.sum(h_res, axis=(0, 1)) - jnp.trace(h_res)
                ) / n
                column = jnp.max(
                    jnp.abs(jnp.sum(h_res, axis=0) - 1.0), axis=0
                )
                self.sow("moe_stats", HYPER_STAT_NAME, jnp.stack([
                    jnp.sum(off * counted), jnp.sum(counted),
                    jnp.max(column * counted),
                ]))
            with jax.named_scope("hyper_mix_in"):
                u = sum(
                    h_pre[i][..., None] * stream[:, :, i].astype(jnp.float32)
                    for i in range(n)
                ).astype(stream.dtype)
        return u, h_post, h_res


def mix_out(stream, h_res, h_post, y):
    """``X' = H_res X + h_post y^T`` in float32, stored as the stream
    is. stream: [B, S, n, d]; y: [B, S, d]; the maps as
    ``HyperConnection`` returns them."""
    n = stream.shape[2]
    with jax.named_scope("hyper"), jax.named_scope("hyper_mix_out"):
        x = stream.astype(jnp.float32)
        y = y.astype(jnp.float32)
        return jnp.stack([
            h_post[i][..., None] * y + sum(
                h_res[i, j][..., None] * x[:, :, j] for j in range(n)
            )
            for i in range(n)
        ], axis=2).astype(stream.dtype)
