"""Plain decoder whose layers differ in their attention, with routed
experts, as ``poolside/Laguna-XS.2`` publishes it: layers that keep the
whole context and layers that see a sliding window, each kind with its
own number of query heads and its own rotary positions, a sigmoid gate
a head on the attention's output, a leading dense layer and routed
SwiGLU experts after it. Float32 at ``highest`` matmul precision, whole
sequences, no cache, no ring, no kernel, every expert applied plainly
to every token under its gate (0 where the token did not choose it).

Per layer ``l``, with ``x^ = RMSNorm(x)``, ``H_l`` query heads
(``num_attention_heads_per_layer``), ``num_key_value_heads`` KV heads,
``d = head_dim``:

- ``q = x^ W_q`` -> ``H_l x d``; ``k, v = x^ W_k, x^ W_v`` -> KV heads
  ``x d``. A ``full_attention`` layer rotates (rotate-half) the FIRST
  ``partial_rotary_factor * d`` values of every head of ``q`` and ``k``
  with YaRN's blended inverse frequencies over that many dimensions,
  cos and sin times ``attention_factor``; the rest pass. A
  ``sliding_attention`` layer rotates the whole head, plainly, with its
  own base.
- ``score_h(t, s) = q_h(t) . k_g(h)(s) * d^-0.5``, ``g(h) = h // (H_l /
  KV heads)``; key ``s`` is visible to query ``t`` iff ``s <= t`` and,
  on a sliding layer, ``t - s < sliding_window``; softmax; ``ctx_h =
  sum_s p v_g(h)(s)``.
- ``gamma = sigmoid(x^ W_gamma)``, one value a head; ``ctx_h <- gamma_h
  ctx_h``; ``out = concat_h(ctx_h) W_o``.
- a ``dense`` layer (``mlp_layer_types``): SwiGLU of
  ``intermediate_size``. A ``sparse`` one: ``s = sigmoid(x^ W_r)`` over
  ``num_experts``; ``T = top_k(s)``; ``g_i = moe_routed_scaling_factor
  * s_i / sum_{j in T} s_j``; ``y = sum_{i in T} g_i E_i(x^) +
  E_shared(x^)``, ``E(z) = W_down(silu(W_gate z) * W_up z)``.

Queries are taken a block at a time so that one block's scores are all
that is held. Weights are made here from a seed, layer by layer, in the
type they are served in, so that a server and this reference can each
make the same values without handing anything to one another. Imports
nothing of ``tpudl``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import seed_key  # noqa: F401

INIT_STD = 0.02
ATTENTION_MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj", "g_proj")
DENSE_MATRICES = ("gate_proj", "up_proj", "down_proj")
MOE_MATRICES = ("router", "experts_gate", "experts_up", "experts_down",
                "shared_gate", "shared_up", "shared_down")
#: Queries attended at once (the largest of these that divides the
#: sequence).
QUERY_BLOCKS = (256, 128, 64, 32, 16, 8, 4, 2, 1)


def settings(cfg: dict) -> dict:
    """What the forward pass reads, from a configuration file: the
    public keys, the per-layer lists cut to the layers held, the two
    groups of rotary parameters flattened."""
    n = cfg["num_hidden_layers"]
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_hidden_layers": n,
        "layer_types": tuple(cfg["layer_types"][:n]),
        "mlp_layer_types": tuple(cfg["mlp_layer_types"][:n]),
        "heads": tuple(cfg["num_attention_heads_per_layer"][:n]),
        "num_key_value_heads": cfg["num_key_value_heads"],
        "head_dim": cfg["head_dim"],
        "sliding_window": cfg["sliding_window"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "shared_expert_intermediate_size":
            cfg["shared_expert_intermediate_size"],
        "num_experts": cfg["num_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "moe_routed_scaling_factor": cfg["moe_routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "full_theta": full["rope_theta"],
        "full_rotary_dim": int(
            cfg["head_dim"] * full["partial_rotary_factor"]
        ),
        "yarn_factor": full["factor"],
        "yarn_original": full["original_max_position_embeddings"],
        "yarn_beta_fast": full["beta_fast"],
        "yarn_beta_slow": full["beta_slow"],
        "attention_factor": full["attention_factor"],
        "sliding_theta": sliding["rope_theta"],
        "sliding_rotary_dim": int(
            cfg["head_dim"] * sliding["partial_rotary_factor"]
        ),
    }


def frozen(s: dict) -> tuple:
    """The settings as something ``jax.jit`` takes as a static
    argument."""
    return tuple(sorted(s.items()))


def _shapes(s: dict, layer: int) -> dict:
    h, d = s["hidden_size"], s["head_dim"]
    heads, kv = s["heads"][layer], s["num_key_value_heads"]
    out = {
        "q_proj": (h, heads * d), "k_proj": (h, kv * d),
        "v_proj": (h, kv * d), "o_proj": (heads * d, h),
        "g_proj": (h, heads),
    }
    if s["mlp_layer_types"][layer] == "dense":
        f = s["intermediate_size"]
        out.update(gate_proj=(h, f), up_proj=(h, f), down_proj=(f, h))
    else:
        f, e = s["moe_intermediate_size"], s["num_experts"]
        fs = s["shared_expert_intermediate_size"]
        out.update(
            router=(h, e),
            experts_gate=(e, h, f), experts_up=(e, h, f),
            experts_down=(e, f, h),
            shared_gate=(h, fs), shared_up=(h, fs), shared_down=(fs, h),
        )
    return out


def _normal(key, shape, dtype):
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_weights(root, layer: int, s: dict, dtype) -> dict:
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front)
    and norm scales. The router is float32: the choice of experts is
    made there."""
    key = jax.random.fold_in(root, 1 + layer)
    shapes = _shapes(s, layer)
    names = ATTENTION_MATRICES + (
        DENSE_MATRICES if s["mlp_layer_types"][layer] == "dense"
        else MOE_MATRICES
    )
    out = {}
    for i, name in enumerate(names):
        kind = jnp.float32 if name == "router" else dtype
        out[name] = _normal(jax.random.fold_in(key, i), shapes[name], kind)
    out["input_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["post_attention_norm"] = jnp.ones((s["hidden_size"],), dtype)
    return out


def outer_weights(root, s: dict, dtype) -> dict:
    """Embedding table, final norm and output head."""
    key = jax.random.fold_in(root, 0)
    h, v = s["hidden_size"], s["vocab_size"]
    return {
        "embed_tokens": _normal(jax.random.fold_in(key, 0), (v, h), dtype),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": _normal(jax.random.fold_in(key, 1), (h, v), dtype),
    }


def all_weights(key, s: dict, dtype) -> dict:
    return {
        "outer": outer_weights(key, s, dtype),
        "layers": [layer_weights(key, i, s, dtype)
                   for i in range(s["num_hidden_layers"])],
    }


# -- the forward pass --------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def yarn_inv_freq(s: dict):
    """[full_rotary_dim / 2] inverse frequencies: ``1 / theta_i`` where
    a frequency turns more than ``beta_fast`` times over the original
    context, ``1 / (factor theta_i)`` where it turns less than
    ``beta_slow`` times, a linear blend between the two dimensions."""
    dim, base = s["full_rotary_dim"], s["full_theta"]
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)

    def correction_dim(rotations):
        return dim * math.log(
            s["yarn_original"] / (rotations * 2 * math.pi)
        ) / (2 * math.log(base))

    low = max(math.floor(correction_dim(s["yarn_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(s["yarn_beta_slow"])), dim - 1)
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / max(high - low, 1e-3), 0.0, 1.0,
    )
    return plain / s["yarn_factor"] * ramp + plain * (1.0 - ramp)


def _rope(x, s: dict, kind: str):
    """x: [S, H, D]; position = index along S."""
    if kind == "full_attention":
        dim, amp = s["full_rotary_dim"], s["attention_factor"]
        inv_freq = yarn_inv_freq(s)
    else:
        dim, amp = s["sliding_rotary_dim"], 1.0
        inv_freq = 1.0 / s["sliding_theta"] ** (
            jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
        )
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def attention(y, w, s: dict, layer: int):
    """One sequence: y [S, hidden] (normed) -> [S, hidden]."""
    n, d = y.shape[0], s["head_dim"]
    heads, kv = s["heads"][layer], s["num_key_value_heads"]
    kind = s["layer_types"][layer]
    q = _rope((y @ w["q_proj"]).reshape(n, heads, d), s, kind)
    k = _rope((y @ w["k_proj"]).reshape(n, kv, d), s, kind)
    v = (y @ w["v_proj"]).reshape(n, kv, d)
    # Query head h reads KV head h // (heads / kv).
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    gate = jax.nn.sigmoid(y @ w["g_proj"])  # [S, heads]
    block = next(b for b in QUERY_BLOCKS if n % b == 0)
    key_at = jnp.arange(n)[None, :]

    def some_queries(args):
        qb, at = args  # [block, heads, d], the first query's position
        query_at = at + jnp.arange(block)[:, None]
        seen = key_at <= query_at
        if kind == "sliding_attention":
            seen = seen & (query_at - key_at < s["sliding_window"])
        score = jnp.einsum("shd,thd->hst", qb, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen[None], score, -jnp.inf), axis=-1)
        return jnp.einsum("hst,thd->shd", p, v)

    ctx = jax.lax.map(
        some_queries,
        (q.reshape(n // block, block, heads, d), jnp.arange(0, n, block)),
    ).reshape(n, heads, d)
    return (ctx * gate[..., None]).reshape(n, heads * d) @ w["o_proj"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, num_experts] gates: ``g_i`` on the chosen experts, 0
    elsewhere."""
    scores = jax.nn.sigmoid(y @ w["router"])
    _, chosen = jax.lax.top_k(scores, s["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = s["moe_routed_scaling_factor"] * picked / picked.sum(
        -1, keepdims=True
    )
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(gates)


def experts(y, w, s: dict):
    """y [tokens, hidden] (normed) -> the routed experts' sum and the
    shared expert."""
    gates = route(y, w, s)

    def one(total, args):
        gate, up, down, g = args
        return total + g[:, None] * _swiglu(
            y, gate.astype(jnp.float32), up.astype(jnp.float32),
            down.astype(jnp.float32),
        ), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (w["experts_gate"], w["experts_up"], w["experts_down"], gates.T),
    )
    return out + _swiglu(y, w["shared_gate"], w["shared_up"],
                         w["shared_down"])


def block(x, w, s: dict, layer: int, precision="highest"):
    """One layer on x: [B, S, hidden] float32, causal over S; a row at a
    time."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked else v.astype(jnp.float32)
             for k, v in w.items()}
        eps = s["rms_norm_eps"]

        def row(xr):
            xr = xr + attention(
                _rms_norm(xr, w["input_norm"], eps), w, s, layer
            )
            y = _rms_norm(xr, w["post_attention_norm"], eps)
            if s["mlp_layer_types"][layer] == "dense":
                return xr + _swiglu(y, w["gate_proj"], w["up_proj"],
                                    w["down_proj"])
            return xr + experts(y, w, s)

        return jax.lax.map(row, x)


def head(x, outer, s: dict, precision="highest"):
    """Logits of hidden states x: [..., hidden]."""
    with jax.default_matmul_precision(precision):
        y = _rms_norm(x, outer["final_norm"].astype(jnp.float32),
                      s["rms_norm_eps"])
        return y @ outer["lm_head"].astype(jnp.float32)


def forward(key, cfg: dict, dtype, ids, precision="highest"):
    """Hidden states before the final norm, [B, S, hidden]; layer by
    layer, each layer's weights made from the seed and dropped."""
    s = settings(cfg)
    outer = _outer_jit(key, frozen(s), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    for i in range(s["num_hidden_layers"]):
        x = _layer_jit(key, i, x, frozen(s), dtype, precision)
    return x, outer


def logits(key, cfg: dict, dtype, ids, precision="highest"):
    """[B, S, vocab] logits of every position (small cases only)."""
    x, outer = forward(key, cfg, dtype, ids, precision)
    return head(x, outer, settings(cfg), precision)


def margins(key, cfg: dict, dtype, ids, picks, chosen, precision="highest"):
    """By how much the reference's best logit beats each chosen token:
    ``perfbench.reference.decoder.margins``'s contract (``ids`` [B, S]
    prompts followed by the served tokens, right-padded; ``picks`` [B, T]
    positions whose logits chose a token; ``chosen`` [B, T] the token
    chosen there; returns [B, T] float32, 0 where the reference
    agrees)."""
    x, outer = forward(key, cfg, dtype, ids, precision)
    return _margin_jit(x, outer, picks, chosen, frozen(settings(cfg)),
                       precision)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _outer_jit(key, s_items, dtype):
    return outer_weights(key, dict(s_items), dtype)


@functools.partial(jax.jit, static_argnums=(1, 3, 4, 5))
def _layer_jit(key, layer, x, s_items, dtype, precision):
    s = dict(s_items)
    return block(x, layer_weights(key, layer, s, dtype), s, layer, precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _margin_jit(x, outer, picks, chosen, s_items, precision):
    def row(args):
        xr, pr, cr = args
        logits = head(xr[pr], outer, dict(s_items), precision)
        got = jnp.take_along_axis(logits, cr[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(row, (x, picks, chosen))
