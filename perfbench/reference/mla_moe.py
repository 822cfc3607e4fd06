"""Plain decoder with latent attention (MLA) and routed experts, as
``sarvamai/sarvam-105b`` publishes it, for the share of the experts and
of the vocabulary that one chip of a deployment holds. Float32 at
``highest`` matmul precision, whole sequences, no cache, no weight
absorption, every held expert applied plainly to every token.

Per layer, with ``x^ = RMSNorm(x)``:

- attention: ``q = x^ W_q`` -> heads of ``[q_nope | q_rope]``;
  ``[c | k_r] = x^ W_kv_a``; ``c <- RMSNorm(c)``; ``q_rope`` and ``k_r``
  get rotary positions (rotate-half, YaRN frequencies), ``k_r`` shared
  by all heads; ``[k_nope_h | v_h] = c W_kv_b``; ``score_h(t, s) =
  (q_nope_h . k_nope_h + q_rope_h . k_r) * sigma``, causal softmax,
  ``out = concat_h(sum_s p v_h) W_o``;
- layers before ``first_k_dense_replace``: a dense SwiGLU of
  ``intermediate_size``;
- the others: ``s = sigmoid(x^ W_r)`` over the router's published
  width; ``T = top_k(s + b)``; ``g_i = routed_scaling_factor * s_i /
  sum_{j in T} s_j``; ``y = sum_{i in T, i held} g_i E_i(x^) +
  E_shared(x^)``, ``E(z) = W_down(silu(W_gate z) * W_up z)``. What the
  experts held elsewhere would add is left out.

Weights are made here from a seed, layer by layer, in the type they are
served in, so that a server and this reference can each make the same
values without handing anything to one another. Imports nothing of
``tpudl``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

INIT_STD = 0.02
ATTENTION_MATRICES = ("q_proj", "kv_a_proj", "kv_b_proj", "o_proj")
DENSE_MATRICES = ("gate_proj", "up_proj", "down_proj")
MOE_MATRICES = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")


def settings(cfg: dict) -> dict:
    """The scalars the forward pass reads, from a configuration file:
    the public keys, the router's published width and the first expert
    held (``deployment``), YaRN's parameters flattened."""
    yarn = cfg["rope_scaling"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "first_k_dense_replace": cfg["first_k_dense_replace"],
        "num_experts": cfg["num_experts"],
        "router_experts": cfg["deployment"]["router_experts"],
        "first_expert": cfg["deployment"]["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "num_shared_experts": cfg["num_shared_experts"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_theta"],
        "yarn_factor": yarn["factor"],
        "yarn_original": yarn["original_max_position_embeddings"],
        "yarn_beta_fast": yarn["beta_fast"],
        "yarn_beta_slow": yarn["beta_slow"],
        "yarn_mscale": yarn["mscale"],
        "yarn_mscale_all_dim": yarn["mscale_all_dim"],
    }


def _shapes(s: dict, layer: int) -> dict:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    r, dn = s["kv_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    out = {
        "q_proj": (h, heads * (dn + dr)), "kv_a_proj": (h, r + dr),
        "kv_b_proj": (r, heads * (dn + dv)), "o_proj": (heads * dv, h),
    }
    if layer < s["first_k_dense_replace"]:
        f = s["intermediate_size"]
        out.update(gate_proj=(h, f), up_proj=(h, f), down_proj=(f, h))
    else:
        f, e = s["moe_intermediate_size"], s["num_experts"]
        fs = f * s["num_shared_experts"]
        out.update(
            router=(h, s["router_experts"]),
            router_bias=(s["router_experts"],),
            experts_gate=(e, h, f), experts_up=(e, h, f),
            experts_down=(e, f, h),
            shared_gate=(h, fs), shared_up=(h, fs), shared_down=(fs, h),
        )
    return out


def _normal(key, shape, dtype):
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_weights(root, layer: int, s: dict, dtype) -> dict:
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front)
    and norm scales. The router and its selection bias are float32: the
    choice of experts is made there."""
    key = jax.random.fold_in(root, 1 + layer)
    shapes = _shapes(s, layer)
    names = ATTENTION_MATRICES + (
        DENSE_MATRICES if layer < s["first_k_dense_replace"]
        else MOE_MATRICES
    )
    out = {}
    for i, name in enumerate(names):
        kind = jnp.float32 if name.startswith("router") else dtype
        out[name] = _normal(jax.random.fold_in(key, i), shapes[name], kind)
    out["input_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["post_attention_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["kv_norm"] = jnp.ones((s["kv_lora_rank"],), dtype)
    return out


def outer_weights(root, s: dict, dtype) -> dict:
    """Embedding table, final norm and output head over the vocabulary
    rows held here."""
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


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(s: dict):
    """[qk_rope_head_dim / 2] inverse frequencies: ``1 / theta_i`` where
    a frequency turns more than ``beta_fast`` times over the original
    context, ``1 / (factor theta_i)`` where it turns less than
    ``beta_slow`` times, a linear blend between the two dimensions."""
    dim, base = s["qk_rope_head_dim"], s["rope_theta"]
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


def softmax_scale(s: dict) -> float:
    """``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5 * mscale ** 2``."""
    m = _yarn_mscale(s["yarn_factor"], s["yarn_mscale_all_dim"])
    return (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, s: dict):
    """x: [..., S, H, D]; position = index along S."""
    d = x.shape[-1]
    amp = _yarn_mscale(s["yarn_factor"], s["yarn_mscale"]) / _yarn_mscale(
        s["yarn_factor"], s["yarn_mscale_all_dim"]
    )
    ang = jnp.arange(x.shape[-3], dtype=jnp.float32)[:, None] * yarn_inv_freq(s)
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(y, w, s: dict):
    """One sequence: y [S, hidden] (normed) -> [S, hidden]."""
    n, heads = y.shape[0], s["num_attention_heads"]
    r, dn = s["kv_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    q = (y @ w["q_proj"]).reshape(n, heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], s)
    down = y @ w["kv_a_proj"]
    c = _rms_norm(down[:, :r], w["kv_norm"], s["rms_norm_eps"])
    k_rope = _rope(down[:, None, r:], s)[:, 0]
    up = (c @ w["kv_b_proj"]).reshape(n, heads, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    score = jnp.einsum("shd,thd->hst", q_nope, k_nope)
    score = score + jnp.einsum("shd,td->hst", q_rope, k_rope)
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(
        jnp.where(causal[None], score * softmax_scale(s), -jnp.inf), axis=-1
    )
    ctx = jnp.einsum("hst,thd->shd", p, v).reshape(n, heads * dv)
    return ctx @ w["o_proj"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, router_experts] gates: ``g_i`` on the chosen experts, 0
    elsewhere."""
    scores = jax.nn.sigmoid(y @ w["router"])
    _, chosen = jax.lax.top_k(
        scores + w["router_bias"], s["num_experts_per_tok"]
    )
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = s["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(gates)


def experts(y, w, s: dict, shared: bool = True):
    """y [tokens, hidden] (normed) -> the held experts' part of the
    layer, plus the shared expert's unless ``shared`` is off."""
    first, held = s["first_expert"], s["num_experts"]
    gates = route(y, w, s)[:, first:first + held]

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
    if shared and s["num_shared_experts"]:
        out = out + _swiglu(y, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
    return out


def block(x, w, s: dict, layer: int, precision="highest"):
    """One layer on x: [B, S, hidden] float32, causal over S; a row at a
    time, so that the scores of one sequence are all that is held."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked else v.astype(jnp.float32)
             for k, v in w.items()}
        eps = s["rms_norm_eps"]

        def row(xr):
            xr = xr + attention(_rms_norm(xr, w["input_norm"], eps), w, s)
            y = _rms_norm(xr, w["post_attention_norm"], eps)
            if layer < s["first_k_dense_replace"]:
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
