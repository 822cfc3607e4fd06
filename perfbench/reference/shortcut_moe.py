"""Plain decoder of shortcut-connected double layers, as
``meituan-longcat/LongCat-Flash-Chat`` publishes it, for the share of
the experts and of the vocabulary that one chip of a deployment holds.
Float32 at ``highest`` matmul precision, whole sequences, no cache, no
weight absorption, every held expert applied plainly to every token.

One layer, ``h`` the residual stream, two sublayers ``i`` in {0, 1}
each with its own norms, latent attention ``A_i`` and dense SwiGLU
``F_i`` (``ffn_hidden_size``), and ONE expert branch read after the
first attention and added after the second FFN (the shortcut):

    a0 = h  + A_0(norm_in0(h))
    x0 = norm_post0(a0)
    m  = MoE(x0)
    b0 = a0 + F_0(x0)
    a1 = b0 + A_1(norm_in1(b0))
    h' = a1 + F_1(norm_post1(a1)) + m

- ``A(x)``: ``q = W_qb (RMSNorm(W_qa x) s_q)``, ``s_q = (hidden /
  q_lora_rank) ** 0.5``, heads of ``[q_nope | q_rope]``; ``[c' | k'] =
  W_kva x``, ``c = RMSNorm(c') s_kv``, ``s_kv = (hidden /
  kv_lora_rank) ** 0.5``; ``q_rope`` and ``k_r = RoPE(k')`` get rotary
  positions (rotate-half, plain frequencies), ``k_r`` one head shared by
  all and not scaled; ``[k_nope_h | v_h] = W_kvb c``; ``score_h(t, s) =
  (q_nope_h . k_nope_h + q_rope_h . k_r) (dn + dr) ** -0.5``, causal
  softmax, ``out = W_o concat_h(sum_s p v_h)``.
- ``MoE(x)``: ``p = softmax(W_r x)`` over the router's published width
  (routed + identity experts); ``T = top_k(p + b)``; ``g_i =
  routed_scaling_factor p_i`` for ``i`` in ``T``, NOT renormalised;
  ``m = sum_{i in T, i held} g_i E_i(x) + (sum_{i in T, i identity}
  g_i) x``, ``E(z) = W_down(silu(W_gate z) * W_up z)``; no shared
  expert. What the routed experts held elsewhere would add is left
  out; the identity term is whole (it needs no weights).

Weights are made here from a seed, layer by layer, in the type they are
served in, so that a server and this reference can each make the same
values without handing anything to one another. Imports nothing of
``tpudl``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

INIT_STD = 0.02
#: The selection bias: a tenth of the score at which a choice is made or
#: not (about 0.01 at 768 outputs and logits of deviation 1.6), so that
#: it decides the closest choices and no expert is chosen by it alone.
ROUTER_BIAS_STD = 1e-3
SUBLAYER_MATRICES = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj",
                     "o_proj", "gate_proj", "up_proj", "down_proj")
SUBLAYER_NORMS = ("input_norm", "post_attention_norm", "q_norm", "kv_norm")
MOE_MATRICES = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down")


def settings(cfg: dict) -> dict:
    """The scalars the forward pass reads, from a configuration file:
    the public keys, and from ``deployment`` the router's published
    width, the routed experts it names (ids past them are identity
    experts) and the first expert held."""
    h = cfg["hidden_size"]
    d = cfg["deployment"]
    return {
        "hidden_size": h,
        "num_attention_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "q_lora_rank": cfg["q_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "scale_q": (h / cfg["q_lora_rank"]) ** 0.5
        if cfg["mla_scale_q_lora"] else 1.0,
        "scale_kv": (h / cfg["kv_lora_rank"]) ** 0.5
        if cfg["mla_scale_kv_lora"] else 1.0,
        "ffn_hidden_size": cfg["ffn_hidden_size"],
        "expert_ffn_hidden_size": cfg["expert_ffn_hidden_size"],
        "num_layers": cfg["num_layers"],
        "experts_held": cfg["n_routed_experts"],
        "first_expert": d["first_expert"],
        "routed_experts": d["routed_experts"],
        "router_experts": d["router_experts"],
        "moe_topk": cfg["moe_topk"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_theta"],
    }


def _shapes(s: dict) -> dict:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    r, rq, dn = s["kv_lora_rank"], s["q_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    f, fe, e = (s["ffn_hidden_size"], s["expert_ffn_hidden_size"],
                s["experts_held"])
    return {
        "q_a_proj": (h, rq), "q_b_proj": (rq, heads * (dn + dr)),
        "kv_a_proj": (h, r + dr), "kv_b_proj": (r, heads * (dn + dv)),
        "o_proj": (heads * dv, h),
        "gate_proj": (h, f), "up_proj": (h, f), "down_proj": (f, h),
        "input_norm": (h,), "post_attention_norm": (h,),
        "q_norm": (rq,), "kv_norm": (r,),
        "router": (h, s["router_experts"]),
        "router_bias": (s["router_experts"],),
        "experts_gate": (e, h, fe), "experts_up": (e, h, fe),
        "experts_down": (e, fe, h),
    }


def _normal(key, shape, dtype, std=INIT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_weights(root, layer: int, s: dict, dtype) -> dict:
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front)
    and norm scales: a sublayer's under ``<name>_<i>``, the expert
    branch's under its own names. The router and its selection bias are
    float32: the choice of experts is made there."""
    key = jax.random.fold_in(root, 1 + layer)
    shapes = _shapes(s)
    out = {}
    for i in (0, 1):
        sub = jax.random.fold_in(key, i)
        for j, name in enumerate(SUBLAYER_MATRICES):
            out[f"{name}_{i}"] = _normal(
                jax.random.fold_in(sub, j), shapes[name], dtype
            )
        for name in SUBLAYER_NORMS:
            out[f"{name}_{i}"] = jnp.ones(shapes[name], dtype)
    moe = jax.random.fold_in(key, 2)
    for j, name in enumerate(MOE_MATRICES):
        router = name.startswith("router")
        out[name] = _normal(
            jax.random.fold_in(moe, j), shapes[name],
            jnp.float32 if router else dtype,
            ROUTER_BIAS_STD if name == "router_bias" else INIT_STD,
        )
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
                   for i in range(s["num_layers"])],
    }


# -- the forward pass --------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, s: dict):
    """x: [..., S, H, D]; position = index along S; rotate-half."""
    d = x.shape[-1]
    inv_freq = 1.0 / s["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d
    )
    ang = jnp.arange(x.shape[-3], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(y, w, i: int, s: dict):
    """Sublayer ``i`` on one sequence: y [S, hidden] (normed) ->
    [S, hidden]."""
    n, heads = y.shape[0], s["num_attention_heads"]
    r, dn = s["kv_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    eps = s["rms_norm_eps"]
    low = _rms_norm(y @ w[f"q_a_proj_{i}"], w[f"q_norm_{i}"], eps)
    q = ((low * s["scale_q"]) @ w[f"q_b_proj_{i}"]).reshape(
        n, heads, dn + dr
    )
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], s)
    down = y @ w[f"kv_a_proj_{i}"]
    c = _rms_norm(down[:, :r], w[f"kv_norm_{i}"], eps) * s["scale_kv"]
    k_rope = _rope(down[:, None, r:], s)[:, 0]
    up = (c @ w[f"kv_b_proj_{i}"]).reshape(n, heads, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    score = jnp.einsum("shd,thd->hst", q_nope, k_nope)
    score = score + jnp.einsum("shd,td->hst", q_rope, k_rope)
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(
        jnp.where(causal[None], score * (dn + dr) ** -0.5, -jnp.inf), axis=-1
    )
    ctx = jnp.einsum("hst,thd->shd", p, v).reshape(n, heads * dv)
    return ctx @ w[f"o_proj_{i}"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, router_experts] gates: ``g_i`` on the chosen experts, 0
    elsewhere."""
    scores = jax.nn.softmax(y @ w["router"], axis=-1)
    _, chosen = jax.lax.top_k(scores + w["router_bias"], s["moe_topk"])
    gates = s["routed_scaling_factor"] * jnp.take_along_axis(
        scores, chosen, axis=-1
    )
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(gates)


def experts(y, w, s: dict, identity: bool = True):
    """y [tokens, hidden] (normed) -> the held experts' part of the
    branch, plus the identity experts' unless ``identity`` is off (a
    deployment adds that term once, not once a share)."""
    first, held = s["first_expert"], s["experts_held"]
    gates = route(y, w, s)

    def one(total, args):
        gate, up, down, g = args
        return total + g[:, None] * _swiglu(
            y, gate.astype(jnp.float32), up.astype(jnp.float32),
            down.astype(jnp.float32),
        ), None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (w["experts_gate"], w["experts_up"], w["experts_down"],
         gates[:, first:first + held].T),
    )
    if identity:
        out = out + gates[:, s["routed_experts"]:].sum(-1, keepdims=True) * y
    return out


def block(x, w, s: dict, precision="highest"):
    """One double layer on x: [B, S, hidden] float32, causal over S; a
    row at a time, so that the scores of one sequence are all that is
    held."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked else v.astype(jnp.float32)
             for k, v in w.items()}
        eps = s["rms_norm_eps"]

        def ffn(y, i):
            return _swiglu(y, w[f"gate_proj_{i}"], w[f"up_proj_{i}"],
                           w[f"down_proj_{i}"])

        def row(h):
            a0 = h + attention(
                _rms_norm(h, w["input_norm_0"], eps), w, 0, s)
            x0 = _rms_norm(a0, w["post_attention_norm_0"], eps)
            m = experts(x0, w, s)
            b0 = a0 + ffn(x0, 0)
            a1 = b0 + attention(
                _rms_norm(b0, w["input_norm_1"], eps), w, 1, s)
            x1 = _rms_norm(a1, w["post_attention_norm_1"], eps)
            return a1 + ffn(x1, 1) + m

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
    for i in range(s["num_layers"]):
        x = _layer_jit(key, i, x, frozen(s), dtype, precision)
    return x, outer


def logits(key, cfg: dict, dtype, ids, precision="highest"):
    """[B, S, vocabulary held] logits of whole sequences."""
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


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer_jit(key, layer, x, s_items, dtype, precision):
    # ``layer`` is traced: every layer is alike, so one program serves
    # them all.
    s = dict(s_items)
    return block(x, layer_weights(key, layer, s, dtype), s, precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _margin_jit(x, outer, picks, chosen, s_items, precision):
    def row(args):
        xr, pr, cr = args
        logits = head(xr[pr], outer, dict(s_items), precision)
        got = jnp.take_along_axis(logits, cr[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(row, (x, picks, chosen))
