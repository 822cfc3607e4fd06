"""Plain decoder with a residual stream of several vectors a token mixed
by manifold-constrained hyper-connections (arXiv:2512.24880), latent
attention (MLA) with a low-rank query, and sigmoid-routed experts beside
a shared one, as ``XingChen-AGI/Xing4.0-29B-A4B`` publishes it
(``model_type`` ``xing4_0``). Float32 at ``highest`` matmul precision,
whole sequences, no cache, no weight absorption, no kernel, every expert
applied plainly to every token under its gate (0 where the token did not
choose it).

A token's stream is ``X`` in ``R^{n x d}``, ``n = hc_mult``. The
embedding is repeated into the ``n`` streams. Every layer has two
sublayers ``F`` (attention; then a dense SwiGLU in the layers before
``first_k_dense_replace``, the expert layer in the others), each with
its own ``phi`` in ``R^{nd x (2n + n^2)}``, ``b`` in ``R^{2n + n^2}``
and scalars ``alpha_pre``, ``alpha_post``, ``alpha_res``:

- ``x^ = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)``, no learned
  scale; ``[h~_pre | h~_post | h~_res] = x^ phi``, each part times its
  ``alpha``, plus ``b``.
- ``h_pre = sigmoid(h~_pre)``; ``h_post = 2 sigmoid(h~_post)``; ``M =
  exp(clamp(mat(h~_res), mhc_h_res_clamp_min, mhc_h_res_clamp_max))``
  (``mat`` row-major: ``M[i, j]`` is what stream ``i`` takes of stream
  ``j``), then ``hc_sinkhorn_iters`` times: every column over (its sum +
  ``hc_eps``), then every row over (its sum + ``hc_eps``); ``H_res =
  M``.
- ``u = sum_i h_pre[i] X_i``; ``y = F(RMSNorm_w(u))`` with the layer's
  ``input_norm`` (attention) or ``post_attention_norm`` (FFN / experts);
  ``X'_i = sum_j H_res[i, j] X_j + h_post[i] y``.
- after the last layer ``h = sum_i X_i`` goes through the final norm and
  the head.
- attention: ``q = W_qb RMSNorm(W_qa x^)`` -> heads of ``[q_nope |
  q_rope]``; ``[c | k_r] = W_kva x^``; ``c <- RMSNorm(c)``; ``q_rope``
  and ``k_r`` get rotary positions (rotate-half, YaRN frequencies),
  ``k_r`` shared by all heads; ``[k_nope_h | v_h] = W_kvb c``;
  ``score_h(t, s) = (q_nope_h . k_nope_h + q_rope_h . k_r) sigma``,
  ``sigma = (dn + dr) ** -0.5 (0.1 mscale_all_dim ln(factor) + 1)^2``,
  causal softmax, ``out = W_o concat_h(sum_s p v_h)``.
- experts: ``s = sigmoid(W_r x^)``; ``T = top_k(s + b_r)``; ``g_i =
  routed_scaling_factor s_i / sum_{j in T} s_j``; ``y = sum_{i in T} g_i
  E_i(x^) + E_shared(x^)``, ``E(z) = W_down(silu(W_gate z) * W_up z)``.

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

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

INIT_STD = 0.02
#: The selection bias of the router: a hundredth of a sigmoid score's
#: spread, so that it decides the closest choices only.
ROUTER_BIAS_STD = 1e-3
ATTENTION_MATRICES = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj",
                      "o_proj")
DENSE_MATRICES = ("gate_proj", "up_proj", "down_proj")
MOE_MATRICES = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")
#: A layer's two hyper-connections, by the sublayer each is around.
SUBLAYERS = ("attention", "mlp")
#: How the maps' parameters are drawn (the configuration file's
#: ``assumed`` says why): ``phi`` as every matrix, so that ``x^ phi``
#: has deviation 0.02 sqrt(n d) = 2.4 at the published widths; each
#: ``alpha`` 0.4, so that a map's logits move by about 1 from token to
#: token; ``b`` normal(0, 0.5) on every entry, plus 2 on the diagonal of
#: the ``h~_res`` part, so that a stream keeps most of itself and what
#: it hands on differs by token.
HYPER_ALPHA = 0.4
HYPER_BIAS_STD = 0.5
HYPER_DIAGONAL = 2.0
#: Queries attended at once (the largest of these that divides the
#: sequence).
QUERY_BLOCKS = (256, 128, 64, 32, 16, 8, 4, 2, 1)


def settings(cfg: dict) -> dict:
    """The scalars the forward pass reads, from a configuration file:
    the public keys, YaRN's parameters flattened."""
    yarn = cfg["rope_scaling"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "q_lora_rank": cfg["q_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "num_hidden_layers": cfg["num_hidden_layers"],
        "first_k_dense_replace": cfg["first_k_dense_replace"],
        "n_routed_experts": cfg["n_routed_experts"],
        "n_shared_experts": cfg["n_shared_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_theta"],
        "hc_mult": cfg["hc_mult"],
        "hc_sinkhorn_iters": cfg["hc_sinkhorn_iters"],
        "hc_eps": cfg["hc_eps"],
        "hc_clamp_min": cfg["mhc_h_res_clamp_min"],
        "hc_clamp_max": cfg["mhc_h_res_clamp_max"],
        "yarn_factor": yarn["factor"],
        "yarn_original": yarn["original_max_position_embeddings"],
        "yarn_beta_fast": yarn["beta_fast"],
        "yarn_beta_slow": yarn["beta_slow"],
        "yarn_mscale": yarn["mscale"],
        "yarn_mscale_all_dim": yarn["mscale_all_dim"],
    }


def is_dense(s: dict, layer: int) -> bool:
    """Whether ``layer`` keeps the dense SwiGLU (the leading layers)."""
    return layer < s["first_k_dense_replace"]


def _shapes(s: dict, dense: bool) -> dict:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    r, rq, dn = s["kv_lora_rank"], s["q_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    out = {
        "q_a_proj": (h, rq), "q_b_proj": (rq, heads * (dn + dr)),
        "kv_a_proj": (h, r + dr), "kv_b_proj": (r, heads * (dn + dv)),
        "o_proj": (heads * dv, h),
    }
    if dense:
        f = s["intermediate_size"]
        out.update(gate_proj=(h, f), up_proj=(h, f), down_proj=(f, h))
    else:
        f, e = s["moe_intermediate_size"], s["n_routed_experts"]
        fs = f * s["n_shared_experts"]
        out.update(
            router=(h, e), router_bias=(e,),
            experts_gate=(e, h, f), experts_up=(e, h, f),
            experts_down=(e, f, h),
            shared_gate=(h, fs), shared_up=(h, fs), shared_down=(fs, h),
        )
    return out


def _normal(key, shape, dtype, std=INIT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def hyper_weights(key, s: dict) -> dict:
    """One hyper-connection's ``phi``, ``b`` and ``alpha`` (float32:
    the maps are made there, as a router's choice is)."""
    n = s["hc_mult"]
    width = 2 * n + n * n
    diagonal = jnp.concatenate(
        [jnp.zeros((2 * n,)), HYPER_DIAGONAL * jnp.eye(n).reshape(-1)]
    )
    return {
        "phi": _normal(jax.random.fold_in(key, 0),
                       (n * s["hidden_size"], width), jnp.float32),
        "b": diagonal + _normal(jax.random.fold_in(key, 1), (width,),
                                jnp.float32, HYPER_BIAS_STD),
        "alpha": jnp.full((3,), HYPER_ALPHA, jnp.float32),
    }


def layer_weights(root, layer, s: dict, dtype, dense: bool) -> dict:
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front),
    norm scales, and its two hyper-connections under ``hyper_<sublayer>``.
    The router and its selection bias are float32: the choice of experts
    is made there. ``layer`` may be traced; ``dense`` (``is_dense``)
    says which kind it is."""
    key = jax.random.fold_in(root, 1 + layer)
    shapes = _shapes(s, dense)
    names = ATTENTION_MATRICES + (DENSE_MATRICES if dense else MOE_MATRICES)
    out = {}
    for i, name in enumerate(names):
        router = name.startswith("router")
        out[name] = _normal(
            jax.random.fold_in(key, i), shapes[name],
            jnp.float32 if router else dtype,
            ROUTER_BIAS_STD if name == "router_bias" else INIT_STD,
        )
    out["input_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["post_attention_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["q_norm"] = jnp.ones((s["q_lora_rank"],), dtype)
    out["kv_norm"] = jnp.ones((s["kv_lora_rank"],), dtype)
    for i, name in enumerate(SUBLAYERS):
        out[f"hyper_{name}"] = hyper_weights(
            jax.random.fold_in(key, 100 + i), s
        )
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
        "layers": [layer_weights(key, i, s, dtype, is_dense(s, i))
                   for i in range(s["num_hidden_layers"])],
    }


# -- the forward pass --------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def sinkhorn(m, iters: int, eps: float):
    """Columns over (their sums + eps), then rows over (theirs + eps),
    ``iters`` times. m: [..., n, n]."""
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def hyper_maps(x, w, s: dict):
    """x: [S, n, d], one sequence's stream -> ``(h_pre [S, n], h_post
    [S, n], H_res [S, n, n])`` of one sublayer's ``w = {phi, b,
    alpha}``."""
    n = s["hc_mult"]
    flat = x.reshape(x.shape[0], -1)
    normed = flat * jax.lax.rsqrt(
        jnp.mean(flat * flat, -1, keepdims=True) + s["rms_norm_eps"]
    )
    raw = normed @ w["phi"]
    pre = w["alpha"][0] * raw[:, :n] + w["b"][:n]
    post = w["alpha"][1] * raw[:, n:2 * n] + w["b"][n:2 * n]
    res = w["alpha"][2] * raw[:, 2 * n:] + w["b"][2 * n:]
    m = jnp.exp(jnp.clip(
        res.reshape(-1, n, n), s["hc_clamp_min"], s["hc_clamp_max"]
    ))
    return (
        jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
        sinkhorn(m, s["hc_sinkhorn_iters"], s["hc_eps"]),
    )


def hyper_sublayer(x, w, norm, fn, s: dict):
    """``X' = H_res X + h_post F(RMSNorm_w(h_pre^T X))^T`` on one
    sequence's stream x: [S, n, d]."""
    h_pre, h_post, h_res = hyper_maps(x, w, s)
    u = jnp.einsum("sn,snd->sd", h_pre, x)
    y = fn(_rms_norm(u, norm, s["rms_norm_eps"]))
    kept = jnp.einsum("sij,sjd->sid", h_res, x)
    return kept + h_post[:, :, None] * y[:, None]


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
    """x: [..., S, H, D]; position = index along S; rotate-half."""
    d = x.shape[-1]
    amp = _yarn_mscale(s["yarn_factor"], s["yarn_mscale"]) / _yarn_mscale(
        s["yarn_factor"], s["yarn_mscale_all_dim"]
    )
    at = jnp.arange(x.shape[-3], dtype=jnp.float32)
    ang = at[:, None] * yarn_inv_freq(s)
    cos, sin = amp * jnp.cos(ang)[:, None, :], amp * jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(y, w, s: dict):
    """One sequence: y [S, hidden] (normed) -> [S, hidden]; a block of
    queries at a time against every key, under the causal mask."""
    n, heads = y.shape[0], s["num_attention_heads"]
    r, dn = s["kv_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    eps = s["rms_norm_eps"]
    low = _rms_norm(y @ w["q_a_proj"], w["q_norm"], eps)
    q = (low @ w["q_b_proj"]).reshape(n, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], s)], axis=-1)
    down = y @ w["kv_a_proj"]
    c = _rms_norm(down[:, :r], w["kv_norm"], eps)
    k_rope = _rope(down[:, None, r:], s)[:, 0]
    up = (c @ w["kv_b_proj"]).reshape(n, heads, dn + dv)
    k_nope, v = up[..., :dn], up[..., dn:]
    block = next(b for b in QUERY_BLOCKS if n % b == 0)
    key_at = jnp.arange(n)[None, :]

    def some_queries(args):
        qb, at = args  # [block, heads, dn + dr], the first query's position
        score = jnp.einsum("shd,thd->hst", qb[..., :dn], k_nope)
        score = score + jnp.einsum("shd,td->hst", qb[..., dn:], k_rope)
        seen = key_at <= at + jnp.arange(block)[:, None]
        p = jax.nn.softmax(
            jnp.where(seen[None], score * softmax_scale(s), -jnp.inf), axis=-1
        )
        return jnp.einsum("hst,thd->shd", p, v)

    ctx = jax.lax.map(
        some_queries,
        (q.reshape(n // block, block, heads, dn + dr),
         jnp.arange(0, n, block)),
    )
    return ctx.reshape(n, heads * dv) @ w["o_proj"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, n_routed_experts] gates: ``g_i`` on the chosen experts,
    0 elsewhere."""
    scores = jax.nn.sigmoid(y @ w["router"])
    _, chosen = jax.lax.top_k(
        scores + w["router_bias"], s["num_experts_per_tok"]
    )
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = s["routed_scaling_factor"] * picked / picked.sum(-1, keepdims=True)
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(gates)


def experts(y, w, s: dict):
    """y [tokens, hidden] (normed) -> the routed experts' sum under
    their gates, plus the shared expert's."""
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
    return out + _swiglu(y, w["shared_gate"], w["shared_up"], w["shared_down"])


def block(x, w, s: dict, dense: bool, precision="highest"):
    """One layer on x: [B, S, n, hidden] float32, causal over S; a row
    at a time, so that the scores of one block of one sequence are all
    that is held."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked or isinstance(v, dict)
             else v.astype(jnp.float32) for k, v in w.items()}

        def ffn(y):
            if dense:
                return _swiglu(y, w["gate_proj"], w["up_proj"], w["down_proj"])
            return experts(y, w, s)

        def row(xr):
            xr = hyper_sublayer(
                xr, w["hyper_attention"], w["input_norm"],
                lambda y: attention(y, w, s), s,
            )
            return hyper_sublayer(
                xr, w["hyper_mlp"], w["post_attention_norm"], ffn, s
            )

        return jax.lax.map(row, x)


def head(x, outer, s: dict, precision="highest"):
    """Logits of hidden states x: [..., hidden]."""
    with jax.default_matmul_precision(precision):
        y = _rms_norm(x, outer["final_norm"].astype(jnp.float32),
                      s["rms_norm_eps"])
        return y @ outer["lm_head"].astype(jnp.float32)


def forward(key, cfg: dict, dtype, ids, precision="highest"):
    """The streams' sum before the final norm, [B, S, hidden]; layer by
    layer, each layer's weights made from the seed and dropped."""
    s = settings(cfg)
    outer = _outer_jit(key, frozen(s), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    x = jnp.repeat(x[:, :, None], s["hc_mult"], axis=2)
    for i in range(s["num_hidden_layers"]):
        x = _layer_jit(key, i, x, frozen(s), dtype, precision, is_dense(s, i))
    return x.sum(axis=2), outer


def logits(key, cfg: dict, dtype, ids, precision="highest"):
    """[B, S, vocabulary] logits of whole sequences."""
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


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _layer_jit(key, layer, x, s_items, dtype, precision, dense):
    # ``layer`` is traced: the expert layers are alike, so one program
    # serves them all, and one more the leading dense ones.
    s = dict(s_items)
    w = layer_weights(key, layer, s, dtype, dense)
    return block(x, w, s, dense, precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _margin_jit(x, outer, picks, chosen, s_items, precision):
    def row(args):
        xr, pr, cr = args
        logits = head(xr[pr], outer, dict(s_items), precision)
        got = jnp.take_along_axis(logits, cr[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(row, (x, picks, chosen))
