"""Plain decoder with latent attention (MLA, low-rank query) under
learned sparse attention, and sigmoid-routed experts beside a shared
one, as ``zai-org/GLM-5.2`` publishes it (``model_type``
``glm_moe_dsa``), for the share of the experts and of the vocabulary
that one chip of a deployment holds. Float32 at ``highest`` matmul
precision, whole sequences, no cache, no weight absorption, no kernel,
every held expert applied plainly to every token under its gate.

One layer ``l`` on ``h`` [S, hidden], query position ``t`` (pre-norm
residual):

    x   = RMSNorm(h)
    cQ  = RMSNorm(W_qa x)
    q   = W_qb cQ -> heads x [nope | rope];  q_rope = RoPE(q_rope, t)
    [c | k_r] = W_kva x;  c = RMSNorm(c);  k_r = RoPE(k_r, t)

    if indexer_types[l] == "full":
        qI = W_qI cQ -> index_n_heads x index_head_dim, RoPE on the
             FIRST qk_rope_head_dim values of each head
        kI = LayerNorm(W_kI x) (scale and bias, eps 1e-6), RoPE on its
             first qk_rope_head_dim values: ONE key a position
        w  = W_w x  [index_n_heads]
        I[t, s] = sum_j w_j relu(qI_j . kI_s)
                  * index_head_dim ** -0.5 * index_n_heads ** -0.5
        S_t = the index_topk positions s <= t of largest I[t, s]
              (all of 0..t while t < index_topk; a tie goes to the
              lower position, as ``jax.lax.top_k`` breaks it)
    else ("shared"):
        S_t = S_t of the nearest earlier "full" layer, handed on

    [k_nope_h | v_h](s) = W_kvb c_s
    p_h(s) = softmax over s in S_t ONLY of
             (q_nope_h . k_nope_h(s) + q_rope_h . k_r(s)) * (dn + dr) ** -0.5
    h'  = h + W_o concat_h(sum_{s in S_t} p_h(s) v_h(s))
    y   = RMSNorm(h')
    dense layers:  h'' = h' + SwiGLU(y)
    expert layers: g = sigmoid(W_r y) (float32, the router's published
        width); E = top_k(g + b); gate_e = routed_scaling_factor g_e /
        sum_{e' in E} g_e'; h'' = h' + SwiGLU_shared(y)
        + sum_{e in E, e held} gate_e SwiGLU_e(y).
        What the experts held elsewhere would add is left out.

Logits: ``W_head RMSNorm(h_last)`` over the vocabulary rows held here.

Departures from the published inference code, each also a line of the
configuration file's ``assumed``: the rotary pair is rotate-half, not
interleaved (``rope_interleave``, ``indexer_rope_interleave``: a
permutation of weight columns, nothing on seeded weights); the
Hadamard rotation of the indexer's queries and keys and their fp8
storage are left out (an orthogonal map of both sides changes no dot
product in exact arithmetic; fp8 is a storage format); the
multi-token-prediction layer is not built.

Queries are taken a block at a time so that one block's scores are all
that is held; the choice is carried from a "full" layer to the "shared"
ones as a mask [S, S]. Weights are made here from a seed, layer by
layer, in the type they are served in, so that a server and this
reference can each make the same values without handing anything to
one another. Imports nothing of ``tpudl``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

INIT_STD = 0.02
#: The selection bias of the router: a hundredth of a sigmoid score's
#: spread, so that it decides the closest choices only.
ROUTER_BIAS_STD = 1e-3
INDEX_NORM_EPS = 1e-6
ATTENTION_MATRICES = ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj",
                      "o_proj")
INDEXER_MATRICES = ("index_q", "index_k", "index_w")
DENSE_MATRICES = ("gate_proj", "up_proj", "down_proj")
MOE_MATRICES = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down", "shared_gate", "shared_up", "shared_down")
#: Queries attended at once (the largest of these that divides the
#: sequence).
QUERY_BLOCKS = (256, 128, 64, 32, 16, 8, 4, 2, 1)


def settings(cfg: dict) -> dict:
    """The scalars the forward pass reads, from a configuration file:
    the public keys, the router's published width and the first expert
    held (``deployment``), and the two per-layer lists as strings (one
    letter a layer: ``f``ull / ``s``hared, ``d``ense / ``e``xperts) so
    that the settings stay hashable."""
    types = cfg["indexer_types"]
    mlps = cfg["mlp_layer_types"]
    layers = cfg["num_hidden_layers"]
    if len(types) != layers or len(mlps) != layers:
        raise ValueError(
            f"indexer_types ({len(types)}) and mlp_layer_types "
            f"({len(mlps)}) name each of the {layers} layers"
        )
    return {
        "hidden_size": cfg["hidden_size"],
        "num_attention_heads": cfg["num_attention_heads"],
        "kv_lora_rank": cfg["kv_lora_rank"],
        "q_lora_rank": cfg["q_lora_rank"],
        "qk_nope_head_dim": cfg["qk_nope_head_dim"],
        "qk_rope_head_dim": cfg["qk_rope_head_dim"],
        "v_head_dim": cfg["v_head_dim"],
        "index_n_heads": cfg["index_n_heads"],
        "index_head_dim": cfg["index_head_dim"],
        "index_topk": cfg["index_topk"],
        "indexer_types": "".join(t[0] for t in types),
        "mlp_layer_types": "".join(
            "d" if m == "dense" else "e" for m in mlps),
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "num_hidden_layers": layers,
        "n_routed_experts": cfg["n_routed_experts"],
        "router_experts": cfg["deployment"]["router_experts"],
        "first_expert": cfg["deployment"]["first_expert"],
        "n_shared_experts": cfg["n_shared_experts"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "routed_scaling_factor": cfg["routed_scaling_factor"],
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["rms_norm_eps"],
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
    }


def is_dense(s: dict, layer: int) -> bool:
    """Whether ``layer`` keeps the dense SwiGLU."""
    return s["mlp_layer_types"][layer] == "d"


def has_indexer(s: dict, layer: int) -> bool:
    """Whether ``layer`` makes a choice of its own ("full")."""
    return s["indexer_types"][layer] == "f"


def _shapes(s: dict, dense: bool) -> dict:
    h, heads = s["hidden_size"], s["num_attention_heads"]
    r, rq, dn = s["kv_lora_rank"], s["q_lora_rank"], s["qk_nope_head_dim"]
    dr, dv = s["qk_rope_head_dim"], s["v_head_dim"]
    hi, di = s["index_n_heads"], s["index_head_dim"]
    out = {
        "q_a_proj": (h, rq), "q_b_proj": (rq, heads * (dn + dr)),
        "kv_a_proj": (h, r + dr), "kv_b_proj": (r, heads * (dn + dv)),
        "o_proj": (heads * dv, h),
        "index_q": (rq, hi * di), "index_k": (h, di), "index_w": (h, hi),
    }
    if dense:
        f = s["intermediate_size"]
        out.update(gate_proj=(h, f), up_proj=(h, f), down_proj=(f, h))
    else:
        f, e = s["moe_intermediate_size"], s["n_routed_experts"]
        fs = f * s["n_shared_experts"]
        out.update(
            router=(h, s["router_experts"]),
            router_bias=(s["router_experts"],),
            experts_gate=(e, h, f), experts_up=(e, h, f),
            experts_down=(e, f, h),
            shared_gate=(h, fs), shared_up=(h, fs), shared_down=(fs, h),
        )
    return out


def _normal(key, shape, dtype, std=INIT_STD):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_weights(root, layer, s: dict, dtype, dense: bool,
                  indexer: bool) -> dict:
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front)
    and norm scales; with ``indexer`` the indexer's three matrices and
    its LayerNorm (scale 1, bias 0). The router and its selection bias
    are float32: the choice of experts is made there. ``layer`` may be
    traced; ``dense`` and ``indexer`` say which kind it is."""
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
    if indexer:
        for i, name in enumerate(INDEXER_MATRICES):
            out[name] = _normal(
                jax.random.fold_in(key, 200 + i), shapes[name], dtype)
        out["index_k_norm"] = jnp.ones((s["index_head_dim"],), dtype)
        out["index_k_bias"] = jnp.zeros((s["index_head_dim"],), dtype)
    out["input_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["post_attention_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["q_norm"] = jnp.ones((s["q_lora_rank"],), dtype)
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
        "layers": [
            layer_weights(key, i, s, dtype, is_dense(s, i), has_indexer(s, i))
            for i in range(s["num_hidden_layers"])
        ],
    }


# -- the forward pass --------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def softmax_scale(s: dict) -> float:
    return (s["qk_nope_head_dim"] + s["qk_rope_head_dim"]) ** -0.5


def _rope(x, s: dict):
    """x: [S, H, D]; position = index along S; rotate-half over the
    FIRST ``qk_rope_head_dim`` values of D, the rest pass."""
    d = s["qk_rope_head_dim"]
    inv_freq = 1.0 / s["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:d]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., d:]], -1)


def index_terms(y, low, w, s: dict):
    """The indexer's queries [S, Hi, Di], keys [S, Di] and head weights
    [S, Hi] (the two constants folded in) of one sequence."""
    n, hi, di = y.shape[0], s["index_n_heads"], s["index_head_dim"]
    q = _rope((low @ w["index_q"]).reshape(n, hi, di), s)
    k = _layer_norm(y @ w["index_k"], w["index_k_norm"], w["index_k_bias"],
                    INDEX_NORM_EPS)
    k = _rope(k[:, None], s)[:, 0]
    return q, k, (y @ w["index_w"]) * di ** -0.5 * hi ** -0.5


def choose(scores, seen, k: int):
    """scores [block, S] float32, seen [block, S] bool (s <= t) -> bool
    [block, S]: each query's ``k`` best positions among those it sees,
    all of them where it sees no more than ``k``."""
    n = scores.shape[-1]
    if n <= k:
        return seen
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    rows = jnp.arange(scores.shape[0])[:, None]
    picked = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return picked & seen


def attention(y, w, s: dict, choice):
    """One sequence: y [S, hidden] (normed) -> ``([S, hidden], choice)``.
    ``choice`` [S, S] bool is the earlier "full" layer's; a layer with
    an indexer (``index_q`` among its weights) makes and returns its
    own. A block of queries at a time against every key."""
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
    starts = jnp.arange(0, n, block)

    def seen_from(at):
        return key_at <= at + jnp.arange(block)[:, None]

    if "index_q" in w:
        qi, ki, wi = index_terms(y, low, w, s)

        def some_choices(args):
            qb, wb, at = args  # [block, Hi, Di], [block, Hi]
            dots = jnp.einsum("shd,td->sht", qb, ki)
            scores = jnp.einsum("sht,sh->st", jax.nn.relu(dots), wb)
            return choose(scores, seen_from(at), s["index_topk"])

        choice = jax.lax.map(some_choices, (
            qi.reshape(n // block, block, *qi.shape[1:]),
            wi.reshape(n // block, block, -1), starts,
        )).reshape(n, n)

    def some_queries(args):
        qb, cb = args  # [block, heads, dn + dr], [block, S] bool
        score = jnp.einsum("shd,thd->hst", qb[..., :dn], k_nope)
        score = score + jnp.einsum("shd,td->hst", qb[..., dn:], k_rope)
        p = jax.nn.softmax(
            jnp.where(cb[None], score * softmax_scale(s), -jnp.inf), axis=-1
        )
        return jnp.einsum("hst,thd->shd", p, v)

    ctx = jax.lax.map(some_queries, (
        q.reshape(n // block, block, heads, dn + dr),
        choice.reshape(n // block, block, n),
    ))
    return ctx.reshape(n, heads * dv) @ w["o_proj"], choice


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, router_experts] gates: ``gate_e`` on the chosen experts,
    0 elsewhere."""
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
    first, held = s["first_expert"], s["n_routed_experts"]
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
    if shared and s["n_shared_experts"]:
        out = out + _swiglu(y, w["shared_gate"], w["shared_up"],
                            w["shared_down"])
    return out


def block(x, choice, w, s: dict, dense: bool, precision="highest"):
    """One layer on x: [B, S, hidden] float32 with the choice handed in
    [B, S, S] bool, causal over S; a row at a time, so that the scores
    of one block of one sequence are all that is held. -> ``(x,
    choice)``."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked else v.astype(jnp.float32)
             for k, v in w.items()}
        eps = s["rms_norm_eps"]

        def row(args):
            xr, cr = args
            a, cr = attention(_rms_norm(xr, w["input_norm"], eps), w, s, cr)
            xr = xr + a
            y = _rms_norm(xr, w["post_attention_norm"], eps)
            if dense:
                return xr + _swiglu(y, w["gate_proj"], w["up_proj"],
                                    w["down_proj"]), cr
            return xr + experts(y, w, s), cr

        return jax.lax.map(row, (x, choice))


def head(x, outer, s: dict, precision="highest"):
    """Logits of hidden states x: [..., hidden]."""
    with jax.default_matmul_precision(precision):
        y = _rms_norm(x, outer["final_norm"].astype(jnp.float32),
                      s["rms_norm_eps"])
        return y @ outer["lm_head"].astype(jnp.float32)


def forward(key, cfg: dict, dtype, ids, precision="highest"):
    """``(x, outer, choices)``: the hidden states before the final norm
    [B, S, hidden], and each "full" layer's choice [B, S, S] bool in
    layer order; layer by layer, each layer's weights made from the
    seed and dropped."""
    s = settings(cfg)
    outer = _outer_jit(key, frozen(s), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    choice = jnp.zeros((*ids.shape, ids.shape[1]), bool)
    choices = []
    for i in range(s["num_hidden_layers"]):
        x, choice = _layer_jit(
            key, i, x, choice, frozen(s), dtype, precision,
            is_dense(s, i), has_indexer(s, i),
        )
        if has_indexer(s, i):
            choices.append(choice)
    return x, outer, choices


def logits(key, cfg: dict, dtype, ids, precision="highest"):
    """[B, S, vocabulary] logits of whole sequences."""
    x, outer, _ = forward(key, cfg, dtype, ids, precision)
    return head(x, outer, settings(cfg), precision)


def margins(key, cfg: dict, dtype, ids, picks, chosen, precision="highest"):
    """By how much the reference's best logit beats each chosen token:
    ``perfbench.reference.decoder.margins``'s contract (``ids`` [B, S]
    prompts followed by the served tokens, right-padded; ``picks`` [B, T]
    positions whose logits chose a token; ``chosen`` [B, T] the token
    chosen there; returns [B, T] float32, 0 where the reference
    agrees)."""
    return margins_and_choices(
        key, cfg, dtype, ids, picks, chosen, precision)[0]


def margins_and_choices(key, cfg: dict, dtype, ids, picks, chosen,
                        precision="highest"):
    """``margins`` and, for each "full" layer in order, the positions
    the reference chose at ``picks``: bool [B, T, S]."""
    x, outer, choices = forward(key, cfg, dtype, ids, precision)
    rows = jnp.arange(ids.shape[0])[:, None]
    return (
        _margin_jit(x, outer, picks, chosen, frozen(settings(cfg)),
                    precision),
        [c[rows, picks] for c in choices],
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _outer_jit(key, s_items, dtype):
    return outer_weights(key, dict(s_items), dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _layer_jit(key, layer, x, choice, s_items, dtype, precision, dense,
               indexer):
    # ``layer`` is traced: layers of one kind are alike, so one program
    # serves them all.
    s = dict(s_items)
    w = layer_weights(key, layer, s, dtype, dense, indexer)
    return block(x, choice, w, s, dense, precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _margin_jit(x, outer, picks, chosen, s_items, precision):
    def row(args):
        xr, pr, cr = args
        logits = head(xr[pr], outer, dict(s_items), precision)
        got = jnp.take_along_axis(logits, cr[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(row, (x, picks, chosen))
