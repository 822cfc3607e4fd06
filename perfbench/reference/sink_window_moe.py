"""Plain decoder whose layers are of two kinds, as
``XiaomiMiMo/MiMo-V2-Flash`` publishes it (``model_type``
``mimo_v2_flash``): layers that keep the whole context beside layers
that see a sliding window, each kind with its own number of KV heads,
its own rotary base and (the window layers) a learned sink a query head
in the softmax; keys and queries wider than values; a leading dense
layer and sigmoid-routed SwiGLU experts after it, with no shared
expert, for the share of the experts and of the vocabulary that one
chip of a deployment holds. Float32 at ``highest`` matmul precision,
whole sequences, no cache, no ring, no kernel, every held expert
applied plainly to every token under its gate.

One layer ``l`` of kind ``K`` (``hybrid_layer_pattern[l]``: 0 full,
1 swa) on ``x`` [T, hidden]:

    h   = RMSNorm(x)
    q   = h W_q -> [T, H(K), d(K)]     k = h W_k -> [T, Hkv(K), d(K)]
    v   = attention_value_scale * (h W_v) -> [T, Hkv(K), dv(K)]
    q,k : the first int(partial_rotary_factor * d) values of every head
          rotate (rotate-half within them, the frequencies of a head
          that wide), theta ``rope_theta`` (full) / ``swa_rope_theta``
          (swa); the rest pass
    s[t,u] = q_t . k_u * d ** -0.5, query head j reads KV head
          j // (H / Hkv); visible: u <= t (full), 0 <= t - u <
          sliding_window (swa: the query itself counts)
    p[t,u] = exp(s[t,u]) / (SINK + sum_u' exp(s[t,u'])), SINK =
          exp(b_j) where the kind has a sink (``add_swa_attention_sink_
          bias`` / ``add_full_attention_sink_bias``; b_j one float32
          scalar a QUERY head: it takes probability and adds no value)
          and 0 where it has none
    x   = x + concat_j(sum_u p[t,u] v_u) W_o
    h'  = RMSNorm(x)
    dense layers (``moe_layer_freq[l]`` 0): x = x + SwiGLU(h')
    expert layers: s = sigmoid(h' W_r) (float32, the router's published
          width); E = top_k(s + e) (e the selection bias); g_i = s_i /
          sum_{j in E} s_j (``norm_topk_prob``) * routed_scaling_factor
          (null = 1); x = x + sum_{i in E, i held} g_i SwiGLU_i(h').
          What the experts held elsewhere would add is left out.

Logits: ``W_head RMSNorm(x_last)`` over the vocabulary rows held here.

Departures and choices the catalog's keys leave open are lines of the
configuration file's ``assumed``. Queries are taken a block at a time
so that one block's scores are all that is held. Weights are made here
from a seed, layer by layer, in the type they are served in, so that a
server and this reference can each make the same values without handing
anything to one another. Imports nothing of ``tpudl``.
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
#: The sinks: normal(SINK_MEAN, SINK_STD). With seeded weights a
#: window's scores are all near 0, so a sink near 0 would hold 1/129 of
#: the mass and a program without it would pass; at 3 +- 1 it holds
#: 5-50 % of a full window's mass and nearly all of a young sequence's.
SINK_MEAN, SINK_STD = 3.0, 1.0
ATTENTION_MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj")
DENSE_MATRICES = ("gate_proj", "up_proj", "down_proj")
MOE_MATRICES = ("router", "router_bias", "experts_gate", "experts_up",
                "experts_down")
#: Queries attended at once (the largest of these that divides the
#: sequence).
QUERY_BLOCKS = (256, 128, 64, 32, 16, 8, 4, 2, 1)


def settings(cfg: dict) -> dict:
    """The scalars the forward pass reads, from a configuration file:
    the public keys, the router's published width and the first expert
    held (``deployment``), and the two per-layer lists cut to the
    layers held, as strings (one letter a layer: ``f``ull / ``s``wa,
    ``d``ense / ``e``xperts) so that the settings stay hashable."""
    n = cfg["num_hidden_layers"]
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    if len(pattern) < n or len(freq) < n:
        raise ValueError(
            f"hybrid_layer_pattern ({len(pattern)}) and moe_layer_freq "
            f"({len(freq)}) name each of the {n} layers held"
        )
    scaling = cfg["routed_scaling_factor"]
    return {
        "hidden_size": cfg["hidden_size"],
        "num_hidden_layers": n,
        "layer_kinds": "".join("s" if k else "f" for k in pattern[:n]),
        "mlp_kinds": "".join("e" if k else "d" for k in freq[:n]),
        "heads_f": cfg["num_attention_heads"],
        "heads_s": cfg["swa_num_attention_heads"],
        "kv_heads_f": cfg["num_key_value_heads"],
        "kv_heads_s": cfg["swa_num_key_value_heads"],
        "head_dim_f": cfg["head_dim"],
        "head_dim_s": cfg["swa_head_dim"],
        "v_head_dim_f": cfg["v_head_dim"],
        "v_head_dim_s": cfg["swa_v_head_dim"],
        "partial_rotary_factor": cfg["partial_rotary_factor"],
        "theta_f": cfg["rope_theta"],
        "theta_s": cfg["swa_rope_theta"],
        "sliding_window": cfg["sliding_window"],
        "sink_f": int(bool(cfg["add_full_attention_sink_bias"])),
        "sink_s": int(bool(cfg["add_swa_attention_sink_bias"])),
        "attention_value_scale": cfg["attention_value_scale"],
        "intermediate_size": cfg["intermediate_size"],
        "moe_intermediate_size": cfg["moe_intermediate_size"],
        "n_routed_experts": cfg["n_routed_experts"],
        "router_experts": cfg["deployment"]["router_experts"],
        "first_expert": cfg["deployment"]["first_expert"],
        "num_experts_per_tok": cfg["num_experts_per_tok"],
        "norm_topk_prob": int(bool(cfg["norm_topk_prob"])),
        "routed_scaling_factor": 1.0 if scaling is None else scaling,
        "vocab_size": cfg["vocab_size"],
        "rms_norm_eps": cfg["layernorm_epsilon"],
    }


def kind_of(s: dict, layer: int) -> str:
    """``f`` (the whole context) or ``s`` (a sliding window)."""
    return s["layer_kinds"][layer]


def is_dense(s: dict, layer: int) -> bool:
    """Whether ``layer`` keeps the dense SwiGLU."""
    return s["mlp_kinds"][layer] == "d"


def rotary_dim(s: dict, kind: str) -> int:
    return int(s["partial_rotary_factor"] * s[f"head_dim_{kind}"])


def _shapes(s: dict, kind: str, dense: bool) -> dict:
    h = s["hidden_size"]
    heads, kv = s[f"heads_{kind}"], s[f"kv_heads_{kind}"]
    d, dv = s[f"head_dim_{kind}"], s[f"v_head_dim_{kind}"]
    out = {
        "q_proj": (h, heads * d), "k_proj": (h, kv * d),
        "v_proj": (h, kv * dv), "o_proj": (heads * dv, h),
    }
    if dense:
        f = s["intermediate_size"]
        out.update(gate_proj=(h, f), up_proj=(h, f), down_proj=(f, h))
    else:
        f, e = s["moe_intermediate_size"], s["n_routed_experts"]
        out.update(
            router=(h, s["router_experts"]),
            router_bias=(s["router_experts"],),
            experts_gate=(e, h, f), experts_up=(e, h, f),
            experts_down=(e, f, h),
        )
    return out


def _normal(key, shape, dtype, std=INIT_STD, mean=0.0):
    return (
        mean + std * jax.random.normal(key, shape, jnp.float32)
    ).astype(dtype)


def layer_weights(root, layer, s: dict, dtype, kind: str, dense: bool):
    """Layer ``layer``'s matrices ([in, out]; experts stacked in front),
    norm scales and, on a kind that has one, its sinks ``sink``
    [heads] float32. The router and its selection bias are float32:
    the choice of experts is made there. ``layer`` may be traced;
    ``kind`` and ``dense`` say which sort it is."""
    key = jax.random.fold_in(root, 1 + layer)
    shapes = _shapes(s, kind, dense)
    names = ATTENTION_MATRICES + (DENSE_MATRICES if dense else MOE_MATRICES)
    out = {}
    for i, name in enumerate(names):
        router = name.startswith("router")
        out[name] = _normal(
            jax.random.fold_in(key, i), shapes[name],
            jnp.float32 if router else dtype,
            ROUTER_BIAS_STD if name == "router_bias" else INIT_STD,
        )
    if s[f"sink_{kind}"]:
        out["sink"] = _normal(
            jax.random.fold_in(key, 100), (s[f"heads_{kind}"],),
            jnp.float32, SINK_STD, SINK_MEAN,
        )
    out["input_norm"] = jnp.ones((s["hidden_size"],), dtype)
    out["post_attention_norm"] = jnp.ones((s["hidden_size"],), dtype)
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
            layer_weights(key, i, s, dtype, kind_of(s, i), is_dense(s, i))
            for i in range(s["num_hidden_layers"])
        ],
    }


# -- the forward pass --------------------------------------------------------


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, s: dict, kind: str):
    """x: [S, H, D]; position = index along S. The first
    ``rotary_dim`` values of every head rotate, the rest pass."""
    dim = rotary_dim(s, kind)
    inv_freq = 1.0 / s[f"theta_{kind}"] ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1
    )


def attention(y, w, s: dict, kind: str):
    """One sequence: y [S, hidden] (normed) -> [S, hidden]."""
    n = y.shape[0]
    heads, kv = s[f"heads_{kind}"], s[f"kv_heads_{kind}"]
    d, dv = s[f"head_dim_{kind}"], s[f"v_head_dim_{kind}"]
    g = heads // kv
    # Query head j reads KV head j // g: heads as [kv, g].
    q = _rope((y @ w["q_proj"]).reshape(n, heads, d), s, kind)
    q = q.reshape(n, kv, g, d)
    k = _rope((y @ w["k_proj"]).reshape(n, kv, d), s, kind)
    v = s["attention_value_scale"] * (y @ w["v_proj"]).reshape(n, kv, dv)
    sink = w["sink"].reshape(kv, g, 1, 1) if "sink" in w else None
    block = next(b for b in QUERY_BLOCKS if n % b == 0)
    key_at = jnp.arange(n)[None, :]

    def some_queries(args):
        qb, at = args  # [block, kv, g, d], the first query's position
        query_at = at + jnp.arange(block)[:, None]
        seen = key_at <= query_at
        if kind == "s":
            seen = seen & (query_at - key_at < s["sliding_window"])
        score = jnp.einsum("skgd,tkd->kgst", qb, k) * d ** -0.5
        score = jnp.where(seen[None, None], score, -jnp.inf)
        if sink is None:
            p = jax.nn.softmax(score, axis=-1)
        else:
            # The published code appends b_j as one more score column,
            # takes the softmax and drops the column.
            column = jnp.broadcast_to(sink, (kv, g, block, 1))
            p = jax.nn.softmax(
                jnp.concatenate([score, column], axis=-1), axis=-1
            )[..., :-1]
        return jnp.einsum("kgst,tkd->skgd", p, v)

    ctx = jax.lax.map(
        some_queries,
        (q.reshape(n // block, block, kv, g, d), jnp.arange(0, n, block)),
    ).reshape(n, heads * dv)
    return ctx @ w["o_proj"]


def _swiglu(z, gate, up, down):
    return (jax.nn.silu(z @ gate) * (z @ up)) @ down


def route(y, w, s: dict):
    """[tokens, router_experts] gates: ``g_i`` on the chosen experts,
    0 elsewhere."""
    scores = jax.nn.sigmoid(y @ w["router"])
    _, chosen = jax.lax.top_k(
        scores + w["router_bias"], s["num_experts_per_tok"]
    )
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if s["norm_topk_prob"]:
        picked = picked / picked.sum(-1, keepdims=True)
    gates = s["routed_scaling_factor"] * picked
    rows = jnp.arange(y.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(gates)


def experts(y, w, s: dict):
    """y [tokens, hidden] (normed) -> the held experts' part of the
    layer (there is no shared expert)."""
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
    return out


def block(x, w, s: dict, kind: str, dense: bool, precision="highest"):
    """One layer on x: [B, S, hidden] float32, causal over S; a row at a
    time, so that the scores of one block of one sequence are all that
    is held."""
    with jax.default_matmul_precision(precision):
        stacked = ("experts_gate", "experts_up", "experts_down")
        w = {k: v if k in stacked else v.astype(jnp.float32)
             for k, v in w.items()}
        eps = s["rms_norm_eps"]

        def row(xr):
            xr = xr + attention(
                _rms_norm(xr, w["input_norm"], eps), w, s, kind
            )
            y = _rms_norm(xr, w["post_attention_norm"], eps)
            if dense:
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
    """``(x, outer)``: the hidden states before the final norm
    [B, S, hidden]; layer by layer, each layer's weights made from the
    seed and dropped."""
    s = settings(cfg)
    outer = _outer_jit(key, frozen(s), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    for i in range(s["num_hidden_layers"]):
        x = _layer_jit(key, i, x, frozen(s), dtype, precision,
                       kind_of(s, i), is_dense(s, i))
    return x, outer


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


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _layer_jit(key, layer, x, s_items, dtype, precision, kind, dense):
    # ``layer`` is traced: layers of one sort are alike, so one program
    # serves them all.
    s = dict(s_items)
    w = layer_weights(key, layer, s, dtype, kind, dense)
    return block(x, w, s, kind, dense, precision)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _margin_jit(x, outer, picks, chosen, s_items, precision):
    def row(args):
        xr, pr, cr = args
        logits = head(xr[pr], outer, dict(s_items), precision)
        got = jnp.take_along_axis(logits, cr[:, None], axis=-1)[:, 0]
        return jnp.max(logits, axis=-1) - got

    return jax.lax.map(row, (x, picks, chosen))
