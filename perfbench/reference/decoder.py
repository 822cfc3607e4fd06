"""Plain decoder-only transformer: RMSNorm, rotary positions
(rotate-half), grouped-query attention, SwiGLU, untied head — the block
Mistral-7B-v0.3 publishes. Float32 at ``highest`` matmul precision,
whole sequences, no cache.

Weights are made here from a seed, layer by layer, in the type they are
served in, so that a server and this reference can each make the same
values without handing anything to one another.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

#: Matrices of one block, in the order their keys are folded.
LAYER_MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj",
                  "gate_proj", "up_proj", "down_proj")
INIT_STD = 0.02


def _dims(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    inter = cfg["intermediate_size"]
    return {
        "q_proj": (h, nq * hd), "k_proj": (h, nkv * hd),
        "v_proj": (h, nkv * hd), "o_proj": (nq * hd, h),
        "gate_proj": (h, inter), "up_proj": (h, inter),
        "down_proj": (inter, h),
    }


def _normal(key, shape, dtype):
    return (INIT_STD * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def layer_weights(root, layer: int, cfg: dict, dtype) -> dict:
    """Block ``layer``'s matrices ([in, out]) and norm scales."""
    key = jax.random.fold_in(root, 1 + layer)
    dims = _dims(cfg)
    out = {
        name: _normal(jax.random.fold_in(key, i), dims[name], dtype)
        for i, name in enumerate(LAYER_MATRICES)
    }
    h = cfg["hidden_size"]
    out["input_norm"] = jnp.ones((h,), dtype)
    out["post_attention_norm"] = jnp.ones((h,), dtype)
    return out


def outer_weights(root, cfg: dict, dtype) -> dict:
    """Embedding table, final norm and output head."""
    key = jax.random.fold_in(root, 0)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed_tokens": _normal(jax.random.fold_in(key, 0), (v, h), dtype),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": _normal(jax.random.fold_in(key, 1), (h, v), dtype),
    }


def all_weights(key, cfg: dict, dtype) -> dict:
    """The whole model in one traced call (jit it over ``key``, which is
    ``seed_key(seed)``): ``{"outer": ..., "layers": [...]}``."""
    return {
        "outer": outer_weights(key, cfg, dtype),
        "layers": [
            layer_weights(key, i, cfg, dtype)
            for i in range(cfg["num_hidden_layers"])
        ],
    }


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: [B, S, H, D]; position = index along S."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, w, cfg: dict, precision="highest"):
    """One block on x: [B, S, hidden] float32, causal over S."""
    with jax.default_matmul_precision(precision):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, h = x.shape
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = cfg.get("head_dim") or h // nq
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        y = _rms_norm(x, w["input_norm"], eps)
        q = _rope((y @ w["q_proj"]).reshape(b, s, nq, hd), theta)
        k = _rope((y @ w["k_proj"]).reshape(b, s, nkv, hd), theta)
        v = (y @ w["v_proj"]).reshape(b, s, nkv, hd)
        g = nq // nkv
        q = q.reshape(b, s, nkv, g, hd)
        att = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        att = jnp.where(causal[None, None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        ctx = jnp.einsum("bhgqk,bkhd->bqhgd", att, v).reshape(b, s, nq * hd)
        x = x + ctx @ w["o_proj"]
        y = _rms_norm(x, w["post_attention_norm"], eps)
        act = jax.nn.silu(y @ w["gate_proj"]) * (y @ w["up_proj"])
        return x + act @ w["down_proj"]


def head(x, outer, cfg: dict, precision="highest"):
    """Logits of hidden states x: [..., hidden]."""
    with jax.default_matmul_precision(precision):
        y = _rms_norm(x, outer["final_norm"].astype(jnp.float32),
                      cfg["rms_norm_eps"])
        return y @ outer["lm_head"].astype(jnp.float32)


def margins(key, cfg: dict, dtype, ids, picks, chosen):
    """By how much the reference's best logit beats each chosen token.

    ``ids``: [B, S] int32, each row a prompt followed by the tokens
    served after it, right-padded (causal attention keeps padding out of
    what precedes it). ``picks``: [B, T] positions whose logits chose a
    token; ``chosen``: [B, T] the token chosen there. Returns [B, T]
    float32 ``max(logits) - logits[chosen]`` (0 where the reference
    agrees). Runs layer by layer, making each layer's weights from the
    seed and dropping them, so that it fits beside nothing else.
    """
    outer = _outer_jit(key, frozen(cfg), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer_jit(key, i, x, frozen(cfg), dtype)
    return _margin_jit(x, outer, picks, chosen, frozen(cfg))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _outer_jit(key, cfg_items, dtype):
    return outer_weights(key, dict(cfg_items), dtype)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _layer_jit(key, layer, x, cfg_items, dtype):
    cfg = dict(cfg_items)
    return block(x, layer_weights(key, layer, cfg, dtype), cfg)


@functools.partial(jax.jit, static_argnums=(4,))
def _margin_jit(x, outer, picks, chosen, cfg_items):
    rows = jnp.take_along_axis(x, picks[..., None], axis=1)
    logits = head(rows, outer, dict(cfg_items))
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return jnp.max(logits, axis=-1) - got
