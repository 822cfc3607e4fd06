"""Plain looped decoder-only transformer (Ouro-2.6B, arXiv:2510.25741
"Scaling Latent Reasoning via Looped Language Models"): ONE stack of
sandwich-normed layers (RMSNorm, rotary positions, multi-head attention,
SwiGLU) run ``total_ut_steps`` times over the same weights, the final
norm after every pass, an exit gate after every pass, an untied head on
the last pass's state. Float32 at ``highest`` matmul precision, whole
sequences, no cache, no kernel; nothing of ``tpudl`` is imported.

    h_0 = Embed(ids)
    for t in 0..T-1:                         # the SAME L layers every pass
        x = h_t
        for l in 0..L-1:
            x = x + norm_2(Attn_l(norm_1(x)))            # causal, RoPE
            x = x + norm_4(W_down(silu(W_gate u) * (W_up u))), u = norm_3(x)
        h_{t+1} = norm_final(x)
        lambda_t = sigmoid(w_gate . h_{t+1} + b_gate)
    p_t = lambda_t prod_{s<t}(1 - lambda_s)  (t < T-1);  p_{T-1} = the rest
    logits = W_head h_T

What this file takes as given where the published description leaves
room (each is also a line of ``assumed`` in
``perfbench/configs/ouro-2.6b.json``):

- sandwich norms: a layer has FOUR RMSNorms with a learned scale, one
  before and one after each sublayer; the norm AFTER a sublayer is
  applied to the sublayer's output before the residual add;
- the final norm runs after EVERY pass and the next pass reads the
  normed state (it is not applied once at the end);
- the exit gate is one linear map hidden -> 1 with a bias and a
  sigmoid, read from the normed state of each pass; the exit
  distribution is the geometric-like product above, the last pass
  taking what is left, so that it sums to 1; with
  ``early_exit_threshold`` 1.0 the cumulative mass reaches the
  threshold only at the last pass and the logits are the last pass's;
- keys and values belong to a (pass, layer): a token at pass ``t``
  attends to what the earlier tokens computed AT PASS ``t`` (whole
  sequences and no cache here, so this is what a causal pass over the
  sequence gives);
- no bias in attention or the MLP, no norm on queries or keys,
  rotate-half RoPE over all ``head_dim`` values, ``torch_dtype``
  bfloat16 (the weights are made in the type they are served in).

Weights are made here from a seed, layer by layer, by
``perfbench/reference/seeds.py``'s rule, so that a server and this
reference can each make the same values without handing anything to one
another: matrices normal(0, 0.02), norm scales 1, the gate's weight
normal(0, 0.02) (float32) and its bias 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

#: Matrices of one layer, in the order their keys are folded.
LAYER_MATRICES = ("q_proj", "k_proj", "v_proj", "o_proj",
                  "gate_proj", "up_proj", "down_proj")
#: A layer's four norms, in the order they are applied.
LAYER_NORMS = ("input_norm", "input_norm_2",
               "post_attention_norm", "post_attention_norm_2")
INIT_STD = 0.02
#: Ways to be wrong in ONE part (tests/test_loop_decoder.py): the final
#: norm once at the end instead of after every pass; the second and
#: fourth norms (those on the sublayers' outputs) left out.
FAULTS = ("final_norm_once", "no_post_norms")


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


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
    """Layer ``layer``'s matrices ([in, out]) and four norm scales."""
    key = jax.random.fold_in(root, 1 + layer)
    dims = _dims(cfg)
    out = {
        name: _normal(jax.random.fold_in(key, i), dims[name], dtype)
        for i, name in enumerate(LAYER_MATRICES)
    }
    for name in LAYER_NORMS:
        out[name] = jnp.ones((cfg["hidden_size"],), dtype)
    return out


def outer_weights(root, cfg: dict, dtype) -> dict:
    """Embedding table, final norm, output head and the exit gate
    (float32 whatever the model is served in)."""
    key = jax.random.fold_in(root, 0)
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {
        "embed_tokens": _normal(jax.random.fold_in(key, 0), (v, h), dtype),
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": _normal(jax.random.fold_in(key, 1), (h, v), dtype),
        "exit_gate": _normal(jax.random.fold_in(key, 2), (h, 1), jnp.float32),
        "exit_gate_bias": jnp.zeros((1,), jnp.float32),
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
    """x: [B, S, H, D]; position = index along S; rotate-half, all D."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(x, w, cfg: dict, precision="highest", faults=()):
    """One sandwich-normed layer on x: [B, S, hidden] float32, causal
    over S. ``faults``: ``FAULTS`` to commit."""
    with jax.default_matmul_precision(precision):
        w = {k: v.astype(jnp.float32) for k, v in w.items()}
        b, s, h = x.shape
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = cfg.get("head_dim") or h // nq
        eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])

        def after(name, y):
            if "no_post_norms" in faults:
                return y
            return _rms_norm(y, w[name], eps)

        y = _rms_norm(x, w["input_norm"], eps)
        q = _rope((y @ w["q_proj"]).reshape(b, s, nq, hd), theta)
        k = _rope((y @ w["k_proj"]).reshape(b, s, nkv, hd), theta)
        v = (y @ w["v_proj"]).reshape(b, s, nkv, hd)
        q = q.reshape(b, s, nkv, nq // nkv, hd)
        att = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * hd ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        att = jnp.where(causal[None, None, None], att, -jnp.inf)
        att = jax.nn.softmax(att, axis=-1)
        ctx = jnp.einsum("bhgqk,bkhd->bqhgd", att, v).reshape(b, s, nq * hd)
        x = x + after("input_norm_2", ctx @ w["o_proj"])
        y = _rms_norm(x, w["post_attention_norm"], eps)
        act = jax.nn.silu(y @ w["gate_proj"]) * (y @ w["up_proj"])
        return x + after("post_attention_norm_2", act @ w["down_proj"])


def end_of_pass(x, outer, cfg: dict, precision="highest"):
    """``(h_{t+1}, lambda_t)``: the final norm of a pass's output and
    the exit gate read from it, [B, S]."""
    with jax.default_matmul_precision(precision):
        h = _rms_norm(x, outer["final_norm"].astype(jnp.float32),
                      cfg["rms_norm_eps"])
        logit = h @ outer["exit_gate"] + outer["exit_gate_bias"]
        return h, jax.nn.sigmoid(logit[..., 0])


def exit_pdf(gates) -> jax.Array:
    """``[..., T]`` from the T gates ``lambda_t`` (the last one is not
    used: the last pass takes what is left)."""
    stay = jnp.ones_like(gates[0])
    pdf = []
    for lam in gates[:-1]:
        pdf.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(pdf + [stay], axis=-1)


def forward(key, cfg: dict, dtype, ids, faults=()):
    """``(h_T [B, S, hidden], pdf [B, S, T])`` of ``ids`` [B, S] int32.
    Runs layer by layer, making each layer's weights from the seed and
    dropping them (T times each), so that it fits beside nothing else."""
    faults = tuple(faults)
    outer = _outer_jit(key, frozen(cfg), dtype)
    x = outer["embed_tokens"][ids].astype(jnp.float32)
    gates = []
    for t in range(passes(cfg)):
        for i in range(cfg["num_hidden_layers"]):
            x = _layer_jit(key, i, x, frozen(cfg), dtype, faults)
        h, lam = _end_jit(x, outer, frozen(cfg))
        gates.append(lam)
        if "final_norm_once" not in faults or t == passes(cfg) - 1:
            x = h
    return x, exit_pdf(gates)


def logits(key, cfg: dict, dtype, ids, faults=()):
    """``(logits [B, S, vocab], pdf [B, S, T])``: the whole forward."""
    x, pdf = forward(key, cfg, dtype, ids, faults)
    outer = _outer_jit(key, frozen(cfg), dtype)
    return _head_jit(x, outer), pdf


def margins(key, cfg: dict, dtype, ids, picks, chosen):
    """By how much the reference's best logit beats each chosen token.

    ``ids``: [B, S] int32, each row a prompt followed by the tokens
    served after it, right-padded (causal attention keeps padding out of
    what precedes it). ``picks``: [B, T] positions whose logits chose a
    token; ``chosen``: [B, T] the token chosen there. Returns [B, T]
    float32 ``max(logits) - logits[chosen]`` (0 where the reference
    agrees)."""
    x, _ = forward(key, cfg, dtype, ids)
    outer = _outer_jit(key, frozen(cfg), dtype)
    return _margin_jit(x, outer, picks, chosen)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _outer_jit(key, cfg_items, dtype):
    return outer_weights(key, dict(cfg_items), dtype)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer_jit(key, layer, x, cfg_items, dtype, faults):
    cfg = dict(cfg_items)
    return block(x, layer_weights(key, layer, cfg, dtype), cfg, faults=faults)


@functools.partial(jax.jit, static_argnums=(2,))
def _end_jit(x, outer, cfg_items):
    return end_of_pass(x, outer, dict(cfg_items))


@jax.jit
def _head_jit(x, outer):
    with jax.default_matmul_precision("highest"):
        return x @ outer["lm_head"].astype(jnp.float32)


@jax.jit
def _margin_jit(x, outer, picks, chosen):
    rows = jnp.take_along_axis(x, picks[..., None], axis=1)
    with jax.default_matmul_precision("highest"):
        out = rows @ outer["lm_head"].astype(jnp.float32)
    got = jnp.take_along_axis(out, chosen[..., None], axis=-1)[..., 0]
    return jnp.max(out, axis=-1) - got
