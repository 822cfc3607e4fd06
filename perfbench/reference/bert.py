"""Plain BERT with a classification head, its loss, its gradients and
the AdamW step that trains it: float32 at ``highest`` matmul precision,
``jax.numpy`` only, rows in blocks so that a full batch fits.

Follows Devlin et al. 2018 as ``google-bert/bert-base-uncased`` is
published: learned word, position and segment embeddings, post-norm
encoder layers with exact (erf) GELU, a tanh pooler on the first token,
inverted dropout after the embeddings' norm, on the attention
probabilities, on both sub-layers' outputs before their residual, and on
the pooled vector. The optimizer is AdamW as the training configuration
states it: gradients clipped by their global norm, bias-corrected
moments, decoupled weight decay on every leaf, a linear warm-up into a
cosine decay.

Dropout's masks cannot be invented here: a step is compared number for
number, so the reference drops what the program drops. It takes nothing
from the program for that. It draws every mask itself, from the key the
harness hands the step, by the rule the program's masks follow
(``DropoutRule``), with ``jax.random`` and flax's own key-per-site
derivation. A program change that draws its masks otherwise has to come
with a benchmark PR that restates the rule.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from flax.core.scope import LazyRng

from perfbench.reference.seeds import frozen, seed_key  # noqa: F401

INIT_STD = 0.02


class DropoutRule:
    """How the masks of one training step follow from the step's key.

    The step folds its number into the key it is called with. Each site
    that drops draws from that key folded with its place in the module
    tree, as flax's ``make_rng("dropout")`` does (the first draw of a
    scope has the count 1). A mask keeps an element whose uint8 random
    bits reach ``round(rate * 256)``, so the rate in effect is a multiple
    of 1/256 (0.1 becomes 26/256), and what is kept is divided by one
    less that rate.
    """

    EMBEDDINGS = ("bert", "embeddings", "Dropout_0")
    POOLED = ("Dropout_0",)

    @staticmethod
    def layer(i: int) -> dict:
        at = ("bert", "encoder", f"layer_{i}")
        return {"probs": at + ("attention",),
                "attention_out": at + ("attention", "Dropout_0"),
                "output": at + ("Dropout_0",)}

    @staticmethod
    def step_key(key, step):
        return jax.random.fold_in(key, step)

    @staticmethod
    def threshold(rate: float) -> int:
        return min(int(round(rate * 256.0)), 255)

    @classmethod
    def keep(cls, step_key, site: tuple, shape: tuple, rate: float):
        """The site's mask: True where the element is kept."""
        key = LazyRng.create(step_key, *site, 1).as_jax_rng()
        return jax.random.bits(key, shape, jnp.uint8) >= jnp.uint8(
            cls.threshold(rate))

    @classmethod
    def masks(cls, step_key, cfg: dict, batch: int, seq: int) -> dict:
        """Every mask of one step over the whole batch, by site; empty
        where the configuration drops nothing."""
        h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
        hidden = cfg["hidden_dropout_prob"]
        probs = cfg["attention_probs_dropout_prob"]
        out = {}
        if cls.threshold(hidden) > 0:
            out["embeddings"] = cls.keep(
                step_key, cls.EMBEDDINGS, (batch, seq, h), hidden)
            out["pooled"] = cls.keep(step_key, cls.POOLED, (batch, h), hidden)
        for i in range(cfg["num_hidden_layers"]):
            site = cls.layer(i)
            if cls.threshold(probs) > 0:
                out[f"layer_{i}/probs"] = cls.keep(
                    step_key, site["probs"], (batch, heads, seq, seq), probs)
            if cls.threshold(hidden) > 0:
                for n in ("attention_out", "output"):
                    out[f"layer_{i}/{n}"] = cls.keep(
                        step_key, site[n], (batch, seq, h), hidden)
        return out


def _drop(x, keep, rate: float):
    """Inverted dropout of ``x`` by the mask ``keep`` (None: nothing is
    dropped)."""
    if keep is None:
        return x
    return jnp.where(keep, x / (1.0 - DropoutRule.threshold(rate) / 256.0), 0.0)


def leaf_shapes(cfg: dict) -> dict:
    """Canonical leaf name -> (shape, kind); kind is how it is made."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    out = {
        "embeddings/word": ((cfg["vocab_size"], h), "normal"),
        "embeddings/position": ((cfg["max_position_embeddings"], h), "normal"),
        "embeddings/token_type": ((cfg["type_vocab_size"], h), "normal"),
        "embeddings/norm/scale": ((h,), "ones"),
        "embeddings/norm/bias": ((h,), "zeros"),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer_{i}"
        for n in ("query", "key", "value", "out"):
            out[f"{p}/{n}/kernel"] = ((h, h), "normal")
            out[f"{p}/{n}/bias"] = ((h,), "zeros")
        out[f"{p}/intermediate/kernel"] = ((h, inter), "normal")
        out[f"{p}/intermediate/bias"] = ((inter,), "zeros")
        out[f"{p}/output/kernel"] = ((inter, h), "normal")
        out[f"{p}/output/bias"] = ((h,), "zeros")
        for n in ("attention_norm", "output_norm"):
            out[f"{p}/{n}/scale"] = ((h,), "ones")
            out[f"{p}/{n}/bias"] = ((h,), "zeros")
    out["pooler/kernel"] = ((h, h), "normal")
    out["pooler/bias"] = ((h,), "zeros")
    out["classifier/kernel"] = ((h, cfg["num_labels"]), "normal")
    out["classifier/bias"] = ((cfg["num_labels"],), "zeros")
    return out


def make_weights(key, cfg: dict) -> dict:
    """Every leaf, float32, from ``key`` (traced: jit over it)."""
    out = {}
    for i, (name, (shape, kind)) in enumerate(leaf_shapes(cfg).items()):
        if kind == "normal":
            out[name] = INIT_STD * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32
            )
        elif kind == "ones":
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            out[name] = jnp.zeros(shape, jnp.float32)
    return out


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def logits_fn(w: dict, cfg: dict, input_ids, attention_mask, masks=None):
    """[B, S] ids and mask -> [B, num_labels] logits. ``masks``: the
    rows' dropout masks by site (``DropoutRule.masks``); None or empty
    drops nothing."""
    masks = masks or {}
    hidden_rate = cfg["hidden_dropout_prob"]
    probs_rate = cfg["attention_probs_dropout_prob"]
    eps = cfg["layer_norm_eps"]
    heads = cfg["num_attention_heads"]
    b, s = input_ids.shape
    h = cfg["hidden_size"]
    hd = h // heads
    x = (w["embeddings/word"][input_ids]
         + w["embeddings/position"][jnp.arange(s)][None]
         + w["embeddings/token_type"][jnp.zeros_like(input_ids)])
    x = _layer_norm(x, w["embeddings/norm/scale"], w["embeddings/norm/bias"], eps)
    x = _drop(x, masks.get("embeddings"), hidden_rate)
    keep = attention_mask.astype(bool)[:, None, None, :]
    for i in range(cfg["num_hidden_layers"]):
        p = f"layer_{i}"

        def proj(n, y):
            return y @ w[f"{p}/{n}/kernel"] + w[f"{p}/{n}/bias"]

        q = proj("query", x).reshape(b, s, heads, hd)
        k = proj("key", x).reshape(b, s, heads, hd)
        v = proj("value", x).reshape(b, s, heads, hd)
        att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(keep, att, -jnp.inf), axis=-1)
        att = _drop(att, masks.get(f"{p}/probs"), probs_rate)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(b, s, h)
        out = _drop(proj("out", ctx), masks.get(f"{p}/attention_out"),
                    hidden_rate)
        x = _layer_norm(x + out, w[f"{p}/attention_norm/scale"],
                        w[f"{p}/attention_norm/bias"], eps)
        inter = jax.nn.gelu(proj("intermediate", x), approximate=False)
        out = _drop(proj("output", inter), masks.get(f"{p}/output"),
                    hidden_rate)
        x = _layer_norm(x + out, w[f"{p}/output_norm/scale"],
                        w[f"{p}/output_norm/bias"], eps)
    pooled = jnp.tanh(x[:, 0] @ w["pooler/kernel"] + w["pooler/bias"])
    pooled = _drop(pooled, masks.get("pooled"), hidden_rate)
    return pooled @ w["classifier/kernel"] + w["classifier/bias"]


def loss_fn(w, cfg, input_ids, attention_mask, labels, masks=None):
    """Mean cross-entropy of the rows."""
    logits = logits_fn(w, cfg, input_ids, attention_mask, masks)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def learning_rate(optim: dict, count):
    """Linear warm-up from 0 into a cosine decay to 0, at step ``count``
    (0 for the first step)."""
    lr, warm = optim["learning_rate"], optim["warmup_steps"]
    decay = max(optim["total_steps"] - warm, 1)
    t = jnp.clip((count - warm) / decay, 0.0, 1.0)
    cosine = lr * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(count < warm, lr * count / max(warm, 1), cosine)


SAMPLE = 65536


def sample(leaf, signed: bool = False):
    """Up to ``SAMPLE`` elements of a leaf, evenly strided (their
    magnitudes unless ``signed``): enough to compare two leaves element
    by element, small enough to keep."""
    flat = leaf.reshape(-1)
    flat = flat[:: max(1, flat.shape[0] // SAMPLE)][:SAMPLE]
    return flat if signed else jnp.abs(flat)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _step(carry, cfg_items, optim_items, blocks, batch, dropout_key):
    cfg, optim = dict(cfg_items), dict(optim_items)
    w, mu, nu, count = carry
    with jax.default_matmul_precision("highest"):
        split = lambda a: a.reshape(blocks, a.shape[0] // blocks, *a.shape[1:])
        masks = DropoutRule.masks(
            DropoutRule.step_key(dropout_key, count), cfg,
            *batch["input_ids"].shape)
        ids, mask, labels, masks = jax.tree.map(split, (
            batch["input_ids"], batch["attention_mask"], batch["label"], masks
        ))

        def body(acc, xs):
            loss, grads = jax.value_and_grad(loss_fn)(w, cfg, *xs)
            return (acc[0] + loss / blocks,
                    jax.tree.map(lambda a, g: a + g / blocks, acc[1], grads)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        (loss, grads), _ = jax.lax.scan(body, zero, (ids, mask, labels, masks))
    clip = optim.get("grad_clip_norm")
    if clip:
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.where(norm < clip, 1.0, clip / norm)
        grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = optim["b1"], optim["b2"], 1e-8
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    t = count + 1
    lr = learning_rate(optim, count)
    wd = optim["weight_decay"]

    def new(p, m, v):
        adam = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (adam + wd * p)

    new_w = jax.tree.map(new, w, mu, nu)
    grad_norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
    return (new_w, mu, nu, t), loss, grad_norms, jax.tree.map(sample, grads)


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, cfg_items):
    w = make_weights(key, dict(cfg_items))
    zeros = jax.tree.map(jnp.zeros_like, w)
    return w, zeros, zeros, jnp.zeros((), jnp.int32)


@jax.jit
def _delta(new, old):
    """By leaf, the norm of the change and a signed sample of it."""
    change = jax.tree.map(lambda a, b: a - b, new, old)
    return (jax.tree.map(lambda d: jnp.sqrt(jnp.sum(jnp.square(d))), change),
            jax.tree.map(lambda d: sample(d, signed=True), change))


def follow(key, cfg: dict, optim: dict, batches: list, block_rows: int,
           dropout_key) -> dict:
    """Train ``len(batches)`` steps from the seeded weights, dropping
    what ``dropout_key`` makes the program drop. Returns each step's
    loss, the norm of the first (clipped) gradient by leaf, a sample of
    that gradient's magnitudes by leaf (on the device), and the norm and
    a signed sample (on the device) of the parameters' change over the
    steps by leaf."""
    cfg_items, optim_items = frozen(cfg), frozen(optim)
    carry = _init(key, cfg_items)
    start = carry[0]
    losses, first, first_sample = [], None, None
    for batch in batches:
        blocks = max(1, batch["label"].shape[0] // block_rows)
        carry, loss, norms, sampled = _step(
            carry, cfg_items, optim_items, blocks, batch, dropout_key
        )
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
            first_sample = sampled
    norms, delta_sample = _delta(carry[0], start)
    return {"loss": losses, "grad_norm": first, "grad_sample": first_sample,
            "delta_norm": {k: float(v) for k, v in norms.items()},
            "delta_sample": delta_sample}
