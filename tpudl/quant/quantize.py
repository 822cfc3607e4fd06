"""Post-training weight quantization of a trained param tree.

Rule-driven (the SNIPPETS.md [2] ``match_partition_rules`` shape): a
rule list of ``(path_regex, weight_dtype_or_None)`` pairs is matched
against each leaf's ``module/submodule/kernel`` path string, first
match wins, and the matched dtype decides the leaf's fate — ``None``
keeps full precision, ``"int8"``/``"fp8_e4m3"`` quantize. The default
rule sets quantize exactly the decode-bandwidth-dominant matmul
weights (attention + MLP projections) and keep everything whose
precision is load-bearing (LayerNorm/RMSNorm scales, embeddings, the
LM/classifier head) full precision.

Quantization is symmetric per-OUTPUT-channel: a ``[in, out]`` kernel
gets one f32 scale per output column (``scale = max|w| / range``), so
the matmul dequantizes AFTER the contraction with a single broadcast
multiply (tpudl.quant.dense) — the weight matrix never exists at full
precision on the serving path.

Storage contract: a quantized leaf is a plain dict
``{"qvalues": int8|float8_e4m3fn [..., out], "qscale": f32 [out]}``
sitting under the ORIGINAL param key. The tree's module structure is
therefore identical to the full-precision tree — flax ``apply`` hands
the dict to ``QuantDense``, Orbax checkpoints round-trip it as two
ordinary arrays, and jax.export serializes the in_tree without any
custom pytree registration.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from tpudl import rules as rules_engine

#: Supported weight storage dtypes. ``int8``: symmetric [-127, 127]
#: (4x smaller than f32, the headline serving mode). ``fp8_e4m3``:
#: values stored in the e4m3 grid (native ``jnp.float8_e4m3fn``) with
#: a per-channel scale mapping the channel max onto e4m3's 448 top —
#: same 4x bytes, coarser mantissa but wider dynamic range per channel.
QUANT_DTYPES = ("int8", "fp8_e4m3")

#: Symmetric int8 range (matches tpudl.models.paged's KV quantizer).
INT8_MAX = 127.0
#: Largest finite e4m3 magnitude.
E4M3_MAX = 448.0
#: Scale floor: an all-zero channel dequantizes to zeros, not NaN.
SCALE_EPS = 1e-12

#: One rule: (regex searched against the leaf's "a/b/kernel" path,
#: weight dtype or None = keep full precision).
Rule = Tuple[str, Optional[str]]
Rules = Sequence[Rule]

#: Which Llama leaves quantize: the seven per-block projections (the
#: low-rank latent query's pair ``q_a_proj`` / ``q_b_proj`` where a
#: configuration has one in place of ``q_proj``) — embeddings, norms,
#: lm_head, the classifier, and any LoRA adapters stay full precision
#: (the rule-class contract tests/test_quant.py pins). Patterns are
#: dtype-free; ``default_quant_rules`` pairs them with the requested
#: storage dtype and appends the keep-all fallback.
LLAMA_QUANT_PATTERNS = (
    r"(q|k|v|o)_proj/kernel$",
    r"(gate|up|down)_proj/kernel$",
    r"q_(a|b)_proj/kernel$",
)

#: Which Llama leaves never quantize, whatever a later pattern says: a
#: hyper-connection's maps (``hyper_attention/phi``, ``hyper_mlp/phi``;
#: tpudl.models.hyper) decide how every value of the residual stream is
#: mixed, in float32, as a router's matrix decides the choice of experts
#: (``router/kernel``, which no pattern above names); a looped stack's
#: exit gate (``early_exit_gate``, float32: its sigmoid is the exit
#: distribution). The sandwich form's four norms a layer are ``scale``
#: leaves, which no pattern names. Of a learned-sparse-attention
#: indexer (``attention/indexer``, tpudl.models.llama.Indexer) the query
#: and key matrices quantize with the attention's (``q_proj``,
#: ``k_proj``: the int8 control's choice is then int8's); its head
#: weights ``weights_proj`` (``[hidden, heads]``: they weigh every
#: score of a choice that is exact) and its LayerNorm are kept.
LLAMA_KEEP_PATTERNS = (
    r"hyper_\w+/phi$", r"early_exit_gate/", r"indexer/(weights_proj|k_norm)/",
)

#: Which BERT leaves quantize: encoder attention + MLP projections.
#: The pooler/classifier head and embeddings keep full precision.
BERT_QUANT_PATTERNS = (
    r"attention/(query|key|value|out)/kernel$",
    r"encoder/layer_\d+/(intermediate|output)/kernel$",
)


def validate_weight_dtype(weight_dtype: str) -> str:
    if weight_dtype not in QUANT_DTYPES:
        raise ValueError(
            f"weight_dtype must be one of {QUANT_DTYPES}, got "
            f"{weight_dtype!r}"
        )
    if weight_dtype == "fp8_e4m3" and not hasattr(jnp, "float8_e4m3fn"):
        raise RuntimeError(
            "fp8_e4m3 weight storage needs jnp.float8_e4m3fn, which this "
            "jax build does not provide — use weight_dtype='int8'"
        )
    return weight_dtype


def is_quantized(leaf: Any) -> bool:
    """True for the ``{"qvalues", "qscale"}`` quantized-leaf dict."""
    return isinstance(leaf, dict) and set(leaf) == {"qvalues", "qscale"}


def quantize_leaf(w: jax.Array, weight_dtype: str) -> dict:
    """Symmetric per-output-channel quantization of one kernel.

    ``w`` [..., out] -> ``{"qvalues": [..., out] in the storage dtype,
    "qscale": f32 [out]}`` with ``scale = max|w_channel| / range``;
    ``qvalues * qscale`` reconstructs ``w`` to within half a
    quantization step (int8) / one e4m3 ulp (fp8) of the channel max —
    the bound tests/test_quant.py asserts per rule class."""
    validate_weight_dtype(weight_dtype)
    if w.ndim < 2:
        raise ValueError(
            f"per-output-channel quantization needs a >=2-D kernel, got "
            f"shape {jnp.shape(w)} — rules must leave scalars/vectors "
            f"(biases, norm scales) full precision"
        )
    wf = jnp.asarray(w, jnp.float32)
    reduce_axes = tuple(range(wf.ndim - 1))
    absmax = jnp.max(jnp.abs(wf), axis=reduce_axes)
    if weight_dtype == "int8":
        scale = jnp.maximum(absmax / INT8_MAX, SCALE_EPS)
        q = jnp.clip(
            jnp.round(wf / scale), -INT8_MAX, INT8_MAX
        ).astype(jnp.int8)
    else:  # fp8_e4m3: cast onto the e4m3 grid at the channel's scale
        scale = jnp.maximum(absmax / E4M3_MAX, SCALE_EPS)
        q = (wf / scale).astype(jnp.float8_e4m3fn)
    return {"qvalues": q, "qscale": scale.astype(jnp.float32)}


def dequantize_leaf(leaf: dict, dtype=jnp.float32) -> jax.Array:
    """Materialize a quantized leaf at full precision (the composite
    reference path; the fused serving matmul never calls this)."""
    return (
        leaf["qvalues"].astype(jnp.float32) * leaf["qscale"]
    ).astype(dtype)


def _path_str(path) -> str:
    return rules_engine.path_str(path)


def _quant_special(name: str, leaf: Any):
    """The quantizer's intrinsic per-leaf rule: leaves with ndim < 2
    (biases, norm scales, scalars) and already-quantized dicts never
    quantize regardless of rules — they annotate None without a rule
    lookup (tpudl.rules.annotate ``special`` hook)."""
    if is_quantized(leaf) or jnp.ndim(leaf) < 2:
        return True, None
    return False, None


def _dtype_for(name: str, leaf: Any, rules: Rules) -> Optional[str]:
    """First-match rule lookup for one leaf through the shared engine
    (tpudl.rules.first_match — bitwise-identical resolution to the
    pre-factoring private loop, tests/test_rules.py pins it). A >=2-D
    leaf no rule covers raises — an uncovered parameter is a rule-set
    bug, not a default."""
    handled, annotation = _quant_special(name, leaf)
    if handled:
        return annotation
    dtype = rules_engine.first_match(rules, name)
    if dtype is rules_engine.NO_MATCH:
        raise ValueError(
            f"no quantization rule matches parameter {name!r} — add an "
            f"explicit (pattern, None) keep rule or a catch-all"
        )
    return dtype


def match_quant_rules(rules: Rules, params: Any) -> Any:
    """Pytree of weight-dtype-or-None per leaf by first-match regex
    over the leaf's ``module/submodule/kernel`` path (the SNIPPETS.md
    [2] shape, via tpudl.rules.annotate). Quantized dicts stay opaque
    to the walk (their two arrays are one logical leaf), hence is_leaf
    on the marker."""
    return rules_engine.annotate(
        rules,
        params,
        special=_quant_special,
        is_leaf=is_quantized,
        what="quantization rule",
    )


def quantize_tree(params: Any, rules: Rules) -> Any:
    """Quantize a trained param tree by rules. Module structure is
    preserved exactly (matched kernels become ``{"qvalues","qscale"}``
    dicts in place); already-quantized leaves pass through untouched,
    so the transform is idempotent."""

    def one(path, leaf):
        dtype = _dtype_for(_path_str(path), leaf, rules)
        return leaf if dtype is None else quantize_leaf(leaf, dtype)

    return jax.tree_util.tree_map_with_path(
        one, params, is_leaf=is_quantized
    )


def dequantize_tree(params: Any, dtype=jnp.float32) -> Any:
    """Inverse transform (to quantized precision, not the original
    values): every quantized leaf materialized at ``dtype``."""
    return jax.tree_util.tree_map(
        lambda leaf: dequantize_leaf(leaf, dtype)
        if is_quantized(leaf)
        else leaf,
        params,
        is_leaf=is_quantized,
    )


def default_quant_rules(model_or_cfg: Any, weight_dtype: str) -> Rules:
    """The model family's rule set at ``weight_dtype``: quantize the
    attention/MLP projections, keep everything else (final ``(".*",
    None)`` fallback). Dispatches on the config shape — Llama
    (``rope_theta``) or BERT (``type_vocab_size``)."""
    validate_weight_dtype(weight_dtype)
    cfg = getattr(model_or_cfg, "cfg", model_or_cfg)
    keep = ()
    if hasattr(cfg, "rope_theta"):
        patterns, keep = LLAMA_QUANT_PATTERNS, LLAMA_KEEP_PATTERNS
    elif hasattr(cfg, "type_vocab_size"):
        patterns = BERT_QUANT_PATTERNS
    else:
        raise ValueError(
            f"no default quantization rules for {type(cfg).__name__}; "
            f"pass explicit rules to quantize_tree"
        )
    return (
        tuple((p, None) for p in keep)
        + tuple((p, weight_dtype) for p in patterns) + ((r".*", None),)
    )


def quantize_model(
    model: Any, params: Any, weight_dtype: str, rules: Optional[Rules] = None
) -> Tuple[Any, Any]:
    """The one-call serving entry: ``(model, params) -> (model with
    ``cfg.weight_dtype`` set — its projections become QuantDense —
    quantized param tree)``. This is what
    ``ServeSession.from_model(weight_dtype=...)`` runs."""
    validate_weight_dtype(weight_dtype)
    cfg = model.cfg
    if not hasattr(cfg, "weight_dtype"):
        raise ValueError(
            f"{type(cfg).__name__} has no weight_dtype seam — only the "
            f"Llama/BERT families serve quantized"
        )
    if rules is None:
        rules = default_quant_rules(cfg, weight_dtype)
    if cfg.weight_dtype != weight_dtype:
        model = model.clone(
            cfg=dataclasses.replace(cfg, weight_dtype=weight_dtype)
        )
    return model, quantize_tree(params, rules)


def weight_bytes_report(params: Any) -> dict:
    """Bytes accounting for the serving bytes-moved model: total
    resident param bytes, the quantized layers' stored bytes vs their
    f32 equivalent (``quant_ratio`` — the >= 3.5x bar the parity grid
    asserts for int8; 4x minus the scale rows), and leaf counts."""
    total = 0
    quant_bytes = 0
    quant_f32_equiv = 0
    n_quant = 0
    n_leaves = 0
    for leaf in jax.tree.leaves(params, is_leaf=is_quantized):
        n_leaves += 1
        if is_quantized(leaf):
            n_quant += 1
            stored = leaf["qvalues"].nbytes + leaf["qscale"].nbytes
            quant_bytes += stored
            quant_f32_equiv += leaf["qvalues"].size * 4
            total += stored
        else:
            total += leaf.nbytes
    return {
        "total_bytes": total,
        "quantized_layer_bytes": quant_bytes,
        "quantized_layer_f32_bytes": quant_f32_equiv,
        "quant_ratio": (
            round(quant_f32_equiv / quant_bytes, 3) if quant_bytes else None
        ),
        "num_quantized_leaves": n_quant,
        "num_leaves": n_leaves,
    }
