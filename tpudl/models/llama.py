"""Llama-family decoder (RoPE + RMSNorm + SwiGLU + GQA), TPU-native.

The reference's stretch workload is a Llama-3-8B LoRA fine-tune
(BASELINE.json configs[4]; the reference tree ships no decoder at all —
SURVEY.md §0). First-party implementation, same design rules as
tpudl.models.bert: bf16 compute / f32 params, f32 norms and softmax,
attention through the tpudl.ops.attend seam (reference / Pallas flash /
ring over `sp` — causal masking never materializes [S, S]), activation
sharding constraints on the (dp, fsdp) x sp x tp mesh, optional per-layer
remat. LoRA drops in via cfg.lora_rank>0, swapping the attention
projections to tpudl.models.lora.LoRADense (frozen-base training is the
optimizer's job — see lora.lora_optimizer).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from functools import partial
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpudl.models.lora import LoRADense
from tpudl.ops.attention import attend
from tpudl.parallel.sharding import constrain


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN as the ``deepseek_yarn`` configs publish it: per-frequency
    blend of the plain and the ``factor``-times-stretched inverse
    frequencies, by a linear ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` rotations over the original
    context. ``attention_scale`` is what the blend asks of the softmax
    (``mscale ** 2``); cos and sin stay unscaled where ``mscale ==
    mscale_all_dim``, which is how the published configs set them.
    A config of the plain ``yarn`` rope type states the one number
    instead, ``attention_factor``: cos and sin are multiplied by it
    and the softmax is left alone."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    attention_factor: Optional[float] = None

    @staticmethod
    def _mscale(factor: float, m: float) -> float:
        return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        if self.attention_factor is not None:
            return self.attention_factor
        return self._mscale(self.factor, self.mscale) / self._mscale(
            self.factor, self.mscale_all_dim
        )

    @property
    def attention_scale(self) -> float:
        if self.attention_factor is not None:
            return 1.0
        return self._mscale(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, dim: int, theta: float) -> jax.Array:
        """[dim / 2] float32 inverse frequencies."""
        exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
        plain = 1.0 / theta ** exponent

        def correction_dim(rotations: float) -> float:
            return dim * math.log(
                self.original_max_position / (rotations * 2 * math.pi)
            ) / (2 * math.log(theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), dim - 1)
        ramp = jnp.clip(
            (jnp.arange(dim // 2, dtype=jnp.float32) - low)
            / max(high - low, 1e-3),
            0.0, 1.0,
        )
        return plain / self.factor * ramp + plain * (1.0 - ramp)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """One layer's grouped-query attention (``LlamaConfig.layer_spec``):
    ``scope`` "full_attention" or "window_attention", its query heads,
    its RoPE (``rotary_dim`` leading values of the head rotate, the
    rest pass), ``window`` (0 = the whole context), whether its
    output is gated by head, its KV heads and whether its softmax has
    a learned sink a query head."""

    scope: str
    num_heads: int
    rope_theta: float
    rope_scaling: Optional[RopeScaling]
    rotary_dim: int
    window: int
    gate: bool
    num_kv_heads: int
    sink: bool


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128_256
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 14_336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    rms_norm_eps: float = 1e-5
    num_labels: int = 2
    dtype: Any = jnp.bfloat16
    attention_impl: str = "reference"
    remat: bool = False
    lora_rank: int = 0
    lora_alpha: float = 16.0

    def __post_init__(self):
        if self.lora_rank < 0:
            raise ValueError(
                f"lora_rank must be >= 0 (0 = adapters off), got "
                f"{self.lora_rank}"
            )
        if self.attention not in ("gqa", "mla"):
            raise ValueError(
                f"attention must be 'gqa' or 'mla', got {self.attention!r}"
            )
        if self.attention == "mla" and not (
            self.kv_lora_rank > 0 and self.qk_nope_head_dim > 0
            and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
        ):
            raise ValueError(
                "attention='mla' needs kv_lora_rank, qk_nope_head_dim, "
                "qk_rope_head_dim and v_head_dim"
            )
        if self.num_experts > 0 and self.moe_experts > 0:
            raise ValueError(
                "num_experts (dropless serving experts) and moe_experts "
                "(the capacity-dropping training layer) are two layers: "
                "set one"
            )
        for name in ("layer_types", "num_heads_per_layer"):
            per_layer = getattr(self, name)
            if per_layer is not None and len(per_layer) != self.num_layers:
                raise ValueError(
                    f"{name} names {len(per_layer)} layers, num_layers "
                    f"is {self.num_layers}"
                )
        if self.layer_types is not None:
            kinds = set(self.layer_types)
            if not kinds <= {"full_attention", "sliding_attention"}:
                raise ValueError(
                    f"layer_types must be 'full_attention' or "
                    f"'sliding_attention', got {sorted(kinds)}"
                )
            if "sliding_attention" in kinds and self.sliding_window < 1:
                raise ValueError(
                    "a 'sliding_attention' layer needs sliding_window >= 1"
                )
            if self.attention == "mla":
                raise ValueError(
                    "layer_types are the grouped-query block's: latent "
                    "attention keeps every layer alike"
                )
        _check_block(self)
    # tpudl.ops.norms / mlp_fused: True = Pallas on TPU; "force" = always.
    fused_ops: Any = False
    # tpudl.quant: "int8" / "fp8_e4m3" = the projections become
    # QuantDense over the quantize_tree output (from_model(weight_dtype=)).
    weight_dtype: Optional[str] = None
    fp8_train: Any = False  # tpudl.ops.fp8_dot; a string pins its impl
    # ``loop_passes`` T > 1: the stack runs T times over the SAME weights
    # (a cache a (pass, layer); final norm and exit gate after every pass).
    loop_passes: int = 1
    loop_exit_threshold: float = 1.0  # 1.0: every token runs every pass
    # MoE (tpudl.ops.moe): >0 swaps every block's dense MLP for MoEMlp.
    moe_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    # ``attention``: "gqa" (the block above) or "mla", latent attention:
    # ONE normed latent of ``kv_lora_rank`` values a position plus one
    # roped key shared by all heads are all the cache keeps.
    attention: str = "gqa"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[RopeScaling] = None
    # Learned sparse attention over the latent cache (``_check_sparse``):
    # a query attends the ``index_topk`` positions an indexer of
    # ``index_n_heads`` x ``index_head_dim`` scores highest; a layer's
    # ``indexer_types`` entry: "full" (its own indexer, a second cache
    # leaf of its keys) or "shared" (the last "full" layer's choice).
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_types: Optional[Tuple[str, ...]] = None
    # Dropless routed experts (tpudl.ops.moe.DroplessMoE) from layer
    # ``first_k_dense`` on, a dense SwiGLU before. ``num_experts``: the
    # router's width; ``experts_held = (first, count)``: those held here.
    num_experts: int = 0
    experts_per_token: int = 0
    moe_intermediate_size: int = 0
    num_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    first_k_dense: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    # Layers that differ in their attention (``layer_spec``).
    # ``layer_types``: "full_attention" (the whole context) or
    # "sliding_attention" (the last ``sliding_window`` positions, the
    # query's own counted); ``num_heads_per_layer``: its query heads;
    # ``head_size``: the head's width where it is not ``hidden_size //
    # num_heads``. A full layer rotates the first ``partial_rotary_factor``
    # of the head, a sliding layer all of it with ``sliding_rope_theta``.
    # ``attention_gate``: a sigmoid gate a head before ``o_proj``.
    head_size: int = 0
    layer_types: Optional[Tuple[str, ...]] = None
    num_heads_per_layer: Optional[Tuple[int, ...]] = None
    sliding_window: int = 0
    sliding_rope_theta: float = 10_000.0
    partial_rotary_factor: float = 1.0
    attention_gate: bool = False
    # ``block``: "llama" or "shortcut" (ShortcutBlock: two latent
    # attentions and two dense FFNs around one expert branch).
    # ``q_lora_rank`` > 0: the latent query is low-rank (q_a_proj,
    # RMSNorm, q_b_proj); ``mla_scale_q`` / ``_kv``: constants on it and
    # on the latent. ``router_*``, ``zero_experts``: DroplessMoE's.
    block: str = "llama"
    q_lora_rank: int = 0
    mla_scale_q: float = 1.0
    mla_scale_kv: float = 1.0
    router_scoring: str = "sigmoid"
    router_renormalize: bool = True
    zero_experts: int = 0
    # ``hyper_streams`` n > 0: n residual vectors a token (HyperBlock).
    # ``sandwich_norm``: a norm on each sublayer's OUTPUT too (SandwichBlock).
    hyper_streams: int = 0
    hyper_sinkhorn_iters: int = 20
    hyper_eps: float = 1e-6
    hyper_clamp: float = 30.0
    sandwich_norm: bool = False
    # More that differs by layer kind (``layer_spec``; ``_check_kinds``):
    # a sliding layer's KV heads (0: ``num_kv_heads``) and the share of
    # its head that rotates; a learned sink a query head in the softmax
    # of each kind. Grouped-query values ``value_head_size`` wide where
    # that is not the head's width (``v_head_dim`` is the latent
    # attention's), times the constant ``attention_value_scale``.
    value_head_size: int = 0
    sliding_num_kv_heads: int = 0
    sliding_partial_rotary_factor: float = 1.0
    full_attention_sink: bool = False
    sliding_attention_sink: bool = False
    attention_value_scale: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def value_dim(self) -> int:
        """A grouped-query head's value width."""
        return self.value_head_size or self.head_dim

    def layer_spec(self, layer: int) -> "AttentionSpec":
        """What ``layer``'s grouped-query attention is made of."""
        heads = (
            self.num_heads_per_layer[layer]
            if self.num_heads_per_layer is not None else self.num_heads
        )
        kind = (
            self.layer_types[layer] if self.layer_types is not None
            else "full_attention"
        )
        if kind == "sliding_attention":
            return AttentionSpec(
                "window_attention", heads, self.sliding_rope_theta, None,
                int(self.head_dim * self.sliding_partial_rotary_factor),
                self.sliding_window, self.attention_gate,
                self.sliding_num_kv_heads or self.num_kv_heads,
                self.sliding_attention_sink,
            )
        return AttentionSpec(
            "full_attention", heads, self.rope_theta, self.rope_scaling,
            int(self.head_dim * self.partial_rotary_factor), 0,
            self.attention_gate, self.num_kv_heads,
            self.full_attention_sink,
        )

    @property
    def window_layers(self) -> int:
        """Layers that keep a window of the context only."""
        return sum(
            self.layer_spec(i).window > 0 for i in range(self.num_layers)
        )

    def mlp_kind(self, layer: int) -> str:
        """"moe" (dropless routed experts) or "dense" for ``layer``."""
        if self.num_experts > 0 and layer >= self.first_k_dense:
            return "moe"
        return "dense"

    @property
    def expert_layers(self) -> int:
        return sum(
            self.mlp_kind(i) == "moe" for i in range(self.num_layers)
        )


LLAMA_TINY = partial(
    LlamaConfig,
    vocab_size=512,
    hidden_size=128,
    num_layers=2,
    num_heads=4,
    num_kv_heads=2,
    intermediate_size=256,
    max_seq_len=256,
    rope_theta=10_000.0,
)
LLAMA3_8B = LlamaConfig
#: Llama-3.2-1B shape — the largest decoder a single 16G chip serves
#: comfortably in bf16.
LLAMA3_1B = partial(
    LlamaConfig,
    hidden_size=2048,
    num_layers=16,
    num_heads=32,
    num_kv_heads=8,
    intermediate_size=8192,
)

#: Size-name registry for tpudl.models.registry.build_llama.
LLAMA_SIZES = {
    "llama-tiny": LLAMA_TINY,
    "llama3-1b": LLAMA3_1B,
    "llama3-8b": LLAMA3_8B,
}


def _proj(cfg: LlamaConfig, features: int, name: str):
    """Attention/MLP projection: plain Dense, LoRADense when adapters
    are on (cfg.lora_rank > 0), or QuantDense when the low-precision
    weight seam is set (cfg.weight_dtype — serving only; the quantized
    sites are exactly the leaves tpudl.quant's LLAMA_QUANT_PATTERNS
    match). The two COMPOSE: weight_dtype + lora_rank > 0 runs a
    LoRADense over a quantized base kernel (the base matmul dispatches
    on what the tree holds, exactly like QuantDense) with the adapters
    full precision on top — the QLoRA-style quantized-base fine-tune
    shape. Adapter leaves fall under the quantizer's keep-all rule, so
    quantize_model on a LoRA tree quantizes only the base kernels.
    ``fp8_train`` (training-time fp8 matmuls, tpudl.ops.fp8_dot) swaps
    the same sites to Fp8Dense instead — exclusive with serving
    quantization, but it COMPOSES with ``lora_rank``: Fp8Dense carries
    the same ``lora_a``/``lora_b`` leaves as LoRADense (full-precision
    delta over the fp8 base product), so the frozen-base optimizer and
    adapter extraction seams see an identical tree shape."""
    if cfg.fp8_train:
        if cfg.weight_dtype is not None:
            raise ValueError(
                "fp8_train (training-time fp8 matmuls) does not compose "
                "with weight_dtype (frozen-tree serving quantization) "
                "— pick one"
            )
        from tpudl.ops.fp8_dot import Fp8Dense

        impl = cfg.fp8_train if isinstance(cfg.fp8_train, str) else "auto"
        if impl == "force":
            impl = "fused"
        return Fp8Dense(
            features,
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            impl=impl,
            rank=cfg.lora_rank,
            alpha=cfg.lora_alpha,
            name=name,
        )
    if cfg.weight_dtype is not None and cfg.lora_rank == 0:
        from tpudl.quant.dense import QuantDense

        return QuantDense(
            features,
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            name=name,
        )
    if cfg.lora_rank > 0:
        return LoRADense(
            features,
            rank=cfg.lora_rank,
            alpha=cfg.lora_alpha,
            use_bias=False,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            name=name,
        )
    return nn.Dense(
        features,
        use_bias=False,
        dtype=cfg.dtype,
        kernel_init=nn.initializers.normal(0.02),
        name=name,
    )


class RMSNorm(nn.Module):
    """RMS normalization through the tpudl.ops.norms seam. The default
    ``impl="reference"`` is the original composite math verbatim
    (rms_norm_ref); ``impl="auto"/"fused"`` routes to the Pallas fused
    kernel, which also takes the residual add (``residual=`` returns
    ``(normed, x + residual)`` — the pre-norm block's carried sum) in
    the same activation pass."""

    eps: float = 1e-5
    impl: str = "reference"

    @nn.compact
    def __call__(self, x, residual=None):
        from tpudl.ops.norms import rms_norm

        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        # `norm`, `mlp`, `embeddings` (like `attention`, `lm_head`, which
        # the module names give) are scope components a profiler trace
        # groups device time by; HLO metadata only.
        with jax.named_scope("norm"):
            return rms_norm(
                x, scale, residual, eps=self.eps, impl=self.impl
            )


def rope(
    x: jax.Array, positions: jax.Array, theta: float,
    scaling: Optional[RopeScaling] = None,
    rotary_dim: Optional[int] = None,
) -> jax.Array:
    """Rotary embedding on [B, S, H, D] (rotate-half convention);
    ``scaling`` swaps the plain frequencies for YaRN's; ``rotary_dim``
    rotates the head's first values only (the frequencies are those of
    a head that wide) and passes the rest."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    if scaling is None:
        inv_freq = 1.0 / (
            theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
        )  # [d/2]
        amp = 1.0
    else:
        inv_freq = scaling.inv_freq(d, theta)
        amp = scaling.cos_sin_scale
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq  # [B,S,d/2]
    cos = amp * jnp.cos(angles)[:, :, None, :]  # [B,S,1,d/2]
    sin = amp * jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:d]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x32_1 * cos - x32_2 * sin, x32_2 * cos + x32_1 * sin], axis=-1
    )
    if d < x.shape[-1]:
        return jnp.concatenate([out.astype(x.dtype), x[..., d:]], axis=-1)
    return out.astype(x.dtype)


def _gqa_decode_attention(q, k, v, mask, scale=None, sink=None):
    """Decode-path attention with query heads grouped over shared KV
    heads. q: [B, S, H, D]; k: [B, T, Hkv, D]; v: [B, T, Hkv, Dv]; mask:
    [B, 1, S, T] (True = attend); ``scale``: the softmax's, D ** -0.5
    unless given; ``sink`` [H] float32: a learned score a query head
    that takes its share of the softmax and adds no value (one more
    term of the denominator). f32 logits/softmax like ops.attention's."""
    from tpudl.ops.attention import MASK_VALUE

    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * (scale or d ** -0.5)
    logits = logits.astype(jnp.float32)
    logits = jnp.where(mask[:, :, None, :, :], logits, MASK_VALUE)
    if sink is None:
        weights = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    else:
        with jax.named_scope("attention_sink"):
            bias = sink.astype(jnp.float32).reshape(1, hkv, g, 1, 1)
            top = jnp.maximum(logits.max(-1, keepdims=True), bias)
            weights = jnp.exp(logits - top)
            weights = (weights / (
                weights.sum(-1, keepdims=True) + jnp.exp(bias - top)
            )).astype(v.dtype)
    ctx = jnp.einsum("bhgqk,bkhd->bqhgd", weights, v)
    return ctx.reshape(b, s, h, v.shape[-1])


def _paged_cache_missing():
    raise ValueError(
        "paged decode requires a provided 'cache' collection (the page "
        "pools tpudl.serve.cache.PagedKVCache builds) — there is no "
        "shape information to initialize one here"
    )


#: A prefill that starts a cache attends its own chunk. Where its scores
#: against the whole row cache ([B, H, S, max_seq_len] float32) would pass
#: this many bytes (static shapes: 512 rows over 1,024 positions at 32
#: heads are 67 MB and keep the one dense pass, 4,096 rows at 64 heads
#: would be 5.4 GB), it is attended ``PREFILL_BLOCK`` queries at a time.
PREFILL_SCORE_BYTES = 256 << 20
PREFILL_BLOCK = 256


def _blocked_attention(q, k, v, valid, window, block, scale=None, chosen=None,
                       sink=None, forward_only=False):
    """Causal grouped-query attention of a chunk over itself with no [H, S, S]
    tensor: a block of queries at a time against the keys up to its last slot
    (a ``window`` layer: the band), or for a serving prefill (``forward_only``:
    the kernel has no backward pass, and takes no ``sink`` [H]) one Pallas call
    where ``prefill_kernel_ok`` (ops.flash_attention). q: [B, S, H, D]; k, v:
    [B, S, Hkv, D] in slot order; valid: [B, S] bool; ``chosen`` [B, S, S] bool."""
    from tpudl.ops.flash_attention import prefill_attention, prefill_kernel_ok
    if forward_only and sink is None and prefill_kernel_ok(q, k, v, window):
        return prefill_attention(q, k, v, valid, scale, chosen)
    s = q.shape[1]
    out = []
    for at in range(0, s, block):
        end = min(at + block, s)
        low = max(at - (window - 1), 0) // block * block if window else 0
        q_slot = jnp.arange(at, end)[:, None]
        kv_slot = jnp.arange(low, end)[None, :]
        mask = (kv_slot <= q_slot)[None] & valid[:, None, low:end]
        if chosen is not None:
            mask = mask & chosen[:, at:end, low:end]
        if window:
            mask = mask & (q_slot - kv_slot < window)[None]
        out.append(_gqa_decode_attention(
            q[:, at:end], k[:, low:end], v[:, low:end], mask[:, None], scale,
            sink,
        ))
    return jnp.concatenate(out, axis=1)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    #: Which layer this is: ``cfg.layer_spec(layer)`` says what it is
    #: made of where the layers differ.
    layer: int = 0

    @nn.compact
    def __call__(
        self, hidden, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None, loop_pass: int = 0,
    ):
        cfg = self.cfg
        # Layers that differ say which kind they are in a trace.
        scope = (
            jax.named_scope(cfg.layer_spec(self.layer).scope)
            if cfg.layer_types is not None else contextlib.nullcontext()
        )
        with scope:
            return self._attend(
                hidden, positions, kv_mask, decode, paged, adapters, loop_pass
            )

    def _attend(self, hidden, positions, kv_mask, decode, paged, adapters, t):
        from tpudl.models.lora import adapter_delta

        cfg = self.cfg
        spec = cfg.layer_spec(self.layer)
        B, S, _ = hidden.shape
        hd, H, window = cfg.head_dim, spec.num_heads, spec.window
        # A layer kind has its own KV heads; values may be another
        # width than keys (the head's width on both counts unless the
        # configuration says otherwise).
        Hkv, vd = spec.num_kv_heads, cfg.value_dim
        # Pass ``t`` of a looped stack keeps cache leaves of its own.
        of_pass, last_pass = _pass_leaves(cfg, t)
        # Multi-tenant adapters (tpudl.models.lora.AdapterView): a slot's
        # LoRA delta rides AFTER the shared base projection, one dispatch.
        q = _proj(cfg, H * hd, "q_proj")(hidden)
        q = q + adapter_delta(adapters, "q_proj", hidden)
        k = _proj(cfg, Hkv * hd, "k_proj")(hidden)
        k = k + adapter_delta(adapters, "k_proj", hidden)
        v = _proj(cfg, Hkv * vd, "v_proj")(hidden)
        v = v + adapter_delta(adapters, "v_proj", hidden)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = _scaled(cfg.attention_value_scale, v.reshape(B, S, Hkv, vd))
        q = rope(q, positions, spec.rope_theta, spec.rope_scaling,
                 spec.rotary_dim)
        k = rope(k, positions, spec.rope_theta, spec.rope_scaling,
                 spec.rotary_dim)
        # A learned score a query head that every softmax of the layer
        # counts and no value follows (float32, like a router's bias).
        sink = (
            self.param("sink", nn.initializers.zeros, (H,), jnp.float32)
            if spec.sink else None
        )

        def project_out(ctx):
            """[B, S, H, vd] -> the layer's output: the gate by head
            where the layer has one, then ``o_proj``."""
            if spec.gate:
                with jax.named_scope("gate"):
                    gate = jax.nn.sigmoid(nn.Dense(
                        H, use_bias=False, dtype=cfg.dtype,
                        kernel_init=nn.initializers.normal(0.02),
                        name="g_proj",
                    )(hidden).astype(jnp.float32))
                    ctx = (ctx * gate[..., None]).astype(ctx.dtype)
            ctx = ctx.reshape(B, S, H * vd)
            out = _proj(cfg, cfg.hidden_size, "o_proj")(ctx)
            return out + adapter_delta(adapters, "o_proj", ctx)

        if decode and paged is not None:
            # Paged decode (tpudl.models.paged): KV lives in page pools
            # addressed by the host-provided page table instead of the
            # dense [B, max_seq] rows below — each slot has its OWN
            # length (no shared write index).
            # This step's rows are scattered into the donated pool
            # first (paged_write), then attention reads the pool: in
            # place where it can (tpudl.ops.paged_attention: a kernel
            # that visits only the pages a slot's live positions lie
            # on), and through the dense gather of every slot's whole
            # table where it cannot — int8 pools (per-(page, row, head)
            # dequant scales fused into the gather), a pool committed
            # to a mesh, any CPU run. The program chooses by what it
            # can observe and records the choice (PagedView.took).
            # Token chunks of any length step together (S=1 is the
            # plain decode step; S=k is the speculative-verify window,
            # causal within itself: query j attends up to lens + j);
            # prefill stays dense batch-1 (its row cache is scattered
            # into pages by PagedKVCache.seat).
            # A layer with a window keeps a RING of pages a slot in a
            # pool of its own; write and read address it through the
            # rotated view (PagedView.ring_view), as any other table.
            from tpudl.models.paged import paged_write
            from tpudl.ops.paged_attention import paged_attention

            if window:
                if S > 1:
                    raise ValueError(
                        "a window layer steps one token at a time: a "
                        "chunk's queries would each need their own "
                        "window over the ring"
                    )
                paged = paged.ring_view(window)
            pk = self.variable("cache", of_pass("pages_k"), _paged_cache_missing)
            pv = self.variable("cache", of_pass("pages_v"), _paged_cache_missing)
            sk = sv = None
            if paged.quantized:
                sk = self.variable("cache", of_pass("scale_k"), _paged_cache_missing)
                sv = self.variable("cache", of_pass("scale_v"), _paged_cache_missing)
            new_k, new_sk = paged_write(
                pk.value, sk.value if sk is not None else None, k, paged
            )
            new_v, new_sv = paged_write(
                pv.value, sv.value if sv is not None else None, v, paged
            )
            pk.value, pv.value = new_k, new_v
            if paged.quantized:
                sk.value, sv.value = new_sk, new_sv
            ctx = paged_attention(
                q, pk.value, pv.value, paged,
                scale_k=sk.value if sk is not None else None,
                scale_v=sv.value if sv is not None else None,
                sink=sink,
            )
            return project_out(ctx)

        if decode:
            # KV cache (flax decode idiom): static [B, max_seq, Hkv, D]
            # buffers updated in place at the current index — the
            # autoregressive serving path (the reference repo's decoder
            # analog). Shapes stay static so the step jits once.
            _count_prefill_attention()
            fresh = not self.has_variable("cache", of_pass("k"))
            ck = self.variable(
                "cache", of_pass("k"),
                jnp.zeros, (B, cfg.max_seq_len, Hkv, hd), k.dtype,
            )
            cv = self.variable(
                "cache", of_pass("v"),
                jnp.zeros, (B, cfg.max_seq_len, Hkv, vd), v.dtype,
            )
            # Per-slot validity: padded prompt slots hold garbage k/v and
            # must never be attended. Written alongside k/v from the
            # chunk's kv_mask, so the cache knows which of its slots are
            # real — the contract that lets generate() serve ragged
            # (left-padded) prompt batches.
            cvalid = self.variable(
                "cache", "valid",
                jnp.zeros, (B, cfg.max_seq_len), jnp.bool_,
            )
            idx = self.variable(
                "cache", "index", lambda: jnp.zeros((), jnp.int32)
            )
            if window:
                # What the layer keeps, declared beside its rows: the
                # page manager sizes this layer's pool as rings
                # (tpudl.serve.cache: the leaf's LENGTH is the window).
                self.variable(
                    "cache", "window", jnp.zeros, (window,), jnp.int8
                )
            start = idx.value
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, start, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, start, 0, 0)
            )
            chunk_valid = (
                jnp.ones((B, S), jnp.bool_)
                if kv_mask is None
                else kv_mask.astype(jnp.bool_)
            )
            cvalid.value = jax.lax.dynamic_update_slice(
                cvalid.value, chunk_valid, (0, start)
            )
            idx.value = start + (S if last_pass else 0)
            if fresh and (
                4 * B * H * S * cfg.max_seq_len > PREFILL_SCORE_BYTES
            ):
                # A long prompt into an empty cache: the chunk is all
                # there is to attend to, and it is attended in blocks.
                return project_out(_blocked_attention(
                    q, k, v, chunk_valid, window, PREFILL_BLOCK, sink=sink,
                    forward_only=True))
            k, v = ck.value, cv.value
            # Attend to slots that are (a) causally prior in WRITE order —
            # slots fill in token order, so slot order IS causal order
            # regardless of padding — and (b) valid. Positions (which pads
            # alias) play no role in masking; they only drive RoPE phases.
            kv_slot = jnp.arange(cfg.max_seq_len)[None, None, None, :]
            q_slot = (start + jnp.arange(S))[None, None, :, None]
            mask = (kv_slot <= q_slot) & cvalid.value[:, None, None, :]
            if window:
                mask = mask & (q_slot - kv_slot < window)
            # Grouped-query attention against the UNEXPANDED cache — never
            # materialize [B, max_seq, H, D] (the 4x KV blowup per decode
            # step that GQA exists to avoid).
            return project_out(
                _gqa_decode_attention(q, k, v, mask, sink=sink)
            )

        if window or sink is not None:
            # Training / scoring with a window (the band) or a sink (a
            # term no ``attend`` implementation has): under an explicit mask,
            # in XLA's blocks, which differentiate (not ``forward_only``).
            valid = (
                jnp.ones((B, S), jnp.bool_) if kv_mask is None
                else kv_mask.astype(jnp.bool_)
            )
            return project_out(_blocked_attention(
                q, k, v, valid, window, PREFILL_BLOCK, sink=sink
            ))
        if Hkv != H:  # GQA: expand kv heads
            reps = H // Hkv
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
        q = constrain(q, ("dp", "fsdp"), "sp", "tp", None)
        k = constrain(k, ("dp", "fsdp"), "sp", "tp", None)
        v = constrain(v, ("dp", "fsdp"), "sp", "tp", None)
        # kv_mask ([B, S] validity row) masks padding alongside the causal
        # triangle — without it a LEFT-padded batch would attend to pad
        # garbage (causality only happens to hide trailing pads). All four
        # attention implementations accept the [B, S] row contract.
        ctx = attend(
            q, k, v, mask=kv_mask, causal=True,
            implementation=cfg.attention_impl,
        )
        return project_out(ctx)


class LatentAttention(nn.Module):
    """Latent (MLA) attention. A position is cached as ONE row
    ``[c | k_r]``: the RMS-normed latent ``c`` (``kv_lora_rank``, times
    the constant ``mla_scale_kv``: the scale is IN the row) and the
    roped key ``k_r`` (``qk_rope_head_dim``) every head shares. No
    value pool, no head axis: the cache declares the one leaf ``kv``
    (dense rows) / ``pages_kv`` (page pool); tpudl.serve.cache pools
    whatever leaves a layer declares. The query: ``_latent_query``.

    Two forms of the same attention, which must agree. Prefill and
    training up-project: ``[k_nope_h | v_h] = c W_kv_b``. Paged decode
    absorbs ``W_kv_b`` into the query and the output and attends the
    cached rows as they are, one head for all query heads: ``score =
    (W_kv_b^K q_nope)·c + q_rope·k_r``, ``ctx_h = (Σ p c) W_kv_b^V``; in
    place through the latent kernel where it can, else gathered dense.

    With ``cfg.index_topk`` (learned sparse attention, below the stack)
    both forms attend a CHOICE of the cached positions: ``layer``'s
    indexer makes it ("full") or ``choice`` hands the last one in
    ("shared"), and the call returns ``(output, choice)``."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, hidden, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None, layer: int = 0, choice=None,
    ):
        cfg = self.cfg
        if adapters is not None:
            raise ValueError(
                "per-tenant adapters are not wired to latent attention"
            )
        B, S, _ = hidden.shape
        H, r = cfg.num_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        scale = (dn + dr) ** -0.5
        if cfg.rope_scaling is not None:
            scale *= cfg.rope_scaling.attention_scale
        q, low = _latent_query(cfg, hidden)
        q_nope = q[..., :dn]
        q_rope = rope(q[..., dn:], positions, cfg.rope_theta, cfg.rope_scaling)
        with jax.named_scope("kv_down"):
            down = nn.Dense(
                r + dr, use_bias=False, dtype=cfg.dtype,
                kernel_init=nn.initializers.normal(0.02), name="kv_a_proj",
            )(hidden)
            c = _latent_norm(cfg, down[..., :r])
            k_r = rope(
                down[..., None, r:], positions, cfg.rope_theta,
                cfg.rope_scaling,
            )[:, :, 0]
            latent = jnp.concatenate([c, k_r.astype(c.dtype)], axis=-1)
        kv_b = self.param(
            "kv_b_proj", nn.initializers.normal(0.02), (r, H * (dn + dv))
        ).astype(cfg.dtype).reshape(r, H, dn + dv)
        # A "full" layer's indexer: its queries, keys and head weights.
        index = _index_of(self, layer, low, hidden, positions)
        real = None if index is None else real_tokens(hidden, kv_mask, paged)

        if decode and paged is not None:
            from tpudl.models.paged import paged_write
            from tpudl.ops.paged_attention import paged_latent_attention

            pool = self.variable("cache", "pages_kv", _paged_cache_missing)
            sc = None
            if paged.quantized:
                sc = self.variable("cache", "scale_kv", _paged_cache_missing)
            # This step's row is scattered into the donated pool first.
            scales = sc.value if sc is not None else None
            pool.value, scales = paged_write(pool.value, scales, latent, paged)
            if sc is not None:
                sc.value = scales
            # Then the absorbed attention reads the pool: in place where
            # it can, else gathered (PagedView.took records the choice).
            choice = _paged_choice(self, index, choice, paged, real)
            with jax.named_scope("mla_core"):
                query = _absorbed_query(q_nope, q_rope, kv_b, dn)
            u = paged_latent_attention(
                query, pool.value, paged, rank=r, scale=scale, scales=scales, chosen=choice
            )
            with jax.named_scope("mla_core"):
                ctx = jnp.einsum("bshr,rhd->bshd", u, kv_b[..., dn:])
        elif decode:
            # The dense row cache of a prefill (a chunked suffix prefill is handed
            # the prefix's rows). Made HERE it starts empty: the chunk is all.
            _count_prefill_attention()
            fresh = not self.has_variable("cache", "kv")
            ckv = self.variable(
                "cache", "kv", jnp.zeros, (B, cfg.max_seq_len, r + dr),
                latent.dtype,
            )
            cvalid = self.variable(
                "cache", "valid", jnp.zeros, (B, cfg.max_seq_len), jnp.bool_
            )
            idx = self.variable(
                "cache", "index", lambda: jnp.zeros((), jnp.int32)
            )
            start = idx.value
            chunk_valid = (
                jnp.ones((B, S), jnp.bool_) if kv_mask is None
                else kv_mask.astype(jnp.bool_)
            )
            ckv.value = jax.lax.dynamic_update_slice(
                ckv.value, latent, (0, start, 0)
            )
            cvalid.value = jax.lax.dynamic_update_slice(
                cvalid.value, chunk_valid, (0, start)
            )
            idx.value = start + S
            q_slot = jnp.arange(S)[None, None, :, None]
            if fresh:
                rows, valid = latent, chunk_valid
            else:
                rows, valid = ckv.value, cvalid.value
                q_slot = q_slot + start
            kv_slot = jnp.arange(rows.shape[1])[None, None, None, :]
            mask = (kv_slot <= q_slot) & valid[:, None, None, :]
            choice = _dense_choice(self, index, choice, mask, real, start, fresh)
            ctx = _mla_prefill(q_nope, q_rope, rows, kv_b, dn, mask, scale,
                               valid, fresh, choice)
        else:
            slot = jnp.arange(S)
            mask = (slot[None, :] <= slot[:, None])[None, None]
            if kv_mask is not None:
                mask = mask & kv_mask.astype(jnp.bool_)[:, None, None, :]
            choice = _dense_choice(self, index, choice, mask, real)
            ctx = _mla_prefill(q_nope, q_rope, latent, kv_b, dn, mask, scale,
                               None, False, choice)
        out = _proj(cfg, cfg.hidden_size, "o_proj")(ctx.reshape(B, S, H * dv))
        return (out, choice) if cfg.index_topk else out


def _masked_softmax(logits, mask, dtype):
    from tpudl.ops.attention import MASK_VALUE

    logits = jnp.where(mask, logits.astype(jnp.float32), MASK_VALUE)
    return jax.nn.softmax(logits, axis=-1).astype(dtype)


def _mla_up_projected(q_nope, q_rope, rows, kv_b, dn, mask, scale):
    """Keys and values up-projected from the cached rows. q_nope:
    [B, S, H, dn]; q_rope: [B, S, H, dr]; rows: [B, T, r + dr]; kv_b:
    [r, H, dn + dv]; mask: [B, 1, S, T] (True = attend). -> [B, S, H,
    dv]."""
    r = kv_b.shape[0]
    with jax.named_scope("mla_core"):
        up = jnp.einsum("btr,rhd->bthd", rows[..., :r], kv_b)
        logits = jnp.einsum("bshd,bthd->bhst", q_nope, up[..., :dn])
        logits = logits + jnp.einsum("bshd,btd->bhst", q_rope, rows[..., r:])
        weights = _masked_softmax(logits * scale, mask, rows.dtype)
        return jnp.einsum("bhst,bthd->bshd", weights, up[..., dn:])


def _absorbed_query(q_nope, q_rope, kv_b, dn):
    """``[q_nope W_kv_b^K | q_rope]``: [B, S, H, r + dr], the query that
    meets a cached row ``[c | k_r]`` as it is."""
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, kv_b[..., :dn])
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def attend_latent_rows(query, rows, mask, scale, r):
    """Dense attention of the absorbed ``query`` [B, S, H, C] over the
    cached rows, one head shared by all query heads: a row's score is
    ``query . row``, its value the row's first ``r``. -> [B, S, H, r].

    ``rows`` may come FOLDED, as a folded pool holds them
    (tpudl.models.paged.page_fold): [B, T / f, f * C], logical position
    ``f * i + g`` in lanes ``g * C ...`` of held row ``i``, with
    ``mask`` [B, f, S, T / f] in the same order. The ``f`` lane blocks
    are attended as ``f`` groups of positions under one softmax.
    Cutting block ``g`` out of a view that size at lane ``g * C`` would
    copy it on the chip, so each group reads a window of WHOLE lanes
    around its block: the query is padded with zeros to the window and
    the values' block is cut out of the small result."""
    from tpudl.models.paged import lane_window
    from tpudl.ops.attention import MASK_VALUE

    C, width = query.shape[-1], rows.shape[-1]
    logits = []
    for g in range(width // C):
        lo, hi = lane_window(g * C, C, width)
        padded = jnp.pad(
            query, [(0, 0)] * 3 + [(g * C - lo, hi - (g + 1) * C)]
        )
        group = jnp.einsum("bshc,btc->bhst", padded, rows[..., lo:hi])
        logits.append(jnp.where(
            mask[:, g, None], group.astype(jnp.float32) * scale,
            MASK_VALUE,
        ))
    # One softmax over every group's positions (jax.nn.softmax,
    # written out over the list).
    top = logits[0].max(-1, keepdims=True)
    for x in logits[1:]:
        top = jnp.maximum(top, x.max(-1, keepdims=True))
    weights = [jnp.exp(x - jax.lax.stop_gradient(top)) for x in logits]
    total = sum(w.sum(-1, keepdims=True) for w in weights)
    u = 0.0
    for g, w in enumerate(weights):
        lo, hi = lane_window(g * C, r, width)
        part = jnp.einsum(
            "bhst,btc->bshc", (w / total).astype(rows.dtype),
            rows[..., lo:hi], preferred_element_type=jnp.float32,
        )
        u = u + part[..., g * C - lo:g * C - lo + r]
    return u.astype(rows.dtype)


def _mla_absorbed(q_nope, q_rope, rows, kv_b, dn, mask, scale):
    """The same attention with ``kv_b`` absorbed into the query and the
    output, the rows attended as they are cached: what paged decode
    computes (the kernel in place of ``attend_latent_rows`` on a TPU)."""
    with jax.named_scope("mla_core"):
        query = _absorbed_query(q_nope, q_rope, kv_b, dn)
        u = attend_latent_rows(query, rows, mask, scale, kv_b.shape[0])
        return jnp.einsum("bshr,rhd->bshd", u, kv_b[..., dn:])


class LlamaBlock(nn.Module):
    cfg: LlamaConfig
    #: "dense" or "moe" (``LlamaConfig.mlp_kind`` of this layer).
    mlp: str = "dense"
    #: The layer's index, for ``LlamaConfig.layer_spec``.
    layer: int = 0

    @nn.compact
    def __call__(
        self, hidden, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None, choice=None,
    ):
        from tpudl.models.lora import adapter_delta
        from tpudl.ops.norms import fused_ops_impl
        cfg = self.cfg
        impl = fused_ops_impl(cfg.fused_ops)
        # With an indexer a layer takes the last choice of rows, hands its own on.
        sparse = {"layer": self.layer, "choice": choice} if cfg.index_topk else {}
        if cfg.attention == "mla":
            attention = LatentAttention(cfg, name="attention")
        else:
            attention = LlamaAttention(cfg, self.layer, name="attention")
        attn = attention(
            RMSNorm(cfg.rms_norm_eps, impl, name="input_norm")(hidden),
            positions,
            kv_mask,
            decode,
            paged,
            adapters, **sparse,
        )
        attn, choice = attn if cfg.index_topk else (attn, None)
        # The attention residual add rides inside the post-attention norm
        # kernel; the summed value comes back as the carried residual.
        x, hidden = RMSNorm(
            cfg.rms_norm_eps, impl, name="post_attention_norm"
        )(attn, residual=hidden)
        with jax.named_scope("mlp"):
            if self.mlp == "moe":
                from tpudl.ops.moe import DroplessMoE

                if adapters is not None:
                    raise ValueError(
                        "per-tenant adapters are not wired to routed experts"
                    )
                # Tokens that are real: a prompt's, not its padding's;
                # a seated slot's, not an idle slot's ride-along (whose
                # table row maps the trash page).
                if paged is not None:
                    real = jnp.broadcast_to(
                        (paged.page_table[:, :1] != 0), x.shape[:2]
                    )
                elif kv_mask is not None:
                    real = kv_mask.astype(jnp.bool_)
                else:
                    real = jnp.ones(x.shape[:2], jnp.bool_)
                down = DroplessMoE(
                    num_experts=cfg.num_experts,
                    experts_per_token=cfg.experts_per_token,
                    intermediate_size=cfg.moe_intermediate_size,
                    shared_intermediate_size=(
                        cfg.num_shared_experts * cfg.moe_intermediate_size
                    ),
                    routed_scaling_factor=cfg.routed_scaling_factor,
                    experts_held=cfg.experts_held,
                    dtype=cfg.dtype,
                    weight_dtype=cfg.weight_dtype,
                    name="moe",
                )(x, real)
            elif cfg.moe_experts > 0:
                from tpudl.ops.moe import MoEMlp

                down = MoEMlp(
                    num_experts=cfg.moe_experts,
                    intermediate_size=cfg.intermediate_size,
                    k=cfg.moe_k,
                    capacity_factor=cfg.moe_capacity_factor,
                    gated=True,
                    act=nn.silu,
                    dtype=cfg.dtype,
                    name="moe",
                )(x)
            else:
                from tpudl.ops.mlp_fused import swiglu

                gate = _proj(cfg, cfg.intermediate_size, "gate_proj")(x)
                gate = gate + adapter_delta(adapters, "gate_proj", x)
                up = _proj(cfg, cfg.intermediate_size, "up_proj")(x)
                up = up + adapter_delta(adapters, "up_proj", x)
                act = swiglu(gate, up, impl=impl)
                down = _proj(cfg, cfg.hidden_size, "down_proj")(act)
                down = down + adapter_delta(adapters, "down_proj", act)
        hidden = constrain(hidden + down, ("dp", "fsdp"), "sp", "tp")
        return (hidden, choice) if cfg.index_topk else hidden


class LlamaModel(nn.Module):
    """Decoder stack: embeddings + N blocks + final RMSNorm."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, input_ids, attention_mask=None, decode=False, positions=None,
        paged=None, adapters=None,
    ):
        cfg = self.cfg
        # kv_mask=None keeps the unpadded fast path (no in-kernel validity
        # masking); any explicit attention_mask is enforced in attention.
        kv_mask = attention_mask
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        if positions is None:
            # Positions skip padding so RoPE phases match left-padded
            # batches. Decode callers pass absolute positions explicitly
            # (tpudl.models.generate tracks the cache offset).
            positions = jnp.maximum(
                jnp.cumsum(attention_mask, axis=-1) - 1, 0
            ).astype(jnp.int32)
        with jax.named_scope("embeddings"):
            x = nn.Embed(
                cfg.vocab_size, cfg.hidden_size, name="embed_tokens",
                embedding_init=nn.initializers.normal(0.02),
            )(input_ids).astype(cfg.dtype)
        x = _enter_stream(cfg, constrain(x, ("dp", "fsdp"), "sp", "tp"))
        block = _block_of(cfg)
        if cfg.index_topk:  # layers that hand on an indexer's choice
            return _sparse_stack(self, block, x, positions, kv_mask, decode, paged, adapters)
        if cfg.loop_passes > 1:
            return _loop(self, block, x, positions, kv_mask, decode, paged, adapters)
        if cfg.remat and not decode:  # (adapter views are decode-only)
            block = nn.remat(block, static_argnums=(4, 5))
        for i in range(cfg.num_layers):
            x = block(cfg, cfg.mlp_kind(i), i, name=f"layer_{i}")(
                x, positions, kv_mask, decode, paged,
                adapters.for_layer(f"layer_{i}")
                if adapters is not None
                else None,
            )
        from tpudl.ops.norms import fused_ops_impl

        return RMSNorm(
            cfg.rms_norm_eps, fused_ops_impl(cfg.fused_ops),
            name="final_norm"
        )(_leave_stream(cfg, x))


class LlamaForCausalLM(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(
        self, input_ids, attention_mask=None, decode=False, positions=None,
        paged=None, adapters=None, last_only: bool = False,
    ):
        x = LlamaModel(self.cfg, name="model")(
            input_ids, attention_mask, decode, positions, paged, adapters
        )
        if last_only:
            # A caller that reads the last position's logits alone (a
            # long prefill) says so: the head then runs on one row.
            x = x[:, -1:]
        logits = nn.Dense(
            self.cfg.vocab_size,
            use_bias=False,
            dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02),
            name="lm_head",
        )(x)
        return logits.astype(jnp.float32)


class LlamaForSequenceClassification(nn.Module):
    """configs[4]-style fine-tune head: classify from the last non-padding
    token's hidden state (causal LM pooling)."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, train: bool = False):
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        x = LlamaModel(self.cfg, name="model")(input_ids, attention_mask)
        last = jnp.maximum(jnp.sum(attention_mask, axis=-1) - 1, 0)
        pooled = jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32), axis=1
        )[:, 0]
        logits = nn.Dense(
            self.cfg.num_labels,
            dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02),
            name="classifier",
        )(pooled)
        return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# The shortcut-connected double layer, and what the latent attention and
# the configuration take for it. Kept BELOW the decoder stack: the
# compiled programs of the configurations in the benchmark hold the
# line numbers of the attention call chain above (PERF.md section 7).
# ---------------------------------------------------------------------------


def _check_block(cfg: LlamaConfig) -> None:
    """``__post_init__``: the block, the low-rank query, the router."""
    _check_stream(cfg)
    _check_loop(cfg)
    _check_sparse(cfg)
    _check_kinds(cfg)
    if cfg.block not in _BLOCKS:
        raise ValueError(
            f"block must be one of {sorted(_BLOCKS)}, got {cfg.block!r}"
        )
    if cfg.router_scoring not in ("sigmoid", "softmax"):
        raise ValueError(
            f"router_scoring must be 'sigmoid' or 'softmax', got {cfg.router_scoring!r}"
        )
    if cfg.q_lora_rank < 0 or cfg.zero_experts < 0:
        raise ValueError(
            f"q_lora_rank and zero_experts must be >= 0, got "
            f"{cfg.q_lora_rank} and {cfg.zero_experts}")
    if cfg.zero_experts and not cfg.num_experts:
        raise ValueError(
            "zero_experts are ids past num_experts of a routed-expert "
            "layer's router: set num_experts"
        )
    if cfg.block == "shortcut" and not (
        cfg.attention == "mla" and cfg.num_experts > 0
        and cfg.first_k_dense == 0
    ):
        raise ValueError(
            "block='shortcut' is two latent attentions and two dense "
            "FFNs around one expert branch in EVERY layer: it needs "
            "attention='mla', num_experts > 0 and first_k_dense == 0"
        )


def _scaled(scale: float, x):
    """``x`` times a configuration's constant; untouched (the program
    it had) where the constant is 1."""
    return x if scale == 1.0 else (x * scale).astype(x.dtype)


def _latent_query(cfg: LlamaConfig, hidden):
    """``LatentAttention``'s query [B, S, H, dn + dr]: one matrix ``q_proj``,
    or with ``q_lora_rank`` ``W_qb (RMSNorm(W_qa x) * mla_scale_q)``; and
    that normed low-rank input, which an indexer reads too (None without)."""
    heads = (*hidden.shape[:2], cfg.num_heads, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if not cfg.q_lora_rank:
        return _proj(cfg, heads[2] * heads[3], "q_proj")(hidden).reshape(heads), None
    with jax.named_scope("q_down"):
        low = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
            _proj(cfg, cfg.q_lora_rank, "q_a_proj")(hidden))
    q = _proj(cfg, heads[2] * heads[3], "q_b_proj")(_scaled(cfg.mla_scale_q, low))
    return q.reshape(heads), low


def _latent_norm(cfg: LlamaConfig, down):
    """``LatentAttention``'s cached latent: ``RMSNorm(c') *
    mla_scale_kv``."""
    return _scaled(
        cfg.mla_scale_kv, RMSNorm(cfg.rms_norm_eps, name="kv_norm")(down)
    )


def real_tokens(x, kv_mask, paged):
    """[B, S] bool: the tokens that are real: a prompt's, not its
    padding's; a seated slot's, not an idle slot's ride-along (whose
    table row maps the trash page). ``LlamaBlock`` keeps the same three
    lines inline, and its own dense MLP beside ``_DenseFFN``: its line
    numbers are held (the note above)."""
    if paged is not None:
        return jnp.broadcast_to(paged.page_table[:, :1] != 0, x.shape[:2])
    if kv_mask is not None:
        return kv_mask.astype(jnp.bool_)
    return jnp.ones(x.shape[:2], jnp.bool_)


class _DenseFFN(nn.Module):
    """A dense SwiGLU of ``intermediate_size`` under the projection
    names the quantizer's rules address."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        from tpudl.ops.mlp_fused import swiglu
        from tpudl.ops.norms import fused_ops_impl

        cfg = self.cfg
        act = swiglu(
            _proj(cfg, cfg.intermediate_size, "gate_proj")(x),
            _proj(cfg, cfg.intermediate_size, "up_proj")(x),
            impl=fused_ops_impl(cfg.fused_ops),
        )
        return _proj(cfg, cfg.hidden_size, "down_proj")(act)


class ShortcutBlock(nn.Module):
    """The shortcut-connected double layer (``LlamaConfig.block ==
    "shortcut"``): two sublayers ``i`` of latent attention ``A_i`` and
    dense SwiGLU FFN ``F_i``, each with its own norms, and ONE routed
    expert branch that reads the first sublayer's normed
    post-attention stream and joins the residual at the END of the
    second:

        a0 = h  + A_0(norm_in0(h))
        x0 = norm_post0(a0)
        m  = MoE(x0)                          # read here ...
        b0 = a0 + F_0(x0)
        a1 = b0 + A_1(norm_in1(b0))
        h' = a1 + F_1(norm_post1(a1)) + m     # ... added here

    so the experts (and in a deployment their exchange) run beside the
    first dense FFN and the whole second attention. A layer declares
    TWO latent row leaves (``attention_0/kv``, ``attention_1/kv``):
    the page manager pools whatever a layer declares. Scopes:
    ``attention`` (> ``mla_core``, ``kv_down``), ``mlp`` >
    ``dense_ffn`` | ``moe`` (> ``router``, ``experts``,
    ``zero_experts``)."""

    cfg: LlamaConfig
    #: ``LlamaBlock``'s fields: every layer of this kind routes.
    mlp: str = "moe"
    layer: int = 0

    @nn.compact
    def __call__(
        self, hidden, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None,
    ):
        from tpudl.ops.moe import DroplessMoE
        from tpudl.ops.norms import fused_ops_impl

        cfg = self.cfg
        if adapters is not None:
            raise ValueError(
                "per-tenant adapters are not wired to the shortcut "
                "double layer"
            )
        impl = fused_ops_impl(cfg.fused_ops)
        for i in (0, 1):
            normed = RMSNorm(
                cfg.rms_norm_eps, impl, name=f"input_norm_{i}"
            )(hidden)
            with jax.named_scope("attention"):
                attn = LatentAttention(cfg, name=f"attention_{i}")(
                    normed, positions, kv_mask, decode, paged
                )
            x, hidden = RMSNorm(
                cfg.rms_norm_eps, impl, name=f"post_attention_norm_{i}"
            )(attn, residual=hidden)
            with jax.named_scope("mlp"):
                if i == 0:
                    shortcut = DroplessMoE(
                        num_experts=cfg.num_experts,
                        experts_per_token=cfg.experts_per_token,
                        intermediate_size=cfg.moe_intermediate_size,
                        routed_scaling_factor=cfg.routed_scaling_factor,
                        experts_held=cfg.experts_held,
                        dtype=cfg.dtype,
                        weight_dtype=cfg.weight_dtype,
                        scoring=cfg.router_scoring,
                        renormalize=cfg.router_renormalize,
                        zero_experts=cfg.zero_experts,
                        name="moe",
                    )(x, real_tokens(x, kv_mask, paged))
                with jax.named_scope("dense_ffn"):
                    hidden = hidden + _DenseFFN(cfg, name=f"mlp_{i}")(x)
        hidden = hidden + shortcut
        return constrain(hidden, ("dp", "fsdp"), "sp", "tp")


class HyperBlock(nn.Module):
    """``LlamaBlock``'s two sublayers around a residual stream of
    ``cfg.hyper_streams`` vectors a token, [B, S, n, d]
    (tpudl.models.hyper): each sublayer reads a learned mixture of the
    streams through the layer's own norm (``input_norm`` /
    ``post_attention_norm``), and its output is written back through a
    doubly-stochastic n x n map and a learned fan-out:

        u, h_post, H = maps(X);  X <- H X + h_post F(norm(u))^T

    once for the attention, once for the dense SwiGLU or the expert
    layer, each with maps of its own (``hyper_attention``,
    ``hyper_mlp``). Nothing is added to anything else: there is no
    residual sum for a norm to fold. Scopes: ``hyper`` (>
    ``hyper_maps``, ``hyper_mix_in``, ``hyper_mix_out``) beside
    ``attention`` and ``mlp``. A block of its own, so that
    ``LlamaBlock`` keeps its program, and its lines, as they were."""

    cfg: LlamaConfig
    mlp: str = "dense"
    layer: int = 0

    @nn.compact
    def __call__(
        self, stream, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None,
    ):
        from tpudl.models.hyper import HyperConnection, mix_out
        from tpudl.ops.norms import fused_ops_impl

        cfg = self.cfg
        if adapters is not None:
            raise ValueError(
                "per-tenant adapters are not wired to a stream of "
                "several vectors a token (hyper_streams)"
            )
        impl = fused_ops_impl(cfg.fused_ops)
        real = real_tokens(stream, kv_mask, paged)

        def attention(x):
            if cfg.attention == "mla":
                module = LatentAttention(cfg, name="attention")
            else:
                module = LlamaAttention(cfg, self.layer, name="attention")
            return module(x, positions, kv_mask, decode, paged)

        def mlp(x):
            with jax.named_scope("mlp"):
                if self.mlp != "moe":
                    return _DenseFFN(cfg, name="mlp")(x)
                from tpudl.ops.moe import DroplessMoE

                return DroplessMoE(
                    num_experts=cfg.num_experts,
                    experts_per_token=cfg.experts_per_token,
                    intermediate_size=cfg.moe_intermediate_size,
                    shared_intermediate_size=(
                        cfg.num_shared_experts * cfg.moe_intermediate_size
                    ),
                    routed_scaling_factor=cfg.routed_scaling_factor,
                    experts_held=cfg.experts_held,
                    dtype=cfg.dtype,
                    weight_dtype=cfg.weight_dtype,
                    scoring=cfg.router_scoring,
                    renormalize=cfg.router_renormalize,
                    zero_experts=cfg.zero_experts,
                    name="moe",
                )(x, real)

        for name, norm, sublayer in (
            ("hyper_attention", "input_norm", attention),
            ("hyper_mlp", "post_attention_norm", mlp),
        ):
            u, h_post, h_res = HyperConnection(
                cfg.hyper_streams, cfg.hyper_sinkhorn_iters, cfg.hyper_eps,
                cfg.hyper_clamp, cfg.rms_norm_eps, name=name,
            )(stream, real)
            y = sublayer(RMSNorm(cfg.rms_norm_eps, impl, name=norm)(u))
            stream = mix_out(stream, h_res, h_post, y)
        return constrain(stream, ("dp", "fsdp"), "sp", None, "tp")


#: ``LlamaConfig.block`` -> the module a layer is.
_BLOCKS = {"llama": LlamaBlock, "shortcut": ShortcutBlock}


def _check_kinds(cfg: LlamaConfig) -> None:
    """``_check_block``'s checks of what a grouped-query layer kind may
    have of its own (KV heads, rotary share, a sink in the softmax) and
    of a value width and scale: what they are, and what they are not
    wired to."""
    sliding = (
        cfg.sliding_num_kv_heads or cfg.sliding_partial_rotary_factor != 1.0
        or cfg.sliding_attention_sink
    )
    sink = cfg.full_attention_sink or cfg.sliding_attention_sink
    value = cfg.attention_value_scale != 1.0 or cfg.value_head_size
    if not (sliding or sink or value):
        return
    if cfg.sliding_num_kv_heads < 0 or not (
        0.0 < cfg.sliding_partial_rotary_factor <= 1.0
    ):
        raise ValueError(
            f"sliding_num_kv_heads must be >= 0 (0: num_kv_heads) and "
            f"sliding_partial_rotary_factor in (0, 1], got "
            f"{cfg.sliding_num_kv_heads} and "
            f"{cfg.sliding_partial_rotary_factor}"
        )
    if sliding and "sliding_attention" not in (cfg.layer_types or ()):
        raise ValueError(
            "sliding_num_kv_heads, sliding_partial_rotary_factor and "
            "sliding_attention_sink describe 'sliding_attention' layers: "
            "layer_types names none"
        )
    if cfg.attention == "mla":
        raise ValueError(
            "a softmax sink, a value scale and KV heads by layer kind are "
            "the grouped-query block's: latent attention keeps ONE "
            "headless row a position, its value width is the up-"
            "projection's (v_head_dim, not value_head_size), and neither "
            "of its forms has a sink term"
        )
    if cfg.block != "llama":
        raise ValueError(
            f"block={cfg.block!r} is built of latent attentions: a sink, "
            f"a value width or scale and KV heads by layer kind are "
            f"LlamaAttention's"
        )
    if cfg.hyper_streams:
        raise ValueError(
            "hyper_streams is not wired to a sink, a value width or scale "
            "or KV heads by layer kind: HyperBlock has been built and "
            "checked without them"
        )
    if cfg.loop_passes > 1 or cfg.sandwich_norm:
        raise ValueError(
            "a looped (sandwich-normed) stack keeps a k / v pool pair a "
            "(pass, layer) of ONE shape under one table: a sink, a value "
            "width or scale and KV heads by layer kind are not wired to it"
        )
    if cfg.index_topk:
        raise ValueError(
            "an indexer's choice is attended by the latent attention: a "
            "sink, a value width or scale and KV heads by layer kind are "
            "the grouped-query block's"
        )
    heads = set(cfg.num_heads_per_layer or (cfg.num_heads,))
    for name, kv in (("num_kv_heads", cfg.num_kv_heads),
                     ("sliding_num_kv_heads", cfg.sliding_num_kv_heads)):
        if kv and any(h % kv for h in heads):
            raise ValueError(
                f"{name} {kv} must divide a layer's query heads "
                f"{sorted(heads)}"
            )


def _check_stream(cfg: LlamaConfig) -> None:
    """``_check_block``'s checks of ``hyper_streams``: what a stream of
    several vectors a token is, and what it is not wired to."""
    if cfg.hyper_streams < 0 or cfg.hyper_streams == 1:
        raise ValueError(
            f"hyper_streams must be 0 (one residual vector a token) or "
            f">= 2 (that many, mixed by hyper-connections), got "
            f"{cfg.hyper_streams}"
        )
    if cfg.hyper_streams:
        # What a stream of several vectors a token is not wired to.
        if cfg.block == "shortcut":
            raise ValueError(
                "hyper_streams is not wired to block='shortcut': the "
                "double layer's expert branch leaves the residual after "
                "one attention and rejoins it after the second FFN, and "
                "which sublayer's maps would read and write the stream "
                "for it is not defined"
            )
        if cfg.lora_rank or cfg.moe_experts or cfg.fp8_train:
            raise ValueError(
                "hyper_streams is not wired to lora_rank, moe_experts "
                "or fp8_train: HyperBlock builds the serving sublayers "
                "(attention, dense SwiGLU, dropless experts) and none "
                "of the training tiers"
            )


def _block_of(cfg: LlamaConfig):
    """The module a layer of ``cfg`` is: by ``block``, for a stream of
    several vectors a token ``HyperBlock``, with a norm on each
    sublayer's output ``SandwichBlock``."""
    if cfg.sandwich_norm:
        return SandwichBlock
    return HyperBlock if cfg.hyper_streams else _BLOCKS[cfg.block]


def _enter_stream(cfg: LlamaConfig, x):
    """The embedding as the blocks' residual: itself, or repeated into
    ``hyper_streams`` streams, [B, S, n, d]."""
    if not cfg.hyper_streams:
        return x
    return jnp.repeat(x[:, :, None], cfg.hyper_streams, axis=2)


def _leave_stream(cfg: LlamaConfig, x):
    """What the final norm reads: the residual, or the streams' sum
    (float32, stored as the stream is)."""
    if not cfg.hyper_streams:
        return x
    return jnp.sum(x, axis=2, dtype=jnp.float32).astype(x.dtype)


# ---------------------------------------------------------------------------
# A stack run several times over the same weights (a looped language
# model, arXiv:2510.25741), and the sandwich-normed layer it is made of.
# ---------------------------------------------------------------------------

#: The statistic a looped stack sows into the serving statistics'
#: collection (tpudl.models.generate), float32 [T + 1] over the call's
#: real tokens: the exit distribution's mass at each pass, summed, and
#: how many tokens they were.
LOOP_STAT_NAME = "loop_exit"


def _pass_leaves(cfg: LlamaConfig, t: int):
    """``(name -> the cache leaf pass t keeps under it, whether t is the
    last pass)``. One pass (every model but a looped one) keeps the
    names as they are; pass ``t`` of several keeps ``<name>_pass<t>``,
    so that a layer declares T row leaves of each kind beside ONE
    ``valid`` and ONE ``index``, which only the last pass advances:
    the page manager pools whatever leaves a layer declares."""
    if cfg.loop_passes == 1:
        return (lambda name: name), True
    return (lambda name: f"{name}_pass{t}"), t == cfg.loop_passes - 1


def _check_loop(cfg: LlamaConfig) -> None:
    """``_check_block``'s checks of ``loop_passes`` and
    ``sandwich_norm``: what they are, and what they are not wired to."""
    if cfg.loop_passes < 1:
        raise ValueError(
            f"loop_passes must be >= 1 (1: every layer runs once a "
            f"token), got {cfg.loop_passes}"
        )
    if cfg.loop_exit_threshold != 1.0:
        raise ValueError(
            f"loop_exit_threshold {cfg.loop_exit_threshold} < 1 lets a "
            f"token leave the loop before the last pass: the later "
            f"passes' cache rows of a token that left must still be "
            f"filled for the tokens after it, and a step's rows would "
            f"run different numbers of passes. Only 1.0 (every pass, the "
            f"last one's logits) is served (ROADMAP B-mech)"
        )
    if cfg.loop_passes > 1 and not cfg.sandwich_norm:
        raise ValueError(
            "loop_passes > 1 needs sandwich_norm: the block that takes "
            "the pass it runs in (a cache a (pass, layer)) is "
            "SandwichBlock, and a pass that reads the last one's NORMED "
            "state has only been defined with the norms on the "
            "sublayers' outputs"
        )
    if cfg.sandwich_norm and (
        cfg.attention != "gqa" or cfg.num_experts or cfg.hyper_streams
        or cfg.block != "llama" or cfg.layer_types is not None
        or cfg.lora_rank or cfg.moe_experts or cfg.fp8_train or cfg.remat
    ):
        raise ValueError(
            "sandwich_norm is grouped-query attention and a dense SwiGLU "
            "with a norm before and after each: it is not wired to "
            "attention='mla', routed experts, hyper_streams, "
            "block='shortcut', layer_types, lora_rank, moe_experts, "
            "fp8_train or remat"
        )


class SandwichBlock(nn.Module):
    """``LlamaBlock``'s two sublayers with a norm on each one's OUTPUT
    too, before the residual add (four norms a layer):

        x = x + norm_2(Attn(norm_1(x)));  x = x + norm_4(MLP(norm_3(x)))

    ``input_norm``, ``input_norm_2``, ``post_attention_norm``,
    ``post_attention_norm_2`` in that order; the dense SwiGLU under
    ``mlp``. Nothing is folded into a norm: what is added is a norm's
    OUTPUT. Takes the pass it runs in (``loop_pass``) for the
    attention's cache leaves. A block of its own, so that ``LlamaBlock``
    keeps its program, and its lines, as they were."""

    cfg: LlamaConfig
    mlp: str = "dense"
    layer: int = 0

    @nn.compact
    def __call__(
        self, hidden, positions, kv_mask=None, decode: bool = False,
        paged=None, adapters=None, loop_pass: int = 0,
    ):
        from tpudl.ops.norms import fused_ops_impl

        cfg = self.cfg
        if adapters is not None:
            raise ValueError(
                "per-tenant adapters are not wired to a sandwich-normed "
                "layer (sandwich_norm)"
            )
        impl = fused_ops_impl(cfg.fused_ops)

        def norm(name, x):
            return RMSNorm(cfg.rms_norm_eps, impl, name=name)(x)

        attn = LlamaAttention(cfg, self.layer, name="attention")(
            norm("input_norm", hidden), positions, kv_mask, decode, paged,
            None, loop_pass,
        )
        hidden = hidden + norm("input_norm_2", attn)
        with jax.named_scope("mlp"):
            down = _DenseFFN(cfg, name="mlp")(
                norm("post_attention_norm", hidden)
            )
        hidden = hidden + norm("post_attention_norm_2", down)
        return constrain(hidden, ("dp", "fsdp"), "sp", "tp")


def _loop(model, block, x, positions, kv_mask, decode, paged, adapters):
    """``LlamaModel``'s stack for ``loop_passes`` T > 1, called inside
    its ``__call__``: the SAME ``num_layers`` modules (one set of
    weights) run T times, pass ``t`` on cache leaves of its own; the
    final norm runs after EVERY pass and the next pass reads the normed
    state; after each, the exit gate ``lambda_t = sigmoid(w . h_{t+1} +
    b)`` (float32). The exit distribution

        p_t = lambda_t prod_{s<t} (1 - lambda_s)   (t < T - 1)
        p_{T-1} = prod_{s<T-1} (1 - lambda_s)

    is sown summed over the real tokens (``LOOP_STAT_NAME``); what is
    returned is the LAST pass's normed state (an exit threshold of 1).
    Scopes: ``loop_pass_<t>`` around each pass of the stack and its
    norm, ``exit_gate``."""
    from tpudl.ops.norms import fused_ops_impl

    cfg = model.cfg
    if adapters is not None:
        raise ValueError(
            "per-tenant adapters are not wired to a looped stack "
            "(loop_passes > 1)"
        )
    layers = [
        block(cfg, cfg.mlp_kind(i), i, name=f"layer_{i}")
        for i in range(cfg.num_layers)
    ]
    final_norm = RMSNorm(
        cfg.rms_norm_eps, fused_ops_impl(cfg.fused_ops), name="final_norm"
    )
    gate = nn.Dense(
        1, dtype=jnp.float32, param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(0.02), name="early_exit_gate",
    )
    real = real_tokens(x, kv_mask, paged).astype(jnp.float32)
    stay = jnp.ones(x.shape[:2], jnp.float32)
    pdf = []
    for t in range(cfg.loop_passes):
        with jax.named_scope(f"loop_pass_{t}"):
            for layer in layers:
                x = layer(x, positions, kv_mask, decode, paged, None, t)
            x = final_norm(x)
        with jax.named_scope("exit_gate"):
            if t == cfg.loop_passes - 1:
                pdf.append(stay)
            else:
                leave = jax.nn.sigmoid(gate(x.astype(jnp.float32))[..., 0])
                pdf.append(leave * stay)
                stay = stay * (1.0 - leave)
    with jax.named_scope("exit_gate"):
        model.sow("moe_stats", LOOP_STAT_NAME, jnp.stack(
            [jnp.sum(p * real) for p in pdf] + [jnp.sum(real)]
        ))
    return x


def _mla_prefill(
    q_nope, q_rope, rows, kv_b, dn, mask, scale, valid, fresh, choice=None
):
    """``_mla_up_projected`` for a chunk written into a row cache.
    Where the chunk starts the cache (``fresh``: its own rows are all
    there is to attend to; ``valid`` [B, S] marks the real ones) and
    its scores [B, H, S, S] float32 would pass ``PREFILL_SCORE_BYTES``,
    it is attended a block of ``PREFILL_BLOCK`` queries at a time by
    the grouped-query routine, with one KV head a query head: keys
    ``[k_nope_h | k_r]`` (the roped key repeated to every head),
    values ``v_h``. Chosen from the static shapes of the program being
    traced: 512 rows at 64 heads are 67 MB and keep the one pass.
    ``choice`` [B, S, T] bool: an indexer's choice of the rows, for
    each query (learned sparse attention, below): a mask more."""
    b, s, h, _ = q_nope.shape
    if not fresh or 4 * b * h * s * s <= PREFILL_SCORE_BYTES:
        if choice is not None:
            mask = mask & choice[:, None]
        return _mla_up_projected(q_nope, q_rope, rows, kv_b, dn, mask, scale)
    from tpudl.models.paged import LANES
    from tpudl.ops.flash_attention import prefill_kernel_ok
    from tpudl.ops.pallas_utils import round_up

    r, dr, dv = kv_b.shape[0], q_rope.shape[-1], kv_b.shape[-1] - dn
    with jax.named_scope("mla_core"):
        # The kernel takes a head as a column block of whole lanes.
        wide = round_up(dn + dr, LANES)
        like = jax.ShapeDtypeStruct((b, s, h, wide), rows.dtype)
        value = jax.ShapeDtypeStruct((b, s, h, dv), rows.dtype)
        if prefill_kernel_ok(like, like, value, 0):
            return _blocked_attention(
                *_kernel_operands(q_nope, q_rope, rows, kv_b, dn, wide),
                valid, 0, PREFILL_BLOCK, scale, choice, forward_only=True,
            )
        up = jnp.einsum("btr,rhd->bthd", rows[..., :r], kv_b)
        k_rope = jnp.broadcast_to(
            rows[:, :, None, r:], (b, s, h, rows.shape[-1] - r)
        )
        return _blocked_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1),
            jnp.concatenate([up[..., :dn], k_rope.astype(up.dtype)], axis=-1),
            up[..., dn:], valid, 0, PREFILL_BLOCK, scale, choice,
        )


def _kernel_operands(q_nope, q_rope, rows, kv_b, dn, wide):
    """``_mla_prefill``'s query, key and value as the prefill kernel
    reads them: ``[B, S, H, wide]``, ``[B, S, H, wide]``, ``[B, S, H,
    dv]``, each the result of ONE plain matmul or concatenate whose
    minor axes are ``H x D`` (the chip then writes it row by row, as the
    kernel's blocks read it; the einsum over ``[r, H, D]`` writes the
    POSITIONS minor and a transposing copy of every operand follows).
    The key is ``rows @ W``: a head's columns are its ``W_kv_b^K`` over
    the latent values, the identity over the roped ones (every head's
    roped key is the row's own: copied exactly, the sums of the latent
    part are the einsum's), and zeros up to ``wide``, whole lanes (the
    query's columns there are zeros too)."""
    b, s, h, _ = q_nope.shape
    r, dr, dv = kv_b.shape[0], q_rope.shape[-1], kv_b.shape[-1] - dn
    w = jnp.zeros((r + dr, h, wide), kv_b.dtype)
    w = w.at[:r, :, :dn].set(kv_b[..., :dn])
    w = w.at[r:, :, dn:dn + dr].set(jnp.eye(dr, dtype=kv_b.dtype)[:, None])
    key = rows.astype(kv_b.dtype) @ w.reshape(r + dr, h * wide)
    value = rows[..., :r].astype(kv_b.dtype) @ (
        kv_b[..., dn:].reshape(r, h * dv)
    )
    query = [q_nope, q_rope]
    if wide != dn + dr:
        query.append(jnp.zeros((b, s, h, wide - dn - dr), q_nope.dtype))
    return (
        jnp.concatenate(query, axis=-1), key.reshape(b, s, h, wide),
        value.reshape(b, s, h, dv),
    )


# ---------------------------------------------------------------------------
# Learned sparse attention over the latent cache (``model_type``
# ``glm_moe_dsa``; DeepSeek-V3.2's form): a small indexer scores every
# cached position for a query, and the latent attention above attends
# the ``index_topk`` best of them only. Most layers have no indexer and
# reuse the choice of the last layer that has one.
# ---------------------------------------------------------------------------

#: The statistic a layer with an indexer sows into the serving
#: statistics' collection (tpudl.models.generate), int32 [2] over the
#: call's real queries: the positions they attended, and the positions
#: they could see.
SPARSE_STAT_NAME = "sparse_rows"
#: ``k_norm`` is a LayerNorm with bias, at this epsilon.
INDEX_NORM_EPS = 1e-6


def _check_sparse(cfg: LlamaConfig) -> None:
    """``_check_block``'s checks of ``index_topk`` and the indexer's
    keys: what they are, and what they are not wired to."""
    if cfg.index_topk < 0:
        raise ValueError(
            f"index_topk must be >= 0 (0: every cached position is "
            f"attended), got {cfg.index_topk}"
        )
    if not cfg.index_topk:
        if cfg.indexer_types or cfg.index_n_heads or cfg.index_head_dim:
            raise ValueError(
                "indexer_types, index_n_heads and index_head_dim describe "
                "the indexer of learned sparse attention: set index_topk"
            )
        return
    if cfg.attention != "mla" or not cfg.q_lora_rank:
        raise ValueError(
            "index_topk (learned sparse attention) chooses rows of the "
            "LATENT cache, with an indexer that reads the low-rank "
            "query: it needs attention='mla' and q_lora_rank > 0"
        )
    if cfg.index_n_heads < 1 or cfg.index_head_dim < cfg.qk_rope_head_dim:
        raise ValueError(
            f"the indexer needs index_n_heads >= 1 and an index_head_dim "
            f"that holds the {cfg.qk_rope_head_dim} roped values, got "
            f"{cfg.index_n_heads} x {cfg.index_head_dim}"
        )
    types = cfg.indexer_types
    if (
        types is None or len(types) != cfg.num_layers
        or not set(types) <= {"full", "shared"} or types[0] != "full"
    ):
        raise ValueError(
            f"indexer_types names each of the {cfg.num_layers} layers "
            f"'full' (its own indexer) or 'shared' (the last 'full' "
            f"layer's choice), the first one 'full': got {types}"
        )
    for what, asked, why in (
        ("block='shortcut'", cfg.block != "llama",
         "the double layer's two attentions would each need a choice, "
         "and which of them a later 'shared' layer reuses is not defined"),
        ("hyper_streams", cfg.hyper_streams,
         "HyperBlock hands on the residual stream alone, not a choice "
         "of cached positions beside it"),
        ("loop_passes > 1", cfg.loop_passes > 1,
         "a pass would need the choice made by the same layer in the "
         "pass before, and a pool of indexer keys a (pass, layer)"),
        ("lora_rank", cfg.lora_rank,
         "latent attention takes no adapter view, and no adapter "
         "addresses the indexer's projections"),
        ("moe_experts, fp8_train or remat",
         cfg.moe_experts or cfg.fp8_train or cfg.remat,
         "the training tiers run no indexer, and a rematerialised block "
         "would trace the choice it hands on twice"),
    ):
        if asked:
            raise ValueError(
                f"{what} is not wired to learned sparse attention "
                f"(index_topk): {why}"
            )


class Indexer(nn.Module):
    """A "full" layer's indexer: for each position ``index_n_heads``
    queries from the normed low-rank query ``low``, ONE key (LayerNorm
    with bias) and a weight a head from the layer's normed input, the
    first ``qk_rope_head_dim`` values of queries and key roped. The
    key is what the layer caches beside its latent row. The score of
    key ``s`` for query ``t`` (``index_scores``):

        I[t, s] = sum_j w_j[t] relu(q_j[t] . k[s])

    with ``index_head_dim ** -0.5 * index_n_heads ** -0.5`` folded
    into ``w`` (float32). -> ``(q [B, S, Hi, Di], k [B, S, Di],
    w [B, S, Hi])``. Scope: ``dsa_index``."""

    cfg: LlamaConfig

    @nn.compact
    def __call__(self, low, hidden, positions):
        cfg = self.cfg
        B, S, _ = hidden.shape
        heads, width = cfg.index_n_heads, cfg.index_head_dim

        def roped(x):
            return rope(x, positions, cfg.rope_theta, cfg.rope_scaling,
                        cfg.qk_rope_head_dim)

        q = _proj(cfg, heads * width, "q_proj")(low)
        key = nn.LayerNorm(
            epsilon=INDEX_NORM_EPS, dtype=cfg.dtype, name="k_norm"
        )(_proj(cfg, width, "k_proj")(hidden))
        w = nn.Dense(
            heads, use_bias=False, dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02), name="weights_proj",
        )(hidden)
        return (
            roped(q.reshape(B, S, heads, width)),
            roped(key[:, :, None])[:, :, 0],
            w.astype(jnp.float32) * (heads * width) ** -0.5,
        )


def index_scores(q, w, keys):
    """``I[b, t, s]`` float32 of the indexer's queries q [B, S, Hi, Di]
    and head weights w [B, S, Hi] against keys [B, T, Di]."""
    dots = jnp.einsum(
        "bshd,btd->bsht", q, keys, preferred_element_type=jnp.float32
    )
    return jnp.einsum("bsht,bsh->bst", jax.nn.relu(dots), w)


def top_rows(scores, k: int):
    """bool like ``scores`` [..., T] float32: each row's ``k`` largest,
    a tie going to the lower position: what ``jax.lax.top_k`` picks, as
    a mask, without its sort. The k-th largest score is found EXACTLY
    by bisection over the 32 bits of a key that orders as the floats do
    (32 counts of a compare, each one pass over the row, against a sort
    of every row); scores above it are in, and of those equal to it the
    first ones, as many as there is room for."""
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.uint32)  # no -0.0
    top = jnp.uint32(1 << 31)
    keys = jnp.where(bits >= top, ~bits, bits | top)

    def keep_bit(i, kth):
        higher = kth | (top >> i)
        fits = jnp.sum(keys >= higher, axis=-1, keepdims=True) >= k
        return jnp.where(fits, higher, kth)

    kth = jax.lax.fori_loop(
        0, 32, keep_bit, jnp.zeros((*scores.shape[:-1], 1), jnp.uint32)
    )
    above, ties = keys > kth, keys == kth
    room = k - above.sum(-1, keepdims=True)
    return above | (ties & (jnp.cumsum(ties, axis=-1) <= room))


def choose_rows(q, w, keys, mask, k: int, causal: bool):
    """The indexer's choice for a dense pass, bool [B, S, T]: of the
    positions ``mask`` [B, S, T] lets each query see, the ``k`` it
    scores highest (all of them where there are no more). A block of
    ``PREFILL_BLOCK`` queries at a time, so that one block's scores
    [Hi, block, T] are all that is held; ``causal``: query ``i`` sees
    no key past ``i``, so a block is scored against the keys up to its
    own last and one that sees ``k`` keys or fewer is not scored."""
    B, S, T = q.shape[0], q.shape[1], keys.shape[1]
    mask = jnp.broadcast_to(mask, (B, S, T))
    if T <= k:
        return mask
    out = []
    for at in range(0, S, PREFILL_BLOCK):
        end = min(at + PREFILL_BLOCK, S)
        seen = min(end, T) if causal else T
        if seen <= k:
            out.append(mask[:, at:end])
            continue
        with jax.named_scope("dsa_index"):
            scores = jnp.where(
                mask[:, at:end, :seen],
                index_scores(q[:, at:end], w[:, at:end], keys[:, :seen]),
                -jnp.inf,
            )
        with jax.named_scope("dsa_select"):
            chosen = top_rows(scores, k) & mask[:, at:end, :seen]
            out.append(jnp.pad(chosen, ((0, 0), (0, 0), (0, T - seen))))
    return jnp.concatenate(out, axis=1)


def _index_of(module, layer: int, low, hidden, positions):
    """``LatentAttention``'s indexer outputs on a "full" layer (the
    module ``indexer``), None on a "shared" one and without
    ``index_topk``."""
    cfg = module.cfg
    if not cfg.index_topk or cfg.indexer_types[layer] != "full":
        return None
    with jax.named_scope("dsa_index"):
        return Indexer(cfg, name="indexer")(low, hidden, positions)


def _sow_choice(module, choice, chosen, live, real):
    """A "full" layer's record of its choice: ``SPARSE_STAT_NAME``
    (chosen and live [B, S], summed over the real queries) beside the
    other serving statistics, and the choice itself for a caller that
    asks for ``intermediates`` (a test; a no-op in every program)."""
    real = real.astype(jnp.int32)
    module.sow("moe_stats", SPARSE_STAT_NAME, jnp.stack([
        jnp.sum(chosen.astype(jnp.int32) * real),
        jnp.sum(live.astype(jnp.int32) * real),
    ]))
    module.sow("intermediates", "index_choice", choice)


def _dense_choice(module, index, choice, mask, real, start=None, fresh=True):
    """The choice a dense pass of ``LatentAttention`` attends under,
    bool [B, S, T]: a "shared" layer's (``index`` None) is the one
    handed in; a "full" layer makes its own (``choose_rows``) from
    ``mask`` [B, 1, S, T], what each query may see. Into a row cache
    (``start``: where the chunk is written) the layer declares a SECOND
    row leaf, ``index_k``, its indexer's keys: the page manager pools
    it under the same table as the latent rows."""
    if index is None:
        return choice
    cfg = module.cfg
    q, keys, w = index
    if start is not None:
        cached = module.variable(
            "cache", "index_k", jnp.zeros,
            (keys.shape[0], cfg.max_seq_len, keys.shape[-1]), keys.dtype,
        )
        cached.value = jax.lax.dynamic_update_slice(
            cached.value, keys, (0, start, 0)
        )
        if not fresh:
            keys = cached.value
    choice = choose_rows(
        q, w, keys, mask[:, 0], cfg.index_topk, causal=fresh
    )
    _sow_choice(module, choice, choice.sum(-1), mask[:, 0].sum(-1), real)
    return choice


def _paged_choice(module, index, choice, paged, real):
    """The choice a paged decode step attends under, int32 [B, S, k]
    logical positions of each slot (None: the cache holds no more than
    ``k`` a slot, every live one is attended): a "shared" layer's is
    the one handed in; a "full" layer scatters this step's indexer key
    into its second pool, ``pages_index_k``, and chooses among the
    slot's live keys (tpudl.ops.paged_attention.paged_index_choice)."""
    if index is None:
        return choice
    from tpudl.models.paged import paged_write
    from tpudl.ops.paged_attention import paged_index_choice

    q, keys, w = index
    pool = module.variable("cache", "pages_index_k", _paged_cache_missing)
    sc = None
    if paged.quantized:
        sc = module.variable("cache", "scale_index_k", _paged_cache_missing)
    scales = sc.value if sc is not None else None
    pool.value, scales = paged_write(pool.value, scales, keys, paged)
    if sc is not None:
        sc.value = scales
    k = module.cfg.index_topk
    choice, live = paged_index_choice(q, w, pool.value, paged, k, scales)
    _sow_choice(module, choice, jnp.minimum(live, k), live, real)
    return choice


def _sparse_stack(model, block, x, positions, kv_mask, decode, paged, adapters):
    """``LlamaModel``'s stack for ``index_topk`` > 0, called inside its
    ``__call__``: every layer is handed the last choice of cached
    positions and hands on its own ("full") or the same ("shared")."""
    from tpudl.ops.norms import fused_ops_impl

    cfg = model.cfg
    if adapters is not None:
        raise ValueError(
            "per-tenant adapters are not wired to learned sparse "
            "attention (index_topk)"
        )
    choice = None
    for i in range(cfg.num_layers):
        x, choice = block(cfg, cfg.mlp_kind(i), i, name=f"layer_{i}")(
            x, positions, kv_mask, decode, paged, None, choice
        )
    return RMSNorm(
        cfg.rms_norm_eps, fused_ops_impl(cfg.fused_ops), name="final_norm"
    )(x)


# ---------------------------------------------------------------------------
# HuggingFace weight import (torch state_dict -> tpudl param tree).
#
# The reference's first act is loading pretrained weights
# (reference notebooks/cv/onnx_experiments.py:19, resnet50(pretrained=True))
# and BASELINE.json configs[4] is a *pretrained* Llama LoRA fine-tune —
# random-init fine-tuning is not the workload. Same recipe as
# tpudl.models.bert.params_from_hf_bert: regex map, transpose Linear
# kernels, keep norms/embeddings as-is.
# ---------------------------------------------------------------------------

#: HF name pattern -> tpudl path template; bool = transpose ([out,in] ->
#: [in,out]). Conventions verified against this module: rotate-half RoPE,
#: consecutive-group GQA (q head h uses kv head h // (H/Hkv)), silu-gated
#: MLP, f32 RMSNorm — all match HF's modeling_llama semantics, so the map
#: is pure renaming + kernel transposes.
_HF_LLAMA_MAP = [
    (r"^model\.embed_tokens\.weight$", "model/embed_tokens/embedding", False),
    (r"^model\.layers\.(\d+)\.self_attn\.(q|k|v|o)_proj\.weight$",
     "model/layer_{0}/attention/{1}_proj/kernel", True),
    (r"^model\.layers\.(\d+)\.mlp\.(gate|up|down)_proj\.weight$",
     "model/layer_{0}/{1}_proj/kernel", True),
    (r"^model\.layers\.(\d+)\.input_layernorm\.weight$",
     "model/layer_{0}/input_norm/scale", False),
    (r"^model\.layers\.(\d+)\.post_attention_layernorm\.weight$",
     "model/layer_{0}/post_attention_norm/scale", False),
    (r"^model\.norm\.weight$", "model/final_norm/scale", False),
    (r"^lm_head\.weight$", "lm_head/kernel", True),
    # HF LlamaForSequenceClassification names its head `score`.
    (r"^score\.weight$", "classifier/kernel", True),
    (r"^score\.bias$", "classifier/bias", False),
]


def _tensor_to_numpy(value):
    """torch tensor (any dtype, incl. bfloat16 — the dtype pretrained
    Llama checkpoints ship in, which Tensor.numpy() refuses) or array-like
    -> numpy array."""
    import numpy as _np

    if hasattr(value, "detach"):  # torch tensor
        value = value.detach()
        try:
            return value.numpy()
        except TypeError:  # bf16/f8: upcast through f32
            return value.float().numpy()
    return _np.asarray(value)


def params_from_hf_llama(state_dict, like=None):
    """Convert a HF Llama state_dict (LlamaForCausalLM or
    LlamaForSequenceClassification; torch tensors or numpy arrays) to a
    tpudl param tree.

    With ``like`` (a template tree from ``model.init``), mapped leaves are
    grafted into a copy of it — unmapped template leaves (e.g. LoRA
    adapters, a fresh classifier head) keep their initialized values, and
    every graft is shape-checked. Tied-embedding checkpoints (no
    ``lm_head.weight``) fall back to the transposed token embedding when
    the template wants an ``lm_head``.
    """
    converted: dict = {}
    unmapped = []
    for hf_name, value in state_dict.items():
        arr = _tensor_to_numpy(value)
        for pattern, template, transpose in _HF_LLAMA_MAP:
            m = re.match(pattern, hf_name)
            if m:
                converted[template.format(*m.groups())] = (
                    arr.T if transpose else arr
                )
                break
        else:
            if not (
                "rotary_emb" in hf_name or hf_name.endswith("position_ids")
            ):
                unmapped.append(hf_name)
    if unmapped:
        raise ValueError(f"unmapped HF parameters: {unmapped}")
    if (
        "lm_head/kernel" not in converted
        and "model/embed_tokens/embedding" in converted
    ):
        # tie_word_embeddings: the output head shares the embedding.
        converted["lm_head/kernel"] = converted[
            "model/embed_tokens/embedding"
        ].T

    if like is None:
        tree: dict = {}
        for path, arr in converted.items():
            node = tree
            parts = path.split("/")
            for p in parts[:-1]:
                node = node.setdefault(p, {})
            node[parts[-1]] = jnp.asarray(arr)
        return tree

    tree = jax.tree.map(lambda x: x, like)  # shallow-copied structure
    used = set()

    def _graft(node, prefix):
        out = {}
        for name, leaf in node.items():
            path = f"{prefix}/{name}" if prefix else name
            if isinstance(leaf, dict):
                out[name] = _graft(leaf, path)
            elif path in converted:
                arr = converted[path]
                if tuple(arr.shape) != tuple(jnp.shape(leaf)):
                    raise ValueError(
                        f"shape mismatch at {path}: HF {arr.shape} vs "
                        f"model {jnp.shape(leaf)}"
                    )
                used.add(path)
                out[name] = jnp.asarray(arr, dtype=leaf.dtype)
            else:
                out[name] = leaf  # keep init (LoRA adapters, fresh heads)
        return out

    tree = _graft(dict(tree), "")
    unused = set(converted) - used - {"lm_head/kernel", "classifier/kernel",
                                      "classifier/bias"}
    if unused:
        raise ValueError(
            f"HF parameters with no destination in the template: "
            f"{sorted(unused)}"
        )
    return tree


def build_llama(name: str, num_classes: int, dtype=jnp.bfloat16, **kwargs):
    """Registry entry: 'llama-tiny' / 'llama3-8b', with composable
    suffixes: '-lora' enables rank-16 adapters (override via lora_rank=),
    '-moe' swaps every MLP for an 8-expert MoE (override via
    moe_experts=)."""
    base = name
    lora = moe = False
    while True:
        if base.endswith("-lora"):
            base, lora = base.removesuffix("-lora"), True
        elif base.endswith("-moe"):
            base, moe = base.removesuffix("-moe"), True
        else:
            break
    if base not in LLAMA_SIZES:
        raise ValueError(
            f"unknown llama size {base!r}; available: {sorted(LLAMA_SIZES)}"
        )
    if lora:
        kwargs.setdefault("lora_rank", 16)
    if moe:
        kwargs.setdefault("moe_experts", 8)
    cfg = LLAMA_SIZES[base](num_labels=num_classes, dtype=dtype, **kwargs)
    return LlamaForSequenceClassification(cfg)


def _count_prefill_attention():
    """An attention over a dense row cache is being traced: the prefill
    contract counts its layers by these (tpudl.models.generate
    .prefill_fn). Down here, and one line a site, so that no line above
    moves: a serving program's text holds the line numbers of its
    kernel's call chain through this file."""
    from tpudl.ops.flash_attention import count_prefill_attention

    count_prefill_attention()
