"""Flax BERT: encoder, pooler, classification head, HF weight import.

The reference declares an NLP workload family but ships nothing in it
(reference notebooks/nlp/README.md is empty — SURVEY.md §0); the concrete
workloads come from BASELINE.json: BERT-base SST-2 fine-tune (configs[1]),
BERT-large multi-host (configs[3]). This is a first-party TPU-native
implementation, not a port of HF's torch modeling code:

- bf16 compute / f32 params, f32 softmax and LayerNorm;
- attention flows through tpudl.ops.attend so flash/ring kernels and
  sequence parallelism drop in without model changes;
- activation sharding constraints on the (dp,fsdp) x sp x tp mesh axes at
  block boundaries;
- optional per-layer rematerialization (jax.checkpoint) to trade FLOPs for
  HBM on long sequences;
- `params_from_hf_bert` maps a HuggingFace torch state_dict onto the
  parameter tree (transpose Linear kernels, rename LayerNorm), so HF
  checkpoints fine-tune here directly — SURVEY.md §7.4 hard part #3.
"""

from __future__ import annotations

import dataclasses
import re
from functools import partial
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpudl.ops.attention import attend, padding_mask
from tpudl.ops.dropout import Dropout
from tpudl.parallel.sharding import constrain


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    # True = bit-exact jax.random.bernoulli dropout masks; False (default,
    # the headline-perf path) = low-width hardware bits, rate quantized to
    # 1/256 (tpudl.ops.dropout).
    dropout_exact: bool = False
    num_labels: int = 2
    dtype: Any = jnp.bfloat16
    attention_impl: str = "reference"
    #: Rematerialization scope: False/"none" = store all activations;
    #: True/"layer" = recompute the whole encoder layer in the backward
    #: (max memory saving, measured WORSE on the single-chip BERT-large
    #: step: 43.0% vs 46.5% MFU — BASELINE.md); "attention" = recompute
    #: only the self-attention block (drops the S x S probability tensors,
    #: the dominant per-layer activation at large batch, while keeping the
    #:  cheap-to-store/expensive-to-recompute matmul outputs).
    remat: Any = False
    #: jax.checkpoint policy name for "layer" remat — "dots_saveable"
    #: keeps MXU outputs and recomputes only elementwise/softmax work,
    #: a middle ground between full remat and none. None = save nothing.
    remat_policy: Optional[str] = None
    #: Fused-epilogue kernel tier (tpudl.ops.norms / mlp_fused): False
    #: (default) = the original composite path, bit-identical to before
    #: the tier existed; True = Pallas fused LayerNorm(+residual) and
    #: bias+GeLU on TPU, composite off-TPU (what bench flips on as a
    #: measured variant); "force" = Pallas everywhere (interpret mode
    #: off-TPU — the CPU parity-test mode). Param tree is identical in
    #: all modes, so checkpoints and HF imports are interchangeable.
    fused_ops: Any = False
    #: Low-precision weight tier (tpudl.quant): None (default) = plain
    #: nn.Dense, bit-identical to before the tier; "int8"/"fp8_e4m3" =
    #: encoder attention + MLP projections become QuantDense (serves
    #: the quantize_tree output with dequant fused into the
    #: contraction; full-precision kernels run the exact nn.Dense
    #: math). Embeddings, LayerNorms, pooler, and the classifier head
    #: always stay full precision. Param-tree structure is identical
    #: in all modes.
    weight_dtype: Optional[str] = None
    #: fp8 TRAINING tier (tpudl.ops.fp8_dot + the tpudl.train.precision
    #: "fp8" policy): False (default) = nothing changes; True = the
    #: SAME rule-class sites the quantizer addresses (encoder
    #: attention + MLP projections — tpudl.quant BERT_QUANT_PATTERNS)
    #: become Fp8Dense: e4m3 forward / e5m2 gradient matmuls with
    #: delayed scaling, params still nn.Dense-identical f32 masters
    #: (checkpoints interchange); the per-site amax rings live in the
    #: "fp8" variable collection the train step threads through
    #: TrainState.precision. "force"/"fused"/"reference" pin the
    #: fp8_dot impl seam (CPU parity-test modes). Mutually exclusive
    #: with weight_dtype (serving quantization of a frozen tree).
    fp8_train: Any = False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


BERT_TINY = partial(BertConfig, hidden_size=128, num_layers=2, num_heads=2,
                    intermediate_size=512)
BERT_BASE = BertConfig
BERT_LARGE = partial(BertConfig, hidden_size=1024, num_layers=24, num_heads=16,
                     intermediate_size=4096)


def _dense(cfg: BertConfig, features: int, name: str, quantize: bool = False):
    """Dense projection. ``quantize=True`` marks the encoder
    attention/MLP sites the ``weight_dtype`` seam swaps to QuantDense
    (exactly the leaves tpudl.quant's BERT_QUANT_PATTERNS match) and
    the ``fp8_train`` seam swaps to Fp8Dense — ONE rule-class set,
    three precision tiers; pooler/classifier callers leave it False
    and always stay full precision."""
    if quantize and cfg.fp8_train:
        if cfg.weight_dtype is not None:
            raise ValueError(
                "fp8_train (training-time fp8 matmuls) and weight_dtype "
                "(serving quantization of a frozen tree) are mutually "
                "exclusive — pick one"
            )
        from tpudl.ops.fp8_dot import Fp8Dense

        impl = cfg.fp8_train if isinstance(cfg.fp8_train, str) else "auto"
        if impl == "force":
            impl = "fused"
        return Fp8Dense(
            features,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            impl=impl,
            name=name,
        )
    if quantize and cfg.weight_dtype is not None:
        from tpudl.quant.dense import QuantDense

        return QuantDense(
            features,
            dtype=cfg.dtype,
            kernel_init=nn.initializers.normal(0.02),
            name=name,
        )
    return nn.Dense(
        features,
        dtype=cfg.dtype,
        kernel_init=nn.initializers.normal(0.02),
        name=name,
    )


class FusedLayerNorm(nn.Module):
    """LayerNorm(+optional residual-add) through the tpudl.ops.norms
    seam. Param tree (scale/bias, f32, ones/zeros init) is identical to
    ``nn.LayerNorm``, so fused and composite checkpoints interchange.
    With ``residual`` returns ``(normed, x + residual)``."""

    eps: float
    impl: str

    @nn.compact
    def __call__(self, x, residual=None, return_sum=True):
        from tpudl.ops.norms import layer_norm

        h = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (h,))
        bias = self.param("bias", nn.initializers.zeros, (h,))
        return layer_norm(
            x, scale, bias, residual, eps=self.eps, return_sum=return_sum,
            impl=self.impl,
        )


class FusedBiasGeluDense(nn.Module):
    """``nn.Dense`` + exact GeLU with the bias add fused into the GeLU
    epilogue (tpudl.ops.mlp_fused.bias_gelu) — the matmul runs pre-bias
    so the [N, 4H] stream is read/written once. Params (kernel/bias,
    same init) are identical to the composite ``nn.Dense``."""

    cfg: BertConfig
    features: int
    impl: str

    @nn.compact
    def __call__(self, x):
        from tpudl.ops.mlp_fused import bias_gelu
        from tpudl.quant.dense import quant_dot
        from tpudl.quant.quantize import is_quantized

        cfg = self.cfg
        # Read a quantized kernel around self.param (flax shape-checks
        # stored params against the initializer; the (qvalues, qscale)
        # pair is not the init-time kernel shape) — same dispatch as
        # tpudl.quant.dense.QuantDense.
        stored = (
            self.get_variable("params", "kernel")
            if self.has_variable("params", "kernel")
            else None
        )
        if is_quantized(stored):
            kernel = stored
        else:
            kernel = self.param(
                "kernel", nn.initializers.normal(0.02),
                (x.shape[-1], self.features),
            )
        bias = self.param("bias", nn.initializers.zeros, (self.features,))
        # quant_dot dispatches on the kernel itself: a quantized pair
        # runs the contraction-fused dequant (the weight_dtype seam), a
        # plain kernel the exact pre-existing dot_general in cfg.dtype.
        # The bias+GeLU epilogue is unchanged either way.
        y = quant_dot(x, kernel, compute_dtype=cfg.dtype)
        return bias_gelu(y, bias, impl=self.impl)


class BertEmbeddings(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids, train: bool):
        cfg = self.cfg
        we = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                      embedding_init=nn.initializers.normal(0.02),
                      name="word_embeddings")(input_ids)
        pos = jnp.arange(input_ids.shape[1])[None, :]
        pe = nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                      embedding_init=nn.initializers.normal(0.02),
                      name="position_embeddings")(pos)
        te = nn.Embed(cfg.type_vocab_size, cfg.hidden_size,
                      embedding_init=nn.initializers.normal(0.02),
                      name="token_type_embeddings")(token_type_ids)
        x = we + pe + te
        if cfg.fused_ops:
            from tpudl.ops.norms import fused_ops_impl

            x = FusedLayerNorm(
                cfg.layer_norm_eps, fused_ops_impl(cfg.fused_ops),
                name="layer_norm",
            )(x)
        else:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                             name="layer_norm")(x)
        x = Dropout(cfg.hidden_dropout, exact=cfg.dropout_exact)(x, deterministic=not train)
        return x.astype(cfg.dtype)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_mask, train: bool):
        cfg = self.cfg
        B, S, _ = hidden.shape
        shape = (B, S, cfg.num_heads, cfg.head_dim)
        q = _dense(cfg, cfg.hidden_size, "query", quantize=True)(
            hidden
        ).reshape(shape)
        k = _dense(cfg, cfg.hidden_size, "key", quantize=True)(
            hidden
        ).reshape(shape)
        v = _dense(cfg, cfg.hidden_size, "value", quantize=True)(
            hidden
        ).reshape(shape)
        q = constrain(q, ("dp", "fsdp"), "sp", "tp", None)
        k = constrain(k, ("dp", "fsdp"), "sp", "tp", None)
        v = constrain(v, ("dp", "fsdp"), "sp", "tp", None)
        attn_dropout_rng = None
        if train and cfg.attention_dropout > 0.0:
            attn_dropout_rng = self.make_rng("dropout")
        ctx = attend(
            q,
            k,
            v,
            mask=attn_mask,
            implementation=cfg.attention_impl,
            dropout_rate=cfg.attention_dropout if train else 0.0,
            dropout_rng=attn_dropout_rng,
            dropout_exact=cfg.dropout_exact,
        )
        ctx = ctx.reshape(B, S, cfg.hidden_size)
        out = _dense(cfg, cfg.hidden_size, "out", quantize=True)(ctx)
        out = Dropout(cfg.hidden_dropout, exact=cfg.dropout_exact)(out, deterministic=not train)
        return out


def _remat_policy(name: Optional[str]):
    if name is None:
        return None
    return getattr(jax.checkpoint_policies, name)


def remat_options(cli_name: str) -> dict:
    """CLI remat mode name -> BertConfig kwargs — the ONE mapping the
    training driver (notebooks/nlp/train_sst2.py --remat) and any other
    caller that names a remat mode share."""
    opts = {
        "none": {"remat": False},
        "layer": {"remat": "layer"},
        "attention": {"remat": "attention"},
        "dots": {"remat": "layer", "remat_policy": "dots_saveable"},
    }
    if cli_name not in opts:
        raise ValueError(
            f"remat mode must be one of {sorted(opts)}, got {cli_name!r}"
        )
    return dict(opts[cli_name])


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_mask, train: bool):
        cfg = self.cfg
        attn_cls = BertSelfAttention
        if cfg.remat == "attention":
            attn_cls = nn.remat(BertSelfAttention, static_argnums=(3,))
        attn_out = attn_cls(cfg, name="attention")(
            hidden, attn_mask, train
        )
        # `norm` and `ffn` (like `attention`, `embeddings`, `classifier`,
        # which the module names give) are scope components a profiler
        # trace groups device time by; HLO metadata only.
        if cfg.fused_ops:
            # Fused-epilogue path (tpudl.ops.norms / mlp_fused): the
            # residual add rides inside the LayerNorm kernel, and BERT's
            # post-norm blocks never consume the summed value, so the
            # kernels skip that write (return_sum=False via the module's
            # residual call returning only the normed value). Composite
            # fallback off-TPU keeps these numerics (fused_ops_impl).
            from tpudl.ops.norms import fused_ops_impl

            impl = fused_ops_impl(cfg.fused_ops)
            with jax.named_scope("norm"):
                hidden = FusedLayerNorm(
                    cfg.layer_norm_eps, impl, name="attention_norm"
                )(attn_out, hidden, return_sum=False).astype(cfg.dtype)
            with jax.named_scope("ffn"):
                inter = FusedBiasGeluDense(
                    cfg, cfg.intermediate_size, impl, name="intermediate"
                )(hidden)
                out = _dense(
                    cfg, cfg.hidden_size, "output", quantize=True
                )(inter)
                out = Dropout(cfg.hidden_dropout, exact=cfg.dropout_exact)(
                    out, deterministic=not train
                )
            with jax.named_scope("norm"):
                hidden = FusedLayerNorm(
                    cfg.layer_norm_eps, impl, name="output_norm"
                )(out, hidden, return_sum=False).astype(cfg.dtype)
        else:
            with jax.named_scope("norm"):
                hidden = nn.LayerNorm(
                    epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                    name="attention_norm"
                )(hidden + attn_out).astype(cfg.dtype)

            with jax.named_scope("ffn"):
                inter = _dense(
                    cfg, cfg.intermediate_size, "intermediate",
                    quantize=True,
                )(hidden)
                inter = nn.gelu(inter, approximate=False)
                out = _dense(
                    cfg, cfg.hidden_size, "output", quantize=True
                )(inter)
                out = Dropout(cfg.hidden_dropout, exact=cfg.dropout_exact)(
                    out, deterministic=not train
                )
            with jax.named_scope("norm"):
                hidden = nn.LayerNorm(
                    epsilon=cfg.layer_norm_eps, dtype=jnp.float32,
                    name="output_norm"
                )(hidden + out).astype(cfg.dtype)
        hidden = constrain(hidden, ("dp", "fsdp"), "sp", "tp")
        return hidden


class BertEncoder(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, hidden, attn_mask, train: bool):
        layer_cls = BertLayer
        if self.cfg.remat in (True, "layer"):
            layer_cls = nn.remat(
                BertLayer,
                static_argnums=(3,),
                policy=_remat_policy(self.cfg.remat_policy),
            )
        for i in range(self.cfg.num_layers):
            hidden = layer_cls(self.cfg, name=f"layer_{i}")(
                hidden, attn_mask, train
            )
        return hidden


class BertModel(nn.Module):
    """Encoder + pooler ([CLS] tanh projection), HF-compatible structure."""

    cfg: BertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        train: bool = False,
    ):
        cfg = self.cfg
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = BertEmbeddings(cfg, name="embeddings")(input_ids, token_type_ids, train)
        x = constrain(x, ("dp", "fsdp"), "sp", "tp")
        mask = padding_mask(attention_mask)
        x = BertEncoder(cfg, name="encoder")(x, mask, train)
        pooled = _dense(cfg, cfg.hidden_size, "pooler")(x[:, 0])
        pooled = jnp.tanh(pooled)
        return x, pooled


class BertForSequenceClassification(nn.Module):
    """The configs[1]/configs[3] fine-tune model (SST-2-style)."""

    cfg: BertConfig

    @nn.compact
    def __call__(
        self,
        input_ids,
        attention_mask=None,
        token_type_ids=None,
        train: bool = False,
    ):
        _, pooled = BertModel(self.cfg, name="bert")(
            input_ids, attention_mask, token_type_ids, train
        )
        pooled = Dropout(self.cfg.hidden_dropout, exact=self.cfg.dropout_exact)(
            pooled, deterministic=not train
        )
        logits = nn.Dense(
            self.cfg.num_labels,
            dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.02),
            name="classifier",
        )(pooled)
        return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# HuggingFace weight import (torch state_dict -> tpudl param tree).
# ---------------------------------------------------------------------------

#: HF name pattern -> tpudl path template. Linear weights transpose
#: ([out,in] -> [in,out]); embeddings and LayerNorm keep orientation.
_HF_MAP = [
    (r"^bert\.embeddings\.word_embeddings\.weight$",
     "bert/embeddings/word_embeddings/embedding", False),
    (r"^bert\.embeddings\.position_embeddings\.weight$",
     "bert/embeddings/position_embeddings/embedding", False),
    (r"^bert\.embeddings\.token_type_embeddings\.weight$",
     "bert/embeddings/token_type_embeddings/embedding", False),
    (r"^bert\.embeddings\.LayerNorm\.weight$",
     "bert/embeddings/layer_norm/scale", False),
    (r"^bert\.embeddings\.LayerNorm\.bias$",
     "bert/embeddings/layer_norm/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.self\.(query|key|value)\.weight$",
     "bert/encoder/layer_{0}/attention/{1}/kernel", True),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.self\.(query|key|value)\.bias$",
     "bert/encoder/layer_{0}/attention/{1}/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.dense\.weight$",
     "bert/encoder/layer_{0}/attention/out/kernel", True),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.dense\.bias$",
     "bert/encoder/layer_{0}/attention/out/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.LayerNorm\.weight$",
     "bert/encoder/layer_{0}/attention_norm/scale", False),
    (r"^bert\.encoder\.layer\.(\d+)\.attention\.output\.LayerNorm\.bias$",
     "bert/encoder/layer_{0}/attention_norm/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.intermediate\.dense\.weight$",
     "bert/encoder/layer_{0}/intermediate/kernel", True),
    (r"^bert\.encoder\.layer\.(\d+)\.intermediate\.dense\.bias$",
     "bert/encoder/layer_{0}/intermediate/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.dense\.weight$",
     "bert/encoder/layer_{0}/output/kernel", True),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.dense\.bias$",
     "bert/encoder/layer_{0}/output/bias", False),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.LayerNorm\.weight$",
     "bert/encoder/layer_{0}/output_norm/scale", False),
    (r"^bert\.encoder\.layer\.(\d+)\.output\.LayerNorm\.bias$",
     "bert/encoder/layer_{0}/output_norm/bias", False),
    (r"^bert\.pooler\.dense\.weight$", "bert/pooler/kernel", True),
    (r"^bert\.pooler\.dense\.bias$", "bert/pooler/bias", False),
    (r"^classifier\.weight$", "classifier/kernel", True),
    (r"^classifier\.bias$", "classifier/bias", False),
]


def params_from_hf_bert(
    state_dict: Dict[str, "np.ndarray"],
    like: Optional[Dict] = None,
) -> Dict:
    """Convert a HF BertForSequenceClassification state_dict to a tpudl
    param tree. `state_dict` values may be torch tensors or numpy arrays.
    `like` (a template param tree) enables shape validation.

    Ignored HF keys: position_ids buffers and the cls.* pretraining heads.
    """
    from tpudl.models.llama import _tensor_to_numpy

    tree: Dict = {}
    unmapped = []
    for hf_name, value in state_dict.items():
        arr = _tensor_to_numpy(value)
        for pattern, template, transpose in _HF_MAP:
            m = re.match(pattern, hf_name)
            if m:
                path = template.format(*m.groups())
                if transpose:
                    arr = arr.T
                node = tree
                parts = path.split("/")
                for p in parts[:-1]:
                    node = node.setdefault(p, {})
                node[parts[-1]] = jnp.asarray(arr)
                break
        else:
            if not (
                hf_name.endswith("position_ids")
                or hf_name.startswith("cls.")
                or ".seq_relationship." in hf_name
            ):
                unmapped.append(hf_name)
    if unmapped:
        raise ValueError(f"unmapped HF parameters: {unmapped}")
    if like is not None:
        flat_like = jax.tree_util.tree_leaves_with_path(like)
        flat_new = dict(
            (jax.tree_util.keystr(p), l.shape)
            for p, l in jax.tree_util.tree_leaves_with_path(tree)
        )
        for path, leaf in flat_like:
            key = jax.tree_util.keystr(path)
            if key not in flat_new:
                raise ValueError(f"missing parameter {key} in converted tree")
            if tuple(flat_new[key]) != tuple(leaf.shape):
                raise ValueError(
                    f"shape mismatch at {key}: HF {flat_new[key]} vs "
                    f"model {leaf.shape}"
                )
    return tree
