"""TPU ops: attention behind one dispatch seam, the fused-epilogue
kernel tier, and expert-parallel MoE.

- attention.py        — reference einsum attention (+ masks, dropout);
- flash_attention.py  — Pallas fused online-softmax kernel, fwd + bwd;
- fused_attention.py  — Pallas fused short/mid-seq attention (full
                        softmax per cell, one-pass backward, in-kernel
                        hardware-PRNG dropout);
- softmax_dropout.py  — Pallas fused softmax(+mask)+dropout for the
                        short-seq hybrid path (XLA matmuls around it);
- ring_attention.py   — sequence-parallel ring attention over `sp`
                        (ppermute K/V rotation, online-softmax merge);
- ulysses.py          — sequence-parallel attention over `sp` via
                        all-to-all head/seq resharding (exact numerics);
- norms.py            — fused LayerNorm/RMSNorm(+residual-add), f32
                        statistics, one-pass backward;
- mlp_fused.py        — fused bias+GeLU (exact erf) and SwiGLU MLP
                        epilogues, recompute-free backward;
- cross_entropy.py    — fused softmax-cross-entropy streaming the vocab
                        axis (online logsumexp; the [B, V] softmax is
                        never materialized);
- segmented_lora.py   — heterogeneous-adapter batched LoRA delta over
                        page pools (gather-from-pool in-kernel, f32
                        accumulation; the multi-tenant serving matmul);
- paged_attention.py  — decode attention over the paged k / v pool,
                        read in place: live pages fetched by the page
                        table (scalar prefetch + DMA), running softmax;
                        the dense gather stays as its reference;
- fp8_dot.py          — fp8 TRAINING matmul (e4m3 fwd / e5m2 grad) with
                        delayed scaling: per-tensor amax-history rings
                        as traced state, saturate-don't-NaN casts,
                        gradient amax via the g_probe cotangent;
- moe.py              — top-k routed expert FFN over `ep` (all-to-all).
"""

from tpudl.ops.attention import (  # noqa: F401
    attend,
    causal_mask,
    dot_product_attention,
    padding_mask,
)
from tpudl.ops.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_with_lse,
)
from tpudl.ops.fused_attention import fused_attention  # noqa: F401
from tpudl.ops.softmax_dropout import (  # noqa: F401
    hybrid_attention,
    softmax_dropout,
)
from tpudl.ops.ring_attention import ring_attention  # noqa: F401
from tpudl.ops.ulysses import ulysses_attention  # noqa: F401
from tpudl.ops.norms import (  # noqa: F401
    fused_ops_impl,
    layer_norm,
    layer_norm_ref,
    rms_norm,
    rms_norm_ref,
)
from tpudl.ops.mlp_fused import (  # noqa: F401
    bias_gelu,
    bias_gelu_ref,
    swiglu,
    swiglu_ref,
)
from tpudl.ops.cross_entropy import (  # noqa: F401
    softmax_cross_entropy,
    softmax_cross_entropy_ref,
)
from tpudl.ops.segmented_lora import (  # noqa: F401
    segmented_lora,
    segmented_lora_ref,
)
from tpudl.ops.fp8_dot import (  # noqa: F401
    Fp8Dense,
    fp8_dot,
)
from tpudl.ops.moe import (  # noqa: F401
    EP_MOE_RULES,
    MoEMlp,
    expert_capacity,
    route_topk,
    with_moe_rules,
)
