"""Low-precision weight tier: post-training quantization for serving.

Decode is weight-bandwidth-bound — every parameter is read once per
generated token — so shrinking the resident weight bytes is the TPOT
lever that matches the KV-side int8 tier (tpudl.models.paged). This
package quantizes a TRAINED param tree for serving:

- ``quantize.py``: regex-over-path rules (the SNIPPETS.md [2]
  ``match_partition_rules`` shape) select which leaves quantize —
  attention/MLP projections do, LayerNorm/embeddings/heads stay full
  precision — to symmetric per-output-channel **int8** or bf16-scaled
  **fp8 (e4m3)**. A quantized leaf is carried as a plain
  ``{"qvalues", "qscale"}`` dict under the ORIGINAL kernel key, so the
  param tree's module structure is identical to the full-precision
  tree and checkpoints / StableHLO in_trees round-trip unchanged.
- ``dense.py``: the quantized matmul with dequantization fused into
  the contraction (``lax.dot_general(preferred_element_type=...)``
  then one per-output-channel scale multiply — the weight matrix is
  never materialized at full precision), behind the same ``impl=``
  dispatch seam as tpudl.ops, plus ``QuantDense`` — the flax module
  the ``BertConfig.weight_dtype`` / ``LlamaConfig.weight_dtype`` seams
  swap in (param tree identical to ``nn.Dense`` at init, and it serves
  quantized and full-precision kernels interchangeably).

End to end: ``ServeSession.from_model(..., weight_dtype="int8")``
serves the quantized tree (composing with the paged int8 KV cache),
``tpudl.export.decode`` exports the quantized decoder through the
existing StableHLO path, and ``tests/test_quant.py`` holds every
precision x backend cell to ``assert_serving_parity``.
"""

from tpudl.quant.dense import (  # noqa: F401
    QuantDense,
    quant_dot,
    resolve_impl,
)
from tpudl.quant.quantize import (  # noqa: F401
    BERT_QUANT_PATTERNS,
    LLAMA_QUANT_PATTERNS,
    QUANT_DTYPES,
    default_quant_rules,
    dequantize_leaf,
    dequantize_tree,
    is_quantized,
    match_quant_rules,
    quantize_leaf,
    quantize_model,
    quantize_tree,
    weight_bytes_report,
)
