"""L4+ request-level serving: continuous batching over the compiled
decode path, scaled out by a multi-replica router.

The reference repo's substance is export -> session -> infer on single
inputs (reference notebooks/cv/onnx_experiments.py); this package is
what sits between that and "serve heavy traffic": a bounded admission
queue (tpudl.serve.queue), the paged, optionally int8-quantized KV
cache (tpudl.serve.cache), a continuous-batching engine multiplexing many
requests onto the compiled XLA programs (tpudl.serve.engine), a
synchronous Request/Result front end with token streaming that serves
either a live model or a deserialized StableHLO artifact
(tpudl.serve.api), a load-balancing router over N engine replicas
with prefill/decode disaggregation and SLO-aware shedding
(tpudl.serve.router), the SLO-driven autoscaler that grows and
drains the replica fleet off the router's measured signals
(tpudl.serve.autoscale), and multi-tenant LoRA serving — one resident
base model with per-tenant adapters paged in and out like KV pages,
decoded heterogeneously by the segmented-LoRA kernel
(tpudl.serve.lora + tpudl.ops.segmented_lora).
"""

from tpudl.serve import chaos  # noqa: F401
from tpudl.serve.api import (  # noqa: F401
    Request,
    Result,
    ServeSession,
    StreamChunk,
    assert_serving_parity,
)
from tpudl.serve.autoscale import (  # noqa: F401
    AutoscaleConfig,
    Autoscaler,
)
from tpudl.serve.cache import (  # noqa: F401
    MigrationCompatError,
    MigrationCorruptError,
    PagedKVCache,
    RadixPrefixTree,
)
from tpudl.serve.engine import Engine  # noqa: F401
from tpudl.serve.lora import (  # noqa: F401
    AdapterPool,
    assert_tenant_parity,
)
from tpudl.serve.queue import AdmissionQueue  # noqa: F401
from tpudl.serve.speculate import Speculator  # noqa: F401
from tpudl.serve.router import (  # noqa: F401
    PrefillWorker,
    Replica,
    Router,
)
