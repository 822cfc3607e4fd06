"""Sharding-rule engine: map parameter paths to PartitionSpecs.

The reference lineage distributes by wrapping the model in Horovod /
DistributedDataParallel hooks (SURVEY.md §2.3 — absent from the reference
tree itself). The TPU-native design is declarative instead: a list of
``(path_regex, PartitionSpec)`` rules assigns every parameter a sharding
over the named mesh (tpudl.runtime.mesh.MESH_AXES); pjit/GSPMD then emits
the ICI collectives. Strategy presets (DP / FSDP / TP) are just different
rule lists.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpudl import rules as rules_engine

P = PartitionSpec

#: A rule list: first regex (searched, not fullmatch) wins.
Rules = Sequence[Tuple[str, PartitionSpec]]

#: Fully-replicated default.
REPLICATED = P()

#: Canonical keypath -> "a/b/kernel" conversion now lives in the shared
#: rules engine (tpudl.rules); kept under the historical name for the
#: call sites (quant, tests) that import it from here.
_path_str = rules_engine.path_str


def spec_for_path(
    path: str, rules: Optional[Rules], shape: Sequence[int] = ()
) -> PartitionSpec:
    """First matching rule wins (tpudl.rules.first_match — the shared
    resolution primitive). A rule's spec may be a PartitionSpec or a
    callable ``shape -> PartitionSpec`` (for rank-dependent placement,
    e.g. conv vs dense kernels under FSDP). No match replicates — the
    legacy default; ``tpudl.rules.match_partition_rules`` is the
    coverage-checked adapter."""
    spec = rules_engine.first_match(rules, path)
    if spec is rules_engine.NO_MATCH:
        return REPLICATED
    return spec(shape) if callable(spec) else spec


def _axes_size(mesh: Mesh, entry) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def _clamp_entries(
    mesh: Mesh, spec: PartitionSpec, shape, relocate: bool = False
) -> PartitionSpec:
    """Truncate a spec to the array rank and unshard any dimension whose size
    the named mesh axes don't divide — keeps one rule list usable across
    full-size and tiny-test configurations.

    ``relocate`` (parameter placement): an entry its own dimension
    cannot take moves to the largest unsharded dimension the axes DO
    divide, and is dropped only when there is none. BERT's 30,522-row
    word embedding has no factor 4: under fsdp=4 it shards along the
    hidden dimension instead of sitting whole on every device."""
    entries = list(spec)[: len(shape)]
    spec_rank = len(entries)
    entries += [None] * (len(shape) - spec_rank)
    fixed = [
        entry
        if entry is None or shape[dim] % _axes_size(mesh, entry) == 0
        else None
        for dim, entry in enumerate(entries)
    ]
    if relocate:
        for dim, entry in enumerate(entries):
            if entry is None or fixed[dim] is not None:
                continue
            size = _axes_size(mesh, entry)
            homes = [
                d for d in range(len(shape))
                if entries[d] is None and fixed[d] is None
                and shape[d] % size == 0
            ]
            if homes and size > 1:
                fixed[max(homes, key=lambda d: shape[d])] = entry
    # Same length as the rule's own spec unless an entry moved past it.
    while len(fixed) > spec_rank and fixed[-1] is None:
        fixed.pop()
    return P(*fixed)


def tree_shardings(
    mesh: Mesh, tree: Any, rules: Optional[Rules] = None
) -> Any:
    """NamedSharding pytree for `tree` by matching paths against `rules`,
    with per-dimension divisibility clamping (see _clamp_entries)."""

    def one(path, leaf):
        shape = getattr(leaf, "shape", ())
        spec = spec_for_path(_path_str(path), rules, shape)
        return NamedSharding(
            mesh, _clamp_entries(mesh, spec, shape, relocate=True)
        )

    return jax.tree_util.tree_map_with_path(one, tree)


def param_shardings(mesh: Mesh, params: Any, rules: Optional[Rules] = None) -> Any:
    return tree_shardings(mesh, params, rules)


def host_to_global_array(x: Any, sharding: "jax.sharding.Sharding"):
    """Place a host value onto ``sharding`` even when the sharding spans
    NON-addressable devices (a multi-process mesh), where plain
    ``jax.device_put`` refuses host inputs.

    ``x`` is interpreted as the GLOBAL value; each process materializes
    only its addressable shards (``jax.make_array_from_callback``) — the
    multi-process placement path for replicated train state, rng keys,
    and checkpoint-restored leaves. Scalars/ints go through
    ``jnp.asarray`` first so weak-typing matches what device_put would
    have produced (a Python int stays int32, not numpy's int64).
    """
    if sharding.is_fully_addressable:
        return jax.device_put(x, sharding)
    import numpy as np

    if not isinstance(x, (np.ndarray, jax.Array)):
        x = jax.numpy.asarray(x)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        raise ValueError(
            "host_to_global_array needs a host value or fully-"
            f"addressable array; got a global array sharded as "
            f"{x.sharding}"
        )
    arr = np.asarray(x)
    return jax.make_array_from_callback(
        arr.shape, sharding, lambda idx: arr[idx]
    )


# ---------------------------------------------------------------------------
# Activation-sharding constraints.
#
# Model code calls ``constrain(x, ('dp','fsdp'), 'sp', None)`` on hot
# activations. Outside any mesh context this is a no-op, so models run
# unmodified on a single device.
# ---------------------------------------------------------------------------

_ctx = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_ctx, "mesh", None)


@contextlib.contextmanager
def active_mesh(mesh: Optional[Mesh]):
    prev = current_mesh()
    _ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _ctx.mesh = prev


def constrain(x: jax.Array, *spec_entries) -> jax.Array:
    """with_sharding_constraint against the active mesh (no-op without one).

    Entries naming mesh axes whose size doesn't divide the corresponding
    array dimension are dropped, so the same model code serves full-scale
    and tiny-test shapes.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = _clamp_entries(mesh, P(*spec_entries), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Strategy presets (SURVEY.md §2.3 checklist).
# ---------------------------------------------------------------------------

#: Pure data parallelism: every parameter replicated.
DP_RULES: Rules = ()


def _fsdp_largest_dim(shape) -> PartitionSpec:
    """Shard the largest dimension over the fsdp axis (rank-agnostic: for a
    (kh, kw, in, out) conv kernel this picks the channel dim, not kh)."""
    if not shape:
        return REPLICATED
    largest = max(range(len(shape)), key=lambda d: shape[d])
    entries = [None] * len(shape)
    entries[largest] = "fsdp"
    return P(*entries)


#: FSDP / ZeRO-3-style: shard the largest dim of every weight over the fsdp
#: axis; XLA all-gathers per layer and reduce-scatters grads.
FSDP_RULES: Rules = (
    (r"embedding$", P("fsdp", None)),
    (r"kernel$", _fsdp_largest_dim),
)

#: Tensor parallelism for transformer blocks (megatron-style column/row
#: split), composed with fsdp on the other dim.
TP_TRANSFORMER_RULES: Rules = (
    (r"(query|key|value|q_proj|k_proj|v_proj)/kernel$", P("fsdp", "tp")),
    (r"(out|o_proj|attention_output)/kernel$", P("tp", "fsdp")),
    (r"(intermediate|wi|up_proj|gate_proj|mlp_in)/kernel$", P("fsdp", "tp")),
    (r"(output|wo|down_proj|mlp_out)/kernel$", P("tp", "fsdp")),
    (r"(embedding|word_embeddings)/embedding$", P("tp", "fsdp")),
    (r"kernel$", P("fsdp", None)),
)


def strategy_rules(strategy: str) -> Rules:
    """TrainConfig.strategy -> the sharding rule set it names (the
    round-2 'dead config field' is now load-bearing: notebooks pass
    ``strategy_rules(cfg.strategy)`` to compile_step)."""
    if strategy == "dp":
        return DP_RULES
    if strategy == "fsdp":
        return FSDP_RULES
    if strategy in ("tp", "fsdp+tp"):
        return TP_TRANSFORMER_RULES
    if strategy == "lora":
        from tpudl.models.lora import LORA_RULES, compose_rules

        return compose_rules(LORA_RULES, TP_TRANSFORMER_RULES)
    if strategy == "pp":
        from tpudl.parallel.pipelined_bert import PIPELINED_BERT_RULES

        return PIPELINED_BERT_RULES
    if strategy == "pp+fsdp":
        from tpudl.parallel.pipelined_bert import PIPELINED_BERT_FSDP_RULES

        return PIPELINED_BERT_FSDP_RULES
    raise ValueError(
        f"unknown strategy {strategy!r}; expected dp | fsdp | tp | "
        f"fsdp+tp | lora | pp | pp+fsdp"
    )
