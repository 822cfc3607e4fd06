"""Operations and bytes of a decoder whose layers differ in their
attention (full-context layers beside sliding-window layers, each kind
with its own head count, under a two-group page cache) with routed
experts, from shapes alone, beside ``perfbench/flops.py`` and by its
rules: a multiply-add is 2 operations, bytes are the least the
algorithm must move (a weight that is used once, a live cache row
once), 2 bytes a value.

A step's live positions come by group: ``live_full`` is the sum over
sequences of the positions a full-context layer reads, ``live_window``
of those ONE window layer reads (at most ``sliding_window`` a
sequence).
"""

from __future__ import annotations

BYTES = 2


def layers(cfg: dict) -> range:
    return range(cfg["num_hidden_layers"])


def layer_counts(cfg: dict) -> tuple:
    """(full-context layers, window layers) among the layers held."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    window = sum(k == "sliding_attention" for k in kinds)
    return len(kinds) - window, window


def attention_params(cfg: dict, layer: int) -> int:
    """W_q, W_k, W_v, W_o and the gate (one column a head) of
    ``layer``."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kv = cfg["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + heads * d * h + h * heads


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_routed_experts(cfg: dict, layer: int) -> int:
    """What every token of a step passes through in ``layer``:
    attention, then the dense MLP or the router and the shared
    expert."""
    h = cfg["hidden_size"]
    if cfg["mlp_layer_types"][layer] == "dense":
        return attention_params(cfg, layer) + 3 * h * cfg["intermediate_size"]
    return (attention_params(cfg, layer) + h * cfg["num_experts"]
            + 3 * h * cfg["shared_expert_intermediate_size"])


def params_outside_routed_experts(cfg: dict) -> int:
    """All of them, with the output head (the embedding is gathered, a
    row a token)."""
    return sum(
        layer_params_outside_routed_experts(cfg, i) for i in layers(cfg)
    ) + cfg["hidden_size"] * cfg["vocab_size"]


def kv_bytes_per_position_a_layer(cfg: dict) -> int:
    """Keys and values of one position in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BYTES


def live_kv_bytes(cfg: dict, live_full: int, live_window: int) -> int:
    """The live keys and values a step reads, both groups: the
    full-context layers read ``live_full`` positions each, the window
    layers ``live_window`` each."""
    full, window = layer_counts(cfg)
    return kv_bytes_per_position_a_layer(cfg) * (
        full * live_full + window * live_window
    )


def uniform_kv_bytes(cfg: dict, live_full: int) -> int:
    """What one table for every layer would read: every layer the whole
    live context."""
    return (kv_bytes_per_position_a_layer(cfg)
            * cfg["num_hidden_layers"] * live_full)


def attention_flops(cfg: dict, live_full: int, live_window: int) -> float:
    """Scores and values of one query a sequence against the live
    positions, every query head, by layer kind."""
    d = cfg["head_dim"]
    total = 0.0
    for i in layers(cfg):
        live = (live_window if cfg["layer_types"][i] == "sliding_attention"
                else live_full)
        total += 2.0 * 2 * cfg["num_attention_heads_per_layer"][i] * d * live
    return total


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * moe_intermediate`` an assignment (a token sent
    to an expert)."""
    return 2.0 * assignments * expert_params(cfg)


def dense_dispatch_flops(rows: int, cfg: dict) -> float:
    """The dense dispatch form of ONE layer: every expert over every
    row."""
    return 2.0 * rows * cfg["num_experts"] * expert_params(cfg)


def sorted_dispatch_flops(rows: int, cfg: dict) -> float:
    """The sorted dispatch form of ONE layer: each row meets the experts
    it chose."""
    return routed_experts_flops(rows * cfg["num_experts_per_tok"], cfg)


def sorted_dispatch_bytes(assignments: int, experts_touched: int,
                          cfg: dict) -> int:
    """The sorted form: the touched experts' weights once, and every
    assignment's row in sorted order in (hidden wide) and out (hidden
    wide, float32) once."""
    return (routed_experts_bytes(experts_touched, cfg)
            + assignments * cfg["hidden_size"] * (BYTES + 4))


def decode_step_bytes(cfg: dict, live_full: int, live_window: int,
                      experts_touched: int) -> int:
    """Least bytes of one decode step: every weight outside the routed
    experts once, the touched experts once, the live keys and values of
    both groups once."""
    return (BYTES * params_outside_routed_experts(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + live_kv_bytes(cfg, live_full, live_window))


def decode_step_flops(cfg: dict, active: int, live_full: int,
                      live_window: int, assignments: int) -> float:
    """Operations of one decode step with ``active`` sequences."""
    return (2.0 * active * params_outside_routed_experts(cfg)
            + routed_experts_flops(assignments, cfg)
            + attention_flops(cfg, live_full, live_window))


def least_seconds(n_bytes: float, n_flops: float, peak: dict) -> float:
    """The roofline: the larger of bytes over bandwidth and operations
    over the peak rate."""
    return max(n_bytes / peak["hbm_bytes_per_s"],
               n_flops / peak["bf16_flops_per_s"])
