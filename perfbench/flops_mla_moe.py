"""Operations and bytes of a decoder with latent attention (MLA) and
routed experts, from shapes alone, beside ``perfbench/flops.py`` and by
its rules: a multiply-add is 2 operations, bytes are the least the
algorithm must move (a weight that is used once, a live cache row once),
2 bytes a value.

A configuration's ``num_experts`` and ``vocab_size`` are what is held
here (``perfbench/configs/sarvam-105b-l5-e32.json``); the router keeps
its published width, ``deployment.router_experts``.
"""

from __future__ import annotations

BYTES = 2


def attention_params(cfg: dict) -> int:
    """W_q, W_kv_a, W_kv_b and W_o of one layer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    r, dn = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_routed_experts(cfg: dict, layer: int) -> int:
    """What every token of a step passes through in ``layer``:
    attention, then the dense MLP or the router and the shared
    expert."""
    h = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return attention_params(cfg) + 3 * h * cfg["intermediate_size"]
    return (attention_params(cfg)
            + h * cfg["deployment"]["router_experts"]
            + cfg["num_shared_experts"] * expert_params(cfg))


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def cache_bytes_per_position(cfg: dict) -> int:
    """One cached row ``[c | k_r]`` in every layer."""
    return (cfg["num_hidden_layers"] * BYTES
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * moe_intermediate`` an assignment (a token sent
    to a held expert)."""
    return 2.0 * assignments * expert_params(cfg)


def latent_core_bytes(live_positions: int, cfg: dict) -> int:
    """The live rows once in every layer, and W_kv_b (absorbed into the
    query and the output) once a layer."""
    kv_b = (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))
    return (live_positions * cache_bytes_per_position(cfg)
            + cfg["num_hidden_layers"] * BYTES * kv_b)


def latent_core_flops(live_positions: int, cfg: dict) -> float:
    """Absorbed decode, one query a sequence: scores over ``r + dr`` and
    values over ``r`` for every head and live position, in every layer
    (``2 * heads * (576 + 512)`` a live position at the published
    sizes)."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (2.0 * cfg["num_attention_heads"] * (r + dr + r)
            * live_positions * cfg["num_hidden_layers"])


def decode_step_bytes(cfg: dict, live_positions: int,
                      experts_touched: int) -> int:
    """Least bytes of one decode step: every layer's weights outside the
    routed experts and the output head once (the embedding is gathered,
    a row a token), the touched experts once, the live rows once."""
    outside = sum(layer_params_outside_routed_experts(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    outside += cfg["hidden_size"] * cfg["vocab_size"]
    return (BYTES * outside + routed_experts_bytes(experts_touched, cfg)
            + live_positions * cache_bytes_per_position(cfg))


def decode_step_flops(cfg: dict, active: int, live_positions: int,
                      assignments: int) -> float:
    """Operations of one decode step with ``active`` sequences."""
    outside = sum(layer_params_outside_routed_experts(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    outside += cfg["hidden_size"] * cfg["vocab_size"]
    return (2.0 * active * outside + routed_experts_flops(assignments, cfg)
            + latent_core_flops(live_positions, cfg))


def least_seconds(n_bytes: float, n_flops: float, peak: dict) -> float:
    """The roofline: the larger of bytes over bandwidth and operations
    over the peak rate."""
    return max(n_bytes / peak["hbm_bytes_per_s"],
               n_flops / peak["bf16_flops_per_s"])
