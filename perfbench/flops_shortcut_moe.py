"""Operations and bytes of a decoder of shortcut-connected double layers
(two latent attentions and two dense FFNs around one routed-expert
branch with identity experts), from shapes alone, beside
``perfbench/flops_mla_moe.py`` and by its rules: a multiply-add is 2
operations, bytes are the least the algorithm must move (a weight that
is used once, a live cache row once), 2 bytes a value.

A configuration's ``n_routed_experts`` and ``vocab_size`` are what is
held here (``perfbench/configs/longcat-flash-l4-e16.json``); the router
keeps its published width, ``deployment.router_experts``. An identity
expert has no weights and no operations worth counting (one multiply a
value). ``live_positions`` is the positions the seated slots hold in ONE
pool (the span attribute ``tokens_live``); every layer has two.
"""

from __future__ import annotations

from perfbench.flops_mla_moe import least_seconds  # noqa: F401

BYTES = 2
SUBLAYERS = 2


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one sublayer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rq, dn = cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h)


def dense_ffn_params(cfg: dict) -> int:
    """Gate, up and down of one sublayer's dense FFN."""
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def layer_params_outside_routed_experts(cfg: dict) -> int:
    """What every token of a step passes through in a layer: two
    attentions, two dense FFNs and the router."""
    return (SUBLAYERS * (attention_params(cfg) + dense_ffn_params(cfg))
            + cfg["hidden_size"] * cfg["deployment"]["router_experts"])


def params_held(cfg: dict) -> int:
    """Every matrix this chip holds: the layers with their held
    experts, the embedding and the head (norm scales left out)."""
    layer = (layer_params_outside_routed_experts(cfg)
             + cfg["n_routed_experts"] * expert_params(cfg))
    return (cfg["num_layers"] * layer
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def cache_bytes_per_position(cfg: dict) -> int:
    """One cached row ``[c | k_r]`` in each of a layer's two pools."""
    return (cfg["num_layers"] * SUBLAYERS * BYTES
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the held experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * expert_ffn`` an assignment (a token sent to a
    held expert)."""
    return 2.0 * assignments * expert_params(cfg)


def _kv_b(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def latent_core_bytes(live_positions: int, cfg: dict) -> int:
    """The live rows once in every pool, and W_kvb (absorbed into the
    query and the output) once a sublayer."""
    return (live_positions * cache_bytes_per_position(cfg)
            + cfg["num_layers"] * SUBLAYERS * BYTES * _kv_b(cfg))


def latent_core_flops(active: int, live_positions: int, cfg: dict) -> float:
    """Absorbed decode, one query a sequence, in every sublayer: W_kvb
    against each of ``active`` queries and outputs, then scores over
    ``r + dr`` and values over ``r`` for every head and live
    position."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    a_pool = (2.0 * active * _kv_b(cfg)
              + 2.0 * cfg["num_attention_heads"] * (r + dr + r)
              * live_positions)
    return a_pool * cfg["num_layers"] * SUBLAYERS


def _outside(cfg: dict) -> int:
    return (cfg["num_layers"] * layer_params_outside_routed_experts(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def decode_step_bytes(cfg: dict, live_positions: int,
                      experts_touched: int) -> int:
    """Least bytes of one decode step: every layer's weights outside the
    routed experts and the output head once (the embedding is gathered,
    a row a token), the touched experts once, the live rows once."""
    return (BYTES * _outside(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + live_positions * cache_bytes_per_position(cfg))


def decode_step_flops(cfg: dict, active: int, live_positions: int,
                      assignments: int) -> float:
    """Operations of one decode step with ``active`` sequences. The
    absorbed attention's W_kvb products stand for the up-projection
    that ``attention_params`` would count: not twice."""
    no_kv_b = _outside(cfg) - cfg["num_layers"] * SUBLAYERS * _kv_b(cfg)
    return (2.0 * active * no_kv_b + routed_experts_flops(assignments, cfg)
            + latent_core_flops(active, live_positions, cfg))
