"""Operations and bytes of a decoder with latent attention under learned
sparse attention (an indexer on some layers chooses ``index_topk``
cached positions a query, the other layers reuse the choice) and routed
experts beside a shared one, one share of the experts and of the
vocabulary held, from shapes alone, beside ``perfbench/flops_mla_moe.py``
and by its rules: a multiply-add is 2 operations, bytes are the least
the algorithm must move (a weight that is used once, a cache row that
is read once), 2 bytes a value. Every count is the LEAST any
implementation must do, so that no share of a peak can pass 100 %: a
query's attention is counted over ``min(t + 1, index_topk)`` keys, never
over all; an indexer's scores only for the queries that see more than
``index_topk`` keys at a prefill (the others choose everything), and
over every live key at a decode step (where the table holds more than
``index_topk``).

The keys are those of ``perfbench/configs/glm-5.2-l6-e16.json``:
``n_routed_experts`` and ``vocab_size`` are what is held here, the
router keeps ``deployment.router_experts`` outputs, ``indexer_types``
and ``mlp_layer_types`` say what each layer is. ``chosen_rows`` and
``live_rows`` are ONE indexer layer's sums over a program's queries
(the span attributes ``sparse_rows_chosen`` / ``sparse_rows_live``);
``rows`` the rows a program ran, padding included.
"""

from __future__ import annotations

from perfbench.flops_mla_moe import least_seconds  # noqa: F401

BYTES = 2


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rq, dn = cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h)


def indexer_params(cfg: dict) -> int:
    """W_qI, W_kI, W_w and the key LayerNorm's scale and bias."""
    heads, width = cfg["index_n_heads"], cfg["index_head_dim"]
    return (cfg["q_lora_rank"] * heads * width
            + cfg["hidden_size"] * (width + heads) + 2 * width)


def expert_params(cfg: dict) -> int:
    """One routed expert (and the shared one): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router's matrix and its selection bias (float32)."""
    return (cfg["hidden_size"] + 1) * cfg["deployment"]["router_experts"]


def _norms(cfg: dict) -> int:
    return (2 * cfg["hidden_size"] + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"])


def is_dense(cfg: dict, layer: int) -> bool:
    return cfg["mlp_layer_types"][layer] == "dense"


def has_indexer(cfg: dict, layer: int) -> bool:
    return cfg["indexer_types"][layer] == "full"


def index_layers(cfg: dict) -> int:
    return sum(has_indexer(cfg, i) for i in range(cfg["num_hidden_layers"]))


def layer_params_outside_routed_experts(cfg: dict, layer: int) -> int:
    """What every token of a step passes through in ``layer``:
    attention, the norms, the indexer where the layer has one, and the
    dense SwiGLU or the router and the shared expert."""
    outside = attention_params(cfg) + _norms(cfg)
    if has_indexer(cfg, layer):
        outside += indexer_params(cfg)
    if is_dense(cfg, layer):
        return outside + 3 * cfg["hidden_size"] * cfg["intermediate_size"]
    return (outside + router_params(cfg)
            + cfg["n_shared_experts"] * expert_params(cfg))


def expert_layers(cfg: dict) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds: the layers with their held
    routed experts, the embedding, the final norm and the head."""
    layers = range(cfg["num_hidden_layers"])
    return (
        sum(layer_params_outside_routed_experts(cfg, i) for i in layers)
        + expert_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def weight_bytes_held(cfg: dict) -> int:
    """``params_held`` at 2 bytes, the routers' float32 parameters at 4."""
    return BYTES * (params_held(cfg) + expert_layers(cfg) * router_params(cfg))


def latent_row_bytes(cfg: dict) -> int:
    """One cached row ``[c | k_r]`` of one layer."""
    return BYTES * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def index_key_bytes(cfg: dict) -> int:
    """One cached indexer key of one "full" layer."""
    return BYTES * cfg["index_head_dim"]


def cache_bytes_per_position(cfg: dict) -> int:
    """A latent row in every layer's pool and an indexer key in every
    "full" layer's second pool."""
    return (cfg["num_hidden_layers"] * latent_row_bytes(cfg)
            + index_layers(cfg) * index_key_bytes(cfg))


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * moe_intermediate`` an assignment (a token sent
    to an expert held here)."""
    return 2.0 * assignments * expert_params(cfg)


def _outside(cfg: dict) -> int:
    """Parameters every row of a program meets: the layers outside
    their routed experts and the output head (the embedding is
    gathered, a row a token)."""
    return (sum(layer_params_outside_routed_experts(cfg, i)
                for i in range(cfg["num_hidden_layers"]))
            + cfg["hidden_size"] * cfg["vocab_size"])


def _outside_bytes(cfg: dict) -> int:
    return BYTES * (_outside(cfg) + expert_layers(cfg) * router_params(cfg))


def _matrix_flops(rows: int, head_rows: int, cfg: dict) -> float:
    """``rows`` rows through every matrix outside the routed experts,
    ``head_rows`` of them through the head."""
    body = _outside(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
    return 2.0 * (rows * body
                  + head_rows * cfg["hidden_size"] * cfg["vocab_size"])


# -- the two kernels alone ----------------------------------------------------


def index_decode_bytes(cfg: dict, live_rows: int) -> int:
    """The live indexer keys of every "full" layer, once."""
    return index_layers(cfg) * live_rows * index_key_bytes(cfg)


def index_decode_flops(cfg: dict, live_rows: int) -> float:
    """Every live key against the query's ``index_n_heads`` heads, and
    the weighted sum over them."""
    per_key = cfg["index_n_heads"] * (cfg["index_head_dim"] + 1)
    return 2.0 * index_layers(cfg) * live_rows * per_key


def _kv_b(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def chosen_attention_bytes(cfg: dict, chosen_rows: int) -> int:
    """The chosen latent rows of every layer, once, and W_kvb, which
    the absorbed form multiplies into the query and out of the
    result."""
    return cfg["num_hidden_layers"] * (
        chosen_rows * latent_row_bytes(cfg) + BYTES * _kv_b(cfg))


def chosen_attention_flops(cfg: dict, active: int, chosen_rows: int) -> float:
    """Absorbed attention: W_kvb against each of the ``active`` queries
    and results, then every head scores a chosen row over ``r + dr``
    values and sums it over ``r``."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return cfg["num_hidden_layers"] * 2.0 * (
        active * _kv_b(cfg)
        + cfg["num_attention_heads"] * (r + dr + r) * chosen_rows)


# -- whole programs -----------------------------------------------------------


def decode_step_bytes(cfg: dict, chosen_rows: int, live_rows: int,
                      experts_touched: int) -> int:
    """Least bytes of one decode step: the weights outside the routed
    experts and the head once, the touched experts once, the live
    indexer keys and the chosen latent rows once."""
    return (_outside_bytes(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + index_decode_bytes(cfg, live_rows)
            + cfg["num_hidden_layers"] * chosen_rows * latent_row_bytes(cfg))


def decode_step_flops(cfg: dict, active: int, chosen_rows: int,
                      live_rows: int, assignments: int) -> float:
    """Operations of one decode step with ``active`` sequences."""
    return (_matrix_flops(active, active, cfg)
            + index_decode_flops(cfg, live_rows)
            + chosen_attention_flops(cfg, 0, chosen_rows)
            + routed_experts_flops(assignments, cfg))


def prefill_pairs(rows: int, topk: int) -> tuple:
    """``(attended, scored)`` (query, key) pairs of a causal prefill of
    ``rows`` rows: query ``t`` attends ``min(t + 1, topk)`` keys, and an
    indexer scores all ``t + 1`` it sees only where they are more than
    ``topk``."""
    under = min(rows, topk)
    attended = under * (under + 1) // 2 + (rows - under) * topk
    scored = rows * (rows + 1) // 2 - under * (under + 1) // 2
    return attended, scored


def prefill_bytes(cfg: dict, rows: int, experts_touched: int) -> int:
    """Least bytes of one batch-1 prefill of ``rows`` rows: the weights
    outside the routed experts and the head once, the touched experts
    once, the row cache (both leaves) written."""
    return (_outside_bytes(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + rows * cache_bytes_per_position(cfg))


def prefill_flops(cfg: dict, rows: int, assignments: int) -> float:
    """Operations of one batch-1 prefill: every row through the
    matrices (W_kvb up-projects), the last row alone through the head,
    scores and values over the chosen keys, the indexers' scores, the
    routed experts an assignment."""
    attended, scored = prefill_pairs(rows, cfg["index_topk"])
    per_pair = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                + cfg["v_head_dim"])
    per_score = cfg["index_n_heads"] * (cfg["index_head_dim"] + 1)
    return (_matrix_flops(rows, 1, cfg)
            + (cfg["num_hidden_layers"] * 2.0 * cfg["num_attention_heads"]
               * per_pair * attended)
            + index_layers(cfg) * 2.0 * per_score * scored
            + routed_experts_flops(assignments, cfg))
