"""Operations and bytes of a decoder with a residual stream of several
vectors a token (hyper-connections), latent attention with a low-rank
query and routed experts beside a shared one, every routed expert held,
from shapes alone, beside ``perfbench/flops_mla_moe.py`` and by its
rules: a multiply-add is 2 operations, bytes are the least the algorithm
must move (a weight that is used once, a live cache row once, the stream
once a pass), 2 bytes a value.

The keys are those of ``perfbench/configs/xing4-29b-a4b-l6.json``:
``n_routed_experts`` (all held: ``ep_size`` 1), ``q_lora_rank``,
``hc_mult``. ``live_positions`` is the positions the seated slots hold
(the span attribute ``tokens_live``); ``rows`` the rows a program ran,
padding included.
"""

from __future__ import annotations

from perfbench.flops_mla_moe import least_seconds  # noqa: F401

BYTES = 2
#: A layer's sublayers, each inside a hyper-connection of its own.
SUBLAYERS = 2


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb and W_o of one layer."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    r, rq, dn = cfg["kv_lora_rank"], cfg["q_lora_rank"], cfg["qk_nope_head_dim"]
    dr, dv = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (h * rq + rq * heads * (dn + dr) + h * (r + dr)
            + r * heads * (dn + dv) + heads * dv * h)


def hyper_params(cfg: dict) -> int:
    """``phi``, ``b`` and the three ``alpha`` of one layer's two
    hyper-connections (float32: twice the bytes of the others)."""
    n = cfg["hc_mult"]
    width = 2 * n + n * n
    return SUBLAYERS * (n * cfg["hidden_size"] * width + width + 3)


def expert_params(cfg: dict) -> int:
    """One routed expert (and the shared one): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _norms(cfg: dict) -> int:
    return (2 * cfg["hidden_size"] + cfg["q_lora_rank"]
            + cfg["kv_lora_rank"])


def layer_params_outside_routed_experts(cfg: dict, dense: bool) -> int:
    """What every token of a step passes through in a layer: attention,
    the two hyper-connections, the norms, and the dense SwiGLU or the
    router (with its selection bias) and the shared expert."""
    outside = attention_params(cfg) + hyper_params(cfg) + _norms(cfg)
    if dense:
        return outside + dense_ffn_params(cfg)
    experts = cfg["n_routed_experts"]
    return (outside + cfg["hidden_size"] * experts + experts
            + cfg["n_shared_experts"] * expert_params(cfg))


def _layers(cfg: dict):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def params_held(cfg: dict) -> int:
    """Every parameter this chip holds: the layers with all their
    routed experts, the embedding, the final norm and the head."""
    dense, sparse = _layers(cfg)
    return (
        dense * layer_params_outside_routed_experts(cfg, True)
        + sparse * (layer_params_outside_routed_experts(cfg, False)
                    + cfg["n_routed_experts"] * expert_params(cfg))
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    )


def weight_bytes_held(cfg: dict) -> int:
    """``params_held`` at 2 bytes, the maps' and the routers' float32
    parameters at 4."""
    dense, sparse = _layers(cfg)
    float32 = (cfg["num_hidden_layers"] * hyper_params(cfg)
               + sparse * (cfg["hidden_size"] + 1) * cfg["n_routed_experts"])
    return BYTES * params_held(cfg) + BYTES * float32


def cache_bytes_per_position(cfg: dict) -> int:
    """One cached row ``[c | k_r]`` in every layer's pool."""
    return (cfg["num_hidden_layers"] * BYTES
            * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]))


def stream_bytes(rows: int, cfg: dict) -> int:
    """The least a program of ``rows`` rows must move for its residual
    stream: a sublayer reads the ``n`` streams once for its maps and
    its mixture (``n d``), writes the mixture (``d``), reads the
    sublayer's output (``d``), reads the streams again and writes them
    mixed (``2 n d``): ``(3 n + 2) d`` values a row a sublayer,
    whatever implements it."""
    n = cfg["hc_mult"]
    return (rows * cfg["num_hidden_layers"] * SUBLAYERS
            * (3 * n + 2) * cfg["hidden_size"] * BYTES)


def stream_flops(rows: int, cfg: dict) -> float:
    """The maps' projection, the mixture and the write-back:
    ``n d (2 n + n^2)``, ``n d`` and ``(n + 1) n d`` multiply-adds a
    row a sublayer (the Sinkhorn iterations' few hundred are left
    out)."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    per_row = n * d * (2 * n + n * n) + n * d + (n + 1) * n * d
    return 2.0 * rows * cfg["num_hidden_layers"] * SUBLAYERS * per_row


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * moe_intermediate`` an assignment."""
    return 2.0 * assignments * expert_params(cfg)


def _kv_b(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] * cfg["num_attention_heads"]
            * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def _outside(cfg: dict) -> int:
    """Parameters every row of a program meets: the layers outside
    their routed experts and the output head (the embedding is
    gathered, a row a token)."""
    dense, sparse = _layers(cfg)
    return (dense * layer_params_outside_routed_experts(cfg, True)
            + sparse * layer_params_outside_routed_experts(cfg, False)
            + cfg["hidden_size"] * cfg["vocab_size"])


def _matrix_flops(rows: int, head_rows: int, cfg: dict) -> float:
    """``rows`` rows through every matrix outside the routed experts
    (the maps' projection is ``stream_flops``'), ``head_rows`` of them
    through the head."""
    body = (_outside(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
            - cfg["num_hidden_layers"] * hyper_params(cfg))
    return 2.0 * (rows * body
                  + head_rows * cfg["hidden_size"] * cfg["vocab_size"])


def decode_step_bytes(cfg: dict, slots: int, live_positions: int,
                      experts_touched: int) -> int:
    """Least bytes of one decode step of ``slots`` rows: the weights
    outside the routed experts and the head once, the touched experts
    once, the live rows once, the stream's passes."""
    return (BYTES * _outside(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + live_positions * cache_bytes_per_position(cfg)
            + stream_bytes(slots, cfg))


def decode_step_flops(cfg: dict, active: int, live_positions: int,
                      assignments: int) -> float:
    """Operations of one decode step with ``active`` sequences:
    absorbed attention (W_kvb against each query and output, then
    scores over ``r + dr`` and values over ``r`` for every head and
    live position) in place of the up-projection."""
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    layers = cfg["num_hidden_layers"]
    core = layers * 2.0 * cfg["num_attention_heads"] * (
        r + dr + r) * live_positions
    return (_matrix_flops(active, active, cfg) + core
            + routed_experts_flops(assignments, cfg)
            + stream_flops(active, cfg))


def prefill_bytes(cfg: dict, rows: int, experts_touched: int) -> int:
    """Least bytes of one batch-1 prefill of ``rows`` rows: the weights
    outside the routed experts and the head once, the touched experts
    once, the stream's passes, the row cache written."""
    return (BYTES * _outside(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + stream_bytes(rows, cfg)
            + rows * cache_bytes_per_position(cfg))


def prefill_flops(cfg: dict, rows: int, assignments: int) -> float:
    """Operations of one batch-1 prefill: every row through the
    matrices (W_kvb up-projects), the last row alone through the head,
    causal scores and values (a query meets the keys up to itself:
    ``rows (rows + 1) / 2`` pairs a head), the routed experts an
    assignment, the stream."""
    heads = cfg["num_attention_heads"]
    per_pair = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                + cfg["v_head_dim"])
    pairs = rows * (rows + 1) / 2
    return (_matrix_flops(rows, 1, cfg)
            + cfg["num_hidden_layers"] * 2.0 * heads * per_pair * pairs
            + routed_experts_flops(assignments, cfg)
            + stream_flops(rows, cfg))
