"""Operations and bytes the algorithms need, from shapes alone.

Counted from the architecture, not from the compiled program: a
multiply-add is 2 FLOPs, recomputation counts for nothing, and bytes
are the least the algorithm must move (weights once, live KV once).
"""

from __future__ import annotations


def bert_params_per_layer(hidden: int, inter: int) -> int:
    """Matmul weights of one encoder layer (biases and norms left out:
    they carry no matmul FLOPs)."""
    return 4 * hidden * hidden + 2 * hidden * inter


def bert_train_flops_per_sample(cfg: dict, seq_len: int) -> float:
    """Forward + backward FLOPs of one sequence through BERT with a
    classification head: 6 x matmul weights per token (2 forward, 4
    backward), plus attention's two S x S products per layer (QK^T and
    PV: 2 x 2 x S x S x hidden forward, twice that backward), plus the
    pooler and the classifier on one token. Embedding lookups are
    gathers, not matmuls."""
    h, i, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_hidden_layers"]
    per_token = 6.0 * n * bert_params_per_layer(h, i)
    attn = 3.0 * n * (2 * 2 * seq_len * seq_len * h)
    head = 6.0 * (h * h + h * cfg.get("num_labels", 2))
    return per_token * seq_len + attn + head


def decoder_layer_params(cfg: dict) -> int:
    """Matmul weights of one pre-norm GQA + SwiGLU block."""
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q = h * cfg["num_attention_heads"] * hd
    kv = 2 * h * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * h
    mlp = 3 * h * cfg["intermediate_size"]
    return q + kv + o + mlp


def decoder_weight_bytes(cfg: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of weights one decode step must read: every block and the
    output head (the embedding table is gathered, a row a token)."""
    h = cfg["hidden_size"]
    layers = cfg["num_hidden_layers"] * decoder_layer_params(cfg)
    return bytes_per_weight * (layers + h * cfg["vocab_size"])


def kv_bytes_per_position(cfg: dict, bytes_per_value: int = 2) -> int:
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * hd * bytes_per_value)


def decode_step_bytes(cfg: dict, live_positions: int) -> int:
    """Least bytes of one decode step: the weights once and the live
    keys and values once (``live_positions`` summed over the slots in
    use)."""
    return decoder_weight_bytes(cfg) + live_positions * kv_bytes_per_position(cfg)


def decode_step_flops(cfg: dict, active: int, live_positions: int) -> float:
    """FLOPs of one decode step with ``active`` sequences."""
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    dense = 2.0 * active * (
        cfg["num_hidden_layers"] * decoder_layer_params(cfg)
        + h * cfg["vocab_size"]
    )
    attn = (2.0 * 2 * live_positions * cfg["num_attention_heads"] * hd
            * cfg["num_hidden_layers"])
    return dense + attn
