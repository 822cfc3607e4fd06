"""Operations and bytes of a decoder whose ONE stack of sandwich-normed
layers runs ``total_ut_steps`` times over the same weights a token (a
k/v cache a (pass, layer)), from shapes alone, beside
``perfbench/flops.py`` and by its rules: a multiply-add is 2 operations,
bytes are the least the algorithm must move, 2 bytes a value. What the
loop changes: a program reads the layers' weights ONCE A PASS (the head
once), and a position's keys and values lie in ``passes x layers``
pools.

The keys are those of ``perfbench/configs/ouro-2.6b.json``.
``live_positions`` is the positions the seated slots hold in ONE pool
(the span attribute ``tokens_live``); ``rows`` the rows a program ran,
padding included.
"""

from __future__ import annotations

from perfbench.flops_mla_moe import least_seconds  # noqa: F401

BYTES = 2
#: RMSNorms of one layer (the sandwich form).
LAYER_NORMS = 4


def _sizes(cfg: dict) -> tuple:
    h = cfg["hidden_size"]
    hd = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return h, hd, cfg["num_attention_heads"], cfg["num_key_value_heads"]


def passes(cfg: dict) -> int:
    return int(cfg["total_ut_steps"])


def layer_matmul_params(cfg: dict) -> int:
    """q, k, v, o and the gated MLP's three matrices of one layer."""
    h, hd, nq, nkv = _sizes(cfg)
    return (h * nq * hd + 2 * h * nkv * hd + nq * hd * h
            + 3 * h * cfg["intermediate_size"])


def layer_params(cfg: dict) -> int:
    return layer_matmul_params(cfg) + LAYER_NORMS * cfg["hidden_size"]


def gate_params(cfg: dict) -> int:
    """The exit gate's weight and bias (float32)."""
    return cfg["hidden_size"] + 1


def params_held(cfg: dict) -> int:
    """ONE set of layers, the embedding, the head, the final norm and
    the exit gate."""
    h = cfg["hidden_size"]
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * h + h + gate_params(cfg))


def weight_bytes_held(cfg: dict) -> int:
    """``params_held`` at 2 bytes, the gate's float32 values at 4."""
    return BYTES * params_held(cfg) + BYTES * gate_params(cfg)


def cache_bytes_per_position(cfg: dict) -> int:
    """A position's keys and values in every (pass, layer) pool."""
    _, hd, _, nkv = _sizes(cfg)
    return passes(cfg) * cfg["num_hidden_layers"] * 2 * nkv * hd * BYTES


def weights_read_bytes(cfg: dict) -> int:
    """What one program reads of the weights: every layer and the final
    norm once a PASS, the gate after every pass but the last, the head
    once (the embedding table is gathered, a row a token)."""
    h, t = cfg["hidden_size"], passes(cfg)
    return (t * BYTES * (cfg["num_hidden_layers"] * layer_params(cfg) + h)
            + (t - 1) * 2 * BYTES * gate_params(cfg)
            + BYTES * h * cfg["vocab_size"])


def decode_step_bytes(cfg: dict, live_positions: int) -> int:
    """Least bytes of one decode step: the weights (the layers' once a
    pass) and the live keys and values of all ``passes x layers``
    pools."""
    return (weights_read_bytes(cfg)
            + live_positions * cache_bytes_per_position(cfg))


def _attention_flops(cfg: dict, query_key_pairs: float) -> float:
    """QK^T and PV over ``query_key_pairs`` in every (pass, layer)."""
    _, hd, nq, _ = _sizes(cfg)
    return (2.0 * 2 * query_key_pairs * nq * hd
            * passes(cfg) * cfg["num_hidden_layers"])


def decode_step_flops(cfg: dict, active: int, live_positions: int) -> float:
    """Operations of one decode step with ``active`` sequences."""
    dense = 2.0 * active * (
        passes(cfg) * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )
    return dense + _attention_flops(cfg, live_positions)


def prefill_bytes(cfg: dict, rows: int) -> int:
    """Least bytes of one batch-1 prefill of ``rows`` rows: the weights
    as a decode step reads them, and the rows' keys and values written
    for every (pass, layer)."""
    return weights_read_bytes(cfg) + rows * cache_bytes_per_position(cfg)


def prefill_flops(cfg: dict, rows: int) -> float:
    """Operations of one prefill of ``rows`` rows (padding included):
    every row through every layer of every pass, the causal scores, the
    head on the one row whose logits are read."""
    dense = 2.0 * (
        rows * passes(cfg) * cfg["num_hidden_layers"]
        * layer_matmul_params(cfg)
        + cfg["hidden_size"] * cfg["vocab_size"]
    )
    return dense + _attention_flops(cfg, rows * (rows + 1) / 2)
