"""Operations and bytes of a decoder whose layers are of two kinds
(full-context layers beside sliding-window layers, each kind with its
own KV heads, keys wider than values, under a two-group page cache) with
routed experts of which one chip's share is held, from shapes alone,
beside ``perfbench/flops.py`` and by its rules: a multiply-add is 2
operations, bytes are the least the algorithm must move (a weight that
is used once, a live cache row once), 2 bytes a value. Keys and values
are counted apart (192 and 128 wide); rows differ by kind, so positions
are weighted by their kind's row bytes.

A step's live positions come by group: ``live_full`` is the sum over
sequences of the positions a full-context layer reads, ``live_window``
of those ONE window layer reads (at most ``sliding_window`` a
sequence). A prefill's operations are those of the prompt's OWN tokens
(full layers the causal pairs, window layers the pairs with ``t - u <
sliding_window``): rows of padding show as lost share.
"""

from __future__ import annotations

BYTES = 2


def layer_kinds(cfg: dict) -> list:
    """``[(window layer?, expert layer?)]`` of the layers held."""
    n = cfg["num_hidden_layers"]
    return [(bool(w), bool(e)) for w, e in zip(
        cfg["hybrid_layer_pattern"][:n], cfg["moe_layer_freq"][:n])]


def layer_counts(cfg: dict) -> tuple:
    """(full-context layers, window layers) among the layers held."""
    window = sum(w for w, _ in layer_kinds(cfg))
    return cfg["num_hidden_layers"] - window, window


def _head(cfg: dict, window: bool) -> tuple:
    """(query heads, KV heads, key width, value width) of a kind."""
    pre = "swa_" if window else ""
    return (cfg[f"{pre}num_attention_heads"], cfg[f"{pre}num_key_value_heads"],
            cfg[f"{pre}head_dim"], cfg[f"{pre}v_head_dim"])


def attention_params(cfg: dict, window: bool) -> int:
    """W_q, W_k, W_v and W_o of a layer of that kind."""
    h = cfg["hidden_size"]
    heads, kv, d, dv = _head(cfg, window)
    return h * heads * d + h * kv * d + h * kv * dv + heads * dv * h


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params_outside_routed_experts(cfg: dict, layer: int) -> int:
    """What every token of a step passes through in ``layer``: the
    attention, then the dense SwiGLU or the router (its published
    width; there is no shared expert)."""
    window, experts = layer_kinds(cfg)[layer]
    h = cfg["hidden_size"]
    rest = (h * cfg["deployment"]["router_experts"] if experts
            else 3 * h * cfg["intermediate_size"])
    return attention_params(cfg, window) + rest


def params_outside_routed_experts(cfg: dict) -> int:
    """All of them, with the output head over the rows held (the
    embedding is gathered, a row a token)."""
    return sum(
        layer_params_outside_routed_experts(cfg, i)
        for i in range(cfg["num_hidden_layers"])
    ) + cfg["hidden_size"] * cfg["vocab_size"]


def parameters(cfg: dict) -> int:
    """The matrices held here: every layer's, the held experts', the
    embedding and the head (norm scales, sinks and the routers'
    selection biases, a few tens of thousands of values, apart)."""
    _, expert_layers = zip(*layer_kinds(cfg))
    return (params_outside_routed_experts(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"]
            + sum(expert_layers) * cfg["n_routed_experts"] * expert_params(cfg))


def kv_bytes_per_position(cfg: dict, window: bool) -> int:
    """Keys and values of one position in ONE layer of that kind."""
    _, kv, d, dv = _head(cfg, window)
    return kv * (d + dv) * BYTES


def live_kv_bytes(cfg: dict, live_full: int, live_window: int) -> int:
    """The live keys and values a step reads, both groups: the
    full-context layers read ``live_full`` positions each, the window
    layers ``live_window`` each, at their own row bytes."""
    full, window = layer_counts(cfg)
    return (full * live_full * kv_bytes_per_position(cfg, False)
            + window * live_window * kv_bytes_per_position(cfg, True))


def uniform_kv_bytes(cfg: dict, live_full: int) -> int:
    """What one table for every layer would read: every layer the whole
    live context, at its own row bytes."""
    return live_kv_bytes(cfg, live_full, live_full)


def reserved_kv_bytes(cfg: dict, page_size: int, pages_full: int,
                      pages_window: int) -> tuple:
    """(the full-context pools', the rings') bytes of the pages the
    seated slots hold."""
    full, window = layer_counts(cfg)
    return (full * pages_full * page_size * kv_bytes_per_position(cfg, False),
            window * pages_window * page_size
            * kv_bytes_per_position(cfg, True))


def attention_flops(cfg: dict, live_full: int, live_window: int) -> float:
    """Scores (keys' width) and values (values' width) of one query a
    sequence against the live positions, every query head, by kind."""
    total = 0.0
    for window, _ in layer_kinds(cfg):
        heads, _, d, dv = _head(cfg, window)
        total += 2.0 * heads * (d + dv) * (live_window if window else live_full)
    return total


def routed_experts_bytes(experts_touched: int, cfg: dict) -> int:
    """Weights of the experts that got a token, once each
    (``experts_touched`` counts them over the layers)."""
    return BYTES * experts_touched * expert_params(cfg)


def routed_experts_flops(assignments: int, cfg: dict) -> float:
    """``2 * 3 * hidden * moe_intermediate`` an assignment (a token sent
    to an expert held here)."""
    return 2.0 * assignments * expert_params(cfg)


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


def prefill_pairs(tokens: int, window: int) -> tuple:
    """``(causal, banded)`` (query, key) pairs of a prompt of ``tokens``
    tokens: query ``t`` sees ``t + 1`` keys in a full-context layer and
    ``min(t + 1, window)`` in a window layer."""
    under = min(tokens, window)
    return (tokens * (tokens + 1) // 2,
            under * (under + 1) // 2 + (tokens - under) * window)


def prefill_bytes(cfg: dict, rows: int, experts_touched: int) -> int:
    """Least bytes of one batch-1 prefill that ran ``rows`` rows: the
    weights outside the routed experts and the head once, the touched
    experts once, the row cache of every layer written."""
    return (BYTES * params_outside_routed_experts(cfg)
            + routed_experts_bytes(experts_touched, cfg)
            + live_kv_bytes(cfg, rows, rows))


def prefill_flops(cfg: dict, tokens: int, assignments: int) -> float:
    """Operations of one batch-1 prefill of a prompt of ``tokens`` OWN
    tokens: each through the matrices outside the routed experts, the
    last alone through the head, scores and values over the pairs each
    kind sees, the routed experts an assignment."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    body = params_outside_routed_experts(cfg) - head
    causal, banded = prefill_pairs(tokens, cfg["sliding_window"])
    attention = 0.0
    for window, _ in layer_kinds(cfg):
        heads, _, d, dv = _head(cfg, window)
        attention += 2.0 * heads * (d + dv) * (banded if window else causal)
    return (2.0 * (tokens * body + head) + attention
            + routed_experts_flops(assignments, cfg))


def least_seconds(n_bytes: float, n_flops: float, peak: dict) -> float:
    """The roofline: the larger of bytes over bandwidth and operations
    over the peak rate."""
    return max(n_bytes / peak["hbm_bytes_per_s"],
               n_flops / peak["bf16_flops_per_s"])
