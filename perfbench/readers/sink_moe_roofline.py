from statistics import fmean

from perfbench import flops_sink_window_moe as fl
from perfbench.device import peaks
from perfbench.readers import _sink_window_moe as sw

#: The scope both paged-attention kernels' operations sit under
#: (tpudl.ops.paged_attention), inside the layer's kind scope.
SCOPE = "paged_attention"


def _prefill(ctx, trace, prefills, peak):
    cfg = ctx.config
    least = sum(
        fl.least_seconds(
            fl.prefill_bytes(cfg, s["rows"], s["moe_experts_touched"]),
            fl.prefill_flops(cfg, s["tokens"], s["moe_assignments"]),
            peak,
        ) for _, _, s in prefills
    )
    return least, sw.busy_seconds(trace, prefills)


def _decode(ctx, trace, decodes, steps, peak, part):
    cfg = ctx.config
    if not steps:
        return 0.0, 0.0

    def least(s):
        live = s["tokens_live"], s["tokens_live_window"]
        if part == "paged_attention":
            return fl.least_seconds(
                fl.live_kv_bytes(cfg, *live),
                fl.attention_flops(cfg, *live), peak)
        return fl.least_seconds(
            fl.decode_step_bytes(cfg, *live, s["moe_experts_touched"]),
            fl.decode_step_flops(
                cfg, s["busy"], *live, s["moe_assignments"]),
            peak)

    busy = sw.busy_seconds(
        trace, decodes, SCOPE if part == "paged_attention" else None)
    return len(decodes) * fmean(least(s) for s in steps), busy


def read(ctx, part):
    """The least time the traced programs of one kind, or the paged
    attention's part of the decode programs, could take (the larger of
    their least bytes over the bandwidth and their operations over the
    peak rate, a program at a time, from
    ``perfbench/flops_sink_window_moe.py``) over the device's busy time
    there, %, for a decoder whose two cache groups hold rows of
    different bytes:

    - ``paged_attention``: the live keys and values of all layers, each
      group's positions at that group's row bytes, against the scores'
      and values' operations; over the decode programs' busy time under
      ``paged_attention`` (the kernel, the query it is handed with every
      head in its KV head's lanes, the cut of its result);
    - ``decode_step``: the weights outside the routed experts and the
      head once, the touched experts once, the live rows once, against
      the step's operations; over the decode programs' busy time;
    - ``prefill``: the same weights, the touched experts, the row cache
      written, against the operations of the prompt's OWN tokens (rows
      of padding show as lost share); over the prefill programs' busy
      time.

    The traced steps' span records are averaged over the decode
    programs of the trace (``readers/_hyper_moe.py``)."""
    found = sw.programs(ctx)
    if found is None:
        return None
    trace, prefills, decodes, steps = found
    peak = peaks(ctx.device["kind"])
    if part == "prefill":
        least, busy = _prefill(ctx, trace, prefills, peak)
    elif part in ("paged_attention", "decode_step"):
        least, busy = _decode(ctx, trace, decodes, steps, peak, part)
    else:
        raise ValueError(
            f"part must be paged_attention, decode_step or prefill: {part!r}")
    return 100.0 * least / busy if busy > 0 else None
