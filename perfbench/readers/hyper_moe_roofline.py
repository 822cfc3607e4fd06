from statistics import fmean

from perfbench import flops_hyper_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _hyper_moe as hm


def _prefill(ctx, trace, prefills, peak):
    cfg = ctx.config
    k = cfg["num_experts_per_tok"]
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    least = sum(
        fl.least_seconds(
            fl.prefill_bytes(cfg, s["rows"], s["moe_experts_touched"]),
            fl.prefill_flops(cfg, s["rows"], s["rows"] * k * sparse),
            peak,
        ) for _, _, s in prefills
    )
    return least, hm.busy_seconds(trace, prefills)


def _decode_step(ctx, trace, decodes, steps, peak):
    cfg = ctx.config
    slots = int(cfg["session"]["num_slots"])
    if not steps:
        return 0.0, 0.0
    least = len(decodes) * fmean(
        fl.least_seconds(
            fl.decode_step_bytes(
                cfg, slots, s["tokens_live"], s["moe_experts_touched"]),
            fl.decode_step_flops(
                cfg, s["busy"], s["tokens_live"], s["moe_assignments"]),
            peak,
        ) for s in steps
    )
    return least, hm.busy_seconds(trace, decodes)


def read(ctx, part):
    """The least time the traced programs of one kind could take (the
    larger of their least bytes over the bandwidth and their operations
    over the peak rate, a program at a time, from
    ``perfbench/flops_hyper_mla_moe.py``) over the device's busy time
    inside their module events, %, for a decoder with a residual stream
    of several vectors a token:

    - ``prefill``: the weights outside the routed experts and the head
      once, the touched experts once, the stream's passes and the row
      cache, against every row the program ran (padding included)
      through the matrices, the causal scores, 4 expert assignments a
      row a layer and the stream;
    - ``decode_step``: the same weights, the touched experts, the live
      rows of the pool and 64 rows of stream, against the step's
      operations; the traced steps' span records are averaged over the
      decode programs of the trace (``readers/_hyper_moe.py``).
    """
    found = hm.programs(ctx)
    if found is None:
        return None
    trace, prefills, decodes, steps = found
    peak = peaks(ctx.device["kind"])
    if part == "prefill":
        least, busy = _prefill(ctx, trace, prefills, peak)
    else:
        least, busy = _decode_step(ctx, trace, decodes, steps, peak)
    return 100.0 * least / busy if busy > 0 else None
