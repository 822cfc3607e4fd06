from statistics import fmean

from perfbench import flops_sparse_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _sparse_moe as sm

#: The decode program's scopes a kernel's share is timed over.
SCOPES = {"index_decode": ("dsa_index", "dsa_select"),
          "latent_attention": ("mla_core",)}


def _prefill(ctx, trace, prefills, peak):
    cfg = ctx.config
    least = sum(
        fl.least_seconds(
            fl.prefill_bytes(cfg, s["rows"], s["moe_experts_touched"]),
            fl.prefill_flops(cfg, s["rows"], s["moe_assignments"]),
            peak,
        ) for _, _, s in prefills
    )
    return least, sm.busy_seconds(trace, prefills)


def _decode(ctx, trace, decodes, steps, peak, part):
    cfg = ctx.config
    if not steps:
        return 0.0, 0.0

    def least(s):
        chosen, live = s["sparse_rows_chosen"], s["sparse_rows_live"]
        if part == "index_decode":
            return fl.least_seconds(
                fl.index_decode_bytes(cfg, live),
                fl.index_decode_flops(cfg, live), peak)
        if part == "latent_attention":
            return fl.least_seconds(
                fl.chosen_attention_bytes(cfg, chosen),
                fl.chosen_attention_flops(cfg, s["busy"], chosen), peak)
        return fl.least_seconds(
            fl.decode_step_bytes(cfg, chosen, live, s["moe_experts_touched"]),
            fl.decode_step_flops(
                cfg, s["busy"], chosen, live, s["moe_assignments"]),
            peak)

    busy = (
        sm.busy_seconds(trace, decodes) if part == "decode_step"
        else sum(sm.busy_seconds(trace, decodes, scope)
                 for scope in SCOPES[part])
    )
    return len(decodes) * fmean(least(s) for s in steps), busy


def read(ctx, part):
    """The least time the traced programs of one kind, or one kernel's
    part of the decode programs, could take (the larger of their least
    bytes over the bandwidth and their operations over the peak rate, a
    program at a time, from ``perfbench/flops_sparse_mla_moe.py``) over
    the device's busy time there, %, for a decoder with learned sparse
    attention:

    - ``prefill``: the weights outside the routed experts and the head
      once, the touched experts once and both cache leaves written,
      against every row the program ran (padding included) through the
      matrices, scores and values over ``min(t + 1, index_topk)`` keys
      a query, the indexers' scores for the queries that see more, and
      the assignments to held experts; over the prefill programs' busy
      time;
    - ``decode_step``: the same weights, the touched experts, the live
      indexer keys and the CHOSEN latent rows, against the step's
      operations; over the decode programs' busy time;
    - ``index_decode``: the live indexer keys of the layers with an
      indexer against their scores' operations, over the decode
      programs' busy time under ``dsa_index`` and ``dsa_select``;
    - ``latent_attention``: the chosen rows of every layer and W_kvb
      against the absorbed attention's operations, over the decode programs' busy
      time under ``mla_core`` (the gather of the chosen rows,
      ``dsa_gather``, is inside it), whatever implements the read.

    The traced steps' span records are averaged over the decode
    programs of the trace (``readers/_hyper_moe.py``)."""
    found = sm.programs(ctx)
    if found is None:
        return None
    trace, prefills, decodes, steps = found
    peak = peaks(ctx.device["kind"])
    if part == "prefill":
        least, busy = _prefill(ctx, trace, prefills, peak)
    else:
        least, busy = _decode(ctx, trace, decodes, steps, peak, part)
    return 100.0 * least / busy if busy > 0 else None
