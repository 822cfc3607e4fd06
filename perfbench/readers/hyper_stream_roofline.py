from perfbench import flops_hyper_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _hyper_moe as hm


def read(ctx):
    """The least time the traced prefill and decode programs could take
    for their residual stream (``(3 n + 2) d`` values a row a sublayer,
    ``perfbench/flops_hyper_mla_moe.py``, over the bandwidth; a prefill
    counts the rows its program ran, padding included, a decode program
    every slot) over the device's busy time in their ``hyper`` scope, %.
    The same count whatever implements the stream."""
    found = hm.programs(ctx)
    if found is None:
        return None
    trace, prefills, decodes, _ = found
    slots = int(ctx.config["session"]["num_slots"])
    rows = sum(s["rows"] for _, _, s in prefills) + slots * len(decodes)
    windows = list(prefills) + decodes
    busy = hm.busy_seconds(trace, windows, "hyper")
    if busy <= 0:
        return None
    least = fl.stream_bytes(rows, ctx.config) / peaks(
        ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / busy
