from perfbench import flops_window_moe as fl
from perfbench.device import peaks
from perfbench.readers import _window_moe as wm


def read(ctx):
    """The least time the routed experts of the traced prefills could
    take, dispatched by sorted groups (the touched experts' weights
    once and the sorted rows in and out once an assignment, against
    ``2 * 3 * hidden * moe_intermediate`` operations an assignment: the
    larger of bytes over bandwidth and operations over the peak rate),
    over the device's busy time in the prefill program's ``experts``
    scope inside those prefills, %. ``moe_assignments`` counts the real
    tokens' assignments; the program also runs the padding's rows, so
    the share reads low by the padded part."""
    found = wm.traced_spans(ctx, "prefill", wm.PREFILL_ATTRS)
    if found is None:
        return None
    trace, prefills = found
    busy = wm.busy_seconds(trace, prefills, "prefill", ("experts",))
    if busy <= 0:
        return None
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(
            fl.sorted_dispatch_bytes(
                s["moe_assignments"], s["moe_experts_touched"], ctx.config),
            fl.routed_experts_flops(s["moe_assignments"], ctx.config),
            peak,
        ) for _, _, s in prefills
    )
    return 100.0 * least / busy
