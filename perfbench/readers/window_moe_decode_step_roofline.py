from perfbench import flops_window_moe as fl
from perfbench.device import peaks
from perfbench.readers import _window_moe as wm


def read(ctx):
    """The least time the traced decode steps could take (weights
    outside the routed experts and the head once, the experts that got
    a token once, the live keys and values of both cache groups once;
    against the operations of the step) over the time the device was
    busy inside those steps, %: ``decode_step_roofline`` for a decoder
    whose step is experts and a two-group page cache."""
    found = wm.traced_spans(ctx, "decode_step", wm.DECODE_ATTRS + ("busy",))
    if found is None:
        return None
    trace, steps = found
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(
            fl.decode_step_bytes(
                ctx.config, s["tokens_live"], s["tokens_live_window"],
                s["moe_experts_touched"]),
            fl.decode_step_flops(
                ctx.config, s["busy"], s["tokens_live"],
                s["tokens_live_window"], s["moe_assignments"]),
            peak,
        ) for _, _, s in steps
    )
    busy = wm.busy_seconds(trace, steps)
    return 100.0 * least / busy if busy > 0 else None
