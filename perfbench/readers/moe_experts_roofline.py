from perfbench import flops_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _latent_moe as lm


def read(ctx):
    """The least time the routed-expert matmuls of the traced decode
    steps could take (the weights of the experts that got a token, once;
    ``2 * 3 * hidden * moe_intermediate`` operations an assignment; the
    larger of bytes over bandwidth and operations over the peak rate)
    over the device's busy time in the decode program's ``experts``
    scope inside those steps, %."""
    found = lm.decode_steps(ctx)
    if found is None:
        return None
    trace, steps = found
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(
            fl.routed_experts_bytes(s["moe_experts_touched"], ctx.config),
            fl.routed_experts_flops(s["moe_assignments"], ctx.config), peak,
        ) for _, _, s in steps
    )
    busy = lm.busy_seconds(trace, steps, "decode", ("experts",))
    return 100.0 * least / busy if busy > 0 else None
