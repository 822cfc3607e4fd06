from perfbench import flops_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _latent_moe as lm


def read(ctx):
    """The least time the traced decode steps could take (weights
    outside the routed experts and the head once, the experts that got a
    token once, the live latent rows once; against the operations of
    the step) over the time the device was busy inside those steps, %:
    ``decode_step_roofline`` for a decoder whose step is experts and a
    latent cache."""
    found = lm.decode_steps(ctx)
    if found is None:
        return None
    trace, steps = found
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(
            fl.decode_step_bytes(
                ctx.config, s["tokens_live"], s["moe_experts_touched"]),
            fl.decode_step_flops(
                ctx.config, s["busy"], s["tokens_live"],
                s["moe_assignments"]),
            peak,
        ) for _, _, s in steps
    )
    busy = lm.busy_seconds(trace, steps)
    return 100.0 * least / busy if busy > 0 else None
