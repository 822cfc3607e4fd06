from perfbench import flops_shortcut_moe as fl
from perfbench.device import peaks
from perfbench.readers import _latent_moe as lm

#: part -> (least bytes of a step, its operations, the scopes of the
#: decode program whose busy time it is held against; None: the step).
PARTS = {
    "experts": (
        lambda s, cfg: fl.routed_experts_bytes(s["moe_experts_touched"], cfg),
        lambda s, cfg: fl.routed_experts_flops(s["moe_assignments"], cfg),
        ("experts",),
    ),
    "mla": (
        lambda s, cfg: fl.latent_core_bytes(s["tokens_live"], cfg),
        lambda s, cfg: fl.latent_core_flops(s["busy"], s["tokens_live"], cfg),
        ("kv_gather", "mla_core"),
    ),
    "step": (
        lambda s, cfg: fl.decode_step_bytes(
            cfg, s["tokens_live"], s["moe_experts_touched"]),
        lambda s, cfg: fl.decode_step_flops(
            cfg, s["busy"], s["tokens_live"], s["moe_assignments"]),
        None,
    ),
}


def read(ctx, part):
    """The least time ``part`` of the traced decode steps could take
    (the larger of its least bytes over the bandwidth and its operations
    over the peak rate, a step at a time, from
    ``perfbench/flops_shortcut_moe.py``) over the device's busy time in
    it inside those steps, %, for a decoder of shortcut-connected
    double layers:

    - ``experts``: the weights of the held experts that got a token,
      once, and ``2 * 3 * hidden * expert_ffn`` operations an
      assignment, against the decode program's ``experts`` scope (an
      identity choice is in neither);
    - ``mla``: the live rows of all the pools and W_kvb once a
      sublayer, against the absorbed operations, over ``kv_gather`` and
      ``mla_core`` (the gather is counted, because a kernel that reads
      the live rows where they lie has none);
    - ``step``: the weights outside the routed experts and the head
      once, the touched experts once, the live rows once, against the
      step's operations, over everything the device did in the step.
    """
    found = lm.decode_steps(ctx)
    if found is None:
        return None
    trace, steps = found
    n_bytes, n_flops, scopes = PARTS[part]
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(n_bytes(s, ctx.config), n_flops(s, ctx.config), peak)
        for _, _, s in steps
    )
    if scopes is None:
        busy = lm.busy_seconds(trace, steps)
    else:
        busy = lm.busy_seconds(trace, steps, "decode", scopes)
    return 100.0 * least / busy if busy > 0 else None
