from perfbench import flops_mla_moe as fl
from perfbench.device import peaks
from perfbench.readers import _latent_moe as lm


def read(ctx):
    """The least time attention over the latent cache could take in the
    traced decode steps (every live row once a layer, W_kv_b once a
    layer; scores over ``r + dr`` and values over ``r`` a head and live
    position) over the device's busy time in the decode program's
    ``kv_gather`` and ``mla_core`` scopes inside those steps, %: the
    gather is counted, because a kernel that reads the live rows where
    they lie has none."""
    found = lm.decode_steps(ctx)
    if found is None:
        return None
    trace, steps = found
    peak = peaks(ctx.device["kind"])
    least = sum(
        fl.least_seconds(
            fl.latent_core_bytes(s["tokens_live"], ctx.config),
            fl.latent_core_flops(s["tokens_live"], ctx.config), peak,
        ) for _, _, s in steps
    )
    busy = lm.busy_seconds(trace, steps, "decode", ("kv_gather", "mla_core"))
    return 100.0 * least / busy if busy > 0 else None
