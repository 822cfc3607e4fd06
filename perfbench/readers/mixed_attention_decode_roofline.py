from perfbench import flops_window_moe as fl
from perfbench.device import peaks
from perfbench.readers import _window_moe as wm

#: The scope the kernel's operations sit under (tpudl.ops.paged_attention).
SCOPE = "paged_attention"


def read(ctx):
    """The least time decode attention over the two-group page cache
    could take in the traced decode steps (both groups' live keys and
    values once: ``tokens_live`` in every full-context layer,
    ``tokens_live_window`` in every window layer, over the chip's
    memory bandwidth) over the device's busy time in the decode
    program's ``paged_attention`` scope inside those steps, %. The
    kernel fetches whole pages and meets every KV head with every
    query head, so the share stays under 100."""
    found = wm.traced_spans(ctx, "decode_step", wm.DECODE_ATTRS)
    if found is None:
        return None
    trace, steps = found
    busy = wm.busy_seconds(trace, steps, "decode", (SCOPE,))
    if busy <= 0:
        return None
    least = sum(
        fl.live_kv_bytes(ctx.config, s["tokens_live"], s["tokens_live_window"])
        for _, _, s in steps
    ) / peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / busy
