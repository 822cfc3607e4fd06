from perfbench import trace as tr
from perfbench.device import peaks
from perfbench.flops import kv_bytes_per_position
from perfbench.readers import _program_trace as pt

#: The scope the kernel's operations sit under (tpudl.ops.paged_attention).
SCOPE = "paged_attention"


def read(ctx):
    """The least time attention over the paged k / v pool could take in
    the traced decode steps (every live position's keys and values
    once, in every layer, over the chip's memory bandwidth: the step's
    ``tokens_live``) over the device's busy time in the decode
    program's ``paged_attention`` scope inside those steps, %. The
    kernel fetches whole pages, so it reads more than the live
    positions and the share stays under 100. Nothing on a CPU (a share
    of a chip's peak is never reported from one), from an untraced run,
    or where no operation has the scope (a program that gathers)."""
    if ctx.device["platform"] == "cpu":
        return None
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    mine = tr.busy_intervals([
        [o[0], o[1], o[2]] for o in trace["ops"]
        if o[3] == "jit_tpudl_decode" and pt.has_scope(o[4], SCOPE)
    ])
    if not mine:
        return None
    live = {s["id"]: s["tokens_live"] for s in ctx.spans
            if s.get("kind") == "span" and s.get("name") == "decode_step"
            and "tokens_live" in s}
    steps = [(a, b, live[i]) for a, b, i in
             pt.occurrences(trace, "decode_step") if i in live]
    busy_s = tr.busy_inside(mine, sorted((a, b) for a, b, _ in steps)) / 1e9
    if busy_s <= 0:
        return None
    least_s = (
        sum(n for _, _, n in steps) * kv_bytes_per_position(ctx.config)
        / peaks(ctx.device["kind"])["hbm_bytes_per_s"]
    )
    return 100.0 * least_s / busy_s
