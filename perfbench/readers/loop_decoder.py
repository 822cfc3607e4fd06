from statistics import fmean

from perfbench import flops_loop_decoder as fl
from perfbench.device import peaks
from perfbench.readers import _hyper_moe as hm
from perfbench.readers import _program_trace as pt

#: The scope the k/v kernel's operations sit under.
KERNEL_SCOPE = "paged_attention"


def _programs(ctx):
    """``(trace, prefills, decodes, steps)`` as
    ``readers/_hyper_moe.programs`` gives them (a program's time is its
    ``jit_tpudl_prefill`` / ``jit_tpudl_decode`` MODULE event, not the
    host's span window, which reads high where steps run ahead), for
    the spans a looped stack writes (``loop_passes``). None on a CPU,
    for an untraced run, and where no span carries the attribute (a
    program from before the loop)."""
    if ctx.device["platform"] == "cpu":
        return None
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    by_id = hm._records(ctx, "prefill", ("rows", "loop_passes"))
    spans = [(a, b, by_id[i]) for a, b, i in pt.occurrences(trace, "prefill")
             if i in by_id]
    prefills = []
    for name, start, dur in trace["modules"]:
        if name != hm.PREFILL:
            continue
        middle = start + dur / 2
        for a, b, record in spans:
            if a <= middle <= b:
                prefills.append((start, start + dur, record))
                break
    decodes = [(start, start + dur) for name, start, dur in trace["modules"]
               if name == hm.DECODE]
    by_id = hm._records(
        ctx, "decode_step", ("tokens_live", "busy", "loop_passes"))
    steps = [by_id[i] for _, _, i in pt.occurrences(trace, "decode_step")
             if i in by_id]
    if not (prefills or (decodes and steps)):
        return None
    return trace, prefills, decodes, steps


def _kv_bytes_share(ctx):
    spans = [s for s in ctx.window_spans("decode_step")
             if "tokens_live" in s and "loop_passes" in s]
    if not spans:
        return None
    cfg = ctx.config
    return 100.0 * fmean(
        s["tokens_live"] * fl.cache_bytes_per_position(cfg)
        / fl.decode_step_bytes(cfg, s["tokens_live"]) for s in spans
    )


def read(ctx, part):
    """Metrics of a decoder whose stack runs several times over the
    same weights a token (``perfbench/flops_loop_decoder.py`` counts):

    - ``kv_bytes_share``: the live keys and values of all ``passes x
      layers`` pools over all the bytes a decode step must read, %, the
      mean over the window's ``decode_step`` spans (their
      ``tokens_live``): which half of the mechanism sets the step. From
      spans alone, on any device.
    - ``decode_step_roofline``: the least time the traced decode
      programs could take (the larger of their least bytes over the
      bandwidth and their operations over the peak rate; the traced
      steps' span records are averaged over the decode programs of the
      trace) over the device's busy time inside their module events, %.
    - ``prefill_roofline``: the same for each traced prefill program at
      the rows it ran (padding included).
    - ``paged_attention_roofline``: the live k/v bytes of all ``passes
      x layers`` pool pairs over the bandwidth, over the device's busy
      time under the scope ``paged_attention`` inside the decode
      programs, %: the count is of the live bytes, whatever implements
      the read.

    The three shares of a peak read nothing on a CPU or untraced."""
    if part == "kv_bytes_share":
        return _kv_bytes_share(ctx)
    found = _programs(ctx)
    if found is None:
        return None
    trace, prefills, decodes, steps = found
    cfg, peak = ctx.config, peaks(ctx.device["kind"])
    if part == "prefill_roofline":
        least = sum(
            fl.least_seconds(fl.prefill_bytes(cfg, s["rows"]),
                             fl.prefill_flops(cfg, s["rows"]), peak)
            for _, _, s in prefills
        )
        busy = hm.busy_seconds(trace, prefills)
    elif not steps:
        return None
    elif part == "decode_step_roofline":
        least = len(decodes) * fmean(
            fl.least_seconds(
                fl.decode_step_bytes(cfg, s["tokens_live"]),
                fl.decode_step_flops(cfg, s["busy"], s["tokens_live"]),
                peak,
            ) for s in steps
        )
        busy = hm.busy_seconds(trace, decodes)
    elif part == "paged_attention_roofline":
        least = len(decodes) * fmean(
            s["tokens_live"] for s in steps
        ) * fl.cache_bytes_per_position(cfg) / peak["hbm_bytes_per_s"]
        busy = hm.busy_seconds(trace, decodes, KERNEL_SCOPE)
    else:
        raise ValueError(f"unknown part {part!r}")
    return 100.0 * least / busy if busy > 0 else None
