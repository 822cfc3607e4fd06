"""Shared by the roofline readers of a decoder whose residual is a
stream of several vectors a token (hyper-connections) over latent
attention and routed experts: the traced window's prefill and decode
PROGRAMS, each with the span record that describes it, and the device's
busy time inside them.

A program's time is taken from its ``jit_tpudl_prefill`` /
``jit_tpudl_decode`` module event, not from the host's span window:
where a full engine runs a step ahead, a step is on the device under
the span of the call before (PERF.md section 6, PR 37), and a share of
a peak that divides by the window reads high. A prefill is joined to
its span through the ``tpudl.prefill`` annotation that holds the middle
of its module event (a prompt is prefilled and waited for inside its
span; the two clocks of a trace sit 1-2 ms apart, a prefill runs 30 ms
and more). A decode program is one of many alike: the steps' span
records are not joined one to one, their MEAN describes every decode
program of the trace.
"""

from perfbench import trace as tr
from perfbench.readers import _program_trace as pt

PREFILL, DECODE = "jit_tpudl_prefill", "jit_tpudl_decode"
PREFILL_ATTRS = ("rows", "moe_experts_touched", "hyper_res_offdiag")
DECODE_ATTRS = ("tokens_live", "busy", "moe_experts_touched",
                "moe_assignments", "hyper_res_offdiag")


def _records(ctx, name: str, attrs: tuple) -> dict:
    return {s["id"]: s for s in ctx.spans
            if s.get("kind") == "span" and s.get("name") == name
            and all(a in s for a in attrs)}


def programs(ctx):
    """``(trace, prefills, decodes, steps)`` or None. ``prefills``:
    ``[(start_ns, end_ns, span record)]``, a prefill program that lies
    whole inside the trace with the record of the span that ran it;
    ``decodes``: ``[(start_ns, end_ns)]``, every decode program;
    ``steps``: the ``decode_step`` records of the traced window. None
    on a CPU (a share of a chip's peak is never reported from one), for
    an untraced run, and where the program wrote no such spans or
    attributes (a program from before it had the stream)."""
    if ctx.device["platform"] == "cpu":
        return None
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    by_id = _records(ctx, "prefill", PREFILL_ATTRS)
    spans = [(a, b, by_id[i]) for a, b, i in pt.occurrences(trace, "prefill")
             if i in by_id]
    prefills = []
    for name, start, dur in trace["modules"]:
        if name != PREFILL:
            continue
        middle = start + dur / 2
        for a, b, record in spans:
            if a <= middle <= b:
                prefills.append((start, start + dur, record))
                break
    decodes = [(start, start + dur) for name, start, dur in trace["modules"]
               if name == DECODE]
    by_id = _records(ctx, "decode_step", DECODE_ATTRS)
    steps = [by_id[i] for _, _, i in pt.occurrences(trace, "decode_step")
             if i in by_id]
    if not (prefills or (decodes and steps)):
        return None
    return trace, prefills, decodes, steps


def busy_seconds(trace, windows, scope=None) -> float:
    """Device-busy seconds inside ``windows`` (module events): of every
    operation, or of those whose scope path has ``scope`` as a
    component."""
    windows = sorted((a, b) for a, b, *_ in windows)
    if scope is None:
        return tr.busy_inside(pt.busy(trace), windows) / 1e9
    mine = tr.busy_intervals([
        [o[0], o[1], o[2]] for o in trace["ops"] if pt.has_scope(o[4], scope)
    ])
    return tr.busy_inside(mine, windows) / 1e9
