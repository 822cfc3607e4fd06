"""Shared by the readers of a decoder with a two-group page cache whose
rows differ by group (KV heads by layer kind, keys wider than values)
and routed experts: the traced window's prefill and decode PROGRAMS,
each with the span record that describes it, as ``readers/_hyper_moe.py``
finds them for its family (a program's time is its ``jit_tpudl_prefill``
/ ``jit_tpudl_decode`` module event, a prefill is joined to its span
through the annotation that holds the middle of its module event, the
decode steps' span records are averaged over the decode programs of the
trace), with the attributes this family reads. A span of a program from
before ``kv_bytes_live`` lacks it: the readers then find nothing and
return nothing.
"""

from perfbench.readers import _program_trace as pt
from perfbench.readers._hyper_moe import DECODE, PREFILL, busy_seconds  # noqa: F401

PREFILL_ATTRS = ("rows", "tokens", "moe_experts_touched", "moe_assignments")
DECODE_ATTRS = ("busy", "tokens_live", "tokens_live_window", "kv_bytes_live",
                "moe_experts_touched", "moe_assignments")


def _records(ctx, name: str, attrs: tuple) -> dict:
    return {s["id"]: s for s in ctx.spans
            if s.get("kind") == "span" and s.get("name") == name
            and all(a in s for a in attrs)}


def programs(ctx):
    """``(trace, prefills, decodes, steps)`` or None: ``prefills``
    ``[(start_ns, end_ns, span record)]``, every prefill program that
    lies whole inside the trace with the record of the span that ran
    it; ``decodes`` ``[(start_ns, end_ns)]``, every decode program;
    ``steps`` the ``decode_step`` records of the traced window. None on
    a CPU (a share of a chip's peak is never reported from one), for an
    untraced run, and where the program wrote no such spans or
    attributes."""
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
