"""Shared by the readers of a decoder with a two-group page cache
(full-context layers and window layers) and routed experts: the spans
of the traced window joined to the program's own annotations
(``tpudl.decode_step`` / ``tpudl.prefill``), and the device's busy
time inside them. A span of a program from before the window group
lacks ``tokens_live_window``: the readers then find nothing and return
nothing."""

from perfbench.readers import _program_trace as pt
from perfbench.readers._latent_moe import busy_seconds  # noqa: F401

DECODE_ATTRS = ("tokens_live", "tokens_live_window", "moe_experts_touched",
                "moe_assignments")
PREFILL_ATTRS = ("moe_experts_touched", "moe_assignments")


def traced_spans(ctx, name: str, attrs: tuple):
    """``(trace, [(start_ns, end_ns, span record), ...])`` or None: not
    on a CPU (a share of a chip's peak is never reported from one), not
    untraced, and not where the program wrote no such spans or
    attributes."""
    if ctx.device["platform"] == "cpu":
        return None
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    by_id = {s["id"]: s for s in ctx.spans
             if s.get("kind") == "span" and s.get("name") == name
             and all(a in s for a in attrs)}
    found = [(a, b, by_id[i]) for a, b, i in pt.occurrences(trace, name)
             if i in by_id]
    return (trace, found) if found else None
