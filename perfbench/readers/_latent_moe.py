"""Shared by the roofline readers of a decoder with latent attention
and routed experts: the decode steps of the traced window (the
program's ``tpudl.decode_step`` annotations joined to their span
records, which carry ``tokens_live``, ``busy``, ``moe_assignments`` and
``moe_experts_touched``), and the device's busy time inside them."""

from perfbench import trace as tr
from perfbench.readers import _program_trace as pt


def decode_steps(ctx):
    """``(trace, [(start_ns, end_ns, span record), ...])`` or None: not
    on a CPU (a share of a chip's peak is never reported from one), not
    untraced, and not where the program wrote no such spans or
    counters (a program from before it had them)."""
    if ctx.device["platform"] == "cpu":
        return None
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    by_id = {s["id"]: s for s in ctx.spans
             if s.get("kind") == "span" and s.get("name") == "decode_step"
             and "moe_experts_touched" in s and "tokens_live" in s}
    steps = [(a, b, by_id[i]) for a, b, i in
             pt.occurrences(trace, "decode_step") if i in by_id]
    return (trace, steps) if steps else None


def busy_seconds(trace, steps, program=None, scopes=None) -> float:
    """Device-busy seconds inside the steps: of every operation, or of
    those of ``jit_tpudl_<program>`` whose scope path has one of
    ``scopes`` as a component."""
    windows = sorted((a, b) for a, b, _ in steps)
    if program is None and scopes is None:
        return tr.busy_inside(pt.busy(trace), windows) / 1e9
    wanted = f"jit_tpudl_{program}"
    mine = tr.busy_intervals([
        [o[0], o[1], o[2]] for o in trace["ops"]
        if o[3] == wanted and any(pt.has_scope(o[4], s) for s in scopes)
    ])
    return tr.busy_inside(mine, windows) / 1e9
