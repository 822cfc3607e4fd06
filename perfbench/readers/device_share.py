from perfbench import trace as tr
from perfbench.readers import _program_trace as pt


def read(ctx, program=None, scope=None):
    """Device-busy time of the operations whose program is
    ``jit_tpudl_<program>`` and/or whose scope path has the component
    ``scope`` (forward, or under ``transpose(jvp(..))``), over all
    device-busy time in the trace, %. A fusion counts where its root
    counts. Nothing where the trace names no such program or scope at
    all (a program from before they had names)."""
    trace = pt.of_run(ctx)
    if trace is None or not trace["ops"]:
        return None
    wanted = f"jit_tpudl_{program}" if program is not None else None
    if wanted is not None and not any(
        m[0] == wanted for m in trace["modules"]
    ):
        return None
    if scope is not None and not any(
        pt.has_scope(o[4], scope) for o in trace["ops"]
    ):
        return None
    mine = [
        [o[0], o[1], o[2]] for o in trace["ops"]
        if (wanted is None or o[3] == wanted)
        and (scope is None or pt.has_scope(o[4], scope))
    ]
    total = sum(e - s for s, e in pt.busy(trace))
    if total <= 0:
        return None
    return 100.0 * sum(e - s for s, e in tr.busy_intervals(mine)) / total
