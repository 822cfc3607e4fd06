"""The device's idle time, put down to what the host was doing.

Every span the serve engine records is also an annotation ``tpudl.<name>``
on the profiler's own clock, beside the device's operations, and the
annotations nest as the spans do (``tpudl.obs.spans``). Over the traced
window (first operation's start to last operation's end, as
``_program_trace.busy`` has it) each instant in which no operation ran
belongs to the INNERMOST named annotation open at that instant, or to
``outside`` where no ``tpudl.engine_step`` is open: whoever drives the
engine (here the load generator's loop). A gap between two operations is
SPLIT among the phases it overlaps: nothing is binned by a midpoint, and
no clock offset is used. An annotation that is not in ``NAMED`` (a
migration's, a router's) counts to the nearest named one around it. Over
``NAMED`` and ``outside`` the shares add up to the device's idle share of
the window.

Innermost by containment: sorted by start, the longer first, the younger
``span_id`` inside where two share both ends. The benchmark drives its
engine from ONE thread and the structure ``_program_trace.load`` gives
holds no thread, so none is needed here; annotations of several threads
that overlap without nesting would be read as if the later-begun were
inside.

    python3 -m perfbench.readers.device_idle_by_phase <trace_dir | .json>

prints every phase's share of such a trace and the decode turnaround.
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Tuple

from perfbench.readers import _program_trace as pt

STEP = "engine_step"
OUTSIDE = "outside"
#: The annotations a metric's ``phases`` may name.
NAMED = (
    STEP, "admit", "prefill", "prefill.dispatch", "prefill.readback",
    "seat", "decode_prepare", "decode_step", "decode.dispatch",
    "decode.address", "decode.readback", "emit",
)
#: Every engine step opens one. A trace without it is of a program whose
#: spans still had holes (admission and the step's host arrays were
#: ``engine_step``'s unnamed time): its shares would carry these names
#: and mean something else, so it reads nothing.
_COMPLETE = pt.PREFIX + "admit"


def phase_segments(annotations, lo: float, hi: float
                   ) -> List[Tuple[float, float, str]]:
    """``(start_ns, end_ns, phase)`` tiling ``[lo, hi)``."""
    items = sorted(
        (a[1], -(a[1] + a[2]), a[3] or 0, a[0][len(pt.PREFIX):])
        for a in annotations if a[1] < hi and a[1] + a[2] > lo
    )
    segments: List[Tuple[float, float, str]] = []
    open_: List[Tuple[float, str]] = []  # (end, name), outermost first
    at = lo

    def close(upto: float) -> None:
        nonlocal at
        upto = min(upto, hi)
        if upto <= at:
            return
        names = [n for _, n in open_]
        phase = OUTSIDE
        if STEP in names:
            phase = next(n for n in reversed(names) if n in NAMED)
        segments.append((at, upto, phase))
        at = upto

    for start, neg_end, _, name in items:
        while open_ and open_[-1][0] <= start:
            close(open_[-1][0])
            open_.pop()
        close(start)
        open_.append((-neg_end, name))
    while open_:
        close(open_[-1][0])
        open_.pop()
    close(hi)
    return segments


def idle_by_phase(trace: dict) -> Optional[Dict[str, float]]:
    """``{phase: idle ns}`` over ``NAMED`` and ``outside``, and the
    window's length under ``"window"``; reckoned once per trace. None
    where no operation ran or the program lacks the annotations."""
    if "_idle_by_phase" in trace:
        return trace["_idle_by_phase"]
    merged = pt.busy(trace)
    out = None
    if merged and any(a[0] == _COMPLETE for a in trace["annotations"]):
        lo, hi = merged[0][0], merged[-1][1]
        segments = phase_segments(trace["annotations"], lo, hi)
        out = {phase: 0.0 for phase in (*NAMED, OUTSIDE)}
        j = 0
        for (_, gap_start), (gap_end, _) in zip(merged, merged[1:]):
            while segments[j][1] <= gap_start:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < gap_end:
                start, end, phase = segments[k]
                out[phase] += min(end, gap_end) - max(start, gap_start)
                k += 1
        out["window"] = hi - lo
    trace["_idle_by_phase"] = out
    return out


def read(ctx, phases):
    """The device's idle time while the innermost annotation open was
    one of ``phases`` (names of ``NAMED``, or ``outside``), % of the
    traced window. 0.0 for a phase that held no idle time; nothing for
    an untraced run or a program without these annotations."""
    trace = pt.of_run(ctx)
    if trace is None:
        return None
    idle = idle_by_phase(trace)
    if idle is None or idle["window"] <= 0:
        return None
    return 100.0 * sum(idle[p] for p in phases) / idle["window"]


def main(argv) -> int:
    from perfbench import trace as tr
    from perfbench.readers import decode_turnaround
    from perfbench.stats import percentile

    path = argv[1]
    if path.endswith(".json"):
        with open(path) as f:
            trace = json.load(f)
    else:
        trace = pt.load(path if path.endswith(".pb") else tr.find_xplane(path))
    idle = idle_by_phase(trace)
    if idle is None:
        print("no operations, or a program without the annotations")
        return 1
    window = idle.pop("window")
    shares = {p: round(100.0 * ns / window, 4) for p, ns in idle.items()}
    pairs = decode_turnaround.turnarounds(trace)
    print(json.dumps({
        "window_s": window / 1e9,
        "idle_share": round(sum(shares.values()), 4),
        "shares": shares,
        "decode_pairs": len(pairs),
        "decode_turnaround_idle_ms_p50": (
            1e-6 * percentile(pairs, 50) if pairs else None
        ),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
