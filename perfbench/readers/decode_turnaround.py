"""What a pure decode step's device waits for the host: the idle time
from the end of one decode program to the start of the next, whichever
span the wait falls in."""

from __future__ import annotations

import bisect
from typing import List

from perfbench.readers import _program_trace as pt
from perfbench.stats import percentile

DECODE = "jit_tpudl_decode"
#: Programs that make the step between two decodes another kind of
#: step: a prompt was prefilled or seated, a window verified.
_BREAKS = ("jit_tpudl_prefill", "jit_tpudl_chunk_prefill",
           "jit_tpudl_verify")
_SEAT = "jit_tpudl_seat"


def turnarounds(trace: dict) -> List[float]:
    """Idle ns between each two consecutive ``jit_tpudl_decode`` module
    events with no prefill, seat or verify program between them. A small
    program in between (the selection's) counts as busy."""
    merged = pt.busy(trace)
    starts = [s for s, _ in merged]
    ends = [e for _, e in merged]
    out, last_end = [], None
    for name, start, dur in trace["modules"]:
        if name == DECODE:
            if last_end is not None and start > last_end:
                # Only the busy intervals that reach into the pair's gap.
                between = merged[bisect.bisect_right(ends, last_end):
                                 bisect.bisect_left(starts, start)]
                out.append(pt.idle_inside(between, last_end, start))
            last_end = start + dur
        elif name in _BREAKS or name.startswith(_SEAT):
            last_end = None
    return out


def read(ctx, p):
    """Percentile ``p`` of ``turnarounds``, ms; nothing for an untraced
    run or a trace that holds no such pair."""
    trace = pt.of_run(ctx)
    if trace is None:
        return None
    gaps = turnarounds(trace)
    return 1e-6 * percentile(gaps, p) if gaps else None
