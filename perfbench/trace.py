"""Reduction of a profiler trace to the numbers the benchmark reports.

A trace is read once into a plain structure (``load``; the same
structure is what ``perfbench/tests`` keeps as a small recorded trace):

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

Times are nanoseconds since the trace began. Host spans arrive on the
host's monotonic clock; ``ClockSync`` ties the two clocks together
through annotations the harness writes into the trace itself.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple


#: Prefix of the annotations the harness writes into a trace.
HOST_PREFIX = "perfbench."
SYNC_NAME = HOST_PREFIX + "sync"
#: Lines of a device plane that do not hold single operations.
_NOT_OPS = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
            "Framework Name Scope", "Source code")

def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not hits:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str, max_events_per_line: Optional[int] = None,
         host_prefix: Optional[str] = None) -> dict:
    """Read an ``.xplane.pb`` into the plain structure above. With
    ``host_prefix``, host planes keep only the events so named (the
    harness's own annotations: the runtime's are many and unread)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        keep = host_prefix if plane.name.startswith("/host") else None
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                if keep and not ev.name.startswith(keep):
                    continue
                events.append(
                    [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                )
                if max_events_per_line and len(events) >= max_events_per_line:
                    break
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


class ClockSync:
    """Ties the host's monotonic clock to the trace's clock: each
    ``mark()`` notes the monotonic time and writes an annotation into
    the trace; the k-th annotation found there is the k-th mark."""

    def __init__(self):
        self.marks_s: List[float] = []

    def mark(self) -> None:
        import jax

        self.marks_s.append(time.monotonic())
        with jax.profiler.TraceAnnotation(SYNC_NAME):
            pass


def clock_offset_ns(trace: dict, marks_s: Sequence[float]) -> Optional[float]:
    """``trace_ns = monotonic_s * 1e9 + offset``; None without marks."""
    found = sorted(
        ev[1]
        for plane in trace["planes"] if plane["name"].startswith("/host")
        for line in plane["lines"]
        for ev in line["events"] if ev[0] == SYNC_NAME
    )
    n = min(len(found), len(marks_s))
    if n == 0:
        return None
    # A mark before start_trace or after stop_trace leaves no
    # annotation; the harness marks only while tracing, so pair in order.
    diffs = sorted(found[i] - marks_s[i] * 1e9 for i in range(n))
    return diffs[n // 2]


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and "CUSTOM" not in p["name"].upper()]


def op_events(plane: dict) -> List[list]:
    """The plane's single-operation events (the ``XLA Ops`` line)."""
    named = [l for l in plane["lines"] if l["name"] == "XLA Ops"]
    if named:
        return named[0]["events"]
    rest = [l for l in plane["lines"] if l["name"] not in _NOT_OPS]
    if not rest:
        return []
    return max(rest, key=lambda l: len(l["events"]))["events"]


_HLO = re.compile(r"^%?([A-Za-z_\-]+?)[.\d]*\s*=\s*\(?(\w+)\[([\d,]*)\]")


def stable_name(name: str) -> str:
    """A name that survives a renumbering of the program: the op (with
    the fusion's kind), and the type and shape of its result, e.g.
    ``fusion:kCustom_bf16_49152_8_128_``. The TPU's profiler names an
    operation by its whole HLO line (``%fusion.21 = bf16[49152,8,128]{..}
    fusion(..), kind=kCustom, ..``); other names pass with their number
    cut."""
    hlo = _HLO.match(name)
    if not hlo:
        return re.sub(r"[.\d]+$", "", name)[:80] or name[:80]
    base, dtype, dims = hlo.groups()
    kind = re.search(r"kind=(k\w+)", name)
    if kind:
        base = f"{base}:{kind.group(1)}"
    return f"{base}_{dtype}_" + "".join(f"{d}_" for d in dims.split(",") if d)


def busy_intervals(events: Sequence[list]) -> List[Tuple[float, float]]:
    """Merged (start_ns, end_ns) intervals in which an operation ran."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted((ev[1], ev[1] + ev[2]) for ev in events if ev[2] > 0):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_inside(merged: Sequence[Tuple[float, float]],
                windows: Sequence[Tuple[float, float]]) -> float:
    """Nanoseconds of ``merged`` busy time that fall inside ``windows``
    (both sorted, windows disjoint)."""
    total, j = 0.0, 0
    for ws, we in windows:
        while j < len(merged) and merged[j][1] <= ws:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < we:
            total += min(merged[k][1], we) - max(merged[k][0], ws)
            k += 1
    return total


def reduce(trace: dict, host_spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> dict:
    """Busy seconds averaged over the device planes, the traced window's
    length, the operations that took most time and the longest idle
    gaps by what the host was doing.

    ``host_spans``: (name, start_ns, end_ns) on the TRACE's clock,
    innermost spans first in priority: a gap goes to the first listed
    span kind that covers its middle."""
    planes = device_planes(trace)
    per_plane = [busy_intervals(op_events(p)) for p in planes]
    per_plane = [m for m in per_plane if m]
    if not per_plane:
        return {"busy_s": 0.0, "window_s": 0.0, "device_ops": [],
                "idle_gaps": [], "devices": 0}
    start = min(m[0][0] for m in per_plane)
    end = max(m[-1][1] for m in per_plane)
    busy = [sum(e - s for s, e in m) for m in per_plane]
    ops: Dict[str, float] = {}
    for ev in op_events(planes[0]):
        key = stable_name(ev[0])
        ops[key] = ops.get(key, 0.0) + ev[2]
    starts = [s for _, s, _ in host_spans]
    order = sorted(range(len(host_spans)), key=lambda i: starts[i])
    sorted_spans = [host_spans[i] for i in order]
    sorted_starts = [s for _, s, _ in sorted_spans]
    gaps: Dict[str, float] = {}
    first = per_plane[0]
    for (_, e0), (s1, _) in zip(first, first[1:]):
        mid = 0.5 * (e0 + s1)
        name = "outside_spans"
        i = bisect.bisect_right(sorted_starts, mid) - 1
        # Spans may nest (a harness span around the program's own):
        # walk back to the innermost, latest-started span covering mid.
        while i >= 0:
            sname, ss, se = sorted_spans[i]
            if ss <= mid <= se:
                name = sname
                break
            if mid - ss > 5e9:
                break
            i -= 1
        gaps[name] = gaps.get(name, 0.0) + (s1 - e0)
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (end - start) / 1e9,
        "start_ns": start,
        "end_ns": end,
        "devices": len(per_plane),
        "device_ops": [
            [k, v / 1e9] for k, v in
            sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [
            [k, v / 1e9] for k, v in
            sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
        ],
    }


def spans_on_trace_clock(records: Sequence[dict], offset_ns: float,
                         names: Sequence[str]) -> List[Tuple[str, float, float]]:
    """The program's span records (``ts``/``dur`` in monotonic seconds)
    of the given names, as (name, start_ns, end_ns) on the trace's
    clock."""
    out = []
    for r in records:
        if r.get("kind") == "span" and r.get("name") in names:
            s = r["ts"] * 1e9 + offset_ns
            out.append((r["name"], s, s + r["dur"] * 1e9))
    return out
