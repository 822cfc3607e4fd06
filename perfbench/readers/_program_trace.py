"""Shared by the readers of the program's own timeline: the ``tpudl.*``
annotations the program writes into a profiler trace (one per span of
``tpudl.obs.spans``, joined to its record by ``span_id``) and the
device's operations with the program and the scope each belongs to.

The run's ``.xplane.pb`` is read once per process into a plain
structure (the same that ``perfbench/tests`` keeps as a small recorded
trace). Times are nanoseconds on the trace's own clock:

    {"annotations": [[name, start_ns, duration_ns, span_id], ...],
     "modules":     [[program, start_ns, duration_ns], ...],
     "ops":         [[name, start_ns, duration_ns, program, scope], ...]}

``program`` is the name of the ``XLA Modules`` event that covers the
operation (``jit_tpudl_decode``); ``scope`` is the operation's path of
named scopes (``jit(tpudl_decode)/.../attention/kv_gather/gather``): a
fusion carries the path of its root. A program that writes no
annotations and names no scopes (the parent of the PR that brought
these readers) gives empty lists, and the readers return nothing.

Where the scope is (TPU v5e, JAX 0.9.0, found on the chip in PR 24): the
``tf_op`` stat of the ``XLA Ops`` event's METADATA record
(``XEventMetadata.stats`` of the device plane, beside ``hlo_category``,
``program_id``, ``flops``, ``source``), as ``<path>:<type>``.
``jax.profiler.ProfileData`` shows an event's own stats only (offset and
duration), and the raw trace has no ``Framework Ops`` / ``Framework Name
Scope`` lines (TensorBoard derives those), so that one table is read
from the file's bytes by the small protobuf reader below; everything
else comes through ``ProfileData``.

    python3 -m perfbench.readers._program_trace <trace_dir> <out.json> [ops [skip]]

writes the structure, cut to ``ops`` operations after the first ``skip``
and what lies beside them, names shortened, as a recorded trace for the
tests.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import trace as tr

#: Prefix of the program's annotations (``tpudl.obs.spans``).
PREFIX = "tpudl."
#: Stats that may hold an operation's scope path, in the order they
#: are tried.
_SCOPE_STATS = ("tf_op", "op_name", "name")
_U64 = (1 << 64) - 1

_cache: Dict[str, dict] = {}


def program_of(module_event_name: str) -> str:
    """``jit_tpudl_decode(16817519888760548089)`` -> ``jit_tpudl_decode``."""
    return module_event_name.split("(", 1)[0]


def program_id_of(module_event_name: str) -> Optional[int]:
    """The program's fingerprint, the number in the brackets."""
    m = re.search(r"\((\d+)\)\s*$", module_event_name)
    return int(m.group(1)) if m else None


# -- the one table ProfileData does not show --------------------------------
#
# XSpace{1: XPlane*}; XPlane{2: name, 3: XLine*, 4: map<id, XEventMetadata>,
# 5: map<id, XStatMetadata>}; XEventMetadata{1: id, 2: name, 5: XStat*};
# XStatMetadata{1: id, 2: name}; XStat{1: metadata_id, 3: uint64, 4: int64,
# 5: str, 7: ref to a stat metadata's name}; a map entry is {1: key,
# 2: value}.


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int):
    """(field number, value) over one message: an int for a varint, an
    (start, end) pair for a length-delimited field; fixed-width fields
    are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, (i, i + size)
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an .xplane.pb")


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def scope_table(raw: bytes) -> Dict[Tuple[Optional[int], str], str]:
    """{(program id, operation's event name): scope path} from the
    device planes' event metadata. Two programs may hold operations of
    one name (``%copy.1 = ...``); the id is the number in the brackets
    of the ``XLA Modules`` event."""
    buf = memoryview(raw)
    table: Dict[Tuple[Optional[int], str], str] = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f == 4:
                metadata.append(v)
            elif f == 5:
                entry = dict(_fields(buf, *v))
                if 2 in entry:
                    md = dict(_fields(buf, *entry[2]))
                    stat_names[entry.get(1, md.get(1))] = (
                        _text(buf, md[2]) if 2 in md else ""
                    )
        if not name.startswith("/device:") or "CUSTOM" in name.upper():
            continue
        for span in metadata:
            entry = dict(_fields(buf, *span))
            if 2 not in entry:
                continue
            op_name, program, found = "", None, {}
            for f, v in _fields(buf, *entry[2]):
                if f == 2:
                    op_name = _text(buf, v)
                elif f == 5:
                    stat = dict(_fields(buf, *v))
                    key = stat_names.get(stat.get(1))
                    if key == "program_id":
                        program = stat.get(3, stat.get(4, 0)) & _U64
                    elif key in _SCOPE_STATS:
                        if 5 in stat:
                            found[key] = _text(buf, stat[5])
                        elif 7 in stat:
                            found[key] = stat_names.get(stat[7], "")
            for key in _SCOPE_STATS:
                if found.get(key):
                    # ``<path>:<type>``; the type is empty for JAX.
                    table[(program, op_name)] = found[key].rsplit(":", 1)[0]
                    break
    return table


def load(path: str) -> dict:
    """Read an ``.xplane.pb`` into the plain structure above."""
    import jax

    with open(path, "rb") as f:
        raw = f.read()
    scopes = scope_table(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    annotations, modules, ops = [], [], []
    device = None
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if not ev.name.startswith(PREFIX):
                        continue
                    span_id = None
                    for key, value in ev.stats:
                        if key == "span_id":
                            span_id = int(value)
                    annotations.append([
                        ev.name, float(ev.start_ns), float(ev.duration_ns),
                        span_id,
                    ])
        elif (device is None and plane.name.startswith("/device:")
              and "CUSTOM" not in plane.name.upper()):
            device = plane
    if device is not None:
        ids = []
        for line in device.lines:
            if line.name == "XLA Modules":
                found = sorted(
                    (float(ev.start_ns), float(ev.duration_ns), ev.name)
                    for ev in line.events
                )
                modules = [[program_of(n), s, d] for s, d, n in found]
                ids = [program_id_of(n) for _, _, n in found]
        starts = [m[1] for m in modules]
        for line in device.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                start = float(ev.start_ns)
                program, program_id = "", None
                i = bisect.bisect_right(starts, start) - 1
                if i >= 0 and start <= modules[i][1] + modules[i][2]:
                    program, program_id = modules[i][0], ids[i]
                ops.append([
                    ev.name, start, float(ev.duration_ns), program,
                    scopes.get((program_id, ev.name), ""),
                ])
    annotations.sort(key=lambda a: a[1])
    ops.sort(key=lambda o: o[1])
    return {"annotations": annotations, "modules": modules, "ops": ops}


def of_run(ctx) -> Optional[dict]:
    """The traced run's program trace, read once per process; None for
    an untraced run."""
    if ctx.tracer is None or not ctx.tracer.done:
        return None
    try:
        path = tr.find_xplane(ctx.tracer.out_dir)
    except FileNotFoundError:
        return None
    if path not in _cache:
        _cache[path] = load(path)
    return _cache[path]


def busy(trace: dict) -> List[Tuple[float, float]]:
    """Merged intervals in which an operation ran, as
    ``perfbench.trace.busy_intervals`` gives them; reckoned once per
    trace (several metrics of a run read it)."""
    if "_busy" not in trace:
        trace["_busy"] = tr.busy_intervals(trace["ops"])
    return trace["_busy"]


def occurrences(trace: dict, name: str) -> List[Tuple[float, float, int]]:
    """(start_ns, end_ns, span_id) of the annotations ``tpudl.<name>``
    that lie wholly inside the traced window: between the first
    operation's start and the last one's end."""
    merged = busy(trace)
    if not merged:
        return []
    lo, hi = merged[0][0], merged[-1][1]
    return [
        (a[1], a[1] + a[2], a[3]) for a in trace["annotations"]
        if a[0] == PREFIX + name and a[1] >= lo and a[1] + a[2] <= hi
    ]


def idle_inside(merged: Sequence[Tuple[float, float]], start: float,
                end: float) -> float:
    """Nanoseconds of [start, end) in which no operation ran."""
    return (end - start) - tr.busy_inside(merged, [(start, end)])


_COMPONENT = re.compile(r"[/()]")


def has_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is a component of the path, forward
    (``.../attention/dropout/...``) or under a transformation
    (``transpose(jvp(dropout))``)."""
    return scope in _COMPONENT.split(path)


def cut(trace: dict, ops: int, skip: int = 0, name_chars: int = 64) -> dict:
    """``ops`` operations after the first ``skip``, with the modules and
    annotations that overlap the time they span; operation names cut to
    ``name_chars`` characters (on the chip a name is the whole HLO
    line)."""
    kept = [[o[0][:name_chars]] + o[1:] for o in trace["ops"][skip:skip + ops]]
    if not kept:
        return {"annotations": [], "modules": [], "ops": []}
    start = kept[0][1]
    end = max(o[1] + o[2] for o in kept)
    return {
        "annotations": [a for a in trace["annotations"]
                        if a[1] <= end and a[1] + a[2] >= start],
        "modules": [m for m in trace["modules"]
                    if m[1] <= end and m[1] + m[2] >= start],
        "ops": kept,
    }


def main(argv) -> int:
    limit = int(argv[3]) if len(argv) > 3 else 400
    skip = int(argv[4]) if len(argv) > 4 else 0
    path = argv[1]
    if not path.endswith(".pb"):
        path = tr.find_xplane(path)
    trace = cut(load(path), limit, skip)
    with open(argv[2], "w") as f:
        json.dump(trace, f, separators=(",", ":"))
    print({k: len(v) for k, v in trace.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
