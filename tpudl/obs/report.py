"""Aggregate span/counter streams into the run report: step-time
breakdown, goodput, outliers, and per-host straggler attribution.

    python -m tpudl.obs.report /path/to/obs-dir        # or *.jsonl files
    python -m tpudl.obs.report run.jsonl --json
    python -m tpudl.obs.report run.jsonl --chrome-trace trace.json
    python -m tpudl.obs.report serve-run.jsonl --request r17

This is the "why was this run only 71% productive, and which host was
slow" answer as an artifact, not a vibe: it loads one or many span JSONL
files (a distributor run merges its workers' files into the parent's —
see tpudl.runtime.distributor — but loose per-worker files work too
since every record carries host/process tags), then prints

- a per-category latency table (count, total, mean, p50/p95/p99) over
  data_wait / step / compile / checkpoint spans;
- the goodput classification (tpudl.obs.goodput);
- outlier steps (duration > ``outlier_factor`` x the p50 step time),
  each attributed to its host/process;
- per-host step-time means with stragglers flagged (mean above
  ``straggler_factor`` x the cross-host median);
- a served-request outcome breakdown (completed vs each shed reason,
  with queue-wait/TTFT means per reason), when a serve run's
  ``request_complete`` events rode the stream;
- the serve engine's phases as the tree its spans make (``engine_step``
  > ``admit`` > ``prefill.dispatch``, ``seat``; ``decode_prepare``;
  ``decode_step`` > ``decode.dispatch`` > ``decode.address``,
  ``decode.readback``; ``emit``; ``prefill`` > ``prefill.readback``),
  each with its total and its SELF time (its duration less its
  children's), and the queue depth ``admit`` left behind;
- a start-up table, when the stream holds the ``startup.*`` phases and
  ``program.*`` records every process makes of how it began
  (``tpudl.obs.spans.startup_recorder``): the phases by self time, the
  programs by their trace / lower / compile-or-load seconds with the
  compile cache's hits, the Pallas kernels by trace seconds;
- the last counters snapshot per process, if any rode the stream.

``--request <id>`` switches to per-request trace mode: the serve
path's distributed trace (``request_id`` propagated from admission
through prefill, every decode chunk, and completion) is stitched into
one timeline for that request, and its TTFT is decomposed into
queue-wait / prefill / first-decode-chunk, with the total checked
against the measured TTFT + generation time.

``--chrome-trace`` additionally re-exports the loaded records as
Chrome trace-event JSON for Perfetto: the host spans alone, on each
process's monotonic clock (the one timeline with the device's
operations is the profiler's trace, which holds every span as a
``tpudl.*`` annotation — ``tpudl.obs.spans``)."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, Iterable, List, Optional

from tpudl.obs import goodput as goodput_mod
from tpudl.obs.counters import percentile
from tpudl.obs.spans import (
    CAT_CHECKPOINT,
    CAT_CKPT_BG,
    CAT_COMPILE,
    CAT_DATA_WAIT,
    CAT_EVAL,
    CAT_METRIC_WAIT,
    CAT_RECOVERY,
    CAT_STARTUP,
    CAT_STEP,
    chrome_trace_events,
    read_jsonl,
    self_seconds,
    without_same_category_children,
)

#: Table row order: start-up, then the lifecycle order of one step; the
#: overlapped background-write row and recovery last (present only when
#: nonzero).
_TABLE_CATS = (CAT_STARTUP, CAT_DATA_WAIT, CAT_STEP, CAT_EVAL, CAT_COMPILE,
               CAT_METRIC_WAIT, CAT_CHECKPOINT, CAT_CKPT_BG, CAT_RECOVERY)
#: The stages JAX times of every program it builds, as
#: tpudl.analysis.dispatch records them (``program.<stage>``).
_PROGRAM_STAGES = ("trace", "lower", "compile")


def load_records(paths: Iterable[str]) -> List[dict]:
    """Load span records from JSONL files and/or directories (directories
    glob ``*.jsonl``, recursively — a distributor obs dir with a
    ``workers/`` subdir loads in one argument)."""
    from tpudl.obs.requestlog import _parse_segment_name

    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            hits = sorted(
                glob.glob(os.path.join(p, "**", "*.jsonl"), recursive=True)
            )
            # Durable request-log segments (requests-*.jsonl) are a
            # different artifact with a different schema: a run dir
            # that nests its requestlog under the obs dir must not
            # leak usage records into the span report.
            hits = [
                h for h in hits
                if _parse_segment_name(os.path.basename(h)) is None
            ]
            if not hits:
                raise FileNotFoundError(f"no *.jsonl files under {p}")
            files.extend(hits)
        else:
            files.append(p)
    records: List[dict] = []
    for f in files:
        records.extend(read_jsonl(f))
    return records


#: The serve engine's spans, a step's order, with their depth in the
#: tree (tpudl.serve.engine) as a call that seats behind a decode step
#: in flight and then dispatches has it; such a call's ``decode_step``
#: is open from before ``admit``, and a ``prefill`` (the wait for a
#: first token) that precedes the landed step lies inside it.
_SERVE_TREE = (
    ("engine_step", 0), ("admit", 1), ("prefill.dispatch", 2), ("seat", 2),
    ("decode_prepare", 1), ("decode_step", 1), ("decode.dispatch", 2),
    ("decode.address", 3), ("decode.readback", 2), ("emit", 1),
    ("prefill", 1), ("prefill.readback", 2),
)


def _dist(durs: List[float]) -> dict:
    vals = sorted(durs)
    return {
        "count": len(vals),
        "total_s": sum(vals),
        "mean_ms": 1e3 * sum(vals) / len(vals) if vals else 0.0,
        "p50_ms": 1e3 * percentile(vals, 0.50) if vals else 0.0,
        "p95_ms": 1e3 * percentile(vals, 0.95) if vals else 0.0,
        "p99_ms": 1e3 * percentile(vals, 0.99) if vals else 0.0,
    }


def build_report(
    records: List[dict],
    outlier_factor: float = 3.0,
    straggler_factor: float = 1.2,
) -> dict:
    """Span records -> report dict (see module docstring for contents)."""
    # A phase split into children of its own category (decode_step into
    # its dispatch and read-back) is one row entry, not three.
    spans = without_same_category_children(
        r for r in records if r.get("kind") == "span"
    )
    by_cat: Dict[str, List[float]] = {}
    for s in spans:
        by_cat.setdefault(s.get("cat", "other"), []).append(float(s["dur"]))
    breakdown = {
        cat: _dist(by_cat[cat]) for cat in _TABLE_CATS if cat in by_cat
    }
    for cat in sorted(set(by_cat) - set(_TABLE_CATS)):
        breakdown[cat] = _dist(by_cat[cat])

    # Outlier steps: anything beyond outlier_factor x the p50 TRAIN-step
    # time (eval steps have their own duration scale and stay out of
    # these statistics), attributed to host/process so cross-host blips
    # are visible. Fused dispatch_window spans cover K steps each (the
    # "window" attr), so their duration normalizes to per-step time
    # before comparison — a K=8 window is not an 8x outlier.
    step_spans = [s for s in spans if s.get("cat") == CAT_STEP]

    def _per_step_dur(s) -> float:
        return float(s["dur"]) / int(s.get("window", 1) or 1)

    outliers: List[dict] = []
    p50 = (
        percentile(sorted(_per_step_dur(s) for s in step_spans), 0.50)
        if step_spans else 0.0
    )
    if p50 > 0:
        for s in step_spans:
            dur = _per_step_dur(s)
            if dur > outlier_factor * p50:
                outliers.append({
                    "host": s.get("host", "?"),
                    "process": s.get("process", 0),
                    "step": s.get("step"),
                    "ms": 1e3 * dur,
                    "x_p50": dur / p50,
                })
        outliers.sort(key=lambda o: -o["ms"])

    # Per-host/process straggler attribution over per-step means
    # (grouped by recording process incl. OS pid — see
    # goodput.process_key).
    per_host_keyed: Dict[tuple, List[float]] = {}
    for s in step_spans:
        per_host_keyed.setdefault(
            goodput_mod.process_key(s), []
        ).append(_per_step_dur(s))
    labels = goodput_mod.process_labels(per_host_keyed)
    per_host = {
        labels[k]: per_host_keyed[k]
        for k in sorted(per_host_keyed, key=lambda k: labels[k])
    }
    host_rows = {key: _dist(durs) for key, durs in per_host.items()}
    means = sorted(r["mean_ms"] for r in host_rows.values())
    median_mean = percentile(means, 0.50) if means else 0.0
    for key, row in host_rows.items():
        ratio = row["mean_ms"] / median_mean if median_mean > 0 else 0.0
        row["x_median"] = ratio
        row["straggler"] = bool(
            len(host_rows) > 1 and ratio > straggler_factor
        )

    # Last counters snapshot per recording process, if any rode the
    # stream.
    counters_keyed: Dict[tuple, dict] = {}
    for r in records:
        if r.get("kind") == "counters":
            counters_keyed[goodput_mod.process_key(r)] = r.get("data", {})
    clabels = goodput_mod.process_labels(counters_keyed)
    counters = {
        clabels[k]: counters_keyed[k]
        for k in sorted(counters_keyed, key=lambda k: clabels[k])
    }

    return {
        "num_records": len(records),
        "num_span_records": len(spans),
        "breakdown": breakdown,
        "goodput": goodput_mod.classify_by_process(records),
        "outlier_steps": outliers,
        "outlier_factor": outlier_factor,
        "per_host": host_rows,
        "straggler_factor": straggler_factor,
        "serve_requests": serve_request_breakdown(records),
        "serve_phases": serve_phase_breakdown(records),
        "startup": startup_breakdown(records),
        "counters": counters,
    }


#: The names of the timeline a process records of how it began.
_STARTUP_NAMES = ("startup.", "program.", "kernel.")
#: What every span record holds; the rest are a site's own attributes.
_SPAN_KEYS = frozenset((
    "kind", "name", "cat", "ts", "dur", "id", "parent", "host", "process",
    "pid", "tid",
))


def _covered_seconds(spans: Iterable[dict], lo: float, hi: float) -> float:
    """Seconds of ``[lo, hi]`` that the spans' intervals cover, each
    second once."""
    covered, end = 0.0, lo
    for ts, dur in sorted((float(s["ts"]), float(s["dur"])) for s in spans):
        start, stop = max(ts, end), min(ts + dur, hi)
        if stop > start:
            covered += stop - start
            end = stop
    return covered


def startup_breakdown(records: Iterable[dict]) -> dict:
    """How the process began, from the records its start-up sites make
    recorder or not (tpudl.obs.spans): ``phases`` (one row a
    ``startup.*`` record, in the order they began: ``at_s`` since the
    first of its process, total and SELF seconds, and the site's own
    attributes: a pool's bytes and pages, a dry run's rows and kernel
    layers), ``programs`` (``program.trace`` / ``.lower`` / ``.compile``
    by program: seconds a stage, executables built, how many the
    compile cache held and the seconds it took to read them, largest
    total first) and ``kernels`` (``kernel.trace`` by kernel). A
    phase's self time is its duration less what the start-up records of
    its thread cover INSIDE it (a phase recorded after the fact, a
    session's first requests, has no children by id). Empty where the
    stream holds none."""
    spans = [r for r in records if r.get("kind") == "span"
             and str(r.get("name", "")).startswith(_STARTUP_NAMES)]
    began: Dict[tuple, float] = {}
    for s in spans:
        key = goodput_mod.process_key(s)
        began[key] = min(began.get(key, float(s["ts"])), float(s["ts"]))
    phases: List[dict] = []
    kernels: Dict[str, dict] = {}
    programs: Dict[str, dict] = {}
    for s in sorted(spans, key=lambda s: (str(goodput_mod.process_key(s)), s["ts"])):
        name, ts, dur = s["name"], float(s["ts"]), float(s["dur"])
        if name.startswith("startup."):
            inside = [
                o for o in spans
                if o is not s and o.get("tid") == s.get("tid")
                and goodput_mod.process_key(o) == goodput_mod.process_key(s)
                and float(o["ts"]) >= ts
                and float(o["ts"]) + float(o["dur"]) <= ts + dur
            ]
            phases.append({
                "name": name,
                "at_s": ts - began[goodput_mod.process_key(s)],
                "total_s": dur,
                "self_s": dur - _covered_seconds(inside, ts, ts + dur),
                "attrs": {k: v for k, v in s.items() if k not in _SPAN_KEYS},
            })
        elif name == "kernel.trace":
            row = kernels.setdefault(
                str(s.get("kernel")), {"count": 0, "trace_s": 0.0}
            )
            row["count"] += 1
            row["trace_s"] += dur
        else:
            stage = name[len("program."):]
            if stage not in _PROGRAM_STAGES:
                continue
            row = programs.setdefault(str(s.get("program")), {
                **{f"{st}_s": 0.0 for st in _PROGRAM_STAGES},
                "built": 0, "cache_hits": 0, "cache_read_s": 0.0,
            })
            row[f"{stage}_s"] += dur
            if stage == "compile":
                row["built"] += 1
                row["cache_hits"] += int(s.get("cache_hit", 0))
                row["cache_read_s"] += float(s.get("cache_read_s", 0.0))
    if not (phases or kernels or programs):
        return {}

    def by(rows: dict, seconds) -> dict:
        return dict(sorted(rows.items(), key=lambda kv: -seconds(kv[1])))

    return {
        "phases": phases,
        "programs": by(programs, lambda r: sum(
            r[f"{st}_s"] for st in _PROGRAM_STAGES
        )),
        "kernels": by(kernels, lambda r: r["trace_s"]),
    }


def serve_phase_breakdown(records: Iterable[dict]) -> dict:
    """The serve engine's phases by span name, in the tree's order:
    count, total seconds, SELF seconds (a span's duration less its
    direct children's, so the rows' self times add up to the steps'
    total) and mean ms. ``admit``'s row also says the queue depth it
    left behind (mean and most): its self time is what admission costs
    the host a step (SLO checks, slot scans, the queue's pop, gauges),
    and a depth that grows while that stays flat is load the engine
    cannot seat, not a slow admission. Empty without serve spans."""
    names = {name for name, _ in _SERVE_TREE}
    rows: Dict[str, dict] = {}
    depths: List[float] = []
    for s, own in self_seconds(
        r for r in records if r.get("kind") == "span"
    ):
        if s["name"] not in names or not str(s.get("cat", "")).startswith(
            "serve_"
        ):
            continue
        row = rows.setdefault(
            s["name"], {"count": 0, "total_s": 0.0, "self_s": 0.0}
        )
        row["count"] += 1
        row["total_s"] += float(s["dur"])
        row["self_s"] += own
        if s["name"] == "admit" and "queue_depth" in s:
            depths.append(float(s["queue_depth"]))
    out = {}
    for name, depth in _SERVE_TREE:
        if name in rows:
            row = rows[name]
            row["depth"] = depth
            row["mean_ms"] = 1e3 * row["total_s"] / row["count"]
            out[name] = row
    if depths and "admit" in out:
        out["admit"]["queue_depth_mean"] = sum(depths) / len(depths)
        out["admit"]["queue_depth_max"] = max(depths)
    return out


def serve_request_breakdown(records: Iterable[dict]) -> dict:
    """Aggregate serve ``request_complete`` events by outcome: one row
    per finish_reason (completed-by-eos/length vs each shed reason)
    with count and queue-wait/TTFT means — the cross-request view of
    what admission did under load. Empty dict when the stream carries
    no serve traffic."""
    by_reason: Dict[str, List[dict]] = {}
    for r in records:
        if r.get("kind") == "event" and r.get("name") == "request_complete":
            by_reason.setdefault(
                r.get("finish_reason", "?"), []
            ).append(r)
    out: dict = {}
    for reason in sorted(by_reason):
        evs = by_reason[reason]
        waits = [
            float(e["queue_wait_s"]) for e in evs
            if e.get("queue_wait_s") is not None
        ]
        ttfts = [
            float(e["ttft_s"]) for e in evs if e.get("ttft_s") is not None
        ]
        out[reason] = {
            "count": len(evs),
            "mean_queue_wait_ms": (
                1e3 * sum(waits) / len(waits) if waits else None
            ),
            "mean_ttft_ms": 1e3 * sum(ttfts) / len(ttfts) if ttfts else None,
            "tokens": sum(int(e.get("num_tokens", 0) or 0) for e in evs),
        }
    return out


# ---------------------------------------------------------------------------
# Per-request trace mode (--request)
# ---------------------------------------------------------------------------


#: Logical hop order of one served request's life — the sort key the
#: stitched timeline uses FIRST, before timestamps: records from
#: different processes carry unrelated monotonic clocks, so cross-
#: stream ordering must come from the protocol, not the numbers.
_HOP_RANK = {
    "routed": 0, "failover": 1, "replica_dequeue": 2, "queued": 3,
    "prefill": 4, "decode_chunk": 5, "served": 6, "complete": 7,
}


def build_request_timeline(records: Iterable[dict], request_id) -> dict:
    """Stitch one request's distributed trace out of a serve run's
    records — possibly MERGED from several processes' span streams (the
    fleet case: router door events in the router's stream, admission /
    prefill / decode spans in each replica's): the router-door
    ``request_routed`` event, the replica-inbox ``replica_dequeue``
    hop, the admission event, the prefill span carrying its
    ``request_id``, every decode chunk whose ``rids`` include it, and
    the completion event — plus the TTFT/generation decomposition
    (queue-wait / prefill / first-decode-chunk / decode total) checked
    against the completion event's measured aggregates, and the
    router-level decomposition (inbox wait + queue wait + prefill vs
    the router-measured TTFT — all DURATIONS, so the sums survive
    cross-process clock skew; timestamps are never compared across
    streams).

    A hop named in a router event whose records are absent (that
    process's span stream not on disk) lands in ``warnings`` as a
    "partial trace" — the merged directory is incomplete, not the
    request unobserved.

    IDs are matched by string form too: a CLI ``--request 17`` finds an
    integer request_id 17."""
    rid = request_id

    def _match(v) -> bool:
        return v == rid or str(v) == str(rid)

    routed = None
    dequeues: List[dict] = []
    served_events: List[dict] = []
    failovers: List[dict] = []
    queued = None
    prefills: List[dict] = []
    decode_chunks: List[dict] = []
    complete = None
    for r in records:
        kind = r.get("kind")
        if kind == "event" and _match(r.get("request_id")):
            name = r.get("name")
            if name == "request_queued":
                queued = r
            elif name == "request_complete":
                complete = r
            elif name == "request_routed":
                routed = r
            elif name == "replica_dequeue":
                dequeues.append(r)
            elif name == "request_served":
                served_events.append(r)
            elif name == "request_failover":
                failovers.append(r)
        elif kind == "span":
            # Only the two legs themselves: a `seat` span names its
            # request too, and lies inside the same engine step.
            if r.get("name") == "prefill" and _match(r.get("request_id")):
                prefills.append(r)
            elif r.get("name") == "decode_step" and any(
                _match(x) for x in (r.get("rids") or ())
            ):
                decode_chunks.append(r)
    if (
        queued is None and not prefills and complete is None
        and routed is None and not dequeues and not served_events
    ):
        raise KeyError(
            f"no trace records carry request_id {request_id!r} — was the "
            f"serve run recorded with TPUDL_OBS_DIR set?"
        )

    # A failed-over request leaves records from BOTH attempts; the
    # completing process's are authoritative (the restarted copy). Key
    # by recording process and prefer its records when the streams
    # disagree — within one stream, "latest wins" is safe (same clock).
    proc_key = (
        goodput_mod.process_key(complete) if complete is not None else None
    )

    def _prefer_proc(cands: List[dict]) -> Optional[dict]:
        if not cands:
            return None
        if proc_key is not None:
            same = [
                c for c in cands
                if goodput_mod.process_key(c) == proc_key
            ]
            if same:
                return max(same, key=lambda s: float(s["ts"]))
        return max(cands, key=lambda s: float(s["ts"]))

    prefill = _prefer_proc(prefills)
    if proc_key is not None:
        same_chunks = [
            c for c in decode_chunks
            if goodput_mod.process_key(c) == proc_key
        ]
        if same_chunks:
            decode_chunks = same_chunks
    decode_chunks.sort(key=lambda s: float(s["ts"]))
    dequeue = _prefer_proc(dequeues)
    served = _prefer_proc(served_events)

    warnings: List[str] = []
    # Any record beyond the router's own door event proves the routed
    # hop's stream made it into the merge — a replica_dequeue with no
    # engine records is a replica-side shed, not a missing stream.
    engine_side = bool(
        queued or prefill or decode_chunks or complete
        or dequeues or served_events
    )
    if routed is not None and not engine_side:
        if routed.get("replica"):
            kind, hop = "replica", routed["replica"]
        elif routed.get("worker"):
            kind, hop = "prefill worker", routed["worker"]
        else:
            kind, hop = "hop", "?"
        warnings.append(
            f"partial trace: request {request_id!r} was routed to "
            f"{kind} {hop!r} but no spans from that hop are on disk — "
            f"merge that process's span stream (TPUDL_OBS_DIR) into "
            f"this report"
        )
    if complete is None and (routed is not None or queued is not None):
        warnings.append(
            f"partial trace: no completion event for {request_id!r} — "
            f"the request is still in flight, or the completing "
            f"process's stream is missing"
        )

    timeline: List[dict] = []
    if routed is not None:
        timeline.append({
            "ts": float(routed["ts"]), "dur": 0.0, "what": "routed",
            "detail": {"replica": routed.get("replica"),
                       "worker": routed.get("worker"),
                       "priority": routed.get("priority")},
            "record": routed,
        })
    for f in failovers:
        timeline.append({
            "ts": float(f["ts"]), "dur": 0.0, "what": "failover",
            "detail": {"from_replica": f.get("from_replica")},
            "record": f,
        })
    if dequeue is not None:
        timeline.append({
            "ts": float(dequeue["ts"]),
            "dur": float(dequeue.get("inbox_wait_s") or 0.0),
            "what": "replica_dequeue",
            "detail": {"replica": dequeue.get("replica"),
                       "inbox_wait_s": dequeue.get("inbox_wait_s")},
            "record": dequeue,
        })
    if queued is not None:
        timeline.append({
            "ts": float(queued["ts"]), "dur": 0.0, "what": "queued",
            "detail": {"priority": queued.get("req_priority"),
                       "deadline_s": queued.get("deadline_s"),
                       "depth": queued.get("depth")},
            "record": queued,
        })
    prefill_s = None
    if prefill is not None:
        # The engine's own span is the wait for the first token alone
        # and says how long the request had been popped by its end
        # (``since_pop_s``: the dispatch and what the device held
        # before the prefill included); a prefill worker's span is the
        # whole of it.
        prefill_s = float(prefill.get("since_pop_s", prefill["dur"]))
        timeline.append({
            "ts": float(prefill["ts"]) + float(prefill["dur"]) - prefill_s,
            "dur": prefill_s,
            "what": "prefill",
            # prefix_hit_tokens: how much of the prompt the radix
            # prefix cache served for free — the TTFT attribution
            # (prefill dur covers only the unshared suffix when > 0).
            "detail": {"slot": prefill.get("slot"),
                       "worker": prefill.get("worker"),
                       "prefix_hit_tokens": prefill.get(
                           "prefix_hit_tokens")},
            "record": prefill,
        })
    def _spec_share(c: dict):
        """THIS request's (accepted, proposed, emitted) within one
        speculative window: the decode_step span batch-sums its
        numbers, but slot_accepted/slot_emitted align with rids, so a
        single request's trace reads its own column instead of
        claiming the whole batch's."""
        if c.get("proposed") is None:
            return None
        idx = next(
            (j for j, x in enumerate(c.get("rids") or ())
             if _match(x)), None,
        )
        slot_acc = c.get("slot_accepted")
        if idx is not None and slot_acc is not None:
            return (
                int(slot_acc[idx]),
                int(c.get("proposed_per_slot") or 0),
                int((c.get("slot_emitted") or [0] * (idx + 1))[idx]),
            )
        # Older streams without per-slot columns: batch totals are the
        # best available (overstates under multi-slot occupancy).
        return (
            int(c.get("accepted") or 0), int(c.get("proposed") or 0),
            int(c.get("emitted") or 0),
        )

    for i, c in enumerate(decode_chunks):
        detail = {"index": i, "busy": c.get("busy")}
        share = _spec_share(c)
        if share is not None:
            # Speculative windows: accepted/proposed per step shows
            # where TPOT went (a low ratio = the draft disagrees and
            # windows are mostly wasted draft dispatches).
            detail["accepted"], detail["proposed"], detail["emitted"] = (
                share
            )
        timeline.append({
            "ts": float(c["ts"]), "dur": float(c["dur"]),
            "what": "decode_chunk",
            "detail": detail,
            "record": c,
        })
    if served is not None:
        timeline.append({
            "ts": float(served["ts"]), "dur": 0.0, "what": "served",
            "detail": {"replica": served.get("replica"),
                       "router_ttft_s": served.get("router_ttft_s")},
            "record": served,
        })
    if complete is not None:
        timeline.append({
            "ts": float(complete["ts"]), "dur": 0.0, "what": "complete",
            "detail": {"finish_reason": complete.get("finish_reason"),
                       "num_tokens": complete.get("num_tokens")},
            "record": complete,
        })
    # Logical hop order first, timestamps only within it: records from
    # different processes carry unrelated monotonic clocks.
    timeline.sort(key=lambda e: (_HOP_RANK.get(e["what"], 99), e["ts"]))
    # Tag each entry with its recording process (rendered when the
    # stitched trace spans more than one stream) and drop the raw
    # record from the output.
    proc_keys = {goodput_mod.process_key(e["record"]) for e in timeline}
    labels = goodput_mod.process_labels(proc_keys)
    for e in timeline:
        e["process"] = labels[goodput_mod.process_key(e.pop("record"))]
    multi_process = len(proc_keys) > 1

    # Decomposition. Queue wait prefers the completion event's measured
    # value (exact), falling back to prefill-start minus queued-event
    # time (the two clocks agree when recorder and engine share one).
    queue_wait_s = None
    if complete is not None and complete.get("queue_wait_s") is not None:
        queue_wait_s = float(complete["queue_wait_s"])
    elif prefill is not None and queued is not None:
        queue_wait_s = (
            float(prefill["ts"]) + float(prefill["dur"]) - prefill_s
            - float(queued["ts"])
        )
    # A chunk whose ``decode_step`` was open while the engine still
    # waited for this request's first token (the step dispatched from a
    # token on the device, its span around that wait) counts from that
    # token on: the time before it is the prefill leg's. Only against
    # the engine's own span, which shares the chunks' clock.
    first_at = (
        float(prefill["ts"]) + float(prefill["dur"])
        if prefill is not None and "since_pop_s" in prefill else None
    )
    decode_s = sum(
        float(c["dur"]) if first_at is None else max(
            0.0,
            float(c["ts"]) + float(c["dur"])
            - max(float(c["ts"]), first_at),
        )
        for c in decode_chunks
    )
    first_chunk_s = (
        float(decode_chunks[0]["dur"]) if decode_chunks else None
    )
    accounted_s = sum(
        v for v in (queue_wait_s, prefill_s, decode_s) if v is not None
    )
    measured_s = None
    ttft_s = None
    generation_s = None
    if complete is not None:
        ttft_s = complete.get("ttft_s")
        generation_s = complete.get("generation_s")
        if ttft_s is not None:
            measured_s = float(ttft_s) + float(generation_s or 0.0)

    # Router-level decomposition (fleet runs): the replica-inbox hop
    # plus the engine-measured TTFT is the router-door -> first-token
    # time. Both sides are duration sums, so the identity holds across
    # processes with unrelated clocks:
    #   inbox_wait + queue_wait + prefill  ==  router_ttft
    # (== inbox_wait + ttft, since queue_wait + prefill == ttft by the
    # engine's own timestamps).
    inbox_wait_s = None
    if dequeue is not None and dequeue.get("inbox_wait_s") is not None:
        inbox_wait_s = float(dequeue["inbox_wait_s"])
    elif served is not None and served.get("inbox_wait_s") is not None:
        inbox_wait_s = float(served["inbox_wait_s"])
    router_ttft_s = None
    if served is not None and served.get("router_ttft_s") is not None:
        router_ttft_s = float(served["router_ttft_s"])
    elif ttft_s is not None:
        router_ttft_s = float(ttft_s) + (inbox_wait_s or 0.0)
    router_accounted_s = None
    if queue_wait_s is not None or prefill_s is not None:
        router_accounted_s = sum(
            v for v in (inbox_wait_s, queue_wait_s, prefill_s)
            if v is not None
        )
    return {
        "request_id": request_id,
        "found": {
            "queued": queued is not None,
            "prefill": prefill is not None,
            "decode_chunks": len(decode_chunks),
            "complete": complete is not None,
        },
        "hops": {
            "routed": routed is not None,
            "replica": (
                (served or dequeue or {}).get("replica")
                or (routed or {}).get("replica")
            ),
            "worker": (prefill or routed or {}).get("worker"),
            "failovers": len(failovers),
            "processes": sorted(labels.values()),
            "multi_process": multi_process,
        },
        "warnings": warnings,
        "finish_reason": (
            complete.get("finish_reason") if complete is not None else None
        ),
        "num_tokens": (
            complete.get("num_tokens") if complete is not None else None
        ),
        "prefix_hit_tokens": (
            prefill.get("prefix_hit_tokens")
            if prefill is not None else None
        ),
        "speculation": (
            {
                "proposed": sum(
                    s[1] for s in map(_spec_share, decode_chunks)
                    if s is not None
                ),
                "accepted": sum(
                    s[0] for s in map(_spec_share, decode_chunks)
                    if s is not None
                ),
            }
            if any(c.get("proposed") is not None for c in decode_chunks)
            else None
        ),
        "timeline": timeline,
        "decomposition": {
            "inbox_wait_s": inbox_wait_s,
            "queue_wait_s": queue_wait_s,
            "prefill_s": prefill_s,
            "first_decode_chunk_s": first_chunk_s,
            "decode_s": decode_s,
            "accounted_s": accounted_s,
            "router_accounted_s": router_accounted_s,
            "measured_ttft_s": ttft_s,
            "router_ttft_s": router_ttft_s,
            "measured_generation_s": generation_s,
            "measured_total_s": measured_s,
            # Host bookkeeping between chunks is real wall-clock the
            # chunks don't cover; coverage near 1.0 says the trace
            # explains the request's life.
            "coverage": (
                accounted_s / measured_s
                if measured_s not in (None, 0.0) else None
            ),
        },
    }


def format_request_timeline(tl: dict) -> str:
    """Human rendering of ``build_request_timeline``. In a stitched
    multi-process trace, ``t_ms`` is relative to the FIRST entry of
    the SAME process's stream (cross-stream timestamps are on
    unrelated monotonic clocks and are never subtracted); the process
    column names the stream each hop came from."""

    def ms(v):
        return f"{1e3 * v:9.3f}" if v is not None else "        —"

    hops = tl.get("hops", {})
    multi = bool(hops.get("multi_process"))
    lines = [
        f"request {tl['request_id']} — "
        f"finish_reason={tl['finish_reason']} "
        f"tokens={tl['num_tokens']}",
    ]
    for w in tl.get("warnings", ()):
        lines.append(f"WARNING: {w}")
    if tl.get("prefix_hit_tokens"):
        lines.append(
            f"prefix cache: {tl['prefix_hit_tokens']} prompt tokens "
            f"served from shared pages (prefill paid only the suffix)"
        )
    spec = tl.get("speculation")
    if spec and spec.get("proposed"):
        lines.append(
            f"speculation: {spec['accepted']}/{spec['proposed']} "
            f"proposed tokens accepted across decode windows"
        )
    lines += [
        "",
        f"{'t_ms':>10} {'dur_ms':>9}  event"
        + ("  (t_ms per-process)" if multi else ""),
    ]
    proc_t0: Dict[str, float] = {}
    for e in tl["timeline"]:
        proc_t0.setdefault(e.get("process", "?"), e["ts"])
    for e in tl["timeline"]:
        detail = " ".join(
            f"{k}={v}" for k, v in e["detail"].items() if v is not None
        )
        proc = e.get("process", "?")
        tag = f" @{proc}" if multi else ""
        lines.append(
            f"{1e3 * (e['ts'] - proc_t0[proc]):10.3f} "
            f"{1e3 * e['dur']:9.3f}  "
            f"{e['what']}{'  [' + detail + ']' if detail else ''}{tag}"
        )
    d = tl["decomposition"]
    lines += [
        "",
        "TTFT/generation decomposition (ms):",
    ]
    if d.get("inbox_wait_s") is not None:
        lines.append(f"  replica_inbox_wait {ms(d['inbox_wait_s'])}")
    lines += [
        f"  queue_wait         {ms(d['queue_wait_s'])}",
        f"  prefill            {ms(d['prefill_s'])}",
        f"  first_decode_chunk {ms(d['first_decode_chunk_s'])}",
        f"  decode total       {ms(d['decode_s'])}",
        f"  accounted          {ms(d['accounted_s'])}",
        f"  measured ttft      {ms(d['measured_ttft_s'])}",
    ]
    if d.get("router_ttft_s") is not None:
        lines.append(
            f"  router ttft        {ms(d['router_ttft_s'])}"
            + (
                f"  (hops sum {ms(d['router_accounted_s']).strip()})"
                if d.get("router_accounted_s") is not None else ""
            )
        )
    lines.append(
        f"  measured total     {ms(d['measured_total_s'])}"
        + (
            f"  (coverage {d['coverage']:.3f})"
            if d["coverage"] is not None else ""
        ),
    )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Fleet mode (--fleet): the cross-replica view over merged streams
# ---------------------------------------------------------------------------


def build_fleet_report(records: List[dict]) -> dict:
    """The fleet-level rollup over records MERGED from every member's
    span stream (tpudl.obs.fleet.FleetMonitor.trace_records, or just
    ``report.py --fleet dir1 dir2 ...``): per-process record counts,
    the serve-request outcome breakdown, router hop-latency
    distributions (inbox wait, router-level TTFT — duration sums, clock
    -skew free), failover/membership/autoscale activity, and every
    request whose stitched trace is PARTIAL (a hop's stream missing
    from the merge)."""
    per_proc: Dict[tuple, dict] = {}
    rids: List = []
    seen_rids = set()
    membership: List[dict] = []
    autoscale_actions: List[dict] = []
    for r in records:
        key = goodput_mod.process_key(r)
        row = per_proc.setdefault(key, {"records": 0, "spans": 0,
                                        "events": 0})
        row["records"] += 1
        kind = r.get("kind")
        if kind == "span":
            row["spans"] += 1
        elif kind == "event":
            row["events"] += 1
            name = r.get("name")
            if name in (
                "request_routed", "request_served", "request_complete",
            ):
                rid = r.get("request_id")
                marker = str(rid)
                if marker not in seen_rids:
                    seen_rids.add(marker)
                    rids.append(rid)
            elif name in ("replica_added", "replica_removed"):
                membership.append({
                    "what": name, "replica": r.get("replica"),
                    "drained": r.get("drained"),
                })
            elif name == "autoscale":
                autoscale_actions.append({
                    "action": r.get("action"),
                    "replica": r.get("replica"),
                    "reason": r.get("reason"),
                })
    labels = goodput_mod.process_labels(per_proc)
    processes = {
        labels[k]: per_proc[k]
        for k in sorted(per_proc, key=lambda k: labels[k])
    }

    # Bucket records per request ONCE (string-keyed, matching the
    # stitcher's id coercion): stitching each request from its own
    # bucket keeps the report linear in the record count instead of
    # O(requests x records) full rescans.
    buckets: Dict[str, List[dict]] = {}
    for r in records:
        keys = set()
        if r.get("request_id") is not None:
            keys.add(str(r["request_id"]))
        for x in r.get("rids") or ():
            keys.add(str(x))
        for k in keys:
            buckets.setdefault(k, []).append(r)

    router_ttfts: List[float] = []
    inbox_waits: List[float] = []
    failovers = 0
    partial: Dict[str, List[str]] = {}
    for rid in rids:
        try:
            tl = build_request_timeline(buckets.get(str(rid), []), rid)
        except KeyError:
            partial[str(rid)] = ["no stitchable records"]
            continue
        d = tl["decomposition"]
        if d.get("router_ttft_s") is not None:
            router_ttfts.append(float(d["router_ttft_s"]))
        if d.get("inbox_wait_s") is not None:
            inbox_waits.append(float(d["inbox_wait_s"]))
        failovers += tl["hops"]["failovers"]
        if tl["warnings"]:
            partial[str(rid)] = list(tl["warnings"])
    return {
        "num_records": len(records),
        "processes": processes,
        "num_requests": len(rids),
        "serve_requests": serve_request_breakdown(records),
        "router_ttft": _dist(router_ttfts) if router_ttfts else None,
        "replica_inbox_wait": _dist(inbox_waits) if inbox_waits else None,
        "failovers": failovers,
        "membership": membership,
        "autoscale_actions": autoscale_actions,
        "partial_traces": partial,
    }


def format_fleet_report(report: dict) -> str:
    """Human rendering of ``build_fleet_report``."""
    lines = [
        f"tpudl fleet report — {report['num_records']} records from "
        f"{len(report['processes'])} process stream(s), "
        f"{report['num_requests']} request(s)",
        "",
        f"{'process':24} {'records':>8} {'spans':>7} {'events':>7}",
    ]
    for label, row in report["processes"].items():
        lines.append(
            f"{label:24} {row['records']:8d} {row['spans']:7d} "
            f"{row['events']:7d}"
        )
    if report.get("serve_requests"):
        lines += [
            "",
            f"{'serve requests':16} {'count':>6} {'tokens':>8} "
            f"{'q_wait_ms':>10} {'ttft_ms':>9}",
        ]
        for reason, r in report["serve_requests"].items():
            qw = (
                f"{r['mean_queue_wait_ms']:10.2f}"
                if r["mean_queue_wait_ms"] is not None else f"{'—':>10}"
            )
            tt = (
                f"{r['mean_ttft_ms']:9.2f}"
                if r["mean_ttft_ms"] is not None else f"{'—':>9}"
            )
            lines.append(
                f"{reason:16} {r['count']:6d} {r['tokens']:8d} {qw} {tt}"
            )
    for name, key in (
        ("router TTFT", "router_ttft"),
        ("replica inbox wait", "replica_inbox_wait"),
    ):
        d = report.get(key)
        if d:
            lines.append(
                f"{name}: n={d['count']} mean={d['mean_ms']:.2f}ms "
                f"p50={d['p50_ms']:.2f}ms p95={d['p95_ms']:.2f}ms "
                f"p99={d['p99_ms']:.2f}ms"
            )
    if report["failovers"]:
        lines.append(f"failovers: {report['failovers']}")
    for m in report["membership"]:
        drained = (
            f" (drained={m['drained']})"
            if m.get("drained") is not None else ""
        )
        lines.append(f"membership: {m['what']} {m['replica']}{drained}")
    for a in report["autoscale_actions"]:
        lines.append(
            f"autoscale: {a['action']} {a['replica']} "
            f"[reason: {a['reason']}]"
        )
    if report["partial_traces"]:
        lines.append("")
        lines.append(
            f"PARTIAL TRACES ({len(report['partial_traces'])} "
            f"request(s) with hops missing from the merge):"
        )
        for rid, warnings in sorted(report["partial_traces"].items()):
            for w in warnings:
                lines.append(f"  {rid}: {w}")
    return "\n".join(lines)


def format_report(report: dict) -> str:
    """Human-readable rendering of a ``build_report`` result."""
    lines = [
        f"tpudl obs report — {report['num_span_records']} spans, "
        f"{len(report['per_host']) or 1} process(es)",
        "",
        f"{'category':14} {'count':>6} {'total_s':>8} {'mean_ms':>9} "
        f"{'p50_ms':>9} {'p95_ms':>9} {'p99_ms':>9}",
    ]
    for cat, r in report["breakdown"].items():
        lines.append(
            f"{cat:14} {r['count']:6d} {r['total_s']:8.2f} "
            f"{r['mean_ms']:9.2f} {r['p50_ms']:9.2f} {r['p95_ms']:9.2f} "
            f"{r['p99_ms']:9.2f}"
        )

    gp = report["goodput"]
    lines += ["", goodput_mod.format_goodput(gp["overall"])]
    if len(gp["per_process"]) > 1:
        for key, cls in gp["per_process"].items():
            lines.append(f"  {key:20} {goodput_mod.format_goodput(cls)}")

    if report["per_host"]:
        lines += [
            "",
            f"{'host/process':20} {'steps':>6} {'mean_ms':>9} "
            f"{'p95_ms':>9} {'x_median':>9}",
        ]
        for key, r in report["per_host"].items():
            flag = "  STRAGGLER" if r["straggler"] else ""
            lines.append(
                f"{key:20} {r['count']:6d} {r['mean_ms']:9.2f} "
                f"{r['p95_ms']:9.2f} {r['x_median']:9.2f}{flag}"
            )

    if report["outlier_steps"]:
        lines += [
            "",
            f"outlier steps (> {report['outlier_factor']:g}x p50): "
            f"{len(report['outlier_steps'])}",
        ]
        for o in report["outlier_steps"][:10]:
            step = f" step {o['step']}" if o["step"] is not None else ""
            lines.append(
                f"  {o['ms']:9.2f} ms ({o['x_p50']:.1f}x p50) "
                f"{o['host']}/p{o['process']}{step}"
            )

    if report.get("serve_requests"):
        lines += [
            "",
            f"{'serve requests':16} {'count':>6} {'tokens':>8} "
            f"{'q_wait_ms':>10} {'ttft_ms':>9}",
        ]
        for reason, r in report["serve_requests"].items():
            qw = (
                f"{r['mean_queue_wait_ms']:10.2f}"
                if r["mean_queue_wait_ms"] is not None else f"{'—':>10}"
            )
            tt = (
                f"{r['mean_ttft_ms']:9.2f}"
                if r["mean_ttft_ms"] is not None else f"{'—':>9}"
            )
            lines.append(
                f"{reason:16} {r['count']:6d} {r['tokens']:8d} {qw} {tt}"
            )

    if report.get("serve_phases"):
        lines += [
            "",
            f"{'serve phase':24} {'count':>6} {'total_s':>8} "
            f"{'self_s':>8} {'mean_ms':>9}",
        ]
        for name, r in report["serve_phases"].items():
            depth = ""
            if "queue_depth_mean" in r:
                depth = (
                    f"  queue_depth mean {r['queue_depth_mean']:.1f} "
                    f"max {r['queue_depth_max']:g}"
                )
            lines.append(
                f"{'  ' * r['depth'] + name:24} {r['count']:6d} "
                f"{r['total_s']:8.2f} {r['self_s']:8.2f} "
                f"{r['mean_ms']:9.2f}{depth}"
            )

    startup = report.get("startup")
    if startup:
        lines += [
            "",
            f"{'start-up phase':28} {'at_s':>8} {'total_s':>8} "
            f"{'self_s':>8}",
        ]
        for r in startup["phases"]:
            attrs = " ".join(f"{k}={v}" for k, v in r["attrs"].items())
            lines.append(
                f"{r['name']:28} {r['at_s']:8.2f} {r['total_s']:8.2f} "
                f"{r['self_s']:8.2f}  {attrs}".rstrip()
            )
        lines += [
            "",
            f"{'program':28} {'built':>6} {'trace_s':>8} {'lower_s':>8} "
            f"{'compile_s':>9} {'cache_hits':>10} {'cache_read_s':>12}",
        ]
        for name, r in startup["programs"].items():
            lines.append(
                f"{name:28} {r['built']:6d} {r['trace_s']:8.2f} "
                f"{r['lower_s']:8.2f} {r['compile_s']:9.2f} "
                f"{r['cache_hits']:10d} {r['cache_read_s']:12.2f}"
            )
        if startup["kernels"]:
            lines += ["", f"{'kernel':28} {'count':>6} {'trace_s':>8}"]
            for name, r in startup["kernels"].items():
                lines.append(
                    f"{name:28} {r['count']:6d} {r['trace_s']:8.2f}"
                )

    for key, snap in report["counters"].items():
        cs = snap.get("counters", {})
        if cs:
            rendered = " ".join(f"{k}={v:g}" for k, v in sorted(cs.items()))
            lines.append(f"counters {key}: {rendered}")
        gs = snap.get("gauges", {})
        if gs:
            rendered = " ".join(f"{k}={v:g}" for k, v in sorted(gs.items()))
            lines.append(f"gauges {key}: {rendered}")
        # Registry histograms (e.g. the serving engine's serve_ttft_ms /
        # serve_tpot_ms / serve_queue_wait_ms) ride the same snapshot;
        # quote the tail, which is what a serving SLO reads.
        for name, h in sorted(snap.get("histograms", {}).items()):
            if not h.get("count"):
                continue
            lines.append(
                f"histogram {key}: {name} n={h['count']} "
                f"mean={h['mean']:.3f} p50={h['p50']:.3f} "
                f"p95={h['p95']:.3f} p99={h['p99']:.3f}"
            )
    return "\n".join(lines)


def load_request_records(paths: Iterable[str]) -> List[dict]:
    """Load durable request-log records (tpudl.obs.requestlog) from
    directories: each path is a request-log directory itself or a run
    directory holding a ``requestlog/`` subdir (the
    TPUDL_OBS_REQUEST_LOG convention of pointing it next to
    TPUDL_OBS_DIR)."""
    from tpudl.obs import requestlog

    records: List[dict] = []
    for p in paths:
        found = None
        for d in (p, os.path.join(p, "requestlog")):
            if os.path.isdir(d) and requestlog.list_segments(d):
                found = d
                break
        if found is None:
            raise FileNotFoundError(
                f"no request-log segments (requests-*.jsonl) under {p}"
            )
        records.extend(requestlog.read_request_log(found))
    return records


def find_request_record(paths: Iterable[str], request_id) -> Optional[dict]:
    """The durable terminal record for one request, or None — the
    ``--request`` fallback when the span stream is gone. Matched by
    string form too (CLI args are strings)."""
    try:
        records = load_request_records(paths)
    except FileNotFoundError:
        return None
    for rec in records:
        rid = rec.get("request_id")
        if rid == request_id or str(rid) == str(request_id):
            return rec
    return None


def build_tenant_report(records: Iterable[dict]) -> dict:
    """Cost-attribution rollup over durable request-log records: one
    row per tenant with request/token volumes, chip-seconds (slot
    occupancy), KV byte-seconds (the bytes-model cost numerator), and
    each tenant's share of total chip time. Reuses the live metering
    plane's fold (``TenantMeter.ingest``) so the offline table and the
    scraped ``serve_tenant_*`` series can never disagree."""
    from tpudl.obs.metering import TenantMeter

    m = TenantMeter()
    n = 0
    for rec in records:
        m.ingest(rec)
        n += 1
    tenants = m.tenants()
    total_chip = sum(u["chip_seconds"] for u in tenants.values())
    for u in tenants.values():
        u["chip_share"] = (
            u["chip_seconds"] / total_chip if total_chip else 0.0
        )
    return {
        "records": n,
        "tenants": tenants,
        "total_chip_seconds": total_chip,
    }


def format_tenant_report(report: dict) -> str:
    lines = [
        f"request-log records: {report['records']}  "
        f"total chip-seconds: {report['total_chip_seconds']:.3f}",
        "",
        f"{'tenant':<16} {'req':>6} {'done':>6} {'shed':>6} "
        f"{'tok_in':>8} {'tok_out':>8} {'chip_s':>10} "
        f"{'kv_gb_s':>10} {'reloads':>8} {'share':>7}",
    ]
    for tenant in sorted(report["tenants"]):
        u = report["tenants"][tenant]
        shed = sum(u["sheds"].values())
        lines.append(
            f"{tenant:<16} {u['requests_total']:>6} "
            f"{u['requests_completed']:>6} {shed:>6} "
            f"{u['tokens_in']:>8} {u['tokens_out']:>8} "
            f"{u['chip_seconds']:>10.3f} "
            f"{u['kv_byte_seconds'] / 1e9:>10.4f} "
            f"{u['adapter_reloads']:>8} {u['chip_share']:>6.1%}"
        )
    sheds: Dict[str, int] = {}
    for u in report["tenants"].values():
        for reason, count in u["sheds"].items():
            sheds[reason] = sheds.get(reason, 0) + count
    if sheds:
        lines.append("")
        lines.append(
            "sheds by reason: " + " ".join(
                f"{r}={n}" for r, n in sorted(sheds.items())
            )
        )
    return "\n".join(lines)


def load_flywheel_state(paths: Iterable[str]) -> dict:
    """The persisted ``FlywheelController`` state
    (``flywheel-state.json``, written next to the request-log
    segments) from the first path that holds one — paths follow the
    ``--tenants`` convention (a request-log directory, or a run dir
    with a ``requestlog/`` subdir)."""
    from tpudl.flywheel.loop import STATE_FILENAME

    for p in paths:
        for d in (p, os.path.join(p, "requestlog")):
            f = os.path.join(d, STATE_FILENAME)
            if os.path.isfile(f):
                with open(f, "r", encoding="utf-8") as fh:
                    return json.load(fh)
    raise FileNotFoundError(
        f"no flywheel-state.json under {list(paths)} — has a "
        f"FlywheelController run against this request log?"
    )


def build_flywheel_report(state: dict) -> dict:
    """Per-tenant refresh rollup over the controller's persisted
    history: refresh count, records consumed, the last consumed log
    position, last swap time, and the last refresh's loss delta."""
    tenants: Dict[str, dict] = {}
    for entry in state.get("history", ()):
        t = str(entry.get("tenant"))
        row = tenants.setdefault(t, {
            "refreshes": 0,
            "records_consumed": 0,
            "steps": 0,
            "log_position": None,
            "last_swap_ts": None,
            "loss_first": None,
            "loss_last": None,
            "pending_swap": False,
        })
        row["refreshes"] += 1
        row["records_consumed"] += int(entry.get("records_consumed", 0))
        row["steps"] += int(entry.get("steps", 0))
        row["log_position"] = entry.get("log_position")
        row["loss_first"] = entry.get("loss_first")
        row["loss_last"] = entry.get("loss_last")
        if entry.get("swapped"):
            row["last_swap_ts"] = entry.get("swap_ts")
            row["pending_swap"] = False
        else:
            row["pending_swap"] = True
    for t, pos in state.get("positions", {}).items():
        tenants.setdefault(str(t), {
            "refreshes": 0, "records_consumed": 0, "steps": 0,
            "log_position": None, "last_swap_ts": None,
            "loss_first": None, "loss_last": None,
            "pending_swap": False,
        })["log_position"] = {
            k: v for k, v in pos.items() if k in ("epoch", "offset")
        }
    return {
        "tenants": tenants,
        "total_refreshes": sum(
            r["refreshes"] for r in tenants.values()
        ),
        "last_swap_ts": state.get("last_swap_ts"),
    }


def format_flywheel_report(report: dict) -> str:
    import datetime

    def when(ts):
        if ts is None:
            return "—"
        return datetime.datetime.fromtimestamp(ts).strftime(
            "%Y-%m-%d %H:%M:%S"
        )

    lines = [
        f"flywheel refreshes: {report['total_refreshes']}  "
        f"last swap: {when(report['last_swap_ts'])}",
        "",
        f"{'tenant':<16} {'refreshes':>9} {'records':>8} {'steps':>6} "
        f"{'log_pos':>12} {'loss_delta':>11} {'last_swap':>20}",
    ]
    for tenant in sorted(report["tenants"]):
        r = report["tenants"][tenant]
        pos = r["log_position"] or {}
        pos_s = (
            f"{pos.get('epoch', '?')}:{pos.get('offset', '?')}"
            if pos else "—"
        )
        if r["loss_first"] is not None and r["loss_last"] is not None:
            delta = f"{r['loss_last'] - r['loss_first']:+11.4f}"
        else:
            delta = f"{'—':>11}"
        swap = when(r["last_swap_ts"]) + (
            " (pending)" if r["pending_swap"] else ""
        )
        lines.append(
            f"{tenant:<16} {r['refreshes']:>9} "
            f"{r['records_consumed']:>8} {r['steps']:>6} "
            f"{pos_s:>12} {delta} {swap:>20}"
        )
    return "\n".join(lines)


def format_request_record(rec: dict) -> str:
    """Render one durable terminal record — the ``--request`` answer
    when the span stream no longer exists (no per-hop timeline, but
    the outcome, volumes, and latency aggregates survive)."""
    lines = [
        f"request {rec.get('request_id')!r} "
        f"(durable record, schema v{rec.get('v')})",
        f"  tenant={rec.get('tenant')} site={rec.get('site')} "
        f"finish_reason={rec.get('finish_reason')}",
        f"  tokens_in={rec.get('tokens_in')} "
        f"tokens_out={rec.get('tokens_out')} "
        f"prefix_hit={rec.get('prefix_hit_tokens')} "
        f"spec={rec.get('spec_accepted')}/{rec.get('spec_proposed')}",
    ]
    qw, ttft, tpot = (
        rec.get("queue_wait_s"), rec.get("ttft_s"), rec.get("tpot_s")
    )
    lines.append(
        "  queue_wait={} ttft={} tpot={}".format(
            f"{1e3 * qw:.1f}ms" if qw is not None else "-",
            f"{1e3 * ttft:.1f}ms" if ttft is not None else "-",
            f"{1e3 * tpot:.2f}ms" if tpot is not None else "-",
        )
    )
    lines.append(
        f"  kv_page_s={rec.get('kv_page_seconds', 0.0):.3f} "
        f"kv_byte_s={rec.get('kv_byte_seconds', 0.0):.1f} "
        f"adapter_reloads={rec.get('adapter_reloads')} "
        f"migrations={rec.get('migrations')}"
    )
    return "\n".join(lines)


def main(argv: Optional[list] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Aggregate tpudl obs span files into a step-time "
        "breakdown, goodput fraction, and straggler attribution"
    )
    ap.add_argument(
        "paths", nargs="+",
        help="span *.jsonl files and/or obs directories",
    )
    ap.add_argument("--outlier-factor", type=float, default=3.0,
                    help="flag steps slower than this multiple of p50")
    ap.add_argument("--straggler-factor", type=float, default=1.2,
                    help="flag hosts with mean step time above this "
                    "multiple of the cross-host median")
    ap.add_argument("--chrome-trace", metavar="OUT.json",
                    help="also export the records as Chrome trace-event "
                    "JSON for Perfetto")
    ap.add_argument("--request", metavar="ID",
                    help="print ONE served request's stitched trace "
                    "(router door -> admission -> prefill -> decode "
                    "chunks -> completion, merged across every span "
                    "stream given) with its TTFT decomposition, "
                    "instead of the run report")
    ap.add_argument("--fleet", action="store_true",
                    help="print the fleet rollup over the merged "
                    "streams: per-process record counts, request "
                    "outcomes, router hop latencies, failover/"
                    "autoscale activity, and partial-trace warnings")
    ap.add_argument("--tenants", action="store_true",
                    help="print the per-tenant cost-attribution table "
                    "from durable request-log records (paths are "
                    "request-log directories or run dirs holding a "
                    "requestlog/ subdir) instead of the span report")
    ap.add_argument("--flywheel", action="store_true",
                    help="print the per-tenant continual-refresh "
                    "history (records consumed, log position, last "
                    "swap, loss delta) from the FlywheelController's "
                    "flywheel-state.json next to the request log")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    if args.flywheel:
        try:
            fly_state = load_flywheel_state(args.paths)
        except FileNotFoundError as e:
            print(e)
            return 1
        fly = build_flywheel_report(fly_state)
        print(
            json.dumps(fly) if args.json else format_flywheel_report(fly)
        )
        return 0
    if args.tenants:
        # The durable log, not the span stream: --tenants answers
        # "who consumed which chips" after the serving processes (and
        # their TPUDL_OBS_DIR streams) are gone.
        try:
            reqlog = load_request_records(args.paths)
        except FileNotFoundError as e:
            print(e)
            return 1
        tenant_report = build_tenant_report(reqlog)
        print(
            json.dumps(tenant_report)
            if args.json else format_tenant_report(tenant_report)
        )
        return 0
    if args.request is not None:
        # Prefer the stitched span timeline; fall back to the durable
        # terminal record when the span stream is gone (or never held
        # this request) — the request log outlives TPUDL_OBS_DIR.
        try:
            records = load_records(args.paths)
            tl = build_request_timeline(records, args.request)
        except (KeyError, FileNotFoundError) as e:
            rec = find_request_record(args.paths, args.request)
            if rec is not None:
                print(
                    json.dumps(rec)
                    if args.json else format_request_record(rec)
                )
                return 0
            print(e.args[0] if e.args else str(e))
            return 1
        print(
            json.dumps(tl) if args.json else format_request_timeline(tl)
        )
        return 0

    records = load_records(args.paths)
    if args.fleet:
        fleet = build_fleet_report(records)
        if args.chrome_trace:
            with open(args.chrome_trace, "w") as f:
                json.dump(
                    {"traceEvents": chrome_trace_events(records)}, f
                )
        print(
            json.dumps(fleet) if args.json else format_fleet_report(fleet)
        )
        return 0
    report = build_report(
        records,
        outlier_factor=args.outlier_factor,
        straggler_factor=args.straggler_factor,
    )
    if args.chrome_trace:
        with open(args.chrome_trace, "w") as f:
            json.dump({"traceEvents": chrome_trace_events(records)}, f)
    print(json.dumps(report) if args.json else format_report(report))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
