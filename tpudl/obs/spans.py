"""Host-side span/event recorder: the wall-clock half of observability.

`tpudl.train.profiling` answers "where does the DEVICE step go" from the
XLA trace; this module answers "where does the rest of the RUN go" —
data stalls, compile, checkpointing, idle — by recording host-side spans
around the runtime's blocking calls. Records are plain dicts with a
monotonic timestamp, duration, category, and host/process tags, exported
two ways:

- **JSONL** (one record per line, written in blocks) — the greppable
  artifact ``python -m tpudl.obs.report`` aggregates into goodput and
  straggler tables;
- **Chrome trace-event JSON** (``export_chrome_trace``) — the host spans
  alone, on the host's monotonic clock, for Perfetto/chrome://tracing.

**One timeline.** While a recorder is active, every span that is begun
(``begin``/``end`` or the ``span`` context manager) is also open as a
``jax.profiler.TraceAnnotation("tpudl.<name>", span_id=<id>)``. A
profiler trace taken by anyone (``TPUDL_PROFILE_DIR``, a benchmark
harness) then holds the program's phases on the trace's own clock,
beside the device's operations, and ``span_id`` joins each annotation
to its JSONL record and that record's attributes. The annotation is
made only if ``jax`` is already imported, so the module stays
importable in data workers.

Every span carries an ``id`` (unique in the process) and ``parent``
(the ``id`` of the span open on the same thread when it began, else
None), so self time can be reckoned and a child is never counted twice
(``without_same_category_children``, ``tpudl.obs.goodput``).

Design constraints, all load-bearing:

- **zero hard dependencies** — stdlib only, importable everywhere
  (data workers, checkpoint path, spawned distributor ranks);
- **thread-safe** — async checkpoint flushes and data prefetch threads
  record concurrently with the train loop;
- **injectable clock** — tests pass a fake monotonic clock and get
  byte-deterministic exports;
- **disabled is free** — ``active_recorder()`` returns None unless
  ``enable()`` was called or TPUDL_OBS_DIR is set; instrumentation
  sites guard on that None. The environment is looked up once per
  process while off (``disable()`` allows one more look), so a disabled
  call is one global read;
- **off the hot path** — records are kept in memory and written in
  blocks of ``BLOCK_RECORDS``: on ``close``/``disable``, when
  ``records`` is read, and when the buffer is full. A killed worker
  loses at most its last block, which ``read_jsonl`` tolerates as a
  torn tail.

Activation mirrors the profiler hook: set ``TPUDL_OBS_DIR=/path`` (or
call ``enable(path)``) and every instrumented layer writes into
``spans-<host>-p<process>-<pid>.jsonl`` under it.

**Start-up is recorded before anyone asks.** The sites that run once a
process (a session's construction and first requests, a train state's
initialisation and first step, a Pallas kernel's trace, and the
``program.*`` spans ``tpudl.analysis.dispatch`` opens around every
stage of every program JAX builds) write through
``startup_recorder()``: the active recorder, else ONE bounded in-memory
recorder (``STARTUP_RECORDS`` records, counter
``startup_records_dropped`` beyond). ``enable()`` hands what that one
holds to the new recorder, ``id`` / ``parent`` / ``ts`` as they were,
and empties it, so a recorder turned on after set-up still has set-up's
timeline. Only those sites use it: the hot paths keep their
``active_recorder()`` guard and record nothing while off. A phase that
lies around the hot path (``startup.first_requests``,
``startup.first_step``) is recorded AFTER the fact, from a reading of
the clock, as a ``CAT_ENCLOSING`` span: a recorder's tree of a first
call is then that of any other call, and no second is counted twice.
"""

from __future__ import annotations

import atexit
import functools
import itertools
import json
import os
import socket
import sys
import threading
import time
from typing import Callable, Iterable, List, Optional

from tpudl.analysis.registry import env_int, env_str

#: Span categories the goodput classifier understands (see
#: tpudl.obs.goodput). Instrumentation may invent others; they land in
#: the report's "other" bucket.
CAT_STEP = "step"
CAT_EVAL = "eval"
CAT_COMPILE = "compile"
CAT_DATA_WAIT = "data_wait"
#: Time the train loop blocked on metric readback (the async drain's
#: backpressure or its end-of-fit flush) — separate from data_wait so a
#: report distinguishes "starved for batches" from "throttled by
#: telemetry".
CAT_METRIC_WAIT = "metric_wait"
CAT_CHECKPOINT = "checkpoint"
#: Time lost to failure recovery (supervisor backoff between a cohort
#: death and its relaunch) — accounted as lost wall-clock, the
#: "lost-to-recovery" column of the goodput report.
CAT_RECOVERY = "recovery"
#: Background checkpoint writes (tpudl.ft.writer): they OVERLAP train
#: steps by design, so the classifier reports them but never charges
#: them against the run's wall-clock budget.
CAT_CKPT_BG = "ckpt_bg"
#: Enclosing lifetime spans (a distributor worker's whole run; a
#: session's first requests and a step's first call, which are recorded
#: after the fact around the hot path's own spans): they OVERLAP the
#: categorized spans inside them, so the goodput classifier uses them
#: only to extend the run window, never as accounted time.
CAT_ENCLOSING = "worker"
#: Phases a process runs once, before its steady state: a serving
#: session's construction, a train state's initialisation, a kernel's
#: trace. Recorded whether or not a recorder is on
#: (``startup_recorder``).
CAT_STARTUP = "startup"


#: Records a file-backed recorder holds before it writes them out.
BLOCK_RECORDS = 1024
#: Prefix of the profiler annotations that mirror the spans.
ANNOTATION_PREFIX = "tpudl."
#: Records the start-up recorder holds while no recorder is active.
STARTUP_RECORDS = 512

# Span ids are unique in the process, whichever recorder hands them out
# (itertools.count.__next__ is atomic under the GIL).
_span_ids = itertools.count(1)
_annotation_cls = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if ``jax`` is already imported,
    else None: this module never imports JAX itself."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            _annotation_cls = jax.profiler.TraceAnnotation
        except AttributeError:  # jax is still being imported
            return None
    return _annotation_cls


class _Span:
    """One open span: begun by ``SpanRecorder.begin`` (or on entering
    ``SpanRecorder.span``), recorded by ``end``. Never created when
    recording is disabled (the module-level ``span()`` returns a shared
    no-op instead)."""

    __slots__ = ("_rec", "_name", "_cat", "_attrs", "_annotation",
                 "id", "parent", "t0")

    def __init__(self, rec: "SpanRecorder", name: str, cat: str, attrs: dict):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._attrs = attrs

    def _begin(self, ts: Optional[float] = None) -> "_Span":
        stack = self._rec._open_spans()
        self.id = next(_span_ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        cls = _trace_annotation()
        self._annotation = None
        if cls is not None:
            self._annotation = cls(
                ANNOTATION_PREFIX + self._name, span_id=self.id
            )
            self._annotation.__enter__()
        # Read last, so that the span's extent is the work's and the
        # annotation encloses it.
        self.t0 = self._rec.clock() if ts is None else ts
        return self

    def end(self, ts: Optional[float] = None, **attrs) -> dict:
        """Close the span and record it; ``attrs`` are those known only
        now, ``ts`` the end if the caller has read the clock already.
        Spans begun after this one on the same thread and never ended
        (an exception unwound past them) are dropped unrecorded.
        Returns the record."""
        rec = self._rec
        dur = (rec.clock() if ts is None else ts) - self.t0
        self.cancel()
        if attrs:
            self._attrs = {**self._attrs, **attrs}
        return rec._span_record(
            self._name, self._cat, self.t0, dur, self._attrs,
            self.id, self.parent,
        )

    def end_lasting(self, dur: float, **attrs) -> dict:
        """Close the span NOW as one that lasted ``dur`` seconds by
        another's measure (JAX's own, of a stage it timed): it began
        ``dur`` ago, so it ends inside whatever is open around it."""
        now = self._rec.clock()
        self.t0 = now - dur
        return self.end(now, **attrs)

    def note(self, **attrs) -> None:
        """Attributes known only once the work is under way, for a span
        that a ``with`` block will close."""
        self._attrs = {**self._attrs, **attrs}

    def cancel(self) -> None:
        """Close the span without a record: what it waited for did not
        happen (a pull that returned nothing, a wait of no length)."""
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = self._rec._open_spans()
        if self.id in stack:
            del stack[stack.index(self.id):]

    def __enter__(self) -> "_Span":
        return self._begin()

    def __exit__(self, *exc) -> None:
        self.end()


class _NullSpan:
    """Shared no-op context manager for the disabled path (one module
    singleton — entering it allocates nothing)."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NULL_SPAN = _NullSpan()


class SpanRecorder:
    """Thread-safe span/event sink: records are kept in memory and, with
    a ``path``, written to JSONL in blocks.

    Every record is a flat dict:

    - spans:    ``{"kind": "span", "name", "cat", "ts", "dur", "id",
      "parent", "host", "process", "pid", "tid", ...attrs}``
    - events:   ``{"kind": "event", "name", "cat", "ts", ...tags}``
    - counters: ``{"kind": "counters", "ts", "data": {...}}`` (a
      tpudl.obs.counters snapshot riding the same stream)

    ``ts``/``dur`` are seconds on the injected monotonic ``clock``
    (default ``time.monotonic`` — comparable within one process, not
    across hosts; the report aggregates durations, never cross-host
    timestamps).
    """

    def __init__(
        self,
        path: Optional[str] = None,
        clock: Callable[[], float] = time.monotonic,
        host: Optional[str] = None,
        process: Optional[int] = None,
    ):
        self.clock = clock
        self.path = path
        self.host = host if host is not None else socket.gethostname()
        self.process = (
            process
            if process is not None
            else env_int("TPUDL_PROCESS_ID", 0)
        )
        self._lock = threading.Lock()
        # With a file: the block not yet written. Without: every record.
        self._records: list = []
        self._open = threading.local()
        self._file = None
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._file = open(path, "a")

    # -- recording -----------------------------------------------------

    def span(self, name: str, cat: str = CAT_STEP, **attrs) -> _Span:
        """Context manager: ``with rec.span("save", "checkpoint"): ...``"""
        return _Span(self, name, cat, attrs)

    def begin(self, name: str, cat: str = CAT_STEP,
              ts: Optional[float] = None, **attrs) -> _Span:
        """Open a span now (at ``ts`` on this recorder's clock, if the
        caller has read it already); ``.end(**attrs)`` on what is
        returned records it. The form the hot loops use behind their
        ``rec is not None`` guard: spans begun in between, on the same
        thread, become its children."""
        return _Span(self, name, cat, attrs)._begin(ts)

    def _open_spans(self) -> List[int]:
        """Ids of the spans open on the calling thread, outermost
        first."""
        try:
            return self._open.stack
        except AttributeError:
            self._open.stack = []
            return self._open.stack

    def record(
        self, name: str, cat: str, ts: float, dur: float,
        attrs: Optional[dict] = None,
    ) -> dict:
        """Append one span after the fact (no profiler annotation: the
        time has passed). Its parent is the span open on this thread
        now."""
        stack = self._open_spans()
        return self._span_record(
            name, cat, ts, dur, attrs, next(_span_ids),
            stack[-1] if stack else None,
        )

    def _span_record(
        self, name: str, cat: str, ts: float, dur: float,
        attrs: Optional[dict], span_id: int, parent: Optional[int],
    ) -> dict:
        rec = {
            "kind": "span", "name": name, "cat": cat,
            "ts": ts, "dur": dur, "id": span_id, "parent": parent,
            "host": self.host, "process": self.process,
            "pid": os.getpid(), "tid": threading.get_ident(),
        }
        if attrs:
            rec.update(attrs)
        self._emit(rec)
        return rec

    def event(self, name: str, cat: str = "event", **tags) -> dict:
        """Instant (zero-duration) event — e.g. a per-step metrics blob.
        ``tags`` must not use the reserved record keys (kind/name/cat/
        ts/host/process/pid); nest free-form payloads under one tag
        (see MetricLogger's ``metrics=``)."""
        rec = {
            "kind": "event", "name": name, "cat": cat, "ts": self.clock(),
            "host": self.host, "process": self.process, "pid": os.getpid(),
        }
        reserved = set(rec) & set(tags)
        if reserved:
            raise ValueError(
                f"event tags collide with reserved record keys: "
                f"{sorted(reserved)} — nest them under one tag instead"
            )
        rec.update(tags)
        self._emit(rec)
        return rec

    def counters(self, snapshot: dict) -> dict:
        """Attach a tpudl.obs.counters snapshot to the stream."""
        rec = {
            "kind": "counters", "ts": self.clock(),
            "host": self.host, "process": self.process, "pid": os.getpid(),
            "data": snapshot,
        }
        self._emit(rec)
        return rec

    def ingest(self, record: dict) -> None:
        """Append an already-built record verbatim (the distributor's
        merge path: worker records keep THEIR host/process tags)."""
        self._emit(record)

    def _emit(self, rec: dict) -> None:
        # A file-backed recorder holds one block at most (a million-step
        # run must not grow the host RSS by its own telemetry) and
        # serialises it off the per-record path; `records` re-reads the
        # file.
        with self._lock:
            self._records.append(rec)
            if (
                self._file is not None
                and len(self._records) >= BLOCK_RECORDS
            ):
                self._write_block()

    def _write_block(self) -> None:
        """Write the held block out (lock held, file open)."""
        if self._records:
            self._file.write(
                "".join(json.dumps(r) + "\n" for r in self._records)
            )
            self._file.flush()
            self._records = []

    # -- export --------------------------------------------------------

    @property
    def records(self) -> list:
        with self._lock:
            if self.path is not None:
                if self._file is not None:
                    self._write_block()
                if not os.path.exists(self.path):
                    return []
                return read_jsonl(self.path)
            return list(self._records)

    def export_jsonl(self, path: str) -> str:
        """Write the in-memory records to ``path`` (one JSON per line)."""
        with open(path, "w") as f:
            for rec in self.records:
                f.write(json.dumps(rec) + "\n")
        return path

    def export_chrome_trace(self, path: str) -> str:
        """Write records as Chrome trace-event JSON (see module docstring)."""
        with open(path, "w") as f:
            json.dump({"traceEvents": chrome_trace_events(self.records)}, f)
        return path

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._write_block()
                self._file.close()
                self._file = None

    def __enter__(self) -> "SpanRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class StartupRecorder(SpanRecorder):
    """The in-memory recorder start-up sites write to while no recorder
    is active: at most ``STARTUP_RECORDS`` records, the rest counted
    (``startup_records_dropped``) and dropped, so that a process nobody
    observes holds a bounded timeline of how it began."""

    def __init__(self):
        super().__init__(None)

    def _emit(self, rec: dict) -> None:
        with self._lock:
            if len(self._records) < STARTUP_RECORDS:
                self._records.append(rec)
                return
        from tpudl.obs.counters import registry

        registry().counter("startup_records_dropped").inc()

    def drain(self) -> list:
        """What is held, which is then held no more."""
        with self._lock:
            records, self._records = self._records, []
        return records


def chrome_trace_events(records: Iterable[dict]) -> list:
    """tpudl span/event records -> Chrome trace-event list.

    Spans become complete ("X") events, instants become "i" events; each
    recording process — keyed (host, process-index, OS pid), since a
    distributor parent and its rank-0 worker share the first two but
    have unrelated monotonic clocks — gets its own trace pid with a
    process_name metadata row, so a merged multi-host file renders one
    lane per worker. Timestamps are each process's monotonic clock:
    the file does not line up with a profiler trace (the ``tpudl.*``
    annotations inside that trace do)."""
    out = []
    proc_ids: dict = {}
    seen_labels: dict = {}
    for rec in records:
        key = (rec.get("host", "?"), rec.get("process", 0), rec.get("pid"))
        if key not in proc_ids:
            proc_ids[key] = len(proc_ids) + 1
            label = f"tpudl host:{key[0]} p{key[1]}"
            if seen_labels.setdefault(label, key) != key:
                label = f"{label} pid{key[2]}"
            out.append({
                "ph": "M", "pid": proc_ids[key], "name": "process_name",
                "args": {"name": label},
            })
        pid = proc_ids[key]
        tid = rec.get("tid", 0)
        if rec.get("kind") == "span":
            args = {
                k: v for k, v in rec.items()
                if k not in ("kind", "name", "cat", "ts", "dur", "id",
                             "parent", "host", "process", "pid", "tid")
            }
            out.append({
                "ph": "X", "name": rec["name"], "cat": rec["cat"],
                "ts": rec["ts"] * 1e6, "dur": rec["dur"] * 1e6,
                "pid": pid, "tid": tid, "args": args,
            })
        elif rec.get("kind") == "event":
            out.append({
                "ph": "i", "s": "t", "name": rec["name"],
                "cat": rec.get("cat", "event"), "ts": rec["ts"] * 1e6,
                "pid": pid, "tid": tid,
            })
    return out


def read_jsonl(path: str) -> list:
    """Load one span JSONL file back into record dicts.

    A TORN FINAL LINE is skipped, not raised: span files are written
    append-only by live processes, so a worker SIGKILLed mid-flush
    legitimately leaves a partial last record — and the distributor's
    merge runs exactly when workers died, where a JSONDecodeError would
    mask the real failure. Corruption anywhere else still raises."""
    records = []
    with open(path) as f:
        lines = [ln.strip() for ln in f]
    lines = [ln for ln in lines if ln]
    for idx, line in enumerate(lines):
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError:
            if idx == len(lines) - 1:
                break  # torn tail of a killed writer
            raise
    return records


def _span_key(record: dict, span_id) -> tuple:
    """A span id is unique in its process only: records merged from
    several (the distributor's ingest) are told apart by who recorded
    them."""
    return (record.get("host"), record.get("process"), record.get("pid"),
            span_id)


def without_same_category_children(spans: Iterable[dict]) -> list:
    """The spans with no span of their own category around them: a
    phase that is split into children (``decode_step`` into
    ``decode.dispatch`` and ``decode.readback``) counts once in a sum,
    a count or a histogram over its category, and so does one whose
    part lies under a phase of another category (the serve engine's
    ``admit`` inside the ``decode_step`` of a call that lands a step in
    flight, inside its ``engine_step``). Records without ids (older
    files) all pass."""
    spans = list(spans)
    by_key = {
        _span_key(s, s["id"]): s for s in spans if s.get("id") is not None
    }

    def enclosed(s: dict) -> bool:
        around = s
        while around.get("parent") is not None:
            around = by_key.get(_span_key(s, around["parent"]))
            if around is None:
                return False
            if around.get("cat") == s.get("cat"):
                return True
        return False

    return [s for s in spans if not enclosed(s)]


def self_seconds(spans: Iterable[dict]) -> list:
    """``(span, seconds)`` pairs: each span's duration less that of its
    direct children, so that the pairs sum to the time the outermost
    spans cover and every second is counted once, under the innermost
    span that held it."""
    spans = list(spans)
    children: dict = {}
    for s in spans:
        if s.get("parent") is not None:
            key = _span_key(s, s["parent"])
            children[key] = children.get(key, 0.0) + float(s["dur"])
    out = []
    for s in spans:
        own = float(s["dur"])
        if s.get("id") is not None:
            own -= children.get(_span_key(s, s["id"]), 0.0)
        out.append((s, max(0.0, own)))
    return out


# ---------------------------------------------------------------------------
# Module-level active recorder (the switch every instrumentation site
# consults).
# ---------------------------------------------------------------------------

_active: Optional[SpanRecorder] = None
# Where start-up sites record while ``_active`` is None; made at its
# first use (its host and process tags are read then).
_startup: Optional[StartupRecorder] = None
_atexit_registered = False
# TPUDL_OBS_DIR has been looked up and was not set: while this holds,
# ``active_recorder()`` is one global read.
_env_is_off = False


def default_span_path(directory: str) -> str:
    """Per-(host, process-index, os-pid) span file under ``directory`` —
    collision-free when a distributor parent and its rank-0 worker share
    the directory."""
    host = socket.gethostname()
    proc = env_int("TPUDL_PROCESS_ID", 0)
    return os.path.join(
        directory, f"spans-{host}-p{proc}-{os.getpid()}.jsonl"
    )


def enable(
    path: str,
    clock: Callable[[], float] = time.monotonic,
    process: Optional[int] = None,
) -> SpanRecorder:
    """Activate recording. ``path`` is a directory (a per-process
    ``spans-*.jsonl`` is created inside) or an explicit ``*.jsonl``
    file. Idempotent per path; re-enabling replaces the active
    recorder."""
    global _active, _atexit_registered
    if _active is not None:
        _active.close()
    file_path = (
        path if path.endswith(".jsonl") else default_span_path(path)
    )
    _active = SpanRecorder(file_path, clock=clock, process=process)
    if _startup is not None:
        # Start-up as it was recorded before anyone asked: both clocks
        # are time.monotonic, so ``ts`` stands as it is.
        for record in _startup.drain():
            _active.ingest(record)
    if not _atexit_registered:
        atexit.register(disable)
        _atexit_registered = True
    return _active


def disable() -> None:
    """Deactivate the active recorder and write out what it holds (no-op
    when inactive). The next ``active_recorder()`` looks the
    environment up once more."""
    global _active, _env_is_off
    _env_is_off = False
    if _active is not None:
        _active.close()
        _active = None


def active_recorder() -> Optional[SpanRecorder]:
    """The active recorder, auto-enabling from TPUDL_OBS_DIR on first
    call (mirrors fit()'s TPUDL_PROFILE_DIR idiom) — None when disabled,
    which is the branch every hot path takes for free: the environment
    is read once per process while off, not once per call."""
    global _env_is_off
    if _active is not None:
        return _active
    if _env_is_off:
        return None
    obs_dir = env_str("TPUDL_OBS_DIR")
    if obs_dir:
        return enable(obs_dir)
    _env_is_off = True
    return None


def span(name: str, cat: str = CAT_STEP, **attrs):
    """Module-level convenience: a recording context manager when
    observability is on, a shared no-op otherwise. Cold paths use this
    (ingest chunks, checkpoint saves); per-step loops use the explicit
    ``active_recorder()``/``record()`` form instead."""
    rec = active_recorder()
    if rec is None:
        return _NULL_SPAN
    return rec.span(name, cat, **attrs)


def startup_recorder() -> SpanRecorder:
    """Where a START-UP site records: the active recorder, else the
    bounded in-memory one that ``enable()`` later hands over. For the
    sites that run once a process and never in a steady state (module
    docstring); a hot path asks ``active_recorder()``."""
    global _startup
    rec = active_recorder()
    if rec is not None:
        return rec
    if _startup is None:
        _startup = StartupRecorder()
    return _startup


def startup_span(name: str, **attrs) -> _Span:
    """A start-up phase as a context manager: ``with
    startup_span("startup.pools") as phase: ...; phase.note(pages=n)``."""
    return startup_recorder().span(name, CAT_STARTUP, **attrs)


def startup_phase(name: str, result_attrs: Optional[Callable] = None):
    """Decorator: every call of the function is the start-up phase
    ``name``; ``result_attrs(result)`` gives the span its attributes."""

    def decorate(fn):
        @functools.wraps(fn)
        def phase(*args, **kwargs):
            with startup_span(name) as span:
                result = fn(*args, **kwargs)
                if result_attrs is not None:
                    span.note(**result_attrs(result))
                return result

        return phase

    return decorate
