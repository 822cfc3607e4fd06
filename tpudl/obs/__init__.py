"""L-cross runtime observability: spans, counters, goodput, reports.

Three observability layers exist in tpudl, deliberately split:

- ``tpudl.train.metrics``   — model-quality and throughput math
  (images/sec/chip, MFU): numbers ABOUT the training computation.
- ``tpudl.train.profiling`` — inside-the-step device view: parses the
  XLA trace ``jax.profiler.trace`` writes into per-op-category time /
  TFLOP/s / GB/s. Answers "where does the DEVICE step go".
- ``tpudl.obs`` (this package) — outside-the-step host view: where the
  rest of the RUN's wall-clock goes. Spans around the runtime's blocking
  calls (data wait, compiled-step dispatch, compile, checkpoint save)
  stream to JSONL; counters accumulate volumes (bytes ingested,
  saves); the goodput classifier turns them into "this run was 71%
  productive and host-3 was the straggler". Answers "where does the
  WALL-CLOCK go" — the question neither of the other two can.

The two trace views compose inside the profiler's trace: while
recording is on, every span that is begun is also open as a
``jax.profiler.TraceAnnotation("tpudl.<name>", span_id=<id>)``, so a
trace taken by anyone holds the program's phases on its own clock
beside the device's operations (``tpudl.obs.spans``; set
``TPUDL_OBS_DIR`` and ``TPUDL_PROFILE_DIR`` together).

Zero hard dependencies (stdlib only; JAX is touched only if already
imported), thread-safe, and free when disabled: every instrumentation
site guards on ``spans.active_recorder() is None``. Enable by setting
``TPUDL_OBS_DIR=/path`` (the profiler-hook idiom) or calling
``tpudl.obs.enable(path)``; report with
``python -m tpudl.obs.report /path``.

On top of the post-mortem stream sits the LIVE plane
(``tpudl.obs.exporter``, enabled via ``TPUDL_OBS_PORT``): a stdlib
HTTP server exposing ``/metrics`` (Prometheus text from the registry),
``/healthz`` (heartbeats + component health sources, probe-compatible
200/503), and ``/snapshot`` (registry + live goodput + the active span
-stream path) while the process runs — ``tpudl.obs.slo`` evaluates
declarative latency objectives with burn-rate alerting over it, and
``tpudl.obs.fleet`` aggregates N such processes into one labeled
fleet view (merged ``/metrics``, health rollup, cross-process trace
stitching) for the serve tier's autoscaler.

The serve tier additionally persists one versioned record per terminal
``Result`` into a durable crc-guarded request log
(``tpudl.obs.requestlog``, enabled via ``TPUDL_OBS_REQUEST_LOG``) —
the span stream dies with the process, the request log is the artifact
the continual-learning flywheel ingests — and the same records feed
the per-tenant metering plane (``tpudl.obs.metering``):
tenant-labeled Prometheus series and the ``report.py --tenants``
cost-attribution table.
"""

from tpudl.obs.counters import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    Registry,
    registry,
)
from tpudl.obs.exporter import (  # noqa: F401
    Heartbeat,
    ObsExporter,
    active_exporter,
    format_labels,
    health_snapshot,
    register_health_source,
    render_prometheus,
    start_exporter,
    stop_exporter,
    unregister_health_source,
)
from tpudl.obs.fleet import (  # noqa: F401
    FleetMonitor,
    render_fleet_prometheus,
)
from tpudl.obs.goodput import (  # noqa: F401
    classify,
    classify_by_process,
    format_goodput,
)
from tpudl.obs.metering import (  # noqa: F401
    TenantMeter,
    meter,
    render_tenants,
)
from tpudl.obs.requestlog import (  # noqa: F401
    SCHEMA_VERSION,
    RequestLogCorruptError,
    RequestLogReader,
    RequestLogWriter,
    build_record,
    log_result,
    read_request_log,
)
from tpudl.obs.report import (  # noqa: F401
    build_fleet_report,
    build_report,
    build_request_timeline,
    format_fleet_report,
    format_report,
    format_request_timeline,
    load_records,
)
from tpudl.obs.slo import Objective, SloMonitor  # noqa: F401
from tpudl.obs.spans import (  # noqa: F401
    SpanRecorder,
    active_recorder,
    chrome_trace_events,
    disable,
    enable,
    read_jsonl,
    span,
)
