"""Central declaration table for every ``TPUDL_*`` knob and the metric
naming contract — the single source of truth the registry linter
(tpudl.analysis.lint) enforces against the tree.

Every environment variable the framework reads is DECLARED here with
its type, default, and one-line doc; runtime code reads knobs through
the typed accessors (``env_str`` / ``env_int`` / ``env_float`` /
``env_flag`` / ``env_require``) instead of raw ``os.environ``. The
linter flags any raw ``os.environ["TPUDL_*"]`` read outside this
module, any ``TPUDL_*`` literal that is not declared here, and any
declared knob missing from the README knob table (which
``scripts/lint_tpudl.py --knob-table`` generates from this table, so
docs can never drift from code).

Accessor semantics match the idioms they replaced: an UNSET or
EMPTY-STRING variable reads as the default (``TPUDL_X= python ...``
disables a knob the same way unsetting it does), malformed numerics
raise ``ValueError`` naming the variable, and flags accept
``1/true/yes/on`` (case-insensitive).

Stdlib-only: this module is imported by ``tpudl.obs.counters`` and the
runtime bootstrap, so it must not import jax or any tpudl subsystem.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, Optional

#: Prometheus-conformant metric name: what ``registry().counter(name)``
#: / ``.gauge`` / ``.histogram`` literals must match so the /metrics
#: exposition needs no sanitizing (PR-6 conformance contract — the
#: exporter appends ``_sum`` / ``_count`` / ``_heartbeat_age_s``
#: suffixes, so names stay lower_snake_case with no leading digit).
METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")

#: Characters legal ANYWHERE inside a metric name — the rule applied to
#: the static fragments of f-string metric names (the dynamic parts are
#: runtime-sanitized by the call sites, e.g. router's _metric_suffix).
METRIC_FRAGMENT_RE = re.compile(r"^[a-z0-9_]*$")

_FLAG_TRUTHY = ("1", "true", "yes", "on")


@dataclasses.dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str
    kind: str  # "int" | "float" | "str" | "flag" | "path"
    default: object
    help: str
    #: Owning module (dotted), for the generated table.
    owner: str
    #: True for process-coordination variables SET by the framework
    #: itself (TpuDistributor worker bootstrap) rather than operator
    #: tuning knobs.
    internal: bool = False


KNOBS: Dict[str, Knob] = {}


def _declare(
    name: str,
    kind: str,
    default,
    help: str,
    owner: str,
    internal: bool = False,
) -> None:
    if name in KNOBS:
        raise ValueError(f"knob {name!r} declared twice")
    if not name.startswith("TPUDL_"):
        raise ValueError(f"knob {name!r} must start with TPUDL_")
    KNOBS[name] = Knob(name, kind, default, help, owner, internal)


# --- observability -------------------------------------------------------
_declare("TPUDL_OBS_DIR", "path", None,
         "Span/counter JSONL output directory; set = recording on.",
         "tpudl.obs.spans")
_declare("TPUDL_OBS_PORT", "int", None,
         "Live telemetry HTTP port (/metrics, /healthz, /snapshot); "
         "0 = ephemeral port (test idiom); unset = exporter off.",
         "tpudl.obs.exporter")
_declare("TPUDL_OBS_HOST", "str", "127.0.0.1",
         "Exporter bind host; loopback by default (endpoints are "
         "unauthenticated), 0.0.0.0 opts into container scraping.",
         "tpudl.obs.exporter")
_declare("TPUDL_OBS_HIST_WINDOW", "int", 65_536,
         "Histogram rolling-window size (bounded memory; cumulative "
         "count/sum are kept regardless).",
         "tpudl.obs.counters")
_declare("TPUDL_OBS_HEARTBEAT_STALE_S", "float", 60.0,
         "Heartbeat staleness floor for /healthz (the effective "
         "threshold is cadence-adaptive: max(floor, 5x last interval)).",
         "tpudl.obs.exporter")
_declare("TPUDL_OBS_REQUEST_LOG", "path", None,
         "Durable request-log output directory (crc-guarded rotated "
         "JSONL segments, one record per terminal serve Result); "
         "set = logging on.",
         "tpudl.obs.requestlog")
_declare("TPUDL_OBS_REQUEST_LOG_SEGMENT_BYTES", "int", 1_048_576,
         "Request-log segment rotation threshold in bytes (each "
         "rotation commits the segment with its crc32 in the name).",
         "tpudl.obs.requestlog")
_declare("TPUDL_OBS_REQUEST_LOG_QUEUE", "int", 1024,
         "Request-log writer queue depth; overflow drops records "
         "(counted in requestlog_records_dropped) instead of blocking "
         "the decode loop.",
         "tpudl.obs.requestlog")
_declare("TPUDL_OBS_REQUEST_LOG_SAMPLES", "flag", False,
         "Capture prompt/output token ids on COMPLETED request-log "
         "records (schema v2 optional fields — the flywheel's "
         "training feedstock); off = records carry metrics only.",
         "tpudl.obs.requestlog")
_declare("TPUDL_PROFILE_DIR", "path", None,
         "jax.profiler trace output directory for fit(profile=...).",
         "tpudl.train.loop")

# --- data / dispatch -----------------------------------------------------
_declare("TPUDL_PREFETCH_DEPTH", "int", None,
         "Pin the device prefetch queue depth and disable the "
         "autotuner; unset = autotune.",
         "tpudl.data.prefetch")
_declare("TPUDL_OVERLAP_BUCKET_MB", "float", None,
         "Gradient-accumulation overlap bucket size in MiB; 0 "
         "disables bucketing; unset = auto (4 MiB buckets on "
         "multi-shard meshes).",
         "tpudl.parallel.overlap")

# --- training precision --------------------------------------------------
_declare("TPUDL_TRAIN_PRECISION", "str", None,
         "Mixed-precision training policy preset (f32 | bf16 | fp8), "
         "resolved by policy_from_env; unset = no policy.",
         "tpudl.train.precision")
_declare("TPUDL_FP8_AMAX_WINDOW", "int", 16,
         "fp8 delayed-scaling amax-history ring length per tensor "
         "site (larger = smoother scales, slower reaction to "
         "distribution shift).",
         "tpudl.ops.fp8_dot")
_declare("TPUDL_LOSS_SCALE_INIT", "float", 32768.0,
         "Dynamic loss-scale starting value (power of two; backs off "
         "on nonfinite grads, grows back after a clean streak).",
         "tpudl.train.precision")
_declare("TPUDL_LOSS_SCALE_GROWTH_INTERVAL", "int", 2000,
         "Consecutive finite steps before the dynamic loss scale "
         "doubles (capped at 2^24).",
         "tpudl.train.precision")

# --- serving -------------------------------------------------------------
_declare("TPUDL_SERVE_SLOTS", "int", 4,
         "Default decode slot count for ServeSession.from_model "
         "(artifact sessions carry theirs in the program batch dim).",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_QUEUE_DEPTH", "int", 256,
         "Admission queue capacity; overflow sheds shed_capacity.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_PAGE_SIZE", "int", 16,
         "Paged KV page size in tokens.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_KV_DTYPE", "str", None,
         "Paged KV storage dtype (int8 = quantized pages, ~3.5x "
         "resident slots/byte); unset = the model dtype.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_WEIGHT_DTYPE", "str", None,
         "Post-training weight quantization for from_model (int8 | "
         "fp8); unset = full precision.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_PREFIX_SHARE", "flag", False,
         "Radix prefix-sharing KV: COW page sharing + chunked suffix "
         "prefill.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_SPEC_K", "int", None,
         "Speculative-decoding window (draft proposes k tokens per "
         "verify dispatch); 0/unset = off.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_LORA_RANK", "int", None,
         "Multi-tenant adapter serving: per-tenant LoRA rank budget "
         "(r_max, the adapter page-table width); unset = the largest "
         "rank among the registered adapters.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_LORA_PAGES", "int", None,
         "Multi-tenant adapter serving: adapter pool size in pages "
         "(one page = one rank unit across every site; page 0 is the "
         "all-zero page); unset = 64 full-rank adapters + 1.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_LORA_DTYPE", "str", None,
         "Multi-tenant adapter serving: adapter page storage (int8 = "
         "quantized pages with per-page f32 dequant scales); unset = "
         "f32 pages.",
         "tpudl.serve.api")
_declare("TPUDL_SERVE_TENANT_QUOTA_TOKENS", "int", None,
         "Router default per-tenant in-flight token quota (sum of "
         "outstanding max_new_tokens); over it a tenant's requests "
         "shed as shed_quota — the isolation lever; unset = "
         "unlimited. Per-tenant overrides via Router(tenant_classes).",
         "tpudl.serve.router")
_declare("TPUDL_SERVE_MAX_FAILOVERS", "int", 3,
         "Per-request failover-resubmission cap: a request ping-"
         "ponging across successively dying replicas sheds as "
         "failover_exhausted instead of looping forever (migrations "
         "resume state and do not count).",
         "tpudl.serve.router")

# --- flywheel ------------------------------------------------------------
_declare("TPUDL_FLYWHEEL_MIN_RECORDS", "int", 8,
         "New completed records a tenant must accrue (TenantMeter "
         "delta since its last refresh) before the controller "
         "triggers a LoRA refresh.",
         "tpudl.flywheel.loop")
_declare("TPUDL_FLYWHEEL_INTERVAL_S", "float", 30.0,
         "FlywheelController.watch() poll cadence in seconds.",
         "tpudl.flywheel.loop")
_declare("TPUDL_FLYWHEEL_PRECISION", "str", "bf16",
         "RefreshTrainer precision policy preset (f32 | bf16 | fp8); "
         "fp8 opens the fp8-base x LoRA-factor training cell.",
         "tpudl.flywheel.refresh")
_declare("TPUDL_FLYWHEEL_HOLDOUT_FRAC", "float", 0.25,
         "Fraction of each refresh's sample stream held OUT of "
         "training and used as the promotion gate's eval slice "
         "(0 disables the gate).",
         "tpudl.flywheel.loop")
_declare("TPUDL_FLYWHEEL_GATE_TOL", "float", 0.0,
         "Promotion gate tolerance: refreshed factors publish only if "
         "held-out loss <= prior-factor loss + tol; failures roll "
         "back to the prior adapter.",
         "tpudl.flywheel.loop")

# --- fault tolerance / chaos --------------------------------------------
_declare("TPUDL_FT_GRACE_S", "float", 15.0,
         "Preemption grace window (SIGTERM -> emergency checkpoint -> "
         "hard-exit watchdog).",
         "tpudl.ft.preemption")
_declare("TPUDL_FT_MAX_RESTARTS", "int", 3,
         "Supervisor cohort-restart retry budget.",
         "tpudl.ft.supervisor")
_declare("TPUDL_FT_BACKOFF_S", "float", 1.0,
         "Initial supervisor restart backoff.",
         "tpudl.ft.supervisor")
_declare("TPUDL_FT_MAX_BACKOFF_S", "float", 30.0,
         "Supervisor restart backoff cap.",
         "tpudl.ft.supervisor")
_declare("TPUDL_CHAOS_KILL_AT_STEP", "int", None,
         "Fault injection: SIGKILL the matching rank at step N.",
         "tpudl.ft.chaos")
_declare("TPUDL_CHAOS_KILL_RANK", "int", None,
         "Fault injection: rank to kill (unset = rank 0).",
         "tpudl.ft.chaos")
_declare("TPUDL_CHAOS_ONCE_DIR", "path", None,
         "Fault injection: marker directory making each rank's kill "
         "fire exactly once across supervised restarts.",
         "tpudl.ft.chaos")
_declare("TPUDL_CHAOS_IO_DELAY_S", "float", 0.0,
         "Fault injection: added per-write delay in the checkpoint "
         "writer (slow-disk simulation).",
         "tpudl.ft.chaos")
_declare("TPUDL_SERVE_CHAOS_KILL_STEP", "int", None,
         "Serving chaos: raise ChaosKill in Engine.step at decode "
         "step N — the replica driver thread crashes (resubmit-"
         "fallback path; KV unrecoverable).",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_PREEMPT_STEP", "int", None,
         "Serving chaos: raise ChaosPreempt at decode step N — the "
         "replica turns lame duck (unready, thread answers) and its "
         "seated KV must migrate to survivors.",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_FREEZE_STEP", "int", None,
         "Serving chaos: freeze Engine.step at decode step N for "
         "TPUDL_SERVE_CHAOS_FREEZE_S seconds (stale-heartbeat path).",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_FREEZE_S", "float", 1.0,
         "Serving chaos: freeze duration for the step freezer.",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_ONCE_DIR", "path", None,
         "Serving chaos: marker directory making each injected fault "
         "fire exactly once across every engine in the process (kill "
         "ONE replica, not all).",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_SCRAPE_FAIL_N", "int", 0,
         "Serving chaos: blackhole the next N FleetMonitor scrape "
         "attempts (install_scrape_chaos; retries consume the budget).",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_SCRAPE_DELAY_S", "float", 0.0,
         "Serving chaos: added delay per FleetMonitor scrape attempt.",
         "tpudl.serve.chaos")
_declare("TPUDL_SERVE_CHAOS_FLIP_MIGRATION", "flag", False,
         "Serving chaos: flip one bit of every migration payload in "
         "transfer — the crc must catch it and shed the request as "
         "failed, never resume it.",
         "tpudl.serve.chaos")

# --- fleet (pod-real meshes / chip mover) --------------------------------
_declare("TPUDL_FLEET_TRANSPORT_HOST", "str", None,
         "Bind/connect host for cross-process MigrationEndpoints "
         "(unset = 127.0.0.1).",
         "tpudl.fleet.transport")
_declare("TPUDL_FLEET_TRANSPORT_TIMEOUT_S", "float", 30.0,
         "Socket send/recv timeout for migration transfers.",
         "tpudl.fleet.transport")
_declare("TPUDL_FLEET_SPOOL_DIR", "path", None,
         "Default directory for FileChannel() spool-file migration "
         "(shared-filesystem transport).",
         "tpudl.fleet.transport")
_declare("TPUDL_FLEET_BURN_SUSTAIN_S", "float", 2.0,
         "How long SLO burn must persist before the chip mover "
         "preempts training and lends devices to serving.",
         "tpudl.fleet.chipmover")
_declare("TPUDL_FLEET_CLEAR_SUSTAIN_S", "float", 5.0,
         "How long burn must stay clear before borrowed devices "
         "drain back to training.",
         "tpudl.fleet.chipmover")
_declare("TPUDL_FLEET_COOLDOWN_S", "float", 2.0,
         "Minimum gap between chip moves (flap damping, the "
         "Autoscaler's cooldown applied to device moves).",
         "tpudl.fleet.chipmover")
_declare("TPUDL_FLEET_SERVE_SHARE", "float", 0.5,
         "Fraction of the training cohort's devices a move lends to "
         "the borrowed serving replica (training keeps >= 1).",
         "tpudl.fleet.chipmover")

# --- analysis ------------------------------------------------------------
_declare("TPUDL_DEBUG_LOCK_ORDER", "flag", False,
         "Wrap subsystem locks (router/replica/fleet) in the ordered-"
         "lock monitor: every acquisition is checked against the "
         "statically derived lock order and the live wait-for graph; "
         "an inversion raises LockOrderViolation at the acquire site.",
         "tpudl.analysis.concurrency")

# --- process coordination (set by TpuDistributor, not operators) ---------
_declare("TPUDL_COORDINATOR", "str", None,
         "jax.distributed coordinator address for spawned workers.",
         "tpudl.runtime.distributor", internal=True)
_declare("TPUDL_NUM_PROCESSES", "int", None,
         "World size handed to spawned workers.",
         "tpudl.runtime.distributor", internal=True)
_declare("TPUDL_PROCESS_ID", "int", 0,
         "This worker's rank (also tags span streams).",
         "tpudl.runtime.distributor", internal=True)


class UnknownKnobError(KeyError):
    """A knob read that is not declared in the table — declare it in
    tpudl.analysis.registry before reading it."""


def _lookup(name: str) -> Knob:
    knob = KNOBS.get(name)
    if knob is None:
        raise UnknownKnobError(
            f"{name!r} is not a declared TPUDL knob — add it to "
            f"tpudl.analysis.registry.KNOBS"
        )
    return knob


def env_raw(name: str) -> Optional[str]:
    """The raw string value, or None when unset OR empty (an empty
    assignment disables a knob the same way unsetting it does)."""
    _lookup(name)
    raw = os.environ.get(name)
    return raw if raw else None


def env_str(name: str, default: Optional[str] = None) -> Optional[str]:
    raw = env_raw(name)
    return raw if raw is not None else default


def env_require(name: str) -> str:
    """A coordination variable the caller cannot run without (worker
    bootstrap); raises KeyError naming it when missing."""
    raw = env_raw(name)
    if raw is None:
        raise KeyError(f"required environment variable {name} is not set")
    return raw


def env_int(
    name: str,
    default: Optional[int] = None,
    min_value: Optional[int] = None,
    required: bool = False,
) -> Optional[int]:
    raw = env_raw(name)
    if raw is None:
        if required:
            raise KeyError(
                f"required environment variable {name} is not set"
            )
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be an integer, got {raw!r}"
        ) from None
    if min_value is not None and value < min_value:
        raise ValueError(f"{name} must be >= {min_value}, got {value}")
    return value


def env_float(
    name: str, default: Optional[float] = None
) -> Optional[float]:
    raw = env_raw(name)
    if raw is None:
        return default
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            f"{name} must be a number, got {raw!r}"
        ) from None


def env_flag(name: str) -> bool:
    raw = env_raw(name)
    return raw is not None and raw.strip().lower() in _FLAG_TRUTHY


def knob_table_markdown(include_internal: bool = True) -> str:
    """The env-knob reference table, generated from the declaration
    table — ``scripts/lint_tpudl.py --knob-table`` prints this, and the
    README embeds it between ``<!-- knob-table:begin/end -->`` markers
    (tests/test_analysis.py asserts they match, so the docs cannot
    drift from the code)."""
    lines = [
        "| Knob | Type | Default | What it does |",
        "| --- | --- | --- | --- |",
    ]
    internal_lines: list = []
    for name in sorted(KNOBS):
        knob = KNOBS[name]
        default = "unset" if knob.default is None else str(knob.default)
        row = (
            f"| `{knob.name}` | {knob.kind} | {default} | "
            f"{knob.help} (`{knob.owner}`) |"
        )
        (internal_lines if knob.internal else lines).append(row)
    if include_internal and internal_lines:
        lines.append(
            "\nSet by the framework itself (TpuDistributor worker "
            "bootstrap), not operator knobs:\n"
        )
        lines.append("| Variable | Type | Default | What it does |")
        lines.append("| --- | --- | --- | --- |")
        lines.extend(internal_lines)
    return "\n".join(lines) + "\n"
