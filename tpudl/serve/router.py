"""Multi-replica serving router: load balancing, disaggregation, SLO shed.

One ServeSession is one engine over one (local) mesh. Serving "heavy
traffic from millions of users" (ROADMAP north-star, item 2) needs N of
them behind one front door. This module is that front door, built
entirely from contracts earlier PRs shipped:

- **Replica**: one ServeSession driven by its own thread (on a real
  pod, one replica = one process mesh; in-process they are threads
  whose device dispatches overlap). The thread drains an inbox,
  steps the engine, harvests Results, and PUBLISHES a health snapshot —
  the same payload the PR-6 ``/healthz`` endpoint serves under
  ``sources.serve_engine``. The router reads that snapshot directly,
  or SCRAPES it over HTTP (``health_url``) when the replica runs
  behind a real exporter — replica choice is driven by scraped
  slot/queue state either way.
- **Placement**: sticky first (``Request.session_key`` pins a stream
  of requests to one replica — KV/prefix affinity), then least-loaded
  by scraped ``(slots_busy + queue_depth) / (num_slots +
  queue_capacity)``. Unready replicas (scrape failed, 503, or
  ``healthy: false``) take no new work.
- **Failover, migration-first**: when a replica goes unready
  mid-stream, every request assigned to it that has not produced a
  Result leaves it. If the replica's engine thread still answers (lame
  duck, SLO 503, operator preemption), seated requests' page-granular
  KV state is EXPORTED (crc-guarded payloads, tpudl.serve.cache) and
  resumed mid-stream on survivors — zero re-prefill, byte-exact
  continuation. A crashed thread means payloads are unavailable: the
  request resubmits from scratch (greedy requests produce identical
  tokens, sampled ones reproduce via the per-request fold_in stream),
  capped per request by ``TPUDL_SERVE_MAX_FAILOVERS`` — a request
  ping-ponging across successively dying replicas sheds as
  ``failover_exhausted`` instead of looping forever. Late results from
  a failed replica are ignored: the assignment map names the one
  replica a Result is accepted from.
- **Prefill/decode disaggregation**: with ``PrefillWorker``s attached,
  the router routes admitted requests through dedicated prefill
  replicas (batch-1 program only) which hand ``(row cache, first
  token)`` to the least-loaded DECODE replica's ``prefill_inbox`` —
  the same mid-stream insertion contract continuous batching already
  relies on. Decode replicas never pay a prefill dispatch between
  decode steps, which is the TPOT win disaggregation exists for.
- **SLO-aware admission**: the router subscribes every replica's
  SloMonitor. While any objective burns, requests in the best-effort
  class (``priority > shed_priority_above``) are shed AT THE ROUTER
  (``shed_slo``) — latency-sensitive work keeps flowing to replicas
  that are not burning — and the ``serve_router_autoscale_hint`` gauge
  publishes the scale-out signal (burning replicas + unready
  replicas): an autoscaler that adds replicas drives it back to 0.

Observability: per-replica gauges (``serve_replica_<name>_slots_busy``
/ ``_queue_depth`` / ``_ready``), ``serve_router_ready_replicas``,
the autoscale hint, and ``serve_router_requests_{routed,failed_over}``
counters; a ``serve_router`` health source reports ready/total (ready
== 0 is unhealthy — the router itself should probe 503).

Thread model: replica threads own their sessions EXCLUSIVELY; the
router talks to them only through thread-safe deques and published
snapshots, and does its own scraping/failover inline on a time gate
inside submit()/poll()/collect() — no router-side polling thread.
"""

from __future__ import annotations

import dataclasses
import json
import threading
import time
import urllib.request
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence

from tpudl.analysis.concurrency import maybe_wrap_locks
from tpudl.analysis.registry import env_int
from tpudl.obs import metering, registry, requestlog
from tpudl.obs.spans import active_recorder
from tpudl.serve import chaos as serve_chaos
from tpudl.serve.api import (
    Request,
    Result,
    ServeSession,
    left_pad,
    validate_request,
)
from tpudl.serve.queue import CAT_SERVE_REQUEST, _Entry


def _metric_suffix(name: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in str(name))


class Replica:
    """One serving replica: a ServeSession plus the thread that drives
    it. The session is touched ONLY by the replica thread; the router
    communicates through ``submit()`` (thread-safe inbox), ``take()``
    (harvested results), and ``scrape()`` (published health)."""

    def __init__(
        self,
        name: str,
        session: ServeSession,
        health_url: Optional[str] = None,
        health_fn: Optional[Callable[[], dict]] = None,
        idle_sleep_s: float = 0.0005,
        scrape_timeout_s: float = 1.0,
        stale_after_s: Optional[float] = None,
    ):
        self.name = str(name)
        self.session = session
        self.health_url = health_url
        self.health_fn = health_fn
        self.idle_sleep_s = idle_sleep_s
        self.scrape_timeout_s = scrape_timeout_s
        #: In-process stale-heartbeat bound: a loop that has not
        #: published for this long (frozen mid-step) scrapes UNREADY —
        #: the in-process analog of the exporter's cadence-adaptive
        #: /healthz staleness. None (default) disables; size it well
        #: above one engine step.
        self.stale_after_s = stale_after_s
        self._inbox: deque = deque()
        self._results: Dict[Any, Result] = {}
        self._results_lock = threading.Lock()
        #: Router->replica-thread command queue (migration pulls): the
        #: session is thread-exclusive, so KV exports run ON the loop
        #: thread and the router waits on the command's event.
        self._control: deque = deque()
        self._published_at = time.monotonic()
        #: Lame duck (chaos preemption notice / operator): scrapes
        #: unready so the router stops placing and pulls our work, but
        #: the thread stays alive to answer the migration command —
        #: unlike ``failed``, which exits the loop (crash semantics).
        self.lame = False
        maybe_wrap_locks(self)
        #: rid -> measured inbox wait (seconds), popped when the result
        #: is harvested: the router-door -> engine-admission hop of the
        #: stitched fleet trace (router TTFT = inbox wait + engine
        #: TTFT; both are durations, so the sum survives cross-process
        #: clock skew).
        self._inbox_waits: Dict[Any, float] = {}
        self._published: dict = {"healthy": True, **session.engine.health()}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.failed = False  # a test/chaos hook: failed => loop exits

    # -- router-facing surface (thread-safe) ---------------------------

    def submit(
        self, request: Request, deadline_at: Optional[float] = None
    ) -> None:
        """Queue a request for the replica thread. ``deadline_at`` is
        the ABSOLUTE deadline stamped at the router door — the replica
        evaluates the remaining budget when it pops the inbox, so time
        spent queued here counts against the client's deadline instead
        of restarting it."""
        self._inbox.append((request, deadline_at, time.monotonic()))

    def seat_prefilled(self, item) -> None:
        """Queue an externally prefilled request (engine._Prefilled)
        straight onto the engine's disaggregation inbox."""
        self.session.engine.prefill_inbox.append(item)

    def seat_migrated(self, rid, payload, lease=None) -> None:
        """Queue a migrated-in request's payload onto the engine's
        migration inbox. The crc is verified ON the engine thread, so
        a corrupted transfer becomes that request's ``failed`` Result
        instead of a router-thread crash."""
        from tpudl.serve.engine import _Migrated

        self.session.engine.migrate_inbox.append(
            _Migrated(rid, payload, lease)
        )

    def request_migration(
        self, skip_map: Dict[Any, int], timeout_s: float
    ) -> Optional[dict]:
        """Ask the replica THREAD to hand over every outstanding
        request: seated slots exported as crc-guarded KV payloads
        (``skip_map``: rid -> reference-prefix tokens the router
        already leased on the chosen target), waiting work returned as
        plain Requests. Returns None when the thread is gone or does
        not answer within ``timeout_s`` — the crash half of the
        contract: payload unavailable, the caller falls back to
        resubmission."""
        if self._thread is None or not self._thread.is_alive():
            return None
        if timeout_s <= 0:
            return None  # no budget: don't enqueue work we won't read
        box = {
            "done": threading.Event(),
            "lock": threading.Lock(),
            "claimed": False,
            "abandoned": False,
            "skip": dict(skip_map),
            "payloads": {},
            "requests": {},
        }
        self._control.append(box)
        if not box["done"].wait(timeout_s):
            # The claim handshake makes abandonment safe: the loop
            # CLAIMS the box (under its lock) before touching any
            # state, so either we abandon an unclaimed box (the loop
            # will skip it — frozen/dead thread, nothing was moved) or
            # the export is actively running and we wait it out —
            # exports free source slots, and an unread payload would
            # be a silently lost request.
            with box["lock"]:
                if not box["claimed"]:
                    box["abandoned"] = True
                    return None
            if not box["done"].wait(max(timeout_s, 5.0)):
                return None  # export itself hung: give up loudly
        return box

    def _migrate_out(self, box: dict) -> None:
        """Replica-thread half of a migration pull: everything
        outstanding leaves this replica. Waiting work (inbox, admission
        queue, disaggregation inbox) returns as Requests — nothing is
        seated, nothing to export; seated slots export page-granular
        payloads (skipping speculating engines, which the caller
        resubmits instead); already-queued migrate-inbox payloads
        forward as-is, their local leases released."""
        engine = self.session.engine
        with box["lock"]:
            if box.get("abandoned"):
                return  # the router gave up waiting: touch nothing
            box["claimed"] = True  # from here the router waits us out
        while self._inbox:
            request, _deadline_at, _enqueued_at = self._inbox.popleft()
            box["requests"][request.request_id] = request
        for entry in engine.queue.drain_all():
            box["requests"][entry.request.request_id] = entry.request
        while engine.prefill_inbox:
            item = engine.prefill_inbox.popleft()
            box["requests"][item.entry.request.request_id] = (
                item.entry.request
            )
        while engine.migrate_inbox:
            item = engine.migrate_inbox.popleft()
            if item.lease is not None:
                engine.cache.release_lease(item.lease[1])
            try:
                meta = item.ensure_parsed()
            except Exception:
                box["payloads"][item.rid] = item.payload
                continue  # corrupt either way: the next engine sheds it
            if int(meta.get("skip_tokens", 0)) > 0:
                # A reference-skipped payload is whole ONLY against the
                # tree it was probed on (whose lease we just released):
                # forwarding it would make the next target refuse it.
                # Hand back the Request instead — resubmission is the
                # recoverable path.
                box["requests"][item.rid] = Request(**meta["request"])
            else:
                box["payloads"][item.rid] = item.payload
        # The decode step in flight lands before the seats are listed: a
        # request it finishes leaves as its Result, not as a payload.
        engine.land()
        for rid in [
            s.request.request_id for s in engine._slots if s is not None
        ]:
            try:
                payload = engine.export_request(
                    rid, box["skip"].get(rid, 0)
                )
            except Exception:
                payload = None  # caller resubmits from scratch
            if payload is not None:
                box["payloads"][rid] = payload
        for rid in list(box["payloads"]) + list(box["requests"]):
            self.session._pending_ids.discard(rid)
            self._inbox_waits.pop(rid, None)

    def take(self) -> Dict[Any, Result]:
        """Hand over every Result harvested since the last take()."""
        with self._results_lock:
            out = self._results
            self._results = {}
        return out

    def scrape(self) -> dict:
        """The router's view of this replica's health: the published
        engine snapshot, or — when ``health_url`` is set — a real HTTP
        GET of a ``/healthz`` endpoint (non-200, unreachable, or
        ``healthy: false`` all read as unready). ``health_fn`` overrides
        both (test seam / custom probes)."""
        if self.failed:
            return {"healthy": False, "error": "replica failed"}
        if self.lame:
            # Preempted: out of service (no new placements, failover
            # pulls our work) but the thread still answers exports.
            return {
                **self._published,
                "healthy": False,
                "error": "replica preempted (lame duck)",
            }
        if (
            self.stale_after_s is not None
            and self._thread is not None
            and time.monotonic() - self._published_at > self.stale_after_s
        ):
            # Frozen mid-step: the loop stopped publishing. The last
            # snapshot may claim healthy — staleness overrides it.
            return {
                **self._published,
                "healthy": False,
                "error": (
                    f"stale heartbeat (no publish for "
                    f"> {self.stale_after_s}s)"
                ),
            }
        if self.health_fn is not None:
            try:
                return dict(self.health_fn())
            except Exception as e:
                return {"healthy": False, "error": f"{type(e).__name__}: {e}"}
        if self.health_url is not None:
            try:
                with urllib.request.urlopen(
                    self.health_url, timeout=self.scrape_timeout_s
                ) as resp:
                    payload = json.loads(resp.read().decode())
            except urllib.error.HTTPError as e:
                # 503 carries the health JSON in its body; surface it.
                try:
                    payload = json.loads(e.read().decode())
                except Exception:
                    payload = {}
                payload["healthy"] = False
                payload.setdefault("error", f"HTTP {e.code}")
                return payload
            except Exception as e:
                return {"healthy": False, "error": f"{type(e).__name__}: {e}"}
            # A full /healthz document: the engine's state lives under
            # sources.serve_engine; overall healthy gates readiness.
            engine = payload.get("sources", {}).get("serve_engine", {})
            out = {**self._published, **engine}
            out["healthy"] = bool(payload.get("healthy", True))
            return out
        return dict(self._published)

    @property
    def load(self) -> float:
        """Normalized busyness from the last scrape/publish — the
        least-loaded placement key."""
        h = self._published
        cap = max(
            1, h.get("num_slots", 1) + h.get("queue_capacity", 0)
        )
        return (h.get("slots_busy", 0) + h.get("queue_depth", 0)) / cap

    def prefix_match_len(self, input_ids) -> int:
        """Longest prompt prefix (tokens) this replica's radix tree
        already holds — 0 when prefix sharing is off. Read-only and
        lock-guarded inside the tree, so the router probes it from its
        own thread while the replica thread serves."""
        try:
            cache = self.session.engine.cache
            return int(getattr(cache, "prefix_match_len")(input_ids)) if (
                getattr(cache, "prefix_share", False)
            ) else 0
        except Exception:
            return 0

    def adapter_resident_since(self, tenant) -> Optional[float]:
        """When this replica's adapter pool loaded ``tenant``'s LoRA
        pages (None = not resident / no pool) — the router's
        adapter-affinity probe, the prefix-affinity shape applied to
        adapters: the replica holding the adapter LONGEST wins ties,
        so a tenant's stream keeps hitting warm pages instead of
        forcing a load on every replica. Read-only and lock-guarded
        inside the pool."""
        try:
            pool = self.session.engine.adapter_pool
            return (
                pool.resident_since(tenant) if pool is not None else None
            )
        except Exception:
            return None

    def serves_tenant(self, tenant) -> bool:
        """Whether this replica's pool can serve ``tenant`` at all
        (registered + rank fits the pool) — the migration-target
        filter: resuming a tenant's decode on a replica without its
        adapter would silently change tokens."""
        if tenant is None:
            return True
        try:
            pool = self.session.engine.adapter_pool
            return pool is not None and pool.can_ever_seat(tenant)
        except Exception:
            return False

    # -- the replica thread --------------------------------------------

    def start(self) -> "Replica":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, name=f"tpudl-replica-{self.name}",
            daemon=True,
        )
        self._thread.start()
        return self

    def stop(self, join_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=join_s)
            self._thread = None

    def _loop(self) -> None:
        session = self.session
        engine = session.engine
        error = "replica stopped"
        try:
            while not self._stop.is_set() and not self.failed:
                worked = False
                while self._control:
                    # Migration pull: the router is waiting on the
                    # command's event — answer before anything else
                    # (and ALWAYS set it, or the router times out and
                    # double-places the work it thinks we kept).
                    box = self._control.popleft()
                    try:
                        self._migrate_out(box)
                    finally:
                        box["done"].set()
                    worked = True
                while self._inbox:
                    request, deadline_at, enqueued_at = self._inbox.popleft()
                    inbox_wait = max(0.0, time.monotonic() - enqueued_at)
                    self._inbox_waits[request.request_id] = inbox_wait
                    rec = active_recorder()
                    if rec is not None:
                        # The replica-inbox hop of the stitched fleet
                        # trace: a DURATION, so report.py can sum it
                        # with the engine's hops without comparing this
                        # process's clock to the router's.
                        rec.event(
                            "replica_dequeue", CAT_SERVE_REQUEST,
                            request_id=request.request_id,
                            replica=self.name,
                            inbox_wait_s=inbox_wait,
                        )
                    if deadline_at is not None:
                        remaining = deadline_at - time.monotonic()
                        if remaining <= 0:
                            # Deadline expired while queued in THIS
                            # inbox: shed, never start (AdmissionQueue's
                            # guarantee, kept across the router hop).
                            wait = 0.0
                            if request.deadline_s is not None:
                                wait = max(
                                    0.0,
                                    time.monotonic()
                                    - (deadline_at - request.deadline_s),
                                )
                            self._inbox_waits.pop(request.request_id, None)
                            with self._results_lock:
                                self._results[request.request_id] = Result(
                                    request_id=request.request_id,
                                    tokens=[],
                                    finish_reason="shed_timeout",
                                    queue_wait_s=wait,
                                )
                            registry().counter(
                                "serve_requests_shed_timeout"
                            ).inc()
                            if rec is not None:
                                # Close the trace here: this Result
                                # never reaches the engine, so no other
                                # completion event will.
                                rec.event(
                                    "request_complete",
                                    CAT_SERVE_REQUEST,
                                    request_id=request.request_id,
                                    finish_reason="shed_timeout",
                                    queue_wait_s=wait, num_tokens=0,
                                    shed_by="replica_inbox",
                                )
                            requestlog.log_result(requestlog.build_record(
                                request.request_id, "shed_timeout",
                                site="router",
                                tenant=getattr(request, "tenant", None),
                                tokens_in=len(request.input_ids),
                                queue_wait_s=wait,
                            ))
                            worked = True
                            continue
                        # Hand the engine only the REMAINING budget —
                        # session.submit would otherwise restart the
                        # full deadline_s from its own clock.
                        request = dataclasses.replace(
                            request, deadline_s=remaining
                        )
                    try:
                        session.submit(request)
                    except ValueError as e:
                        # Unservable at this session's compiled shapes
                        # (or a duplicate) — surface a Result instead
                        # of swallowing it, or the router would wait
                        # forever.
                        self._inbox_waits.pop(request.request_id, None)
                        with self._results_lock:
                            self._results[request.request_id] = Result(
                                request_id=request.request_id, tokens=[],
                                finish_reason=f"rejected: {e}",
                            )
                        if rec is not None:
                            rec.event(
                                "request_complete", CAT_SERVE_REQUEST,
                                request_id=request.request_id,
                                finish_reason="rejected",
                                error=str(e), num_tokens=0,
                                shed_by="replica_inbox",
                            )
                        requestlog.log_result(requestlog.build_record(
                            request.request_id, f"rejected: {e}",
                            site="router",
                            tenant=getattr(request, "tenant", None),
                            tokens_in=len(request.input_ids),
                        ))
                    worked = True
                try:
                    if engine.step():
                        worked = True
                except serve_chaos.ChaosPreempt:
                    # Injected preemption notice: leave service (the
                    # next scrape reads unready and the router pulls
                    # our seated KV) but keep the loop alive to answer
                    # that pull — the drain-without-warning path.
                    self.lame = True
                    worked = True
                # Drain engine.results directly (NOT via _pending_ids):
                # disaggregated requests arrive through the prefill
                # inbox without a session.submit, but their Results
                # land in the same dict.
                harvested = {}
                for rid in list(engine.results):
                    harvested[rid] = engine.results.pop(rid)
                    session._pending_ids.discard(rid)
                if harvested:
                    rec = active_recorder()
                    for rid, res in harvested.items():
                        wait = self._inbox_waits.pop(rid, None)
                        if rec is None:
                            continue
                        # Router-level TTFT: the inbox hop plus the
                        # engine-measured TTFT (which, for a
                        # disaggregated request, already spans from the
                        # router door — its _Entry was stamped there).
                        router_ttft = None
                        if res.ttft_s is not None:
                            router_ttft = res.ttft_s + (wait or 0.0)
                        rec.event(
                            "request_served", CAT_SERVE_REQUEST,
                            request_id=rid, replica=self.name,
                            finish_reason=res.finish_reason,
                            inbox_wait_s=wait,
                            router_ttft_s=router_ttft,
                        )
                    with self._results_lock:
                        self._results.update(harvested)
                    worked = True
                self._published = engine.health()
                self._published_at = time.monotonic()
                if not worked:
                    time.sleep(self.idle_sleep_s)
        except BaseException as e:
            error = f"replica crashed: {type(e).__name__}: {e}"
            raise
        finally:
            # A dead thread drains nothing: ALWAYS publish unhealthy —
            # clean stop() AND crash alike — so a router still scraping
            # this replica stops routing to it and fails its
            # outstanding work over. Before this ran in straight-line
            # code, an engine.step() exception left the last HEALTHY
            # snapshot published forever while submissions rotted.
            try:
                base = engine.health()
            except Exception:
                base = {}
            self._published = {**base, "healthy": False, "error": error}


class PrefillWorker:
    """A dedicated prefill replica: runs ONLY the batch-1 prefill
    program, turning popped requests into ``(row cache, first token)``
    handoffs for decode replicas — the prefill half of prefill/decode
    disaggregation. ``place`` (set by the Router) picks the decode
    replica at completion time, so placement uses post-prefill load."""

    def __init__(
        self,
        name: str,
        prefill_call: Callable,
        params: Any,
        prompt_len: int,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.name = str(name)
        self.prefill_call = prefill_call
        self.params = params
        self.prompt_len = prompt_len
        self.clock = clock
        self.place: Optional[Callable[[Any], None]] = None
        #: Set by the Router: called with an _Entry whose deadline
        #: passed before prefill started (the disaggregated analog of
        #: AdmissionQueue's pop-time shedding).
        self.shed: Optional[Callable[[Any], None]] = None
        #: Set by the Router: called with (entry, exception) when a
        #: request blows up mid-prefill — the worker thread must
        #: survive (its inbox feeds every later disaggregated request),
        #: so the failure surfaces as a Result instead of killing it.
        self.fail: Optional[Callable[[Any, BaseException], None]] = None
        self._inbox: deque = deque()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.num_prefills = 0

    @classmethod
    def from_model(
        cls, name: str, model, params, prompt_len: int, **kwargs
    ) -> "PrefillWorker":
        import jax

        from tpudl.models.generate import prefill_fn

        return cls(
            name, jax.jit(prefill_fn(model)), params, prompt_len, **kwargs
        )

    def submit(self, entry: _Entry) -> None:
        self._inbox.append(entry)

    def __len__(self) -> int:
        return len(self._inbox)

    def start(self) -> "PrefillWorker":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._loop, name=f"tpudl-prefill-{self.name}",
            daemon=True,
        )
        self._thread.start()
        return self

    def stop(self, join_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=join_s)
            self._thread = None

    def _loop(self) -> None:
        from tpudl.serve.engine import (
            CAT_SERVE_PREFILL,
            _Prefilled,
            first_token,
        )

        while not self._stop.is_set():
            if not self._inbox:
                time.sleep(0.0005)
                continue
            entry = self._inbox.popleft()
            if (
                entry.deadline is not None
                and self.clock() > entry.deadline
            ):
                # Never START a request past its deadline — the same
                # guarantee AdmissionQueue's pop-time shedding gives
                # the non-disaggregated path.
                if self.shed is not None:
                    self.shed(entry)
                continue
            try:
                req = entry.request
                # One length here, the window: the worker's program
                # is whatever it was handed, and a decode replica seats
                # a row of any of its own lengths.
                padded, mask = left_pad(req.input_ids, self.prompt_len)
                t0 = self.clock()
                logits, row_cache, *_ = self.prefill_call(
                    self.params, padded, mask
                )
                first = first_token(logits, req)
                now = self.clock()
                rec = active_recorder()
                if rec is not None:
                    rec.record(
                        "prefill", CAT_SERVE_PREFILL, t0, now - t0,
                        {"worker": self.name,
                         "request_id": req.request_id,
                         "queue_wait_s": t0 - entry.submitted_at,
                         "disaggregated": True,
                         "rows": self.prompt_len,
                         "tokens": len(req.input_ids)},
                    )
                self.num_prefills += 1
                reg = registry()
                reg.counter("serve_prefills").inc()
                reg.counter("serve_prefill_rows").inc(self.prompt_len)
                reg.counter("serve_prefill_tokens").inc(len(req.input_ids))
                reg.counter("serve_disaggregated_prefills").inc()
                item = _Prefilled(
                    entry, row_cache, first, len(req.input_ids), t0, now,
                    self.prompt_len,
                )
                if self.place is None:
                    raise RuntimeError(
                        "PrefillWorker has no placement hook — attach "
                        "it to a Router (prefill=[...]) before "
                        "submitting work"
                    )
                self.place(item)
            except Exception as e:
                # One poisoned request must not kill the worker thread
                # and strand every later inbox entry; without a router
                # hook (standalone use) the failure still propagates.
                if self.fail is None:
                    raise
                self.fail(entry, e)


class Router:
    """Load-balancing front over N serving replicas.

    ``submit()`` places a request (sticky, then least-loaded among
    ready replicas — or onto the prefill tier when disaggregating),
    ``collect()`` blocks until every outstanding request has a Result
    (driving scrape/failover on the way), ``poll()`` is the
    non-blocking harvest for open-loop drivers. Results are keyed by
    request_id exactly like ServeSession's.
    """

    def __init__(
        self,
        replicas: Sequence[Replica],
        prefill: Sequence[PrefillWorker] = (),
        scrape_interval_s: float = 0.02,
        shed_priority_above: int = 0,
        clock: Callable[[], float] = time.monotonic,
        migrate: bool = True,
        migrate_timeout_s: float = 2.0,
        max_failovers: Optional[int] = None,
        tenant_classes: Optional[Dict[Any, dict]] = None,
        tenant_quota_tokens: Optional[int] = None,
    ):
        if not replicas:
            raise ValueError("Router needs at least one replica")
        names = [r.name for r in replicas]
        if len(set(names)) != len(names):
            raise ValueError(f"replica names must be unique, got {names}")
        self.replicas: List[Replica] = list(replicas)
        self.prefill_workers: List[PrefillWorker] = list(prefill)
        # Replicas share compiled shapes (they are built from the same
        # programs); admission-validate at the router door so an
        # unservable request is a caller-visible ValueError instead of
        # a prefill-worker crash or a forever-blocked engine inbox.
        session0 = self.replicas[0].session
        self._prompt_len = session0.prompt_len
        self._max_seq_len = session0.max_seq_len
        self.scrape_interval_s = scrape_interval_s
        self.shed_priority_above = shed_priority_above
        self.clock = clock
        #: Migration-first recovery: on failover/drain, pull seated
        #: requests' page-granular KV payloads from the leaving replica
        #: (if its thread still answers within ``migrate_timeout_s``)
        #: and resume them on survivors with zero re-prefill; False
        #: restores the resubmit-only behavior.
        self.migrate = bool(migrate)
        self.migrate_timeout_s = migrate_timeout_s
        #: Per-request cap on failover RESUBMISSIONS (from-scratch
        #: restarts; migrations resume state and do not count): past
        #: it the request sheds as ``failover_exhausted`` instead of
        #: ping-ponging across dying replicas forever.
        self.max_failovers = (
            max_failovers
            if max_failovers is not None
            else env_int("TPUDL_SERVE_MAX_FAILOVERS", 3)
        )
        #: Per-tenant serving classes on top of the existing priority
        #: classes: ``{tenant: {"priority": int, "max_inflight_tokens":
        #: int}}``. ``priority`` maps the tenant onto the SLO shed
        #: ladder (priority > shed_priority_above sheds first under
        #: burn — a tenant's latency class is one line of config);
        #: ``max_inflight_tokens`` caps the tenant's outstanding token
        #: budget — past it, its requests shed as ``shed_quota`` at the
        #: door, so one tenant's overload cannot queue out everyone
        #: else (the isolation bar tests/test_tenant_lora.py
        #: holds). ``tenant_quota_tokens`` (or
        #: ``TPUDL_SERVE_TENANT_QUOTA_TOKENS``) is the default quota
        #: for tenants without an explicit class; None = unlimited.
        self.tenant_classes: Dict[Any, dict] = dict(tenant_classes or {})
        self.tenant_quota_tokens = (
            tenant_quota_tokens
            if tenant_quota_tokens is not None
            else env_int("TPUDL_SERVE_TENANT_QUOTA_TOKENS")
        )
        self.results: Dict[Any, Result] = {}
        self._assigned: Dict[Any, Any] = {}  # rid -> (replica_name|None, Request)
        self._sticky: Dict[Any, str] = {}  # session_key -> replica name
        # rid -> ABSOLUTE deadline, stamped once at first submit: the
        # client's budget spans every hop (router -> replica inbox ->
        # engine queue) and survives failover — a resubmission must not
        # restart it.
        self._deadline_at: Dict[Any, float] = {}
        # Router-side in-flight TOKEN budget per replica (sum of
        # outstanding max_new_tokens): the placement signal BETWEEN
        # scrapes. A burst submitted faster than replicas publish
        # health would otherwise all land on one replica (every scraped
        # load still reads 0), and counting REQUESTS instead of tokens
        # piles every long request onto one replica on a ragged mix.
        self._inflight: Dict[str, int] = {r.name: 0 for r in replicas}
        # Guards the routing books — _inflight, _assigned, _sticky,
        # and results: all four are mutated from the router's caller
        # thread AND the prefill workers' placement/shed hooks — an
        # unguarded dict mutation can crash a concurrent _failover
        # iteration, and a lost in-flight update skews placement
        # forever. Reentrant because _failover resubmits through
        # submit() and placement sheds through _shed().
        self._books = threading.RLock()
        # TPUDL_DEBUG_LOCK_ORDER: the books join the process-global
        # ordered-lock monitor (the live companion of the static pass —
        # cross-object cycles like books->replica-results vs
        # results->books are only visible at runtime).
        maybe_wrap_locks(self)
        self._ready: Dict[str, bool] = {r.name: True for r in replicas}
        # Replicas being drained for removal: still scraped, harvested,
        # and failed over, but they take NO new placements — the
        # drain-then-remove half of autoscaling.
        self._draining: set = set()
        # Last scraped health per replica (slots/queue/capacity): the
        # load_report() the autoscaler reads.
        self._last_health: Dict[str, dict] = {}
        self._burning: Dict[str, frozenset] = {}
        self._last_scrape = float("-inf")
        self._seq = 0
        self.num_failovers = 0
        self.num_migrations = 0
        # rid -> failover-resubmission count (a routing book: mutated
        # by _resubmit_failover and cleaned at every Result site).
        self._failover_counts: Dict[Any, int] = {}
        for worker in self.prefill_workers:
            worker.place = self._place_prefilled
            worker.shed = self._shed_prefill_entry
            worker.fail = self._fail_prefill_entry
            worker.start()
        for replica in self.replicas:
            replica.start()
            slo = replica.session.engine._slo
            if slo is not None:
                self._subscribe_slo(replica.name, slo)
        self._register_health_source()
        self._scrape(force=True)

    # -- SLO / health wiring -------------------------------------------

    def _subscribe_slo(self, name: str, monitor) -> None:
        with self._books:
            self._burning[name] = frozenset()

        def _on_transition(objective, state):
            # SLO transitions fire on the monitor's evaluating thread
            # (replica/engine side): _burning is a routing book like
            # _assigned, and remove_replica mutates it from the
            # autoscaler's thread — same lock, same discipline.
            with self._books:
                prev = self._burning.get(name, frozenset())
                if state["burning"]:
                    self._burning[name] = prev | {objective.name}
                else:
                    self._burning[name] = prev - {objective.name}
                burning = sum(1 for b in self._burning.values() if b)
            registry().gauge("serve_router_burning_replicas").set(burning)

        monitor.subscribe(_on_transition)

    @property
    def burning(self) -> bool:
        """True while ANY replica's SLO monitor has a burning
        objective — the router's per-class shed condition."""
        return any(self._burning.values())

    def _register_health_source(self) -> None:
        import weakref

        from tpudl.obs import exporter as obs_exporter

        self_ref = weakref.ref(self)

        def _router_health() -> dict:
            router = self_ref()
            if router is None:
                return {"healthy": True, "router": "collected"}
            ready = sum(1 for v in router._ready.values() if v)
            return {
                "healthy": ready > 0,
                "ready_replicas": ready,
                "total_replicas": len(router.replicas),
                "burning_replicas": sorted(
                    n for n, b in router._burning.items() if b
                ),
                "outstanding": len(router._assigned),
                "autoscale_hint": router._autoscale_hint(),
            }

        obs_exporter.register_health_source("serve_router", _router_health)

    def _autoscale_hint(self) -> int:
        """Replicas' worth of missing capacity: burning replicas are
        overloaded (each wants one more), unready ones are gone (each
        wants a replacement). 0 = fleet is sized right."""
        burning = sum(1 for b in self._burning.values() if b)
        unready = sum(1 for v in self._ready.values() if not v)
        return burning + unready

    # -- scraping / failover -------------------------------------------

    def _scrape(self, force: bool = False) -> None:
        """Refresh every replica's readiness from its scraped health
        (time-gated by ``scrape_interval_s``); requeue the outstanding
        work of replicas that went unready."""
        now = self.clock()
        if not force and now - self._last_scrape < self.scrape_interval_s:
            return
        self._last_scrape = now
        reg = registry()
        newly_down: List[str] = []
        # Snapshot under the books: add_replica/remove_replica mutate
        # the list from the autoscaler's thread.
        with self._books:
            replicas = list(self.replicas)
        # Scrapes can block on real HTTP — run them OUTSIDE the books,
        # then apply the results under them: _ready/_last_health are
        # routing books (add_replica/remove_replica mutate them from
        # the autoscaler's thread, load_report reads them under _books)
        # and an unguarded store here races both.
        scraped = [
            (replica, h, bool(h.get("healthy", True)))
            for replica in replicas
            for h in [replica.scrape()]
        ]
        with self._books:
            for replica, h, ready in scraped:
                if self._ready.get(replica.name) and not ready:
                    newly_down.append(replica.name)
                self._ready[replica.name] = ready
                self._last_health[replica.name] = h
            ready_count = sum(1 for v in self._ready.values() if v)
        for replica, h, ready in scraped:
            suffix = _metric_suffix(replica.name)
            reg.gauge(f"serve_replica_{suffix}_ready").set(int(ready))
            reg.gauge(f"serve_replica_{suffix}_slots_busy").set(
                h.get("slots_busy", 0)
            )
            reg.gauge(f"serve_replica_{suffix}_queue_depth").set(
                h.get("queue_depth", 0)
            )
        reg.gauge("serve_router_ready_replicas").set(ready_count)
        reg.gauge("serve_router_total_replicas").set(len(replicas))
        reg.gauge("serve_router_autoscale_hint").set(self._autoscale_hint())
        for name in newly_down:
            self._failover(name)

    def _failover(self, name: str) -> None:
        """Move every outstanding request off an unready replica,
        MIGRATION-FIRST: completed results are harvested (kept), then
        seated decode state is pulled as page-granular KV payloads and
        resumed on survivors with zero re-prefill — if the replica's
        engine thread still answers. A crashed thread (payload
        unavailable) falls back to today's resubmission path, now
        capped per request (``max_failovers``). Sticky keys pinned to
        the replica are released either way."""
        with self._books:
            replica = next(
                (r for r in self.replicas if r.name == name), None
            )
        if replica is None:  # removed concurrently: nothing to rescue
            return
        self._relocate_outstanding(
            replica, count_resubmits=True,
            timeout_s=self.migrate_timeout_s,
        )

    def _pick_migration_target(
        self,
        exclude: str,
        source_cache,
        tentative: Dict[str, int],
        request: Optional[Request] = None,
    ) -> Optional[Replica]:
        """Least-loaded ready survivor whose cache can SEAT the
        payload (same KV quantization) — chosen BEFORE the
        export so the reference-prefix probe pins pages on the replica
        the payload will actually reach. ``tentative`` carries the
        token load of payloads already directed at each survivor in
        THIS relocation (the books only update at placement, so
        without it every payload of a multi-slot failover would pick
        the same replica)."""
        quantized = bool(getattr(source_cache, "quantized", False))
        with self._books:
            ready = [
                r for r in self.replicas
                if r.name != exclude
                and self._ready.get(r.name)
                and r.name not in self._draining
                and bool(r.session.engine.cache.quantized) == quantized
                # Tenant requests only resume where the adapter can be
                # re-pinned (install would refuse anyway; filtering
                # here avoids exporting a payload no survivor seats).
                and (
                    request is None
                    or r.serves_tenant(request.tenant)
                )
            ]
            if not ready:
                return None
            return min(
                ready,
                key=lambda r: (
                    self._inflight[r.name] + tentative.get(r.name, 0),
                    r.load,
                ),
            )

    def _relocate_outstanding(
        self, replica: Replica, count_resubmits: bool, timeout_s: float
    ) -> None:
        """The shared failover/drain mover: every outstanding request
        leaves ``replica``. Seated decode state migrates (export ->
        crc-guarded payload -> survivor's migrate inbox, resuming
        mid-stream); waiting work and anything the replica could not
        export (crashed/frozen thread, speculating engine) resubmits from scratch — counted against the
        per-request failover cap when ``count_resubmits`` (unplanned
        failover) and uncounted on planned drains. The caller already
        took the replica out of placement (unready or draining)."""
        name = replica.name
        self._harvest_one(replica)
        with self._books:
            doomed = {
                rid: req
                for rid, (owner, req) in self._assigned.items()
                if owner == name
            }
            self._sticky = {
                k: v for k, v in self._sticky.items() if v != name
            }
        if not doomed:
            return
        box = None
        targets: Dict[Any, tuple] = {}
        source_cache = getattr(replica.session.engine, "cache", None)
        if self.migrate:
            skip_map: Dict[Any, int] = {}
            tentative: Dict[str, int] = {}
            for rid, req in doomed.items():
                target = self._pick_migration_target(
                    name, source_cache, tentative, request=req
                )
                if target is None:
                    continue  # no survivor: resubmission will shed
                tentative[target.name] = (
                    tentative.get(target.name, 0) + req.max_new_tokens
                )
                skip = 0
                lease = None
                cache = target.session.engine.cache
                if getattr(cache, "prefix_share", False) and getattr(
                    source_cache, "prefix_share", False
                ):
                    # Reference-first prefix contract: probe the
                    # TARGET's radix tree and PRE-LEASE the match, so
                    # those tokens ship as token-block references and
                    # eviction cannot invalidate them mid-transfer
                    # (tree ops are lock-guarded — safe cross-thread).
                    # Source must ALSO share: only left-aligned slots
                    # can ship a prefix by reference.
                    if cache.prefix_match_len(req.input_ids) > 0:
                        lease = cache.match_and_lease(req.input_ids)
                        skip = len(lease[0]) * cache.page_size
                targets[rid] = (target, lease)
                skip_map[rid] = skip
            if targets:
                box = replica.request_migration(
                    skip_map, timeout_s=timeout_s
                )
        reg = registry()
        rec = active_recorder()
        for rid, req in doomed.items():
            payload = box["payloads"].get(rid) if box is not None else None
            returned = box is not None and rid in box["requests"]
            target, lease = targets.get(rid, (None, None))
            target_ok = False
            owned = False
            if payload is not None and target is not None:
                with self._books:
                    # Ownership re-check INSIDE the mutation block: a
                    # completion harvested between the doomed snapshot
                    # and now already popped the assignment and
                    # decremented the in-flight books — acting on the
                    # stale entry would double-decrement and resurrect
                    # a delivered request.
                    cur = self._assigned.get(rid)
                    owned = cur is not None and cur[0] == name
                    target_ok = (
                        owned
                        and self._ready.get(target.name)
                        and target.name not in self._draining
                    )
                    if target_ok:
                        # Reassign BEFORE placing, so a late Result
                        # from the leaving replica can't race the
                        # resumed copy (harvest accepts a Result only
                        # from the current assignee).
                        self._assigned[rid] = (target.name, req)
                        self._inflight[name] -= req.max_new_tokens
                        self._inflight[target.name] += req.max_new_tokens
                if target_ok:
                    # Chaos seam: an env-gated bit flip here models a
                    # corrupted transfer — the target's crc check MUST
                    # shed it as failed, never resume it.
                    payload = serve_chaos.maybe_corrupt_migration(payload)
                    target.seat_migrated(rid, payload, lease=lease)
                    self.num_migrations += 1
                    reg.counter("serve_migrations_total").inc()
                    if rec is not None:
                        rec.event(
                            "request_migrated", CAT_SERVE_REQUEST,
                            request_id=rid, from_replica=name,
                            to_replica=target.name,
                            payload_bytes=len(payload),
                        )
                    continue
            if lease is not None and target is not None:
                # Pre-pinned reference prefix never shipped: unpin.
                target.session.engine.cache.release_lease(lease[1])
            if payload is not None and not owned:
                continue  # completed concurrently: payload is moot
            if (
                not count_resubmits
                and payload is None
                and not returned
            ):
                # Planned drain and the request never left the replica
                # (seated but unexportable — speculating engine —
                # or the command went unanswered): leave it
                # assigned; the caller's wait loop delivers it in place
                # rather than restarting mid-stream work.
                continue
            with self._books:
                cur = self._assigned.get(rid)
                if cur is None or cur[0] != name:
                    continue  # resolved concurrently: nothing to move
                self._assigned.pop(rid)
                self._inflight[name] -= req.max_new_tokens
            self._resubmit_failover(
                rid, req, from_replica=name, count=count_resubmits
            )

    def _resubmit_failover(
        self, rid, req: Request, from_replica: str, count: bool
    ) -> None:
        """The from-scratch fallback (KV unrecoverable): re-place the
        request as if freshly submitted — the original deadline stamp
        survives in ``_deadline_at``. ``count=True`` charges the
        per-request failover budget: a request ping-ponging across
        successively dying replicas sheds as ``failover_exhausted``
        instead of re-paying prefill forever. ``count=False`` is the
        planned-drain REQUEUE of waiting work — separate accounting,
        because a drain is not a failover."""
        rec = active_recorder()
        if count:
            with self._books:
                n = self._failover_counts.get(rid, 0) + 1
                self._failover_counts[rid] = n
            if n > self.max_failovers:
                self._shed(req, "failover_exhausted")
                return
            self.num_failovers += 1
            registry().counter("serve_router_requests_failed_over").inc()
            if rec is not None:
                rec.event(
                    "request_failover", CAT_SERVE_REQUEST,
                    request_id=rid, from_replica=from_replica,
                )
        else:
            registry().counter("serve_router_requests_requeued").inc()
            if rec is not None:
                rec.event(
                    "request_requeued", CAT_SERVE_REQUEST,
                    request_id=rid, from_replica=from_replica,
                )
        self.submit(req)

    def _harvest_one(self, replica: Replica) -> None:
        taken = replica.take()
        if not taken:
            return
        with self._books:
            for rid, res in taken.items():
                owner, _ = self._assigned.get(rid, (None, None))
                if owner == replica.name:
                    _, req = self._assigned.pop(rid)
                    self._inflight[owner] -= req.max_new_tokens
                    self._deadline_at.pop(rid, None)
                    self._failover_counts.pop(rid, None)
                    self.results[rid] = res
                # else: a late result from a failed-over assignment —
                # the restarted copy is authoritative; drop this one.

    def _harvest(self) -> None:
        with self._books:
            replicas = list(self.replicas)
        for replica in replicas:
            self._harvest_one(replica)

    # -- placement ------------------------------------------------------

    def _ready_replicas(self) -> List[Replica]:
        return [
            r for r in self.replicas
            if self._ready.get(r.name) and r.name not in self._draining
        ]

    def _least_loaded(self) -> Optional[Replica]:
        ready = self._ready_replicas()
        if not ready:
            return None
        # In-flight books lead (request-count accurate the instant a
        # placement happens); the scraped load refines between equal
        # counts (a replica deep in long generations scrapes busier).
        return min(
            ready, key=lambda r: (self._inflight[r.name], r.load)
        )

    def _shed(
        self, request: Request, reason: str, queue_wait_s: float = 0.0
    ) -> None:
        with self._books:
            self._deadline_at.pop(request.request_id, None)
            self._failover_counts.pop(request.request_id, None)
            self.results[request.request_id] = Result(
                request_id=request.request_id, tokens=[],
                finish_reason=reason, queue_wait_s=queue_wait_s,
            )
        registry().counter(f"serve_requests_{reason}").inc()
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "request_complete", CAT_SERVE_REQUEST,
                request_id=request.request_id, finish_reason=reason,
                queue_wait_s=queue_wait_s, num_tokens=0, shed_by="router",
            )
        requestlog.log_result(requestlog.build_record(
            request.request_id, reason, site="router",
            tenant=getattr(request, "tenant", None),
            tokens_in=len(request.input_ids), queue_wait_s=queue_wait_s,
        ))

    def _shed_prefill_entry(self, entry) -> None:
        """PrefillWorker deadline hook (worker thread): the
        disaggregated analog of AdmissionQueue's pop-time shedding —
        release the assignment and record a ``shed_timeout`` Result
        with the real queue wait, mirroring the engine's shape."""
        request = entry.request
        with self._books:
            self._assigned.pop(request.request_id, None)
        self._shed(
            request, "shed_timeout",
            queue_wait_s=self.clock() - entry.submitted_at,
        )

    def _fail_prefill_entry(self, entry, exc: BaseException) -> None:
        """PrefillWorker exception hook (worker thread): a request
        that blew up mid-prefill surfaces as a Result — releasing its
        assignment so collect() doesn't wait forever — and the worker
        thread survives for the rest of its inbox."""
        request = entry.request
        with self._books:
            self._assigned.pop(request.request_id, None)
            self._deadline_at.pop(request.request_id, None)
            self.results[request.request_id] = Result(
                request_id=request.request_id, tokens=[],
                finish_reason=f"failed: {type(exc).__name__}: {exc}",
                queue_wait_s=self.clock() - entry.submitted_at,
            )
        registry().counter("serve_requests_failed").inc()
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "request_complete", CAT_SERVE_REQUEST,
                request_id=request.request_id, finish_reason="failed",
                error=f"{type(exc).__name__}: {exc}",
                num_tokens=0, shed_by="router",
            )
        requestlog.log_result(requestlog.build_record(
            request.request_id, f"failed: {type(exc).__name__}: {exc}",
            site="router", tenant=getattr(request, "tenant", None),
            tokens_in=len(request.input_ids),
            queue_wait_s=self.clock() - entry.submitted_at,
        ))

    def submit(self, request: Request) -> Any:
        """Place one request. Sticky key first, else least-loaded ready
        replica (or the prefill tier when disaggregating). While any
        replica's SLO burns, best-effort requests
        (priority > shed_priority_above) shed at the door."""
        rid = request.request_id
        validate_request(request, self._prompt_len, self._max_seq_len)
        if request.tenant is not None and self.prefill_workers:
            raise ValueError(
                "disaggregated prefill does not support tenant "
                "adapters yet (the prefill workers run the plain base "
                "program — a tenant's prompt would prefill unadapted)"
            )
        self._scrape()
        with self._books:
            if rid in self._assigned or rid in self.results:
                raise ValueError(f"duplicate request_id {rid!r}")
            if request.tenant is not None:
                cls = self.tenant_classes.get(request.tenant, {})
                if "priority" in cls and (
                    request.priority != cls["priority"]
                ):
                    # The tenant's SLO class IS its priority: map it
                    # onto the existing shed ladder at the door.
                    request = dataclasses.replace(
                        request, priority=cls["priority"]
                    )
                quota = cls.get(
                    "max_inflight_tokens", self.tenant_quota_tokens
                )
                if quota is not None and (
                    self._tenant_inflight(request.tenant)
                    + request.max_new_tokens
                    > quota
                ):
                    # Over its token budget: the tenant sheds at the
                    # DOOR, before any queue position is consumed —
                    # one tenant's 4x overload must not move its
                    # neighbors' tail (the isolation contract).
                    self._shed(request, "shed_quota")
                    return rid
            if (
                self.burning
                and request.priority > self.shed_priority_above
            ):
                self._shed(request, "shed_slo")
                return rid
            target = self._pick(request)
            if target is None:
                # No ready replica at all: overload/outage is data, not
                # an exception (same contract as a full admission
                # queue).
                self._shed(request, "shed_capacity")
                return rid
            now = self.clock()
            deadline_at = self._deadline_at.get(rid)
            if deadline_at is None and request.deadline_s is not None:
                # Stamped ONCE: a failover resubmission finds the
                # original stamp and keeps the client's real budget
                # instead of granting a fresh full one.
                deadline_at = now + request.deadline_s
                self._deadline_at[rid] = deadline_at
            if self.prefill_workers:
                # Disaggregated path: the request becomes a queue entry
                # on the least-busy prefill worker; the decode replica
                # (and any sticky pin) is chosen at prefill completion,
                # when post-prefill load is known. The assignment owner
                # is resolved then, so track it as in-flight (owner
                # None).
                self._assigned[rid] = (None, request)
                worker = min(self.prefill_workers, key=len)
                self._seq += 1
                worker.submit(_Entry(
                    priority=request.priority, seq=self._seq,
                    request=request,
                    deadline=deadline_at,
                    submitted_at=now,
                ))
                routed_to = {"worker": worker.name}
            else:
                if request.session_key is not None:
                    self._sticky[request.session_key] = target.name
                self._assigned[rid] = (target.name, request)
                self._inflight[target.name] += request.max_new_tokens
                target.submit(request, deadline_at)
                routed_to = {"replica": target.name}
        registry().counter("serve_router_requests_routed").inc()
        rec = active_recorder()
        if rec is not None:
            # The router-door marker of the stitched fleet trace: names
            # the hop the request was handed to, so report.py can warn
            # "partial trace" when that hop's stream is missing from
            # disk.
            rec.event(
                "request_routed", CAT_SERVE_REQUEST,
                request_id=rid, priority=request.priority,
                **routed_to,
            )
        return rid

    def _tenant_inflight(self, tenant) -> int:
        """Outstanding token budget one tenant holds (sum of assigned
        requests' max_new_tokens). Derived from ``_assigned`` on read
        instead of counter-maintained: every mutation site of the
        assignment book would otherwise need a paired tenant-side
        update, and a single missed pair skews the quota forever.
        Callers hold ``_books``."""
        return sum(
            req.max_new_tokens
            for _, req in self._assigned.values()
            if req.tenant == tenant
        )

    def _pick(self, request: Request) -> Optional[Replica]:
        """Sticky pin first (if its replica is still ready), then
        ADAPTER AFFINITY for tenant requests — the ready replica whose
        pool has held this tenant's adapter RESIDENT longest wins
        (warm pages beat a less-loaded replica paying a fresh load;
        the prefix-affinity shape applied to adapters) — then PREFIX
        AFFINITY — the ready replica whose radix tree holds the
        longest cached prefix of this prompt (at least one full page)
        serves it with O(unshared suffix) prefill, which beats a
        less-loaded cold replica re-paying the whole window — then
        least-loaded. Affinity ties break by load, so identical-prefix
        floods still spread. Callers hold ``_books``."""
        if request.session_key is not None:
            pinned = self._sticky.get(request.session_key)
            if (
                pinned is not None
                and self._ready.get(pinned)
                and pinned not in self._draining
            ):
                target = next(
                    r for r in self.replicas if r.name == pinned
                )
                # A pin set by this session's tenantless (or other-
                # tenant) traffic must not route a tenant request to a
                # replica that cannot serve its adapter.
                if target.serves_tenant(request.tenant):
                    return target
        ready = self._ready_replicas()
        if request.tenant is not None:
            # Only replicas that can serve this tenant at all: placing
            # on one that cannot would terminally reject the request
            # at the replica door even while a serving replica idles
            # (the same filter the migration target pick applies).
            ready = [
                r for r in ready if r.serves_tenant(request.tenant)
            ]
            if not ready:
                return None
        if request.tenant is not None and len(ready) > 1:
            resident = [
                (since, r)
                for r in ready
                for since in [r.adapter_resident_since(request.tenant)]
                if since is not None
            ]
            if resident:
                # Longest-resident wins: the earliest load stamp —
                # recency churn would bounce a tenant between
                # replicas, each load evicting someone else's pages.
                best = min(since for since, _ in resident)
                contenders = [r for since, r in resident if since == best]
                return min(
                    contenders,
                    key=lambda r: (self._inflight[r.name], r.load),
                )
        if len(ready) > 1:
            matches = [
                (r.prefix_match_len(request.input_ids), r) for r in ready
            ]
            best = max(m for m, _ in matches)
            if best > 0:
                contenders = [r for m, r in matches if m == best]
                return min(
                    contenders,
                    key=lambda r: (self._inflight[r.name], r.load),
                )
        # Least-loaded over the (possibly tenant-filtered) ready set.
        if not ready:
            return None
        return min(
            ready, key=lambda r: (self._inflight[r.name], r.load)
        )

    def _place_prefilled(self, item) -> None:
        """PrefillWorker completion hook (worker thread): hand the
        prefilled request to its sticky replica, else the least-loaded
        ready decode replica's engine inbox — the same placement
        contract submit() gives the non-disaggregated path."""
        request = item.entry.request
        rid = request.request_id
        with self._books:
            if rid not in self._assigned:
                # Assignment already resolved elsewhere (shed/cancel):
                # placing it would decode a request the caller was
                # already handed a Result for.
                return
            target = self._pick(request)
            if target is None:
                # Nothing ready to decode: shed rather than park the
                # work on a dead replica — failover only fires on a
                # ready->unready EDGE, so a request placed on an
                # already-unready replica would strand forever.
                self._assigned.pop(rid, None)
                self._shed(
                    request, "shed_capacity",
                    queue_wait_s=self.clock() - item.entry.submitted_at,
                )
                return
            if request.session_key is not None:
                self._sticky[request.session_key] = target.name
            self._assigned[rid] = (target.name, request)
            self._inflight[target.name] += request.max_new_tokens
        target.seat_prefilled(item)

    # -- live fleet membership (the autoscaler's surface) ---------------

    def add_replica(self, replica: Replica) -> Replica:
        """Grow the fleet live: start ``replica``, enter it into the
        routing books, subscribe its SLO monitor, and scrape it so the
        next placement can use it. The replica must share the fleet's
        compiled shapes (admission validation happened against them)."""
        session = replica.session
        if (
            session.prompt_len != self._prompt_len
            or session.max_seq_len != self._max_seq_len
        ):
            raise ValueError(
                f"replica {replica.name!r} compiled shapes "
                f"(prompt_len={session.prompt_len}, "
                f"max_seq_len={session.max_seq_len}) do not match the "
                f"fleet's ({self._prompt_len}, {self._max_seq_len})"
            )
        with self._books:
            if any(r.name == replica.name for r in self.replicas):
                raise ValueError(
                    f"duplicate replica name {replica.name!r}"
                )
            self.replicas.append(replica)
            self._inflight[replica.name] = 0
            self._ready[replica.name] = True
        replica.start()
        slo = session.engine._slo
        if slo is not None:
            self._subscribe_slo(replica.name, slo)
        registry().counter("serve_router_replicas_added").inc()
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "replica_added", CAT_SERVE_REQUEST, replica=replica.name
            )
        self._scrape(force=True)
        return replica

    def remove_replica(
        self,
        name: str,
        drain: bool = True,
        timeout_s: Optional[float] = None,
    ) -> Replica:
        """Shrink the fleet live. ``drain=True`` (the autoscaler's
        scale-down): the replica takes no new placements, its sticky
        pins are released, and its in-flight decode state MIGRATES to
        the surviving replicas (page-granular KV export, resumed
        mid-stream — zero re-prefill), making drain latency
        ~O(payload transfer) instead of O(longest generation); waiting
        work resubmits. Work that cannot migrate (no survivors,
        speculating engine, a thread that stopped answering) is
        WAITED out exactly as before — a drain never drops in-flight
        work either way. ``drain=False`` stops the replica immediately
        and fails its outstanding work over to the survivors (the
        replacement path for a sick replica).

        On drain timeout the replica is returned to service (draining
        flag cleared) and TimeoutError raises — half-removed state is
        never left behind."""
        with self._books:
            replica = next(
                (r for r in self.replicas if r.name == name), None
            )
            if replica is None:
                raise ValueError(f"no replica named {name!r}")
            self._draining.add(name)
            self._sticky = {
                k: v for k, v in self._sticky.items() if v != name
            }
        deadline = (
            None if timeout_s is None else self.clock() + timeout_s
        )
        if drain:
            t_drain = self.clock()
            with self._books:
                survivors = any(
                    r.name != name
                    and self._ready.get(r.name)
                    and r.name not in self._draining
                    for r in self.replicas
                )
            if (
                self.migrate
                and survivors
                and replica._thread is not None
                and replica._thread.is_alive()
            ):
                # Migration drain: planned, so resubmissions of
                # waiting work do NOT charge the failover cap.
                budget = self.migrate_timeout_s
                if timeout_s is not None:
                    budget = min(budget, timeout_s)
                self._relocate_outstanding(
                    replica, count_resubmits=False, timeout_s=budget
                )
            while True:
                self._scrape()
                self._harvest()
                with self._books:
                    outstanding = sum(
                        1 for owner, _ in self._assigned.values()
                        if owner == name
                    )
                if outstanding == 0:
                    break
                if deadline is not None and self.clock() > deadline:
                    with self._books:
                        self._draining.discard(name)
                    raise TimeoutError(
                        f"remove_replica({name!r}): {outstanding} "
                        f"requests still in flight after {timeout_s}s"
                    )
                time.sleep(0.001)
            registry().histogram("serve_drain_ms").observe(
                1e3 * (self.clock() - t_drain)
            )
        replica.stop()
        self._harvest_one(replica)
        if not drain:
            # Outstanding work moves to the survivors before the books
            # forget this replica existed.
            self._failover(name)
        with self._books:
            self.replicas = [r for r in self.replicas if r.name != name]
            self._inflight.pop(name, None)
            self._ready.pop(name, None)
            self._draining.discard(name)
            self._burning.pop(name, None)
            self._last_health.pop(name, None)
            ready = sum(1 for v in self._ready.values() if v)
            total = len(self.replicas)
        reg = registry()
        suffix = _metric_suffix(name)
        reg.gauge(f"serve_replica_{suffix}_ready").set(0)
        reg.gauge("serve_router_ready_replicas").set(ready)
        reg.gauge("serve_router_total_replicas").set(total)
        reg.counter("serve_router_replicas_removed").inc()
        rec = active_recorder()
        if rec is not None:
            rec.event(
                "replica_removed", CAT_SERVE_REQUEST, replica=name,
                drained=drain,
            )
        return replica

    def autoscale_hint(self) -> int:
        """Public read of the scale-out signal the
        ``serve_router_autoscale_hint`` gauge publishes."""
        return self._autoscale_hint()

    def load_report(self) -> dict:
        """One fleet-load sample from the last scrape — the signal set
        the Autoscaler's hysteresis runs on. ``busy_frac`` is occupied
        capacity over total capacity of the PLACEABLE (ready,
        non-draining) replicas; ``queue_frac`` the same for admission
        queues alone."""
        self._scrape()
        with self._books:
            active = [
                r for r in self.replicas
                if r.name not in self._draining
            ]
            busy = cap = qdepth = qcap = 0.0
            per_replica: Dict[str, dict] = {}
            for r in active:
                h = self._last_health.get(r.name, {})
                r_busy = h.get("slots_busy", 0) + h.get("queue_depth", 0)
                busy += r_busy
                cap += h.get("num_slots", 0) + h.get("queue_capacity", 0)
                qdepth += h.get("queue_depth", 0)
                qcap += h.get("queue_capacity", 0)
                per_replica[r.name] = {
                    "ready": bool(self._ready.get(r.name)),
                    "busy": r_busy,
                    "inflight_tokens": self._inflight.get(r.name, 0),
                }
            # Per-tenant quota view: every tenant with a declared class
            # plus every tenant currently holding assignments, so a
            # quota-less bursting tenant is still visible. Utilization
            # also lands on the metering plane's labeled gauge
            # (serve_tenant_quota_utilization) — the scrape and the
            # report read the same number.
            tenants: Dict[str, dict] = {}
            seen = set(self.tenant_classes)
            seen.update(
                req.tenant
                for _, req in self._assigned.values()
                if req.tenant is not None
            )
            for tenant in sorted(seen):
                cls = self.tenant_classes.get(tenant, {})
                quota = cls.get(
                    "max_inflight_tokens", self.tenant_quota_tokens
                )
                inflight = self._tenant_inflight(tenant)
                util = (inflight / quota) if quota else 0.0
                tenants[tenant] = {
                    "inflight_tokens": inflight,
                    "quota_tokens": quota,
                    "quota_utilization": util,
                }
                metering.meter().set_quota_utilization(tenant, util)
            return {
                "per_replica": per_replica,
                "replicas": len(self.replicas),
                "active_replicas": len(active),
                "ready_replicas": sum(
                    1 for v in self._ready.values() if v
                ),
                "draining": sorted(self._draining),
                "busy_frac": busy / cap if cap else 0.0,
                "queue_frac": qdepth / qcap if qcap else 0.0,
                "outstanding": len(self._assigned),
                "burning": self.burning,
                "autoscale_hint": self._autoscale_hint(),
                "tenants": tenants,
            }

    # -- the request lifecycle ------------------------------------------

    def poll(self) -> Dict[Any, Result]:
        """Non-blocking: scrape (failover if needed), harvest, and hand
        over every Result completed so far."""
        self._scrape()
        self._harvest()
        with self._books:
            out = self.results
            self.results = {}
        return out

    def collect(self, timeout_s: Optional[float] = None) -> Dict[Any, Result]:
        """Block until every outstanding request has a Result (scraping
        and failing over on the way)."""
        deadline = (
            None if timeout_s is None else self.clock() + timeout_s
        )
        out: Dict[Any, Result] = {}
        while True:
            out.update(self.poll())
            if not self._assigned:
                return out
            if deadline is not None and self.clock() > deadline:
                raise TimeoutError(
                    f"router collect(): {len(self._assigned)} requests "
                    f"still outstanding after {timeout_s}s "
                    f"(ready replicas: {sorted(n for n, v in self._ready.items() if v)})"
                )
            time.sleep(0.001)

    def serve(
        self, requests: Sequence[Request], timeout_s: Optional[float] = None
    ) -> Dict[Any, Result]:
        for request in requests:
            self.submit(request)
        return self.collect(timeout_s=timeout_s)

    def close(self) -> None:
        for worker in self.prefill_workers:
            worker.stop()
        for replica in self.replicas:
            replica.stop()

    def __enter__(self) -> "Router":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
