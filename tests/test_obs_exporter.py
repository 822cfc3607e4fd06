"""tpudl.obs.exporter: the live telemetry plane (ISSUE 6 tentpole).

The contract under test: while a process runs, ``GET /metrics`` is
valid Prometheus text rendered from the registry (scrapes racing
observation threads stay consistent), ``GET /healthz`` is a
probe-compatible liveness+readiness report that flips to 503 on a
sticky background-thread error or a stale heartbeat, ``/snapshot``
carries the full registry + live goodput — and the bounded-window
Histogram keeps every scrape O(window) with memory that stops growing
(the regression the old keep-everything implementation would fail)."""

import json
import re
import threading
import urllib.error
import urllib.request

import pytest

import tpudl.obs as obs
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import spans as obs_spans


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Exporter/health/registry state is process-global; isolate."""
    monkeypatch.delenv("TPUDL_OBS_PORT", raising=False)
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    monkeypatch.delenv("TPUDL_OBS_HIST_WINDOW", raising=False)
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter.stop_exporter()
    obs_exporter._reset_health_for_tests()
    yield
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter.stop_exporter()
    obs_exporter._reset_health_for_tests()


def _get(url, timeout=10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# Bounded rolling-window histogram (the memory-regression satellite)
# ---------------------------------------------------------------------------


def test_histogram_window_bounds_memory_and_keeps_cumulative_totals():
    h = obs_counters.Histogram(window=8)
    for i in range(100):
        h.observe(float(i))
    # Memory is bounded by the window; count/sum stay cumulative (the
    # monotone pair rate() math needs). The old implementation kept all
    # 100 raw values — this asserts the bound itself.
    assert len(h._values) == 8
    assert h.values == [92.0, 93.0, 94.0, 95.0, 96.0, 97.0, 98.0, 99.0]
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == sum(range(100))
    # Percentiles/min/max describe the WINDOW (recent behavior): the
    # early small values were evicted.
    assert snap["min"] == 92.0 and snap["max"] == 99.0
    assert 92.0 <= snap["p50"] <= 99.0
    # Snapshot keys unchanged from the unbounded implementation.
    assert set(snap) == {
        "count", "sum", "min", "max", "mean", "p50", "p95", "p99",
    }


def test_histogram_under_window_is_exact_and_env_sets_default(monkeypatch):
    h = obs_counters.Histogram(window=16)
    for v in [1.0, 2.0, 3.0]:
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["min"] == 1.0 and snap["p50"] == 2.0
    assert h.count == 3 and h.values == [1.0, 2.0, 3.0]

    monkeypatch.setenv("TPUDL_OBS_HIST_WINDOW", "4")
    h2 = obs_counters.Histogram()
    assert h2.window == 4
    for i in range(10):
        h2.observe(i)
    assert len(h2.values) == 4 and h2.count == 10
    with pytest.raises(ValueError, match="window"):
        obs_counters.Histogram(window=0)


def test_registry_histogram_growth_is_bounded(monkeypatch):
    """The acceptance regression test: a registry histogram fed far
    past its window holds exactly window values — a long-lived serving
    process's telemetry memory is a constant, not a leak."""
    monkeypatch.setenv("TPUDL_OBS_HIST_WINDOW", "32")
    reg = obs_counters.Registry()
    h = reg.histogram("serve_ttft_ms")
    for i in range(32 * 50):
        h.observe(float(i % 7))
    assert len(h._values) == 32
    assert h.snapshot()["count"] == 32 * 50


# ---------------------------------------------------------------------------
# /metrics: Prometheus text conformance
# ---------------------------------------------------------------------------

# One metric line: name, optional {labels}, a float/int/NaN/Inf value.
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\"(,[a-zA-Z_][a-zA-Z0-9_]*="
    r"\"[^\"]*\")*\})?"
    r" (NaN|[+-]?Inf|[+-]?[0-9]*\.?[0-9]+([eE][+-]?[0-9]+)?)$"
)


def test_metrics_prometheus_text_conformance():
    reg = obs_counters.registry()
    reg.counter("bytes_ingested").inc(1234)
    reg.gauge("serve_slots_busy").set(3)
    h = reg.histogram("serve ttft.ms")  # name needs sanitizing
    for v in [10.0, 20.0, 30.0, 40.0]:
        h.observe(v)
    hb = obs_exporter.Heartbeat("train_loop")
    hb.beat(step=7)
    with obs_exporter.ObsExporter(port=0) as ex:
        status, text = _get(f"http://127.0.0.1:{ex.port}/metrics")
    assert status == 200
    lines = text.strip().splitlines()
    types = {}
    for line in lines:
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split()
            types[name] = kind
            continue
        assert _PROM_LINE.match(line), f"malformed exposition line: {line!r}"
    assert types["bytes_ingested"] == "counter"
    assert types["serve_slots_busy"] == "gauge"
    # The sanitized histogram renders as a summary: quantile rows plus
    # the cumulative _sum/_count pair.
    assert types["serve_ttft_ms"] == "summary"
    assert 'serve_ttft_ms{quantile="0.5"} 25.0' in lines
    assert "serve_ttft_ms_sum 100.0" in lines
    assert "serve_ttft_ms_count 4" in lines
    # Heartbeat age rides as a gauge.
    assert types["train_loop_heartbeat_age_s"] == "gauge"
    assert any(l.startswith("train_loop_heartbeat_age_s ") for l in lines)


def test_metrics_scrape_races_observers():
    """Scrapes must parse and stay internally consistent while four
    threads hammer the instruments — the concurrent scrape-vs-observe
    thread-safety bar."""
    reg = obs_counters.registry()
    stop = threading.Event()

    def work():
        h = reg.histogram("lat_ms")
        c = reg.counter("events")
        while not stop.is_set():
            h.observe(1.0)
            c.inc()

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        with obs_exporter.ObsExporter(port=0) as ex:
            url = f"http://127.0.0.1:{ex.port}/metrics"
            last_count = -1
            for _ in range(10):
                status, text = _get(url)
                assert status == 200
                count = sum_ = None
                for line in text.splitlines():
                    if line.startswith("lat_ms_count "):
                        count = int(line.split()[1])
                    elif line.startswith("lat_ms_sum "):
                        sum_ = float(line.split()[1])
                    elif not line.startswith("#"):
                        assert _PROM_LINE.match(line), line
                if count is not None:
                    # Counts only move forward across scrapes, and
                    # every 1.0-valued observation keeps sum ~= count
                    # (each taken under the instrument lock, so both
                    # are internally consistent even mid-hammer).
                    assert count >= last_count
                    last_count = count
                    assert sum_ is not None and abs(sum_ - count) <= 4
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert last_count > 0


# ---------------------------------------------------------------------------
# /healthz: sources, sticky errors, heartbeats
# ---------------------------------------------------------------------------


def test_healthz_reports_sources_and_flips_503():
    obs_exporter.register_health_source(
        "serve_engine", lambda: {"healthy": True, "slots_busy": 2}
    )
    with obs_exporter.ObsExporter(port=0) as ex:
        url = f"http://127.0.0.1:{ex.port}/healthz"
        status, body = _get(url)
        assert status == 200
        h = json.loads(body)
        assert h["healthy"] is True
        assert h["sources"]["serve_engine"]["slots_busy"] == 2

        obs_exporter.register_health_source(
            "slo", lambda: {"healthy": False, "burning": ["ttft_p99"]}
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10.0)
        assert ei.value.code == 503
        h = json.load(ei.value)
        assert h["healthy"] is False
        assert h["sources"]["slo"]["burning"] == ["ttft_p99"]

        # A RAISING source is an unhealthy source, not a broken probe.
        obs_exporter.unregister_health_source("slo")
        obs_exporter.register_health_source(
            "boom", lambda: (_ for _ in ()).throw(RuntimeError("dead"))
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(url, timeout=10.0)
        assert ei.value.code == 503
        assert "dead" in json.load(ei.value)["sources"]["boom"]["error"]


def test_healthz_flips_on_sticky_metric_fetcher_error():
    """The failure /healthz exists for: the MetricFetcher's worker dies
    on a poisoned readback, the error is sticky, and the probe reports
    unhealthy from the moment the worker dies — including after
    close()."""
    from tpudl.train.metrics import MetricFetcher

    class _Boom:
        def __array__(self, *a, **k):
            raise RuntimeError("poisoned readback")

    fetcher = MetricFetcher(window=4)
    try:
        fetcher.submit(0, {"loss": _Boom()}, 1)
        # The worker dies asynchronously; flush surfaces the error.
        with pytest.raises(RuntimeError, match="poisoned"):
            fetcher.flush()
        with obs_exporter.ObsExporter(port=0) as ex:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{ex.port}/healthz", timeout=10.0
                )
            assert ei.value.code == 503
            src = json.load(ei.value)["sources"]["metric_fetcher"]
            assert src["healthy"] is False
            assert "poisoned readback" in src["error"]
    finally:
        fetcher.close()
    # Sticky THROUGH close: the dead worker stays visible post-mortem.
    assert fetcher.health()["healthy"] is False
    assert obs_exporter.health_snapshot()["healthy"] is False


def test_healthz_flips_on_sticky_checkpoint_writer_error(tmp_path):
    """Same bar for the ft writer thread: the health view of a write
    failure survives the step path consuming the deferred exception."""
    from tpudl.ft.writer import AsyncCheckpointWriter

    class BoomStore:
        def write(self, *a, **k):
            raise OSError("disk gone")

        def retain(self):
            pass

    w = AsyncCheckpointWriter(BoomStore())
    w.submit(0, [])
    with pytest.raises(RuntimeError, match="checkpoint write failed"):
        w.wait()
    # The step path consumed the deferred error — health still reports
    # it, sticky.
    assert w.health()["healthy"] is False
    assert "disk gone" in w.health()["error"]
    snap = obs_exporter.health_snapshot()
    assert snap["sources"]["checkpoint_writer"]["healthy"] is False
    # wait() consumed the one-shot deferred error; close() is clean —
    # but the health view stays unhealthy regardless.
    w.close()
    assert w.health()["healthy"] is False


def test_heartbeat_staleness_and_stop():
    t = [0.0]
    hb = obs_exporter.Heartbeat(
        "train_loop", stale_after=10.0, clock=lambda: t[0]
    )
    hb.beat(step=5)
    t[0] = 5.0
    h = obs_exporter.health_snapshot()
    assert h["healthy"] is True
    assert h["heartbeats"]["train_loop"]["age_s"] == 5.0
    assert h["heartbeats"]["train_loop"]["step"] == 5
    # Running + stale = hung: unhealthy.
    t[0] = 30.0
    h = obs_exporter.health_snapshot()
    assert h["healthy"] is False
    assert h["heartbeats"]["train_loop"]["stale"] is True
    # Stopped (finished) is never stale, whatever the age.
    hb.stop()
    h = obs_exporter.health_snapshot()
    assert h["healthy"] is True
    assert h["heartbeats"]["train_loop"]["running"] is False


def test_heartbeat_staleness_adapts_to_beat_cadence():
    """A loop whose dispatch windows legitimately take minutes must not
    read as hung between beats: the stale threshold stretches to
    adaptive_factor x the established beat interval."""
    t = [0.0]
    hb = obs_exporter.Heartbeat(
        "train_loop", stale_after=10.0, clock=lambda: t[0],
        adaptive_factor=5.0,
    )
    hb.beat()
    t[0] = 100.0
    hb.beat()  # interval 100s >> stale_after
    assert hb.stale_threshold_s() == 500.0
    # 3 intervals late: still healthy (inside 5x the cadence)...
    t[0] = 400.0
    assert hb.health()["healthy"] is True
    # ...but far outside its own rhythm = hung.
    t[0] = 700.0
    assert hb.health()["stale"] is True
    # Before any interval exists, the flat floor applies.
    hb2 = obs_exporter.Heartbeat("x", stale_after=10.0, clock=lambda: t[0])
    hb2.beat()
    assert hb2.stale_threshold_s() == 10.0


# ---------------------------------------------------------------------------
# /snapshot + env activation
# ---------------------------------------------------------------------------


def test_snapshot_carries_registry_and_live_goodput(tmp_path):
    rec = obs.enable(str(tmp_path))
    rec.record("train_step", obs_spans.CAT_STEP, 1.0, 2.0, {"step": 0})
    rec.record("data_wait", obs_spans.CAT_DATA_WAIT, 3.0, 1.0, {"step": 1})
    obs_counters.registry().counter("steps").inc(2)
    with obs_exporter.ObsExporter(port=0) as ex:
        status, body = _get(f"http://127.0.0.1:{ex.port}/snapshot")
    assert status == 200
    snap = json.loads(body)
    assert snap["registry"]["counters"]["steps"] == 2
    # The LIVE goodput classification of the active span stream — what
    # report.py would compute post-mortem, served mid-run.
    assert snap["goodput"]["wall_s"] == 3.0
    assert snap["goodput"]["productive_s"] == 2.0
    assert snap["health"]["healthy"] is True


def test_env_port_activation(monkeypatch):
    monkeypatch.setenv("TPUDL_OBS_PORT", "0")  # ephemeral: the test idiom
    ex = obs_exporter.maybe_start_from_env()
    assert ex is not None and ex.port > 0
    assert obs_exporter.active_exporter() is ex
    # Idempotent: a second instrumented layer gets the same exporter.
    assert obs_exporter.maybe_start_from_env() is ex
    status, _ = _get(f"http://127.0.0.1:{ex.port}/metrics")
    assert status == 200

    obs_exporter.stop_exporter()
    monkeypatch.delenv("TPUDL_OBS_PORT")
    assert obs_exporter.maybe_start_from_env() is None
    monkeypatch.setenv("TPUDL_OBS_PORT", "nope")
    with pytest.raises(ValueError, match="TPUDL_OBS_PORT"):
        obs_exporter.maybe_start_from_env()


def test_env_bind_failure_warns_instead_of_killing_the_run(monkeypatch):
    """Distributor workers inherit TPUDL_OBS_PORT and a supervised
    restart can overlap its predecessor's grace window: a port
    conflict on the ENV path must degrade to a warning, never crash
    fit()/serving. An explicit start still raises."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    taken = s.getsockname()[1]
    try:
        monkeypatch.setenv("TPUDL_OBS_PORT", str(taken))
        with pytest.warns(RuntimeWarning, match="could not bind"):
            assert obs_exporter.maybe_start_from_env() is None
        with pytest.raises(OSError):
            obs_exporter.ObsExporter(port=taken).start()
    finally:
        s.close()


def test_metrics_scrape_has_no_health_side_effects():
    """/metrics is read-only: it must not evaluate health sources
    (SloMonitor.health drives burn-state transitions) — heartbeat ages
    render from the heartbeat table alone."""
    calls = []
    obs_exporter.register_health_source(
        "probe", lambda: calls.append(1) or {"healthy": True}
    )
    hb = obs_exporter.Heartbeat("train_loop")
    hb.beat()
    with obs_exporter.ObsExporter(port=0) as ex:
        _, text = _get(f"http://127.0.0.1:{ex.port}/metrics")
    assert "train_loop_heartbeat_age_s" in text
    assert calls == []


def test_histogram_mean_is_windowed_after_wrap():
    """mean sits next to the windowed min/max/percentiles and must
    describe the same window — not the cumulative series."""
    h = obs_counters.Histogram(window=4)
    for v in [1.0] * 4 + [100.0] * 4:
        h.observe(v)
    snap = h.snapshot()
    assert snap["mean"] == 100.0  # the window is all-100s now
    assert snap["count"] == 8 and snap["sum"] == 404.0  # cumulative


def test_dropped_engine_is_collectable_and_health_degrades():
    """Neither the health-source registration nor an attached
    SloMonitor's callback may pin a dropped engine's KV cache; the
    health source reports the collection gracefully."""
    import gc

    from tpudl.obs.slo import Objective, SloMonitor
    from tpudl.serve.cache import PagedKVCache
    from tpudl.serve.engine import Engine
    from tpudl.serve.queue import AdmissionQueue

    import jax
    import jax.numpy as jnp

    template = {
        "layer": {
            "k": jax.ShapeDtypeStruct((2, 16, 2, 4), jnp.float32),
            "valid": jax.ShapeDtypeStruct((2, 16), jnp.bool_),
            "index": jax.ShapeDtypeStruct((), jnp.int32),
        }
    }
    mon = SloMonitor([Objective("o", "serve_ttft_ms", threshold=1.0)])
    engine = Engine(
        prefill_call=lambda *a: None, decode_call=lambda *a: None,
        params=None, cache=PagedKVCache(template, page_size=4),
        queue=AdmissionQueue(capacity=4), prompt_len=4,
    )
    engine.attach_slo(mon)
    import weakref

    ref = weakref.ref(engine)
    del engine
    gc.collect()
    assert ref() is None, "engine must be collectable once dropped"
    snap = obs_exporter.health_snapshot()
    assert snap["sources"]["serve_engine"] == {
        "healthy": True, "engine": "collected",
    }
    mon.observe("serve_ttft_ms", 0.5)  # the surviving monitor still works
    assert mon.health()["healthy"] is True


def test_unknown_path_404():
    with obs_exporter.ObsExporter(port=0) as ex:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{ex.port}/nope", timeout=10.0
            )
        assert ei.value.code == 404


# ---------------------------------------------------------------------------
# Live end-to-end: scraping a fit() in flight (the tier-1 smoke)
# ---------------------------------------------------------------------------


def test_fit_serves_live_metrics_and_heartbeat(tmp_path, monkeypatch):
    """The acceptance path: with TPUDL_OBS_PORT set, a running fit()
    serves /metrics (train histograms) and /healthz (ready, fresh
    train_loop heartbeat) MID-RUN — scraped from inside a logger
    callback while the loop is live."""
    import jax

    from tests.test_obs import _tiny_fit_setup
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    monkeypatch.setenv("TPUDL_OBS_PORT", "0")
    obs.enable(str(tmp_path / "obs"))
    state, step = _tiny_fit_setup()
    scraped = {}

    def logger(step_no, metrics):
        if scraped:
            return
        ex = obs_exporter.active_exporter()
        assert ex is not None, "fit() must start the exporter from env"
        _, scraped["metrics"] = _get(f"http://127.0.0.1:{ex.port}/metrics")
        scraped["status"], body = _get(f"http://127.0.0.1:{ex.port}/healthz")
        scraped["health"] = json.loads(body)

    state, metrics, info = fit(
        step, state,
        synthetic_classification_batches(
            8, image_shape=(16, 16, 3), num_classes=4, num_batches=8
        ),
        jax.random.key(1),
        log_every=4,
        logger=logger,
    )
    assert info["steps"] == 8
    assert scraped["status"] == 200
    hb = scraped["health"]["heartbeats"]["train_loop"]
    assert hb["running"] is True and hb["age_s"] < 60.0
    text = scraped["metrics"]
    assert "step_time_s_count" in text
    assert "data_wait_s_count" in text
    assert any(
        l.startswith("train_last_step ") for l in text.splitlines()
    )
    # After fit returns the heartbeat reports finished, not hung.
    final = obs_exporter.health_snapshot()["heartbeats"]["train_loop"]
    assert final["running"] is False and final["healthy"] is True


# ---------------------------------------------------------------------------
# Distributor per-rank heartbeats (unit level; the slow spawn test
# exercises the live path)
# ---------------------------------------------------------------------------


def test_distributor_rank_heartbeats_from_span_file_mtime(tmp_path):
    import os
    import time as _time

    from tpudl.runtime.distributor import _update_rank_heartbeats

    workers = tmp_path / "workers"
    workers.mkdir()
    hearts = {
        pid: obs_exporter.Heartbeat(
            f"rank{pid}", stale_after=10.0, clock=_time.time
        )
        for pid in (0, 1)
    }
    t0 = _time.time()
    for hb in hearts.values():
        hb.beat_at(t0)
    # Rank 0 made progress (recent span-file mtime); rank 1 hung 100
    # virtual seconds ago.
    f0 = workers / "spans-h-p0-111.jsonl"
    f0.write_text('{"kind": "span"}\n')
    f1 = workers / "spans-h-p1-222.jsonl"
    f1.write_text('{"kind": "span"}\n')
    os.utime(f1, (t0 - 100.0, t0 - 100.0))
    reg = obs_counters.registry()
    _update_rank_heartbeats(hearts, {0, 1}, str(workers))
    assert reg.gauge("rank0_last_heartbeat_age_s").value < 5.0
    assert reg.gauge("rank1_last_heartbeat_age_s").value > 90.0
    h = obs_exporter.health_snapshot()
    assert h["heartbeats"]["rank0"]["healthy"] is True
    # The hung rank flips /healthz within one poll interval.
    assert h["heartbeats"]["rank1"]["stale"] is True
    assert h["healthy"] is False
    # Rank exits (collected): stopped, never reported hung.
    _update_rank_heartbeats(hearts, {0}, str(workers))
    h = obs_exporter.health_snapshot()
    assert h["heartbeats"]["rank1"]["running"] is False
    assert h["healthy"] is True


def test_distributor_rank_heartbeats_degrade_to_liveness_without_obs():
    """Without span recording there is no progress signal to read, so
    an alive rank's heartbeat stays fresh (process liveness) — a
    healthy obs-less cohort must never false-flip /healthz stale, no
    matter how long it runs."""
    import time as _time

    from tpudl.runtime.distributor import _update_rank_heartbeats

    hearts = {
        0: obs_exporter.Heartbeat("rank0", stale_after=10.0,
                                  clock=_time.time)
    }
    hearts[0].beat_at(_time.time() - 1000.0)  # stale launch seed
    _update_rank_heartbeats(hearts, {0}, None)  # no obs dir
    h = obs_exporter.health_snapshot()["heartbeats"]["rank0"]
    assert h["healthy"] is True and h["age_s"] < 5.0
