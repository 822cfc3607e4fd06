"""tpudl.obs: span recorder determinism, counters, goodput
classification, the report CLI, runtime instrumentation end-to-end
through fit(), and the distributor's per-worker span merge.

The observability contract under test (ISSUE 1 acceptance): a CPU
synthetic run of >= 20 steps leaves a span JSONL whose report shows the
data-wait / step / compile / checkpoint breakdown, a goodput fraction,
and per-host attribution; the Chrome-trace export is valid trace-event
JSON; and with observability disabled fit() leaves no file behind."""

import json
import os
import threading

import numpy as np
import pytest

import tpudl.obs as obs
from tpudl.obs import counters as obs_counters
from tpudl.obs import goodput as obs_goodput
from tpudl.obs import report as obs_report
from tpudl.obs import spans as obs_spans


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    """Observability state is process-global; isolate every test."""
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    obs.disable()
    obs_counters.registry().reset()
    yield
    obs.disable()
    obs_counters.registry().reset()


class FakeClock:
    """Monotonic fake: each call advances by `tick` seconds."""

    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


def _span(cat, ts, dur, host="h", process=0, **kw):
    return {
        "kind": "span", "name": cat, "cat": cat, "ts": float(ts),
        "dur": float(dur), "host": host, "process": process, **kw,
    }


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_span_nesting_and_export_determinism(tmp_path):
    rec = obs_spans.SpanRecorder(clock=FakeClock(), host="h", process=3)
    with rec.span("outer", obs_spans.CAT_STEP, step=0):
        with rec.span("inner", obs_spans.CAT_DATA_WAIT):
            pass
    # Clock ticks: outer enter=1, inner enter=2, inner exit=3, outer
    # exit=4 — the inner span closes (and records) first, fully nested
    # inside the outer one.
    inner, outer = rec.records
    assert (inner["name"], inner["ts"], inner["dur"]) == ("inner", 2.0, 1.0)
    assert (outer["name"], outer["ts"], outer["dur"]) == ("outer", 1.0, 3.0)
    assert outer["step"] == 0
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert all(r["host"] == "h" and r["process"] == 3 for r in rec.records)

    # JSONL round-trip is exact.
    p = rec.export_jsonl(str(tmp_path / "s.jsonl"))
    assert obs_spans.read_jsonl(p) == rec.records

    # Chrome trace export: valid trace-event JSON, microsecond units,
    # one process lane with a metadata row.
    cp = rec.export_chrome_trace(str(tmp_path / "t.json"))
    trace = json.load(open(cp))
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(meta) == 1 and "h p3" in meta[0]["args"]["name"]
    assert [(e["name"], e["ts"], e["dur"]) for e in xs] == [
        ("inner", 2e6, 1e6), ("outer", 1e6, 3e6),
    ]
    assert xs[1]["args"] == {"step": 0}


def test_streaming_jsonl_and_enable_disable(tmp_path):
    rec = obs.enable(str(tmp_path), clock=FakeClock())
    assert obs_spans.active_recorder() is rec
    rec.record("train_step", obs_spans.CAT_STEP, 1.0, 0.5, {"step": 0})
    rec.event("metrics", cat="metrics", step=1, loss=0.5)
    rec.counters({"counters": {"bytes_ingested": 7}})
    path = rec.path
    obs.disable()
    assert obs_spans.active_recorder() is None
    kinds = [r["kind"] for r in obs_spans.read_jsonl(path)]
    assert kinds == ["span", "event", "counters"]


def test_read_jsonl_tolerates_torn_tail(tmp_path):
    """A worker SIGKILLed mid-flush leaves a partial final line; the
    reader (and so the distributor's failure-path merge) must skip it
    instead of masking the real failure with a JSONDecodeError.
    Corruption ANYWHERE ELSE still raises."""
    p = tmp_path / "s.jsonl"
    good = json.dumps(_span("step", 0, 1))
    p.write_text(good + "\n" + '{"kind": "span", "na')
    assert obs_spans.read_jsonl(str(p)) == [json.loads(good)]
    p.write_text('{"tornemiddle\n' + good + "\n")
    with pytest.raises(json.JSONDecodeError):
        obs_spans.read_jsonl(str(p))


def test_env_var_auto_enables(tmp_path, monkeypatch):
    monkeypatch.setenv("TPUDL_OBS_DIR", str(tmp_path))
    rec = obs_spans.active_recorder()
    assert rec is not None and rec.path.startswith(str(tmp_path))


def test_disabled_span_is_shared_noop():
    s1 = obs.span("x", obs_spans.CAT_STEP)
    s2 = obs.span("y", obs_spans.CAT_COMPILE)
    assert s1 is s2  # one singleton: the disabled path allocates nothing
    with s1:
        pass


def test_recorder_thread_safety():
    rec = obs_spans.SpanRecorder(clock=FakeClock(0.001), host="h", process=0)

    def work():
        for i in range(200):
            rec.record("train_step", obs_spans.CAT_STEP, float(i), 0.1)

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(rec.records) == 800


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_counters_gauges_histograms():
    reg = obs_counters.Registry()
    reg.counter("bytes").inc(100)
    reg.counter("bytes").inc(50)
    reg.gauge("lr").set(0.1)
    h = reg.histogram("step_time_s")
    for v in [1.0, 2.0, 3.0, 4.0, 100.0]:
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["bytes"] == 150
    assert snap["gauges"]["lr"] == 0.1
    hs = snap["histograms"]["step_time_s"]
    assert hs["count"] == 5 and hs["min"] == 1.0 and hs["max"] == 100.0
    np.testing.assert_allclose(hs["p50"], 3.0)
    np.testing.assert_allclose(hs["p99"], np.percentile([1, 2, 3, 4, 100], 99))
    with pytest.raises(ValueError, match="monotonic"):
        reg.counter("bytes").inc(-1)
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("bytes")


# ---------------------------------------------------------------------------
# goodput
# ---------------------------------------------------------------------------


def test_goodput_classification_synthetic_timeline():
    # compile [1,6), then 10 x (0.2s data_wait + 0.8s step), then a 1s
    # checkpoint: wall 16s, productive 8s -> goodput 0.5, no idle.
    recs = [_span("compile", 1, 5)]
    t = 6.0
    for i in range(10):
        recs.append(_span("data_wait", t, 0.2))
        recs.append(_span("step", t + 0.2, 0.8))
        t += 1.0
    recs.append(_span("checkpoint", t, 1.0))
    cls = obs_goodput.classify(recs)
    np.testing.assert_allclose(cls["wall_s"], 16.0)
    np.testing.assert_allclose(cls["productive_s"], 8.0)
    np.testing.assert_allclose(cls["compile_s"], 5.0)
    np.testing.assert_allclose(cls["data_wait_s"], 2.0)
    np.testing.assert_allclose(cls["checkpoint_s"], 1.0)
    np.testing.assert_allclose(cls["idle_s"], 0.0, atol=1e-9)
    np.testing.assert_allclose(cls["goodput"], 0.5)
    assert cls["steps"] == 10

    # An uninstrumented gap becomes idle; an unknown category lands in
    # other_s; goodput drops accordingly.
    cls2 = obs_goodput.classify(
        [_span("step", 0, 1), _span("restart", 1, 2), _span("step", 5, 1)]
    )
    np.testing.assert_allclose(cls2["wall_s"], 6.0)
    np.testing.assert_allclose(cls2["other_s"], 2.0)
    np.testing.assert_allclose(cls2["idle_s"], 2.0)
    np.testing.assert_allclose(cls2["goodput"], 2.0 / 6.0)

    # An enclosing worker_run span (same clock, covers everything) only
    # WIDENS the window — summing it would double-count its interior and
    # wipe idle out.
    cls3 = obs_goodput.classify(
        [_span("worker", 0, 10), _span("step", 1, 2)]
    )
    np.testing.assert_allclose(cls3["wall_s"], 10.0)
    np.testing.assert_allclose(cls3["productive_s"], 2.0)
    np.testing.assert_allclose(cls3["other_s"], 0.0)
    np.testing.assert_allclose(cls3["idle_s"], 8.0)

    # Eval steps are useful work with their own bucket.
    cls4 = obs_goodput.classify(
        [_span("step", 0, 1), _span("eval", 1, 1)]
    )
    np.testing.assert_allclose(cls4["eval_s"], 1.0)
    np.testing.assert_allclose(cls4["goodput"], 1.0)
    assert cls4["steps"] == 1  # eval steps don't count as train steps

    assert obs_goodput.classify([])["goodput"] == 0.0


def test_goodput_by_process_aggregates():
    recs = (
        [_span("step", i, 0.5, process=0) for i in range(4)]
        + [_span("step", i, 1.0, process=1) for i in range(4)]
    )
    out = obs_goodput.classify_by_process(recs)
    assert set(out["per_process"]) == {"h/p0", "h/p1"}
    # p0: 2s productive / 3.5s wall; p1: 4s / 4s. Overall sums.
    np.testing.assert_allclose(
        out["overall"]["productive_s"], 6.0
    )
    np.testing.assert_allclose(out["overall"]["wall_s"], 7.5)
    np.testing.assert_allclose(out["overall"]["goodput"], 0.8)
    assert "goodput" in obs_goodput.format_goodput(out["overall"])


def test_goodput_separates_parent_and_worker_with_same_index():
    """A distributor parent and its rank-0 worker share (host, process
    index 0) but run unrelated monotonic clocks — grouping them together
    would compute wall-clock across incomparable epochs. The OS pid
    splits them, and the labels disambiguate."""
    # Parent clock near 100s; worker clock near 1e6s (different epoch).
    recs = (
        [_span("step", 100 + i, 1.0, pid=10) for i in range(3)]
        + [_span("step", 1e6 + i, 1.0, pid=20) for i in range(3)]
    )
    out = obs_goodput.classify_by_process(recs)
    assert set(out["per_process"]) == {"h/p0@10", "h/p0@20"}
    for cls in out["per_process"].values():
        np.testing.assert_allclose(cls["wall_s"], 3.0)
        np.testing.assert_allclose(cls["goodput"], 1.0)
    np.testing.assert_allclose(out["overall"]["wall_s"], 6.0)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------


def _report_fixture_records():
    """Two hosts: hostA steady 10 ms steps, hostB 20 ms steps (the
    straggler) plus one 150 ms outlier; a compile and a checkpoint."""
    recs = [_span("compile", 0, 2.0, host="hostA")]
    for i in range(20):
        recs.append(_span("data_wait", 2 + i * 0.012, 0.002,
                          host="hostA", step=i))
        recs.append(_span("step", 2.002 + i * 0.012, 0.010,
                          host="hostA", step=i))
    for i in range(20):
        dur = 0.150 if i == 7 else 0.020
        recs.append(_span("step", 2 + i * 0.022, dur,
                          host="hostB", process=1, step=i))
    recs.append(_span("checkpoint", 3.0, 0.5, host="hostA"))
    return recs


def test_report_build_and_straggler_attribution(tmp_path):
    recs = _report_fixture_records()
    rep = obs_report.build_report(recs)
    b = rep["breakdown"]
    assert set(b) >= {"data_wait", "step", "compile", "checkpoint"}
    assert b["step"]["count"] == 40
    assert b["compile"]["count"] == 1
    # hostB mean (26.5 ms) > 1.2x median-of-means -> straggler; hostA not.
    assert rep["per_host"]["hostB/p1"]["straggler"] is True
    assert rep["per_host"]["hostA/p0"]["straggler"] is False
    # The 150 ms step is an outlier (>3x p50), attributed to hostB.
    assert any(
        o["host"] == "hostB" and o["step"] == 7
        for o in rep["outlier_steps"]
    )
    assert 0.0 < rep["goodput"]["overall"]["goodput"] <= 1.0


def test_report_cli_golden(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    with open(path, "w") as f:
        for r in _report_fixture_records():
            f.write(json.dumps(r) + "\n")
    assert obs_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    # Golden structure: the breakdown table rows, the goodput line, the
    # per-host table with the straggler flagged, and the outlier list.
    for token in ("category", "data_wait", "step", "compile", "checkpoint",
                  "goodput", "host/process", "STRAGGLER", "outlier steps"):
        assert token in out, (token, out)
    assert "hostB/p1" in out
    # Golden step row: 20x10ms + 19x20ms + 1x150ms = 0.73 s total,
    # mean 18.25 ms, p50 15 ms (midpoint of the 10/20 ms halves),
    # p95 20 ms, p99 99.30 ms (interpolating toward the outlier).
    step_row = [l for l in out.splitlines() if l.startswith("step ")][0]
    assert step_row.split() == ["step", "40", "0.73", "18.25", "15.00",
                                "20.00", "99.30"]

    # --json round-trips; --chrome-trace writes valid trace-event JSON.
    trace_out = str(tmp_path / "trace.json")
    assert obs_report.main([str(path), "--json",
                            "--chrome-trace", trace_out]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["breakdown"]["step"]["count"] == 40
    trace = json.load(open(trace_out))
    # Every span re-exported: 1 compile + 20 data_wait + 40 steps + 1
    # checkpoint.
    assert sum(1 for e in trace["traceEvents"] if e.get("ph") == "X") == 62


def test_report_loads_directories(tmp_path):
    d = tmp_path / "obs" / "workers"
    d.mkdir(parents=True)
    with open(tmp_path / "obs" / "a.jsonl", "w") as f:
        f.write(json.dumps(_span("step", 0, 1)) + "\n")
    with open(d / "b.jsonl", "w") as f:
        f.write(json.dumps(_span("step", 1, 1, process=1)) + "\n")
    recs = obs_report.load_records([str(tmp_path / "obs")])
    assert len(recs) == 2  # recursive: workers/ included
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(FileNotFoundError, match="no .*jsonl"):
        obs_report.load_records([str(empty)])


# ---------------------------------------------------------------------------
# runtime instrumentation end-to-end
# ---------------------------------------------------------------------------


def _tiny_fit_setup():
    import jax
    import jax.numpy as jnp
    import optax

    from tpudl.models.resnet import ResNetTiny
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    model = ResNetTiny(num_classes=4)
    state = create_train_state(
        jax.random.key(0), model, jnp.zeros((1, 16, 16, 3)),
        optax.sgd(0.05),
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    step = compile_step(make_classification_train_step(), mesh, state, None)
    return state, step


def test_fit_observability_end_to_end(tmp_path, capsys):
    """The acceptance path: >= 20 fit steps with obs + checkpointing on,
    then the report CLI over the span dir shows the full breakdown,
    goodput, and per-host table, and the Chrome export is valid."""
    import jax

    from tpudl.checkpoint import CheckpointManager
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit
    from tpudl.train.logging import MetricLogger

    obs_dir = tmp_path / "obs"
    obs.enable(str(obs_dir))
    state, step = _tiny_fit_setup()
    with CheckpointManager(str(tmp_path / "ckpt")) as mgr:
        state, metrics, info = fit(
            step, state,
            synthetic_classification_batches(
                16, image_shape=(16, 16, 3), num_classes=4, num_batches=22
            ),
            jax.random.key(1),
            log_every=10,
            logger=MetricLogger(),
            checkpoint_manager=mgr,
            checkpoint_every=10,
        )
    assert info["steps"] == 22
    rec = obs_spans.active_recorder()
    records = rec.records
    cats = {r.get("cat") for r in records if r.get("kind") == "span"}
    assert {"step", "compile", "data_wait", "checkpoint"} <= cats
    # 22 calls = 1 compile + 21 steps; every step has a data_wait twin.
    spans = [r for r in records if r.get("kind") == "span"]
    assert sum(1 for s in spans if s["cat"] == "step") == 21
    # (The loop's own: the programs JAX built on the way are records of
    # their own, ``program.*``, tests/test_obs_startup.py.)
    assert sum(1 for s in spans if s["cat"] == "compile"
               and not s["name"].startswith("program.")) == 1
    assert sum(1 for s in spans if s["cat"] == "data_wait") == 22
    assert sum(1 for s in spans if s["cat"] == "checkpoint") >= 2
    # MetricLogger fanned metrics into the SAME stream (nested, so user
    # metric names can't collide with reserved record keys); fit
    # appended a counters snapshot with the latency histograms.
    assert any(
        r["kind"] == "event" and r["name"] == "metrics"
        and "loss" in r.get("metrics", {})
        for r in records
    )
    snaps = [r for r in records if r["kind"] == "counters"]
    assert snaps and snaps[-1]["data"]["histograms"]["step_time_s"][
        "count"
    ] == 21
    assert snaps[-1]["data"]["counters"]["checkpoint_saves"] >= 2

    chrome = rec.export_chrome_trace(str(tmp_path / "trace.json"))
    obs.disable()
    trace = json.load(open(chrome))
    assert sum(1 for e in trace["traceEvents"] if e.get("ph") == "X") == len(
        spans
    )

    capsys.readouterr()
    assert obs_report.main([str(obs_dir)]) == 0
    out = capsys.readouterr().out
    for token in ("data_wait", "step", "compile", "checkpoint", "goodput",
                  "host/process"):
        assert token in out, (token, out)


def test_fit_disabled_is_noop(tmp_path, monkeypatch):
    """No recorder, no env var: fit leaves NO span file anywhere and the
    loop takes the uninstrumented branch."""
    import jax

    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    monkeypatch.chdir(tmp_path)
    state, step = _tiny_fit_setup()
    state, metrics, info = fit(
        step, state,
        synthetic_classification_batches(
            16, image_shape=(16, 16, 3), num_classes=4, num_batches=3
        ),
        jax.random.key(1),
    )
    assert info["steps"] == 3
    assert obs_spans.active_recorder() is None
    assert list(tmp_path.rglob("*.jsonl")) == []


def test_evaluate_records_eval_spans(tmp_path):
    import jax

    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        evaluate,
        make_classification_eval_step,
    )

    state, _ = _tiny_fit_setup()
    mesh = make_mesh(MeshSpec(dp=-1))
    eval_step = compile_step(
        make_classification_eval_step(), mesh, state, None,
        donate_state=False, has_rng=False,
    )
    rec = obs.enable(str(tmp_path))
    evaluate(
        eval_step, state,
        synthetic_classification_batches(
            8, image_shape=(16, 16, 3), num_classes=4, num_batches=3
        ),
    )
    spans = [r for r in rec.records if r.get("kind") == "span"
             and not r["name"].startswith("program.")]
    assert sum(1 for s in spans if s["cat"] == "compile") == 1
    # Eval steps carry their own category so the report's train-step
    # outlier/straggler statistics never mix in eval durations.
    assert sum(1 for s in spans if s["cat"] == "eval") == 2
    assert sum(1 for s in spans if s["cat"] == "step") == 0
    assert sum(1 for s in spans if s["cat"] == "data_wait") == 3


def test_checkpoint_spans(tmp_path):
    import jax.numpy as jnp
    import optax

    from tpudl.checkpoint import restore_train_state, save_train_state
    from tpudl.train.loop import TrainState

    state = TrainState.create(
        apply_fn=lambda *a, **k: None,
        params={"w": jnp.ones((4,))},
        tx=optax.sgd(0.1),
    )
    rec = obs.enable(str(tmp_path / "obs"))
    save_train_state(str(tmp_path / "ckpt"), state)
    restore_train_state(str(tmp_path / "ckpt"), state)
    names = [
        r["name"] for r in rec.records
        if r.get("cat") == obs_spans.CAT_CHECKPOINT
    ]
    assert names == ["save_train_state", "restore_train_state"]


def test_ingest_spans_and_byte_counters(tmp_path):
    from tpudl.data.ingest import ingest_sst2_tsv

    tsv = tmp_path / "train.tsv"
    sentence = "a fine movie about observability " * 8  # ~264 bytes
    with open(tsv, "w", encoding="utf-8") as f:
        f.write("sentence\tlabel\n")
        for i in range(8):
            f.write(f"{sentence}{i}\t{i % 2}\n")
    rec = obs.enable(str(tmp_path / "obs"))
    ingest_sst2_tsv(str(tsv), str(tmp_path / "out"))
    chunks = [r for r in rec.records if r.get("name") == "ingest_chunk"]
    assert len(chunks) == 1 and chunks[0]["rows"] == 8
    snap = obs_counters.registry().snapshot()
    # Text columns count STRING PAYLOAD bytes (8 x ~264-byte sentences),
    # not 8-byte object pointers — pointer counting would report < 200.
    assert snap["counters"]["bytes_ingested"] > 8 * 200
    assert snap["counters"]["rows_ingested"] == 8


# ---------------------------------------------------------------------------
# distributor merge
# ---------------------------------------------------------------------------


def test_distributor_merges_worker_span_files(tmp_path):
    """run()'s merge step folds per-worker span files (host/process
    tagged) into the parent's stream and removes them, so one report
    sees every rank exactly once."""
    from tpudl.runtime.distributor import TpuDistributor

    rec = obs.enable(str(tmp_path))
    d = TpuDistributor(num_processes=2)
    workers = d._obs_workers_dir()
    assert workers == os.path.join(os.path.dirname(rec.path), "workers")
    os.makedirs(workers)
    for p in range(2):
        with open(os.path.join(workers, f"spans-h-p{p}.jsonl"), "w") as f:
            f.write(json.dumps(
                _span("step", 0, 0.01 * (p + 1), host="wh", process=p)
            ) + "\n")
    d._merge_worker_spans(workers)
    merged = [
        r for r in rec.records
        if r.get("kind") == "span" and r.get("host") == "wh"
    ]
    assert sorted(r["process"] for r in merged) == [0, 1]
    assert not os.path.exists(workers)  # consumed: no double counting


def test_distributor_without_obs_has_no_workers_dir():
    from tpudl.runtime.distributor import TpuDistributor

    assert TpuDistributor(num_processes=2)._obs_workers_dir() is None


@pytest.mark.slow
def test_spawn_merge_and_straggler_report(tmp_path):
    """Real 2-process spawn: each worker streams its own span file (rank
    1 deliberately 10x slower), run() merges, and the report attributes
    the straggler — the cross-host diagnosis path, executed."""
    from tests import dist_helpers
    from tpudl.runtime.distributor import TpuDistributor

    rec = obs.enable(str(tmp_path))
    d = TpuDistributor(num_processes=2, platform="cpu",
                       devices_per_process=1)
    assert d.run(dist_helpers.record_obs_spans) == [0, 1]
    records = rec.records
    step_procs = sorted(
        r["process"] for r in records
        if r.get("cat") == "step" and r.get("step") == 0
    )
    assert step_procs == [0, 1]
    assert any(r.get("name") == "worker_run" for r in records)
    rep = obs_report.build_report(records)
    stragglers = [k for k, v in rep["per_host"].items() if v["straggler"]]
    assert len(stragglers) == 1 and stragglers[0].endswith("/p1")
