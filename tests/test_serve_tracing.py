"""Per-request distributed tracing through the serve path (ISSUE 6
tentpole piece 2): request_id propagated from admission through
prefill, every decode chunk, and completion — and ``report.py
--request <id>`` stitching one request's timeline with a TTFT
decomposition that sums (within tolerance) to the measured
TTFT + generation time."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.obs as obs
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import report as obs_report
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession

PROMPT_LEN = 8


@pytest.fixture(autouse=True)
def _clean():
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter._reset_health_for_tests()
    yield
    obs.disable()
    obs_counters.registry().reset()
    obs_exporter._reset_health_for_tests()


@pytest.fixture(scope="module")
def model_and_params():
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _recorded_run(model, params, tmp_path, n=5, **kw):
    obs.enable(str(tmp_path / "obs"))
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2, **kw
    )
    rng = np.random.default_rng(0)
    requests = [
        Request(
            f"r{i}",
            rng.integers(1, 500, size=4).tolist(),
            max_new_tokens=int(rng.integers(3, 8)),
        )
        for i in range(n)
    ]
    results = session.serve(requests)
    rec = obs_spans.active_recorder()
    records = rec.records
    path = rec.path
    obs.disable()
    return records, path, results


def test_request_trace_legs_recorded(model_and_params, tmp_path):
    model, params = model_and_params
    records, _, results = _recorded_run(model, params, tmp_path)
    # Admission events for every request, in the queue's own push.
    queued = [
        r for r in records
        if r.get("kind") == "event" and r.get("name") == "request_queued"
    ]
    assert sorted(r["request_id"] for r in queued) == [
        f"r{i}" for i in range(5)
    ]
    # Every prefill span carries its request_id; every decode chunk
    # names the requests it advanced.
    # (A prefill's and a decode_step's children share their parent's
    # category; a count over a category takes the spans with no parent
    # of it.)
    prefills = obs_spans.without_same_category_children(
        r for r in records
        if r.get("kind") == "span" and r.get("cat") == "serve_prefill"
    )
    assert sorted(p["request_id"] for p in prefills) == [
        f"r{i}" for i in range(5)
    ]
    decodes = obs_spans.without_same_category_children(
        r for r in records
        if r.get("kind") == "span" and r.get("cat") == "serve_decode"
    )
    assert decodes and all(d["name"] == "decode_step" for d in decodes)
    assert all("rids" in d for d in decodes)
    assert all(len(d["rids"]) == d["busy"] for d in decodes)
    # Completion events close each trace with the measured aggregates.
    completes = {
        r["request_id"]: r for r in records
        if r.get("kind") == "event" and r.get("name") == "request_complete"
    }
    for rid, res in results.items():
        assert completes[rid]["finish_reason"] == res.finish_reason
        assert completes[rid]["num_tokens"] == len(res.tokens)
        assert completes[rid]["ttft_s"] == pytest.approx(res.ttft_s)


@pytest.mark.parametrize("options, prompts, rows, tokens", [
    # Padded to the one length of an 8-token window, whatever they hold.
    ({}, [[5, 6, 7], [8, 9, 10, 11, 12, 13, 14, 15], [16]],
     [8, 8, 8], [3, 8, 1]),
    # A shared prefix: the first prompt runs whole, the next run the
    # suffix the radix tree leaves them (pages of 2; the last token is
    # always run), left-aligned with no padding.
    ({"prefix_share": True, "page_size": 2},
     [[5, 6, 7, 8, 9]] * 2 + [[5, 6, 7, 8, 20, 21]],
     [8, 1, 2], [5, 1, 2]),
])
def test_prefill_spans_and_counters_say_rows_and_tokens(
    model_and_params, tmp_path, options, prompts, rows, tokens
):
    """A ``prefill`` span says the length its program ran (``rows``)
    and the prompt's tokens among them (``tokens``); the counters
    ``serve_prefill_rows`` / ``serve_prefill_tokens`` sum them beside
    ``serve_prefills``."""
    model, params = model_and_params
    obs.enable(str(tmp_path / "obs"))
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=1, **options
    )
    got = session.serve([
        Request(f"p{i}", ids, max_new_tokens=2)
        for i, ids in enumerate(prompts)
    ])
    records = obs_spans.active_recorder().records
    obs.disable()
    assert all(r.ok for r in got.values())
    spans = {
        r["request_id"]: r for r in records
        if r.get("kind") == "span" and r.get("name") == "prefill"
    }
    assert [spans[f"p{i}"]["rows"] for i in range(3)] == rows
    assert [spans[f"p{i}"]["tokens"] for i in range(3)] == tokens
    assert [spans[f"p{i}"]["prefix_hit_tokens"] for i in range(3)] == [
        len(ids) - n for ids, n in zip(prompts, tokens)
    ]
    reg = obs_counters.registry()
    assert reg.counter("serve_prefills").value == 3
    assert reg.counter("serve_prefill_rows").value == sum(rows)
    assert reg.counter("serve_prefill_tokens").value == sum(tokens)


def test_request_timeline_decomposition_sums(model_and_params, tmp_path):
    """The acceptance criterion: queue-wait + prefill + decode
    decomposition sums (within tolerance) to the measured
    TTFT + generation time — and queue_wait + prefill equals TTFT
    exactly (both ends measured on the same clock)."""
    model, params = model_and_params
    records, _, results = _recorded_run(model, params, tmp_path)
    for rid, res in results.items():
        tl = obs_report.build_request_timeline(records, rid)
        assert tl["found"] == {
            "queued": True, "prefill": True,
            "decode_chunks": tl["found"]["decode_chunks"],
            "complete": True,
        }
        assert tl["found"]["decode_chunks"] >= len(res.tokens) - 1
        d = tl["decomposition"]
        # Exact identity: TTFT = queue wait (submit -> seat) + prefill
        # span (seat -> first token), by construction of the engine's
        # timestamps.
        assert d["queue_wait_s"] + d["prefill_s"] == pytest.approx(
            res.ttft_s, rel=1e-6
        )
        # The full decomposition covers the request's measured life up
        # to host bookkeeping between decode chunks.
        assert d["measured_total_s"] == pytest.approx(
            res.ttft_s + (res.tpot_s or 0.0) * (len(res.tokens) - 1),
            rel=1e-6,
        )
        assert d["accounted_s"] <= d["measured_total_s"] * 1.02
        assert d["coverage"] is not None and d["coverage"] > 0.5, d
        # Timeline ordering: queued -> prefill -> chunks -> complete.
        whats = [e["what"] for e in tl["timeline"]]
        assert whats[0] == "queued" and whats[1] == "prefill"
        assert whats[-1] == "complete"


def test_report_request_cli(model_and_params, tmp_path, capsys):
    model, params = model_and_params
    _, path, results = _recorded_run(model, params, tmp_path, n=3)
    assert obs_report.main([path, "--request", "r1"]) == 0
    out = capsys.readouterr().out
    for token in ("request r1", "queued", "prefill", "decode_chunk",
                  "complete", "queue_wait", "measured ttft", "coverage"):
        assert token in out, (token, out)
    # --json round-trips the same structure.
    assert obs_report.main([path, "--request", "r1", "--json"]) == 0
    tl = json.loads(capsys.readouterr().out)
    assert tl["request_id"] == "r1"
    assert tl["num_tokens"] == len(results["r1"].tokens)
    # Unknown id: a clear error, nonzero exit.
    assert obs_report.main([path, "--request", "nope"]) == 1
    assert "no trace records" in capsys.readouterr().out


def test_shed_reason_breakdown_row(model_and_params, tmp_path, capsys):
    """The cross-request aggregation: completed and shed requests land
    in the report's serve-requests breakdown by finish_reason."""
    model, params = model_and_params
    t = [0.0]
    obs.enable(str(tmp_path / "obs"))
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        clock=lambda: t[0], queue_capacity=5,
    )
    session.submit(Request("late", [1, 2, 3], max_new_tokens=3,
                           deadline_s=1.0))
    t[0] = 5.0  # deadline passes while queued
    for i in range(4):
        session.submit(Request(f"ok{i}", [1, 2, 3], max_new_tokens=3))
    session.submit(Request("over", [1, 2, 3], max_new_tokens=3))  # full
    results = session.collect()
    rec = obs_spans.active_recorder()
    records, path = rec.records, rec.path
    obs.disable()

    assert results["late"].finish_reason == "shed_timeout"
    assert results["over"].finish_reason == "shed_capacity"
    breakdown = obs_report.serve_request_breakdown(records)
    assert breakdown["length"]["count"] == 4
    assert breakdown["shed_timeout"]["count"] == 1
    assert breakdown["shed_capacity"]["count"] == 1
    assert breakdown["shed_timeout"]["mean_queue_wait_ms"] == pytest.approx(
        5000.0
    )
    assert breakdown["length"]["tokens"] == 12
    # And the rendered report carries the row.
    assert obs_report.main([path]) == 0
    out = capsys.readouterr().out
    assert "serve requests" in out
    assert "shed_timeout" in out and "shed_capacity" in out


def test_admit_spans_count_what_was_seated_shed_and_left(
        model_and_params, tmp_path):
    """``admit`` on the engine's own clock readings: what it seated,
    what it shed and what it left waiting, and the prefill dispatch and
    the seat it caused inside it; the wait for each first token is a
    ``prefill`` of its own, where the engine reads it back."""
    model, params = model_and_params
    t = [0.0]
    obs.enable(str(tmp_path / "obs"))
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        clock=lambda: t[0],
    )
    session.submit(Request("late", [1, 2, 3], max_new_tokens=3,
                           deadline_s=1.0))
    t[0] = 5.0  # its deadline passes while it waits
    for i in range(3):
        session.submit(Request(f"ok{i}", [1, 2, 3], max_new_tokens=3))
    session.engine.step()
    session.collect()
    records = obs_spans.active_recorder().records
    obs.disable()
    spans = [r for r in records if r.get("kind") == "span"]
    admits = [s for s in spans if s["name"] == "admit"]
    first = admits[0]
    assert (first["popped"], first["shed"], first["queue_depth"]) == (2, 1, 1)
    assert sum(a["popped"] for a in admits) == 3
    assert sum(a["shed"] for a in admits) == 1
    assert admits[-1]["queue_depth"] == 0
    caused = [s["name"] for s in spans if s["parent"] == first["id"]]
    assert caused == ["prefill.dispatch", "seat", "prefill.dispatch", "seat"]
    # Nothing was in flight at any seat (both slots end on one step):
    # each first token is read back inside the ``decode_step`` of the
    # call that seated it, in seat order, in a wait of its own.
    steps = [s["id"] for s in spans if s["name"] == "decode_step"]
    waits = [s for s in spans if s["name"] == "prefill"]
    assert [w["request_id"] for w in waits] == ["ok0", "ok1", "ok2"]
    assert [w["parent"] for w in waits] == [steps[0], steps[0], steps[2]]
    assert [w["behind"] for w in waits] == [0, 0, 0]
    # The report's serve totals count a step and a prefill once each.
    rows = obs_report.build_report(records)["breakdown"]
    assert rows["serve_engine"]["count"] == len(
        [s for s in spans if s["name"] == "engine_step"])
    assert rows["serve_prefill"]["count"] == 3
    assert rows["serve_seat"]["count"] == 3


def test_live_metrics_during_serve_session(model_and_params, tmp_path,
                                           monkeypatch):
    """Acceptance: with the exporter up, a live serve session's
    TTFT/TPOT/queue-wait histograms are scrapeable as Prometheus text
    and /healthz reports the engine's slot/queue state ready."""
    import urllib.request

    model, params = model_and_params
    monkeypatch.setenv("TPUDL_OBS_PORT", "0")
    try:
        session = ServeSession.from_model(
            model, params, prompt_len=PROMPT_LEN, num_slots=2
        )
        ex = obs_exporter.active_exporter()
        assert ex is not None, "ServeSession must start the exporter"
        session.serve([
            Request(f"r{i}", [1, 2, 3], max_new_tokens=4) for i in range(4)
        ])
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/metrics", timeout=10.0
        ) as r:
            text = r.read().decode()
        for name in ("serve_ttft_ms", "serve_tpot_ms",
                     "serve_queue_wait_ms"):
            assert f"# TYPE {name} summary" in text
            assert f"{name}_count" in text
        assert "serve_slots_busy" in text
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ex.port}/healthz", timeout=10.0
        ) as r:
            health = json.loads(r.read().decode())
        assert health["healthy"] is True
        eng = health["sources"]["serve_engine"]
        assert eng["num_slots"] == 2 and eng["queue_depth"] == 0
        assert eng["slots_busy"] == 0  # drained
    finally:
        obs_exporter.stop_exporter()


def test_shed_timeline_is_single_completion(model_and_params, tmp_path):
    model, params = model_and_params
    t = [0.0]
    obs.enable(str(tmp_path / "obs"))
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2,
        clock=lambda: t[0],
    )
    session.submit(Request("late", [1, 2], max_new_tokens=2, deadline_s=1.0))
    t[0] = 9.0
    session.submit(Request("ok", [1, 2], max_new_tokens=2))
    session.collect()
    records = obs_spans.active_recorder().records
    obs.disable()
    tl = obs_report.build_request_timeline(records, "late")
    assert tl["finish_reason"] == "shed_timeout"
    assert tl["found"]["prefill"] is False
    assert tl["found"]["decode_chunks"] == 0
    assert [e["what"] for e in tl["timeline"]] == ["queued", "complete"]


def test_router_trace_hops_and_router_ttft_decomposition(
    model_and_params, tmp_path
):
    """A LIVE two-replica router run records the fleet-trace hops
    (router door -> replica inbox -> admission -> prefill -> decode ->
    served -> complete) and the stitched decomposition sums to the
    router-measured TTFT: inbox_wait + queue_wait + prefill ==
    router_ttft, every term a measured duration."""
    from tpudl.serve import Replica, Router

    model, params = model_and_params
    obs.enable(str(tmp_path / "obs"))
    replicas = [
        Replica(
            f"rep{i}",
            ServeSession.from_model(
                model, params, prompt_len=PROMPT_LEN, num_slots=2
            ),
        )
        for i in range(2)
    ]
    rng = np.random.default_rng(2)
    requests = [
        Request(
            f"r{i}",
            rng.integers(1, 500, size=4).tolist(),
            max_new_tokens=int(rng.integers(3, 8)),
        )
        for i in range(5)
    ]
    with Router(replicas) as router:
        results = router.serve(requests, timeout_s=300.0)
    records = obs_spans.active_recorder().records
    obs.disable()
    assert all(res.ok for res in results.values())
    for rid, res in results.items():
        tl = obs_report.build_request_timeline(records, rid)
        assert tl["warnings"] == []
        assert tl["hops"]["routed"] is True
        assert tl["hops"]["replica"] in {"rep0", "rep1"}
        whats = [e["what"] for e in tl["timeline"]]
        assert whats[0] == "routed"
        assert "replica_dequeue" in whats and "served" in whats
        assert whats[-1] == "complete"
        d = tl["decomposition"]
        assert d["inbox_wait_s"] is not None
        assert d["router_ttft_s"] == pytest.approx(
            res.ttft_s + d["inbox_wait_s"], rel=1e-6
        )
        # The fleet acceptance identity, on real measurements.
        assert (
            d["inbox_wait_s"] + d["queue_wait_s"] + d["prefill_s"]
            == pytest.approx(d["router_ttft_s"], rel=1e-6)
        )
    # The same records render as a fleet report with every request
    # fully stitched.
    fleet = obs_report.build_fleet_report(records)
    assert fleet["num_requests"] == 5
    assert fleet["partial_traces"] == {}
    assert fleet["router_ttft"]["count"] == 5
