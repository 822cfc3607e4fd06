"""Set-up has a timeline of its own (ISSUE 49).

The sites that run once a process (a session's construction and first
requests, a train state's initialisation and first step, a Pallas
kernel's trace) and the ONE ``jax.monitoring`` hook-up record whether or
not a span recorder is on: into the active recorder, else into one
bounded in-memory recorder that ``enable()`` hands over. The hot paths
keep their ``active_recorder()`` guard (tests/test_obs_timeline.py holds
them to it).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.analysis import dispatch
from tpudl.obs import counters as obs_counters
from tpudl.obs import report as obs_report
from tpudl.obs import spans as obs_spans


class FakeClock:
    """Every reading is one tick later than the last."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(autouse=True)
def _no_recorder(monkeypatch):
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    obs_spans.disable()
    yield
    obs_spans.disable()


def _held():
    """What the start-up recorder holds now."""
    return obs_spans.startup_recorder().records


def _named(records, name):
    return [r for r in records if r.get("name") == name]


def _inside(child, parent):
    return (child["ts"] >= parent["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-9)


def _children(records, parent):
    return sorted((r for r in records if r.get("parent") == parent["id"]),
                  key=lambda r: r["ts"])


def _within(records, phase):
    """What lies inside ``phase`` by the clock (a phase recorded after
    the fact is no parent)."""
    return [r for r in records if r is not phase and _inside(r, phase)]


def _no_span_outlasts_its_children(records):
    """A span's children lie inside it, one after another: their
    seconds never sum past its own (a kernel's trace is its program's
    trace's child, not its sibling)."""
    spans = [r for r in records if r.get("kind") == "span"]
    for parent in spans:
        kids = _children(spans, parent)
        assert all(_inside(k, parent) for k in kids), parent["name"]
        assert sum(k["dur"] for k in kids) <= parent["dur"] + 1e-9, (
            parent["name"])
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9, parent["name"]


# ---------------------------------------------------------------------------
# the start-up recorder and its hand-over
# ---------------------------------------------------------------------------


def test_a_recorder_enabled_late_is_handed_the_start_up(tmp_path):
    with obs_spans.startup_span("startup.outer", slots=2) as outer:
        with obs_spans.startup_span("startup.inner"):
            pass
        outer.note(pages=7)
    before = _held()
    assert [r["name"] for r in before] == ["startup.inner", "startup.outer"]
    assert all(r["cat"] == obs_spans.CAT_STARTUP for r in before)
    inner, outer = before
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert outer["slots"] == 2 and outer["pages"] == 7
    rec = obs_spans.enable(str(tmp_path))
    # id / parent / ts as they were: both clocks are time.monotonic.
    assert rec.records == before
    assert _held() == rec.records  # the sites now write to it directly
    assert obs_spans._startup.records == []
    with obs_spans.startup_span("startup.later"):
        pass
    assert [r["name"] for r in rec.records][-1] == "startup.later"
    assert obs_spans._startup.records == []
    # A later span's id is above the handed-over ones: one counter.
    assert rec.records[-1]["id"] > outer["id"]


def test_a_fake_clock_gives_byte_equal_exports(tmp_path, monkeypatch):
    texts = []
    for _ in range(2):
        rec = obs_spans.StartupRecorder()
        rec.clock, rec.host, rec.process = FakeClock(), "h", 0
        monkeypatch.setattr(obs_spans, "_startup", rec)
        with obs_spans.startup_span("startup.a", n=1) as a:
            obs_spans.startup_recorder().record(
                "program.trace", obs_spans.CAT_COMPILE, 1.5, 0.5,
                {"program": "p"},
            )
            a.note(m=2)
        texts.append(json.dumps([
            {k: v for k, v in r.items() if k not in ("id", "parent", "tid")}
            for r in rec.records
        ]))
        a_rec = _named(rec.records, "startup.a")[0]
        assert (a_rec["ts"], a_rec["dur"]) == (1.0, 1.0)
        assert _named(rec.records, "program.trace")[0]["parent"] == a_rec["id"]
    assert texts[0] == texts[1]


def test_the_start_up_recorder_is_bounded_and_counts_what_it_drops(
        monkeypatch):
    assert obs_spans.STARTUP_RECORDS == 512
    monkeypatch.setattr(obs_spans, "STARTUP_RECORDS", 4)
    rec = obs_spans.StartupRecorder()
    monkeypatch.setattr(obs_spans, "_startup", rec)
    dropped = obs_counters.registry().counter("startup_records_dropped")
    before = dropped.value
    for i in range(7):
        with obs_spans.startup_span("startup.x", i=i):
            pass
    assert [r["i"] for r in rec.records] == [0, 1, 2, 3]
    assert dropped.value - before == 3
    assert rec.drain() and rec.records == []


# ---------------------------------------------------------------------------
# what JAX says of every program, by program
# ---------------------------------------------------------------------------


def test_a_named_jit_yields_one_record_a_stage_and_a_second_call_none():
    def tpudl_probe_program(x):
        # ``jnp.where`` and ``jnp.einsum`` are jitted functions traced
        # INSIDE this trace: their seconds are this program's.
        return jnp.where(x > 0, jnp.einsum("ij,jk->ik", x, x), 0.0)

    x = jnp.ones((4, 4))
    obs_spans.startup_recorder().drain()
    counted = dispatch.compile_count()
    seconds = dispatch.compile_seconds()
    program = jax.jit(tpudl_probe_program)
    with obs_spans.startup_span("startup.probe"):
        program(x)
    records = _held()
    phase = _named(records, "startup.probe")[0]
    stages = [r for r in records if r["name"].startswith("program.")]
    assert [r["name"] for r in stages] == [
        "program.trace", "program.lower", "program.compile"
    ]
    for r in stages:
        assert r["program"] == "tpudl_probe_program"
        assert r["cat"] == obs_spans.CAT_COMPILE
        assert r["parent"] == phase["id"] and _inside(r, phase)
        assert r["dur"] > 0
    # ``compile_seconds`` reads the same seconds as the record: JAX's.
    assert dispatch.compile_seconds() - seconds == pytest.approx(
        stages[-1]["dur"])
    assert dispatch.compile_count() == counted + 1
    # Each stage was an OPEN span while it ran: nothing is left open.
    assert dispatch._building.stages == []
    assert obs_spans.startup_recorder()._open_spans() == []
    program(x)
    assert _held() == records


def test_the_counters_and_the_watcher_read_what_they_read_before():
    """``compile_count`` / ``compile_seconds`` / ``RecompileWatcher`` /
    ``assert_no_recompiles``: one backend compile each, whichever
    listener feeds them."""
    fn = jax.jit(lambda x: x * 2 + 1)
    three, five = jnp.ones((3,)), jnp.ones((5,))
    count, seconds = dispatch.compile_count(), dispatch.compile_seconds()
    with dispatch.RecompileWatcher("probe") as watch:
        fn(three)
        assert watch.count == 1
    assert watch.count == 1
    assert dispatch.compile_count() == count + 1
    assert dispatch.compile_seconds() > seconds
    with dispatch.assert_no_recompiles():
        fn(three)
    with pytest.raises(dispatch.DispatchHygieneError):
        with dispatch.assert_no_recompiles():
            fn(five)


def test_cache_hit_is_0_then_1_across_two_processes_worth_of_cache(
        tmp_path, monkeypatch, compile_cache_config):
    from tpudl.runtime import compile_cache

    cache_dir = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    assert compile_cache.enable_compile_cache() == cache_dir
    reg = obs_counters.registry()
    hits = reg.counter("compile_cache_hits").value
    misses = reg.counter("compile_cache_misses").value

    def built_anew():
        def tpudl_cached_probe(x):
            return jnp.tanh(x) * 3 + x

        obs_spans.startup_recorder().drain()
        jax.jit(tpudl_cached_probe)(jnp.ones((8, 8)))
        return [r for r in _named(_held(), "program.compile")
                if r["program"] == "tpudl_cached_probe"]

    (cold,) = built_anew()  # writes the entry
    (warm,) = built_anew()  # a fresh jit of the same text reads it
    assert cold["cache_hit"] == 0 and "cache_read_s" not in cold
    assert warm["cache_hit"] == 1 and warm["cache_read_s"] > 0
    assert reg.counter("compile_cache_hits").value == hits + 1
    assert reg.counter("compile_cache_misses").value >= misses + 1


# ---------------------------------------------------------------------------
# the phases, where the work happens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_decoder():
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=640)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def test_from_model_yields_the_phase_tree(tiny_decoder):
    import time

    from tpudl.serve import Request, ServeSession

    model, params = tiny_decoder
    obs_spans.startup_recorder().drain()
    t0 = time.monotonic()
    session = ServeSession.from_model(
        model, params, prompt_len=512, num_slots=2
    )
    requests = [Request(request_id=i, input_ids=[1, 2, 3], max_new_tokens=3)
                for i in range(2)]
    assert all(r.ok for r in session.serve(requests[:1]).values())
    wall = time.monotonic() - t0
    records = _held()
    (whole,) = _named(records, "startup.from_model")
    assert whole["parent"] is None
    assert (whole["slots"], whole["prompt_len"]) == (2, 512)
    assert whole["lengths"] == [256, 512]
    kids = _children(records, whole)
    assert [k["name"] for k in kids if k["name"].startswith("startup.")] == [
        "startup.weights", "startup.cache_template", "startup.pools",
        "startup.prefill_lengths",
    ]
    assert all(_inside(k, whole) for k in kids)
    (weights,) = _named(records, "startup.weights")
    assert (weights["leaves"], weights["bytes"]) == (0, 0)  # no chip here
    (pools,) = _named(records, "startup.pools")
    cache = session.engine.cache
    assert pools["pages"] == cache.num_pages
    assert pools["leaves"] == len(jax.tree.leaves(cache.cache))
    assert pools["bytes"] == sum(
        leaf.nbytes for leaf in jax.tree.leaves(cache.cache)
    )
    (lengths,) = _named(records, "startup.prefill_lengths")
    dry = _children(records, lengths)
    assert [(d["name"], d["rows"], d["kernel_layers"]) for d in dry] == [
        ("startup.prefill_dry_run", 256, 0),
        ("startup.prefill_dry_run", 512, 0),
    ]
    # The programs say where they were built.
    template = _named(records, "startup.cache_template")[0]
    assert [(r["name"], r["program"]) for r in _children(records, template)
            ] == [("program.trace", "tpudl_prefill")]
    # (A session jits its own prefill and seats; the programs shared by
    # every session, ``tpudl_first_token``, were built by whichever
    # test of this process came first.)
    built = {r["program"] for d in dry for r in _children(records, d)}
    assert {"tpudl_prefill", "tpudl_seat"} <= built
    # The session's FIRST call of serve, whole: the decode program.
    # Recorded after the fact and as an enclosing span: no parent of
    # what ran inside it, which goodput would else count twice.
    (first,) = _named(records, "startup.first_requests")
    assert first["parent"] is None and first["ts"] >= whole["ts"] + whole["dur"]
    assert first["cat"] == obs_spans.CAT_ENCLOSING
    assert _children(records, first) == []
    assert "tpudl_decode" in {
        r["program"] for r in _within(records, first)
        if r["name"] == "program.compile"
    }
    # A union no longer than the wall time.
    assert whole["dur"] + first["dur"] <= wall
    _no_span_outlasts_its_children(records)
    # goodput counts every second once: the first requests' span only
    # widens the window, the programs built inside it are accounted.
    from tpudl.obs import goodput

    cls = goodput.classify(records)
    assert cls["compile_s"] + cls["other_s"] <= cls["wall_s"] + 1e-9
    assert cls["compile_s"] >= sum(
        r["dur"] for r in _within(records, first)
        if r["name"] == "program.compile"
    )
    session.serve(requests[1:])
    assert len(_named(_held(), "startup.first_requests")) == 1


def test_a_prefill_span_counts_its_attentions_by_layer(tiny_decoder, tmp_path):
    """``attention_layers``: what the prefill program counted of itself
    while it was traced (every attention over a dense row cache), beside
    the layers whose attention the prefill kernel took (none here)."""
    from tpudl.serve import Request, ServeSession

    model, params = tiny_decoder
    rec = obs_spans.enable(str(tmp_path))
    session = ServeSession.from_model(model, params, prompt_len=8, num_slots=2)
    session.serve([Request(request_id=i, input_ids=[1, 2, 3],
                           max_new_tokens=2) for i in range(2)])
    prefills = _named(rec.records, "prefill")
    assert len(prefills) == 2
    for p in prefills:
        assert p["attention_layers"] == model.cfg.num_layers > 0
        assert p["attention_kernel_layers"] == p["attention_in_kernel"] == 0
    # With a recorder on, the first call's tree is any other call's.
    assert all(s["parent"] is None
               for s in _named(rec.records, "engine_step"))


def test_quantizing_is_a_phase_only_where_it_is_asked_for(tiny_decoder):
    from tpudl.serve import ServeSession

    model, params = tiny_decoder
    obs_spans.startup_recorder().drain()
    ServeSession.from_model(
        model, params, prompt_len=8, num_slots=2, weight_dtype="int8"
    )
    records = _held()
    (whole,) = _named(records, "startup.from_model")
    assert whole["lengths"] == [8]
    (quantize,) = _named(records, "startup.quantize")
    assert quantize["parent"] == whole["id"] and _inside(quantize, whole)
    # A quantized tree is held as given: nothing is turned.
    assert _named(records, "startup.weights") == []
    assert _named(records, "startup.prefill_lengths") == []


def test_the_first_stream_is_recorded_whole_too(tiny_decoder):
    from tpudl.serve import Request, ServeSession

    model, params = tiny_decoder
    session = ServeSession.from_model(
        model, params, prompt_len=8, num_slots=2
    )
    obs_spans.startup_recorder().drain()
    chunks = list(session.stream(
        [Request(request_id="a", input_ids=[1, 2], max_new_tokens=3)]
    ))
    assert chunks[-1].done
    (first,) = _named(_held(), "startup.first_requests")
    built = [r for r in _named(_held(), "program.compile")
             if r["program"] == "tpudl_decode"]
    assert built and all(_inside(r, first) for r in built)
    list(session.stream(
        [Request(request_id="b", input_ids=[1, 2], max_new_tokens=3)]
    ))
    assert len(_named(_held(), "startup.first_requests")) == 1


def test_a_train_state_and_its_first_step_are_start_up_phases():
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    import optax

    from tpudl.models.resnet import ResNetTiny
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    obs_spans.startup_recorder().drain()
    # Seven classes: a head no other test of this process initialises,
    # so its initialiser is a program built here and now.
    state = create_train_state(
        jax.random.key(0), ResNetTiny(num_classes=7),
        jnp.zeros((1, 16, 16, 3)), optax.sgd(0.05),
    )
    step = compile_step(
        make_classification_train_step(), make_mesh(MeshSpec(dp=-1)),
        state, None,
    )
    (init,) = _named(_held(), "startup.init_state")
    assert init["programs"] > 0
    assert init["programs"] == len([
        r for r in _named(_held(), "program.compile") if _inside(r, init)
    ])
    (made,) = _named(_held(), "startup.compile_step")
    assert made["ts"] >= init["ts"] + init["dur"]
    assert step._tpudl_first_step_began is None
    state, _, info = fit(
        step, state,
        synthetic_classification_batches(
            16, image_shape=(16, 16, 3), num_classes=7, num_batches=3
        ),
        jax.random.key(1),
    )
    assert info["steps"] == 3
    (first,) = _named(_held(), "startup.first_step")
    assert first["cat"] == obs_spans.CAT_ENCLOSING
    assert {(r["name"], r["program"]) for r in _within(_held(), first)
            if r.get("program") == "tpudl_train_step"} == {
        ("program.trace", "tpudl_train_step"),
        ("program.lower", "tpudl_train_step"),
        ("program.compile", "tpudl_train_step"),
    }
    # Asked once a call of fit(): a step that has run opens nothing.
    fit(step, state,
        synthetic_classification_batches(
            16, image_shape=(16, 16, 3), num_classes=7, num_batches=2
        ),
        jax.random.key(1))
    assert len(_named(_held(), "startup.first_step")) == 1
    assert step._tpudl_first_step_began is None
    _no_span_outlasts_its_children(_held())


def test_a_fit_that_never_calls_its_step_records_no_first_step():
    from tpudl.train import fit

    from tests.test_obs import _tiny_fit_setup

    state, step = _tiny_fit_setup()
    obs_spans.startup_recorder().drain()
    fit(step, state, iter(()), jax.random.key(1))
    assert _named(_held(), "startup.first_step") == []
    # A later call outside fit() is no first step of that fit().
    assert step._tpudl_first_step_began is None
    assert obs_spans.startup_recorder()._open_spans() == []


def test_a_pallas_wrapper_traced_in_interpret_mode_yields_kernel_trace():
    from tpudl.ops import grouped_matmul as gm

    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(32, 128)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(2, 128, 128)), jnp.float32)
    sizes = jnp.asarray([20, 12], jnp.int32)
    obs_spans.startup_recorder().drain()

    def tpudl_grouped_probe(lhs, rhs, sizes):
        # The wrapper's own jit would keep a trace another test made at
        # these shapes: the function under it is traced here and now.
        return gm.grouped_matmul.__wrapped__(lhs, rhs, sizes, interpret=True)

    with obs_spans.startup_span("startup.probe"):
        out = jax.jit(tpudl_grouped_probe)(lhs, rhs, sizes)
    records = _held()
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4)
    (kernel,) = _named(records, "kernel.trace")
    assert kernel["kernel"] == gm.NAME == "moe_grouped_matmul"
    assert kernel["cat"] == obs_spans.CAT_STARTUP
    # It ran while the program was traced: that trace's CHILD, so that
    # its seconds are taken from the trace's own and not from the
    # phase's a second time.
    (trace,) = [r for r in _named(records, "program.trace")
                if r["program"] == "tpudl_grouped_probe"]
    assert kernel["parent"] == trace["id"] and _inside(kernel, trace)
    (phase,) = _named(records, "startup.probe")
    assert trace["parent"] == phase["id"]
    _no_span_outlasts_its_children(records)
    from tpudl.obs import goodput

    own = dict((r["id"], s) for r, s in obs_spans.self_seconds(records))
    assert sum(own.values()) == pytest.approx(phase["dur"])
    cls = goodput.classify(records)
    assert cls["compile_s"] + cls["other_s"] == pytest.approx(phase["dur"])
    assert cls["idle_s"] == pytest.approx(0.0, abs=1e-6)


# ---------------------------------------------------------------------------
# the report's start-up table
# ---------------------------------------------------------------------------


def test_the_report_prints_a_start_up_table(tmp_path, capsys):
    rec = obs_spans.SpanRecorder(clock=FakeClock(), host="h", process=0)
    with rec.span("startup.from_model", obs_spans.CAT_STARTUP, slots=4):
        with rec.span("startup.pools", obs_spans.CAT_STARTUP,
                      bytes=1024, pages=9):
            pass
        trace = rec.begin("program.trace", obs_spans.CAT_COMPILE,
                          program="tpudl_decode")
        with rec.span("kernel.trace", obs_spans.CAT_STARTUP,
                      kernel="paged_attention"):
            pass
        trace.end()
        rec.record("program.lower", obs_spans.CAT_COMPILE, rec.clock(), 1.0,
                   {"program": "tpudl_decode"})
    # A session's first requests, after the fact: around the program
    # that was built on the way, by the clock alone.
    began = rec.clock()
    rec.record("program.compile", obs_spans.CAT_COMPILE, rec.clock(), 1.0,
               {"program": "tpudl_decode", "cache_hit": 1,
                "cache_read_s": 0.5})
    rec.clock()
    rec.record("startup.first_requests", obs_spans.CAT_ENCLOSING, began,
               rec.clock() - began)
    path = rec.export_jsonl(str(tmp_path / "spans.jsonl"))
    table = obs_report.startup_breakdown(rec.records)
    assert list(table) == ["phases", "programs", "kernels"]
    # One row a phase, in the order they began, each with its own
    # attributes; every second is counted once, under the innermost.
    assert [(p["name"], p["at_s"], p["total_s"], p["self_s"], p["attrs"])
            for p in table["phases"]] == [
        ("startup.from_model", 0.0, 8.0, 3.0, {"slots": 4}),
        ("startup.pools", 1.0, 1.0, 1.0, {"bytes": 1024, "pages": 9}),
        ("startup.first_requests", 9.0, 3.0, 2.0, {}),
    ]
    assert table["programs"]["tpudl_decode"] == {
        "trace_s": 3.0, "lower_s": 1.0, "compile_s": 1.0,
        "built": 1, "cache_hits": 1, "cache_read_s": 0.5,
    }
    assert table["kernels"] == {
        "paged_attention": {"count": 1, "trace_s": 1.0}
    }
    assert obs_report.main([path]) == 0
    out = capsys.readouterr().out
    for token in ("start-up phase", "startup.from_model", "slots=4",
                  "bytes=1024 pages=9", "program", "tpudl_decode",
                  "cache_hits", "cache_read_s", "kernel", "paged_attention"):
        assert token in out
    assert obs_report.startup_breakdown([]) == {}
    assert "startup" in obs_report.build_report(rec.records)["breakdown"]
