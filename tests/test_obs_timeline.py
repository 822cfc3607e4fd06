"""One timeline from inside the program (ISSUE 24): span ids and
parents, the recorder's blocks, the engine's and the trainer's phases
as spans and as profiler annotations, the paged cache's occupancy
counters, and names for what runs on the device."""

import glob
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.obs as obs
from tpudl.obs import counters as obs_counters
from tpudl.obs import exporter as obs_exporter
from tpudl.obs import goodput
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


class FakeClock:
    def __init__(self, tick=1.0):
        self.t = 0.0
        self.tick = tick

    def __call__(self):
        self.t += self.tick
        return self.t


@pytest.fixture(scope="module")
def model_and_params():
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _requests(n, tag="r", seed=0, new=(3, 8)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            f"{tag}{i}", rng.integers(1, 500, size=4).tolist(),
            max_new_tokens=int(rng.integers(*new)),
        )
        for i in range(n)
    ]


def _session(model, params, **kw):
    kw.setdefault("page_size", 4)
    return ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=2, **kw
    )


#: What a process records of how it began, recorder or not (ISSUE 49,
#: tests/test_obs_startup.py): its phases, the programs JAX built and
#: the kernels traced on the way.
START_UP = ("startup.", "program.", "kernel.")


def _spans(records, name=None):
    """The spans called ``name``; with no name, the hot path's (the
    start-up timeline left out)."""
    return [r for r in records if r.get("kind") == "span"
            and (r["name"] == name if name is not None
                 else not r["name"].startswith(START_UP))]


# ---------------------------------------------------------------------------
# A. the recorder
# ---------------------------------------------------------------------------


def test_ids_and_parents_nest_on_one_thread():
    rec = obs_spans.SpanRecorder(clock=FakeClock(), host="h", process=0)
    outer = rec.begin("outer", "a", step=1)
    mid = rec.begin("mid", "a")
    with rec.span("inner", "b"):
        pass
    after = rec.record("after_the_fact", "b", 2.0, 0.5)
    mid.end(extra=7)
    sibling = rec.begin("sibling", "a")
    sibling.end()
    outer.end(busy=3)
    by = {s["name"]: s for s in _spans(rec.records)}
    assert by["outer"]["parent"] is None
    assert by["mid"]["parent"] == by["outer"]["id"]
    assert by["inner"]["parent"] == by["mid"]["id"]
    assert after["parent"] == by["mid"]["id"]
    assert by["sibling"]["parent"] == by["outer"]["id"]
    ids = [s["id"] for s in by.values()]
    assert len(set(ids)) == len(ids)
    # Attributes given at the beginning and at the end both land.
    assert by["outer"]["step"] == 1 and by["outer"]["busy"] == 3
    assert by["mid"]["extra"] == 7


def test_ids_are_unique_across_recorders_in_one_process():
    a = obs_spans.SpanRecorder(clock=FakeClock())
    b = obs_spans.SpanRecorder(clock=FakeClock())
    for rec in (a, b, a, b):
        with rec.span("x", "c"):
            pass
    ids = [s["id"] for s in _spans(a.records) + _spans(b.records)]
    assert len(set(ids)) == 4


def test_parents_do_not_cross_threads():
    """What a prefetch (or checkpoint) thread records while the loop's
    thread holds a span open is no child of that span; its own spans
    nest among themselves."""
    rec = obs_spans.SpanRecorder(clock=FakeClock(0.001))
    ready, done = threading.Event(), threading.Event()

    def worker():
        ready.wait()
        outer = rec.begin("assemble", "data")
        rec.begin("place", "data").end()
        outer.end()
        done.set()

    t = threading.Thread(target=worker)
    t.start()
    step = rec.begin("train_step", obs_spans.CAT_STEP)
    ready.set()
    done.wait()
    step.end()
    t.join()
    by = {s["name"]: s for s in _spans(rec.records)}
    assert by["train_step"]["parent"] is None
    assert by["assemble"]["parent"] is None
    assert by["place"]["parent"] == by["assemble"]["id"]
    assert by["assemble"]["tid"] != by["train_step"]["tid"]


def test_prefetch_thread_records_are_no_children_of_the_step(tmp_path):
    """Through fit and ``prefetch_to_device``: every data_wait and
    train_step span of the loop is a top-level span of the loop's
    thread, whatever the prefetch thread does meanwhile."""
    from tpudl.data.prefetch import prefetch_to_device
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    from tests.test_obs import _tiny_fit_setup

    rec = obs.enable(str(tmp_path))
    state, step = _tiny_fit_setup()
    feed = prefetch_to_device(synthetic_classification_batches(
        16, image_shape=(16, 16, 3), num_classes=4, num_batches=5
    ))
    fit(step, state, feed, jax.random.key(1))
    spans = _spans(rec.records)
    loop = [s for s in spans
            if s["name"] in ("data_wait", "train_step", "compile_step")]
    assert len(loop) >= 10
    assert all(s["parent"] is None for s in loop)
    assert len({s["tid"] for s in loop}) == 1


def test_out_of_order_end_drops_what_was_left_open():
    """An exception that unwinds past open spans: ending the outer one
    drops them, unrecorded, and the next span has no stale parent."""
    rec = obs_spans.SpanRecorder(clock=FakeClock())
    outer = rec.begin("outer", "a")
    rec.begin("abandoned", "a")
    outer.end()
    nxt = rec.begin("next", "a")
    nxt.end()
    names = [s["name"] for s in _spans(rec.records)]
    assert names == ["outer", "next"]
    assert _spans(rec.records, "next")[0]["parent"] is None


def test_cancel_records_nothing():
    rec = obs_spans.SpanRecorder(clock=FakeClock())
    rec.begin("waited_for_nothing", "a").cancel()
    with rec.span("real", "a"):
        pass
    assert [s["name"] for s in _spans(rec.records)] == ["real"]
    assert _spans(rec.records)[0]["parent"] is None


def test_explicit_timestamps_set_the_extent():
    rec = obs_spans.SpanRecorder(clock=FakeClock())
    got = rec.begin("prefill", "serve_prefill", 10.0).end(12.5)
    assert (got["ts"], got["dur"]) == (10.0, 2.5)
    assert rec.clock() == 1.0  # the clock was never read


def test_buffered_records_survive_disable_and_a_read(tmp_path):
    rec = obs.enable(str(tmp_path))
    path = rec.path
    for i in range(5):
        rec.record("train_step", obs_spans.CAT_STEP, float(i), 0.5)
    # Nothing is written per record...
    assert os.path.getsize(path) == 0
    # ...a read of `records` writes the block out and reads it back,
    assert len(rec.records) == 5
    assert os.path.getsize(path) > 0
    rec.event("late", cat="x")
    # ...and so does disable().
    obs.disable()
    got = obs_spans.read_jsonl(path)
    assert [r["kind"] for r in got] == ["span"] * 5 + ["event"]


def test_a_full_block_is_written_without_being_asked(tmp_path, monkeypatch):
    monkeypatch.setattr(obs_spans, "BLOCK_RECORDS", 8)
    rec = obs.enable(str(tmp_path))
    for i in range(7):
        rec.record("s", "c", float(i), 0.1)
    assert os.path.getsize(rec.path) == 0
    rec.record("s", "c", 7.0, 0.1)
    assert len(obs_spans.read_jsonl(rec.path)) == 8
    for i in range(3):
        rec.record("s", "c", 8.0 + i, 0.1)
    # A killed worker loses at most its last block.
    assert len(obs_spans.read_jsonl(rec.path)) == 8
    assert len(rec.records) == 11


def test_active_recorder_reads_the_environment_once(monkeypatch):
    calls = []
    real = obs_spans.env_str

    def counting(name, *a, **kw):
        calls.append(name)
        return real(name, *a, **kw)

    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    monkeypatch.setattr(obs_spans, "env_str", counting)
    obs.disable()
    for _ in range(50):
        assert obs_spans.active_recorder() is None
    assert calls == ["TPUDL_OBS_DIR"]
    # disable() allows one more look (a test, or a worker, that sets
    # the variable afterwards).
    obs.disable()
    assert obs_spans.active_recorder() is None
    assert len(calls) == 2


def test_spans_module_does_not_import_jax():
    import subprocess
    import sys

    code = (
        "import sys; import tpudl.obs.spans as s; "
        "r = s.SpanRecorder(); r.begin('x', 'c').end(); "
        "assert 'jax' not in sys.modules, 'jax was imported'; "
        "assert r.records[0]['id'] >= 1"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root)
    subprocess.run([sys.executable, "-c", code], check=True, env=env)


# ---------------------------------------------------------------------------
# A5. a child is counted once
# ---------------------------------------------------------------------------


def _nested_records(parts=False):
    """Two engine steps on one clock: the first seats a request. With
    ``parts``, the same script of clock readings with the spans that
    ISSUE 36 put around and inside the old ones."""
    rec = obs_spans.SpanRecorder(clock=FakeClock(), host="h", process=0)
    for seats in (1, 0):
        step = rec.begin("engine_step", "serve_engine", 100.0 * (2 - seats))
        t = step.t0
        admit = (rec.begin("admit", "serve_engine", t + 0.5)
                 if parts else None)
        if seats:
            p = rec.begin("prefill", "serve_prefill", t + 1, request_id="a",
                          queue_wait_s=0.5)
            if parts:
                rec.begin("prefill.dispatch", "serve_prefill", t + 1).end(t + 4)
                rec.begin("prefill.readback", "serve_prefill", t + 4).end(t + 11)
            p.end(t + 11)
            rec.begin("seat", "serve_seat", t + 11, request_id="a").end(t + 13)
        if parts:
            admit.end(t + 15, popped=seats, shed=0, queue_depth=0)
            rec.begin("decode_prepare", "serve_engine", t + 16).end(
                t + 20, slots=2)
        d = rec.begin("decode_step", "serve_decode", t + 20)
        dispatch = rec.begin("decode.dispatch", "serve_decode", t + 20)
        if parts:
            rec.begin("decode.address", "serve_decode", t + 20).end(
                t + 21, bytes=64)
        dispatch.end(t + 23)
        rec.begin("decode.readback", "serve_decode", t + 23).end(t + 29)
        d.end(t + 30, busy=1, rids=["a"])
        rec.begin("emit", "serve_emit", t + 30).end(t + 32)
        step.end(t + 40, seats=seats, busy=1)
    return rec.records


PARTS = pytest.mark.parametrize("parts", [False, True],
                                ids=["old_spans", "with_parts"])


@PARTS
def test_goodput_counts_each_second_once(parts):
    records = _nested_records(parts)
    cls = goodput.classify(records)
    # Two steps of 40 s; every second inside them is accounted once.
    assert cls["wall_s"] == pytest.approx(140.0)
    assert cls["other_s"] == pytest.approx(80.0)
    assert cls["idle_s"] == pytest.approx(60.0)  # between the two steps
    # By category: decode_step's children add nothing to its category.
    by_cat = {}
    for s, own in obs_spans.self_seconds(_spans(records)):
        by_cat[s["cat"]] = by_cat.get(s["cat"], 0.0) + own
    assert by_cat["serve_decode"] == pytest.approx(20.0)
    assert by_cat["serve_prefill"] == pytest.approx(10.0)
    assert by_cat["serve_seat"] == pytest.approx(2.0)
    assert by_cat["serve_emit"] == pytest.approx(4.0)
    # The engine's category: what a step did outside the other four
    # (engine_step's self time, and with the parts admit's and the
    # step's preparation).
    assert by_cat["serve_engine"] == pytest.approx(80.0 - 36.0)
    if parts:
        own = {}
        for s, sec in obs_spans.self_seconds(_spans(records)):
            own[s["name"]] = own.get(s["name"], 0.0) + sec
        # admit: 14.5 s a step less the prefill and the seat it caused.
        assert own["admit"] == pytest.approx(2 * 14.5 - 12.0)
        assert own["decode_prepare"] == pytest.approx(8.0)
        assert own["prefill"] == pytest.approx(0.0)
        assert own["decode.dispatch"] == pytest.approx(2 * 2.0)
        assert own["engine_step"] == pytest.approx(
            44.0 - 17.0 - 8.0
        )


def test_goodput_train_step_with_a_child_of_its_category_is_one_step():
    rec = obs_spans.SpanRecorder(clock=FakeClock())
    step = rec.begin("train_step", obs_spans.CAT_STEP, 0.0)
    rec.begin("train_step.dispatch", obs_spans.CAT_STEP, 0.0).end(1.0)
    step.end(4.0)
    cls = goodput.classify(rec.records)
    assert cls["steps"] == 1
    assert cls["productive_s"] == pytest.approx(4.0)
    assert cls["goodput"] == pytest.approx(1.0)


def test_records_without_ids_classify_as_before():
    """Span files written before spans had ids."""
    old = [
        {"kind": "span", "name": "train_step", "cat": "step", "ts": 0.0,
         "dur": 2.0, "host": "h", "process": 0},
        {"kind": "span", "name": "data_wait", "cat": "data_wait", "ts": 2.0,
         "dur": 1.0, "host": "h", "process": 0},
    ]
    cls = goodput.classify(old)
    assert (cls["productive_s"], cls["data_wait_s"], cls["steps"]) == (
        2.0, 1.0, 1
    )
    assert obs_spans.without_same_category_children(old) == old


@PARTS
def test_report_breakdown_counts_decode_step_once(parts):
    """The serve totals are what they were: a child of its parent's
    category (admit and decode_prepare under engine_step, the halves of
    a prefill, the addressing inside the dispatch) adds no row entry."""
    report = obs_report.build_report(_nested_records(parts))
    rows = report["breakdown"]
    assert rows["serve_decode"]["count"] == 2
    assert rows["serve_decode"]["total_s"] == pytest.approx(20.0)
    assert rows["serve_engine"]["count"] == 2
    assert rows["serve_engine"]["total_s"] == pytest.approx(80.0)
    assert rows["serve_prefill"]["count"] == 1
    assert rows["serve_prefill"]["total_s"] == pytest.approx(10.0)
    assert rows["serve_seat"]["total_s"] == pytest.approx(2.0)
    assert rows["serve_emit"]["total_s"] == pytest.approx(4.0)


def test_report_lists_the_serve_phases_as_their_tree():
    records = _nested_records(parts=True)
    phases = obs_report.build_report(records)["serve_phases"]
    assert list(phases) == [
        "engine_step", "admit", "prefill.dispatch", "seat",
        "decode_prepare", "decode_step", "decode.dispatch",
        "decode.address", "decode.readback", "emit", "prefill",
        "prefill.readback",
    ]
    assert [phases[n]["depth"] for n in phases] == [
        0, 1, 2, 2, 1, 1, 2, 3, 2, 1, 1, 2
    ]
    # Every second of the two steps is some phase's own.
    assert sum(r["self_s"] for r in phases.values()) == pytest.approx(80.0)
    admit = phases["admit"]
    assert (admit["count"], admit["total_s"]) == (2, pytest.approx(29.0))
    assert admit["self_s"] == pytest.approx(17.0)
    assert (admit["queue_depth_mean"], admit["queue_depth_max"]) == (0.0, 0.0)
    assert phases["decode.dispatch"]["self_s"] == pytest.approx(4.0)
    text = obs_report.format_report(obs_report.build_report(records))
    assert "serve phase" in text and "      decode.address" in text
    assert "queue_depth mean 0.0 max 0" in text
    # The old spans alone make the old rows; a run that served nothing
    # has no such table.
    old = obs_report.build_report(_nested_records())["serve_phases"]
    assert list(old) == ["engine_step", "seat", "decode_step",
                         "decode.dispatch", "decode.readback", "emit",
                         "prefill"]
    assert old["engine_step"]["self_s"] == pytest.approx(44.0)
    assert obs_report.build_report([])["serve_phases"] == {}
    assert "serve phase" not in obs_report.format_report(
        obs_report.build_report([]))


@PARTS
def test_request_timeline_takes_only_the_two_legs(parts):
    """`seat` names its request too, and the children of a prefill and
    of a decode step share their category: none is a leg of the
    timeline."""
    records = _nested_records(parts) + [
        {"kind": "event", "name": "request_complete", "cat": "serve_request",
         "ts": 240.0, "request_id": "a", "finish_reason": "length",
         "ttft_s": 10.5, "tpot_s": 10.0, "queue_wait_s": 0.5,
         "generation_s": 100.0, "num_tokens": 3,
         "host": "h", "process": 0, "pid": os.getpid()},
    ]
    tl = obs_report.build_request_timeline(records, "a")
    assert tl["found"]["prefill"] is True
    assert tl["found"]["decode_chunks"] == 2
    assert tl["decomposition"]["prefill_s"] == pytest.approx(10.0)
    whats = [e["what"] for e in tl["timeline"]]
    assert whats.count("prefill") == 1
    assert whats.count("decode_chunk") == 2


# ---------------------------------------------------------------------------
# B. phases where the work happens
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(model_and_params, tmp_path_factory):
    """One recorded paged serve run: (records, results)."""
    model, params = model_and_params
    obs.disable()
    obs.enable(str(tmp_path_factory.mktemp("obs")))
    session = _session(model, params)
    results = session.serve(_requests(5))
    records = obs_spans.active_recorder().records
    obs.disable()
    return records, results, session


def _inside(child, parent, eps=1e-9):
    return (child["ts"] >= parent["ts"] - eps
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + eps)


# A call that lands a decode step already in flight opens its
# ``decode_step`` before it admits, so that what is left of that step on
# the device lies inside the span: ``admit`` and ``decode_prepare`` are
# then ITS children. A first token is waited for (``prefill``) inside the
# ``decode_step`` of the call that seated it with nothing in flight, and
# after ``emit``, under ``engine_step``, where it was seated behind a
# step.
@pytest.mark.parametrize("child,parents", [
    ("admit", ("engine_step", "decode_step")),
    ("prefill.dispatch", ("admit",)),
    ("seat", ("admit",)),
    ("prefill", ("decode_step", "engine_step")),
    ("prefill.readback", ("prefill",)),
    ("decode_prepare", ("engine_step", "decode_step")),
    ("decode_step", ("engine_step",)),
    ("emit", ("engine_step",)),
    ("decode.dispatch", ("decode_step",)),
    ("decode.address", ("decode.dispatch",)),
    ("decode.readback", ("decode_step",)),
])
def test_engine_phase_nests_in_its_parent(served, child, parents):
    records, _, _ = served
    by_id = {s["id"]: s for s in _spans(records)}
    children = _spans(records, child)
    assert children
    seen = set()
    for c in children:
        p = by_id[c["parent"]]
        seen.add(p["name"])
        assert _inside(c, p)
        if child in ("admit", "decode_prepare"):
            # Inside ``decode_step`` where that step was in flight when
            # the call began, and nowhere else.
            assert (p["name"] == "decode_step") == bool(p.get("ahead"))
        if child == "prefill":
            assert (p["name"] == "engine_step") == bool(c["behind"])
    assert seen == set(parents)


def test_engine_step_is_top_level_and_counts_its_seats(served):
    records, results, _ = served
    steps = _spans(records, "engine_step")
    assert steps and all(s["parent"] is None for s in steps)
    assert sum(s["seats"] for s in steps) == len(results)
    assert len(_spans(records, "seat")) == len(results)
    # A step's children do not overlap one another.
    for step in steps:
        kids = sorted(
            (s for s in _spans(records) if s["parent"] == step["id"]),
            key=lambda s: s["ts"],
        )
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-9
    # The last step found nothing to do: it only looked for work.
    assert steps[-1]["busy"] == 0
    assert [s["name"] for s in _spans(records)
            if s["parent"] == steps[-1]["id"]] == ["admit"]


def test_children_add_up_to_no_more_than_their_parent(served):
    records, _, _ = served
    total = {}
    for s in _spans(records):
        if s["parent"] is not None:
            total[s["parent"]] = total.get(s["parent"], 0.0) + s["dur"]
    assert total
    for s in _spans(records):
        assert total.get(s["id"], 0.0) <= s["dur"] + 1e-9, s["name"]


def test_the_wait_for_a_first_token_is_its_readback(served):
    """``prefill.dispatch`` is host work of ``admit``; ``prefill`` is
    the wait for the first token wherever the engine waits, and
    ``prefill.readback`` tiles it but for its tail (the experts' load)."""
    records, results, _ = served
    prefills = _spans(records, "prefill")
    dispatches = _spans(records, "prefill.dispatch")
    assert len(prefills) == len(dispatches) == len(results)
    assert all(d["cat"] == "serve_engine" for d in dispatches)
    for p in prefills:
        (readback,) = [s for s in _spans(records) if s["parent"] == p["id"]]
        assert readback["name"] == "prefill.readback"
        assert readback["cat"] == p["cat"] == "serve_prefill"
        assert readback["ts"] == p["ts"]
        assert readback["ts"] + readback["dur"] <= p["ts"] + p["dur"]
    # Each wait begins after its own dispatch ended.
    for d, p in zip(dispatches, prefills):
        assert d["ts"] + d["dur"] <= p["ts"]


def test_admit_prepare_and_address_carry_their_attributes(served):
    records, results, session = served
    admits = _spans(records, "admit")
    steps = _spans(records, "engine_step")
    assert len(admits) == len(steps)
    assert all(a["cat"] == "serve_engine" for a in admits)
    assert sum(a["popped"] for a in admits) == len(results)
    assert all(a["shed"] == 0 for a in admits)
    # Five requests on two slots: three wait after the first admission,
    # none after the last.
    assert admits[0]["queue_depth"] == 3
    assert admits[-1]["queue_depth"] == 0
    prepares = _spans(records, "decode_prepare")
    assert len(prepares) == len(_spans(records, "decode_step"))
    assert all(p["slots"] == 2 and p["cat"] == "serve_engine"
               for p in prepares)
    cache = session.engine.cache
    sent = (cache.page_table.nbytes + cache.start.nbytes
            + cache.lens.nbytes)
    assert cache.addressing_nbytes == sent
    assert all(a["bytes"] == sent for a in _spans(records, "decode.address"))


def test_decode_prepare_ends_where_the_dispatch_begins(served):
    """One clock reading ends the preparation and begins the dispatch
    (and the step, where no step was in flight), as one ends the step
    and begins the emit, and one ends the emit and begins the wait for
    a first token seated behind the step."""
    records, _, _ = served
    by_parent = {}
    for s in _spans(records):
        by_parent.setdefault(s["parent"], []).append(s)
    for group in by_parent.values():
        group.sort(key=lambda s: s["ts"])
    landed = {0: 0, 1: 0}
    for step in _spans(records, "engine_step"):
        kids = by_parent.get(step["id"], [])
        names = [k["name"] for k in kids]
        if "decode_step" not in names:
            continue
        decode = kids[names.index("decode_step")]
        emit = kids[names.index("emit")]
        inner = by_parent[decode["id"]]
        inner_names = [k["name"] for k in inner]
        landed[decode["ahead"]] += 1
        if decode["ahead"]:
            # The step was in flight: the span opens first.
            assert names[:2] == ["decode_step", "emit"]
            assert set(names[2:]) <= {"prefill"}
            assert inner_names == ["admit", "decode_prepare",
                                   "decode.dispatch", "decode.readback"]
            prepare = inner[1]
        else:
            assert names == ["admit", "decode_prepare", "decode_step", "emit"]
            assert inner_names[0] == "decode.dispatch"
            assert inner_names[-1] == "decode.readback"
            assert set(inner_names[1:-1]) <= {"prefill"}
            prepare = kids[1]
            assert prepare["ts"] + prepare["dur"] == pytest.approx(
                decode["ts"]
            )
        dispatch = inner[inner_names.index("decode.dispatch")]
        assert prepare["ts"] + prepare["dur"] == pytest.approx(dispatch["ts"])
        assert decode["ts"] + decode["dur"] == pytest.approx(emit["ts"])
        # What is waited for inside or after the step tiles: no time
        # between a dispatch's end, the waits and the read-back, nor
        # between the emit's end and the waits after it.
        for a, b in zip(inner[inner_names.index("decode.dispatch"):],
                        inner[inner_names.index("decode.dispatch") + 1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
        after = kids[names.index("emit"):]
        for a, b in zip(after, after[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
    assert landed[0] and landed[1]


def test_engine_step_self_time_is_its_boundaries_alone(model_and_params,
                                                       tmp_path):
    """On a clock that moves one tick a reading, a span lasts as many
    ticks as clock readings fall inside it. What ``engine_step`` keeps
    for itself in a step that decodes: one tick before its first child
    begins and one after its last ends, and, where nothing was in
    flight, one between ``admit`` and ``decode_prepare``; no clock is
    read, and so no phase of the step runs unnamed, in its own time.
    ``decode_step`` keeps its last tick and, where it holds ``admit``
    and ``decode_prepare``, one before the first and one between them."""
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    session.engine.clock = FakeClock()
    session.serve(_requests(4))
    records = rec.records
    obs.disable()
    own = {
        s["id"]: sec for s, sec in obs_spans.self_seconds(_spans(records))
    }
    decoded = {s["parent"]: s for s in _spans(records, "decode_step")}
    assert {d["ahead"] for d in decoded.values()} == {0, 1}
    for step in _spans(records, "engine_step"):
        if step["id"] in decoded:
            was_in_flight = decoded[step["id"]]["ahead"]
            assert own[step["id"]] == pytest.approx(2.0 if was_in_flight
                                                    else 3.0)
    # decode_step's own tail (the span's attributes, the cache's
    # advance, the experts' counters) holds no clock reading either:
    # its children tile it but for the last tick.
    for d in decoded.values():
        assert own[d["id"]] == pytest.approx(3.0 if d["ahead"] else 1.0)
    # A wait for a first token keeps its last tick (its attributes).
    for p in _spans(records, "prefill"):
        assert own[p["id"]] == pytest.approx(1.0)


def test_dispatch_and_readback_tile_the_front_of_decode_step(served):
    """With the waits for the first tokens a call seated with nothing
    in flight between them, and ``admit`` and ``decode_prepare`` before
    them in a call that found a step in flight."""
    records, _, _ = served
    for d in _spans(records, "decode_step"):
        kids = sorted((s for s in _spans(records) if s["parent"] == d["id"]),
                      key=lambda s: s["ts"])
        names = [k["name"] for k in kids]
        at = names.index("decode.dispatch")
        assert names[:at] == (
            ["admit", "decode_prepare"] if d["ahead"] else []
        )
        assert names[-1] == "decode.readback"
        assert set(names[at + 1:-1]) <= {"prefill"}
        if not d["ahead"]:
            assert kids[0]["ts"] == d["ts"]
        for a, b in zip(kids[at:], kids[at + 1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
        assert all(k["cat"] == d["cat"] for k in kids
                   if k["name"].startswith("decode."))


def test_seat_and_emit_carry_their_attributes(served):
    records, results, session = served
    seats = _spans(records, "seat")
    assert sorted(s["request_id"] for s in seats) == sorted(results)
    cache = session.engine.cache
    for s in seats:
        res = results[s["request_id"]]
        assert 0 <= s["slot"] < 2
        assert s["pages"] >= cache.pages_needed(PROMPT_LEN + len(res.tokens))
    emits = _spans(records, "emit")
    # A request that finishes on its first token is finished where that
    # token lands, not in an emit.
    assert sum(e["finished"] for e in emits) == sum(
        len(r.tokens) > 1 for r in results.values()
    )


def test_prefill_and_decode_step_extents_are_the_engines_timestamps(served):
    """TTFT = queue wait + what the request waited from its pop to the
    end of its ``prefill`` span (``since_pop_s``: the span itself is
    the wait for the token alone), and a request's generation time runs
    from that end to its last decode_step's end: exactly, since the
    spans are given the engine's own clock readings."""
    records, results, _ = served
    completes = {
        r["request_id"]: r for r in records
        if r.get("kind") == "event" and r.get("name") == "request_complete"
    }
    for rid, res in results.items():
        (prefill,) = [p for p in _spans(records, "prefill")
                      if p["request_id"] == rid]
        assert prefill["queue_wait_s"] + prefill["since_pop_s"] == (
            pytest.approx(res.ttft_s, rel=1e-9)
        )
        assert prefill["dur"] <= prefill["since_pop_s"]
        chunks = [d for d in _spans(records, "decode_step")
                  if rid in d["rids"]]
        if len(res.tokens) > 1:
            last = max(chunks, key=lambda d: d["ts"])
            assert completes[rid]["generation_s"] == pytest.approx(
                last["ts"] + last["dur"] - prefill["ts"] - prefill["dur"],
                abs=1e-9,
            )


def test_spec_step_has_the_same_two_children(model_and_params, tmp_path):
    model, params = model_and_params
    obs.enable(str(tmp_path))
    session = _session(model, params, page_size=4, spec_k=2)
    session.serve(_requests(3, new=(4, 7)))
    records = obs_spans.active_recorder().records
    steps = _spans(records, "decode_step")
    assert steps and all("proposed" in s for s in steps)
    for d in steps:
        kids = sorted((s for s in _spans(records) if s["parent"] == d["id"]),
                      key=lambda s: s["ts"])
        assert [k["name"] for k in kids] == [
            "decode.dispatch", "decode.readback"
        ]
        assert all(_inside(k, d) for k in kids)


def test_spec_step_is_prepared_and_addressed_under_spans(model_and_params,
                                                         tmp_path):
    """A speculative window: its host arrays under ``decode_prepare``
    (``slots``: the active ones), the VERIFY dispatch's addressing
    under ``decode.address``; the draft's own dispatches make none."""
    model, params = model_and_params
    obs.enable(str(tmp_path))
    session = _session(model, params, page_size=4, spec_k=2)
    session.serve(_requests(3, new=(4, 7)))
    records = obs_spans.active_recorder().records
    by_id = {s["id"]: s for s in _spans(records)}
    steps = _spans(records, "decode_step")
    prepares = _spans(records, "decode_prepare")
    assert len(prepares) == len(steps)
    for p, d in zip(prepares, steps):
        assert by_id[p["parent"]]["name"] == "engine_step"
        assert p["parent"] == d["parent"]
        assert p["slots"] == d["busy"]
        assert p["ts"] + p["dur"] == pytest.approx(d["ts"])
    addresses = _spans(records, "decode.address")
    assert len(addresses) == len(steps)
    for a in addresses:
        parent = by_id[a["parent"]]
        assert parent["name"] == "decode.dispatch"
        assert _inside(a, parent) and a["bytes"] > 0


def test_without_a_recorder_the_engine_records_nothing(
        model_and_params, tmp_path, monkeypatch):
    model, params = model_and_params
    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    # The hot path begins and makes no span. (The sites that run once a
    # process record whether or not anyone asked: the session's
    # construction, the programs JAX builds, the kernels traced.)
    begun = []
    made = []
    real_init = obs_spans._Span.__init__

    def init(self, rec, name, *a, **kw):
        if not name.startswith(START_UP):
            made.append(name)
        real_init(self, rec, name, *a, **kw)

    monkeypatch.setattr(obs_spans._Span, "__init__", init)
    real_begin = obs_spans.SpanRecorder.begin

    def begin(self, name, *a, **kw):
        if not name.startswith("program."):
            begun.append(name)
            pytest.fail("recorded")
        return real_begin(self, name, *a, **kw)

    monkeypatch.setattr(obs_spans.SpanRecorder, "begin", begin)
    clock_reads = []
    session = _session(model, params)
    engine = session.engine
    real_clock = engine.clock
    engine.clock = lambda: clock_reads.append(1) or real_clock()
    sheds = []
    real_shed = engine._record_shed
    engine._record_shed = lambda *a: sheds.append(1) or real_shed(*a)
    results = session.serve(_requests(2, new=(4, 5)))
    assert all(r.ok for r in results.values())
    assert begun == [] and made == []
    assert obs_spans.active_recorder() is None
    assert list(tmp_path.rglob("*.jsonl")) == []
    assert engine._rec is None
    off = len(clock_reads)
    # As many readings as before the spans of ISSUE 36: two a prefill
    # (its start and its first token), two a decode step (its start
    # and its tokens' time) and one a look for entries to shed.
    assert off == (2 * engine.num_prefills + 2 * engine.num_decode_steps
                   + len(sheds))
    # With a recorder the same requests read the clock more often: the
    # reads the spans need are made only then.
    clock_reads.clear()
    monkeypatch.undo()
    obs.enable(str(tmp_path / "on"))
    session.engine.clock = lambda: clock_reads.append(1) or real_clock()
    session.serve(_requests(2, tag="s", new=(4, 5)))
    assert len(clock_reads) > off


def test_an_exception_in_a_step_leaves_no_span_open(model_and_params,
                                                    tmp_path):
    model, params = model_and_params
    rec = obs.enable(str(tmp_path))
    session = _session(model, params)
    session.submit(_requests(1)[0])

    def boom(_):
        raise RuntimeError("chaos")

    session.engine.chaos_hooks = [boom]
    with pytest.raises(RuntimeError):
        session.engine.step()
    session.engine.chaos_hooks = []
    assert rec._open_spans() == []
    session.collect()
    steps = _spans(rec.records, "engine_step")
    assert steps and all(s["parent"] is None for s in steps)


# -- the trainer ------------------------------------------------------------


def _fit_records(tmp_path, **fit_kwargs):
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    from tests.test_obs import _tiny_fit_setup

    rec = obs.enable(str(tmp_path))
    state, step = _tiny_fit_setup()
    fit(step, state,
        synthetic_classification_batches(
            16, image_shape=(16, 16, 3), num_classes=4, num_batches=6
        ),
        jax.random.key(1), **fit_kwargs)
    return rec.records


def test_synchronous_log_path_records_metric_wait(tmp_path):
    seen = []
    records = _fit_records(
        tmp_path, log_every=2, logger=lambda i, m: seen.append(i)
    )
    waits = _spans(records, "metric_wait")
    assert [w["step"] for w in waits] == seen == [2, 4, 6]
    assert all(w["cat"] == obs_spans.CAT_METRIC_WAIT for w in waits)
    assert all(w["parent"] is None for w in waits)
    cls = goodput.classify(records)
    assert cls["metric_wait_s"] == pytest.approx(
        sum(w["dur"] for w in waits)
    )
    assert "metric_wait" in goodput.format_goodput(cls)


def test_without_log_every_there_is_no_metric_wait(tmp_path):
    records = _fit_records(tmp_path)
    assert _spans(records, "metric_wait") == []
    assert len(_spans(records, "train_step")) == 5
    assert len(_spans(records, "data_wait")) == 6


def test_without_a_recorder_fit_records_nothing(tmp_path, monkeypatch):
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.train import fit

    from tests.test_obs import _tiny_fit_setup

    monkeypatch.chdir(tmp_path)
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    # (But for the programs JAX builds on the way, which record
    # whether or not anyone asked: the step's first call compiles.)
    real_begin = obs_spans.SpanRecorder.begin

    def begin(self, name, *a, **kw):
        if not name.startswith("program."):
            pytest.fail("recorded")
        return real_begin(self, name, *a, **kw)

    monkeypatch.setattr(obs_spans.SpanRecorder, "begin", begin)
    state, step = _tiny_fit_setup()
    _, metrics, info = fit(
        step, state,
        synthetic_classification_batches(
            16, image_shape=(16, 16, 3), num_classes=4, num_batches=3
        ),
        jax.random.key(1), log_every=1, logger=lambda i, m: None,
    )
    assert info["steps"] == 3 and np.isfinite(metrics["loss"])
    assert list(tmp_path.rglob("*.jsonl")) == []


# ---------------------------------------------------------------------------
# C. counters at the same boundaries
# ---------------------------------------------------------------------------


def _recount(cache):
    seated = sorted(set(cache._reserved) | set(cache._leases))
    tokens = int(sum(int(cache.lens[s]) - int(cache.start[s])
                     for s in seated))
    pages = int(sum((cache.page_table[s] != 0).sum() for s in seated))
    return pages, tokens


@pytest.mark.parametrize("kw", [
    {}, {"prefix_share": True}, {"spec_k": 2}, {"kv_dtype": "int8"},
], ids=["paged", "prefix_share", "speculative", "int8"])
def test_occupancy_counters_match_a_recount_and_return_to_zero(
        model_and_params, kw):
    model, params = model_and_params
    session = _session(model, params, **kw)
    cache = session.engine.cache
    for req in _requests(6, new=(3, 9)):
        session.submit(req)
    seen = 0
    while session.engine.step():
        assert (cache.pages_reserved, cache.tokens_live) == _recount(cache)
        if not kw:
            # Plain paged seating: every page a slot maps is its own.
            assert cache.pages_reserved == sum(
                len(p) for p in cache._reserved.values()
            )
        seen = max(seen, cache.tokens_live)
    assert seen > 0
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)
    reg = obs_counters.registry()
    assert reg.gauge("serve_kv_pages_reserved").value == 0
    assert reg.gauge("serve_kv_tokens_live").value == 0
    assert reg.gauge("serve_slots_busy").value == 0


def test_occupancy_gauges_are_set_with_slots_busy(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    for req in _requests(2, new=(6, 7)):
        session.submit(req)
    session.engine.step()
    reg = obs_counters.registry()
    cache = session.engine.cache
    assert reg.gauge("serve_slots_busy").value == 2
    # Set where serve_slots_busy is: after the seats, before the step's
    # decodes advanced the lengths: two dispatches, since both slots are
    # seated (the step that landed and the step ahead, still in flight).
    assert reg.gauge("serve_kv_pages_reserved").value == cache.pages_reserved
    assert session.engine._in_flight is not None
    assert reg.gauge("serve_kv_tokens_live").value == cache.tokens_live - 4
    assert "serve_kv_tokens_live" in obs_exporter.render_prometheus(
        reg.snapshot()
    )
    session.collect()


def test_decode_step_spans_carry_the_counters(served):
    records, _, session = served
    page = session.engine.cache.page_size
    steps = _spans(records, "decode_step")
    assert steps
    for s in steps:
        assert 0 < s["tokens_live"] <= s["pages_reserved"] * page
        # Every busy slot attends at least its prompt and one token.
        assert s["tokens_live"] >= s["busy"] * 5


def test_migration_keeps_the_counters_true(model_and_params):
    model, params = model_and_params
    src = _session(model, params)
    dst = _session(model, params)
    req = _requests(1, new=(8, 9))[0]
    src.submit(req)
    src.engine.step()
    src.engine.step()
    payload = src.engine.export_request(req.request_id)
    assert (src.engine.cache.pages_reserved,
            src.engine.cache.tokens_live) == (0, 0)
    dst.engine.install_migrated(payload)
    cache = dst.engine.cache
    assert (cache.pages_reserved, cache.tokens_live) == _recount(cache)
    assert cache.tokens_live > 0
    dst.engine.run_until_drained()
    assert (cache.pages_reserved, cache.tokens_live) == (0, 0)


# ---------------------------------------------------------------------------
# A2 + D. one clock, and names for what runs on the device
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def profiled(model_and_params, tmp_path_factory):
    """A short CPU profiler trace of a recorded serve run:
    (records, annotations {span_id: name}, hlo modules seen)."""
    model, params = model_and_params
    tmp = tmp_path_factory.mktemp("prof")
    session = _session(model, params)
    session.serve(_requests(2, tag="w"))  # compile outside the trace
    obs.disable()
    obs.enable(str(tmp / "obs"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        session.serve(_requests(3))
    finally:
        jax.profiler.stop_trace()
    records = obs_spans.active_recorder().records
    obs.disable()
    (path,) = glob.glob(
        str(tmp / "trace" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(path)
    annotations, modules = {}, set()
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if ev.name.startswith("tpudl."):
                    annotations[int(stats["span_id"])] = (
                        ev.name, ev.start_ns, ev.duration_ns
                    )
                if "hlo_module" in stats:
                    modules.add(str(stats["hlo_module"]))
    return records, annotations, modules


@pytest.mark.parametrize("name", [
    "engine_step", "prefill", "seat", "decode_step", "decode.dispatch",
    "decode.readback", "emit",
])
def test_profiler_trace_holds_the_spans_as_annotations(profiled, name):
    records, annotations, _ = profiled
    spans = _spans(records, name)
    assert spans
    for s in spans:
        # span_id joins the annotation to its record...
        ann_name, start_ns, dur_ns = annotations[s["id"]]
        assert ann_name == "tpudl." + name
        # ...the annotation lies where the span lies: inside its
        # parent's, on the trace's own clock (exact: they are nested
        # blocks of one thread)...
        if s["parent"] is not None:
            _, p_start, p_dur = annotations[s["parent"]]
            assert p_start <= start_ns
            assert start_ns + dur_ns <= p_start + p_dur
        # ...and is as long as the span (the trace's clock is not the
        # recorder's: compare lengths). The engine reads a boundary's
        # time once, for the span that ends there and the one that
        # begins, so an annotation opens and closes a little after its
        # span: microseconds on an idle host, a time slice on one that
        # runs six workers. That slack does not grow with the span.
        assert abs(dur_ns * 1e-9 - s["dur"]) <= 0.5 * s["dur"] + 5e-3


def test_annotations_keep_the_spans_order_on_the_traces_clock(profiled):
    records, annotations, _ = profiled
    steps = sorted(_spans(records, "decode_step"), key=lambda s: s["ts"])
    starts = [annotations[s["id"]][1] for s in steps]
    assert starts == sorted(starts)
    # One offset ties the two clocks together, with no mark from outside.
    offsets = [annotations[s["id"]][1] * 1e-9 - s["ts"] for s in steps]
    assert max(offsets) - min(offsets) < 5e-3


@pytest.mark.parametrize("module", [
    "jit_tpudl_decode", "jit_tpudl_prefill", "jit_tpudl_seat",
    "jit_tpudl_select",
])
def test_device_programs_have_names_of_their_own(profiled, module):
    _, _, modules = profiled
    assert module in modules
    assert "jit_fn" not in modules


def test_no_annotation_without_a_recorder(model_and_params, tmp_path):
    model, params = model_and_params
    session = _session(model, params)
    session.serve(_requests(1, tag="w"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        session.serve(_requests(1))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    data = jax.profiler.ProfileData.from_file(path)
    names = {ev.name for plane in data.planes for line in plane.lines
             for ev in line.events}
    assert not any(n.startswith("tpudl.") for n in names)


def _scopes(lowered):
    """Components of every op_name path in a compiled program."""
    import re

    text = lowered.compile().as_text()
    parts = set()
    for path in re.findall(r'op_name="([^"]+)"', text):
        parts.update(re.split(r"[/()]", path))
    return parts, text


@pytest.fixture(scope="module")
def decode_scopes(model_and_params):
    model, params = model_and_params
    session = _session(model, params)
    eng = session.engine
    b = eng.num_slots
    lowered = eng.decode_call.lower(
        params, eng.cache.cache, np.zeros(b, np.int32),
        np.zeros(b, np.int32), *eng.cache.dispatch_args(),
    )
    return _scopes(lowered)


@pytest.mark.parametrize("scope", [
    "kv_gather", "kv_scatter", "attention", "mlp", "norm", "embeddings",
    "lm_head",
])
def test_decode_program_names_its_scopes(decode_scopes, scope):
    parts, text = decode_scopes
    assert scope in parts
    assert text.startswith("HloModule jit_tpudl_decode")


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
def test_selection_runs_under_the_select_scope(sampled):
    from tpudl.serve import engine as eng

    logits = jnp.zeros((2, 32), jnp.float32)
    if sampled:
        lowered = eng._select_tokens.lower(
            logits, np.float32([0.0, 1.0]), np.uint32([1, 2]),
            np.int32([0, 0]),
        )
        name = "jit_tpudl_select_sampled"
    else:
        lowered = eng._select_greedy.lower(logits)
        name = "jit_tpudl_select"
    parts, text = _scopes(lowered)
    assert "select" in parts
    assert text.startswith(f"HloModule {name},")


@pytest.fixture(scope="module")
def train_scopes():
    from tpudl.config import OptimConfig
    from tpudl.models.bert import BertConfig, BertForSequenceClassification
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )
    from tpudl.train.optim import make_optimizer

    cfg = BertConfig(
        vocab_size=128, hidden_size=32, num_layers=1, num_heads=2,
        intermediate_size=64, max_position_embeddings=16,
        dtype=jnp.float32,
    )
    net = BertForSequenceClassification(cfg)
    tx = make_optimizer(OptimConfig(
        learning_rate=1e-3, warmup_steps=1, total_steps=10,
        grad_clip_norm=1.0,
    ))
    state = create_train_state(
        jax.random.key(0), net, jnp.zeros((1, 8), jnp.int32), tx
    )
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh, state,
    )
    batch = {
        "input_ids": np.ones((4, 8), np.int32),
        "attention_mask": np.ones((4, 8), np.int32),
        "label": np.zeros((4,), np.int32),
    }
    from tpudl.parallel.sharding import active_mesh

    with active_mesh(mesh):
        lowered = step.jitted.lower(state, batch, jax.random.key(1))
    return _scopes(lowered)


@pytest.mark.parametrize("scope", [
    "dropout", "loss", "grad_clip", "optimizer", "attention", "ffn",
    "norm", "embeddings", "classifier",
])
def test_train_program_names_its_scopes(train_scopes, scope):
    parts, text = train_scopes
    assert scope in parts
    assert text.startswith("HloModule jit_tpudl_train_step")


def test_backward_pass_keeps_the_scopes(train_scopes):
    _, text = train_scopes
    assert "transpose(jvp(" in text
    import re

    paths = re.findall(r'op_name="([^"]+)"', text)
    assert any("transpose" in p and "attention" in p for p in paths)


def test_eval_and_window_programs_have_names():
    import optax

    from tpudl.models.resnet import ResNetTiny
    from tpudl.parallel.sharding import active_mesh
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )
    from tpudl.train.loop import make_classification_eval_step

    model = ResNetTiny(num_classes=4)
    state = create_train_state(
        jax.random.key(0), model, jnp.zeros((1, 16, 16, 3)), optax.sgd(0.05)
    )
    mesh = make_mesh(MeshSpec(dp=1), jax.devices()[:1])
    batch = {"image": np.zeros((2, 16, 16, 3), np.float32),
             "label": np.zeros((2,), np.int32)}
    ev = compile_step(make_classification_eval_step(), mesh, state,
                      has_rng=False)
    tr = compile_step(make_classification_train_step(), mesh, state,
                      steps_per_dispatch=2)
    window = {k: np.stack([v, v]) for k, v in batch.items()}
    with active_mesh(mesh):
        assert ev.jitted.lower(state, batch).as_text().lstrip().startswith(
            "module @jit_tpudl_eval_step"
        )
        assert tr.jitted_window.lower(
            state, window, jax.random.key(0)
        ).as_text().lstrip().startswith("module @jit_tpudl_window_step")


def test_chrome_export_leaves_ids_out_of_args(tmp_path):
    rec = obs_spans.SpanRecorder(clock=FakeClock(), host="h", process=0)
    with rec.span("outer", "a", step=3):
        pass
    path = rec.export_chrome_trace(str(tmp_path / "t.json"))
    (x,) = [e for e in json.load(open(path))["traceEvents"]
            if e["ph"] == "X"]
    assert x["args"] == {"step": 3}
