"""The readers of the program's own timeline (annotations, programs,
scopes, cache counters): on a small trace recorded on the chip, on a
trace from before the program had names, and on hand-made inputs whose
answers are known."""

import json
import os

import pytest

from perfbench import trace as tr
from perfbench.readers import _program_trace as pt
from perfbench.readers import device_idle_in_span, device_share, kv_live_share

HERE = os.path.dirname(os.path.abspath(__file__))


class Ctx:
    """As much of ``perfbench.run.Context`` as these readers touch."""

    def __init__(self, trace=None, spans=(), slots=48, session=None):
        self._trace = trace
        self.spans = list(spans)
        self.record = {"slots": slots, "t0_monotonic": 0.0, "window_s": 1e9}
        self.config = {"session": session or {
            "page_size": 16, "max_seq_len": 1024, "num_slots": slots,
        }}
        self.tracer = None

    def window_spans(self, name):
        return [s for s in self.spans if s.get("name") == name]


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace_program.json")) as f:
        return json.load(f)


@pytest.fixture()
def on(monkeypatch):
    """``of_run`` answers with the trace the context holds."""
    monkeypatch.setattr(pt, "of_run", lambda ctx: ctx._trace)


def overlap_ns(ops, start, end):
    """Busy time inside [start, end), by an arithmetic of the test's
    own: the union of the operations' intervals, clipped."""
    total, upto = 0.0, start
    for s, e in sorted((o[1], o[1] + o[2]) for o in ops if o[2] > 0):
        s, e = max(s, upto), min(e, end)
        if e > s:
            total += e - s
            upto = e
    return total


# -- the recorded trace -----------------------------------------------------


def test_recorded_trace_is_from_a_program_with_names(recorded):
    programs = {m[0] for m in recorded["modules"]}
    assert "jit_fn" not in programs
    assert {"jit_tpudl_decode", "jit_tpudl_select"} <= programs
    names = {a[0] for a in recorded["annotations"]}
    assert {"tpudl.engine_step", "tpudl.decode_step",
            "tpudl.decode.dispatch", "tpudl.decode.readback",
            "tpudl.emit"} <= names
    assert all(isinstance(a[3], int) for a in recorded["annotations"])
    assert any(pt.has_scope(o[4], "kv_gather") for o in recorded["ops"])
    # Every operation lies in the program whose module event covers it.
    for o in recorded["ops"]:
        assert any(m[0] == o[3] and m[1] <= o[1] <= m[1] + m[2]
                   for m in recorded["modules"]), o[0][:60]


@pytest.mark.parametrize("name", [
    "decode.dispatch", "decode.readback", "emit", "decode_step",
])
def test_idle_in_span_is_length_less_busy(recorded, on, name):
    ctx = Ctx(recorded)
    spans = pt.occurrences(recorded, name)
    assert spans, name
    idle = sorted(
        (e - s) - overlap_ns(recorded["ops"], s, e) for s, e, _ in spans
    )
    got = device_idle_in_span.read(ctx, name=name, p=50)
    lo, hi = idle[(len(idle) - 1) // 2], idle[len(idle) // 2]
    assert got == pytest.approx(1e-6 * (lo + hi) / 2)
    assert 0.0 <= got <= 1e-6 * max(e - s for s, e, _ in spans)
    assert device_idle_in_span.read(ctx, name=name, p=100) == pytest.approx(
        1e-6 * idle[-1]
    )


def test_occurrences_lie_wholly_inside_the_traced_window(recorded):
    ops = recorded["ops"]
    lo = min(o[1] for o in ops)
    hi = max(o[1] + o[2] for o in ops)
    inside = pt.occurrences(recorded, "engine_step")
    every = [a for a in recorded["annotations"]
             if a[0] == "tpudl.engine_step"]
    assert 0 < len(inside) <= len(every)
    assert all(lo <= s and e <= hi for s, e, _ in inside)
    # The cut ends inside a step: that one is not counted.
    straddling = [a for a in every if a[1] + a[2] > hi or a[1] < lo]
    assert len(inside) == len(every) - len(straddling)


def test_children_idle_adds_up_to_the_parents(recorded, on):
    """decode_step = dispatch + readback + a tail of host work: the
    idle found in the two children is no more than the parent's."""
    merged = pt.busy(recorded)
    by_id = {a[3]: a for a in recorded["annotations"]}
    steps = pt.occurrences(recorded, "decode_step")
    assert steps
    kids = {"tpudl.decode.dispatch": 0.0, "tpudl.decode.readback": 0.0}
    whole = sum(pt.idle_inside(merged, s, e) for s, e, _ in steps)
    for a in recorded["annotations"]:
        if a[0] in kids and any(s <= a[1] and a[1] + a[2] <= e
                                for s, e, _ in steps):
            kids[a[0]] += pt.idle_inside(merged, a[1], a[1] + a[2])
    assert by_id and 0 < sum(kids.values()) <= whole * (1 + 1e-9)


def test_program_shares_add_up_to_the_busy_time(recorded, on):
    ctx = Ctx(recorded)
    programs = sorted({m[0] for m in recorded["modules"]})
    shares = {}
    for p in programs:
        assert p.startswith("jit_tpudl_"), p
        shares[p] = device_share.read(ctx, program=p[len("jit_tpudl_"):])
    assert all(0 < v <= 100 for v in shares.values())
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-6)
    assert max(shares, key=shares.get) == "jit_tpudl_decode"
    busy = sum(e - s for s, e in pt.busy(recorded))
    mine = [o for o in recorded["ops"] if o[3] == "jit_tpudl_decode"]
    assert shares["jit_tpudl_decode"] == pytest.approx(
        100.0 * overlap_ns(mine, 0.0, float("inf")) / busy
    )


@pytest.mark.parametrize("scope", ["kv_gather", "attention", "mlp", "norm"])
def test_scope_share_counts_the_operations_under_it(recorded, on, scope):
    ctx = Ctx(recorded)
    mine = [o for o in recorded["ops"]
            if scope in o[4].replace("(", "/").replace(")", "/").split("/")]
    assert mine
    busy = sum(e - s for s, e in pt.busy(recorded))
    got = device_share.read(ctx, scope=scope)
    assert got == pytest.approx(
        100.0 * overlap_ns(mine, 0.0, float("inf")) / busy
    )
    assert 0 < got < 100
    # Narrowed to a program it can only shrink.
    assert device_share.read(ctx, program="decode", scope=scope) <= got


def test_the_gather_is_the_decode_steps_largest_scope(recorded, on):
    ctx = Ctx(recorded)
    gather = device_share.read(ctx, scope="kv_gather")
    assert gather > device_share.read(ctx, scope="mlp")
    assert gather == pytest.approx(
        device_share.read(ctx, program="decode", scope="kv_gather")
    )


# -- a trace from before the program had names ------------------------------


@pytest.fixture(scope="module")
def nameless():
    """PR 23's recorded trace as this helper would have read it: one
    program called ``jit_fn``, no scopes, no ``tpudl.*`` annotations."""
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        old = json.load(f)
    plane = tr.device_planes(old)[0]
    mods = [l for l in plane["lines"] if l["name"] == "XLA Modules"][0]
    return {
        "annotations": [],
        "modules": [[pt.program_of(e[0]), e[1], e[2]]
                    for e in mods["events"]],
        "ops": [[e[0], e[1], e[2], "jit_fn", ""]
                for e in tr.op_events(plane)],
    }


@pytest.mark.parametrize("reader,args", [
    (device_idle_in_span, {"name": "decode.dispatch", "p": 50}),
    (device_idle_in_span, {"name": "metric_wait", "p": 50}),
    (device_share, {"program": "seat"}),
    (device_share, {"program": "prefill"}),
    (device_share, {"scope": "kv_gather"}),
    (device_share, {"scope": "dropout"}),
    (kv_live_share, {"over": "reserved"}),
    (kv_live_share, {"over": "gathered"}),
])
def test_a_program_without_names_gives_nothing_and_does_not_raise(
        nameless, on, reader, args):
    spans = [{"kind": "span", "name": "decode_step", "ts": 1.0, "dur": 0.05,
              "busy": 3, "rids": [1, 2, 3]}]
    assert reader.read(Ctx(nameless, spans), **args) is None


@pytest.mark.parametrize("reader,args", [
    (device_idle_in_span, {"name": "emit", "p": 50}),
    (device_share, {"program": "decode"}),
])
def test_an_untraced_run_gives_nothing(reader, args):
    assert reader.read(Ctx(None), **args) is None


# -- hand-made inputs -------------------------------------------------------


def test_hand_made_trace_reads_as_reckoned(on):
    trace = {
        "annotations": [
            ["tpudl.emit", 50.0, 100.0, 1],      # before the first op
            ["tpudl.emit", 1000.0, 100.0, 2],    # ops cover 30 of it
            ["tpudl.emit", 2000.0, 200.0, 3],    # all idle
            ["tpudl.other", 1000.0, 100.0, 4],
            ["tpudl.emit", 2950.0, 100.0, 5],    # ends after the last op
        ],
        "modules": [["jit_tpudl_decode", 100.0, 1000.0],
                    ["jit_tpudl_seat", 2500.0, 500.0]],
        "ops": [
            ["%a", 100.0, 400.0, "jit_tpudl_decode", "jit(x)/attention/dot"],
            ["%b", 400.0, 200.0, "jit_tpudl_decode",
             "jit(x)/transpose(jvp(dropout))/mul"],       # overlaps %a
            ["%c", 1010.0, 30.0, "jit_tpudl_decode", ""],
            ["%d", 2500.0, 500.0, "jit_tpudl_seat", "jit(y)/kv_scatter/s"],
        ],
    }
    ctx = Ctx(trace)
    # Busy: [100, 600) + [1010, 1040) + [2500, 3000) = 1030.
    assert sum(e - s for s, e in pt.busy(trace)) == 1030.0
    assert [i for _, _, i in pt.occurrences(trace, "emit")] == [2, 3]
    assert device_idle_in_span.read(ctx, name="emit", p=0) == (
        pytest.approx(70.0e-6)
    )
    assert device_idle_in_span.read(ctx, name="emit", p=100) == (
        pytest.approx(200.0e-6)
    )
    assert device_idle_in_span.read(ctx, name="absent", p=50) is None
    assert device_share.read(ctx, program="decode") == pytest.approx(
        100.0 * 530.0 / 1030.0
    )
    assert device_share.read(ctx, program="seat") == pytest.approx(
        100.0 * 500.0 / 1030.0
    )
    assert device_share.read(ctx, scope="dropout") == pytest.approx(
        100.0 * 200.0 / 1030.0
    )
    assert device_share.read(ctx, program="decode", scope="attention") == (
        pytest.approx(100.0 * 400.0 / 1030.0)
    )
    assert device_share.read(ctx, program="prefill") is None
    assert device_share.read(ctx, scope="optimizer") is None


@pytest.mark.parametrize("path,scope,found", [
    ("jit(tpudl_decode)/LlamaForCausalLM/model/layer_3/attention/kv_gather/gather",
     "kv_gather", True),
    ("jit(tpudl_decode)/LlamaForCausalLM/model/layer_3/attention/kv_gather/gather",
     "attention", True),
    ("jit(tpudl_decode)/LlamaForCausalLM/model/layer_3/attention/kv_gather/gather",
     "gather", True),
    ("jit(tpudl_decode)/LlamaForCausalLM/model/layer_3/attention/kv_gather/gather",
     "kv", False),
    ("jit(tpudl_train_step)/transpose(jvp(Bert))/bert/encoder/layer_0/ffn/dropout/mul",
     "dropout", True),
    ("jit(tpudl_train_step)/transpose(jvp(dropout))/select_n", "dropout",
     True),
    ("jit(tpudl_train_step)/optimizer/grad_clip/sqrt", "optimizer", True),
    ("jit(tpudl_train_step)/optimizer/grad_clip/sqrt", "grad_clip", True),
    ("jit(tpudl_train_step)/bert/embeddings/layer_norm/add", "norm", False),
    ("", "attention", False),
])
def test_a_scope_is_a_whole_component_of_the_path(path, scope, found):
    assert pt.has_scope(path, scope) is found


def test_kv_live_share_over_what_is_kept():
    spans = [
        {"kind": "span", "name": "decode_step", "ts": 1.0, "dur": 0.05,
         "busy": 2, "tokens_live": 320, "pages_reserved": 40},
        {"kind": "span", "name": "decode_step", "ts": 2.0, "dur": 0.05,
         "busy": 1, "tokens_live": 64, "pages_reserved": 16},
        {"kind": "span", "name": "prefill", "ts": 1.5, "dur": 0.03},
    ]
    ctx = Ctx(None, spans, slots=4,
              session={"page_size": 16, "max_seq_len": 256})
    assert kv_live_share.read(ctx, over="reserved") == pytest.approx(
        100.0 * (320 / 640 + 64 / 256) / 2
    )
    assert kv_live_share.read(ctx, over="gathered") == pytest.approx(
        100.0 * (320 / 1024 + 64 / 1024) / 2
    )
    with pytest.raises(ValueError):
        kv_live_share.read(ctx, over="allocated")


def test_module_event_names():
    name = "jit_tpudl_decode(16817519888760548089)"
    assert pt.program_of(name) == "jit_tpudl_decode"
    assert pt.program_id_of(name) == 16817519888760548089
    assert pt.program_id_of("jit_fn") is None


# -- the scope table, from a trace's bytes ----------------------------------


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint((number << 3) | 2) + _varint(len(value)) + value


def _stat(metadata_id, **kw):
    body = _field(1, metadata_id)
    if "u64" in kw:
        body += _field(3, kw["u64"])
    if "text" in kw:
        body += _field(5, kw["text"])
    if "ref" in kw:
        body += _field(7, kw["ref"])
    if "f64" in kw:  # a fixed-width field, skipped by the reader
        body += _varint((2 << 3) | 1) + b"\x00" * 8
    return body


def _plane(name, events, stat_names):
    body = _field(2, name)
    body += _field(3, b"\x0a\x03abc")  # a line: skipped whole
    for event_id, (op_name, stats) in events.items():
        md = _field(1, event_id) + _field(2, op_name)
        for stat in stats:
            md += _field(5, stat)
        body += _field(4, _field(1, event_id) + _field(2, md))
    for stat_id, stat_name in stat_names.items():
        md = _field(1, stat_id) + _field(2, stat_name)
        body += _field(5, _field(1, stat_id) + _field(2, md))
    return _field(1, body)


def test_scope_table_from_bytes():
    names = {1: "tf_op", 2: "program_id", 3: "flops",
             4: "jit(f)/by/reference:"}
    big = 16817519888760548089
    device = _plane("/device:TPU:0", {
        10: ("%fusion.1 = bf16[8]", [
            _stat(3, f64=True), _stat(2, u64=big),
            _stat(1, text="jit(tpudl_decode)/m/attention/kv_gather/gather:"),
        ]),
        11: ("%fusion.1 = bf16[8]", [   # the same name in another program
            _stat(2, u64=7), _stat(1, text="jit(tpudl_seat)/kv_scatter/s:"),
        ]),
        12: ("%copy-start", [_stat(2, u64=7)]),          # no scope
        13: ("%by.ref", [_stat(2, u64=7), _stat(1, ref=4)]),
    }, names)
    host = _plane("/host:CPU", {
        20: ("tpudl.emit", [_stat(1, text="not/a/device/op:")]),
    }, names)
    custom = _plane("/device:CUSTOM:Megascale Trace", {
        30: ("%x", [_stat(1, text="not/this/plane:")]),
    }, names)
    table = pt.scope_table(host + device + custom)
    assert table == {
        (big, "%fusion.1 = bf16[8]"):
            "jit(tpudl_decode)/m/attention/kv_gather/gather",
        (7, "%fusion.1 = bf16[8]"): "jit(tpudl_seat)/kv_scatter/s",
        (7, "%by.ref"): "jit(f)/by/reference",
    }
    assert pt.scope_table(b"") == {}


def test_cut_keeps_what_lies_beside_the_operations():
    trace = {
        "annotations": [["tpudl.a", 0.0, 5.0, 1], ["tpudl.a", 90.0, 5.0, 2]],
        "modules": [["jit_tpudl_decode", 0.0, 50.0],
                    ["jit_tpudl_decode", 80.0, 50.0]],
        "ops": [["%a", 1.0, 10.0, "jit_tpudl_decode", ""],
                ["%b", 20.0, 10.0, "jit_tpudl_decode", ""],
                ["%c", 85.0, 10.0, "jit_tpudl_decode", ""]],
    }
    got = pt.cut(trace, 2)
    assert [o[0] for o in got["ops"]] == ["%a", "%b"]
    assert len(got["modules"]) == 1 and len(got["annotations"]) == 1
