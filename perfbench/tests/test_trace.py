"""The reduction from a trace to numbers: on a small trace recorded on
the chip, and on a hand-made one whose answers are known."""

import json
import os

import pytest

from perfbench import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def union_ns(intervals):
    """Length of the union of (start, end) intervals — by an arithmetic
    of the test's own, to hold the reducer's against."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_what_its_events_say(recorded):
    planes = tr.device_planes(recorded)
    assert [p["name"] for p in planes] == ["/device:TPU:0"]
    ops = tr.op_events(planes[0])
    assert len(ops) == 150
    out = tr.reduce(recorded)
    # Busy time is the union of the operations' intervals, by an
    # arithmetic of its own.
    busy = union_ns((e[1], e[1] + e[2]) for e in ops)
    assert out["busy_s"] == pytest.approx(busy / 1e9)
    first = min(e[1] for e in ops)
    last = max(e[1] + e[2] for e in ops)
    assert out["window_s"] == pytest.approx((last - first) / 1e9)
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["devices"] == 1
    names = [n for n, _ in out["device_ops"]]
    # The paged cache gathered dense over all 48 x 1,024 positions, and
    # the page pool copied whole by a decode step that does not donate it.
    assert names[0] == "fusion:kCustom_bf16_49152_8_128_"
    assert "copy_bf16_3073_16_8_128_" in names[:3]
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    total = sum(e[2] for e in ops)
    assert sum(s for _, s in out["device_ops"]) <= total / 1e9 + 1e-12


def test_recorded_gaps_go_to_the_span_that_covers_them(recorded):
    host = [
        (e[0], e[1], e[1] + e[2])
        for p in recorded["planes"] if p["name"].startswith("/host")
        for line in p["lines"] for e in line["events"]
        if e[0] != tr.SYNC_NAME
    ]
    assert host and host[0][0] == "perfbench.engine_step"
    out = tr.reduce(recorded, host)
    assert [n for n, _ in out["idle_gaps"]] == ["perfbench.engine_step"]
    assert out["idle_gaps"][0][1] == pytest.approx(
        out["window_s"] - out["busy_s"])


@pytest.mark.parametrize("name,want", [
    ("%fusion.21 = bf16[49152,8,128]{2,1,0:T(8,128)(2,1)} fusion(bf16[4] %b), "
     "kind=kCustom, calls=%f", "fusion:kCustom_bf16_49152_8_128_"),
    ("%copy.790 = bf16[3073,16,8,128]{3,2,1,0} copy(bf16[3073,16,8,128] %c)",
     "copy_bf16_3073_16_8_128_"),
    ("%copy-start = (bf16[4096,14336]{1,0}, u32[]) copy-start(%p)",
     "copy-start_bf16_4096_14336_"),
    ("%fusion.1037 = s32[48]{0:T(128)} fusion(s32[48] %t), kind=kLoop",
     "fusion:kLoop_s32_48_"),
    ("dot_general.1", "dot_general"),
])
def test_stable_names(name, want):
    assert tr.stable_name(name) == want


def _hand_made():
    ops = [["%add.1 = f32[8]{0} add(f32[8] %x)", 100.0, 50.0],
           ["%add.2 = f32[8]{0} add(f32[8] %y)", 140.0, 30.0],   # overlaps
           ["%dot.3 = f32[4,4]{1,0} dot(f32[4,4] %z)", 300.0, 100.0],
           ["%add.4 = f32[8]{0} add(f32[8] %x)", 600.0, 100.0]]
    host = [[tr.SYNC_NAME, 1000.0 + 10.0, 1.0],
            [tr.SYNC_NAME, 1000.0 + 510.0, 1.0],
            ["perfbench.engine_step", 90.0, 320.0]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_fn(1)", 100.0, 600.0]]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["%add.1 = f32[8]{0} add()", 100.0, 350.0]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def test_hand_made_trace():
    trace = _hand_made()
    spans = [("decode_step", 250.0, 450.0),
             ("perfbench.engine_step", 90.0, 410.0)]
    out = tr.reduce(trace, spans)
    # Device 0 is busy [100,170], [300,400], [600,700] = 270 ns; device 1
    # 350 ns; the mean is reported.
    assert out["busy_s"] == pytest.approx((270 + 350) / 2 / 1e9)
    assert out["window_s"] == pytest.approx(600 / 1e9)
    assert out["devices"] == 2
    assert dict(out["device_ops"]) == pytest.approx(
        {"add_f32_8_": 180e-9, "dot_f32_4_4_": 100e-9})
    # Gap [170,300] lies in the engine step and not yet in the decode
    # span (its middle, 235); gap [400,600] is covered by nothing.
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"perfbench.engine_step": 130e-9, "outside_spans": 200e-9})


def test_clock_offset_pairs_marks_with_annotations_in_order():
    trace = _hand_made()
    marks = [5e-9, 505e-9]           # monotonic seconds of the two marks
    assert tr.clock_offset_ns(trace, marks) == pytest.approx(1005.0)
    assert tr.clock_offset_ns(trace, []) is None
    records = [{"kind": "span", "name": "decode_step", "ts": 1e-6, "dur": 2e-6},
               {"kind": "event", "name": "decode_step", "ts": 0.0},
               {"kind": "span", "name": "other", "ts": 0.0, "dur": 1.0}]
    assert tr.spans_on_trace_clock(records, 1005.0, ("decode_step",)) == [
        ("decode_step", pytest.approx(2005.0), pytest.approx(4005.0))]


def test_busy_inside_clips_to_the_windows():
    merged = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    assert tr.busy_inside(merged, [(5.0, 25.0)]) == pytest.approx(10.0)
    assert tr.busy_inside(merged, [(5.0, 8.0), (45.0, 60.0)]) == pytest.approx(8.0)
    assert tr.busy_inside(merged, []) == 0.0


def test_a_trace_without_device_operations_reports_no_busy_time():
    out = tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
    assert out["busy_s"] == 0.0 and out["device_ops"] == []
