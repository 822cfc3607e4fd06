"""The two readers that put the device's idle time down to what the host
was doing (``device_idle_by_phase``, ``decode_turnaround``): on a
hand-made trace reckoned by hand, on a small trace recorded on the chip,
and on traces of programs that lack the annotations."""

import json
import os

import pytest

from perfbench.readers import _program_trace as pt
from perfbench.readers import decode_turnaround, device_idle_by_phase

HERE = os.path.dirname(os.path.abspath(__file__))


class Ctx:
    def __init__(self, trace=None):
        self._trace = trace


@pytest.fixture()
def on(monkeypatch):
    """``of_run`` answers with the trace the context holds."""
    monkeypatch.setattr(pt, "of_run", lambda ctx: ctx._trace)


def step(t, ids, admit, prepare, decode, dispatch, address, readback, emit,
         end, inside_admit=()):
    """One engine step's annotations; ``decode`` = (start, end)."""
    a = [
        ["tpudl.engine_step", t, end - t, next(ids)],
        ["tpudl.admit", admit[0], admit[1] - admit[0], next(ids)],
    ]
    a += [[f"tpudl.{n}", s, e - s, next(ids)] for n, s, e in inside_admit]
    a += [
        ["tpudl.decode_prepare", prepare[0], prepare[1] - prepare[0],
         next(ids)],
        ["tpudl.decode_step", decode[0], decode[1] - decode[0], next(ids)],
        ["tpudl.decode.dispatch", dispatch[0], dispatch[1] - dispatch[0],
         next(ids)],
        ["tpudl.decode.address", address[0], address[1] - address[0],
         next(ids)],
        ["tpudl.decode.readback", readback[0], readback[1] - readback[0],
         next(ids)],
        ["tpudl.emit", emit[0], emit[1] - emit[0], next(ids)],
    ]
    return a


def hand_made():
    """Four engine steps around six idle gaps; the third step seats a
    prompt. Reckoned by hand, ns:

    busy  [100,300) decode, [310,320) select, [500,700) decode,
          [800,1000) prefill, [1010,1020) seat, [1200,1400) decode,
          [1500,1600) decode: 920 of the window [100,1600), idle 580.

    gap [300,310)   readback 10
    gap [320,500)   readback 60, decode_step's tail 20, emit 15,
                    engine_step 5, outside 10, engine_step 5, admit 10,
                    engine_step 5, decode_prepare 20, dispatch 10,
                    address 15, dispatch 5              (twelve pieces)
    gap [700,800)   readback 40, decode_step 20, emit 10, engine_step 5,
                    outside 5, engine_step 5, admit 5, prefill.dispatch 10
    gap [1000,1010) prefill.readback 3, prefill 2, admit 1, seat 4
    gap [1020,1200) seat 10, admit 70 (20 of them under an annotation
                    no metric names), engine_step 5, decode_prepare 15,
                    dispatch 10, address 10, dispatch 50, readback 10
    gap [1400,1500) readback 30, decode_step 20, emit 10, engine_step 5,
                    outside 5, engine_step 2, seat 4 (an annotation with
                    admit's very extent and a younger id), engine_step
                    2, decode_prepare 7, dispatch 5, address 5,
                    dispatch 5
    """
    ids = iter(range(1, 200))
    annotations = (
        step(50, ids, (60, 70), (70, 90), (90, 400), (90, 150), (95, 105),
             (150, 380), (400, 415), 420)
        + step(430, ids, (435, 445), (450, 470), (470, 760), (470, 520),
               (480, 495), (520, 740), (760, 770), 775)
        + step(780, ids, (785, 1100), (1105, 1120), (1120, 1450),
               (1120, 1190), (1130, 1140), (1190, 1430), (1450, 1460), 1465,
               inside_admit=[
                   ("prefill", 790, 1005),
                   ("prefill.dispatch", 790, 850),
                   ("prefill.readback", 850, 1003),
                   ("seat", 1006, 1030),
                   ("migration_import", 1040, 1060),
               ])
        + step(1470, ids, (1472, 1476), (1478, 1485), (1485, 1650),
               (1485, 1510), (1490, 1495), (1510, 1640), (1650, 1660), 1700,
               inside_admit=[("seat", 1472, 1476)])
    )
    modules = [
        ["jit_tpudl_decode", 100.0, 200.0], ["jit_tpudl_select", 310.0, 10.0],
        ["jit_tpudl_decode", 500.0, 200.0],
        ["jit_tpudl_prefill", 800.0, 200.0], ["jit_tpudl_seat", 1010.0, 10.0],
        ["jit_tpudl_decode", 1200.0, 200.0],
        ["jit_tpudl_decode", 1500.0, 100.0],
    ]
    ops = [[f"%op{i}", m[1], m[2], m[0], ""] for i, m in enumerate(modules)]
    annotations = [[n, float(s), float(d), i] for n, s, d, i in annotations]
    return {"annotations": sorted(annotations, key=lambda a: a[1]),
            "modules": modules, "ops": ops}


BY_HAND = {
    "decode.readback": 150.0, "decode_step": 60.0, "emit": 35.0,
    "engine_step": 39.0, "outside": 20.0, "admit": 86.0,
    "decode_prepare": 42.0, "decode.dispatch": 85.0, "decode.address": 30.0,
    "prefill.dispatch": 10.0, "prefill.readback": 3.0, "prefill": 2.0,
    "seat": 18.0,
}


def test_by_hand_adds_up():
    assert sum(BY_HAND.values()) == 580.0
    assert set(BY_HAND) == {*device_idle_by_phase.NAMED, "outside"}


@pytest.mark.parametrize("phase", sorted(BY_HAND))
def test_every_phase_reads_as_reckoned(on, phase):
    got = device_idle_by_phase.read(Ctx(hand_made()), phases=[phase])
    assert got == pytest.approx(100.0 * BY_HAND[phase] / 1500.0)


def test_a_gap_that_straddles_phases_is_split_among_them():
    """The gap [320, 500) alone: twelve pieces over eight phases, where
    the midpoint rule would have given all 180 ns to ``emit``."""
    trace = hand_made()
    segments = device_idle_by_phase.phase_segments(
        trace["annotations"], 320.0, 500.0
    )
    assert [(e - s, p) for s, e, p in segments] == [
        (60.0, "decode.readback"), (20.0, "decode_step"), (15.0, "emit"),
        (5.0, "engine_step"), (10.0, "outside"), (5.0, "engine_step"),
        (10.0, "admit"), (5.0, "engine_step"), (20.0, "decode_prepare"),
        (10.0, "decode.dispatch"), (15.0, "decode.address"),
        (5.0, "decode.dispatch"),
    ]
    # Segments tile whatever they are asked for, end to end.
    whole = device_idle_by_phase.phase_segments(
        trace["annotations"], 100.0, 1600.0
    )
    assert whole[0][0] == 100.0 and whole[-1][1] == 1600.0
    assert all(a[1] == b[0] for a, b in zip(whole, whole[1:]))


def test_the_shares_add_up_to_the_idle_share(on):
    trace = hand_made()
    ctx = Ctx(trace)
    busy = sum(e - s for s, e in pt.busy(trace))
    assert busy == 920.0
    groups = [
        ["decode.dispatch"], ["decode.address"], ["decode.readback"],
        ["emit", "decode_prepare", "decode_step", "seat", "engine_step"],
        ["admit"], ["outside"], ["prefill.dispatch", "prefill"],
        ["prefill.readback"],
    ]
    shares = [device_idle_by_phase.read(ctx, phases=g) for g in groups]
    assert sum(shares) == pytest.approx(100.0 * (1 - busy / 1500.0))
    assert shares[3] == pytest.approx(100.0 * (35 + 42 + 60 + 18 + 39) / 1500)
    assert shares[6] == pytest.approx(100.0 * 12.0 / 1500.0)


def test_the_metric_files_name_every_phase_once():
    """The closure is by construction: in a backlog cell and in
    ``shortchat-steady`` the eight shares' ``phases`` are a partition
    of ``NAMED`` and ``outside``."""
    root = os.path.dirname(os.path.dirname(HERE))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for cell in ("mistral-7b-l16.shortchat-steady",
                 "mistral-7b-l16.longprompt-backlog",
                 "longcat-flash-l4-e16.reasoning-backlog"):
        named = []
        for m in bench["per_layer"]:
            if cell not in m.get("workloads", ()):
                continue
            with open(os.path.join(root, "perfbench", "metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            if spec["reader"] == "device_idle_by_phase":
                assert m["unit"] == "%" and m["source"] == "device_trace"
                named += spec["args"]["phases"]
        assert sorted(named) == sorted(
            (*device_idle_by_phase.NAMED, "outside")
        ), cell


def test_a_prefill_between_two_decodes_breaks_the_pair(on):
    trace = hand_made()
    # (1) 300 -> 500 less the selection's 10; (2) 1400 -> 1500. The
    # second and third decodes have a prefill and a seat between them.
    assert decode_turnaround.turnarounds(trace) == [190.0, 100.0]
    assert decode_turnaround.read(Ctx(trace), p=50) == pytest.approx(145e-6)
    assert decode_turnaround.read(Ctx(trace), p=100) == pytest.approx(190e-6)
    for breaker in ("jit_tpudl_chunk_prefill", "jit_tpudl_verify",
                    "jit_tpudl_seat_shared"):
        broken = dict(trace, modules=sorted(
            trace["modules"] + [[breaker, 1450.0, 10.0]], key=lambda m: m[1]
        ))
        assert decode_turnaround.turnarounds(broken) == [190.0]
    only = dict(trace, modules=trace["modules"][2:5])
    assert decode_turnaround.read(Ctx(only), p=50) is None


def decodes_only():
    """Two pure decode steps: the program has every annotation, and a
    prefill's held no idle time."""
    trace = hand_made()
    keep = {a[3] for a in trace["annotations"] if a[1] < 780.0}
    return {
        "annotations": [a for a in trace["annotations"] if a[3] in keep],
        "modules": trace["modules"][:3], "ops": trace["ops"][:3],
    }


@pytest.mark.parametrize("phases", [
    ["prefill.readback"], ["prefill.dispatch", "prefill"], ["seat"],
])
def test_a_phase_that_held_no_idle_time_reads_zero_not_nothing(on, phases):
    assert device_idle_by_phase.read(Ctx(decodes_only()), phases=phases) == 0.0


def test_a_program_without_the_new_annotations_reads_nothing(on):
    """The parent of the PR that brought these readers: its trace names
    ``decode.dispatch`` and ``emit`` but neither admission nor the
    step's preparation, so a share by innermost annotation would mean
    something else there."""
    with open(os.path.join(HERE, "recorded_trace_program.json")) as f:
        older = json.load(f)
    assert any(a[0] == "tpudl.decode.dispatch" for a in older["annotations"])
    for phases in (["decode.dispatch"], ["outside"], ["admit"]):
        assert device_idle_by_phase.read(Ctx(older), phases=phases) is None
    # The turnaround needs the programs' names alone, which it has.
    assert decode_turnaround.read(Ctx(older), p=50) > 0
    nameless = {"annotations": [], "modules": [["jit_fn", 0.0, 10.0]],
                "ops": [["%a", 0.0, 10.0, "jit_fn", ""]]}
    assert device_idle_by_phase.read(Ctx(nameless), phases=["emit"]) is None
    assert decode_turnaround.read(Ctx(nameless), p=50) is None
    empty = {"annotations": [], "modules": [], "ops": []}
    assert device_idle_by_phase.read(Ctx(empty), phases=["emit"]) is None


@pytest.mark.parametrize("reader,args", [
    (device_idle_by_phase, {"phases": ["emit"]}),
    (decode_turnaround, {"p": 50}),
])
def test_an_untraced_run_gives_nothing(reader, args):
    class Untraced:
        tracer = None

    assert reader.read(Untraced(), **args) is None


def test_a_phase_no_file_may_name_is_an_error(on):
    with pytest.raises(KeyError):
        device_idle_by_phase.read(Ctx(hand_made()), phases=["migration_import"])


# -- a trace recorded on the chip -------------------------------------------
#
# Cut from a traced run of ``mistral-7b-l16.shortchat-steady`` on the
# change of PR 36 (seed 3600000107, one TPU v5e):
#   python3 -m perfbench.readers._program_trace <trace_dir> out.json 3409 20641
# It begins at the end of one decode program and ends inside another:
# decode, decode, a prefill and its seat, decode, decode.


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace_idle.json")) as f:
        return json.load(f)


def idle_ns(trace, start, end):
    """Idle time inside [start, end) clipped to the traced window, by an
    arithmetic of the test's own: the length less the union of the
    operations' intervals there."""
    lo = min(o[1] for o in trace["ops"])
    hi = max(o[1] + o[2] for o in trace["ops"])
    start, end = max(start, lo), min(end, hi)
    if end <= start:
        return 0.0
    busy, upto = 0.0, start
    for s, e in sorted((o[1], o[1] + o[2]) for o in trace["ops"] if o[2] > 0):
        s, e = max(s, upto), min(e, end)
        if e > s:
            busy += e - s
            upto = e
    return (end - start) - busy


def idle_in(trace, name):
    return sum(idle_ns(trace, a[1], a[1] + a[2])
               for a in trace["annotations"] if a[0] == "tpudl." + name)


def test_recorded_trace_is_of_a_program_with_every_span(recorded):
    names = {a[0][len("tpudl."):] for a in recorded["annotations"]}
    assert names == set(device_idle_by_phase.NAMED)
    assert [m[0] for m in recorded["modules"]
            if m[0] != "jit_tpudl_select"] == [
        "jit_tpudl_decode", "jit_tpudl_decode", "jit_tpudl_prefill",
        "jit_tpudl_seat", "jit_tpudl_decode", "jit_tpudl_decode",
    ]


def test_recorded_shares_add_up_to_the_idle_share(recorded):
    idle = dict(device_idle_by_phase.idle_by_phase(recorded))
    window = idle.pop("window")
    lo = min(o[1] for o in recorded["ops"])
    hi = max(o[1] + o[2] for o in recorded["ops"])
    assert window == hi - lo
    assert all(ns >= 0 for ns in idle.values())
    assert sum(idle.values()) == pytest.approx(idle_ns(recorded, lo, hi))
    # Most of a short-chat trace's idle time is the decode turnaround.
    assert max(idle, key=idle.get) == "decode.readback"
    assert 0 < sum(idle.values()) < 0.5 * window


@pytest.mark.parametrize("leaf", [
    "decode.address", "decode.readback", "prefill.dispatch",
    "prefill.readback", "seat", "decode_prepare", "emit",
])
def test_a_leaf_phase_is_the_idle_time_inside_its_annotations(recorded, leaf):
    idle = device_idle_by_phase.idle_by_phase(recorded)
    assert idle[leaf] == pytest.approx(idle_in(recorded, leaf))
    assert idle[leaf] > 0


@pytest.mark.parametrize("parent,children", [
    ("decode.dispatch", ["decode.address"]),
    ("decode_step", ["decode.dispatch", "decode.readback"]),
    ("prefill", ["prefill.dispatch", "prefill.readback"]),
    ("admit", ["prefill", "seat"]),
    ("engine_step", ["admit", "decode_prepare", "decode_step", "emit"]),
])
def test_a_parents_phase_is_its_idle_time_less_its_childrens(
        recorded, parent, children):
    idle = device_idle_by_phase.idle_by_phase(recorded)
    own = idle_in(recorded, parent) - sum(
        idle_in(recorded, c) for c in children
    )
    assert idle[parent] == pytest.approx(own, abs=1e-3)


def test_recorded_turnarounds_are_the_two_pure_pairs(recorded):
    decodes = [m for m in recorded["modules"] if m[0] == "jit_tpudl_decode"]
    first, second, third, fourth = decodes
    by_hand = [
        idle_ns(recorded, first[1] + first[2], second[1]),
        idle_ns(recorded, third[1] + third[2], fourth[1]),
    ]
    got = decode_turnaround.turnarounds(recorded)
    assert got == pytest.approx(by_hand)
    # About 3 ms each: what a Mistral decode step's device waits.
    assert all(2.5e6 < g < 3.5e6 for g in got)
    # The selection's program ran in between and counts as busy.
    assert got[0] < second[1] - (first[1] + first[2])

