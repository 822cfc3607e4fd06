"""The readers of set-up's own timeline (``_setup`` and the four that
use it) and ``span_attr_ratio``, on a hand-made context reckoned by
hand, and on the contexts that hold nothing to read: an untraced run,
and a program from before it recorded its start-up."""

import importlib
import json
import os

import pytest

from perfbench.manifest import Manifest
from perfbench.readers import (
    _setup,
    setup_outside_program,
    setup_program_count,
    setup_program_seconds,
    setup_span_seconds,
    span_attr_ratio,
)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
T0 = 100.0


class Ctx:
    def __init__(self, spans, setup_s=30.0, window_s=50.0):
        self.spans = spans
        self.setup = {"setup_s": setup_s}
        self.record = {"t0_monotonic": T0, "window_s": window_s}

    def window_spans(self, name):
        t0, t1 = T0, T0 + self.record["window_s"]
        return [s for s in self.spans if s.get("kind") == "span"
                and s.get("name") == name and t0 <= s["ts"] < t1]


def span(ident, name, ts, dur, parent=None, **attrs):
    return {"kind": "span", "name": name, "ts": ts, "dur": dur,
            "id": ident, "parent": parent, **attrs}


def hand_made():
    """Set-up from 70 to 100 (``setup_s`` 30), the window from 100.

    70-72    the harness makes weights: ``jit(<lambda>)``, not the
             program's (compile 1.5)
    72-90    startup.from_model
      72-73    startup.quantize
      73-74.5  startup.weights
      75-76    startup.cache_template > program.trace tpudl_prefill 0.9
      76-78    startup.pools > broadcast_in_dim: trace .1 lower .2
               compile .3 (a cache miss), the program's by its phase
      78-90    startup.prefill_lengths > startup.prefill_dry_run:
               kernel.trace 0.4 (inside the trace of tpudl_prefill:
               trace 2, lower 3, compile 4, a cache hit)
    90-93    the reference compiles ``jit(reference_step)``: left out
    93-94    tpudl_select built outside every phase (trace .2, lower .3,
             compile .5, no cache verdict): the program's by its name
    95-99    startup.first_requests, recorded after the fact: around
             tpudl_decode's compile 2.5 (a hit) by the clock, no parent
    99.5-101 a program.compile that ENDS in the window: not set-up
    110-     the window: two ``prefill`` spans
    """
    return [
        span(1, "program.compile", 70.5, 1.5, program="<lambda>",
             cache_hit=0),
        span(2, "startup.from_model", 72.0, 18.0, slots=4),
        span(3, "startup.quantize", 72.0, 1.0, parent=2),
        span(4, "startup.weights", 73.0, 1.5, parent=2, leaves=48),
        span(5, "startup.cache_template", 75.0, 1.0, parent=2),
        span(6, "program.trace", 75.05, 0.9, parent=5,
             program="tpudl_prefill"),
        span(7, "startup.pools", 76.0, 2.0, parent=2, pages=9),
        span(8, "program.trace", 76.1, 0.1, parent=7,
             program="broadcast_in_dim"),
        span(9, "program.lower", 76.2, 0.2, parent=7,
             program="broadcast_in_dim"),
        span(10, "program.compile", 76.4, 0.3, parent=7,
             program="broadcast_in_dim", cache_hit=0),
        span(11, "startup.prefill_lengths", 78.0, 12.0, parent=2),
        span(12, "startup.prefill_dry_run", 78.0, 12.0, parent=11, rows=512),
        span(13, "kernel.trace", 78.5, 0.4, parent=14,
             kernel="paged_attention"),
        span(14, "program.trace", 78.0, 2.0, parent=12,
             program="tpudl_prefill"),
        span(15, "program.lower", 80.0, 3.0, parent=12,
             program="tpudl_prefill"),
        span(16, "program.compile", 83.0, 4.0, parent=12,
             program="tpudl_prefill", cache_hit=1, cache_read_s=3.9),
        span(17, "program.trace", 90.0, 1.0, program="reference_step"),
        span(18, "program.compile", 91.0, 2.0, program="reference_step",
             cache_hit=0),
        span(19, "program.trace", 93.0, 0.2, program="tpudl_select"),
        span(20, "program.lower", 93.2, 0.3, program="tpudl_select"),
        span(21, "program.compile", 93.5, 0.5, program="tpudl_select"),
        span(22, "startup.first_requests", 95.0, 4.0),
        span(23, "program.compile", 96.0, 2.5,
             program="tpudl_decode", cache_hit=1),
        span(24, "program.compile", 99.5, 1.5, program="tpudl_late",
             cache_hit=0),
        span(25, "prefill", 110.0, 1.0, attention_kernel_layers=2,
             attention_layers=5, attention_in_kernel=1),
        span(26, "prefill", 120.0, 1.0, attention_kernel_layers=0,
             attention_layers=5, attention_in_kernel=0),
        {"kind": "event", "name": "compile_cache_hit", "ts": 83.0},
    ]


def test_set_up_is_what_ended_before_the_window_began():
    setup = _setup.records(Ctx(hand_made()))
    assert [s["id"] for s in setup] == list(range(1, 24))
    own = _setup.programs(setup)
    # The harness's weights and the reference's programs are left out;
    # an initialiser's program counts by the phase around it.
    assert [s["id"] for s in own] == [6, 8, 9, 10, 14, 15, 16, 19, 20, 21, 23]


def test_a_program_is_its_phases_by_the_clock_and_not_by_its_parent():
    """``serve`` and ``stream`` record their first requests after the
    fact: what was built inside has no ``startup.*`` ancestor."""
    setup = [
        span(1, "startup.first_requests", 95.0, 4.0),
        span(2, "program.compile", 96.0, 2.5, program="broadcast_in_dim"),
        span(3, "program.compile", 94.5, 1.0, program="broadcast_in_dim",
             parent=1),
    ]
    assert [s["id"] for s in _setup.programs(setup)] == [2]


def test_each_metrics_arithmetic():
    ctx = Ctx(hand_made())
    assert setup_program_seconds.read(ctx, "trace") == pytest.approx(
        0.9 + 0.1 + 2.0 + 0.2)
    assert setup_program_seconds.read(ctx, "lower") == pytest.approx(
        0.2 + 3.0 + 0.3)
    # The cross-check of ``compile_s``.
    assert setup_program_seconds.read(ctx, "compile") == pytest.approx(
        0.3 + 4.0 + 0.5 + 2.5)
    assert setup_program_count.read(ctx) == 4
    assert setup_program_count.read(ctx, cache_hit=0) == 1
    assert setup_span_seconds.read(ctx, ["kernel.trace"]) == pytest.approx(0.4)
    assert setup_span_seconds.read(ctx, ["startup.pools"]) == 2.0
    assert setup_span_seconds.read(
        ctx, ["startup.weights", "startup.quantize"]) == 2.5
    # Nothing of that name in this run: a zero that WAS measured.
    assert setup_span_seconds.read(ctx, ["startup.init_state"]) == 0.0
    # 30 s less the union of [72, 90), [93, 94) and [95, 99).
    assert setup_outside_program.read(ctx) == pytest.approx(30.0 - 23.0)
    assert span_attr_ratio.read(
        ctx, "prefill", "attention_kernel_layers", "attention_layers"
    ) == pytest.approx(2 / 10)


def test_the_union_counts_a_second_once():
    spans = [span(1, "a", 0.0, 4.0), span(2, "b", 1.0, 1.0),
             span(3, "c", 3.0, 3.0), span(4, "d", 10.0, 1.0)]
    assert _setup.union_seconds(spans) == 7.0
    assert _setup.union_seconds([]) == 0.0


READERS = [
    (setup_program_seconds, {"stage": "trace"}),
    (setup_program_count, {}),
    (setup_program_count, {"cache_hit": 0}),
    (setup_span_seconds, {"names": ["startup.pools"]}),
    (setup_outside_program, {}),
]


@pytest.mark.parametrize("reader,args", READERS)
def test_nothing_where_no_start_up_was_recorded(reader, args):
    """An untraced run has no spans; the parent of the PR that brought
    these metrics has only the window's: nothing, never a zero."""
    assert reader.read(Ctx([]), **args) is None
    window_alone = [s for s in hand_made() if s.get("name") == "prefill"]
    assert reader.read(Ctx(window_alone), **args) is None


def test_nothing_where_the_prefill_spans_carry_no_layer_counts():
    old = [span(1, "prefill", 110.0, 1.0, attention_in_kernel=1)]
    args = ("prefill", "attention_kernel_layers", "attention_layers")
    assert span_attr_ratio.read(Ctx(old), *args) is None
    assert span_attr_ratio.read(Ctx([]), *args) is None
    before_the_window = [span(1, "prefill", 80.0, 1.0,
                              attention_kernel_layers=1, attention_layers=1)]
    assert span_attr_ratio.read(Ctx(before_the_window), *args) is None


def test_a_zero_where_a_recorded_start_up_holds_none_of_it():
    ctx = Ctx([span(1, "startup.from_model", 72.0, 10.0)], setup_s=12.0)
    assert setup_program_seconds.read(ctx, "trace") == 0.0
    assert setup_program_count.read(ctx) == 0
    assert setup_program_count.read(ctx, cache_hit=0) == 0
    assert setup_span_seconds.read(ctx, ["kernel.trace"]) == 0.0
    assert setup_outside_program.read(ctx) == pytest.approx(2.0)


NEW = {
    "setup_trace_s": 12, "setup_lower_s": 12, "setup_programs_built": 12,
    "setup_cache_misses": 12, "setup_outside_program_s": 12,
    "setup_kernel_trace_s": 11, "setup_pools_s": 11, "setup_weights_s": 11,
    "setup_state_init_s": 1, "prefill_attention_kernel_layer_share": 4,
}


@pytest.mark.parametrize("name,cells", sorted(NEW.items()))
def test_the_manifest_lists_the_metric_for_its_cells_and_reads_it(name, cells):
    manifest = Manifest(ROOT)
    listed = [w["name"] for w in manifest.bench["workloads"]
              if any(m["name"] == name
                     for m in manifest.metrics(w["name"], "per_layer"))]
    assert len(listed) == cells
    if cells == 11:
        assert "bert-base-sst2.b256" not in listed
    if cells == 1:
        assert listed == ["bert-base-sst2.b256"]
    (m,) = [m for m in manifest.metrics(listed[0], "per_layer")
            if m["name"] == name]
    reader = importlib.import_module(f"perfbench.readers.{m['reader']}")
    value = reader.read(Ctx(hand_made()), **m["args"])
    assert value is not None and value >= 0
    json.dumps({"value": float(value), "unit": m["unit"]})
    if name.startswith("setup_"):
        assert (m["layer"], m["moves"], m["better"]) == (
            "entry points", "setup_s", "lower")
    else:
        share = next(x for x in manifest.bench["per_layer"]
                     if x["name"] == "prefill_attention_in_kernel_share")
        assert m["workloads"] == share["workloads"]
