"""The family of a decoder with a two-group page cache (full-context
and window layers) and routed experts, end to end at tiny widths on
the CPU: build, warm-up, window, the check against
``perfbench/reference/window_moe.py`` and every reader of the cell,
through ``run_cell`` as ``test_rehearsal_mla_moe.py`` runs its family
(a directory of its own, ``rehearsal_window_moe/``, because a PR adds
files to the benchmark and edits none). Answers outrun the window of 16
several times, so the rings wrap inside the rehearsal. What comes out
names the CPU as its device and carries no share of a chip's peak.
Then the new readers on a trace made by hand, and the byte and
operation counts by hand."""

import json
import os
import time
import types

import pytest

from perfbench import flops_window_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.manifest import Manifest
from perfbench.readers import (
    _program_trace as pt,
    mixed_attention_decode_roofline,
    mixed_kv_share,
    moe_prefill_experts_roofline,
    window_moe_decode_step_roofline,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_window_moe")
CELL = "laguna-xs2-l5.longctx-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "laguna-xs2-l5.json")) as f:
    CONFIG = json.load(f)
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    manifest, out = rehearse(seed=2**31 + 30)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    peaks = {n for n in want if n.endswith("_roofline")}
    assert len(peaks) == 4
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span and counter metrics are there.
    assert set(out["metrics"]) <= want - peaks
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "mixed_kv_live_over_uniform", "window_ring_pages_share",
            "backlog_decode_kv_in_place_share", "compile_s",
            } <= set(out["metrics"])
    live = out["metrics"]["mixed_kv_live_over_uniform"]["value"]
    # 2 of 5 layers read everything, 3 at most a window of 16.
    assert 40.0 < live < 100.0
    # Rings of 5 pages in 3 layers against up to 24 pages in 2.
    assert 0.0 < out["metrics"]["window_ring_pages_share"]["value"] < 100.0
    # Every CPU run gathers.
    assert out["metrics"]["backlog_decode_kv_in_place_share"]["value"] == 0.0


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from tpudl.serve import engine

    sound = engine._select_greedy
    monkeypatch.setattr(
        engine, "_select_greedy",
        lambda logits: (sound(logits) + 1) % logits.shape[-1],
    )
    _, out = rehearse()
    assert out["correct"] is False


def test_bytes_and_operations_by_hand():
    """ISSUE 30's arithmetic at the published widths."""
    cfg = CONFIG
    assert fl.layer_counts(cfg) == (2, 3)
    full = 2048 * 6144 + 2 * 2048 * 1024 + 6144 * 2048 + 2048 * 48
    window = 2048 * 8192 + 2 * 2048 * 1024 + 8192 * 2048 + 2048 * 64
    assert fl.attention_params(cfg, 0) == full == 29_458_432
    assert fl.attention_params(cfg, 1) == window == 37_879_808
    assert fl.expert_params(cfg) == 3 * 2048 * 512 == 3_145_728
    dense = full + 3 * 2048 * 8192
    assert fl.layer_params_outside_routed_experts(cfg, 0) == dense
    moe = window + 2048 * 256 + 3_145_728
    assert fl.layer_params_outside_routed_experts(cfg, 2) == moe
    outside = dense + 3 * moe + (full + 2048 * 256 + 3_145_728) + 2048 * 100352
    assert fl.params_outside_routed_experts(cfg) == outside
    # Held: everything outside the experts, the embedding, 4 x 256 experts.
    held = outside + 2048 * 100352 + 4 * 256 * 3_145_728
    assert 7.73e9 < 2 * held < 7.75e9
    assert fl.kv_bytes_per_position_a_layer(cfg) == 4096
    # 64 sequences at a mean live context of 2,300: full layers read it
    # all, window layers 512 each.
    live_full, live_window = 64 * 2300, 64 * 512
    kv = 4096 * (2 * live_full + 3 * live_window)
    assert fl.live_kv_bytes(cfg, live_full, live_window) == kv
    assert 1.60e9 < kv < 1.62e9
    assert fl.uniform_kv_bytes(cfg, live_full) == 4096 * 5 * live_full
    assert 3.0e9 < fl.uniform_kv_bytes(cfg, live_full) < 3.03e9
    # 221 experts a layer got a token: the issue's 8.05 GB a step.
    step = fl.decode_step_bytes(cfg, live_full, live_window, 4 * 221)
    assert step == 2 * outside + 2 * 884 * 3_145_728 + kv
    assert 8.0e9 < step < 8.1e9
    assert fl.routed_experts_flops(1000, cfg) == 2.0 * 1000 * 3_145_728
    assert fl.attention_flops(cfg, 10, 4) == 2.0 * 2 * 128 * (
        2 * 48 * 10 + 3 * 64 * 4)
    # The two dispatch forms of one layer at a 4,096-row prefill: 6.6
    # TFLOP dense, 1/32 of it sorted.
    assert fl.dense_dispatch_flops(4096, cfg) == pytest.approx(6.6e12, rel=0.01)
    assert fl.sorted_dispatch_flops(4096, cfg) == (
        fl.dense_dispatch_flops(4096, cfg) / 32
    )
    assert fl.sorted_dispatch_bytes(32768, 256, cfg) == (
        2 * 256 * 3_145_728 + 32768 * 2048 * 6
    )
    assert fl.least_seconds(819e9, 1.0, PEAK) == pytest.approx(1.0)
    assert fl.least_seconds(1.0, 197e12, PEAK) == pytest.approx(1.0)


# -- the new readers on a trace made by hand ---------------------------------


def _trace():
    """Two decode steps of 20 ms, each holding 4 ms of
    ``paged_attention`` (2 in a full layer, 2 in a window layer), 10 ms
    of ``experts`` and 4 ms with no scope in the decode program; a
    prefill of 30 ms between them with 12 ms of ``experts``."""
    ops, annotations = [], []
    for i, start in enumerate((0.0, 60 * MS)):
        annotations.append(["tpudl.decode_step", start, 20 * MS, 10 + i])
        at = start + MS
        for name, dur, scope in (
            ("full", 2, "jit(tpudl_decode)/model/layer_0/attention/"
                        "full_attention/jit(_fused)/paged_attention/call"),
            ("ring", 2, "jit(tpudl_decode)/model/layer_1/attention/"
                        "window_attention/jit(_fused)/paged_attention/call"),
            ("experts", 10, "jit(tpudl_decode)/model/layer_1/mlp/moe/experts/dot"),
            ("rest", 4, ""),
        ):
            ops.append([name, at, dur * MS, "jit_tpudl_decode", scope])
            at += dur * MS
    annotations.append(["tpudl.prefill", 25 * MS, 30 * MS, 20])
    ops.append(["experts", 27 * MS, 12 * MS, "jit_tpudl_prefill",
                "jit(tpudl_prefill)/model/layer_1/mlp/moe/experts/ragged"])
    ops.append(["attn", 40 * MS, 8 * MS, "jit_tpudl_prefill",
                "jit(tpudl_prefill)/model/layer_1/attention/window_attention/dot"])
    # The traced window reaches past the spans on both sides.
    ops.append(["select", -5 * MS, MS, "jit_tpudl_select", ""])
    ops.append(["select", 85 * MS, MS, "jit_tpudl_select", ""])
    ops.sort(key=lambda o: o[1])
    return {"annotations": annotations, "modules": [], "ops": ops}


LIVE_FULL, LIVE_WINDOW = 147_200, 32_768


def _ctx(platform="tpu", window_group=True):
    extra = {"moe_experts_touched": 884, "moe_assignments": 512,
             "tokens_live": LIVE_FULL, "pages_reserved": 64 * 300,
             "busy": 64, "kv_in_place": 1}
    if window_group:
        extra.update(tokens_live_window=LIVE_WINDOW,
                     pages_reserved_window=64 * 33)
    spans = [{"kind": "span", "name": "decode_step", "id": 10 + i,
              "ts": 0.06 * i, "dur": 0.02, **extra} for i in range(2)]
    spans.append({"kind": "span", "name": "prefill", "id": 20, "ts": 0.025,
                  "dur": 0.03, "moe_experts_touched": 1024,
                  "moe_assignments": 4 * 8 * 2048})
    return types.SimpleNamespace(
        device={"platform": platform, "kind": "TPU v5 lite"},
        config=CONFIG, spans=spans,
        record={"t0_monotonic": 0.0, "window_s": 1.0},
        window_spans=lambda name: [s for s in spans if s["name"] == name],
    )


@pytest.fixture
def traced(monkeypatch):
    trace = _trace()
    monkeypatch.setattr(pt, "of_run", lambda ctx: trace)


def test_each_share_is_its_least_time_over_its_scopes_busy_time(traced):
    ctx = _ctx()
    kv = fl.live_kv_bytes(CONFIG, LIVE_FULL, LIVE_WINDOW)
    assert mixed_attention_decode_roofline.read(ctx) == pytest.approx(
        100 * 2 * (kv / 819e9) / 0.008)
    step = fl.least_seconds(
        fl.decode_step_bytes(CONFIG, LIVE_FULL, LIVE_WINDOW, 884),
        fl.decode_step_flops(CONFIG, 64, LIVE_FULL, LIVE_WINDOW, 512), PEAK)
    assert window_moe_decode_step_roofline.read(ctx) == pytest.approx(
        100 * 2 * step / 0.036)
    # 8.05 GB in 18 ms of busy chip: a little over half the bandwidth.
    assert 50 < window_moe_decode_step_roofline.read(ctx) < 60
    prefill = fl.least_seconds(
        fl.sorted_dispatch_bytes(65_536, 1024, CONFIG),
        fl.routed_experts_flops(65_536, CONFIG), PEAK)
    assert moe_prefill_experts_roofline.read(ctx) == pytest.approx(
        100 * prefill / 0.012)
    assert moe_prefill_experts_roofline.read(ctx) < 100


def test_the_two_group_shares_by_hand():
    ctx = _ctx()
    live = mixed_kv_share.read(ctx, "live_over_uniform")
    assert live == pytest.approx(
        100 * (2 * LIVE_FULL + 3 * LIVE_WINDOW) / (5 * LIVE_FULL))
    assert 53 < live < 54  # ISSUE 30's "about 53 %"
    rings = mixed_kv_share.read(ctx, "ring_pages")
    assert rings == pytest.approx(
        100 * 3 * 64 * 33 / (3 * 64 * 33 + 2 * 64 * 300))
    with pytest.raises(ValueError):
        mixed_kv_share.read(ctx, "other")


@pytest.mark.parametrize(
    "ctx", [_ctx(platform="cpu"), _ctx(window_group=False)],
    ids=["cpu", "a_program_without_the_window_group"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for reader in (mixed_attention_decode_roofline,
                   window_moe_decode_step_roofline):
        assert reader.read(ctx) is None
    if ctx.device["platform"] == "cpu":
        assert moe_prefill_experts_roofline.read(ctx) is None
    else:
        for what in ("live_over_uniform", "ring_pages"):
            assert mixed_kv_share.read(ctx, what) is None
