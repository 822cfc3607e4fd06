"""The shortcut-double-layer family end to end at tiny widths on the
CPU: build, warm-up, window, the check against
``perfbench/reference/shortcut_moe.py`` and every reader of the cell,
through ``run_cell`` as ``test_rehearsal.py`` runs the other families
(its own directory, ``rehearsal_shortcut_moe/``, because a PR adds files
to the benchmark and edits none). What comes out names the CPU as its
device and carries no share of a chip's peak.

The rehearsal is float32 (the program's bfloat16 latent gather does not
run on this CPU backend), so a sound program's margins read 0 and the
check is held to what it has to tell apart: the int8 control, and four
programs that are each wrong in ONE part of what ISSUE 33 adds.

Then ``perfbench/flops_shortcut_moe.py`` against counts made by hand,
and its roofline reader on a trace made by hand.
"""

import dataclasses
import json
import os
import time
import types

import pytest

from perfbench import flops_shortcut_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.families import shortcut_moe_serve as family
from perfbench.manifest import Manifest
from perfbench.readers import _program_trace as pt
from perfbench.readers import shortcut_roofline, span_attr_share

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_shortcut_moe")
CELL = "longcat-flash-l4-e16.reasoning-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "longcat-flash-l4-e16.json")) as f:
    CONFIG = json.load(f)


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def readings(variant="program", seed=7, seconds=3.0):
    """{comparison: (value, limit)} of one short window."""
    cell = Manifest(REHEARSAL).cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    system = family.build(cell["config"], device, seed, variant)
    system.warm_up(cell["traffic"], seconds)
    record = system.run_window(cell["traffic"], seconds)
    system.release()
    return {c["name"]: (c["value"], c["limit"])
            for c in system.check(record)["comparisons"]}


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    manifest, out = rehearse(seed=2**31 + 33)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    peaks = {n for n in want if n.endswith("_roofline")}
    assert peaks == {"shortcut_experts_roofline",
                     "shortcut_mla_decode_roofline",
                     "shortcut_decode_step_roofline"}
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics, the choices that cost nothing among
    # them, are there.
    assert set(out["metrics"]) <= want - peaks
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "zero_expert_choice_share", "compile_s"} <= set(out["metrics"])
    # 8 of the 24 router outputs are identity experts.
    assert 20 < out["metrics"]["zero_expert_choice_share"]["value"] < 45


def test_the_control_is_not_correct():
    sound = readings()
    # About 0.5 % of the control's tokens are the reference's second
    # choice: a window long enough to hold the 64 requests compared.
    control = readings("control", seconds=8.0)
    assert all(v <= lim for v, lim in sound.values()), sound
    value, limit = control["mean_logit_margin"]
    assert value > limit, control
    assert control["wrong_token_count"] == (0, 0)


def _early_shortcut(monkeypatch):
    """The expert branch joins the residual one sublayer early: the
    block is handed zeros for it, and the first dense FFN adds it."""
    import jax.numpy as jnp

    import tpudl.models.llama as llama
    import tpudl.ops.moe as moe

    experts, ffn, branch = moe.DroplessMoE, llama._DenseFFN, []

    def early_experts(**kw):
        def call(x, real):
            branch.append(experts(**kw)(x, real))
            return jnp.zeros_like(branch[-1])
        return call

    def early_ffn(cfg, name):
        def call(x):
            out = ffn(cfg, name=name)(x)
            return out + branch.pop() if name == "mlp_0" else out
        return call

    monkeypatch.setattr(moe, "DroplessMoE", early_experts)
    monkeypatch.setattr(llama, "_DenseFFN", early_ffn)


FAULTS = {
    # Router ids past the routed experts held elsewhere too: their term
    # is left out, as another chip's would be. The tree is the same.
    "identity_term_left_out": lambda c: dataclasses.replace(
        c, num_experts=c.num_experts + c.zero_experts, zero_experts=0),
    "scores_renormalised": lambda c: dataclasses.replace(
        c, router_renormalize=True),
    "s_kv_dropped": lambda c: dataclasses.replace(c, mla_scale_kv=1.0),
    "shortcut_added_one_sublayer_early": lambda c: c,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_program_wrong_in_one_part_is_not_correct(fault, monkeypatch):
    sound = family.model_config
    monkeypatch.setattr(
        family, "model_config", lambda *a: FAULTS[fault](sound(*a)))
    if fault == "shortcut_added_one_sublayer_early":
        _early_shortcut(monkeypatch)
    got = readings()
    value, limit = got["mean_logit_margin"]
    assert value > limit, got
    # Every request still ends with the tokens it asked for: only the
    # comparison with the reference tells.
    assert got["wrong_token_count"] == (0, 0)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    from tpudl.serve import engine

    sound = engine._select_greedy
    monkeypatch.setattr(
        engine, "_select_greedy",
        lambda logits: (sound(logits) + 1) % logits.shape[-1],
    )
    _, out = rehearse()
    assert out["correct"] is False


# -- operations and bytes by hand ---------------------------------------------


def test_bytes_and_operations_by_hand():
    cfg = CONFIG
    attention = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576
                 + 512 * 64 * 256 + 64 * 128 * 6144)
    assert fl.attention_params(cfg) == attention == 90_570_752
    assert fl.dense_ffn_params(cfg) == 3 * 6144 * 12288 == 226_492_416
    assert fl.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    outside = 2 * (attention + 226_492_416) + 6144 * 768
    assert fl.layer_params_outside_routed_experts(cfg) == outside
    assert outside == 638_844_928
    # ISSUE 33: 5,172.6 M parameters (10.35 GB) and 9,216 B a position.
    held = 4 * (outside + 16 * 37_748_736) + 2 * 16384 * 6144
    assert fl.params_held(cfg) == held == 5_172_625_408
    assert fl.cache_bytes_per_position(cfg) == 4 * 2 * 1152 == 9216
    assert 192 * 1536 * 9216 == 2_717_908_992
    # A step that touches all 4 x 16 held experts at 100,000 live
    # positions a pool: everything but the gathered embedding.
    want = 2 * (held - 16384 * 6144) + 100_000 * 9216
    assert fl.decode_step_bytes(cfg, 100_000, 64) == want
    assert fl.routed_experts_bytes(3, cfg) == 2 * 3 * 37_748_736
    assert fl.routed_experts_flops(1024, cfg) == 2.0 * 1024 * 37_748_736
    kv_b = 512 * 64 * 256
    assert fl.latent_core_bytes(1000, cfg) == 1000 * 9216 + 8 * 2 * kv_b
    core = 8 * (2.0 * 192 * kv_b + 2.0 * 64 * (576 + 512) * 1000)
    assert fl.latent_core_flops(192, 1000, cfg) == core
    dense = 2.0 * 192 * (4 * outside + 6144 * 16384 - 8 * kv_b)
    assert fl.decode_step_flops(cfg, 192, 1000, 500) == pytest.approx(
        dense + 2.0 * 500 * 37_748_736 + core)


# -- the roofline reader on a trace made by hand ------------------------------

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds


def _trace():
    """Two decode steps of 30 ms, each holding 5 ms of ``experts``, 4 ms
    of ``mla_core``, 12 ms of ``dense_ffn`` and 4 ms with no scope in
    the decode program; a prefill's experts between them count for
    nothing."""
    ops, annotations = [], []
    layer = "jit(tpudl_decode)/model/layer_1"
    for i, start in enumerate((0.0, 50 * MS)):
        annotations.append(["tpudl.decode_step", start, 30 * MS, 10 + i])
        at = start + MS
        for name, dur, scope in (
            ("experts", 5, f"{layer}/mlp/moe/moe/experts/dot"),
            ("core", 4, f"{layer}/attention/attention_1/mla_core/dot"),
            ("ffn", 12, f"{layer}/mlp/dense_ffn/mlp_0/up_proj/dot"),
            ("rest", 4, ""),
        ):
            ops.append([name, at, dur * MS, "jit_tpudl_decode", scope])
            at += dur * MS
    ops.append(["experts", 35 * MS, 5 * MS, "jit_tpudl_prefill",
                "jit(tpudl_prefill)/model/layer_1/mlp/moe/moe/experts/dot"])
    ops.append(["select", -5 * MS, MS, "jit_tpudl_select", ""])
    ops.append(["select", 85 * MS, MS, "jit_tpudl_select", ""])
    ops.sort(key=lambda o: o[1])
    return {"annotations": annotations, "modules": [], "ops": ops}


STEP = {"moe_experts_touched": 60, "moe_assignments": 600,
        "tokens_live": 100_000, "busy": 192,
        "moe_zero_assignments": 3000, "moe_real_assignments": 6216}


def _ctx(platform="tpu", attrs=STEP):
    spans = [{"kind": "span", "name": "decode_step", "id": 10 + i,
              "ts": 0.05 * i, "dur": 0.03, **attrs} for i in range(2)]
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
    experts = fl.least_seconds(
        fl.routed_experts_bytes(60, CONFIG),
        fl.routed_experts_flops(600, CONFIG), PEAK)
    assert shortcut_roofline.read(ctx, "experts") == pytest.approx(
        100 * experts / 5e-3)
    core = fl.least_seconds(
        fl.latent_core_bytes(100_000, CONFIG),
        fl.latent_core_flops(192, 100_000, CONFIG), PEAK)
    assert shortcut_roofline.read(ctx, "mla") == pytest.approx(
        100 * core / 4e-3)
    step = fl.least_seconds(
        fl.decode_step_bytes(CONFIG, 100_000, 60),
        fl.decode_step_flops(CONFIG, 192, 100_000, 600), PEAK)
    assert shortcut_roofline.read(ctx, "step") == pytest.approx(
        100 * step / 25e-3)
    # Every share stays a share: the step's least time is under the
    # 25 ms the device was busy in it.
    assert 40 < shortcut_roofline.read(ctx, "step") < 100
    assert span_attr_share.read(
        ctx, "decode_step", "moe_zero_assignments",
        ["moe_zero_assignments", "moe_real_assignments"],
    ) == pytest.approx(100 * 3000 / 9216)


@pytest.mark.parametrize("ctx", [
    _ctx("cpu"), _ctx(attrs={"busy": 192}),
], ids=["cpu", "a_program_without_the_counters"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for part in shortcut_roofline.PARTS:
        assert shortcut_roofline.read(ctx, part) is None
    if ctx.device["platform"] != "cpu":
        assert span_attr_share.read(
            ctx, "decode_step", "moe_zero_assignments",
            ["moe_zero_assignments", "moe_real_assignments"]) is None


def test_the_cells_traffic_is_what_the_issue_names():
    """ISSUE 33 fixes the mix but for the block. The block divides the
    slots, and is no smaller than 12: with 8 distinct lengths and fewer
    the requests finish in waves and ``serve_tokens_per_s`` is a
    staircase of the machine's speed (PERF.md section 6, PR 33)."""
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "reasoning-backlog.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sampling"]) == (
        "closed", 384, "greedy")
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.7,
        "min": 32, "max": 512}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5,
        "min": 128, "max": 1024}
    slots = CONFIG["session"]["num_slots"]
    assert mix["clients"] == 2 * slots
    assert mix["block"] >= 12 and slots % mix["block"] == 0
    # A window finishes ~350 requests beside the 384 outstanding.
    assert mix["block"] * mix["blocks"] >= 4 * mix["clients"]
