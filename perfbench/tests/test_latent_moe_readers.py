"""The roofline readers of the latent-attention, routed-expert cell on a
trace made by hand: the arithmetic, and that a program which writes no
such counters (the parent of the PR that brought them) or a CPU run
reads as nothing."""

import json
import os
import types

import pytest

from perfbench import flops_mla_moe as fl
from perfbench.readers import (
    _program_trace as pt,
    latent_moe_decode_step_roofline,
    mla_decode_roofline,
    moe_experts_roofline,
    span_attr_mean,
)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "sarvam-105b-l5-e32.json")) as f:
    CONFIG = json.load(f)
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds


def _trace():
    """Two decode steps of 20 ms, each holding 10 ms of ``experts``,
    2 ms of ``kv_gather`` and 2 ms of ``mla_core`` in the decode program
    and 4 ms with no scope; a prefill between them, and its experts,
    count for nothing."""
    ops, annotations = [], []
    for i, start in enumerate((0.0, 40 * MS)):
        annotations.append(["tpudl.decode_step", start, 20 * MS, 10 + i])
        at = start + MS
        for name, dur, scope in (
            ("experts", 10, "jit(tpudl_decode)/model/layer_1/mlp/moe/experts/dot"),
            ("gather", 2, "jit(tpudl_decode)/model/layer_1/attention/kv_gather/gather"),
            ("core", 2, "jit(tpudl_decode)/model/layer_1/attention/mla_core/dot"),
            ("rest", 4, ""),
        ):
            ops.append([name, at, dur * MS, "jit_tpudl_decode", scope])
            at += dur * MS
    ops.append(["experts", 25 * MS, 5 * MS, "jit_tpudl_prefill",
                "jit(tpudl_prefill)/model/layer_1/mlp/moe/experts/dot"])
    # The traced window reaches past the steps on both sides.
    ops.append(["select", -5 * MS, MS, "jit_tpudl_select", ""])
    ops.append(["select", 65 * MS, MS, "jit_tpudl_select", ""])
    ops.sort(key=lambda o: o[1])
    return {"annotations": annotations, "modules": [], "ops": ops}


def _ctx(platform="tpu", attrs=True):
    extra = {"moe_experts_touched": 128, "moe_assignments": 1000,
             "tokens_live": 50_000, "busy": 128} if attrs else {"busy": 128}
    spans = [{"kind": "span", "name": "decode_step", "id": 10 + i,
              "ts": 0.04 * i, "dur": 0.02,
              "moe_load_max_over_mean": 2.0 + i, **extra}
             for i in range(2)]
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
        fl.routed_experts_bytes(128, CONFIG),
        fl.routed_experts_flops(1000, CONFIG), PEAK)
    assert moe_experts_roofline.read(ctx) == pytest.approx(
        100 * 2 * experts / 0.020)
    core = fl.least_seconds(fl.latent_core_bytes(50_000, CONFIG),
                            fl.latent_core_flops(50_000, CONFIG), PEAK)
    assert mla_decode_roofline.read(ctx) == pytest.approx(
        100 * 2 * core / 0.008)
    step = fl.least_seconds(
        fl.decode_step_bytes(CONFIG, 50_000, 128),
        fl.decode_step_flops(CONFIG, 128, 50_000, 1000), PEAK)
    assert latent_moe_decode_step_roofline.read(ctx) == pytest.approx(
        100 * 2 * step / 0.036)
    # 6.44 GB of experts in 10 ms is the published bandwidth, near enough.
    assert 75 < moe_experts_roofline.read(ctx) < 85
    assert span_attr_mean.read(
        ctx, "decode_step", "moe_load_max_over_mean") == 2.5


@pytest.mark.parametrize("ctx", [_ctx(platform="cpu"), _ctx(attrs=False)],
                         ids=["cpu", "no_counters"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for reader in (moe_experts_roofline, mla_decode_roofline,
                   latent_moe_decode_step_roofline):
        assert reader.read(ctx) is None


def test_spans_without_the_attribute_read_as_nothing():
    assert span_attr_mean.read(_ctx(), "decode_step", "absent") is None
    assert span_attr_mean.read(_ctx(), "prefill", "busy") is None
