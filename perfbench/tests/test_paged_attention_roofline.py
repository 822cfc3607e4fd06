"""The roofline reader of the paged-attention kernel on a trace made by
hand: the arithmetic, and that a program with no such scope (the parent
of the PR that brought the kernel), a CPU run or an untraced run reads
as nothing."""

import json
import os
import types

import pytest

from perfbench.flops import kv_bytes_per_position
from perfbench.readers import (
    _program_trace as pt,
    device_share,
    paged_attention_roofline,
    span_attr_mean,
)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "mistral-7b-v0.3-l16.json")) as f:
    CONFIG = json.load(f)
MS = 1e6  # nanoseconds
KERNEL = "jit(tpudl_decode)/model/layer_3/attention/paged_attention/pallas_call"
GATHER = "jit(tpudl_decode)/model/layer_3/attention/kv_gather/gather"


def _trace(scope=KERNEL):
    """Two decode steps of 20 ms, each holding 1 ms of the kernel in
    two pieces and 15 ms of the rest; the kernel's scope in another
    program, and outside the steps, counts for nothing."""
    ops, annotations = [], []
    for i, start in enumerate((0.0, 40 * MS)):
        annotations.append(["tpudl.decode_step", start, 20 * MS, 10 + i])
        ops += [
            ["kernel", start + MS, 0.5 * MS, "jit_tpudl_decode", scope],
            ["mlp", start + 2 * MS, 15 * MS, "jit_tpudl_decode",
             "jit(tpudl_decode)/model/layer_3/mlp/dot"],
            ["kernel", start + 18 * MS, 0.5 * MS, "jit_tpudl_decode", scope],
        ]
    ops.append(["kernel", 25 * MS, 3 * MS, "jit_tpudl_verify", scope])
    ops.append(["kernel", 30 * MS, 2 * MS, "jit_tpudl_decode", scope])
    # The traced window reaches past the steps on both sides.
    ops.append(["select", -5 * MS, MS, "jit_tpudl_select", ""])
    ops.append(["select", 65 * MS, MS, "jit_tpudl_select", ""])
    ops.sort(key=lambda o: o[1])
    return {"annotations": annotations, "modules": [], "ops": ops}


def _ctx(platform="tpu", live=(6_000, 7_000)):
    spans = [{"kind": "span", "name": "decode_step", "id": 10 + i,
              "ts": 0.04 * i, "dur": 0.02, "busy": 23, "kv_in_place": 1,
              "pages_live": 400, "tokens_live": n}
             for i, n in enumerate(live)]
    return types.SimpleNamespace(
        device={"platform": platform, "kind": "TPU v5 lite"},
        config=CONFIG, spans=spans,
        record={"t0_monotonic": 0.0, "window_s": 1.0},
        window_spans=lambda name: [s for s in spans if s["name"] == name],
    )


def _traced(monkeypatch, trace):
    monkeypatch.setattr(pt, "of_run", lambda ctx: trace)


def test_share_is_live_bytes_at_bandwidth_over_the_kernels_time(monkeypatch):
    _traced(monkeypatch, _trace())
    # 16 layers x (k + v) x 8 heads x 128 x 2 bytes a position.
    assert kv_bytes_per_position(CONFIG) == 65_536
    least = 13_000 * 65_536 / 819e9
    assert paged_attention_roofline.read(_ctx()) == pytest.approx(
        100 * least / 0.002)
    # About a millisecond of bytes in the two: half the bandwidth.
    assert 50 < paged_attention_roofline.read(_ctx()) < 55
    # The kernel's pieces of every decode program over all busy time.
    assert device_share.read(_ctx(), scope="paged_attention") == pytest.approx(
        100 * (4 * 0.5 + 3 + 2) / (4 * 0.5 + 2 * 15 + 3 + 2 + 2 * 1))
    assert span_attr_mean.read(_ctx(), "decode_step", "kv_in_place") == 1


@pytest.mark.parametrize("case", ["gathers", "cpu", "untraced", "no_counter"])
def test_nothing_to_read_reads_as_nothing(monkeypatch, case):
    _traced(monkeypatch, None if case == "untraced" else
            _trace(GATHER if case == "gathers" else KERNEL))
    ctx = _ctx(platform="cpu" if case == "cpu" else "tpu")
    if case == "no_counter":
        for s in ctx.spans:
            del s["tokens_live"], s["kv_in_place"]
        assert span_attr_mean.read(ctx, "decode_step", "kv_in_place") is None
    assert paged_attention_roofline.read(ctx) is None
