"""The latent-attention, routed-expert family end to end at tiny widths
on the CPU: build, warm-up, window, the check against
``perfbench/reference/mla_moe.py`` and every reader of the cell, through
``run_cell`` as ``test_rehearsal.py`` runs the other families (its own
directory, ``rehearsal_mla_moe/``, because a PR adds files to the
benchmark and edits none). What comes out names the CPU as its device
and carries no share of a chip's peak."""

import json
import os
import time

import pytest

from perfbench import flops_mla_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.manifest import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_mla_moe")
CELL = "sarvam-105b-l5-e32.longgen-backlog"


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    manifest, out = rehearse(seed=2**31 + 26)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    peaks = {n for n in want if n.endswith("_roofline")}
    assert len(peaks) == 3
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics, the expert load among them, are there.
    assert set(out["metrics"]) <= want - peaks
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "compile_s"} <= set(out["metrics"])
    assert out["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0


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
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "sarvam-105b-l5-e32.json")) as f:
        cfg = json.load(f)
    attention = 4096 * 64 * 192 + 4096 * 576 + 512 * 64 * 256 + 64 * 128 * 4096
    assert fl.attention_params(cfg) == attention == 94_633_984
    assert fl.expert_params(cfg) == 3 * 4096 * 2048 == 25_165_824
    dense = attention + 3 * 4096 * 16384
    moe = attention + 4096 * 128 + 25_165_824
    assert fl.layer_params_outside_routed_experts(cfg, 0) == dense
    assert fl.layer_params_outside_routed_experts(cfg, 3) == moe
    assert fl.cache_bytes_per_position(cfg) == 5 * 1152
    # A step that touches all 4 x 32 held experts: ISSUE 26's 8.53 GB.
    outside = dense + 4 * moe + 4096 * 65536
    want = 2 * (outside + 128 * 25_165_824) + 57_600 * 5760
    assert fl.decode_step_bytes(cfg, 57_600, 128) == want
    assert 8.5e9 < want - 57_600 * 5760 < 8.56e9
    assert fl.routed_experts_flops(1024, cfg) == 2.0 * 1024 * 25_165_824
    assert fl.latent_core_flops(1000, cfg) == 2.0 * 64 * (576 + 512) * 1000 * 5
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert fl.least_seconds(819e9, 1.0, peak) == pytest.approx(1.0)
    assert fl.least_seconds(1.0, 197e12, peak) == pytest.approx(1.0)
