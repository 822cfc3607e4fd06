"""The hyper-connection family end to end at tiny widths on the CPU:
build, warm-up, window, the check against
``perfbench/reference/hyper_mla_moe.py`` and every reader of the cell,
through ``run_cell`` as ``test_rehearsal.py`` runs the other families
(its own directory, ``rehearsal_hyper_mla_moe/``, because a PR adds
files to the benchmark and edits none). What comes out names the CPU as
its device and carries no share of a chip's peak.

The rehearsal is float32, so a sound program's margins read 0 and the
check is held to what it has to tell apart: the int8 control, and four
programs that are each wrong in ONE part of what ISSUE 38 adds.

Then ``perfbench/flops_hyper_mla_moe.py`` against counts made by hand
(ISSUE 38 section 3's), its roofline readers on a trace made by hand,
and the cell's traffic.
"""

import json
import os
import time
import types

import pytest

from perfbench import flops_hyper_mla_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.families import hyper_mla_moe_serve as family
from perfbench.manifest import Manifest
from perfbench.readers import _program_trace as pt
from perfbench.readers import (
    device_share,
    hyper_moe_roofline,
    hyper_stream_roofline,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_hyper_mla_moe")
CELL = "xing4-29b-a4b-l6.longdoc-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "xing4-29b-a4b-l6.json")) as f:
    CONFIG = json.load(f)
NEW = {"hyper_device_share", "hyper_stream_roofline",
       "latent_prefill_attention_device_share",
       "hyper_moe_prefill_roofline", "hyper_moe_decode_step_roofline"}


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
    manifest, out = rehearse(seed=2**31 + 38)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert NEW <= want
    peaks = {n for n in want if n.endswith("_roofline")}
    assert peaks == {"hyper_stream_roofline", "hyper_moe_prefill_roofline",
                     "hyper_moe_decode_step_roofline"}
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics are there, the latent kernel is not
    # taken on a CPU.
    assert set(out["metrics"]) <= want - peaks
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "backlog_decode_ahead_share", "backlog_prefill_live_rows_share",
            "latent_decode_kv_in_place_share", "compile_s"} <= set(
                out["metrics"])
    assert out["metrics"]["latent_decode_kv_in_place_share"]["value"] == 0


def test_the_control_is_not_correct():
    sound = readings()
    control = readings("control", seconds=8.0)
    assert all(v <= lim for v, lim in sound.values()), sound
    # At the published size the SHARE of tokens the reference ranks
    # second is what tells the control (the mean gap swings with the
    # seed's weights, PERF.md section 2); here both do.
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = control[name]
        assert value > limit, control
    assert control["wrong_token_count"] == (0, 0)


def _a_single_sinkhorn_iteration(monkeypatch):
    """One iteration in place of ``hc_sinkhorn_iters``: rows that sum to
    1 over columns that do not. (The ORDER inside an iteration is no
    such fault at this size: twenty iterations approach the one
    doubly-stochastic matrix either way, the two orders part by a few
    thousandths an entry, and every served token stays the
    reference's best.)"""
    import dataclasses

    sound = family.model_config
    monkeypatch.setattr(
        family, "model_config", lambda *a: dataclasses.replace(
            sound(*a), hyper_sinkhorn_iters=1))


def _h_res_transposed(monkeypatch):
    import jax.numpy as jnp

    import tpudl.models.hyper as hyper

    sound = hyper.mix_out
    monkeypatch.setattr(
        hyper, "mix_out", lambda stream, h_res, h_post, y: sound(
            stream, jnp.swapaxes(h_res, 0, 1), h_post, y))


def _h_post_not_doubled(monkeypatch):
    import tpudl.models.hyper as hyper

    sound = hyper.mix_out
    monkeypatch.setattr(
        hyper, "mix_out", lambda stream, h_res, h_post, y: sound(
            stream, h_res, h_post / 2.0, y))


def _embedding_in_one_stream(monkeypatch):
    """The embedding put into the first stream alone, zeros in the
    others, in place of the repeat."""
    import jax.numpy as jnp

    import tpudl.models.llama as llama

    monkeypatch.setattr(
        llama, "_enter_stream", lambda cfg, x: jnp.stack(
            [x] + [jnp.zeros_like(x)] * (cfg.hyper_streams - 1), axis=2))


FAULTS = {
    "a_single_sinkhorn_iteration": _a_single_sinkhorn_iteration,
    "h_res_transposed": _h_res_transposed,
    "h_post_not_doubled": _h_post_not_doubled,
    "embedding_in_one_stream": _embedding_in_one_stream,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_program_wrong_in_one_part_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    got = readings()
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = got[name]
        assert value > limit, got
    # Every request still ends with the tokens it asked for: only the
    # comparison with the reference tells.
    assert got["wrong_token_count"] == (0, 0)


# -- operations and bytes by hand ---------------------------------------------


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    """Every number of the catalog's ``config`` under its key, but the
    three in ``reduced``; 64 of 64 experts, the whole vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if "Xing4.0-29B-A4B" in line]
    published = rows[0]["config"]
    assert CONFIG["source"] == rows[0]["source_url"]
    differs = {k for k, v in published.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace",
        "num_nextn_predict_layers"}
    assert CONFIG["published"] == {k: published[k] for k in differs}
    assert (CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (64, 131072)


def test_bytes_and_operations_by_hand():
    cfg = CONFIG
    attention = (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                 + 512 * 32 * 256 + 32 * 128 * 3584)
    assert fl.attention_params(cfg) == attention == 28_409_856
    assert fl.expert_params(cfg) == 3 * 3584 * 1024 == 11_010_048
    assert fl.dense_ffn_params(cfg) == 3 * 3584 * 9216 == 99_090_432
    maps = 2 * (4 * 3584 * 24 + 24 + 3)
    assert fl.hyper_params(cfg) == maps == 688_182
    norms = 2 * 3584 + 768 + 512
    sparse = attention + maps + norms + 3584 * 64 + 64 + 11_010_048
    assert fl.layer_params_outside_routed_experts(cfg, False) == sparse
    assert sparse == 40_345_974  # ISSUE 38: 40.35 M
    dense = attention + maps + norms + 99_090_432
    assert fl.layer_params_outside_routed_experts(cfg, True) == dense
    assert dense == 128_196_918  # 128.20 M
    # ISSUE 38 section 3: 4,792.7 M parameters = 9.59 GB; 6,912 B a
    # position, 64 slots x 4,352 positions = 1.93 GB; 11.5 GB together.
    held = (dense + 5 * (sparse + 64 * 11_010_048)
            + 2 * 131072 * 3584 + 3584)
    assert fl.params_held(cfg) == held == 4_792_669_828
    assert fl.weight_bytes_held(cfg) == 9_595_892_240
    assert fl.cache_bytes_per_position(cfg) == 6 * 1152 == 6912
    sess = cfg["session"]
    cache = sess["num_slots"] * sess["max_seq_len"] * 6912
    assert cache == 1_925_185_536
    assert 11.4e9 < fl.weight_bytes_held(cfg) + cache < 11.6e9
    assert cfg["deployment"]["parameters_held"] == held
    assert cfg["deployment"]["weight_bytes"] == fl.weight_bytes_held(cfg)
    assert cfg["deployment"]["cache_bytes"] == cache
    # The stream: (3 n + 2) d values a row a sublayer, 12 sublayers.
    assert fl.stream_bytes(1, cfg) == 12 * 14 * 3584 * 2 == 1_204_224
    assert fl.stream_flops(1, cfg) == 2.0 * 12 * (
        14336 * 24 + 14336 + 5 * 14336)
    outside = dense + 5 * sparse + 3584 * 131072
    assert fl.decode_step_bytes(cfg, 64, 100_000, 300) == (
        2 * outside + 2 * 300 * 11_010_048 + 100_000 * 6912
        + 64 * 1_204_224)
    body = outside - 3584 * 131072 - 6 * maps
    assert fl.decode_step_flops(cfg, 60, 100_000, 1200) == pytest.approx(
        2.0 * 60 * (body + 3584 * 131072)
        + 6 * 2.0 * 32 * (576 + 512) * 100_000
        + 2.0 * 1200 * 11_010_048 + fl.stream_flops(60, cfg))
    assert fl.prefill_bytes(cfg, 2048, 320) == (
        2 * outside + 2 * 320 * 11_010_048 + 2048 * 1_204_224
        + 2048 * 6912)
    assert fl.prefill_flops(cfg, 2048, 2048 * 20) == pytest.approx(
        2.0 * (2048 * body + 3584 * 131072)
        + 6 * 2.0 * 32 * 320 * 2048 * 2049 / 2
        + 2.0 * 2048 * 20 * 11_010_048 + fl.stream_flops(2048, cfg))


# -- the roofline readers on a trace made by hand ------------------------------

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds
HYPER = "jit(tpudl_{})/model/layer_1/hyper_mlp/hyper/hyper_maps/dot"
CORE = "jit(tpudl_prefill)/model/layer_1/attention/mla_core/dot"


def _trace():
    """A prefill program of 40 ms (2,048 rows) that runs under its span,
    then two decode programs of 16 ms, the first of them ON THE DEVICE
    before the span that lands it opens (a step run ahead), and a
    prefill cut by the trace's start whose span is not in the trace.
    Inside: ``hyper`` 6 ms of the prefill and 0.5 ms of each decode,
    ``mla_core`` 8 ms of the prefill."""
    modules = [["jit_tpudl_prefill", -30 * MS, 40 * MS],
               ["jit_tpudl_prefill", 20 * MS, 40 * MS],
               ["jit_tpudl_decode", 62 * MS, 16 * MS],
               ["jit_tpudl_decode", 79 * MS, 16 * MS]]
    ops = [
        ["cut", 0.0, 10 * MS, "jit_tpudl_prefill", HYPER.format("prefill")],
        ["maps", 20 * MS, 6 * MS, "jit_tpudl_prefill",
         HYPER.format("prefill")],
        ["core", 26 * MS, 8 * MS, "jit_tpudl_prefill", CORE],
        ["rest", 34 * MS, 24 * MS, "jit_tpudl_prefill", ""],
    ]
    for start in (62 * MS, 79 * MS):
        ops += [
            ["maps", start, 0.5 * MS, "jit_tpudl_decode",
             HYPER.format("decode")],
            ["rest", start + 0.5 * MS, 14.5 * MS, "jit_tpudl_decode", ""],
        ]
    annotations = [
        ["tpudl.prefill", 19 * MS, 43 * MS, 1],
        ["tpudl.decode_step", 78.5 * MS, 2 * MS, 10],
        ["tpudl.decode_step", 81 * MS, 14.5 * MS, 11],
    ]
    return {"annotations": annotations, "modules": modules, "ops": ops}


PREFILL = {"rows": 2048, "tokens": 1900, "moe_experts_touched": 320,
           "moe_assignments": 38_000, "hyper_res_offdiag": 0.3}
STEP = {"tokens_live": 150_000, "busy": 64, "moe_experts_touched": 310,
        "moe_assignments": 1280, "hyper_res_offdiag": 0.3, "ahead": 1}


def _ctx(platform="tpu", prefill=PREFILL, step=STEP):
    spans = [{"kind": "span", "name": "prefill", "id": 1, **prefill}] + [
        {"kind": "span", "name": "decode_step", "id": 10 + i, **step}
        for i in range(2)]
    return types.SimpleNamespace(
        device={"platform": platform, "kind": "TPU v5 lite"},
        config=CONFIG, spans=spans,
        tracer=types.SimpleNamespace(done=True),
    )


@pytest.fixture
def traced(monkeypatch):
    trace = _trace()
    monkeypatch.setattr(pt, "of_run", lambda ctx: trace)


def test_each_share_is_its_least_time_over_its_programs_busy_time(traced):
    ctx = _ctx()
    # The stream: 2,048 + 2 x 64 rows over 6 + 2 x 0.5 ms in ``hyper``
    # (the cut prefill's 10 ms belong to no span, and count nowhere).
    stream = fl.stream_bytes(2048 + 128, CONFIG) / 819e9
    assert hyper_stream_roofline.read(ctx) == pytest.approx(
        100 * stream / 7e-3)
    prefill = fl.least_seconds(
        fl.prefill_bytes(CONFIG, 2048, 320),
        fl.prefill_flops(CONFIG, 2048, 2048 * 4 * 5), PEAK)
    assert hyper_moe_roofline.read(ctx, "prefill") == pytest.approx(
        100 * prefill / 38e-3)
    step = fl.least_seconds(
        fl.decode_step_bytes(CONFIG, 64, 150_000, 310),
        fl.decode_step_flops(CONFIG, 64, 150_000, 1280), PEAK)
    # Both decode programs' 15 ms, though one ran before its span.
    assert hyper_moe_roofline.read(ctx, "decode_step") == pytest.approx(
        100 * 2 * step / 30e-3)
    # A share over 100 % is a fault of the counts or of the time.
    for value in (hyper_stream_roofline.read(ctx),
                  hyper_moe_roofline.read(ctx, "prefill"),
                  hyper_moe_roofline.read(ctx, "decode_step")):
        assert 0 < value <= 100
    busy = 10 + 38 + 2 * 15
    assert device_share.read(ctx, scope="hyper") == pytest.approx(
        100 * 17 / busy)
    assert device_share.read(
        ctx, program="prefill", scope="mla_core") == pytest.approx(
            100 * 8 / busy)


@pytest.mark.parametrize("ctx", [
    _ctx("cpu"),
    _ctx(prefill={"rows": 2048}, step={"tokens_live": 1, "busy": 64}),
], ids=["cpu", "a_program_without_the_stream"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    assert hyper_stream_roofline.read(ctx) is None
    for part in ("prefill", "decode_step"):
        assert hyper_moe_roofline.read(ctx, part) is None


def test_the_cells_traffic_is_what_the_issue_names():
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "longdoc-backlog.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sampling"]) == (
        "closed", 128, "greedy")
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 2048, "sigma": 0.5,
        "min": 1024, "max": 4096}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 64, "sigma": 0.6,
        "min": 16, "max": 256}
    sess = CONFIG["session"]
    assert mix["clients"] == 2 * sess["num_slots"] == 128
    assert mix["block"] == sess["num_slots"] == 64
    # A slot holds the longest prompt and the longest answer.
    assert sess["max_seq_len"] == sess["prompt_window"] + 256
    # Over twice what the fastest window finished (PERF.md section 6).
    assert mix["block"] * mix["blocks"] >= 2048
