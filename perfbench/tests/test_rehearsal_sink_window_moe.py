"""The two-kinds-of-layer family (KV heads by layer kind, keys wider
than values, a sink in the sliding layers' softmax) end to end at tiny
widths on the CPU: build, warm-up, window, the check against
``perfbench/reference/sink_window_moe.py`` and every reader of the
cell, through ``run_cell`` as ``test_rehearsal.py`` runs the other
families (its own directory, ``rehearsal_sink_window_moe/``, because a
PR adds files to the benchmark and edits none). What comes out names
the CPU as its device and carries no share of a chip's peak.

The rehearsal is float32, so a sound program's margins read 0 and the
check is held to what it has to tell apart: the int8 control, and
seven programs that are each wrong in ONE part of what ISSUE 47 adds.

Then the published configuration against the catalog, the roofline
reader on a trace made by hand, the span's ``kv_bytes_live`` against
the reader's own count, and the cell's traffic.
(``test_flops_sink_window_moe.py`` holds the operations and bytes.)
"""

import dataclasses
import json
import os
import time
import types

import pytest

from perfbench import flops_sink_window_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.families import sink_window_moe_serve as family
from perfbench.manifest import Manifest
from perfbench.readers import _program_trace as pt
from perfbench.readers import device_share, sink_kv_share, sink_moe_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_sink_window_moe")
CELL = "mimo-v2-flash-l7-e16.mixedctx-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "mimo-v2-flash-l7-e16.json")) as f:
    CONFIG = json.load(f)
ROOFLINES = {"sink_paged_attention_roofline", "sink_moe_decode_step_roofline",
             "sink_moe_prefill_roofline"}
NEW = ROOFLINES | {"sink_kv_live_over_uniform", "sink_ring_bytes_share"}


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def readings(variant="program", seed=7, seconds=3.0, wrong=None):
    """``{comparison: (value, limit)}`` of one short window. ``wrong``:
    keys of the configuration that the PROGRAM is built with and the
    reference is not."""
    cell = Manifest(REHEARSAL).cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    system = family.build(dict(cell["config"], **(wrong or {})), device,
                          seed, variant)
    system.warm_up(cell["traffic"], seconds)
    record = system.run_window(cell["traffic"], seconds)
    system.release()
    system.config = cell["config"]
    check = system.check(record)
    return {c["name"]: (c["value"], c["limit"])
            for c in check["comparisons"]}


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    manifest, out = rehearse(seed=2**31 + 47)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak(tmp_path):
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert NEW <= want
    assert {n for n in want if n.endswith("_roofline")} == ROOFLINES
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics are there, and on the CPU a decode step
    # gathers.
    assert set(out["metrics"]) <= want - ROOFLINES
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "backlog_decode_ahead_share", "backlog_prefill_live_rows_share",
            "backlog_decode_kv_in_place_share",
            "prefill_attention_in_kernel_share", "sink_kv_live_over_uniform",
            "sink_ring_bytes_share", "compile_s"} <= set(out["metrics"])
    assert out["metrics"]["backlog_decode_kv_in_place_share"]["value"] == 0
    # Float32 here: the program's rows are 4 B a value where the counts
    # of ``flops_sink_window_moe.py`` are of the 2 B the cell serves in,
    # so the live share reads double. Sequences of 10-80 positions under
    # a window of 16.
    assert 60 < out["metrics"]["sink_kv_live_over_uniform"]["value"] < 180
    # Rings of 3 pages x 5 layers x 3,072 B against tables of at most 10
    # pages x 2 layers x 1,536 B.
    assert 50 < out["metrics"]["sink_ring_bytes_share"]["value"] < 90
    # The span's own count of bytes is the reader's, by group.
    spans = [json.loads(line) for line in open(
        os.path.join(run.WORK_DIR, "spans.jsonl"))]
    steps = [s for s in spans if s.get("name") == "decode_step"
             and s.get("kind") == "span"]
    cfg = manifest.cell(CELL)["config"]
    assert steps
    for s in steps:
        assert s["kv_bytes_live"] == 2 * fl.live_kv_bytes(
            cfg, s["tokens_live"], s["tokens_live_window"])


def test_the_sound_program_agrees_and_the_control_is_not_correct():
    sound = readings()
    assert all(v <= lim for v, lim in sound.values()), sound
    control = readings("control", seconds=8.0)
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = control[name]
        assert value > limit, control
    assert control["wrong_token_count"] == (0, 0)


FAULTS = {
    "the_sink_left_out": dict(add_swa_attention_sink_bias=False),
    "the_sink_added_to_the_full_layers": dict(
        add_full_attention_sink_bias=True),
    "the_value_scale_left_out": dict(attention_value_scale=1.0),
    "the_kv_heads_of_the_two_kinds_swapped": dict(
        num_key_value_heads=4, swa_num_key_value_heads=2),
    "theta_swapped": dict(rope_theta=10000, swa_rope_theta=5000000),
    "a_window_of_one_more": dict(sliding_window=17),
    "the_sliding_layers_rotated_over_the_whole_head": None,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_program_wrong_in_one_part_is_not_correct(fault, monkeypatch):
    wrong = FAULTS[fault]
    if wrong is None:
        sound = family.model_config
        monkeypatch.setattr(
            family, "model_config",
            lambda *a: dataclasses.replace(
                sound(*a), sliding_partial_rotary_factor=1.0))
    got = readings(wrong=wrong)
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = got[name]
        assert value > limit, got
    # Every request still ends with the tokens it asked for: only the
    # comparison with the reference tells.
    assert got["wrong_token_count"] == (0, 0)


# -- the configuration --------------------------------------------------------


def test_the_configuration_is_the_published_one_cut_as_the_file_says():
    """Every key of the catalog's ``config`` under its name and value,
    but the three in ``reduced``: layers 0-6, 16 of 256 experts, an
    eighth of the vocabulary; the per-layer lists whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f
                if '"name": "MiMo-V2-Flash"' in line]
    published = rows[0]["config"]
    assert CONFIG["source"] == rows[0]["source_url"]
    differs = {k for k, v in published.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert CONFIG["published"] == {k: published[k] for k in differs}
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (7, 16, 19072)
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert CONFIG["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    deployment = CONFIG["deployment"]
    assert deployment["chips_sharing_a_layer"] == 16
    assert deployment["router_experts"] == published["n_routed_experts"]
    assert CONFIG["session"] == {
        "num_slots": 24, "max_seq_len": 16896, "prompt_window": 16384,
        "paged": True, "page_size": 16, "queue_capacity": 4096}
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mimo-v2-flash-l7-e16")
    assert set(entry["reduced"]) == differs
    assert entry["source"] == CONFIG["source"]


# -- the roofline reader on a trace made by hand -------------------------------

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds
SCOPE = "jit(tpudl_{})/model/layer_{}/attention/{}/dot"


def _trace():
    """A seat's 5 ms (the trace's first operation), a prefill program of
    600 ms that runs under its span, then two decode programs of 12 ms,
    the first of them ON THE DEVICE before the span that lands it opens
    (a step run ahead). Inside each decode: ``paged_attention`` 1 ms
    under ``full_attention`` and 0.5 ms under ``window_attention``."""
    modules = [["jit_tpudl_prefill", 20 * MS, 600 * MS],
               ["jit_tpudl_decode", 630 * MS, 12 * MS],
               ["jit_tpudl_decode", 643 * MS, 12 * MS]]
    ops = [
        ["seat", 0.0, 5 * MS, "jit_tpudl_seat", ""],
        ["band", 20 * MS, 100 * MS, "jit_tpudl_prefill",
         SCOPE.format("prefill", 1, "window_attention")],
        ["rest", 120 * MS, 490 * MS, "jit_tpudl_prefill", ""],
    ]
    for start in (630 * MS, 643 * MS):
        ops += [
            ["kernel", start, 1 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", 0, "full_attention/paged_attention")],
            ["kernel", start + 1 * MS, 0.5 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", 1, "window_attention/paged_attention")],
            ["rest", start + 1.5 * MS, 9.5 * MS, "jit_tpudl_decode", ""],
        ]
    annotations = [
        ["tpudl.prefill", 19 * MS, 603 * MS, 1],
        ["tpudl.decode_step", 641 * MS, 2 * MS, 10],
        ["tpudl.decode_step", 644 * MS, 11.5 * MS, 11],
    ]
    return {"annotations": annotations, "modules": modules, "ops": ops}


PREFILL = {"rows": 8192, "tokens": 3000, "moe_experts_touched": 90,
           "moe_assignments": 9000}
LIVE = (24 * 5000, 24 * 128)
STEP = {"tokens_live": LIVE[0], "tokens_live_window": LIVE[1], "busy": 24,
        "kv_bytes_live": fl.live_kv_bytes(CONFIG, *LIVE),
        "pages_reserved": 24 * 400, "pages_reserved_window": 24 * 9,
        "moe_experts_touched": 80, "moe_assignments": 150, "ahead": 1}


def _ctx(platform="tpu", prefill=PREFILL, step=STEP):
    spans = [{"kind": "span", "name": "prefill", "id": 1, "ts": 0.5,
              **prefill}] + [
        {"kind": "span", "name": "decode_step", "id": 10 + i, "ts": 0.6,
         **step} for i in range(2)]
    ctx = types.SimpleNamespace(
        device={"platform": platform, "kind": "TPU v5 lite"},
        config=CONFIG, spans=spans,
        tracer=types.SimpleNamespace(done=True),
    )
    ctx.window_spans = lambda name: [s for s in spans if s["name"] == name]
    return ctx


@pytest.fixture
def traced(monkeypatch):
    trace = _trace()
    monkeypatch.setattr(pt, "of_run", lambda ctx: trace)


def test_each_share_is_its_least_time_over_its_busy_time(traced):
    ctx = _ctx()
    cfg = CONFIG
    prefill = fl.least_seconds(
        fl.prefill_bytes(cfg, 8192, 90),
        fl.prefill_flops(cfg, 3000, 9000), PEAK)
    # The operations of the prompt's 3,000 tokens over the time of the
    # 8,192 rows the program ran.
    assert sink_moe_roofline.read(ctx, "prefill") == pytest.approx(
        100 * prefill / 590e-3)
    step = fl.least_seconds(
        fl.decode_step_bytes(cfg, *LIVE, 80),
        fl.decode_step_flops(cfg, 24, *LIVE, 150), PEAK)
    # Both decode programs' 11 ms, though one ran before its span.
    assert sink_moe_roofline.read(ctx, "decode_step") == pytest.approx(
        100 * 2 * step / 22e-3)
    rows = fl.least_seconds(
        fl.live_kv_bytes(cfg, *LIVE), fl.attention_flops(cfg, *LIVE), PEAK)
    assert rows == pytest.approx(693_043_200 / 819e9)  # bytes bind
    # Both kinds' kernels sit under ``paged_attention``.
    assert sink_moe_roofline.read(ctx, "paged_attention") == pytest.approx(
        100 * 2 * rows / 3e-3)
    # A share over 100 % is a fault of the counts or of the time.
    for part in ("prefill", "decode_step", "paged_attention"):
        assert 0 < sink_moe_roofline.read(ctx, part) <= 100
    with pytest.raises(ValueError, match="part must be"):
        sink_moe_roofline.read(ctx, "other")
    busy = 5 + 590 + 2 * 11
    assert device_share.read(ctx, scope="window_attention") == pytest.approx(
        100 * (100 + 2 * 0.5) / busy)
    assert device_share.read(ctx, scope="full_attention") == pytest.approx(
        100 * 2 * 1 / busy)
    # Bytes, each group at its own row bytes: 693 MB live of the 3.69 GB
    # one table for every layer would read; the rings' 88 MB of the
    # pages held.
    assert sink_kv_share.read(ctx, "live_over_uniform") == pytest.approx(
        100 * 693_043_200 / (24 * 5000 * 30_720))
    rings, tables = 24 * 9 * 16 * 5 * 5120, 24 * 400 * 16 * 2 * 2560
    assert sink_kv_share.read(ctx, "ring_bytes") == pytest.approx(
        100 * rings / (rings + tables))
    with pytest.raises(ValueError, match="what must be"):
        sink_kv_share.read(ctx, "other")


@pytest.mark.parametrize("ctx", [
    _ctx("cpu"),
    _ctx(prefill={"rows": 8192, "moe_experts_touched": 80,
                  "moe_assignments": 1},
         step={"tokens_live": 1, "tokens_live_window": 1, "busy": 12,
               "moe_experts_touched": 1, "moe_assignments": 1}),
], ids=["cpu", "a_program_from_before_the_bytes_were_counted"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for part in ("prefill", "decode_step", "paged_attention"):
        assert sink_moe_roofline.read(ctx, part) is None
    if ctx.device["platform"] != "cpu":
        for what in ("live_over_uniform", "ring_bytes"):
            assert sink_kv_share.read(ctx, what) is None


def test_the_cells_traffic_is_what_the_issue_names():
    from perfbench import traffic

    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "mixedctx-backlog.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sampling"]) == (
        "closed", 48, "greedy")
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.7,
        "min": 1024, "max": 16384}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 192, "sigma": 0.5,
        "min": 64, "max": 512}
    sess = CONFIG["session"]
    assert mix["clients"] == 2 * sess["num_slots"] == 48
    assert (mix["block"], mix["blocks"]) == (sess["num_slots"], 24)
    # A slot holds the longest prompt and the longest answer.
    assert sess["max_seq_len"] == sess["prompt_window"] + 512 == 16_896
    # Short and long in one queue: 4 prompts of a block of 24 run the
    # 16,384-row program, 20 the 8,192-row one; the shortest is 1,024.
    prompts = traffic.int_lengths(mix["prompt_tokens"], mix["block"])
    assert (prompts.min(), prompts.max()) == (1024, sess["prompt_window"])
    assert int((prompts > 8192).sum()) == 4
