"""The looped-decoder family end to end at tiny widths on the CPU:
build, warm-up, window, the check against
``perfbench/reference/loop_decoder.py`` and every reader of the cell,
through ``run_cell`` as ``test_rehearsal.py`` runs the other families
(its own directory, ``rehearsal_loop_decoder/``, because a PR adds files
to the benchmark and edits none). What comes out names the CPU as its
device and carries no share of a chip's peak.

The rehearsal is float32, so a sound program's margins read 0 and the
check is held to what it has to tell apart: the int8 control, and a
program whose passes share one cache.

Then ``perfbench/flops_loop_decoder.py`` against counts made by hand
(ISSUE 40 section 3's), the readers on a trace made by hand, the
published configuration against the catalog, and the cell's traffic.
"""

import json
import os
import time
import types

import pytest

from perfbench import flops_loop_decoder as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.families import loop_decoder_serve as family
from perfbench.manifest import Manifest
from perfbench.readers import _program_trace as pt
from perfbench.readers import device_share, loop_decoder

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_loop_decoder")
CELL = "ouro-2.6b.shortreason-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "ouro-2.6b.json")) as f:
    CONFIG = json.load(f)
NEW = {"loop_decode_step_roofline", "loop_prefill_roofline",
       "loop_paged_attention_roofline", "loop_attention_device_share",
       "loop_norm_device_share", "loop_kv_bytes_share"}


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def readings(variant="program", seed=7, seconds=2.0):
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
    manifest, out = rehearse(seed=2**31 + 40)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=5.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert NEW <= want
    peaks = {n for n in want if n.endswith("_roofline")}
    assert peaks == {"loop_decode_step_roofline", "loop_prefill_roofline",
                     "loop_paged_attention_roofline"}
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics are there, the kernel is not taken on a
    # CPU.
    assert set(out["metrics"]) <= want - peaks
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "backlog_decode_ahead_share",
            "backlog_prefill_live_rows_share", "loop_kv_bytes_share",
            "backlog_decode_kv_in_place_share", "compile_s"} <= set(
                out["metrics"])
    assert out["metrics"]["backlog_decode_kv_in_place_share"]["value"] == 0
    # Tiny weights under a few dozen live positions: the cache is a
    # share of the step's bytes, and neither none nor all of them.
    assert 0 < out["metrics"]["loop_kv_bytes_share"]["value"] < 100


def test_the_control_is_not_correct():
    sound = readings()
    control = readings("control", seconds=6.0)
    assert all(v <= lim for v, lim in sound.values()), sound
    value, limit = control["mean_logit_margin"]
    assert value > limit, control
    assert control["wrong_token_count"] == (0, 0)


def test_passes_that_share_one_cache_are_not_correct(monkeypatch):
    import tpudl.models.llama as llama

    monkeypatch.setattr(
        llama, "_pass_leaves", lambda cfg, t: ((lambda name: name), True))
    got = readings()
    for name in ("mean_logit_margin", "worst_logit_margin"):
        value, limit = got[name]
        assert value > limit, got
    # Every request still ends with the tokens it asked for: only the
    # comparison with the reference tells.
    assert got["wrong_token_count"] == (0, 0)


# -- operations and bytes by hand ---------------------------------------------


def test_the_configuration_is_the_published_one_uncut():
    """Every key of the catalog's ``config`` under its key with its
    value; ``reduced`` empty: depth 48, 4 passes, every head, the whole
    vocabulary."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if '"Ouro-2.6B"' in line]
    published = rows[0]["config"]
    assert CONFIG["source"] == rows[0]["source_url"]
    assert {k for k, v in published.items() if CONFIG.get(k, "-") != v} == set()
    assert CONFIG["reduced"] == {}
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "BENCHMARK.json")) as f:
        entry = [c for c in json.load(f)["configs"]
                 if c["name"] == "ouro-2.6b"][0]
    assert entry["reduced"] == [] and entry["source"] == CONFIG["source"]
    assert (CONFIG["num_hidden_layers"], CONFIG["total_ut_steps"]) == (48, 4)
    for departure in ("sandwich norms", "final norm runs after EVERY pass",
                      "exit gate", "a (pass, layer)", "no bias",
                      "rotate-half RoPE", "torch_dtype bfloat16"):
        assert any(departure in line for line in CONFIG["assumed"]), departure


def test_bytes_and_operations_by_hand():
    cfg = CONFIG
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert fl.layer_params(cfg) == layer == 51_388_416
    assert fl.layer_matmul_params(cfg) == layer - 4 * 2048
    # ISSUE 40 section 3: 48 layers 2,466.6 M; embedding + head 201.3 M;
    # final norm and gate 4,097: 2,667.97 M parameters = 5.34 GB.
    held = 48 * layer + 2 * 49152 * 2048 + 4097
    assert fl.params_held(cfg) == held == 2_667_974_657
    assert fl.weight_bytes_held(cfg) == 2 * held + 2 * 2049 == 5_335_953_412
    assert 5.33e9 < fl.weight_bytes_held(cfg) < 5.34e9
    # 192 pool pairs of 16 heads of 128 at 2 bytes: 1,572,864 B a
    # position, twelve times a Mistral position at its published 32
    # layers (24 times one at this repo's 16).
    assert fl.cache_bytes_per_position(cfg) == 192 * 8192 == 1_572_864
    assert fl.cache_bytes_per_position(cfg) == 12 * (32 * 2 * 8 * 128 * 2)
    sess = cfg["session"]
    assert (sess["num_slots"], sess["max_seq_len"], sess["prompt_window"],
            sess["page_size"]) == (16, 256, 128, 16)
    pages = sess["num_slots"] * sess["max_seq_len"] // sess["page_size"] + 1
    pool = pages * 16 * 16 * 128 * 2
    assert (pages, pool) == (257, 16_842_752)
    cache = 384 * pool
    assert cache == 6_467_616_768
    assert cfg["deployment"]["parameters_held"] == held
    assert cfg["deployment"]["weight_bytes"] == fl.weight_bytes_held(cfg)
    assert cfg["deployment"]["cache_bytes_per_position"] == 1_572_864
    assert cfg["deployment"]["cache_bytes"] == cache
    assert 11.7e9 < fl.weight_bytes_held(cfg) + cache < 11.9e9
    # A step reads the layers' weights FOUR times, the head once.
    read = (4 * 2 * (48 * layer + 2048) + 3 * 4 * 2049
            + 2 * 2048 * 49152)
    assert fl.weights_read_bytes(cfg) == read
    assert 19.9e9 < read < 20.0e9
    assert fl.decode_step_bytes(cfg, 1800) == read + 1800 * 1_572_864
    matmul = 48 * (layer - 4 * 2048)
    assert fl.decode_step_flops(cfg, 16, 1800) == pytest.approx(
        2.0 * 16 * (4 * matmul + 2048 * 49152)
        + 192 * 2.0 * 2 * 1800 * 16 * 128)
    assert fl.prefill_bytes(cfg, 128) == read + 128 * 1_572_864
    assert fl.prefill_flops(cfg, 128) == pytest.approx(
        2.0 * (128 * 4 * matmul + 2048 * 49152)
        + 192 * 2.0 * 2 * (128 * 129 / 2) * 16 * 128)
    # Both programs are bound by the four reads of the weights, not by
    # their operations (2.5 TFLOP a 128-row prefill).
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert 2.4e12 < fl.prefill_flops(cfg, 128) < 2.6e12
    assert fl.least_seconds(
        fl.prefill_bytes(cfg, 128), fl.prefill_flops(cfg, 128), peak
    ) == fl.prefill_bytes(cfg, 128) / 819e9


# -- the readers on a trace made by hand ---------------------------------------

MS = 1e6  # nanoseconds
KERNEL = ("jit(tpudl_decode)/model/loop_pass_1/layer_3/attention/"
          "paged_attention/pallas_call")
NORM = "jit(tpudl_decode)/model/loop_pass_0/layer_0/input_norm/norm/mul"
SCATTER = ("jit(tpudl_decode)/model/loop_pass_2/layer_1/attention/"
           "kv_scatter/scatter")


def _trace():
    """A prefill program of 30 ms (128 rows) under its span, then two
    decode programs of 36 ms, the first of them ON THE DEVICE before
    the span that lands it opens (a step run ahead), and a prefill cut
    by the trace's start whose span is not in the trace. Inside each
    decode: 6 ms under ``paged_attention``, 1 ms of ``kv_scatter``
    (both under ``attention``), 3 ms under ``norm``."""
    modules = [["jit_tpudl_prefill", -20 * MS, 30 * MS],
               ["jit_tpudl_prefill", 20 * MS, 30 * MS],
               ["jit_tpudl_decode", 52 * MS, 36 * MS],
               ["jit_tpudl_decode", 89 * MS, 36 * MS]]
    ops = [
        ["cut", 0.0, 10 * MS, "jit_tpudl_prefill", ""],
        ["all", 20 * MS, 29 * MS, "jit_tpudl_prefill", ""],
    ]
    for start in (52 * MS, 89 * MS):
        ops += [
            ["kernel", start, 6 * MS, "jit_tpudl_decode", KERNEL],
            ["scatter", start + 6 * MS, 1 * MS, "jit_tpudl_decode", SCATTER],
            ["norm", start + 7 * MS, 3 * MS, "jit_tpudl_decode", NORM],
            ["rest", start + 10 * MS, 25 * MS, "jit_tpudl_decode", ""],
        ]
    annotations = [
        ["tpudl.prefill", 19 * MS, 33 * MS, 1],
        ["tpudl.decode_step", 88.5 * MS, 2 * MS, 10],
        ["tpudl.decode_step", 91 * MS, 34.5 * MS, 11],
    ]
    return {"annotations": annotations, "modules": modules, "ops": ops}


PREFILL = {"rows": 128, "tokens": 60, "loop_passes": 4,
           "loop_exit_pdf": [0.4, 0.2, 0.2, 0.2]}
STEP = {"tokens_live": 1800, "busy": 16, "loop_passes": 4,
        "loop_exit_pdf": [0.4, 0.2, 0.2, 0.2], "ahead": 1}


def _ctx(platform="tpu", prefill=PREFILL, step=STEP):
    spans = [{"kind": "span", "name": "prefill", "id": 1, "ts": 0.019,
              **prefill}] + [
        {"kind": "span", "name": "decode_step", "id": 10 + i,
         "ts": 0.09 + i / 100, **step} for i in range(2)]
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


def test_each_share_is_its_least_time_over_its_programs_busy_time(traced):
    ctx = _ctx()
    # Bound by bytes: four reads of the weights and the live cache.
    step = fl.decode_step_bytes(CONFIG, 1800) / 819e9
    assert loop_decoder.read(ctx, "decode_step_roofline") == pytest.approx(
        100 * 2 * step / 70e-3)
    # (The cut prefill's 10 ms belong to no span, and count nowhere.)
    prefill = fl.prefill_bytes(CONFIG, 128) / 819e9
    assert loop_decoder.read(ctx, "prefill_roofline") == pytest.approx(
        100 * prefill / 29e-3)
    kernel = 1800 * 1_572_864 / 819e9
    assert loop_decoder.read(
        ctx, "paged_attention_roofline") == pytest.approx(
            100 * 2 * kernel / 12e-3)
    for part in ("decode_step_roofline", "prefill_roofline",
                 "paged_attention_roofline"):
        assert 0 < loop_decoder.read(ctx, part) <= 100
    live = 1800 * 1_572_864
    assert loop_decoder.read(ctx, "kv_bytes_share") == pytest.approx(
        100 * live / (live + fl.weights_read_bytes(CONFIG)))
    busy = 10 + 29 + 2 * 35
    assert device_share.read(
        ctx, program="decode", scope="attention") == pytest.approx(
            100 * 14 / busy)
    assert device_share.read(
        ctx, program="decode", scope="norm") == pytest.approx(100 * 6 / busy)


@pytest.mark.parametrize("ctx", [
    _ctx("cpu"),
    _ctx(prefill={"rows": 128}, step={"tokens_live": 1, "busy": 16}),
], ids=["cpu", "a_program_without_the_loop"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for part in ("decode_step_roofline", "prefill_roofline",
                 "paged_attention_roofline"):
        assert loop_decoder.read(ctx, part) is None
    if ctx.device["platform"] != "cpu":
        assert loop_decoder.read(ctx, "kv_bytes_share") is None


def test_the_cells_traffic_is_what_the_issue_names():
    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "shortreason-backlog.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sampling"]) == (
        "closed", 32, "greedy")
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 48, "sigma": 0.5,
        "min": 16, "max": 128}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.5,
        "min": 32, "max": 128}
    sess = CONFIG["session"]
    assert mix["clients"] == 2 * sess["num_slots"] == 32
    assert mix["block"] == sess["num_slots"] == 16
    # A slot holds the compiled prefill length and the longest answer.
    assert sess["max_seq_len"] == sess["prompt_window"] + 128
    assert mix["block"] * mix["blocks"] == 1024
