"""The learned-sparse-attention family end to end at tiny widths on the
CPU: build, warm-up, window, the check against
``perfbench/reference/sparse_mla_moe.py`` and every reader of the cell,
through ``run_cell`` as ``test_rehearsal.py`` runs the other families
(its own directory, ``rehearsal_sparse_mla_moe/``, because a PR adds
files to the benchmark and edits none). What comes out names the CPU as
its device and carries no share of a chip's peak.

The rehearsal is float32, so a sound program's margins read 0 and the
check is held to what it has to tell apart: the int8 control, and three
programs that are each wrong in ONE part of what ISSUE 44 adds.

Then the published configuration against the catalog, its parameter and
cache counts (ISSUE 44 section 3's, by shape, no allocation),
``perfbench/flops_sparse_mla_moe.py`` against counts made by hand, its
roofline reader on a trace made by hand, and the cell's traffic.
"""

import json
import os
import time
import types

import pytest

from perfbench import flops_sparse_mla_moe as fl
from perfbench import run
from perfbench.device import require_chips
from perfbench.families import sparse_mla_moe_serve as family
from perfbench.manifest import Manifest
from perfbench.readers import _program_trace as pt
from perfbench.readers import device_share, span_attr_share, sparse_moe_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSAL = os.path.join(HERE, "rehearsal_sparse_mla_moe")
CELL = "glm-5.2-l6-e16.deepctx-backlog"
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "glm-5.2-l6-e16.json")) as f:
    CONFIG = json.load(f)
ROOFLINES = {"sparse_moe_decode_step_roofline", "sparse_moe_prefill_roofline",
             "sparse_index_decode_roofline",
             "sparse_latent_attention_roofline"}
NEW = ROOFLINES | {
    "sparse_index_prefill_device_share", "sparse_select_prefill_device_share",
    "sparse_index_decode_device_share", "sparse_select_decode_device_share",
    "sparse_chosen_over_live"}


def rehearse(trace=False, seconds=2.0, seed=7):
    manifest = Manifest(REHEARSAL)
    cell = manifest.cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    result = run.run_cell(manifest, cell, device, seed, seconds, trace,
                          time.monotonic())
    return manifest, json.loads(json.dumps(result))


def readings(variant="program", seed=7, seconds=3.0):
    """``({comparison: (value, limit)}, the check's other numbers)`` of
    one short window."""
    cell = Manifest(REHEARSAL).cell(CELL)
    device = require_chips(cell["chips"], allow_cpu=True)
    system = family.build(cell["config"], device, seed, variant)
    system.warm_up(cell["traffic"], seconds)
    record = system.run_window(cell["traffic"], seconds)
    system.release()
    check = system.check(record)
    return {c["name"]: (c["value"], c["limit"])
            for c in check["comparisons"]}, check


def test_untraced_run_is_correct_and_reports_the_end_to_end_metrics():
    manifest, out = rehearse(seed=2**31 + 44)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["device"]["platform"] == "cpu"
    want = {m["name"] for m in manifest.metrics(CELL, "end_to_end")}
    assert set(out["metrics"]) == want == {"serve_tokens_per_s", "setup_s"}


def test_traced_run_reports_the_layers_and_no_share_of_a_peak():
    manifest, out = rehearse(trace=True, seconds=6.0)
    assert out["correct"] is True
    want = {m["name"] for m in manifest.metrics(CELL, "per_layer")}
    assert NEW <= want
    assert {n for n in want if n.endswith("_roofline")} == ROOFLINES
    # The CPU's trace names no scope, so the device shares read nothing
    # either; the span metrics are there, and a decode step attends
    # chosen rows, never the pool in place.
    assert set(out["metrics"]) <= want - ROOFLINES
    assert {"backlog_prefill_share", "backlog_decode_step_ms_p50",
            "backlog_seat_ms_p50", "moe_expert_load_max_over_mean",
            "backlog_decode_ahead_share", "backlog_prefill_live_rows_share",
            "latent_decode_kv_in_place_share", "sparse_chosen_over_live",
            "compile_s"} <= set(out["metrics"])
    assert out["metrics"]["latent_decode_kv_in_place_share"]["value"] == 0
    # Sequences of 18-64 positions under a choice of 8.
    assert 10 < out["metrics"]["sparse_chosen_over_live"]["value"] < 50


def test_the_sound_program_agrees_and_the_control_is_not_correct():
    sound, check = readings()
    assert all(v <= lim for v, lim in sound.values()), sound
    # Float32: the program's indexers choose what the reference's do.
    assert check["index_choice_agreement"] == 1.0
    control, check = readings("control", seconds=8.0)
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = control[name]
        assert value > limit, control
    assert control["wrong_token_count"] == (0, 0)
    assert 0.5 < check["index_choice_agreement"] < 1.0


def _shared_layers_choose_the_oldest_keys(monkeypatch):
    """A ``shared`` layer that attends the FIRST ``index_topk``
    positions in place of the choice handed to it."""
    import jax.numpy as jnp

    import tpudl.models.llama as llama

    sound = llama.LatentAttention.__call__

    def call(self, hidden, positions, kv_mask=None, decode=False, paged=None,
             adapters=None, layer=0, choice=None):
        if self.cfg.indexer_types[layer] == "shared" and choice is not None:
            k = self.cfg.index_topk
            if choice.dtype == jnp.bool_:
                choice = jnp.broadcast_to(
                    jnp.arange(choice.shape[-1]) < k, choice.shape)
            else:
                choice = jnp.broadcast_to(
                    jnp.arange(k, dtype=choice.dtype), choice.shape)
        return sound(self, hidden, positions, kv_mask, decode, paged,
                     adapters, layer, choice)

    monkeypatch.setattr(llama.LatentAttention, "__call__", call)


def _scores_without_the_head_weights(monkeypatch):
    """``I[t, s] = sum_j relu(q_j . k_s)``: the weights ``w_j`` left
    out."""
    import jax.numpy as jnp

    import tpudl.models.llama as llama

    sound = llama.index_scores
    monkeypatch.setattr(
        llama, "index_scores",
        lambda q, w, keys: sound(q, jnp.ones_like(w), keys))


def _keys_not_roped(monkeypatch):
    """The indexer's key cached as the LayerNorm leaves it."""
    import tpudl.models.llama as llama

    sound = llama.rope

    def rope(x, positions, theta, scaling=None, rotary_dim=None):
        if rotary_dim is not None and x.shape[2] == 1:
            return x
        return sound(x, positions, theta, scaling, rotary_dim)

    monkeypatch.setattr(llama, "rope", rope)


FAULTS = {
    "shared_layers_choose_the_oldest_keys":
        _shared_layers_choose_the_oldest_keys,
    "scores_without_the_head_weights": _scores_without_the_head_weights,
    "keys_not_roped": _keys_not_roped,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_program_wrong_in_one_part_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    got, _ = readings()
    for name in ("second_choice_share", "mean_logit_margin"):
        value, limit = got[name]
        assert value > limit, got
    # Every request still ends with the tokens it asked for: only the
    # comparison with the reference tells.
    assert got["wrong_token_count"] == (0, 0)


# -- the configuration, and operations and bytes by hand ----------------------


def test_the_configuration_is_the_published_one_cut_as_the_file_says():
    """Every number of the catalog's ``config`` under its key, but the
    seven in ``reduced``: layers 2-7, 16 of 256 experts, an eighth of
    the vocabulary, no MTP layer."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        rows = [json.loads(line) for line in f if '"GLM-5.2"' in line]
    published = rows[0]["config"]
    assert CONFIG["source"] == rows[0]["source_url"]
    differs = {k for k, v in published.items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "indexer_types",
        "mlp_layer_types", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    assert CONFIG["published"] == {
        k: published[k] for k in differs
        if k not in ("indexer_types", "mlp_layer_types")}
    assert CONFIG["indexer_types"] == published["indexer_types"][2:8] == [
        "full", "shared", "shared", "shared", "full", "shared"]
    assert CONFIG["mlp_layer_types"] == published["mlp_layer_types"][2:8]
    assert (CONFIG["n_routed_experts"], CONFIG["vocab_size"]) == (16, 19360)
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["deployment"]["chips_sharing_a_layer"] == 16


def test_parameters_and_cache_by_hand():
    """ISSUE 44 section 3's arithmetic, and the tree ``model.init``
    declares at the published widths (shapes alone)."""
    import math

    import jax
    import jax.numpy as jnp

    from perfbench.reference import sparse_mla_moe as ref
    from tpudl.models.llama import LlamaForCausalLM

    cfg = CONFIG
    attention = (6144 * 2048 + 2048 * 16384 + 6144 * 576 + 512 * 28672
                 + 16384 * 6144)
    assert fl.attention_params(cfg) == attention == 165_019_648
    indexer = 2048 * 4096 + 6144 * 128 + 6144 * 32 + 256
    assert fl.indexer_params(cfg) == indexer == 9_371_904
    assert fl.expert_params(cfg) == 3 * 6144 * 2048 == 37_748_736
    assert fl.router_params(cfg) == 1_573_120
    norms = 2 * 6144 + 2048 + 512
    shared = attention + norms + 1_573_120 + 17 * 37_748_736
    assert shared == 808_336_128
    dense = attention + norms + indexer + 3 * 6144 * 12288
    assert dense == 400_898_816
    assert fl.layer_params_outside_routed_experts(cfg, 0) == dense
    assert fl.layer_params_outside_routed_experts(cfg, 4) == (
        shared + indexer - 16 * 37_748_736)
    held = (dense + 4 * shared + (shared + indexer)
            + 2 * 19360 * 6144 + 6144)
    assert fl.params_held(cfg) == held == 4_689_853_184
    assert fl.weight_bytes_held(cfg) == 2 * held + 2 * 5 * 1_573_120
    assert fl.cache_bytes_per_position(cfg) == 6 * 1152 + 2 * 256 == 7424
    sess = cfg["session"]
    cache = sess["num_slots"] * sess["max_seq_len"] * 7424
    assert cache == 12 * 8384 * 7424 == 746_913_792
    assert 10.1e9 < fl.weight_bytes_held(cfg) + cache < 10.2e9
    # The program's own tree, by shape.
    s = ref.settings(cfg)
    model = LlamaForCausalLM(
        family.model_config(cfg, sess["max_seq_len"], jnp.bfloat16))
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(tree)) == held
    made = jax.eval_shape(
        lambda k: family.to_flax(ref.all_weights(k, s, jnp.bfloat16), s),
        jax.eval_shape(lambda: ref.seed_key(0)))
    assert jax.tree.structure(made) == jax.tree.structure(tree)
    for i, kind in enumerate(cfg["indexer_types"]):
        attention_tree = tree["model"][f"layer_{i}"]["attention"]
        assert ("indexer" in attention_tree) == (kind == "full")


def test_bytes_and_operations_by_hand():
    cfg = CONFIG
    outside = (400_898_816 + 4 * (808_336_128 - 16 * 37_748_736)
               + (817_708_032 - 16 * 37_748_736) + 6144 * 19360)
    router32 = 5 * 1_573_120
    # A decode step of 12 slots that see 70,000 positions together and
    # attend 24,576 of them, 30 held experts touched, 36 assignments.
    assert fl.decode_step_bytes(cfg, 24_576, 70_000, 30) == (
        2 * (outside + router32) + 2 * 30 * 37_748_736
        + 2 * 70_000 * 256 + 6 * 24_576 * 1152)
    body = outside - 6144 * 19360
    assert fl.decode_step_flops(cfg, 12, 24_576, 70_000, 36) == pytest.approx(
        2.0 * 12 * (body + 6144 * 19360)
        + 2 * 2.0 * 70_000 * 32 * 129
        + 6 * 2.0 * 64 * (576 + 512) * 24_576
        + 2.0 * 36 * 37_748_736)
    assert fl.index_decode_bytes(cfg, 70_000) == 2 * 70_000 * 256
    kv_b = 512 * 64 * 448
    assert fl.chosen_attention_bytes(cfg, 24_576) == 6 * (
        24_576 * 1152 + 2 * kv_b)
    assert fl.chosen_attention_flops(cfg, 12, 24_576) == pytest.approx(
        6 * 2.0 * (12 * kv_b + 64 * 1088 * 24_576))
    # A prefill of 8,192 rows: 2,048 queries see no more than they
    # attend; 6,144 attend 2,048 each and are scored over all they see.
    under = 2048 * 2049 // 2
    assert fl.prefill_pairs(8192, 2048) == (
        under + 6144 * 2048, 8192 * 8193 // 2 - under)
    assert fl.prefill_pairs(1000, 2048) == (1000 * 1001 // 2, 0)
    attended, scored = fl.prefill_pairs(8192, 2048)
    assert fl.prefill_bytes(cfg, 8192, 80) == (
        2 * (outside + router32) + 2 * 80 * 37_748_736 + 8192 * 7424)
    assert fl.prefill_flops(cfg, 8192, 20_000) == pytest.approx(
        2.0 * (8192 * body + 6144 * 19360)
        + 6 * 2.0 * 64 * 512 * attended
        + 2 * 2.0 * 32 * 129 * scored
        + 2.0 * 20_000 * 37_748_736)


# -- the roofline reader on a trace made by hand -------------------------------

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1e6  # nanoseconds
SCOPE = "jit(tpudl_{})/model/layer_0/attention/{}/dot"


def _trace():
    """A seat's 5 ms (the trace's first operation), a prefill program of
    900 ms (8,192 rows) that runs under its span, then two decode programs of 20 ms, the first of them ON THE DEVICE
    before the span that lands it opens (a step run ahead). Inside:
    ``dsa_index`` 60 ms and ``dsa_select`` 90 ms of the prefill;
    ``dsa_index`` 2 ms, ``dsa_select`` 1 ms and ``mla_core`` 4 ms (of
    which ``dsa_gather`` 3) of each decode."""
    modules = [["jit_tpudl_prefill", 20 * MS, 900 * MS],
               ["jit_tpudl_decode", 930 * MS, 20 * MS],
               ["jit_tpudl_decode", 951 * MS, 20 * MS]]
    ops = [
        ["seat", 0.0, 5 * MS, "jit_tpudl_seat", ""],
        ["index", 20 * MS, 60 * MS, "jit_tpudl_prefill",
         SCOPE.format("prefill", "dsa_index")],
        ["select", 80 * MS, 90 * MS, "jit_tpudl_prefill",
         SCOPE.format("prefill", "dsa_select")],
        ["rest", 170 * MS, 740 * MS, "jit_tpudl_prefill", ""],
    ]
    for start in (930 * MS, 951 * MS):
        ops += [
            ["index", start, 2 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", "dsa_index")],
            ["select", start + 2 * MS, 1 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", "dsa_select")],
            ["gather", start + 3 * MS, 3 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", "mla_core/dsa_gather")],
            ["core", start + 6 * MS, 1 * MS, "jit_tpudl_decode",
             SCOPE.format("decode", "mla_core")],
            ["rest", start + 7 * MS, 12 * MS, "jit_tpudl_decode", ""],
        ]
    annotations = [
        ["tpudl.prefill", 19 * MS, 903 * MS, 1],
        ["tpudl.decode_step", 949 * MS, 2 * MS, 10],
        ["tpudl.decode_step", 952 * MS, 19.5 * MS, 11],
    ]
    return {"annotations": annotations, "modules": modules, "ops": ops}


PREFILL = {"rows": 8192, "tokens": 6000, "moe_experts_touched": 80,
           "moe_assignments": 20_000, "sparse_rows_chosen": 14_000_000,
           "sparse_rows_live": 33_000_000, "index_layers": 2}
STEP = {"tokens_live": 70_000, "busy": 12, "moe_experts_touched": 30,
        "moe_assignments": 36, "sparse_rows_chosen": 24_576,
        "sparse_rows_live": 70_000, "index_layers": 2, "ahead": 1}


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
        fl.prefill_bytes(cfg, 8192, 80),
        fl.prefill_flops(cfg, 8192, 20_000), PEAK)
    assert sparse_moe_roofline.read(ctx, "prefill") == pytest.approx(
        100 * prefill / 890e-3)
    step = fl.least_seconds(
        fl.decode_step_bytes(cfg, 24_576, 70_000, 30),
        fl.decode_step_flops(cfg, 12, 24_576, 70_000, 36), PEAK)
    # Both decode programs' 19 ms, though one ran before its span.
    assert sparse_moe_roofline.read(ctx, "decode_step") == pytest.approx(
        100 * 2 * step / 38e-3)
    index = fl.least_seconds(
        fl.index_decode_bytes(cfg, 70_000),
        fl.index_decode_flops(cfg, 70_000), PEAK)
    assert sparse_moe_roofline.read(ctx, "index_decode") == pytest.approx(
        100 * 2 * index / 6e-3)
    rows = fl.least_seconds(
        fl.chosen_attention_bytes(cfg, 24_576),
        fl.chosen_attention_flops(cfg, 12, 24_576), PEAK)
    # ``dsa_gather`` is inside ``mla_core`` and counted with it.
    assert sparse_moe_roofline.read(ctx, "latent_attention") == pytest.approx(
        100 * 2 * rows / 8e-3)
    # A share over 100 % is a fault of the counts or of the time.
    for part in ("prefill", "decode_step", "index_decode",
                 "latent_attention"):
        assert 0 < sparse_moe_roofline.read(ctx, part) <= 100
    busy = 5 + 890 + 2 * 19
    assert device_share.read(
        ctx, program="prefill", scope="dsa_select") == pytest.approx(
            100 * 90 / busy)
    assert device_share.read(
        ctx, program="decode", scope="dsa_index") == pytest.approx(
            100 * 4 / busy)
    assert span_attr_share.read(
        ctx, "decode_step", "sparse_rows_chosen",
        ["sparse_rows_live"]) == pytest.approx(100 * 24_576 / 70_000)


@pytest.mark.parametrize("ctx", [
    _ctx("cpu"),
    _ctx(prefill={"rows": 8192, "moe_experts_touched": 80,
                  "moe_assignments": 1},
         step={"tokens_live": 1, "busy": 12, "moe_experts_touched": 1,
               "moe_assignments": 1}),
], ids=["cpu", "a_program_without_an_indexer"])
def test_nothing_to_read_reads_as_nothing(traced, ctx):
    for part in ("prefill", "decode_step", "index_decode",
                 "latent_attention"):
        assert sparse_moe_roofline.read(ctx, part) is None
    if ctx.device["platform"] != "cpu":
        assert span_attr_share.read(
            ctx, "decode_step", "sparse_rows_chosen",
            ["sparse_rows_live"]) is None


def test_the_cells_traffic_is_what_the_issue_names():
    from perfbench import traffic

    with open(os.path.join(os.path.dirname(HERE), "traffic",
                           "deepctx-backlog.json")) as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"], mix["sampling"]) == (
        "closed", 24, "greedy")
    assert mix["shared_prefix"] is None
    assert mix["prompt_tokens"] == {
        "dist": "lognormal", "median": 5632, "sigma": 0.25,
        "min": 4352, "max": 8192}
    assert mix["output_tokens"] == {
        "dist": "lognormal", "median": 96, "sigma": 0.35,
        "min": 48, "max": 192}
    sess = CONFIG["session"]
    assert mix["clients"] == 2 * sess["num_slots"] == 24
    assert (mix["block"], mix["blocks"]) == (sess["num_slots"], 24)
    # A slot holds the longest prompt and the longest answer.
    assert sess["max_seq_len"] == sess["prompt_window"] + 192 == 8384
    # Every prompt is over twice ``index_topk`` and runs the 8,192-row
    # program (the half of the window holds none of them).
    prompts = traffic.int_lengths(mix["prompt_tokens"], mix["block"])
    assert prompts.min() > 2 * CONFIG["index_topk"] == 4096
    assert prompts.max() == sess["prompt_window"]
