"""``perfbench/flops_sink_window_moe.py`` against counts made by hand
for ``mimo-v2-flash-l7-e16`` (ISSUE 47's arithmetic), and the tree the
program declares at the published widths (shapes alone)."""

import json
import math
import os

import pytest

from perfbench import flops_sink_window_moe as fl

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(os.path.dirname(HERE), "configs",
                       "mimo-v2-flash-l7-e16.json")) as f:
    CONFIG = json.load(f)

FULL_ATTENTION = 4096 * 12288 + 4096 * 768 + 4096 * 512 + 8192 * 4096
SWA_ATTENTION = 4096 * 12288 + 4096 * 1536 + 4096 * 1024 + 8192 * 4096
EXPERT = 3 * 4096 * 2048
ROUTER = 4096 * 256
DENSE_FFN = 3 * 4096 * 16384
HEAD = 19072 * 4096


def test_the_layers_held_are_the_published_first_seven():
    assert fl.layer_kinds(CONFIG) == [
        (False, False), (True, True), (True, True), (True, True),
        (True, True), (False, True), (True, True)]
    assert fl.layer_counts(CONFIG) == (2, 5)


def test_parameters_to_the_parameter():
    cfg = CONFIG
    assert fl.attention_params(cfg, False) == FULL_ATTENTION == 89_128_960
    assert fl.attention_params(cfg, True) == SWA_ATTENTION == 94_371_840
    assert fl.expert_params(cfg) == EXPERT == 25_165_824
    assert fl.layer_params_outside_routed_experts(cfg, 0) == (
        FULL_ATTENTION + DENSE_FFN) == 290_455_552
    assert fl.layer_params_outside_routed_experts(cfg, 1) == (
        SWA_ATTENTION + ROUTER)
    assert fl.layer_params_outside_routed_experts(cfg, 5) == (
        FULL_ATTENTION + ROUTER)
    sliding_layer = SWA_ATTENTION + ROUTER + 16 * EXPERT
    full_layer = FULL_ATTENTION + ROUTER + 16 * EXPERT
    assert (sliding_layer, full_layer) == (498_073_600, 492_830_720)
    held = 290_455_552 + 5 * sliding_layer + full_layer + 2 * HEAD
    assert fl.parameters(cfg) == held == 3_429_892_096  # 3,429.9 M


def test_the_programs_tree_is_those_parameters():
    """By shape: the matrices above and, apart, 15 norm scales of 4,096,
    5 x 64 sinks and 6 selection biases of 256."""
    import jax
    import jax.numpy as jnp

    from perfbench.families import sink_window_moe_serve as family
    from perfbench.reference import sink_window_moe as ref
    from tpudl.models.llama import LlamaForCausalLM

    sess = CONFIG["session"]
    model = LlamaForCausalLM(
        family.model_config(CONFIG, sess["max_seq_len"], jnp.bfloat16))
    tree = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.ones((1, 8), jnp.int32))["params"])
    small = 15 * 4096 + 5 * 64 + 6 * 256
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(tree)) == (
        fl.parameters(CONFIG) + small)
    s = ref.settings(CONFIG)
    made = jax.eval_shape(
        lambda k: family.to_flax(ref.all_weights(k, s, jnp.bfloat16), s),
        jax.eval_shape(lambda: ref.seed_key(0)))
    assert jax.tree.map(lambda a: a.shape, made) == jax.tree.map(
        lambda a: a.shape, tree)


def test_cache_bytes_a_position_by_group():
    cfg = CONFIG
    assert fl.kv_bytes_per_position(cfg, False) == (4 * 192 + 4 * 128) * 2 == 2560
    assert fl.kv_bytes_per_position(cfg, True) == (8 * 192 + 8 * 128) * 2 == 5120
    # One table for every layer would keep 30,720 B a position.
    assert fl.uniform_kv_bytes(cfg, 1) == 2 * 2560 + 5 * 5120 == 30_720
    sess = cfg["session"]
    slots, page = sess["num_slots"], sess["page_size"]
    pages = sess["max_seq_len"] // page
    assert (pages, cfg["sliding_window"] // page + 1) == (1056, 9)
    full, rings = fl.reserved_kv_bytes(cfg, page, slots * pages, slots * 9)
    assert full == 24 * 16_896 * 5120 == 2_076_180_480  # 2.08 GB
    assert rings == 24 * 144 * 5 * 5120 == 88_473_600  # 0.09 GB
    # ... and 12.5 GB under one table: it would not fit beside 6.86 GB.
    assert 24 * 16_896 * 30_720 == 12_457_082_880
    assert 2 * fl.parameters(cfg) + full + rings < 9.1e9


def test_a_steps_bytes_and_operations_at_a_stated_occupancy():
    """24 slots of 5,000 live positions each (a window layer reads 128
    of them), 80 held experts touched over the six expert layers, 150
    assignments."""
    cfg = CONFIG
    outside = (FULL_ATTENTION + DENSE_FFN + 5 * (SWA_ATTENTION + ROUTER)
               + FULL_ATTENTION + ROUTER + HEAD)
    assert fl.params_outside_routed_experts(cfg) == outside
    live_full, live_window = 24 * 5000, 24 * 128
    rows = 2 * live_full * 2560 + 5 * live_window * 5120
    assert fl.live_kv_bytes(cfg, live_full, live_window) == rows == 693_043_200
    assert fl.decode_step_bytes(cfg, live_full, live_window, 80) == (
        2 * outside + 2 * 80 * EXPERT + rows)
    attention = 2.0 * 64 * 320 * (2 * live_full + 5 * live_window)
    assert fl.attention_flops(cfg, live_full, live_window) == attention
    assert fl.decode_step_flops(cfg, 24, live_full, live_window, 150) == (
        pytest.approx(2.0 * 24 * outside + 2.0 * 150 * EXPERT + attention))
    # Streaming every weight held: 6.86 GB, 8.4 ms at 819 GB/s.
    assert 2 * fl.parameters(cfg) / 819e9 == pytest.approx(8.4e-3, rel=0.01)


def test_a_prefills_bytes_and_operations_are_its_own_tokens():
    cfg = CONFIG
    assert fl.prefill_pairs(100, 128) == (5050, 5050)
    assert fl.prefill_pairs(1024, 128) == (
        1024 * 1025 // 2, 128 * 129 // 2 + 896 * 128)
    outside = fl.params_outside_routed_experts(cfg)
    assert fl.prefill_bytes(cfg, 8192, 90) == (
        2 * outside + 2 * 90 * EXPERT + 8192 * 30_720)
    causal, banded = fl.prefill_pairs(1024, 128)
    # A 1,024-token prompt in an 8,192-row program: the operations of
    # 1,024 tokens.
    assert fl.prefill_flops(cfg, 1024, 3000) == pytest.approx(
        2.0 * (1024 * (outside - HEAD) + HEAD)
        + 2.0 * 64 * 320 * (2 * causal + 5 * banded)
        + 2.0 * 3000 * EXPERT)
    peak = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert fl.least_seconds(819e9, 1.0, peak) == 1.0
    assert fl.least_seconds(1.0, 197e12, peak) == 1.0
