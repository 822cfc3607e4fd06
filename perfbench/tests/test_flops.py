"""FLOP and byte functions against counts made by hand."""

import json
import os

import pytest

from perfbench import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_bert_base_by_hand():
    cfg = _cfg("bert-base-sst2")
    # One layer: Q, K, V, O of 768 x 768 and two of 768 x 3072.
    assert flops.bert_params_per_layer(768, 3072) == 4 * 589_824 + 2 * 2_359_296
    assert flops.bert_params_per_layer(768, 3072) == 7_077_888
    per_token = 6 * 12 * 7_077_888                      # 509,607,936
    attention = 3 * 12 * (4 * 128 * 128 * 768)          # 1,811,939,328
    head = 6 * (768 * 768 + 768 * 2)
    want = per_token * 128 + attention + head
    assert flops.bert_train_flops_per_sample(cfg, 128) == pytest.approx(want)
    # 6 N T with N = 85 M matmul weights is within 3 % of it.
    assert want == pytest.approx(6 * 84_934_656 * 128, rel=0.03)


def test_mistral_layer_by_hand():
    cfg = _cfg("mistral-7b-v0.3-l16")
    q = 4096 * 4096
    kv = 2 * 4096 * 1024
    o = 4096 * 4096
    mlp = 3 * 4096 * 14336
    assert flops.decoder_layer_params(cfg) == q + kv + o + mlp == 218_103_808
    assert flops.kv_bytes_per_position(cfg) == 16 * 2 * 8 * 128 * 2 == 65_536
    weights = 2 * (16 * 218_103_808 + 4096 * 32768)
    assert flops.decoder_weight_bytes(cfg) == weights == 7_247_757_312
    assert flops.decode_step_bytes(cfg, 1000) == weights + 65_536_000
    dense = 2.0 * 3 * (16 * 218_103_808 + 4096 * 32768)
    attn = 2.0 * 2 * 500 * 32 * 128 * 16
    assert flops.decode_step_flops(cfg, 3, 500) == pytest.approx(dense + attn)
