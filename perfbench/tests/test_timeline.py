"""The timeline a run logs, on hand-made records."""

from perfbench.families import bert_train, decoder_serve


def test_a_training_window_logs_when_each_step_ended():
    record = {"steps": [(0.2, 0.7), (0.4, 0.6), (0.9, 0.5)]}
    out = bert_train.timeline(record)
    assert out["step_end_ms"] == [200.0, 400.0, 900.0]
    assert out["first_losses"] == [0.7, 0.6, 0.5] == out["last_losses"]


def test_a_serving_window_logs_each_requests_latencies():
    record = {"requests": [
        {"due_s": 1.0, "token_s": [1.05, 1.10, 1.20]},
        {"due_s": 2.0, "token_s": []},          # never served: no latency
        {"due_s": 3.0, "token_s": [3.5]},       # one token: no gap
    ]}
    out = decoder_serve.timeline(record)
    assert out["ttft_ms"] == [50.0, 500.0]
    assert out["tpot_ms"] == [75.0, 0.0]


def test_the_loss_fell_only_where_the_last_losses_are_lower():
    falling = [1.0 - 0.01 * i for i in range(64)]
    assert bert_train.loss_fall(falling) < 0.6
    assert bert_train.loss_fall([0.7] * 64) == 1.0
    assert bert_train.loss_fall(falling[::-1]) > 1.0
    # Too few steps to say.
    assert bert_train.loss_fall([0.7] * 15) != bert_train.loss_fall([0.7] * 15)


def test_leaves_without_a_gradient_are_told_by_the_references_norms():
    norms = {"a/kernel": 0.5, "b/kernel": 0.2, "a/bias": 0.1,
             "key/bias": 3e-9}
    assert bert_train.leaves_with_a_gradient(norms) == [
        "a/kernel", "b/kernel", "a/bias"]
    gap, leaf = bert_train.worst_leaf_gap(
        {**norms, "key/bias": 1e-3, "a/bias": 0.11}, norms,
        ["a/kernel", "b/kernel", "a/bias"])
    assert leaf == "a/bias" and abs(gap - 0.01 / 0.15) < 1e-9
