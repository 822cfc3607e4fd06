"""Percentiles, TPOT and window membership on hand-made timelines."""

import types

import pytest

from perfbench import stats
from perfbench.readers import (
    generator_late_percentile,
    queue_wait_percentile,
    serve_token_rate,
    tpot_percentile,
    train_rate,
    train_step_percentile,
    ttft_percentile,
)


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 95, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4], 50, 2.5),
    ([0, 10], 95, 9.5),
    (list(range(101)), 95, 95.0),
])
def test_percentile(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tpot_is_the_mean_gap():
    assert stats.tpot_s([1.0, 1.1, 1.3, 1.6]) == pytest.approx(0.2)
    assert stats.tpot_s([1.0]) is None


def test_due_in_window():
    assert stats.due_in_window([0.0, 4.9, 5.0, 7.0, -1.0], 5.0) == [0, 1]


def _req(due, submit, tokens, reason="length", queue_wait=0.0, n=None):
    return {"rid": 1, "due_s": due, "submit_s": submit, "token_s": tokens,
            "finish_reason": reason, "queue_wait_s": queue_wait,
            "tokens": list(range(n if n is not None else len(tokens)))}


def _ctx(reqs, seconds=10.0, loop="open", window_s=None):
    return types.SimpleNamespace(record={
        "kind": "serve", "loop": loop, "seconds": seconds,
        "window_s": window_s or seconds, "requests": reqs,
    })


def test_serving_readers_on_a_hand_made_timeline():
    reqs = [
        _req(1.0, 1.01, [1.10, 1.20, 1.30], queue_wait=0.02),   # ttft 100
        _req(2.0, 2.05, [2.30, 2.50], queue_wait=0.10),         # ttft 300
        _req(3.0, 3.00, [3.20], queue_wait=0.0),                # ttft 200, no gap
        _req(9.5, 9.50, [10.4, 10.5], queue_wait=0.5),          # due inside
        _req(10.5, 10.5, [10.6, 10.7]),                         # due after: out
        _req(4.0, 4.0, [], reason="shed_timeout", queue_wait=None),  # failed
        _req(5.0, None, []),                                    # never offered
    ]
    ctx = _ctx(reqs)
    assert ttft_percentile.read(ctx, 50) == pytest.approx(250.0)
    assert ttft_percentile.read(ctx, 100) == pytest.approx(900.0)
    # gaps: 100 ms, 200 ms, 100 ms (the one-token request has none)
    assert tpot_percentile.read(ctx, 50) == pytest.approx(100.0)
    assert tpot_percentile.read(ctx, 100) == pytest.approx(200.0)
    assert generator_late_percentile.read(ctx, 100) == pytest.approx(50.0)
    assert queue_wait_percentile.read(ctx, 100) == pytest.approx(500.0)


def test_token_rate_counts_requests_completed_in_the_window():
    reqs = [_req(0, 0, [0.5, 1.0, 1.5]), _req(0, 0, [4.0, 5.5]),
            _req(0, 0, [1.0], reason=None)]
    ctx = _ctx(reqs, seconds=5.0, loop="closed", window_s=5.2)
    assert serve_token_rate.read(ctx) == pytest.approx(3 / 5.2)


def test_training_readers():
    ctx = types.SimpleNamespace(record={
        "kind": "train", "batch": 4, "window_s": 2.0,
        "steps": [(0.5, 0.7), (1.0, 0.69), (1.4, 0.68), (2.0, 0.6)],
    })
    assert train_rate.read(ctx) == pytest.approx(8.0)
    assert train_step_percentile.read(ctx, 50) == pytest.approx(500.0)


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = _ctx([])
    assert ttft_percentile.read(empty, 50) is None
    assert tpot_percentile.read(empty, 95) is None
    train = types.SimpleNamespace(record={"kind": "train", "steps": []})
    assert train_rate.read(train) is None
    assert serve_token_rate.read(train) is None
