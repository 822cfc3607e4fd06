"""The generator offers the same work under every seed."""

import json
import os

import numpy as np
import pytest

from perfbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC = os.path.join(os.path.dirname(HERE), "traffic")


def _mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


def _shape(items):
    gaps = np.diff([0.0] + [it.due_s for it in items])
    return (
        sorted(len(it.prompt) for it in items),
        sorted(it.max_new for it in items),
        sorted(np.round(gaps, 9)),
    )


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 17])
def test_open_loop_same_multiset_other_order(seed):
    mix = _mix("shortchat-steady")
    base = traffic.generate(mix, 0, 45.0, 32768)
    other = traffic.generate(mix, seed, 45.0, 32768)
    block = round(mix["rate_per_s"] * 45.0 / mix["blocks"])
    assert len(base) == len(other) == block * mix["blocks"]
    b, o = _shape(base), _shape(other)
    assert b[0] == o[0] and b[1] == o[1]
    assert np.allclose(b[2], o[2], atol=1e-8)
    # The arrival instants are the same in every run; which lengths
    # arrive when is the seed's.
    assert [i.due_s for i in base] == [i.due_s for i in other]
    assert [len(i.prompt) for i in base] != [len(i.prompt) for i in other]
    assert [i.prompt for i in base] != [i.prompt for i in other]


def test_open_loop_every_part_of_the_window_holds_the_same_load():
    mix = _mix("shortchat-steady")
    items = traffic.generate(mix, 11, 51.0, 32768)
    blocks = mix["blocks"]
    block = len(items) // blocks
    first = _shape(items[:block])
    for k in range(1, blocks):
        part = items[k * block:(k + 1) * block]
        assert sorted(len(i.prompt) for i in part) == first[0]
        assert sorted(i.max_new for i in part) == first[1]
        # Each part spans its share of the window.
        assert part[-1].due_s - items[k * block - 1].due_s == pytest.approx(
            51.0 * (1 - 0.5 / len(items)) / blocks)


def test_open_loop_is_reproducible_and_inside_the_window():
    mix = _mix("shortchat-steady")
    a = traffic.generate(mix, 7, 45.0, 32768)
    b = traffic.generate(mix, 7, 45.0, 32768)
    assert [(i.due_s, i.prompt, i.max_new) for i in a] == [
        (i.due_s, i.prompt, i.max_new) for i in b]
    due = [i.due_s for i in a]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 45.0
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= len(i.prompt) <= hi for i in a)
    assert all(1 <= t < 32768 for i in a for t in i.prompt)


@pytest.mark.parametrize("seed", [3, 2**31 + 5])
def test_closed_loop_every_block_holds_the_same_lengths(seed):
    mix = _mix("longprompt-backlog")
    items = traffic.generate(mix, seed, 45.0, 32768)
    block = mix["block"]
    assert len(items) == block * mix["blocks"]
    first = sorted(len(i.prompt) for i in items[:block])
    first_out = sorted(i.max_new for i in items[:block])
    for k in range(1, 4):
        part = items[k * block:(k + 1) * block]
        assert sorted(len(i.prompt) for i in part) == first
        assert sorted(i.max_new for i in part) == first_out
    assert all(i.due_s == 0.0 for i in items)


def test_quantiles_follow_the_distribution():
    q = traffic.quantiles({"dist": "exponential"}, 1000)
    assert abs(q.mean() - 1.0) < 0.01
    g = traffic.quantiles({"dist": "gamma", "cv": 3.0}, 2000)
    assert abs(g.mean() - 1.0) < 0.05 and g.std() > 2.0
    ln = traffic.int_lengths(
        {"dist": "lognormal", "median": 24, "sigma": 0.7, "min": 8, "max": 128},
        999)
    assert ln.min() >= 8 and ln.max() <= 128 and ln[499] == 24


def test_train_batches_same_shapes_rows_differ():
    mix = {"loop": "train", "batch": 16, "seq_len": 32}
    a = next(traffic.train_batches(mix, 1, 1000, 2))
    b = next(traffic.train_batches(mix, 2, 1000, 2))
    assert a["input_ids"].shape == b["input_ids"].shape == (16, 32)
    assert a["input_ids"].dtype == np.int32
    assert len({tuple(r) for r in a["input_ids"]}) == 16
    assert not np.array_equal(a["input_ids"], b["input_ids"])
    assert set(np.unique(a["label"])) <= {0, 1}
