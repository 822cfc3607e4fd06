"""One general traffic generator, driven by a data file.

A mix is a JSON file of parameters (``perfbench/traffic/<mix>.json``).
Every seed offers the SAME multiset of arrival gaps and lengths: the
values are the distribution's quantiles at (i + 1/2)/n. The arrival
instants are the same in every run too (the gaps are put in an order
drawn from the file's own ``arrival_seed``); ``--seed`` shuffles which
lengths arrive when and draws the token ids. So runs differ in order,
never in the amount of work or in when it arrives: where requests bunch
up decides a tail, and a tail that moved with the seed could not be
judged (PERF.md, PR 23).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the generator offers it."""

    rid: int
    due_s: float  # open loop: when it is due; closed loop: 0.0
    prompt: List[int]
    max_new: int


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``dist``'s quantiles at (i + 1/2)/n, ascending.

    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` (clipped)
    ``{"dist": "exponential"}`` (mean 1)
    ``{"dist": "gamma", "cv"}`` (mean 1, coefficient of variation cv)
    ``{"dist": "constant", "value"}``
    """
    q = _grid(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(vals, dist["min"], dist["max"])
    if kind == "exponential":
        return -np.log1p(-q)
    if kind == "gamma":
        from scipy.stats import gamma

        shape = 1.0 / float(dist["cv"]) ** 2
        return gamma.ppf(q, shape, scale=1.0 / shape)
    if kind == "constant":
        return np.full(n, float(dist["value"]))
    raise ValueError(f"unknown distribution {kind!r}")


def int_lengths(dist: dict, n: int) -> np.ndarray:
    return np.maximum(np.rint(quantiles(dist, n)).astype(np.int64), 1)


def _shuffled(values: np.ndarray, rng: np.random.Generator, block: int):
    """Shuffle inside consecutive blocks of ``block`` values, each block
    holding the same quantile grid — any whole number of blocks offers
    the same multiset."""
    out = []
    for start in range(0, len(values), block):
        chunk = values[start:start + block].copy()
        rng.shuffle(chunk)
        out.append(chunk)
    return np.concatenate(out)


def generate(mix: dict, seed: int, seconds: float, vocab_size: int) -> List[Item]:
    """The requests of one run.

    Open loop (``"loop": "open"``, ``rate_per_s``, ``blocks``): the
    window is cut into ``blocks`` equal parts of round(rate x seconds /
    blocks) requests each; every part holds the same quantile grid of
    gaps (rescaled to span its part of the window, ordered by
    ``arrival_seed``) and of lengths (ordered by ``seed``), so that the
    load is the same in every part of every run. Closed loop (``"loop": "closed"``,
    ``clients``, ``block``, ``blocks``): ``blocks`` blocks of ``block``
    requests; each block holds the same quantile grid of lengths.
    """
    rng = np.random.default_rng(seed)
    if mix["loop"] == "open":
        blocks = int(mix.get("blocks", 1))
        block = max(1, int(round(mix["rate_per_s"] * seconds / blocks)))
        n = block * blocks
        gaps = _shuffled(
            np.tile(quantiles(mix["gaps"], block), blocks),
            np.random.default_rng(int(mix["arrival_seed"])), block,
        )
        # Every gap precedes its request; the last request falls half a
        # mean gap before the window's end, so all n are due inside it.
        gaps = gaps * (seconds * (1.0 - 0.5 / n) / gaps.sum())
        due = np.cumsum(gaps)
    elif mix["loop"] == "closed":
        block = int(mix["block"])
        n = block * int(mix["blocks"])
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    reps = n // block
    prompts = _shuffled(
        np.tile(int_lengths(mix["prompt_tokens"], block), reps), rng, block
    )
    outputs = _shuffled(
        np.tile(int_lengths(mix["output_tokens"], block), reps), rng, block
    )
    ids = rng.integers(1, vocab_size, size=int(prompts.sum()), dtype=np.int64)
    items, at = [], 0
    for i in range(n):
        k = int(prompts[i])
        items.append(Item(i, float(due[i]), ids[at:at + k].tolist(),
                          int(outputs[i])))
        at += k
    return items


def train_batches(mix: dict, seed: int, vocab_size: int, num_classes: int):
    """Endless batches of a classification job (``"loop": "train"``):
    ``batch`` rows of ``seq_len`` token ids whose label is signalled by
    how often a class's marker token occurs (learnable by attention, as
    SST-2's sentiment words are), every row different, all from the
    seed. Every seed offers the same shapes."""
    rng = np.random.default_rng(seed)
    batch, seq_len = int(mix["batch"]), int(mix["seq_len"])
    markers = rng.integers(10, vocab_size, size=(num_classes,))
    k = max(1, seq_len // 8)
    while True:
        labels = rng.integers(0, num_classes, size=(batch,))
        ids = rng.integers(10, vocab_size, size=(batch, seq_len))
        pos = rng.integers(1, seq_len, size=(batch, k))
        ids[np.arange(batch)[:, None], pos] = markers[labels][:, None]
        ids[:, 0] = 1
        yield {
            "input_ids": ids.astype(np.int32),
            "attention_mask": np.ones((batch, seq_len), np.int32),
            "label": labels.astype(np.int32),
        }
