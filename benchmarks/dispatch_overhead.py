"""Isolate per-step host dispatch overhead: single vs fused-K dispatch.

The round-5 bench left BERT-base stuck at 0.527 MFU across three rounds
while BERT-large reached 0.73 on the same pipeline — the gap is not
math, it is per-step overhead: one compiled-step dispatch per Python
iteration pays host dispatch latency every ~170 ms step, and
proportionally more on every cheaper
step (ResNet-18's 9 ms steps drown in it). ``fit(steps_per_dispatch=K)``
amortizes that cost K-fold; this benchmark measures exactly the delta:

    per_step_ms(K=1) - per_step_ms(K=k)  ->  dispatch overhead recovered

Standalone run (tiny BERT so it finishes anywhere, CPU included):

    python benchmarks/dispatch_overhead.py [--ks 1,2,4,8,16]

``bench.py`` imports :func:`time_fused_per_step` to measure the
headline BERT-base ``fused_dispatch_speedup`` / ``step_dispatch_
overhead_ms`` fields on the real chip, so the plateau stays trackable
across future rounds.
"""

from __future__ import annotations

import time

import numpy as np


def _sync_scalar(metrics) -> float:
    """Close a timing window with ONE scalar host readback (the repo's
    timing protocol). Works for scalar and [K]-stacked metric leaves."""
    loss = np.asarray(metrics["loss"])
    return float(loss.reshape(-1)[-1])


def time_single_per_step(
    step, state, batch, rng, warmup: int = 5, steps: int = 20
):
    """Seconds per step of the single-dispatch path. Returns
    ``(per_step_seconds, state)`` — state is threaded through so a
    donating step stays usable by the caller afterwards."""
    for _ in range(warmup):
        state, metrics = step(state, batch, rng)
    _sync_scalar(metrics)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch, rng)
    _sync_scalar(metrics)
    return (time.perf_counter() - t0) / steps, state


def time_fused_per_step(
    step, state, window, rng, k: int,
    warmup_dispatches: int = 2, dispatches: int = 4,
):
    """Seconds per TRAIN STEP (not per dispatch) of the fused K-step
    program ``step.window_step`` over a pre-placed [K, B, ...] window.
    Returns ``(per_step_seconds, state)``."""
    for _ in range(warmup_dispatches):
        state, metrics = step.window_step(state, window, rng)
    _sync_scalar(metrics)
    t0 = time.perf_counter()
    for _ in range(dispatches):
        state, metrics = step.window_step(state, window, rng)
    _sync_scalar(metrics)
    return (time.perf_counter() - t0) / (dispatches * k), state


def stack_window(batch: dict, k: int) -> dict:
    """k copies of one host/device batch -> one [k, B, ...] host window
    (benchmark feed: the same batch repeated is fine for timing — the
    compiled program cannot tell)."""
    return {key: np.stack([np.asarray(v)] * k) for key, v in batch.items()}


def measure_dispatch_overhead(ks=(1, 2, 4, 8, 16), batch_size: int = 16):
    """Per-step wall time of a tiny BERT train step at each fused width
    in ``ks`` (1 = the single-dispatch baseline). Returns a dict with
    ``per_step_ms`` per K plus the recovered-overhead estimate."""
    import jax
    import jax.numpy as jnp
    import optax

    from tpudl.models.bert import BertConfig, BertForSequenceClassification
    from tpudl.runtime.mesh import MeshSpec, make_mesh
    from tpudl.train.loop import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    cfg = BertConfig(
        vocab_size=1024, hidden_size=64, num_layers=2, num_heads=2,
        intermediate_size=128, hidden_dropout=0.0, attention_dropout=0.0,
        dtype=jnp.float32,
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    rng_np = np.random.default_rng(0)
    batch = {
        "input_ids": rng_np.integers(0, 1024, (batch_size, 32)).astype(
            np.int32
        ),
        "attention_mask": np.ones((batch_size, 32), np.int32),
        "label": rng_np.integers(0, 2, (batch_size,)).astype(np.int32),
    }
    rng = jax.random.key(1)
    step_fn = make_classification_train_step(
        input_keys=("input_ids", "attention_mask"), label_key="label"
    )

    per_step_ms = {}
    for k in ks:
        model = BertForSequenceClassification(cfg)
        state = create_train_state(
            jax.random.key(0), model, jnp.zeros((1, 32), jnp.int32),
            optax.adamw(1e-3),
        )
        step = compile_step(
            step_fn, mesh, state, None, steps_per_dispatch=max(k, 1)
        )
        state = jax.device_put(state, step.state_shardings)
        if k == 1:
            placed = jax.device_put(batch, step.batch_sharding)
            dt, _ = time_single_per_step(step, state, placed, rng)
        else:
            window = jax.device_put(
                stack_window(batch, k), step.window_sharding
            )
            dt, _ = time_fused_per_step(step, state, window, rng, k)
        per_step_ms[k] = dt * 1e3

    base = per_step_ms.get(1)
    best_k = min(per_step_ms, key=per_step_ms.get)
    return {
        "per_step_ms": {str(k): round(v, 4) for k, v in per_step_ms.items()},
        "best_k": best_k,
        "step_dispatch_overhead_ms": (
            round(base - per_step_ms[best_k], 4) if base else None
        ),
        "fused_dispatch_speedup": (
            round(base / per_step_ms[best_k], 3) if base else None
        ),
    }


def main():
    import argparse
    import json

    ap = argparse.ArgumentParser(
        description="Per-step dispatch overhead: single vs fused-K "
        "training dispatch on a tiny BERT"
    )
    ap.add_argument(
        "--ks", default="1,2,4,8,16",
        help="comma-separated fused widths (1 = baseline)",
    )
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args()
    ks = tuple(int(x) for x in args.ks.split(","))
    print(json.dumps(measure_dispatch_overhead(ks, args.batch)))


if __name__ == "__main__":
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    main()
