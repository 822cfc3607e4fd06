"""Driver benchmark: one JSON line with the headline metrics.

BASELINE.json names two `metric` quantities; both are measured here on the
real chip, steady-state:

- BERT-base SST-2-shaped fine-tune samples/sec + MFU (the north-star
  acceptance is an MFU number, so it is first-class) — configs[1];
- ResNet-18 / CIFAR-10-shaped training images/sec/chip — configs[0]
  (continuity with the round-1 bank).

The reference publishes no numbers (`BASELINE.json` "published": {}), so
``vs_baseline`` compares against the values this repo banked in
BASELINE.md; a metric with no banked value reports 1.0 and its measurement
becomes the bank.

Timing protocol: every window is closed by a scalar host readback, and a
warmup burst absorbs the compile.
"""

import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import optax

from tpudl.runtime import use_hardware_rng

# Dropout-mask generation rides the TPU hardware RBG (+12% on the BERT
# fine-tune step vs the default threefry — tpudl/runtime/rng.py).
use_hardware_rng()

# Values banked in BASELINE.md (1x TPU v5 lite).
# Protocol correction (round 6, the BENCH_r05 0.923 investigation): the
# round-5 "best vs best" bank compared each round's SINGLE
# best-of-4-windows run against the MAX of four same-day
# best-of-4-windows runs (25.1k/29.9k/35.0k/36.9k -> 36.9k) — an
# order-statistic mismatch: one draw of a ±20% one-sided-noise metric
# almost never reaches the max of four draws, so the ratio reads < 1.0
# with no code change (the r05 bisect confirms: this bench feeds a
# synthetic device-resident batch and touches neither prefetch depth
# nor wire format). Corrected bank: the MEDIAN of those four
# same-protocol runs, so both sides of the ratio are single
# best-of-4-windows draws. The BERT metric's 170 ms steps hold ±1.5%
# and carry the headline; benchmarks/dispatch_overhead.py now tracks
# the dispatch stalls that make short-step metrics noisy in the first
# place.
BASELINE_RESNET_IMAGES_PER_SEC_BEST = 32_450.0
BASELINE_RESNET50_IMAGES_PER_SEC = 2482.6  # banked 2026-07-30 (round 2)
# Re-banked at batch 256 (round 2 close: 1320 samples/sec/chip) so
# vs_baseline is a like-for-like speedup at the same config — the old
# batch-32 bank (813) conflated a config change with optimization.
BASELINE_BERT_SAMPLES_PER_SEC = 1320.0

RESNET_BATCH = 256
RESNET_WARMUP_STEPS = 25
# ~9 ms/step. Host-side jitter on short steps is ONE-SIDED (stalls,
# never speedups) and measured up to 35% spread between whole runs
# (24.3k..36.9k img/s same day, same code); the steady-state capability
# is the BEST of several windows, so measure RESNET_WINDOWS of
# RESNET_MEASURE_STEPS each and report the max.
RESNET_MEASURE_STEPS = 100
RESNET_WINDOWS = 4
RESNET50_BATCH = 128
RESNET50_WARMUP_STEPS = 10
# ~50 ms/step: 48 steps give a ~2.4 s window (16 measured 10% run-to-run
# noise).
RESNET50_MEASURE_STEPS = 48
# Batch 256 keeps the MXU fed: 32 -> 256 raised measured MFU 34% -> 49%
# (sweep 2026-07-30); dropout stays at the standard fine-tune 0.1.
BERT_BATCH = 256
BERT_SEQ = 128
BERT_WARMUP_STEPS = 15
BERT_MEASURE_STEPS = 30
# Fused-dispatch comparison width: 8 steps per compiled dispatch (the
# tentpole's default recommendation; benchmarks/dispatch_overhead.py
# sweeps other widths).
BERT_FUSED_K = 8


def _bench_resnet():
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.models import ResNet18
    from tpudl.runtime import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    model = ResNet18(num_classes=10, small_inputs=True)
    state = create_train_state(
        jax.random.key(0),
        model,
        jnp.zeros((1, 32, 32, 3)),
        optax.sgd(0.1, momentum=0.9),
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    step = compile_step(make_classification_train_step(), mesh, state, None)

    batch = next(
        synthetic_classification_batches(
            RESNET_BATCH, image_shape=(32, 32, 3), num_classes=10
        )
    )
    batch = jax.device_put(batch)
    rng = jax.random.key(1)

    for _ in range(RESNET_WARMUP_STEPS):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])  # close the warmup window with a readback

    best = float("inf")
    for _ in range(RESNET_WINDOWS):
        start = time.perf_counter()
        for _ in range(RESNET_MEASURE_STEPS):
            state, metrics = step(state, batch, rng)
        float(metrics["loss"])
        best = min(best, time.perf_counter() - start)
    return RESNET_BATCH * RESNET_MEASURE_STEPS / best / jax.device_count()


def _bench_resnet50():
    """ResNet-50 at 224x224 — the BASELINE.json configs[2] headline shape
    (the reference's model: torchvision resnet50 at
    reference notebooks/cv/onnx_experiments.py:19,29-30)."""
    from tpudl.data.synthetic import synthetic_classification_batches
    from tpudl.models import ResNet50
    from tpudl.runtime import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )

    model = ResNet50(num_classes=1000)
    state = create_train_state(
        jax.random.key(0),
        model,
        jnp.zeros((1, 224, 224, 3)),
        optax.sgd(0.1, momentum=0.9),
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    step = compile_step(make_classification_train_step(), mesh, state, None)

    batch = next(
        synthetic_classification_batches(
            RESNET50_BATCH, image_shape=(224, 224, 3), num_classes=1000
        )
    )
    batch = jax.device_put(batch)
    rng = jax.random.key(1)

    for _ in range(RESNET50_WARMUP_STEPS):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])

    start = time.perf_counter()
    for _ in range(RESNET50_MEASURE_STEPS):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])
    elapsed = time.perf_counter() - start
    return RESNET50_BATCH * RESNET50_MEASURE_STEPS / elapsed / jax.device_count()


def _bench_bert(fused_ops=False, warmup=None, measure=None,
                precision=None):
    """BERT-base fine-tune step: samples/sec/chip and MFU (compiled-cost
    FLOPs, 6ND transformer fallback).

    ``precision`` (a tpudl.train.precision preset name) measures the
    SAME workload under that mixed-precision policy — the ROADMAP
    item-6 training variant, reported as ``bert_base_mfu_bf16`` next
    to the headline. Lean step counts, and the fused-dispatch
    sub-bench is skipped (measured once, on the headline path).

    ``fused_ops=True`` measures the SAME workload with the fused
    epilogue tier on (Pallas LayerNorm+residual / bias+GeLU via
    ``BertConfig.fused_ops`` and the fused cross-entropy via
    ``loss_impl="auto"``) — the ROADMAP item-1 variant, reported as
    ``bert_base_mfu_fused_ops`` next to the headline until it earns the
    default. Lean step counts for the variant keep total bench runtime
    bounded."""
    from tpudl.data.synthetic import synthetic_token_batches
    from tpudl.models.registry import build_model
    from tpudl.runtime import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )
    from tpudl.train.metrics import (
        compiled_flops,
        device_peak_flops,
        mfu,
        transformer_train_flops,
    )

    from tpudl.config import get_config
    from tpudl.train.optim import make_optimizer

    # The real configs[1] optimizer stack (AdamW, bf16 first moment —
    # +2.6% step throughput, benchmarks/bert_mu_dtype.py) at a constant
    # LR so steady-state steps are identical.
    import dataclasses

    ocfg = dataclasses.replace(
        get_config("sst2_bert_base").optim, schedule="constant", warmup_steps=0
    )
    warmup = BERT_WARMUP_STEPS if warmup is None else warmup
    measure = BERT_MEASURE_STEPS if measure is None else measure
    model_kwargs = {"fused_ops": True} if fused_ops else {}
    model = build_model("bert-base", num_classes=2, **model_kwargs)
    state = create_train_state(
        jax.random.key(0),
        model,
        jnp.zeros((1, BERT_SEQ), jnp.int32),
        make_optimizer(ocfg),
        precision=precision,
    )
    num_params = sum(p.size for p in jax.tree.leaves(state.params))
    mesh = make_mesh(MeshSpec(dp=-1))
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label",
            loss_impl="auto" if fused_ops else "reference",
            precision=precision,
        ),
        mesh,
        state,
        None,
        precision=precision,
    )

    batch = next(
        synthetic_token_batches(BERT_BATCH, seq_len=BERT_SEQ, vocab_size=30_522)
    )
    # Explicit placement to the step's shardings, then ONE AOT compile
    # serves both the cost analysis (the compiled-cost MFU basis banked
    # since round 2) and the stepping — lowering separately for
    # cost_analysis would pay a duplicate multi-minute BERT compile.
    state = jax.device_put(state, step.state_shardings)
    batch = jax.device_put(batch, step.batch_sharding)
    rng = jax.device_put(
        jax.random.key(1),
        jax.sharding.NamedSharding(
            step.batch_sharding.mesh, jax.sharding.PartitionSpec()
        ),
    )
    # Lower under the active mesh: constrain() activation constraints
    # are trace-time thread-local no-ops otherwise, and this executable
    # is the one actually benchmarked (on one chip they clamp away; on a
    # real slice dropping them would benchmark a different program than
    # training runs).
    from tpudl.parallel.sharding import active_mesh

    with active_mesh(step.batch_sharding.mesh):
        compiled = step.jitted.lower(state, batch, rng).compile()
    flops = compiled_flops(compiled)
    if flops is None:
        flops = transformer_train_flops(num_params, BERT_BATCH * BERT_SEQ)
    step = compiled  # donation/shardings baked into the executable

    for _ in range(warmup):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])

    start = time.perf_counter()
    for _ in range(measure):
        state, metrics = step(state, batch, rng)
    float(metrics["loss"])
    elapsed = time.perf_counter() - start

    step_seconds = elapsed / measure
    samples_per_sec = BERT_BATCH / step_seconds / jax.device_count()

    # Fused K-step dispatch (tpudl/train/loop.py steps_per_dispatch):
    # the same step scanned 8x inside ONE executable, so the per-step
    # host dispatch cost — the suspected driver of the three-round
    # 0.527-MFU plateau — is paid once per 8 steps. The headline metric
    # above stays the default single-dispatch path (the new path is off
    # by default); this delta quantifies what turning it on recovers.
    # Skipped for the fused-ops variant (measured once, on the headline
    # path).
    if fused_ops or precision is not None:
        return samples_per_sec, mfu(
            flops, step_seconds, jax.device_count(),
            device_peak_flops(),
        ), {}
    from benchmarks.dispatch_overhead import (
        stack_window,
        time_fused_per_step,
    )

    step8 = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"),
            label_key="label",
        ),
        mesh,
        state,
        None,
        steps_per_dispatch=BERT_FUSED_K,
    )
    window = jax.device_put(
        stack_window(batch, BERT_FUSED_K), step8.window_sharding
    )
    fused_step_seconds, _ = time_fused_per_step(
        step8, state, window, rng, BERT_FUSED_K,
        warmup_dispatches=2, dispatches=4,
    )
    fused = {
        "step_dispatch_overhead_ms": round(
            (step_seconds - fused_step_seconds) * 1e3, 3
        ),
        "fused_dispatch_speedup": round(
            step_seconds / fused_step_seconds, 3
        ),
    }

    return samples_per_sec, mfu(
        flops, step_seconds, jax.device_count(), device_peak_flops()
    ), fused


def _bench_bert_large():
    """BERT-large at configs[3]'s declared global batch 256 (4x64
    gradient-accumulation microbatches — the round-4 lever stack: bf16
    first moment, state donation, in-step accumulation; BASELINE.md).
    Lean step counts: this is the secondary metric."""
    import optax

    from tpudl.data.synthetic import synthetic_token_batches
    from tpudl.models.bert import BERT_LARGE, BertForSequenceClassification
    from tpudl.runtime import MeshSpec, make_mesh
    from tpudl.train import (
        compile_step,
        create_train_state,
        make_classification_train_step,
    )
    from tpudl.train.metrics import (
        device_peak_flops,
        mfu,
        transformer_train_flops,
    )

    batch, accum = 256, 4
    mesh = make_mesh(MeshSpec(dp=-1))
    model = BertForSequenceClassification(BERT_LARGE())
    state = create_train_state(
        jax.random.key(0),
        model,
        jnp.zeros((1, BERT_SEQ), jnp.int32),
        optax.adamw(2e-5, weight_decay=0.01, mu_dtype=jnp.bfloat16),
    )
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label",
            accum_steps=accum,
        ),
        mesh,
        state,
        None,
    )
    data = jax.device_put(
        next(synthetic_token_batches(batch, seq_len=BERT_SEQ,
                                     vocab_size=30_522)),
        step.batch_sharding,
    )
    state = jax.device_put(state, step.state_shardings)
    rng = jax.device_put(
        jax.random.key(1),
        jax.sharding.NamedSharding(
            step.batch_sharding.mesh, jax.sharding.PartitionSpec()
        ),
    )
    flops = transformer_train_flops(n_params, batch * BERT_SEQ)
    # ONE AOT compile serves both the stepping and the compiled-cost MFU
    # basis (same pattern as _bench_bert — the step compiles exactly once
    # either way). cost_analysis counts the accumulation scan BODY once
    # (one batch/accum microbatch — XLA does not multiply loop trip
    # counts), so the true step cost is accum x the reported flops; the
    # ratio guard below catches a jax version changing that behavior
    # (BASELINE.md round-5 row: body/6ND-per-microbatch ratio is ~0.93).
    from tpudl.train.metrics import compiled_flops
    from tpudl.parallel.sharding import active_mesh

    with active_mesh(step.batch_sharding.mesh):
        compiled = step.jitted.lower(state, data, rng).compile()
    body_flops = compiled_flops(compiled)
    flops_compiled = None
    if body_flops is not None and 0.5 < body_flops / (flops / accum) < 1.1:
        flops_compiled = body_flops * accum
    step = compiled
    # Lean counts: each accumulated step is ~450 ms and very stable
    # (4 scanned microbatches average out per-step noise), and bench.py's
    # total runtime must stay comfortably inside the driver's window.
    for _ in range(4):
        state, m = step(state, data, rng)
    float(m["loss"])
    start = time.perf_counter()
    n = 6
    for _ in range(n):
        state, m = step(state, data, rng)
    float(m["loss"])
    dt = (time.perf_counter() - start) / n
    peak = device_peak_flops()
    return (
        batch / dt / jax.device_count(),
        mfu(flops, dt, jax.device_count(), peak),
        mfu(flops_compiled, dt, jax.device_count(), peak)
        if flops_compiled is not None
        else None,
    )


def _bench_input_pipeline():
    """Host input-pipeline feeding rate (images/sec delivered to the
    device, model-free — benchmarks/input_pipeline.py): the perf
    trajectory must capture the feeding rate, not just what the chips do
    with the batches (an input-bound model regresses here first)."""
    from benchmarks.input_pipeline import measure_both

    legacy, pipelined = measure_both(
        rows=8_192, batch_size=256, measure_batches=24
    )
    return legacy, pipelined


def _bench_serve():
    """Serving headline: continuous-batching tokens/sec, p99 TTFT, and
    the speedup over run-to-completion static batching at equal slots
    (benchmarks/serve_load.py — tiny-Llama engine, warmed up, ragged
    request mix)."""
    from benchmarks.serve_load import measure_serve

    return measure_serve(n_requests=16, num_slots=4)


def _bench_serve_replicas():
    """Multi-replica serving tier (benchmarks/serve_load.py): routed
    2-replica tokens/sec + scaling efficiency on the ragged mix
    (simulated per-step device latency — see the benchmark docstring)
    and resident slots per GB of the int8 paged KV cache. Banked by
    scripts/bench_regress.py from r06 onward (new keys enter the bank
    as no-baseline on their first round)."""
    from benchmarks.serve_load import measure_serve_replicas

    return measure_serve_replicas()


def _bench_fleet():
    """Fleet observability + autoscaling tier (benchmarks/
    serve_load.py): the scale-up-to-burn-clear recovery time of the
    SLO-driven autoscaler under 2x overload, and the FleetMonitor's
    per-cycle real-HTTP scrape overhead. Banked by
    scripts/bench_regress.py from r06 onward (lower is better for
    both)."""
    from benchmarks.serve_load import measure_fleet

    return measure_fleet()


def _bench_fleet_mesh():
    """Pod-real fleet tier (tpudl.fleet via benchmarks/fleet_mesh.py):
    elastic reshard-restore wall time (4-device checkpoint onto an
    8-device mesh), routed throughput over two 4-device MeshReplicas,
    and the chip mover's burn-to-cleared time for the full
    preempt -> shrink -> serve -> drain -> grow scenario. Runs as a
    subprocess: the forced host-device count must be set before jax
    imports, which this process has long since done."""
    import subprocess

    env = dict(os.environ)
    # Set, not defaulted: this process holds the chip, and a child sent
    # after it by an inherited JAX_PLATFORMS fails or hangs.
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fleet_mesh", "--json"],
        capture_output=True, text=True, timeout=1800, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"fleet_mesh subprocess failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}"
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _bench_parity_grid():
    """Low-precision serving grid (benchmarks/parity_grid.py): every
    precision x backend cell parity-gated against the f32 reference,
    reporting the int8-weights simulated-device TPOT, the quantized
    weight-bytes ratio, and the number of cells that passed. Banked by
    scripts/bench_regress.py from r06 onward."""
    from benchmarks.parity_grid import measure_parity_grid

    return measure_parity_grid()


def _bench_prefix_spec():
    """Prefix-sharing + speculative-decoding tier (benchmarks/
    serve_load.py): p50 TTFT on the 50%-shared-prefix ragged mix with
    radix sharing on (asserted >= 2x under no-sharing inside the
    benchmark), accepted-tokens-per-step of the greedy int8 self-draft
    (asserted >= 2), and speculative tokens/sec on the simulated
    device (asserted above the non-speculative baseline). Banked from
    r07 onward (new keys enter as no-baseline on their first round)."""
    from benchmarks.serve_load import measure_prefix_spec

    return measure_prefix_spec()


def _bench_block_pins():
    """ROADMAP item-1 follow-through: run the fused-epilogue
    block-size sweep and record the winning env pins in the JSON tail,
    so a TPU round's evidence for flipping fused defaults is banked
    next to the metrics it would move. Off-TPU the sweep runs the tiny
    smoke shapes (interpret-mode Pallas) — plumbing-checkable, but the
    pins that matter come from the driver's TPU rounds."""
    from benchmarks.fused_epilogue import block_pins, sweep_args, sweep_blocks
    from tpudl.ops.attention import is_tpu_backend

    best = sweep_blocks(sweep_args(smoke=not is_tpu_backend()), measure=5)
    pins, command = block_pins(best)
    return {"per_family": best, "pins": pins, "command": command}


def _bench_tenants():
    """Multi-tenant LoRA serving tier (tpudl.serve.lora +
    tpudl.ops.segmented_lora via benchmarks/serve_load.py --tenants):
    resident adapters per GB of pool (byte-accurate arithmetic),
    heterogeneous batched decode tokens/sec at 64 resident adapters
    (asserted >= 2x over the sequential per-tenant-dispatch baseline
    inside the benchmark), and the tenant-isolation p99 TTFT ratio
    under one tenant's 4x overload (asserted <= 1.3x solo)."""
    from benchmarks.serve_load import measure_tenants

    return measure_tenants()


def _bench_chaos():
    """Serving fault tolerance (tpudl.serve migration + chaos via
    benchmarks/serve_load.py --chaos): p99 latency of draining a
    loaded replica (page-granular KV migration makes it ~payload
    transfer, asserted < 10% of the longest in-flight generation) and
    the median client-visible token gap across a mid-decode replica
    preemption (zero re-prefill, generate()-parity asserted inside the
    benchmark). Banked from r08 onward (lower is better for both)."""
    from benchmarks.serve_load import measure_chaos

    return measure_chaos()


def _bench_requestlog():
    """Durable request-log tier (tpudl.obs.requestlog via
    benchmarks/serve_load.py): p99 TTFT with logging on vs off under
    the closed-loop serve mix (the never-blocks-the-decode-loop claim,
    measured) and on-disk bytes per logged request, with the
    rotation + per-tenant reconciliation round-trip asserted on the
    way. Banked from r16 onward (lower is better for both)."""
    from benchmarks.serve_load import measure_requestlog

    return measure_requestlog()


def _bench_flywheel():
    """Data-flywheel tier (tpudl.flywheel via benchmarks/
    serve_load.py): the steady-state refresh latency — one
    ``FlywheelController.poll()`` wall time (log flush -> filter ->
    LoRA train -> safe hot-swap) with the train step pre-compiled —
    and the ingestion tax: serving p99 TTFT with sample capture + the
    durable log on over the same closed-loop mix with them off. The
    serve -> refresh -> swap cycle is asserted end-to-end inside the
    benchmark. Banked from r18 onward (lower is better for both)."""
    from benchmarks.serve_load import measure_flywheel

    return measure_flywheel()


def _bench_ft():
    """Fault-tolerance costs (benchmarks/ft_recovery.py): the async
    checkpoint's on-step stall and the kill-to-first-post-restart-step
    recovery time — the two numbers a preemptible-capacity run budget
    is built from."""
    from benchmarks.ft_recovery import measure_ft

    return measure_ft()


def _bench_train_precision():
    """Mixed-precision TRAINING tier (tpudl.train.precision +
    tpudl.ops.fp8_dot via benchmarks/train_precision.py): every
    precision cell loss-parity gated against the f32 control on a
    fixed-seed run (the assertion lives in the benchmark), the fp8
    cell's weight+activation bytes-moved ratio (the speedup ceiling;
    >= 2x asserted, model says ~4x), and the passed-cell count —
    the training-side mirror of the serving parity grid."""
    from benchmarks.train_precision import run_precision_sweep
    from tpudl.ops.attention import is_tpu_backend

    sweep = run_precision_sweep(smoke=not is_tpu_backend())
    return {
        "train_precision_parity_cells": sweep["parity_cells_passed"],
        "train_precision_parity_cells_total": sweep[
            "parity_cells_total"
        ],
        "train_fp8_bytes_ratio": sweep.get(
            "fp8_weight_act_bytes_ratio"
        ),
    }


def _regression_gate(result: dict, strict: bool) -> int:
    """Advisory noise-aware regression check of this run against the
    banked BENCH_r*.json history (scripts/bench_regress.py — the
    median-of-bank protocol BASELINE.md derived from the r05 false
    alarm). Prints the per-metric table to stderr; only ``--strict``
    turns a regression into a nonzero exit, so the driver's JSON line
    always lands."""
    try:
        from scripts.bench_regress import (
            default_history_paths,
            format_rows,
            gate,
            normalize_round,
        )

        rows = gate(normalize_round(result), default_history_paths())
    except Exception:
        print("bench_regress gate failed:", file=sys.stderr)
        traceback.print_exc()
        # Under --strict an inoperative gate IS a failure — a CI job
        # whose purpose is gating must not go green with the gate
        # crashed. Advisory mode still reports the JSON line and moves
        # on.
        return 2 if strict else 0
    print(format_rows(rows), file=sys.stderr)
    regressions = [r["metric"] for r in rows if r["status"] == "regression"]
    if regressions:
        print(f"REGRESSION vs banked history: {', '.join(regressions)}",
              file=sys.stderr)
        return 1 if strict else 0
    return 0


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when a metric regresses beyond "
                    "its noise band vs the banked BENCH_r*.json history")
    args = ap.parse_args(argv)

    # No chip, no benchmark: a CPU run of these tiers is not a
    # measurement, and must not print one.
    device = jax.devices()[0]
    device_info = {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(jax.devices()),
    }
    print(f"bench device: {json.dumps(device_info)}", file=sys.stderr)
    if device.platform != "tpu":
        print("bench.py needs a TPU: refusing to start on "
              f"{device.platform!r}", file=sys.stderr)
        return 2

    failed = []

    def tier(name, fn, default):
        """One optional tier: a raise leaves the traceback on stderr, the
        tier's fields null, and the run's exit code non-zero."""
        try:
            return fn()
        except Exception:
            print(f"{name} failed:", file=sys.stderr)
            traceback.print_exc()
            failed.append(name)
            return default

    bert_sps, bert_mfu, bert_fused = _bench_bert()
    # Fused-epilogue variant (BertConfig.fused_ops=True +
    # loss_impl="auto"): the ROADMAP item-1 measured variant, lean
    # step counts.
    fo_sps, fo_mfu, _ = tier(
        "fused-ops bench variant",
        lambda: _bench_bert(fused_ops=True, warmup=10, measure=20),
        (None, None, None),
    )
    # Mixed-precision training variant (tpudl.train.precision "bf16"
    # policy: rule-cast bf16 compute, f32 masters, f32 reductions) —
    # the ROADMAP item-6 training half, lean step counts like the
    # fused-ops variant.
    bf16_sps, bf16_mfu, _ = tier(
        "bf16-precision bench variant",
        lambda: _bench_bert(precision="bf16", warmup=10, measure=20),
        (None, None, None),
    )
    resnet_ips = _bench_resnet()
    resnet50_ips = _bench_resnet50()
    bl_sps, bl_mfu, bl_mfu_compiled = _bench_bert_large()
    pipe_legacy, pipe_new = tier(
        "input-pipeline bench", _bench_input_pipeline, (None, None)
    )
    serve = tier("serve bench", _bench_serve, {})
    serve_replicas = tier(
        "serve replica bench", _bench_serve_replicas, {}
    )
    fleet = tier("fleet autoscale bench", _bench_fleet, {})
    tenants = tier("multi-tenant bench", _bench_tenants, {})
    chaos_tier = tier("serve chaos bench", _bench_chaos, {})
    rlog = tier("request-log bench", _bench_requestlog, {})
    flywheel = tier("flywheel bench", _bench_flywheel, {})
    ft = tier("fault-tolerance bench", _bench_ft, {})
    fleet_mesh = tier("fleet mesh bench", _bench_fleet_mesh, {})
    parity_grid = tier("parity-grid bench", _bench_parity_grid, {})
    prefix_spec = tier("prefix/spec bench", _bench_prefix_spec, {})
    block_pins = tier("block-pin sweep", _bench_block_pins, {})
    train_prec = tier(
        "train-precision bench", _bench_train_precision, {}
    )

    vs_baseline = (
        bert_sps / BASELINE_BERT_SAMPLES_PER_SEC
        if BASELINE_BERT_SAMPLES_PER_SEC
        else 1.0
    )
    result = {
        "device": device_info,
        "metric": "bert_base_sst2_train_throughput",
        "value": round(bert_sps, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": round(vs_baseline, 3),
        "mfu": round(bert_mfu, 4),
        "bert_batch": BERT_BATCH,
        # Fused K-step dispatch (steps_per_dispatch=8) vs the
        # single-dispatch headline above: per-step wall-time
        # delta and ratio (benchmarks/dispatch_overhead.py has
        # the width sweep). The headline path stays
        # single-dispatch — the fused path is opt-in.
        "step_dispatch_overhead_ms": bert_fused.get(
            "step_dispatch_overhead_ms"
        ),
        "fused_dispatch_speedup": bert_fused.get(
            "fused_dispatch_speedup"
        ),
        # Fused-epilogue kernel tier (tpudl/ops norms/mlp_fused/
        # cross_entropy behind BertConfig.fused_ops + loss_impl):
        # the same BERT-base workload with the Pallas epilogue
        # kernels on — the ROADMAP item-1 attack (target MFU
        # >= 0.65), measured as a variant until it earns the
        # default. benchmarks/fused_epilogue.py has the
        # per-kernel decomposition.
        "bert_base_mfu_fused_ops": round(fo_mfu, 4)
        if fo_mfu is not None
        else None,
        "bert_base_fused_ops_samples_per_sec": round(fo_sps, 1)
        if fo_sps is not None
        else None,
        # Mixed-precision training tier (tpudl.train.precision +
        # tpudl.ops.fp8_dot via benchmarks/train_precision.py): the
        # bf16-policy BERT-base MFU variant, the fp8 cell's
        # weight+activation bytes-moved ratio vs f32 (the speedup
        # ceiling — the bytes model says ~4x, >= 2x asserted in the
        # benchmark), and the loss-parity cell count (every cell
        # gated against the fixed-seed f32 control inside the
        # benchmark; a failed gate raises there, so a banked count
        # means every band held).
        "bert_base_mfu_bf16": round(bf16_mfu, 4)
        if bf16_mfu is not None
        else None,
        "bert_base_bf16_samples_per_sec": round(bf16_sps, 1)
        if bf16_sps is not None
        else None,
        "train_fp8_bytes_ratio": train_prec.get(
            "train_fp8_bytes_ratio"
        ),
        "train_precision_parity_cells": train_prec.get(
            "train_precision_parity_cells"
        ),
        "resnet50_imagenet_images_per_sec_chip": round(resnet50_ips, 1),
        "resnet50_vs_baseline": round(
            resnet50_ips / BASELINE_RESNET50_IMAGES_PER_SEC, 3
        )
        if BASELINE_RESNET50_IMAGES_PER_SEC
        else 1.0,
        "resnet18_images_per_sec_chip_best_of_windows": round(
            resnet_ips, 1
        ),
        # Ratio base corrected round 6: median (not max) of the
        # banked same-protocol best-of-4-windows runs, so both
        # sides are single draws — see BASELINE.md (the r05
        # 0.923 was the max-of-4 denominator bias, not a
        # regression).
        "resnet18_vs_baseline_like_protocol": round(
            resnet_ips / BASELINE_RESNET_IMAGES_PER_SEC_BEST, 3
        ),
        # configs[3] building block at its DECLARED batch 256 via
        # 4x64 accumulation (round 4; r3 banked 356 samples/s,
        # 46.5% MFU at batch 64 monolithic).
        "bert_large_samples_per_sec_chip": round(bl_sps, 1),
        "bert_large_mfu_6nd": round(bl_mfu, 4),
        # Compiled-cost basis (the honest one — see BASELINE.md
        # round-5 row): live AOT cost_analysis x accum, None if
        # the counted-once ratio guard tripped.
        "bert_large_mfu_compiled": round(bl_mfu_compiled, 4)
        if bl_mfu_compiled is not None
        else None,
        # Host feeding rate (model-free, benchmarks/
        # input_pipeline.py): uint8-wire two-stage pipeline, with
        # the pre-overhaul f32 single-worker feed as its ratio
        # base — the perf trajectory of the INPUT path.
        "input_pipeline_images_per_sec_host": round(pipe_new, 1)
        if pipe_new is not None
        else None,
        "input_pipeline_vs_legacy_feed": round(
            pipe_new / pipe_legacy, 3
        )
        if pipe_new is not None and pipe_legacy
        else None,
        # Serving engine (tpudl.serve via benchmarks/
        # serve_load.py): continuous-batching throughput, tail
        # TTFT, and the continuous-vs-static speedup at equal
        # slot count on the ragged request mix.
        "serve_tokens_per_sec": serve.get("serve_tokens_per_sec"),
        "serve_p99_ttft_ms": serve.get("serve_p99_ttft_ms"),
        "serve_vs_static_batching": serve.get(
            "serve_vs_static_batching"
        ),
        # Dispatch hygiene (tpudl.analysis wired into serve_load's
        # timed window): backend compiles observed during the decode
        # steady state. Expected 0; bench_regress gates this
        # zero-tolerance (any positive draw is a regression — a
        # shape/dtype/static arg quietly varying per step).
        "serve_steady_state_recompiles": serve.get(
            "serve_steady_state_recompiles"
        ),
        # Multi-replica router tier (tpudl.serve.router): routed
        # 2-replica throughput, scaling efficiency vs 2x one
        # replica, and the int8 paged KV cache's resident slots
        # per GB (the capacity lever paging + quantization buy).
        "serve_tokens_per_sec_2rep": serve_replicas.get(
            "serve_tokens_per_sec_2rep"
        ),
        "serve_scaling_efficiency": serve_replicas.get(
            "serve_scaling_efficiency"
        ),
        "serve_kv_slots_per_gb": serve_replicas.get(
            "serve_kv_slots_per_gb"
        ),
        # Fleet observability + autoscaling tier (tpudl.obs.fleet +
        # tpudl.serve.autoscale via benchmarks/serve_load.py): how
        # long the SLO-driven control loop takes from scale-up to
        # burn-clear under 2x overload, and the FleetMonitor's
        # per-cycle HTTP scrape cost over live exporters.
        "autoscale_recovery_s": fleet.get("autoscale_recovery_s"),
        "fleet_scrape_overhead_ms": fleet.get(
            "fleet_scrape_overhead_ms"
        ),
        # Multi-tenant LoRA serving (tpudl.serve.lora adapter pool +
        # the segmented-LoRA kernel via benchmarks/serve_load.py
        # --tenants): resident adapters per GB of pool, batched
        # heterogeneous decode throughput at 64 resident adapters
        # (>= 2x sequential per-tenant dispatch asserted in the
        # benchmark), and the victims' p99 TTFT ratio under one
        # tenant's 4x overload (quota isolation, <= 1.3x asserted).
        "serve_adapters_per_gb": tenants.get("serve_adapters_per_gb"),
        "serve_tokens_per_sec_64adapters": tenants.get(
            "serve_tokens_per_sec_64adapters"
        ),
        "serve_tenant_isolation_p99_ratio": tenants.get(
            "serve_tenant_isolation_p99_ratio"
        ),
        # Serving fault tolerance (tpudl.serve KV migration + chaos
        # harness via benchmarks/serve_load.py --chaos): p99 drain of
        # a loaded replica (migration-based — ~transfer time, not the
        # longest generation) and the median failover token gap a
        # client sees across a mid-decode preemption.
        "serve_drain_p99_ms": chaos_tier.get("serve_drain_p99_ms"),
        "failover_token_gap_ms": chaos_tier.get(
            "failover_token_gap_ms"
        ),
        # Durable request log (tpudl.obs.requestlog via benchmarks/
        # serve_load.py): p99 TTFT with the log enabled over the same
        # closed-loop mix with it disabled (the bounded-queue writer's
        # never-blocks-the-decode-loop claim, measured), and on-disk
        # bytes per logged request (rotation + per-tenant token
        # reconciliation asserted inside the benchmark).
        "requestlog_overhead_p99_ttft_ratio": rlog.get(
            "requestlog_overhead_p99_ttft_ratio"
        ),
        "requestlog_bytes_per_request": rlog.get(
            "requestlog_bytes_per_request"
        ),
        # Data flywheel (tpudl.flywheel via benchmarks/serve_load.py):
        # the steady-state refresh lag — one controller poll's wall
        # time from record threshold to refreshed factors swapped in
        # (train step pre-compiled) — and the ingestion tax, serving
        # p99 TTFT with sample capture + the durable log on vs off
        # over the same closed-loop mix (the serve -> refresh -> swap
        # cycle asserted inside the benchmark).
        "flywheel_refresh_latency_s": flywheel.get(
            "flywheel_refresh_latency_s"
        ),
        "flywheel_serving_p99_impact_ratio": flywheel.get(
            "flywheel_serving_p99_impact_ratio"
        ),
        # Pod-real fleet tier (tpudl.fleet via benchmarks/
        # fleet_mesh.py, subprocess): elastic reshard-restore wall
        # time for a 4-device checkpoint onto an 8-device mesh (the
        # payload MB rides for the bytes model), routed tokens/sec
        # over two 4-device tensor-parallel MeshReplicas, and the
        # chip mover's burn-to-cleared time across the full
        # preempt -> shrink -> serve -> drain -> grow scenario
        # (zero dropped results asserted inside the benchmark).
        "fleet_reshard_restore_s": fleet_mesh.get(
            "fleet_reshard_restore_s"
        ),
        "fleet_reshard_payload_mb": fleet_mesh.get(
            "fleet_reshard_payload_mb"
        ),
        "serve_tokens_per_sec_2mesh": fleet_mesh.get(
            "serve_tokens_per_sec_2mesh"
        ),
        "chipmover_burn_cleared_s": fleet_mesh.get(
            "chipmover_burn_cleared_s"
        ),
        # Fault tolerance (tpudl.ft via benchmarks/
        # ft_recovery.py): the async checkpoint's mean on-step
        # stall (vs the synchronous save of the same payload)
        # and the kill-to-first-post-restart-step recovery
        # time.
        "checkpoint_step_stall_ms": round(
            ft["checkpoint_step_stall_ms"], 2
        )
        if "checkpoint_step_stall_ms" in ft
        else None,
        "checkpoint_sync_save_ms": round(
            ft["checkpoint_sync_save_ms"], 2
        )
        if "checkpoint_sync_save_ms" in ft
        else None,
        "recovery_time_sec": round(ft["recovery_time_sec"], 3)
        if "recovery_time_sec" in ft
        else None,
        # Low-precision serving grid (tpudl.quant via benchmarks/
        # parity_grid.py): simulated-device TPOT of the int8-weights
        # cell, the stored-bytes ratio on its quantized layers
        # (>= 3.5x asserted in the benchmark), and how many
        # precision x backend cells passed their parity gate.
        "serve_tpot_int8_weights_ms": parity_grid.get(
            "serve_tpot_int8_weights_ms"
        ),
        "quant_weight_bytes_ratio": parity_grid.get(
            "quant_weight_bytes_ratio"
        ),
        "parity_grid_cells_passed": parity_grid.get(
            "parity_grid_cells_passed"
        ),
        # Prefix-sharing + speculative decoding (tpudl.serve radix
        # cache + speculate via benchmarks/serve_load.py): p50 TTFT on
        # the 50%-shared-prefix mix with sharing on (the benchmark
        # asserts >= 2x vs no-sharing), per-stream accepted tokens per
        # speculative window (>= 2 asserted), and speculative
        # tokens/sec on the simulated device (beats the plain paged
        # baseline, asserted).
        "serve_ttft_shared_prefix_ms": prefix_spec.get(
            "serve_ttft_shared_prefix_ms"
        ),
        "spec_accepted_tokens_per_step": prefix_spec.get(
            "spec_accepted_tokens_per_step"
        ),
        "serve_tokens_per_sec_spec": prefix_spec.get(
            "serve_tokens_per_sec_spec"
        ),
        # JSON tail: the fused-epilogue block-size sweep's winning
        # pins (benchmarks/fused_epilogue.py --sweep-blocks) — the
        # evidence a TPU round uses to flip fused defaults. Non-numeric
        # on purpose; the regression gate skips them.
        "fused_block_pins": block_pins.get("pins"),
        "fused_block_pin_cmd": block_pins.get("command"),
    }
    print(json.dumps(result))
    gate_rc = _regression_gate(result, strict=args.strict)
    if failed:
        print(f"FAILED tiers: {', '.join(failed)}", file=sys.stderr)
        return 1
    return gate_rc


if __name__ == "__main__":
    sys.exit(main())
