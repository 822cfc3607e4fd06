"""CV training workloads (BASELINE.json configs[0] and configs[2]).

The CV training the reference lineage runs through HorovodRunner/Lightning
on GPU clusters, as config-driven TPU runs:

  python notebooks/cv/train_cifar10.py                                # configs[0]
  python notebooks/cv/train_cifar10.py --config imagenet_resnet50_dp  # configs[2]

--config selects the BASELINE.json entry: model, dataset schema +
materializer, mesh, strategy, optimizer, label smoothing, and gradient
accumulation all come from tpudl.config. The declared mesh auto-clamps to
the local device count (MeshSpec.fit), so the same command drives one
chip or a pod slice. configs[2]'s declared global batch 1024 is realized
on a single 16G chip via accum_steps (microbatches scanned inside the
compiled step — tpudl.train.loop.microbatch).

--data-dir points at a Parquet dataset in the config's schema, fed
through the converter layer (pass --materialize to generate a synthetic
one there first); without it, an in-memory synthetic stream is used.

L5 composition (SURVEY.md §5.3-§5.5): --checkpoint-dir saves/RESUMES
through tpudl.checkpoint.CheckpointManager (kill the run, rerun the same
command, training continues), --log-dir streams metrics through
MetricLogger (JSONL + TensorBoard), and a held-out eval (true holdout —
last Parquet file, or the last rows of a single-file dataset) prints
final accuracy — the reference verifies model outputs every run
(reference notebooks/cv/onnx_experiments.py:98-100,178-184); so does
this.
"""

import argparse
import itertools
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp

from tpudl.config import get_config
from tpudl.data.converter import make_converter, prefetch_to_device
from tpudl.data.datasets import eval_stream, split_train_eval
from tpudl.data.synthetic import synthetic_classification_batches
from tpudl.models.registry import build_model
from tpudl.parallel.sharding import strategy_rules
from tpudl.runtime import make_mesh
from tpudl.train import (
    compile_step,
    create_train_state,
    evaluate,
    fit,
    make_classification_eval_step,
    make_classification_train_step,
)
from tpudl.train.metrics import compiled_flops, device_peak_flops, mfu
from tpudl.train.optim import make_optimizer

#: CV configs this driver accepts, with their dataset materializers.
CV_CONFIGS = ("cifar10_resnet18", "imagenet_resnet50_dp")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default="cifar10_resnet18",
                        choices=CV_CONFIGS,
                        help="BASELINE.json config to drive")
    parser.add_argument("--steps", type=int, default=200,
                        help="total optimizer-step budget (warmup included)")
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--accum", type=int, default=None,
                        help="gradient-accumulation microbatches "
                        "(default: config accum_steps)")
    parser.add_argument("--data-dir", type=str, default=None,
                        help="Parquet dataset directory (config schema)")
    parser.add_argument("--materialize", action="store_true",
                        help="generate a synthetic dataset into --data-dir first")
    parser.add_argument("--ingest", type=str, default=None,
                        help="REAL dataset to ingest into --data-dir "
                        "Parquet before training (tpudl.data.ingest): the "
                        "CIFAR-10 python archive (cifar-10-python.tar.gz "
                        "or its extracted directory) for cifar10 configs, "
                        "or a class-subdirectory JPEG/PNG tree (ImageNet "
                        "train/ layout) for imagenet-shape configs")
    parser.add_argument("--rows", type=int, default=None,
                        help="rows to materialize (default: dataset-specific)")
    parser.add_argument("--strategy", type=str, default=None,
                        help="override config strategy: dp | fsdp")
    parser.add_argument("--checkpoint-dir", type=str, default=None,
                        help="CheckpointManager directory: saves every "
                        "--checkpoint-every steps and RESUMES from the "
                        "latest checkpoint on restart")
    parser.add_argument("--checkpoint-every", type=int, default=50)
    parser.add_argument("--log-dir", type=str, default=None,
                        help="MetricLogger directory (JSONL + TensorBoard)")
    parser.add_argument("--eval-steps", type=int, default=8,
                        help="held-out eval batches after training (0 = off)")
    parser.add_argument("--mfu-compiled", action="store_true",
                        help="exact compiled-cost FLOPs for an MFU print "
                        "(pays a second full XLA compile at the end)")
    args = parser.parse_args()
    if (args.materialize or args.ingest) and not args.data_dir:
        parser.error("--materialize/--ingest require --data-dir")
    if args.ingest and not os.path.exists(args.ingest):
        parser.error(f"--ingest path does not exist: {args.ingest}")

    overrides = {}
    if args.strategy:
        overrides["strategy"] = args.strategy
    if args.checkpoint_dir:
        overrides["checkpoint_dir"] = args.checkpoint_dir
    cfg = get_config(args.config, **overrides)
    batch_size = args.batch or cfg.global_batch_size
    accum = args.accum if args.accum is not None else cfg.accum_steps
    is_cifar = cfg.dataset == "cifar10"

    mesh_spec = cfg.mesh.fit(jax.device_count())
    mesh = make_mesh(mesh_spec)
    model = build_model(cfg.model, cfg.num_classes, small_inputs=is_cifar)
    state = create_train_state(
        jax.random.key(cfg.seed),
        model,
        jnp.zeros((1, cfg.image_size, cfg.image_size, 3)),
        make_optimizer(cfg.optim),
    )
    num_params = sum(p.size for p in jax.tree.leaves(state.params))
    print(f"{cfg.name}: {cfg.model} {num_params / 1e6:.1f}M params, "
          f"batch {batch_size} (accum {accum}), image {cfg.image_size}, "
          f"strategy {cfg.strategy}, mesh {dict(mesh.shape)}")
    rules = strategy_rules(cfg.strategy)
    # Parquet-fed runs ship uint8 over the host->device link and
    # normalize ON DEVICE (fused into the first conv) — 4x less transfer
    # (tpudl.data.augment.device_normalize). The synthetic stream is
    # already f32.
    from tpudl.data.augment import (
        CIFAR10_MEAN,
        CIFAR10_STD,
        IMAGENET_MEAN,
        IMAGENET_STD,
        device_normalize,
    )

    norm_mean = CIFAR10_MEAN if is_cifar else IMAGENET_MEAN
    norm_std = CIFAR10_STD if is_cifar else IMAGENET_STD
    input_transform = (
        device_normalize(norm_mean, norm_std) if args.data_dir else None
    )
    step = compile_step(
        make_classification_train_step(
            cfg.label_smoothing, accum_steps=accum,
            input_transform=input_transform,
        ),
        mesh, state, rules,
    )

    warmup_steps = 2
    if args.data_dir:
        from tpudl.data.augment import BatchAugmenter
        from tpudl.data.datasets import (
            materialize_cifar10_like,
            materialize_imagenet_like,
        )

        if args.ingest:
            from tpudl.data.ingest import ingest_cifar10, ingest_image_folder

            if is_cifar:
                conv = ingest_cifar10(args.ingest, args.data_dir)
            else:
                conv = ingest_image_folder(
                    args.ingest, args.data_dir, image_size=cfg.image_size,
                )
            print(f"ingested {args.ingest} -> {args.data_dir} "
                  f"({conv.num_rows} rows)")
        elif args.materialize:
            if is_cifar:
                conv = materialize_cifar10_like(
                    args.data_dir, num_rows=args.rows or 50_000
                )
            else:
                conv = materialize_imagenet_like(
                    args.data_dir, num_rows=args.rows or 8_192,
                    image_size=cfg.image_size, num_classes=cfg.num_classes,
                )
        else:
            conv = make_converter(args.data_dir)
        train_conv, eval_conv = split_train_eval(conv)
        # Standard training augmentation (pad+random crop + flip) in
        # uint8 on the host; normalization happens on device
        # (input_transform above).
        augment = BatchAugmenter(
            crop=(cfg.image_size, cfg.image_size),
            pad=4 if is_cifar else 8, seed=cfg.seed,
            mean=norm_mean, std=norm_std, normalize=False,
        )
        # Augmentation is passed to the PREFETCHER (below), not the
        # converter: converter transforms run inside the source lock,
        # one at a time; the prefetcher's assembly pool crops/flips N
        # batches in parallel.
        raw = train_conv.make_batch_iterator(
            batch_size, epochs=None, shuffle=True, seed=cfg.seed,
        )
        host_transform = augment

        # Eval path: SAME device normalization, center crop, no flip.
        eval_augment = BatchAugmenter(
            crop=(cfg.image_size, cfg.image_size), pad=0, hflip=False,
            train=False, mean=norm_mean, std=norm_std, normalize=False,
        )

        def _eval_normalize(b):
            out = eval_augment(b)
            out["label"] = out["label"].astype("int32")
            return out

        eval_raw = eval_stream(
            eval_conv, batch_size, _eval_normalize,
            batch_divisor=mesh.shape["dp"] * mesh.shape["fsdp"],
        )
    else:
        host_transform = None  # synthetic stream is already f32
        raw = synthetic_classification_batches(
            batch_size,
            image_shape=(cfg.image_size, cfg.image_size, 3),
            num_classes=cfg.num_classes,
            seed=cfg.seed,
            num_batches=args.steps + warmup_steps,
        )

        def eval_raw():
            # Held-out synthetic stream: same distribution, disjoint seed.
            return synthetic_classification_batches(
                batch_size,
                image_shape=(cfg.image_size, cfg.image_size, 3),
                num_classes=cfg.num_classes,
                seed=cfg.seed + 10_000,
                num_batches=args.eval_steps,
            )

    # Checkpoint/resume: restore the latest state if the directory has
    # one; fast-forward the stream so a killed run rerun with the same
    # flags continues where it stopped.
    ckpt_mgr = None
    start_step = 0
    if cfg.checkpoint_dir:
        from tpudl.checkpoint import CheckpointManager
        from tpudl.train import resume_latest

        ckpt_mgr = CheckpointManager(cfg.checkpoint_dir)
        state, start_step = resume_latest(ckpt_mgr, state, mesh, rules)
        if start_step:
            print(f"resumed from step {start_step} ({cfg.checkpoint_dir})")

    # Prefetch either stream: explicit placement overlaps the host->device
    # transfer with compute (jit's implicit numpy-arg transfer runs
    # inside the dispatch). Parquet-fed runs
    # get an assembly pool (row-group decode + uint8 augmentation
    # parallelize host-side); the in-memory synthetic stream needs none.
    # Depth autotunes off the data-wait p95 (TPUDL_PREFETCH_DEPTH pins).
    # Fast-forward a resumed run on the HOST side (before device
    # prefetch) so skipped batches never pay a transfer.
    if start_step:
        raw = itertools.islice(iter(raw), start_step, None)
    batches = iter(
        prefetch_to_device(
            raw, mesh=mesh, transform=host_transform,
            assembly_workers=4 if host_transform is not None else 1,
        )
    )
    rng = jax.random.key(cfg.seed + 1)

    logger = None
    if args.log_dir:
        from tpudl.train import MetricLogger

        logger = MetricLogger(args.log_dir)

    def log(i, metrics):
        print(f"step {i}: loss {metrics['loss']:.4f} acc {metrics['accuracy']:.3f}")
        if logger:
            logger(start_step + i, metrics)

    # Warmup outside the timing window, closed by a readback (compile is
    # synchronous, but program upload + first execution on the chip is
    # async behind the dispatch).
    # --steps is the TOTAL optimizer-step budget (warmup included); a run
    # resumed at or past the budget trains zero further steps.
    budget = max(args.steps - start_step, 0)
    wsteps = min(warmup_steps, budget)
    remaining = budget - wsteps
    warm = None
    for _ in range(wsteps):
        state, warm = step(state, next(batches), rng)
    if warm is not None:
        float(warm["loss"])
    state, metrics, info = fit(
        step, state, itertools.islice(batches, remaining), rng,
        log_every=cfg.log_every, logger=log,
        checkpoint_manager=ckpt_mgr,
        checkpoint_every=args.checkpoint_every if ckpt_mgr else 0,
    )
    print(f"final: {metrics}")

    if args.eval_steps:
        eval_step = compile_step(
            make_classification_eval_step(input_transform=input_transform),
            mesh, state, rules, has_rng=False
        )
        eval_metrics = evaluate(
            eval_step, state, eval_raw(), num_steps=args.eval_steps
        )
        print(
            f"held-out eval (<= {args.eval_steps} batches): "
            f"loss {eval_metrics['loss']:.4f} "
            f"accuracy {eval_metrics['accuracy']:.3f}"
        )
        if logger:
            logger(start_step + info["steps"],
                   {f"eval_{k}": v for k, v in eval_metrics.items()})
    if logger:
        logger.close()
    if info["steps"] == 0:
        from tpudl.train import finalize_zero_step_run

        print(finalize_zero_step_run(ckpt_mgr, state, wsteps))
        return
    images_per_sec = batch_size * info["steps"] / max(info["seconds"], 1e-9)
    line = (
        f"throughput ~{images_per_sec:.0f} images/sec over {info['steps']} "
        f"steady-state steps (compile + warmup excluded)"
    )
    # MFU from the compiled executable's FLOPs (SURVEY.md §5.5) — opt-in:
    # lower().compile() pays a SECOND full XLA compile.
    if args.mfu_compiled:
        try:
            example = next(synthetic_classification_batches(
                batch_size, image_shape=(cfg.image_size, cfg.image_size, 3),
                num_classes=cfg.num_classes, num_batches=1,
            ))
            if input_transform is not None:
                # Parquet-fed runs train on uint8-wire batches; the
                # lowered example must match or the FLOPs describe a
                # program that never ran.
                example = dict(
                    example,
                    image=(example["image"] * 255).clip(0, 255).astype(
                        "uint8"
                    ),
                )
            flops = compiled_flops(step.jitted.lower(state, example, rng))
            if flops:
                step_seconds = info["seconds"] / max(info["steps"], 1)
                line += (
                    f"; MFU ~{100 * mfu(flops, step_seconds, jax.device_count()):.1f}%"
                    f" (peak {device_peak_flops() / 1e12:.0f} TFLOP/s/chip)"
                )
        except Exception:
            pass
    print(line)


if __name__ == "__main__":
    main()
