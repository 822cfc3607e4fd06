"""BERT fine-tune workloads (BASELINE.json configs[1] and configs[3]).

  python notebooks/nlp/train_sst2.py                              # configs[1]
  python notebooks/nlp/train_sst2.py --config bert_large_v4_32    # configs[3]

The NLP workload the reference declares but never ships (reference
notebooks/nlp/README.md is an empty placeholder — SURVEY.md §0), built
TPU-native: Flax BERT through the attend() seam, Optax AdamW with warmup,
pjit over the (dp, fsdp, sp, tp) mesh, samples/sec + MFU reported the way
BASELINE.json `metric`/`north_star` ask. configs[3] is the
HorovodRunner -> TpuDistributor migration config: its declared
(dp=-1, fsdp=4) mesh clamps to the local chip count, and its global
batch fits small meshes via gradient accumulation (--accum).

--data-dir points at an SST-2-schema Parquet dataset fed through the
converter layer (pass --materialize to generate a synthetic one there
first); without it, an in-memory synthetic stream is used. In an
environment with network access, real pretrained weights drop in via
tpudl.models.params_from_hf_bert on a HuggingFace state_dict (parity
guaranteed by tests/test_bert.py::test_hf_weight_import_logits_parity).

Run: python notebooks/nlp/train_sst2.py [--steps N] [--model bert-tiny]
"""

import argparse
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp

from tpudl.config import get_config
from tpudl.data.converter import make_converter, prefetch_to_device
from tpudl.data.datasets import eval_stream, split_train_eval
from tpudl.data.synthetic import synthetic_token_batches
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
from tpudl.train.metrics import (
    compiled_flops,
    device_peak_flops,
    mfu,
    transformer_train_flops,
)
from tpudl.train.optim import make_optimizer


#: NLP fine-tune configs this driver accepts (configs[1] and configs[3];
#: configs[4]'s LoRA vertical is notebooks/nlp/finetune_lora.py).
NLP_CONFIGS = ("sst2_bert_base", "bert_large_v4_32")


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default="sst2_bert_base",
                        choices=NLP_CONFIGS,
                        help="BASELINE.json config to drive; the declared "
                        "mesh auto-clamps to the local device count "
                        "(MeshSpec.fit), so bert_large_v4_32 trains on one "
                        "chip and shards fsdp=4 on a pod")
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--accum", type=int, default=None,
                        help="gradient-accumulation microbatches "
                        "(default: config accum_steps)")
    parser.add_argument("--remat", type=str, default=None,
                        choices=["none", "layer", "attention", "dots"],
                        help="rematerialization scope for BERT models "
                        "(default: model default; 'dots' = layer remat "
                        "with the dots_saveable policy)")
    parser.add_argument("--model", type=str, default=None,
                        help="override config model (e.g. bert-tiny for smoke)")
    parser.add_argument("--seq-len", type=int, default=None)
    parser.add_argument("--data-dir", type=str, default=None,
                        help="SST-2-schema Parquet dataset directory")
    parser.add_argument("--materialize", action="store_true",
                        help="generate a synthetic dataset into --data-dir first")
    parser.add_argument("--ingest", type=str, default=None,
                        help="REAL GLUE SST-2 TSV (train.tsv or the SST-2 "
                        "directory): ingested into the --text-data text "
                        "Parquet before tokenization (tpudl.data.ingest)")
    parser.add_argument(
        "--text-data", action="store_true",
        help="raw-text vertical: materialize a TEXT-schema dataset "
        "(sentence, label) under --data-dir, train a first-party WordPiece "
        "vocab on it, tokenize into an ids dataset, and fine-tune on that "
        "— text -> ids -> fine-tune in one command",
    )
    parser.add_argument("--strategy", type=str, default=None,
                        help="override config strategy: dp | fsdp | tp | "
                        "fsdp+tp | pp | pp+fsdp")
    parser.add_argument("--mesh", type=str, default=None,
                        help="dp,fsdp,sp,tp[,pp[,ep]] (e.g. 2,1,1,1,4)")
    parser.add_argument("--microbatches", type=int, default=4,
                        help="GPipe microbatches (strategy=pp only)")
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
                        help="exact compiled-cost FLOPs for the MFU print "
                        "(pays a second full XLA compile; default: 6ND "
                        "estimate)")
    args = parser.parse_args()
    if (args.materialize or args.text_data) and not args.data_dir:
        parser.error("--materialize/--text-data require --data-dir")
    if args.ingest and not args.text_data:
        parser.error("--ingest feeds the raw-text vertical: add --text-data")

    overrides = {}
    if args.model:
        overrides["model"] = args.model
    if args.strategy:
        overrides["strategy"] = args.strategy
    if args.checkpoint_dir:
        overrides["checkpoint_dir"] = args.checkpoint_dir
    if args.mesh:
        from tpudl.runtime import MeshSpec

        overrides["mesh"] = MeshSpec(
            *(int(x) for x in args.mesh.split(","))
        )
    cfg = get_config(args.config, **overrides)
    batch_size = args.batch or cfg.global_batch_size
    seq_len = args.seq_len or cfg.seq_len
    accum = args.accum if args.accum is not None else cfg.accum_steps

    model_kwargs = {}
    if args.remat:
        from tpudl.models.bert import remat_options

        model_kwargs.update(remat_options(args.remat))

    # An explicit --mesh is taken literally; the config's declared mesh
    # clamps to whatever devices this host actually has.
    mesh_spec = cfg.mesh if args.mesh else cfg.mesh.fit(jax.device_count())
    mesh = make_mesh(mesh_spec)
    if cfg.strategy in ("pp", "pp+fsdp"):
        from tpudl.models.registry import build_pipelined_model

        model = build_pipelined_model(
            cfg.model, cfg.num_classes,
            num_stages=mesh.shape["pp"], num_microbatches=args.microbatches,
            param_fsdp=cfg.strategy == "pp+fsdp",
            **model_kwargs,
        )
    else:
        model = build_model(cfg.model, cfg.num_classes, **model_kwargs)
    sample_ids = jnp.zeros((1, seq_len), jnp.int32)
    state = create_train_state(
        jax.random.key(cfg.seed),
        model,
        sample_ids,
        make_optimizer(cfg.optim),
    )
    num_params = sum(
        p.size for p in jax.tree_util.tree_leaves(state.params)
    )
    print(f"{cfg.name}: {cfg.model} {num_params / 1e6:.1f}M params, "
          f"batch {batch_size} (accum {accum}), seq {seq_len}, "
          f"strategy {cfg.strategy}, mesh {dict(mesh.shape)}")

    rules = strategy_rules(cfg.strategy)
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label",
            accum_steps=accum,
        ),
        mesh,
        state,
        rules,
    )

    warmup_steps = 2
    if args.text_data:
        import os

        from tpudl.data.datasets import (
            materialize_sst2_text,
            normalize_sst2_batch,
            tokenize_text_dataset,
        )
        from tpudl.data.tokenizer import (
            WordPieceTokenizer,
            build_wordpiece_vocab,
        )

        from tpudl.data.converter import make_converter as _mk

        text_dir = os.path.join(args.data_dir, "text")
        ids_dir = os.path.join(args.data_dir, "ids")
        vocab_path = os.path.join(args.data_dir, "vocab.txt")
        if os.path.isdir(ids_dir) and not (args.materialize or args.ingest):
            # Petastorm contract: materialize once, train many. Pass
            # --materialize to force regeneration.
            print(f"reusing tokenized dataset {ids_dir} (vocab {vocab_path})")
            conv = _mk(ids_dir)
        else:
            if args.ingest:
                from tpudl.data.ingest import ingest_sst2_tsv

                text_conv = ingest_sst2_tsv(args.ingest, text_dir)
                print(f"ingested {args.ingest} -> {text_dir} "
                      f"({text_conv.num_rows} rows)")
            else:
                text_conv = materialize_sst2_text(text_dir, num_rows=8_192)
            corpus = (
                str(s)
                for b in text_conv.make_batch_iterator(
                    1024, epochs=1, shuffle=False, drop_last=False,
                    columns=("sentence",),
                )
                for s in b["sentence"]
            )
            tok = WordPieceTokenizer(build_wordpiece_vocab(corpus, 4096))
            tok.save_vocab(vocab_path)
            print(f"trained WordPiece vocab ({len(tok.vocab)} tokens) -> "
                  f"{vocab_path}")
            conv = tokenize_text_dataset(
                text_dir, ids_dir, tok, seq_len=seq_len
            )
        conv, eval_conv = split_train_eval(conv)
        # Wire casts run in the prefetcher's assembly pool (parallel,
        # outside the source lock), not inside the source iterator.
        raw = conv.make_batch_iterator(
            batch_size, epochs=None, shuffle=True, seed=cfg.seed
        )
        host_transform = normalize_sst2_batch
        eval_raw = eval_stream(
            eval_conv, batch_size, normalize_sst2_batch,
            batch_divisor=mesh.shape["dp"] * mesh.shape["fsdp"],
        )
    elif args.data_dir:
        from tpudl.data.datasets import materialize_sst2_like, normalize_sst2_batch

        if args.materialize:
            conv = materialize_sst2_like(
                args.data_dir, num_rows=8_192, seq_len=seq_len,
                vocab_size=model.cfg.vocab_size,
            )
        else:
            conv = make_converter(args.data_dir)
        conv, eval_conv = split_train_eval(conv)
        # Wire casts run in the prefetcher's assembly pool (parallel,
        # outside the source lock), not inside the source iterator.
        raw = conv.make_batch_iterator(
            batch_size, epochs=None, shuffle=True, seed=cfg.seed
        )
        host_transform = normalize_sst2_batch
        eval_raw = eval_stream(
            eval_conv, batch_size, normalize_sst2_batch,
            batch_divisor=mesh.shape["dp"] * mesh.shape["fsdp"],
        )
    else:
        host_transform = None  # synthetic stream is already wire-ready
        raw = synthetic_token_batches(
            batch_size,
            seq_len=seq_len,
            vocab_size=model.cfg.vocab_size,
            num_classes=cfg.num_classes,
            seed=cfg.seed,
            num_batches=args.steps + warmup_steps,
        )
        # Held-out synthetic stream: same distribution, disjoint seed.
        eval_raw = lambda: synthetic_token_batches(  # noqa: E731
            batch_size,
            seq_len=seq_len,
            vocab_size=model.cfg.vocab_size,
            num_classes=cfg.num_classes,
            seed=cfg.seed + 10_000,
            num_batches=args.eval_steps,
        )
    # Checkpoint/resume (SURVEY.md §5.3/§5.4): restore the latest state
    # if the directory has one; fast-forward the stream so a killed run
    # rerun with the same flags continues where it stopped.
    ckpt_mgr = None
    start_step = 0
    if cfg.checkpoint_dir:
        from tpudl.checkpoint import CheckpointManager
        from tpudl.train import resume_latest

        ckpt_mgr = CheckpointManager(cfg.checkpoint_dir)
        state, start_step = resume_latest(ckpt_mgr, state, mesh, rules)
        if start_step:
            print(f"resumed from step {start_step} ({cfg.checkpoint_dir})")

    # Fast-forward a resumed run on the HOST side (before device
    # prefetch), so skipped batches never pay a transfer; then prefetch:
    # explicit placement overlaps the host->device transfer with compute
    # (jit's implicit numpy-arg transfer runs inside the dispatch).
    import itertools

    if start_step:
        raw = itertools.islice(iter(raw), start_step, None)
    # The int64->int32 token casts run in the prefetcher's assembly pool
    # (outside the source lock, overlapped with the transfer stage);
    # depth autotunes off data-wait (TPUDL_PREFETCH_DEPTH pins it).
    batches = prefetch_to_device(
        raw, mesh=mesh, transform=host_transform,
        assembly_workers=2 if host_transform else 1,
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

    # Warmup outside the timing window, CLOSED BY A READBACK: the first
    # call pays the XLA compile synchronously, but the compiled program's
    # upload + first execution on the chip happens asynchronously
    # behind the dispatch — without the scalar sync it
    # lands inside the timed window and deflates samples/sec and MFU
    # (the BASELINE.json metrics are steady-state quantities).
    batches = iter(batches)
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
            make_classification_eval_step(
                input_keys=("input_ids", "attention_mask"), label_key="label"
            ),
            mesh,
            state,
            rules,
            has_rng=False,
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
    samples_per_sec = batch_size * info["steps"] / info["seconds"]
    # 6ND transformer estimate by default (the BASELINE.md basis);
    # --mfu-compiled opts into exact compiled-cost FLOPs, which pays a
    # SECOND full XLA compile via lower().compile() — minutes at
    # BERT-large scale, not worth it on every training run.
    flops = None
    if args.mfu_compiled:
        try:
            example = next(synthetic_token_batches(
                batch_size, seq_len=seq_len, vocab_size=model.cfg.vocab_size,
                num_batches=1,
            ))
            flops = compiled_flops(step.jitted.lower(state, example, rng))
        except Exception:
            pass
    if flops is None:
        flops = transformer_train_flops(num_params, batch_size * seq_len)
    step_seconds = info["seconds"] / max(info["steps"], 1)
    print(
        f"throughput ~{samples_per_sec:.0f} samples/sec over {info['steps']} "
        f"steady-state steps (compile excluded); "
        f"MFU ~{100 * mfu(flops, step_seconds, jax.device_count()):.1f}% "
        f"(peak {device_peak_flops() / 1e12:.0f} TFLOP/s/chip)"
    )


if __name__ == "__main__":
    main()
