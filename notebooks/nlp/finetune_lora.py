"""Llama LoRA fine-tune (BASELINE.json configs[4], the GSPMD stretch).

The reference declares this workload only through the driver north-star
(nothing exists in the reference tree — SURVEY.md §0). TPU-native shape:
a Llama decoder with rank-r adapters (tpudl.models.lora), frozen base via
optax.multi_transform (no optimizer moments for frozen weights — the
memory win that fits 8B), sharded by composed LORA+TP+FSDP rules over the
(dp, fsdp, sp, tp) mesh, classification from the last non-pad token.

Defaults run the tiny model so the script executes anywhere (including
the 8-device fake CPU mesh); pass --model llama3-8b-lora on a pod slice.
--text-data runs the Llama-family raw-text vertical: text corpus ->
first-party byte-level BPE (tpudl.data.bpe) -> ids Parquet -> LoRA
fine-tune, in one command (--ingest points it at a real GLUE SST-2 TSV).

Run: python notebooks/nlp/finetune_lora.py [--steps N] [--model llama-tiny-lora]
"""

import argparse
import itertools
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax
import jax.numpy as jnp

from tpudl.config import get_config
from tpudl.data.synthetic import synthetic_token_batches
from tpudl.models.lora import (
    LORA_RULES,
    compose_rules,
    lora_optimizer,
    trainable_param_count,
)
from tpudl.models.registry import build_model
from tpudl.parallel.sharding import TP_TRANSFORMER_RULES
from tpudl.runtime import MeshSpec, make_mesh
from tpudl.train import (
    MetricLogger,
    TrainState,
    compile_step,
    fit,
    make_classification_train_step,
)
from tpudl.train.optim import make_optimizer


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=100)
    parser.add_argument("--batch", type=int, default=16)
    parser.add_argument("--model", type=str, default="llama-tiny-lora")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--mesh", type=str, default=None,
                        help="dp,fsdp,sp,tp (e.g. 2,2,1,2); default all-dp")
    parser.add_argument("--log-dir", type=str, default=None)
    parser.add_argument("--data-dir", type=str, default=None,
                        help="dataset directory (required for --text-data)")
    parser.add_argument(
        "--text-data", action="store_true",
        help="raw-text vertical, Llama-style: materialize (or --ingest) a "
        "TEXT-schema dataset under --data-dir, train a first-party "
        "byte-level BPE vocab on it (tpudl.data.bpe), tokenize into an "
        "ids dataset, and LoRA-fine-tune on that — text -> BPE ids -> "
        "fine-tune in one command",
    )
    parser.add_argument("--ingest", type=str, default=None,
                        help="REAL GLUE SST-2 TSV (train.tsv or the SST-2 "
                        "directory) as the raw-text source")
    parser.add_argument("--materialize", action="store_true",
                        help="force re-materialization/re-tokenization of "
                        "--data-dir")
    parser.add_argument("--dtype", type=str, default="f32",
                        choices=["f32", "bf16"],
                        help="compute dtype (bf16 for real-scale runs; "
                        "f32 default keeps the tiny-model CI exact)")
    parser.add_argument("--attn", type=str, default="reference",
                        choices=["reference", "fused", "flash", "ring",
                                 "ulysses"],
                        help="attention implementation: 'fused'/'flash' "
                        "use the Pallas kernels (flash streams any length "
                        "with in-kernel dropout — the seq-2048 configs[4] "
                        "path); 'ring'/'ulysses' add sequence parallelism "
                        "over the sp mesh axis (both flash-bodied on TPU; "
                        "on one chip they degenerate to flash/reference)")
    parser.add_argument("--remat", action="store_true",
                        help="per-layer rematerialization (trade FLOPs "
                        "for HBM — how billion-param seq-2048 fits one "
                        "16G chip)")
    parser.add_argument(
        "--hf-checkpoint", type=str, default=None,
        help="local HuggingFace Llama checkpoint directory: base weights "
        "are grafted onto the model before LoRA fine-tuning (the actual "
        "configs[4] workload — pretrained, not random-init); adapters and "
        "the classifier head keep their fresh init",
    )
    args = parser.parse_args()
    if args.text_data and not args.data_dir:
        parser.error("--text-data requires --data-dir")
    if args.ingest and not args.text_data:
        parser.error("--ingest feeds the raw-text vertical: add --text-data")

    cfg = get_config("llama3_8b_lora", model=args.model)
    model = build_model(
        cfg.model, cfg.num_classes,
        dtype=jnp.bfloat16 if args.dtype == "bf16" else jnp.float32,
        attention_impl=args.attn,
        remat=args.remat,
    )

    sample = jnp.zeros((1, args.seq_len), jnp.int32)
    params = model.init(jax.random.key(cfg.seed), sample)["params"]
    if args.hf_checkpoint:
        import transformers

        from tpudl.models.llama import params_from_hf_llama

        hf = transformers.AutoModelForCausalLM.from_pretrained(
            args.hf_checkpoint, local_files_only=True
        )
        params = params_from_hf_llama(hf.state_dict(), like=params)
        print(f"grafted pretrained weights from {args.hf_checkpoint}")
    trainable, total = trainable_param_count(params, ("classifier",))
    print(f"{cfg.model}: {total/1e6:.1f}M params, "
          f"{trainable/1e6:.3f}M trainable ({100*trainable/total:.2f}%)")

    tx = lora_optimizer(make_optimizer(cfg.optim), params, ("classifier",))
    # Build the state directly from the already-initialized (possibly
    # HF-grafted) tree — create_train_state would run a second full init
    # only to throw it away (2x startup cost at 8B scale).
    state = TrainState.create(apply_fn=model.apply, params=params, tx=tx)

    if args.mesh:
        mesh_spec = MeshSpec(*(int(x) for x in args.mesh.split(",")))
    else:
        mesh_spec = MeshSpec(dp=-1)
    mesh = make_mesh(mesh_spec)
    rules = compose_rules(LORA_RULES, TP_TRANSFORMER_RULES)
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh,
        state,
        rules,
    )

    warmup = min(2, args.steps)
    if args.text_data:
        import os

        from tpudl.data.bpe import ByteBPETokenizer, train_bpe
        from tpudl.data.converter import make_converter as _mk
        from tpudl.data.datasets import (
            materialize_sst2_text,
            normalize_sst2_batch,
            tokenize_text_dataset,
        )

        text_dir = os.path.join(args.data_dir, "text")
        ids_dir = os.path.join(args.data_dir, "ids")
        bpe_dir = os.path.join(args.data_dir, "bpe")
        if os.path.isdir(ids_dir) and not (args.materialize or args.ingest):
            # Petastorm contract: materialize once, train many.
            print(f"reusing tokenized dataset {ids_dir} (BPE {bpe_dir})")
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
            tok = train_bpe(
                corpus, vocab_size=min(model.cfg.vocab_size, 4096)
            )
            tok.save(bpe_dir)
            print(f"trained byte-level BPE ({len(tok.vocab)} tokens, "
                  f"{len(tok.merges)} merges) -> {bpe_dir}")
            conv = tokenize_text_dataset(
                text_dir, ids_dir, tok, seq_len=args.seq_len
            )
        batches = (
            normalize_sst2_batch(b)
            for b in conv.make_batch_iterator(
                args.batch, epochs=None, shuffle=True, seed=cfg.seed
            )
        )
    else:
        batches = synthetic_token_batches(
            args.batch,
            seq_len=args.seq_len,
            vocab_size=model.cfg.vocab_size,
            num_classes=cfg.num_classes,
            seed=cfg.seed,
            num_batches=args.steps + warmup,
        )
    logger = MetricLogger(args.log_dir) if args.log_dir else None
    rng = jax.random.key(cfg.seed + 1)
    # Warmup fit absorbs compile so the throughput print is steady-state
    # (the repo-wide timing doctrine). islice hands fit exactly
    # `warmup` items: fit's own num_steps break would pull (and discard)
    # one extra batch from the shared generator.
    state, _, _ = fit(step, state, itertools.islice(batches, warmup), rng)
    state, metrics, info = fit(
        step,
        state,
        batches,
        rng,
        num_steps=args.steps,
        log_every=20,
        logger=logger,
    )
    if logger:
        logger.close()
    print(f"final: {metrics}")
    print(f"{args.batch * info['steps'] / info['seconds']:.1f} samples/sec "
          f"over {info['steps']} steady-state steps (compile excluded) on "
          f"mesh {dict(mesh.shape)}")


if __name__ == "__main__":
    main()
