"""Workload configurations.

The reference hardcodes every knob as a literal inside the notebook
(image dims at notebooks/cv/onnx_experiments.py:29-30, opset at :38,
artifact paths at :36,48, EP choice by commenting lines in/out at :81-83 —
"configuration by comment", SURVEY.md §5.6). Here each BASELINE.json
configs[i] entry is a dataclass with CLI overrides.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from tpudl.runtime.mesh import MeshSpec


@dataclasses.dataclass(frozen=True)
class OptimConfig:
    name: str = "adamw"  # adamw | sgd
    learning_rate: float = 1e-3
    warmup_steps: int = 100
    total_steps: int = 1000
    weight_decay: float = 1e-4
    momentum: float = 0.9  # sgd only
    b1: float = 0.9
    b2: float = 0.999
    #: AdamW first-moment dtype. bf16 halves that state's HBM footprint
    #: and traffic; the second moment stays f32 for numerical range.
    #: Default f32 so existing checkpoints restore unchanged — opt in
    #: per config.
    mu_dtype: str = "float32"  # float32 | bfloat16
    grad_clip_norm: Optional[float] = 1.0
    schedule: str = "cosine"  # cosine | constant | linear


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    name: str
    model: str  # resnet18 | resnet50 | bert-base | bert-large | llama3-8b-lora
    dataset: str  # cifar10 | imagenet | sst2
    global_batch_size: int = 128
    image_size: int = 32
    seq_len: int = 128
    num_classes: int = 10
    precision: str = "bf16"  # bf16 | f32
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    strategy: str = "dp"  # dp | fsdp | tp | fsdp+tp | lora | pp
    optim: OptimConfig = dataclasses.field(default_factory=OptimConfig)
    num_steps: int = 200
    log_every: int = 20
    #: Gradient-accumulation microbatches per optimizer step (>1 = the
    #: compiled step scans over microbatches — how a declared global batch
    #: larger than the mesh's memory fits; tpudl.train.loop.microbatch).
    accum_steps: int = 1
    label_smoothing: float = 0.0
    data_dir: Optional[str] = None  # parquet dir; None -> synthetic
    checkpoint_dir: Optional[str] = None
    seed: int = 0


# One config per BASELINE.json configs[i] (SURVEY.md §5.6).
CONFIGS = {
    # configs[0]: ResNet-18 on CIFAR-10, single-process smoke.
    "cifar10_resnet18": TrainConfig(
        name="cifar10_resnet18",
        model="resnet18",
        dataset="cifar10",
        global_batch_size=256,
        image_size=32,
        num_classes=10,
        optim=OptimConfig(name="sgd", learning_rate=0.1, warmup_steps=50,
                          total_steps=2000, weight_decay=5e-4),
        num_steps=2000,
    ),
    # configs[1]: BERT-base SST-2 fine-tune, single-process.
    "sst2_bert_base": TrainConfig(
        name="sst2_bert_base",
        model="bert-base",
        dataset="sst2",
        global_batch_size=32,
        seq_len=128,
        num_classes=2,
        optim=OptimConfig(name="adamw", learning_rate=2e-5, warmup_steps=100,
                          total_steps=2000, weight_decay=0.01,
                          mu_dtype="bfloat16"),
        num_steps=2000,
    ),
    # configs[2]: ResNet-50 ImageNet, data-parallel on v4-8.
    "imagenet_resnet50_dp": TrainConfig(
        name="imagenet_resnet50_dp",
        model="resnet50",
        dataset="imagenet",
        global_batch_size=1024,
        image_size=224,
        num_classes=1000,
        mesh=MeshSpec(dp=-1),
        strategy="dp",
        optim=OptimConfig(name="sgd", learning_rate=0.4, warmup_steps=500,
                          total_steps=56300, weight_decay=1e-4),
        num_steps=56300,
        label_smoothing=0.1,
        # Declared global batch 1024 via 128-row microbatches — the
        # measured-good single-chip ResNet-50 batch (BASELINE.md); on a
        # real v4-8 the same config runs accumulated per-chip too.
        accum_steps=8,
    ),
    # configs[3]: BERT-large fine-tune, v4-32 (Horovod -> TpuDistributor migration).
    "bert_large_v4_32": TrainConfig(
        name="bert_large_v4_32",
        model="bert-large",
        dataset="sst2",
        global_batch_size=256,
        seq_len=128,
        num_classes=2,
        mesh=MeshSpec(dp=-1, fsdp=4),
        strategy="fsdp",
        optim=OptimConfig(name="adamw", learning_rate=3e-5, warmup_steps=200,
                          mu_dtype="bfloat16",
                          total_steps=5000, weight_decay=0.01),
        num_steps=5000,
        # Global batch 256 as 4x64 microbatches: the single-chip step OOMs
        # monolithic at batch >=96; accumulated it runs at 74.0% MFU
        # (BASELINE.md). Meshes with more batch shards just split each
        # microbatch further.
        accum_steps=4,
    ),
    # configs[4]: Llama-3-8B LoRA (stretch — FSDP->GSPMD on v5p-64).
    "llama3_8b_lora": TrainConfig(
        name="llama3_8b_lora",
        model="llama3-8b-lora",
        dataset="sst2",
        global_batch_size=64,
        seq_len=2048,
        num_classes=2,
        mesh=MeshSpec(dp=-1, fsdp=8, tp=2),
        strategy="lora",
        optim=OptimConfig(name="adamw", learning_rate=1e-4, warmup_steps=100,
                          total_steps=1000, weight_decay=0.0),
        num_steps=1000,
    ),
}


def get_config(name: str, **overrides) -> TrainConfig:
    cfg = CONFIGS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
