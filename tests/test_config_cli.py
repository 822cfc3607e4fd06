"""Every BASELINE.json configs[i] entry is drivable from a CLI one-liner
(SURVEY.md §5.6: "one config file per configs[i] entry" — made
load-bearing: the round-3 gap was configs[2]/[3] hardcoded out of reach).

Each test launches the real workload script as a subprocess on the fake
8-device CPU mesh (JAX_PLATFORMS=cpu + the host-device-count XLA flag),
at toy step counts. Big models
override to tiny shapes via the SAME CLI the full run uses; the config's
mesh / strategy / schema / accumulation path is what's exercised.
"""

import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

ENV = {
    **os.environ,
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "JAX_PLATFORMS": "cpu",
}


def _run(script, *argv, timeout=600):
    out = subprocess.run(
        [sys.executable, str(REPO / script), *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=str(REPO),
        env=ENV,
    )
    assert out.returncode == 0, (
        f"{script} {' '.join(argv)} failed:\n{out.stdout[-2000:]}\n"
        f"{out.stderr[-2000:]}"
    )
    return out.stdout


# configs[0]: ResNet-18 / CIFAR-10 smoke.
def test_configs0_cifar10_resnet18_cli():
    out = _run(
        "notebooks/cv/train_cifar10.py",
        "--config", "cifar10_resnet18",
        "--steps", "4", "--batch", "32", "--eval-steps", "1",
    )
    assert "cifar10_resnet18: resnet18" in out
    assert "held-out eval" in out


# configs[1]: BERT-base SST-2 fine-tune (tiny model via the same CLI).
def test_configs1_sst2_bert_base_cli():
    out = _run(
        "notebooks/nlp/train_sst2.py",
        "--config", "sst2_bert_base",
        "--model", "bert-tiny", "--steps", "4", "--batch", "32",
        "--eval-steps", "1",
    )
    assert "sst2_bert_base: bert-tiny" in out
    assert "held-out eval" in out


# configs[2]: ResNet-50 / ImageNet DP — declared batch 1024 realized via
# gradient accumulation; tiny batch here, real 224x224 schema + augmenter.
def test_configs2_imagenet_resnet50_cli(tmp_path):
    out = _run(
        "notebooks/cv/train_cifar10.py",
        "--config", "imagenet_resnet50_dp",
        "--steps", "3", "--batch", "16", "--accum", "2",
        "--eval-steps", "1",
        "--data-dir", str(tmp_path / "im"), "--materialize",
        "--rows", "128",
        # ResNet-50 fwd+bwd inside the accumulation scan is a heavy CPU
        # compile; generous ceiling so host contention can't flake it.
        timeout=1800,
    )
    assert "imagenet_resnet50_dp: resnet50" in out
    assert "(accum 2)" in out
    assert "held-out eval" in out


# configs[3]: BERT-large v4-32 fine-tune — fsdp mesh clamps to the fake
# 8-device mesh (fsdp=4 x dp=2), accumulation path on.
def test_configs3_bert_large_cli():
    out = _run(
        "notebooks/nlp/train_sst2.py",
        "--config", "bert_large_v4_32",
        "--model", "bert-tiny", "--steps", "4", "--batch", "64",
        "--accum", "2", "--eval-steps", "1",
    )
    assert "bert_large_v4_32: bert-tiny" in out
    assert "'fsdp': 4" in out  # the declared mesh actually clamped+used
    assert "strategy fsdp" in out
    assert "held-out eval" in out


# configs[4]: Llama LoRA (tiny model via the same CLI).
def test_configs4_llama_lora_cli():
    out = _run(
        "notebooks/nlp/finetune_lora.py",
        "--model", "llama-tiny-lora", "--steps", "4", "--batch", "16",
        "--mesh", "2,2,1,2",
    )
    assert "llama-tiny-lora" in out
    assert "trainable" in out


@pytest.mark.parametrize(
    "spec,devices,expect",
    [
        ((-1, 4, 1, 1, 1, 1), 1, (1, 1, 1, 1, 1, 1)),
        ((-1, 4, 1, 1, 1, 1), 8, (2, 4, 1, 1, 1, 1)),
        ((-1, 8, 1, 2, 1, 1), 8, (1, 8, 1, 1, 1, 1)),
        ((-1, 1, 1, 1, 1, 1), 8, (8, 1, 1, 1, 1, 1)),
    ],
)
def test_meshspec_fit(spec, devices, expect):
    from tpudl.runtime import MeshSpec

    fitted = MeshSpec(*spec).fit(devices)
    assert fitted.resolve(devices) == expect


def test_meshspec_fit_requires_wildcard():
    from tpudl.runtime import MeshSpec

    with pytest.raises(ValueError, match="wildcard"):
        MeshSpec(2, 2, 1, 1, 1, 1).fit(4)


# configs[4] raw-text vertical: TSV -> byte-level BPE -> ids -> LoRA
# fine-tune, one command.
def test_configs4_text_data_bpe_vertical(tmp_path):
    tsv = tmp_path / "train.tsv"
    with open(tsv, "w", encoding="utf-8") as f:
        f.write("sentence\tlabel\n")
        for i in range(256):
            s = ("a wonderful charming movie" if i % 2
                 else "a dull dreadful film")
            f.write(f"{s}\t{i % 2}\n")
    out = _run(
        "notebooks/nlp/finetune_lora.py",
        "--model", "llama-tiny-lora", "--steps", "4", "--batch", "16",
        "--seq-len", "32",
        "--text-data", "--ingest", str(tsv),
        "--data-dir", str(tmp_path / "data"),
    )
    assert "trained byte-level BPE" in out
    assert "ingested" in out
    # reuse path: second run skips ingestion/tokenization
    out2 = _run(
        "notebooks/nlp/finetune_lora.py",
        "--model", "llama-tiny-lora", "--steps", "2", "--batch", "16",
        "--seq-len", "32",
        "--text-data", "--data-dir", str(tmp_path / "data"),
    )
    assert "reusing tokenized dataset" in out2
