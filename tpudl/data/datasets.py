"""Dataset helpers: materialize CIFAR-10 / SST-2-shaped data as Parquet.

Zero-egress environment: these write synthetic datasets with the real
schemas (CIFAR-10: 32x32x3 uint8 + label; SST-2: token ids + mask + label)
so the full Parquet->converter->device pipeline is exercised end-to-end.
Drop real exports of the same schema into the directory and everything
downstream is unchanged — that is the Petastorm/Delta contract
(BASELINE.json `north_star`).
"""

from __future__ import annotations

import os

import numpy as np

from tpudl.data.converter import make_converter, write_parquet


def _class_pattern_images(
    rng, labels, image_size: int, block: int, num_classes: int
) -> np.ndarray:
    """uint8 [N, image_size, image_size, 3] images carrying a learnable
    low-frequency per-class signal under noise (the synthetic-signal
    contract shared by the CIFAR- and ImageNet-schema materializers;
    same construction as tpudl.data.synthetic). Built in row chunks so
    peak memory stays bounded at ImageNet sizes."""
    if image_size % block != 0 or image_size < block:
        raise ValueError(
            f"image_size {image_size} must be a positive multiple of the "
            f"{block}px pattern block"
        )
    rep = image_size // block
    coarse = rng.normal(size=(num_classes, block, block, 3)).astype(np.float32)
    pattern = np.repeat(np.repeat(coarse, rep, axis=1), rep, axis=2)
    pattern /= np.abs(pattern).max()
    n = len(labels)
    images = np.empty((n, image_size, image_size, 3), np.uint8)
    chunk = max(1, (1 << 24) // (image_size * image_size * 3 * 4))
    for lo in range(0, n, chunk):
        idx = labels[lo : lo + chunk]
        noise = rng.normal(
            0.0, 0.15, size=(len(idx), image_size, image_size, 3)
        ).astype(np.float32)
        block_imgs = 0.5 + 0.35 * pattern[idx] + noise
        images[lo : lo + chunk] = (
            np.clip(block_imgs, 0.0, 1.0) * 255
        ).astype(np.uint8)
    return images


def materialize_cifar10_like(
    directory: str,
    num_rows: int = 10_000,
    num_classes: int = 10,
    seed: int = 0,
    rows_per_file: int = 2048,
    row_group_size: int = 256,
):
    """CIFAR-10-schema Parquet dataset (image uint8 HWC, int64 label) with a
    learnable low-frequency class signal.

    ``row_group_size`` bounds rows per Parquet row group. 256 (vs the old
    one-group-per-file layout) is the converter's streaming/parallelism
    granularity: the reader-thread pool overlaps group decode (one 6 MB
    group per file decodes single-threaded AND pays superlinear
    combine/reshape cost)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=(num_rows,))
    images = _class_pattern_images(rng, labels, 32, 4, num_classes)
    write_parquet(
        directory,
        {"image": images, "label": labels.astype(np.int64)},
        rows_per_file=rows_per_file,
        row_group_size=row_group_size,
    )
    return make_converter(directory)


def materialize_sst2_like(
    directory: str,
    num_rows: int = 8_192,
    seq_len: int = 128,
    vocab_size: int = 30_522,  # BERT wordpiece vocab size
    seed: int = 0,
    rows_per_file: int = 2048,
):
    """SST-2-schema Parquet dataset (input_ids, attention_mask, label) where
    sentiment is signalled by marker-token frequency (attention-learnable)."""
    rng = np.random.default_rng(seed)
    markers = rng.integers(1000, vocab_size, size=(2,))
    labels = rng.integers(0, 2, size=(num_rows,))
    ids = rng.integers(1000, vocab_size, size=(num_rows, seq_len))
    lengths = rng.integers(seq_len // 4, seq_len + 1, size=(num_rows,))
    mask = (np.arange(seq_len)[None, :] < lengths[:, None]).astype(np.int64)
    for i in range(num_rows):
        pos = rng.integers(1, max(lengths[i], 2), size=(max(int(lengths[i]) // 8, 1),))
        ids[i, pos] = markers[labels[i]]
    ids[:, 0] = 101  # [CLS]
    ids = np.where(mask.astype(bool), ids, 0)
    write_parquet(
        directory,
        {
            "input_ids": ids.astype(np.int64),
            "attention_mask": mask,
            "label": labels.astype(np.int64),
        },
        rows_per_file=rows_per_file,
    )
    return make_converter(directory)


def materialize_imagenet_like(
    directory: str,
    num_rows: int = 512,
    image_size: int = 224,
    num_classes: int = 1000,
    seed: int = 0,
    rows_per_file: int = 128,
    row_group_size: int = 32,
):
    """ImageNet-schema Parquet dataset (image uint8 HWC at 224x224, int64
    label) — the configs[2] data contract at reduced row count.
    ``image_size`` must be a multiple of 8 (the class-pattern block).
    Files are written with small row groups (~150 KB rows x 32), so the
    converter's row-group streaming is genuinely exercised: readers hold
    one group, never a whole file."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, num_classes, size=(num_rows,))
    images = _class_pattern_images(rng, labels, image_size, 8, num_classes)
    write_parquet(
        directory,
        {"image": images, "label": labels.astype(np.int64)},
        rows_per_file=rows_per_file,
        row_group_size=row_group_size,
    )
    return make_converter(directory)


def normalize_cifar_batch(batch: dict) -> dict:
    """uint8 HWC -> float32 normalized, keeping other columns.

    HOST-side normalization: quadruples the bytes crossing the
    host->device link (uint8 -> f32). The training paths ship the wire
    dtype instead (``wire_cifar_batch`` on the host +
    ``device_normalize_cifar`` inside the compiled step); this stays as
    the one-shot/debug path and the input-pipeline benchmark's legacy
    baseline."""
    out = dict(batch)
    out["image"] = (batch["image"].astype(np.float32) / 255.0 - 0.5) / 0.25
    out["label"] = batch["label"].astype(np.int32)
    return out


def wire_cifar_batch(batch: dict) -> dict:
    """Host-side wire prep for the device-preprocessed CIFAR path: the
    image column stays uint8 (4x fewer H2D bytes than the float32
    host-normalize path), only the (tiny) label column is cast for the
    device. Pair with ``device_normalize_cifar`` as the step's
    ``input_transform``/``preprocess`` so the cast+scale fuses into the
    forward pass under pjit."""
    out = dict(batch)
    out["label"] = batch["label"].astype(np.int32)
    return out


#: The simple stats ``normalize_cifar_batch`` bakes in: (px/255-0.5)/0.25.
CIFAR_SIMPLE_MEAN = (0.5, 0.5, 0.5)
CIFAR_SIMPLE_STD = (0.25, 0.25, 0.25)


def device_normalize_cifar(image_key: str = "image"):
    """Device-side counterpart of ``normalize_cifar_batch``: the same
    (px/255 - 0.5)/0.25 normalization, traced inside the compiled step
    (``make_classification_train_step(input_transform=...)`` or
    ``compile_step(preprocess=...)``) so host- and device-placed
    normalization train identically while uint8 crosses the link.
    Delegates to ``tpudl.data.augment.device_normalize`` (ONE device
    normalization implementation) with the simple CIFAR stats; the
    scale+bias formulation differs from the host path only in f32
    rounding (parity asserted in tests)."""
    from tpudl.data.augment import device_normalize

    return device_normalize(
        CIFAR_SIMPLE_MEAN, CIFAR_SIMPLE_STD, image_key=image_key
    )


def normalize_sst2_batch(batch: dict) -> dict:
    """Parquet int64 token columns -> int32 for the device."""
    return {
        "input_ids": batch["input_ids"].astype(np.int32),
        "attention_mask": batch["attention_mask"].astype(np.int32),
        "label": batch["label"].astype(np.int32),
    }


# ---------------------------------------------------------------------------
# Raw-text SST-2 path (tokenizer vertical).
# ---------------------------------------------------------------------------

#: Tiny sentiment lexicons for the synthetic raw-text corpus: the label
#: signal is carried by natural-language words, so the full
#: text -> WordPiece -> ids -> fine-tune pipeline is learnable end-to-end.
_POSITIVE = (
    "wonderful great delightful brilliant moving charming superb "
    "heartfelt dazzling triumphant funny warm engaging masterful fresh"
).split()
_NEGATIVE = (
    "dreadful boring tedious clumsy hollow lifeless bland grating "
    "shallow messy dull forgettable awkward stale tiresome"
).split()
_FILLER = (
    "the a this that film movie story plot acting cast script scene "
    "direction pacing and but with about feels is was rather quite "
    "truly somewhat performance ending dialogue camera moments it"
).split()


def synthetic_review(rng, label: int, min_words: int = 6,
                     max_words: int = 24) -> str:
    """One synthetic review sentence whose sentiment words match `label`."""
    n = int(rng.integers(min_words, max_words + 1))
    lexicon = _POSITIVE if label == 1 else _NEGATIVE
    words = []
    for _ in range(n):
        if rng.random() < 0.25:
            words.append(lexicon[int(rng.integers(0, len(lexicon)))])
        else:
            words.append(_FILLER[int(rng.integers(0, len(_FILLER)))])
    sentence = " ".join(words)
    if rng.random() < 0.3:
        sentence += "."
    return sentence


def materialize_sst2_text(
    directory: str,
    num_rows: int = 8_192,
    seed: int = 0,
    rows_per_file: int = 2048,
):
    """RAW-TEXT SST-2-schema Parquet dataset (sentence: str, label: int64)
    — the true shape of the reference workload's input (SST-2 is a text
    dataset; the reference's analog is raw-image preprocessing at
    reference notebooks/cv/onnx_experiments.py:55-66). Feed through
    tokenize_text_dataset to get the ids-schema dataset the training
    pipeline consumes."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 2, size=(num_rows,))
    sentences = np.asarray(
        [synthetic_review(rng, int(lab)) for lab in labels], dtype=object
    )
    write_parquet(
        directory,
        {"sentence": sentences, "label": labels.astype(np.int64)},
        rows_per_file=rows_per_file,
    )
    return make_converter(directory)


def tokenize_text_dataset(
    text_dir: str,
    out_dir: str,
    tokenizer,
    seq_len: int = 128,
    batch_size: int = 1024,
    rows_per_file: int = 2048,
):
    """text-schema Parquet -> ids-schema Parquet (the preprocessing step of
    the Petastorm contract: materialize once, train many).

    ``tokenizer``: a tpudl.data.tokenizer.WordPieceTokenizer (or anything
    with its __call__(texts, max_len) -> {input_ids, attention_mask}).
    Genuinely streaming: one text batch is tokenized and flushed to its
    own part-file at a time (write_parquet part_offset), so peak memory
    is one chunk regardless of corpus size.
    """
    conv = make_converter(text_dir)
    buf_ids, buf_mask, buf_labels, buffered = [], [], [], 0
    part = 0

    def _flush():
        nonlocal part, buf_ids, buf_mask, buf_labels, buffered
        if not buffered:
            return
        write_parquet(
            out_dir,
            {
                "input_ids": np.concatenate(buf_ids),
                "attention_mask": np.concatenate(buf_mask),
                "label": np.concatenate(buf_labels),
            },
            rows_per_file=rows_per_file,
            part_offset=part,
        )
        part += -(-buffered // rows_per_file)
        buf_ids, buf_mask, buf_labels, buffered = [], [], [], 0

    for batch in conv.make_batch_iterator(
        batch_size, epochs=1, shuffle=False, drop_last=False
    ):
        enc = tokenizer([str(s) for s in batch["sentence"]], seq_len)
        buf_ids.append(enc["input_ids"].astype(np.int64))
        buf_mask.append(enc["attention_mask"].astype(np.int64))
        buf_labels.append(batch["label"].astype(np.int64))
        buffered += len(batch["label"])
        if buffered >= rows_per_file:
            _flush()
    _flush()
    return make_converter(out_dir)


def split_train_eval(conv, eval_fraction: float = 0.1):
    """Holdout split shared by the training notebooks, mirroring the
    reference's habit of verifying model outputs every run (reference
    notebooks/cv/onnx_experiments.py:98-100,178-184). Multi-file datasets
    hold out the last Parquet file (file granularity — ``eval_fraction``
    does not apply there); a single-file dataset auto-splits its rows
    (last ``eval_fraction`` of rows, min 1) via the converter's
    row-window support — either way train and eval rows are DISJOINT
    (asserted by tests/test_datasets.py), never the round-3 overlapping
    fallback."""
    from tpudl.data.converter import Converter

    if conv.row_ranges is not None:
        raise ValueError(
            "split_train_eval on an already-windowed converter would "
            "rebuild windows in absolute file coordinates (leaking rows "
            "from outside the original split) — split the full dataset "
            "once instead"
        )
    if not 0.0 < eval_fraction < 1.0:
        raise ValueError(f"eval_fraction must be in (0, 1), got {eval_fraction}")
    if len(conv.files) >= 2:
        ordered = sorted(conv.files)
        return make_converter(ordered[:-1]), make_converter(ordered[-1:])
    n = conv.num_rows
    if n < 2:
        raise ValueError(
            f"cannot split a {n}-row dataset into train and eval"
        )
    cut = n - max(1, int(n * eval_fraction))
    train = Converter(
        files=conv.files, num_rows=cut, files_rows=conv.files_rows,
        row_ranges=[(0, cut)],
    )
    holdout = Converter(
        files=conv.files, num_rows=n - cut, files_rows=conv.files_rows,
        row_ranges=[(cut, n)],
    )
    return train, holdout


def eval_stream(eval_conv, batch_size: int, normalize, batch_divisor: int = 1):
    """Re-iterable held-out batch stream (tpudl.train.evaluate drains one
    epoch per call). A holdout smaller than one batch PER SHARD keeps its
    partial batch (drop_last=False) so evaluate() sees at least one batch
    instead of raising. ``batch_divisor`` (the mesh's dp*fsdp batch-shard
    count) trims any partial batch down to a divisible row count — a
    12-row final batch on an 8-way batch sharding would otherwise fail
    pjit's divisibility check; batches smaller than the divisor are
    skipped (at most divisor-1 rows of the holdout go unevaluated,
    reported example-weighted by evaluate())."""
    import jax

    drop_last = len(eval_conv) // jax.process_count() >= batch_size

    def gen():
        for b in eval_conv.make_batch_iterator(
            batch_size, epochs=1, shuffle=False, drop_last=drop_last
        ):
            n = len(next(iter(b.values())))
            keep = (n // batch_divisor) * batch_divisor
            if keep == 0:
                continue
            if keep != n:
                b = {k: v[:keep] for k, v in b.items()}
            yield normalize(b)

    return gen
