"""Real-dataset ingesters: on-disk archive formats -> tpudl Parquet.

The reference's first acts are loading real pretrained weights and a real
input file (reference notebooks/cv/onnx_experiments.py:19,47-50). tpudl
ingests real HF *weights* via params_from_hf_bert/llama; this module is
the *dataset* counterpart — it converts the standard on-disk distribution
formats into the schemas the converter layer already consumes, so
"drop real data in" is one function call, not an exercise for the user:

- ``ingest_cifar10``: the CIFAR-10 python-pickle archive
  (cifar-10-python.tar.gz, or its extracted cifar-10-batches-py/
  directory of data_batch_1..5 + test_batch pickles, each a dict with
  b"data" [N, 3072] uint8 rows in CHW plane order and b"labels") ->
  the CIFAR image/label Parquet schema
  (tpudl.data.datasets.materialize_cifar10_like's schema).
- ``ingest_sst2_tsv``: a GLUE SST-2 TSV (header ``sentence\\tlabel``,
  tab-separated, no quoting — the glue_data/SST-2/{train,dev}.tsv
  layout) -> the raw-text Parquet schema
  (tpudl.data.datasets.materialize_sst2_text's schema), feeding the
  tokenizer vertical (tokenize_text_dataset) unchanged.

Everything downstream (converter sharding/shuffle, augmenter, training
notebooks) is untouched — that is the Petastorm "materialize once, train
many" contract (BASELINE.json north_star).
"""

from __future__ import annotations

import os
import pickle
import shutil
import tarfile
from typing import Dict, List

import numpy as np

from tpudl.data.converter import make_converter, write_parquet
from tpudl.obs import counters as obs_counters
from tpudl.obs import spans as obs_spans

#: Obs span category for ingest chunks (outside the goodput step/compile
#: categories on purpose — ingest is a materialize-once cost, reported in
#: the breakdown table's extra rows, not against training goodput).
_INGEST_CAT = "ingest"


def _carry_over_non_ingest(retired: str, out_dir: str) -> None:
    """Move everything that is NOT ingest output (part files /
    classes.txt) from a retired out_dir into the published one — user
    files placed next to the dataset survive a re-ingest swap."""
    for name in os.listdir(retired):
        if name == "classes.txt" or (
            name.startswith("part-") and name.endswith(".parquet")
        ):
            continue  # superseded ingest output, dropped with the dir
        os.replace(
            os.path.join(retired, name), os.path.join(out_dir, name)
        )


def _col_bytes(arr) -> int:
    """Payload bytes of one column. dtype=object arrays (raw text)
    count their encoded string payloads — ndarray.nbytes would count
    8-byte pointers and underreport text ingest volume ~100x."""
    a = np.asarray(arr)
    if a.dtype == object:
        return sum(len(str(x).encode("utf-8")) for x in a.ravel())
    return int(a.nbytes)


def _write_chunk(
    directory: str,
    columns: Dict[str, np.ndarray],
    part: int,
    **write_kwargs,
) -> None:
    """write_parquet one chunk with an obs span + byte/row counters
    (no-op overhead when observability is off)."""
    rec = obs_spans.active_recorder()
    if rec is None:
        write_parquet(directory, columns, part_offset=part, **write_kwargs)
        return
    nbytes = int(sum(_col_bytes(v) for v in columns.values()))
    rows = len(next(iter(columns.values())))
    t0 = rec.clock()
    write_parquet(directory, columns, part_offset=part, **write_kwargs)
    rec.record(
        "ingest_chunk", _INGEST_CAT, t0, rec.clock() - t0,
        {"part": part, "rows": rows, "bytes": nbytes},
    )
    reg = obs_counters.registry()
    reg.counter("bytes_ingested").inc(nbytes)
    reg.counter("rows_ingested").inc(rows)

#: Member names inside the CIFAR-10 python archive, in canonical order.
_CIFAR_TRAIN_BATCHES = tuple(f"data_batch_{i}" for i in range(1, 6))
_CIFAR_TEST_BATCH = "test_batch"


def _cifar_rows_to_hwc(data: np.ndarray) -> np.ndarray:
    """[N, 3072] uint8 rows (1024 R + 1024 G + 1024 B planes, row-major
    within each plane) -> [N, 32, 32, 3] uint8 HWC."""
    if data.ndim != 2 or data.shape[1] != 3072:
        raise ValueError(
            f"CIFAR-10 batch rows must be [N, 3072], got {data.shape}"
        )
    return (
        data.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1).astype(np.uint8)
    )


def _load_cifar_batch(fileobj) -> tuple:
    """One CIFAR-10 pickle (the real distribution pickles with bytes keys
    under py3's encoding='bytes') -> (images HWC uint8, labels int64)."""
    d = pickle.load(fileobj, encoding="bytes")
    data = d.get(b"data", d.get("data"))
    labels = d.get(b"labels", d.get("labels"))
    if data is None or labels is None:
        raise ValueError(
            f"not a CIFAR-10 batch pickle (keys: {list(d)[:6]})"
        )
    return _cifar_rows_to_hwc(np.asarray(data)), np.asarray(
        labels, np.int64
    )


def ingest_cifar10(
    source: str,
    out_dir: str,
    split: str = "train",
    rows_per_file: int = 10_000,
):
    """CIFAR-10 python archive -> image/label Parquet dataset.

    ``source``: the distribution tarball (cifar-10-python.tar.gz), the
    extracted cifar-10-batches-py/ directory, or a directory containing
    it. ``split``: "train" (data_batch_1..5 -> one Parquet part per
    batch file) or "test" (test_batch). Returns a Converter over
    ``out_dir``; feed it to the CIFAR notebook exactly like a
    materialized synthetic dataset:

        python notebooks/cv/train_cifar10.py \\
            --ingest /path/cifar-10-python.tar.gz --data-dir /tmp/c10
    """
    if split == "train":
        members = list(_CIFAR_TRAIN_BATCHES)
    elif split == "test":
        members = [_CIFAR_TEST_BATCH]
    else:
        raise ValueError(f"split must be train|test, got {split!r}")

    batches: List[tuple] = []
    if os.path.isfile(source):
        with tarfile.open(source, "r:*") as tf:
            by_base = {
                os.path.basename(m.name): m
                for m in tf.getmembers()
                if m.isfile()
            }
            for name in members:
                if name not in by_base:
                    raise FileNotFoundError(
                        f"{name} not found in archive {source}"
                    )
                batches.append(_load_cifar_batch(tf.extractfile(by_base[name])))
    else:
        base = source
        nested = os.path.join(source, "cifar-10-batches-py")
        if not os.path.exists(os.path.join(base, members[0])) and os.path.isdir(
            nested
        ):
            base = nested
        for name in members:
            path = os.path.join(base, name)
            if not os.path.exists(path):
                raise FileNotFoundError(path)
            with open(path, "rb") as f:
                batches.append(_load_cifar_batch(f))

    part = 0
    for images, labels in batches:
        _write_chunk(
            out_dir,
            {"image": images, "label": labels},
            part,
            rows_per_file=rows_per_file,
        )
        part += -(-len(labels) // rows_per_file)
    return make_converter(out_dir)


def ingest_sst2_tsv(
    source: str,
    out_dir: str,
    split: str = "train",
    rows_per_file: int = 16_384,
    sentence_column: str = "sentence",
    label_column: str = "label",
):
    """GLUE SST-2 TSV -> raw-text (sentence, label) Parquet dataset.

    ``source``: a .tsv file, or the glue SST-2 directory holding
    {train,dev}.tsv (``split`` picks which). The GLUE format is a
    header line then tab-separated rows with NO quoting (sentences may
    contain anything but tab/newline), so parsing is a literal
    ``split("\\t")`` — csv-module quoting rules would corrupt sentences
    containing quote characters. Returns a Converter over ``out_dir``
    whose output feeds tokenize_text_dataset (the raw-text vertical):

        python notebooks/nlp/train_sst2.py --text-data \\
            --ingest /path/SST-2/train.tsv --data-dir /tmp/sst2
    """
    path = source
    if os.path.isdir(source):
        path = os.path.join(source, f"{split}.tsv")
    if not os.path.exists(path):
        raise FileNotFoundError(path)

    sentences: List[str] = []
    labels: List[int] = []
    with open(path, encoding="utf-8") as f:
        header = f.readline().rstrip("\n").split("\t")
        try:
            s_idx = header.index(sentence_column)
            l_idx = header.index(label_column)
        except ValueError:
            raise ValueError(
                f"{path} header {header} lacks "
                f"{sentence_column!r}/{label_column!r} columns"
            )
        for lineno, line in enumerate(f, start=2):
            line = line.rstrip("\n")
            if not line:
                continue
            parts = line.split("\t")
            if len(parts) <= max(s_idx, l_idx):
                raise ValueError(f"{path}:{lineno}: short row {parts!r}")
            sentences.append(parts[s_idx])
            labels.append(int(parts[l_idx]))

    if not sentences:
        raise ValueError(f"{path} contains no data rows")
    _write_chunk(
        out_dir,
        {
            "sentence": np.asarray(sentences, dtype=object),
            "label": np.asarray(labels, np.int64),
        },
        0,
        rows_per_file=rows_per_file,
    )
    return make_converter(out_dir)


#: Image file extensions ingest_image_folder picks up (case-insensitive).
IMAGE_EXTENSIONS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def ingest_image_folder(
    source: str,
    out_dir: str,
    image_size: int = 224,
    resize_shorter: int | None = None,
    rows_per_file: int = 1024,
    row_group_size: int = 32,
    extensions: tuple = IMAGE_EXTENSIONS,
):
    """Class-subdirectory image tree -> ImageNet-schema Parquet dataset.

    ``source`` is the torchvision-ImageFolder / ImageNet-train layout —
    one subdirectory per class holding encoded images (nested dirs are
    walked) — the real-data entry point for the configs[2] CV vertical
    (the reference's first act on the CV side is decoding a real image
    file: reference notebooks/cv/onnx_experiments.py:47-66). Classes are
    the SORTED subdirectory names -> label indices 0..C-1, recorded in
    ``out_dir``/classes.txt (one name per line, index order).

    Per image: PIL decode -> RGB, shorter side resized to
    ``resize_shorter`` (default ``image_size``; pass e.g. 256 with
    image_size 224 for the standard eval headroom), center crop to
    ``image_size`` square, uint8 HWC. Images stream to Parquet in
    ``rows_per_file`` chunks, so host memory stays bounded at ImageNet
    scale; small row groups keep the converter's row-group streaming
    effective on 150 KB rows (same rationale as
    tpudl.data.datasets.materialize_imagenet_like). Everything
    downstream (augmenter crop/flip, uint8 wire + device_normalize) is
    the existing configs[2] path.

    The ingest is ATOMIC at directory granularity: parts and classes.txt
    stream into a ``<out_dir>.ingest-tmp`` staging directory and publish
    to ``out_dir`` only on completion — a multi-hour ImageNet ingest
    killed partway leaves no valid-looking part files that a converter
    could open label-mapped-but-unnamed, and a re-run never mixes fresh
    parts with a prior interrupted run's (stale staging dirs are wiped
    on start; a complete prior ``out_dir`` is replaced wholesale).
    Example:

        python notebooks/cv/train_cifar10.py --config imagenet_resnet50_dp \\
            --ingest /path/imagenet/train --data-dir /tmp/imagenet-parquet
    """
    from PIL import Image

    short = resize_shorter if resize_shorter is not None else image_size
    if short < image_size:
        raise ValueError(
            f"resize_shorter {short} < image_size {image_size}: the center "
            f"crop would need upscaling"
        )
    classes = sorted(
        d
        for d in os.listdir(source)
        if os.path.isdir(os.path.join(source, d))
    )
    if not classes:
        raise ValueError(f"{source} has no class subdirectories")
    files: List[tuple] = []
    for idx, cls in enumerate(classes):
        for root, dirs, names in os.walk(os.path.join(source, cls)):
            dirs.sort()
            for name in sorted(names):
                if os.path.splitext(name)[1].lower() in extensions:
                    files.append((os.path.join(root, name), idx))
    if not files:
        raise ValueError(
            f"{source} contains no {'/'.join(extensions)} files under its "
            f"class subdirectories"
        )

    def _decode(path: str) -> np.ndarray:
        with Image.open(path) as im:
            im = im.convert("RGB")
            w, h = im.size
            scale = short / min(w, h)
            im = im.resize(
                (
                    max(image_size, round(w * scale)),
                    max(image_size, round(h * scale)),
                ),
                Image.BILINEAR,
            )
            w, h = im.size
            left, top = (w - image_size) // 2, (h - image_size) // 2
            im = im.crop((left, top, left + image_size, top + image_size))
            return np.asarray(im, np.uint8)

    out_dir = out_dir.rstrip("/\\") or out_dir
    stage = out_dir + ".ingest-tmp"
    retired = out_dir + ".ingest-old"
    if os.path.isdir(stage):  # staging from an interrupted run: garbage
        shutil.rmtree(stage)
    if os.path.isdir(retired):
        # A prior run died mid-swap. If out_dir is gone the old dataset
        # lives ONLY here — restore it, never delete it; if out_dir
        # exists the swap completed, so only rescue the unrelated user
        # files the dead run didn't carry over.
        if not os.path.isdir(out_dir):
            os.rename(retired, out_dir)
        else:
            _carry_over_non_ingest(retired, out_dir)
            shutil.rmtree(retired)
    os.makedirs(stage)
    part = 0
    for start in range(0, len(files), rows_per_file):
        chunk = files[start : start + rows_per_file]
        _write_chunk(
            stage,
            {
                "image": np.stack([_decode(p) for p, _ in chunk]),
                "label": np.asarray([i for _, i in chunk], np.int64),
            },
            part,
            rows_per_file=rows_per_file,
            row_group_size=row_group_size,
        )
        part += 1
    with open(os.path.join(stage, "classes.txt"), "w") as f:
        f.write("\n".join(classes) + "\n")
    # Publish by DIRECTORY RENAME only — never by per-file delete/move,
    # which would open a window where out_dir holds a partial mix of old
    # and new parts. Re-ingest over an existing out_dir swaps: the old
    # dir is renamed aside (atomic), the stage renamed in (atomic), then
    # any unrelated user files are carried over and the old dir deleted
    # — a kill at any point leaves either the complete old or the
    # complete new dataset, plus detectable .ingest-* leftovers that the
    # next run wipes.
    if os.path.isdir(out_dir):
        os.rename(out_dir, retired)
    os.rename(stage, out_dir)
    if os.path.isdir(retired):
        _carry_over_non_ingest(retired, out_dir)
        shutil.rmtree(retired)
    return make_converter(out_dir)
