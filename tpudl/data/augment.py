"""Batch augmentation for the CV input pipeline: crop + flip + normalize.

The torchvision-transform analog (the reference preprocesses with
Resize/CenterCrop/ToTensor/Normalize — reference
notebooks/cv/onnx_experiments.py:55-66) recast for throughput training:
pad-and-random-crop + horizontal flip + per-channel normalize, fused into
one pass over the uint8 batch by the native C++ kernel
(tpudl/native/augment.cpp) with a bit-identical numpy fallback.

Design rule: all randomness (crop offsets, flip coins) is drawn HERE from
one numpy Generator, and both backends consume the same draws and the
same f32 scale/bias formulation — so native vs numpy can never change
training beyond float32 rounding (parity asserted at 1e-6 in
tests/test_augment.py).

Wiring: pass it as ``prefetch_to_device(transform=BatchAugmenter(...))``
so the prefetcher's assembly pool crops/flips batches in parallel
(``Converter.make_batch_iterator(transform=...)`` also works, serially
inside the reader). Draws are lock-protected, so concurrent callers are
safe; under a multi-worker pool the draw->batch assignment follows
completion order, so augmentation stays correctly distributed but is
only bit-reproducible for a fixed seed with ONE worker.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

#: torchvision's ImageNet normalization (the reference's constants at
#: notebooks/cv/onnx_experiments.py:63 — inherited as a contract, like the
#: parity tolerances).
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
#: Common CIFAR-10 statistics.
CIFAR10_MEAN = (0.4914, 0.4822, 0.4465)
CIFAR10_STD = (0.2470, 0.2435, 0.2616)


def _scale_bias(mean, std):
    """px * scale + bias == (px/255 - mean)/std, in f32 like the kernel."""
    scale = np.float32(1.0) / (np.float32(255.0) * std)
    bias = -mean / std
    return scale.astype(np.float32), bias.astype(np.float32)


def _augment_numpy(images, pad, crop_h, crop_w, offsets, flip, mean, std,
                   normalize=True):
    n, h, w, c = images.shape
    padded = np.zeros((n, h + 2 * pad, w + 2 * pad, c), np.uint8)
    padded[:, pad : pad + h, pad : pad + w, :] = images
    out = np.empty(
        (n, crop_h, crop_w, c), np.float32 if normalize else np.uint8
    )
    for i in range(n):
        top, left = offsets[i]
        crop = padded[i, top : top + crop_h, left : left + crop_w, :]
        if flip[i]:
            crop = crop[:, ::-1, :]
        out[i] = crop
    if normalize:
        scale, bias = _scale_bias(mean, std)
        out *= scale
        out += bias
    return out


def device_normalize(
    mean: Sequence[float] = CIFAR10_MEAN,
    std: Sequence[float] = CIFAR10_STD,
    image_key: str = "image",
):
    """Device-side (px/255 - mean)/std as a train-step input_transform.

    Pair with BatchAugmenter(normalize=False): the host crops/flips
    uint8 and ships 4x fewer bytes over the host->device link (616 ->
    154 MB per 1024-image ImageNet batch); XLA fuses
    the scale+bias into the first convolution. Exactly the same f32
    arithmetic as the host path (same _scale_bias formulation), so the
    two placements train identically (tests/test_augment.py).
    """
    import jax.numpy as jnp

    scale, bias = _scale_bias(
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
    )
    scale_j, bias_j = jnp.asarray(scale), jnp.asarray(bias)

    def transform(batch: Dict) -> Dict:
        out = dict(batch)
        out[image_key] = (
            batch[image_key].astype(jnp.float32) * scale_j + bias_j
        )
        return out

    return transform


def _normalize_numpy(images, crop_h, crop_w, mean, std):
    n, h, w, c = images.shape
    scale, bias = _scale_bias(mean, std)
    top = (h - crop_h) // 2
    left = (w - crop_w) // 2
    out = images[:, top : top + crop_h, left : left + crop_w, :].astype(
        np.float32
    )
    out *= scale
    out += bias
    return out


class BatchAugmenter:
    """Host-side training augmentation over a batch dict's image column.

    - ``pad`` + random crop to ``crop`` (torchvision RandomCrop(padding=)
      semantics, zero padding);
    - horizontal flip with probability 0.5 (``hflip=True``);
    - (px/255 - mean)/std normalization to f32 NHWC.

    ``backend``: "auto" uses the native kernel when it loads, else numpy;
    "native" requires it; "numpy" forces the fallback. The kernel handles
    up to 16 channels — wider images take the numpy path regardless.
    Call with a batch dict (transform-hook contract) or a raw [N,H,W,C]
    uint8 array.
    """

    def __init__(
        self,
        crop: Tuple[int, int] = (32, 32),
        pad: int = 4,
        hflip: bool = True,
        mean: Sequence[float] = CIFAR10_MEAN,
        std: Sequence[float] = CIFAR10_STD,
        image_key: str = "image",
        seed: int = 0,
        train: bool = True,
        backend: str = "auto",
        normalize: bool = True,
    ):
        self.crop = tuple(crop)
        self.pad = int(pad)
        self.hflip = hflip
        self.image_key = image_key
        self.train = train
        #: normalize=False keeps the output uint8 (crop/flip only) for
        #: device-side normalization — pair with device_normalize(mean,
        #: std) as the train step's input_transform (4x less H2D traffic).
        self.normalize = normalize
        self._rng = np.random.default_rng(seed)
        # numpy Generators are not thread-safe; the prefetcher's
        # assembly pool calls __call__ concurrently.
        self._rng_lock = threading.Lock()
        self._mean = np.ascontiguousarray(mean, np.float32)
        self._std = np.ascontiguousarray(std, np.float32)

        if backend not in ("auto", "native", "numpy"):
            raise ValueError(f"unknown backend {backend!r}")
        self._lib = None
        if backend in ("auto", "native"):
            from tpudl.native import load_library

            self._lib = load_library()
            if self._lib is None and backend == "native":
                raise RuntimeError(
                    "backend='native' but the C++ kernel is unavailable "
                    "(the g++ build of tpudl/native/augment.cpp failed)"
                )

    @property
    def backend(self) -> str:
        return "native" if self._lib is not None else "numpy"

    def __call__(self, batch):
        if isinstance(batch, dict):
            out = dict(batch)
            out[self.image_key] = self._images(batch[self.image_key])
            return out
        return self._images(batch)

    def _images(self, images: np.ndarray) -> np.ndarray:
        images = np.ascontiguousarray(images)
        if images.dtype != np.uint8 or images.ndim != 4:
            raise ValueError(
                f"expected uint8 [N,H,W,C] images, got {images.dtype} "
                f"{images.shape}"
            )
        n, h, w, c = images.shape
        ch, cw = self.crop
        if self.normalize and len(self._mean) != c:
            # (normalize=False never touches mean/std — a pure crop/flip
            # pipeline over grayscale/RGBA needs no constants.)
            raise ValueError(
                f"mean/std have {len(self._mean)} channels, images have {c}"
            )
        lib = self._lib if c <= 16 else None  # kernel caps channels at 16
        if not self.normalize:
            # uint8 out: pure crop/flip on the host, normalization on
            # device — the native kernel fuses normalize so this takes
            # the (cheap) numpy slicing path.
            lib = None
        if not self.train:
            return self._center(images, lib)
        max_top = h + 2 * self.pad - ch
        max_left = w + 2 * self.pad - cw
        if max_top < 0 or max_left < 0:
            raise ValueError(
                f"crop {self.crop} larger than padded image "
                f"({h + 2 * self.pad}, {w + 2 * self.pad})"
            )
        with self._rng_lock:
            offsets = np.stack(
                [
                    self._rng.integers(0, max_top + 1, n),
                    self._rng.integers(0, max_left + 1, n),
                ],
                axis=1,
            ).astype(np.int32)
            flip = (
                self._rng.random(n) < 0.5
                if self.hflip
                else np.zeros(n, bool)
            ).astype(np.uint8)

        if lib is None:
            return _augment_numpy(
                images, self.pad, ch, cw, offsets, flip, self._mean,
                self._std, normalize=self.normalize,
            )
        import ctypes

        out = np.empty((n, ch, cw, c), np.float32)
        lib.tpudl_augment_batch(
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, h, w, c, self.pad, ch, cw,
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            flip.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            self._mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        return out

    def _center(self, images: np.ndarray, lib) -> np.ndarray:
        n, h, w, c = images.shape
        ch, cw = self.crop
        if ch > h or cw > w:
            raise ValueError(f"center crop {self.crop} larger than ({h}, {w})")
        if not self.normalize:
            top = (h - ch) // 2
            left = (w - cw) // 2
            return np.ascontiguousarray(
                images[:, top : top + ch, left : left + cw, :]
            )
        if lib is None:
            return _normalize_numpy(images, ch, cw, self._mean, self._std)
        import ctypes

        out = np.empty((n, ch, cw, c), np.float32)
        lib.tpudl_normalize_batch(
            images.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, h, w, c, ch, cw,
            self._mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._std.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        return out
