"""Native (C++) data-path kernels, loaded via ctypes.

The reference lineage's input pipeline runs per-image work in native code
(torchvision transforms drive libtorch C++ — reference
notebooks/cv/onnx_experiments.py:55-66); tpudl's equivalent lives in
augment.cpp and is consumed through tpudl.data.augment.BatchAugmenter,
which falls back to a numpy implementation equal to f32 rounding when no
C++ toolchain is available — the native layer accelerates, never
changes, training.

Build: `load_library()` builds with g++ on first use, next to the
sources, under a name that carries the hash of ``augment.cpp`` — so the
library loaded is always the one built from the source as it stands,
whatever a copy of the tree did to file times (`*.so` is git-ignored).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

_log = logging.getLogger("tpudl.native")
_dir = os.path.dirname(os.path.abspath(__file__))
_src_path = os.path.join(_dir, "augment.cpp")
_lock = threading.Lock()
_lib: "ctypes.CDLL | None | bool" = None  # None = untried, False = failed


def _so_path() -> str:
    with open(_src_path, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_dir, f"libtpudl_data.{digest}.so")


def _build(so_path: str) -> bool:
    # Build beside the target and rename: a second process loading at
    # the same moment sees the whole library or none.
    tmp_path = f"{so_path}.{os.getpid()}.tmp"
    cmd = [
        os.environ.get("CXX", "g++"),
        "-O3",
        "-fPIC",
        "-fopenmp",
        "-shared",
        "-o",
        tmp_path,
        _src_path,
    ]
    try:
        subprocess.run(
            cmd, check=True, capture_output=True, text=True, timeout=120
        )
        os.replace(tmp_path, so_path)
        return True
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        _log.warning("native build failed (%s); using numpy fallback", detail)
        return False


def load_library() -> Optional[ctypes.CDLL]:
    """The native kernel library, building it if needed. None when no
    library built from the current source exists and no compiler works
    (callers fall back to numpy; ``BatchAugmenter.backend`` says which
    ran)."""
    global _lib
    with _lock:
        if _lib is None:
            so_path = _so_path()
            if not os.path.exists(so_path) and not _build(so_path):
                _lib = False
            else:
                try:
                    lib = ctypes.CDLL(so_path)
                    _configure(lib)
                    _lib = lib
                except OSError as e:
                    _log.warning("failed to load %s: %s", so_path, e)
                    _lib = False
        return _lib or None


def _configure(lib: ctypes.CDLL) -> None:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    i64 = ctypes.c_int64
    lib.tpudl_augment_batch.restype = None
    lib.tpudl_augment_batch.argtypes = [
        u8p, i64, i64, i64, i64, i64, i64, i64, i32p, u8p, f32p, f32p, f32p,
    ]
    lib.tpudl_normalize_batch.restype = None
    lib.tpudl_normalize_batch.argtypes = [
        u8p, i64, i64, i64, i64, i64, i64, f32p, f32p, f32p,
    ]
