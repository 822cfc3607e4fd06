"""Model serialization: StableHLO artifacts and Orbax parameter checkpoints.

TPU-native analog of the reference's three export paths
(reference notebooks/cv/onnx_experiments.py):
- ONNX opset-12 export (:33-42)        -> jax.export / StableHLO bytes
- whole-module pickle torch.save (:198) -> Orbax param checkpoint
- TorchScript trace (:206-215)          -> the same StableHLO artifact
  (XLA graph capture is inherent in jit; no separate tracer product)
- artifact size comparison via `ls -all` (:194,202,219) -> artifact_sizes()
"""

from __future__ import annotations

import os
from typing import Any, Callable, Optional, Sequence, Union

import jax
from jax import export as jax_export


def export_stablehlo(
    fn: Callable,
    args: Sequence[Any],
    path: Optional[str] = None,
    platforms: Optional[Sequence[str]] = None,
) -> bytes:
    """Trace+lower `fn` at `args` and serialize the StableHLO artifact.

    `platforms` (e.g. ("cpu", "tpu")) bakes multi-platform lowering into one
    artifact — the single-artifact-many-backends property the reference gets
    from ONNX.
    """
    jitted = jax.jit(fn)
    if platforms:
        exported = jax_export.export(jitted, platforms=tuple(platforms))(*args)
    else:
        exported = jax_export.export(jitted)(*args)
    blob = exported.serialize()
    if path:
        with open(path, "wb") as f:
            f.write(blob)
    return blob


def load_exported_obj(blob_or_path: Union[bytes, str]) -> "jax_export.Exported":
    """Deserialize a StableHLO artifact into the full Exported object —
    callable via ``.call`` AND introspectable via ``.in_avals`` /
    ``.in_tree`` (how a serving runtime recovers the compiled shapes —
    slot count, prompt window, cache bound — from the artifact alone;
    see tpudl.serve.api.ServeSession.from_artifacts)."""
    if isinstance(blob_or_path, str):
        with open(blob_or_path, "rb") as f:
            blob = f.read()
    else:
        blob = blob_or_path
    try:
        return jax_export.deserialize(blob)
    except Exception as e:
        source = blob_or_path if isinstance(blob_or_path, str) else "<bytes>"
        raise ValueError(
            f"{source} is not a valid serialized StableHLO artifact "
            f"(expected output of export_stablehlo): {type(e).__name__}: {e}"
        ) from e


def load_exported(blob_or_path: Union[bytes, str]) -> Callable:
    """Deserialize a StableHLO artifact into a callable (the
    InferenceSession analog, reference notebooks/cv/onnx_experiments.py:81)."""
    return load_exported_obj(blob_or_path).call


def save_params(path: str, params: Any, overwrite: bool = True) -> None:
    """Orbax checkpoint of a parameter pytree (the torch.save analog)."""
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, params, force=overwrite)


def load_params(path: str, like: Optional[Any] = None) -> Any:
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with ocp.StandardCheckpointer() as ckptr:
        if like is not None:
            return ckptr.restore(path, like)
        return ckptr.restore(path)


def artifact_sizes(*paths: str) -> dict:
    """Byte sizes of export artifacts (files or checkpoint dirs)."""
    out = {}
    for p in paths:
        if os.path.isdir(p):
            total = 0
            for root, _, files in os.walk(p):
                total += sum(os.path.getsize(os.path.join(root, f)) for f in files)
            out[p] = total
        elif os.path.exists(p):
            out[p] = os.path.getsize(p)
        else:
            out[p] = None
    return out
