"""The boundary where a session takes its weights: held as the serving
programs read them (tpudl.models.turned), where that buys something.

What decides is what the session can observe, no knob: parameters that
lie whole on ONE TPU device get their head-split attention kernels
turned, once, at set-up; host arrays, a CPU run, a tree spread over a
mesh (its sharding rules name the declared axes), a quantized tree (its
scales run along the declared axes) and the tree under the adapter
decode are kept exactly as given.

Beside it, the two instruments that say whether it engaged and whether
the rule still holds: ``asked_layouts`` (what the chip's compiler
answers when a program's weight layouts are left to it) and
``weight_copies`` (the kernels a compiled program turns over in every
call). ``scripts/weight_copies.py`` prints both for the benchmark's
configurations; tests/test_tpu_compile.py holds the rule to them.
"""

from __future__ import annotations

import math
import re
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from tpudl.models.turned import turn
from tpudl.obs.spans import startup_phase


def chip_of(params) -> Optional[jax.Device]:
    """The one TPU device every leaf lies on (arrays, or the placed
    shapes of a compile rehearsal), else None: host arrays, shapes
    placed nowhere, another backend, a tree spread over a mesh."""
    devices = set()
    for leaf in jax.tree.leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if not isinstance(sharding, SingleDeviceSharding):
            return None
        devices |= sharding.device_set
    if len(devices) != 1:
        return None
    (device,) = devices
    return device if device.platform == "tpu" else None


@startup_phase(
    "startup.weights", lambda out: {"leaves": out[1], "bytes": out[2]}
)
def held(params) -> Tuple[Any, int, int]:
    """``params`` as a session on a chip holds them: ``(tree, leaves,
    nbytes)``, the kernels of ``tpudl.models.turned.TURNED`` turned and
    counted; as given, ``0, 0``, where ``chip_of`` finds no chip.
    Recorded as ``startup.weights`` (the count and the bytes are its
    attributes); the turning is dispatched, not waited for."""
    if chip_of(params) is None:
        return params, 0, 0
    return turn(params)


def asked_layouts(
    fn: Callable, params, rest: Sequence[Any], donate_argnums=()
) -> List[Tuple[str, tuple, tuple]]:
    """Ask the compiler: ``fn(params, *rest)`` compiled for the chip
    ``params`` is placed on with the layout of every weight matrix left
    to it (``jax.experimental.layout``, ``Layout.AUTO``; every other
    argument keeps the default), and the matrices it wants another way
    round than their shapes declare: ``(path, shape, major_to_minor)``.
    Arguments are arrays or shapes; nothing runs. A stacked tensor of
    experts is not asked about: a grouped matmul reads it where it lies
    (no copy as given), and the compiler, asked, still has it turned
    over (the sarvam cell: 4.3 of 9 GB)."""
    from jax.experimental.layout import Format, Layout

    chip = SingleDeviceSharding(chip_of(params))

    def placed(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree,
        )

    ask = jax.tree.map(
        lambda leaf: Format(Layout.AUTO if leaf.ndim == 2 else None, chip),
        params,
    )
    compiled = jax.jit(
        fn,
        in_shardings=(ask, *(None,) * len(rest)),
        donate_argnums=donate_argnums,
    ).lower(placed(params), *placed(tuple(rest))).compile()
    answered = jax.tree_util.tree_flatten_with_path(
        compiled.input_formats[0][0]
    )[0]
    return [
        (jax.tree_util.keystr(path), leaf.shape, a.layout.major_to_minor)
        for (path, a), leaf in zip(answered, jax.tree.leaves(params))
        if a.layout.major_to_minor != tuple(range(leaf.ndim))
    ]


_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def weight_copies(compiled_text: str, params) -> List[str]:
    """The ``copy`` and ``transpose`` instructions in the ENTRY of a
    compiled program's text whose result holds as many elements as a
    weight of two or more axes in ``params``, in that weight's dtype: a
    kernel turned over inside the program, in every call (``copy-start``
    / ``copy-done`` and ``slice-done`` are prefetches, bytes that move
    anyway, and are not counted). What a trace's
    ``copy_<dtype>_<shape>_`` row is. A row of activations that happens
    to hold as many elements shows too (Laguna's 2,048-row keys): read
    the shape."""
    sizes = set()
    for leaf in jax.tree.leaves(params):
        if len(leaf.shape) >= 2:
            dtype = jnp.dtype(leaf.dtype).name
            sizes.add((_HLO_DTYPE.get(dtype, dtype), math.prod(leaf.shape)))
    entry = compiled_text[compiled_text.index("\nENTRY "):]
    return [
        f"{m[1]}[{m[2]}] {m[3]}"
        for m in re.finditer(
            r"= (\w+)\[([\d,]+)\]\S* (copy|transpose)\(", entry
        )
        if (m[1], math.prod(map(int, m[2].split(",")))) in sizes
    ]
