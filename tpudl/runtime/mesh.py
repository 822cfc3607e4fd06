"""Device-mesh construction for TPU slices.

TPU-native replacement for the reference lineage's process-group topology
(HorovodRunner / NCCL worker rings — named as the thing being replaced by
BASELINE.json `north_star`; the reference itself ships no communication
backend: the only device-boundary ops in the whole tree are host<->device
copies at notebooks/cv/onnx_experiments.py:69-72,93).

Design: one logical 6-axis mesh covers every parallelism strategy the
framework supports. Unused axes have size 1 and cost nothing:

- ``dp``   — pure data parallelism (gradients psum'd over ICI).
- ``fsdp`` — data parallelism with parameter/optimizer sharding
             (ZeRO-3 / GSPMD-style; params all-gathered per layer by XLA).
- ``sp``   — sequence/context parallelism (activations sharded along the
             sequence axis; ring attention rotates K/V via ppermute, or
             ulysses attention reshards heads<->sequence via all-to-all).
- ``tp``   — tensor (model) parallelism (contracting-dim sharding of
             matmuls; XLA inserts all-reduce/reduce-scatter).
- ``pp``   — pipeline parallelism (layer stages spread over devices;
             activations hop stage-to-stage via ppermute —
             tpudl.parallel.pipeline).
- ``ep``   — expert parallelism (MoE expert weights sharded over the
             expert dim; token dispatch rides all-to-all —
             tpudl.ops.moe).

Shardings are expressed as ``PartitionSpec``s over these names; XLA/GSPMD
lowers them to ICI collectives inside the compiled step (no Python in the
gradient-sync path — the structural difference from Horovod's per-tensor
allreduce hooks).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_DATA = "dp"
AXIS_FSDP = "fsdp"
AXIS_SEQ = "sp"
AXIS_TENSOR = "tp"
AXIS_PIPE = "pp"
AXIS_EXPERT = "ep"

#: Canonical axis order of every tpudl mesh.
MESH_AXES: tuple[str, ...] = (
    AXIS_DATA,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
    AXIS_PIPE,
    AXIS_EXPERT,
)

#: Axes over which the global batch is split (data-like axes).
BATCH_AXES: tuple[str, ...] = (AXIS_DATA, AXIS_FSDP)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh shape. ``-1`` on at most one axis means "fill with the
    remaining devices" (like a reshape wildcard)."""

    dp: int = -1
    fsdp: int = 1
    sp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1

    def resolve(self, num_devices: int) -> tuple[int, ...]:
        sizes = [self.dp, self.fsdp, self.sp, self.tp, self.pp, self.ep]
        wild = [i for i, s in enumerate(sizes) if s == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one wildcard (-1) axis allowed, got {sizes}")
        fixed = math.prod(s for s in sizes if s != -1)
        if wild:
            if num_devices % fixed != 0:
                raise ValueError(
                    f"{num_devices} devices not divisible by fixed axes {sizes}"
                )
            sizes[wild[0]] = num_devices // fixed
        if math.prod(sizes) != num_devices:
            raise ValueError(
                f"Mesh {dict(zip(MESH_AXES, sizes))} needs {math.prod(sizes)} "
                f"devices, have {num_devices}"
            )
        return tuple(sizes)  # type: ignore[return-value]

    def build(self, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
        return make_mesh(self, devices)

    def fit(self, num_devices: int) -> "MeshSpec":
        """Clamp this spec to a device count it doesn't fit — each fixed
        axis shrinks to gcd(size, remaining devices) in declaration order,
        the wildcard absorbs the rest. A config declared for a v4-32
        (e.g. dp=-1, fsdp=4) then runs unchanged on the pod but clamps to
        (1,1,1,1,1,1) on the one local chip, so every BASELINE.json config
        is drivable anywhere. Requires a wildcard axis (all tpudl configs
        declare dp=-1)."""
        sizes = [self.dp, self.fsdp, self.sp, self.tp, self.pp, self.ep]
        if -1 not in sizes:
            raise ValueError(
                f"fit() needs a wildcard (-1) axis to absorb devices, got "
                f"{sizes}"
            )
        remaining = num_devices
        fitted = []
        for s in sizes:
            if s == -1:
                fitted.append(-1)
                continue
            s = math.gcd(s, remaining)
            fitted.append(s)
            remaining //= s
        return MeshSpec(*fitted)


def make_mesh(
    spec: MeshSpec | Sequence[int] | None = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a 6-axis ``Mesh`` (dp, fsdp, sp, tp, pp, ep) over ``devices``.

    Uses ``mesh_utils.create_device_mesh`` so that on real TPU slices the
    mesh axes are laid out along the physical ICI torus (nearest-neighbor
    axes get the fastest links); on CPU fake devices it degrades to a plain
    reshape.
    """
    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec()
    if not isinstance(spec, MeshSpec):
        spec = MeshSpec(*spec)
    shape = spec.resolve(len(devices))
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError):
        # Fallback for device sets create_device_mesh can't topologize
        # (e.g. single device, or odd CPU fake-device counts).
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def batch_partition_spec(extra_dims: int = 0) -> PartitionSpec:
    """PartitionSpec for a batch-leading array: batch over (dp, fsdp)."""
    return PartitionSpec(BATCH_AXES, *([None] * extra_dims))


def batch_sharding(mesh: Mesh, extra_dims: int = 0) -> NamedSharding:
    return NamedSharding(mesh, batch_partition_spec(extra_dims))


def window_partition_spec(extra_dims: int = 0) -> PartitionSpec:
    """PartitionSpec for a [K, B, ...] stacked dispatch window (the
    fused multi-step path): the scan axis is replicated — every device
    steps through the same K slots — and the batch dim shards over
    (dp, fsdp) exactly as a single batch would."""
    return PartitionSpec(None, BATCH_AXES, *([None] * extra_dims))


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-process batch size given a global batch sharded over (dp, fsdp)."""
    n_shards = mesh.shape[AXIS_DATA] * mesh.shape[AXIS_FSDP]
    n_proc = jax.process_count()
    if global_batch % n_shards != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by dp*fsdp = {n_shards}"
        )
    if global_batch % n_proc != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {n_proc}"
        )
    return global_batch // n_proc
