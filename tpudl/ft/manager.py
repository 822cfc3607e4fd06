"""AsyncCheckpointManager: full-resume-state checkpoints with a bounded
on-step stall.

The CheckpointManager-compatible face of the fault-tolerance subsystem
(tpudl.checkpoint.CheckpointManager(async_save=True) constructs one):

- ``save(step, state, rng=..., data_state=...)`` snapshots the device
  arrays to host copies synchronously (the only step-path cost, plus
  back-pressure if the previous save has not committed) and hands the
  bytes to a background writer thread that stages, fsyncs, and
  atomically commits (tpudl.ft.store / tpudl.ft.writer);
- the payload round-trips FULL resume state: params, optimizer state,
  BatchNorm stats, the step counter, the training RNG key, and the data
  position — so a restarted run is schedule-identical to an
  uninterrupted one (the resume-determinism contract, README "Fault
  tolerance");
- ``restore``/``restore_full`` are sharding-aware (leaves land placed
  per mesh+rules, like the Orbax path) and validate leaf shapes/dtypes
  against the committed metadata FIRST, raising CheckpointShapeError
  with the offending paths instead of a downstream reshape crash;
- a corrupted latest checkpoint (truncated payload, chaos-injected bit
  rot) makes ``restore_full(step=None)`` walk BACK to the newest
  committed step that loads, counting ``ft_corrupt_checkpoints`` —
  an operator signal, not a dead run.

Multi-process: arrays must be fully addressable or fully replicated
(the replicated-state + sharded-batch DP shape); process 0 is the sole
writer, every rank may restore from the shared directory. For state
sharded ACROSS processes use the Orbax mode, which coordinates
per-rank shard IO.
"""

from __future__ import annotations

import warnings
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.ft import chaos
from tpudl.ft.store import (
    CheckpointCorruptError,
    CheckpointShapeError,
    CheckpointStore,
)
from tpudl.ft.writer import AsyncCheckpointWriter
from tpudl.obs import counters as obs_counters
from tpudl.obs import spans as obs_spans

_RNG_KEY = "__rng__"


def state_payload(state: Any) -> dict:
    """The serializable subset of a TrainState (duck-typed — apply_fn/tx
    are code, supplied by the resuming program)."""
    payload = {
        "params": state.params,
        "opt_state": state.opt_state,
        "step": jnp.asarray(state.step, jnp.int32),
    }
    if getattr(state, "batch_stats", None) is not None:
        payload["batch_stats"] = state.batch_stats
    if getattr(state, "precision", None) is not None:
        # Mixed-precision policy state (loss scale + fp8 amax rings):
        # part of FULL resume — a restart must pick up the loss-scale
        # schedule and delayed-scaling windows exactly where they were.
        payload["precision"] = state.precision
    return payload


def flatten_with_keys(tree: Any) -> List[Tuple[str, Any]]:
    """[(keystr, leaf)] in flatten order — the on-disk leaf naming."""
    return [
        (jax.tree_util.keystr(path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def snapshot_to_host(leaves: List[Tuple[str, Any]]) -> List[Tuple[str, np.ndarray]]:
    """Device->host copies of every leaf — the bounded on-step stall.
    Fully-addressable arrays batch through one jax.device_get;
    fully-replicated cross-process arrays read their local replica."""
    out: List[Optional[np.ndarray]] = [None] * len(leaves)
    batched_idx, batched_vals = [], []
    for i, (key, leaf) in enumerate(leaves):
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            if leaf.is_fully_replicated:
                out[i] = np.asarray(leaf.addressable_data(0))
                continue
            raise ValueError(
                f"async checkpointing requires fully-addressable or "
                f"fully-replicated arrays; leaf {key!r} is sharded "
                f"across processes — use the Orbax mode "
                f"(CheckpointManager(async_save=False)) for "
                f"cross-process sharded state"
            )
        batched_idx.append(i)
        batched_vals.append(leaf)
    for i, host in zip(batched_idx, jax.device_get(batched_vals)):
        out[i] = np.asarray(host)
    return [(key, arr) for (key, _), arr in zip(leaves, out)]


def _encode_rng(rng: Optional[jax.Array]):
    """(host key data, meta) for a PRNG key — typed keys keep their impl
    name so hardware-RBG keys round-trip too."""
    if rng is None:
        return None, None
    if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key):
        try:
            impl = str(jax.random.key_impl(rng))
        except Exception:
            impl = None
        return np.asarray(jax.device_get(jax.random.key_data(rng))), {
            "typed": True, "impl": impl,
        }
    return np.asarray(jax.device_get(rng)), {"typed": False, "impl": None}


def _decode_rng(arr: np.ndarray, meta: dict) -> jax.Array:
    if not meta.get("typed"):
        return jnp.asarray(arr)
    impl = meta.get("impl")
    data = jnp.asarray(arr)
    if impl:
        try:
            return jax.random.wrap_key_data(data, impl=impl)
        except (TypeError, ValueError):
            pass
    return jax.random.wrap_key_data(data)


def validate_template(
    saved: "dict[str, dict]", template_leaves: List[Tuple[str, Any]]
) -> None:
    """Compare saved leaf shapes AND dtypes against a restore template;
    raise CheckpointShapeError naming every mismatch (the changed-
    model/changed-topology error a silent cast or downstream reshape
    crash would hide). The rng leaf is a save-side extra, not part of
    the template."""
    from tpudl.ft.store import diff_leaf_shapes

    saved = {k: v for k, v in saved.items() if k != _RNG_KEY}
    diff_leaf_shapes(
        {key: tuple(spec["shape"]) for key, spec in saved.items()},
        {
            key: tuple(getattr(leaf, "shape", ()))
            for key, leaf in template_leaves
        },
        "checkpoint/template mismatch",
        saved_dtypes={
            key: spec["dtype"] for key, spec in saved.items()
        },
        template_dtypes={
            key: str(getattr(leaf, "dtype", ""))
            for key, leaf in template_leaves
            if getattr(leaf, "dtype", None) is not None
        },
    )


class AsyncCheckpointManager:
    """Step-indexed async checkpoints with atomic commit + full resume
    state (see module docstring)."""

    def __init__(self, directory: str, max_to_keep: int = 3):
        self._store = CheckpointStore(directory, max_to_keep=max_to_keep)
        self._is_writer = jax.process_index() == 0
        self._writer: Optional[AsyncCheckpointWriter] = None
        if self._is_writer:
            self._store.gc_stale()
            self._writer = AsyncCheckpointWriter(self._store)

    @property
    def directory(self) -> str:
        return self._store.directory

    # -- save ----------------------------------------------------------

    def save(
        self,
        step: int,
        state: Any,
        rng: Optional[jax.Array] = None,
        data_state: Optional[dict] = None,
        block: bool = False,
    ) -> bool:
        """Snapshot + enqueue one checkpoint. Returns False on
        non-writer ranks and for steps already committed. ``block=True``
        waits for the commit (emergency/final saves)."""
        if not self._is_writer:
            return False
        if self._store.is_committed(step):
            return False
        rec = obs_spans.active_recorder()
        span = None
        if rec is not None:
            span = rec.begin(
                "checkpoint_save", obs_spans.CAT_CHECKPOINT, step=step,
                **{"async": True},
            )
        leaves = flatten_with_keys(state_payload(state))
        extra_meta: dict = {}
        if rng is not None:
            rng_arr, rng_meta = _encode_rng(rng)
            leaves.append((_RNG_KEY, rng_arr))
            extra_meta["rng"] = rng_meta
        if data_state is not None:
            extra_meta["data_state"] = data_state
        # The stall the step loop actually pays: back-pressure (inside
        # submit) + the device->host snapshot. The snapshot MUST finish
        # before returning — fit() donates this state's buffers to the
        # next compiled step.
        host_leaves = snapshot_to_host(leaves)
        waited = self._writer.submit(
            step, host_leaves, extra_meta=extra_meta,
            delay_hook=chaos.io_delay_hook(),
        )
        if span is not None:
            # One span covers the whole stall; back-pressure rides as
            # an attribute.
            dur = span.end(backpressure_s=waited)["dur"]
            reg = obs_counters.registry()
            reg.histogram("checkpoint_stall_s").observe(dur)
            if waited > 0:
                reg.histogram("checkpoint_backpressure_s").observe(waited)
        if block:
            self._writer.wait()
        return True

    # -- restore -------------------------------------------------------

    def restore(
        self,
        state: Any,
        step: Optional[int] = None,
        mesh=None,
        rules=None,
    ) -> Any:
        return self.restore_full(state, step=step, mesh=mesh, rules=rules)[0]

    def restore_full(
        self,
        state: Any,
        step: Optional[int] = None,
        mesh=None,
        rules=None,
    ) -> Tuple[Any, Optional[jax.Array], Optional[dict]]:
        """Restore ``(state, rng, data_state)``. ``step=None`` means the
        newest committed checkpoint, walking back past corrupt ones;
        an explicit step raises CheckpointCorruptError instead."""
        if step is not None:
            return self._restore_one(state, step, mesh, rules)
        steps = self._store.all_steps()
        if not steps:
            raise FileNotFoundError(
                f"no checkpoint found in {self._store.directory}"
            )
        last_err: Optional[Exception] = None
        for candidate in reversed(steps):
            try:
                return self._restore_one(state, candidate, mesh, rules)
            except CheckpointCorruptError as e:
                obs_counters.registry().counter(
                    "ft_corrupt_checkpoints"
                ).inc()
                warnings.warn(
                    f"checkpoint step {candidate} is corrupt, falling "
                    f"back to the previous committed step: {e}",
                    stacklevel=2,
                )
                last_err = e
        raise CheckpointCorruptError(
            f"every committed checkpoint in {self._store.directory} "
            f"failed to load"
        ) from last_err

    def _restore_one(self, state, step, mesh, rules):
        with obs_spans.span(
            "checkpoint_restore", obs_spans.CAT_CHECKPOINT, step=step
        ):
            meta, arrays = self._store.read(step)
            payload = state_payload(state)
            template = flatten_with_keys(payload)
            # Shapes AND dtypes validated up front — a mismatch raises
            # here with the offending paths, never a silent cast.
            validate_template(
                {l["key"]: l for l in meta["leaves"]}, template
            )
            if mesh is not None:
                from tpudl.parallel.sharding import (
                    host_to_global_array,
                    tree_shardings,
                )

                shardings = flatten_with_keys(
                    tree_shardings(mesh, payload, rules)
                )
                # host_to_global_array handles multi-process meshes
                # (non-addressable devices) that device_put refuses.
                placed = [
                    host_to_global_array(arrays[key], sh)
                    for (key, _), (_, sh) in zip(template, shardings)
                ]
            else:
                placed = [jnp.asarray(arrays[key]) for key, _ in template]
            treedef = jax.tree_util.tree_structure(payload)
            restored = jax.tree_util.tree_unflatten(treedef, placed)
        extra = {}
        if hasattr(state, "precision"):
            extra["precision"] = restored.get("precision", state.precision)
        new_state = state.replace(
            params=restored["params"],
            opt_state=restored["opt_state"],
            step=restored["step"],
            batch_stats=restored.get(
                "batch_stats", getattr(state, "batch_stats", None)
            ),
            **extra,
        )
        rng = None
        if meta.get("rng") is not None:
            rng = _decode_rng(arrays[_RNG_KEY], meta["rng"])
        return new_state, rng, meta.get("data_state")

    # -- bookkeeping ---------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._store.latest_step()

    def all_steps(self) -> List[int]:
        return self._store.all_steps()

    def wait_until_finished(self) -> None:
        if self._writer is not None:
            self._writer.wait()

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()

    def __enter__(self) -> "AsyncCheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
