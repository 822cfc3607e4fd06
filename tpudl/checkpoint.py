"""Train-state checkpoint / resume (Orbax), the recovery half of the
failure story.

The reference serializes models three ways but never training state and
never reads anything back to resume (reference
notebooks/cv/onnx_experiments.py:33-42,198,212-215 — ONNX export,
whole-module pickle, TorchScript trace; SURVEY.md §5.4). Here the full
TrainState — params, optimizer state, step counter, BatchNorm statistics —
round-trips through step-indexed Orbax checkpoints, and restore is
sharding-aware: leaves come back already placed according to the mesh +
rule set of the run being resumed (possibly a different topology than the
one that saved), so no full-state replication spike on big models.
"""

from __future__ import annotations

import json
import os
import shutil
import warnings
from typing import Optional, Tuple

import jax
import orbax.checkpoint as ocp
from jax.sharding import Mesh

from tpudl.ft.store import CheckpointShapeError  # noqa: F401  (re-export:
# the error both backends' restores raise on a changed-model template)
from tpudl.obs import counters as obs_counters
from tpudl.obs import spans as obs_spans
from tpudl.parallel.sharding import Rules, tree_shardings
from tpudl.train.loop import TrainState


def _ckpt_span(name: str, **attrs):
    """Checkpoint-category obs span (no-op when observability is off).
    Covers the SYNCHRONOUS part of a save — for async saves that is the
    device->host copy, which is exactly the slice of wall-clock the
    train loop loses to checkpointing."""
    return obs_spans.span(name, obs_spans.CAT_CHECKPOINT, **attrs)


def _state_payload(state: TrainState) -> dict:
    """The serializable subset of a TrainState (apply_fn/tx are code, not
    data — they come from the resuming program)."""
    payload = {
        "params": state.params,
        "opt_state": state.opt_state,
        # step may be a Python int on a fresh state; canonicalize for Orbax.
        "step": jax.numpy.asarray(state.step, jax.numpy.int32),
    }
    if state.batch_stats is not None:
        payload["batch_stats"] = state.batch_stats
    if getattr(state, "precision", None) is not None:
        # Mixed-precision policy state (tpudl.train.precision): loss
        # scale + fp8 amax rings — without it a resume would restart
        # the loss-scale schedule and re-warm every amax window.
        payload["precision"] = state.precision
    return payload


def _abstract_payload(
    state: TrainState, mesh: Optional[Mesh], rules: Optional[Rules]
) -> dict:
    """ShapeDtypeStruct tree for restore; with a mesh, each leaf carries the
    NamedSharding the rule set assigns, so Orbax materializes shards
    directly onto devices."""
    payload = _state_payload(state)
    if mesh is None:
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype), payload
        )
    shardings = tree_shardings(mesh, payload, rules)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(jax.numpy.shape(x), x.dtype, sharding=s),
        payload,
        shardings,
    )


_STAGE_SUFFIX = ".tpudl-staging"
_PREV_SUFFIX = ".tpudl-prev"


def save_train_state(path: str, state: TrainState, overwrite: bool = True) -> None:
    """One-shot full-train-state checkpoint at `path`.

    Crash-safe by construction: the payload is written to a STAGING
    sibling (``<path>.tpudl-staging``) first, then published with two
    renames (old -> ``<path>.tpudl-prev``, staging -> ``<path>``). A
    crash at any point leaves either the old checkpoint at `path`, or
    the new one, or — in the one window between the renames — the old
    one intact under the ``.tpudl-prev`` name, which
    ``restore_train_state`` falls back to. Never a torn directory that
    restore would trust."""
    path = os.path.abspath(path)
    staging = path + _STAGE_SUFFIX
    prev = path + _PREV_SUFFIX
    if os.path.exists(path) and not overwrite:
        raise FileExistsError(f"checkpoint exists at {path}")
    with _ckpt_span("save_train_state"):
        # Stale staging debris from an earlier crash must not block
        # this save.
        shutil.rmtree(staging, ignore_errors=True)
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(staging, _state_payload(state), force=True)
        if os.path.exists(path):
            shutil.rmtree(prev, ignore_errors=True)
            os.rename(path, prev)
        # If only a .tpudl-prev survives (a PREVIOUS save crashed
        # mid-publish), it is the sole restorable checkpoint — it must
        # outlive the publish rename below, never be deleted before it.
        os.rename(staging, path)
        shutil.rmtree(prev, ignore_errors=True)


def restore_train_state(
    path: str,
    state: TrainState,
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
) -> TrainState:
    """Restore a checkpoint into `state`'s structure (a freshly-initialized
    TrainState from the same model/optimizer code). With `mesh`/`rules`,
    leaves arrive sharded for that topology. If `path` is missing but a
    ``.tpudl-prev`` sibling exists (a save crashed mid-publish), the
    previous committed checkpoint restores with a warning."""
    path = os.path.abspath(path)
    if not os.path.exists(path) and os.path.exists(path + _PREV_SUFFIX):
        warnings.warn(
            f"checkpoint {path} missing but a previous committed copy "
            f"exists ({path + _PREV_SUFFIX}) — a save crashed "
            f"mid-publish; restoring the previous checkpoint",
            stacklevel=2,
        )
        path = path + _PREV_SUFFIX
    with _ckpt_span("restore_train_state"):
        with ocp.StandardCheckpointer() as ckptr:
            payload = ckptr.restore(
                path, _abstract_payload(state, mesh, rules)
            )
    extra = {}
    if hasattr(state, "precision"):
        extra["precision"] = payload.get("precision", state.precision)
    return state.replace(
        params=payload["params"],
        opt_state=payload["opt_state"],
        step=payload["step"],
        batch_stats=payload.get("batch_stats", state.batch_stats),
        **extra,
    )


class CheckpointManager:
    """Step-indexed checkpoints with retention — the periodic-save side of
    fail-fast-then-resume (SURVEY.md §5.3/§5.4).

    save() is asynchronous (training continues while shards flush);
    close()/context-manager exit drains pending writes.

    Multi-process invariants (proved by
    tests/test_distributor.py::test_spawn_checkpoint_save_resume — a
    2-process TpuDistributor spawn that trains, saves, exits, and a
    FRESH spawn restores and continues):

    - every rank calls save()/restore() collectively; Orbax coordinates
      the write over jax.distributed (which TpuDistributor initializes)
      and the shared checkpoint directory, so no rank-0-only gating is
      needed in caller code;
    - restore() with mesh/rules materializes each rank's addressable
      shards directly onto its devices (no full-state host replication);
    - the restored trajectory is EXACTLY the uninterrupted one: params,
      optimizer momenta, BatchNorm stats, and the step counter (which
      seeds the per-step dropout/rng fold) all round-trip, and all
      ranks report identical global losses after the resume boundary.

    Two backends behind one API:

    - **Orbax** (default): multi-process-coordinated shard IO — the pod
      path for state sharded ACROSS processes.
    - **async_save=True**: tpudl.ft.AsyncCheckpointManager — the
      bounded-stall path: device->host snapshot on the step path only,
      serialization + atomic commit on a background writer thread
      (tpudl/ft/). fit() works identically against both.

    Both modes carry FULL resume state when ``save`` is given ``rng`` /
    ``data_state`` (the training RNG key and the data position), and
    ``restore_full`` returns them — so a resumed run replays neither
    batches nor dropout masks (Orbax mode keeps them in an atomically-
    written ``_tpudl_resume/`` sidecar next to the step dirs; the ft
    store carries them natively). Restores validate leaf shapes against
    the SAVED checkpoint's metadata and raise CheckpointShapeError
    naming the mismatched paths — Orbax would otherwise silently return
    the saved shapes and crash later inside the jitted step.
    """

    def __init__(
        self, directory: str, max_to_keep: int = 3, async_save: bool = False
    ):
        directory = os.path.abspath(directory)
        self._max_to_keep = max_to_keep
        self._impl = None
        self._mgr = None
        if async_save:
            from tpudl.ft.manager import AsyncCheckpointManager

            self._impl = AsyncCheckpointManager(
                directory, max_to_keep=max_to_keep
            )
            self.directory = self._impl.directory
        else:
            self._mgr = ocp.CheckpointManager(
                directory,
                options=ocp.CheckpointManagerOptions(
                    max_to_keep=max_to_keep, enable_async_checkpointing=True
                ),
            )
            self.directory = directory

    # -- resume-state sidecar (Orbax mode) ----------------------------

    def _sidecar_dir(self) -> str:
        return os.path.join(self.directory, "_tpudl_resume")

    def _sidecar_path(self, step: int) -> str:
        return os.path.join(self._sidecar_dir(), f"{step}.json")

    def _write_sidecar(
        self, step: int, rng: Optional[jax.Array], data_state: Optional[dict]
    ) -> None:
        if rng is None and data_state is None:
            return
        if jax.process_index() != 0:
            return  # one writer; every rank reads the shared file
        from tpudl.ft.manager import _encode_rng

        payload: dict = {"data_state": data_state}
        if rng is not None:
            rng_arr, rng_meta = _encode_rng(rng)
            payload["rng_data"] = rng_arr.tolist()
            payload["rng_dtype"] = str(rng_arr.dtype)
            payload["rng_meta"] = rng_meta
        os.makedirs(self._sidecar_dir(), exist_ok=True)
        tmp = self._sidecar_path(step) + f".tmp{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, self._sidecar_path(step))
        # Retention mirrors the manager's: keep the newest max_to_keep;
        # crash debris (tmp files whose os.replace never ran) is reaped
        # too — this process is the sole writer, so any tmp not our own
        # is a dead writer's.
        try:
            entries = os.listdir(self._sidecar_dir())
        except OSError:
            return
        own_suffix = f".tmp{os.getpid()}"
        for name in entries:
            if ".json.tmp" in name and not name.endswith(own_suffix):
                try:
                    os.remove(os.path.join(self._sidecar_dir(), name))
                except OSError:
                    pass
        if not self._max_to_keep:
            return
        try:
            names = sorted(
                int(n[: -len(".json")])
                for n in entries
                if n.endswith(".json")
            )
        except ValueError:
            return
        for victim in names[: -self._max_to_keep]:
            try:
                os.remove(self._sidecar_path(victim))
            except OSError:
                pass

    def _read_sidecar(
        self, step: int
    ) -> Tuple[Optional[jax.Array], Optional[dict]]:
        try:
            with open(self._sidecar_path(step)) as f:
                payload = json.load(f)
        except (OSError, json.JSONDecodeError):
            return None, None
        rng = None
        if payload.get("rng_data") is not None:
            import numpy as np

            from tpudl.ft.manager import _decode_rng

            rng = _decode_rng(
                np.asarray(
                    payload["rng_data"],
                    dtype=payload.get("rng_dtype", "uint32"),
                ),
                payload.get("rng_meta") or {},
            )
        return rng, payload.get("data_state")

    # -- save/restore --------------------------------------------------

    def save(
        self,
        step: int,
        state: TrainState,
        rng: Optional[jax.Array] = None,
        data_state: Optional[dict] = None,
    ) -> bool:
        # INVARIANT callers rely on (tpudl.train.loop.fit donates the
        # just-saved state's buffers to the next compiled step): both
        # backends perform the device-to-host copy synchronously inside
        # save() and only background the serialization/disk write.
        if self._impl is not None:
            return self._impl.save(step, state, rng=rng, data_state=data_state)
        rec = obs_spans.active_recorder()
        span = None
        if rec is not None:
            span = rec.begin(
                "checkpoint_save", obs_spans.CAT_CHECKPOINT, step=step
            )
        saved = self._mgr.save(
            step, args=ocp.args.StandardSave(_state_payload(state))
        )
        if saved:
            self._write_sidecar(step, rng, data_state)
        if span is not None:
            dur = span.end()["dur"]
            reg = obs_counters.registry()
            reg.histogram("checkpoint_time_s").observe(dur)
            if saved:
                reg.counter("checkpoint_saves").inc()
        return saved

    def _validate_against_metadata(self, step: int, abstract: dict) -> None:
        """Compare the restore template against the checkpoint's SAVED
        array metadata; raise CheckpointShapeError on mismatch (Orbax
        silently restores the saved shapes otherwise — the wrong-shape
        state then crashes later, far from the cause)."""
        try:
            meta = self._mgr.item_metadata(step)
        except Exception:
            return  # metadata unavailable: keep legacy behavior
        if meta is None:
            return
        jtu = jax.tree_util

        def norm(path) -> str:
            # Orbax metadata renders tuple positions as STRING dict
            # keys ('opt_state'/'0'/...), the abstract tree as
            # SequenceKey ints — normalize both to one spelling.
            parts = []
            for k in path:
                if hasattr(k, "key"):
                    parts.append(str(k.key))
                elif hasattr(k, "idx"):
                    parts.append(str(k.idx))
                elif hasattr(k, "name"):
                    parts.append(str(k.name))
                else:
                    parts.append(str(k))
            return "/".join(parts)

        from tpudl.ft.store import diff_leaf_shapes

        diff_leaf_shapes(
            {
                norm(p): tuple(getattr(m, "shape", ()) or ())
                for p, m in jtu.tree_flatten_with_path(meta)[0]
            },
            {
                norm(p): tuple(leaf.shape)
                for p, leaf in jtu.tree_flatten_with_path(abstract)[0]
            },
            f"checkpoint step {step} does not match the restore template",
        )

    def restore(
        self,
        state: TrainState,
        step: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        rules: Optional[Rules] = None,
    ) -> TrainState:
        if self._impl is not None:
            return self._impl.restore(state, step=step, mesh=mesh, rules=rules)
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint found in {self._mgr.directory}"
                )
        abstract = _abstract_payload(state, mesh, rules)
        self._validate_against_metadata(step, abstract)
        with _ckpt_span("checkpoint_restore", step=step):
            payload = self._mgr.restore(
                step, args=ocp.args.StandardRestore(abstract)
            )
        extra = {}
        if hasattr(state, "precision"):
            extra["precision"] = payload.get("precision", state.precision)
        return state.replace(
            params=payload["params"],
            opt_state=payload["opt_state"],
            step=payload["step"],
            batch_stats=payload.get("batch_stats", state.batch_stats),
            **extra,
        )

    def restore_full(
        self,
        state: TrainState,
        step: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        rules: Optional[Rules] = None,
    ) -> Tuple[TrainState, Optional[jax.Array], Optional[dict]]:
        """Restore ``(state, rng, data_state)`` — the training RNG key
        and data position saved alongside the state (None each when the
        checkpoint predates them)."""
        if self._impl is not None:
            return self._impl.restore_full(
                state, step=step, mesh=mesh, rules=rules
            )
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint found in {self._mgr.directory}"
                )
        restored = self.restore(state, step=step, mesh=mesh, rules=rules)
        rng, data_state = self._read_sidecar(step)
        return restored, rng, data_state

    def latest_step(self) -> Optional[int]:
        if self._impl is not None:
            return self._impl.latest_step()
        return self._mgr.latest_step()

    def all_steps(self):
        if self._impl is not None:
            return self._impl.all_steps()
        return self._mgr.all_steps()

    def wait_until_finished(self) -> None:
        if self._impl is not None:
            self._impl.wait_until_finished()
        else:
            self._mgr.wait_until_finished()

    def close(self) -> None:
        if self._impl is not None:
            self._impl.close()
        else:
            self._mgr.close()

    def __enter__(self) -> "CheckpointManager":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
