"""MeshReplica: serving replicas whose programs span a device mesh.

A Router ``Replica`` has always been one driver thread over one local
device view. This module keeps that placement contract byte-identical
(health snapshots, routing books, sticky/adapter/prefix affinity,
migration pulls — all unchanged) and moves the MESH below it: the
replica's ServeSession is built from params committed to a
tensor-parallel ``Mesh`` via ``jax.device_put(params,
tree_shardings(mesh, params, rules))``, so every jitted serving
program (prefill, paged decode, chunk verify, the draft path) compiles
for that mesh's device assignment and GSPMD inserts the ICI
collectives. Host-side inputs (token ids, page tables) stay
uncommitted and replicate by propagation — the engine's bookkeeping
code does not know the mesh exists.

Tier-1 surface: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(tests/conftest.py) fakes an 8-device host, and several MeshReplicas
may share those devices — exactly like N thread replicas sharing one
chip today. Greedy traffic over a mesh replica is token-for-token
identical to ``generate()`` (tests/test_fleet_pod.py pins router
parity over two 8-device mesh replicas).

Multi-process (a REAL pod: one process per host, jax.distributed):
initialize the slice first — ``TpuDistributor.pod().run(worker)`` or
``jax.distributed.initialize`` — then build the same session over
``jax.devices()`` inside the worker; ``serving_mesh`` lays the tp axis
over the global device list. The CPU jaxlib cannot compile
cross-process computations, so that tier runs under
``@pytest.mark.needs_multiprocess`` (auto-skipped off-TPU by
conftest).
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax

from tpudl.parallel.sharding import TP_TRANSFORMER_RULES, tree_shardings
from tpudl.runtime.mesh import MeshSpec, make_mesh
from tpudl.serve.api import ServeSession
from tpudl.serve.router import Replica

#: Default placement for serving params: megatron column/row splits
#: over the tp axis (the fsdp entries clamp to size 1 on a pure-tp
#: serving mesh). Replicated leaves (norms, biases) ride the engine's
#: replicate-by-default; serving has no optimizer state to cover.
SERVE_MESH_RULES = TP_TRANSFORMER_RULES


def serving_mesh(
    devices: Optional[Sequence[jax.Device]] = None,
    tp: Optional[int] = None,
):
    """A serving mesh over ``devices`` (default: all local devices):
    tensor-parallel over ``tp`` of them (default: all). ``tp`` is
    gcd-clamped to the device count, so one knob value drives full and
    shrunk device grants alike (the chip mover hands this function
    arbitrary subsets)."""
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    spec = MeshSpec(dp=-1, fsdp=1, sp=1, tp=len(devices) if tp is None else tp)
    return make_mesh(spec.fit(len(devices)), devices)


def build_mesh_session(
    model,
    params: Any,
    prompt_len: int,
    mesh=None,
    devices: Optional[Sequence[jax.Device]] = None,
    tp: Optional[int] = None,
    rules=None,
    **from_model_kwargs,
) -> ServeSession:
    """A ServeSession whose params are committed to ``mesh`` (built
    over ``devices``/``tp`` when not given). Everything else is
    ``ServeSession.from_model`` verbatim — committed params are what
    make jit compile the serving programs for the mesh's device
    assignment; the cache template and speculative draft build from
    the sharded tree and follow by propagation, the paged pools are
    committed to the mesh before any program is built for them
    (``PagedKVCache.commit``, through ``from_model``'s ``mesh``). The
    returned session carries the mesh as ``session.mesh``."""
    if mesh is None:
        mesh = serving_mesh(devices, tp=tp)
    if rules is None:
        rules = SERVE_MESH_RULES
    sharded = jax.device_put(params, tree_shardings(mesh, params, rules))
    session = ServeSession.from_model(
        model, sharded, prompt_len, mesh=mesh, **from_model_kwargs
    )
    session.mesh = mesh
    return session


class MeshReplica(Replica):
    """A Router replica over a pjit-sharded ServeSession.

    Identical to ``Replica`` above the session (the router cannot tell
    them apart — that is the point); construction either wraps a
    prebuilt mesh session or builds one from ``(model, params,
    prompt_len)`` plus mesh arguments. ``replica.mesh`` names the
    devices this replica occupies — the chip mover's accounting unit.
    """

    def __init__(
        self,
        name: str,
        session: Optional[ServeSession] = None,
        model=None,
        params: Any = None,
        prompt_len: Optional[int] = None,
        mesh=None,
        devices: Optional[Sequence[jax.Device]] = None,
        tp: Optional[int] = None,
        rules=None,
        session_kwargs: Optional[dict] = None,
        **replica_kwargs,
    ):
        if session is None:
            if model is None or params is None or prompt_len is None:
                raise ValueError(
                    "MeshReplica needs either a prebuilt session or "
                    "(model, params, prompt_len) to build one"
                )
            session = build_mesh_session(
                model, params, prompt_len, mesh=mesh, devices=devices,
                tp=tp, rules=rules, **(session_kwargs or {}),
            )
        super().__init__(name, session, **replica_kwargs)
        self.mesh = getattr(session, "mesh", mesh)

    @property
    def mesh_devices(self) -> tuple:
        """The devices this replica's programs run on (flat)."""
        if self.mesh is None:
            return ()
        return tuple(self.mesh.devices.flat)
