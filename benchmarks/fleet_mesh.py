"""Fleet tier benchmarks: reshard-restore, 2-mesh serving, chip mover.

Run AS A SUBPROCESS (``python -m benchmarks.fleet_mesh --json``):
the forced host-device count must be set before jax imports, so
bench.py shells out to this module instead of importing it.

Three numbers, one per tpudl.fleet claim:

- ``fleet_reshard_restore_s``: wall time for
  ``reshard_restore`` to place a 4-device-mesh checkpoint onto an
  8-device mesh (template validate -> coverage check -> per-leaf
  host_to_global_array). The payload is full host arrays, so the
  bytes model is ``payload_bytes / restore_s`` — reported as
  ``fleet_reshard_payload_mb`` for the ratio.
- ``serve_tokens_per_sec_2mesh``: routed throughput over TWO
  MeshReplicas on disjoint 4-device tensor-parallel meshes — the
  pod-shaped sibling of ``serve_tokens_per_sec_2rep`` (thread
  replicas, one device view). On the CPU tier the mesh collectives
  are emulated, so the number tracks dispatch/routing overhead, not
  ICI bandwidth; the TPU rounds give it teeth.
- ``chipmover_burn_cleared_s``: the full chip-mover scenario's
  burn-to-cleared wall time — sustained burn detected, training
  preempted (SIGTERM protocol) and reshard-restored smaller, a
  borrowed MeshReplica spawned on the freed devices (serving program
  compiles included: that IS the move's honest cost), burn cleared,
  the borrowed replica drained migration-first, training grown back.
  Zero dropped results is asserted inside the benchmark.
"""

from __future__ import annotations

import os

# CPU by design: the meshes here are forced host devices, whatever
# accelerator the machine has (set, not defaulted — a parent may hold
# the chip).
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )

import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def _requests(cfg, n, prompt_len, seed=0, max_new=10):
    from tpudl.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            request_id=f"b{seed}-{i}",
            input_ids=rng.integers(
                1, cfg.vocab_size,
                size=int(rng.integers(2, prompt_len + 1)),
            ).tolist(),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def measure_reshard(smoke: bool = False) -> dict:
    import optax

    from tpudl.ft.manager import AsyncCheckpointManager, state_payload
    from tpudl.fleet.reshard import (
        ELASTIC_RESNET_RULES, cohort_mesh, elastic_shardings,
        reshard_restore,
    )
    from tpudl.models.resnet import ResNetTiny
    from tpudl.runtime.mesh import MeshSpec
    from tpudl.train import create_train_state

    model = ResNetTiny(num_classes=4)

    def make_state(seed):
        return create_train_state(
            jax.random.key(seed), model, jnp.zeros((1, 16, 16, 3)),
            optax.sgd(0.05, momentum=0.9),
        )

    devs = jax.devices()
    mesh4 = cohort_mesh(devs[:4], MeshSpec(dp=1, fsdp=-1))
    mesh8 = cohort_mesh(devs, MeshSpec(dp=1, fsdp=-1))
    state = make_state(0)
    payload = state_payload(state)
    payload_bytes = sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(payload)
    )
    sh4 = elastic_shardings(mesh4, state, ELASTIC_RESNET_RULES)
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, s), payload, sh4,
        is_leaf=lambda x: not isinstance(x, dict),
    )
    state4 = state.replace(
        params=placed["params"], opt_state=placed["opt_state"],
        step=placed["step"],
    )
    reps = 1 if smoke else 3
    times = []
    with tempfile.TemporaryDirectory() as d:
        with AsyncCheckpointManager(d) as mgr:
            mgr.save(1, state4, block=True)
            mgr.wait_until_finished()
            for rep in range(reps):
                tmpl = make_state(rep + 1)
                t0 = time.perf_counter()
                restored, _, _ = reshard_restore(
                    mgr, tmpl, mesh8, ELASTIC_RESNET_RULES
                )
                jax.block_until_ready(restored.params)
                times.append(time.perf_counter() - t0)
    return {
        "fleet_reshard_restore_s": round(min(times), 4),
        "fleet_reshard_payload_mb": round(payload_bytes / 2**20, 3),
    }


def measure_serve_2mesh(smoke: bool = False) -> dict:
    from tpudl.fleet import MeshReplica
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
    from tpudl.serve import Router

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
    prompt_len = 8
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, prompt_len), jnp.int32)
    )["params"]
    devs = jax.devices()
    replicas = [
        MeshReplica(
            f"m{i}", model=model, params=params, prompt_len=prompt_len,
            devices=devs[4 * i:4 * i + 4],
            session_kwargs={"num_slots": 2},
        )
        for i in range(2)
    ]
    warm = _requests(cfg, 2, prompt_len, seed=9, max_new=4)
    n = 4 if smoke else 8
    timed = _requests(cfg, n, prompt_len, seed=1, max_new=10)
    with Router(replicas) as router:
        router.serve(warm, timeout_s=600.0)  # compile warm-up
        t0 = time.perf_counter()
        results = router.serve(timed, timeout_s=600.0)
        elapsed = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in results.values())
    assert len(results) == len(timed), "2-mesh bench dropped requests"
    return {
        "serve_tokens_per_sec_2mesh": round(tokens / elapsed, 2),
    }


def measure_chipmover(smoke: bool = False) -> dict:
    import optax

    from tpudl.data import synthetic_classification_batches
    from tpudl.ft.manager import AsyncCheckpointManager
    from tpudl.fleet import ChipMover, ChipMoverConfig, ElasticTrainer
    from tpudl.fleet.meshrep import MeshReplica
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
    from tpudl.models.resnet import ResNetTiny
    from tpudl.serve import Replica, Router, ServeSession
    from tpudl.train import create_train_state, make_classification_train_step

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
    prompt_len = 8
    serve_model = LlamaForCausalLM(cfg)
    serve_params = serve_model.init(
        jax.random.key(0), jnp.zeros((1, prompt_len), jnp.int32)
    )["params"]
    train_model = ResNetTiny(num_classes=4)

    def make_state():
        return create_train_state(
            jax.random.key(0), train_model, jnp.zeros((1, 16, 16, 3)),
            optax.sgd(0.05, momentum=0.9),
        )

    def make_batches():
        return synthetic_classification_batches(
            8, image_shape=(16, 16, 3), num_classes=4,
            num_batches=2000, seed=7,
        )

    def spawn_replica(name, devices):
        return MeshReplica(
            name, model=serve_model, params=serve_params,
            prompt_len=prompt_len, devices=devices,
            session_kwargs={"num_slots": 2},
        )

    burn = {"on": False}
    results = {}
    n_wave = 2 if smoke else 4
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = ElasticTrainer(
            make_state,
            make_classification_train_step(),
            make_batches,
            AsyncCheckpointManager(ckpt_dir),
            jax.devices(),
            total_steps=100_000,
            checkpoint_every=25,
        )
        r0 = Replica(
            "r0",
            ServeSession.from_model(
                serve_model, serve_params, prompt_len, num_slots=2
            ),
        )
        mover = None
        with Router([r0]) as router:
            mover = ChipMover(
                router, trainer.start(), spawn_replica,
                ChipMoverConfig(
                    burn_sustain_s=0.1, clear_sustain_s=0.1,
                    cooldown_s=0.0,
                ),
                burn_fn=lambda: burn["on"],
            )
            results.update(router.serve(
                _requests(cfg, n_wave, prompt_len, seed=2),
                timeout_s=600.0,
            ))
            burn["on"] = True
            deadline = time.monotonic() + 600.0
            while mover.state != "borrowed":
                mover.evaluate()
                if time.monotonic() > deadline:
                    raise TimeoutError("chip mover never lent devices")
                time.sleep(0.02)
            results.update(router.serve(
                _requests(cfg, n_wave, prompt_len, seed=3),
                timeout_s=600.0,
            ))
            burn["on"] = False
            while mover.state != "training_full":
                mover.evaluate()
                if time.monotonic() > deadline:
                    raise TimeoutError("chip mover never returned devices")
                time.sleep(0.02)
            results.update(router.serve(
                _requests(cfg, n_wave, prompt_len, seed=4),
                timeout_s=600.0,
            ))
        trainer.close()
    assert len(results) == 3 * n_wave, "chip-mover scenario dropped results"
    assert all(
        not r.finish_reason.startswith("failed") for r in results.values()
    ), "chip-mover scenario failed a request"
    assert trainer.restarts >= 2, "trainer never cycled through both moves"
    return {
        "chipmover_burn_cleared_s": round(mover.last_burn_cleared_s, 3),
        "chipmover_moves": mover.moves,
    }


def measure_fleet_mesh(smoke: bool = False) -> dict:
    out = {
        "platform": f"{jax.default_backend()} ({jax.device_count()} "
        f"forced host devices, by design)",
    }
    out.update(measure_reshard(smoke))
    out.update(measure_serve_2mesh(smoke))
    out.update(measure_chipmover(smoke))
    return out


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="minimal request/step counts (CI plumbing check)")
    ap.add_argument("--json", action="store_true",
                    help="print the metrics dict as one JSON line")
    args = ap.parse_args(argv)
    result = measure_fleet_mesh(smoke=args.smoke)
    if args.json:
        print(json.dumps(result))
    else:
        for key, value in result.items():
            print(f"{key}: {value}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
