"""tpudl.fleet: pod-real mesh replicas, migration transport, elastic
reshard-restore, and the chip mover (ISSUE 19).

Correctness bars, all on the fake 8-device CPU host
(``--xla_force_host_platform_device_count=8``, tests/conftest.py):

- a Router over TWO pjit-sharded ``MeshReplica``s (disjoint 4-device
  tensor-parallel meshes) is token-for-token ``generate()`` — the
  placement contract does not know the mesh exists;
- a checkpoint written on a 4-device fsdp mesh reshard-restores
  BITWISE (params AND optimizer state) onto an 8-device mesh and back,
  and an uncovered leaf raises instead of silently replicating;
- a mid-stream request migrates across a real process boundary
  (socket transport into a separately-compiled survivor) with ZERO
  prefill dispatches on the target and an exact continuation;
- a speculating engine's migration payload carries the draft-cache
  remainder, so draft/target lens-lockstep survives failover — pinned
  by exact sampled-stream parity through the transport layer (a
  corrupted draft would change which proposals are made and therefore
  which uniforms are consumed);
- the chip mover's hysteresis tick moves devices training -> serving
  -> training with sustain windows and cooldown honored (fake clock),
  and the same two moves end to end with a real trainer and router.
"""

import dataclasses
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl.fleet import (
    ChipMover,
    ChipMoverConfig,
    ElasticTrainer,
    FileChannel,
    MeshReplica,
    MigrationEndpoint,
    TransportError,
    build_mesh_session,
    deliver_to_session,
    migrate_request,
    recv_frame,
    reshard_restore,
    send_frame,
)
from tpudl.fleet.reshard import (
    ELASTIC_RESNET_RULES,
    cohort_mesh,
    elastic_shardings,
)
from tpudl.fleet.transport import FRAME_MAGIC, payload_request_id
from tpudl.ft.manager import AsyncCheckpointManager, state_payload
from tpudl.models.generate import generate, paged_decode_fn, prefill_fn
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.models.resnet import ResNetTiny
from tpudl.obs import registry
from tpudl.parallel.sharding import FSDP_RULES
from tpudl.runtime.mesh import MeshSpec
from tpudl.serve import (
    MigrationCompatError,
    Replica,
    Request,
    Router,
    ServeSession,
)
from tpudl.serve.cache import PagedKVCache
from tpudl.train import create_train_state, make_classification_train_step

CFG = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
PROMPT_LEN = 8
PAGE = 8


@pytest.fixture(scope="module")
def model_and_params():
    model = LlamaForCausalLM(CFG)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def programs(model_and_params):
    """One compiled prefill/decode pair shared by every plain paged
    session below (the test_serve_chaos idiom — per-test sessions,
    module-wide compiles)."""
    model, params = model_and_params
    pf = jax.jit(prefill_fn(model))
    dec = jax.jit(paged_decode_fn(model, PAGE, False))
    ids = jax.ShapeDtypeStruct((2, PROMPT_LEN), jnp.int32)
    _, template = jax.eval_shape(prefill_fn(model), params, ids, ids)
    return {
        "model": model, "params": params, "prefill": pf,
        "decode": dec, "template": template,
    }


def _psession(programs, **kw):
    cache = PagedKVCache(programs["template"], page_size=PAGE)
    return ServeSession(
        programs["prefill"], programs["decode"], programs["params"],
        cache, PROMPT_LEN, **kw,
    )


def _want(model, params, req):
    return np.asarray(
        generate(
            model, params, jnp.asarray(req.input_ids, jnp.int32)[None, :],
            max_new_tokens=req.max_new_tokens,
        )
    )[0]


def _greedy_requests(n, seed=0, max_new=10, tag="r"):
    rng = np.random.default_rng(seed)
    return [
        Request(
            f"{tag}{i}",
            rng.integers(
                1, CFG.vocab_size,
                size=int(rng.integers(2, PROMPT_LEN + 1)),
            ).tolist(),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


# ---------------------------------------------------------------------------
# transport framing + spool (no model, no mesh)
# ---------------------------------------------------------------------------


def test_frame_roundtrip_and_clean_eof():
    a, b = socket.socketpair()
    try:
        payloads = [b"x" * 3, b"", b"y" * 1000]
        for p in payloads:
            send_frame(a, p)
        a.close()
        got = []
        while True:
            p = recv_frame(b)
            if p is None:
                break
            got.append(p)
        assert got == payloads
    finally:
        b.close()


def test_frame_bad_magic_and_truncation():
    a, b = socket.socketpair()
    try:
        a.sendall(b"NOTFRAME" + b"\x00" * 8)
        with pytest.raises(TransportError, match="magic"):
            recv_frame(b)
    finally:
        a.close()
        b.close()
    a, b = socket.socketpair()
    try:
        # A frame that promises more bytes than the stream delivers.
        import struct

        a.sendall(FRAME_MAGIC + struct.pack("<Q", 100) + b"short")
        a.close()
        with pytest.raises(TransportError, match="truncated"):
            recv_frame(b)
    finally:
        b.close()


def test_frame_oversize_refused_before_allocation():
    import struct

    a, b = socket.socketpair()
    try:
        a.sendall(FRAME_MAGIC + struct.pack("<Q", 1 << 40))
        with pytest.raises(TransportError, match="exceeds"):
            recv_frame(b)
    finally:
        a.close()
        b.close()


def test_file_channel_spool_order_and_claims():
    with tempfile.TemporaryDirectory() as d:
        ch = FileChannel(d)
        names = [ch.put(p) for p in (b"first", b"second", b"third")]
        assert len(names) == len(set(names))
        # An uncommitted temp file must be invisible to take/drain.
        with open(os.path.join(d, "junk.tmp"), "wb") as f:
            f.write(b"garbage")
        assert len(ch) == 3
        assert ch.take() == b"first"
        assert ch.drain() == [b"second", b"third"]
        assert ch.take() is None
        assert len(ch) == 0


# ---------------------------------------------------------------------------
# chip mover hysteresis (fake trainer/router/clock — policy only)
# ---------------------------------------------------------------------------


class _FakeTrainer:
    def __init__(self, devices):
        self.devices = list(devices)
        self.grants = [list(devices)]
        self.restarts = 0
        self.preempts = 0

    def preempt(self, timeout_s=None):
        self.preempts += 1

    def restart(self, devices):
        self.devices = list(devices)
        self.grants.append(list(devices))
        self.restarts += 1
        return self


class _FakeRouter:
    def __init__(self):
        self.added = []
        self.removed = []

    def add_replica(self, replica):
        self.added.append(replica)

    def remove_replica(self, name, drain=False):
        self.removed.append((name, drain))


def test_chipmover_hysteresis_cooldown_and_split():
    devices = [f"d{i}" for i in range(8)]
    trainer = _FakeTrainer(devices)
    router = _FakeRouter()
    burn = {"on": False}
    now = {"t": 0.0}
    spawned = []

    def spawn(name, devs):
        spawned.append((name, list(devs)))
        return (name, tuple(devs))

    mover = ChipMover(
        router, trainer, spawn,
        ChipMoverConfig(burn_sustain_s=1.0, clear_sustain_s=2.0,
                        cooldown_s=5.0, serve_share=0.5),
        clock=lambda: now["t"], burn_fn=lambda: burn["on"],
    )
    assert mover.evaluate() is None  # idle, no burn
    burn["on"] = True
    assert mover.evaluate() is None  # burn starts the sustain window
    now["t"] = 0.5
    assert mover.evaluate() is None  # not sustained yet
    now["t"] = 1.0
    assert mover.evaluate() == "to_serving"
    assert mover.state == "borrowed"
    assert trainer.preempts == 1 and trainer.restarts == 1
    assert trainer.devices == devices[:4]  # training kept the head
    assert spawned == [("borrowed-1", devices[4:])]
    assert router.added == [("borrowed-1", tuple(devices[4:]))]
    # Burn clears, but the return waits for the clear sustain AND the
    # post-move cooldown.
    burn["on"] = False
    now["t"] = 1.1
    assert mover.evaluate() is None  # clear window opens
    now["t"] = 3.2
    assert mover.evaluate() is None  # sustained clear, still cooling
    now["t"] = 6.5
    assert mover.evaluate() == "to_training"
    assert mover.state == "training_full"
    assert router.removed == [("borrowed-1", True)]  # drained, not killed
    assert trainer.devices == devices  # full grant back
    assert mover.last_burn_cleared_s == pytest.approx(6.5)
    assert mover.moves == 2
    # A burn flicker after the move must restart the sustain window,
    # and the second loan still honors the cooldown.
    burn["on"] = True
    now["t"] = 7.0
    mover.evaluate()
    burn["on"] = False
    now["t"] = 7.5
    mover.evaluate()
    burn["on"] = True
    now["t"] = 8.0
    mover.evaluate()
    now["t"] = 9.1  # sustained > 1s, but inside the post-move cooldown
    assert mover.evaluate() is None
    now["t"] = 11.6
    assert mover.evaluate() == "to_serving"
    assert mover.state == "borrowed"


def test_chipmover_config_rejects_full_loan():
    with pytest.raises(ValueError, match="serve_share"):
        ChipMoverConfig(burn_sustain_s=1, clear_sustain_s=1,
                        cooldown_s=0, serve_share=1.0)


def test_chipmover_end_to_end_real_trainer_and_router(
    model_and_params, monkeypatch
):
    """Both moves with nothing faked but the burn signal: sustained
    burn -> the training cohort is preempted (the SIGTERM protocol)
    and reshard-restored on fewer devices -> a borrowed MeshReplica
    serves on the freed ones -> burn clear -> the borrowed replica
    drains and training grows back. No request of any wave is
    dropped or failed, and the trainer went through both restarts."""
    from tpudl.data import synthetic_classification_batches

    # The grace window's watchdog is os._exit: on a loaded machine the
    # cohort may still be compiling its step when the signal lands, and
    # the default 15 s would take this pytest worker down with it.
    monkeypatch.setenv("TPUDL_FT_GRACE_S", "900")
    model, params = model_and_params

    def make_batches():
        return synthetic_classification_batches(
            8, image_shape=(16, 16, 3), num_classes=4,
            num_batches=2000, seed=7,
        )

    def spawn_replica(name, devices):
        return MeshReplica(
            name, model=model, params=params, prompt_len=PROMPT_LEN,
            devices=devices, session_kwargs={"num_slots": 2},
        )

    burn = {"on": False}
    results = {}
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = ElasticTrainer(
            _resnet_state, make_classification_train_step(), make_batches,
            AsyncCheckpointManager(ckpt_dir), jax.devices(),
            total_steps=100_000, checkpoint_every=25,
        )
        r0 = Replica("r0", ServeSession.from_model(
            model, params, PROMPT_LEN, num_slots=2
        ))
        try:
            with Router([r0]) as router:
                mover = ChipMover(
                    router, trainer.start(), spawn_replica,
                    ChipMoverConfig(
                        burn_sustain_s=0.1, clear_sustain_s=0.1,
                        cooldown_s=0.0,
                    ),
                    burn_fn=lambda: burn["on"],
                )

                def tick_until(state):
                    deadline = time.monotonic() + 600.0
                    while mover.state != state:
                        mover.evaluate()
                        assert time.monotonic() < deadline, (
                            f"chip mover never reached {state!r}"
                        )
                        time.sleep(0.02)

                def wave(tag):
                    results.update(router.serve(
                        _greedy_requests(2, seed=2, tag=tag),
                        timeout_s=600.0,
                    ))

                wave("full")
                burn["on"] = True
                tick_until("borrowed")
                assert len(router.replicas) == 2
                wave("lent")
                burn["on"] = False
                tick_until("training_full")
                assert len(router.replicas) == 1
                wave("back")
        finally:
            trainer.close()
    assert len(results) == 6
    assert all(r.ok for r in results.values()), {
        k: r.finish_reason for k, r in results.items()
    }
    assert mover.moves == 2
    assert trainer.restarts >= 2
    assert len(set(trainer.mesh_shapes)) == 2  # shrunk, then grown back


# ---------------------------------------------------------------------------
# elastic reshard-restore (the acceptance bar: 4 -> 8 -> 4 bitwise)
# ---------------------------------------------------------------------------


def _resnet_state(seed=0):
    model = ResNetTiny(num_classes=4)
    return create_train_state(
        jax.random.key(seed), model, jnp.zeros((1, 16, 16, 3)),
        optax.sgd(0.05, momentum=0.9),
    )


def _assert_payload_bitwise(got_state, want_payload):
    got = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x)),
        state_payload(got_state),
    )
    got_leaves, got_def = jax.tree.flatten(got)
    want_leaves, want_def = jax.tree.flatten(want_payload)
    assert got_def == want_def
    for g, w in zip(got_leaves, want_leaves):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_reshard_restore_4_to_8_to_4_bitwise():
    devs = jax.devices()
    assert len(devs) == 8, "conftest forces an 8-device CPU host"
    spec = MeshSpec(dp=1, fsdp=-1)  # fsdp=4 on 4 devices, 8 on 8
    mesh4 = cohort_mesh(devs[:4], spec)
    mesh8 = cohort_mesh(devs, spec)
    state = _resnet_state(0)
    want = jax.tree.map(np.asarray, state_payload(state))
    sh4 = elastic_shardings(mesh4, state, ELASTIC_RESNET_RULES)
    placed = jax.tree.map(
        lambda x, s: jax.device_put(x, s), state_payload(state), sh4,
        is_leaf=lambda x: not isinstance(x, dict),
    )
    state4 = state.replace(
        params=placed["params"], opt_state=placed["opt_state"],
        step=placed["step"],
    )
    with tempfile.TemporaryDirectory() as d:
        with AsyncCheckpointManager(os.path.join(d, "a")) as mgr:
            assert mgr.save(1, state4, block=True)
            mgr.wait_until_finished()
            restored8, _, _ = reshard_restore(
                mgr, _resnet_state(1), mesh8, ELASTIC_RESNET_RULES
            )
        _assert_payload_bitwise(restored8, want)
        # The restore genuinely RESHARDED: at least one leaf is split
        # across all 8 devices (not merely replicated wider).
        assert any(
            len(x.sharding.device_set) == 8
            and not x.sharding.is_fully_replicated
            for x in jax.tree.leaves(restored8.params)
            if hasattr(x, "sharding") and x.ndim > 0
        ), "no parameter was fsdp-split on the 8-device mesh"
        # And back down: 8 -> 4 restores the same bytes again.
        with AsyncCheckpointManager(os.path.join(d, "b")) as mgr2:
            assert mgr2.save(2, restored8, block=True)
            mgr2.wait_until_finished()
            restored4, _, _ = reshard_restore(
                mgr2, _resnet_state(2), mesh4, ELASTIC_RESNET_RULES
            )
        _assert_payload_bitwise(restored4, want)


def test_reshard_strict_coverage_raises_on_uncovered_leaf():
    devs = jax.devices()
    mesh = cohort_mesh(devs[:4], MeshSpec(dp=1, fsdp=-1))
    state = _resnet_state(0)
    # FSDP_RULES alone do not cover BatchNorm statistics: strict mode
    # must raise with the leaf's path named instead of silently
    # replicating it (which on a reshard would change placement).
    with pytest.raises(ValueError, match="batch_stats"):
        elastic_shardings(mesh, state, tuple(FSDP_RULES))


def test_elastic_trainer_resumes_across_mesh_shapes():
    """A cohort that checkpointed on 4 devices resumes on 8 (the
    restart path the chip mover drives), continuing toward
    total_steps with the grown mesh actually recorded."""
    devs = jax.devices()
    step_fn = make_classification_train_step()

    def make_batches():
        from tpudl.data import synthetic_classification_batches

        return synthetic_classification_batches(
            8, image_shape=(16, 16, 3), num_classes=4,
            num_batches=50, seed=7,
        )

    with tempfile.TemporaryDirectory() as d:
        mgr = AsyncCheckpointManager(d)
        t1 = ElasticTrainer(
            _resnet_state, step_fn, make_batches, mgr, devs[:4],
            total_steps=2, checkpoint_every=1,
            install_signal_handlers=False,
        )
        t1.start()
        t1.join(timeout_s=600)
        assert t1.error is None
        assert t1.finished and t1.steps_done == 2
        t2 = ElasticTrainer(
            _resnet_state, step_fn, make_batches, mgr, devs,
            total_steps=4, checkpoint_every=1,
            install_signal_handlers=False,
        )
        t2.start()
        t2.join(timeout_s=600)
        assert t2.error is None
        assert t2.finished and t2.steps_done == 4
        assert int(jax.device_get(t2.state.step)) == 4
        mgr.wait_until_finished()
        mgr.close()
    assert t1.mesh_shapes != t2.mesh_shapes, (
        "the resume must have compiled for the grown mesh"
    )


# ---------------------------------------------------------------------------
# mesh replicas behind the router (the acceptance bar: exact parity)
# ---------------------------------------------------------------------------


def test_router_parity_over_two_mesh_replicas(model_and_params):
    model, params = model_and_params
    devs = jax.devices()
    replicas = [
        MeshReplica(
            f"m{i}", model=model, params=params, prompt_len=PROMPT_LEN,
            devices=devs[4 * i:4 * i + 4],
            session_kwargs={"num_slots": 2},
        )
        for i in range(2)
    ]
    assert set(replicas[0].mesh_devices).isdisjoint(
        replicas[1].mesh_devices
    )
    assert all(len(r.mesh_devices) == 4 for r in replicas)
    requests = _greedy_requests(4, seed=3)
    with Router(replicas) as router:
        results = router.serve(list(requests), timeout_s=600.0)
    for req in requests:
        res = results[req.request_id]
        assert res.ok, (req.request_id, res.finish_reason)
        got = np.asarray(res.tokens)
        np.testing.assert_array_equal(
            got, _want(model, params, req)[: got.shape[0]],
            err_msg=f"{req.request_id} diverged on a mesh replica",
        )
    # Least-loaded placement spread the work: both meshes prefilled.
    assert all(r.session.engine.num_prefills > 0 for r in replicas)


@pytest.mark.needs_multiprocess
def test_pod_mesh_replica_multiprocess(model_and_params):
    """The pod-real tier: after ``jax.distributed.initialize`` (one
    process per host), the SAME session builder lays the tp axis over
    the global device list. Auto-skipped off-TPU — the CPU jaxlib
    cannot compile cross-process computations."""
    model, params = model_and_params
    session = build_mesh_session(
        model, params, PROMPT_LEN, devices=jax.devices(), num_slots=2
    )
    res = session.serve(
        [Request("pod0", [3, 1, 4, 1], max_new_tokens=4)]
    )["pod0"]
    assert res.ok


# ---------------------------------------------------------------------------
# migration over the transport layer
# ---------------------------------------------------------------------------


def test_migration_over_socket_endpoint_zero_reprefill(programs):
    """Source exports mid-stream, payload travels through a real TCP
    frame into the survivor's inbox, continuation is exact with zero
    prefill dispatches — all in one process (the cross-process variant
    below pays the second compile)."""
    src = _psession(programs)
    dst = _psession(programs)
    req = Request("sock0", [3, 5, 7, 11, 2], max_new_tokens=16)
    src.submit(req)
    for _ in range(4):
        src.engine.step()
    with MigrationEndpoint(
        lambda p: deliver_to_session(dst, p)
    ) as endpoint:
        sent = migrate_request(src, "sock0", address=endpoint.address)
        assert sent is not None and sent > 0
        deadline = time.monotonic() + 60.0
        while not dst.engine.migrate_inbox and endpoint.received == 0:
            assert time.monotonic() < deadline, "payload never arrived"
            time.sleep(0.005)
        while "sock0" not in dst.engine.results:
            if not dst.engine.step():
                time.sleep(0.005)
            assert time.monotonic() < deadline
    res = dst.engine.results["sock0"]
    assert res.finish_reason == "length"
    np.testing.assert_array_equal(
        np.asarray(res.tokens),
        _want(programs["model"], programs["params"], req),
    )
    assert dst.engine.num_prefills == 0
    assert endpoint.received == 1 and endpoint.errors == 0


def test_migration_cross_process_zero_reprefill(programs):
    """THE process-boundary acceptance: the survivor is a separately
    compiled python process; the payload crosses a socket; the child
    resumes byte-exact with zero prefill dispatches."""
    req = Request("xp0", [2, 9, 4, 7], max_new_tokens=12)
    src = _psession(programs)
    src.submit(req)
    for _ in range(3):
        src.engine.step()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-m", "tests.fleet_helpers", "xp0"],
        cwd=repo, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
    )
    try:
        hello = json.loads(proc.stdout.readline())
        port = int(hello["port"])
        sent = migrate_request(src, "xp0", address=("127.0.0.1", port))
        assert sent is not None and sent > 0
        out = json.loads(proc.stdout.readline())
        rc = proc.wait(timeout=600)
    finally:
        proc.kill()
    assert rc == 0, proc.stderr.read()
    assert "error" not in out, out
    assert out["finish_reason"] == "length"
    assert out["prefills"] == 0, (
        "the child engine re-paid prefill for a migrated request"
    )
    np.testing.assert_array_equal(
        np.asarray(out["tokens"], np.int64),
        _want(programs["model"], programs["params"], req),
        err_msg="continuation diverged across the process boundary",
    )


def test_draft_cache_migrates_with_the_request(model_and_params, programs):
    """The speculative failover contract, end to end through the spool
    transport: a speculating engine's payload carries the draft-cache
    remainder; the survivor resumes in lens-lockstep. Greedy parity
    alone cannot pin this (greedy correction repairs any draft), so
    the sharp check is a SAMPLED stream — its tokens depend on the
    draft's proposal distribution, which depends on the draft KV."""
    model, params = model_and_params

    def spec_session():
        return ServeSession.from_model(
            model, params, PROMPT_LEN, num_slots=2,
            page_size=PAGE, spec_k=3,
        )

    greedy = Request("fg0", [3, 1, 4, 1, 5], max_new_tokens=12)
    sampled = Request("fs0", [5, 6, 7, 8], max_new_tokens=12,
                      temperature=0.8, seed=42)
    dst = spec_session()
    # The uninterrupted comparator runs on the DESTINATION session
    # (same compiled programs that will resume the migrated copies).
    want = dst.serve(
        [dataclasses.replace(greedy, request_id="wg0"),
         dataclasses.replace(sampled, request_id="ws0")]
    )
    src = spec_session()
    src.submit(dataclasses.replace(greedy))
    src.submit(dataclasses.replace(sampled))
    for _ in range(2):
        src.engine.step()
    for rid in ("fg0", "fs0"):
        assert rid not in src.engine.results, "migrate mid-stream"
    with tempfile.TemporaryDirectory() as d:
        channel = FileChannel(d)
        for rid in ("fg0", "fs0"):
            assert migrate_request(src, rid, channel=channel) > 0
        payloads = channel.drain()
    assert len(payloads) == 2
    assert {payload_request_id(p) for p in payloads} == {"fg0", "fs0"}
    emitted0 = registry().counter("spec_emitted_tokens").value
    prefills0 = dst.engine.num_prefills
    for p in payloads:
        deliver_to_session(dst, p)
    while ("fg0" not in dst.engine.results
           or "fs0" not in dst.engine.results):
        dst.engine.step()
    assert dst.engine.num_prefills == prefills0, (
        "draft migration must not re-pay prefill on either cache"
    )
    assert registry().counter("spec_emitted_tokens").value > emitted0, (
        "the survivor stopped speculating after the install"
    )
    assert dst.engine.results["fg0"].tokens == want["wg0"].tokens
    assert dst.engine.results["fs0"].tokens == want["ws0"].tokens, (
        "sampled stream diverged: the draft KV did not survive the move"
    )


def test_draftless_payload_refused_by_speculating_engine(
    model_and_params, programs
):
    """A payload from a non-speculating engine lacks the draft
    remainder: a speculating survivor must refuse it loudly (resuming
    with an empty draft cache breaks lens-lockstep) — and the reverse
    direction is fine: a non-speculating survivor ignores the rider."""
    model, params = model_and_params
    plain_src = _psession(programs)
    req = Request("nd0", [4, 4, 2, 1], max_new_tokens=10)
    plain_src.submit(req)
    for _ in range(3):
        plain_src.engine.step()
    payload = plain_src.engine.export_request("nd0")
    spec_dst = ServeSession.from_model(
        model, params, PROMPT_LEN, num_slots=2,
        page_size=PAGE, spec_k=3,
    )
    with pytest.raises(MigrationCompatError, match="draft"):
        spec_dst.engine.install_migrated(payload)
    # Reverse: a speculating source's payload (with the draft rider)
    # installs cleanly into a plain engine — the rider is inert.
    spec_req = Request("sd0", [9, 8, 7, 6], max_new_tokens=10)
    spec_dst.submit(spec_req)
    spec_dst.engine.step()
    assert "sd0" not in spec_dst.engine.results
    spec_payload = spec_dst.engine.export_request("sd0")
    plain_dst = _psession(programs)
    assert plain_dst.engine.install_migrated(spec_payload) == "sd0"
    while plain_dst.engine.step():
        pass
    got = np.asarray(plain_dst.engine.results["sd0"].tokens)
    np.testing.assert_array_equal(
        got, _want(model, params, spec_req)[: got.shape[0]],
        err_msg="rider leaf corrupted a plain-engine install",
    )
    assert plain_dst.engine.num_prefills == 0
