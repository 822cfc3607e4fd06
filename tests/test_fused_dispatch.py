"""Fused multi-step dispatch (compile_step(steps_per_dispatch=K) +
fit + window-mode prefetch), async metric drain, overlap bucketing,
donation audit, and the persistent compile cache.

Parity contract: fit(steps_per_dispatch=K) is BIT-FOR-BIT identical to
K single dispatches — same final params, opt state, rng key, and
per-step losses — asserted exactly on a matmul (no-dropout) model.
XLA schedules the fused-scan and straight-line programs independently,
so conv/dropout models may show float-reassociation-level divergence
(the same caveat class test_accumulation documents); the contract suite
pins the exact case.
"""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl.data.prefetch import prefetch_to_device
from tpudl.obs import counters as obs_counters
from tpudl.obs import spans as obs_spans
from tpudl.runtime.mesh import MeshSpec, make_mesh
from tpudl.train import loop as loop_mod
from tpudl.train.loop import (
    compile_step,
    create_train_state,
    fit,
    make_classification_eval_step,
    make_classification_train_step,
)
from tpudl.train.metrics import MetricFetcher


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    from tpudl.obs import spans as obs

    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    monkeypatch.delenv("TPUDL_OVERLAP_BUCKET_MB", raising=False)
    obs.disable()
    obs_counters.registry().reset()
    yield
    obs.disable()
    obs_counters.registry().reset()


def _bert_state(lr=1e-3, seed=0):
    from tpudl.models.bert import BertConfig, BertForSequenceClassification

    cfg = BertConfig(
        vocab_size=256, hidden_size=32, num_layers=2, num_heads=2,
        intermediate_size=64, hidden_dropout=0.0, attention_dropout=0.0,
        dtype=jnp.float32,
    )
    model = BertForSequenceClassification(cfg)
    return create_train_state(
        jax.random.key(seed), model, jnp.zeros((1, 16), jnp.int32),
        optax.adamw(lr),
    )


def _token_batches(n, batch=16, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "input_ids": rng.integers(0, 256, (batch, seq)).astype(np.int32),
            "attention_mask": np.ones((batch, seq), np.int32),
            "label": rng.integers(0, 2, (batch,)).astype(np.int32),
        }
        for _ in range(n)
    ]


def _train_step():
    return make_classification_train_step(
        input_keys=("input_ids", "attention_mask"), label_key="label"
    )


def _tree_equal(a, b):
    return all(
        jax.tree.leaves(
            jax.tree.map(
                lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()),
                a, b,
            )
        )
    )


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------


def test_fused_dispatch_bitwise_parity():
    """fit(steps_per_dispatch=4) over 8 batches == steps_per_dispatch=1
    bit-for-bit: final params, opt state, rng key, per-step losses."""
    mesh = make_mesh(MeshSpec(dp=-1))
    batches = _token_batches(8)
    rng = jax.random.key(1)

    results = {}
    for k in (1, 4):
        state = _bert_state()
        step = compile_step(
            _train_step(), mesh, state, None, donate_state=False,
            steps_per_dispatch=k,
        )
        losses = []
        state, metrics, info = fit(
            step, state, list(batches), rng, log_every=1,
            logger=lambda i, m, ls=losses: ls.append(m["loss"]),
        )
        results[k] = (state, metrics, losses, info)

    s1, m1, l1, i1 = results[1]
    s4, m4, l4, i4 = results[4]
    assert l1 == l4  # exact float equality, all 8 steps
    assert m1 == m4
    assert _tree_equal(s1.params, s4.params)
    assert _tree_equal(s1.opt_state, s4.opt_state)
    assert int(s1.step) == int(s4.step) == 8
    # the rng key is never consumed destructively by either path
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(rng)),
        np.asarray(jax.random.key_data(jax.random.key(1))),
    )
    assert i1["dispatches"] == 8 and i4["dispatches"] == 2
    assert i4["steps"] == 8 and i4["steps_per_dispatch"] == 4


def test_fused_dispatch_ragged_tail_falls_back_to_single():
    """10 batches at K=4: 2 fused windows + 2 single-step dispatches,
    result identical to 10 single dispatches."""
    mesh = make_mesh(MeshSpec(dp=-1))
    batches = _token_batches(10)
    rng = jax.random.key(1)

    state_ref = _bert_state()
    step_ref = compile_step(
        _train_step(), mesh, state_ref, None, donate_state=False
    )
    state_ref, _, _ = fit(step_ref, state_ref, list(batches), rng)

    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    state, _, info = fit(step, state, list(batches), rng)
    assert info["steps"] == 10
    assert info["dispatches"] == 4  # 2 windows + 2 tail singles
    assert _tree_equal(state_ref.params, state.params)


def test_fused_dispatch_respects_num_steps():
    """num_steps not divisible by K: windows run while K steps remain,
    the remainder runs single-step, and exactly num_steps execute."""
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    state, _, info = fit(
        step, state, _token_batches(12), jax.random.key(1), num_steps=6
    )
    assert info["steps"] == 6
    assert info["dispatches"] == 3  # 1 window + 2 singles
    assert int(state.step) == 6


def test_fit_rejects_mismatched_steps_per_dispatch():
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(_train_step(), mesh, state, None, donate_state=False)
    with pytest.raises(ValueError, match="steps_per_dispatch"):
        fit(step, state, _token_batches(4), jax.random.key(1),
            steps_per_dispatch=4)


def test_compile_step_rejects_fused_eval():
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    with pytest.raises(ValueError, match="has_rng"):
        compile_step(
            make_classification_eval_step(), mesh, state, None,
            has_rng=False, steps_per_dispatch=4,
        )


# ---------------------------------------------------------------------------
# window-mode prefetcher
# ---------------------------------------------------------------------------


def test_prefetcher_window_mode_feeds_fused_fit():
    """prefetch_to_device(window=K) assembles [K, B, ...] windows
    host-side; fit consumes them via pull_window and the result matches
    the single-dispatch reference exactly (including the ragged tail)."""
    mesh = make_mesh(MeshSpec(dp=-1))
    batches = _token_batches(10)
    rng = jax.random.key(1)

    state_ref = _bert_state()
    step_ref = compile_step(
        _train_step(), mesh, state_ref, None, donate_state=False
    )
    state_ref, _, _ = fit(step_ref, state_ref, list(batches), rng)

    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    with prefetch_to_device(iter(batches), mesh=mesh, window=4) as pf:
        assert pf.window == 4
        state, _, info = fit(step, state, pf, rng)
    assert info["steps"] == 10
    assert _tree_equal(state_ref.params, state.params)


def test_prefetcher_pull_window_protocol():
    """pull_window returns stacked windows in source order, then None
    once only the ragged tail remains; iteration drains the tail."""
    batches = [{"x": np.full((4, 2), i, np.float32)} for i in range(7)]
    with prefetch_to_device(iter(batches), window=3) as pf:
        w1 = pf.pull_window()
        np.testing.assert_array_equal(
            np.asarray(w1["x"])[:, 0, 0], [0, 1, 2]
        )
        assert np.asarray(w1["x"]).shape == (3, 4, 2)
        w2 = pf.pull_window(3)
        np.testing.assert_array_equal(
            np.asarray(w2["x"])[:, 0, 0], [3, 4, 5]
        )
        assert pf.pull_window() is None  # tail single held back
        tail = list(pf)
        assert [int(np.asarray(b["x"])[0, 0]) for b in tail] == [6]
        with pytest.raises(ValueError, match="window"):
            pf.pull_window(2)


def test_prefetcher_window_plain_iteration_unstacks():
    """Iterating a window-mode prefetcher without pull_window still
    yields the exact single-batch sequence (lazy unstack fallback)."""
    batches = [{"x": np.full((2,), i, np.float32)} for i in range(5)]
    with prefetch_to_device(iter(batches), window=2) as pf:
        seen = [float(np.asarray(b["x"])[0]) for b in pf]
    assert seen == [0.0, 1.0, 2.0, 3.0, 4.0]


def test_prefetcher_window_shape_break_flushes_singles():
    """A smaller partial batch landing INSIDE a would-be window (not
    just at the stream end) must not crash the stack — the group
    flushes as singles and every batch still arrives, in order."""
    sizes = [4, 4, 4, 3, 4, 4]
    batches = [
        {"x": np.full((n, 2), i, np.float32)}
        for i, n in enumerate(sizes)
    ]
    with prefetch_to_device(iter(batches), window=2) as pf:
        w1 = pf.pull_window()
        np.testing.assert_array_equal(np.asarray(w1["x"])[:, 0, 0], [0, 1])
        # Batch 3 (size 3) breaks group [2]; from here the consumer is
        # in single-batch mode and drains everything in source order.
        assert pf.pull_window() is None
        rest = [
            (int(np.asarray(b["x"])[0, 0]), np.asarray(b["x"]).shape[0])
            for b in pf
        ]
    assert rest == [(2, 4), (3, 3), (4, 4), (5, 4)]


def test_fit_rejects_prefetcher_window_mismatch():
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    with prefetch_to_device(iter(_token_batches(8)), window=2) as pf:
        with pytest.raises(ValueError, match="window"):
            fit(step, state, pf, jax.random.key(1))


# ---------------------------------------------------------------------------
# async metric drain
# ---------------------------------------------------------------------------


def test_async_metrics_no_sync_fetch_per_logged_step(monkeypatch):
    """With the async drain on, fit() performs ZERO synchronous metric
    fetches per logged step in the steady state (the acceptance
    criterion): every host conversion happens on the fetcher thread,
    and every logger callback still fires, in order, before return."""
    calls = []
    real = loop_mod._to_host_metrics
    monkeypatch.setattr(
        loop_mod, "_to_host_metrics",
        lambda m: calls.append(1) or real(m),
    )
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    logged = []
    state, metrics, info = fit(
        step, state, _token_batches(8), jax.random.key(1),
        log_every=1, logger=lambda i, m: logged.append((i, m["loss"])),
    )
    assert calls == []  # no synchronous fetch, steady state or final
    assert [i for i, _ in logged] == list(range(1, 9))
    assert metrics is not None and metrics["loss"] == logged[-1][1]

    # Control: the sync path fetches once per logged step.
    state2 = _bert_state()
    step2 = compile_step(
        _train_step(), mesh, state2, None, donate_state=False
    )
    fit(step2, state2, _token_batches(4), jax.random.key(1),
        log_every=1, logger=lambda i, m: None, async_metrics=False)
    assert len(calls) >= 4


def test_async_metrics_on_single_step_path():
    """async_metrics=True works with steps_per_dispatch=1 too."""
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(_train_step(), mesh, state, None, donate_state=False)
    logged = []
    state, metrics, _ = fit(
        step, state, _token_batches(4), jax.random.key(1),
        log_every=2, logger=lambda i, m: logged.append(i),
        async_metrics=True,
    )
    assert logged == [2, 4]
    assert set(metrics) == {"loss", "accuracy"}


def test_metric_fetcher_roundtrip_and_order():
    with MetricFetcher(window=2) as f:
        f.submit(1, {"loss": np.float32(0.5)}, 1)
        f.submit(2, {"loss": np.arange(3, dtype=np.float32)}, 3)
        out = f.flush()
    assert [s for s, _ in out] == [1, 2, 3, 4]
    assert out[1][1]["loss"] == 0.0 and out[3][1]["loss"] == 2.0


def test_metric_fetcher_backpressure_and_errors():
    import threading
    import time as _time

    gate = threading.Event()

    class Slow:
        def __array__(self, dtype=None):
            gate.wait(5.0)
            return np.array(1.0)

    f = MetricFetcher(window=1)
    assert f.submit(1, {"loss": Slow()}, 1) == 0.0
    timer = threading.Timer(0.2, gate.set)
    timer.start()
    t0 = _time.perf_counter()
    waited = f.submit(2, {"loss": np.float32(2.0)}, 1)
    assert waited > 0.05  # blocked on the window until the gate opened
    assert _time.perf_counter() - t0 > 0.05
    out = f.flush()
    assert [s for s, _ in out] == [1, 2]
    f.close()

    class Boom:
        def __array__(self, dtype=None):
            raise RuntimeError("metric readback exploded")

    f2 = MetricFetcher(window=4)
    f2.submit(1, {"loss": Boom()}, 1)
    with pytest.raises(RuntimeError, match="exploded"):
        for _ in range(50):
            _time.sleep(0.01)
            f2.flush()
    # Sticky: the error keeps raising on every later call instead of
    # being consumed once (a cleared error let a later flush() wait
    # forever on work the dead worker would never finish).
    with pytest.raises(RuntimeError, match="exploded"):
        f2.flush()
    f2.close()

    # Deadlock regression: a worker error with MORE dispatches still
    # outstanding must abandon them — flush() raises promptly instead
    # of hanging on pending work no thread will ever convert. The gate
    # guarantees dispatches 2 and 3 are queued behind the failing one.
    gate2 = threading.Event()

    class GatedBoom:
        def __array__(self, dtype=None):
            gate2.wait(5.0)
            raise RuntimeError("exploded late")

    f3 = MetricFetcher(window=8)
    f3.submit(1, {"loss": GatedBoom()}, 1)
    f3.submit(2, {"loss": np.float32(1.0)}, 1)
    f3.submit(3, {"loss": np.float32(2.0)}, 1)
    gate2.set()
    t0 = _time.perf_counter()
    with pytest.raises(RuntimeError, match="exploded late"):
        f3.flush()
    assert _time.perf_counter() - t0 < 3.0
    f3.close()


def test_fused_fit_records_dispatch_and_metric_spans(tmp_path):
    """The obs stream of a fused run carries dispatch_window spans whose
    window attr makes goodput count K steps each, and the end-of-fit
    flush records metric_wait separately from data_wait."""
    from tpudl.obs import goodput as obs_goodput

    rec = obs_spans.enable(str(tmp_path / "obs"))
    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    fit(step, state, _token_batches(8), jax.random.key(1), log_every=1,
        logger=lambda i, m: None)
    records = rec.records
    obs_spans.disable()
    windows = [
        r for r in records
        if r.get("kind") == "span" and r.get("name") == "dispatch_window"
    ]
    assert len(windows) == 1  # first window classifies as compile
    assert windows[0]["window"] == 4
    cls = obs_goodput.classify(records)
    assert cls["steps"] == 4  # 1 span, window-weighted
    assert "metric_wait_s" in cls
    compile_spans = [
        r for r in records
        if r.get("kind") == "span" and r.get("cat") == "compile"
        and not r["name"].startswith("program.")  # JAX's own stages
    ]
    assert compile_spans and compile_spans[0].get("window") == 4


# ---------------------------------------------------------------------------
# ft interaction: checkpoint / preemption at window granularity
# ---------------------------------------------------------------------------


def test_fused_checkpoint_window_granularity_and_resume(tmp_path):
    """Checkpoints commit at dispatch-window ends keyed by the true step
    counter, and a fused resume is schedule-identical to the
    uninterrupted fused run (losses bit-equal across the boundary)."""
    from tpudl.ft.data import ResumableIterator
    from tpudl.ft.manager import AsyncCheckpointManager
    from tpudl.ft.supervisor import resume_run

    mesh = make_mesh(MeshSpec(dp=-1))
    rng = jax.random.key(42)
    total = 8
    batches = _token_batches(total)

    def build_step(state, k):
        return compile_step(
            _train_step(), mesh, state, None, donate_state=False,
            steps_per_dispatch=k,
        )

    # Uninterrupted fused control.
    state = _bert_state()
    control = []
    fit(build_step(state, 4), state, ResumableIterator(iter(batches)),
        rng, num_steps=total, log_every=1,
        logger=lambda i, m: control.append(m["loss"]))

    # Interrupted: cadence 3 with K=4 -> saves land at window ends 4, 8
    # (crossed cadence steps commit at the window's final step).
    with AsyncCheckpointManager(str(tmp_path / "ck")) as mgr:
        state = _bert_state()
        head = []
        fit(build_step(state, 4), state,
            ResumableIterator(iter(batches)), rng, num_steps=4,
            log_every=1, logger=lambda i, m: head.append(m["loss"]),
            checkpoint_manager=mgr, checkpoint_every=3)
        assert mgr.latest_step() == 4  # window end, not cadence step 3

    with AsyncCheckpointManager(str(tmp_path / "ck")) as mgr2:
        template = _bert_state(seed=5)
        state, r_rng, rbatches, start = resume_run(
            mgr2, template, ResumableIterator(iter(batches))
        )
        assert start == 4
        tail = []
        fit(build_step(state, 4), state, rbatches, r_rng,
            num_steps=total - start, log_every=1,
            logger=lambda i, m: tail.append(m["loss"]))
    assert head == control[:4]
    assert tail == control[4:]


def test_fused_preemption_stops_at_window_boundary():
    """A preemption flag raised mid-window stops the loop at the NEXT
    window boundary: steps stay a multiple of K and the run reports
    preempted."""
    from tpudl.ft import preemption as ft_preemption

    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, donate_state=False,
        steps_per_dispatch=4,
    )
    batches = _token_batches(16)

    def feed():
        for j, b in enumerate(batches):
            if j == 5:  # mid window 2: delivered, then the flag is seen
                os.kill(os.getpid(), signal.SIGTERM)
            yield b

    with ft_preemption.PreemptionGuard(grace_s=60.0):
        state, _, info = fit(step, state, feed(), jax.random.key(1))
        assert ft_preemption.requested()
    assert info["preempted"] is True
    assert info["steps"] == 8  # window 2 completes; window 3 never starts


# ---------------------------------------------------------------------------
# donation audit
# ---------------------------------------------------------------------------


def test_donation_audit_single_and_fused():
    """Train-mode compile_step AND the fused K-step program donate the
    state buffers: every old state leaf is deleted after the call, and
    the output state reuses the donated buffers (pointer identity on
    CPU) rather than silently copying. Eval steps must NOT donate.

    Runs through the generalized tpudl.analysis.donation audit (this
    test's original inline check, promoted to a reusable helper)."""
    from tpudl.analysis.donation import audit_donation

    mesh = make_mesh(MeshSpec(dp=-1))
    state = _bert_state()
    step = compile_step(
        _train_step(), mesh, state, None, steps_per_dispatch=4
    )
    state = jax.device_put(state, step.state_shardings)
    batch = _token_batches(1)[0]
    rng = jax.random.key(1)

    # Most buffers must be reused in place, not copied: min_reuse=0.8
    # allows a few small leaves (step counter, scalars) elsewhere.
    (state2, _), report = audit_donation(
        step, (state, batch, rng), donate_argnums=(0,)
    )
    assert report.ok, report.describe()

    window = {k: np.stack([batch[k]] * 4) for k in batch}
    (state3, stacked), report2 = audit_donation(
        step.window_step, (state2, window, rng), donate_argnums=(0,)
    )
    assert report2.ok, (
        f"fused program: {report2.describe()} (donation lost across "
        f"the scan carry)"
    )
    assert np.asarray(stacked["loss"]).shape == (4,)

    # Eval never donates: the caller's state survives repeated use.
    eval_step = compile_step(
        make_classification_eval_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh, state3, None, has_rng=False,
    )
    eval_step(state3, batch)
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(state3))
    eval_step(state3, batch)


# ---------------------------------------------------------------------------
# overlap bucketing
# ---------------------------------------------------------------------------


def test_overlap_bucket_assignment():
    from tpudl.parallel import overlap

    leaves = [np.zeros((256,), np.float32) for _ in range(4)]  # 1 KiB each
    buckets = overlap.bucket_assignment(leaves, 2048)
    assert buckets == [[0, 1], [2, 3]]
    # An oversized leaf gets its own bucket, never split.
    leaves = [
        np.zeros((64,), np.float32),
        np.zeros((4096,), np.float32),
        np.zeros((64,), np.float32),
    ]
    buckets = overlap.bucket_assignment(leaves, 1024)
    assert buckets == [[0], [1], [2]]
    with pytest.raises(ValueError):
        overlap.bucket_assignment(leaves, 0)


def test_overlap_accumulate_is_identity_on_values():
    from tpudl.parallel import overlap

    rng = np.random.default_rng(0)
    acc = {"a": rng.normal(size=(128,)).astype(np.float32),
           "b": {"c": rng.normal(size=(64, 3)).astype(np.float32)}}
    new = jax.tree.map(lambda x: x * 0.5, acc)
    plain = jax.tree.map(np.add, acc, new)
    bucketed = jax.jit(
        lambda a, b: overlap.accumulate(a, b, bucket_bytes=256)
    )(acc, new)
    for p, q in zip(jax.tree.leaves(plain), jax.tree.leaves(bucketed)):
        np.testing.assert_array_equal(p, np.asarray(q))


def test_accum_step_with_overlap_buckets_matches_plain(mesh8, tmp_path):
    """accum_steps=2 with tiny buckets forced on == the plain
    accumulated step bit-for-bit (barriers are identity), and tracing
    the bucketed step sets the overlap_buckets gauge."""
    batch = _token_batches(1, batch=32)[0]
    rng = jax.random.key(1)

    def run(bucket_mb):
        state = _bert_state()
        step = compile_step(
            make_classification_train_step(
                input_keys=("input_ids", "attention_mask"),
                label_key="label", accum_steps=2,
                overlap_bucket_mb=bucket_mb,
            ),
            mesh8, state, None, donate_state=False,
        )
        new_state, metrics = step(state, batch, rng)
        return new_state, metrics

    rec = obs_spans.enable(str(tmp_path / "obs"))
    s_bucketed, m_bucketed = run(0.001)  # ~1 KiB buckets: many of them
    gauge = obs_counters.registry().gauge("overlap_buckets").value
    obs_spans.disable()
    assert gauge > 1, "bucketed trace must record the bucket count"
    s_plain, m_plain = run(None)  # auto default (4 MiB ~= one bucket here)
    assert float(m_plain["loss"]) == float(m_bucketed["loss"])
    assert _tree_equal(s_plain.params, s_bucketed.params)
    assert rec is not None


def test_overlap_env_knob(monkeypatch):
    from tpudl.parallel import overlap

    monkeypatch.setenv("TPUDL_OVERLAP_BUCKET_MB", "2")
    assert overlap.bucket_bytes_from_env() == 2 << 20
    monkeypatch.setenv("TPUDL_OVERLAP_BUCKET_MB", "0")
    assert overlap.bucket_bytes_from_env() is None
    # 0 disables even with an explicit request at the accumulate level.
    acc = {"a": np.ones((8,), np.float32)}
    out = overlap.accumulate(acc, acc)
    np.testing.assert_array_equal(np.asarray(out["a"]), 2.0)


# ---------------------------------------------------------------------------
# persistent compile cache
# ---------------------------------------------------------------------------


# ``compile_cache_config``: tests/conftest.py.


def test_compile_cache_second_compile_records_hit(
    tmp_path, monkeypatch, compile_cache_config
):
    """With JAX_COMPILATION_CACHE_DIR set, tpudl sets no other
    directory; a second compile_step of the same signature is served
    from the persistent cache there and the obs stream records the
    hit."""
    from tpudl.runtime import compile_cache

    cache_dir = str(tmp_path / "cache")
    # What JAX does with the variable at start-up (it was imported long
    # before this test could set it).
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    assert compile_cache.enable_compile_cache() == cache_dir
    try:
        rec = obs_spans.enable(str(tmp_path / "obs"))
        mesh = make_mesh(MeshSpec(dp=-1))
        batch = _token_batches(1)[0]
        rng = jax.random.key(1)

        def compile_and_step():
            state = _bert_state()
            step = compile_step(
                _train_step(), mesh, state, None, donate_state=False
            )
            step(state, batch, rng)

        reg = obs_counters.registry()
        compile_and_step()  # cold cache: this compile writes the entry
        hits_before = reg.counter("compile_cache_hits").value
        compile_and_step()  # same signature, fresh jit -> persistent hit
        assert reg.counter("compile_cache_hits").value > hits_before
        events = [
            r for r in rec.records
            if r.get("kind") == "event" and r["name"] == "compile_cache_hit"
        ]
        assert events, "cache hit must land in the span stream"
        assert os.listdir(cache_dir), "entries land where the variable says"
    finally:
        obs_spans.disable()


def test_compile_cache_default_is_one_fixed_directory(
    monkeypatch, compile_cache_config
):
    """Without the variable the cache is one fixed, git-ignored path
    inside the package — the same on every call, whatever the process
    or the time."""
    import pathlib

    import tpudl
    from tpudl.runtime import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    expected = str(
        pathlib.Path(tpudl.__file__).resolve().parent / ".compile_cache"
    )
    assert compile_cache.enable_compile_cache() == expected
    assert compile_cache.enable_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    ignored = (
        pathlib.Path(tpudl.__file__).resolve().parents[1] / ".gitignore"
    ).read_text().split()
    assert "tpudl/.compile_cache/" in ignored


def test_device_peak_flops_unknown_device_is_an_error():
    """A utilization over the wrong peak looks like a result: a
    device_kind the table does not know raises; the CPU is an explicit
    entry, not the default."""
    from tpudl.train.metrics import PEAK_FLOPS, device_peak_flops

    class _Device:
        def __init__(self, kind):
            self.device_kind = kind

    assert device_peak_flops(_Device("TPU v5 lite")) == 197e12
    assert device_peak_flops() == PEAK_FLOPS["cpu"]  # this run's backend
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        device_peak_flops(_Device("TPU v9 imaginary"))
