"""Mixed-precision training tier (tpudl.train.precision +
tpudl.ops.fp8_dot) — ISSUE 15 / ROADMAP item 6's training half.

Five contracts: (1) IDENTITY — the f32 policy is bitwise the legacy
no-policy step, and policy=None stays untouched; (2) PARITY — bf16 and
fp8 fixed-seed runs hold their documented loss bands against the f32
control while master weights stay f32; (3) DYNAMICS — dynamic loss
scaling grows/backs off exactly, a nonfinite gradient SKIPS the step
(params/opt/step/rings bitwise untouched) inside the SAME compiled
program, fp8 amax rings advance with observed forward/gradient amaxes,
saturation clips instead of NaNing, and moving scales never recompile
(RecompileWatcher audit); (4) RESUME — both checkpoint managers
round-trip the whole precision state (loss-scale schedule + amax
windows) and a mid-run restore replays the uninterrupted run bitwise;
(5) SEAMS — rule-selected moment dtypes are bitwise optax's mu_dtype,
and every invalid policy/state/config combination raises by name.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpudl.models.bert import BertConfig, BertForSequenceClassification
# tpudl.ops re-exports the fp8_dot FUNCTION, shadowing the submodule
# name in the package namespace (the flash_attention precedent) —
# resolve the MODULE explicitly.
import importlib

fp8_mod = importlib.import_module("tpudl.ops.fp8_dot")
from tpudl.runtime import MeshSpec, make_mesh
from tpudl.train import (
    compile_step,
    create_train_state,
    make_classification_eval_step,
    make_classification_train_step,
)
from tpudl.train import precision as precision_mod
from tpudl.train.precision import LossScaleConfig

SEQ = 8
BATCH = 8  # divisible by the CPU host's 8 virtual devices (dp=-1)
STEPS = 6

_CFG = dict(
    vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
    intermediate_size=64, max_position_embeddings=16, num_labels=2,
    dtype=jnp.float32, hidden_dropout=0.0, attention_dropout=0.0,
)

#: Loss-parity bands against the f32 run (bf16 / fp8 train steps).
BF16_BAND = 0.03
FP8_BAND = 0.08


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(dp=-1))


@pytest.fixture(scope="module")
def batches():
    rng = np.random.default_rng(7)
    return [
        {
            "input_ids": jnp.asarray(
                rng.integers(1, 64, (BATCH, SEQ)), jnp.int32
            ),
            "attention_mask": jnp.ones((BATCH, SEQ), jnp.int32),
            "label": jnp.asarray(rng.integers(0, 2, (BATCH,)), jnp.int32),
        }
        for _ in range(STEPS)
    ]


def _build(mesh, precision, fp8_train=False):
    cfg = BertConfig(**_CFG, fp8_train="force" if fp8_train else False)
    if precision is not None:
        # Compute dtype rides the model's dtype seam (configure_model)
        # — the bf16/fp8 cells really compute in bf16, which
        # test_bf16_matmuls_actually_run_bf16 pins via jaxpr.
        cfg = precision_mod.resolve_policy(precision).configure_model(cfg)
    model = BertForSequenceClassification(cfg)
    state = create_train_state(
        jax.random.key(0), model, jnp.zeros((1, SEQ), jnp.int32),
        optax.adamw(1e-3), precision=precision,
    )
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"),
            label_key="label", precision=precision,
        ),
        mesh, state, None, precision=precision,
    )
    return model, state, step


def _drive(step, state, batches, rng=None):
    rng = jax.random.key(1) if rng is None else rng
    losses, metrics = [], None
    for batch in batches:
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    return state, losses, metrics


def _fork(state):
    """Deep copy of a TrainState's buffers — the compiled train step
    DONATES its state argument, so anything a later test reads (or
    re-drives) must step a copy, never a shared fixture state."""
    return jax.tree.map(jnp.copy, state)


_RUNS = {}


@pytest.fixture(scope="module")
def runs(mesh, batches):
    """One fixed-seed run per cell, compiled once and shared by every
    test in the module (1-vCPU budget: compiles dominate). ``state0``
    is the pristine init (the drive consumed a fork of it)."""
    if not _RUNS:
        for name, (prec, fp8) in {
            "legacy": (None, False),
            "f32": ("f32", False),
            "bf16": ("bf16", False),
            "fp8": ("fp8", True),
        }.items():
            model, state0, step = _build(mesh, prec, fp8_train=fp8)
            state, losses, metrics = _drive(step, _fork(state0), batches)
            _RUNS[name] = {
                "model": model, "state0": state0, "step": step,
                "state": state, "losses": losses, "metrics": metrics,
            }
    return _RUNS


# ---------------------------------------------------------------------------
# 1. Identity + parity
# ---------------------------------------------------------------------------


def test_f32_policy_bitwise_identical_to_legacy(runs):
    """policy("f32") is the identity: same losses, same final params,
    bit for bit — the control arm costs nothing."""
    assert runs["legacy"]["losses"] == runs["f32"]["losses"]
    for a, b in zip(
        jax.tree.leaves(runs["legacy"]["state"].params),
        jax.tree.leaves(runs["f32"]["state"].params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_parity_band_and_f32_masters(runs):
    diff = abs(runs["bf16"]["losses"][-1] - runs["legacy"]["losses"][-1])
    assert diff <= BF16_BAND, diff
    # Master weights never leave f32 — the policy casts inside the
    # loss function only.
    assert all(
        leaf.dtype == jnp.float32
        for leaf in jax.tree.leaves(runs["bf16"]["state"].params)
    )
    # And the cast actually happened: bf16 arithmetic diverges from
    # the control at SOME step (fixed seed — divergence IS precision).
    assert any(
        a != b
        for a, b in zip(runs["bf16"]["losses"], runs["legacy"]["losses"])
    )


def test_fp8_parity_band_and_ring_advance(runs):
    diff = abs(runs["fp8"]["losses"][-1] - runs["legacy"]["losses"][-1])
    assert diff <= FP8_BAND, diff
    metrics = runs["fp8"]["metrics"]
    assert float(metrics["loss_scale"]) == 2.0**15
    assert float(metrics["grad_skipped"]) == 0.0
    prec = runs["fp8"]["state"].precision
    assert int(np.asarray(prec["loss_scale"]["skipped"])) == 0
    # Every site's rings advanced with real (positive) amaxes in all
    # three tensor classes.
    flat = {
        jax.tree_util.keystr(p): np.asarray(v)
        for p, v in jax.tree_util.tree_flatten_with_path(prec["fp8"])[0]
    }
    for kind in ("x_hist", "w_hist", "g_hist"):
        hists = [v for k, v in flat.items() if kind in k]
        assert hists
        assert all(h[: STEPS].min() > 0 for h in hists), kind


def _dot_operand_dtypes(closed_jaxpr):
    """Dtypes of every dot_general's operands, walking call/closed
    sub-jaxprs — the compute-precision ground truth."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                out.extend(v.aval.dtype for v in eqn.invars)
            for param in eqn.params.values():
                if hasattr(param, "jaxpr"):
                    walk(param.jaxpr)
                elif hasattr(param, "eqns"):
                    walk(param)

    walk(closed_jaxpr.jaxpr)
    return out


def test_bf16_matmuls_actually_run_bf16(runs, batches):
    """The compute dtype must LAND: a flax module re-promotes params
    to its own dtype, so only the configure_model seam moves the
    matmul precision — this pins the traced dot operands so a policy
    whose compute dtype silently stops taking effect (the rounded-f32
    failure mode) breaks loudly."""
    ids, mask = batches[0]["input_ids"], batches[0]["attention_mask"]

    def trace(run):
        model, params = run["model"], run["state0"].params
        return jax.make_jaxpr(
            lambda p: model.apply({"params": p}, ids, mask, train=False)
        )(params)

    bf16_dots = _dot_operand_dtypes(trace(runs["bf16"]))
    f32_dots = _dot_operand_dtypes(trace(runs["legacy"]))
    assert bf16_dots and f32_dots
    # Every encoder/pooler matmul runs bf16; the only f32 dot allowed
    # is the CLASSIFIER head (no dtype seam by design — the same
    # full-precision keep class the quantizer names).
    n_f32 = sum(1 for d in bf16_dots if d == jnp.float32)
    assert n_f32 <= 2, bf16_dots  # one head dot = two operands
    assert sum(1 for d in bf16_dots if d == jnp.bfloat16) >= 10
    assert all(d == jnp.float32 for d in f32_dots), set(f32_dots)


def test_cast_params_rule_classes(runs):
    """bf16 cast rules: kernels/embeddings go bf16, norm scales and
    biases stay f32 — the same keep classes as the quantizer."""
    pol = precision_mod.policy("bf16")
    casted = pol.cast_params(runs["legacy"]["state0"].params)
    flat = jax.tree_util.tree_flatten_with_path(casted)[0]
    n_bf16 = n_f32 = 0
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        if name.endswith("['kernel']") or name.endswith("['embedding']"):
            assert leaf.dtype == jnp.bfloat16, name
            n_bf16 += 1
        else:
            assert leaf.dtype == jnp.float32, name
            n_f32 += 1
    assert n_bf16 > 10 and n_f32 > 10


# ---------------------------------------------------------------------------
# 2. Loss-scale dynamics + skip semantics
# ---------------------------------------------------------------------------


def test_loss_scale_transitions_unit():
    cfg = LossScaleConfig(
        init=4.0, growth_factor=2.0, backoff_factor=0.5,
        growth_interval=3, max_scale=16.0, min_scale=1.0,
    )
    ls = {
        "scale": jnp.float32(4.0),
        "growth_count": jnp.int32(0),
        "skipped": jnp.int32(0),
    }
    ok = jnp.asarray(True)
    for expect_scale, expect_count in [(4, 1), (4, 2), (8, 0), (8, 1)]:
        ls = precision_mod.update_loss_scale(ls, cfg, ok)
        assert float(ls["scale"]) == expect_scale
        assert int(ls["growth_count"]) == expect_count
    # Backoff resets the streak and floors at min_scale.
    bad = jnp.asarray(False)
    for expect_scale in (4.0, 2.0, 1.0, 1.0):
        ls = precision_mod.update_loss_scale(ls, cfg, bad)
        assert float(ls["scale"]) == expect_scale
        assert int(ls["growth_count"]) == 0
    assert int(ls["skipped"]) == 4
    # Growth caps at max_scale.
    ls = {
        "scale": jnp.float32(16.0),
        "growth_count": jnp.int32(2),
        "skipped": jnp.int32(0),
    }
    ls = precision_mod.update_loss_scale(ls, cfg, ok)
    assert float(ls["scale"]) == 16.0


def test_nonfinite_grad_skips_step_in_same_program(runs, batches):
    """Poison one weight to inf so the backward goes nonfinite: the
    SAME compiled fp8 program must skip — params, opt state, step
    counter, and amax rings bitwise untouched; the loss scale backs
    off; the skipped counter advances. No recompile (values are data)."""
    from tpudl.analysis.dispatch import RecompileWatcher

    base = runs["fp8"]["state"]
    forked = _fork(base)
    marked = [False]

    def poison(leaf):
        if not marked[0] and jnp.ndim(leaf) == 2:
            marked[0] = True
            return leaf.at[0, 0].set(jnp.inf)
        return leaf

    poisoned = forked.replace(
        params=jax.tree.map(poison, forked.params)
    )
    assert marked[0]
    # Host snapshots BEFORE the step: donation deletes the inputs.
    params_before = jax.device_get(poisoned.params)
    rings_before = jax.device_get(poisoned.precision["fp8"])
    step_before = int(np.asarray(base.step))

    with RecompileWatcher() as watcher:
        new_state, metrics = runs["fp8"]["step"](
            poisoned, batches[0], jax.random.key(1)
        )
    assert watcher.count == 0
    assert float(metrics["grad_skipped"]) == 1.0
    assert int(np.asarray(new_state.step)) == step_before
    for a, b in zip(
        jax.tree.leaves(params_before),
        jax.tree.leaves(new_state.params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(
        jax.tree.leaves(rings_before),
        jax.tree.leaves(new_state.precision["fp8"]),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    ls = new_state.precision["loss_scale"]
    assert float(ls["scale"]) == 2.0**14  # backed off from 2^15
    assert int(ls["growth_count"]) == 0
    assert int(ls["skipped"]) == 1


def test_fp8_steady_state_never_recompiles(runs, batches):
    """Delayed scaling's whole point: amax windows and scales move as
    traced data, so steps after warmup compile NOTHING."""
    from tpudl.analysis.dispatch import assert_no_recompiles

    state = _fork(runs["fp8"]["state"])
    step = runs["fp8"]["step"]
    with assert_no_recompiles(label="fp8 train steady state"):
        for batch in batches[:3]:
            state, _ = step(state, batch, jax.random.key(1))


# ---------------------------------------------------------------------------
# 3. fp8 kernel units: saturation, ring hygiene
# ---------------------------------------------------------------------------


def test_fp8_saturation_clips_never_nans():
    """A step whose values outgrow the window's scale saturates (clip
    to the format max before the cast — a bare astype would NaN on
    e4m3) and reports the TRUE amax so the next scale covers it."""
    hist = fp8_mod.update_amax_history(
        fp8_mod.amax_history_init(4), jnp.float32(1.0)
    )  # window says amax 1.0 -> scale 1/448
    x = jnp.full((2, 4), 1000.0, jnp.float32)  # 448x past the window
    w = jnp.eye(4, dtype=jnp.float32)
    out, x_amax, _ = fp8_mod.fp8_dot(
        x, w, hist, hist, hist, jnp.zeros(()), impl="fused"
    )
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(x_amax) == 1000.0
    # Ring advance with the true amax widens next step's scale.
    new_hist = fp8_mod.update_amax_history(hist, x_amax)
    assert float(fp8_mod.history_scale(new_hist, fp8_mod.E4M3_MAX)) == (
        pytest.approx(1000.0 / 448.0)
    )


def test_amax_ring_rejects_nonfinite():
    hist = fp8_mod.update_amax_history(
        fp8_mod.amax_history_init(3), jnp.float32(5.0)
    )
    poisoned = fp8_mod.update_amax_history(hist, jnp.float32(np.inf))
    assert bool(jnp.all(jnp.isfinite(poisoned)))
    assert float(poisoned[0]) == 5.0  # window max, not the inf


def test_fp8_dot_grad_parity_and_probe():
    """Both impls agree with the f32 reference within the fp8 grid's
    tolerance, and the gradient amax rides out as g_probe's cotangent."""
    key = jax.random.key(3)
    x = jax.random.normal(key, (4, 8), jnp.float32) * 0.5
    w = jax.random.normal(jax.random.key(4), (8, 3), jnp.float32) * 0.1
    hist = fp8_mod.amax_history_init(4)

    gref = jax.grad(lambda x, w: jnp.sum((x @ w) ** 2), argnums=(0, 1))(
        x, w
    )
    for impl in ("fused", "reference"):

        def f(x, w, probe):
            out, _, _ = fp8_mod.fp8_dot(
                x, w, hist, hist, hist, probe, impl=impl
            )
            return jnp.sum(out**2)

        grads = jax.grad(f, argnums=(0, 1, 2))(x, w, jnp.zeros(()))
        np.testing.assert_allclose(grads[0], gref[0], atol=0.08)
        np.testing.assert_allclose(grads[1], gref[1], atol=0.08)
        assert float(grads[2]) > 0.0  # the amax ride-out


# ---------------------------------------------------------------------------
# 4. Checkpoint round-trip: schedule-identical resume (the PR-4 idiom)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("async_save", [False, True])
def test_precision_state_resumes_schedule_identical(
    runs, batches, tmp_path, async_save
):
    """Save mid-run, restore into a FRESH state, continue: the resumed
    trajectory is bitwise the uninterrupted one — which can only hold
    if the loss-scale schedule AND every amax window round-tripped."""
    from tpudl.checkpoint import CheckpointManager

    step = runs["fp8"]["step"]
    state0 = runs["fp8"]["state0"]
    rng = jax.random.key(1)

    # Uninterrupted control over the module's fixed batch stream.
    control_losses = runs["fp8"]["losses"]

    with CheckpointManager(
        str(tmp_path / f"ckpt_{async_save}"), async_save=async_save
    ) as mgr:
        state = _fork(state0)
        for batch in batches[:3]:
            state, _ = step(state, batch, rng)
        mgr.save(3, state)
        mgr.wait_until_finished()

        # Restore into a freshly-initialized state (different values,
        # same structure) — the resuming-program contract.
        _, fresh_state, _ = _build_cached_fresh(runs)
        restored = mgr.restore(fresh_state, 3)

    # The precision state round-tripped exactly.
    for a, b in zip(
        jax.tree.leaves(state.precision),
        jax.tree.leaves(restored.precision),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    resumed_losses = []
    for batch in batches[3:]:
        restored, metrics = step(restored, batch, rng)
        resumed_losses.append(float(metrics["loss"]))
    assert resumed_losses == control_losses[3:]


def _build_cached_fresh(runs):
    """A fresh fp8 TrainState (same structure as the module's run,
    different init values) without recompiling anything."""
    if "fresh" not in _RUNS:
        cfg = BertConfig(**_CFG, fp8_train="force")
        model = BertForSequenceClassification(cfg)
        state = create_train_state(
            jax.random.key(99), model, jnp.zeros((1, SEQ), jnp.int32),
            optax.adamw(1e-3), precision="fp8",
        )
        _RUNS["fresh"] = (model, state, None)
    model, state, _ = _RUNS["fresh"]
    return model, state, None


def test_state_payloads_carry_precision(runs):
    from tpudl.checkpoint import _state_payload
    from tpudl.ft.manager import state_payload

    state = runs["fp8"]["state"]
    for payload in (_state_payload(state), state_payload(state)):
        assert "precision" in payload
        assert "loss_scale" in payload["precision"]
        assert "fp8" in payload["precision"]
    # Legacy states serialize exactly as before — no new keys.
    legacy = runs["legacy"]["state"]
    for payload in (_state_payload(legacy), state_payload(legacy)):
        assert "precision" not in payload


# ---------------------------------------------------------------------------
# 5. Seams: moment rules, eval, validation errors
# ---------------------------------------------------------------------------


def test_moment_rules_bitwise_match_optax_mu_dtype():
    """apply_moment_rules is numerically optax's mu_dtype: same stored
    dtypes, same values, bit for bit."""
    params = {
        "a": {"kernel": jnp.ones((4, 3)) * 0.1, "bias": jnp.zeros((3,))},
        "b": {"kernel": jnp.ones((3, 2)) * 0.2},
    }
    pol = precision_mod.policy("f32", bf16_moments=True)
    tx_policy = precision_mod.apply_moment_rules(
        optax.adamw(1e-2), pol
    )
    tx_optax = optax.adamw(1e-2, mu_dtype=jnp.bfloat16)
    s_pol, s_opt = tx_policy.init(params), tx_optax.init(params)
    grads = jax.tree.map(lambda p: p * 0.5 + 0.01, params)
    for _ in range(3):
        u_pol, s_pol = tx_policy.update(grads, s_pol, params)
        u_opt, s_opt = tx_optax.update(grads, s_opt, params)
    for a, b in zip(jax.tree.leaves(s_pol), jax.tree.leaves(s_opt)):
        assert jnp.asarray(a).dtype == jnp.asarray(b).dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(u_pol), jax.tree.leaves(u_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # And the mu leaves actually store bf16.
    mus = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(s_pol)[0]
        if ".mu" in jax.tree_util.keystr(path)
    ]
    assert mus and all(m.dtype == jnp.bfloat16 for m in mus)


def test_eval_step_reads_fp8_state(runs, batches, mesh):
    eval_step = compile_step(
        make_classification_eval_step(
            input_keys=("input_ids", "attention_mask"), label_key="label"
        ),
        mesh, runs["fp8"]["state"], None, has_rng=False,
    )
    metrics = eval_step(runs["fp8"]["state"], batches[0])
    assert np.isfinite(float(metrics["loss"]))


def test_validation_errors(runs, mesh):
    # A policy that carries state must find it on the TrainState.
    with pytest.raises(ValueError, match="loss-scale state"):
        compile_step(
            make_classification_train_step(precision="fp8"),
            mesh, runs["legacy"]["state0"], None, precision="fp8",
        )
    # fp8 policy needs a model with fp8 sites.
    cfg = BertConfig(**_CFG)
    with pytest.raises(ValueError, match="fp8_train"):
        create_train_state(
            jax.random.key(0),
            BertForSequenceClassification(cfg),
            jnp.zeros((1, SEQ), jnp.int32),
            optax.adamw(1e-3),
            precision="fp8",
        )
    # fp8_train is exclusive with serving quantization.
    bad = BertConfig(**_CFG, fp8_train=True, weight_dtype="int8")
    with pytest.raises(ValueError, match="mutually exclusive"):
        BertForSequenceClassification(bad).init(
            jax.random.key(0), jnp.zeros((1, SEQ), jnp.int32)
        )
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM

    with pytest.raises(ValueError, match="does not compose"):
        LlamaForCausalLM(
            LLAMA_TINY(fp8_train=True, weight_dtype="int8")
        ).init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="unknown precision policy"):
        precision_mod.policy("fp4")


def test_fp8_accumulation_parity_band(runs, mesh, batches):
    """fp8 x gradient accumulation (the lifted refusal): accum_steps=2
    over the same fixed-seed batches stays within the fp8 parity band
    of the monolithic fp8 run. Forward amax observations combine by
    max across microbatches (exactly the monolithic amax); the g ring
    sees the per-microbatch cotangent scale, so the comparison is a
    band, not bitwise. Each batch is self-concatenated to 2B rows so
    the accum split divides the mesh's 8 dp shards; duplicated rows
    leave the mean loss and gradient unchanged, so the monolithic
    B-row run stays the valid control."""
    doubled = [
        {k: jnp.concatenate([v, v]) for k, v in batch.items()}
        for batch in batches
    ]
    cfg = precision_mod.resolve_policy("fp8").configure_model(
        BertConfig(**_CFG, fp8_train="force")
    )
    model = BertForSequenceClassification(cfg)
    state = create_train_state(
        jax.random.key(0), model, jnp.zeros((1, SEQ), jnp.int32),
        optax.adamw(1e-3), precision="fp8",
    )
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"),
            label_key="label", precision="fp8", accum_steps=2,
        ),
        mesh, state, None, precision="fp8",
    )
    _, losses, _ = _drive(step, state, doubled)
    diff = abs(losses[-1] - runs["fp8"]["losses"][-1])
    assert diff <= FP8_BAND, diff
    # The rings really advanced under accumulation (positive amaxes).
    assert all(np.isfinite(losses))


def test_fp8_lora_cell(mesh):
    """fp8_train x lora_rank (the opened cell): Fp8Dense carries the
    LoRADense adapter leaves, so one tree holds fp8 amax state AND
    extractable rank-r factors — the flywheel refresh's fp8 arm."""
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
    from tpudl.models.lora import extract_adapters, lora_param_labels

    model = LlamaForCausalLM(LLAMA_TINY(fp8_train=True, lora_rank=2))
    variables = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )
    assert "fp8" in variables
    adapters = extract_adapters(variables["params"])
    assert adapters  # every projection site carries (lora_a, lora_b)
    for site in adapters.values():
        assert site["lora_a"].shape[-1] == 2
        np.testing.assert_array_equal(np.asarray(site["lora_b"]), 0.0)
    # The frozen-base optimizer split sees the same labels as LoRADense.
    labels = jax.tree.leaves(lora_param_labels(variables["params"]))
    assert "train" in labels and "freeze" in labels
    # Forward runs (zero-init B: fp8-base output, adapters contribute 0).
    logits = model.apply(
        {"params": variables["params"], "fp8": variables["fp8"]},
        jnp.zeros((1, 8), jnp.int32),
    )
    assert np.isfinite(np.asarray(logits)).all()
