"""Optax training loops under pjit.

Replaces the reference lineage's PyTorch/Lightning train loops driven by
HorovodRunner / TorchDistributor (BASELINE.json `north_star`; the reference
tree itself contains no training code — SURVEY.md §0). Structural
difference from the Horovod design: gradient synchronization is not a
framework hook — sharding annotations on the step's inputs/outputs make
GSPMD emit psum/reduce-scatter inside the one compiled XLA executable per
step (SURVEY.md §3.6, §5.8).
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax.training import train_state
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpudl.ft import preemption as ft_preemption
from tpudl.obs import counters as obs_counters
from tpudl.obs import spans as obs_spans
from tpudl.parallel import overlap as grad_overlap
from tpudl.parallel.sharding import (
    Rules,
    active_mesh,
    constrain,
    current_mesh,
    host_to_global_array,
    tree_shardings,
)
from tpudl.runtime.mesh import batch_partition_spec, window_partition_spec


def microbatch(batch: dict, accum_steps: int) -> dict:
    """Split [B, ...] batch columns into [A, B/A, ...] microbatches for
    gradient accumulation, communication-free under the (dp, fsdp) batch
    sharding.

    A naive ``x.reshape(A, B/A)`` makes microbatch 0 the first B/A GLOBAL
    rows — which live on the first A⁻¹ fraction of devices — so GSPMD must
    all-to-all every step. Gradient averaging is permutation-invariant, so
    we instead pick the assignment where microbatch ``a`` takes a
    contiguous slice of each device's LOCAL rows: factor the batch through
    the shard grid ([nb, A, B/(nb·A)]), swap the loop axis out front, and
    merge back. Every reshape/transpose factors through the sharded
    dimension, so XLA compiles it to local moves.

    Called at trace time inside a compile_step-wrapped step (the active
    mesh supplies the batch-shard count); outside any mesh nb=1 and the
    plain reshape is already local.
    """
    mesh = current_mesh()
    nb = 1
    if mesh is not None:
        for ax in ("dp", "fsdp"):
            if ax in mesh.shape:
                nb *= mesh.shape[ax]

    def one(x):
        b = x.shape[0]
        if b % (nb * accum_steps):
            raise ValueError(
                f"batch {b} not divisible by accum_steps {accum_steps} x "
                f"batch shards {nb}"
            )
        xb = x.reshape(nb, accum_steps, b // (nb * accum_steps), *x.shape[1:])
        xb = constrain(xb, ("dp", "fsdp"))
        xb = jnp.swapaxes(xb, 0, 1)
        xb = constrain(xb, None, ("dp", "fsdp"))
        xb = xb.reshape(accum_steps, b // accum_steps, *x.shape[1:])
        return constrain(xb, None, ("dp", "fsdp"))

    return {k: one(v) for k, v in batch.items()}


class TrainState(train_state.TrainState):
    """TrainState extended with BatchNorm running statistics and the
    mixed-precision policy state (``tpudl.train.precision``): loss
    scale scalars + fp8 amax rings, carried as traced leaves so scale
    updates never recompile and checkpoints resume schedule-identical.
    ``None`` (the default) is the legacy no-policy state — zero new
    leaves, checkpoints unchanged."""

    batch_stats: Any = None
    precision: Any = None


def create_train_state(
    rng: jax.Array,
    model,
    sample_input: jax.Array,
    tx: optax.GradientTransformation,
    init_kwargs: Optional[dict] = None,
    precision: "Any | str | None" = None,
) -> TrainState:
    """``precision``: a ``tpudl.train.precision.PrecisionPolicy`` (or
    preset name) — wraps ``tx`` with the policy's rule-selected moment
    dtypes and seeds ``TrainState.precision`` (loss scale, and the
    model's ``"fp8"`` amax collection when the policy routes matmuls
    through fp8). None = exactly the pre-policy behavior.

    Recorded as the start-up phase ``startup.init_state``; ``programs``
    is how many programs the initialisers built on the way (flax runs
    them op by op)."""
    from tpudl.analysis.dispatch import compile_count

    if init_kwargs is None:
        init_kwargs = {"train": False}
    with obs_spans.startup_span("startup.init_state") as phase:
        programs = compile_count()
        variables = model.init(rng, sample_input, **init_kwargs)
        prec_state = None
        if precision is not None:
            from tpudl.train import precision as precision_mod

            pol = precision_mod.resolve_policy(precision)
            tx = precision_mod.apply_moment_rules(tx, pol)
            prec_state = precision_mod.init_precision_state(
                pol, variables.get("fp8")
            )
        state = TrainState.create(
            apply_fn=model.apply,
            params=variables["params"],
            batch_stats=variables.get("batch_stats"),
            precision=prec_state,
            tx=tx,
        )
        phase.note(programs=compile_count() - programs)
    return state


def cross_entropy_loss(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
    impl: str = "reference",
) -> jax.Array:
    """Mean cross-entropy through the tpudl.ops.cross_entropy seam.

    ``impl="reference"`` (default) is the optax composite this function
    always was; ``"fused"``/``"auto"`` stream the vocab axis through the
    Pallas online-logsumexp kernel so the [B, V] softmax is never
    materialized (the LM-vocab loss-step bandwidth fix — bench measures
    it as the fused-ops variant before any default flips)."""
    from tpudl.ops.cross_entropy import softmax_cross_entropy

    return softmax_cross_entropy(
        logits, labels, label_smoothing, impl=impl
    ).mean()


def make_classification_train_step(
    label_smoothing: float = 0.0,
    input_keys: "str | tuple" = ("image",),
    label_key: str = "label",
    moe_aux_weight: float = 0.0,
    accum_steps: int = 1,
    input_transform: Optional[Callable[[dict], dict]] = None,
    overlap_bucket_mb: Optional[float] = None,
    loss_impl: str = "reference",
    precision: "Any | str | None" = None,
) -> Callable:
    """Train step for image/sequence classification models.

    ``precision`` (a ``tpudl.train.precision.PrecisionPolicy`` or
    preset name — None keeps the legacy path bit-identical) applies
    the mixed-precision contract inside the step: rule-matched params
    cast to the compute dtype INSIDE the loss function (f32 masters,
    f32 grads), logits/loss reduce in f32, dynamic loss scaling (when
    the policy carries it) multiplies the loss before the backward,
    unscales the grads after, and a nonfinite gradient SKIPS the
    optimizer update (params/opt-state/step and fp8 amax windows
    untouched, scale backs off) — the skip is a traced select, one
    compiled program. With ``use_fp8`` the model's Fp8Dense sites run
    the delayed-scaling fp8 matmul: their amax rings ride
    ``state.precision["fp8"]`` in, advance with the step's observed
    amaxes (forward amaxes sown, gradient amax via the g_probe
    cotangent), and ride out on the returned state. Reported metrics
    gain ``loss_scale`` / ``grad_skipped`` when scaling is on; the
    ``loss`` metric is always the UNSCALED loss. fp8 composes with
    gradient accumulation too: each site's per-microbatch amax
    observations combine by elementwise max through the scan carry —
    the forward amaxes of the microbatches partition the full batch,
    so their max IS the monolithic step's amax, and the ring advances
    once per optimizer step exactly as at ``accum_steps=1``
    (tests/test_precision.py holds the accum-vs-monolithic fp8 loss
    trajectory to the fp8 parity band).

    ``loss_impl`` routes the cross-entropy through the
    tpudl.ops.cross_entropy dispatch seam ("reference" = the optax
    composite, unchanged default; "auto"/"fused" = the Pallas fused
    loss that never materializes the [B, V] softmax).

    `input_keys` name the batch columns passed positionally to the model —
    ("image",) for CV, ("input_ids", "attention_mask") for BERT-style.

    Works with or without BatchNorm state. All reductions (loss mean, batch
    statistics) have global semantics under pjit: with the batch sharded
    over (dp, fsdp) they compile to ICI collectives — synchronized BN and
    gradient all-reduce with zero framework code.

    ``moe_aux_weight`` > 0 adds the MoE load-balance losses the model's
    MoE layers sowed as ``moe_aux_loss`` (tpudl.ops.moe.MoEMlp) into the
    objective, and reports their sum as the ``moe_aux`` metric.

    ``accum_steps`` > 1 enables gradient accumulation: the batch splits
    into that many microbatches (communication-free — see ``microbatch``),
    a lax.scan computes and averages their gradients, and the optimizer
    applies ONCE — peak activation memory drops by the factor while the
    optimizer sees the full global batch (how configs[2]'s batch 1024 and
    BERT-large batch >=128 fit small meshes; BASELINE.json configs[2]/[3]).
    Exactly equal to the monolithic step for models whose loss is a mean
    over examples (tests/test_accumulation.py asserts parity at f32);
    BatchNorm models update their running stats per microbatch
    sequentially, matching the smaller per-microbatch statistics.

    ``input_transform`` runs INSIDE the compiled step, per microbatch,
    before the model sees the batch — the device-side preprocessing hook
    (e.g. tpudl.data.augment.device_normalize: uint8 pixels cross the
    host->device link, the scale+bias fuses into the first conv). Under
    accumulation it applies after the microbatch split, so the full
    batch stays in its compact wire dtype.

    Under accumulation the per-microbatch gradient add goes through
    ``tpudl.parallel.overlap.accumulate``: gradient leaves bucket in
    traversal order and each bucket's add carries its own optimization
    barrier, so on multi-device meshes XLA can interleave each bucket's
    cross-device reduction with the remaining backward compute instead
    of one monolithic end-of-microbatch sync. Identity on values
    (test_accumulation parity unchanged); ``overlap_bucket_mb``
    overrides the ``TPUDL_OVERLAP_BUCKET_MB`` default, and on a single
    batch shard the bucketing self-disables (nothing to overlap).
    """
    if isinstance(input_keys, str):
        input_keys = (input_keys,)
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    from tpudl.train import precision as precision_mod

    policy = precision_mod.resolve_policy(precision)
    # None = auto (env knob, else default-on-multi-shard); an explicit
    # 0 disables — mapped to 0 bytes, which accumulate() treats as off.
    overlap_bucket_bytes = (
        None if overlap_bucket_mb is None
        else int(overlap_bucket_mb * (1 << 20))
    )

    def _sown_aux(mutated: dict) -> jax.Array:
        """Sum only the sown ``moe_aux_loss`` entries (other intermediates
        — diagnostic probes — must not leak into the objective)."""
        total = jnp.zeros((), jnp.float32)
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            mutated.get("intermediates", {})
        ):
            if "moe_aux_loss" in jax.tree_util.keystr(path):
                total = total + jnp.sum(leaf)
        return total

    def _grads_and_metrics(state, params, stats, batch, dropout_rng):
        """value_and_grad of one (micro)batch; returns (grads, metrics,
        new_stats, prec_aux) with metrics as means over the
        (micro)batch. ``prec_aux`` is None on the legacy path; under an
        fp8 policy it carries the fp8-collection cotangents and the
        sown forward amaxes the step needs to advance the rings."""
        if input_transform is not None:
            batch = input_transform(batch)
        inputs = tuple(batch[k] for k in input_keys)
        prec = getattr(state, "precision", None) or {}
        loss_scale = (
            prec["loss_scale"]["scale"]
            if policy is not None and policy.loss_scale is not None
            else None
        )
        fp8_vars = (
            prec.get("fp8")
            if policy is not None and policy.use_fp8
            else None
        )

        def loss_fn(params, fp8_vars=None):
            run_params = (
                policy.cast_params(params) if policy is not None else params
            )
            variables = {"params": run_params}
            if fp8_vars is not None:
                variables["fp8"] = fp8_vars
            mutable = []
            if stats is not None:
                variables["batch_stats"] = stats
                mutable.append("batch_stats")
            if moe_aux_weight > 0.0 or fp8_vars is not None:
                mutable.append("intermediates")
            if mutable:
                outputs, mutated = state.apply_fn(
                    variables,
                    *inputs,
                    train=True,
                    mutable=mutable,
                    rngs={"dropout": dropout_rng},
                )
                new_stats = mutated.get("batch_stats")
            else:
                outputs = state.apply_fn(
                    variables, *inputs, train=True,
                    rngs={"dropout": dropout_rng},
                )
                mutated = {}
                new_stats = None
            if policy is not None:
                # Reduce-dtype contract: logits (and therefore the
                # loss reduction) leave the compute dtype before any
                # mean — the bf16/fp8 forward never degrades the loss
                # arithmetic itself.
                outputs = outputs.astype(policy.reduce_dtype)
            with jax.named_scope("loss"):
                loss = cross_entropy_loss(
                    outputs, batch[label_key], label_smoothing,
                    impl=loss_impl,
                )
            aux = None
            if moe_aux_weight > 0.0:
                aux = _sown_aux(mutated)
                loss = loss + moe_aux_weight * aux
            # Dynamic loss scaling: the OBJECTIVE is scaled (after any
            # aux terms, so the whole backward sees one factor); the
            # reported loss stays unscaled via the aux tuple.
            objective = loss if loss_scale is None else loss * loss_scale
            return objective, (loss, outputs, new_stats, aux, mutated)

        if fp8_vars is not None:
            (
                (_, (loss, logits, new_stats, aux, mutated)),
                (grads, fp8_grads),
            ) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(
                params, fp8_vars
            )
        else:
            (
                (_, (loss, logits, new_stats, aux, mutated)),
                grads,
            ) = jax.value_and_grad(loss_fn, has_aux=True)(params)
            fp8_grads = None
        if loss_scale is not None:
            # Unscale per (micro)batch — linear, so accumulation-order
            # independent; a scaled overflow stays nonfinite through
            # the division and trips the skip select.
            grads = jax.tree.map(lambda g: g / loss_scale, grads)
        metrics = {
            "loss": loss,
            "accuracy": jnp.mean(jnp.argmax(logits, -1) == batch[label_key]),
        }
        if aux is not None:
            metrics["moe_aux"] = aux
        prec_aux = None
        if fp8_vars is not None:
            prec_aux = {
                "fp8_grads": fp8_grads,
                "intermediates": mutated.get("intermediates", {}),
            }
        return grads, metrics, new_stats, prec_aux

    def _finish_policy_step(state, grads, metrics, new_stats, prec_aux):
        """Optimizer apply under a precision policy: the skip-on-
        nonfinite select, the loss-scale transition, and the fp8 ring
        advance — all traced (one compiled program; a skipped step is
        a select, not a cond)."""
        prec = state.precision or {}
        with jax.named_scope("optimizer"):
            applied = state.apply_gradients(grads=grads)
        if new_stats is not None:
            applied = applied.replace(batch_stats=new_stats)
        if policy.loss_scale is not None:
            ok = precision_mod.all_finite(grads)
            # Skip = the whole state transition never happened: params,
            # opt state, step counter, batch stats all keep their old
            # values (precision state is replaced below either way).
            new_state = precision_mod.select_tree(ok, applied, state)
        else:
            ok = jnp.asarray(True)
            new_state = applied
        new_prec = dict(prec)
        metrics = dict(metrics)
        if policy.loss_scale is not None:
            # Report the scale the step USED (pre-transition) so logs
            # line up with the backward that just ran.
            metrics["loss_scale"] = prec["loss_scale"]["scale"]
            metrics["grad_skipped"] = jnp.where(ok, 0.0, 1.0)
            new_prec["loss_scale"] = precision_mod.update_loss_scale(
                prec["loss_scale"], policy.loss_scale, ok
            )
        if policy.use_fp8 and prec_aux is not None:
            from tpudl.ops.fp8_dot import updated_fp8_state

            new_prec["fp8"] = updated_fp8_state(
                prec["fp8"],
                prec_aux["intermediates"],
                prec_aux["fp8_grads"],
                ok,
            )
        if new_prec:
            new_state = new_state.replace(precision=new_prec)
        return new_state, metrics

    def step(state: TrainState, batch: dict, rng: jax.Array):
        step_rng = jax.random.fold_in(rng, state.step)
        if accum_steps == 1:
            grads, metrics, new_stats, prec_aux = _grads_and_metrics(
                state, state.params, state.batch_stats, batch, step_rng
            )
        else:
            micro = microbatch(batch, accum_steps)

            def body(carry, xs):
                grads_acc, stats, metrics_acc, prec_acc = carry
                mb, a = xs
                grads, metrics, new_stats, prec_aux = _grads_and_metrics(
                    state, state.params, stats,
                    mb, jax.random.fold_in(step_rng, a),
                )
                grads_acc = grad_overlap.accumulate(
                    grads_acc, grads, bucket_bytes=overlap_bucket_bytes
                )
                metrics_acc = jax.tree.map(jnp.add, metrics_acc, metrics)
                # fp8 amax observations combine by MAX, not sum: every
                # leaf is a max-|value| reduction (forward amaxes sown
                # per site, the g_probe cotangent; the hist cotangents
                # are structural zeros), all >= 0 — so a zeros carry
                # is the identity and the combined tree is exactly the
                # monolithic step's observation for forward sites.
                prec_acc = jax.tree.map(jnp.maximum, prec_acc, prec_aux)
                return (grads_acc, new_stats, metrics_acc, prec_acc), None

            # All microbatches run inside the one scan (a single copy of
            # the layer graph in the executable — unrolling microbatch 0
            # to learn the carry structure would double it); the metrics
            # tree structure comes from eval_shape, which traces without
            # executing. BatchNorm stats thread through the carry,
            # updating per microbatch sequentially.
            mb0 = {k: v[0] for k, v in micro.items()}
            _, m_shape, _, aux_shape = jax.eval_shape(
                lambda s, b, r: _grads_and_metrics(
                    state, state.params, s, b, r
                ),
                state.batch_stats, mb0, step_rng,
            )
            zeros_of = lambda sh: jnp.zeros(sh.shape, sh.dtype)  # noqa: E731
            carry0 = (
                jax.tree.map(jnp.zeros_like, state.params),
                state.batch_stats,
                jax.tree.map(zeros_of, m_shape),
                # None (no fp8 policy) stays None through the scan;
                # under fp8 the zeros tree is the max-combine identity.
                jax.tree.map(zeros_of, aux_shape),
            )
            (grads, new_stats, metrics, prec_aux), _ = jax.lax.scan(
                body, carry0, (micro, jnp.arange(accum_steps))
            )
            # Equal-sized microbatches: mean of per-microbatch means is
            # the global mean — both grads (linear in the loss mean) and
            # metrics divide by the microbatch count.
            grads = jax.tree.map(lambda g: g / accum_steps, grads)
            metrics = jax.tree.map(lambda m: m / accum_steps, metrics)
        if policy is not None:
            return _finish_policy_step(
                state, grads, metrics, new_stats, prec_aux
            )
        # Scopes name the step's device operations in a profiler trace
        # (``loss`` above; a ``make_optimizer`` chain puts its clip
        # under ``optimizer/grad_clip``).
        with jax.named_scope("optimizer"):
            new_state = state.apply_gradients(grads=grads)
        if new_stats is not None:
            new_state = new_state.replace(batch_stats=new_stats)
        return new_state, metrics

    return step


def make_classification_eval_step(
    input_keys: "str | tuple" = ("image",),
    label_key: str = "label",
    input_transform: Optional[Callable[[dict], dict]] = None,
    loss_impl: str = "reference",
) -> Callable:
    """Eval step returning mean loss/accuracy over the batch.

    ``loss_impl``: the tpudl.ops.cross_entropy dispatch seam for the
    per-example loss ("reference" default = the optax composite;
    "auto"/"fused" = the vocab-streaming Pallas kernel).

    A ``"_valid"`` batch column ([B] 0/1 row mask — see ``pad_batch``)
    switches the reductions to masked means over the real rows only, so
    a zero-padded tail batch reports exactly the metrics of its real
    rows. Without the column the reductions are plain means (the fast
    path full batches keep).
    """
    if isinstance(input_keys, str):
        input_keys = (input_keys,)

    def step(state: TrainState, batch: dict):
        if input_transform is not None:
            batch = input_transform(batch)
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        prec = getattr(state, "precision", None)
        if prec and "fp8" in prec:
            # fp8-trained models (Fp8Dense sites) read their amax rings
            # at apply time; eval quantizes with the trained scales —
            # the same numerics the train forward saw. Read-only: the
            # sow is dropped, the rings don't advance.
            variables["fp8"] = prec["fp8"]
        logits = state.apply_fn(
            variables, *(batch[k] for k in input_keys), train=False
        )
        labels = batch[label_key]
        from tpudl.ops.cross_entropy import softmax_cross_entropy

        per_loss = softmax_cross_entropy(logits, labels, impl=loss_impl)
        correct = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
        valid = batch.get("_valid")
        if valid is None:
            return {"loss": per_loss.mean(), "accuracy": correct.mean()}
        w = valid.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(w), 1.0)
        return {
            "loss": jnp.sum(per_loss * w) / denom,
            "accuracy": jnp.sum(correct * w) / denom,
        }

    # evaluate() may only auto-pad ragged tails into steps that weight
    # the pads out; this marker (propagated by compile_step) is how it
    # knows. Custom mask-unaware steps keep exact per-size execution.
    step._tpudl_mask_aware = True
    return step


def pad_batch(batch: dict, to_size: int) -> dict:
    """Pad every [B, ...] column of ``batch`` to ``to_size`` rows with
    zeros and add a ``"_valid"`` float32 [to_size] column marking the
    real rows (1.0) vs the pads (0.0).

    This is how a ragged tail batch rides the SAME compiled executable
    as the full batches on a sharded mesh: the padded batch keeps the
    divisible leading dim, and mask-aware consumers
    (make_classification_eval_step, evaluate) weight the pads out of
    every metric. An existing ``"_valid"`` column is extended with
    zeros (already-padded batches pass through idempotently).
    """
    sizes = {k: v.shape[0] for k, v in batch.items()}
    b = next(iter(sizes.values()))
    if any(s != b for s in sizes.values()):
        raise ValueError(f"ragged leading dims within one batch: {sizes}")
    if to_size < b:
        raise ValueError(f"cannot pad batch of {b} down to {to_size}")

    def _pad0(x, width):
        widths = [(0, width)] + [(0, 0)] * (x.ndim - 1)
        if isinstance(x, jax.Array):
            return jnp.pad(x, widths)
        return np.pad(np.asarray(x), widths)

    valid = batch.get("_valid")
    if valid is None:
        valid = np.ones((b,), np.float32)
    out = {k: _pad0(v, to_size - b) for k, v in batch.items() if k != "_valid"}
    out["_valid"] = _pad0(valid, to_size - b)
    return out


@obs_spans.startup_phase("startup.compile_step")
def compile_step(
    step_fn: Callable,
    mesh: Mesh,
    state: TrainState,
    rules: Optional[Rules] = None,
    donate_state: Optional[bool] = None,
    has_rng: bool = True,
    preprocess: Optional[Callable[[dict], dict]] = None,
    steps_per_dispatch: int = 1,
    precision: "Any | str | None" = None,
) -> Callable:
    """jit a (state, batch[, rng]) step with mesh shardings.

    ``precision``: the ``tpudl.train.precision.PrecisionPolicy`` (or
    preset name) the step was built with — compile_step validates the
    state actually carries the policy's traced pieces (loss-scale
    scalars, fp8 amax rings) so a state built without
    ``create_train_state(precision=...)`` fails HERE with a named
    error instead of silently training unscaled, and exposes it as
    ``wrapped.precision`` for drivers/benchmarks. The policy's dtype
    work itself lives inside the step function
    (``make_classification_train_step(precision=...)``); the new state
    leaves shard replicated like any scalar under the rule engine.

    - state (params / opt state / batch stats) sharded by `rules`
      (replicated for pure DP, fsdp/tp specs for sharded training);
    - batch sharded over the (dp, fsdp) axes on dim 0;
    - metrics replicated.

    ``donate_state`` defaults to ``has_rng``: train steps (which take an rng
    and return a new state) donate the old state's buffers; eval steps
    (``has_rng=False``, returning only metrics) must NOT donate or the
    caller's state would be destroyed on first use.

    ``preprocess`` runs on the batch INSIDE the jitted program, before
    ``step_fn`` sees it — the device-side preprocessing hook for ANY step
    shape (e.g. ``tpudl.data.datasets.device_normalize_cifar``: uint8
    pixels cross the host->device link at 1/4 the bytes, XLA fuses the
    cast+scale into the first layer). It applies to the whole batch
    before any gradient-accumulation split; a step built by
    ``make_classification_train_step(input_transform=...)`` instead
    applies per microbatch, which keeps the full batch in its compact
    wire dtype under accumulation — prefer that for ``accum_steps > 1``.

    ``steps_per_dispatch=K`` > 1 additionally compiles a FUSED K-step
    program — a ``lax.scan`` of ``step_fn`` over a [K, B, ...] stacked
    batch window — exposed as ``wrapped.window_step(state, window,
    rng)``, which returns the final state plus [K]-stacked per-step
    metrics from ONE device dispatch. Why: each single dispatch pays
    host dispatch latency (the round-5 bench's BERT-base plateau);
    fusing K steps pays it once per
    K. Semantics are bit-for-bit identical to K single dispatches with
    the same ``rng``: the scan threads the state carry exactly as the
    caller would, per-step randomness derives from ``state.step``
    (which increments inside the carry — ``make_classification_train_
    step`` folds it), and the carry keeps donation. The single-step
    program is always built too — it serves ragged tails (batch counts
    not divisible by K) via the same ``wrapped(state, batch, rng)``
    call. Train-only: ``has_rng=False`` steps (eval) raise.
    """
    if donate_state is None:
        donate_state = has_rng
    if steps_per_dispatch < 1:
        raise ValueError(
            f"steps_per_dispatch must be >= 1, got {steps_per_dispatch}"
        )
    if steps_per_dispatch > 1 and not has_rng:
        raise ValueError(
            "steps_per_dispatch > 1 requires a train-shaped step "
            "(has_rng=True): eval steps return no carried state to scan"
        )
    precision_policy = None
    if precision is not None:
        from tpudl.train import precision as precision_mod

        precision_policy = precision_mod.resolve_policy(precision)
        precision_mod.validate_state(precision_policy, state)
    if preprocess is not None:
        base_fn = step_fn
        if has_rng:
            def step_fn(state, batch, rng, _base=base_fn):
                return _base(state, preprocess(batch), rng)
        else:
            def step_fn(state, batch, _base=base_fn):
                return _base(state, preprocess(batch))
        step_fn._tpudl_mask_aware = getattr(
            base_fn, "_tpudl_mask_aware", False
        )
    state_sh = tree_shardings(mesh, state, rules)
    batch_sh = NamedSharding(mesh, batch_partition_spec())
    repl = NamedSharding(mesh, PartitionSpec())

    # Programs under names of their own: a trace's ``XLA Modules`` line
    # then reads ``jit_tpudl_train_step``, whatever the step function
    # was called.
    if has_rng:
        def tpudl_train_step(state, batch, rng):
            return step_fn(state, batch, rng)

        jitted = jax.jit(
            tpudl_train_step,
            in_shardings=(state_sh, batch_sh, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate_state else (),
        )
    else:
        def tpudl_eval_step(state, batch):
            return step_fn(state, batch)

        jitted = jax.jit(
            tpudl_eval_step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=repl,
            donate_argnums=(0,) if donate_state else (),
        )

    jitted_window = None
    window_sh = None
    if steps_per_dispatch > 1:
        window_sh = NamedSharding(mesh, window_partition_spec())

        def tpudl_window_step(state, window, rng):
            # One compiled program for K steps: the scan body IS the
            # single-step function (one copy of the layer graph in the
            # executable), the state threads through the carry with the
            # same donation the single-step program has, and metrics
            # stack on the scan's ys axis -> [K] per leaf. rng passes
            # through unchanged per inner step — exactly what fit()
            # does across K single dispatches; per-step variation comes
            # from folding state.step, which increments in the carry.
            def body(carry, batch):
                return step_fn(carry, batch, rng)

            return jax.lax.scan(body, state, window)

        jitted_window = jax.jit(
            tpudl_window_step,
            in_shardings=(state_sh, window_sh, repl),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,) if donate_state else (),
        )

    def _placed(tree, shardings):
        # Explicit placement before the call, for two reasons:
        # - jit's implicit numpy-arg transfer runs synchronously inside
        #   the dispatch, where an explicit put overlaps with compute;
        # - an uncommitted first argument compiles a second executable the
        #   moment the (committed) outputs are fed back in — a silent
        #   duplicate compile (~60 s for BERT-base) inside the first
        #   training step.
        # Committed args pass through untouched, so the steady state is a
        # no-op scan over the leaves.
        leaves, treedef = jax.tree.flatten(tree)
        if all(
            isinstance(leaf, jax.Array) and leaf.committed
            for leaf in leaves
        ):
            return tree
        # Leaf-wise placement, NOT jax.device_put(tree, shardings): the
        # whole-tree form compares treedefs including static pytree
        # fields, so a TrainState rebuilt by the same code (fresh
        # apply_fn/tx closures, identical array structure) would be
        # rejected as a structure mismatch. A single Sharding (the batch
        # prefix case) broadcasts over all leaves.
        if isinstance(shardings, jax.sharding.Sharding):
            sh_leaves = [shardings] * len(leaves)
        else:
            sh_leaves = jax.tree.leaves(shardings)
        # Multi-process shardings span non-addressable devices, where
        # device_put refuses host values: build those leaves from their
        # addressable shards instead (make_array_from_callback, treating
        # the host value as the GLOBAL value — correct for the
        # replicated state/rng leaves; batch columns in multi-process
        # runs arrive as already-global arrays and pass through).
        placed: list = [None] * len(leaves)
        put_idx: list = []
        for idx, (leaf, sh) in enumerate(zip(leaves, sh_leaves)):
            if sh.is_fully_addressable:
                put_idx.append(idx)
            elif isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
                placed[idx] = leaf  # already global; jit validates it
            else:
                placed[idx] = host_to_global_array(leaf, sh)
        if put_idx:
            for idx, arr in zip(
                put_idx,
                jax.device_put(
                    [leaves[i] for i in put_idx],
                    [sh_leaves[i] for i in put_idx],
                ),
            ):
                placed[idx] = arr
        return jax.tree.unflatten(treedef, placed)

    state_treedef = jax.tree.structure(state)
    # Distinct tx objects already warned about, keyed by id with the
    # object held so ids can't be recycled by the allocator. Seeded with
    # the compile-time tx: a rebuilt state that still carries the
    # ORIGINAL tx (apply_fn-only rebuild) grafts silently. Bounded: a
    # caller rebuilding its state EVERY call would otherwise grow this
    # dict (and the warning stream) one entry per step — past the cap,
    # one final suppression notice and no further tracking.
    seen_txs = {id(state.tx): state.tx}
    _TX_WARN_CAP = 8

    def _grafted(state_arg):
        if jax.tree.structure(state_arg) == state_treedef:
            return state_arg
        # Same array structure, different static metadata: a
        # TrainState rebuilt by the same code carries fresh
        # apply_fn/tx closures that compare unequal, which pjit's
        # in_shardings prefix matching rejects. The executable
        # encodes the ORIGINAL tx, so grafting the incoming leaves
        # into the compile-time treedef is the correct semantics
        # (leaf-count mismatches still raise here). Warn once PER
        # DISTINCT incoming tx — not once per wrapper — so a second
        # rebuilt state whose tx genuinely carries different
        # hyperparameters (a new lr, a different schedule) is
        # flagged too, instead of passing silently after the first
        # warning fired.
        tx = getattr(state_arg, "tx", None)
        if (
            tx is not None
            and id(tx) not in seen_txs
            and len(seen_txs) <= _TX_WARN_CAP
        ):
            seen_txs[id(tx)] = tx
            import warnings

            if len(seen_txs) > _TX_WARN_CAP:
                warnings.warn(
                    "compile_step: more than "
                    f"{_TX_WARN_CAP - 1} distinct rebuilt optimizers "
                    "grafted into this compiled step — further ones "
                    "will not be reported individually (the "
                    "ORIGINALLY-COMPILED optimizer still applies to "
                    "all of them)",
                    stacklevel=3,
                )
            else:
                warnings.warn(
                    "compile_step: incoming state's pytree metadata "
                    "(apply_fn/tx) differs from the compile-time "
                    "state; its array leaves are grafted into the "
                    "ORIGINAL treedef and the ORIGINALLY-COMPILED "
                    "optimizer still applies — rebuild the compiled "
                    "step if you changed optimizer hyperparameters",
                    stacklevel=3,
                )
        return jax.tree.unflatten(
            state_treedef, jax.tree.leaves(state_arg)
        )

    def wrapped(state_arg, batch, *rest):
        state_arg = _grafted(state_arg)
        state_arg = _placed(state_arg, state_sh)
        batch = _placed(batch, batch_sh)
        with active_mesh(mesh):
            out = jitted(state_arg, batch, *rest)
        if wrapped._tpudl_compile_pending:
            # First-call marker for the observability layer: fit() and
            # evaluate() read it BEFORE each call to classify that
            # call's wall-clock as "compile" (trace+compile dominates
            # the first invocation) vs "step". Approximate on purpose —
            # a later new-shape recompile (e.g. evaluate's padded
            # variant) still counts as a step.
            wrapped._tpudl_compile_pending = False
            _first_step_made()
        return out

    def _first_step_made():
        # The step's first call in a fit(): traced, compiled and
        # dispatched (not waited for), from where fit() read the clock
        # before its loop. ``startup.first_step``, recorded after the
        # fact and as an ENCLOSING span: it lies around the loop's own
        # spans and the programs' on the same clock.
        began, wrapped._tpudl_first_step_began = (
            wrapped._tpudl_first_step_began, None
        )
        if began is not None:
            rec = obs_spans.startup_recorder()
            rec.record(
                "startup.first_step", obs_spans.CAT_ENCLOSING, began,
                rec.clock() - began,
            )

    wrapped.jitted = jitted  # expose for lower()/cost analysis
    wrapped.state_shardings = state_sh
    wrapped.batch_sharding = batch_sh
    wrapped._tpudl_mask_aware = getattr(step_fn, "_tpudl_mask_aware", False)
    wrapped._tpudl_compile_pending = True
    wrapped._tpudl_first_step_began = None
    wrapped.steps_per_dispatch = steps_per_dispatch
    wrapped.precision = precision_policy

    if jitted_window is not None:

        def window_step(state_arg, window, *rest):
            """Fused K-step dispatch: (state, [K, B, ...] window, rng)
            -> (final state, [K]-stacked metrics), one device call."""
            state_arg = _grafted(state_arg)
            state_arg = _placed(state_arg, state_sh)
            window = _placed(window, window_sh)
            with active_mesh(mesh):
                out = jitted_window(state_arg, window, *rest)
            if wrapped._tpudl_window_compile_pending:
                wrapped._tpudl_window_compile_pending = False
                _first_step_made()
            return out

        wrapped.window_step = window_step
        wrapped.jitted_window = jitted_window
        wrapped.window_sharding = window_sh
        wrapped._tpudl_window_compile_pending = True
    return wrapped


def _obs_pull(rec, it, attrs):
    """Timed ``next(it)`` recording a data_wait span — the instrumented
    arm shared by fit() and evaluate() (their uninstrumented fast paths
    stay inline so the disabled mode allocates nothing per step).
    Returns ``(batch, wait_seconds)`` or ``None`` on exhaustion."""
    span = rec.begin("data_wait", obs_spans.CAT_DATA_WAIT, **attrs)
    try:
        batch = next(it)
    except StopIteration:
        span.cancel()
        return None
    return batch, span.end()["dur"]


def _to_host_arrays(metrics: dict) -> dict:
    """The same for a fused window's [K]-stacked metrics."""
    return {k: np.asarray(v) for k, v in metrics.items()}


def _to_host_metrics(metrics: dict) -> dict:
    """Synchronous device->host readback of one metrics dict — the
    blocking conversion fit()'s async drain avoids in the steady state.
    Module-level on purpose: tests count calls to it to assert the
    async path never fetches synchronously per logged step."""
    return {k: float(v) for k, v in metrics.items()}


def _stack_window(batch_list: list) -> dict:
    """Stack K same-shape batch dicts into one [K, B, ...] window.

    Host (numpy) columns stack with ``np.stack`` — one host copy, and
    the compiled window program's placement then does a single H2D
    transfer of the whole window. Device columns stack with
    ``jnp.stack`` (a device-side copy); feed fit() from a window-mode
    ``DevicePrefetcher`` (``prefetch_to_device(window=K)``) to assemble
    the window BEFORE the H2D stage and skip that copy entirely."""
    out = {}
    for k in batch_list[0]:
        vals = [b[k] for b in batch_list]
        if all(isinstance(v, np.ndarray) for v in vals):
            out[k] = np.stack(vals)
        else:
            out[k] = jnp.stack(vals)
    return out


def fit(
    compiled_step: Callable,
    state: TrainState,
    batches: Iterable[dict],
    rng: jax.Array,
    num_steps: Optional[int] = None,
    log_every: int = 0,
    logger: Optional[Callable[[int, dict], None]] = None,
    profile_dir: Optional[str] = None,
    profile_window: tuple = (2, 8),
    checkpoint_manager=None,
    checkpoint_every: int = 0,
    steps_per_dispatch: Optional[int] = None,
    async_metrics: Optional[bool] = None,
    metric_window: int = 8,
):
    """Drive the compiled step over a batch iterator; returns final state and
    the last metrics (host-synced once at the end, not per step).

    Fused dispatch (``steps_per_dispatch=K``, default: whatever the
    compiled step was built with): each loop iteration pulls K batches,
    stacks them into one [K, B, ...] window, and runs the step's fused
    K-step program (``compile_step(..., steps_per_dispatch=K)``) — ONE
    host dispatch and one ``dispatch_window`` span per K train steps,
    which is the lever against per-step dispatch latency (the round-5
    BERT-base MFU plateau). Bit-for-bit identical to K single
    dispatches; a ragged tail (fewer than K batches left, or a
    ``num_steps`` not divisible by K) falls back to the single-step
    program batch by batch. Feed a window-mode prefetcher
    (``prefetch_to_device(window=K)``) so windows assemble host-side
    before the H2D stage; any other iterator works too (fit stacks K
    pulls itself). Checkpoint cadence and preemption flags are honored
    at dispatch-window granularity: a cadence step inside a window
    commits at the window's final step, and saves stay keyed by the
    state's true step counter so resume is schedule-identical.

    Async metrics (``async_metrics``, default: on exactly when
    ``steps_per_dispatch > 1``): per-dispatch device metrics go to a
    ``tpudl.train.metrics.MetricFetcher`` that reads them back on its
    own thread, so the loop never blocks on metric readback in the
    steady state — logger callbacks still fire in step order, just up
    to ``metric_window`` dispatches late (staleness, not loss; all of
    them fire before fit returns). Time blocked on the fetcher
    (backpressure past ``metric_window``, the end-of-fit flush) records
    as ``metric_wait`` spans, separate from ``data_wait``. With async
    off, logging synchronously fetches per logged step exactly as
    before.

    Profiling (SURVEY.md §5.1): with `profile_dir` set — or the
    TPUDL_PROFILE_DIR environment variable — steps
    [profile_window[0], profile_window[1]) are captured with
    jax.profiler.trace into a TensorBoard-viewable XLA trace (op-level,
    including ICI collective time), skipping the compile step.

    Checkpointing (SURVEY.md §5.3/§5.4): with a `checkpoint_manager`
    (tpudl.checkpoint.CheckpointManager) and `checkpoint_every` > 0, the
    train state is saved every N steps (async — training continues while
    shards flush) and once at the end. Saves are keyed by the state's own
    step counter, so a restored-and-continued run lines up with the
    schedule of an uninterrupted one. Use `resume_latest` to restore
    before calling fit. Managers whose ``save`` accepts ``rng`` /
    ``data_state`` (both backends of tpudl.checkpoint.CheckpointManager)
    get the FULL resume state: the training rng key and — when
    ``batches`` exposes a ``state()`` position (tpudl.ft.
    ResumableIterator) — the data position, so ``tpudl.ft.resume_run``
    restarts schedule-identically without replaying batches or dropout
    masks.

    Preemption (tpudl.ft.preemption): when a grace-window handler is
    installed and a SIGTERM/SIGINT has arrived, the loop stops before
    the next step, writes the final checkpoint (the EMERGENCY save —
    same end-of-fit path), and returns with ``info["preempted"] =
    True`` so the worker can exit cleanly within the grace window.

    Observability (tpudl.obs): with TPUDL_OBS_DIR set (or
    tpudl.obs.enable called), every step records a data-wait span (time
    blocked on the batch iterator) and a step span (time in the
    compiled-step call — the FIRST call classifies as "compile" via
    compile_step's first-call marker), and step/data-wait/compile
    latency histograms accumulate in the counters registry, snapshotted
    into the span stream at the end. Host-side accounting: under JAX
    async dispatch the per-step span measures dispatch + backpressure
    time, which converges to device step time in the steady state.
    Disabled (the default) costs one env lookup per fit() call and
    nothing per step.
    """
    from tpudl.analysis.registry import env_str

    profile_dir = profile_dir or env_str("TPUDL_PROFILE_DIR")
    prof_start, prof_stop = profile_window
    profiling = False
    prof_done = False  # one trace per fit: no restart after the window

    if steps_per_dispatch is None:
        K = int(getattr(compiled_step, "steps_per_dispatch", 1) or 1)
    else:
        K = int(steps_per_dispatch)
    if K < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {K}")
    window_step = getattr(compiled_step, "window_step", None) if K > 1 else None
    if K > 1:
        compiled_k = int(getattr(compiled_step, "steps_per_dispatch", 1) or 1)
        if window_step is None or compiled_k != K:
            raise ValueError(
                f"fit(steps_per_dispatch={K}) needs a step built with "
                f"compile_step(..., steps_per_dispatch={K}); this one "
                f"was built with steps_per_dispatch={compiled_k}"
            )

    async_on = (K > 1) if async_metrics is None else bool(async_metrics)
    fetcher = None
    if async_on:
        from tpudl.train.metrics import MetricFetcher

        fetcher = MetricFetcher(window=metric_window)

    rec = obs_spans.active_recorder()
    if rec is not None:
        reg = obs_counters.registry()
        h_step = reg.histogram("step_time_s")
        h_data = reg.histogram("data_wait_s")
        h_compile = reg.histogram("compile_time_s")
        h_mwait = reg.histogram("metric_wait_s") if fetcher else None

    # Live telemetry (tpudl.obs.exporter): with TPUDL_OBS_PORT set the
    # process serves /metrics | /healthz | /snapshot while fit runs;
    # the train_loop heartbeat beats once per dispatch so a hung loop
    # (stuck iterator, wedged collective) reads as a growing
    # heartbeat age on /healthz instead of silence. The beat itself is
    # a lock + two stores — noise against a compiled-step dispatch.
    from tpudl.obs import exporter as obs_exporter

    obs_exporter.maybe_start_from_env()
    heartbeat = obs_exporter.Heartbeat("train_loop")
    g_last_step = obs_counters.registry().gauge("train_last_step")

    metrics = None          # last dispatch's DEVICE metrics tree
    metrics_count = 1       # 1 (scalar leaves) or K ([K]-stacked leaves)
    host_metrics_last = None  # last host dict the async drain delivered
    start = time.perf_counter()
    n = 0
    dispatches = 0
    # One host sync up front; the counter advances exactly 1 per compiled
    # step, so per-step int(state.step) (a device round-trip that would
    # stall async dispatch) is never needed.
    start_step = (
        int(state.step) if checkpoint_manager is not None else 0
    )
    # Full-resume support is a capability of the manager's save
    # signature (both tpudl.checkpoint backends have it; third-party
    # managers with the legacy 2-arg save keep working).
    full_resume = False
    if checkpoint_manager is not None:
        import inspect

        try:
            save_params = inspect.signature(
                checkpoint_manager.save
            ).parameters
            full_resume = (
                "rng" in save_params and "data_state" in save_params
            )
        except (TypeError, ValueError):
            pass
    data_position = getattr(batches, "state", None)

    def _save_ckpt(step_no, state):
        if full_resume:
            checkpoint_manager.save(
                step_no, state, rng=rng,
                data_state=(
                    data_position() if callable(data_position) else None
                ),
            )
        else:
            checkpoint_manager.save(step_no, state)

    last_ckpt_step = None

    def _log_line(step_no, host_metrics):
        if logger:
            logger(step_no, host_metrics)
        else:
            print(f"step {step_no}: {host_metrics}")
        # Live numerics at log cadence: reads the CURRENT state (the
        # closure sees fit's loop variable), which may be a few steps
        # past the metrics being logged — staleness a telemetry gauge
        # tolerates, a per-step device fetch would not.
        from tpudl.train import precision as precision_mod

        precision_mod.publish_numerics_telemetry(
            getattr(state, "precision", None)
        )

    def _deliver(results):
        """Hand drained (step, host_metrics) pairs to the logger — in
        step order (the fetcher is FIFO), possibly several dispatches
        after the step ran (the staleness tradeoff)."""
        nonlocal host_metrics_last
        for step_no, hm in results:
            host_metrics_last = hm
            if log_every and step_no % log_every == 0:
                _log_line(step_no, hm)

    def _read_back(step_no, read, m):
        """The synchronous ``log_every`` path's blocking read-back of
        one dispatch's metrics. The dispatch returned at once, so this
        wait holds the device's whole step: goodput shows a
        ``log_every=1`` run as waiting on its metrics."""
        if rec is None:
            return read(m)
        span = rec.begin(
            "metric_wait", obs_spans.CAT_METRIC_WAIT, step=step_no
        )
        host = read(m)
        span.end()
        return host

    def _submit(first_step, m, count):
        """Queue one dispatch's device metrics on the async fetcher and
        drain whatever finished — never blocking except on the bounded
        window (recorded as metric_wait)."""
        if rec is not None:
            span = rec.begin(
                "metric_wait", obs_spans.CAT_METRIC_WAIT,
                step=first_step + count - 1,
            )
            waited = fetcher.submit(first_step, m, count)
            if waited > 0:
                span.end(span.t0 + waited)
                h_mwait.observe(waited)
            else:
                span.cancel()
        else:
            fetcher.submit(first_step, m, count)
        _deliver(fetcher.ready())

    preempted = False
    it = iter(batches)
    use_pf_window = False
    if K > 1 and hasattr(it, "pull_window"):
        pf_window = int(getattr(it, "window", 1) or 1)
        if pf_window not in (1, K):
            raise ValueError(
                f"batch source assembles windows of {pf_window} but "
                f"fit runs steps_per_dispatch={K} — configure "
                f"prefetch_to_device(window={K})"
            )
        use_pf_window = pf_window == K
    windows_done = K == 1  # no fused program / no more full windows
    from collections import deque

    pending = deque()  # leftover singles from a partial window pull
    i = 0
    # A compiled step that has not run before: its first call (trace,
    # compile, dispatch) is the start-up phase ``startup.first_step``,
    # recorder or not. Asked once a call of fit(), here; the step's own
    # first-call branch records it from this reading of the clock.
    first_step = getattr(
        compiled_step,
        "_tpudl_compile_pending" if K == 1
        else "_tpudl_window_compile_pending",
        False,
    )
    if first_step:
        compiled_step._tpudl_first_step_began = (
            obs_spans.startup_recorder().clock()
        )
    try:
        while num_steps is None or i < num_steps:
            if ft_preemption.requested():
                # Grace window is ticking: stop pulling work; the
                # emergency checkpoint is the end-of-fit save below.
                # With K > 1 this check sits between dispatch windows —
                # the documented preemption granularity.
                preempted = True
                if rec is not None:
                    rec.event("preempted", "recovery", step=i)
                obs_counters.registry().counter("ft_preemptions").inc()
                break

            window = None
            if (
                not windows_done
                and not pending
                and (num_steps is None or num_steps - i >= K)
            ):
                span = None
                if rec is not None:
                    span = rec.begin(
                        "data_wait", obs_spans.CAT_DATA_WAIT,
                        step=i, window=K,
                    )
                if use_pf_window:
                    window = it.pull_window()
                    if window is None:
                        windows_done = True
                else:
                    buf = []
                    try:
                        for _ in range(K):
                            buf.append(next(it))
                    except StopIteration:
                        pass
                    if len(buf) == K:
                        window = _stack_window(buf)
                    else:
                        pending.extend(buf)
                        windows_done = True
                # Record even a None-returning prefetcher pull: it
                # still blocked on the device queue (the ragged-tail
                # single arriving) and that time is input starvation,
                # not idle.
                if span is not None:
                    if window is not None or pending or use_pf_window:
                        h_data.observe(span.end()["dur"])
                    else:
                        span.cancel()

            if window is not None:
                # Window-granularity profiling: start before the first
                # NON-COMPILE dispatch that reaches prof_start (tracing
                # the compile dispatch would fill the trace with XLA
                # compile time and stop before any steady-state step).
                if (
                    profile_dir
                    and not profiling
                    and not prof_done
                    and i + K > prof_start
                    and not getattr(
                        compiled_step, "_tpudl_window_compile_pending",
                        False,
                    )
                ):
                    jax.profiler.start_trace(profile_dir)
                    profiling = True
                if rec is None:
                    state, metrics = window_step(state, window, rng)
                else:
                    is_compile = getattr(
                        compiled_step, "_tpudl_window_compile_pending",
                        False,
                    )
                    # ONE span covers K steps (its "window" attr is
                    # how goodput counts them).
                    span = (
                        rec.begin("compile_step", obs_spans.CAT_COMPILE,
                                  step=i, window=K)
                        if is_compile else
                        rec.begin("dispatch_window", obs_spans.CAT_STEP,
                                  step=i, window=K)
                    )
                    state, metrics = window_step(state, window, rng)
                    dur = span.end()["dur"]
                    if is_compile:
                        h_compile.observe(dur)
                    else:
                        # The per-step histogram gets K observations of
                        # the amortized time so its count stays
                        # per-step.
                        for _ in range(K):
                            h_step.observe(dur / K)
                metrics_count = K
                dispatches += 1
                heartbeat.beat(step=i + K)
                g_last_step.set(start_step + n + K)
                if profiling and prof_stop <= i + K:
                    jax.block_until_ready(metrics)
                    jax.profiler.stop_trace()
                    profiling = False
                    prof_done = True
                n += K
                i += K
                if checkpoint_manager is not None and checkpoint_every:
                    step_no = start_step + n
                    if (step_no // checkpoint_every) > (
                        (step_no - K) // checkpoint_every
                    ):
                        # Window granularity: a cadence step inside the
                        # window commits at the window's end, keyed by
                        # the state's true step counter.
                        _save_ckpt(step_no, state)
                        last_ckpt_step = step_no
                if fetcher is not None:
                    _submit(i - K + 1, metrics, K)
                elif log_every:
                    first = i - K + 1
                    host_all = None
                    for s in range(first, i + 1):
                        if s % log_every == 0:
                            if host_all is None:
                                host_all = _read_back(
                                    i, _to_host_arrays, metrics
                                )
                            _log_line(s, {
                                k: float(a[s - first])
                                for k, a in host_all.items()
                            })
                continue

            if pending:
                batch = pending.popleft()
            elif rec is None:
                try:
                    batch = next(it)
                except StopIteration:
                    break
            else:
                pulled = _obs_pull(rec, it, {"step": i})
                if pulled is None:
                    break
                batch, wait = pulled
                h_data.observe(wait)
            if (
                profile_dir
                and not profiling
                and not prof_done
                and prof_start <= i < prof_stop
                and not getattr(
                    compiled_step, "_tpudl_compile_pending", False
                )
            ):
                # >= (not ==): a fused run whose windows jumped past
                # prof_start can still open the trace on a tail single.
                jax.profiler.start_trace(profile_dir)
                profiling = True
            if rec is None:
                state, metrics = compiled_step(state, batch, rng)
            else:
                is_compile = getattr(
                    compiled_step, "_tpudl_compile_pending", False
                )
                span = (
                    rec.begin("compile_step", obs_spans.CAT_COMPILE, step=i)
                    if is_compile else
                    rec.begin("train_step", obs_spans.CAT_STEP, step=i)
                )
                state, metrics = compiled_step(state, batch, rng)
                (h_compile if is_compile else h_step).observe(
                    span.end()["dur"]
                )
            metrics_count = 1
            dispatches += 1
            heartbeat.beat(step=i + 1)
            g_last_step.set(start_step + n + 1)
            if profiling and i + 1 >= prof_stop:
                jax.block_until_ready(metrics)
                jax.profiler.stop_trace()
                profiling = False
                prof_done = True
            n += 1
            if checkpoint_manager is not None and checkpoint_every:
                step_no = start_step + n
                if step_no % checkpoint_every == 0:
                    # Safe despite the next step donating `state`'s
                    # buffers: CheckpointManager.save copies device->host
                    # before returning (see its docstring invariant).
                    _save_ckpt(step_no, state)
                    last_ckpt_step = step_no
            if fetcher is not None:
                _submit(i + 1, metrics, 1)
            elif log_every and (i + 1) % log_every == 0:
                _log_line(
                    i + 1, _read_back(i + 1, _to_host_metrics, metrics)
                )
            i += 1
    finally:
        # Orderly exit (or unwind) is "finished", not "hung": a stopped
        # heartbeat is never stale on /healthz.
        heartbeat.stop()
        if first_step:
            # Where the step was never called (no batch, a preemption),
            # a later call outside fit() is no first step of this one.
            compiled_step._tpudl_first_step_began = None
        if profiling:
            jax.profiler.stop_trace()
        if fetcher is not None:
            # Drain every in-flight dispatch so all logger callbacks
            # fire (in order) before fit returns; the blocked time is
            # the one legitimate steady-state-exempt sync point. When
            # an exception is already propagating (often the fetcher's
            # own sticky readback error, raised once by _submit), a
            # second raise here would mask it — swallow the re-raise
            # and let the original unwind.
            import sys as _sys

            propagating = _sys.exc_info()[0] is not None
            try:
                if rec is not None:
                    span = rec.begin(
                        "metric_wait", obs_spans.CAT_METRIC_WAIT,
                        flush=True,
                    )
                    _deliver(fetcher.flush())
                    h_mwait.observe(span.end()["dur"])
                else:
                    _deliver(fetcher.flush())
            except BaseException:
                if not propagating:
                    raise
            finally:
                fetcher.close()
        if rec is not None:
            rec.counters(obs_counters.registry().snapshot())
    if checkpoint_manager is not None and n:
        step_no = start_step + n
        if last_ckpt_step != step_no:
            # Doubles as the preemption EMERGENCY save: on a grace-
            # window exit this is the last committed state the
            # supervisor's restarted cohort resumes from.
            _save_ckpt(step_no, state)
        checkpoint_manager.wait_until_finished()
        if rec is not None:
            # Re-snapshot: the final save's counters/histograms landed
            # after the loop's finally-block snapshot (the report keeps
            # the LAST snapshot per process).
            rec.counters(obs_counters.registry().snapshot())
    if fetcher is not None:
        metrics = host_metrics_last
    elif metrics is not None:
        if metrics_count > 1:
            metrics = {
                k: float(np.asarray(v)[-1]) for k, v in metrics.items()
            }
        else:
            metrics = _to_host_metrics(metrics)
    elapsed = time.perf_counter() - start
    return state, metrics, {
        "steps": n, "seconds": elapsed, "preempted": preempted,
        "dispatches": dispatches, "steps_per_dispatch": K,
    }


def evaluate(
    compiled_eval_step: Callable,
    state: TrainState,
    batches: Iterable[dict],
    num_steps: Optional[int] = None,
    pad_to: Optional[int] = None,
) -> dict:
    """Drive a compiled eval step (``compile_step(..., has_rng=False)``)
    over a dataset and return example-weighted mean metrics.

    Metrics are weighted by each batch's REAL row count, so a smaller
    last batch is averaged correctly. Ragged tails are handled by
    padding, not recompilation: the first batch fixes the executable's
    batch size (or pass ``pad_to`` explicitly), and any later smaller
    batch is zero-padded to it with a ``"_valid"`` row mask
    (``pad_batch``) that the eval step weights out — so a ragged-tail
    dataset costs at most 2 executables (the maskless fast path + one
    masked variant) and keeps shard divisibility on sharded meshes.

    Padding is only safe for mask-AWARE steps (ones that weight
    ``"_valid"`` out of their reductions — make_classification_eval_step
    is; compile_step propagates the marker). A custom step without the
    marker keeps the exact legacy behavior — every batch runs at its
    true size (one executable per distinct size, shard divisibility is
    the caller's problem) — unless ``pad_to`` is passed explicitly,
    which asserts the step handles ``"_valid"``. Batches LARGER than
    the target still compile their own executable; pass ``pad_to`` >=
    the max batch size to avoid that. One host sync at the end.
    """
    if num_steps is not None and num_steps <= 0:
        raise ValueError(f"num_steps must be positive, got {num_steps}")
    may_pad = pad_to is not None or getattr(
        compiled_eval_step, "_tpudl_mask_aware", False
    )
    rec = obs_spans.active_recorder()
    totals: dict = {}
    n_examples = 0
    target = pad_to
    it = iter(batches)
    i = 0
    while num_steps is None or i < num_steps:
        if rec is None:
            try:
                batch = next(it)
            except StopIteration:
                break
        else:
            pulled = _obs_pull(rec, it, {"step": i, "phase": "eval"})
            if pulled is None:
                break
            batch = pulled[0]
        bs = next(iter(batch.values())).shape[0]
        if "_valid" in batch:
            # Caller pre-padded: the mask knows the real count.
            weight = float(np.sum(np.asarray(batch["_valid"])))
        else:
            weight = bs
        if target is None:
            target = bs
        if bs < target and may_pad:
            batch = pad_batch(batch, target)
        if rec is None:
            metrics = compiled_eval_step(state, batch)
        else:
            is_compile = getattr(
                compiled_eval_step, "_tpudl_compile_pending", False
            )
            # CAT_EVAL, not CAT_STEP: eval steps have their own duration
            # scale — mixing them into the train-step distribution would
            # skew the report's outlier and straggler statistics.
            span = rec.begin(
                "eval_step",
                obs_spans.CAT_COMPILE if is_compile else obs_spans.CAT_EVAL,
                step=i, phase="eval",
            )
            metrics = compiled_eval_step(state, batch)
            span.end()
        n_examples += weight
        for k, v in metrics.items():
            totals[k] = totals.get(k, 0.0) + v * weight
        i += 1
    if n_examples == 0:
        raise ValueError("evaluate() received no batches")
    return {k: float(v) / n_examples for k, v in totals.items()}


def finalize_zero_step_run(
    checkpoint_manager, state: TrainState, warmup_steps_run: int
) -> str:
    """Shared driver epilogue for runs where fit() saw zero batches (a
    resume landed at — or within warmup of — the step budget): fit's
    final checkpoint never fired, so any warmup-trained steps must be
    saved here or every rerun would retrain them forever. Returns the
    status line to print."""
    if checkpoint_manager is not None and warmup_steps_run:
        checkpoint_manager.save(int(state.step), state)
        checkpoint_manager.wait_until_finished()
    if warmup_steps_run:
        return (
            f"trained {warmup_steps_run} warmup step(s) only — no "
            f"steady-state throughput window to report"
        )
    return "no training steps this run (budget already met)"


def resume_latest(
    checkpoint_manager,
    state: TrainState,
    mesh: Optional[Mesh] = None,
    rules: Optional[Rules] = None,
) -> tuple:
    """Restore the latest checkpoint into `state` if one exists.

    Returns ``(state, resumed_step)`` — ``(state, 0)`` untouched when the
    directory is empty, so cold start and resume are one call site.
    Fast-forward the data past the consumed steps, or the resumed run
    re-trains on early batches (``tpudl.ft.resume_run`` does this
    automatically, restoring the checkpointed rng key and data position
    too):

        state, start_step = resume_latest(mgr, state, mesh, rules)
        fit(step, state, itertools.islice(batches, start_step, None), rng,
            num_steps=total_steps - start_step, checkpoint_manager=mgr, ...)
    """
    latest = checkpoint_manager.latest_step()
    if latest is None:
        return state, 0
    return (
        checkpoint_manager.restore(state, latest, mesh=mesh, rules=rules),
        latest,
    )
