"""Mixed-precision training policies: one declarative contract for
compute / param / reduce dtypes, optimizer-moment storage, fp8 matmul
routing, and dynamic loss scaling.

The training-side mirror of the PR-9 serving quantizer, built on the
same rules engine (tpudl.rules): a ``PrecisionPolicy`` answers, per
parameter leaf by regex-over-path, "what dtype does this leaf compute
in?" and "what dtype do its optimizer moments store in?" — while the
master weights stay f32 in the TrainState and every loss / gradient
reduction stays f32. The policy is applied inside the compiled train
step (``make_classification_train_step(precision=...)`` +
``compile_step(precision=...)``), so the cast work fuses into the step
and the policy state (loss scale, fp8 amax rings) is carried as traced
``TrainState.precision`` leaves — checkpoints resume
schedule-identically (loss-scale schedule and amax windows included,
tests/test_precision.py pins it) and nothing recompiles when scales
move.

Presets (``policy(name)``):

- ``"f32"``    — the identity policy (everything exactly as without
  one; useful as the control arm of a parity sweep).
- ``"bf16"``   — kernels/embeddings cast to bf16 for the forward and
  backward (f32 master weights, f32 grads out of the cast's
  transpose); norm scales and biases stay f32; loss and logits reduce
  in f32. No loss scaling by default — bf16 keeps f32's exponent
  range. ``policy("bf16", bf16_moments=True)`` additionally stores
  AdamW's first moment in bf16 (the OptimConfig.mu_dtype memory win,
  now rule-selected).
- ``"fp8"``    — bf16 compute as above, PLUS the rule-class projection
  matmuls run through ``tpudl.ops.fp8_dot`` (e4m3 forward / e5m2
  gradient, delayed scaling — requires a model built with
  ``fp8_train=True`` so those sites are ``Fp8Dense``), with dynamic
  loss scaling on: the loss is multiplied by a running power-of-two
  scale before the backward, gradients are unscaled after, a nonfinite
  gradient SKIPS the optimizer update (params / opt state / step / fp8
  windows untouched) and backs the scale off, and ``growth_interval``
  clean steps grow it back. Skip-step semantics ride the state, so a
  mid-run restore resumes the exact schedule.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax

from tpudl import rules as rules_engine
from tpudl.rules import Rules

#: Default cast rules: matmul weights and embedding tables compute in
#: the policy dtype; everything else (norm scales, biases, scalars —
#: the precision-load-bearing leaves, same classes as the quantizer's
#: keep classes) stays f32. The catch-all keeps the uncovered->raise
#: engine contract satisfied explicitly.
DEFAULT_CAST_RULES: Rules = (
    (r"(kernel|embedding)$", "compute"),
    (r".*", None),
)

#: Rule-selected bf16 first moments (half that state's bytes):
#: every AdamW mu leaf stores bf16; the second moment
#: always stays f32 for range (the OptimConfig.mu_dtype contract).
BF16_MOMENT_RULES: Rules = ((r".*", "bfloat16"),)


def default_loss_scale_config() -> "LossScaleConfig":
    from tpudl.analysis.registry import env_float, env_int

    return LossScaleConfig(
        init=env_float("TPUDL_LOSS_SCALE_INIT", 2.0**15),
        growth_interval=env_int(
            "TPUDL_LOSS_SCALE_GROWTH_INTERVAL", 2000, min_value=1
        ),
    )


@dataclasses.dataclass(frozen=True)
class LossScaleConfig:
    """Dynamic loss scaling (Micikevicius et al., mixed-precision
    training): multiply the loss by ``scale`` before the backward so
    small gradients survive the low-precision format, divide the
    gradients by it after, and adapt: a nonfinite gradient skips the
    step and backs off, ``growth_interval`` consecutive finite steps
    double it (capped)."""

    init: float = 2.0**15
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    max_scale: float = 2.0**24
    min_scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Declarative mixed-precision contract (module docstring). All
    rule fields follow the tpudl.rules shape: regex over the leaf's
    param path, first match wins."""

    name: str
    #: Forward/backward compute dtype for cast_rules-matched leaves.
    compute_dtype: Any = jnp.float32
    #: Master-weight dtype in the TrainState (never changed by the
    #: policy — documented, and asserted by tests).
    param_dtype: Any = jnp.float32
    #: Loss and logits reduce in this dtype regardless of compute.
    reduce_dtype: Any = jnp.float32
    #: regex -> "compute" | None: which param leaves cast to
    #: compute_dtype inside the step's loss function.
    cast_rules: Rules = DEFAULT_CAST_RULES
    #: regex -> dtype-name | None: AdamW first-moment storage per leaf
    #: (uncovered leaves keep the optimizer's own dtype).
    moment_rules: Rules = ()
    #: Route the model's Fp8Dense sites (cfg.fp8_train seam) through
    #: the delayed-scaling fp8 matmul and carry their amax rings.
    use_fp8: bool = False
    #: fp8 amax-history ring length (TPUDL_FP8_AMAX_WINDOW's default).
    amax_window: int = 16
    #: Dynamic loss scaling; None = off (grads applied every step).
    loss_scale: Optional[LossScaleConfig] = None

    # -- model configuration -----------------------------------------------
    def configure_model(self, cfg: Any) -> Any:
        """Thread the policy's compute dtype into a model config's
        ``dtype`` seam — THE mechanism that makes matmuls/activations
        actually run at ``compute_dtype`` on the flax model families:
        a flax module promotes its inputs AND params to its own
        ``dtype`` at apply time, so a cast applied outside the module
        cannot lower (or keep) the in-module compute precision — only
        the seam can. The policy tests build their models through this
        (and tests/test_precision.py pins the traced dot dtypes via
        jaxpr, so a policy whose compute dtype silently stops landing
        fails loudly)."""
        if not hasattr(cfg, "dtype"):
            raise ValueError(
                f"{type(cfg).__name__} has no dtype seam to carry the "
                f"policy's compute dtype — models without one run at "
                f"their promoted dtype regardless of the policy"
            )
        return dataclasses.replace(cfg, dtype=self.compute_dtype)

    # -- param casting -----------------------------------------------------
    def cast_params(self, params: Any) -> Any:
        """Rule-driven forward-cast of the param tree: matched
        ``"compute"`` leaves cast to ``compute_dtype`` (float leaves
        only), everything else passes through. The cast happens INSIDE
        the differentiated loss function, so its transpose returns f32
        gradients against the f32 masters — this is the master-weight
        boundary. It does NOT set the compute precision by itself: a
        dtype-seamed module re-promotes params to its own ``dtype``
        (making this cast a value-level no-op there); pair it with
        ``configure_model`` to actually move the matmul dtype."""
        ann = rules_engine.annotate(
            self.cast_rules, params, what="precision cast rule"
        )

        def one(leaf, a):
            if a == "compute" and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating
            ):
                return leaf.astype(self.compute_dtype)
            return leaf

        return jax.tree.map(one, params, ann)


def policy(name: str, bf16_moments: bool = False) -> PrecisionPolicy:
    """Preset factory — see the module docstring for what each name
    means. ``bf16_moments`` adds the rule-selected bf16 first-moment
    storage to any preset."""
    moment_rules = BF16_MOMENT_RULES if bf16_moments else ()
    if name == "f32":
        return PrecisionPolicy(
            name="f32", cast_rules=((r".*", None),),
            moment_rules=moment_rules,
        )
    if name == "bf16":
        return PrecisionPolicy(
            name="bf16", compute_dtype=jnp.bfloat16,
            moment_rules=moment_rules,
        )
    if name == "fp8":
        from tpudl.ops.fp8_dot import default_amax_window

        return PrecisionPolicy(
            name="fp8", compute_dtype=jnp.bfloat16,
            moment_rules=moment_rules, use_fp8=True,
            amax_window=default_amax_window(),
            loss_scale=default_loss_scale_config(),
        )
    raise ValueError(
        f"unknown precision policy {name!r}; expected f32 | bf16 | fp8"
    )


def resolve_policy(
    precision: "PrecisionPolicy | str | None",
) -> Optional[PrecisionPolicy]:
    """None / preset name / policy -> policy (None passes through: the
    no-policy legacy path stays bit-identical)."""
    if precision is None or isinstance(precision, PrecisionPolicy):
        return precision
    return policy(precision)


def policy_from_env() -> Optional[PrecisionPolicy]:
    """TPUDL_TRAIN_PRECISION -> policy (unset = None = legacy path)."""
    from tpudl.analysis.registry import env_str

    name = env_str("TPUDL_TRAIN_PRECISION")
    return None if not name else resolve_policy(name)


# ---------------------------------------------------------------------------
# Precision state: the traced leaves the policy threads through
# TrainState.precision (and therefore through checkpoints).
# ---------------------------------------------------------------------------


def init_precision_state(
    pol: Optional[PrecisionPolicy], fp8_vars: Any = None
) -> Optional[dict]:
    """The TrainState.precision pytree for a policy: loss-scale
    scalars when scaling is on, the model's ``"fp8"`` variable
    collection (amax rings per site) when fp8 is on, None when the
    policy carries no state (f32 / plain bf16 — checkpoints unchanged).
    """
    if pol is None:
        return None
    state: dict = {}
    if pol.loss_scale is not None:
        state["loss_scale"] = {
            "scale": jnp.asarray(pol.loss_scale.init, jnp.float32),
            "growth_count": jnp.asarray(0, jnp.int32),
            "skipped": jnp.asarray(0, jnp.int32),
        }
    if pol.use_fp8:
        if fp8_vars is None:
            raise ValueError(
                "precision policy 'fp8' needs a model with fp8 matmul "
                "sites — build it with cfg.fp8_train=True so the "
                "projection Denses are Fp8Dense (its init creates the "
                "'fp8' amax-state collection)"
            )
        state["fp8"] = fp8_vars
    return state or None


def validate_state(pol: Optional[PrecisionPolicy], state: Any) -> None:
    """compile_step's consistency gate: a policy that carries state
    must find it on the TrainState (a state built WITHOUT
    ``create_train_state(precision=...)`` would silently train
    unscaled / with frozen amax windows otherwise)."""
    if pol is None:
        return
    prec = getattr(state, "precision", None)
    if pol.loss_scale is not None and (
        prec is None or "loss_scale" not in prec
    ):
        raise ValueError(
            f"policy {pol.name!r} uses dynamic loss scaling but the "
            f"TrainState carries no loss-scale state — build it with "
            f"create_train_state(..., precision=policy)"
        )
    if pol.use_fp8 and (prec is None or "fp8" not in prec):
        raise ValueError(
            f"policy {pol.name!r} routes matmuls through fp8 but the "
            f"TrainState carries no amax state — build the model with "
            f"cfg.fp8_train=True and the state with "
            f"create_train_state(..., precision=policy)"
        )


def all_finite(tree: Any) -> jax.Array:
    """Scalar bool: every float leaf of ``tree`` is finite (the
    skip-step predicate)."""
    leaves = [
        jnp.all(jnp.isfinite(leaf))
        for leaf in jax.tree.leaves(tree)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    ]
    if not leaves:
        return jnp.asarray(True)
    return jnp.stack(leaves).all()


def update_loss_scale(ls: dict, cfg: LossScaleConfig, ok: jax.Array) -> dict:
    """One dynamic-loss-scale transition: finite step counts toward
    growth (doubling after ``growth_interval`` in a row, capped);
    nonfinite step backs off (floored) and resets the streak."""
    grown = ok & (ls["growth_count"] + 1 >= cfg.growth_interval)
    scale = jnp.where(
        ok,
        jnp.where(
            grown,
            jnp.minimum(ls["scale"] * cfg.growth_factor, cfg.max_scale),
            ls["scale"],
        ),
        jnp.maximum(ls["scale"] * cfg.backoff_factor, cfg.min_scale),
    )
    growth = jnp.where(ok & ~grown, ls["growth_count"] + 1, 0).astype(
        jnp.int32
    )
    skipped = ls["skipped"] + jnp.where(ok, 0, 1).astype(jnp.int32)
    return {"scale": scale, "growth_count": growth, "skipped": skipped}


def select_tree(ok: jax.Array, new: Any, old: Any) -> Any:
    """Per-leaf ``where(ok, new, old)`` — the skip-step select (both
    branches are computed; the select is how the skip stays one
    compiled program instead of a recompile-prone cond)."""
    return jax.tree.map(lambda a, b: jnp.where(ok, a, b), new, old)


def publish_numerics_telemetry(precision_state: Any) -> None:
    """Push the precision stack's live numerics into the obs registry
    (the stack trained blind before this — a collapsing loss scale or
    a drifting amax window was only visible post-mortem):

    - ``train_loss_scale`` gauge — the current dynamic scale;
    - ``train_grad_skipped_total`` counter — nonfinite-gradient skip
      steps (the state's ``skipped`` is cumulative, so the counter is
      advanced by delta and survives repeated publishes);
    - ``train_fp8_amax_drift`` histogram — per-site ring spread
      ``(max - min) / max`` over each amax window (x/w/g): near 0 =
      stationary scales, near 1 = the site's magnitude moved an order
      within the window and delayed scaling is chasing it.

    Called from fit() at log cadence with the CURRENT TrainState
    .precision (device fetches are per-publish, never per-step); a
    None/empty state is a no-op, so f32/bf16-without-scaling runs pay
    nothing."""
    if not precision_state:
        return
    import numpy as np

    from tpudl.obs import counters as obs_counters

    reg = obs_counters.registry()
    ls = precision_state.get("loss_scale")
    if ls is not None:
        reg.gauge("train_loss_scale").set(
            float(jax.device_get(ls["scale"]))
        )
        skipped = int(jax.device_get(ls["skipped"]))
        ctr = reg.counter("train_grad_skipped_total")
        delta = skipped - int(ctr.value)
        if delta > 0:
            ctr.inc(delta)
    fp8 = precision_state.get("fp8")
    if fp8 is not None:
        hist = reg.histogram("train_fp8_amax_drift")

        def _walk(node: Any) -> None:
            if not hasattr(node, "items"):
                return
            for key, val in node.items():
                if hasattr(val, "items"):
                    _walk(val)
                elif str(key).endswith("_hist"):
                    ring = np.asarray(
                        jax.device_get(val), np.float32
                    )
                    hi = float(ring.max()) if ring.size else 0.0
                    if hi > 0.0:
                        hist.observe((hi - float(ring.min())) / hi)

        _walk(fp8)


# ---------------------------------------------------------------------------
# Optimizer-moment precision (the rule-selected mu_dtype).
# ---------------------------------------------------------------------------


def _map_mu(opt_state: Any, fn) -> Any:
    """Apply ``fn`` to every ``mu`` field found in the (possibly
    nested/chained) optax state. Second moments (``nu``) are left
    alone by design — they store squared magnitudes and need f32
    range (the OptimConfig.mu_dtype precedent)."""
    if isinstance(opt_state, tuple) and hasattr(opt_state, "_fields"):
        replacements = {}
        for field in opt_state._fields:
            value = getattr(opt_state, field)
            replacements[field] = (
                fn(value) if field == "mu" else _map_mu(value, fn)
            )
        return opt_state._replace(**replacements)
    if isinstance(opt_state, (tuple, list)):
        return type(opt_state)(_map_mu(entry, fn) for entry in opt_state)
    return opt_state


def apply_moment_rules(
    tx: optax.GradientTransformation, pol: Optional[PrecisionPolicy]
) -> optax.GradientTransformation:
    """Wrap an optimizer so its first-moment leaves store in the
    policy's rule-selected dtypes (mu trees mirror the param tree, so
    the same ``kernel$``-style regexes address them). Numerically
    identical to optax's global ``mu_dtype``: moments promote to f32
    inside the update and re-cast on the way back to storage
    (tests/test_precision.py holds the two bit for bit)."""
    if pol is None or not pol.moment_rules:
        return tx

    def cast_mu(mu_tree):
        ann = rules_engine.annotate(
            pol.moment_rules, mu_tree, default=None,
            what="moment rule",
        )
        return jax.tree.map(
            lambda leaf, d: leaf.astype(jnp.dtype(d)) if d else leaf,
            mu_tree,
            ann,
        )

    def init(params):
        return _map_mu(tx.init(params), cast_mu)

    def update(updates, state, params=None):
        updates, new_state = tx.update(updates, state, params)
        return updates, _map_mu(new_state, cast_mu)

    return optax.GradientTransformation(init, update)
