"""Optimizer + schedule construction from OptimConfig."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from tpudl.config import OptimConfig


def make_schedule(cfg: OptimConfig) -> optax.Schedule:
    if cfg.schedule == "constant":
        sched = optax.constant_schedule(cfg.learning_rate)
    elif cfg.schedule == "linear":
        sched = optax.linear_schedule(
            cfg.learning_rate, 0.0, max(cfg.total_steps - cfg.warmup_steps, 1)
        )
    else:
        sched = optax.cosine_decay_schedule(
            cfg.learning_rate, max(cfg.total_steps - cfg.warmup_steps, 1)
        )
    if cfg.warmup_steps > 0:
        warmup = optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
        sched = optax.join_schedules([warmup, sched], [cfg.warmup_steps])
    return sched


def scoped(name: str, tx: optax.GradientTransformation):
    """``tx`` with its update under ``jax.named_scope(name)``: a
    profiler trace then says which device operations are its (HLO
    metadata only, nothing at run time)."""

    def update(updates, state, params=None):
        with jax.named_scope(name):
            return tx.update(updates, state, params)

    return optax.GradientTransformation(tx.init, update)


def make_optimizer(cfg: OptimConfig) -> optax.GradientTransformation:
    sched = make_schedule(cfg)
    if cfg.name == "sgd":
        tx = optax.chain(
            optax.add_decayed_weights(cfg.weight_decay),
            optax.sgd(sched, momentum=cfg.momentum, nesterov=True),
        )
    else:
        tx = optax.adamw(
            sched,
            b1=cfg.b1,
            b2=cfg.b2,
            weight_decay=cfg.weight_decay,
            mu_dtype=jnp.dtype(cfg.mu_dtype),
        )
    if cfg.grad_clip_norm:
        tx = optax.chain(
            scoped("grad_clip", optax.clip_by_global_norm(cfg.grad_clip_norm)),
            tx,
        )
    return tx
