"""BERT with a classification head, trained through tpudl's own path:
``compile_step`` over a mesh, ``prefetch_to_device`` and the ``fit`` loop.

Set-up builds ONE compiled step with its state, drives it from the seed
through its first three steps (which the plain reference follows), and
hands that same object to the measured window.
"""

from __future__ import annotations

import gc
import itertools
import math
import time

import numpy as np

from perfbench import traffic
from perfbench.reference import bert as ref

CHECK_STEPS = 3
ZERO_GRADIENT = 1e-4


def to_flax(w: dict, num_layers: int) -> dict:
    """Canonical leaves in the tree ``BertForSequenceClassification``
    reads (works on any values: arrays, or the names themselves)."""
    def dense(p):
        return {"kernel": w[f"{p}/kernel"], "bias": w[f"{p}/bias"]}

    def norm(p):
        return {"scale": w[f"{p}/scale"], "bias": w[f"{p}/bias"]}

    encoder = {}
    for i in range(num_layers):
        p = f"layer_{i}"
        encoder[p] = {
            "attention": {n: dense(f"{p}/{n}")
                          for n in ("query", "key", "value", "out")},
            "attention_norm": norm(f"{p}/attention_norm"),
            "intermediate": dense(f"{p}/intermediate"),
            "output": dense(f"{p}/output"),
            "output_norm": norm(f"{p}/output_norm"),
        }
    return {
        "bert": {
            "embeddings": {
                "word_embeddings": {"embedding": w["embeddings/word"]},
                "position_embeddings": {"embedding": w["embeddings/position"]},
                "token_type_embeddings": {
                    "embedding": w["embeddings/token_type"]},
                "layer_norm": norm("embeddings/norm"),
            },
            "encoder": encoder,
            "pooler": dense("pooler"),
        },
        "classifier": dense("classifier"),
    }


def worst_leaf_gap(got: dict, want: dict, leaves=None) -> tuple:
    """(gap, leaf): the widest gap between the program's norm of a leaf
    and the reference's, against the reference's norm of that leaf or of
    the median leaf, whichever is larger (some gradients are all but 0).
    Over ``leaves``, or all of them."""
    median = float(np.median(list(want.values())))
    return max(
        (abs(got[k] - want[k]) / max(want[k], median), k)
        for k in (want if leaves is None else leaves)
    )


def leaves_with_a_gradient(grad_norm: dict) -> list:
    """The leaves whose first gradient, by the reference, is not all but
    zero (a key's bias shifts every score of a query alike, so softmax
    leaves it no gradient): under ``ZERO_GRADIENT`` of the median leaf's
    norm there is only rounding, and Adam turns rounding into a step."""
    floor = ZERO_GRADIENT * float(np.median(list(grad_norm.values())))
    return [k for k, v in grad_norm.items() if v > floor]


def loss_fall(losses: list) -> float:
    """The mean of the window's last losses over the mean of its first
    (a sixteenth of the steps each, eight at least): under 1 where the
    loss fell."""
    k = max(8, len(losses) // 16)
    if len(losses) < 2 * k:
        return float("nan")
    return float(np.mean(losses[-k:]) / np.mean(losses[:k]))


class Cell:
    kind = "train"

    def __init__(self, config: dict, device: dict, seed: int,
                 variant: str = "program"):
        self.config = config
        self.device = device
        self.seed = seed
        self.variant = variant
        self.key = ref.seed_key(seed)
        self.reference_seconds = 0.0

    def warm_up(self, mix: dict, seconds: float) -> None:
        import jax
        import jax.numpy as jnp

        from tpudl.config import OptimConfig
        from tpudl.data.prefetch import prefetch_to_device
        from tpudl.models.bert import BertConfig, BertForSequenceClassification
        from tpudl.parallel.sharding import strategy_rules
        from tpudl.runtime import MeshSpec, make_mesh
        from tpudl.train import (
            compile_step,
            create_train_state,
            fit,
            make_classification_train_step,
        )
        from tpudl.train.optim import make_optimizer

        cfg, job = self.config, self.config["job"]
        optim = dict(job["optimizer"])
        self.batch = int(mix["batch"])
        gen = traffic.train_batches(
            mix, self.seed, cfg["vocab_size"], cfg["num_labels"]
        )
        first = [next(gen) for _ in range(CHECK_STEPS)]

        # The reference goes first, while the device holds nothing else.
        from tpudl.analysis.dispatch import compile_seconds

        # The key the program's steps are called with; the reference
        # draws the same dropout masks from it.
        self.rng = jax.random.key(1)
        t, c = time.monotonic(), compile_seconds()
        self.want = ref.follow(
            self.key, cfg, optim, first, int(job["reference_block_rows"]),
            self.rng,
        )
        jax.clear_caches()
        gc.collect()
        self.reference_seconds = time.monotonic() - t
        self.reference_compile_seconds = compile_seconds() - c

        control = cfg.get("control", {}) if self.variant == "control" else {}
        net = BertForSequenceClassification(BertConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            layer_norm_eps=cfg["layer_norm_eps"],
            hidden_dropout=cfg["hidden_dropout_prob"],
            attention_dropout=cfg["attention_probs_dropout_prob"],
            num_labels=cfg["num_labels"],
            dtype={"bfloat16": jnp.bfloat16,
                   "float32": jnp.float32}[cfg["torch_dtype"]],
            **control.get("model", {}),
        ))
        layers = cfg["num_hidden_layers"]
        self._weights = jax.jit(
            lambda key: to_flax(ref.make_weights(key, cfg), layers)
        )
        names = jax.tree.leaves(to_flax(
            {k: k for k in ref.leaf_shapes(cfg)}, layers
        ))
        tx = make_optimizer(OptimConfig(**optim))
        precision = control.get("precision")
        # The program's own initialiser, as a user's job calls it; the
        # benchmark then lays its weights, which the reference also has,
        # over the initial values.
        state = create_train_state(
            jax.random.key(0), net,
            jnp.zeros((1, int(mix["seq_len"])), jnp.int32), tx,
            precision=precision,
        ).replace(params=self._weights(self.key))
        chips = self.device["count"]
        mesh = make_mesh(MeshSpec(dp=chips), jax.devices()[:chips])
        self.step = compile_step(
            make_classification_train_step(
                input_keys=("input_ids", "attention_mask"),
                label_key="label",
                accum_steps=int(job.get("accum_steps", 1)),
                precision=precision,
            ),
            mesh, state, strategy_rules(job.get("strategy", "dp")),
            precision=precision,
        )
        self.feed = prefetch_to_device(itertools.chain(first, gen), mesh=mesh)

        b2 = optim["b2"]

        # The first gradient as the optimizer got it, from its second
        # moment after one step: nu = (1 - b2) g^2.
        @jax.jit
        def grad_norms(nu):
            return [jnp.sqrt(jnp.sum(v.astype(jnp.float32)) / (1 - b2))
                    for v in jax.tree.leaves(nu)]

        @jax.jit
        def grad_error(nu, want):
            """By leaf, the squared error and the squared size of the
            gradient's magnitudes against the reference's, over the
            sampled elements."""
            err, size = [], []
            for v, w in zip(jax.tree.leaves(nu), want):
                got = ref.sample(jnp.sqrt(v.astype(jnp.float32) / (1 - b2)))
                err.append(jnp.sum(jnp.square(got - w)))
                size.append(jnp.sum(jnp.square(w)))
            return jnp.stack(err), jnp.stack(size)

        @jax.jit
        def delta(new, old, want):
            """By leaf, the norm of the parameters' change, and the
            squared error and squared size of its sampled elements,
            signs kept, against the reference's."""
            norm, err, size = [], [], []
            for a, b, w in zip(jax.tree.leaves(new), jax.tree.leaves(old),
                               want):
                norm.append(jnp.sqrt(jnp.sum(jnp.square(a - b))))
                err.append(jnp.sum(jnp.square(
                    ref.sample(a - b, signed=True) - w)))
                size.append(jnp.sum(jnp.square(w)))
            return norm, jnp.stack(err), jnp.stack(size)

        losses = []
        logger = lambda i, m: losses.append(float(m["loss"]))  # noqa: E731
        state, _, _ = fit(self.step, state, self.feed, self.rng,
                          num_steps=1, log_every=1, logger=logger)
        adam = [s for s in jax.tree.leaves(
            state.opt_state, is_leaf=lambda x: hasattr(x, "nu")
        ) if hasattr(s, "nu")][0]
        first_grad = dict(zip(names, map(float, grad_norms(adam.nu))))
        err, size = grad_error(
            adam.nu, [self.want["grad_sample"][k] for k in names]
        )
        err, size = np.asarray(err), np.asarray(size)
        grad_err = float(np.sqrt(err.sum() / size.sum()))
        by_leaf = np.sqrt(err[size > 0] / size[size > 0])
        grad_err_median = float(np.median(by_leaf))
        del self.want["grad_sample"]
        state, _, _ = fit(self.step, state, self.feed, self.rng,
                          num_steps=CHECK_STEPS - 1, log_every=1,
                          logger=logger)
        norm, err, size = delta(
            state.params, self._weights(self.key),
            [self.want["delta_sample"][k] for k in names],
        )
        del self.want["delta_sample"]
        err, size = np.asarray(err), np.asarray(size)
        self.state = state
        self.got = {"loss": losses, "grad_norm": first_grad,
                    "grad_error": grad_err,
                    "grad_error_median": grad_err_median,
                    "delta_norm": dict(zip(names, map(float, norm))),
                    "delta_error_median": float(np.median(
                        np.sqrt(err[size > 0] / size[size > 0])))}

    def run_window(self, mix: dict, seconds: float, tracer=None) -> dict:
        from tpudl.analysis.dispatch import RecompileWatcher
        from tpudl.train import fit

        clock = time.monotonic
        log = []
        t0 = clock()

        def timed():
            while clock() - t0 < seconds:
                yield next(self.feed)

        def logger(i, m):
            now = clock() - t0
            log.append((now, float(m["loss"])))
            if tracer is not None:
                tracer.poll(now)
                if tracer.tracing:
                    tracer.sync.mark()

        try:
            with RecompileWatcher("train window") as watch:
                self.state, _, _ = fit(
                    self.step, self.state, timed(), self.rng,
                    log_every=1, logger=logger,
                )
        finally:
            if tracer is not None:
                tracer.finish()
        return {
            "kind": "train", "seconds": seconds,
            "window_s": log[-1][0] if log else 0.0,
            "t0_monotonic": t0, "steps": log, "batch": self.batch,
            "seq_len": int(mix["seq_len"]),
            "compiles_in_window": watch.count,
        }

    def release(self) -> None:
        self.feed.close()
        self.state = self.step = self.feed = None
        gc.collect()

    def check(self, record: dict) -> dict:
        lim = self.config["correctness"]
        got, want = self.got, self.want
        comparisons = [
            {"name": f"loss_step{i + 1}_gap",
             "value": abs(got["loss"][i] - want["loss"][i]),
             "limit": lim["loss_gap_limit"]}
            for i in range(CHECK_STEPS)
        ]
        comparisons.append({
            "name": "first_grad_median_leaf_error",
            "value": got["grad_error_median"],
            "limit": lim["first_grad_median_leaf_error_limit"],
        })
        comparisons.append({
            "name": "param_change_median_leaf_error",
            "value": got["delta_error_median"],
            "limit": lim["param_change_median_leaf_error_limit"],
        })
        # Where the reference's gradient is all but zero the program's
        # is rounding, and Adam makes a full step of either: those
        # leaves' changes say nothing, and are left out of the norms.
        moved = leaves_with_a_gradient(want["grad_norm"])
        for name, key, leaves in (
            ("first_grad_norm_worst_leaf_gap", "grad_norm", None),
            ("param_change_norm_worst_leaf_gap", "delta_norm", moved),
        ):
            gap, leaf = worst_leaf_gap(got[key], want[key], leaves)
            comparisons.append({
                "name": name, "value": gap, "leaf": leaf,
                "limit": lim[name.replace("_worst_leaf", "") + "_limit"],
            })
        losses = [l for _, l in record["steps"]]
        fall = loss_fall(losses)
        if math.isfinite(fall):  # else the window held too few steps to say
            comparisons.append({
                "name": "window_loss_last_over_first", "value": fall,
                "limit": lim["window_loss_last_over_first_limit"],
            })
        comparisons.append({
            "name": "nonfinite_losses",
            "value": sum(not math.isfinite(l) for l in losses), "limit": 0,
        })
        comparisons.append({
            "name": "compiles_in_window",
            "value": record["compiles_in_window"], "limit": 0,
        })
        return {"comparisons": comparisons,
                "leaves_left_out_of_the_change": len(want["grad_norm"])
                - len(moved),
                "window_loss_last_over_first": fall,
                "first_grad_error_over_all_leaves": got["grad_error"],
                "reference_losses": want["loss"],
                "program_losses": got["loss"]}


def build(config: dict, device: dict, seed: int, variant: str = "program"):
    return Cell(config, device, seed, variant)


def timeline(record: dict) -> dict:
    """For the log: when each step of the window ended, ms from its
    start (a stall shows as one long gap), and its first and last
    losses."""
    return {
        "step_end_ms": [round(1e3 * t, 1) for t, _ in record["steps"]],
        "first_losses": [l for _, l in record["steps"][:4]],
        "last_losses": [l for _, l in record["steps"][-4:]],
    }


def attempted_failed(record: dict) -> tuple:
    """Steps the window completed; a step whose loss is not finite
    failed."""
    failed = sum(not math.isfinite(l) for _, l in record["steps"])
    return len(record["steps"]), failed
