"""A decoder whose ONE stack of sandwich-normed layers runs several
times over the same weights a token (a looped language model: the final
norm and an exit gate after every pass, a k/v cache a (pass, layer)),
served through ``ServeSession.from_model`` and driven as
``decoder_serve`` drives its decoder: the same window, the same
one-thread loop, the same teacher-forced logit-margin check, against
``perfbench/reference/loop_decoder.py``.

``Cell`` subclasses ``decoder_serve.Cell`` for the driving (``warm_up``,
``run_window``, ``release``); ``check`` is ``decoder_serve.Cell.check``
with this family's reference in place of the other (the fifth copy: a
``benchmark`` PR that may edit ``decoder_serve.py`` folds them by
handing the reference in).
"""

from __future__ import annotations

import numpy as np

from perfbench.families import decoder_serve
from perfbench.families.decoder_serve import (  # noqa: F401
    attempted_failed,
    dtype_of,
    timeline,
)
from perfbench.reference import loop_decoder as ref


def to_flax(weights: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads
    for ``sandwich_norm`` and ``loop_passes > 1`` (``SandwichBlock``:
    four norms a layer, the dense SwiGLU under ``mlp``; the exit gate
    beside the final norm)."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
        "early_exit_gate": {"kernel": outer["exit_gate"],
                            "bias": outer["exit_gate_bias"]},
    }
    for i, w in enumerate(weights["layers"]):
        model[f"layer_{i}"] = {
            "attention": {
                n: {"kernel": w[n]}
                for n in ("q_proj", "k_proj", "v_proj", "o_proj")
            },
            **{n: {"scale": w[n]} for n in ref.LAYER_NORMS},
            "mlp": {n: {"kernel": w[n]}
                    for n in ("gate_proj", "up_proj", "down_proj")},
        }
    return {"model": model, "lm_head": {"kernel": outer["lm_head"]}}


def model_config(config: dict, max_seq_len: int, dtype):
    """The program's configuration for a configuration file. A program
    from before the loop refuses the keys, at once (a ``TypeError``)."""
    from tpudl.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_size=config["head_dim"],
        intermediate_size=config["intermediate_size"],
        max_seq_len=max_seq_len,
        rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        dtype=dtype,
        loop_passes=config["total_ut_steps"],
        loop_exit_threshold=float(config["early_exit_threshold"]),
        sandwich_norm=True,
    )


class Cell(decoder_serve.Cell):
    """The served model of one run."""

    def __init__(self, config: dict, device: dict, seed: int,
                 variant: str = "program"):
        import jax

        from tpudl.models.llama import LlamaForCausalLM
        from tpudl.serve import ServeSession

        self.config = config
        self.device = device
        self.seed = seed
        sess = dict(config["session"])
        if variant == "control":
            # The program's own lower-precision path, switched on.
            sess.update(config["control"]["session"])
        self.dtype = dtype_of(config["torch_dtype"])
        self.prompt_window = int(sess.pop("prompt_window"))
        self.slots = int(sess["num_slots"])
        model = LlamaForCausalLM(model_config(
            config, int(sess.pop("max_seq_len")), self.dtype
        ))
        self.key = ref.seed_key(seed)
        params = jax.jit(
            lambda key: to_flax(ref.all_weights(key, config, self.dtype))
        )(self.key)
        self.session = ServeSession.from_model(
            model, params, self.prompt_window, **sess
        )
        del params
        self._rid = 0

    def check(self, record: dict) -> dict:
        """``decoder_serve.Cell.check`` against this family's reference:
        every request counted finished with the token count it asked
        for, and a sample of finished requests, drawn from the seed and
        holding the longest, is teacher-forced through the reference
        (all passes); the widest and the mean gap by which a served
        token's logit lies below the reference's best are held to the
        configuration's limits."""
        import jax.numpy as jnp

        limits = self.config["correctness"]
        sample = int(limits["sample_requests"])
        rows = int(limits["reference_rows"])
        done = [r for r in record["requests"]
                if r["finish_reason"] in ("length", "eos")]
        short = [r for r in done if len(r["tokens"]) != r["max_new"]]
        rng = np.random.default_rng(self.seed)
        longest = max(
            done, key=lambda r: r["prompt_len"] + len(r["tokens"]),
            default=None,
        )
        picked = [] if longest is None else [longest]
        rest = [r for r in done if r is not longest]
        if rest:
            idx = rng.choice(len(rest), size=min(sample - 1, len(rest)),
                             replace=False)
            picked += [rest[int(i)] for i in idx]
        comparisons = [
            {"name": "wrong_token_count", "value": len(short), "limit": 0},
            {"name": "compiles_in_window",
             "value": record["compiles_in_window"], "limit": 0},
            {"name": "requests_not_compared", "value": int(not picked),
             "limit": 0},
        ]
        t_max = max(r["max_new"] for r in record["requests"])
        width = int(self.config["session"]["prompt_window"]) + t_max
        gaps = []
        for at in range(0, len(picked), rows):
            ids = np.zeros((rows, width), np.int32)
            picks = np.zeros((rows, t_max), np.int32)
            chosen = np.zeros((rows, t_max), np.int32)
            valid = np.zeros((rows, t_max), bool)
            for row, r in enumerate(picked[at:at + rows]):
                seq = list(r["prompt"]) + list(r["tokens"])[:-1]
                ids[row, : len(seq)] = seq
                k = len(r["tokens"])
                picks[row, :k] = r["prompt_len"] - 1 + np.arange(k)
                chosen[row, :k] = r["tokens"]
                valid[row, :k] = True
            margin = np.asarray(ref.margins(
                self.key, self.config, self.dtype, jnp.asarray(ids),
                jnp.asarray(picks), jnp.asarray(chosen),
            ))
            gaps.append(margin[valid])
        info = {"compared_requests": len(picked), "compared_tokens": 0}
        if picked:
            gaps = np.concatenate(gaps)
            comparisons += [
                {"name": "worst_logit_margin", "value": float(gaps.max()),
                 "limit": float(limits["worst_logit_margin_limit"])},
                {"name": "mean_logit_margin", "value": float(gaps.mean()),
                 "limit": float(limits["mean_logit_margin_limit"])},
            ]
            info.update(
                compared_tokens=int(gaps.size),
                tokens_the_reference_ranks_second=float((gaps > 0).mean()),
                margin_p99=float(np.percentile(gaps, 99)),
            )
        return {"comparisons": comparisons, **info}


def build(config: dict, device: dict, seed: int, variant: str = "program"):
    return Cell(config, device, seed, variant)
