"""A decoder of shortcut-connected double layers (two latent attentions
and two dense FFNs around one routed-expert branch with identity
experts), held as one chip's share of a deployment, served through
``ServeSession.from_model`` and driven as ``decoder_serve`` drives its
decoder: the same window, the same one-thread loop, the same
teacher-forced logit-margin check, against
``perfbench/reference/shortcut_moe.py``.

``Cell`` subclasses ``decoder_serve.Cell`` for the driving (``warm_up``,
``run_window``, ``release``); ``check`` is ``mla_moe_serve.Cell.check``
copied, with this family's reference in place of the other (a
``benchmark`` PR that may edit ``decoder_serve.py`` folds the three by
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
from perfbench.reference import shortcut_moe as ref


def to_flax(weights: dict, s: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads
    for ``block="shortcut"``."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
    }
    for n, w in enumerate(weights["layers"]):
        layer = {"moe": {
            "router": {"kernel": w["router"]},
            "router_bias": w["router_bias"],
            **{f"{p}_proj": {"kernel": w[f"experts_{p}"]}
               for p in ("gate", "up", "down")},
        }}
        for i in (0, 1):
            layer[f"attention_{i}"] = {
                **{p: {"kernel": w[f"{p}_{i}"]}
                   for p in ("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj")},
                "kv_b_proj": w[f"kv_b_proj_{i}"],
                "q_norm": {"scale": w[f"q_norm_{i}"]},
                "kv_norm": {"scale": w[f"kv_norm_{i}"]},
            }
            layer[f"input_norm_{i}"] = {"scale": w[f"input_norm_{i}"]}
            layer[f"post_attention_norm_{i}"] = {
                "scale": w[f"post_attention_norm_{i}"]
            }
            layer[f"mlp_{i}"] = {
                p: {"kernel": w[f"{p}_{i}"]}
                for p in ("gate_proj", "up_proj", "down_proj")
            }
        model[f"layer_{n}"] = layer
    return {"model": model, "lm_head": {"kernel": outer["lm_head"]}}


def model_config(config: dict, max_seq_len: int, dtype):
    """The program's configuration for a configuration file. A program
    from before the shortcut block refuses the keys, at once."""
    from tpudl.models.llama import LlamaConfig

    s = ref.settings(config)
    return LlamaConfig(
        vocab_size=s["vocab_size"],
        hidden_size=s["hidden_size"],
        num_layers=s["num_layers"],
        num_heads=s["num_attention_heads"],
        num_kv_heads=s["num_attention_heads"],
        intermediate_size=s["ffn_hidden_size"],
        max_seq_len=max_seq_len,
        rope_theta=float(s["rope_theta"]),
        rms_norm_eps=s["rms_norm_eps"],
        dtype=dtype,
        attention="mla",
        kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"],
        v_head_dim=s["v_head_dim"],
        block="shortcut",
        q_lora_rank=s["q_lora_rank"],
        mla_scale_q=s["scale_q"],
        mla_scale_kv=s["scale_kv"],
        num_experts=s["routed_experts"],
        zero_experts=s["router_experts"] - s["routed_experts"],
        experts_per_token=s["moe_topk"],
        moe_intermediate_size=s["expert_ffn_hidden_size"],
        routed_scaling_factor=float(s["routed_scaling_factor"]),
        router_scoring="softmax",
        router_renormalize=False,
        experts_held=(s["first_expert"], s["experts_held"]),
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
        s = ref.settings(config)

        def make(key):
            tree = to_flax(ref.all_weights(key, s, self.dtype), s)
            if sess.get("weight_dtype"):
                # Quantized where the weights are made, so that the two
                # trees never lie side by side; ``from_model`` passes an
                # already quantized tree through.
                from tpudl.quant import default_quant_rules, quantize_tree

                tree = quantize_tree(tree, default_quant_rules(
                    model.cfg, sess["weight_dtype"]))
            return tree

        params = jax.jit(make)(self.key)
        self.session = ServeSession.from_model(
            model, params, self.prompt_window, **sess
        )
        del params
        self._rid = 0

    def check(self, record: dict) -> dict:
        """``decoder_serve.Cell.check`` against this family's reference:
        every request counted finished with the token count it asked
        for, and a sample of finished requests, drawn from the seed and
        holding the longest, is teacher-forced through the reference;
        the widest and the mean gap by which a served token's logit
        lies below the reference's best are held to the
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
