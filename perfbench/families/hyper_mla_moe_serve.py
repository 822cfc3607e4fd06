"""A decoder whose residual is a stream of several vectors a token mixed
by manifold-constrained hyper-connections, with latent attention
(low-rank query) and sigmoid-routed experts beside a shared one, every
routed expert held, served through ``ServeSession.from_model`` and
driven as ``decoder_serve`` drives its decoder: the same window, the
same one-thread loop, the same teacher-forced logit-margin check,
against ``perfbench/reference/hyper_mla_moe.py``.

``Cell`` subclasses ``decoder_serve.Cell`` for the driving (``warm_up``,
``run_window``, ``release``); ``check`` is ``mla_moe_serve.Cell.check``
copied, with this family's reference in place of the other (a
``benchmark`` PR that may edit ``decoder_serve.py`` folds the four by
handing the reference in) and ONE comparison more,
``second_choice_share``: the share of the compared tokens that are not
the reference's best. With every routed expert held and four of 64
chosen a token, a choice that flips on bfloat16 rounding moves a token's
logits by a quarter of a layer's routed output, and how often that
happens is a property of the seed's weights: the sound program's MEAN
gap reads 0.03-0.11 from seed to seed and its int8 control's 0.07-0.25
(PERF.md section 2), so the mean cannot tell the two apart here, as the
widest gap cannot in any family. How OFTEN the reference disagrees can
(0.10-0.19 against 0.25-0.40).
"""

from __future__ import annotations

import numpy as np

from perfbench.families import decoder_serve
from perfbench.families.decoder_serve import (  # noqa: F401
    attempted_failed,
    dtype_of,
    timeline,
)
from perfbench.reference import hyper_mla_moe as ref


def to_flax(weights: dict, s: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads
    for ``hyper_streams > 0`` (``HyperBlock``: the dense layers' SwiGLU
    under ``mlp``, a sublayer's maps under ``hyper_<sublayer>``)."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
    }
    for i, w in enumerate(weights["layers"]):
        layer = {
            "attention": {
                **{p: {"kernel": w[p]}
                   for p in ("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj")},
                "kv_b_proj": w["kv_b_proj"],
                "q_norm": {"scale": w["q_norm"]},
                "kv_norm": {"scale": w["kv_norm"]},
            },
            "input_norm": {"scale": w["input_norm"]},
            "post_attention_norm": {"scale": w["post_attention_norm"]},
            **{f"hyper_{name}": dict(w[f"hyper_{name}"])
               for name in ref.SUBLAYERS},
        }
        if ref.is_dense(s, i):
            layer["mlp"] = {
                p: {"kernel": w[p]}
                for p in ("gate_proj", "up_proj", "down_proj")
            }
        else:
            layer["moe"] = {
                "router": {"kernel": w["router"]},
                "router_bias": w["router_bias"],
                **{f"{p}_proj": {"kernel": w[f"experts_{p}"]}
                   for p in ("gate", "up", "down")},
                **{f"shared_{p}_proj": {"kernel": w[f"shared_{p}"]}
                   for p in ("gate", "up", "down")},
            }
        model[f"layer_{i}"] = layer
    return {"model": model, "lm_head": {"kernel": outer["lm_head"]}}


def model_config(config: dict, max_seq_len: int, dtype):
    """The program's configuration for a configuration file. A program
    from before the stream refuses the keys, at once."""
    from tpudl.models.llama import LlamaConfig, RopeScaling

    s = ref.settings(config)
    if -s["hc_clamp_min"] != s["hc_clamp_max"]:
        raise ValueError(
            "the program clamps the maps' logits to one symmetric bound: "
            f"got {s['hc_clamp_min']} and {s['hc_clamp_max']}"
        )
    return LlamaConfig(
        vocab_size=s["vocab_size"],
        hidden_size=s["hidden_size"],
        num_layers=s["num_hidden_layers"],
        num_heads=s["num_attention_heads"],
        num_kv_heads=s["num_attention_heads"],
        intermediate_size=s["intermediate_size"],
        max_seq_len=max_seq_len,
        rope_theta=float(s["rope_theta"]),
        rms_norm_eps=s["rms_norm_eps"],
        dtype=dtype,
        attention="mla",
        kv_lora_rank=s["kv_lora_rank"],
        qk_nope_head_dim=s["qk_nope_head_dim"],
        qk_rope_head_dim=s["qk_rope_head_dim"],
        v_head_dim=s["v_head_dim"],
        q_lora_rank=s["q_lora_rank"],
        rope_scaling=RopeScaling(
            factor=float(s["yarn_factor"]),
            original_max_position=int(s["yarn_original"]),
            beta_fast=float(s["yarn_beta_fast"]),
            beta_slow=float(s["yarn_beta_slow"]),
            mscale=float(s["yarn_mscale"]),
            mscale_all_dim=float(s["yarn_mscale_all_dim"]),
        ),
        num_experts=s["n_routed_experts"],
        experts_per_token=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        num_shared_experts=s["n_shared_experts"],
        routed_scaling_factor=float(s["routed_scaling_factor"]),
        first_k_dense=s["first_k_dense_replace"],
        hyper_streams=s["hc_mult"],
        hyper_sinkhorn_iters=s["hc_sinkhorn_iters"],
        hyper_eps=float(s["hc_eps"]),
        hyper_clamp=float(s["hc_clamp_max"]),
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
        lies below the reference's best, and the share of the tokens
        for which there is a gap at all, are held to the
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
                {"name": "second_choice_share",
                 "value": float((gaps > 0).mean()),
                 "limit": float(limits["second_choice_share_limit"])},
            ]
            info.update(
                compared_tokens=int(gaps.size),
                tokens_the_reference_ranks_second=float((gaps > 0).mean()),
                margin_p99=float(np.percentile(gaps, 99)),
            )
        return {"comparisons": comparisons, **info}


def build(config: dict, device: dict, seed: int, variant: str = "program"):
    return Cell(config, device, seed, variant)
