"""A decoder whose layers are of two kinds (layers that keep the whole
context beside layers that see a sliding window, each kind with its own
KV heads and rotary base, the window layers with a learned sink a query
head in the softmax; keys wider than values) with sigmoid-routed experts
and no shared one, one chip's share of the experts and of the vocabulary
held, served through ``ServeSession.from_model`` and driven as
``decoder_serve`` drives its decoder: the same window, the same
one-thread loop, the same teacher-forced logit-margin check, against
``perfbench/reference/sink_window_moe.py``.

``Cell`` subclasses ``decoder_serve.Cell`` for the driving (``warm_up``,
``run_window``, ``release``); ``check`` is ``sparse_mla_moe_serve``'s
without the indexer's agreement, with this family's reference in place
of the other (a ``benchmark`` PR that may edit ``decoder_serve.py`` folds
the copies by handing the reference in).
"""

from __future__ import annotations

import numpy as np

from perfbench.families import decoder_serve
from perfbench.families.decoder_serve import (  # noqa: F401
    attempted_failed,
    dtype_of,
    timeline,
)
from perfbench.reference import sink_window_moe as ref


def to_flax(weights: dict, s: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads
    for this configuration: a layer with a sink has the leaf
    ``attention/sink``; the experts have no shared one."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
    }
    for i, w in enumerate(weights["layers"]):
        attention = {n: {"kernel": w[n]} for n in ref.ATTENTION_MATRICES}
        if "sink" in w:
            attention["sink"] = w["sink"]
        layer = {
            "attention": attention,
            "input_norm": {"scale": w["input_norm"]},
            "post_attention_norm": {"scale": w["post_attention_norm"]},
        }
        if ref.is_dense(s, i):
            layer.update({n: {"kernel": w[n]} for n in ref.DENSE_MATRICES})
        else:
            layer["moe"] = {
                "router": {"kernel": w["router"]},
                "router_bias": w["router_bias"],
                **{f"{n}_proj": {"kernel": w[f"experts_{n}"]}
                   for n in ("gate", "up", "down")},
            }
        model[f"layer_{i}"] = layer
    return {"model": model, "lm_head": {"kernel": outer["lm_head"]}}


def model_config(config: dict, max_seq_len: int, dtype):
    """The program's configuration for a configuration file. A program
    from before KV heads by layer kind refuses the keys, at once."""
    from tpudl.models.llama import LlamaConfig

    s = ref.settings(config)
    for both in ("heads", "head_dim", "v_head_dim"):
        if s[f"{both}_f"] != s[f"{both}_s"]:
            raise ValueError(
                f"the program has one value of {both} for both layer "
                f"kinds: got {s[f'{both}_f']} and {s[f'{both}_s']}"
            )
    if not s["norm_topk_prob"]:
        raise ValueError(
            "the program's block renormalises the chosen scores: "
            "norm_topk_prob false is not wired"
        )
    dense = s["mlp_kinds"].rstrip("e")
    if "e" in dense:
        raise ValueError(
            "the program keeps its dense layers in front "
            f"(first_k_dense): got moe_layer_freq {config['moe_layer_freq']}"
        )
    return LlamaConfig(
        vocab_size=s["vocab_size"],
        hidden_size=s["hidden_size"],
        num_layers=s["num_hidden_layers"],
        num_heads=s["heads_f"],
        num_kv_heads=s["kv_heads_f"],
        sliding_num_kv_heads=s["kv_heads_s"],
        intermediate_size=s["intermediate_size"],
        max_seq_len=max_seq_len,
        rms_norm_eps=s["rms_norm_eps"],
        dtype=dtype,
        head_size=s["head_dim_f"],
        value_head_size=s["v_head_dim_f"],
        attention_value_scale=float(s["attention_value_scale"]),
        layer_types=tuple(
            "sliding_attention" if k == "s" else "full_attention"
            for k in s["layer_kinds"]
        ),
        sliding_window=s["sliding_window"],
        rope_theta=float(s["theta_f"]),
        sliding_rope_theta=float(s["theta_s"]),
        partial_rotary_factor=float(s["partial_rotary_factor"]),
        sliding_partial_rotary_factor=float(s["partial_rotary_factor"]),
        full_attention_sink=bool(s["sink_f"]),
        sliding_attention_sink=bool(s["sink_s"]),
        num_experts=s["router_experts"],
        experts_per_token=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        num_shared_experts=0,
        routed_scaling_factor=float(s["routed_scaling_factor"]),
        first_k_dense=len(dense),
        experts_held=(s["first_expert"], s["n_routed_experts"]),
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
        # Fixed shapes, so that one program serves every run: as wide
        # as the mix's longest request.
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
