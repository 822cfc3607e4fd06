"""A decoder with latent attention under learned sparse attention (an
indexer chooses ``index_topk`` cached positions a query; most layers
reuse the last indexer's choice) and sigmoid-routed experts beside a
shared one, one chip's share of the experts and of the vocabulary
held, served through ``ServeSession.from_model`` and driven as
``decoder_serve`` drives its decoder: the same window, the same
one-thread loop, the same teacher-forced logit-margin check, against
``perfbench/reference/sparse_mla_moe.py``.

``Cell`` subclasses ``decoder_serve.Cell`` for the driving; ``check``
is ``hyper_mla_moe_serve.Cell.check`` copied, with this family's
reference in place of the other (a ``benchmark`` PR that may edit
``decoder_serve.py`` folds the five by handing the reference in), and
ONE number more that is reported and not judged,
``index_choice_agreement``: the share of the positions the PROGRAM's
indexers chose for the compared tokens that the reference's chose too.
The program's choice is read from the program itself: before the
session is released, the served model runs the compared requests once
more as one fresh prefill each, with its ``intermediates`` collection
mutable (``tpudl.models.llama._sow_choice``; no serving program
carries it). Near-ties flip on bfloat16 rounding, so a sound run reads
a little under 1; an indexer that chose at random would read near
``index_topk / t``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from perfbench.families import decoder_serve
from perfbench.families.decoder_serve import (  # noqa: F401
    attempted_failed,
    dtype_of,
    timeline,
)
from perfbench.reference import sparse_mla_moe as ref


def to_flax(weights: dict, s: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads
    for ``index_topk > 0``: a "full" layer's indexer under
    ``attention/indexer``."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
    }
    for i, w in enumerate(weights["layers"]):
        attention = {
            **{p: {"kernel": w[p]}
               for p in ("q_a_proj", "q_b_proj", "kv_a_proj", "o_proj")},
            "kv_b_proj": w["kv_b_proj"],
            "q_norm": {"scale": w["q_norm"]},
            "kv_norm": {"scale": w["kv_norm"]},
        }
        if ref.has_indexer(s, i):
            attention["indexer"] = {
                "q_proj": {"kernel": w["index_q"]},
                "k_proj": {"kernel": w["index_k"]},
                "weights_proj": {"kernel": w["index_w"]},
                "k_norm": {"scale": w["index_k_norm"],
                           "bias": w["index_k_bias"]},
            }
        layer = {
            "attention": attention,
            "input_norm": {"scale": w["input_norm"]},
            "post_attention_norm": {"scale": w["post_attention_norm"]},
        }
        if ref.is_dense(s, i):
            layer.update({
                p: {"kernel": w[p]}
                for p in ("gate_proj", "up_proj", "down_proj")
            })
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
    from before the indexer refuses the keys, at once."""
    from tpudl.models.llama import LlamaConfig

    s = ref.settings(config)
    dense = s["mlp_layer_types"].rstrip("e")
    if "e" in dense:
        raise ValueError(
            "the program keeps its dense layers in front "
            f"(first_k_dense): got mlp_layer_types {config['mlp_layer_types']}"
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
        index_topk=s["index_topk"],
        index_n_heads=s["index_n_heads"],
        index_head_dim=s["index_head_dim"],
        indexer_types=tuple(config["indexer_types"]),
        num_experts=s["router_experts"],
        experts_per_token=s["num_experts_per_tok"],
        moe_intermediate_size=s["moe_intermediate_size"],
        num_shared_experts=s["n_shared_experts"],
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
        self.model = LlamaForCausalLM(model_config(
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
                    self.model.cfg, sess["weight_dtype"]))
            return tree

        params = jax.jit(make)(self.key)
        self.session = ServeSession.from_model(
            self.model, params, self.prompt_window, **sess
        )
        del params
        self._rid = 0
        self._weight_dtype = sess.get("weight_dtype")
        self._picked = self._choices = None

    # -- correctness ---------------------------------------------------

    def _pick(self, record: dict) -> list:
        """The finished requests the check compares: drawn from the
        seed, the longest among them."""
        sample = int(self.config["correctness"]["sample_requests"])
        done = [r for r in record["requests"]
                if r["finish_reason"] in ("length", "eos")]
        if not done:
            return []
        rng = np.random.default_rng(self.seed)
        longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
        rest = [r for r in done if r is not longest]
        picked = [longest]
        if rest:
            idx = rng.choice(len(rest), size=min(sample - 1, len(rest)),
                             replace=False)
            picked += [rest[int(i)] for i in idx]
        return picked

    def run_window(self, mix: dict, seconds: float, tracer=None) -> dict:
        record = super().run_window(mix, seconds, tracer)
        self._picked = self._pick(record)
        self._width = _width(self.config, record)
        return record

    def release(self) -> None:
        """Before the program's state goes: what its indexers chose for
        the tokens the check will compare."""
        if self._picked:
            self._choices = self._program_choices(self._picked)
        self.model = None
        super().release()

    def _program_choices(self, picked: list) -> list:
        """For each picked request, a list over the "full" layers of
        bool [tokens, width]: the positions the served model's indexer
        chose at each compared position, from ONE fresh prefill of the
        request's whole sequence (``intermediates`` mutable)."""
        import jax
        import jax.numpy as jnp

        from tpudl.models.turned import as_declared

        params = self.session.engine.params
        width = self._width
        # The served model at the sequences' width, its weights as the
        # session holds them (the control's are int8).
        model = self.model.clone(cfg=dataclasses.replace(
            self.model.cfg, max_seq_len=width,
            weight_dtype=self._weight_dtype))

        @jax.jit
        def choices(params, ids, picks):
            _, state = model.apply(
                {"params": as_declared(params)}, ids, jnp.ones_like(ids),
                decode=True, last_only=True,
                mutable=["cache", "intermediates"],
            )
            found = jax.tree_util.tree_leaves_with_path(
                state["intermediates"])
            found = sorted(
                (jax.tree_util.keystr(path), leaf) for path, leaf in found
                if "index_choice" in jax.tree_util.keystr(path)
            )
            return [leaf[0][picks] for _, leaf in found]

        # Fixed shapes, so that one program serves every request of
        # every run: ``t_max`` picks, a request's own in front.
        t_max = width - int(self.config["session"]["prompt_window"])
        out = []
        for r in picked:
            seq = list(r["prompt"]) + list(r["tokens"])[:-1]
            ids = np.zeros((1, width), np.int32)
            ids[0, : len(seq)] = seq
            k = len(r["tokens"])
            picks = np.zeros((t_max,), np.int32)
            picks[:k] = r["prompt_len"] - 1 + np.arange(k)
            out.append([np.asarray(c)[:k] for c in choices(
                params, jnp.asarray(ids), jnp.asarray(picks))])
        return out

    def check(self, record: dict) -> dict:
        """``decoder_serve.Cell.check`` against this family's reference:
        every request counted finished with the token count it asked
        for, and a sample of finished requests, drawn from the seed and
        holding the longest, is teacher-forced through the reference,
        which makes its OWN choice of positions; the widest and the
        mean gap by which a served token's logit lies below the
        reference's best, and the share of the tokens for which there
        is a gap at all, are held to the configuration's limits."""
        import jax.numpy as jnp

        limits = self.config["correctness"]
        rows = int(limits["reference_rows"])
        done = [r for r in record["requests"]
                if r["finish_reason"] in ("length", "eos")]
        short = [r for r in done if len(r["tokens"]) != r["max_new"]]
        picked = self._picked if self._picked is not None else (
            self._pick(record))
        comparisons = [
            {"name": "wrong_token_count", "value": len(short), "limit": 0},
            {"name": "compiles_in_window",
             "value": record["compiles_in_window"], "limit": 0},
            {"name": "requests_not_compared", "value": int(not picked),
             "limit": 0},
        ]
        t_max = max(r["max_new"] for r in record["requests"])
        width = _width(self.config, record)
        gaps, agree = [], []
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
            margin, theirs = ref.margins_and_choices(
                self.key, self.config, self.dtype, jnp.asarray(ids),
                jnp.asarray(picks), jnp.asarray(chosen),
            )
            gaps.append(np.asarray(margin)[valid])
            if self._choices is not None:
                for row in range(len(picked[at:at + rows])):
                    k = int(valid[row].sum())
                    for mine, ref_layer in zip(
                            self._choices[at + row], theirs):
                        agree.append((
                            int((mine & np.asarray(ref_layer)[row, :k]).sum()),
                            int(mine.sum()),
                        ))
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
        if agree:
            both, mine = map(sum, zip(*agree))
            info["index_choice_agreement"] = both / max(mine, 1)
        return {"comparisons": comparisons, **info}


def _width(config: dict, record: dict) -> int:
    """Fixed shapes, so that one program serves every run: as wide as
    the mix's longest request."""
    return int(config["session"]["prompt_window"]) + max(
        r["max_new"] for r in record["requests"])


def build(config: dict, device: dict, seed: int, variant: str = "program"):
    return Cell(config, device, seed, variant)
