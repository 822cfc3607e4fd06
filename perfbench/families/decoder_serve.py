"""A decoder that ``tpudl.models.llama.LlamaConfig`` can express, served
through ``ServeSession.from_model`` and driven by one thread.

The configuration file gives the published sizes under their public
``config.json`` names, and the session's shapes under ``session``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from perfbench import traffic
from perfbench.reference import decoder as ref
from perfbench.traffic import Item

ENGINE_STEP = "perfbench.engine_step"


def dtype_of(name: str):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def to_flax(weights: dict) -> dict:
    """The reference's weights in the tree ``LlamaForCausalLM`` reads."""
    outer = weights["outer"]
    model = {
        "embed_tokens": {"embedding": outer["embed_tokens"]},
        "final_norm": {"scale": outer["final_norm"]},
    }
    for i, w in enumerate(weights["layers"]):
        model[f"layer_{i}"] = {
            "attention": {
                n: {"kernel": w[n]}
                for n in ("q_proj", "k_proj", "v_proj", "o_proj")
            },
            "input_norm": {"scale": w["input_norm"]},
            "post_attention_norm": {"scale": w["post_attention_norm"]},
            **{n: {"kernel": w[n]}
               for n in ("gate_proj", "up_proj", "down_proj")},
        }
    return {"model": model, "lm_head": {"kernel": outer["lm_head"]}}


class Cell:
    """The served model of one run: built from the seed in set-up, then
    driven through as many windows as the caller wants."""

    kind = "serve"

    def __init__(self, config: dict, device: dict, seed: int,
                 variant: str = "program"):
        import jax

        from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
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
        model = LlamaForCausalLM(LlamaConfig(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            max_seq_len=int(sess.pop("max_seq_len")),
            rope_theta=config["rope_theta"],
            rms_norm_eps=config["rms_norm_eps"],
            dtype=self.dtype,
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

    # -- driving -------------------------------------------------------

    def _request(self, item: Item):
        from tpudl.serve import Request

        self._rid += 1
        return Request(request_id=self._rid, input_ids=item.prompt,
                       max_new_tokens=item.max_new)

    def warm_up(self, mix: dict, seconds: float) -> None:
        """Make the run's traffic, and run every program the window
        uses: batch-1 prefill at the prompt window, the seat, the
        slot-batched decode step, token selection. Two short requests
        run them all."""
        vocab = self.config["vocab_size"]
        self.items = traffic.generate(mix, self.seed, seconds, vocab)
        rng = np.random.default_rng(0)
        items = [
            Item(i, 0.0, rng.integers(1, vocab, size=16).tolist(), 4)
            for i in range(2)
        ]
        got = self.session.serve([self._request(it) for it in items])
        bad = [r.finish_reason for r in got.values() if not r.ok]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")

    def run_window(self, mix: dict, seconds: float, tracer=None) -> dict:
        """Offer the run's traffic for ``seconds`` and return the timeline.

        Open loop: each request is submitted when it is due, the engine
        stepped in between, and the requests due in the window drained
        after it. Closed loop: ``clients`` requests are always
        outstanding; the window closes at the first step boundary after
        ``seconds`` and what is in flight is abandoned."""
        import jax

        from tpudl.analysis.dispatch import RecompileWatcher

        session, engine = self.session, self.session.engine
        items = self.items
        clock = time.monotonic
        reqs = [{
            "rid": None, "due_s": it.due_s, "submit_s": None, "token_s": [],
            "prompt_len": len(it.prompt), "max_new": it.max_new,
            "prompt": it.prompt,
        } for it in items]
        by_rid = {}
        closed = mix["loop"] == "closed"
        clients = int(mix.get("clients", 0))
        n, nxt, steps = len(items), 0, 0
        done_before = len(engine.results)

        def on_token(rid, tok):
            by_rid[rid]["token_s"].append(clock() - t0)

        def submit(i):
            req = self._request(items[i])
            reqs[i]["rid"] = req.request_id
            by_rid[req.request_id] = reqs[i]
            reqs[i]["submit_s"] = clock() - t0
            session.submit(req)

        engine.on_token = on_token
        t0 = clock()
        try:
            with RecompileWatcher("serve window") as watch:
                while True:
                    now = clock() - t0
                    if tracer is not None:
                        tracer.poll(now)
                    if closed:
                        if now >= seconds:
                            break
                        finished = len(engine.results) - done_before
                        while nxt - finished < clients:
                            if nxt >= n:
                                raise RuntimeError(
                                    "the closed loop ran out of requests: "
                                    "the traffic file needs more blocks"
                                )
                            submit(nxt)
                            nxt += 1
                    else:
                        while nxt < n and items[nxt].due_s <= now:
                            submit(nxt)
                            nxt += 1
                    if tracer is not None and tracer.tracing:
                        tracer.sync.mark()
                        with jax.profiler.TraceAnnotation(ENGINE_STEP):
                            active = engine.step()
                    else:
                        active = engine.step()
                    steps += 1
                    if not active and not closed:
                        if nxt >= n:
                            break
                        wait = items[nxt].due_s - (clock() - t0)
                        if wait > 0:
                            time.sleep(wait)
                window_s = clock() - t0
        finally:
            engine.on_token = None
            if tracer is not None:
                tracer.finish()
        for r in reqs:
            res = engine.results.get(r["rid"])
            r["finish_reason"] = res.finish_reason if res else None
            r["tokens"] = list(res.tokens) if res else None
            r["queue_wait_s"] = res.queue_wait_s if res else None
        return {
            "kind": "serve", "loop": mix["loop"], "seconds": seconds,
            "window_s": window_s if closed else seconds,
            "t0_monotonic": t0, "requests": reqs, "engine_steps": steps,
            "slots": self.slots, "compiles_in_window": watch.count,
        }

    def release(self) -> None:
        """Free the program's state (weights, cache) before the
        reference runs."""
        self.session = None
        gc.collect()

    # -- correctness ---------------------------------------------------

    def check(self, record: dict) -> dict:
        """Compare what the window served with the plain reference.

        Every request counted must have finished with the token count it
        asked for. Then a sample of finished requests, drawn from the
        seed and holding the longest, is teacher-forced through the
        reference, and two numbers are read from the gaps by which each
        served token's logit lies below the reference's best:

        - the widest gap, which a wrong cache, mask or position drives to
          whole logits, and which swings from seed to seed by its nature;
        - the mean gap over the served tokens (0 where the reference
          agrees), which is steady and separates bfloat16 from int8.
        """
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
            # Nothing finished: nothing was shown to be right.
            {"name": "requests_not_compared", "value": int(not picked),
             "limit": 0},
        ]
        # Fixed shapes, so that one program serves every run: ``rows``
        # rows a pass, as wide as the mix's longest request.
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


def timeline(record: dict) -> dict:
    """For the log: every measured request's time to first token and
    mean gap between tokens, ms, in the order they were due, so that a
    statistic other than the judged one can be worked out afterwards."""
    from perfbench.stats import tpot_s

    rows = [r for r in record["requests"] if r.get("token_s")]
    return {
        "ttft_ms": [round(1e3 * (r["token_s"][0] - r["due_s"]), 2)
                    for r in rows],
        "tpot_ms": [round(1e3 * (tpot_s(r["token_s"]) or 0.0), 2)
                    for r in rows],
    }


def attempted_failed(record: dict) -> tuple:
    """Requests offered in the window, and those that failed, were shed
    or refused. Open loop: the requests due in the window. Closed loop:
    those submitted, less what was still in flight when it closed."""
    reqs = [r for r in record["requests"] if r["submit_s"] is not None]
    if record["loop"] == "closed":
        reqs = [r for r in reqs if r["finish_reason"] is not None]
    failed = [r for r in reqs if r["finish_reason"] not in ("length", "eos")]
    return len(reqs), len(failed)
