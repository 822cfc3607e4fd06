#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpudl still starts on the chip.

    python chip_smoke.py [--seed N]     # one TPU chip, two phases
    python chip_smoke.py --chips 4      # four chips: the sharded paths only

Drives both main paths once, at full width, through the entry points a
user calls, with weights, data and prompts made from ``--seed``:

- ``serve``: ``ServeSession.from_model`` on Llama-3.2-1B shape (2048
  wide, 16 layers, 32/8 heads, vocab 128,256, bf16 parameters), the
  paged pool at its defaults: two waves of eight ragged requests; every
  request completes with the token count it asked for and greedy tokens
  equal ``generate()`` on the same prompts; the second wave compiles
  nothing.
- ``train``: ``notebooks/nlp/train_sst2.py``'s path for
  ``--config sst2_bert_base --batch 256`` (BERT-base, seq 128):
  ``build_model``, ``create_train_state``, ``make_mesh``,
  ``compile_step``, ``prefetch_to_device``, ``fit``; loss finite on every
  step and lower at the end than at the start, nothing compiled after
  warm-up.

The phases run in order of memory (the train step leaves ~13 of 16 GB
in use) and each drops what it built, so they never coexist on the chip.

``--chips 4`` runs two comparisons and no other phase: BERT-base steps
under ``dp=4`` and ``fsdp=4`` against a one-device mesh (same seed,
dropout 0), and a tensor-parallel ``MeshReplica(tp=4)`` on the Llama
against the one-device session.

Each phase prints one JSON line; a phase that raises ends the script
with a non-zero exit. Without a TPU the script exits non-zero before
any phase and prints no result. The last line of standard output is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import sys
import time

#: How far the best reference logit may beat a token the engine chose
#: (see ``check_served``). The hidden state is bf16 — 2^-8 relative per
#: rounding, through 16 layers — under a 2048-term f32 head whose
#: logits have unit scale: a few hundredths of a logit between two
#: correct programs, against the ~4 logits by which a random token
#: loses to the best of 128,256.
LOGIT_MARGIN_ATOL = 0.25
#: Per-step |loss - one-device loss| bound for the four-chip meshes: the
#: same f32-accumulated bf16 program, summed in another order (measured
#: on four chips: 1.6e-4).
MESH_LOSS_TOL = 2e-3


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero unless it is a TPU
    with at least ``chips`` chips. First act of :func:`main`."""
    import jax

    devices = jax.devices()
    info = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if info["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {info}")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX found {info}")
    return info


class Probe:
    """Brackets one phase: wall seconds, XLA compile seconds, the
    persistent compile cache's hits and misses, and the devices' memory
    counters."""

    def __init__(self, phase: str):
        self.phase = phase

    @staticmethod
    def _counts() -> tuple:
        """(compile seconds, cache hits, cache misses) so far."""
        from tpudl.analysis.dispatch import compile_seconds
        from tpudl.obs import counters

        reg = counters.registry()
        return (compile_seconds(),
                reg.counter("compile_cache_hits").value,
                reg.counter("compile_cache_misses").value)

    def __enter__(self) -> "Probe":
        self._t0 = time.perf_counter()
        self._before = self._counts()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0

    def line(self, **fields) -> dict:
        """Print and return the phase's JSON line."""
        import jax

        compile_s, hits, misses = (
            now - before for now, before in zip(self._counts(), self._before)
        )
        out = {
            "phase": self.phase,
            "seconds": round(self.seconds, 2),
            "compile_seconds": round(compile_s, 2),
            "compile_cache_hits": int(hits),
            "compile_cache_misses": int(misses),
            **fields,
        }
        # Per device, as the runtime counts them (peak_bytes_in_use is
        # the process's high-water mark, so the phases run in order of
        # memory).
        out["memory_stats"] = [d.memory_stats() for d in jax.devices()]
        print(json.dumps(out), flush=True)
        return out


# ---------------------------------------------------------------------------
# train: notebooks/nlp/train_sst2.py's main() path
# ---------------------------------------------------------------------------


def run_train_steps(
    seed: int,
    *,
    mesh,
    model: str,
    batch: int,
    seq_len: int,
    warmup: int,
    steps: int,
    strategy=None,
    model_kwargs=None,
    optim=None,
) -> dict:
    """``warmup`` direct steps, then ``steps`` through ``fit`` with every
    step logged. Returns every step's loss, the compiles ``fit`` saw,
    and the final state (for the caller to inspect and drop). ``optim``
    overrides fields of the config's optimizer (the tiny CPU rehearsal
    needs a tiny model's learning rate)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from tpudl.analysis.dispatch import RecompileWatcher
    from tpudl.config import get_config
    from tpudl.data.converter import prefetch_to_device
    from tpudl.data.synthetic import synthetic_token_batches
    from tpudl.models.registry import build_model
    from tpudl.parallel.sharding import strategy_rules
    from tpudl.train import (
        compile_step,
        create_train_state,
        fit,
        make_classification_train_step,
    )
    from tpudl.train.optim import make_optimizer

    cfg = get_config("sst2_bert_base", model=model, seed=seed)
    if optim:
        cfg = dataclasses.replace(
            cfg, optim=dataclasses.replace(cfg.optim, **optim)
        )
    net = build_model(cfg.model, cfg.num_classes, **(model_kwargs or {}))
    state = create_train_state(
        jax.random.key(cfg.seed), net,
        jnp.zeros((1, seq_len), jnp.int32), make_optimizer(cfg.optim),
    )
    step = compile_step(
        make_classification_train_step(
            input_keys=("input_ids", "attention_mask"), label_key="label",
            accum_steps=cfg.accum_steps,
        ),
        mesh, state, strategy_rules(strategy or cfg.strategy),
    )
    raw = synthetic_token_batches(
        batch, seq_len=seq_len, vocab_size=net.cfg.vocab_size,
        num_classes=cfg.num_classes, seed=cfg.seed,
        num_batches=warmup + steps,
    )
    rng = jax.random.key(cfg.seed + 1)
    losses = []
    with prefetch_to_device(raw, mesh=mesh) as batches:
        for _ in range(warmup):
            state, metrics = step(state, next(batches), rng)
            losses.append(float(metrics["loss"]))
        with RecompileWatcher("train steady state") as watch:
            state, _, info = fit(
                step, state, batches, rng, log_every=1,
                logger=lambda i, m: losses.append(float(m["loss"])),
            )
    if info["steps"] != steps or len(losses) != warmup + steps:
        raise AssertionError(
            f"asked for {warmup}+{steps} steps, fit ran {info['steps']} "
            f"and logged {len(losses)} losses"
        )
    return {"losses": losses, "recompiles": watch.count, "state": state}


def train_phase(
    seed: int,
    *,
    model: str = "bert-base",
    batch: int = 256,
    seq_len: int = 128,
    warmup: int = 3,
    steps: int = 12,
    optim=None,
) -> dict:
    import math

    import jax

    from tpudl.config import get_config
    from tpudl.runtime import make_mesh

    with Probe("train") as probe:
        mesh = make_mesh(
            get_config("sst2_bert_base").mesh.fit(jax.device_count())
        )
        run = run_train_steps(
            seed, mesh=mesh, model=model, batch=batch, seq_len=seq_len,
            warmup=warmup, steps=steps, optim=optim,
        )
    losses = run.pop("losses")
    del run["state"]
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    # Lower at the end than at the start, three steps a side (one
    # step's loss carries the batch's and the dropout masks' noise).
    first, last = sum(losses[:3]) / 3, sum(losses[-3:]) / 3
    if not last < first:
        raise AssertionError(f"loss did not fall: {losses}")
    if run["recompiles"]:
        raise AssertionError(
            f"{run['recompiles']} compilation(s) after warm-up"
        )
    return probe.line(
        model=model, batch=batch, seq_len=seq_len, steps=len(losses),
        first_loss=round(first, 4), last_loss=round(last, 4),
        losses=[round(x, 4) for x in losses],
        recompiles_after_warmup=run["recompiles"],
    )


# ---------------------------------------------------------------------------
# serve: ServeSession.from_model on the Llama
# ---------------------------------------------------------------------------


def build_llama(seed: int, size: str, max_seq_len: int, dtype):
    """The model and its random parameters in ``dtype`` — initialised
    and cast in one jitted program, so the f32 tree never sits on the
    device beside the served one."""
    import jax
    import jax.numpy as jnp

    from tpudl.models.llama import LLAMA_SIZES, LlamaForCausalLM

    model = LlamaForCausalLM(
        LLAMA_SIZES[size](max_seq_len=max_seq_len, dtype=dtype)
    )

    @jax.jit
    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        return jax.tree.map(lambda p: p.astype(dtype), params)

    return model, init(jax.random.key(seed))


def make_requests(rng, n: int, vocab: int, prompt_len: int, max_new: int,
                  tag: str) -> list:
    """``n`` greedy requests of ragged prompt and output lengths."""
    from tpudl.serve import Request

    return [
        Request(
            request_id=f"{tag}{i}",
            input_ids=rng.integers(
                1, vocab, size=int(rng.integers(2, prompt_len + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(max_new // 3, max_new + 1)),
        )
        for i in range(n)
    ]


def left_padded(rows, prompt_len: int, width: int):
    """``rows`` of (prompt ids, following ids) as one LEFT-padded
    ``[n, width]`` batch — prompts end at column ``prompt_len`` — and
    its attention mask (the ragged-batch contract of ``generate()``)."""
    import numpy as np

    ids = np.zeros((len(rows), width), np.int32)
    mask = np.zeros((len(rows), width), np.int32)
    for row, (prompt, tail) in enumerate(rows):
        seq = list(prompt) + list(tail)
        start = prompt_len - len(prompt)
        ids[row, start:start + len(seq)] = seq
        mask[row, start:] = 1
    return ids, mask


@functools.lru_cache(maxsize=None)
def _margins_fn(model, prompt_len: int):
    """Jitted once per model: by how much the best teacher-forced logit
    beats each chosen token."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def margins(params, ids, mask, chosen):
        logits = model.apply({"params": params}, ids, mask)
        # Column prompt_len - 1 + t holds the distribution token t was
        # drawn from.
        logits = logits[:, prompt_len - 1:, :]
        picked = jnp.take_along_axis(logits, chosen[..., None], axis=-1)
        return jnp.max(logits, axis=-1) - picked[..., 0]

    return margins


def check_served(model, params, requests, results, prompt_len: int,
                 max_new: int) -> dict:
    """What came out is right, by the repo's own means
    (``assert_serving_parity``'s two modes, batched so that each
    reference compiles once):

    - every request completed with the token count it asked for;
    - greedy tokens against ``generate()`` on the same prompts, served
      as one LEFT-padded batch (each row generates what it would alone
      — tests/test_generate.py): the number of requests that agree
      token for token is reported;
    - EVERY token the engine chose is the reference's own choice up to
      ``LOGIT_MARGIN_ATOL``: the prompt and the engine's tokens are
      teacher-forced through the plain forward pass, and the best logit
      there may beat the chosen token's by no more than the bound. Two
      correct bf16 programs that round in another order part ways at a
      near-tie, and stay apart; a wrong cache, mask or position loses
      by whole units."""
    import jax.numpy as jnp
    import numpy as np

    from tpudl.models.generate import generate

    for req in requests:
        res = results[req.request_id]
        if res.finish_reason != "length":
            raise AssertionError(
                f"{req.request_id}: finished {res.finish_reason!r}"
            )
        if len(res.tokens) != req.max_new_tokens:
            raise AssertionError(
                f"{req.request_id}: asked {req.max_new_tokens} tokens, "
                f"got {len(res.tokens)}"
            )
    got = [results[req.request_id].tokens for req in requests]
    ids, mask = left_padded(
        [(req.input_ids, ()) for req in requests], prompt_len, prompt_len
    )
    want = np.asarray(generate(
        model, params, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
        max_new_tokens=max_new,
    ))
    equal = sum(
        np.array_equal(np.asarray(toks), want[row, : len(toks)])
        for row, toks in enumerate(got)
    )

    ids, mask = left_padded(
        [(req.input_ids, toks[:-1]) for req, toks in zip(requests, got)],
        prompt_len, prompt_len + max_new - 1,
    )
    chosen = np.zeros((len(requests), max_new), np.int32)
    for row, toks in enumerate(got):
        chosen[row, : len(toks)] = toks
    margin = np.asarray(
        _margins_fn(model, prompt_len)(params, ids, mask, chosen)
    )
    worst = max(
        float(margin[row, : len(toks)].max()) for row, toks in enumerate(got)
    )
    if not worst <= LOGIT_MARGIN_ATOL:
        raise AssertionError(
            f"an engine token loses to the reference's choice by {worst} "
            f"> {LOGIT_MARGIN_ATOL} logits"
        )
    return {
        "tokens": sum(len(toks) for toks in got),
        "requests_equal_generate": int(equal),
        "max_logit_margin": worst,
    }


def serve_phase(
    seed: int,
    *,
    size: str = "llama3-1b",
    dtype=None,
    prompt_len: int = 128,
    max_seq_len: int = 512,
    num_slots: int = 8,
    requests_per_wave: int = 8,
    max_new: int = 33,
) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from tpudl.analysis.dispatch import RecompileWatcher
    from tpudl.obs import registry
    from tpudl.serve import ServeSession

    dtype = jnp.bfloat16 if dtype is None else dtype
    with Probe("serve") as probe:
        model, params = build_llama(seed, size, max_seq_len, dtype)
        session = ServeSession.from_model(
            model, params, prompt_len, num_slots=num_slots
        )
        rng = np.random.default_rng(seed)
        vocab = model.cfg.vocab_size
        warm = make_requests(
            rng, requests_per_wave, vocab, prompt_len, max_new, "w"
        )
        measured = make_requests(
            rng, requests_per_wave, vocab, prompt_len, max_new, "m"
        )
        # Wave 1 compiles every program of the session and of the
        # references; wave 2 — other prompts, other lengths — compiles
        # nothing.
        first = check_served(
            model, params, warm, session.serve(warm), prompt_len, max_new
        )
        with RecompileWatcher("serve steady state") as watch:
            second = check_served(
                model, params, measured, session.serve(measured),
                prompt_len, max_new,
            )
        engine = session.engine
        gauges = registry().snapshot()["gauges"]
        counts = {
            "prefills": engine.num_prefills,
            "decode_steps": engine.num_decode_steps,
            # Kernels the session holds turned (tpudl.serve.weights):
            # 3 a layer on a chip, none on a CPU.
            "weights_relaid_leaves": gauges["serve_weights_relaid_leaves"],
            "weights_relaid_bytes": gauges["serve_weights_relaid_bytes"],
        }
    del session, engine, params
    if watch.count:
        raise AssertionError(f"{watch.count} compilation(s) after warm-up")
    return probe.line(
        model=size, num_slots=num_slots,
        prompt_len=prompt_len, requests=2 * requests_per_wave,
        tokens=first["tokens"] + second["tokens"],
        requests_equal_generate=first["requests_equal_generate"]
        + second["requests_equal_generate"],
        max_logit_margin=round(
            max(first["max_logit_margin"], second["max_logit_margin"]), 4
        ),
        logit_margin_atol=LOGIT_MARGIN_ATOL,
        recompiles_after_warmup=watch.count, **counts,
    )


# ---------------------------------------------------------------------------
# --chips 4: the paths that exist only across chips
# ---------------------------------------------------------------------------


def _bytes_in_use(devices) -> list:
    return [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]


def _tree_bytes_per_device(tree, devices) -> list:
    """Bytes of ``tree``'s shards resident on each of ``devices``."""
    import jax

    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            for shard in leaf.addressable_shards:
                held[shard.device] += shard.data.nbytes
    return list(held.values())


def mesh_train_phase(
    seed: int,
    *,
    model: str = "bert-base",
    batch: int = 256,
    seq_len: int = 128,
    warmup: int = 2,
    steps: int = 4,
    chips: int = 4,
) -> dict:
    """BERT steps under dp=N and fsdp=N (bert_large_v4_32's rules)
    against the same seed on a one-device mesh, dropout 0 (the hardware
    generator's masks depend on the sharding)."""
    import jax

    from tpudl.runtime import MeshSpec, make_mesh

    no_dropout = {"hidden_dropout": 0.0, "attention_dropout": 0.0}
    devices = jax.devices()[:chips]
    cases = [
        ("one_device", make_mesh(MeshSpec(dp=1), devices[:1]), "dp"),
        (f"dp{chips}", make_mesh(MeshSpec(dp=chips), devices), "dp"),
        (f"fsdp{chips}",
         make_mesh(MeshSpec(dp=1, fsdp=chips), devices), "fsdp"),
    ]
    with Probe("mesh_train") as probe:
        report = {}
        for name, mesh, strategy in cases:
            run = run_train_steps(
                seed, mesh=mesh, model=model, batch=batch, seq_len=seq_len,
                warmup=warmup, steps=steps, strategy=strategy,
                model_kwargs=no_dropout,
            )
            report[name] = {
                "losses": [round(x, 5) for x in run["losses"]],
                "recompiles_after_warmup": run["recompiles"],
                "state_bytes_per_device": _tree_bytes_per_device(
                    run["state"], devices
                ),
                "bytes_in_use_per_device": _bytes_in_use(devices),
            }
            del run
            gc.collect()
    base = report["one_device"]["losses"]
    for name, rep in report.items():
        worst = max(abs(a - b) for a, b in zip(rep["losses"], base))
        rep["max_abs_loss_diff"] = round(worst, 5)
        if not worst <= MESH_LOSS_TOL:
            raise AssertionError(
                f"{name} losses {rep['losses']} differ from the "
                f"one-device {base} by {worst} > {MESH_LOSS_TOL}"
            )
        if rep["recompiles_after_warmup"]:
            raise AssertionError(f"{name} compiled after warm-up")
    # Sharded state: under fsdp every device holds about 1/N of
    # parameters and optimizer state and none holds it whole; under dp
    # every device holds a full copy.
    whole = report["one_device"]["state_bytes_per_device"][0]
    fsdp = report[f"fsdp{chips}"]["state_bytes_per_device"]
    if not all(b < 1.5 * whole / chips for b in fsdp):
        raise AssertionError(
            f"fsdp state not sharded {chips} ways: {fsdp} of {whole}"
        )
    dp = report[f"dp{chips}"]["state_bytes_per_device"]
    if not all(abs(b - whole) <= 0.01 * whole for b in dp):
        raise AssertionError(f"dp state not replicated: {dp} of {whole}")
    return probe.line(
        model=model, batch=batch, seq_len=seq_len, steps=warmup + steps,
        loss_tolerance=MESH_LOSS_TOL, **report,
    )


def mesh_serve_phase(
    seed: int,
    *,
    size: str = "llama3-1b",
    dtype=None,
    prompt_len: int = 128,
    max_seq_len: int = 512,
    num_slots: int = 8,
    n_requests: int = 8,
    max_new: int = 33,
    chips: int = 4,
) -> dict:
    """``MeshReplica(tp=N)`` behind a ``Router`` against the one-device
    ``ServeSession`` and the one-device references of ``check_served``;
    parameters spread over N."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpudl.fleet import MeshReplica
    from tpudl.serve import Router, ServeSession

    dtype = jnp.bfloat16 if dtype is None else dtype
    devices = jax.devices()[:chips]
    with Probe("mesh_serve") as probe:
        model, params = build_llama(seed, size, max_seq_len, dtype)
        requests = make_requests(
            np.random.default_rng(seed), n_requests, model.cfg.vocab_size,
            prompt_len, max_new, "r",
        )
        session = ServeSession.from_model(
            model, jax.device_put(params, devices[0]), prompt_len,
            num_slots=num_slots,
        )
        want = session.serve(requests)
        del session
        replica = MeshReplica(
            "tp", model=model, params=params, prompt_len=prompt_len,
            devices=devices, tp=chips,
            session_kwargs={"num_slots": num_slots},
        )
        sharded = replica.session.engine.params
        param_bytes = _tree_bytes_per_device(sharded, devices)
        total = sum(
            leaf.nbytes for leaf in jax.tree.leaves(sharded)
        )
        with Router([replica]) as router:
            got = router.serve(list(requests), timeout_s=900.0)
        del replica, router, sharded
        checked = check_served(
            model, params, requests, got, prompt_len, max_new
        )
        del params
    equal = sum(
        got[req.request_id].tokens == want[req.request_id].tokens
        for req in requests
    )
    # Megatron splits leave norms and embeddings whole: each device
    # holds well under the tree, and the shares are equal.
    if not (max(param_bytes) < 0.6 * total
            and max(param_bytes) - min(param_bytes) <= 0.01 * total):
        raise AssertionError(
            f"parameters not spread over {chips} devices: "
            f"{param_bytes} of {total}"
        )
    return probe.line(
        model=size, tp=chips, requests=n_requests,
        requests_equal_one_device=int(equal), **checked,
        logit_margin_atol=LOGIT_MARGIN_ATOL,
        param_bytes_total=int(total), param_bytes_per_device=param_bytes,
    )


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument(
        "--chips", type=int, default=1, choices=(1, 4),
        help="4: run only the dp/fsdp and MeshReplica(tp) comparisons",
    )
    args = parser.parse_args(argv)
    device = require_tpu(args.chips)
    if args.chips == 1:
        phases = [
            lambda: serve_phase(args.seed),
            lambda: train_phase(args.seed),
        ]
    else:
        phases = [
            lambda: mesh_serve_phase(args.seed, chips=args.chips),
            lambda: mesh_train_phase(args.seed, chips=args.chips),
        ]
    for phase in phases:
        phase()
        gc.collect()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
