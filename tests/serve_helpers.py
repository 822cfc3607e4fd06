"""Tiny-Llama serving sessions and request mixes for the tests.

Not collected by pytest (no ``test_`` prefix; ``tests/fleet_helpers.py``
is the precedent). Every session is f32 so that CPU runs are
deterministic. Nothing here reports a time.
"""

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpudl.models.generate import paged_decode_fn, prefill_fn
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.models.lora import extract_adapters
from tpudl.serve import Request, ServeSession
from tpudl.serve.cache import PagedKVCache

# Workload shape: ragged max_new_tokens is WHY continuous batching wins
# (a static batch waits for its longest row); the 4:1 long:short mix
# mirrors the bimodal request lengths real serving sees.
SHORT_TOKENS = 6
LONG_TOKENS = 40
PROMPT_LEN = 8
MAX_SEQ_LEN = 256
PAGE_SIZE = 16


def _tiny_model():
    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=MAX_SEQ_LEN)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def build_session(num_slots: int = 4):
    """Tiny-Llama serving session."""
    model, params = _tiny_model()
    return ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=num_slots
    )


def build_programs(num_slots: int = 4):
    """Compile the serving programs ONCE and share them across every
    replica (jitted callables are pure and thread-safe; each replica
    still owns its private cache/queue/engine) — N replicas cost one
    compilation, here and on a real pod with identical meshes."""
    model, params = _tiny_model()
    pf = prefill_fn(model)
    ids = jax.ShapeDtypeStruct((num_slots, PROMPT_LEN), jnp.int32)
    _, template = jax.eval_shape(pf, params, ids, ids)
    decode = jax.jit(paged_decode_fn(model, PAGE_SIZE, False))
    return {
        "model": model, "params": params, "prefill": jax.jit(pf),
        "decode": decode, "template": template,
    }


def session_from_programs(programs: dict, **kwargs):
    """One replica's ServeSession over the shared compiled programs."""
    cache = PagedKVCache(programs["template"], page_size=PAGE_SIZE)
    return ServeSession(
        programs["prefill"], programs["decode"], programs["params"],
        cache, PROMPT_LEN, **kwargs,
    )


def make_requests(
    n: int,
    seed: int = 0,
    long_every: int = 4,
    best_effort_every: Optional[int] = None,
    tag: str = "req",
) -> List:
    """Ragged request mix: every ``long_every``-th request is long;
    every ``best_effort_every``-th (when set) is priority-1 — the
    class the router sheds first under SLO burn."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        prompt = rng.integers(
            1, 512, size=int(rng.integers(2, PROMPT_LEN + 1))
        ).tolist()
        out.append(
            Request(
                request_id=f"{tag}{i}",
                input_ids=prompt,
                max_new_tokens=(
                    LONG_TOKENS if i % long_every == 0 else SHORT_TOKENS
                ),
                priority=(
                    1
                    if best_effort_every and i % best_effort_every == 0
                    else 0
                ),
            )
        )
    return out


def warmup_session(session) -> None:
    """Drive every compiled path once (prefill, decode, both selection
    shapes, insert/free, refill) so that what follows is steady-state
    serving, not first-call compilation."""
    n = session.num_slots + 1  # +1 forces one mid-stream refill
    session.serve(make_requests(n, seed=9999, long_every=2, tag="warm"))


def make_adapters(
    n_tenants: int, rank: int = 2, seed: int = 0
) -> Dict[str, dict]:
    """N synthetic tenants' LoRA adapters for the tiny-Llama serving
    model, in the extract_adapters flat form. A real fine-tune's B
    starts at zero and trains away from it; synthetic tenants get a
    small random B instead (zero B would make every tenant identical
    to the base and the heterogeneous path untestable)."""
    cfg = LLAMA_TINY(
        dtype=jnp.float32, max_seq_len=MAX_SEQ_LEN, lora_rank=rank
    )
    template = extract_adapters(
        LlamaForCausalLM(cfg).init(
            jax.random.key(seed), jnp.zeros((1, PROMPT_LEN), jnp.int32)
        )["params"]
    )
    shapes = {
        path: (np.shape(f["lora_a"]), np.shape(f["lora_b"]))
        for path, f in template.items()
    }
    rng = np.random.default_rng(seed)
    out: Dict[str, dict] = {}
    for t in range(n_tenants):
        out[f"tenant{t}"] = {
            path: {
                "lora_a": rng.normal(
                    scale=0.5 / rank, size=a_shape
                ).astype(np.float32),
                "lora_b": rng.normal(
                    scale=0.02, size=b_shape
                ).astype(np.float32),
            }
            for path, (a_shape, b_shape) in shapes.items()
        }
    return out


def build_tenant_session(adapters: Dict[str, dict], num_slots: int = 8):
    """Tiny-Llama multi-tenant session: base resident once, every
    tenant registered with the adapter pool, and the lora prefill /
    decode programs (with one adapter load/bind cycle) driven once."""
    model, params = _tiny_model()
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=num_slots,
        adapters=adapters,
    )
    first = next(iter(adapters))
    session.serve([
        Request(
            request_id="_warm0", input_ids=[1, 2, 3],
            max_new_tokens=3, tenant=first,
        ),
        Request(
            request_id="_warm1", input_ids=[1, 2], max_new_tokens=2,
        ),
    ])
    return session
