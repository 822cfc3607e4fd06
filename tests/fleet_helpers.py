"""Subprocess target for the cross-process migration test.

Run as ``python -m tests.fleet_helpers <request_id>`` from the repo
root. The child builds the SAME deterministic tiny serving session the
parent holds (same init seed, same config — params are therefore
byte-identical), opens a ``MigrationEndpoint``, prints its port as one
JSON line, and then drives the engine until the migrated-in request
finishes, printing the result as a second JSON line:

    {"port": <int>}
    {"tokens": [...], "finish_reason": "...", "prefills": <int>}

``prefills`` is the child engine's TOTAL prefill-dispatch count — the
parent asserts it stays 0, which is the whole point of shipping KV
instead of re-prefilling on the survivor.
"""

import json
import os
import sys
import time

# Same hermetic backend as tests/conftest.py — this process has no
# conftest, so pin it here before jax initializes.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

PROMPT_LEN = 8
PAGE = 8


def build_session():
    from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
    from tpudl.serve import ServeSession

    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=96)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return ServeSession.from_model(
        model, params, PROMPT_LEN, num_slots=2,
        page_size=PAGE,
    )


def main(argv) -> int:
    from tpudl.fleet.transport import MigrationEndpoint, deliver_to_session

    rid = argv[1]
    session = build_session()
    with MigrationEndpoint(
        lambda p: deliver_to_session(session, p)
    ) as endpoint:
        print(json.dumps({"port": endpoint.address[1]}), flush=True)
        deadline = time.monotonic() + 600.0
        while rid not in session.engine.results:
            if not session.engine.step():
                time.sleep(0.01)
            if time.monotonic() > deadline:
                print(json.dumps({"error": "timeout"}), flush=True)
                return 1
    res = session.engine.results[rid]
    print(json.dumps({
        "tokens": [int(t) for t in res.tokens],
        "finish_reason": res.finish_reason,
        "prefills": int(session.engine.num_prefills),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
