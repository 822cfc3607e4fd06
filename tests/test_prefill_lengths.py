"""A prompt is prefilled at the shortest compiled length that holds it
(ISSUE 34): ``from_model`` compiles its batch-1 prefill and seat at a
ladder of lengths from the prompt window down (``prefill_lengths``:
the window and its half), and ``Engine._seat`` pads a prompt to the
shortest of them.

Held here, at tiny sizes on the CPU in float32: the rule of the ladder;
for a grouped-query model (a window of 1,024), a latent model with
routed experts and a model with window layers (ring seat; 512 each),
a prompt of every length's boundary serves the greedy tokens the same
session serves when held to the window alone, at the length and with
the pages the rule gives; after ``from_model`` no request of any length
compiles; a session that was handed one length keeps one program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.obs as obs
from tpudl.analysis.dispatch import RecompileWatcher
from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.obs import spans as obs_spans
from tpudl.serve import Request, ServeSession
from tpudl.serve.api import prefill_lengths

MAX_NEW = 6


@pytest.mark.parametrize("window, lengths", [
    (512, (256, 512)),            # the half is the floor, 256 rows
    (1024, (512, 1024)),          # one half, no quarter
    (4096, (2048, 4096)),
    (8192, (4096, 8192)),
    (511, (511,)),                # no half to take
    (256, (256,)),                # a half would be under the floor
    (64, (64,)),
    (600, (300, 600)),
])
def test_the_ladder_is_the_window_and_its_half(window, lengths):
    assert prefill_lengths(window) == lengths


def _gqa(window):
    cfg = LLAMA_TINY(dtype=jnp.float32, max_seq_len=window + 16)
    model = LlamaForCausalLM(cfg)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params, cfg.vocab_size, {}


def _latent_experts(window):
    from perfbench.families.mla_moe_serve import model_config, to_flax
    from perfbench.reference import mla_moe as ref
    from tests.test_latent_moe import CONFIG

    settings = ref.settings(CONFIG)
    model = LlamaForCausalLM(model_config(CONFIG, window + 16, jnp.float32))
    params = to_flax(
        ref.all_weights(ref.seed_key(34), settings, jnp.float32), settings
    )
    return model, params, CONFIG["vocab_size"], {"page_size": 4}


def _window_layers(window):
    from perfbench.families import window_moe_serve as family
    from perfbench.reference import window_moe as ref
    from tests.test_window_moe import tiny_config

    cfg = tiny_config()
    model = LlamaForCausalLM(
        family.model_config(cfg, window + 16, jnp.float32)
    )
    s = ref.settings(cfg)
    params = family.to_flax(
        ref.all_weights(ref.seed_key(34), s, jnp.float32), s
    )
    return model, params, cfg["vocab_size"], {"page_size": 4}


#: model -> (builder, prompt window): two lengths each.
MODELS = {
    "gqa": (_gqa, 1024),
    "latent_experts": (_latent_experts, 512),
    "window_layers": (_window_layers, 512),
}


@pytest.fixture(scope="module", params=list(MODELS))
def built(request):
    """(model, params, vocabulary, session options, window)."""
    build, window = MODELS[request.param]
    return (*build(window), window)


def _session(built):
    model, params, _, options, window = built
    return ServeSession.from_model(
        model, params, window, num_slots=2, **options
    )


@pytest.fixture(scope="module")
def pair(built):
    """(ladder session, the same session held to the window alone,
    vocabulary, window) of one model."""
    ladder, held = _session(built), _session(built)
    held.engine.prefill_lengths = (built[-1],)
    return ladder, held, built[2], built[-1]


def _recorded(session, requests, tmp_path):
    obs.enable(str(tmp_path / "obs"))
    try:
        got = session.serve(requests)
        records = obs_spans.active_recorder().records
    finally:
        obs.disable()
    spans = {
        name: [r for r in records
               if r.get("kind") == "span" and r.get("name") == name]
        for name in ("prefill", "seat")
    }
    return got, spans


#: Where a prompt falls against the half L and the window W: one token,
#: the last two the half holds, the first it does not, and the last two
#: the window holds.
@pytest.mark.parametrize("where", ["one", "L-1", "L", "L+1", "W-1", "W"])
def test_a_boundary_prompt_serves_the_window_sessions_tokens(
    pair, where, tmp_path
):
    ladder, held, vocab, window = pair
    lengths = ladder.engine.prefill_lengths
    assert lengths == prefill_lengths(window) == (window // 2, window)
    half = lengths[0]
    n = {"one": 1, "L-1": half - 1, "L": half, "L+1": half + 1,
         "W-1": window - 1, "W": window}[where]
    rows = next(r for r in lengths if r >= n)
    rng = np.random.default_rng(n)
    request = Request(where, rng.integers(1, vocab, size=n).tolist(),
                      max_new_tokens=MAX_NEW)
    got, spans = _recorded(ladder, [request], tmp_path)
    want = held.serve([request])
    assert got[where].ok and got[where].tokens == want[where].tokens
    assert len(got[where].tokens) == MAX_NEW
    # The length that ran, the prompt's tokens among its rows, and the
    # pages the slot reserved: from that length, not the window.
    (prefill,), (seat,) = spans["prefill"], spans["seat"]
    assert (prefill["rows"], prefill["tokens"]) == (rows, n)
    cache = ladder.engine.cache
    assert seat["pages"] == cache.pages_needed(rows + MAX_NEW)
    assert cache.pages_reserved == 0 and cache.tokens_live == 0


def test_no_request_of_any_length_compiles_after_from_model(built):
    """``from_model`` left every length's prefill and seat compiled: on
    a new session, once a shortest prompt has made what is independent
    of the length (decode, token selection: what a caller's warm-up
    reaches), prompts of every length compile nothing, the longest
    included."""
    session, vocab = _session(built), built[2]
    lengths = session.engine.prefill_lengths
    rng = np.random.default_rng(0)

    def request(i, n):
        return Request(f"c{i}", rng.integers(1, vocab, size=n).tolist(),
                       max_new_tokens=3)

    session.serve([request("warm", 2)])
    with RecompileWatcher("every length") as watch:
        got = session.serve([
            request(i, n) for i, n in enumerate(
                [*lengths, *(r - 1 for r in lengths), 3]
            )
        ])
    assert all(r.ok for r in got.values())
    assert watch.count == 0


def test_the_window_alone_pads_every_prompt_to_the_window(pair, tmp_path):
    """What the ladder is compared with: one length, every prompt at
    the window whatever it holds."""
    _, held, vocab, window = pair
    rng = np.random.default_rng(1)
    requests = [
        Request(f"o{n}", rng.integers(1, vocab, size=n).tolist(),
                max_new_tokens=2)
        for n in (1, 200, window)
    ]
    _, spans = _recorded(held, requests, tmp_path)
    assert [s["rows"] for s in spans["prefill"]] == [window] * 3
    assert sorted(s["tokens"] for s in spans["prefill"]) == [1, 200, window]


def test_an_artifact_session_keeps_its_one_program(tmp_path):
    """An exported prefill is ONE program at one length: a session
    built from it has a ladder of that length alone, whatever the rule
    would give its window, and seats through one program."""
    from tpudl.export.decode import export_serving_decoder

    window = 512
    model, params, vocab, _ = _gqa(window)
    prefix = str(tmp_path / "one_length")
    export_serving_decoder(
        model, params, num_slots=2, prompt_len=window, path_prefix=prefix,
    )
    art = ServeSession.from_artifacts(
        f"{prefix}.prefill.stablehlo", f"{prefix}.decode.stablehlo", params
    )
    assert len(prefill_lengths(window)) == 2
    assert art.engine.prefill_lengths == (window,)
    rng = np.random.default_rng(2)
    requests = [
        Request(f"a{n}", rng.integers(1, vocab, size=n).tolist(),
                max_new_tokens=2)
        for n in (1, 256, window)
    ]
    got, spans = _recorded(art, requests, tmp_path)
    assert all(r.ok for r in got.values())
    assert [s["rows"] for s in spans["prefill"]] == [window] * 3
    assert len(art.engine.cache._seat_jit) == 1
    live = ServeSession.from_model(model, params, window, num_slots=2)
    assert live.engine.prefill_lengths == (256, window)
    want = live.serve(requests)
    assert {k: r.tokens for k, r in got.items()} == {
        k: r.tokens for k, r in want.items()
    }
