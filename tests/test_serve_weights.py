"""The boundary where a session takes its weights (ISSUE 39,
tpudl.serve.weights, tpudl.models.turned), on the CPU.

A session that finds no chip under its parameters holds them exactly as
given; so does one whose tree is quantized, spread over a mesh or under
the adapter programs. Where it finds one (answered for it here, in the
test: the sandbox has none), the head-split attention kernels are held
turned, the gauges say how many, and greedy tokens through the engine
equal ``generate()``'s on the plain tree token for token, in float32.
The chip's side (no weight-shaped copy left in a compiled program) is
tests/test_tpu_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from tpudl.models.llama import LLAMA_TINY, LlamaForCausalLM
from tpudl.models.turned import TURNED, as_declared, turn, turned_nodes
from tpudl.obs import registry
from tpudl.serve import Request, ServeSession, assert_serving_parity
from tpudl.serve import weights

PROMPT_LEN, SLOTS = 8, 4
LATENT = dict(
    attention="mla", kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16,
)
CONFIGS = {
    "gqa": LLAMA_TINY(dtype=jnp.float32, max_seq_len=96),
    "mla": LLAMA_TINY(dtype=jnp.float32, max_seq_len=96, **LATENT),
    "mla-low-rank-query": LLAMA_TINY(
        dtype=jnp.float32, max_seq_len=96, q_lora_rank=24, **LATENT
    ),
}
#: The projections each family holds turned, a layer.
HELD = {
    "gqa": {"q_proj", "k_proj", "v_proj"},
    "mla": {"q_proj", "kv_b_proj"},
    "mla-low-rank-query": {"q_b_proj", "kv_b_proj"},
}


def _built(name):
    model = LlamaForCausalLM(CONFIGS[name])
    params = model.init(
        jax.random.key(0), jnp.zeros((1, PROMPT_LEN), jnp.int32)
    )["params"]
    return model, params


def _requests(vocab, n=6):
    rng = np.random.default_rng(39)
    return [
        Request(
            request_id=f"r{i}",
            input_ids=rng.integers(
                1, vocab, size=int(rng.integers(2, PROMPT_LEN + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(4, 12)),
        )
        for i in range(n)
    ]


def _turned_nodes(tree):
    return list(turned_nodes(tree).values())


def _gauges():
    snap = registry().snapshot()["gauges"]
    return (snap["serve_weights_relaid_leaves"],
            snap["serve_weights_relaid_bytes"])


@pytest.fixture
def on_a_chip(monkeypatch):
    """The one question the boundary asks of the backend, answered yes
    (as tests/test_paged_attention.py answers ``is_tpu_backend``)."""
    monkeypatch.setattr(
        weights, "chip_of", lambda params: jax.devices()[0]
    )


def test_a_cpu_session_holds_its_weights_exactly_as_given():
    model, params = _built("gqa")
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS
    )
    given = jax.tree.leaves(params)
    held = jax.tree.leaves(session.engine.params)
    assert len(given) == len(held)
    assert all(a is b for a, b in zip(given, held))
    assert _gauges() == (0, 0)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_turned_kernels_serve_the_same_tokens(name, on_a_chip):
    model, params = _built(name)
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS
    )
    held = session.engine.params
    nodes = _turned_nodes(held)
    layers = CONFIGS[name].num_layers
    assert len(nodes) == len(HELD[name]) * layers
    assert HELD[name] <= TURNED
    assert _gauges() == (
        len(nodes), sum(4 * node.value.size for node in nodes)
    )
    # The same bits the other way round, every other leaf the caller's
    # own array; and the declared tree comes back from it.
    declared = as_declared(held)
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(params)[0],
        jax.tree.leaves(declared),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), str(path))
    kept = set(map(id, jax.tree.leaves(params)))
    assert sum(id(leaf) in kept for leaf in jax.tree.leaves(held)) == (
        len(kept) - len(nodes)
    )
    # Token for token what generate() makes of the plain tree.
    assert_serving_parity(
        session, model, params, _requests(CONFIGS[name].vocab_size)
    )


@pytest.mark.parametrize("kept", ["quantized", "adapters"])
def test_a_tree_the_boundary_leaves_alone(kept, on_a_chip):
    import dataclasses

    from tpudl.models.lora import extract_adapters

    model, params = _built("gqa")
    if kept == "quantized":
        options = {"weight_dtype": "int8"}
    else:
        with_lora = LlamaForCausalLM(
            dataclasses.replace(CONFIGS["gqa"], lora_rank=2)
        ).init(
            jax.random.key(3), jnp.zeros((1, PROMPT_LEN), jnp.int32)
        )["params"]
        options = {"adapters": {"t0": extract_adapters(with_lora)}}
    session = ServeSession.from_model(
        model, params, prompt_len=PROMPT_LEN, num_slots=SLOTS, **options
    )
    assert not _turned_nodes(session.engine.params)
    assert _gauges() == (0, 0)


def test_no_chip_is_found_under_host_arrays_a_mesh_or_another_backend():
    model, params = _built("gqa")
    # The sandbox's arrays lie on one CPU device: one device, no chip.
    assert weights.chip_of(params) is None
    assert weights.held(params) == (params, 0, 0)
    assert weights.chip_of(jax.tree.map(np.asarray, params)) is None
    mesh = Mesh(np.array(jax.devices()[:2]), ("tp",))
    spread = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    assert weights.chip_of(spread) is None
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
    )
    assert weights.chip_of(shapes) is None


def test_turn_counts_and_keeps_shapes_alone_as_shapes():
    _, params = _built("mla-low-rank-query")
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
    )
    tree, leaves, nbytes = turn(shapes)
    nodes = _turned_nodes(tree)
    assert leaves == len(nodes) == 2 * CONFIGS["mla-low-rank-query"].num_layers
    assert nbytes == sum(4 * node.value.size for node in nodes)
    assert all(
        isinstance(node.value, jax.ShapeDtypeStruct) for node in nodes
    )
    declared = jax.eval_shape(as_declared, tree)
    assert jax.tree.map(lambda a: a.shape, declared) == jax.tree.map(
        lambda a: a.shape, shapes
    )
