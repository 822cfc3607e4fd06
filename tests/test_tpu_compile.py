"""The main path's Pallas kernels compile for the chip — asked of the
TPU's own compiler, with no chip attached.

libtpu compiles for a DESCRIBED topology (``v5e:2x2``), so each case
lowers a kernel at the width the models run it at (BERT-base b256 s128:
768/3072; Llama-3.2-1B: 2048/8192, vocab 128,256, decode rows 8 and
prefill rows 4096) and hands it to the compiler the chip uses. This is
what interpret mode cannot show: a block the tiling refuses, a call that
outgrows VMEM, a primitive with no Mosaic lowering. A compile that
passes is a compile, not a chip run — ``chip_smoke.py`` is the chip run.

Plus the CPU rehearsal of ``chip_smoke.py``: its phases at a tiny size,
and its device check refusing a CPU.
"""

import functools
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = pathlib.Path(__file__).resolve().parents[1]


@functools.lru_cache(maxsize=1)
def _v5e_device():
    """One device of a described v5e host, or None where this
    installation cannot describe it (no libtpu, or another process
    holds it)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception:  # no TPU compiler here: nothing to ask
        return None
    return topo.devices[0]


_s = jax.ShapeDtypeStruct
bf16, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32


def _placed(tree, sharding):
    """The shapes of ``tree``, placed: there is no device to hold an
    array, so the compiler is handed shapes."""
    return jax.tree.map(
        lambda a: _s(a.shape, a.dtype, sharding=sharding), tree
    )


def _grad(fn, argnums):
    def loss(*args):
        return sum(
            jnp.sum(leaf.astype(jnp.float32))
            for leaf in jax.tree.leaves(fn(*args))
        )

    return jax.grad(loss, argnums=argnums)


def _layer_norm_residual():
    from tpudl.ops.norms import layer_norm

    x, p = _s((256, 128, 768), bf16), _s((768,), f32)
    fn = lambda x, r, s, b: layer_norm(  # noqa: E731
        x, s, b, r, return_sum=False, impl="fused", interpret=False
    )
    return _grad(fn, (0, 1, 2, 3)), (x, x, p, p)


def _cross_entropy(shape, dtype):
    from tpudl.ops.cross_entropy import softmax_cross_entropy

    fn = lambda z, y: softmax_cross_entropy(  # noqa: E731
        z, y, impl="fused", interpret=False
    )
    return _grad(fn, (0,)), (_s(shape, dtype), _s(shape[:-1], i32))


def _bias_gelu():
    from tpudl.ops.mlp_fused import bias_gelu

    fn = lambda x, b: bias_gelu(x, b, impl="fused", interpret=False)  # noqa: E731
    return _grad(fn, (0, 1)), (_s((256, 128, 3072), bf16), _s((3072,), f32))


def _fused_attention():
    from tpudl.ops.fused_attention import fused_attention

    qkv = _s((256, 128, 12, 64), bf16)
    fn = lambda q, k, v, m: fused_attention(  # noqa: E731
        q, k, v, mask=m, interpret=False
    )
    return _grad(fn, (0, 1, 2)), (qkv, qkv, qkv, _s((256, 128), i32))


def _softmax_dropout():
    from tpudl.ops.softmax_dropout import softmax_dropout

    def fn(z, m, key):
        return softmax_dropout(
            z, mask=m, dropout_rate=0.1, dropout_rng=key, interpret=False
        )

    return _grad(fn, (0,)), (
        _s((256, 12, 128, 128), bf16), _s((256, 128), i32),
        _s((), jax.random.key(0).dtype),
    )


def _rms_norm(rows, backward):
    from tpudl.ops.norms import rms_norm

    x = _s((*rows, 2048), bf16)
    fn = lambda x, r, s: rms_norm(  # noqa: E731
        x, s, r, impl="fused", interpret=False
    )
    return (_grad(fn, (0, 1, 2)) if backward else fn), (
        x, x, _s((2048,), f32)
    )


def _swiglu(rows, backward):
    from tpudl.ops.mlp_fused import swiglu

    x = _s((*rows, 8192), bf16)
    fn = lambda g, u: swiglu(g, u, impl="fused", interpret=False)  # noqa: E731
    return (_grad(fn, (0, 1)) if backward else fn), (x, x)


def _segmented_lora(pool_dtype):
    from tpudl.ops.segmented_lora import segmented_lora

    pools = {"a": _s((64, 2048), pool_dtype), "b": _s((64, 8192), pool_dtype)}
    if pool_dtype == jnp.int8:
        pools["a_scale"] = pools["b_scale"] = _s((64,), f32)
    fn = lambda x, pools, t, s: segmented_lora(  # noqa: E731
        x, pools, t, s, impl="fused", interpret=False
    )
    return fn, (
        _s((8, 2048), bf16), pools, _s((8, 8), i32), _s((8,), f32),
    )


def _flash_attention():
    from tpudl.ops.flash_attention import flash_attention

    qkv = _s((2, 2048, 8, 64), bf16)
    fn = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=False
    )
    return _grad(fn, (0, 1, 2)), (qkv, qkv, qkv)


#: Mistral-7B-v0.3's widths and the serving cells' session
#: (perfbench/configs/mistral-7b-v0.3-l16.json), two layers deep: what
#: is asked is asked of each layer alike.
POOL_SLOTS, POOL_WINDOW, POOL_SEQ, POOL_PAGE = 48, 512, 1024, 16
POOL_SHAPE = (POOL_SLOTS * POOL_SEQ // POOL_PAGE + 1, POOL_PAGE, 8, 128)


def _paged_attention(chunk):
    """Mistral-7B's decode attention over the serving cells' pool: 48
    slots x 64 pages of 16 positions, 8 KV heads of 128; ``chunk`` 1 is
    the decode step, 3 a speculative-verify window."""
    from tpudl.models.paged import PagedView
    from tpudl.ops.paged_attention import paged_attention

    def fn(q, pages_k, pages_v, table, start, lens):
        view = PagedView(table, start, lens, POOL_PAGE, False)
        return paged_attention(
            q, pages_k, pages_v, view, impl="fused", interpret=False
        )

    pool, vec = _s(POOL_SHAPE, bf16), _s((POOL_SLOTS,), i32)
    return fn, (
        _s((POOL_SLOTS, chunk, 32, 128), bf16), pool, pool,
        _s((POOL_SLOTS, POOL_SEQ // POOL_PAGE), i32), vec, vec,
    )


def _grouped_matmul(
    experts, per_token, k, n, result, rows=4096, by_index=False
):
    """One grouped matmul of a sorted expert layer over a 4,096-row
    prefill's assignments: gate / up (``k`` the hidden size, a bfloat16
    result) or down (``k`` the experts' width, a float32 result), the
    down call's rows put at their assignments' places where
    ``by_index`` (PR 45: ``rows_to`` scalar-prefetched, one copy a
    row)."""
    from tpudl.ops.grouped_matmul import grouped_matmul

    specs = (
        _s((rows * per_token, k), bf16), _s((experts, k, n), bf16),
        _s((experts,), i32),
    )
    if by_index:
        fn = lambda lhs, rhs, sizes, places: grouped_matmul(  # noqa: E731
            lhs, rhs, sizes, result, rows_to=places, interpret=False
        )
        return fn, specs + (_s((rows * per_token,), i32),)
    fn = lambda lhs, rhs, sizes: grouped_matmul(  # noqa: E731
        lhs, rhs, sizes, result, interpret=False
    )
    return fn, specs


def _sum_choices(per_token, n, rows=4096):
    """The token's sum over the indexed down call's result."""
    from tpudl.ops.grouped_matmul import sum_choices

    fn = lambda out, held: sum_choices(out, held, interpret=False)  # noqa: E731
    return fn, (
        _s((rows * per_token, 1, n), f32), _s((rows, per_token), jnp.bool_),
    )


def _prefill_attention(rows, heads, dk, dv, choice, kv_heads=None):
    import importlib

    fa = importlib.import_module("tpudl.ops.flash_attention")
    kv_heads = kv_heads or heads

    def fn(q, k, v, valid, chosen=None):
        return fa.prefill_attention(
            q, k, v, valid, 0.0625, chosen, interpret=False
        )

    return fn, (
        _s((1, rows, heads, dk), bf16), _s((1, rows, kv_heads, dk), bf16),
        _s((1, rows, kv_heads, dv), bf16), _s((1, rows), jnp.bool_),
        *([_s((1, rows, rows), jnp.bool_)] if choice else []),
    )


CASES = {
    # BERT-base, b256 s128
    "bert/layer_norm+residual": _layer_norm_residual,
    "bert/cross_entropy": lambda: _cross_entropy((256, 2), f32),
    "bert/bias_gelu": _bias_gelu,
    "bert/fused_attention": _fused_attention,
    "bert/softmax_dropout": _softmax_dropout,
    # Llama-3.2-1B
    "llama/rms_norm-decode": lambda: _rms_norm((8, 1), False),
    "llama/rms_norm-prefill": lambda: _rms_norm((1, 4096), True),
    "llama/swiglu-decode": lambda: _swiglu((8, 1), False),
    "llama/swiglu-prefill": lambda: _swiglu((1, 4096), True),
    "llama/cross_entropy": lambda: _cross_entropy((4, 512, 128256), bf16),
    "llama/segmented_lora-f32": lambda: _segmented_lora(f32),
    "llama/segmented_lora-int8": lambda: _segmented_lora(jnp.int8),
    "llama/flash_attention": _flash_attention,
    # Mistral-7B-v0.3, the serving cells' pool
    "mistral/paged_attention-decode": lambda: _paged_attention(1),
    "mistral/paged_attention-verify": lambda: _paged_attention(3),
    # The sorted experts of xing4 (64 of [3584, 1024], 4 a token) and
    # of Laguna (256 of [2048, 512], 8 a token)
    "xing4/moe_grouped_matmul-gate": lambda: _grouped_matmul(
        64, 4, 3584, 1024, bf16),
    "xing4/moe_grouped_matmul-down": lambda: _grouped_matmul(
        64, 4, 1024, 3584, f32),
    "laguna/moe_grouped_matmul-gate": lambda: _grouped_matmul(
        256, 8, 2048, 512, bf16),
    "laguna/moe_grouped_matmul-down": lambda: _grouped_matmul(
        256, 8, 512, 2048, f32),
    # ... and the down call whose rows go back to assignment order by
    # the kernel's own copies, at the three served shapes: GLM's share
    # (16 experts of [2048, 6144] held, 8 a token, 8,192 rows: a
    # float32 [65536, 1, 6144] result walked in three column tiles)
    "glm/moe_grouped_matmul-down-by-index": lambda: _grouped_matmul(
        16, 8, 2048, 6144, f32, rows=8192, by_index=True),
    "xing4/moe_grouped_matmul-down-by-index": lambda: _grouped_matmul(
        64, 4, 1024, 3584, f32, by_index=True),
    "laguna/moe_grouped_matmul-down-by-index": lambda: _grouped_matmul(
        256, 8, 512, 2048, f32, by_index=True),
    # ... whose rows the second kernel sums a token
    "glm/moe_sum_choices": lambda: _sum_choices(8, 6144, rows=8192),
    "xing4/moe_sum_choices": lambda: _sum_choices(4, 3584),
    "laguna/moe_sum_choices": lambda: _sum_choices(8, 2048),
    # The long latent prefill's attention, one call a layer (PR 46):
    # GLM's 8,192 rows x 64 heads, keys 192 + 64 and values 256, under
    # the indexer's choice; xing4's 4,096 x 32, keys 128 + 64 padded to
    # 256 and values 128. Lowers, and its tiles fit VMEM.
    "glm/prefill_attention": lambda: _prefill_attention(
        8192, 64, 256, 256, True),
    "xing4/prefill_attention": lambda: _prefill_attention(
        4096, 32, 256, 128, False),
    # ... and the full layers of the grouped-query prefills at their
    # cells' longer length (PR 48), a group's heads a grid step: MiMo's
    # (64 query heads on 4 KV heads, keys 192 beside values 128) and
    # Laguna's (48 on 8). Their sliding layers keep the XLA blocks.
    "mimo/prefill_attention": lambda: _prefill_attention(
        16384, 64, 192, 128, False, kv_heads=4),
    "laguna/prefill_attention": lambda: _prefill_attention(
        4096, 48, 128, 128, False, kv_heads=8),
}


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache without one (the next run would warn): off around
    these, whatever the session set."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, no_compile_cache):
    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    fn, specs = CASES[name]()
    args = _placed(specs, SingleDeviceSharding(device))
    compiled = jax.jit(fn).lower(*args).compile()
    # The kernel is IN the program: neither interpreted nor swapped
    # for the composite.
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The page pool changes hands: the session's own pool programs, at the
# benchmark's pool size, write in place on the chip.
# ---------------------------------------------------------------------------


def _pool_session():
    """A session over shapes alone (nothing is run), its own pool as
    small as a pool may be: the programs are lowered at POOL_SHAPE."""
    from tpudl.models.generate import prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
    from tpudl.serve import ServeSession

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=32768, hidden_size=4096, num_layers=2, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, max_seq_len=POOL_SEQ,
        rope_theta=1e6, dtype=bf16,
    ))
    ids = _s((1, POOL_WINDOW), i32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    session = ServeSession.from_model(
        model, params, prompt_len=POOL_WINDOW, num_slots=POOL_SLOTS,
        page_size=POOL_PAGE,
        num_pages=POOL_SEQ // POOL_PAGE + 1,
    )
    _, row = jax.eval_shape(prefill_fn(model), params, ids, ids)
    return session, params, row


pool_session = pytest.fixture(scope="module")(_pool_session)


def _pool_program(name, session, params, row, on_chip):
    cache = session.engine.cache
    pool = jax.tree.map(
        lambda leaf: _s(POOL_SHAPE, leaf.dtype, sharding=on_chip),
        cache.cache,
    )
    vec = _s((POOL_SLOTS,), i32, sharding=on_chip)
    if name == "decode":
        table = _s((POOL_SLOTS, POOL_SEQ // POOL_PAGE), i32,
                   sharding=on_chip)
        return session.engine.decode_call.lower(
            _placed(params, on_chip), pool, vec, vec, table, vec, vec
        )
    pages = POOL_WINDOW // POOL_PAGE
    return cache._seat_program(pages).lower(
        pool, _placed(row, on_chip), _s((pages,), i32, sharding=on_chip)
    )


@pytest.mark.parametrize("name", ["decode", "seat"])
def test_pool_program_writes_in_place_on_v5e(
    name, pool_session, no_compile_cache
):
    """What ISSUE 25 removed stays removed: the chip's compiler aliases
    every pool leaf to its successor and makes no pool-shaped copy (the
    parent's decode held 32 a step at 16 layers, its seat 33)."""
    import math
    import re

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    session, params, row = pool_session
    compiled = _pool_program(
        name, session, params, row, SingleDeviceSharding(device)
    ).compile()
    leaves = jax.tree.leaves(session.engine.cache.cache)
    pool_bytes = len(leaves) * math.prod(POOL_SHAPE) * 2
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
    shape = ",".join(map(str, POOL_SHAPE))
    copies = re.findall(
        rf"= bf16\[{shape}\][^ ]* copy(?:-start|-done)?\(",
        compiled.as_text(),
    )
    assert not copies


def test_decode_program_reads_the_pool_in_place_on_v5e(
    monkeypatch, no_compile_cache
):
    """ISSUE 27: on the chip the session's decode program attends
    through the kernel (the sandbox's backend is the CPU, so the one
    question ``is_tpu_backend`` is answered for it here, in the test),
    still takes the pool donated with no copy of its shape, and has no
    operation left under the scope ``kv_gather``."""
    import math
    import re

    import tpudl.ops.attention
    import tpudl.ops.paged_attention

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    session, params, row = _pool_session()
    compiled = _pool_program(
        "decode", session, params, row, SingleDeviceSharding(device)
    ).compile()
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (True, True)
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "paged_attention" in text and "kv_gather" not in text
    leaves = jax.tree.leaves(session.engine.cache.cache)
    pool_bytes = len(leaves) * math.prod(POOL_SHAPE) * 2
    assert compiled.memory_analysis().alias_size_in_bytes == pool_bytes
    shape = ",".join(map(str, POOL_SHAPE))
    assert not re.findall(
        rf"= bf16\[{shape}\][^ ]* copy(?:-start|-done)?\(", text
    )
    # Nothing of a slot's whole logical view is made any more.
    assert f"bf16[{POOL_SLOTS},{POOL_SEQ}," not in text


# ISSUE 39: no serving program turns a weight over in every call. The
# two-layer Mistral-width session is built by ``from_model`` over shapes
# placed on the described chip, as a cell's is over arrays: its own tree
# holds q_proj, k_proj and v_proj turned (tpudl.models.turned), and the
# decode program and a 256-row prefill compiled from that tree have no
# weight-shaped ``copy`` in their ENTRY; handed the weights as the
# parent handed them (the declared tree) the same programs have three a
# layer, which is how the test is known to see the thing. And the rule
# that names those kernels is held to the compiler's own answer.


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_no_serving_program_turns_a_weight_over_on_v5e(
    name, monkeypatch, no_compile_cache
):
    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
    from tpudl.models.turned import turned_nodes
    from tpudl.serve import ServeSession
    from tpudl.serve.weights import asked_layouts, weight_copies

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    on_chip = SingleDeviceSharding(device)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=32768, hidden_size=4096, num_layers=2, num_heads=32,
        num_kv_heads=8, intermediate_size=14336, max_seq_len=POOL_SEQ,
        rope_theta=1e6, dtype=bf16,
    ))
    declared = jax.tree.map(
        lambda a: _s(a.shape, bf16, sharding=on_chip),
        jax.eval_shape(
            model.init, jax.random.key(0), _s((1, POOL_WINDOW), i32)
        )["params"],
    )
    session = ServeSession.from_model(
        model, declared, prompt_len=POOL_WINDOW, num_slots=POOL_SLOTS,
        page_size=POOL_PAGE, num_pages=POOL_SEQ // POOL_PAGE + 1,
    )
    engine = session.engine
    if name == "decode":
        vec = _s((POOL_SLOTS,), i32, sharding=on_chip)
        rest = (
            _placed(engine.cache.cache, on_chip), vec, vec,
            *_placed(engine.cache.dispatch_args(), on_chip),
        )
        program = engine.decode_call
    else:
        rest = (_s((1, POOL_WINDOW // 2), i32, sharding=on_chip),) * 2
        program = engine.prefill_call
    held = program.lower(engine.params, *rest).compile()
    assert weight_copies(held.as_text(), declared) == []
    given = program.lower(declared, *rest).compile()
    assert sorted(weight_copies(given.as_text(), declared)) == (
        4 * ["bf16[1024,4096] copy"] + 2 * ["bf16[4096,4096] copy"]
    )
    if name == "decode":
        # Asked, the compiler wants exactly the kernels the session
        # holds turned the other way round, and no other matrix.
        turned = set(turned_nodes(engine.params))
        asked = asked_layouts(
            program.__wrapped__, declared, rest, donate_argnums=(1,)
        )
        assert {path for path, _, _ in asked} == turned
        assert len(turned) == 6
        assert {order for _, _, order in asked} == {(1, 0)}


# The latent (MLA) pool of ISSUE 26 at its cell's size: sarvam-105b's
# widths (perfbench/configs/sarvam-105b-l5-e32.json), one dense and one
# expert layer deep. The decode and seat programs compile for the chip,
# take ONE donated pool a layer and fit its memory. Since ISSUE 29 the
# pool is HELD in the shape the chip lays out major-to-minor with a page
# contiguous (tpudl.models.paged.page_fold: a row of 576 is 4.5 lanes of
# 128, and [NP, 16, 576] would be laid page-index-minor and re-laid on
# the way in and out of every program; two positions a held row,
# [NP, 8, 1152], is not). So the test holds what the k / v pool's test
# holds: each leaf enters and leaves major-to-minor, is aliased whole,
# and no copy of its shape is in the compiled text.
LATENT_SLOTS, LATENT_WINDOW, LATENT_SEQ = 128, 512, 1280
LATENT_SHAPE = (
    LATENT_SLOTS * LATENT_SEQ // POOL_PAGE + 1, POOL_PAGE // 2, 2 * 576
)


def _latent_program(name, on_chip):
    """The session of the sarvam cell's shapes over shapes alone, and
    its decode or seat program lowered at LATENT_SHAPE."""
    from tpudl.models.generate import prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM, RopeScaling
    from tpudl.serve import ServeSession

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=65536, hidden_size=4096, num_layers=2, num_heads=64,
        num_kv_heads=64, intermediate_size=16384, max_seq_len=LATENT_SEQ,
        rope_theta=10000.0, rms_norm_eps=1e-6, dtype=bf16, attention="mla",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128,
        rope_scaling=RopeScaling(40.0, 4096, mscale=1.0, mscale_all_dim=1.0),
        num_experts=128, experts_per_token=8, moe_intermediate_size=2048,
        num_shared_experts=1, routed_scaling_factor=2.5, first_k_dense=1,
        experts_held=(0, 32),
    ))
    ids = _s((1, LATENT_WINDOW), i32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    session = ServeSession.from_model(
        model, params, prompt_len=LATENT_WINDOW, num_slots=LATENT_SLOTS,
        page_size=POOL_PAGE,
        num_pages=LATENT_SEQ // POOL_PAGE + 1,
    )
    cache = session.engine.cache
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == 2  # ONE pool a layer
    assert all(leaf.shape[1:] == LATENT_SHAPE[1:] for leaf in leaves)
    pool = jax.tree.map(
        lambda leaf: _s(LATENT_SHAPE, leaf.dtype, sharding=on_chip),
        cache.cache,
    )
    vec = _s((LATENT_SLOTS,), i32, sharding=on_chip)
    if name == "decode":
        table = _s((LATENT_SLOTS, LATENT_SEQ // POOL_PAGE), i32,
                   sharding=on_chip)
        return session, session.engine.decode_call.lower(
            _placed(params, on_chip), pool, vec, vec, table, vec, vec
        )
    _, row, _ = jax.eval_shape(prefill_fn(model), params, ids, ids)
    pages = LATENT_WINDOW // POOL_PAGE
    return session, cache._seat_program(pages).lower(
        pool, _placed(row, on_chip), _s((pages,), i32, sharding=on_chip)
    )


def _held_pool_stays_put(compiled):
    """Both pools are donated whole (1,152 bytes a position a layer, as
    declared), each enters and leaves laid major-to-minor, nothing of
    their shape is copied, and the weights of two layers, the pools and
    the step's temporaries fit the chip. -> the compiled text."""
    import re

    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == 2 * 10241 * 16 * 576 * 2
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9
    text = compiled.as_text()
    shape = ",".join(map(str, LATENT_SHAPE))
    boundary = re.search(r"entry_computation_layout=\{(.*)", text).group(1)
    assert re.findall(rf"bf16\[{shape}\]\{{([\d,]+)", boundary) == ["2,1,0"] * 4
    assert not re.findall(
        rf"= bf16\[{shape}\][^ ]* copy(?:-start|-done)?\(", text
    )
    return text


@pytest.mark.parametrize("name", ["decode", "seat"])
def test_latent_pool_program_compiles_for_v5e(name, no_compile_cache):
    """The programs as the sandbox's backend chooses them: the decode
    program through the gather (the path an int8 latent pool keeps on
    the chip), the seat."""
    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    session, lowered = _latent_program(name, SingleDeviceSharding(device))
    _held_pool_stays_put(lowered.compile())
    if name == "decode":
        took = session.engine.decode_call.__wrapped__.attention_in_place
        assert took == (False, False)


def test_latent_decode_program_reads_the_pool_in_place_on_v5e(
    monkeypatch, no_compile_cache
):
    """ISSUE 31: on the chip the latent decode program attends through
    the latent kernel (``is_tpu_backend`` is answered for it here, as
    for the k / v pool above): one kernel call a layer takes the HELD
    pool as it lies (no copy of its shape, still donated whole),
    nothing is left under ``kv_gather`` and nothing of a slot's whole
    view ([128, 640, 1152] held rows, or 1,280 positions) is made."""
    import tpudl.ops.attention
    import tpudl.ops.paged_attention

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    session, lowered = _latent_program("decode", SingleDeviceSharding(device))
    text = _held_pool_stays_put(lowered.compile())
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (True, True)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "latent_paged_attention" in text and "kv_gather" not in text
    held_view = LATENT_SEQ // 2
    assert f"[{LATENT_SLOTS},{held_view}," not in text
    assert f"[{LATENT_SLOTS},{LATENT_SEQ // POOL_PAGE}," not in text.replace(
        f"s32[{LATENT_SLOTS},{LATENT_SEQ // POOL_PAGE}]", ""
    )


# Laguna-XS.2's five-layer stage of ISSUE 30 at its cell's size
# (perfbench/configs/laguna-xs2-l5.json through its family's own
# ``model_config``): two full-context layers of 48 heads over a table of
# 320 pages a slot, three window layers of 64 heads over rings of 33.
# What the k / v pool's test holds for Mistral holds here for BOTH
# groups: every layer's attention is the in-place kernel, each pool leaf
# is aliased whole, nothing of a pool's shape is copied and nothing of a
# slot's whole logical view (table or ring) is made. The batch-1
# prefill at a window of 4,096 attends in blocks and dispatches its
# experts by sorted groups: its temporaries stay far under what one
# dense pass would take ([64, 4096, 5120] float32 alone is 5.4 GB, two
# [256, 4096, 512] expert tensors 2.1 GB).


def _on_one_chip(monkeypatch):
    """The sorted experts' rule (tpudl.ops.grouped_matmul) answered as
    the chip machine answers it: a TPU backend of one device."""
    import tpudl.ops.grouped_matmul as gm

    monkeypatch.setattr(gm, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(gm, "one_device", lambda: True)


def _prefill_kernel_calls(text: str, scopes=("mla_core",)) -> int:
    """The calls of the prefill attention kernel in a compiled
    program's text, each inside one of the scopes ``scopes``."""
    found = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and re.match(r"\s*(ROOT )?%prefill_attention[. ]", line)
    ]
    assert all(any(f"/{s}/" in line for s in scopes) for line in found)
    return len(found)


def _copies_into_prefill_kernel(text: str, elements: int) -> list:
    """The operands of a compiled program's prefill-kernel calls that a
    copy or a transpose of at least ``elements`` values wrote (looked
    up through bitcasts and moves between memories): what XLA puts in
    front of a kernel whose operand it holds the other way round (PR
    46: three a layer, 3.7 ms each at GLM's shape)."""
    import math

    made = {}
    for line in text.splitlines():
        hit = re.match(
            r"\s*(?:ROOT )?%(\S+) = (?:\w+\[([\d,]*)\]\S* )?([\w-]+)\((.*)", line)
        if hit:
            made[hit[1]] = (hit[2], hit[3], hit[4])
    found = []
    for name, (_, op, rest) in made.items():
        if op != "custom-call" or not name.startswith("prefill_attention"):
            continue
        for operand in re.findall(r"%([\w.-]+)", rest.split(")")[0]):
            while made.get(operand, ("", "", ""))[1] in (
                    "bitcast", "copy-done", "copy-start"):
                operand = re.match(r"%([\w.-]+)", made[operand][2])[1]
            shape, op, _ = made.get(operand, ("", "", ""))
            size = math.prod(map(int, shape.split(","))) if shape else 0
            turned = op in ("copy", "transpose") or re.match(
                r"(copy|transpose)", operand)
            if turned and size >= elements:
                found.append(f"{name} <- %{operand} [{shape}] {op}")
    assert any(n.startswith("prefill_attention") for n in made)
    return found


def _grouped_kernel_calls(text: str) -> int:
    """The calls of the grouped-matmul kernel in a compiled program's
    text, each inside the scope ``experts``; no ``ragged-dot`` is left
    beside them, and a layer's three are followed by ONE call of the
    kernel that sums a token's rows (PR 45: the down call's rows go
    back by index)."""
    def calls(kernel):
        found = [
            line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and re.match(rf"\s*(ROOT )?%{kernel}[. ]", line)
        ]
        assert all("/experts/" in line for line in found)
        return len(found)

    assert "ragged-dot" not in text
    assert 3 * calls("moe_sum_choices") == calls("moe_grouped_matmul")
    return calls("moe_grouped_matmul")


def _window_moe_session():
    import json

    from perfbench.families import window_moe_serve as family
    from perfbench.reference import window_moe as ref
    from tpudl.models.llama import LlamaForCausalLM
    from tpudl.serve import ServeSession

    with open(REPO / "perfbench/configs/laguna-xs2-l5.json") as f:
        cfg = json.load(f)
    sess = cfg["session"]
    model = LlamaForCausalLM(
        family.model_config(cfg, sess["max_seq_len"], bf16)
    )
    s = ref.settings(cfg)
    key = jax.eval_shape(lambda: ref.seed_key(0))
    params = jax.eval_shape(
        lambda k: family.to_flax(ref.all_weights(k, s, bf16), s), key
    )
    session = ServeSession.from_model(
        model, params, sess["prompt_window"], num_slots=sess["num_slots"],
        page_size=sess["page_size"],
        num_pages=sess["max_seq_len"] // sess["page_size"] + 1,
    )
    return sess, model, params, session


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_window_moe_program_compiles_for_v5e(
    name, monkeypatch, no_compile_cache
):
    import math
    import re

    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.models.generate import prefill_fn

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    _on_one_chip(monkeypatch)
    on_chip = SingleDeviceSharding(device)
    sess, model, params, session = _window_moe_session()
    slots, page = sess["num_slots"], sess["page_size"]
    if name == "prefill":
        ids = _s((1, sess["prompt_window"]), i32, sharding=on_chip)
        compiled = jax.jit(prefill_fn(model)).lower(
            _placed(params, on_chip), ids, ids
        ).compile()
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes < 1.5e9
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
        # The rule has no shape threshold: the three grouped matmuls
        # of the four expert layers are the kernel, as in xing4's.
        text = compiled.as_text()
        assert _grouped_kernel_calls(text) == 3 * 4
        # Since PR 48 the attention of the two FULL layers is the
        # prefill kernel (48 query heads on 8 KV heads, a group's heads
        # sharing each key tile); the three sliding layers keep the XLA
        # blocks (the rule excludes a window: PERF.md §6, PR 48). No
        # [1, rows, rows] mask is built, and XLA feeds the kernel no
        # copy as large as its query (4,096 x 48 x 128).
        rows = sess["prompt_window"]
        assert _prefill_kernel_calls(text, ("full_attention",)) == 2
        assert not re.search(rf"(?:s8|pred)\[1,{rows},{rows}\]", text)
        assert not _copies_into_prefill_kernel(text, rows * 48 * 128)
        return
    cache = session.engine.cache
    assert cache.ring_pages == 33
    table_pages = sess["max_seq_len"] // page
    shapes = {
        False: (slots * table_pages + 1, page, 8, 128),
        True: (slots * cache.ring_pages + 1, page, 8, 128),
    }
    pool = jax.tree.map(
        lambda leaf: _s(
            shapes[leaf.shape[0] == cache.num_ring_pages], leaf.dtype,
            sharding=on_chip,
        ),
        cache.cache,
    )
    vec = _s((slots,), i32, sharding=on_chip)
    tables = (
        _s((slots, table_pages), i32, sharding=on_chip),
        _s((slots, cache.ring_pages), i32, sharding=on_chip),
    )
    compiled = session.engine.decode_call.lower(
        _placed(params, on_chip), pool, vec, vec, tables, vec, vec
    ).compile()
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (True,) * 5
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5
    assert "paged_attention" in text and "kv_gather" not in text
    memory = compiled.memory_analysis()
    pool_bytes = 2 * 2 * (
        2 * math.prod(shapes[False]) + 3 * math.prod(shapes[True])
    )
    assert memory.alias_size_in_bytes == pool_bytes
    assert 3.0e9 < pool_bytes < 3.2e9  # one table for all: 6.7 GB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
    for shape in shapes.values():
        dims = ",".join(map(str, shape))
        assert not re.findall(
            rf"= bf16\[{dims}\][^ ]* copy(?:-start|-done)?\(", text
        )
    # Nothing of a slot's whole logical view, table or ring.
    assert f"bf16[{slots},{sess['max_seq_len']}," not in text
    assert f"bf16[{slots},{cache.ring_pages * page}," not in text


# LongCat-Flash-Chat's four double layers of ISSUE 33 at its cell's size
# (perfbench/configs/longcat-flash-l4-e16.json through its family's own
# ``model_config``): EIGHT latent pools (two a layer) of the sarvam
# cell's held shape under one table, 192 slots of 1,536 positions. The
# decode program attends all eight in place through the latent kernel,
# takes every pool donated and copies none; 10.35 GB of weights, 2.72 GB
# of pools and the step's temporaries fit the chip. The batch-1 prefill
# at a window of 512 fits beside them.


def _shortcut_moe_session():
    import json

    from perfbench.families import shortcut_moe_serve as family
    from perfbench.reference import shortcut_moe as ref
    from tpudl.models.llama import LlamaForCausalLM
    from tpudl.serve import ServeSession

    with open(REPO / "perfbench/configs/longcat-flash-l4-e16.json") as f:
        cfg = json.load(f)
    sess = cfg["session"]
    model = LlamaForCausalLM(
        family.model_config(cfg, sess["max_seq_len"], bf16)
    )
    s = ref.settings(cfg)
    key = jax.eval_shape(lambda: ref.seed_key(0))
    params = jax.eval_shape(
        lambda k: family.to_flax(ref.all_weights(k, s, bf16), s), key
    )
    session = ServeSession.from_model(
        model, params, sess["prompt_window"], num_slots=sess["num_slots"],
        page_size=sess["page_size"],
        num_pages=sess["max_seq_len"] // sess["page_size"] + 1,
    )
    return sess, model, params, session


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_shortcut_moe_program_compiles_for_v5e(
    name, monkeypatch, no_compile_cache
):
    import math
    import re

    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.models.generate import prefill_fn

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    on_chip = SingleDeviceSharding(device)
    sess, model, params, session = _shortcut_moe_session()
    slots, page = sess["num_slots"], sess["page_size"]
    weights = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params)
    )
    assert 10.3e9 < weights < 10.4e9
    if name == "prefill":
        ids = _s((1, sess["prompt_window"]), i32, sharding=on_chip)
        compiled = jax.jit(prefill_fn(model)).lower(
            _placed(params, on_chip), ids, ids
        ).compile()
        memory = compiled.memory_analysis()
        # Beside the pools (2.72 GB) on a chip of 16 GB.
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12.5e9
        return
    cache = session.engine.cache
    table_pages = sess["max_seq_len"] // page
    shape = (slots * table_pages + 1, page // 2, 2 * 576)
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == 8 and cache.folds == (2,) * 8
    assert all(leaf.shape[1:] == shape[1:] for leaf in leaves)
    pool = jax.tree.map(
        lambda leaf: _s(shape, leaf.dtype, sharding=on_chip), cache.cache
    )
    vec = _s((slots,), i32, sharding=on_chip)
    table = _s((slots, table_pages), i32, sharding=on_chip)
    compiled = session.engine.decode_call.lower(
        _placed(params, on_chip), pool, vec, vec, table, vec, vec
    ).compile()
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (True,) * 8
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 8
    assert "latent_paged_attention" in text and "kv_gather" not in text
    memory = compiled.memory_analysis()
    pool_bytes = 8 * math.prod(shape) * 2
    assert memory.alias_size_in_bytes == pool_bytes
    assert 2.7e9 < pool_bytes < 2.75e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15e9
    dims = ",".join(map(str, shape))
    assert not re.findall(
        rf"= bf16\[{dims}\][^ ]* copy(?:-start|-done)?\(", text
    )
    # Nothing of a slot's whole logical view (held rows or positions).
    assert f"[{slots},{sess['max_seq_len'] // 2}," not in text
    # An identity choice reaches no expert matmul: the grouped matmuls
    # are over the 16 experts held, whatever the router's width.
    assert "bf16[16,192,2048]" in text and "bf16[768," not in text


# Xing4.0-29B-A4B's stage 0 of ISSUE 38 at its cell's size
# (perfbench/configs/xing4-29b-a4b-l6.json through its family's own
# ``model_config``): a residual of four streams a token, six latent
# pools of the sarvam cell's held shape, 64 slots of 4,352 positions
# (272 pages a slot: nine blocks of the latent kernel), 32 heads, all 64
# experts of five layers held. The decode program attends all six pools
# in place, takes them donated and copies none; 9.60 GB of weights,
# 1.93 GB of pools and a step's temporaries fit the chip. The batch-1
# prefill at 2,048 rows (the half of the window: the ladder's shorter
# length) attends in blocks and fits beside them.


def _family_session(config: str, name: str):
    """A session of ``perfbench/configs/<config>.json`` at its cell's
    size over shapes alone, through the family ``<name>_serve`` and the
    weights its reference ``<name>`` makes: ``(session shapes, model,
    params, session)``."""
    import importlib
    import json

    from tpudl.models.llama import LlamaForCausalLM
    from tpudl.serve import ServeSession

    family = importlib.import_module(f"perfbench.families.{name}_serve")
    ref = importlib.import_module(f"perfbench.reference.{name}")
    with open(REPO / f"perfbench/configs/{config}.json") as f:
        cfg = json.load(f)
    sess = cfg["session"]
    model = LlamaForCausalLM(
        family.model_config(cfg, sess["max_seq_len"], bf16)
    )
    s = ref.settings(cfg)
    key = jax.eval_shape(lambda: ref.seed_key(0))
    params = jax.eval_shape(
        lambda k: family.to_flax(ref.all_weights(k, s, bf16), s), key
    )
    session = ServeSession.from_model(
        model, params, sess["prompt_window"], num_slots=sess["num_slots"],
        page_size=sess["page_size"],
        num_pages=sess["max_seq_len"] // sess["page_size"] + 1,
    )
    return sess, model, params, session


def _hyper_mla_moe_session():
    return _family_session("xing4-29b-a4b-l6", "hyper_mla_moe")


@pytest.mark.parametrize("name", ["decode", "prefill", "prefill_4096"])
def test_hyper_mla_moe_program_compiles_for_v5e(
    name, monkeypatch, no_compile_cache
):
    import math
    import re

    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.models.generate import prefill_fn

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    _on_one_chip(monkeypatch)
    on_chip = SingleDeviceSharding(device)
    sess, model, params, session = _hyper_mla_moe_session()
    slots, page = sess["num_slots"], sess["page_size"]
    weights = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params)
    )
    assert 9.59e9 < weights < 9.61e9
    if name.startswith("prefill"):
        rows = 4096 if name == "prefill_4096" else 2048
        ids = _s((1, rows), i32, sharding=on_chip)
        compiled = jax.jit(prefill_fn(model)).lower(
            _placed(params, on_chip), ids, ids
        ).compile()
        memory = compiled.memory_analysis()
        # Beside the pools (1.93 GB) on a chip of 16 GB.
        assert memory.temp_size_in_bytes < 1.5e9
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 11e9
        text = compiled.as_text()
        # No score matrix of the whole prompt, at any precision, and
        # since PR 46 none of a block of 256 queries either: a layer's
        # attention is ONE call of the prefill kernel, its scores in
        # VMEM. (At 4,096 rows a weight has the first shape: the heads
        # name it.) Since PR 48 no [1, rows, rows] mask is built
        # either: no indexer chose, so the kernel makes its own.
        assert not re.search(rf"32,(?:1,)?{rows},{rows}\]", text)
        assert not re.search(rf"(?:s8|pred)\[1,{rows},{rows}\]", text)
        assert "f32[1,32,1,256," not in text
        assert _prefill_kernel_calls(text) == 6
        # The three grouped matmuls of the five expert layers are the
        # kernel, at either prefill length.
        assert _grouped_kernel_calls(text) == 3 * 5
        return
    cache = session.engine.cache
    table_pages = sess["max_seq_len"] // page
    assert table_pages == 272
    shape = (slots * table_pages + 1, page // 2, 2 * 576)
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == 6 and cache.folds == (2,) * 6
    assert all(leaf.shape[1:] == shape[1:] for leaf in leaves)
    pool = jax.tree.map(
        lambda leaf: _s(shape, leaf.dtype, sharding=on_chip), cache.cache
    )
    vec = _s((slots,), i32, sharding=on_chip)
    table = _s((slots, table_pages), i32, sharding=on_chip)
    compiled = session.engine.decode_call.lower(
        _placed(params, on_chip), pool, vec, vec, table, vec, vec
    ).compile()
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (True,) * 6
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert "latent_paged_attention" in text and "kv_gather" not in text
    memory = compiled.memory_analysis()
    pool_bytes = 6 * math.prod(shape) * 2
    assert memory.alias_size_in_bytes == pool_bytes
    assert 1.92e9 < pool_bytes < 1.93e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
    dims = ",".join(map(str, shape))
    assert not re.findall(
        rf"= bf16\[{dims}\][^ ]* copy(?:-start|-done)?\(", text
    )
    # Nothing of a slot's whole logical view (held rows or positions).
    assert f"[{slots},{sess['max_seq_len'] // 2}," not in text
    # Every choice lands on a held expert: the grouped matmuls are over
    # all 64, at the step's 64 rows in the dense form.
    assert "bf16[64,64,1024]" in text


# The looped decoder of ISSUE 40 at its cell's size: Ouro-2.6B's widths
# and FULL depth (perfbench/configs/ouro-2.6b.json: 48 sandwich-normed
# layers run 4 times, 16 query heads over 16 KV heads of 128, 16 slots
# of 256 positions). Unrolled, the decode program has 192 layer bodies:
# it takes the k/v kernel at ONE query head a KV head in every one of
# them (192 calls), takes all 384 pools donated, turns no weight over,
# and fits the chip beside the 5.34 GB of weights; the batch-1 prefill
# at the window's 128 rows turns no weight over either. (Two of the
# 384 pools are carried through VMEM and back by XLA's own choice,
# PERF.md section 7: that is counted here, so that a change of it
# shows.)


def _loop_decoder_session(on_chip, layers=None):
    import json

    from perfbench.families import loop_decoder_serve as family
    from tpudl.models.llama import LlamaForCausalLM
    from tpudl.serve import ServeSession

    with open(REPO / "perfbench/configs/ouro-2.6b.json") as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = layers or cfg["num_hidden_layers"]
    sess = cfg["session"]
    model = LlamaForCausalLM(
        family.model_config(cfg, sess["max_seq_len"], bf16)
    )
    declared = jax.tree.map(
        # The exit gate and its bias are float32, as declared.
        lambda a: _s(a.shape, a.dtype if a.shape[-1] == 1 else bf16,
                     sharding=on_chip),
        jax.eval_shape(
            model.init, jax.random.key(0), _s((1, 8), i32)
        )["params"],
    )
    session = ServeSession.from_model(
        model, declared, sess["prompt_window"], num_slots=sess["num_slots"],
        page_size=sess["page_size"],
    )
    return cfg, declared, session


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_loop_decoder_program_compiles_for_v5e(
    name, monkeypatch, no_compile_cache
):
    import math
    import re

    import tpudl.ops.attention
    import tpudl.ops.paged_attention
    from tpudl.serve.weights import weight_copies

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    on_chip = SingleDeviceSharding(device)
    if name == "prefill":
        # A quarter of the depth (12 layers x 4 passes: 48 bodies, a
        # quarter of the compile): what it shows does not grow with
        # depth but the weights, which are counted at full depth below.
        cfg, declared, session = _loop_decoder_session(on_chip, layers=12)
        sess, engine = cfg["session"], session.engine
        ids = _s((1, sess["prompt_window"]), i32, sharding=on_chip)
        compiled = engine.prefill_call.lower(engine.params, ids, ids).compile()
        memory = compiled.memory_analysis()
        # The program's temporaries (the row cache of 48 (pass, layer)
        # pairs, 128 rows of activations) are under half a GB; at full
        # depth the sandbox compile reads 1.63 GB (PERF.md section 5).
        assert memory.temp_size_in_bytes < 0.5e9
        assert weight_copies(compiled.as_text(), declared) == []
        return
    cfg, declared, session = _loop_decoder_session(on_chip)
    sess, deployment = cfg["session"], cfg["deployment"]
    engine, cache = session.engine, session.engine.cache
    weights = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(declared)
    )
    assert weights == deployment["weight_bytes"] == 5_335_953_412
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == 2 * passes * layers == 384
    pool = (sess["num_slots"] * sess["max_seq_len"] // 16 + 1, 16, 16, 128)
    assert all(leaf.shape == pool for leaf in leaves)
    pool_bytes = 384 * math.prod(pool) * 2
    assert pool_bytes == deployment["cache_bytes"] == 6_467_616_768
    vec = _s((sess["num_slots"],), i32, sharding=on_chip)
    compiled = engine.decode_call.lower(
        engine.params, _placed(cache.cache, on_chip), vec, vec,
        *_placed(cache.dispatch_args(), on_chip),
    ).compile()
    took = engine.decode_call.__wrapped__.attention_in_place
    assert took == (True,) * (passes * layers) and len(took) == 192
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 192
    assert "kv_gather" not in text
    assert weight_copies(text, declared) == []
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.5e9
    # XLA carries these through VMEM and back (a pool under 128 MiB is
    # exposed to that choice, ROADMAP A9): 2 of 384, in and out.
    dims = ",".join(map(str, pool))
    carried = re.findall(rf"copy-start\(%cache__\w*pages_\w+\)", text)
    moved = re.findall(rf"= \(bf16\[{dims}\][^ ]* copy-start\(", text)
    assert len(moved) <= 4 and len(carried) <= 2
    # No plain copy of a pool anywhere.
    assert not re.findall(rf"= bf16\[{dims}\][^ ]* copy\(", text)


# A configuration that sets none of ISSUE 33's keys (the block's kind,
# the low-rank query and its scales, the router's scoring, identity
# experts) builds the programs it built before: the lowered text of a
# grouped-query and of a latent + sigmoid-routed decoder's prefill and
# paged decode, counted at the parent commit (PR 31) and held here. A
# later PR that changes one of these programs on purpose counts again.
_PLAIN = dict(
    vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
    intermediate_size=128, max_seq_len=64, rope_theta=1e4, dtype=bf16,
)
OLD_PROGRAMS = {
    "gqa": (dict(num_kv_heads=2), {"decode": (742, 19), "prefill": (589, 19)}),
    "mla_moe": (
        dict(
            num_kv_heads=4, attention="mla", kv_lora_rank=48,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
            num_experts=8, experts_per_token=2, moe_intermediate_size=32,
            num_shared_experts=1, routed_scaling_factor=2.5, first_k_dense=1,
            experts_held=(2, 4),
        ),
        {"decode": (1090, 29), "prefill": (767, 25)},
    ),
}


@pytest.mark.parametrize("name", sorted(OLD_PROGRAMS))
def test_a_configuration_without_the_new_keys_lowers_the_old_programs(name):
    import re

    from tpudl.models.generate import prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM, RopeScaling
    from tpudl.serve import ServeSession

    keys, want = OLD_PROGRAMS[name]
    if name == "mla_moe":
        keys = dict(keys, rope_scaling=RopeScaling(
            40.0, 16, mscale=1.0, mscale_all_dim=1.0))
    model = LlamaForCausalLM(LlamaConfig(**_PLAIN, **keys))
    ids = _s((1, 16), i32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    session = ServeSession.from_model(
        model, params, 16, num_slots=4, page_size=16
    )
    cache = session.engine.cache
    vec = _s((4,), i32)
    lowered = {
        "decode": session.engine.decode_call.lower(
            params, cache.cache, vec, vec, *cache.dispatch_args()
        ),
        "prefill": jax.jit(prefill_fn(model)).lower(params, ids, ids),
    }
    got = {
        program: (
            len(re.findall(r"stablehlo\.\w+", text)),
            text.count("stablehlo.dot_general"),
        )
        for program, text in (
            (k, v.as_text()) for k, v in lowered.items()
        )
    }
    assert got == want
    # None of the new block's parts is in a tree that did not ask.
    names = "".join(jax.tree_util.keystr(p) for p, _ in
                    jax.tree_util.tree_flatten_with_path(params)[0])
    assert "q_a_proj" not in names and "attention_0" not in names


# ---------------------------------------------------------------------------
# chip_smoke.py on the CPU: the phases at a tiny size (one serve phase,
# on the page pool; one train phase; the two mesh phases), through a
# path only the tests take — the script's own device check is not
# weakened.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TINY_SERVE = dict(
    size="llama-tiny", dtype=jnp.float32, prompt_len=8, max_seq_len=64,
    num_slots=4, max_new=9,
)
TINY_TRAIN = dict(model="bert-tiny", seq_len=16)


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as exit_info:
        chip_smoke.main([])
    assert exit_info.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no phase ran, no result line


def test_chip_smoke_serve_phase_tiny(chip_smoke):
    line = chip_smoke.serve_phase(0, requests_per_wave=4, **TINY_SERVE)
    assert line["phase"] == "serve"
    # f32 on one backend: every request agrees token for token.
    assert line["requests"] == 8 and line["requests_equal_generate"] == 8
    assert line["recompiles_after_warmup"] == 0
    # On a CPU a session holds its weights as given.
    assert line["weights_relaid_leaves"] == line["weights_relaid_bytes"] == 0


def test_chip_smoke_train_phase_tiny(chip_smoke):
    line = chip_smoke.train_phase(
        0, batch=32, warmup=2, steps=22,
        optim={"learning_rate": 3e-3, "warmup_steps": 0}, **TINY_TRAIN,
    )
    assert line["steps"] == 24 and line["last_loss"] < line["first_loss"]
    assert line["recompiles_after_warmup"] == 0


def test_chip_smoke_mesh_phases_tiny(chip_smoke):
    """``--chips 4`` on four of the forced host devices."""
    serve = chip_smoke.mesh_serve_phase(0, n_requests=4, **TINY_SERVE)
    assert serve["requests_equal_one_device"] == 4
    assert serve["requests_equal_generate"] == 4
    assert len(set(serve["param_bytes_per_device"])) == 1
    train = chip_smoke.mesh_train_phase(0, batch=8, **TINY_TRAIN)
    whole = train["one_device"]["state_bytes_per_device"][0]
    assert max(train["fsdp4"]["state_bytes_per_device"]) < whole / 3
    assert train["fsdp4"]["max_abs_loss_diff"] <= chip_smoke.MESH_LOSS_TOL


def test_chip_smoke_phase_failure_is_nonzero(chip_smoke, monkeypatch):
    """A phase that raises ends the script: nothing catches it and
    nothing prints ``ok``."""
    monkeypatch.setattr(
        chip_smoke, "require_tpu",
        lambda chips: {"platform": "tpu", "kind": "test", "count": 1},
    )

    def boom(*args, **kwargs):
        raise RuntimeError("phase failed")

    monkeypatch.setattr(chip_smoke, "serve_phase", boom)
    with pytest.raises(RuntimeError, match="phase failed"):
        chip_smoke.main([])
