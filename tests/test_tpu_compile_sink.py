"""MiMo-V2-Flash's share of ISSUE 47 compiles for the chip at its cell's
size, asked of the TPU's own compiler with no chip attached, as
``tests/test_tpu_compile.py`` asks for the other configurations (whose
helpers this file borrows); and the decode programs of the
configurations that read a k / v pool pair held as declared still take
the kernel that was there. A file of its own: under ``--dist loadfile``
a file is one worker's, and ``test_tpu_compile.py`` is the longest of
the suite.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.test_tpu_compile import (  # noqa: F401  (the fixture)
    REPO,
    _copies_into_prefill_kernel,
    _family_session,
    _prefill_kernel_calls,
    _on_one_chip,
    _placed,
    _s,
    _v5e_device,
    i32,
    no_compile_cache,
)

MERGED = "merged_paged_attention"


def _kernels_taken(monkeypatch):
    import tpudl.ops.attention
    import tpudl.ops.paged_attention

    device = _v5e_device()
    if device is None:
        pytest.skip("this installation cannot describe a v5e topology")
    for module in (tpudl.ops.attention, tpudl.ops.paged_attention):
        monkeypatch.setattr(module, "is_tpu_backend", lambda: True)
    _on_one_chip(monkeypatch)
    return SingleDeviceSharding(device)


# MiMo-V2-Flash's share at its cell's size
# (perfbench/configs/mimo-v2-flash-l7-e16.json through its family's own
# ``model_config``): two full-context layers' pools [25,345, 16, 768]
# and [.., 512] (4 KV heads merged into the lanes, keys 192 and values
# 128 wide) under a table of 1,056 pages a slot, five sliding layers'
# [217, 16, 1536] and [.., 1024] (8 KV heads) as rings of 9 pages, 24
# slots of 16,896 positions. The decode program attends all seven in
# place through the merged kernel (the sliding layers' with a sink),
# takes every pool donated and copies none, and no pool is padded:
# 2,560 and 5,120 B a position a layer.


def test_sink_window_moe_decode_compiles_for_v5e(monkeypatch, no_compile_cache):
    import math
    import re

    on_chip = _kernels_taken(monkeypatch)
    sess, model, params, session = _family_session(
        "mimo-v2-flash-l7-e16", "sink_window_moe")
    slots, page = sess["num_slots"], sess["page_size"]
    weights = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params)
    )
    # 3,429,892,096 matrix parameters at 2 B (the six routers and their
    # biases at 4), 15 norm scales, 5 x 64 sinks.
    assert weights == (
        2 * 3_429_892_096 + 2 * 6 * 4096 * 256 + 2 * 15 * 4096
        + 4 * 5 * 64 + 4 * 6 * 256
    )
    cache = session.engine.cache
    assert (cache.window, cache.ring_pages) == (128, 9)
    table_pages = sess["max_seq_len"] // page
    assert table_pages == 1056
    shapes = {
        (False, "pages_k"): (slots * table_pages + 1, page, 4 * 192),
        (False, "pages_v"): (slots * table_pages + 1, page, 4 * 128),
        (True, "pages_k"): (slots * 9 + 1, page, 8 * 192),
        (True, "pages_v"): (slots * 9 + 1, page, 8 * 128),
    }
    pool = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _s(
            shapes[leaf.shape[0] == cache.num_ring_pages, path[-1].key],
            leaf.dtype, sharding=on_chip),
        cache.cache,
    )
    # The session over shapes was built with one slot's pages; at the
    # cell's size ``nbytes`` is the published rows, to the byte.
    assert cache.row_bytes == [2 * 2560, 5 * 5120]
    pool_bytes = 2 * sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(pool))
    assert pool_bytes == (
        (24 * 1056 + 1) * 16 * 2 * 2560 + (24 * 9 + 1) * 16 * 5 * 5120
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
    assert took == (True,) * 7
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 7
    assert MERGED in text and "kv_gather" not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == pool_bytes
    assert 2.1e9 < pool_bytes < 2.2e9  # one table for all: 12.5 GB
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
    for (ring, _), shape in shapes.items():
        dims = ",".join(map(str, shape))
        # No plain copy of a pool anywhere, and nothing at all of the
        # two tables' pools (311 and 208 MB each).
        assert not re.findall(rf"= bf16\[{dims}\][^ ]* copy\(", text)
        moved = re.findall(
            rf"= bf16\[{dims}\][^ ]* copy(?:-start|-done)\(", text)
        # XLA carries a ring pool (10.7 / 7.1 MB) through VMEM for its
        # scatter and back (a pool under 128 MiB is exposed to that
        # choice, ROADMAP A9): at most once a ring pool.
        assert len(moved) <= (5 if ring else 0)
    # Nothing of a slot's whole logical view, table or ring, and no key
    # row padded to 256 lanes.
    assert f"bf16[{slots},{sess['max_seq_len']}," not in text
    assert f"bf16[{slots},{cache.ring_pages * page}," not in text
    assert ",4,256]" not in text and ",8,256]" not in text


# The 8,192-row prefill of a full layer and a sliding one (the first
# two of the published pattern: a dense FFN, then the experts) since PR
# 48: the full layer's attention is ONE call of the prefill kernel (64
# query heads on 4 KV heads, keys 192 beside values 128); the sliding
# layer keeps the XLA blocks (the rule excludes a window). No block of
# 256 queries' scores of the full layer and no [1, rows, rows] mask is
# left in the program, the query reaches the kernel as ``q_proj`` and
# the rotation write it (positions minor) and nothing as large as the
# kernel's result is copied or turned on its way in (the keys,
# zero-padded to 256, and the values, a sixteenth of that, are).


def test_sink_window_moe_prefill_attends_in_the_kernel_on_v5e(
    monkeypatch, no_compile_cache
):
    import json
    import re

    from perfbench.families import sink_window_moe_serve as family
    from tpudl.models.generate import prefill_fn
    from tpudl.models.llama import LlamaForCausalLM

    on_chip = _kernels_taken(monkeypatch)
    with open(REPO / "perfbench/configs/mimo-v2-flash-l7-e16.json") as f:
        cfg = json.load(f)
    cfg["num_hidden_layers"] = 2
    rows = cfg["session"]["prompt_window"] // 2
    assert rows == 8192
    model = LlamaForCausalLM(family.model_config(
        cfg, cfg["session"]["max_seq_len"], jnp.bfloat16))
    params = jax.eval_shape(
        model.init, jax.random.key(0), _s((1, 8), i32))["params"]
    ids = _s((1, rows), i32, sharding=on_chip)
    program = prefill_fn(model)
    text = jax.jit(program).lower(
        _placed(params, on_chip), ids, ids).compile().as_text()
    assert program.attention_in_kernel == {rows: 1}
    assert _prefill_kernel_calls(text, ("full_attention",)) == 1
    assert not re.search(rf"(?:s8|pred)\[1,{rows},{rows}\]", text)
    assert ",16,256," not in text  # the full layer's blocks: 4 x 16 heads
    assert not _copies_into_prefill_kernel(text, rows * 64 * 128)


# The configurations whose decode programs read a k / v pool pair held
# as declared ([NP, ps, Hkv, 128], Mistral's, Laguna's two groups and
# the looped stack's): their pools are the pools they had, and the
# lowered program calls ``paged_attention``, not the merged kernel.
DECLARED = {
    "mistral": dict(num_layers=2, num_heads=32, num_kv_heads=8,
                    hidden_size=4096, intermediate_size=14336),
    "laguna": dict(
        num_layers=2, num_heads=48, num_kv_heads=8, hidden_size=2048,
        intermediate_size=8192, head_size=128,
        layer_types=("full_attention", "sliding_attention"),
        num_heads_per_layer=(48, 64), sliding_window=512,
        attention_gate=True, partial_rotary_factor=0.5),
    "ouro": dict(num_layers=2, num_heads=16, num_kv_heads=16,
                 hidden_size=2048, intermediate_size=5632,
                 loop_passes=4, sandwich_norm=True),
}


@pytest.mark.parametrize("name", sorted(DECLARED))
def test_declared_pools_still_take_the_first_kernel(name, monkeypatch,
                                                    no_compile_cache):
    from tpudl.models.generate import paged_decode_fn, prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM
    from tpudl.serve.cache import PagedKVCache

    on_chip = _kernels_taken(monkeypatch)
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=1024, max_seq_len=512, dtype=jnp.bfloat16,
        **DECLARED[name]))
    slots, page = 8, 16
    ids = _s((slots, 64), i32)
    params = jax.eval_shape(model.init, jax.random.key(0), ids)["params"]
    _, template, *_ = jax.eval_shape(prefill_fn(model), params, ids, ids)
    cache, addressing = jax.eval_shape(
        lambda: (lambda c: (c.cache, c.dispatch_args()))(
            PagedKVCache(template, page_size=page)))
    for leaf in jax.tree.leaves(cache):
        assert leaf.shape[1:] == (page, DECLARED[name]["num_kv_heads"], 128)
    vec = _s((slots,), i32, sharding=on_chip)
    step = paged_decode_fn(model, page, False)
    text = jax.jit(step).lower(
        _placed(params, on_chip), _placed(cache, on_chip), vec, vec,
        *_placed(addressing, on_chip),
    ).as_text()
    layers = 2 * DECLARED[name].get("loop_passes", 1)
    assert step.attention_in_place == (True,) * layers
    assert MERGED not in text
    assert "paged_attention" in text and "tpu_custom_call" in text
