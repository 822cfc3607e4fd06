"""GLM-5.2's share of ISSUE 44 compiles for the chip at its cell's size:
asked of the TPU's own compiler with no chip attached, as
``tests/test_tpu_compile.py`` asks for the other configurations (whose
helpers this file borrows). A file of its own because the 8,192-row
prefill takes three minutes to compile and ``test_tpu_compile.py`` is
already the longest file of the suite: under ``--dist loadfile`` a file
is one worker's.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from tests.test_tpu_compile import (  # noqa: F401  (the fixture)
    _family_session,
    _grouped_kernel_calls,
    _on_one_chip,
    _prefill_kernel_calls,
    _placed,
    _s,
    _v5e_device,
    i32,
    no_compile_cache,
)

# GLM-5.2's share of ISSUE 44 at its cell's size
# (perfbench/configs/glm-5.2-l6-e16.json through its family's own
# ``model_config``): six latent pools of the sarvam cell's held shape and
# TWO pools of indexer keys ([NP, 16, 128], on the "full" layers) under
# one table, 12 slots of 8,384 positions, 64 heads, 16 of 256 experts of
# five layers held. The decode program chooses 2,048 of a slot's live
# positions twice and reads the chosen ROWS of all six latent pools
# (the gather of rows, not the in-place kernel, which reads every live
# block), takes the eight pools donated and copies none; 9.40 GB of
# weights, 0.75 GB of pools and a step's temporaries fit the chip. The
# batch-1 prefill at 8,192 rows attends under the choice, a kernel call a
# layer (PR 46).


@pytest.mark.parametrize("name", ["decode", "prefill_8192"])
def test_sparse_mla_moe_program_compiles_for_v5e(
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
    sess, model, params, session = _family_session(
        "glm-5.2-l6-e16", "sparse_mla_moe")
    slots, page = sess["num_slots"], sess["page_size"]
    weights = sum(
        math.prod(leaf.shape) * leaf.dtype.itemsize
        for leaf in jax.tree.leaves(params)
    )
    # 4,689,853,184 parameters at 2 B, the five routers at 4.
    assert weights == 2 * 4_689_853_184 + 2 * 5 * 1_573_120
    if name == "prefill_8192":
        rows = 8192
        ids = _s((1, rows), i32, sharding=on_chip)
        compiled = jax.jit(prefill_fn(model)).lower(
            _placed(params, on_chip), ids, ids
        ).compile()
        memory = compiled.memory_analysis()
        # Beside the pools (0.75 GB) on a chip of 15.75 GB.
        assert memory.temp_size_in_bytes < 5.5e9
        assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.5e9
        text = compiled.as_text()
        # No score matrix of the whole prompt, the attention's or the
        # indexer's (a block of 256 of the indexer's queries meets the
        # keys up to its own end), and since PR 46 none of a block of
        # the attention's queries: a layer's attention under the
        # choice is ONE call of the prefill kernel.
        assert not re.search(rf"(?:64|32),(?:1,)?{rows},{rows}\]", text)
        assert "f32[1,64,256," not in text and "f32[1,64,1,256," not in text
        assert _prefill_kernel_calls(text) == 6
        assert _grouped_kernel_calls(text) == 3 * 5
        return
    cache = session.engine.cache
    table_pages = sess["max_seq_len"] // page
    assert table_pages == 524
    pages = slots * table_pages + 1
    leaves = jax.tree.leaves(cache.cache)
    assert len(leaves) == 8 and sorted(cache.folds) == [1, 1] + [2] * 6
    shapes = {"pages_kv": (pages, page // 2, 2 * 576),
              "pages_index_k": (pages, page, 128)}
    pool = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _s(shapes[path[-1].key], leaf.dtype,
                              sharding=on_chip), cache.cache
    )
    vec = _s((slots,), i32, sharding=on_chip)
    table = _s((slots, table_pages), i32, sharding=on_chip)
    compiled = session.engine.decode_call.lower(
        _placed(params, on_chip), pool, vec, vec, table, vec, vec
    ).compile()
    took = session.engine.decode_call.__wrapped__.attention_in_place
    assert took == (False,) * 6
    memory = compiled.memory_analysis()
    pool_bytes = 2 * pages * page * (6 * 576 + 2 * 128)
    assert memory.alias_size_in_bytes == pool_bytes
    # 12 x 8,384 positions of 7,424 B (and the trash page).
    assert pool_bytes == (12 * 8384 + 16) * 7424
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 12e9
    text = compiled.as_text()
    for shape in shapes.values():
        # No plain copy of a pool anywhere. (XLA carries some of the
        # latent pools through VMEM and back, ``copy-start``: a pool
        # under 128 MiB is exposed to that choice, ROADMAP A9.)
        dims = ",".join(map(str, shape))
        assert not re.findall(rf"= bf16\[{dims}\][^ ]* copy\(", text)
    # Nothing of a slot's whole LATENT view: the rows read are the
    # 2,048 chosen ones (held rows of two positions).
    assert f"[{slots},{sess['max_seq_len'] // 2},1152]" not in text
    assert "bf16[12,2048,1152]" in text or "bf16[12,1,2048,1152]" in text
