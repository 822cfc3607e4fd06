"""The sorted experts' grouped-matmul kernel (tpudl.ops.grouped_matmul)
on the CPU, in Pallas interpret mode: against ``jax.lax.ragged_dot`` on
rows that lie sorted, inside ``DroplessMoE`` against the layer's
``dense`` form and its ``ragged_dot`` path, and the rule that says
where the layer takes it.

A test steers the rule by answering ``is_tpu_backend`` and
``one_device`` in ``tpudl.ops.grouped_matmul`` alone: the kernel then
runs interpreted, as the paged kernels' tests have it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.ops.grouped_matmul as gm
from tpudl.obs import registry
from tpudl.ops.moe import DroplessMoE
from tpudl.quant.quantize import quantize_leaf

bf16, f32 = jnp.bfloat16, jnp.float32

#: name -> (group sizes, rows): the row tile is 16 in these cases.
GROUPS = {
    "all_equal": ([16, 16, 16, 16], 64),
    "equal_but_off_the_tiles": ([24, 24, 24, 24], 96),
    "one_group_holds_every_row": ([0, 64, 0, 0], 64),
    "empty_first": ([0, 0, 30, 34], 64),
    "empty_last": ([41, 23, 0, 0], 64),
    "empty_in_the_middle": ([9, 0, 0, 17, 0, 38], 64),
    "sizes_off_the_tiles": ([5, 40, 3, 1, 15], 64),
    "rows_behind_the_last_group": ([7, 0, 21, 2], 64),
    "rows_not_a_whole_tile": ([13, 29, 8], 50),
    "no_group_has_rows": ([0, 0, 0], 32),
}


@pytest.mark.parametrize("result", [bf16, f32], ids=["bf16", "f32"])
@pytest.mark.parametrize("name", sorted(GROUPS))
def test_kernel_matches_ragged_dot(name, result):
    sizes, rows = GROUPS[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    k, n = 256, 128
    lhs = jax.random.normal(jax.random.key(0), (rows, k), bf16)
    rhs = jax.random.normal(jax.random.key(1), (len(sizes), k, n), bf16)
    want = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=result)
    got = gm.grouped_matmul(lhs, rhs, sizes, result, row_tile=16)
    assert got.shape == want.shape and got.dtype == want.dtype
    grouped = int(sizes.sum())
    # float32 accumulation on both sides, in another order: the float32
    # results agree to rounding, the bfloat16 ones to one of its steps.
    np.testing.assert_allclose(
        np.asarray(got[:grouped], np.float32),
        np.asarray(want[:grouped], np.float32),
        rtol=1e-2 if result == bf16 else 1e-5, atol=1e-3,
    )
    if grouped:
        assert np.abs(np.asarray(want[:grouped], np.float32)).max() > 10


def test_a_wide_matrix_is_walked_in_column_tiles(monkeypatch):
    monkeypatch.setattr(gm, "MATRIX_BLOCK_BYTES", 256 * 128 * 2)
    assert gm._column_tile(256, 384, 2) == 128
    sizes = jnp.asarray([5, 0, 40, 19], jnp.int32)
    lhs = jax.random.normal(jax.random.key(0), (64, 256), bf16)
    rhs = jax.random.normal(jax.random.key(1), (4, 256, 384), bf16)
    want = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=f32)
    got = gm.grouped_matmul(lhs, rhs, sizes, f32, row_tile=16)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-3)


def _on_one_chip(monkeypatch, backend=True, one=True):
    monkeypatch.setattr(gm, "is_tpu_backend", lambda: backend)
    monkeypatch.setattr(gm, "one_device", lambda: one)


def _layer(dispatch, held=None, k=2):
    return DroplessMoE(
        num_experts=8, experts_per_token=k, intermediate_size=128,
        shared_intermediate_size=128, routed_scaling_factor=2.5,
        experts_held=held, dispatch=dispatch,
    )


def _weights(layer, x, real, bias):
    """bfloat16 experts, as a session holds them, scaled so that the
    routed part is of the order of 1, and a router bias that decides
    which experts are chosen at all."""
    params = layer.init(jax.random.key(0), x, real)["params"]
    params = jax.tree.map(lambda a: 4 * a, params)
    for name in ("gate_proj", "up_proj", "down_proj"):
        params[name]["kernel"] = params[name]["kernel"].astype(bf16)
    return dict(params, router_bias=jnp.asarray(bias, f32))


#: name -> (experts held, choices a token, router bias over 8 experts).
LAYERS = {
    "all_held": (None, 2, [0.0] * 8),
    "one_expert_takes_every_row": (None, 1, [0, 0, 0, 9, 0, 0, 0, 0]),
    "first_experts_empty": (None, 2, [-9, -9, -9, 0, 0, 0, 0, 0]),
    "last_experts_empty": (None, 2, [0, 0, 0, 0, 0, -9, -9, -9]),
    "middle_experts_empty": (None, 2, [0, 0, -9, -9, -9, 0, 0, 0]),
    "a_share_of_the_experts": ((2, 4), 2, [0.0] * 8),
    "a_share_nobody_chose": ((0, 2), 2, [-9, -9, 0, 0, 0, 0, 0, 0]),
}


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_sorted_layer_with_the_kernel_matches_its_other_forms(
    name, monkeypatch
):
    held, k, bias = LAYERS[name]
    # 200 tokens: 400 (or 200) sorted rows over several 128-row tiles.
    x = jax.random.normal(jax.random.key(1), (2, 100, 128), bf16)
    real = jnp.ones((2, 100), bool).at[0, :7].set(False)
    dense, by_group = _layer("dense", held, k), _layer("sorted", held, k)
    params = _weights(dense, x, real, bias)

    def run(layer):
        y, sown = layer.apply({"params": params}, x, real,
                              mutable=["moe_stats"])
        return np.asarray(y, np.float32), np.asarray(
            sown["moe_stats"]["tokens_per_expert"][0])

    want, counts = run(dense)
    ragged, _ = run(by_group)
    took = registry().counter("serve_moe_grouped_kernel")
    before = took.value
    _on_one_chip(monkeypatch)
    got, counts_sorted = run(by_group)
    assert took.value == before + 1
    np.testing.assert_array_equal(counts_sorted, counts)
    if name == "a_share_nobody_chose":
        assert counts.sum() == 0
    else:
        assert np.abs(want).max() > 0.5
    # The same products and sums as the ``ragged_dot`` path, a
    # float32 accumulation in another order; the dense form folds the
    # gates in at another place (bfloat16 steps of a sum of k terms).
    np.testing.assert_allclose(got, ragged, rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)


def _traces_the_kernel(layer, params, x, real) -> bool:
    text = str(jax.make_jaxpr(lambda p: layer.apply(
        {"params": p}, x, real, mutable=["moe_stats"]
    ))(params))
    assert ("pallas_call" in text) != ("ragged_dot" in text)
    return "pallas_call" in text


@pytest.mark.parametrize(
    "case",
    ["yes", "not_a_tpu", "several_devices", "int8_experts",
     "float32_experts", "width_not_whole_lanes"],
)
def test_the_rule_reads_backend_devices_and_the_traced_kernels(
    case, monkeypatch
):
    """The kernel wherever the sorted form runs on ONE TPU device over
    unquantized bfloat16 experts of whole-lane widths, ``ragged_dot``
    for everything else; the counter moves exactly when it says yes."""
    hidden = 96 if case == "width_not_whole_lanes" else 128
    x = jax.random.normal(jax.random.key(1), (1, 40, hidden), bf16)
    real = jnp.ones((1, 40), bool)
    layer = _layer("sorted")
    params = _weights(layer, x, real, [0.0] * 8)
    if case == "int8_experts":
        for name in ("gate_proj", "up_proj", "down_proj"):
            params[name]["kernel"] = quantize_leaf(
                params[name]["kernel"].astype(f32), "int8")
    if case == "float32_experts":
        layer = layer.clone(dtype=f32)
        x = x.astype(f32)
    _on_one_chip(
        monkeypatch, backend=case != "not_a_tpu",
        one=case != "several_devices",
    )
    took = registry().counter("serve_moe_grouped_kernel")
    sorted_layers = registry().counter("serve_moe_dispatch_sorted")
    before, before_sorted = took.value, sorted_layers.value
    kernel = _traces_the_kernel(layer, params, x, real)
    assert sorted_layers.value == before_sorted + 1
    assert kernel == (case == "yes")
    assert took.value == before + (case == "yes")


def test_one_device_counts_the_backends_devices():
    # The sandbox's CPU backend is forced to 8 devices (conftest): a
    # layer traced here may be committed to a mesh over them.
    assert jax.device_count() > 1 and not gm.one_device()
    lhs = jnp.zeros((16, 128), bf16)
    rhs = jnp.zeros((2, 128, 128), bf16)
    assert not gm.grouped_kernel_ok(lhs, (rhs,), (None,))


def test_the_walk_visits_each_cut_tile_once_a_group():
    sizes = jnp.asarray([5, 0, 40, 3, 0], jnp.int32)
    group_of, tile_of, starts, ends, first_visit, following, buffer_of, total = (
        np.asarray(a) for a in gm._walk(sizes, 4, 16)
    )
    assert total[0] == 5 and len(group_of) == 4 + 5 - 1
    np.testing.assert_array_equal(group_of[:5], [0, 2, 2, 2, 3])
    np.testing.assert_array_equal(tile_of[:5], [0, 0, 1, 2, 2])
    # Past the walk's end a visit repeats the last: no block moves.
    np.testing.assert_array_equal(group_of[5:], [3] * 3)
    np.testing.assert_array_equal(tile_of[5:], [2] * 3)
    np.testing.assert_array_equal(starts, [0, 5, 5, 45, 48])
    np.testing.assert_array_equal(ends, [5, 5, 45, 48, 48])
    np.testing.assert_array_equal(first_visit[[0, 2, 3]], [0, 1, 4])
    np.testing.assert_array_equal(following, [2, 2, 3, -1, -1])
    np.testing.assert_array_equal(buffer_of[[0, 2, 3]], [0, 1, 0])
