"""The sorted experts' grouped-matmul kernel (tpudl.ops.grouped_matmul)
on the CPU, in Pallas interpret mode: against ``jax.lax.ragged_dot`` on
rows that lie sorted, inside ``DroplessMoE`` against the layer's
``dense`` form and its ``ragged_dot`` path, and the rule that says
where the layer takes it.

A test steers the rule by answering ``is_tpu_backend`` and
``one_device`` in ``tpudl.ops.grouped_matmul`` alone: the kernel then
runs interpreted, as the paged kernels' tests have it. The result put
back in assignment order by the kernel's own row copies (``rows_to``,
PR 45) is held to the same products bit for bit, and the layer's way
back to token order to the formulation it replaced
(``_sorted_as_pr_42``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tpudl.ops.grouped_matmul as gm
import tpudl.ops.moe as moe
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


def _places(rows):
    """A place for each sorted row, none named twice."""
    return jax.random.permutation(jax.random.key(7), rows).astype(jnp.int32)


def _by_index(lhs, rhs, sizes, result, **kwargs):
    """The kernel's result taken through ``rows_to`` and read back in
    sorted order, and the rows it left unwritten (behind the last
    group), which interpret mode shows as NaN."""
    places = _places(lhs.shape[0])
    out = gm.grouped_matmul(
        lhs, rhs, sizes, result, rows_to=places, **kwargs
    )
    assert out.shape == (lhs.shape[0], 1, rhs.shape[-1])
    return out[places, 0]


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
    _equal_to_ragged_dot(got, want, grouped, result)


@pytest.mark.parametrize("name", sorted(GROUPS))
def test_rows_by_index_are_the_kernels_own_rows(name):
    """The same products, each at the place ``rows_to`` names; a place
    whose row lies behind the last group is never written."""
    sizes, rows = GROUPS[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs = jax.random.normal(jax.random.key(0), (rows, 256), bf16)
    rhs = jax.random.normal(jax.random.key(1), (len(sizes), 256, 128), bf16)
    plain = gm.grouped_matmul(lhs, rhs, sizes, f32, row_tile=16)
    got = _by_index(lhs, rhs, sizes, f32, row_tile=16)
    grouped = int(sizes.sum())
    np.testing.assert_array_equal(got[:grouped], plain[:grouped])
    assert np.isnan(np.asarray(got[grouped:])).all()
    _equal_to_ragged_dot(
        got, jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=f32),
        grouped, f32,
    )


def _equal_to_ragged_dot(got, want, grouped, result):
    # float32 accumulation on both sides, in another order: the float32
    # results agree to rounding, the bfloat16 ones to one of its steps.
    np.testing.assert_allclose(
        np.asarray(got[:grouped], np.float32),
        np.asarray(want[:grouped], np.float32),
        rtol=1e-2 if result == bf16 else 1e-5, atol=1e-3,
    )
    if grouped:
        assert np.abs(np.asarray(want[:grouped], np.float32)).max() > 10


@pytest.mark.parametrize("call", ["in_sorted_order", "rows_by_index"])
def test_a_wide_matrix_is_walked_in_column_tiles(call, monkeypatch):
    monkeypatch.setattr(gm, "MATRIX_BLOCK_BYTES", 256 * 128 * 2)
    assert gm._column_tile(256, 384, 2) == 128
    sizes = jnp.asarray([5, 0, 40, 19], jnp.int32)
    lhs = jax.random.normal(jax.random.key(0), (70, 256), bf16)
    rhs = jax.random.normal(jax.random.key(1), (4, 256, 384), bf16)
    want = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=f32)
    grouped = gm.grouped_matmul if call == "in_sorted_order" else _by_index
    got = grouped(lhs, rhs, sizes, f32, row_tile=16)
    # A row copy is its column tile's slice: every tile of a row lands.
    np.testing.assert_allclose(got[:64], want[:64], rtol=1e-5, atol=1e-3)


def _on_one_chip(monkeypatch, backend=True, one=True):
    monkeypatch.setattr(gm, "is_tpu_backend", lambda: backend)
    monkeypatch.setattr(gm, "one_device", lambda: one)


def _layer(dispatch, held=None, k=2, experts=8):
    return DroplessMoE(
        num_experts=experts, experts_per_token=k, intermediate_size=128,
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


def _sorted_as_pr_42(layer, params, x):
    """The routed part of the sorted form as PR 42 left it, written out:
    every float32 row selected over, gathered through a second
    ``argsort`` and summed. What the layer computes now is held to it
    bit for bit."""
    first, count = layer.experts_held or (0, layer.num_experts)
    k, ragged = layer.experts_per_token, jax.lax.ragged_dot
    tokens = x.reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(tokens.astype(f32) @ params["router"]["kernel"])
    _, chosen = jax.lax.top_k(scores + params["router_bias"], k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = layer.routed_scaling_factor * picked
    gates = gates / jnp.sum(picked, axis=-1, keepdims=True)
    local = chosen - first
    key = jnp.where((local >= 0) & (local < count), local, count).reshape(-1)
    order = jnp.argsort(key)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    rows = tokens[order // k]
    wg, wu, wd = (
        params[name]["kernel"] for name in ("gate_proj", "up_proj", "down_proj")
    )
    act = jax.nn.silu(ragged(rows, wg, sizes)) * ragged(rows, wu, sizes)
    act = act * gates.reshape(-1)[order, None].astype(act.dtype)
    out = ragged(act, wd, sizes, preferred_element_type=f32)
    out = jnp.where((key[order] < count)[:, None], out, 0.0)
    routed = out[jnp.argsort(order)].reshape(-1, k, x.shape[-1]).sum(axis=1)
    return np.asarray(routed.astype(bf16).reshape(x.shape), np.float32)


#: name -> (experts, experts held, choices a token, router bias).
LAYERS = {
    "all_held": (8, None, 2, [0.0] * 8),
    "one_expert_takes_every_row": (8, None, 1, [0, 0, 0, 9, 0, 0, 0, 0]),
    "first_experts_empty": (8, None, 2, [-9, -9, -9, 0, 0, 0, 0, 0]),
    "last_experts_empty": (8, None, 2, [0, 0, 0, 0, 0, -9, -9, -9]),
    "middle_experts_empty": (8, None, 2, [0, 0, -9, -9, -9, 0, 0, 0]),
    "a_share_of_the_experts": (8, (2, 4), 2, [0.0] * 8),
    "a_share_nobody_chose": (8, (0, 2), 2, [-9, -9, 0, 0, 0, 0, 0, 0]),
    # One of sixteen chips: most assignments lie behind the last group.
    "sixteen_of_256_held": (256, (32, 16), 8, [0.0] * 256),
}


def _run(layer, params, x, real):
    y, sown = layer.apply({"params": params}, x, real, mutable=["moe_stats"])
    return np.asarray(y, np.float32), np.asarray(
        sown["moe_stats"]["tokens_per_expert"][0])


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_sorted_layer_with_the_kernel_matches_its_other_forms(
    name, monkeypatch
):
    experts, held, k, bias = LAYERS[name]
    # 200 tokens: 400 (or 200, or 1,600) sorted rows over several
    # 128-row tiles.
    x = jax.random.normal(jax.random.key(1), (2, 100, 128), bf16)
    real = jnp.ones((2, 100), bool).at[0, :7].set(False)
    dense = _layer("dense", held, k, experts)
    by_group = _layer("sorted", held, k, experts)
    params = _weights(dense, x, real, bias)

    want, counts = _run(dense, params, x, real)
    ragged, _ = _run(by_group, params, x, real)
    took = registry().counter("serve_moe_grouped_kernel")
    by_index = registry().counter("serve_moe_rows_by_index")
    before, before_by_index = took.value, by_index.value
    _on_one_chip(monkeypatch)
    got, counts_sorted = _run(by_group, params, x, real)
    # With the kernel the rows go back by index, from inside it.
    assert took.value == before + 1
    assert by_index.value == before_by_index + 1
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


@pytest.mark.parametrize("k", [1, 2, 4, 6, 8])
def test_a_tokens_choices_are_summed_as_the_chip_reduces_sublanes(k):
    """Halves folded onto each other, down to one (or to an odd count,
    summed in turn): for 8 choices ((c0 + c4) + (c2 + c6)) + ((c1 + c5)
    + (c3 + c7)). Values whose float32 sum depends on the order, 40
    tokens (not a whole number of steps), NaN where nothing is held."""
    tokens, n = 40, 256
    out = jax.random.normal(jax.random.key(k), (tokens, k, n), f32) * (
        10.0 ** jax.random.randint(jax.random.key(9), (tokens, k, n), -3, 4)
    )
    held = jax.random.bernoulli(jax.random.key(3), 0.7, (tokens, k))
    c = [jnp.where(held[:, i, None], out[:, i], 0.0) for i in range(k)]
    want = {
        1: lambda: c[0],
        2: lambda: c[0] + c[1],
        4: lambda: (c[0] + c[2]) + (c[1] + c[3]),
        6: lambda: ((c[0] + c[3]) + (c[1] + c[4])) + (c[2] + c[5]),
        8: lambda: ((c[0] + c[4]) + (c[2] + c[6]))
        + ((c[1] + c[5]) + (c[3] + c[7])),
    }[k]()
    rows = jnp.where(held[..., None], out, jnp.nan).reshape(tokens * k, 1, n)
    got = gm.sum_choices(rows, held)
    assert got.shape == (tokens, n) and got.dtype == f32
    np.testing.assert_array_equal(got, want)
    if k > 2:
        in_turn = functools.reduce(jnp.add, c)
        assert (np.asarray(in_turn) != np.asarray(want)).any()


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_the_way_back_to_token_order_keeps_pr_42s_bits(name):
    """``ragged_dot`` on both sides (the CPU's path): the sort's inverse
    as a scatter and the select inside the token's sum give what the
    select over every row, the second ``argsort`` and the gather gave."""
    experts, held, k, bias = LAYERS[name]
    x = jax.random.normal(jax.random.key(1), (2, 100, 128), bf16)
    real = jnp.ones((2, 100), bool)
    layer = _layer("sorted", held, k, experts).clone(
        shared_intermediate_size=0)
    params = _weights(layer, x, real, bias)
    got, _ = _run(layer, params, x, real)
    np.testing.assert_array_equal(got, _sorted_as_pr_42(layer, params, x))
    if name != "a_share_nobody_chose":
        assert np.abs(got).max() > 0.5


def test_rows_nobody_wrote_do_not_reach_the_output(monkeypatch):
    """The places of assignments held elsewhere are never written: with
    NaN laid there, the layer's output is what it was."""
    experts, held, k, bias = LAYERS["sixteen_of_256_held"]
    x = jax.random.normal(jax.random.key(1), (2, 100, 128), bf16)
    real = jnp.ones((2, 100), bool)
    layer = _layer("sorted", held, k, experts)
    params = _weights(layer, x, real, bias)
    _on_one_chip(monkeypatch)
    want, _ = _run(layer, params, x, real)
    unwritten = []

    def poisoned(lhs, rhs, sizes, *args, rows_to=None, **kwargs):
        out = gm.grouped_matmul(
            lhs, rhs, sizes, *args, rows_to=rows_to, **kwargs)
        if rows_to is None:
            return out
        assert np.isnan(np.asarray(out)).any()  # interpret mode's own fill
        written = jnp.zeros(out.shape[0], bool).at[rows_to].set(
            jnp.arange(out.shape[0]) < sizes.sum())
        unwritten.append(int((~written).sum()))
        return jnp.where(written[:, None, None], out, jnp.nan)

    monkeypatch.setattr(moe, "grouped_matmul", poisoned)
    got, _ = _run(layer, params, x, real)
    # About fifteen assignments in sixteen are held elsewhere.
    assert unwritten and unwritten[0] > 0.8 * 200 * k
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


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
    by_index = registry().counter("serve_moe_rows_by_index")
    sorted_layers = registry().counter("serve_moe_dispatch_sorted")
    before, before_sorted = took.value, sorted_layers.value
    before_by_index = by_index.value
    kernel = _traces_the_kernel(layer, params, x, real)
    assert sorted_layers.value == before_sorted + 1
    assert kernel == (case == "yes")
    assert took.value == before + (case == "yes")
    # Rows go back by index exactly where the kernel is taken.
    assert by_index.value == before_by_index + (case == "yes")


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
