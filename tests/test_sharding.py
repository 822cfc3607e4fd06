import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from tpudl.parallel.sharding import (
    FSDP_RULES,
    TP_TRANSFORMER_RULES,
    spec_for_path,
    tree_shardings,
)


def test_spec_for_path_default_replicated():
    assert spec_for_path("params/Dense_0/kernel", None) == P()
    assert spec_for_path("params/bn/scale", FSDP_RULES) == P()


def test_spec_for_path_fsdp():
    assert spec_for_path("params/Dense_0/kernel", FSDP_RULES, (128, 64)) == P(
        "fsdp", None
    )


def test_fsdp_conv_kernel_shards_channel_dim(mesh8):
    # (kh, kw, in, out) conv kernel: FSDP must shard the channel dim, not kh=3.
    tree = {"conv": {"kernel": jnp.zeros((3, 3, 16, 32))}}
    sh = tree_shardings(mesh8, tree, FSDP_RULES)
    assert sh["conv"]["kernel"].spec == P(None, None, None, "fsdp")


def test_spec_for_path_tp_rules_order():
    assert spec_for_path(
        "params/layer_0/attention/query/kernel", TP_TRANSFORMER_RULES
    ) == P("fsdp", "tp")
    assert spec_for_path(
        "params/layer_0/mlp/wo/kernel", TP_TRANSFORMER_RULES
    ) == P("tp", "fsdp")
    # generic kernel falls through to the last rule
    assert spec_for_path("params/head/kernel", TP_TRANSFORMER_RULES) == P(
        "fsdp", None
    )


def test_tree_shardings_clamps_indivisible(mesh8):
    # fsdp axis is size 2: largest dim sharded; an entry its own dim
    # cannot take moves to a dim that can, and only a leaf with no such
    # dim replicates.
    tree = {
        "a": {"kernel": jnp.zeros((8, 6))},
        "b": {"kernel": jnp.zeros((4, 7))},  # largest dim 7: moves to dim 0
        "c": {"bias": jnp.zeros((6,))},
        "d": {"kernel": jnp.zeros((3, 7))},  # nothing divisible by 2
        # BERT's 30,522 rows have no factor 4; odd here, for fsdp=2:
        # hidden takes the axis.
        "word": {"embedding": jnp.zeros((30521, 768))},
    }
    sh = tree_shardings(mesh8, tree, FSDP_RULES)
    assert sh["a"]["kernel"].spec == P("fsdp", None)
    assert sh["b"]["kernel"].spec == P("fsdp", None)
    assert sh["c"]["bias"].spec == P()
    assert sh["d"]["kernel"].spec == P(None, None)
    assert sh["word"]["embedding"].spec == P(None, "fsdp")


def test_tree_shardings_puts_arrays(mesh8):
    import jax

    tree = {"w": {"kernel": jnp.ones((8, 4))}}
    sh = tree_shardings(mesh8, tree, FSDP_RULES)
    placed = jax.device_put(tree, sh)
    np.testing.assert_array_equal(np.asarray(placed["w"]["kernel"]), 1.0)
    assert placed["w"]["kernel"].sharding.spec == P("fsdp", None)
