"""Compile a serving cell's programs for a described v5e, without the
chip (on-chip-measurement guide, section 2.3): the weights from the
seed, the batch-1 prefill, the slot-batched paged decode step and the
reference's layer. What the chip's compiler would refuse, it refuses
here. Nothing runs, so this says nothing about results or times.

    JAX_PLATFORMS=cpu python3 -m perfbench.tools.compile_check <config.json>
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(path: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from tpudl.models.generate import paged_decode_fn, prefill_fn
    from tpudl.models.llama import LlamaConfig, LlamaForCausalLM

    from perfbench.families.decoder_serve import dtype_of, to_flax
    from perfbench.reference import decoder as ref

    with open(path) as f:
        cfg = json.load(f)
    sess = cfg["session"]
    dtype = dtype_of(cfg["torch_dtype"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), tree
        )

    def compiled(name, fn, *args):
        t = time.monotonic()
        out = jax.jit(fn).lower(*args).compile()
        mem = out.memory_analysis()
        print(f"{name}: compiled in {time.monotonic() - t:.1f} s; "
              f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"outputs {mem.output_size_in_bytes / 1e9:.2f} GB, "
              f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB", flush=True)

    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_seq_len=sess["max_seq_len"], rope_theta=cfg["rope_theta"],
        rms_norm_eps=cfg["rms_norm_eps"], dtype=dtype,
    ))
    make = lambda key: to_flax(ref.all_weights(key, cfg, dtype))  # noqa: E731
    key = jax.eval_shape(lambda: ref.seed_key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=chip)
    compiled("weights from the seed", make, key)
    params = on_chip(jax.eval_shape(make, key))
    window, slots = sess["prompt_window"], sess["num_slots"]
    ids1 = jax.ShapeDtypeStruct((1, window), jnp.int32, sharding=chip)
    compiled("prefill [1, window]", prefill_fn(model), params, ids1, ids1)
    ids = jax.ShapeDtypeStruct((slots, window), jnp.int32)
    _, template = jax.eval_shape(prefill_fn(model), params, ids, ids)
    pages = slots * sess["max_seq_len"] // sess["page_size"] + 1
    hd = cfg["head_dim"]
    pool = jax.ShapeDtypeStruct(
        (pages, sess["page_size"], cfg["num_key_value_heads"], hd), dtype,
        sharding=chip,
    )
    def as_pools(node):
        if isinstance(node, dict) and "k" in node and "v" in node:
            return {"pages_k": pool, "pages_v": pool}
        return {k: as_pools(v) for k, v in node.items()}

    # The page pools as PagedKVCache lays them out: one pair a layer.
    pools = as_pools(template)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    table = jax.ShapeDtypeStruct(
        (slots, sess["max_seq_len"] // sess["page_size"]), jnp.int32,
        sharding=chip,
    )
    compiled("paged decode [slots]",
             paged_decode_fn(model, sess["page_size"], False),
             params, pools, vec, vec, table, vec, vec)
    rows = cfg["correctness"]["reference_rows"]
    width = window + 128
    x = jax.ShapeDtypeStruct((rows, width, cfg["hidden_size"]), jnp.float32,
                             sharding=chip)
    layer = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled("reference layer (float32, highest)",
             lambda k, i, h: ref.block(
                 h, ref.layer_weights(k, i, cfg, dtype), cfg),
             key, layer, x)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
